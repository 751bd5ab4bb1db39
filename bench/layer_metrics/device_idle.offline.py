"""Share of the window in which no operation ran on the device (offline
cells): 1 minus the union of the device's op intervals over the window."""


def value(reduced, record):
    if reduced["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
