"""Device time per execution of the serving window program
(`_window_core`: one window of per-request draws and outcomes)."""
from trace_reduce import module_time


def value(reduced, record):
    hit = module_time(reduced, "_window_core")
    if hit is None or hit[1] == 0:
        return None
    return 1e3 * hit[0] / hit[1]
