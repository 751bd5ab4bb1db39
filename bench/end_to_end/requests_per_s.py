"""Work of the window's whole units over the window's time."""
from harness import rate


def value(record):
    return rate(record)
