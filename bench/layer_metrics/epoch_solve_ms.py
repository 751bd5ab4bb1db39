"""Device time per execution of the Algorithm-1 solve program
(`solve_jobs`, the grid-solve kernel on the chip): one per warm epoch."""
from trace_reduce import module_time


def value(reduced, record):
    hit = module_time(reduced, "solve_jobs")
    if hit is None or hit[1] == 0:
        return None
    return 1e3 * hit[0] / hit[1]
