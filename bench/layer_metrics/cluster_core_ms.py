"""Device time per unit of the slot-pool replay program (`_cluster_core`:
attempt-table build, FIFO slot-pool scan, reductions)."""
from trace_reduce import module_time


def value(reduced, record):
    hit = module_time(reduced, "_cluster_core")
    if hit is None:
        return None
    return 1e3 * hit[0] / len(record["units"])
