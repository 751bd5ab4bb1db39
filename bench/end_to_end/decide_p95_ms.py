"""95th percentile over every request of the window of the wall time from
its epoch's start to its epoch's results reaching the host."""
from harness import p95_over_requests


def value(record):
    return 1e3 * p95_over_requests(u["latencies_s"] for u in record["units"])
