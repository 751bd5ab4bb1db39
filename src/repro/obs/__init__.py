"""repro.obs — end-to-end observability: span tracing, device-side metric
pytrees, and Pareto-tail telemetry (DESIGN.md §15).

Three pillars:

* `obs.trace` / `obs.export` — host-side nested spans at every pipeline
  stage boundary, with dispatch-vs-execute fencing; exported as
  Chrome-trace / Perfetto JSON or a compact text summary. Off by default;
  zero-cost when off. While the JAX profiler collects, every span is also
  a profiler annotation, on the device trace's clock.
* `obs.metrics` — functional `CapacityMetrics` pytrees threaded through
  the jitted capacity replay (queue-depth histograms, occupancy integrals,
  speculative launch/kill counters, busy-period windows), reduced
  host-side in one fixed order.
* `obs.tail` — a registry of rolling duration windows with online
  quantile / Hill / Pareto-MLE fits and the observe -> refit -> re-solve
  r* governor hook.
"""
from .trace import (Tracer, disable, enable, enabled, fenced, get_tracer,
                    span)
from .export import (stage_breakdown, summary, to_chrome_trace,
                     write_chrome_trace)
from .metrics import (CapacityMetrics, capacity_metrics, combine_windows,
                      reduce_reps, reduce_reps_host)

_TAIL_NAMES = ("TailFit", "TailGovernor", "TailRegistry", "TailWindow")


def __getattr__(name):
    # the tail pillar reaches into runtime/ and core/, which themselves
    # instrument with obs.trace — loading it lazily (PEP 562) keeps
    # `import repro.obs.trace` cycle-free from anywhere in the package
    if name in _TAIL_NAMES:
        from . import tail
        return getattr(tail, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "Tracer", "enable", "disable", "enabled", "span", "fenced",
    "get_tracer",
    "to_chrome_trace", "write_chrome_trace", "summary", "stage_breakdown",
    "CapacityMetrics", "capacity_metrics", "reduce_reps",
    "reduce_reps_host", "combine_windows",
    "TailFit", "TailWindow", "TailRegistry", "TailGovernor",
]
