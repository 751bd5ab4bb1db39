"""Host-side span tracing — the pipeline's wall-clock attribution layer.

Every stage boundary of the Chronos pipeline (workload synthesis, grid
solve, jobset build, capacity replay, fleet shard/chunk dispatch, stream
reduction) wraps itself in a `span(...)`. Spans nest through a stack kept
per-thread, carry free-form attributes, and record perf_counter_ns
timestamps, so the whole run exports as a Chrome-trace / Perfetto JSON
timeline (`repro.obs.export`) or prints as a compact text summary.

Dispatch vs execute attribution: JAX dispatch is asynchronous, so the
wall-clock of the Python call that launches a jitted program covers
tracing + compilation + enqueue, while device execution overlaps the host
arbitrarily. The `fenced(...)` helper therefore times two spans — a
`kind="dispatch"` span around the call itself and a `kind="execute"` span
around `jax.block_until_ready` on its outputs — so compile-dominated and
execute-dominated stages separate cleanly in the timeline. Recompiles are
flagged explicitly: when the traced callable is a jitted function,
`fenced` samples its `_cache_size()` before and after and sets
`compiled=True` on the dispatch span whenever the cache grew.

The tracer is OFF by default and the disabled path is free of fences:
`span(...)` returns a shared no-op context manager and `fenced` reduces
to a plain call (no `block_until_ready`), so an un-traced run executes a
byte-identical program schedule to a build without this module. Overhead
with tracing ON is gated in CI (< 3% on the trace_sim_full smoke — see
benchmarks/obs_overhead.py).

Profiler clock: whenever the JAX profiler is collecting
(`jax.profiler.start_trace` / `trace`), every span is also a
`jax.profiler.TraceAnnotation` of the same name, so the program's spans
land in the trace's host plane on the same clock as the device ops. The
switch is the profiler itself; the tracer above is independent of it.
With only the profiler on, the tracer records nothing and `fenced`
annotates the dispatch without `block_until_ready`: the device plane
already shows execution, and a profiled run keeps the un-traced schedule.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Optional

from jax.profiler import TraceAnnotation

__all__ = ["Span", "Tracer", "enable", "disable", "enabled", "get_tracer",
           "span", "fenced"]

#: True while the JAX profiler is collecting host annotations
_profiling = TraceAnnotation.is_enabled


@dataclass
class Span:
    """One closed (or still-open) interval of the host timeline."""
    name: str
    start_ns: int
    end_ns: Optional[int] = None
    kind: str = "stage"            # "stage" | "dispatch" | "execute"
    attrs: dict = field(default_factory=dict)
    depth: int = 0
    tid: int = 0

    @property
    def duration_ns(self) -> int:
        end = self.end_ns if self.end_ns is not None else time.perf_counter_ns()
        return end - self.start_ns


class _ProfilerSpan:
    """A span only the profiler sees: a TraceAnnotation behind the
    disabled span's interface (`set` is a no-op, `span` is None)."""
    __slots__ = ("_name", "_annotation")
    span = None

    def __init__(self, name: str):
        self._name = name
        self._annotation = None

    def set(self, **attrs):
        return self

    def __enter__(self):
        # a TraceAnnotation's interval starts when it is built
        self._annotation = TraceAnnotation(self._name)
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc):
        self._annotation.__exit__(*exc)
        return False


class _SpanCtx:
    """Context manager recording one Span on the owning tracer, and on the
    profiler's clock through `annotation` when one is given."""
    __slots__ = ("_tracer", "span", "_annotation")

    def __init__(self, tracer: "Tracer", span_: Span,
                 annotation: Optional[_ProfilerSpan] = None):
        self._tracer = tracer
        self.span = span_
        self._annotation = annotation

    def set(self, **attrs):
        self.span.attrs.update(attrs)
        return self

    def __enter__(self):
        self._tracer._push(self.span)
        if self._annotation is not None:
            self._annotation.__enter__()
        return self

    def __exit__(self, *exc):
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        self._tracer._pop(self.span)
        return False


class _NoopCtx:
    """Shared do-nothing span: with the tracer and the profiler off, a span
    costs one profiler check and two no-op calls."""
    __slots__ = ()
    span = None

    def set(self, **attrs):
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _NoopCtx()


class Tracer:
    """Collects spans from any thread; nesting depth is tracked per-thread
    so concurrent host threads (e.g. async checkpoint writers) interleave
    without corrupting each other's stacks."""

    def __init__(self):
        self.spans: list[Span] = []
        self.t0_ns: int = time.perf_counter_ns()
        self._lock = threading.Lock()
        self._local = threading.local()

    # -- span recording ----------------------------------------------------
    def span(self, name: str, kind: str = "stage", **attrs) -> _SpanCtx:
        return _SpanCtx(self, Span(name=name, start_ns=0, kind=kind,
                                   attrs=dict(attrs)),
                        _ProfilerSpan(name) if _profiling() else None)

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _push(self, sp: Span):
        st = self._stack()
        sp.depth = len(st)
        sp.tid = threading.get_ident()
        sp.start_ns = time.perf_counter_ns()
        st.append(sp)

    def _pop(self, sp: Span):
        sp.end_ns = time.perf_counter_ns()
        st = self._stack()
        if st and st[-1] is sp:
            st.pop()
        with self._lock:
            self.spans.append(sp)

    # -- views -------------------------------------------------------------
    def closed_spans(self) -> list[Span]:
        with self._lock:
            return list(self.spans)

    def wall_ns(self) -> int:
        """Wall-clock between the first span start and the last span end."""
        spans = self.closed_spans()
        if not spans:
            return 0
        return (max(s.end_ns for s in spans if s.end_ns is not None)
                - min(s.start_ns for s in spans))

    def clear(self):
        with self._lock:
            self.spans.clear()
        self.t0_ns = time.perf_counter_ns()


# ---------------------------------------------------------------------------
# Module-level switch: one global tracer, enabled explicitly
# ---------------------------------------------------------------------------

_TRACER = Tracer()
_ENABLED = False


def enable(fresh: bool = True) -> Tracer:
    """Turn span collection on (optionally clearing prior spans)."""
    global _ENABLED
    if fresh:
        _TRACER.clear()
    _ENABLED = True
    return _TRACER


def disable() -> Tracer:
    global _ENABLED
    _ENABLED = False
    return _TRACER


def enabled() -> bool:
    return _ENABLED


def get_tracer() -> Tracer:
    return _TRACER


def span(name: str, kind: str = "stage", **attrs):
    """The instrumentation entry every pipeline stage uses.

    Disabled: returns a shared no-op context manager (no allocation beyond
    the kwargs dict the caller built), unless the JAX profiler is
    collecting, in which case the span is a TraceAnnotation of `name`.
    Enabled: records a Span on the global tracer, annotated likewise while
    the profiler collects.
    """
    if _ENABLED:
        return _TRACER.span(name, kind=kind, **attrs)
    if _profiling():
        return _ProfilerSpan(name)
    return _NOOP


def _cache_size(fn) -> Optional[int]:
    probe = getattr(fn, "_cache_size", None)
    if probe is None:
        return None
    try:
        return int(probe())
    except Exception:
        return None


def fenced(name: str, fn, /, *args, **kwargs):
    """Call `fn(*args, **kwargs)` under a dispatch span, then block on its
    outputs under an execute span, attributing compile vs execute time.

    With tracing disabled there is no `block_until_ready`, so the async
    dispatch pipeline (and therefore the exact program schedule) of an
    un-traced run is untouched: a plain call, under the dispatch span
    alone while the JAX profiler collects.
    """
    if not _ENABLED:
        with span(name, kind="dispatch"):
            return fn(*args, **kwargs)
    import jax
    before = _cache_size(fn)
    with _TRACER.span(name, kind="dispatch") as sp:
        out = fn(*args, **kwargs)
        after = _cache_size(fn)
        if before is not None and after is not None and after > before:
            sp.set(compiled=True)
    with _TRACER.span(f"{name}.wait", kind="execute"):
        jax.block_until_ready(out)
    return out

