"""Observability layer (repro.obs): spans, exports, device metrics, tails.

Pins the layer's three contracts (DESIGN.md §15):

* tracing OFF is free — `span` returns a shared no-op, `fenced` degrades
  to a plain call, and the instrumented run's metric payloads are bitwise
  identical to an uninstrumented run's; while the JAX profiler collects,
  spans are annotations on its clock and `fenced` still never blocks;
* the `CapacityMetrics` pytree is a pure function of the replay arrays —
  histogram mass equals the dispatched-attempt count, and the reduced
  pytree is bit-identical across mesh shapes, pad+mask overrides, and the
  single-chunk/monolithic split;
* tail telemetry recovers the Pareto tail it observes and drives the
  observe -> refit -> re-solve hook end to end.
"""
import json

import jax
import numpy as np
import pytest

from repro.cluster import run_cluster_strategy
from repro.fleet import fleet_mesh, run_cluster_fleet_strategy
from repro.obs import export as obs_export
from repro.obs import trace as obs_trace
from repro.obs.metrics import (CapacityMetrics, DEPTH_BINS, N_WINDOWS,
                               combine_windows)
from repro.obs.tail import TailGovernor, TailRegistry, TailWindow
from repro.runtime.telemetry import DurationWindow
from repro.sim import SimParams, run_strategy, uniform_jobset
from repro.strategies import names

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

P = SimParams()
KEY = jax.random.PRNGKey(0)


@pytest.fixture(autouse=True)
def _tracing_off():
    """Every test starts and ends with the global tracer disabled."""
    obs_trace.disable()
    obs_trace.get_tracer().clear()
    yield
    obs_trace.disable()
    obs_trace.get_tracer().clear()


@pytest.fixture(scope="module")
def small_jobs():
    return uniform_jobset(80, 10, t_min=10.0, beta=2.0, D=50.0)


def metrics_equal(a: CapacityMetrics, b: CapacityMetrics) -> bool:
    return all(np.array_equal(np.asarray(getattr(a, f)),
                              np.asarray(getattr(b, f)))
               for f in CapacityMetrics._fields)


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def test_span_disabled_is_shared_noop():
    s1 = obs_trace.span("a", x=1)
    s2 = obs_trace.span("b")
    assert s1 is s2                       # one shared object, no allocation
    with s1 as sp:
        sp.set(y=2)                       # set() is a no-op, not an error
    assert obs_trace.get_tracer().closed_spans() == []


def test_span_nesting_depth_and_attrs():
    obs_trace.enable()
    with obs_trace.span("outer", stage="demo"):
        with obs_trace.span("inner") as sp:
            sp.set(n=3)
    spans = {s.name: s for s in obs_trace.get_tracer().closed_spans()}
    assert spans["outer"].depth == 0
    assert spans["inner"].depth == 1
    assert spans["inner"].attrs == {"n": 3}
    assert spans["outer"].attrs == {"stage": "demo"}
    assert spans["inner"].start_ns >= spans["outer"].start_ns
    assert spans["inner"].end_ns <= spans["outer"].end_ns


def test_enable_fresh_clears_prior_spans():
    obs_trace.enable()
    with obs_trace.span("old"):
        pass
    obs_trace.enable(fresh=True)
    assert obs_trace.get_tracer().closed_spans() == []
    obs_trace.enable(fresh=False)         # and fresh=False preserves
    with obs_trace.span("new"):
        pass
    assert [s.name for s in obs_trace.get_tracer().closed_spans()] == ["new"]


def test_fenced_dispatch_execute_and_compile_flag():
    import jax.numpy as jnp
    obs_trace.enable()
    fn = jax.jit(lambda x: x * 2.0)
    obs_trace.fenced("demo", fn, jnp.float32(3.0))
    obs_trace.fenced("demo", fn, jnp.float32(4.0))
    spans = obs_trace.get_tracer().closed_spans()
    dispatch = [s for s in spans if s.name == "demo"]
    execute = [s for s in spans if s.name == "demo.wait"]
    assert len(dispatch) == 2 and len(execute) == 2
    assert all(s.kind == "dispatch" for s in dispatch)
    assert all(s.kind == "execute" for s in execute)
    # first call compiles; the second hits the jit cache
    assert dispatch[0].attrs.get("compiled") is True
    assert "compiled" not in dispatch[1].attrs


def test_fenced_disabled_is_plain_call():
    calls = []

    def fn(x):
        calls.append(x)
        return x + 1

    assert obs_trace.fenced("demo", fn, 41) == 42
    assert calls == [41]
    assert obs_trace.get_tracer().closed_spans() == []


# ---------------------------------------------------------------------------
# spans on the profiler's clock
# ---------------------------------------------------------------------------


def _profiled_host_events(trace_dir, prefixes):
    """(name, start_ns, end_ns) of the host events of the one profiler
    trace under `trace_dir` whose names start with one of `prefixes`."""
    (path,) = trace_dir.glob("**/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    return sorted(((e.name, e.start_ns, e.end_ns) for p in data.planes
                   if p.name.startswith("/host:") for line in p.lines
                   for e in line.events if e.name.startswith(prefixes)),
                  key=lambda e: (e[1], -e[2]))


def _within(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_profiler_spans_tile_online_serve_epochs(tmp_path):
    """With only the JAX profiler on, a tiny online `serve_trace` (1,000
    requests, epochs of 100, every 5th a probe, windows of 32) leaves its
    spans in the trace's host plane, nested as the loop runs them, and one
    `d2h.wait` per blocking device->host read. Per epoch: 80 hedged + 20
    probe requests = 3 + 1 windows with two reads each, three reads in
    the combiner, and after the first epoch's refit one solve read, and at
    every refit one `solve_strategies` program over all Chronos strategies
    with one read."""
    from repro.serve import make_requests, serve_trace
    reqs = make_requests("request-storm", n_requests=1000, seed=0)
    with jax.profiler.trace(str(tmp_path)):
        out = serve_trace(KEY, reqs, strategy="sresume", window=32,
                          refit_every=100, probe_every=5, min_samples=16)
    assert obs_trace.get_tracer().closed_spans() == []
    events = _profiled_host_events(
        tmp_path, ("serve.", "d2h.wait", "optimizer.", "combiner."))
    by = {}
    for e in events:
        by.setdefault(e[0], []).append(e)
    assert out.n_refits == 10
    assert {k: len(v) for k, v in by.items()} == {
        "serve.trace": 1, "serve.epoch": 10, "serve.solve": 9,
        "serve.window": 40, "serve.combine": 10, "serve.governor": 10,
        "optimizer.solve_strategies": 10, "combiner.finalize": 1,
        "d2h.wait": 131}
    (unit,) = by["serve.trace"]
    epochs = by["serve.epoch"]
    assert all(_within(e, unit) for e in epochs)
    children = ("serve.solve", "serve.window", "serve.combine",
                "serve.governor")
    for name in children:
        assert all(any(_within(c, e) for e in epochs) for c in by[name])
    assert all(any(_within(g, c) for c in by["serve.governor"])
               for g in by["optimizer.solve_strategies"])
    inner = [c for name in children for c in by[name]]
    d2h_per_epoch = [sum(_within(w, e) for w in by["d2h.wait"])
                     for e in epochs]
    # every wait inside an epoch sits in one of its four children
    assert sum(d2h_per_epoch) == sum(
        any(_within(w, c) for c in inner) for w in by["d2h.wait"])
    cold = 4 * 2 + 3 + 1
    assert d2h_per_epoch == [cold] + [cold + 1] * 9
    # the two reads after the loop: the utility and the latency summary
    assert len(by["d2h.wait"]) - sum(d2h_per_epoch) == 2


def test_profiler_only_fenced_does_not_block(tmp_path, monkeypatch):
    """Profiler on, tracer off: `fenced` annotates the dispatch and never
    calls `block_until_ready`, the tracer records nothing, and `span`
    hands out annotations. Both off: `span` is the shared no-op again."""
    blocked = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: blocked.append(x) or x)
    fn = jax.jit(lambda x: x * 2.0)
    with jax.profiler.trace(str(tmp_path)):
        assert obs_trace.span("a") is not obs_trace.span("b")
        with obs_trace.span("demo.outer", n=1) as sp:
            assert sp.set(n=2) is sp and sp.span is None
            out = obs_trace.fenced("demo.fenced", fn, np.float32(3.0))
    assert float(out) == 6.0
    assert blocked == []
    assert obs_trace.get_tracer().closed_spans() == []
    assert obs_trace.span("a") is obs_trace.span("b")
    events = _profiled_host_events(tmp_path, ("demo.",))
    assert [e[0] for e in events] == ["demo.outer", "demo.fenced"]
    assert _within(events[1], events[0])


def test_tracer_and_profiler_both_record(tmp_path):
    """Tracer and profiler on together: each span is a tracer Span and a
    profiler annotation of the same name and nesting."""
    import time
    obs_trace.enable()
    with jax.profiler.trace(str(tmp_path)):
        with obs_trace.span("demo.outer"):
            with obs_trace.span("demo.inner", kind="dispatch"):
                time.sleep(0.002)
    spans = {s.name: s for s in obs_trace.get_tracer().closed_spans()}
    assert set(spans) == {"demo.outer", "demo.inner"}
    assert spans["demo.inner"].depth == 1
    events = _profiled_host_events(tmp_path, ("demo.",))
    assert [e[0] for e in events] == ["demo.outer", "demo.inner"]
    assert _within(events[1], events[0])
    assert events[1][2] - events[1][1] >= 2e6


# ---------------------------------------------------------------------------
# export
# ---------------------------------------------------------------------------


def test_chrome_trace_export(tmp_path):
    obs_trace.enable()
    with obs_trace.span("outer", scenario="demo"):
        with obs_trace.span("inner", kind="dispatch"):
            pass
    path = obs_export.write_chrome_trace(tmp_path / "t.json")
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    slices = {e["name"]: e for e in events if e["ph"] == "X"}
    assert meta and meta[0]["args"]["name"] == "repro"
    assert set(slices) == {"outer", "inner"}
    assert slices["inner"]["cat"] == "dispatch"
    assert slices["outer"]["args"] == {"scenario": "demo"}
    # complete events: microsecond ts/dur, child nested inside parent
    assert slices["inner"]["ts"] >= slices["outer"]["ts"]
    assert (slices["inner"]["ts"] + slices["inner"]["dur"]
            <= slices["outer"]["ts"] + slices["outer"]["dur"] + 1e-3)


def test_stage_breakdown_self_time_excludes_children():
    import time
    obs_trace.enable()
    with obs_trace.span("parent"):
        with obs_trace.span("child"):
            time.sleep(0.02)
    rows = obs_export.stage_breakdown()
    assert rows["child"]["total_ms"] >= 20.0
    # the parent's self time excludes the child's 20 ms
    assert rows["parent"]["self_ms"] <= rows["parent"]["total_ms"] - 15.0
    assert rows["parent"]["count"] == rows["child"]["count"] == 1


def test_traced_run_covers_pipeline(small_jobs):
    """A traced end-to-end run: >= 95% of the wall-clock sits inside
    spans, and the summary names the stage boundaries."""
    obs_trace.enable()
    run_strategy(KEY, small_jobs, "sresume", P, theta=1e-3)
    run_cluster_strategy(KEY, small_jobs, "sresume", P, slots=200,
                         theta=1e-3)
    names_seen = {s.name for s in obs_trace.get_tracer().closed_spans()}
    assert {"sim.run[sresume]", "sim.run[sresume].wait", "cluster.solve",
            "cluster.replay[sresume]"} <= names_seen
    assert obs_export.coverage() >= 0.95
    text = obs_export.summary()
    assert "cluster.replay[sresume]" in text and "coverage" in text


# ---------------------------------------------------------------------------
# DurationWindow capacity (regression) + tail telemetry
# ---------------------------------------------------------------------------


def test_duration_window_honors_capacity():
    """Regression: capacity used to be ignored (deque hardcoded to 512)."""
    w = DurationWindow(capacity=8)
    for i in range(20):
        w.record(float(i))
    assert len(w) == 8
    assert w.snapshot() == [float(i) for i in range(12, 20)]


def test_duration_window_rejects_bad_capacity():
    with pytest.raises(ValueError):
        DurationWindow(capacity=0)


def test_tail_window_recovers_pareto_beta():
    rng = np.random.default_rng(0)
    t_min, beta = 10.0, 1.5
    xs = t_min * (1.0 - rng.random(512)) ** (-1.0 / beta)
    win = TailWindow(capacity=512)
    for x in xs:
        win.observe(float(x))
    fit = win.fit()
    assert fit.n == 512 and fit.k == 52
    assert fit.t_min == pytest.approx(float(xs.min()))
    assert fit.beta == pytest.approx(beta, rel=0.2)
    assert fit.beta_hill == pytest.approx(beta, rel=0.5)
    assert win.quantile(0.5) >= t_min


def test_tail_registry_subscribe_and_snapshot():
    reg = TailRegistry(capacity=64)
    seen = []
    reg.subscribe("map", lambda name, fit: seen.append((name, fit.n)))
    for i in range(10):
        reg.observe("map", 10.0 + i)
    fit = reg.refit("map")
    assert seen == [("map", 10)]
    assert reg.snapshot() == {"map": fit}


def test_tail_governor_observe_refit_resolve():
    rng = np.random.default_rng(1)
    resolved = []
    gov = TailGovernor(deadline=60.0, n_tasks=200, theta=1e-3,
                       cadence=32, min_samples=8,
                       on_resolve=lambda sol, fit: resolved.append(sol))
    xs = 10.0 * (1.0 - rng.random(64)) ** (-1.0 / 1.5)
    outs = [gov.observe(float(x)) for x in xs]
    hits = [o for o in outs if o is not None]
    assert len(hits) == 2 == len(resolved)   # every `cadence` observations
    sol = gov.decision
    assert sol is hits[-1]
    assert sol.strategy in names(kind="chronos")
    assert 0 <= sol.r_opt <= gov.max_r
    assert np.isfinite(sol.utility)
    assert gov.last_fit is not None and gov.last_fit.beta > 1.0


def test_tail_governor_deadline_below_floor():
    gov = TailGovernor(deadline=1.0, n_tasks=50, cadence=4, min_samples=2)
    for x in (10.0, 12.0, 11.0, 13.0):
        gov.observe(x)
    assert gov.decision is None     # deadline below the observed t_min


# ---------------------------------------------------------------------------
# device-side CapacityMetrics
# ---------------------------------------------------------------------------


def test_engine_metrics_off_by_default(small_jobs):
    out = run_cluster_strategy(KEY, small_jobs, "sresume", P, slots=200,
                               theta=1e-3)
    assert out.metrics is None


def test_engine_metrics_do_not_perturb_results(small_jobs):
    """Instrumented replay == uninstrumented replay, bit for bit."""
    ref = run_cluster_strategy(KEY, small_jobs, "sresume", P, slots=200,
                               theta=1e-3)
    out = run_cluster_strategy(KEY, small_jobs, "sresume", P, slots=200,
                               theta=1e-3, collect_metrics=True)
    for fld in ("job_met", "job_completion", "job_cost"):
        assert np.array_equal(np.asarray(getattr(ref.result, fld)),
                              np.asarray(getattr(out.result, fld))), fld
    for fld in ("mean_wait", "max_wait", "utilization", "preempted"):
        assert float(getattr(ref.queue, fld)) == \
            float(getattr(out.queue, fld)), fld
    assert out.metrics is not None


def test_engine_metrics_mass_conservation(small_jobs):
    out = run_cluster_strategy(KEY, small_jobs, "sresume", P, slots=200,
                               theta=1e-3, collect_metrics=True)
    m = out.metrics
    assert m.depth_hist.shape == (DEPTH_BINS,)
    assert m.busy_windows.shape == (N_WINDOWS,)
    # the clip bin guarantees no depth falls off the histogram
    assert int(m.depth_hist.sum()) == int(m.n_dispatched)
    assert int(m.n_dispatched) >= small_jobs.total_tasks
    assert int(m.busy_windows.sum()) <= int(m.n_dispatched)
    assert int(m.spec_launched) <= int(m.n_dispatched)
    assert float(m.occupancy) > 0.0
    assert int(m.reps) == 1


def test_engine_metrics_reps_reduce(small_jobs):
    out = run_cluster_strategy(KEY, small_jobs, "sresume", P, slots=200,
                               theta=1e-3, reps=3, collect_metrics=True)
    m = out.metrics
    assert int(m.reps) == 3
    assert int(m.depth_hist.sum()) == int(m.n_dispatched)
    # counters summed over replications: at least reps * tasks
    assert int(m.n_dispatched) >= 3 * small_jobs.total_tasks


def test_fleet_metrics_do_not_perturb_results(small_jobs):
    """Instrumented fleet replay == uninstrumented, bit for bit. (The
    fleet path keys draws per replication, so its metrics legitimately
    differ from the engine path's — each is self-consistent.)"""
    ref = run_cluster_fleet_strategy(KEY, small_jobs, "sresume", P,
                                     slots=200, theta=1e-3)
    out = run_cluster_fleet_strategy(KEY, small_jobs, "sresume", P,
                                     slots=200, theta=1e-3,
                                     collect_metrics=True)
    assert ref.metrics is None and out.metrics is not None
    for fld in ("job_met", "job_completion", "job_cost"):
        assert np.array_equal(np.asarray(getattr(ref.result, fld)),
                              np.asarray(getattr(out.result, fld))), fld
    for fld in ("mean_wait", "max_wait", "utilization", "preempted"):
        assert float(getattr(ref.queue, fld)) == \
            float(getattr(out.queue, fld)), fld
    assert int(out.metrics.depth_hist.sum()) == int(out.metrics.n_dispatched)


def test_fleet_metrics_pad_invariance(small_jobs):
    """Rep padding (pad+mask) must not leak into the reduced metrics."""
    ref = run_cluster_fleet_strategy(KEY, small_jobs, "sresume", P,
                                     slots=200, theta=1e-3, reps=3,
                                     collect_metrics=True)
    out = run_cluster_fleet_strategy(KEY, small_jobs, "sresume", P,
                                     slots=200, theta=1e-3, reps=3,
                                     pad_to=4, collect_metrics=True)
    assert metrics_equal(ref.metrics, out.metrics)
    assert int(ref.metrics.reps) == 3


def test_fleet_metrics_single_chunk_equals_monolithic(small_jobs):
    """chunk_jobs >= J is one window — bitwise the monolithic replay.
    (Smaller chunks replay per-window slot pools: genuinely different
    dynamics, covered by the mass-conservation test below.)"""
    ref = run_cluster_fleet_strategy(KEY, small_jobs, "sresume", P,
                                     slots=200, theta=1e-3,
                                     collect_metrics=True)
    out = run_cluster_fleet_strategy(KEY, small_jobs, "sresume", P,
                                     slots=200, theta=1e-3,
                                     chunk_jobs=small_jobs.n_jobs,
                                     collect_metrics=True)
    assert metrics_equal(ref.metrics, out.metrics)


def test_fleet_metrics_chunked_mass_conservation(small_jobs):
    out = run_cluster_fleet_strategy(KEY, small_jobs, "sresume", P,
                                     slots=200, theta=1e-3, chunk_jobs=30,
                                     collect_metrics=True)
    m = out.metrics
    assert int(m.depth_hist.sum()) == int(m.n_dispatched)
    assert int(m.n_dispatched) >= small_jobs.total_tasks
    assert int(m.reps) == 1        # windows share replications: max, not sum


def test_combine_windows_sums_and_maxes():
    a = CapacityMetrics(
        depth_hist=np.arange(DEPTH_BINS, dtype=np.int32),
        depth_max=np.int32(3), occupancy=np.float32(10.0),
        spec_launched=np.int32(4), spec_killed=np.int32(1),
        busy_windows=np.ones(N_WINDOWS, np.int32),
        wait_total=np.float32(2.0), n_dispatched=np.int32(120),
        reps=np.int32(2))
    b = a._replace(depth_max=np.int32(7), occupancy=np.float32(5.0))
    m = combine_windows([a, b])
    assert np.array_equal(m.depth_hist,
                          2 * np.arange(DEPTH_BINS, dtype=np.int32))
    assert int(m.depth_max) == 7
    assert float(m.occupancy) == 15.0
    assert int(m.n_dispatched) == 240
    assert int(m.reps) == 2
    with pytest.raises(ValueError):
        combine_windows([])


@multi_device
def test_fleet_metrics_mesh_shape_invariance(small_jobs):
    """1x1 / 2x4 / 8x1 meshes reduce to bit-identical metric pytrees
    (reps=3 does not divide 8, so rep pad+mask is exercised too)."""
    ref = run_cluster_fleet_strategy(KEY, small_jobs, "sresume", P,
                                     slots=200, theta=1e-3, reps=3,
                                     collect_metrics=True)
    for shape in [(1, 1), (2, 4), (8, 1)]:
        out = run_cluster_fleet_strategy(KEY, small_jobs, "sresume", P,
                                         slots=200, theta=1e-3, reps=3,
                                         mesh=fleet_mesh(shape=shape),
                                         collect_metrics=True)
        assert metrics_equal(ref.metrics, out.metrics), shape
