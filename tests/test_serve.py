"""Online serving path: strategy-IR hedged streams, online governor,
stream/mesh invariance, and the RunConfig facade goldens."""
import warnings

import jax
import numpy as np
import pytest

from repro.api import RunConfig, simulate
from repro.serve import (HedgedScheduler, ReplicaPool, RequestTrace,
                         baseline_no_hedge, make_requests, run_serve,
                         serve_trace, uniform_requests)
from repro.sim.runner import run_all
from repro.sim.strategies import SimParams
from repro.strategies import names
from repro.workloads.registry import make_jobset

KEY = jax.random.PRNGKey(11)


def _cols(out):
    r = out.result
    return (np.asarray(r.job_met), np.asarray(r.job_completion),
            np.asarray(r.job_cost))


def _same(a, b):
    return all(np.array_equal(x, y) for x, y in zip(_cols(a), _cols(b)))


# ---------------------------------------------------------------------------
# Dominance: hedging beats no-hedge on the headline serving workloads
# ---------------------------------------------------------------------------


def test_hedged_pocd_dominates_no_hedge_on_flash_crowd():
    """The acceptance headline: hedged PoCD strictly above no-hedge at
    lower or comparable mean machine-time (flash-crowd requests)."""
    reqs = make_requests("flash-crowd", n_requests=600, seed=3)
    outs, r_min = run_serve(KEY, reqs, window=256,
                            strategies=("hadoop_ns", "sresume", "adaptive"))
    base = outs["hadoop_ns"]
    for name in ("sresume", "adaptive"):
        hedged = outs[name]
        assert float(hedged.result.pocd) > float(base.result.pocd), name
        # killing Pareto stragglers at tau_est saves their conditional
        # tails: comparable-or-lower means <= a small slack over baseline
        assert (float(hedged.result.mean_cost)
                <= 1.05 * float(base.result.mean_cost)), name
    assert r_min == pytest.approx(float(base.result.pocd) - 1e-3)


def test_online_refits_lift_pocd_over_no_hedge():
    """Online mode (tail learned from probe completions only) still
    dominates the no-hedge baseline despite probe + cold-epoch traffic."""
    reqs = make_requests("request-storm", n_requests=2000, seed=0)
    on = serve_trace(KEY, reqs, strategy="sresume", window=256,
                     refit_every=250, probe_every=5, min_samples=16)
    base = serve_trace(KEY, reqs, strategy="hadoop_ns", window=256)
    assert on.n_refits >= 3
    assert float(on.result.pocd) > float(base.result.pocd)
    assert on.epoch_strategies[0] == "hadoop_ns"      # cold start
    assert on.epoch_strategies[-1] == "sresume"


# ---------------------------------------------------------------------------
# Determinism / invariance
# ---------------------------------------------------------------------------


def test_window_size_invariance_bitwise():
    reqs = make_requests("flash-crowd", n_requests=300, seed=7)
    a = serve_trace(KEY, reqs, strategy="clone", window=64)
    b = serve_trace(KEY, reqs, strategy="clone", window=512)
    assert _same(a, b)


def test_subset_of_stream_reproduces_outcomes():
    """rid keying: serving a sub-slice yields the slice of the full-stream
    outcomes — draws cannot depend on batch context (order/subset-proof)."""
    reqs = make_requests("flash-crowd", n_requests=256, seed=9)
    full = serve_trace(KEY, reqs, strategy="srestart", window=64)
    part = serve_trace(KEY, reqs.slice(96, 160), strategy="srestart",
                       window=64)
    lo, hi = 96, 160
    assert np.array_equal(np.asarray(part.result.job_completion),
                          np.asarray(full.result.job_completion)[lo:hi])
    assert np.array_equal(np.asarray(part.result.job_cost),
                          np.asarray(full.result.job_cost)[lo:hi])


def test_online_hadoop_ns_equals_known_tail_bitwise():
    """Probes and hedged requests draw through the same spec with the same
    per-rid keys, so the unhedged strategy is bitwise independent of the
    online machinery around it."""
    reqs = make_requests("request-storm", n_requests=512, seed=2)
    on = serve_trace(KEY, reqs, strategy="hadoop_ns", window=64,
                     refit_every=128, probe_every=8)
    off = serve_trace(KEY, reqs, strategy="hadoop_ns", window=64)
    assert _same(on, off)


def test_mesh_sharded_serving_bitwise_equal():
    n_dev = len(jax.devices())
    from repro.fleet import fleet_mesh
    mesh = fleet_mesh(devices=n_dev, reps=1)
    reqs = make_requests("request-storm", n_requests=384, seed=5)
    a = serve_trace(KEY, reqs, strategy="adaptive", window=96,
                    refit_every=128, probe_every=8)
    b = serve_trace(KEY, reqs, strategy="adaptive", window=96,
                    refit_every=128, probe_every=8, mesh=mesh)
    assert _same(a, b)


def test_streamed_equals_monolithic_via_combiner():
    """StreamCombiner accumulation across epochs reproduces a single-shot
    finalize bitwise (the §14 property, extended to serving epochs)."""
    from repro.sim.metrics import StreamCombiner, request_result
    reqs = make_requests("flash-crowd", n_requests=200, seed=4)
    mono = serve_trace(KEY, reqs, strategy="clone", window=256)
    acc = StreamCombiner()
    for lo in range(0, 200, 50):
        part = serve_trace(KEY, reqs.slice(lo, lo + 50), strategy="clone",
                           window=256, combiner=acc)
    assert acc.n_chunks == 4
    assert _same(part, mono)   # last serve_trace finalizes the shared acc


# ---------------------------------------------------------------------------
# Online governor
# ---------------------------------------------------------------------------


def test_governor_refit_recovers_planted_tail_shift():
    """The stream's true tail thickens mid-flight (beta 2.6 -> 1.15); the
    rolling-window refits must track the shift from probe completions."""
    n = 4000
    half = n // 2
    light = uniform_requests(half, t_min=1.0, beta=2.6, D=5.0)
    heavy = uniform_requests(half, t_min=1.0, beta=1.15, D=5.0)
    reqs = RequestTrace(
        rid=np.arange(n, dtype=np.int32),
        arrival=np.concatenate([light.arrival, heavy.arrival]),
        t_min=np.concatenate([light.t_min, heavy.t_min]),
        beta=np.concatenate([light.beta, heavy.beta]),
        D=np.concatenate([light.D, heavy.D]),
        C=np.concatenate([light.C, heavy.C]),
        theta_scale=np.concatenate([light.theta_scale, heavy.theta_scale]),
        job_class=np.concatenate([light.job_class, heavy.job_class]),
        class_names=("shift",))
    out = serve_trace(KEY, reqs, strategy="sresume", window=256,
                      refit_every=400, probe_every=4, tail_capacity=100,
                      min_samples=32)
    assert out.n_refits >= 8
    first_phase = [f.beta for f in out.fits[:3]]
    last_phase = [f.beta for f in out.fits[-2:]]
    assert min(first_phase) > 2.0, first_phase    # light tail seen early
    assert max(last_phase) < 1.6, last_phase      # heavy tail recovered


def test_auto_strategy_follows_governor_decision():
    reqs = make_requests("request-storm", n_requests=1200, seed=6)
    out = serve_trace(KEY, reqs, strategy="auto", window=256,
                      refit_every=200, probe_every=8, min_samples=16)
    assert out.epoch_strategies[0] == "hadoop_ns"
    chosen = set(out.epoch_strategies[1:])
    assert chosen <= set(names(kind="chronos")) | {"hadoop_ns"}
    assert chosen - {"hadoop_ns"}, "governor never picked a hedge"


def test_auto_governor_matches_per_strategy_loop(monkeypatch):
    """The governor's one-program re-solve drives `strategy="auto"` as the
    per-strategy `solve_grid` loop did: the same epoch strategies, fits
    and per-request results."""
    import repro.core as core
    reqs = make_requests("request-storm", n_requests=1200, seed=6)
    kw = dict(strategy="auto", window=256, refit_every=200, probe_every=8,
              min_samples=16)
    new = serve_trace(KEY, reqs, **kw)
    calls = []

    def per_strategy_loop(strategies, job, r_max):
        calls.append(job)
        spec = core.JobSpec.make(**job._asdict())
        best = None
        for s in strategies or names(kind="chronos"):
            sol = core.solve_grid(s, spec, r_max=r_max)
            if best is None or sol.utility > best.utility:
                best = sol
        return best

    monkeypatch.setattr(core, "solve_strategies", per_strategy_loop)
    old = serve_trace(KEY, reqs, **kw)
    assert len(calls) == old.n_refits == new.n_refits > 0
    assert set(new.epoch_strategies) - {"hadoop_ns"}
    assert new.epoch_strategies == old.epoch_strategies
    assert new.fits == old.fits
    assert _same(new, old)


def test_refit_cadence_must_align_with_probes():
    reqs = uniform_requests(64, t_min=1.0, beta=1.5, D=4.0)
    with pytest.raises(ValueError, match="multiple of"):
        serve_trace(KEY, reqs, refit_every=100, probe_every=8)


# ---------------------------------------------------------------------------
# Registry coverage + fixed-r baseline
# ---------------------------------------------------------------------------


def test_every_registered_strategy_serves_via_registry():
    """Serving has no per-strategy code: anything in names() just runs."""
    reqs = uniform_requests(48, t_min=1.0, beta=1.4, D=4.0)
    outs, _ = run_serve(KEY, reqs, window=48, strategies=names())
    assert set(outs) == set(names())
    for name, out in outs.items():
        assert np.isfinite(float(out.result.pocd)), name
        assert np.isfinite(float(out.result.mean_cost)), name


def test_fixed_r_override_baseline():
    reqs = uniform_requests(128, t_min=1.0, beta=1.3, D=4.0)
    out = serve_trace(KEY, reqs, strategy="clone", window=64, r_override=2)
    assert out.mean_r == pytest.approx(2.0)
    base = serve_trace(KEY, reqs, strategy="hadoop_ns", window=64)
    # r=2 cloning lifts PoCD over no-hedge — the benchmark's fixed-r
    # comparison point (at beta=1.3 it is even cheaper: min-of-3 Paretos
    # has tail index 3*beta, far below the unhedged conditional tail)
    assert float(out.result.pocd) > float(base.result.pocd)
    with pytest.raises(ValueError, match="auto"):
        serve_trace(KEY, reqs, strategy="auto", window=64, r_override=2)


def test_scheduler_single_request_consistent_with_stream():
    """HedgedScheduler.execute (one request) and run_workload (stream)
    agree on the same rid when the plan picks the same (strategy, r)."""
    pool = ReplicaPool(n_replicas=8, beta=1.5)
    sched = HedgedScheduler(pool, theta=1e-2, strategy="adaptive",
                            key=jax.random.PRNGKey(3))
    from repro.serve.scheduler import Request
    req = Request(deadline=0.5, rid=17, n_tokens=64)
    o1 = sched.execute(req)
    o2 = sched.execute(req)
    assert o1.latency == o2.latency and o1.machine_time == o2.machine_time


# ---------------------------------------------------------------------------
# RunConfig facade: routing + bit-identity goldens
# ---------------------------------------------------------------------------


def test_runconfig_routing():
    assert RunConfig().resolve_path() == "flat"
    assert RunConfig(devices=8).resolve_path() == "flat"
    assert RunConfig(slots=32).resolve_path() == "capacity"
    assert RunConfig(governor=object()).resolve_path() == "capacity"
    assert RunConfig(serve=True).resolve_path() == "serve"
    assert RunConfig(refit_every=64).resolve_path() == "serve"
    assert RunConfig(slots=2, path="flat").resolve_path() == "flat"
    with pytest.raises(ValueError, match="unknown path"):
        RunConfig(path="warp").resolve_path()


def test_simulate_flat_bit_identical_to_run_all():
    jobs = make_jobset("paper-hadoop", n_jobs=48, seed=0)
    p = SimParams()
    got, r_min = simulate(KEY, jobs, p)
    want, r_min_w = run_all(KEY, jobs, p)
    assert r_min == r_min_w
    assert set(got) == set(want)
    for name in got:
        assert np.array_equal(
            np.asarray(got[name].result.job_completion),
            np.asarray(want[name].result.job_completion)), name
        assert np.array_equal(
            np.asarray(got[name].result.job_cost),
            np.asarray(want[name].result.job_cost)), name


def test_simulate_capacity_bit_identical_to_run_cluster():
    from repro.cluster.engine import run_cluster
    jobs = make_jobset("flash-crowd", n_jobs=40, seed=1)
    p = SimParams()
    cfg = RunConfig(slots=16, strategies=("hadoop_ns", "clone"))
    got, _ = simulate(KEY, jobs, p, cfg=cfg)
    want, _ = run_cluster(KEY, jobs, p, slots=16,
                          strategies=("hadoop_ns", "clone"))
    for name in got:
        assert np.array_equal(
            np.asarray(got[name].result.job_completion),
            np.asarray(want[name].result.job_completion)), name


def test_simulate_serve_bit_identical_to_run_serve():
    reqs = uniform_requests(96, t_min=1.0, beta=1.5, D=4.0)
    cfg = RunConfig(serve=True, window=48,
                    strategies=("hadoop_ns", "sresume"), theta=1e-3)
    got, r1 = simulate(KEY, reqs, cfg=cfg)
    want, r2 = run_serve(KEY, reqs, theta=1e-3, window=48,
                         strategies=("hadoop_ns", "sresume"))
    assert r1 == r2
    for name in got:
        assert _same(got[name], want[name]), name


def test_legacy_kwargs_shim_warns_and_matches_cfg():
    jobs = make_jobset("paper-hadoop", n_jobs=32, seed=2)
    p = SimParams()
    cfg_outs, _ = simulate(KEY, jobs, p,
                           cfg=RunConfig(theta=1e-3, max_r=6))
    with pytest.warns(DeprecationWarning, match="RunConfig"):
        kw_outs, _ = simulate(KEY, jobs, p, theta=1e-3, max_r=6)
    for name in cfg_outs:
        assert np.array_equal(
            np.asarray(cfg_outs[name].result.job_completion),
            np.asarray(kw_outs[name].result.job_completion)), name


def test_legacy_unknown_kwarg_fails_loudly():
    jobs = make_jobset("paper-hadoop", n_jobs=8, seed=0)
    with pytest.raises(TypeError, match="unexpected keyword"):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            simulate(KEY, jobs, SimParams(), chunk_size=4)


def test_flat_path_rejects_oracle_false():
    jobs = make_jobset("paper-hadoop", n_jobs=8, seed=0)
    with pytest.raises(ValueError, match="oracle"):
        simulate(KEY, jobs, SimParams(), cfg=RunConfig(oracle=False))


def test_import_repro_is_lazy():
    import subprocess
    import sys
    code = ("import sys, repro; "
            "assert 'jax' not in sys.modules, 'import repro pulled in jax'; "
            "from repro import RunConfig; "
            "assert RunConfig().resolve_path() == 'flat'")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True)
    assert r.returncode == 0, r.stderr
