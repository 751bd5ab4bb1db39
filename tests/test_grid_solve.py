"""Fused Algorithm-1 grid solve: Pallas-vs-XLA equivalence and the fused
device-resident fleet pipeline.

The kernel contract (DESIGN.md §18): for every registered optimized
strategy — including the composite `adaptive`, whose sub-strategy argmax
is folded into the kernel — the Pallas backend must agree with the XLA
reference EXACTLY on the integer outputs (r*, choice, sat) and to float
tolerance on the surfaces evaluated at r* (utility/pocd/cost; the
arithmetic is shared but XLA fuses the two programs differently). The
fused fleet chunk programs must be bit-identical to the staged
solve -> stack -> replay pipeline, because fusion only moves WHERE the
same computation runs, never what it computes.

Pallas runs in interpret mode here (CPU container); the same kernel
compiles via Mosaic on TPU.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.optimizer import solve_batch
from repro.fleet import run_cluster_fleet_strategy, run_fleet_strategy
from repro.obs import trace as obs_trace
from repro.sim import SimParams, generate
from repro.sim.runner import jobspecs_of
from repro.strategies import get, names, solve_backend, solve_jobs
from repro.workloads import make_jobset

P = SimParams()
KEY = jax.random.PRNGKey(0)

OPTIMIZED = names(kind="optimized")


def specs_of(jobs, r_min=0.0):
    return jobspecs_of(jobs, P, jnp.float32(1e-4), jnp.float32(r_min))


# ---------------------------------------------------------------------------
# Pallas kernel vs XLA reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("strategy", OPTIMIZED)
@pytest.mark.parametrize("n_jobs,r_max", [(37, 9), (64, 33), (256, 9)])
def test_pallas_matches_xla(strategy, n_jobs, r_max):
    """r*/choice/sat exact, floats within tolerance, for every optimized
    strategy on heterogeneous multi-class jobs. n_jobs=37 and 64 are one
    partial tile (JOB_TILE=128); 256 is two full tiles."""
    jobs = make_jobset("paper-hadoop", n_jobs=n_jobs, seed=2)
    specs = specs_of(jobs)
    xla = solve_jobs(strategy, specs, r_max, backend="xla")
    pal = solve_jobs(strategy, specs, r_max, backend="pallas")
    r_x, ch_x, u_x, p_x, c_x, sat_x = (np.asarray(a) for a in xla)
    r_p, ch_p, u_p, p_p, c_p, sat_p = (np.asarray(a) for a in pal)
    np.testing.assert_array_equal(r_p, r_x)
    np.testing.assert_array_equal(ch_p, ch_x)
    np.testing.assert_array_equal(sat_p, sat_x)
    np.testing.assert_allclose(u_p, u_x, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(p_p, p_x, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(c_p, c_x, rtol=1e-4, atol=1e-5)


def test_backend_selection():
    """"auto" resolves off-TPU to the XLA reference; unknown backends are
    rejected before any dispatch."""
    expected = "pallas" if jax.default_backend() == "tpu" else "xla"
    assert solve_backend("auto") == expected
    assert solve_backend("xla") == "xla"
    assert solve_backend("pallas") == "pallas"
    with pytest.raises(ValueError, match="backend"):
        solve_backend("mosaic")


# ---------------------------------------------------------------------------
# saturation flag (S1)
# ---------------------------------------------------------------------------


def test_saturation_flag_set_and_exact():
    """A too-small grid pins some argmaxes to the last point; sat marks
    exactly those jobs, identically on both backends."""
    jobs = generate(n_jobs=40, seed=1)
    specs = specs_of(jobs)
    for backend in ("xla", "pallas"):
        r, _, _, _, _, sat = solve_jobs("sresume", specs, 2,
                                        backend=backend)
        np.testing.assert_array_equal(np.asarray(sat),
                                      (np.asarray(r) == 1).astype(np.int32))
        assert int(np.asarray(sat).sum()) > 0, backend


def test_solve_batch_warns_on_saturation():
    jobs = generate(n_jobs=40, seed=1)
    specs = specs_of(jobs)
    with pytest.warns(RuntimeWarning, match="saturated"):
        solve_batch("sresume", specs, r_max=2)
    # a generous grid does not saturate — and does not warn
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        solve_batch("sresume", specs, r_max=64)


def test_fleet_warns_on_saturation():
    jobs = generate(n_jobs=30, seed=0)
    with pytest.warns(RuntimeWarning, match="saturated"):
        run_fleet_strategy(KEY, jobs, "sresume", P, reps=1, block_jobs=8,
                           max_r=1)


# ---------------------------------------------------------------------------
# fused chunk programs == staged pipeline, bit for bit (tentpole pin)
# ---------------------------------------------------------------------------


def output_equal(a, b) -> bool:
    for fld in ("job_met", "job_completion", "job_cost"):
        if not np.array_equal(np.asarray(getattr(a.result, fld)),
                              np.asarray(getattr(b.result, fld))):
            return False
    if float(a.result.pocd) != float(b.result.pocd):
        return False
    if float(a.result.mean_cost) != float(b.result.mean_cost):
        return False
    for fld in ("r_opt", "theory_pocd", "theory_cost"):
        if not np.array_equal(np.asarray(getattr(a, fld)),
                              np.asarray(getattr(b, fld))):
            return False
    return True


@pytest.mark.parametrize("strategy", names())
def test_fleet_fused_bit_identical(strategy):
    """Every registered strategy (baselines included: they route through
    the staged path unchanged) replays identically with fused on/off."""
    jobs = generate(n_jobs=40, seed=0)
    kw = dict(reps=2, block_jobs=16, chunk_jobs=20)
    ref = run_fleet_strategy(KEY, jobs, strategy, P, fused=False, **kw)
    out = run_fleet_strategy(KEY, jobs, strategy, P, fused=True, **kw)
    assert output_equal(ref, out), strategy


@pytest.mark.parametrize("strategy", ("sresume", "adaptive", "hadoop_ns"))
def test_cluster_fused_bit_identical(strategy):
    """Finite-capacity path: fused windows (static width = max_r + 2) are
    bit-identical to the staged two-phase pipeline, queue metrics
    included."""
    jobs = generate(n_jobs=45, seed=0)
    kw = dict(slots=200, reps=2, chunk_jobs=15)
    ref = run_cluster_fleet_strategy(KEY, jobs, strategy, P, fused=False,
                                     **kw)
    out = run_cluster_fleet_strategy(KEY, jobs, strategy, P, fused=True,
                                     **kw)
    assert output_equal(ref, out), strategy
    for fld in ("mean_wait", "max_wait", "utilization", "preempted"):
        assert float(getattr(ref.queue, fld)) == \
            float(getattr(out.queue, fld)), fld


def test_fused_pipeline_has_no_solve_dispatch():
    """Acceptance: the fused chunk program shows no solve -> replay host
    transfer — zero phase-1 solve spans and ONE fused dispatch per chunk,
    on both fleet paths."""
    jobs = generate(n_jobs=40, seed=0)
    tr = obs_trace.enable(fresh=True)
    try:
        run_fleet_strategy(KEY, jobs, "sresume", P, reps=1, block_jobs=10,
                           chunk_jobs=20, fused=True)
        run_cluster_fleet_strategy(KEY, jobs, "sresume", P, slots=200,
                                   reps=1, chunk_jobs=20, fused=True)
    finally:
        obs_trace.disable()
    spans = [s.name for s in tr.spans]
    assert sum(s == "fleet.solve" for s in spans) == 0
    assert sum("cluster.solve" in s for s in spans) == 0
    assert sum(s == "fleet.fused[sresume]" for s in spans) == 2
    assert sum(s == "fleet.cluster.fused[sresume]" for s in spans) == 2
    assert sum(s == "fleet.exec[sresume]" for s in spans) == 0
    assert sum(s == "fleet.cluster.replay[sresume]" for s in spans) == 0
