"""Algorithm 1 vs exact grid solve; batch solver; estimator (Eq. 30/31)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (JobSpec, solve_grid, solve_strategies,
                        solve_algorithm1, solve, solve_batch, ProgressReport,
                        estimate_completion_chronos, estimate_completion_naive,
                        handoff_offset, fit_mle, sample)
from repro.strategies import names

CASES = [
    dict(t_min=10, beta=2.0, D=50, N=10, theta=1e-3),
    dict(t_min=10, beta=1.2, D=100, N=50, theta=1e-4),
    dict(t_min=5, beta=1.5, D=40, N=200, theta=1e-4),
    dict(t_min=10, beta=3.0, D=25, N=1000, theta=1e-5),
    dict(t_min=10, beta=2.0, D=50, N=10, theta=1e-2),    # cost-critical
    dict(t_min=10, beta=1.1, D=200, N=5000, theta=1e-6),  # PoCD-critical
]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("strategy", ["clone", "srestart", "sresume"])
def test_algorithm1_is_optimal(case, strategy):
    """Paper-faithful Algorithm 1 finds the same optimum as exhaustive search."""
    job = JobSpec.make(**case)
    a = solve_algorithm1(strategy, job)
    b = solve_grid(strategy, job, r_max=256)
    assert a.utility == pytest.approx(b.utility, abs=1e-4), (a, b)
    # utilities can tie between adjacent r; only require equal utility value


def test_solve_picks_best_strategy():
    job = JobSpec.make(t_min=10, beta=2.0, D=50, N=10, theta=1e-3)
    best = solve(job)
    per = {s: solve_grid(s, job).utility for s in ("clone", "srestart", "sresume")}
    assert best.utility == pytest.approx(max(per.values()), abs=1e-6)
    assert best.strategy == max(per, key=per.get)


def _governor_spec(t_min, beta, D, N, theta, R_min=0.0):
    """A JobSpec with host leaves, built as the tail governor builds it."""
    return JobSpec(t_min=t_min, beta=beta, D=D, N=N, tau_est=0.3 * t_min,
                   tau_kill=(0.3 + 0.5) * t_min, phi_est=0.25, C=1.0,
                   theta=theta, R_min=R_min)


# request-storm: one leaf request, Pareto(0.709 ms, 1.677), D = 2x the mean
_STORM = dict(t_min=0.000709, beta=1.677, D=0.00351, N=1, theta=1e-3)
# its no-hedge PoCD, P(T <= D) = 1 - (t_min / D)^beta
_STORM_NS = 1.0 - (_STORM["t_min"] / _STORM["D"]) ** _STORM["beta"]
_CHRONOS = names(kind="chronos")


@pytest.mark.parametrize("spec,strategies", [
    (_STORM, _CHRONOS),
    # the clone variants tie on a one-task job: the first listed must win
    (_STORM, ("clone_sjf", "clone", "clone_prop")),
    (dict(t_min=10.0, beta=1.5, D=40.0, N=200, theta=1e-4), _CHRONOS),
    (dict(_STORM, R_min=_STORM_NS - 1e-3), _CHRONOS),
    # an SLA no strategy reaches: every utility is -inf
    (dict(_STORM, R_min=1.0), _CHRONOS),
    (dict(t_min=10.0, beta=2.0, D=50.0, N=10, theta=1e-3), ("srestart",)),
], ids=["storm", "storm-clone-ties", "hadoop-200", "storm-rmin-ns",
        "unreachable-sla", "one-strategy"])
def test_solve_strategies_matches_per_strategy_loop(spec, strategies):
    """One program over a strategy set picks what the per-strategy
    `solve_grid` loop picks (first strictly best utility, ties to the
    earlier strategy), from the inputs `JobSpec.make` would round to."""
    host = _governor_spec(**spec)
    job = JobSpec.make(**host._asdict())
    assert (np.asarray(host, np.float32).tobytes()
            == np.asarray(job, np.float32).tobytes())
    best = None
    for s in strategies:
        sol = solve_grid(s, job, r_max=9)
        if best is None or sol.utility > best.utility:
            best = sol
    got = solve_strategies(strategies, host, r_max=9)
    assert (got.strategy, got.r_opt) == (best.strategy, best.r_opt)
    np.testing.assert_allclose([got.utility, got.pocd, got.cost],
                               [best.utility, best.pocd, best.cost],
                               rtol=1e-6)


def test_solve_batch_matches_scalar():
    rng = np.random.default_rng(1)
    n = 64
    jobs = JobSpec.make(
        t_min=jnp.asarray(rng.uniform(5, 20, n), jnp.float32),
        beta=jnp.asarray(rng.uniform(1.2, 3.0, n), jnp.float32),
        D=jnp.asarray(rng.uniform(60, 200, n), jnp.float32),
        N=jnp.asarray(rng.integers(5, 500, n), jnp.float32),
        tau_est=jnp.asarray(rng.uniform(2, 5, n), jnp.float32),
        tau_kill=jnp.asarray(rng.uniform(6, 10, n), jnp.float32),
        phi_est=jnp.asarray(rng.uniform(0.1, 0.8, n), jnp.float32),
        C=1.0 + jnp.zeros(n), theta=1e-4 + jnp.zeros(n), R_min=jnp.zeros(n))
    r_b, u_b, _, _ = solve_batch("sresume", jobs, r_max=64)
    for i in range(0, n, 7):
        job_i = JobSpec(*(leaf[i] for leaf in jobs))
        s = solve_grid("sresume", job_i, r_max=64)
        assert int(r_b[i]) == s.r_opt or float(u_b[i]) == pytest.approx(
            s.utility, abs=1e-5)


def test_estimator_startup_awareness():
    """Eq. 30: chronos estimator is exact for linear-progress tasks with
    startup overhead; the naive one overestimates completion time."""
    startup, work, t_lau = 12.0, 40.0, 2.0
    t_now = t_lau + startup + 0.5 * work
    rep = ProgressReport(
        t_lau=jnp.float32(t_lau), t_fp=jnp.float32(t_lau + startup),
        fp=jnp.float32(1e-6), t_now=jnp.float32(t_now), cp=jnp.float32(0.5))
    true_completion = t_lau + startup + work
    est_c = float(estimate_completion_chronos(rep))
    est_n = float(estimate_completion_naive(rep))
    assert est_c == pytest.approx(true_completion, rel=1e-3)
    assert est_n > true_completion  # startup inflates the naive estimate


def test_estimator_reduces_false_positives():
    """With heavy startup, naive estimation flags non-stragglers (paper SecVI)."""
    rng = np.random.default_rng(2)
    n = 2000
    startup = 10.0
    work = 20.0 * rng.uniform(size=n) ** (-1 / 2.0)  # Pareto work
    deadline = 120.0
    tau = 25.0
    cp = np.clip((tau - startup) / work, 1e-6, 1.0)
    rep = ProgressReport(
        t_lau=jnp.zeros(n), t_fp=jnp.full((n,), startup, jnp.float32),
        fp=jnp.full((n,), 1e-6, jnp.float32),
        t_now=jnp.full((n,), tau, jnp.float32), cp=jnp.asarray(cp, jnp.float32))
    true_straggler = (startup + work) > deadline
    flag_c = np.asarray(estimate_completion_chronos(rep)) > deadline
    flag_n = np.asarray(estimate_completion_naive(rep)) > deadline
    fp_c = (flag_c & ~true_straggler).sum()
    fp_n = (flag_n & ~true_straggler).sum()
    assert fp_c <= fp_n
    assert fp_c / n < 0.02


def test_handoff_offset_eq31():
    b = float(handoff_offset(b_start=100.0, b_est=50.0, tau_est=20.0,
                             t_fp=10.0, t_lau=2.0))
    rate = 50.0 / 10.0
    assert b == pytest.approx(100.0 + 50.0 + rate * 8.0)


def test_pareto_mle_recovers_params():
    key = jax.random.PRNGKey(0)
    x = sample(key, 7.0, 1.8, (20000,))
    fit = fit_mle(x)
    assert float(fit.t_min) == pytest.approx(7.0, rel=2e-2)
    assert float(fit.beta) == pytest.approx(1.8, rel=5e-2)
