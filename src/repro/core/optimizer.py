"""Algorithm 1 — the unifying optimization algorithm (paper Section V.B).

Two implementations, tested to agree:

1. `solve_algorithm1` — paper-faithful hybrid: gradient-based line search on
   the continuous relaxation over the concave region r > Gamma_strategy
   (Theorem 8), then exhaustive search over the integer prefix
   r in {0, ..., ceil(Gamma) - 1}. Guaranteed optimal (Theorem 9): U is concave
   above Gamma so the best integer there is adjacent to the continuous optimum.

2. `solve_grid` / `solve_batch` — the production path: vectorized evaluation of
   U over an integer grid with a *certified* upper bound on the optimal r
   (cost grows at least linearly in r while the utility term is bounded above
   by lg(1 - R_min), so no maximizer can exist beyond the bound). This is
   exact, jit-friendly, and solves millions of jobs per second under vmap —
   the form the serving scheduler uses online. `solve_strategies` solves one
   job over a whole strategy set on one grid in one program: the re-solve of
   the tail and step governors.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import trace as obs_trace
from .utility import JobSpec, gamma, utility, pocd_of, cost_of


class Solution(NamedTuple):
    strategy: str
    r_opt: int
    utility: float
    pocd: float
    cost: float


# ---------------------------------------------------------------------------
# Certified grid bound
# ---------------------------------------------------------------------------


def r_upper_bound(strategy: str, job: JobSpec, u_floor) -> int:
    """Smallest R such that U(r) < u_floor for all r >= R.

    U(r) <= lg(1 - R_min) - theta*C*slope*r, where the spec's `r_slope`
    lower-bounds the marginal machine-time of one extra attempt (clone:
    N * tau_kill — every task kills r clones; reactive strategies:
    N * p_straggler * (tau_kill - tau_est)).
    """
    from ..strategies import get
    spec = get(strategy)
    if spec.r_slope is None:
        raise ValueError(f"strategy {strategy!r} has no certified grid "
                         f"bound (r_slope)")
    slope = spec.r_slope(job) * float(job.theta) * float(job.C)
    cap = float(np.log10(max(1.0 - float(job.R_min), 1e-30)))
    if slope <= 0.0 or not np.isfinite(u_floor):
        return 64
    bound = int(np.ceil((cap - u_floor) / slope)) + 1
    return int(np.clip(bound, 1, 4096))


# ---------------------------------------------------------------------------
# Production path: exact vectorized grid solve
# ---------------------------------------------------------------------------


def utility_grid(strategy: str, job: JobSpec, r_max: int):
    rs = jnp.arange(r_max, dtype=jnp.float32)
    return rs, utility(strategy, rs, job)


@functools.partial(jax.jit, static_argnames=("strategy", "r_max"))
def _solve_grid_device(strategy: str, job: JobSpec, r_max: int):
    """The whole single-job solve as one program: (r*, U(r*), pocd, cost)
    device scalars, fetched by the wrapper in ONE transfer."""
    rs = jnp.arange(r_max, dtype=jnp.float32)
    us = utility(strategy, rs, job)
    i = jnp.argmax(us)
    r = rs[i]
    return i.astype(jnp.int32), us[i], pocd_of(strategy, r, job), \
        cost_of(strategy, r, job)


def solve_grid(strategy: str, job: JobSpec, r_max: int | None = None) -> Solution:
    """Exact integer solve for one strategy (python wrapper, jit inside).

    One device->host transfer per call: the argmax, the r*-indexed gather,
    and the pocd/cost evaluation all stay in a single compiled program
    whose four scalars come back in one batched `device_get` (the previous
    float()/int() coercions each forced their own sync inside the span).
    """
    with obs_trace.span("optimizer.solve_grid", strategy=strategy) as sp:
        if r_max is None:
            u0 = utility(strategy, jnp.float32(0.0), job)
            with obs_trace.span("d2h.wait"):
                u0 = float(u0)
            r_max = max(r_upper_bound(strategy, job, u0), 2)
        sp.set(r_max=int(r_max))
        out = _solve_grid_device(strategy, job, int(r_max))
        with obs_trace.span("d2h.wait"):
            r, u, p, c = jax.device_get(out)
        return Solution(strategy, int(r), float(u), float(p), float(c))


@functools.partial(jax.jit, static_argnames=("strategies", "r_max"))
def _solve_strategies_device(strategies: tuple, packed, r_max: int):
    """Every strategy's grid solve and the pick among them as one program:
    (strategy index, r*, U(r*), pocd, cost) device scalars. `packed` holds
    the JobSpec's leaves in field order, one float32 vector."""
    job = JobSpec(*(packed[i] for i in range(len(JobSpec._fields))))
    rs = jnp.arange(r_max, dtype=jnp.float32)
    best = None
    for k, s in enumerate(strategies):
        us = utility(s, rs, job)
        i = jnp.argmax(us)
        r = rs[i]
        sol = (jnp.int32(k), i.astype(jnp.int32), us[i],
               pocd_of(s, r, job), cost_of(s, r, job))
        if best is None:
            best = sol
        else:
            # strict: on a tie the earlier strategy keeps its place
            take = sol[2] > best[2]
            best = tuple(jnp.where(take, a, b) for a, b in zip(sol, best))
    return best


def solve_strategies(strategies, job: JobSpec, r_max: int) -> Solution:
    """Best (strategy, r*) over a strategy set on the grid r < r_max, as one
    compiled program with one input transfer and one device->host read.

    The same answer as solving each strategy with `solve_grid(s, job,
    r_max)` in turn and keeping the first strictly higher utility, so ties
    go to the earlier strategy. `strategies=None` means every registered
    Chronos strategy. `job` is one job with host leaves (Python or NumPy
    scalars): they are rounded to float32 as `JobSpec.make` rounds them and
    sent as one vector.
    """
    if strategies is None:
        from ..strategies import names
        strategies = names(kind="chronos")
    strategies = tuple(strategies)
    if not strategies:
        raise ValueError("solve_strategies needs at least one strategy")
    with obs_trace.span("optimizer.solve_strategies",
                        n_strategies=len(strategies), r_max=int(r_max)):
        out = _solve_strategies_device(
            strategies, np.asarray(job, np.float32), int(r_max))
        with obs_trace.span("d2h.wait"):
            k, r, u, p, c = jax.device_get(out)
    return Solution(strategies[int(k)], int(r), float(u), float(p), float(c))


def solve(job: JobSpec, strategies=None) -> Solution:
    """Best (strategy, r) pair for a job.

    `strategies=None` sweeps every registered Chronos strategy
    (`repro.strategies.names(kind="chronos")`). Each strategy is solved on
    its own certified grid by `solve_grid`, which reads its result before
    the next strategy is dispatched: one program and one or two reads per
    strategy. `solve_strategies` solves a set on one shared grid in one.
    """
    if strategies is None:
        from ..strategies import names
        strategies = names(kind="chronos")
    with obs_trace.span("optimizer.solve", n_strategies=len(strategies)):
        best = None
        for s in strategies:
            sol = solve_grid(s, job)
            if best is None or sol.utility > best.utility:
                best = sol
        return best


def solve_batch(strategy: str, jobs: JobSpec, r_max: int = 64,
                backend: str = "auto"):
    """Vectorized exact solve for a batch of jobs (stacked JobSpec leaves).

    Returns (r_opt[int32], utility, pocd, cost) arrays — a thin wrapper over
    the strategy IR's `grid_solve` on the named spec (`backend` selects the
    fused Pallas kernel vs the vmapped XLA reference; "auto" = pallas on
    TPU). The grid bound r_max must be >= the certified bound for
    correctness (64 covers every configuration the paper sweeps; the
    governor asserts via r_upper_bound) — a too-small grid is no longer
    silent: any job whose argmax saturated at r_max - 1 triggers a
    RuntimeWarning here (the jitted entries below return the raw flag
    instead, host checks being impossible under trace).
    """
    r, u, p, c, sat = solve_batch_sat_jit(strategy, jobs, r_max,
                                          backend=backend)
    n_sat = int(np.asarray(sat).sum())
    if n_sat:
        import warnings
        warnings.warn(
            f"solve_batch({strategy!r}, r_max={r_max}): argmax saturated "
            f"at the grid edge for {n_sat} job(s) — r* may be truncated; "
            f"raise r_max past core.optimizer.r_upper_bound",
            RuntimeWarning, stacklevel=2)
    return r, u, p, c


def _solve_batch_sat(strategy: str, jobs: JobSpec, r_max: int = 64,
                     backend: str = "auto"):
    """(r_opt, utility, pocd, cost, sat) — solve_batch plus the saturation
    flag, jit-safe (no host check)."""
    from ..strategies import get, grid_solve
    return grid_solve(get(strategy), jobs, r_max, backend=backend)


solve_batch_sat_jit = jax.jit(_solve_batch_sat, static_argnums=(0, 2),
                              static_argnames=("backend",))


@functools.partial(jax.jit, static_argnums=(0, 2),
                   static_argnames=("backend",))
def solve_batch_jit(strategy: str, jobs: JobSpec, r_max: int = 64,
                    backend: str = "auto"):
    """Jitted legacy 4-tuple entry (benchmarks, governor hot loops)."""
    return _solve_batch_sat(strategy, jobs, r_max, backend=backend)[:4]


# ---------------------------------------------------------------------------
# Paper-faithful Algorithm 1
# ---------------------------------------------------------------------------


def solve_algorithm1(strategy: str, job: JobSpec, eta: float = 1e-6,
                     alpha: float = 0.3, xi: float = 0.5,
                     max_iters: int = 200) -> Solution:
    """Phase 1: gradient ascent + backtracking line search on the concave
    region r >= max(ceil(Gamma), 0); Phase 2: exhaustive over the integer
    prefix below Gamma. Mirrors the paper's pseudocode (ascent on -U's
    gradient with Armijo backtracking, parameters eta/alpha/xi)."""
    g = float(gamma(strategy, job))
    r0 = max(int(np.ceil(g)), 0)

    u_fn = lambda r: utility(strategy, jnp.float32(r), job)
    du_fn = jax.grad(lambda r: utility(strategy, r, job))

    # --- Phase 1: continuous concave maximization from r0 ---
    r = float(r0)
    if np.isfinite(float(u_fn(r))):
        for _ in range(max_iters):
            grad_val = float(du_fn(jnp.float32(r)))
            if abs(grad_val) <= eta:
                break
            step = 1.0
            dr = grad_val  # ascent direction
            # Armijo backtracking
            while True:
                cand = max(r + step * dr, float(r0))
                if float(u_fn(cand)) >= float(u_fn(r)) + alpha * step * grad_val * dr:
                    break
                step *= xi
                if step < 1e-10:
                    break
            new_r = max(r + step * dr, float(r0))
            if abs(new_r - r) < 1e-9:
                break
            r = new_r
    # Concave region: best integer is adjacent to the continuous optimum.
    cands = {r0, int(np.floor(r)), int(np.ceil(r))}
    # --- Phase 2: integer prefix below Gamma ---
    cands.update(range(0, r0))
    cands = sorted(c for c in cands if c >= 0)
    best_r, best_u = 0, -np.inf
    for c in cands:
        u = float(u_fn(c))
        if u > best_u:
            best_r, best_u = c, u
    return Solution(strategy, best_r, best_u,
                    float(pocd_of(strategy, best_r, job)),
                    float(cost_of(strategy, best_r, job)))
