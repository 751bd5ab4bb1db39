"""StrategySpec — the declarative strategy IR and its registry.

One `StrategySpec` is the single source of truth for a speculative-execution
strategy across all backends:

  analytic        — `log_task_fail` / `cost` closed-forms (paper Thms 1-6
                    style), lowered by `utility_of` / `grid_solve` into the
                    Algorithm-1 exact integer solve;
  Monte Carlo     — `draw`: one replication of per-task (completion,
                    machine-time) over a flat JobSet (`repro.sim`);
  capacity replay — `build_table`: the AttemptTable lowering the cluster
                    engine schedules on a bounded slot pool (`repro.cluster`);
  Pallas          — `tile_outcome`: the per-tile kernel body the fused MC
                    kernel derives its modes from (`repro.kernels`);
  online serving  — `draw` again, one lane per request: `repro.serve`
                    executes every registered strategy as a hedging policy
                    on live request streams with zero serving-side edits.

`register()` / `get()` / `names()` form the registry; every runner,
optimizer dispatch, kernel mode table, and CLI flag enumerates strategies
through `names()` — there is deliberately no other strategy list in the
codebase. Registration order is stable and public: `index_of()` feeds the
per-strategy PRNG key derivation in `run_all` / `run_cluster`
(`fold_in(key, index_of(name))`), so registering new strategies never
perturbs the draws of existing ones.

Import-layering rule: this package may import `repro.core`'s leaf math
(pocd/cost/pareto closed forms) but `repro.core` only imports the registry
*lazily* inside dispatch functions — that one-way rule is what lets
`core.utility` dispatch through specs while spec closures reuse core math.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

NEG_INF = -jnp.inf

#: spec.kind values — "chronos" strategies have analytic forms and solve r*
#: per job (Algorithm 1); "baseline" strategies run at r = 0 with empirical
#: outcomes only; "meta" strategies also solve r* but compose other specs
#: (e.g. `adaptive`) and have no single runtime execution mode of their own.
KINDS = ("baseline", "chronos", "meta")


class StrategySpec(NamedTuple):
    """Declarative strategy description; closures are jit-traceable.

    Closure signatures (jobs: JobSet-like, job: JobSpec-like, p: SimParams):
      draw(key, jobs, r_task, choice_task, p, *, max_r, oracle)
          -> (completion (T,), machine (T,))
      build_table(key, jobs, r_task, choice_task, p, *, max_r, oracle)
          -> AttemptTable
      log_task_fail(r, job) -> log P(one task misses D)      [optional]
      cost(r, job)          -> E[T] machine time per job     [optional]
      gamma(job)            -> Thm-8 concavity threshold     [optional]
      r_slope(job)          -> float lower bound on marginal machine time
                               of one extra attempt (host-side) [optional]
      choose(r, jobs_spec)  -> (J,) int32 per-job sub-strategy id [optional]
      tile_outcome(att, t_min, tau_est, tau_kill, D, r, *, phi)
          -> (completion, machine) Pallas tile body; att (R, N, Jt)
             with jobs on lanes, per-job operands (1, Jt) rows,
             outputs (N, Jt)                                 [optional]
      allocate(jobs_spec, U, cost, budget) -> (J,) int32 r per job
          — budget-allocation policy consulted ONLY by the coupled
          solver (`repro.coupled`): replaces the Lagrangian dual with
          the spec's own split of the shared budget (the arXiv
          1501.02330 competitive-cloning baselines). U/cost are the
          (J, r_max) utility and PRICED machine-time grids. [optional]

    `components` names the registered sub-strategies a composite (meta)
    spec maximizes over, in `choose`-id order. The fused Pallas grid-solve
    kernel folds the composite's per-r sub-strategy argmax into its single
    pass from these names (`choose`'s take_along_axis form has no Mosaic
    lowering); the XLA reference path keeps using the closures.
    """
    name: str
    kind: str                 # one of KINDS
    race: bool                # capacity replay: losers hold slots until the
    #                           task completes (vs a kill-timer hold_cap)
    detectable: bool          # straggler detection honours `oracle=False`
    draw: Callable
    build_table: Callable
    log_task_fail: Optional[Callable] = None
    cost: Optional[Callable] = None
    gamma: Optional[Callable] = None
    r_slope: Optional[Callable] = None
    choose: Optional[Callable] = None
    tile_outcome: Optional[Callable] = None
    components: Optional[tuple] = None
    allocate: Optional[Callable] = None

    @property
    def optimized(self) -> bool:
        """Does Algorithm 1 solve a per-job r* for this strategy?"""
        return self.kind != "baseline"


_REGISTRY: dict[str, StrategySpec] = {}


def register(spec: StrategySpec, replace: bool = False) -> StrategySpec:
    if spec.kind not in KINDS:
        raise ValueError(f"unknown kind {spec.kind!r}; expected one of {KINDS}")
    if spec.optimized and (spec.log_task_fail is None or spec.cost is None):
        raise ValueError(
            f"strategy {spec.name!r} is kind={spec.kind!r} but lacks the "
            f"analytic log_task_fail/cost closed-forms Algorithm 1 needs")
    if spec.components:
        missing = tuple(n for n in spec.components if n not in _REGISTRY)
        if missing:
            raise ValueError(
                f"strategy {spec.name!r} composes unregistered "
                f"components {missing}")
        if spec.choose is None:
            raise ValueError(f"strategy {spec.name!r} declares components "
                             f"but no choose closure")
    if spec.name in _REGISTRY and not replace:
        raise ValueError(f"strategy {spec.name!r} already registered")
    _REGISTRY[spec.name] = spec
    return spec


def get(name: str) -> StrategySpec:
    if name not in _REGISTRY:
        known = ", ".join(_REGISTRY)
        raise ValueError(f"unknown strategy {name!r}; registered: {known}")
    return _REGISTRY[name]


def names(kind: Optional[str] = None) -> tuple:
    """Registered strategy names in registration order.

    `kind` filters on `StrategySpec.kind` ("baseline" | "chronos" | "meta");
    `kind="optimized"` selects every strategy with a per-job r* solve.
    """
    if kind is None:
        return tuple(_REGISTRY)
    if kind == "optimized":
        return tuple(n for n, s in _REGISTRY.items() if s.optimized)
    if kind not in KINDS:
        raise ValueError(f"unknown kind {kind!r}; expected one of {KINDS}")
    return tuple(n for n, s in _REGISTRY.items() if s.kind == kind)


def index_of(name: str) -> int:
    """Stable registration index of a strategy (per-name PRNG key slot)."""
    get(name)
    return list(_REGISTRY).index(name)


# ---------------------------------------------------------------------------
# Analytic lowering: job PoCD, net utility, exact grid solve
# ---------------------------------------------------------------------------


def job_pocd(log_p_fail, N):
    """R = (1 - P_fail)^N, computed stably (core.pocd's log-space form)."""
    from ..core.pocd import _job_pocd_from_log_fail
    return _job_pocd_from_log_fail(log_p_fail, N)


def pocd_of_spec(spec: StrategySpec, r, job):
    """Job-level PoCD R(r) from the spec's per-task closed form."""
    if spec.log_task_fail is None:
        raise ValueError(f"strategy {spec.name!r} has no analytic PoCD")
    return job_pocd(spec.log_task_fail(r, job), job.N)


def cost_of_spec(spec: StrategySpec, r, job):
    """Expected machine time E[T](r) from the spec's closed form."""
    if spec.cost is None:
        raise ValueError(f"strategy {spec.name!r} has no analytic cost")
    return spec.cost(r, job)


def utility_of(spec: StrategySpec, r, job):
    """U(r) = lg(R(r) - R_min) - theta * C * E[T]; -inf below the SLA floor."""
    R = pocd_of_spec(spec, r, job)
    E = cost_of_spec(spec, r, job)
    gap = R - job.R_min
    log_term = jnp.where(gap > 0.0, jnp.log10(jnp.maximum(gap, 1e-30)),
                         NEG_INF)
    return log_term - job.theta * job.C * E


#: Algorithm-1 backends. "xla" is the vmapped reference; "pallas" is the
#: fused grid-solve kernel (kernels/grid_solve.py), asserted equivalent
#: for every registered strategy; "auto" picks pallas on TPU and the XLA
#: reference everywhere else (on CPU the kernel runs in interpret mode —
#: correct but slower than XLA, so it is test-opt-in off-TPU).
BACKENDS = ("auto", "xla", "pallas")


def solve_backend(backend: str = "auto") -> str:
    """Resolve an Algorithm-1 backend name to "xla" | "pallas"."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown solve backend {backend!r}; "
                         f"expected one of {BACKENDS}")
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return backend


def _grid_solve_xla(spec: StrategySpec, jobs, r_max: int):
    def one(job):
        rs = jnp.arange(r_max, dtype=jnp.float32)
        us = utility_of(spec, rs, job)
        i = jnp.argmax(us)
        r = rs[i]
        sat = (i >= r_max - 1).astype(jnp.int32)
        return (i.astype(jnp.int32), us[i], pocd_of_spec(spec, r, job),
                cost_of_spec(spec, r, job), sat)

    return jax.vmap(one)(jobs)


def grid_solve(spec: StrategySpec, jobs, r_max: int, *, backend="auto"):
    """Vectorized exact integer solve over r in {0, ..., r_max - 1}.

    `jobs` is a batched JobSpec (stacked leaves). Returns (r_opt[int32],
    utility, pocd, cost, sat[int32]) arrays — the production Algorithm-1
    path (`core.optimizer.solve_batch` delegates here). `sat` flags jobs
    whose argmax landed on the last grid point: their r* may be silently
    truncated (the grid is only exact when r_max exceeds the certified
    `r_upper_bound`), so callers warn/assert on it.
    """
    if solve_backend(backend) == "pallas":
        # lazy: kernels import this package at module load (layering rule)
        from ..kernels.ops import grid_solve_fused
        r, choice, u, p, c, sat = grid_solve_fused(spec.name, jobs, r_max)
        return r, u, p, c, sat
    return _grid_solve_xla(spec, jobs, r_max)


def solve_jobs(strategy: str, jobs, r_max: int, *, backend="auto"):
    """Grid solve + the spec's per-job sub-strategy choice.

    Returns (r_opt[int32], choice[int32], utility, pocd, cost, sat[int32]);
    `choice` is zeros for every non-composite strategy, `sat` is the grid
    saturation flag (see `grid_solve`).
    """
    spec = get(strategy)
    if solve_backend(backend) == "pallas":
        from ..kernels.ops import grid_solve_fused
        r, choice, u, p, c, sat = grid_solve_fused(strategy, jobs, r_max)
        return r, choice, u, p, c, sat
    r, u, p, c, sat = _grid_solve_xla(spec, jobs, r_max)
    if spec.choose is None:
        choice = jnp.zeros_like(r)
    else:
        choice = spec.choose(r.astype(jnp.float32), jobs)
    return r, choice, u, p, c, sat


solve_jobs_jit = jax.jit(solve_jobs, static_argnums=(0, 2),
                         static_argnames=("backend",))
