"""Strategy execution under finite capacity.

Every registered strategy (`repro.strategies`) is lowered to an
`AttemptTable` by its spec's `build_table` closure, using *exactly the
same* PRNG splits and Pareto draws as the flat simulator
(`sim/strategies.py`), so at `slots=None` (infinite capacity) the cluster
engine reproduces the flat results draw-for-draw; with finite slots the same
draws are replayed through the bounded pool, exposing queueing delay,
utilization, and the PoCD degradation speculation induces under load.

Replay is a small fixed-point relaxation (default 2 passes):

  pass 1  schedules primary attempts only (release = job arrival),
  pass k  recomputes speculative releases as primary_start + rel_offset
          (tau_est checks / launch ranks follow the primary's actual start)
          and reschedules the combined unit set in dispatch order.

The whole replay is ONE compiled program (`backend="jit"`, the default):
every pass is a fused `masked_dispatch` (stable key sort + slot-pool scan +
unsort, all inside jit), the relaxation is a `lax.fori_loop` over those
passes, and `run_cluster_strategy` compiles solve -> build -> replay ->
metrics end-to-end per strategy with zero host round-trips. Monte-Carlo
replications (`reps=`) vmap over split keys inside the same program. The
original host-orchestrated path (numpy flatnonzero/argsort compaction, one
device launch per pass) survives behind `backend="host"` as the equivalence
oracle.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import trace as obs_trace
from ..obs.metrics import CapacityMetrics, capacity_metrics, reduce_reps
from ..sim.metrics import SimResult, aggregate, net_utility
from ..sim.runner import jobspecs_of, mean_over_reps, strategy_keys
from ..sim.strategies import SimParams
from ..sim.trace import JobSet, jobset_arrays, jobset_of
from ..strategies import get, names, solve_jobs_jit
from ..strategies.table import row_major
from .admission import (AdmissionConfig, GovernorConfig, admit_jobs,
                        apply_governor)
from .events import (AttemptTable, dispatch_scan, masked_dispatch,
                     predicted_holds, realize)
from .slots import DISCIPLINES, dispatch_order, make_pool, utilization


class QueueMetrics(NamedTuple):
    mean_wait: jnp.ndarray      # mean slot-acquisition delay over attempts
    max_wait: jnp.ndarray
    utilization: jnp.ndarray    # busy slot-time / (slots * makespan)
    preempted: jnp.ndarray      # attempts killed before finishing their work
    admitted_frac: jnp.ndarray  # fraction of jobs admitted
    slots: Optional[int]        # None = infinite capacity


class ClusterOutput(NamedTuple):
    result: SimResult
    r_opt: jnp.ndarray
    utility: jnp.ndarray
    theory_pocd: jnp.ndarray
    theory_cost: jnp.ndarray
    queue: QueueMetrics
    # device-side observables (repro.obs.metrics), only populated when the
    # caller asks for collect_metrics=True — None otherwise, so existing
    # consumers and the uninstrumented compiled program are untouched
    metrics: Optional[CapacityMetrics] = None
    n_saturated: int = 0        # jobs whose r* hit the grid edge
    coupled: Optional[object] = None  # coupled.CoupledInfo (budget= runs)


# ---------------------------------------------------------------------------
# Strategy -> AttemptTable lowering: each spec's `build_table` closure
# (repro.strategies.*) mirrors its flat simulator's PRNG usage exactly
# ---------------------------------------------------------------------------


def build_strategy_table(key, jobs: JobSet, strategy: str, p: SimParams,
                         theta=1e-4, r_min=0.0, max_r: int = 8):
    """(AttemptTable, race) for a strategy at its solved r* — the shared
    entry point for benchmarks and the replay-equivalence tests."""
    spec = get(strategy)
    T = jobs.total_tasks
    if not spec.optimized:
        zeros = jnp.zeros((T,), jnp.int32)
        table = spec.build_table(key, jobs, zeros, zeros, p, max_r=max_r,
                                 oracle=True)
        return table, spec.race
    specs = jobspecs_of(jobs, p, theta, r_min)
    r_j, choice_j, _, _, _, _ = solve_jobs_jit(strategy, specs, max_r + 1)
    table = spec.build_table(key, jobs, r_j[jobs.job_id],
                             choice_j[jobs.job_id], p, max_r=max_r,
                             oracle=True)
    return table, spec.race


# ---------------------------------------------------------------------------
# Capacity replay
# ---------------------------------------------------------------------------


def _replay_body(table: AttemptTable, race: bool, arrival, D,
                 slots: Optional[int], discipline: str, passes: int,
                 n_tasks: int, count_bound=None):
    """Pure-JAX replay: traceable end-to-end, zero host round-trips.

    Pass 1 dispatches primaries only (static-shape masking, not compaction);
    the primaries-then-combined relaxation is a fori_loop whose carry is
    (release, start). The release the FINAL pass dispatched against is the
    one returned — it is only refreshed while another pass will consume it,
    so wait = start - release reports true queueing (host-path invariant).
    """
    sched_hold = predicted_holds(table, race, n_tasks)
    arrival_u = arrival[table.job_id]

    if slots is None:
        release = arrival_u + table.rel_offset
        start = release
        return (realize(table, release, start, sched_hold, race, n_tasks),
                release, start)

    deadline_u = (arrival + D)[table.job_id]
    U = table.task_id.shape[0]
    assert U % n_tasks == 0, (
        f"AttemptTable must be attempt-major with a uniform width "
        f"(U={U} not divisible by T={n_tasks}); see _assemble")
    A = U // n_tasks
    # _assemble's layout contract: row t*A + a is attempt a of task t, and
    # attempt 0 is the primary — so pass 1 (primaries only) runs on a T-row
    # slice; sorting the full U-row table for it would cost A x more.
    col0 = lambda x: x[::A]
    act_prim_t = col0(table.active & table.is_primary)
    starts_t = masked_dispatch(slots, discipline, col0(arrival_u),
                               col0(sched_hold), act_prim_t,
                               col0(deadline_u), count_bound=n_tasks)
    prim_start = jnp.where(act_prim_t, starts_t, 0.0)       # (T,)
    release0 = jnp.where(table.is_primary, arrival_u,
                         prim_start[table.task_id] + table.rel_offset)
    starts0 = jnp.where(table.is_primary,
                        starts_t[table.task_id], arrival_u)

    def combined_pass(i, carry):
        rel, _ = carry
        st = masked_dispatch(slots, discipline, rel, sched_hold,
                             table.active, deadline_u,
                             count_bound=count_bound)
        ps = jnp.where(act_prim_t, col0(st), 0.0)
        refreshed = jnp.where(table.is_primary, arrival_u,
                              ps[table.task_id] + table.rel_offset)
        return jnp.where(i < passes - 2, refreshed, rel), st

    release, start = jax.lax.fori_loop(0, passes - 1, combined_pass,
                                       (release0, starts0))
    return (realize(table, release, start, sched_hold, race, n_tasks),
            release, start)


@functools.partial(jax.jit, static_argnames=("race", "slots", "discipline",
                                             "passes", "n_tasks"))
def _replay_jit(table: AttemptTable, arrival, D, *, race: bool,
                slots: Optional[int], discipline: str, passes: int,
                n_tasks: int):
    return _replay_body(table, race, arrival, D, slots, discipline, passes,
                        n_tasks)


def replay(table: AttemptTable, race: bool, jobs: JobSet,
           slots: Optional[int], discipline: str = "fifo", passes: int = 2,
           backend: str = "jit"):
    """Replay an AttemptTable through the slot pool; see module docstring.

    `passes` counts scheduling passes total: pass 1 is primaries-only, so at
    least one combined pass (passes >= 2) is required for speculative units
    to ever acquire a slot. backend="jit" runs the whole replay as one
    compiled program; backend="host" is the legacy host-orchestrated path
    (kept as the equivalence oracle — see tests/test_cluster.py).
    """
    if passes < 2:
        raise ValueError(f"passes must be >= 2 (pass 1 schedules primaries "
                         f"only), got {passes}")
    if discipline not in DISCIPLINES:
        raise ValueError(f"unknown discipline {discipline!r}; "
                         f"expected one of {DISCIPLINES}")
    if backend not in ("jit", "host"):
        raise ValueError(f"unknown backend {backend!r}; "
                         f"expected 'jit' or 'host'")
    T = jobs.total_tasks
    if slots is None or backend == "jit":
        return _replay_jit(table, jobs.arrival, jobs.D, race=race,
                           slots=slots, discipline=discipline, passes=passes,
                           n_tasks=T)
    sched_hold = predicted_holds(table, race, T)
    arrival_u = jobs.arrival[table.job_id]

    # host-side orchestration: compact to active units, scan per pass
    tid = np.asarray(table.task_id)
    active = np.asarray(table.active)
    is_prim = np.asarray(table.is_primary)
    rel_off = np.asarray(table.rel_offset)
    hold_np = np.asarray(sched_hold)
    arr_np = np.asarray(arrival_u)
    deadline_u = np.asarray((jobs.arrival + jobs.D))[np.asarray(table.job_id)]

    def scan_subset(idx, release_np):
        order = dispatch_order(discipline, release_np[idx], deadline_u[idx])
        sub = idx[order]
        pool = make_pool(slots, t0=0.0)
        _, starts = dispatch_scan(
            pool, jnp.asarray(release_np[sub]), jnp.asarray(hold_np[sub]),
            jnp.ones((sub.size,), bool))
        out = np.array(release_np)
        out[sub] = np.asarray(starts)
        return out

    prim_idx = np.flatnonzero(active & is_prim)
    all_idx = np.flatnonzero(active)
    primary_start = np.zeros((T,), np.float32)

    starts_np = scan_subset(prim_idx, arr_np)          # pass 1: primaries
    primary_start[tid[prim_idx]] = starts_np[prim_idx]
    release_np = np.where(is_prim, arr_np,
                          primary_start[tid] + rel_off).astype(np.float32)
    for i in range(passes - 1):                        # combined passes
        starts_np = scan_subset(all_idx, release_np)
        # refresh releases only if another scan will consume them: the
        # returned release must be the one the final scan dispatched
        # against, or wait = start - release misreports queueing
        if i < passes - 2:
            primary_start[tid[prim_idx]] = starts_np[prim_idx]
            release_np = np.where(is_prim, arr_np,
                                  primary_start[tid] + rel_off
                                  ).astype(np.float32)

    release = jnp.asarray(release_np)
    start = jnp.asarray(starts_np)
    return realize(table, release, start, sched_hold, race, T), release, start


# ---------------------------------------------------------------------------
# run_cluster — the finite-capacity mirror of sim.runner.run_all
# ---------------------------------------------------------------------------


def _narrow_table(table: AttemptTable, n_tasks: int,
                  width: Optional[int]) -> AttemptTable:
    """Drop trailing attempt columns that can never dispatch.

    Builders materialize `max_r`-wide tables so the PRNG draw shapes (and
    hence draw-for-draw equivalence with the flat simulator) never depend
    on the solved r*; but every unit-indexed replay op — the dispatch-order
    sort above all — costs O(U = T * width). Slicing the (T, A) table to
    the first `width` attempt columns after the draws is exact whenever
    width > max(r*) + 1: the dropped rows are active=False for every task.
    """
    U = table.task_id.shape[0]
    A = U // n_tasks
    if width is None or width >= A:
        return table
    t, a = row_major(n_tasks, width)
    return AttemptTable(*(x[t * A + a] for x in table))


@functools.partial(jax.jit, static_argnames=(
    "n_jobs", "strategy", "p", "slots", "discipline", "passes", "max_r",
    "oracle", "reps", "width", "collect_metrics"))
def _cluster_core(key, arrays, theta, r_min, r_j, choice_j, th_p, th_c,
                  admitted, *, n_jobs: int, strategy: str, p: SimParams,
                  slots: Optional[int], discipline: str, passes: int,
                  max_r: int, oracle: bool, reps: int,
                  width: Optional[int],
                  collect_metrics: bool = False) -> ClusterOutput:
    """Single compiled program per strategy: table build, capacity replay,
    and metric reductions, with `reps` MC replications vmapped over split
    keys. r* (and any composite-strategy choice) enters as data — solved
    once per call by the cached `solve_jobs_jit` entry in the wrapper; it
    is replication-invariant and its max also fixes the static width."""
    jobs = jobset_of(n_jobs, arrays)
    J = jobs.n_jobs
    T = jobs.total_tasks
    spec = get(strategy)
    if r_j is None:
        r_j = jnp.zeros((J,), jnp.int32)
        choice_j = jnp.zeros((J,), jnp.int32)
        th_p = jnp.zeros((J,))
        th_c = jnp.zeros((J,))

    admitted_frac = (jnp.float32(1.0) if admitted is None
                     else jnp.mean(admitted.astype(jnp.float32)))

    def build_rep(k):
        table = spec.build_table(k, jobs, r_j[jobs.job_id],
                                 choice_j[jobs.job_id], p, max_r=max_r,
                                 oracle=oracle)
        if admitted is not None:
            table = table._replace(
                active=table.active & admitted[table.job_id])
        return _narrow_table(table, T, width)

    def replay_rep(table, race, count_bound):
        realized, release, start = _replay_body(
            table, race, jobs.arrival, jobs.D, slots, discipline, passes, T,
            count_bound=count_bound)
        completion_rel = realized.task_completion - jobs.arrival[jobs.job_id]
        res = aggregate(jobs, completion_rel, realized.task_machine)
        n_active = jnp.maximum(jnp.sum(table.active.astype(jnp.float32)), 1.0)
        util = (utilization(realized.busy_time, slots, realized.span)
                if slots is not None else jnp.float32(0.0))
        queue = QueueMetrics(
            mean_wait=jnp.sum(realized.wait) / n_active,
            max_wait=jnp.max(realized.wait),
            utilization=util, preempted=realized.preempted,
            admitted_frac=admitted_frac, slots=None)
        if collect_metrics:
            # functional accumulator pytree, computed from the replay's own
            # arrays inside this same program (no io_callback, no host
            # round-trip); the flag is static, so with it off these ops
            # never enter the jaxpr and the program is byte-identical
            return res, queue, capacity_metrics(table, release, start,
                                                realized)
        return res, queue

    race = spec.race
    metrics = None
    if reps == 1:
        out = replay_rep(build_rep(key), race, None)
    else:
        # Build all replications first, then hoist ONE active-count bound
        # (max over reps) shared by every replay: a per-rep (batched) bound
        # would turn the block-skip cond into both-branch execution under
        # vmap and re-serialize the full table (see dispatch_prefix_scan).
        tables = jax.vmap(build_rep)(jax.random.split(key, reps))
        count_bound = jnp.max(jnp.sum(tables.active.astype(jnp.int32),
                                      axis=1))
        out = jax.vmap(lambda t: replay_rep(t, race, count_bound))(tables)
        if collect_metrics:
            out = (*mean_over_reps(out[:2]), reduce_reps(out[2]))
        else:
            out = mean_over_reps(out)
    if collect_metrics:
        res, queue, metrics = out
    else:
        res, queue = out
    return ClusterOutput(
        result=res, r_opt=r_j,
        utility=net_utility(res.pocd, res.mean_cost, r_min, theta),
        theory_pocd=th_p, theory_cost=th_c, queue=queue, metrics=metrics)


def run_cluster_strategy(key, jobs: JobSet, strategy: str, p: SimParams,
                         slots: Optional[int] = None, theta=1e-4, r_min=0.0,
                         max_r: int = 8, oracle: bool = True,
                         discipline: str = "fifo", passes: int = 2,
                         governor: Optional[GovernorConfig] = None,
                         admitted: Optional[np.ndarray] = None,
                         reps: int = 1, width="auto",
                         collect_metrics: bool = False,
                         budget=None) -> ClusterOutput:
    """Two cached jit entries per strategy — the Algorithm-1 solve and the
    build->replay->metrics program — with no host<->device transfer inside
    the replay. Governor/admission stay host-side trace preprocessing
    (numpy cumsum/searchsorted over arrivals); their outputs enter the
    compiled program as plain arrays.

    width="auto" narrows the attempt table to max(r*) + 2 columns before
    the replay (one scalar read of the solve output fixes the static shape;
    the PRNG draws are unaffected, see _narrow_table). Pass width=None to
    keep the full max_r-wide table — e.g. to run strictly
    solve->replay-fused with zero intermediate syncs. With reps>1 the
    SimResult/QueueMetrics are MC means over replications (job_met becomes
    a met frequency)."""
    if passes < 2:
        raise ValueError(f"passes must be >= 2 (pass 1 schedules primaries "
                         f"only), got {passes}")
    if discipline not in DISCIPLINES:
        raise ValueError(f"unknown discipline {discipline!r}; "
                         f"expected one of {DISCIPLINES}")
    if not get(strategy).detectable:
        oracle = True     # oracle is static: don't compile a second
        #                   identical program for detection-free strategies
    r_j = choice_j = th_p = th_c = None
    n_sat, info = 0, None
    if get(strategy).optimized:
        with obs_trace.span("cluster.solve", strategy=strategy,
                            n_jobs=jobs.n_jobs):
            specs = jobspecs_of(jobs, p, jnp.float32(theta),
                                jnp.float32(r_min))
            if governor is not None and slots is not None:
                specs = apply_governor(specs, jobs, slots, governor)
            if budget is not None:
                # cluster-wide joint solve (repro.coupled): one shared
                # multiplier prices every job's r* (budget traced — a
                # budget sweep reuses the same compiled solve)
                from ..coupled import solve_jobs_coupled_jit, warn_infeasible
                (r_j, choice_j, _, th_p, th_c, sat_j), info = \
                    solve_jobs_coupled_jit(strategy, specs, max_r + 1,
                                           jnp.float32(budget))
            else:
                r_j, choice_j, _, th_p, th_c, sat_j = solve_jobs_jit(
                    strategy, specs, max_r + 1)
            th_c = th_c * specs.C
            n_sat = int(jnp.sum(sat_j))
            if width == "auto":
                width = int(jnp.max(r_j)) + 2
        if info is not None:
            warn_infeasible(strategy, info)
    if width == "auto":
        width = None            # baselines are already minimal-width
    adm = None if admitted is None else jnp.asarray(admitted)
    out = obs_trace.fenced(
        f"cluster.replay[{strategy}]", _cluster_core,
        key, jobset_arrays(jobs), jnp.float32(theta), jnp.float32(r_min),
        r_j, choice_j, th_p, th_c, adm, n_jobs=jobs.n_jobs,
        strategy=strategy, p=p, slots=slots, discipline=discipline,
        passes=passes, max_r=max_r, oracle=oracle, reps=reps, width=width,
        collect_metrics=collect_metrics)
    return out._replace(queue=out.queue._replace(slots=slots),
                        n_saturated=n_sat, coupled=info)


def run_cluster(key, jobs, p: SimParams, slots: Optional[int] = None,
                theta=1e-4, strategies=None,
                r_min_from_ns: bool = True, max_r: int = 8,
                oracle: bool = True, discipline: str = "fifo",
                passes: int = 2,
                governor: Optional[GovernorConfig] = None,
                admission: Optional[AdmissionConfig] = None,
                reps: int = 1, devices=None, mesh=None, chunk_jobs=None,
                collect_metrics: bool = False, chaos=None, checkpoint=None,
                resume: bool = False, budget=None):
    """Finite-capacity mirror of `sim.runner.run_all`.

    `jobs` is a JobSet, or a `repro.workloads.registry` scenario name
    (resolved with that scenario's default size and seed). `strategies=None`
    runs every registered strategy (`repro.strategies.names()`). Returns
    (outs, r_min) where outs maps strategy -> ClusterOutput. With
    slots=None this reproduces run_all's results draw-for-draw (identical
    per-name keys); with finite slots the same draws queue on the bounded
    pool.

    `devices=N` / `mesh=` / `chunk_jobs=M` route to the fleet layer
    (`repro.fleet.cluster`): replications shard over every device of the
    mesh, and chunked traces replay window-by-window on independent slot
    pools. Without them this single-device path is byte-for-byte the
    historical one. See DESIGN.md §14.

    `chaos=` (a `repro.chaos.FaultPlan`) / `checkpoint=` / `resume=` run
    under fault injection with window-boundary checkpoint/resume — fleet
    layer only (implied by any of them). See DESIGN.md §16.
    """
    if (devices is not None or mesh is not None or chunk_jobs is not None
            or chaos is not None or checkpoint is not None):
        from ..fleet import fleet_mesh, run_cluster_fleet
        if mesh is None and devices is not None and int(devices) > 1:
            mesh = fleet_mesh(devices=devices, reps=reps)
        return run_cluster_fleet(
            key, jobs, p, slots=slots, theta=theta, strategies=strategies,
            r_min_from_ns=r_min_from_ns, max_r=max_r, oracle=oracle,
            discipline=discipline, passes=passes, governor=governor,
            admission=admission, reps=reps, mesh=mesh,
            chunk_jobs=chunk_jobs, collect_metrics=collect_metrics,
            chaos=chaos, checkpoint=checkpoint, resume=resume,
            budget=budget)
    if isinstance(jobs, str):
        from ..workloads.registry import make_jobset
        jobs = make_jobset(jobs)
    if strategies is None:
        strategies = names()
    key_of = strategy_keys(key, strategies)
    admitted = None
    if admission is not None and slots is not None:
        admitted = admit_jobs(jobs, slots, admission)
    kw = dict(slots=slots, theta=theta, max_r=max_r, oracle=oracle,
              discipline=discipline, passes=passes, governor=governor,
              admitted=admitted, reps=reps,
              collect_metrics=collect_metrics, budget=budget)
    outs = {}
    r_min = 0.0
    if "hadoop_ns" in strategies:
        outs["hadoop_ns"] = run_cluster_strategy(key_of["hadoop_ns"], jobs,
                                                 "hadoop_ns", p, r_min=0.0,
                                                 **kw)
        if r_min_from_ns:
            r_min = float(outs["hadoop_ns"].result.pocd) - 1e-3
    for name in strategies:
        if name == "hadoop_ns":
            continue
        outs[name] = run_cluster_strategy(key_of[name], jobs, name, p,
                                          r_min=r_min, **kw)
    return outs, r_min
