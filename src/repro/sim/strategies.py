"""Vectorized Monte-Carlo strategy simulators over flat task arrays.

Each simulator returns per-task (completion_time, machine_time). Job-level
PoCD/cost come from segment reductions (metrics.py). All are jit-able and run
millions of tasks per call.

Chronos strategies follow the paper's model exactly (theory-matched mode uses
oracle straggler detection T1 > D and a fixed phi; estimator mode uses the
Eq. 30 startup-aware estimator with a configurable launch overhead).

Baselines:
  * hadoop_ns — no speculation.
  * hadoop_s  — default Hadoop speculation: after the first task of the job
    finishes, one speculative copy per slow task, launched one-per-check-
    period in descending slowness order (rank approximation of "pick the
    worst running task each period"); original and copy race; loser billed
    until the task completes.
  * mantri    — resource-aware restarts: tasks whose remaining time exceeds
    the job mean by a gate get up to 3 staggered extra attempts; attempts
    billed until task completion (Mantri's periodic best-progress kill makes
    it cheaper than this in the best case, but its aggressive duplication is
    what dominates — see DESIGN.md for the approximation notes).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .sorting import stable_argsort
from .trace import JobSet


def _pareto(key, t_min, beta, shape):
    u = jax.random.uniform(key, shape, minval=1e-7, maxval=1.0)
    return t_min * jnp.power(u, -1.0 / beta)


class SimParams(NamedTuple):
    tau_est_frac: float = 0.3     # tau_est = frac * t_min
    tau_kill_gap_frac: float = 0.5  # tau_kill = tau_est + gap * t_min
    phi_est: float = 0.25         # S-Resume progress model (theory-matched)
    launch_overhead_frac: float = 0.2  # startup / JVM analogue, of t_min
    check_period_frac: float = 0.5    # baseline check period, of t_min
    mantri_gate_frac: float = 1.0     # remaining > mean + gate*t_min
    mantri_max_extra: int = 3
    hedge_quantile: float = 0.95      # hedge duplicate launch quantile


# ---------------------------------------------------------------------------
# Chronos strategies (r is per-task, gathered from the per-job optimum)
# ---------------------------------------------------------------------------


def sim_clone(key, jobs: JobSet, r_task, p: SimParams, max_r: int = 8):
    """r_task: (T,) int32 extra attempts per task."""
    T = jobs.total_tasks
    t_min, beta = jobs.task_t_min, jobs.task_beta
    tau_kill = (p.tau_est_frac + p.tau_kill_gap_frac) * t_min
    att = _pareto(key, t_min[:, None], beta[:, None], (T, max_r + 1))
    slot = jnp.arange(max_r + 1)[None, :]
    active = slot <= r_task[:, None]
    best = jnp.min(jnp.where(active, att, jnp.inf), axis=1)
    completion = best
    machine = r_task * tau_kill + best
    return completion, machine


def sim_srestart(key, jobs: JobSet, r_task, p: SimParams, max_r: int = 8,
                 oracle: bool = True):
    T = jobs.total_tasks
    t_min, beta, D = jobs.task_t_min, jobs.task_beta, jobs.task_D
    tau_est = p.tau_est_frac * t_min
    tau_kill = tau_est + p.tau_kill_gap_frac * t_min
    k1, k2 = jax.random.split(key)
    T1 = _pareto(k1, t_min, beta, (T,))
    extras = _pareto(k2, t_min[:, None], beta[:, None], (T, max_r))
    straggler = _detect(T1, t_min, D, tau_est, p, oracle)
    slot = jnp.arange(max_r)[None, :]
    active = (slot < r_task[:, None]) & straggler[:, None]
    best_extra = jnp.min(jnp.where(active, extras, jnp.inf), axis=1)
    w_all = jnp.minimum(T1 - tau_est, best_extra)      # from tau_est
    completion = jnp.where(straggler & (r_task > 0), tau_est + w_all, T1)
    machine = jnp.where(
        straggler & (r_task > 0),
        tau_est + r_task * (tau_kill - tau_est) + w_all, T1)
    return completion, machine


def sim_sresume(key, jobs: JobSet, r_task, p: SimParams, max_r: int = 8,
                oracle: bool = True):
    """Original killed at tau_est; r+1 fresh attempts resume the remaining
    (1-phi) work with the t_min startup floor (theory-matched model)."""
    T = jobs.total_tasks
    t_min, beta, D = jobs.task_t_min, jobs.task_beta, jobs.task_D
    tau_est = p.tau_est_frac * t_min
    tau_kill = tau_est + p.tau_kill_gap_frac * t_min
    k1, k2 = jax.random.split(key)
    T1 = _pareto(k1, t_min, beta, (T,))
    fresh = _pareto(k2, t_min[:, None], beta[:, None], (T, max_r + 1))
    resumed = jnp.maximum(t_min[:, None], (1.0 - p.phi_est) * fresh)
    straggler = _detect(T1, t_min, D, tau_est, p, oracle)
    slot = jnp.arange(max_r + 1)[None, :]
    active = (slot <= r_task[:, None]) & straggler[:, None]
    w_new = jnp.min(jnp.where(active, resumed, jnp.inf), axis=1)
    completion = jnp.where(straggler, tau_est + w_new, T1)
    machine = jnp.where(straggler,
                        tau_est + r_task * (tau_kill - tau_est) + w_new, T1)
    return completion, machine


def _detect(T1, t_min, D, tau_est, p: SimParams, oracle: bool):
    """Straggler detection at tau_est."""
    if oracle:
        return T1 > D
    # Eq. 30 estimator with launch overhead: T1 = startup + work. The
    # extrapolated t_ect = startup + work == T1 (exact for linear progress);
    # before any progress exists (tau_est <= startup) the estimator has
    # nothing to extrapolate, so no task is flagged.
    startup = p.launch_overhead_frac * t_min
    work = jnp.maximum(T1 - startup, 1e-6)
    t_ect = startup + work
    return (tau_est > startup) & (t_ect > D)


# ---------------------------------------------------------------------------
# Baselines
# ---------------------------------------------------------------------------


def sim_hadoop_ns(key, jobs: JobSet, p: SimParams):
    T1 = _pareto(key, jobs.task_t_min, jobs.task_beta, (jobs.total_tasks,))
    return T1, T1


def _rank_among_job_scan(values, job_id, n_jobs):
    """Reference rank via a serial lax.scan (O(T) sequential steps).

    Kept as the oracle for `_rank_among_job`: sort by value descending, then
    the rank of a task is the count of earlier-sorted tasks in the same job,
    accumulated one task at a time.
    """
    T = values.shape[0]
    order = jnp.argsort(-values)
    sorted_jobs = job_id[order]
    # position within job along the sorted order
    seen = jnp.zeros((n_jobs,), jnp.int32)

    def body(seen, j):
        r = seen[j]
        return seen.at[j].add(1), r

    seen, ranks_sorted = jax.lax.scan(body, seen, sorted_jobs)
    ranks = jnp.zeros((T,), jnp.int32).at[order].set(ranks_sorted)
    return ranks


def _rank_among_job(values, job_id, n_jobs):
    """Dense descending rank of each task's value within its job (0 = worst).

    Fully parallel O(T log T): one lexicographic sort by (job_id, -value)
    groups each job's tasks contiguously in descending-value order, so a
    task's rank is its sorted position minus its job's segment offset. Ties
    break by original index (stable sort), matching `_rank_among_job_scan`.
    """
    T = values.shape[0]
    order = stable_argsort(job_id, -values)
    counts = jax.ops.segment_sum(jnp.ones((T,), jnp.int32), job_id, n_jobs)
    starts = jnp.cumsum(counts) - counts          # exclusive prefix sum
    ranks_sorted = jnp.arange(T, dtype=jnp.int32) - starts[job_id[order]]
    return jnp.zeros((T,), jnp.int32).at[order].set(ranks_sorted)


def sim_hadoop_s(key, jobs: JobSet, p: SimParams):
    """Default Hadoop speculation (rank approximation, see module doc)."""
    T = jobs.total_tasks
    t_min, beta = jobs.task_t_min, jobs.task_beta
    k1, k2 = jax.random.split(key)
    T1 = _pareto(k1, t_min, beta, (T,))
    T2 = _pareto(k2, t_min, beta, (T,))
    # first completion within the job gates speculation
    t_first = jax.ops.segment_min(T1, jobs.job_id, jobs.n_jobs)[jobs.job_id]
    delta = p.check_period_frac * t_min
    rank = _rank_among_job(T1, jobs.job_id, jobs.n_jobs).astype(jnp.float32)
    s_launch = t_first + (rank + 1.0) * delta
    speculate = T1 > s_launch                     # still running at launch
    completion = jnp.where(speculate, jnp.minimum(T1, s_launch + T2), T1)
    # both attempts run until the task completes (loser killed then)
    machine = jnp.where(speculate,
                        completion + jnp.maximum(completion - s_launch, 0.0),
                        T1)
    return completion, machine


def sim_mantri(key, jobs: JobSet, p: SimParams):
    """Mantri-style duplication (see module doc for approximation)."""
    T = jobs.total_tasks
    t_min, beta = jobs.task_t_min, jobs.task_beta
    k1, k2 = jax.random.split(key)
    T1 = _pareto(k1, t_min, beta, (T,))
    mean_t = jax.ops.segment_sum(T1, jobs.job_id, jobs.n_jobs) / \
        jnp.maximum(jobs.n_tasks.astype(jnp.float32), 1.0)
    mean_task = mean_t[jobs.job_id]
    gate = mean_task + p.mantri_gate_frac * t_min
    extras = _pareto(k2, t_min[:, None], beta[:, None],
                     (T, p.mantri_max_extra))
    delta = p.check_period_frac * t_min
    # extra attempt i launched at gate-time + i*delta while task still runs
    launch = gate[:, None] + delta[:, None] * jnp.arange(p.mantri_max_extra)[None, :]
    launched = T1[:, None] > launch
    att_completion = jnp.where(launched, launch + extras, jnp.inf)
    completion = jnp.minimum(T1, jnp.min(att_completion, axis=1))
    extra_machine = jnp.sum(
        jnp.where(launched, jnp.maximum(completion[:, None] - launch, 0.0), 0.0),
        axis=1)
    machine = completion + extra_machine
    return completion, machine
