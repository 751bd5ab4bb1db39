"""The three Chronos strategies (paper Section IV) as StrategySpecs.

Each spec wires the paper's closed forms (`core.pocd` / `core.cost`, Thms
1-6), the Thm-8 concavity threshold, the flat Monte-Carlo simulator
(`sim.strategies` — PRNG splits preserved draw-for-draw), the capacity
AttemptTable lowering, and the Pallas tile body into one registry entry.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ..core.pocd import (log_task_fail_clone, log_task_fail_srestart,
                         log_task_fail_sresume)
from ..core.cost import cost_clone, cost_srestart, cost_sresume
from ..sim.strategies import (_detect, _pareto, sim_clone, sim_srestart,
                              sim_sresume)
from .spec import StrategySpec, register
from .table import assemble


# ---------------------------------------------------------------------------
# Thm-8 concavity thresholds (Algorithm 1 phase split)
# ---------------------------------------------------------------------------


def gamma_clone(job):
    """Gamma_Clone = -1/beta * log_{t_min/D} N - 1  (R concave for r > Gamma).

    Equivalent to: R_Clone(r) is concave iff (t_min/D)^(beta(r+1)) <= 1/N.
    """
    log_ratio = jnp.log(job.t_min / job.D)  # < 0
    return -jnp.log(job.N) / (job.beta * log_ratio) - 1.0


def gamma_srestart(job):
    """Gamma_S-Restart = 1/beta * log_{t_min/(D-tau)} (D^beta / (N t_min^beta)).

    Concavity condition: task failure prob q(r) <= 1/N, i.e.
    (t_min/D)^beta * (t_min/(D-tau))^(beta r) <= 1/N.
    """
    lr = jnp.log(job.t_min / (job.D - job.tau_est))  # < 0
    target = job.beta * jnp.log(job.D / job.t_min) - jnp.log(job.N)
    return target / (job.beta * lr)


def gamma_sresume(job):
    """Gamma_S-Resume: same condition with the resumed-attempt failure ratio."""
    lr = jnp.log1p(-job.phi_est) + jnp.log(job.t_min / (job.D - job.tau_est))
    target = job.beta * jnp.log(job.D / job.t_min) - jnp.log(job.N)
    return target / (job.beta * lr) - 1.0


# ---------------------------------------------------------------------------
# Certified grid-bound slopes (host-side floats; see optimizer.r_upper_bound)
# ---------------------------------------------------------------------------


def slope_clone(job) -> float:
    """Every task kills r clones at tau_kill."""
    return float(job.N) * float(job.tau_kill)


def slope_reactive(job) -> float:
    """Only stragglers pay: N * p_straggler * (tau_kill - tau_est)."""
    p_s = float(np.power(float(job.t_min) / float(job.D), float(job.beta)))
    return float(job.N) * p_s * (float(job.tau_kill) - float(job.tau_est))


# ---------------------------------------------------------------------------
# AttemptTable lowerings (PRNG usage mirrors sim/strategies.py exactly)
# ---------------------------------------------------------------------------


def build_clone(key, jobs, r_task, choice_task, p, *, max_r=8, oracle=True):
    T = jobs.total_tasks
    t_min, beta = jobs.task_t_min, jobs.task_beta
    tau_kill = (p.tau_est_frac + p.tau_kill_gap_frac) * t_min
    att = _pareto(key, t_min[:, None], beta[:, None], (T, max_r + 1))
    slot = jnp.arange(max_r + 1)[None, :]
    active = slot <= r_task[:, None]
    return assemble(jobs, jnp.zeros((T, 1)), att, tau_kill[:, None],
                    jnp.ones((T, 1), bool), active)


def build_srestart(key, jobs, r_task, choice_task, p, *, max_r=8,
                   oracle=True):
    T = jobs.total_tasks
    t_min, beta, D = jobs.task_t_min, jobs.task_beta, jobs.task_D
    tau_est = p.tau_est_frac * t_min
    tau_kill = tau_est + p.tau_kill_gap_frac * t_min
    k1, k2 = jax.random.split(key)
    T1 = _pareto(k1, t_min, beta, (T,))
    extras = _pareto(k2, t_min[:, None], beta[:, None], (T, max_r))
    straggler = _detect(T1, t_min, D, tau_est, p, oracle)
    slot = jnp.arange(max_r)[None, :]
    spec_active = (slot < r_task[:, None]) & straggler[:, None]

    rel = jnp.concatenate([jnp.zeros((T, 1)),
                           jnp.broadcast_to(tau_est[:, None], (T, max_r))], 1)
    dur = jnp.concatenate([T1[:, None], extras], 1)
    # losing primary is killed at tau_kill; losing copies at tau_kill too,
    # billed from their tau_est launch (Thm 3's r*(tau_kill - tau_est) term)
    hold = jnp.concatenate([tau_kill[:, None],
                            jnp.broadcast_to((tau_kill - tau_est)[:, None],
                                             (T, max_r))], 1)
    active = jnp.concatenate([jnp.ones((T, 1), bool), spec_active], 1)
    return assemble(jobs, rel, dur, hold,
                    jnp.ones((T, max_r + 1), bool), active)


def build_sresume(key, jobs, r_task, choice_task, p, *, max_r=8,
                  oracle=True):
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
    spec_active = (slot <= r_task[:, None]) & straggler[:, None]

    rel = jnp.concatenate([jnp.zeros((T, 1)),
                           jnp.broadcast_to(tau_est[:, None],
                                            (T, max_r + 1))], 1)
    dur = jnp.concatenate([T1[:, None], resumed], 1)
    # a straggling primary is killed at tau_est (its work is handed off) and
    # can never win; resumed losers are killed at tau_kill
    hold = jnp.concatenate([jnp.where(straggler, tau_est, T1)[:, None],
                            jnp.broadcast_to((tau_kill - tau_est)[:, None],
                                             (T, max_r + 1))], 1)
    can_win = jnp.concatenate([~straggler[:, None],
                               jnp.ones((T, max_r + 1), bool)], 1)
    active = jnp.concatenate([jnp.ones((T, 1), bool), spec_active], 1)
    return assemble(jobs, rel, dur, hold, can_win, active)


# ---------------------------------------------------------------------------
# Pallas tile bodies (shared Pareto draws; see kernels/pocd_mc.py)
#
# Layout: att (R, N, Jt) with the attempt slot leading and jobs on lanes;
# t_min / tau_est / tau_kill / D / r are (1, Jt) rows. Returns
# (completion, machine), both (N, Jt).
# ---------------------------------------------------------------------------


def _slots(att):
    return jax.lax.broadcasted_iota(jnp.int32, att.shape, 0)


def tile_clone(att, t_min, tau_est, tau_kill, D, r, *, phi):
    active = _slots(att) <= r
    best = jnp.min(jnp.where(active, att, jnp.inf), axis=0)
    machine = r.astype(att.dtype) * tau_kill + best
    return best, machine


def tile_srestart(att, t_min, tau_est, tau_kill, D, r, *, phi):
    T1 = att[0]
    strag = T1 > D
    active = (_slots(att[1:]) < r) & strag
    extras = jnp.min(jnp.where(active, att[1:], jnp.inf), axis=0)
    w_all = jnp.minimum(T1 - tau_est, extras)
    use = strag & (r > 0)
    completion = jnp.where(use, tau_est + w_all, T1)
    machine = jnp.where(
        use, tau_est + r.astype(att.dtype) * (tau_kill - tau_est) + w_all, T1)
    return completion, machine


def tile_sresume(att, t_min, tau_est, tau_kill, D, r, *, phi):
    T1 = att[0]
    strag = T1 > D
    resumed = jnp.maximum(t_min, (1.0 - phi) * att[1:])
    active = (_slots(resumed) <= r) & strag
    w_new = jnp.min(jnp.where(active, resumed, jnp.inf), axis=0)
    completion = jnp.where(strag, tau_est + w_new, T1)
    machine = jnp.where(
        strag, tau_est + r.astype(att.dtype) * (tau_kill - tau_est) + w_new,
        T1)
    return completion, machine


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------

CLONE = register(StrategySpec(
    name="clone", kind="chronos", race=False, detectable=False,
    draw=lambda key, jobs, r_task, choice_task, p, *, max_r, oracle:
        sim_clone(key, jobs, r_task, p, max_r=max_r),
    build_table=build_clone,
    log_task_fail=lambda r, job:
        log_task_fail_clone(r, job.t_min, job.beta, job.D),
    cost=lambda r, job:
        cost_clone(r, job.t_min, job.beta, job.D, job.N, job.tau_kill),
    gamma=gamma_clone, r_slope=slope_clone, tile_outcome=tile_clone))

SRESTART = register(StrategySpec(
    name="srestart", kind="chronos", race=False, detectable=True,
    draw=lambda key, jobs, r_task, choice_task, p, *, max_r, oracle:
        sim_srestart(key, jobs, r_task, p, max_r=max_r, oracle=oracle),
    build_table=build_srestart,
    log_task_fail=lambda r, job:
        log_task_fail_srestart(r, job.t_min, job.beta, job.D, job.tau_est),
    cost=lambda r, job:
        cost_srestart(r, job.t_min, job.beta, job.D, job.N, job.tau_est,
                      job.tau_kill),
    gamma=gamma_srestart, r_slope=slope_reactive, tile_outcome=tile_srestart))

SRESUME = register(StrategySpec(
    name="sresume", kind="chronos", race=False, detectable=True,
    draw=lambda key, jobs, r_task, choice_task, p, *, max_r, oracle:
        sim_sresume(key, jobs, r_task, p, max_r=max_r, oracle=oracle),
    build_table=build_sresume,
    log_task_fail=lambda r, job:
        log_task_fail_sresume(r, job.t_min, job.beta, job.D, job.tau_est,
                              job.phi_est),
    cost=lambda r, job:
        cost_sresume(r, job.t_min, job.beta, job.D, job.N, job.tau_est,
                     job.tau_kill, job.phi_est),
    gamma=gamma_sresume, r_slope=slope_reactive, tile_outcome=tile_sresume))
