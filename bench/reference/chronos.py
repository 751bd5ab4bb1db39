"""Plain reference of the Chronos paths the cells time, in numpy.

Written from the paper (arXiv:1804.05890, Thms 5-6 and Algorithm 1) and
from the program's documented contracts: the per-strategy key
`fold_in(key, registry index)`, the S-Resume attempt model, and the
FIFO G/G/K dispatch of attempt units on a pool of identical slots. It
imports nothing of the program and takes nothing the program made
except the decisions (r* per job or request) it is asked to judge, the
way a served model's tokens are judged by the reference's logits.

Every function takes `dt`, the precision it computes in: float64 for
the reference, a lower one (ml_dtypes.bfloat16) for the control.
The uniforms of the Monte-Carlo draws come from `jax.random` with the
program's keys; everything computed from them happens here.
"""
from __future__ import annotations

import heapq

import numpy as np

#: registry index of each strategy: the program keys a strategy's draws
#: with fold_in(key, index), stable under later registrations
STRATEGY_INDEX = {"sresume": 5}
U_MIN = 1e-7          # jax.random.uniform(minval=1e-7, maxval=1.0)


def cast(x, dt):
    return np.asarray(x).astype(dt)


# ---------------------------------------------------------------------------
# Algorithm 1 for S-Resume: PoCD (Thm 5), machine time (Thm 6), utility
# ---------------------------------------------------------------------------


def sresume_utility(r, t_min, beta, D, N, C, theta, r_min, p, dt):
    """U(r) = log10(R(r) - R_min) - theta C E[T](r); -inf at or below the
    SLA floor. Job columns broadcast against r."""
    one = dt(1.0)
    t_min, beta, D, N, C, theta = (cast(x, dt) for x in
                                   (t_min, beta, D, N, C, theta))
    r = cast(r, dt)
    tau_est = dt(p["tau_est_frac"]) * t_min
    tau_kill = tau_est + dt(p["tau_kill_gap_frac"]) * t_min
    phi = dt(p["phi_est"])
    # Thm 5: log P(task misses D)
    window = D - tau_est
    resid = np.log1p(-phi) + np.log(t_min) - np.log(window)
    resid = np.where(window >= t_min, np.minimum(resid, dt(0.0)), dt(0.0))
    log_fail = beta * np.minimum(np.log(t_min) - np.log(D), dt(0.0)) + \
        beta * (r + one) * resid
    q_fail = np.minimum(np.exp(np.minimum(log_fail, dt(0.0))),
                        dt(1.0 - 1e-12))
    R = np.exp(N * np.log1p(-q_fail))
    # Thm 6: expected machine time
    q = np.power(t_min / D, beta)
    e_fast = beta / (beta - one) * (t_min - D * q) / (one - q)
    nb = beta * (r + one)
    e_win = t_min + t_min * np.power(one - phi, nb) / (nb - one)
    e_slow = tau_est + r * (tau_kill - tau_est) + e_win
    E = N * (e_fast * (one - q) + e_slow * q)
    gap = R - cast(r_min, dt)
    with np.errstate(divide="ignore", invalid="ignore"):
        log_term = np.where(gap > 0, np.log10(np.maximum(gap, dt(1e-30))),
                            -np.inf)
    return (log_term - theta * C * E).astype(dt)


def utility_grid(jobs, p, theta, r_min, max_r, dt):
    """(J, max_r + 1) utilities of r = 0..max_r for every job."""
    r = np.arange(max_r + 1)[None, :]
    col = lambda k: np.asarray(jobs[k])[:, None]
    return sresume_utility(r, col("t_min"), col("beta"), col("D"),
                           col("N"), col("C"), col("theta"), r_min, p, dt)


def decision_gap(U, r_chosen) -> float:
    """Widest gap by which a chosen r's utility lies below the best one,
    in utility units (log10 of PoCD above the floor, minus priced cost).
    A chosen r below the SLA floor reads inf."""
    U = np.asarray(U, np.float64)
    best = U.max(axis=1)
    chosen = np.take_along_axis(U, np.asarray(r_chosen)[:, None], 1)[:, 0]
    with np.errstate(invalid="ignore"):
        gap = np.where(np.isfinite(chosen), best - chosen, np.inf)
    return float(np.max(gap)) if gap.size else 0.0


# ---------------------------------------------------------------------------
# S-Resume attempt units (Monte-Carlo draws) and the slot-pool replay
# ---------------------------------------------------------------------------


def pareto(u, t_min, beta, dt):
    """Pareto(t_min, beta) by inversion of the program's uniforms."""
    return cast(t_min, dt) * np.power(cast(u, dt), -dt(1.0) / cast(beta, dt))


def sresume_units(u1, u2, t_min, beta, D, r_task, p, dt):
    """Per task (T,) and attempt (A = max_r + 2) columns of S-Resume.

    Attempt 0 is the primary, drawn from u1; attempts 1..max_r+1 resume
    a straggler's remaining (1 - phi) work with the t_min floor, drawn
    from u2 (T, max_r + 1). A primary straggles when it would miss D
    (oracle detection at tau_est) and is then killed at tau_est; r + 1
    resumed copies launch at tau_est after the primary starts, and the
    losers are killed at tau_kill.
    """
    t_min, beta, D = (cast(x, dt) for x in (t_min, beta, D))
    T, R = np.asarray(u2).shape
    tau_est = dt(p["tau_est_frac"]) * t_min
    tau_kill = tau_est + dt(p["tau_kill_gap_frac"]) * t_min
    T1 = pareto(u1, t_min, beta, dt)
    fresh = pareto(u2, t_min[:, None], beta[:, None], dt)
    resumed = np.maximum(t_min[:, None], (dt(1.0) - dt(p["phi_est"])) * fresh)
    strag = T1 > D
    slot = np.arange(R)[None, :]
    rel = np.concatenate([np.zeros((T, 1), dt),
                          np.broadcast_to(tau_est[:, None], (T, R))], 1)
    dur = np.concatenate([T1[:, None], resumed], 1)
    hold = np.concatenate([np.where(strag, tau_est, T1)[:, None],
                           np.broadcast_to((tau_kill - tau_est)[:, None],
                                           (T, R))], 1)
    can_win = np.concatenate([~strag[:, None], np.ones((T, R), bool)], 1)
    active = np.concatenate(
        [np.ones((T, 1), bool),
         (slot <= np.asarray(r_task)[:, None]) & strag[:, None]], 1)
    return dict(rel=rel.astype(dt), dur=dur.astype(dt),
                hold=hold.astype(dt), can_win=can_win, active=active)


def fifo_starts(release, hold, active, slots, dt):
    """FIFO G/G/K: units in release order (ties by index) each take the
    earliest-idle of `slots` identical slots; start = max(release, idle).
    Inactive units keep their release as start."""
    start = np.array(release, dtype=dt)
    idx = np.flatnonzero(active)
    order = idx[np.argsort(np.asarray(release, np.float64)[idx],
                           kind="stable")]
    if dt is np.float64:      # Python floats are float64, and faster
        rel, hld = start[order].tolist(), np.asarray(hold, dt)[order].tolist()
        rnd = float
    else:
        rel, hld = start[order], np.asarray(hold, dt)[order]
        rnd = dt
    free = [rnd(0.0)] * int(slots)
    out = []
    for r, h in zip(rel, hld):
        s = r if r > free[0] else free[0]
        heapq.heapreplace(free, rnd(s + h))
        out.append(s)
    start[order] = np.asarray(out, dtype=dt) if out else start[order]
    return start


def replay(units, arrival_t, slots, passes, dt):
    """Two-or-more-pass capacity replay of (T, A) attempt units.

    Pass 1 dispatches primaries at their job's arrival; each later pass
    dispatches every active unit, a copy released at its primary's start
    from the pass before plus its offset. Returns (T, A) starts and the
    releases the final pass used.
    """
    T, A = units["dur"].shape
    arrival_t = cast(arrival_t, dt)
    win = units["active"] & units["can_win"]
    pred = np.where(win, units["rel"] + units["dur"], np.inf)
    winner = _first_min(pred, win)
    hold = np.where(winner, units["dur"], units["hold"])
    hold = np.where(units["active"], hold, dt(0.0)).astype(dt)
    prim_start = fifo_starts(arrival_t, hold[:, 0], units["active"][:, 0],
                             slots, dt)
    release = None
    for _ in range(passes - 1):
        release = np.where(np.arange(A)[None, :] == 0, arrival_t[:, None],
                           prim_start[:, None] + units["rel"]).astype(dt)
        start = fifo_starts(release.ravel(), hold.ravel(),
                            units["active"].ravel(), slots,
                            dt).reshape(T, A)
        prim_start = start[:, 0]
    return start, release, hold


def _first_min(values, eligible):
    """(T, A) mask of the first attempt holding the row minimum."""
    v = np.where(eligible, values, np.inf)
    first = np.argmin(v, axis=1)
    out = np.zeros(v.shape, bool)
    ok = np.isfinite(v[np.arange(v.shape[0]), first])
    out[np.arange(v.shape[0])[ok], first[ok]] = True
    return out


def realize(units, start, release, hold, job_of_task, arrival, D, C,
            n_jobs, slots, dt):
    """Task completions and billing from starts, reduced to the job
    metrics and queue figures the program reports."""
    active = units["active"]
    eligible = active & units["can_win"] & (units["dur"] <= hold)
    finish = np.where(eligible, start + units["dur"], np.inf).astype(dt)
    winner = _first_min(finish, eligible)
    completion = finish.min(axis=1)
    billed = np.where(winner, units["dur"], np.minimum(units["hold"], hold))
    billed = np.where(active, np.minimum(billed, hold), dt(0.0)).astype(dt)
    task_machine = billed.sum(axis=1, dtype=dt)
    wait = np.where(active, np.maximum(start - release, dt(0.0)), dt(0.0))
    end = np.where(active, start + billed, -np.inf)
    t0 = np.min(np.where(active, release, np.inf))
    span = max(float(np.max(end)) - float(t0), 1e-9)
    comp_rel = (completion - cast(arrival, dt)[job_of_task]).astype(dt)
    job_completion = np.full(n_jobs, -np.inf, dt)
    np.maximum.at(job_completion, job_of_task, comp_rel)
    job_machine = np.zeros(n_jobs, dt)
    np.add.at(job_machine, job_of_task, task_machine)
    met = job_completion <= cast(D, dt)
    cost = (job_machine * cast(C, dt)).astype(dt)
    n_active = max(int(active.sum()), 1)
    return dict(job_completion=job_completion, job_cost=cost, job_met=met,
                pocd=float(met.mean()), mean_cost=float(cost.mean()),
                utilization=float(billed.sum(dtype=np.float64))
                / (slots * span),
                mean_wait=float(wait.sum(dtype=np.float64)) / n_active)


# ---------------------------------------------------------------------------
# Online serving: 1-task requests, probes, tail refits
# ---------------------------------------------------------------------------


def tail_fit(xs):
    """Pareto MLE (t_min, beta) of observed durations; beta in [1.01, 20]."""
    xs = np.asarray(xs, np.float64)
    t_min = float(xs.min())
    logs = np.log(np.maximum(xs, 1e-30) / max(t_min, 1e-30))
    beta = float(np.clip(xs.size / max(logs.sum(), 1e-9), 1.01, 20.0))
    return t_min, beta


def sresume_request(u1, u2, t_min, beta, D, r, p, dt):
    """(completion, machine) of single-task S-Resume requests."""
    t_min, beta, D = (cast(x, dt) for x in (t_min, beta, D))
    tau_est = dt(p["tau_est_frac"]) * t_min
    tau_kill = tau_est + dt(p["tau_kill_gap_frac"]) * t_min
    T1 = pareto(u1, t_min, beta, dt)
    fresh = pareto(u2, t_min[:, None], beta[:, None], dt)
    resumed = np.maximum(t_min[:, None], (dt(1.0) - dt(p["phi_est"])) * fresh)
    strag = T1 > D
    slot = np.arange(fresh.shape[1])[None, :]
    act = (slot <= np.asarray(r)[:, None]) & strag[:, None]
    w_new = np.min(np.where(act, resumed, np.inf), axis=1).astype(dt)
    rr = cast(r, dt)
    completion = np.where(strag, tau_est + w_new, T1)
    machine = np.where(strag, tau_est + rr * (tau_kill - tau_est) + w_new,
                       T1)
    return completion.astype(dt), machine.astype(dt)
