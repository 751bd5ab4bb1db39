"""Path `capacity`: one strategy replayed on a finite slot pool.

A unit is one call of `repro.simulate(key, jobs, cfg=RunConfig(slots=...,
strategies=(strategy,), r_min_from_ns=False))`: the Algorithm-1 solve,
the attempt-table build, the FIFO slot-pool replay and the PoCD, cost and
queue reductions, read back to the host. Its work is the trace's tasks.
"""
from __future__ import annotations

import jax
import numpy as np

from reference import chronos as ref

#: outputs of a unit that the check reads
_JOB_FIELDS = ("job_met", "job_completion", "job_cost")


class State:
    def __init__(self, ctx):
        from repro import RunConfig
        from repro.sim import SimParams
        from repro.sim.trace import build_jobset

        import traffic
        cfg, opt = ctx.config, ctx.options
        self.trace = traffic.make_trace(cfg, ctx.traffic, ctx.seed)
        t = self.trace
        self.jobs = build_jobset(t["n_tasks"], t["t_min"], t["beta"], t["D"],
                                 t["arrival"], t["C"],
                                 job_class=t["job_class"],
                                 theta_scale=t["theta_scale"])
        self.strategy = opt["strategy"]
        self.params = dict(cfg["sim_params"])
        self.sim_params = SimParams(**self.params)
        self.slots = int(cfg["slots"])
        self.max_r = int(cfg["max_r"])
        self.theta = float(cfg["theta"])
        self.passes = int(opt.get("passes", 2))
        self.run_config = RunConfig(
            slots=self.slots, strategies=(self.strategy,),
            r_min_from_ns=False, theta=self.theta, max_r=self.max_r,
            passes=self.passes, discipline=opt.get("discipline", "fifo"))
        self.n_tasks = int(t["n_tasks"].sum())


def setup(ctx) -> State:
    return State(ctx)


def unit(state: State, key) -> dict:
    """One replay of the trace; returns what the host receives."""
    from repro import simulate
    with jax.profiler.TraceAnnotation("bench.capacity.simulate"):
        outs, _ = simulate(key, state.jobs, state.sim_params,
                           cfg=state.run_config)
        o = outs[state.strategy]
        host = jax.device_get(dict(
            r_opt=o.r_opt, pocd=o.result.pocd, mean_cost=o.result.mean_cost,
            utilization=o.queue.utilization, mean_wait=o.queue.mean_wait,
            **{f: getattr(o.result, f) for f in _JOB_FIELDS}))
    return {"work": state.n_tasks, "out": host}


def free(state: State) -> None:
    state.jobs = None


# ---------------------------------------------------------------------------
# reference and comparison
# ---------------------------------------------------------------------------


def _uniforms(state: State, key):
    """The uniforms behind the strategy's draws, from the program's keys."""
    k = jax.random.fold_in(key, ref.STRATEGY_INDEX[state.strategy])
    k1, k2 = jax.random.split(k)
    T = state.n_tasks
    u = lambda kk, shape: jax.random.uniform(kk, shape, minval=ref.U_MIN,
                                             maxval=1.0)
    return jax.device_get((u(k1, (T,)), u(k2, (T, state.max_r + 1))))


def _job_columns(state: State) -> dict:
    t = state.trace
    return dict(t_min=t["t_min"], beta=t["beta"], D=t["D"],
                N=t["n_tasks"].astype(np.float64), C=t["C"],
                theta=state.theta * t["theta_scale"].astype(np.float64))


def reference(state: State, key, dt, r_opt=None) -> dict:
    """The unit's outputs as the reference computes them in `dt`.

    With `r_opt` it replays those decisions; without, it makes its own
    (the argmax of its utilities), as a program in its place would.
    """
    t = state.trace
    U = ref.utility_grid(_job_columns(state), state.params, state.theta,
                         0.0, state.max_r, dt)
    if r_opt is None:
        r_opt = np.argmax(np.asarray(U, np.float64), axis=1)
    job_of_task = np.repeat(np.arange(t["n_tasks"].size), t["n_tasks"])
    u1, u2 = _uniforms(state, key)
    units = ref.sresume_units(u1, u2, t["t_min"][job_of_task],
                              t["beta"][job_of_task], t["D"][job_of_task],
                              np.asarray(r_opt)[job_of_task], state.params,
                              dt)
    start, release, hold = ref.replay(units, t["arrival"][job_of_task],
                                      state.slots, state.passes, dt)
    out = ref.realize(units, start, release, hold, job_of_task,
                      t["arrival"], t["D"], t["C"], t["n_tasks"].size,
                      state.slots, dt)
    out["r_opt"] = np.asarray(r_opt)
    out["utility"] = U
    return out


def compare(state: State, key, got: dict) -> dict:
    """The numbers compared for one unit: the program's (or a control's)
    outputs `got` against the float64 reference at the same decisions."""
    want = reference(state, key, np.float64, r_opt=got["r_opt"])
    c_got = np.asarray(got["job_completion"], np.float64)
    c_want = np.asarray(want["job_completion"], np.float64)
    rel = np.abs(c_got - c_want) / np.maximum(np.abs(c_want), 1e-9)
    return {
        "decision_gap": ref.decision_gap(want["utility"], got["r_opt"]),
        "completion_gap": float(np.max(np.where(np.isnan(rel), np.inf,
                                                 rel))),
        "met_mismatch": float(np.mean(
            np.asarray(got["job_met"]) != want["job_met"])),
        "cost_gap": _rel(got["mean_cost"], want["mean_cost"]),
        "wait_gap": _rel(got["mean_wait"], want["mean_wait"]),
        "util_gap": _rel(got["utilization"], want["utilization"]),
    }


def _rel(a, b) -> float:
    a, b = float(a), float(b)
    return abs(a - b) / max(abs(b), 1e-12)
