"""Path `serve`: one online-serving pass over a request stream.

A unit is one call of `repro.serve.serve_trace` in online mode: the
stream is cut into epochs of `refit_every` requests; every
`probe_every`-th request is served unhedged and its completion feeds the
tail governor, which refits the Pareto tail once per epoch; each later
epoch solves Algorithm 1 at the latest fit and serves its requests in
`window`-wide compiled windows. The closed loop: an epoch starts when
the previous one's results reach the host. Its work is the requests.

Each request's decide time is the wall time from its epoch's start to
its epoch's results reaching the host, stamped by a `StreamCombiner`
that the benchmark passes in (`combiner=`).
"""
from __future__ import annotations

import functools
import time
from collections import deque

import jax
import numpy as np

from reference import chronos as ref

#: the program's serving defaults for the governor's rolling window
TAIL_CAPACITY = 2048
MIN_SAMPLES = 16
#: unit index of the no-hedge pass that sets r_min during set-up
NS_UNIT = (1 << 30) + 1


def _stamping_combiner(t0):
    from repro.sim.metrics import StreamCombiner

    class Stamping(StreamCombiner):
        """Records when each epoch's results reach the host."""

        def __init__(self):
            super().__init__()
            self.stamps = [t0]
            self.sizes = []

        def add(self, result, n_jobs, queue=None, capacity=None):
            super().add(result, n_jobs, queue=queue, capacity=capacity)
            self.stamps.append(time.perf_counter())
            self.sizes.append(int(n_jobs))

    return Stamping()


class State:
    def __init__(self, ctx):
        from repro.serve import RequestTrace, serve_trace
        from repro.sim import SimParams

        import harness
        import traffic
        cfg, opt = ctx.config, ctx.options
        t = traffic.make_trace(cfg, ctx.traffic, ctx.seed)
        self.cols = t
        n = t["t_min"].size
        self.n = n
        self.reqs = RequestTrace(
            rid=np.arange(n, dtype=np.int32), arrival=t["arrival"],
            t_min=t["t_min"], beta=t["beta"], D=t["D"], C=t["C"],
            theta_scale=t["theta_scale"], job_class=t["job_class"],
            class_names=tuple(c["name"] for c in cfg["classes"]))
        self.params = dict(cfg["sim_params"])
        self.sim_params = SimParams(**self.params)
        self.strategy = opt["strategy"]
        self.theta = float(cfg["theta"])
        self.max_r = int(cfg["max_r"])
        self.window = int(opt["window"])
        self.refit_every = int(opt["refit_every"])
        self.probe_every = int(opt["probe_every"])
        self.ns_key = harness.unit_key(ctx.seed, NS_UNIT)
        self.serve_trace = serve_trace
        # the no-hedge pass: r_min = its PoCD less 1e-3 (the paper's
        # R_min protocol, as the program's run_serve applies it)
        ns = serve_trace(self.ns_key, self.reqs, self.sim_params,
                         strategy="hadoop_ns", theta=self.theta, r_min=0.0,
                         max_r=self.max_r, window=self.window,
                         refit_every=self.refit_every,
                         probe_every=self.probe_every)
        self.r_min = float(ns.result.pocd) - 1e-3


def setup(ctx) -> State:
    return State(ctx)


def unit(state: State, key) -> dict:
    """Serve the stream once; returns what the host receives."""
    with jax.profiler.TraceAnnotation("bench.serve.serve_trace"):
        comb = _stamping_combiner(time.perf_counter())
        out = state.serve_trace(
            key, state.reqs, state.sim_params, strategy=state.strategy,
            theta=state.theta, r_min=state.r_min, max_r=state.max_r,
            window=state.window, refit_every=state.refit_every,
            probe_every=state.probe_every, combiner=comb)
        host = jax.device_get(dict(
            job_completion=out.result.job_completion,
            job_cost=out.result.job_cost, pocd=out.result.pocd))
    host["fits"] = np.asarray([(f.t_min, f.beta) for f in out.fits],
                              np.float64).reshape(-1, 2)
    stamps = np.asarray(comb.stamps)
    latencies = np.repeat(np.diff(stamps), comb.sizes)
    return {"work": state.n, "latencies_s": latencies, "out": host}


def free(state: State) -> None:
    state.reqs = None


# ---------------------------------------------------------------------------
# reference and comparison
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("width",))
def _request_uniforms(key, rids, ns_key, *, width):
    """Per request: the no-hedge draw's uniform under `key` and `ns_key`,
    and S-Resume's (primary, 9 resumed) uniforms under `key`, each from
    fold_in(key, rid) as the program keys a request's draws."""
    u = lambda k, shape: jax.random.uniform(k, shape, minval=ref.U_MIN,
                                            maxval=1.0)

    def one(rid):
        k = jax.random.fold_in(key, rid)
        k1, k2 = jax.random.split(k)
        return (u(k, (1,))[0], u(jax.random.fold_in(ns_key, rid), (1,))[0],
                u(k1, (1,))[0], u(k2, (1, width))[0])

    return jax.vmap(one)(rids)


def _uniforms(state: State, key):
    rids = np.arange(state.n, dtype=np.int32)
    return jax.device_get(_request_uniforms(key, rids, state.ns_key,
                                            width=state.max_r + 1))


def reference(state: State, key, dt) -> dict:
    """The unit's outputs as the reference computes them in `dt`, with its
    own probe fits and its own decisions (argmax of its utilities)."""
    c = state.cols
    p = state.params
    u_ns, u_r_min, u1, u2 = _uniforms(state, key)
    t_min, beta, D, C = (ref.cast(c[k], dt) for k in ("t_min", "beta", "D",
                                                        "C"))
    r_min = float(np.mean(ref.pareto(u_r_min, t_min, beta, dt) <= D)) - 1e-3
    T1_ns = ref.pareto(u_ns, t_min, beta, dt)
    n, E = state.n, state.refit_every
    probe = np.arange(n) % state.probe_every == 0
    completion = np.empty(n, dt)
    machine = np.empty(n, dt)
    r_all = np.zeros(n, np.int64)
    hedged = np.zeros(n, bool)
    U_all = np.full((n, state.max_r + 1), np.nan)
    window = deque(maxlen=TAIL_CAPACITY)
    fits, fit, since = [], None, 0
    cadence = E // state.probe_every
    for lo in range(0, n, E):
        hi = min(lo + E, n)
        s = slice(lo, hi)
        completion[s] = T1_ns[s]
        machine[s] = T1_ns[s]
        if fit is not None:
            h = np.flatnonzero(~probe[s]) + lo
            U = ref.sresume_utility(
                np.arange(state.max_r + 1)[None, :],
                np.full((h.size, 1), fit[0]), np.full((h.size, 1), fit[1]),
                D[h, None], np.ones((h.size, 1)), C[h, None],
                (state.theta * c["theta_scale"][h])[:, None], r_min, p, dt)
            r = np.argmax(np.asarray(U, np.float64), axis=1)
            comp, mach = ref.sresume_request(u1[h], u2[h], t_min[h],
                                             beta[h], D[h], r, p, dt)
            completion[h], machine[h] = comp, mach
            r_all[h], hedged[h], U_all[h] = r, True, U
        for x in completion[s][probe[s]]:
            window.append(float(x))
            since += 1
            if len(window) >= MIN_SAMPLES and since >= cadence:
                since = 0
                fit = ref.tail_fit(np.asarray(window))
                fits.append(fit)
    return dict(job_completion=completion, job_cost=(machine * C).astype(dt),
                pocd=float(np.mean(completion <= D)),
                fits=np.asarray(fits, np.float64).reshape(-1, 2),
                r=r_all, hedged=hedged, utility=U_all, u1=u1, u2=u2)


def compare(state: State, key, got: dict) -> dict:
    """The numbers compared for one unit: the program's (or a control's)
    outputs `got` against the float64 reference. Where a hedged request
    straggles, its machine time reveals the r it was served with; that
    decision is judged by the reference's utilities, and the reference
    serves the request at that r."""
    f8 = np.float64
    want = reference(state, key, f8)
    c = state.cols
    p = state.params
    t_min = c["t_min"].astype(f8)
    gap_t = p["tau_kill_gap_frac"] * t_min
    c_got = np.asarray(got["job_completion"], f8)
    m_got = np.asarray(got["job_cost"], f8) / c["C"].astype(f8)
    T1 = ref.pareto(want["u1"], t_min, c["beta"], f8)
    shown = want["hedged"] & (T1 > c["D"])
    r_seen = (m_got - c_got)[shown] / gap_t[shown]
    r_int = np.rint(r_seen)
    valid = (np.abs(r_seen - r_int) < 1e-2) & (r_int >= 0) & \
        (r_int <= state.max_r)
    r_int = np.where(valid, r_int, 0).astype(np.int64)
    U = want["utility"][shown]
    chosen = np.take_along_axis(U, r_int[:, None], 1)[:, 0]
    gaps = np.where(valid, U.max(axis=1) - chosen, np.inf)
    c_want = want["job_completion"].copy()
    cost_want = want["job_cost"].copy()
    idx = np.flatnonzero(shown)
    comp, mach = ref.sresume_request(want["u1"][idx], want["u2"][idx],
                                     t_min[idx], c["beta"][idx],
                                     c["D"][idx], r_int, p, f8)
    c_want[idx], cost_want[idx] = comp, mach * c["C"][idx]
    rel = lambda a, b: np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-12))
    fits_got = np.asarray(got["fits"], f8).reshape(-1, 2)
    fit_gap = (rel(fits_got, want["fits"])
               if fits_got.shape == want["fits"].shape else np.inf)
    return {
        "decision_gap": float(gaps.max()) if gaps.size else 0.0,
        "completion_gap": float(rel(c_got, c_want)),
        "cost_gap": float(rel(np.asarray(got["job_cost"], f8), cost_want)),
        "fit_gap": float(fit_gap),
        "pocd_gap": abs(float(got["pocd"]) - float(np.mean(c_want
                                                           <= c["D"]))),
    }
