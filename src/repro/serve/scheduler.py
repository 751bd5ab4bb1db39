"""Deadline-aware hedged request scheduling — Chronos for serving.

Requests carry SLA deadlines; replicas exhibit heavy-tailed service times
(co-tenancy, cache state, preemption). The scheduler treats each request
as a 1-task job and executes it through the strategy IR: `spec.draw` is
the single execution entry for every registered strategy — clone (fan to
r+1 replicas at t=0), srestart (hedge at tau_est), sresume (cancel the
straggler and re-dispatch carrying the generated prefix — the KV-prefix
migration analogue of Eq. 31), hedge (quantile-delayed duplicate),
adaptive (per-request argmax over the Chronos trio), and any strategy
registered later, with zero edits here.

Determinism contract (the PR 4 keying convention, applied to requests):
every request's draw is keyed by `fold_in(key, rid)` and each window lane
is an independent 1-request JobSet under `vmap`, so outcomes are bitwise
invariant to window size, batching, sub-slicing, and device sharding.
This replaces the seed scheduler's shared mutated `np.random.Generator`
(order-dependent draws) and its hand-rolled per-strategy branches, whose
clone arm billed `r * tau_kill + min(times)` — charging losers a kill
timer in what it simulated as a no-kill race. Lowering through the spec
makes the executed machine-time model the same one Algorithm 1's analytic
`cost` closed form optimizes, per strategy, by construction.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import JobSpec, Solution, solve
from ..obs import trace as obs_trace
from ..sim.strategies import SimParams
from ..sim.trace import JobSet
from ..strategies import get

__all__ = ["Request", "ReplicaPool", "HedgeOutcome", "HedgedScheduler",
           "baseline_no_hedge", "serve_window"]


# ---------------------------------------------------------------------------
# Window execution core: vmapped per-request spec.draw, keyed by rid
# ---------------------------------------------------------------------------


def _one_request_jobset(t_min, beta, D) -> JobSet:
    """A 1-job / 1-task JobSet for one window lane (traced leaves)."""
    one_f = jnp.ones((1,), jnp.float32)
    return JobSet(
        n_jobs=1, n_tasks=jnp.ones((1,), jnp.int32),
        t_min=t_min[None], beta=beta[None], D=D[None],
        arrival=0.0 * one_f, C=one_f,
        job_class=jnp.zeros((1,), jnp.int32), theta_scale=one_f,
        job_id=jnp.zeros((1,), jnp.int32),
        task_t_min=t_min[None], task_beta=beta[None], task_D=D[None])


@functools.partial(jax.jit, static_argnames=("strategy", "p", "max_r",
                                             "oracle"))
def _window_core(key, rids, t_min, beta, D, r, choice, *, strategy: str,
                 p: SimParams, max_r: int, oracle: bool):
    """(completion, machine) for a fixed-width window of requests.

    Each lane folds its rid into the stream key and runs the spec's draw
    on its own 1-request JobSet — no draw ever crosses a lane, so the
    compiled program is reusable for any window of the same width and
    results cannot depend on how the stream was cut into windows.
    """
    spec = get(strategy)

    def one(rid, tm, b, d, ri, ci):
        k = jax.random.fold_in(key, rid)
        jobs = _one_request_jobset(tm, b, d)
        completion, machine = spec.draw(
            k, jobs, ri[None], ci[None], p, max_r=max_r, oracle=oracle)
        return completion[0], machine[0]

    return jax.vmap(one)(rids, t_min, beta, D, r, choice)


def serve_window(key, rids, t_min, beta, D, r, choice, *, strategy: str,
                 p: SimParams, max_r: int = 8, oracle: bool = True,
                 width: Optional[int] = None, sharding=None):
    """Host wrapper: pad to a fixed width, execute, unpad.

    width: compiled window width (>= len(rids)); every call at the same
        width reuses one compiled program. None = exact size.
    sharding: optional NamedSharding for the request axis (fleet mesh's
        "job" axis) — lanes are independent, so sharded and unsharded
        executions are bit-identical.
    """
    n = int(np.asarray(rids).shape[0])
    w = n if width is None else int(width)
    if w < n:
        raise ValueError(f"window width {w} < {n} requests")
    if not get(strategy).detectable:
        oracle = True    # oracle is static: one program per strategy
    pad = w - n
    edge = lambda x, dt: np.pad(np.asarray(x, dt), (0, pad), mode="edge")
    cols = (edge(rids, np.int32), edge(t_min, np.float32),
            edge(beta, np.float32), edge(D, np.float32),
            edge(r, np.int32), edge(choice, np.int32))
    if sharding is not None:
        cols = tuple(jax.device_put(c, sharding) for c in cols)
    completion, machine = _window_core(
        key, *cols, strategy=strategy, p=p, max_r=max_r, oracle=oracle)
    with obs_trace.span("d2h.wait"):
        completion = np.asarray(completion)
    with obs_trace.span("d2h.wait"):
        machine = np.asarray(machine)
    return completion[:n], machine[:n]


# ---------------------------------------------------------------------------
# Request-level API (the seed classes, rebuilt on the IR)
# ---------------------------------------------------------------------------


@dataclass(order=True)
class Request:
    deadline: float
    rid: int = field(compare=False)
    n_tokens: int = field(compare=False, default=32)
    submitted: float = field(compare=False, default=0.0)


@dataclass(frozen=True)
class ReplicaPool:
    """Replica latency model: Pareto(t_min, beta) service-time multiplier.

    Frozen parameters only — draws live in the compiled window core,
    keyed per request, never in a shared mutable generator.
    """
    n_replicas: int
    base_tok_s: float = 200.0
    t_min_mult: float = 1.0
    beta: float = 1.6

    def t_min_of(self, n_tokens: int) -> float:
        """Service-time floor for a request of n_tokens."""
        return n_tokens / self.base_tok_s * self.t_min_mult


@dataclass
class HedgeOutcome:
    rid: int
    latency: float
    met: bool
    machine_time: float
    strategy: str
    r: int


class HedgedScheduler:
    """Chronos-optimized hedging over a replica pool.

    strategy: any `repro.strategies.names()` entry, or "adaptive" (the
        default) for the per-request argmax over the Chronos trio — the
        registry-native form of the seed's per-request `solve` planning.
    """

    def __init__(self, pool: ReplicaPool, theta: float = 1e-3,
                 tau_est_frac: float = 0.3, tau_kill_gap: float = 0.5,
                 phi_est: float = 0.25, strategy: str = "adaptive",
                 max_r: int = 8, key=None):
        self.pool = pool
        self.theta = theta
        self.p = SimParams(tau_est_frac=tau_est_frac,
                           tau_kill_gap_frac=tau_kill_gap,
                           phi_est=phi_est)
        self.strategy = strategy
        self.max_r = max_r
        self.key = jax.random.PRNGKey(0) if key is None else key

    def plan(self, req: Request) -> Solution:
        """Best (strategy, r*) for one request (Algorithm 1)."""
        t_min = self.pool.t_min_of(req.n_tokens)
        if req.deadline <= t_min * 1.05:
            return Solution("clone", 0, 0.0, 0.0, 0.0)
        spec = JobSpec.make(
            t_min=t_min, beta=self.pool.beta, D=req.deadline, N=1,
            tau_est=self.p.tau_est_frac * t_min,
            tau_kill=(self.p.tau_est_frac + self.p.tau_kill_gap_frac)
            * t_min,
            phi_est=self.p.phi_est, C=1.0, theta=self.theta, R_min=0.0)
        return solve(spec)

    def _trace_of(self, requests):
        from .requests import RequestTrace
        if isinstance(requests, RequestTrace):
            return requests
        n = len(requests)
        f32 = np.float32
        return RequestTrace(
            rid=np.asarray([q.rid for q in requests], np.int32),
            arrival=np.asarray([q.submitted for q in requests], f32),
            t_min=np.asarray([self.pool.t_min_of(q.n_tokens)
                              for q in requests], f32),
            beta=np.full(n, self.pool.beta, f32),
            D=np.asarray([q.deadline for q in requests], f32),
            C=np.ones(n, f32), theta_scale=np.ones(n, f32),
            job_class=np.zeros(n, np.int32), class_names=("pool",))

    def execute(self, req: Request) -> HedgeOutcome:
        """Serve one request under its planned (strategy, r*)."""
        sol = self.plan(req)
        trace = self._trace_of([req])
        completion, machine = serve_window(
            self.key, trace.rid, trace.t_min, trace.beta, trace.D,
            np.asarray([sol.r_opt]), np.zeros(1, np.int32),
            strategy=sol.strategy, p=self.p, max_r=self.max_r)
        return HedgeOutcome(
            rid=req.rid, latency=float(completion[0]),
            met=bool(completion[0] <= req.deadline),
            machine_time=float(machine[0]), strategy=sol.strategy,
            r=int(sol.r_opt))

    def run_workload(self, requests) -> dict:
        """Serve a list of Requests (or a RequestTrace) in one stream.

        Known-tail mode: r* solves at the pool's true (t_min, beta); for
        online tail estimation from completed requests use
        `serve.serve_trace(refit_every=...)`.
        """
        from .loop import serve_trace
        out = serve_trace(
            self.key, self._trace_of(requests), self.p,
            strategy=self.strategy, theta=self.theta, max_r=self.max_r)
        return {"pocd": float(out.result.pocd),
                "mean_machine_time": float(out.result.mean_cost),
                "mean_r": out.mean_r, "latency": out.latency,
                "output": out}


def baseline_no_hedge(pool: ReplicaPool, requests, key=None) -> dict:
    """Serve the same stream with no speculation (strategy hadoop_ns)."""
    sched = HedgedScheduler(pool, strategy="hadoop_ns", key=key)
    return sched.run_workload(requests)
