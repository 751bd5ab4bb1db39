"""The online serving path: continuous requests, online tail governor.

`serve_trace` streams a `RequestTrace` through the strategy IR in
fixed-width compiled windows (scheduler.serve_window):

* **Known-tail mode** (refit_every=None): Algorithm 1 solves every
  request's r* once, at the request's own (t_min, beta) — the oracle
  regime the seed scheduler hard-coded.
* **Online mode** (refit_every=E): the stream is cut into epochs of E
  requests. Every probe_every-th request (by rid) is served unhedged —
  exploration traffic whose completion is an unbiased Pareto sample —
  and feeds a `repro.obs.tail.TailGovernor`, which refits the Pareto
  MLE + Hill tail on its rolling window and re-solves Algorithm 1 once
  per epoch (cadence = probes/epoch: the PR 6 observe -> refit ->
  re-solve hook, driven by real completions). Epoch e's hedging runs at
  the fit from epochs < e; cold epochs (no fit yet) serve unhedged.
  With strategy="auto" each epoch also adopts the governor's re-solved
  strategy choice.

Determinism: draws are keyed per request (`fold_in(key, rid)`), solves
are per-lane argmaxes, and fits depend only on the probe prefix — so
serving metrics are bitwise invariant to window size, fleet-mesh shape,
and chunk boundaries; `StreamCombiner` accumulates per-epoch columns and
`finalize()` reproduces a monolithic run exactly (the §14 property,
extended to serving).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.utility import JobSpec
from ..obs import trace as obs_trace
from ..sim.metrics import (SimResult, StreamCombiner, latency_summary,
                           net_utility, request_result)
from ..sim.runner import strategy_keys
from ..sim.strategies import SimParams
from ..strategies import get, names, solve_jobs_jit
from .requests import RequestTrace, make_requests, requests_from_trace
from .scheduler import serve_window

__all__ = ["ServeOutput", "serve_trace", "run_serve"]

_UNHEDGED = "hadoop_ns"   # the probe / cold-epoch / no-hedge draw


class ServeOutput(NamedTuple):
    strategy: str              # requested strategy ("auto" stays "auto")
    result: SimResult          # per-request metrics (finalized columns)
    utility: float             # net_utility(pocd, mean_cost, r_min, theta)
    latency: dict              # p50/p95/p99/mean of request latency
    mean_r: float              # mean r* over hedged requests (0 if none)
    n_probes: int              # unhedged exploration requests served
    n_refits: int              # governor refit/re-solve events
    fits: tuple                # TailFit per refit, in order
    epoch_strategies: tuple    # strategy executed per epoch (online mode)


def _epoch_jobspecs(t_min_fit, beta_fit, reqs: RequestTrace, p: SimParams,
                    theta: float, r_min: float, width: int) -> JobSpec:
    """Batched 1-task JobSpec at the policy's tail belief.

    The tail (t_min, beta) is the policy's *estimate* — fitted online or
    the true per-request values in known-tail mode — while D, C, and
    theta_scale are contractual (known from the SLA). Padded to `width`
    so each (strategy, width) solve compiles once; lanes are
    independent, so padding never changes a real lane's r*.
    """
    n = reqs.n_requests
    pad = width - n
    col = lambda x: jnp.asarray(np.pad(np.asarray(x, np.float32), (0, pad),
                                       mode="edge"))
    t = col(np.broadcast_to(np.asarray(t_min_fit, np.float32), (n,)))
    b = col(np.broadcast_to(np.asarray(beta_fit, np.float32), (n,)))
    tau_est = p.tau_est_frac * t
    full = lambda v: jnp.full((width,), v, jnp.float32)
    return JobSpec(
        t_min=t, beta=b, D=col(reqs.D), N=full(1.0),
        tau_est=tau_est, tau_kill=tau_est + p.tau_kill_gap_frac * t,
        phi_est=full(p.phi_est), C=col(reqs.C),
        theta=jnp.float32(theta) * col(reqs.theta_scale),
        R_min=full(r_min))


def _solve_epoch(strategy: str, t_min_fit, beta_fit, reqs: RequestTrace,
                 p: SimParams, theta, r_min, max_r: int, width: int,
                 backend: str = "auto"):
    """(r, choice) int32 arrays (n_requests,) from the padded grid solve.

    `backend` routes the Algorithm-1 solve (fused Pallas kernel on TPU,
    vmapped XLA reference otherwise); both int32 columns come back in one
    batched device->host transfer rather than one sync each.
    """
    with obs_trace.span("serve.solve"):
        specs = _epoch_jobspecs(t_min_fit, beta_fit, reqs, p, theta, r_min,
                                width)
        r, choice, _, _, _, _ = solve_jobs_jit(strategy, specs, max_r + 1,
                                               backend=backend)
        n = reqs.n_requests
        with obs_trace.span("d2h.wait"):
            r, choice = jax.device_get((r, choice))
        return np.asarray(r)[:n], np.asarray(choice)[:n]


def _serve_chunk(key, reqs: RequestTrace, r, choice, *, strategy, p,
                 max_r, oracle, window, sharding):
    """Serve a request chunk through fixed-width windows; stream order."""
    n = reqs.n_requests
    completion = np.empty(n, np.float32)
    machine = np.empty(n, np.float32)
    for lo in range(0, n, window):
        hi = min(lo + window, n)
        with obs_trace.span("serve.window"):
            c, m = serve_window(
                key, reqs.rid[lo:hi], reqs.t_min[lo:hi], reqs.beta[lo:hi],
                reqs.D[lo:hi], r[lo:hi], choice[lo:hi], strategy=strategy,
                p=p, max_r=max_r, oracle=oracle, width=window,
                sharding=sharding)
        completion[lo:hi], machine[lo:hi] = c, m
    return completion, machine


def _subset(reqs: RequestTrace, idx) -> RequestTrace:
    return reqs._replace(
        rid=reqs.rid[idx], arrival=reqs.arrival[idx],
        t_min=reqs.t_min[idx], beta=reqs.beta[idx], D=reqs.D[idx],
        C=reqs.C[idx], theta_scale=reqs.theta_scale[idx],
        job_class=reqs.job_class[idx])


def serve_trace(key, reqs, p: Optional[SimParams] = None, *,
                strategy: str = "adaptive", theta: float = 1e-3,
                r_min: float = 0.0, max_r: int = 8, oracle: bool = True,
                window: int = 256, refit_every: Optional[int] = None,
                probe_every: int = 8, r_override: Optional[int] = None,
                mesh=None, tail_capacity: int = 2048,
                min_samples: int = 16, combiner: Optional[StreamCombiner]
                = None, backend: str = "auto") -> ServeOutput:
    """Serve one request stream under one strategy; see module doc.

    reqs: a RequestTrace, a workloads WorkloadTrace, or a scenario name.
    mesh: a fleet mesh — windows shard over its "job" axis (bit-identical
        to the unsharded path; window is padded to the axis extent).
    r_override: fixed replication level (the fixed-r baseline) — skips
        both the per-request solve and the governor's fit.
    combiner: accumulate into an existing StreamCombiner (checkpointed
        streaming); a fresh one is created when None.
    backend: Algorithm-1 backend for the per-epoch r* solves ("auto" |
        "xla" | "pallas"; auto picks the fused Pallas grid-solve kernel
        on TPU and the vmapped XLA reference elsewhere).
    """
    if isinstance(reqs, str):
        reqs = make_requests(reqs)
    elif not isinstance(reqs, RequestTrace):
        reqs = requests_from_trace(reqs)
    if p is None:
        p = SimParams()
    requested = strategy
    if strategy == "auto":
        if refit_every is None:
            strategy = "adaptive"   # known-tail auto = per-request argmax
        if r_override is not None:
            raise ValueError("r_override is incompatible with "
                             "strategy='auto' (nothing picks the strategy)")
    optimized = strategy == "auto" or get(strategy).optimized
    sharding = None
    if mesh is not None:
        from ..fleet.mesh import job_sharding, mesh_extents, pad_count
        window = pad_count(window, mesh_extents(mesh)[1])
        sharding = job_sharding(mesh)

    n = reqs.n_requests
    acc = StreamCombiner() if combiner is None else combiner
    zeros = lambda m: np.zeros(m, np.int32)
    sum_r, n_hedged, n_probes = 0.0, 0, 0
    fits: list = []
    epoch_strategies: list = []

    with obs_trace.span("serve.trace", strategy=requested, n_requests=n,
                        online=refit_every is not None):
        if refit_every is None:
            # -- known-tail: one solve at the true per-request tail ------
            if not optimized:
                r, ch = zeros(n), zeros(n)
            elif r_override is not None:
                r = np.full(n, int(r_override), np.int32)
                sp = get(strategy)
                ch = zeros(n) if sp.choose is None else np.asarray(
                    sp.choose(jnp.asarray(r, jnp.float32),
                              _epoch_jobspecs(reqs.t_min, reqs.beta, reqs,
                                              p, theta, r_min, n)),
                    np.int32)
            else:
                r, ch = _solve_epoch(strategy, reqs.t_min, reqs.beta,
                                     reqs, p, theta, r_min, max_r, n,
                                     backend=backend)
            completion, machine = _serve_chunk(
                key, reqs, r, ch, strategy=strategy, p=p, max_r=max_r,
                oracle=oracle, window=window, sharding=sharding)
            with obs_trace.span("serve.combine"):
                acc.add(request_result(reqs, completion, machine), n_jobs=n)
            sum_r += float(r.sum())
            n_hedged += int((r > 0).sum())
        else:
            # -- online: epochs, probes, governor refits -----------------
            if refit_every % probe_every != 0:
                raise ValueError(
                    f"refit_every ({refit_every}) must be a multiple of "
                    f"probe_every ({probe_every}) so refits land exactly "
                    f"on epoch boundaries")
            from ..obs.tail import TailGovernor, TailRegistry
            gov = TailGovernor(
                deadline=float(np.median(reqs.D)), n_tasks=1, theta=theta,
                price=float(np.mean(reqs.C)), r_min=r_min,
                tau_est_frac=p.tau_est_frac,
                tau_kill_gap_frac=p.tau_kill_gap_frac, phi_est=p.phi_est,
                cadence=refit_every // probe_every,
                min_samples=min_samples, max_r=max_r,
                registry=TailRegistry(capacity=tail_capacity),
                window_name="serve",
                on_resolve=lambda sol, fit: fits.append(fit))
            for lo in range(0, n, refit_every):
                with obs_trace.span("serve.epoch"):
                    epoch = reqs.slice(lo, min(lo + refit_every, n))
                    e = epoch.n_requests
                    probe = np.asarray(epoch.rid) % probe_every == 0
                    fit = gov.last_fit
                    if strategy == "auto":
                        epoch_strategy = (gov.decision.strategy
                                          if gov.decision is not None
                                          else _UNHEDGED)
                    else:
                        epoch_strategy = strategy
                    if not optimized:
                        r, ch = zeros(e), zeros(e)
                    elif r_override is not None:
                        r = np.full(e, int(r_override), np.int32)
                        ch = zeros(e)
                    elif fit is None or epoch_strategy == _UNHEDGED:
                        # cold: no tail belief yet
                        epoch_strategy = _UNHEDGED
                        r, ch = zeros(e), zeros(e)
                    else:
                        r, ch = _solve_epoch(
                            epoch_strategy, fit.t_min, fit.beta, epoch, p,
                            theta, r_min, max_r, refit_every,
                            backend=backend)
                    epoch_strategies.append(epoch_strategy)

                    completion = np.empty(e, np.float32)
                    machine = np.empty(e, np.float32)
                    hedged = ~probe
                    for mask, strat, rr, cc in (
                            (hedged, epoch_strategy, r, ch),
                            (probe, _UNHEDGED, zeros(e), zeros(e))):
                        idx = np.flatnonzero(mask)
                        if idx.size == 0:
                            continue
                        c, m = _serve_chunk(
                            key, _subset(epoch, idx), rr[idx], cc[idx],
                            strategy=strat, p=p, max_r=max_r,
                            oracle=oracle, window=window, sharding=sharding)
                        completion[idx], machine[idx] = c, m
                    if epoch_strategy != _UNHEDGED:
                        sum_r += float(r[hedged].sum())
                        n_hedged += int((r[hedged] > 0).sum())
                    n_probes += int(probe.sum())
                    with obs_trace.span("serve.combine"):
                        acc.add(request_result(epoch, completion, machine),
                                n_jobs=e)
                    # completed exploration traffic drives the PR 6
                    # observe -> refit -> re-solve hook; the resolve fires
                    # on the epoch's last probe, so the fresh fit and
                    # decision govern exactly the next epoch
                    if r_override is None:
                        with obs_trace.span("serve.governor"):
                            for x in completion[probe]:
                                gov.observe(float(x))

    result = acc.finalize()
    with obs_trace.span("d2h.wait"):
        utility = float(net_utility(result.pocd, result.mean_cost, r_min,
                                    theta))
    return ServeOutput(
        strategy=requested, result=result, utility=utility,
        latency=latency_summary(result),
        mean_r=(sum_r / max(n_hedged, 1)), n_probes=n_probes,
        n_refits=len(fits), fits=tuple(fits),
        epoch_strategies=tuple(epoch_strategies))


def run_serve(key, reqs, p: Optional[SimParams] = None, *,
              theta: float = 1e-3, strategies=None,
              r_min_from_ns: bool = True, max_r: int = 8,
              oracle: bool = True, window: int = 256,
              refit_every: Optional[int] = None, probe_every: int = 8,
              r_override: Optional[int] = None, mesh=None, devices=None,
              tail_capacity: int = 2048, min_samples: int = 16):
    """Serve the stream under every strategy; the run_all of serving.

    Per-strategy keys come from `strategy_keys` (stable registry-index
    fold_in; "auto" borrows adaptive's slot), r_min for utilities is the
    no-hedge PoCD (the paper's R_min protocol, applied to serving), and
    each strategy's stream is self-contained — subsetting the strategy
    list never perturbs another strategy's draws. Returns (outs, r_min)
    with outs mapping strategy -> ServeOutput.
    """
    if isinstance(reqs, str):
        reqs = make_requests(reqs)
    elif not isinstance(reqs, RequestTrace):
        reqs = requests_from_trace(reqs)
    if p is None:
        p = SimParams()
    if strategies is None:
        strategies = names()
    if mesh is None and devices is not None and int(devices) > 1:
        from ..fleet import fleet_mesh
        mesh = fleet_mesh(devices=devices, reps=1)
    key_of = strategy_keys(
        key, [("adaptive" if s == "auto" else s) for s in strategies])

    kw = dict(theta=theta, max_r=max_r, oracle=oracle, window=window,
              refit_every=refit_every, probe_every=probe_every,
              mesh=mesh, tail_capacity=tail_capacity,
              min_samples=min_samples)
    outs = {}
    r_min = 0.0
    if _UNHEDGED in strategies:
        outs[_UNHEDGED] = serve_trace(key_of[_UNHEDGED], reqs, p,
                                      strategy=_UNHEDGED, r_min=0.0, **kw)
        if r_min_from_ns:
            r_min = float(outs[_UNHEDGED].result.pocd) - 1e-3
    for name in strategies:
        if name == _UNHEDGED:
            continue
        k = key_of["adaptive" if name == "auto" else name]
        outs[name] = serve_trace(k, reqs, p, strategy=name, r_min=r_min,
                                 r_override=r_override, **kw)
    return outs, r_min
