"""Device-sharded finite-capacity execution (the cluster engine's fleet).

Under a shared slot pool every job contends with every other, so the job
axis cannot shard without changing the queueing semantics — the fleet
cluster path therefore shards the Monte-Carlo REPLICATION axis over the
whole mesh (each replication is an independent end-to-end replay), with
the same global-coordinate key derivation as the flat fleet runner:
rep i replays with fold_in(strategy_key, i), replications pad+mask to the
device count, and the replication mean reduces outside the shard_map
region — so cluster metrics too are bit-identical across mesh shapes.

Chunked streaming (`chunk_jobs=`) replays each job-contiguous window of
the trace on its own slot pool and combines PoCD/cost/queue metrics with
`sim.metrics.StreamCombiner`. Traces are arrival-sorted, so windows are
time-contiguous: cross-window slot contention is ignored (exact in the
limit of windows much longer than the queue-drain time — see DESIGN.md
§14). Admission and the r* governor run per window under the same
approximation.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..cluster.admission import (AdmissionConfig, GovernorConfig,
                                 admit_jobs, apply_governor)
from ..cluster.engine import (ClusterOutput, QueueMetrics, _narrow_table,
                              _replay_body)
from ..cluster.slots import DISCIPLINES, utilization
from ..obs import trace as obs_trace
from ..obs.metrics import (capacity_metrics, combine_windows,
                           reduce_reps_host)
from ..sim.metrics import StreamCombiner, aggregate, net_utility
from ..sim.runner import jobspecs_of, strategy_keys
from ..sim.trace import jobset_arrays, jobset_of
from ..strategies import get, names, solve_jobs_jit
from .mesh import AXES, pad_count
from .runner import (_warn_saturated, chunk_jobset, job_columns,
                     solve_on_mesh)


def _cluster_exec(rep_ids, key, arrays, r_j, choice_j, admitted, *,
                  n_jobs: int, strategy: str, p, slots: Optional[int],
                  discipline: str, passes: int, max_r: int, oracle: bool,
                  width: Optional[int], collect_metrics: bool):
    """Per-replication build -> replay -> metrics; vmapped over local reps.

    shard_map body: rep_ids is the sharded axis, everything else enters
    replicated. Each rep's key comes from its global index, so the split
    of reps across devices cannot change any draw.
    """
    jobs = jobset_of(n_jobs, arrays)
    T = jobs.total_tasks
    spec = get(strategy)

    def build_rep(rid):
        k = jax.random.fold_in(key, rid)
        table = spec.build_table(k, jobs, r_j[jobs.job_id],
                                 choice_j[jobs.job_id], p, max_r=max_r,
                                 oracle=oracle)
        if admitted is not None:
            table = table._replace(
                active=table.active & admitted[table.job_id])
        return _narrow_table(table, T, width)

    def replay_rep(table, count_bound):
        realized, release, start = _replay_body(
            table, spec.race, jobs.arrival, jobs.D, slots, discipline,
            passes, T, count_bound=count_bound)
        completion_rel = realized.task_completion - jobs.arrival[jobs.job_id]
        res = aggregate(jobs, completion_rel, realized.task_machine)
        n_active = jnp.maximum(jnp.sum(table.active.astype(jnp.float32)),
                               1.0)
        util = (utilization(realized.busy_time, slots, realized.span)
                if slots is not None else jnp.float32(0.0))
        q = (jnp.sum(realized.wait) / n_active,
             jnp.max(realized.wait), util, realized.preempted)
        if collect_metrics:
            # per-rep functional accumulator; each rep is keyed by its
            # GLOBAL index, so the pytree below is mesh-shape-invariant
            # before any reduction even happens (static flag: off = the
            # byte-identical historical program)
            return res, q, capacity_metrics(table, release, start, realized)
        return res, q

    # build all local replications first and hoist ONE shared active-count
    # bound: a per-rep (batched) bound would collapse the block-skip cond
    # into both-branch execution under vmap and re-serialize the full
    # table (the engine's own hoist idiom, cluster/engine.py). Any bound
    # >= the true count dispatches exactly, so a shard-local max cannot
    # perturb results across mesh shapes.
    tables = jax.vmap(build_rep)(rep_ids)
    count_bound = jnp.max(jnp.sum(tables.active.astype(jnp.int32), axis=1))
    return jax.vmap(lambda t: replay_rep(t, count_bound))(tables)


def _cluster_core_impl(key, rep_ids, arrays, r_j, choice_j, admitted, *,
                       n_jobs: int, strategy: str, p,
                       slots: Optional[int], discipline: str, passes: int,
                       max_r: int, oracle: bool, width: Optional[int],
                       mesh, collect_metrics: bool = False):
    """Compiled fan-out only: per-rep (SimResult, queue scalars), padded.

    As in `runner._core_impl`, the replication mean happens host-side in
    the wrapper — reducing the device-sharded rep axis inside the program
    would let XLA reassociate float sums per mesh shape.
    """
    exec_fn = functools.partial(
        _cluster_exec, n_jobs=n_jobs, strategy=strategy, p=p, slots=slots,
        discipline=discipline, passes=passes, max_r=max_r, oracle=oracle,
        width=width, collect_metrics=collect_metrics)
    args = (rep_ids, key, arrays, r_j, choice_j, admitted)
    if mesh is None or mesh.devices.size == 1:
        return exec_fn(*args)
    # check_vma off: the slot-pool scan starts from replicated carries
    # that the rep-sharded draws then vary; jax.shard_map's type check
    # rejects that carry, the pre-0.8 shard_map did not
    return jax.shard_map(
        exec_fn, mesh=mesh,
        in_specs=(P(AXES), P(), P(), P(), P(), P()),
        out_specs=P(AXES), check_vma=False)(*args)


_cluster_fleet_core = jax.jit(_cluster_core_impl, static_argnames=(
    "n_jobs", "strategy", "p", "slots", "discipline", "passes", "max_r",
    "oracle", "width", "mesh", "collect_metrics"))


def _cluster_fused_impl(key, rep_ids, arrays, specs, admitted, *,
                        n_jobs: int, strategy: str, p,
                        slots: Optional[int], discipline: str, passes: int,
                        max_r: int, oracle: bool, width: Optional[int],
                        mesh, collect_metrics: bool, backend: str):
    """Solve -> build -> replay as ONE device-resident program per window.

    The staged path runs every window's `solve_jobs_jit` as its own
    dispatch (phase 1), syncs the solved r* to host to resolve
    width="auto", and re-threads r*/choice into the replay dispatch. Here
    the Algorithm-1 solve (fused Pallas kernel or XLA reference, per
    `backend`) feeds `spec.build_table` directly on device; the wrapper
    resolves width statically to max_r + 2 instead (sound and replay-
    identical: `_narrow_table` only ever drops inactive columns). The
    governor/admission transforms stay host-side — they are numpy code
    operating on the specs/admitted inputs, not on solve outputs.
    """
    r_j, choice_j, _, th_p, th_c, sat = solve_on_mesh(
        mesh, strategy, specs, max_r + 1, backend)
    th_c = th_c * specs.C
    out = _cluster_core_impl(
        key, rep_ids, arrays, r_j, choice_j, admitted, n_jobs=n_jobs,
        strategy=strategy, p=p, slots=slots, discipline=discipline,
        passes=passes, max_r=max_r, oracle=oracle, width=width, mesh=mesh,
        collect_metrics=collect_metrics)
    return out, (r_j, th_p, th_c, sat)


_CLUSTER_FUSED_STATIC = (
    "n_jobs", "strategy", "p", "slots", "discipline", "passes", "max_r",
    "oracle", "width", "mesh", "collect_metrics", "backend")
# no buffer donation (runner.py): a window's arrays and specs are the
# chunk JobSet's own buffers, which a chaos retry reads again
_cluster_fleet_fused = jax.jit(_cluster_fused_impl,
                               static_argnames=_CLUSTER_FUSED_STATIC)


def _rep_mean(tree, reps: int):
    """Host-side pad+mask epilogue: drop padded reps, mean the rest in a
    fixed order (bool leaves become float frequencies, as mean_over_reps)."""
    host = jax.tree.map(lambda x: np.asarray(x)[:reps], tree)
    if reps == 1:
        return jax.tree.map(lambda x: x[0], host)
    return jax.tree.map(
        lambda x: np.mean(x.astype(np.float32), axis=0), host)


def _window_specs(cjobs, strategy, p, theta, r_min, slots, governor,
                  cost_scale: float = 1.0):
    """Host-side solve inputs for one window — mirrors the legacy
    `run_cluster_strategy` preamble exactly (cost_scale != 1 is the
    elastic governor's capacity re-pricing of this window's solve)."""
    specs = jobspecs_of(cjobs, p, jnp.float32(theta), jnp.float32(r_min))
    if cost_scale != 1.0:
        specs = specs._replace(C=specs.C * jnp.float32(cost_scale))
    if governor is not None and slots is not None:
        specs = apply_governor(specs, cjobs, slots, governor)
    return specs


def _solve_chunk(cjobs, strategy, p, theta, r_min, max_r, slots,
                 governor, cost_scale: float = 1.0):
    """(r_j, choice_j, th_p, th_c, sat) for one chunk (staged path)."""
    J = cjobs.n_jobs
    if not get(strategy).optimized:
        zeros = jnp.zeros((J,), jnp.int32)
        return zeros, zeros, jnp.zeros((J,)), jnp.zeros((J,)), zeros
    specs = _window_specs(cjobs, strategy, p, theta, r_min, slots,
                          governor, cost_scale=cost_scale)
    r_j, choice_j, _, th_p, th_c, sat = solve_jobs_jit(strategy, specs,
                                                       max_r + 1)
    return r_j, choice_j, th_p, th_c * specs.C, sat


def run_cluster_fleet_strategy(key, jobs, strategy: str, p, *, mesh=None,
                               slots: Optional[int] = None, theta=1e-4,
                               r_min=0.0, max_r: int = 8,
                               oracle: bool = True,
                               discipline: str = "fifo", passes: int = 2,
                               governor: Optional[GovernorConfig] = None,
                               admission: Optional[AdmissionConfig] = None,
                               reps: int = 1, width="auto",
                               chunk_jobs=None,
                               pad_to: Optional[int] = None,
                               collect_metrics: bool = False,
                               chaos=None, checkpoint=None,
                               resume: bool = False, fused: bool = True,
                               backend: str = "auto",
                               budget=None) -> ClusterOutput:
    """Fleet mirror of `cluster.engine.run_cluster_strategy`.

    Replications shard over every device of `mesh` (pad+mask to the
    device count); `chunk_jobs` streams job-contiguous windows through
    independent slot pools. `pad_to` (int) overrides the replication
    padding multiple for the pad+mask tests (mesh=None only).
    chaos / checkpoint / resume: as in `runner.run_fleet_strategy`, at
    window granularity — device loss shrinks the rep mesh, slot_change
    events move each window's slot pool, the elastic governor re-prices
    each window's solve, and windows resume from the latest committed
    checkpoint bit-identically.

    fused=True (default) runs optimized strategies as one device-resident
    solve -> build -> replay program per window with no phase-1 solve
    dispatches and no host round-trip between solve and replay; width
    resolves statically to max_r + 2 (replay-identical — see
    `_cluster_fused_impl`). `backend` picks the Algorithm-1 solve kernel
    (`strategies.solve_backend`: "auto" = Pallas on TPU, XLA reference
    elsewhere). Baselines have nothing to solve and always run staged.
    fused=False keeps the two-phase staged pipeline (bit-identical
    results, kept as the reference path and for solved-width narrowing
    when max_r is much larger than any solved r*).
    """
    if passes < 2:
        raise ValueError(f"passes must be >= 2 (pass 1 schedules primaries "
                         f"only), got {passes}")
    if discipline not in DISCIPLINES:
        raise ValueError(f"unknown discipline {discipline!r}; "
                         f"expected one of {DISCIPLINES}")
    if pad_to is not None and mesh is not None:
        raise ValueError("pad_to is a test-only override; incompatible "
                         "with an explicit mesh")
    if resume and checkpoint is None:
        raise ValueError("resume=True requires a checkpoint config")
    if not get(strategy).detectable:
        oracle = True
    if budget is not None and not get(strategy).optimized:
        budget = None     # baselines run at r = 0: nothing to budget
    if budget is not None and chaos is not None:
        raise ValueError(
            "budget= requires a chaos-free run: the shared multiplier is "
            "solved once over the whole trace, and chaos re-pricing or "
            "slot/mesh loss mid-run would invalidate that global solve")

    def layout_of(m):
        rep_mult = (pad_to if pad_to is not None
                    else (m.devices.size if m is not None else 1))
        return jnp.arange(pad_count(reps, rep_mult), dtype=jnp.int32)

    rep_ids = layout_of(mesh)

    cols = job_columns(jobs)
    J = int(cols[0].shape[0])
    chunk = J if chunk_jobs is None else max(1, int(chunk_jobs))
    n_chunks = -(-J // chunk)

    ctx = saver = cfg = fp = None
    start_chunk = 0
    if chaos is not None:
        from ..chaos.inject import as_context
        ctx = as_context(chaos)
        ctx.bind(n_chunks, mesh, reps, slots=slots)
    if checkpoint is not None:
        from ..chaos import recovery
        cfg = recovery.as_checkpoint(checkpoint)
        saver = recovery.ChunkCheckpointer(cfg)
        fp = recovery.run_fingerprint(
            path="cluster", strategy=strategy, n_jobs=J, chunk=chunk,
            reps=reps, max_r=max_r, oracle=oracle, theta=float(theta),
            r_min=float(r_min), slots=slots, discipline=discipline,
            passes=passes, key=np.asarray(key),
            plan=ctx.plan.fingerprint() if ctx is not None else "",
            budget=None if budget is None else float(budget))

    # phase 1 (staged path only) — solve every window first, so
    # width="auto" resolves to ONE static value (max over windows):
    # per-window widths would recompile the replay per chunk, and a
    # narrower-than-global width would be unsound for windows with a
    # larger solved r*. Only the per-job solve outputs are kept; window
    # JobSets (the task-axis memory) are rebuilt one at a time in phase
    # 2. The solves are deterministic, so a resume re-runs this phase
    # rather than checkpointing it. The fused path skips this phase
    # entirely — its width is static and its solves run inside the
    # per-window program.
    # A budgeted run is staged: its solve happens ONCE globally below,
    # and every window replays a slice of that one selection.
    use_fused = fused and get(strategy).optimized and budget is None
    bounds = [(ci * chunk, min((ci + 1) * chunk, J))
              for ci in range(n_chunks)]
    solves = None
    info = None
    if budget is not None:
        # global-lambda pre-pass: concatenate every window's (governor-
        # transformed) solve inputs and solve the joint problem once, so
        # chunked == monolithic bitwise (chunk-local re-solves would give
        # each window its own multiplier). Chaos is rejected above, so
        # every window sees the caller's slots and cost scale.
        from ..coupled import solve_jobs_coupled_jit, warn_infeasible
        with obs_trace.span("fleet.cluster.coupled_solve",
                            strategy=strategy, n_jobs=J,
                            n_chunks=n_chunks):
            parts = [_window_specs(chunk_jobset(cols, lo, hi), strategy,
                                   p, theta, r_min, slots, governor)
                     for lo, hi in bounds]
            gspecs = jax.tree.map(lambda *xs: jnp.concatenate(xs), *parts)
            (g_r, g_ch, _, g_p, g_c, g_sat), info = solve_jobs_coupled_jit(
                strategy, gspecs, max_r + 1, jnp.float32(budget))
            g = tuple(np.asarray(a) for a in
                      (g_r, g_ch, g_p, g_c * gspecs.C, g_sat))
            solves = [tuple(a[lo:hi] for a in g) for lo, hi in bounds]
        warn_infeasible(strategy, info)
    elif not use_fused:
        solves = []
        with obs_trace.span("fleet.cluster.solve", strategy=strategy,
                            n_jobs=J, n_chunks=n_chunks):
            for ci, (lo, hi) in enumerate(bounds):
                slots_ci = (ctx.slots_at(ci, slots) if ctx is not None
                            else slots)
                scale_ci = ctx.cost_scale(ci) if ctx is not None else 1.0
                solves.append(_solve_chunk(chunk_jobset(cols, lo, hi),
                                           strategy, p, theta, r_min,
                                           max_r, slots_ci, governor,
                                           cost_scale=scale_ci))
    if width == "auto":
        if not get(strategy).optimized:
            width = None
        elif use_fused:
            # static: r* < max_r + 1 always, and _narrow_table only ever
            # drops inactive columns, so the full grid width replays
            # bit-identically to the solved-max width
            width = max_r + 2
        else:
            width = int(max(int(jnp.max(s[0])) for s in solves)) + 2

    # phase 2 — replay each window on its own slot pool
    acc = StreamCombiner()
    n_sat = 0
    r_parts, thp_parts, thc_parts = [], [], []
    if resume:
        step = saver.latest()
        if step is not None:
            header, acc, (r_parts, thp_parts, thc_parts) = \
                recovery.unpack_run_state(saver.load(step))
            recovery.check_fingerprint(header["fingerprint"], fp)
            start_chunk = int(header["next_chunk"])
            if ctx is not None:
                mesh = ctx.mesh_through(start_chunk, mesh, reps)
                rep_ids = layout_of(mesh)
                ctx.catch_up(start_chunk)

    try:
        for ci in range(start_chunk, n_chunks):
            if ctx is not None:
                new_mesh = ctx.begin_chunk(ci, mesh, reps)
                if new_mesh is not mesh:
                    mesh = new_mesh
                    rep_ids = layout_of(mesh)
            lo, hi = bounds[ci]
            slots_w = ctx.slots_at(ci, slots) if ctx is not None else slots
            cjobs = chunk_jobset(cols, lo, hi)
            admitted = None
            if admission is not None and slots_w is not None:
                admitted = jnp.asarray(admit_jobs(cjobs, slots_w,
                                                  admission))

            if use_fused:
                # chaos cost_scale / governor are precomputed host-side
                # transforms of the solve INPUTS, so reading them here
                # gives exactly the values phase 1 would have used
                scale_ci = ctx.cost_scale(ci) if ctx is not None else 1.0
                specs = _window_specs(cjobs, strategy, p, theta, r_min,
                                      slots_w, governor,
                                      cost_scale=scale_ci)

                def exec_window(rep_ids=rep_ids, cjobs=cjobs, specs=specs,
                                admitted=admitted, slots_w=slots_w,
                                mesh=mesh):
                    return obs_trace.fenced(
                        f"fleet.cluster.fused[{strategy}]",
                        _cluster_fleet_fused,
                        key, rep_ids, jobset_arrays(cjobs), specs,
                        admitted, n_jobs=cjobs.n_jobs, strategy=strategy,
                        p=p, slots=slots_w, discipline=discipline,
                        passes=passes, max_r=max_r, oracle=oracle,
                        width=width, mesh=mesh,
                        collect_metrics=collect_metrics, backend=backend)
            else:
                r_j, choice_j, th_p, th_c, sat_j = solves[ci]

                def exec_window(rep_ids=rep_ids, cjobs=cjobs, r_j=r_j,
                                choice_j=choice_j, admitted=admitted,
                                slots_w=slots_w, mesh=mesh):
                    return obs_trace.fenced(
                        f"fleet.cluster.replay[{strategy}]",
                        _cluster_fleet_core,
                        key, rep_ids, jobset_arrays(cjobs), r_j, choice_j,
                        admitted, n_jobs=cjobs.n_jobs, strategy=strategy,
                        p=p, slots=slots_w, discipline=discipline,
                        passes=passes, max_r=max_r, oracle=oracle,
                        width=width, mesh=mesh,
                        collect_metrics=collect_metrics)

            out = exec_window() if ctx is None else ctx.execute(
                ci, exec_window)
            if use_fused:
                out, (r_j, th_p, th_c, sat_j) = out
            with obs_trace.span("fleet.cluster.reduce", window=ci):
                if collect_metrics:
                    res, q, rep_metrics = out
                    # pad+mask rep drop + fixed-order reduction, host-side
                    # — mesh topology cannot perturb the combined pytree
                    window_metrics = reduce_reps_host(rep_metrics, reps)
                else:
                    res, q = out
                    window_metrics = None
                res, q = _rep_mean((res, q), reps)
                mean_wait, max_wait, util, preempted = q
                admitted_frac = (1.0 if admitted is None
                                 else float(np.mean(np.asarray(admitted))))
                queue = QueueMetrics(
                    mean_wait=jnp.float32(mean_wait),
                    max_wait=jnp.float32(max_wait),
                    utilization=jnp.float32(util),
                    preempted=jnp.float32(preempted),
                    admitted_frac=jnp.float32(admitted_frac),
                    slots=slots_w)
                acc.add(res, n_jobs=cjobs.n_jobs, queue=queue,
                        capacity=window_metrics)
                r_parts.append(np.asarray(r_j))
                thp_parts.append(np.asarray(th_p))
                thc_parts.append(np.asarray(th_c))
                if get(strategy).optimized:
                    n_sat += int(np.asarray(sat_j).sum())
            if saver is not None:
                crash_here = (ctx is not None
                              and bool(ctx.plan.at(ci, "crash")))
                if ((ci + 1) % cfg.every == 0 or ci == n_chunks - 1
                        or crash_here):
                    saver.save(ci + 1, recovery.pack_run_state(
                        acc, (r_parts, thp_parts, thc_parts),
                        next_chunk=ci + 1, fingerprint=fp))
                    if crash_here:
                        saver.wait()
            if ctx is not None:
                ctx.maybe_crash(ci)
    finally:
        if saver is not None:
            saver.wait()

    if n_sat:
        _warn_saturated(strategy, n_sat, max_r)
    result = acc.finalize()
    queue = acc.finalize_queue()
    return ClusterOutput(
        result=result,
        r_opt=jnp.asarray(np.concatenate(r_parts)),
        utility=net_utility(result.pocd, result.mean_cost, r_min, theta),
        theory_pocd=jnp.asarray(np.concatenate(thp_parts)),
        theory_cost=jnp.asarray(np.concatenate(thc_parts)),
        queue=queue, metrics=acc.finalize_capacity(),
        n_saturated=n_sat, coupled=info)


def run_cluster_fleet(key, jobs, p, slots: Optional[int] = None,
                      theta=1e-4, strategies=None,
                      r_min_from_ns: bool = True, max_r: int = 8,
                      oracle: bool = True, discipline: str = "fifo",
                      passes: int = 2,
                      governor: Optional[GovernorConfig] = None,
                      admission: Optional[AdmissionConfig] = None,
                      reps: int = 1, mesh=None, chunk_jobs=None,
                      collect_metrics: bool = False, chaos=None,
                      checkpoint=None, resume: bool = False,
                      fused: bool = True, backend: str = "auto",
                      budget=None):
    """Fleet mirror of `cluster.engine.run_cluster` (same r_min protocol).

    chaos / checkpoint follow `runner.run_all_fleet`: one FaultPlan shared
    by every strategy (each gets a fresh ChaosContext), per-strategy
    checkpoint subdirectories. A scenario name's declared fault schedule
    becomes the default plan when `chaos` is None.
    """
    if isinstance(jobs, str):
        from ..workloads.registry import get_scenario, make_trace
        if chaos is None:
            faults = getattr(get_scenario(jobs), "faults", None)
            if faults:
                from ..chaos.plan import from_faults
                chaos = from_faults(faults)
        jobs = make_trace(jobs)
    if strategies is None:
        strategies = names()
    key_of = strategy_keys(key, strategies)
    kw = dict(mesh=mesh, slots=slots, theta=theta, max_r=max_r,
              oracle=oracle, discipline=discipline, passes=passes,
              governor=governor, admission=admission, reps=reps,
              chunk_jobs=chunk_jobs, collect_metrics=collect_metrics,
              fused=fused, backend=backend, budget=budget)

    def kw_of(name):
        per = dict(kw)
        if chaos is not None:
            from ..chaos.inject import ChaosContext
            from ..chaos.plan import FaultPlan
            if not isinstance(chaos, FaultPlan):
                raise TypeError("run_cluster_fleet takes a FaultPlan "
                                "(each strategy needs its own "
                                "ChaosContext)")
            per["chaos"] = ChaosContext(chaos)
        if checkpoint is not None:
            from ..chaos.recovery import as_checkpoint
            per["checkpoint"] = as_checkpoint(checkpoint).sub(name)
            per["resume"] = resume
        return per

    outs = {}
    r_min = 0.0
    if "hadoop_ns" in strategies:
        outs["hadoop_ns"] = run_cluster_fleet_strategy(
            key_of["hadoop_ns"], jobs, "hadoop_ns", p, r_min=0.0,
            **kw_of("hadoop_ns"))
        if r_min_from_ns:
            r_min = float(outs["hadoop_ns"].result.pocd) - 1e-3
    for name in strategies:
        if name == "hadoop_ns":
            continue
        outs[name] = run_cluster_fleet_strategy(key_of[name], jobs, name, p,
                                                r_min=r_min, **kw_of(name))
    return outs, r_min
