"""Device-sharded fleet execution of the flat trace simulator.

Runs every StrategySpec's Monte-Carlo backend across an arbitrary
("rep", "job") device mesh (`shard_map`): replications shard over "rep",
job blocks (`blocks.py`) over "job", with pad+mask fallbacks for counts
that do not divide the mesh (the `sharding/planner.py` idiom applied to
the simulation axes).

Key-derivation contract — the invariance the whole layer rests on: the
draw key of (replication i, job block g) is

    fold_in(fold_in(strategy_key, i), g)          # g is the GLOBAL index

so a (rep, block) cell's draws depend only on the caller's key and the
cell's global coordinates — never on the mesh shape, the pad amounts, or
the chunk split. Metrics are therefore bit-identical across 1x1 / 2x4 /
8x1 meshes, the no-mesh single-device path, and any chunk size (chunk
boundaries are forced onto block boundaries), which is what lets a CI
host with 8 forced CPU devices certify the path production meshes take.

Every cross-job reduction happens OUTSIDE the shard_map region, on the
gathered per-job columns, in trace order — shards never psum floats, so
mesh topology cannot perturb a reduction order.

The fleet path draws per (rep, block) rather than per whole-trace key, so
its Monte-Carlo stream is statistically equivalent but not draw-identical
to the legacy single-device `sim.runner` path, which stays byte-for-byte
unchanged (and is still what `run_all` uses when no devices are asked
for). Chunked streaming (`chunk_jobs=`) bounds memory at
O(chunk draws) and reduces through `sim.metrics.StreamCombiner`.
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..obs import trace as obs_trace
from ..sim.metrics import SimResult, StreamCombiner, net_utility
from ..sim.runner import RunOutput, jobspecs_of, strategy_keys
from ..sim.trace import build_jobset
from ..strategies import get, names, solve_jobs, solve_jobs_jit
from .blocks import (block_jobset, block_layout, block_task_counts,
                     gather_index, make_blocks, stack_task_column)
from .mesh import mesh_extents, pad_count

_JOB_COLUMNS = ("n_tasks", "t_min", "beta", "D", "arrival", "C",
                "job_class", "theta_scale")


def job_columns(source) -> tuple:
    """Per-job numpy columns of a JobSet or WorkloadTrace (same schema).

    The chunked streamer slices these — never the flat per-task arrays —
    so a million-job WorkloadTrace is chunked without ever materializing
    its full task axis.
    """
    return tuple(np.asarray(getattr(source, f)) for f in _JOB_COLUMNS)


def chunk_jobset(cols: tuple, lo: int, hi: int):
    """Build the JobSet for jobs [lo, hi) of sliced per-job columns."""
    sl = tuple(c[lo:hi] for c in cols)
    return build_jobset(*sl[:6], job_class=sl[6], theta_scale=sl[7])


# ---------------------------------------------------------------------------
# Compiled core: per-(rep, block) draws -> per-job metrics
# ---------------------------------------------------------------------------


def _exec_blocks(key, rep_ids, blocks, r_blocks, choice_blocks, *,
                 strategy: str, p, max_r: int, oracle: bool):
    """(reps, G, Jb) per-job completion/machine for every (rep, block).

    This is the shard_map body: everything here is local to one mesh cell
    slice, and each (rep, block) is keyed by its global coordinates, so
    the values cannot depend on how the axes were partitioned.
    """
    spec = get(strategy)

    def one_rep(rid):
        k_rep = jax.random.fold_in(key, rid)

        def one_block(blk, r_task, choice_task):
            bjs = block_jobset(blk)
            k = jax.random.fold_in(k_rep, blk.block_id)
            completion, machine = spec.draw(
                k, bjs, r_task, choice_task, p, max_r=max_r, oracle=oracle)
            jc = jax.ops.segment_max(completion, bjs.job_id, bjs.n_jobs)
            jm = jax.ops.segment_sum(
                jnp.where(blk.task_valid, machine, 0.0), bjs.job_id,
                bjs.n_jobs)
            return jc, jm

        return jax.vmap(one_block)(blocks, r_blocks, choice_blocks)

    return jax.vmap(one_rep)(rep_ids)


def _core_impl(key, rep_ids, blocks, r_blocks, choice_blocks, *,
               strategy: str, p, max_r: int, oracle: bool, mesh):
    """Compiled fan-out only: (reps_pad, G_pad, Jb) completion/machine.

    Deliberately returns the RAW per-(rep, block) results: every value is
    a pure function of its cell's global coordinates, so the outputs are
    bitwise mesh-invariant. All cross-rep / cross-job reductions happen
    host-side in `_chunk_result` — reducing a device-sharded axis inside
    the compiled program would let XLA reassociate float sums differently
    per mesh shape, which is exactly the nondeterminism this layer bans.
    """
    exec_fn = functools.partial(_exec_blocks, strategy=strategy, p=p,
                                max_r=max_r, oracle=oracle)
    if mesh is None or mesh.devices.size == 1:
        # single-device fast path: same computation, no partitioning
        return exec_fn(key, rep_ids, blocks, r_blocks, choice_blocks)
    blocks_spec = jax.tree.map(lambda _: P("job"), blocks)
    return jax.shard_map(
        exec_fn, mesh=mesh,
        in_specs=(P(), P("rep"), blocks_spec, P("job"), P("job")),
        out_specs=(P("rep", "job"), P("rep", "job")))(
            key, rep_ids, blocks, r_blocks, choice_blocks)


def solve_on_mesh(mesh, strategy: str, specs, r_max: int, backend: str):
    """Algorithm-1 solve of one chunk inside a (possibly multi-device)
    chunk program.

    XLA cannot partition a Mosaic kernel, so on a multi-device mesh the
    solve runs whole on every device inside a replicated shard_map; the
    outputs are per-job and identical on every device, so results stay
    mesh-invariant.
    """
    solve = functools.partial(solve_jobs, strategy, r_max=r_max,
                              backend=backend)
    if mesh is None or mesh.devices.size == 1:
        return solve(specs)
    # check_vma off: a pallas_call's out_shape carries no varying-axes
    # type, and every output here is replicated by construction
    return jax.shard_map(solve, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)(specs)


def _fused_impl(key, rep_ids, blocks, specs, task_job, *, strategy: str,
                p, max_r: int, oracle: bool, mesh, backend: str):
    """Solve -> gather -> replay as ONE device-resident program per chunk.

    The staged path dispatches `solve_jobs_jit` separately, syncs r*/choice
    to host, and re-threads them through the numpy block assembler before
    the replay dispatch — two host round-trips of per-job columns per
    chunk. Here the solve runs in-program (`backend` picks the fused
    Pallas kernel or the XLA reference) and the block layout's gather is
    applied on device: `task_job` is the host-precomputed geometry column
    (pure layout, no solve outputs) mapping each (block, slot) to its
    chunk job index, with padding slots pointing at the appended zero row
    — exactly the fill value `stack_task_column` writes — so the replay
    consumes bit-identical r/choice blocks without r* ever leaving the
    device.
    """
    r_j, choice_j, _, th_p, th_c, sat = solve_on_mesh(
        mesh, strategy, specs, max_r + 1, backend)
    th_c = th_c * specs.C
    pad0 = lambda x: jnp.concatenate([x, jnp.zeros((1,), x.dtype)])
    r_b = pad0(r_j)[task_job]
    c_b = pad0(choice_j)[task_job]
    jc, jm = _core_impl(key, rep_ids, blocks, r_b, c_b, strategy=strategy,
                        p=p, max_r=max_r, oracle=oracle, mesh=mesh)
    return jc, jm, r_j, th_p, th_c, sat


_STATIC = ("strategy", "p", "max_r", "oracle", "mesh")
# No buffer donation. The large inputs (per-task block columns) never
# match an output's shape, so XLA cannot reuse them; the JobSpec batch
# holds the chunk JobSet's own D / C columns, which the epilogue reads
# after the call; and a chaos retry runs the same chunk thunk, with the
# same input arrays, again.
_fleet_core = jax.jit(_core_impl, static_argnames=_STATIC)
_fleet_fused = jax.jit(_fused_impl, static_argnames=_STATIC + ("backend",))


def _warn_saturated(strategy: str, n_sat: int, max_r: int):
    warnings.warn(
        f"fleet solve[{strategy}]: r* saturated at the grid edge "
        f"(max_r={max_r}) for {n_sat} job(s) — raise max_r past "
        f"core.optimizer.r_upper_bound", RuntimeWarning, stacklevel=3)


def _chunk_result(jc, jm, D, C, reps: int, n_jobs: int,
                  block_jobs: int) -> SimResult:
    """Pad+mask epilogue + metric reductions, host-side and numpy-exact.

    Drops padded reps, gathers real jobs back into trace order, and
    reduces replications/jobs in one fixed order regardless of how (or
    whether) the compiled fan-out was device-sharded. Elementwise steps
    (compare, multiply) are IEEE-exact, so they match what the compiled
    epilogue produced historically; the reductions are the part that must
    live here.
    """
    jc = np.asarray(jc)
    jm = np.asarray(jm)
    gather = gather_index(n_jobs, block_jobs)
    jc = jc[:reps].reshape(reps, -1)[:, gather]
    jm = jm[:reps].reshape(reps, -1)[:, gather]
    met = jc <= np.asarray(D)[None, :]
    cost = jm * np.asarray(C)[None, :]
    if reps == 1:
        met_j, comp_j, cost_j = met[0], jc[0], cost[0]
    else:
        met_j = met.mean(axis=0, dtype=np.float32)
        comp_j = jc.mean(axis=0, dtype=np.float32)
        cost_j = cost.mean(axis=0, dtype=np.float32)
    return SimResult(
        pocd=jnp.float32(met_j.mean(dtype=np.float32)),
        job_met=jnp.asarray(met_j), job_completion=jnp.asarray(comp_j),
        job_cost=jnp.asarray(cost_j),
        mean_cost=jnp.float32(cost_j.mean(dtype=np.float32)))


# ---------------------------------------------------------------------------
# Per-strategy entry: solve -> blocks -> sharded MC -> streaming reduce
# ---------------------------------------------------------------------------


def run_fleet_strategy(key, jobs, strategy: str, p, *, mesh=None,
                       theta=1e-4, r_min=0.0, max_r: int = 8,
                       oracle: bool = True, reps: int = 1,
                       block_jobs: int = 64, chunk_jobs=None,
                       pad_to=None, chaos=None, checkpoint=None,
                       resume: bool = False, fused: bool = True,
                       backend: str = "auto", budget=None) -> RunOutput:
    """Fleet mirror of `sim.runner.run_strategy`.

    jobs: a JobSet or a WorkloadTrace (traces are chunked column-wise, so
        the full task axis is never materialized).
    mesh: a ("rep", "job") mesh from `fleet_mesh` (None = this process's
        single-device path — bit-identical to every mesh shape).
    chunk_jobs: stream the trace in job-contiguous chunks of at most this
        many jobs (rounded down to a block multiple; a chunk_jobs smaller
        than block_jobs shrinks the blocks — the memory bound wins, at
        the price of a different block decomposition and hence different
        draws than an unchunked run). None = one chunk.
    pad_to: (rep_mult, job_mult) padding override for the pad+mask
        property tests; only valid without a mesh.
    block_jobs: jobs per shardable block (the key-derivation granularity —
        changing it changes the draws, so keep it fixed when comparing).
    chaos: a `chaos.FaultPlan` or `chaos.ChaosContext` consulted at chunk
        boundaries (device loss -> mesh shrink + re-pad, injected chunk
        failures/corruption -> retry, crash -> SimulatedCrash after the
        checkpoint commits). None keeps the exact pre-chaos code path.
    checkpoint: a `chaos.CheckpointConfig` (or directory path) — save the
        resumable chunk state after each chunk; with `resume=True`, first
        restore the latest committed checkpoint and continue from it
        (bit-identical to an uninterrupted run; the stored fingerprint
        must match this call's configuration).
    fused: run solve -> block-gather -> replay as one device-resident
        jitted program per chunk (r*/choice never bounce to host between
        stages) — bit-identical to the staged path, which `fused=False`
        preserves verbatim (and which baselines, having no solve, always
        take).
    backend: Algorithm-1 grid-solve backend ("auto" | "xla" | "pallas";
        auto = the fused Pallas kernel on TPU, XLA reference elsewhere).
    budget: shared priced machine-time cap, sum(C * E[T]) <= budget, for
        the whole trace (repro.coupled). The multiplier is GLOBAL: one
        joint solve over every job's grids runs before the chunk loop and
        each chunk replays its slice of that one selection, so chunked
        runs match the monolithic solve bitwise. Incompatible with
        `chaos=` (mid-run re-pricing / mesh loss would invalidate the
        already-solved multiplier).
    """
    spec = get(strategy)
    if not spec.detectable:
        oracle = True
    if pad_to is not None and mesh is not None:
        raise ValueError("pad_to is a test-only override; incompatible "
                         "with an explicit mesh")
    if resume and checkpoint is None:
        raise ValueError("resume=True requires a checkpoint config")
    if budget is not None and not spec.optimized:
        budget = None     # baselines run at r = 0: nothing to budget
    if budget is not None and chaos is not None:
        raise ValueError(
            "budget= requires a chaos-free run: the shared multiplier is "
            "solved once over the whole trace, and chaos re-pricing or "
            "mesh loss mid-run would invalidate that global solve")
    cols = job_columns(jobs)
    J = int(cols[0].shape[0])
    B = max(1, min(int(block_jobs), J))
    if chunk_jobs is not None:
        # the chunk is the memory bound the caller asked for: blocks
        # shrink to honor it (chunk boundaries must land on block
        # boundaries or the global block indices — and hence the draws —
        # would shift between chunked and monolithic runs)
        B = min(B, max(1, int(chunk_jobs)))

    chunk = J if chunk_jobs is None else max(B, (int(chunk_jobs) // B) * B)
    n_chunks = -(-J // chunk)
    blocks_per_chunk = -(-chunk // B)
    # one global task width -> every chunk reuses one compiled program
    Tb = int(block_task_counts(cols[0], B).max())

    def layout_of(m):
        # mesh-dependent padding; re-derived when chaos shrinks the mesh
        # (the pad+mask re-fit over the surviving extents)
        r_ext, j_ext = pad_to if pad_to is not None else mesh_extents(m)
        return (j_ext, jnp.arange(pad_count(reps, r_ext), dtype=jnp.int32),
                pad_count(blocks_per_chunk, j_ext))

    job_ext, rep_ids, min_blocks = layout_of(mesh)

    ctx = saver = cfg = fp = None
    start_chunk = 0
    if chaos is not None:
        from ..chaos.inject import as_context
        ctx = as_context(chaos)
        ctx.bind(n_chunks, mesh, reps)
    if checkpoint is not None:
        from ..chaos import recovery
        cfg = recovery.as_checkpoint(checkpoint)
        saver = recovery.ChunkCheckpointer(cfg)
        fp = recovery.run_fingerprint(
            path="flat", strategy=strategy, n_jobs=J, block_jobs=B,
            chunk=chunk, reps=reps, max_r=max_r, oracle=oracle,
            theta=float(theta), r_min=float(r_min), key=np.asarray(key),
            plan=ctx.plan.fingerprint() if ctx is not None else "",
            budget=None if budget is None else float(budget))

    theta_f = jnp.float32(theta)
    r_min_f = jnp.float32(r_min)
    coupled_sel = info = None
    if budget is not None:
        # global-lambda pre-pass: one joint solve over the concatenated
        # per-chunk JobSpecs (jobspecs_of is elementwise in the job, so
        # chunk-then-concat is bitwise the monolithic spec batch). Each
        # chunk then replays its slice of this one selection — never a
        # per-chunk re-solve, which would give chunk-local multipliers.
        from ..coupled import solve_jobs_coupled_jit, warn_infeasible
        with obs_trace.span("fleet.coupled_solve", strategy=strategy,
                            n_jobs=J, n_chunks=n_chunks):
            parts = [jobspecs_of(chunk_jobset(cols, ci * chunk,
                                              min((ci + 1) * chunk, J)),
                                 p, theta_f, r_min_f)
                     for ci in range(n_chunks)]
            gspecs = jax.tree.map(lambda *xs: jnp.concatenate(xs), *parts)
            (g_r, g_ch, _, g_p, g_c, g_sat), info = solve_jobs_coupled_jit(
                strategy, gspecs, max_r + 1, jnp.float32(budget))
            coupled_sel = (np.asarray(g_r), np.asarray(g_ch),
                           np.asarray(g_p), np.asarray(g_c * gspecs.C),
                           np.asarray(g_sat))
        warn_infeasible(strategy, info)
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
                job_ext, rep_ids, min_blocks = layout_of(mesh)
                ctx.catch_up(start_chunk)

    try:
        for ci in range(start_chunk, n_chunks):
            if ctx is not None:
                new_mesh = ctx.begin_chunk(ci, mesh, reps)
                if new_mesh is not mesh:
                    mesh = new_mesh
                    job_ext, rep_ids, min_blocks = layout_of(mesh)
            lo, hi = ci * chunk, min((ci + 1) * chunk, J)
            cjobs = chunk_jobset(cols, lo, hi)
            Jc = cjobs.n_jobs
            specs = None
            if spec.optimized and coupled_sel is None:
                specs = jobspecs_of(cjobs, p, theta_f, r_min_f)
                scale = ctx.cost_scale(ci) if ctx is not None else 1.0
                if scale != 1.0:
                    # governor re-pricing under capacity loss: chunks
                    # not yet dispatched solve r* at the scaled cost
                    specs = specs._replace(C=specs.C * jnp.float32(scale))
            # baselines have no solve, so there is nothing to fuse: they
            # always take the (identical) staged path. A budgeted run is
            # staged too: its solve already happened globally above.
            use_fused = fused and spec.optimized and coupled_sel is None
            if not use_fused:
                with obs_trace.span("fleet.solve", strategy=strategy,
                                    chunk=ci, n_jobs=Jc):
                    if coupled_sel is not None:
                        r_j, choice_j, th_p, th_c, sat_j = (
                            a[lo:hi] for a in coupled_sel)
                    elif not spec.optimized:
                        r_j = jnp.zeros((Jc,), jnp.int32)
                        choice_j = jnp.zeros((Jc,), jnp.int32)
                        th_p = jnp.zeros((Jc,))
                        th_c = jnp.zeros((Jc,))
                        sat_j = jnp.zeros((Jc,), jnp.int32)
                    else:
                        r_j, choice_j, _, th_p, th_c, sat_j = \
                            solve_jobs_jit(strategy, specs, max_r + 1,
                                           backend=backend)
                        th_c = th_c * specs.C
            with obs_trace.span("fleet.blocks", chunk=ci, block_jobs=B):
                layout = block_layout(cjobs, B, pad_blocks_to=job_ext,
                                      tasks_pad=Tb, min_blocks=min_blocks)
                blocks = make_blocks(cjobs, B,
                                     block_offset=ci * blocks_per_chunk,
                                     layout=layout)
                jid = np.asarray(cjobs.job_id)
                if use_fused:
                    # pure layout geometry (no solve outputs): task ->
                    # chunk-job index, with padding slots pointing at Jc —
                    # the appended zero row in _fused_impl, i.e. exactly
                    # the fill value the staged stack writes
                    tj_b = stack_task_column(layout, jid, Jc, np.int32)
                else:
                    r_b = stack_task_column(layout, np.asarray(r_j)[jid],
                                            0, np.int32)
                    c_b = stack_task_column(layout,
                                            np.asarray(choice_j)[jid],
                                            0, np.int32)

            if use_fused:
                def exec_chunk(rep_ids=rep_ids, blocks=blocks,
                               specs=specs, tj_b=tj_b, mesh=mesh):
                    return obs_trace.fenced(
                        f"fleet.fused[{strategy}]", _fleet_fused,
                        key, rep_ids, blocks, specs, tj_b,
                        strategy=strategy, p=p, max_r=max_r,
                        oracle=oracle, mesh=mesh, backend=backend)

                jc, jm, r_j, th_p, th_c, sat_j = (
                    exec_chunk() if ctx is None
                    else ctx.execute(ci, exec_chunk))
            else:
                def exec_chunk(rep_ids=rep_ids, blocks=blocks, r_b=r_b,
                               c_b=c_b, mesh=mesh):
                    return obs_trace.fenced(
                        f"fleet.exec[{strategy}]", _fleet_core,
                        key, rep_ids, blocks, r_b, c_b,
                        strategy=strategy, p=p, max_r=max_r,
                        oracle=oracle, mesh=mesh)

                jc, jm = exec_chunk() if ctx is None else ctx.execute(
                    ci, exec_chunk)
            with obs_trace.span("fleet.reduce", chunk=ci, n_jobs=Jc):
                res = _chunk_result(jc, jm, cjobs.D, cjobs.C, reps, Jc, B)
                acc.add(res, n_jobs=Jc)
            r_parts.append(np.asarray(r_j))
            thp_parts.append(np.asarray(th_p))
            thc_parts.append(np.asarray(th_c))
            if spec.optimized:
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
                        # a simulated crash must not outrun its own
                        # commit — the resume contract requires the
                        # chunk it died after to be on disk
                        saver.wait()
            if ctx is not None:
                ctx.maybe_crash(ci)
    finally:
        if saver is not None:
            saver.wait()

    if n_sat:
        _warn_saturated(strategy, n_sat, max_r)
    result = acc.finalize()
    return RunOutput(
        result=result,
        r_opt=jnp.asarray(np.concatenate(r_parts)),
        utility=net_utility(result.pocd, result.mean_cost, r_min, theta),
        theory_pocd=jnp.asarray(np.concatenate(thp_parts)),
        theory_cost=jnp.asarray(np.concatenate(thc_parts)),
        n_saturated=jnp.int32(n_sat), coupled=info)


def run_all_fleet(key, jobs, p, theta=1e-4, strategies=None,
                  r_min_from_ns: bool = True, max_r: int = 8,
                  reps: int = 1, mesh=None, block_jobs: int = 64,
                  chunk_jobs=None, pad_to=None, chaos=None,
                  checkpoint=None, resume: bool = False,
                  fused: bool = True, backend: str = "auto", budget=None):
    """Fleet mirror of `sim.runner.run_all` (same r_min-from-NS protocol).

    `jobs` may be a JobSet, a WorkloadTrace, or a workload-registry
    scenario name (resolved to its trace; a Scenario's declared fault
    schedule becomes the default `chaos` plan when none is passed).
    chaos: a `chaos.FaultPlan` applied to EVERY strategy's run — each
        strategy gets a fresh `ChaosContext` (injection budgets are
        stateful) over the same plan, so all strategies see the same
        failure sequence.
    checkpoint: a `chaos.CheckpointConfig` or directory; each strategy
        checkpoints under its own subdirectory.
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
    kw = dict(mesh=mesh, theta=theta, max_r=max_r, reps=reps,
              block_jobs=block_jobs, chunk_jobs=chunk_jobs, pad_to=pad_to,
              fused=fused, backend=backend, budget=budget)

    def kw_of(name):
        per = dict(kw)
        if chaos is not None:
            from ..chaos.inject import ChaosContext
            from ..chaos.plan import FaultPlan
            if not isinstance(chaos, FaultPlan):
                raise TypeError("run_all_fleet takes a FaultPlan (each "
                                "strategy needs its own ChaosContext)")
            per["chaos"] = ChaosContext(chaos)
        if checkpoint is not None:
            from ..chaos.recovery import as_checkpoint
            per["checkpoint"] = as_checkpoint(checkpoint).sub(name)
            per["resume"] = resume
        return per

    outs = {}
    r_min = 0.0
    if "hadoop_ns" in strategies:
        outs["hadoop_ns"] = run_fleet_strategy(
            key_of["hadoop_ns"], jobs, "hadoop_ns", p, r_min=0.0,
            **kw_of("hadoop_ns"))
        if r_min_from_ns:
            r_min = float(outs["hadoop_ns"].result.pocd) - 1e-3
    for name in strategies:
        if name == "hadoop_ns":
            continue
        outs[name] = run_fleet_strategy(key_of[name], jobs, name, p,
                                        r_min=r_min, **kw_of(name))
    return outs, r_min
