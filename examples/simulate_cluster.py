"""Trace-driven cluster simulation — the paper's Section VII.B at full scale.

Simulates a 30-hour Google-trace-like workload (2700 jobs, ~1M tasks),
optimizing r* per job with Algorithm 1 and executing every registered
strategy: Hadoop-NS, Hadoop-S, Mantri, hedge (baselines) and Clone /
S-Restart / S-Resume / adaptive (Chronos IR). Prints the Fig-2/3-style
comparison. All execution routes through the unified facade
(`repro.simulate` + `RunConfig`), which picks the flat, finite-capacity,
or fleet backend from the config.

By default capacity is infinite (the paper's analytic regime). With
`--slots N` the same draws replay through the finite-capacity cluster
engine (repro.cluster): attempts queue on N machine slots under FIFO or
EDF dispatch, and the table gains utilization / queue-wait columns.

With `--scenario NAME` the trace comes from the workload registry
(`repro.workloads`): heterogeneous job classes, arrival processes, and
per-class SLA weights, with a per-class result breakdown.

`--strategies` selects a comma-separated subset of
`repro.strategies.names()` (default: all registered strategies).

With `--budget B` the per-job Algorithm-1 solves couple through one
shared machine-time budget (`repro.coupled`): total priced spend
sum(C * E[T]) is capped at B via a single Lagrange multiplier, the
competitive-cloning baselines (clone_prop / clone_sjf) allocate the
same budget with their own rules, and a per-strategy spend/lambda
table prints after the results.

With `--devices N` and/or `--chunk-jobs M` execution routes through the
device-sharded fleet layer (`repro.fleet`): MC replications and job
blocks shard over a ("rep", "job") mesh and the trace streams in
bounded-memory chunks. On a CPU-only host, `--devices N` forces N XLA
host devices (the flag is applied before JAX is imported), so the
shard_map path is exercisable anywhere — results are bit-identical to
the fleet single-device path by construction.

With `--trace` the run executes under the span tracer (`repro.obs`) and
prints a per-stage wall-clock breakdown (synthesis, solve, replay
dispatch vs device execute); `--trace-out PATH` additionally writes the
timeline as Chrome-trace JSON, openable in https://ui.perfetto.dev or
chrome://tracing.

With `--chaos` the run executes under fault injection (`repro.chaos`):
the scenario's declared fault schedule (e.g. `pod-loss-flash-crowd`
loses 2 devices at chunk 2 and 2 more at chunk 5) — or, for scenarios
without one, a seeded generated plan — fires at chunk boundaries, with
retry/backoff on injected failures, mesh shrink + re-pad on device loss,
and an audit report printed after the run. `--chaos` implies the fleet
path (chunk_jobs defaults to jobs/8 when unset). `--ckpt-dir DIR`
checkpoints the resumable chunk state after every chunk
(atomic + async, bounded retention); after a crash — simulated or real —
re-running with `--resume` restores the latest committed checkpoint and
finishes the run bit-identically to an uninterrupted one.

Run:  PYTHONPATH=src python examples/simulate_cluster.py [--jobs 2700]
      PYTHONPATH=src python examples/simulate_cluster.py --jobs 200 --slots 2000
      PYTHONPATH=src python examples/simulate_cluster.py \
          --scenario diurnal-burst --jobs 50 --slots 500 \
          --strategies hadoop_ns,sresume,hedge,adaptive
      PYTHONPATH=src python examples/simulate_cluster.py \
          --jobs 20000 --devices 8 --chunk-jobs 4096 --reps 4
      PYTHONPATH=src python examples/simulate_cluster.py \
          --jobs 100 --slots 500 --trace --trace-out trace.json
      PYTHONPATH=src python examples/simulate_cluster.py \
          --scenario pod-loss-flash-crowd --jobs 400 --devices 8 \
          --chunk-jobs 64 --chaos --ckpt-dir /tmp/chaos_ckpt
      PYTHONPATH=src python examples/simulate_cluster.py \
          --scenario pod-loss-flash-crowd --jobs 400 --devices 8 \
          --chunk-jobs 64 --chaos --ckpt-dir /tmp/chaos_ckpt --resume
"""
import argparse
import os

ap = argparse.ArgumentParser()
ap.add_argument("--jobs", type=int, default=2700)
ap.add_argument("--scenario", default=None,
                help="workload-registry scenario (default: the legacy "
                     "single-mix Google-trace generator)")
ap.add_argument("--seed", type=int, default=0)
ap.add_argument("--theta", type=float, default=1e-4)
ap.add_argument("--slots", type=int, default=0,
                help="machine slots (0 = infinite capacity, the default)")
ap.add_argument("--discipline", choices=("fifo", "edf"), default="fifo")
ap.add_argument("--passes", type=int, default=2,
                help="relaxation passes of the capacity replay (min 2: "
                     "pass 1 schedules primaries only)")
ap.add_argument("--governor", action="store_true",
                help="enable the load-adaptive r* governor")
ap.add_argument("--admission-slack", type=float, default=0.0,
                help="> 0 enables deadline-aware admission control")
ap.add_argument("--budget", type=float, default=0.0,
                help="> 0 caps total priced machine time sum(C*E[T]) and "
                     "routes the Algorithm-1 solve through the "
                     "cluster-wide joint optimizer (repro.coupled); a "
                     "slack budget reproduces the independent solve "
                     "bitwise")
ap.add_argument("--strategies", default=None,
                help="comma-separated subset of repro.strategies.names() "
                     "(default: all registered strategies)")
ap.add_argument("--devices", type=int, default=0,
                help="> 0 routes through the fleet layer on N devices "
                     "(forcing N XLA host devices on CPU)")
ap.add_argument("--chunk-jobs", type=int, default=0,
                help="> 0 streams the trace in chunks of at most M jobs "
                     "(bounded memory; implies the fleet layer)")
ap.add_argument("--block-jobs", type=int, default=64,
                help="fleet job-block granularity (PRNG/sharding unit)")
ap.add_argument("--reps", type=int, default=1,
                help="Monte-Carlo replications (fleet: sharded over the "
                     "mesh's rep axis)")
ap.add_argument("--chaos", action="store_true",
                help="inject the scenario's fault schedule (or a seeded "
                     "generated plan) at chunk boundaries; implies the "
                     "fleet path and prints a chaos report")
ap.add_argument("--ckpt-dir", default=None, metavar="DIR",
                help="checkpoint resumable chunk state under DIR (atomic "
                     "+ async, bounded retention)")
ap.add_argument("--resume", action="store_true",
                help="resume from the latest committed checkpoint in "
                     "--ckpt-dir (bit-identical to an uninterrupted run)")
ap.add_argument("--trace", action="store_true",
                help="enable span tracing (repro.obs): prints a per-stage "
                     "wall-clock breakdown after the run")
ap.add_argument("--trace-out", default=None, metavar="PATH",
                help="write the span timeline as Chrome-trace JSON "
                     "(open in Perfetto / chrome://tracing; implies "
                     "--trace)")
args = ap.parse_args()

_flags = os.environ.get("XLA_FLAGS", "")
if args.devices > 0 and "xla_force_host_platform_device_count" not in _flags:
    # must happen before jax is imported anywhere in this process; skipped
    # when the caller (e.g. the multi-device CI lane) already forced it
    os.environ["XLA_FLAGS"] = (_flags +
                               " --xla_force_host_platform_device_count="
                               f"{args.devices}")

import jax
import jax.numpy as jnp

from repro import RunConfig, compile_cache, simulate
from repro.sim import generate, SimParams
from repro.sim.metrics import class_summary
from repro.strategies import names
from repro.workloads import list_scenarios, make_trace, summarize, to_jobset

compile_cache.enable()

if args.trace or args.trace_out:
    from repro.obs import trace as obs_trace
    obs_trace.enable()

if args.scenario and args.scenario not in list_scenarios():
    ap.error(f"unknown scenario {args.scenario!r}; registered: "
             + ", ".join(sorted(list_scenarios())))

if args.strategies:
    ORDER = tuple(s.strip() for s in args.strategies.split(",") if s.strip())
    unknown = sorted(set(ORDER) - set(names()))
    if unknown:
        ap.error(f"unknown strategies {', '.join(unknown)}; "
                 f"registered: {', '.join(names())}")
else:
    ORDER = names()

if args.resume and not args.ckpt_dir:
    ap.error("--resume requires --ckpt-dir")

use_fleet = (args.devices > 0 or args.chunk_jobs > 0 or args.chaos
             or bool(args.ckpt_dir))
if args.scenario:
    trace = make_trace(args.scenario, n_jobs=args.jobs, seed=args.seed)
    # the fleet layer consumes the columnar trace directly and streams it
    # chunk-by-chunk — the flat task axis of a million-job trace is never
    # materialized; the legacy single-device paths need the full JobSet
    jobs = trace if use_fleet else to_jobset(trace)
    stats = summarize(trace)
    mix = ", ".join(f"{k} {v:.0%}" for k, v in stats["class_mix"].items())
    print(f"scenario {args.scenario}: {jobs.n_jobs} jobs, "
          f"{jobs.total_tasks} tasks over {stats['hours']:.1f} h ({mix})")
else:
    trace = None
    jobs = generate(n_jobs=args.jobs, seed=args.seed)
print(f"trace: {jobs.n_jobs} jobs, {jobs.total_tasks} tasks, "
      f"beta in [{float(jobs.beta.min()):.2f}, {float(jobs.beta.max()):.2f}]")

devices = args.devices if args.devices > 0 else None
chunk_jobs = args.chunk_jobs if args.chunk_jobs > 0 else None
if use_fleet and chunk_jobs is None and (args.chaos or args.ckpt_dir):
    # chaos/checkpointing act at chunk boundaries — default to ~8 chunks
    chunk_jobs = max(1, args.jobs // 8)
if devices:
    print(f"fleet: {len(jax.devices())} devices"
          + (f", chunks of {chunk_jobs} jobs" if chunk_jobs else ""))

chaos_plan = None
if args.chaos:
    from repro.chaos import from_faults, generate as generate_faults
    from repro.workloads import get_scenario
    faults = (getattr(get_scenario(args.scenario), "faults", None)
              if args.scenario else None)
    if faults:
        chaos_plan = from_faults(faults, seed=args.seed)
        print(f"chaos: scenario fault schedule "
              f"[{chaos_plan.fingerprint()}]")
    else:
        n_chunks = -(-args.jobs // (chunk_jobs or args.jobs))
        chaos_plan = generate_faults(
            seed=args.seed, n_chunks=n_chunks, p_device_loss=0.1,
            p_chunk_fail=0.15, p_corrupt=0.1)
        print(f"chaos: generated plan [{chaos_plan.fingerprint()}]")
ckpt_cfg = args.ckpt_dir

def _run_or_crash(fn, *a, **kw):
    """Run; on a simulated (plan-scheduled) crash, tell the user how to
    finish the run instead of dumping a traceback."""
    from repro.chaos import SimulatedCrash
    try:
        return fn(*a, **kw)
    except SimulatedCrash as e:
        raise SystemExit(
            f"chaos: simulated crash after chunk {e.chunk} (checkpoint "
            f"committed to {args.ckpt_dir}) — re-run with --resume to "
            f"finish the run bit-identically")


if args.slots > 0:
    from repro.cluster import GovernorConfig, AdmissionConfig
    governor = GovernorConfig() if args.governor else None
    admission = (AdmissionConfig(slack=args.admission_slack)
                 if args.admission_slack > 0 else None)
    # one facade call: the slots/governor/admission knobs route this
    # config to the finite-capacity engine (repro.api)
    cfg = RunConfig(
        theta=args.theta, strategies=ORDER, reps=args.reps,
        slots=args.slots, discipline=args.discipline, passes=args.passes,
        governor=governor, admission=admission,
        devices=devices, chunk_jobs=chunk_jobs,
        chaos=chaos_plan, checkpoint=ckpt_cfg, resume=args.resume,
        budget=args.budget if args.budget > 0 else None)
    outs, r_min = _run_or_crash(
        simulate, jax.random.PRNGKey(0), jobs, SimParams(), cfg=cfg)
    print(f"capacity: {args.slots} slots, {args.discipline} dispatch"
          + (", governor on" if governor else "")
          + (f", admission slack {args.admission_slack}" if admission else ""))
    print(f"\n{'strategy':12s} {'PoCD':>8s} {'cost':>10s} {'utility':>9s} "
          f"{'mean r*':>8s} {'util':>6s} {'wait':>8s}")
    for name in ORDER:
        o = outs[name]
        print(f"{name:12s} {float(o.result.pocd):8.3f} "
              f"{float(o.result.mean_cost):10.0f} {float(o.utility):9.3f} "
              f"{float(jnp.mean(o.r_opt)):8.2f} "
              f"{float(o.queue.utilization):6.3f} "
              f"{float(o.queue.mean_wait):8.2f}")
else:
    cfg = RunConfig(
        theta=args.theta, strategies=ORDER, reps=args.reps,
        devices=devices, block_jobs=args.block_jobs,
        chunk_jobs=chunk_jobs, chaos=chaos_plan, checkpoint=ckpt_cfg,
        resume=args.resume,
        budget=args.budget if args.budget > 0 else None)
    outs, r_min = _run_or_crash(
        simulate, jax.random.PRNGKey(0), jobs, SimParams(), cfg=cfg)
    print(f"\n{'strategy':12s} {'PoCD':>8s} {'cost':>10s} {'utility':>9s} "
          f"{'mean r*':>8s}")
    for name in ORDER:
        o = outs[name]
        print(f"{name:12s} {float(o.result.pocd):8.3f} "
              f"{float(o.result.mean_cost):10.0f} {float(o.utility):9.3f} "
              f"{float(jnp.mean(o.r_opt)):8.2f}")

if args.budget > 0:
    print(f"\nbudget {args.budget:.6g} (priced machine time):")
    for name in ORDER:
        c = getattr(outs[name], "coupled", None)
        if c is None:     # baselines run at r = 0 — nothing budgeted
            continue
        tag = ("slack" if not bool(c.binding)
               else ("binding" if bool(c.feasible) else "INFEASIBLE"))
        print(f"  {name:12s} spend {float(c.spend):12.0f}  "
              f"unconstrained {float(c.spend_free):12.0f}  "
              f"lambda {float(c.lam):9.4g}  {tag}")

n_sat = sum(int(getattr(o, "n_saturated", 0)) for o in outs.values())
if n_sat:
    print(f"\nnote: r* saturated at the grid edge for {n_sat} "
          f"job-solve(s) across strategies — consider raising max_r")

# headline strategy: the paper's sresume when run, else the best utility
best_name = ("sresume" if "sresume" in outs
             else max(outs, key=lambda s: float(outs[s].utility)))
best = outs[best_name]

if trace is not None:
    per_cls = class_summary(jobs, best.result)
    print(f"\n{best_name} by class ({args.scenario}):")
    for cid, row in per_cls.items():
        name = trace.class_names[cid]
        print(f"  {name:12s} jobs {row['n_jobs']:4d}  "
              f"PoCD {row['pocd']:.3f}  mean cost {row['mean_cost']:.0f}")

if "hadoop_ns" in outs and best_name != "hadoop_ns":
    ns = outs["hadoop_ns"]
    print(f"\nBest ({best_name}) vs Hadoop-NS: PoCD +"
          f"{(float(best.result.pocd) - float(ns.result.pocd)) * 100:.0f} pts")
if "mantri" in outs and best_name != "mantri":
    mantri = outs["mantri"]
    print(f"Best ({best_name}) vs Mantri:    cost "
          f"{(1 - float(best.result.mean_cost) / float(mantri.result.mean_cost)) * 100:.0f}% lower, "
          f"utility +{float(best.utility) - float(mantri.utility):.2f}")

if args.trace or args.trace_out:
    from repro.obs import export as obs_export
    tracer = obs_trace.get_tracer()
    print()
    print(obs_export.summary(tracer))
    if args.trace_out:
        obs_export.write_chrome_trace(args.trace_out, tracer)
        print(f"chrome trace written to {args.trace_out} "
              f"(open in https://ui.perfetto.dev)")
