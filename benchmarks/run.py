"""Benchmark harness — one entry per paper table/figure plus framework
throughput. Prints ``name,us_per_call,derived`` CSV (derived = the headline
metric for that artifact; see each docstring).

Also maintains ``BENCH_perf.json`` at the repo root. Each tracked entry
carries its provenance explicitly:

  {"entries": {"<bench>": {
      "us_per_call": ..., "derived": ...,        # latest full-size run
      "baseline": {"commit", "label", "us_per_call", "derived"},
      "smoke":    {"commit", "us_per_call", "derived"}}}}

``baseline`` is the recorded pre-optimization reference (never rewritten);
``smoke`` is the reduced-size CI reference, refreshed with
``--smoke --record-smoke``. ``--smoke`` runs only the perf benches at
reduced sizes and does not rewrite the tracked JSON; with
``--check BENCH_perf.json --tolerance 0.25`` it exits non-zero when any
tracked ``us_per_call`` regresses beyond tolerance — the CI
benchmark-regression gate. ``--out PATH`` writes the fresh results as JSON
(uploaded as a CI artifact).

``--only a,b`` restricts the perf benches to the named subset — with
``--smoke --record-smoke`` this re-records just those smoke references
(the recalibration path for benches whose reference drifted on the CI
host) without touching any other entry.

``--trace`` runs each perf bench under the span tracer (``repro.obs``)
and records its per-stage wall-clock breakdown (``stages``) into the
entry's provenance — so BENCH_perf.json answers not just "how fast" but
"which stage". Fencing changes dispatch overlap, so ``--trace`` numbers
are not gate-comparable; it is refused together with ``--check``.
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent

# Recorded pre-optimization references (never rewritten). The PR-1
# entries were measured at commit 1eb85f8 on the CI container (CPU, 2
# cores, interpret-mode kernels) BEFORE the compiled replay / jitted
# runner / fused kernel landed:
#   trace_sim_full     — reps=8 via 8 sequential re-traced run_strategy calls
#                        (2700 jobs; derived = task-executions/sec)
#   cluster_replay     — 8 sequential host-orchestrated run_cluster_strategy
#                        calls (sresume, 300 jobs, 2000 slots; derived =
#                        dispatched attempt-units/sec)
#   kernel_pocd_mc     — single-mode launch, J=1024 N=32 R=6 (samples/sec)
#   kernel_pocd_mc_all — 3-mode sweep via 3 separate pocd_mc launches
# Entries may override the default commit/label:
#   optimizer_batch_solve — its pre-provenance headline (recorded before
#                        entries carried commit stamps), frozen here as
#                        the migration baseline
#   solve_fused        — the staged `solve_batch_jit` pipeline at the
#                        fused bench's own size (10^5 jobs, r_max=64),
#                        the pipeline the fused grid solve replaces
#   fleet_fused        — the staged fleet_chunked pipeline at the fused
#                        bench's own sizes (per-chunk solve dispatch +
#                        r*/choice host round-trip)
BASELINE_COMMIT = "1eb85f8"
BASELINE_LABEL = "PR 1, pre-optimization"
BASELINE = {
    "trace_sim_full": {"us_per_call": 8150181.7, "derived": 895390.1},
    "cluster_replay": {"us_per_call": 13415000.0, "derived": 74703.0},
    "kernel_pocd_mc": {"us_per_call": 6871.1, "derived": 28613714.7},
    "kernel_pocd_mc_all": {"us_per_call": 14406.5, "derived": 40941419.0},
    "optimizer_batch_solve": {
        "us_per_call": 77731.4, "derived": 1286480.7,
        "commit": "pre-provenance",
        "label": "headline recorded before commit stamping (r_max=32)"},
    "solve_fused": {
        "us_per_call": 206140.6, "derived": 485105.7,
        "commit": "91ca71b",
        "label": "staged solve_batch_jit, 10^5 jobs x r_max=64 "
                 "(CPU host, XLA; the >= 2x fused target is the TPU "
                 "bench platform)"},
    "fleet_fused": {
        "us_per_call": 335177.1, "derived": 5967.0,
        "commit": "91ca71b",
        "label": "staged fleet_chunked pipeline, same sizes (per-chunk "
                 "solve dispatch + host round-trip; CPU host)"},
}


def _run(name, fn):
    t0 = time.perf_counter()
    out = fn()
    dt = time.perf_counter() - t0
    if isinstance(out, tuple) and len(out) == 2 and isinstance(out[0], list):
        rows, headline = out
    else:
        rows, headline = None, out
    return {"name": name, "us_per_call": dt * 1e6, "derived": headline,
            "rows": rows}


def perf_benches(perf, smoke: bool):
    """(name, fn) pairs; smoke mode shrinks sizes so CI stays fast while
    still exercising every dispatch path (jit replay, reps vmap, fused
    kernel, workload-scenario generation)."""
    if smoke:
        return [
            ("trace_sim_full",
             lambda: perf.bench_sim_throughput(n_jobs=150, reps=2)),
            ("cluster_replay",
             lambda: perf.bench_cluster_replay(n_jobs=60, slots=200,
                                               reps=2, iters=1)),
            # sub-millisecond benches: more timed iters so the gate
            # compares means, not single-observation noise
            ("kernel_pocd_mc",
             lambda: perf.bench_pocd_kernel(J=200, N=8, R=4, iters=10)),
            ("kernel_pocd_mc_all",
             lambda: perf.bench_pocd_kernel_all(J=200, N=8, R=4, iters=10)),
            ("workload_synthesize",
             lambda: perf.bench_workload_synthesize(n_jobs=400)),
            # fused solve -> replay pipeline: the batched Algorithm-1
            # grid solve in one dispatch, and the device-resident fleet
            # chunk program it feeds (fleet_chunked above stays pinned
            # to the staged pipeline as the comparison reference)
            ("optimizer_batch_solve",
             lambda: perf.bench_optimizer_throughput(n_jobs=5000)),
            ("solve_fused",
             lambda: perf.bench_solve_fused(n_jobs=5000, r_max=32,
                                            iters=5)),
            # cluster-wide joint solve: the Lagrangian dual over the same
            # grids at a binding budget (repro.coupled)
            ("joint_solve",
             lambda: perf.bench_joint_solve(n_jobs=5000, r_max=32,
                                            iters=5)),
            ("fleet_fused",
             lambda: perf.bench_fleet_fused(n_jobs=300, chunk_jobs=96,
                                            block_jobs=32, iters=4)),
            # strategy-IR layer: full-registry dispatch sweep + the two
            # registry-defined strategies added with the IR
            ("strategy_dispatch",
             lambda: perf.bench_strategy_dispatch(n_jobs=40, iters=2)),
            ("strategy_hedge",
             lambda: perf.bench_new_strategy("hedge", n_jobs=100, reps=2,
                                             iters=3)),
            ("strategy_adaptive",
             lambda: perf.bench_new_strategy("adaptive", n_jobs=100, reps=2,
                                             iters=3)),
            # fleet layer: sharded runner (all visible devices) + chunked
            # trace streamer, so the gate guards shard_map dispatch and
            # the per-chunk recompile-free streaming path
            ("fleet_sharded",
             lambda: perf.bench_fleet_sharded(n_jobs=150, reps=2,
                                              block_jobs=32, iters=2)),
            ("fleet_chunked",
             lambda: perf.bench_fleet_chunked(n_jobs=300, chunk_jobs=96,
                                              block_jobs=32, iters=4)),
            # chaos layer: the same chunked streamer under fault injection
            # (injected failure + corruption, both retried) with
            # chunk-boundary checkpoints, so the gate guards the recovery
            # path's overhead
            ("fleet_chaos",
             lambda: perf.bench_fleet_chaos(n_jobs=300, chunk_jobs=96,
                                            block_jobs=32, iters=4)),
            # serving layer: the online hedged loop (windowed spec.draw,
            # epoch solves, governor refits) on a reduced stream
            ("serve_throughput",
             lambda: perf.bench_serve_throughput(
                 n_requests=2048, window=256, refit_every=512,
                 probe_every=16, iters=2)),
        ]
    return [
        ("optimizer_batch_solve", perf.bench_optimizer_throughput),
        ("solve_fused", perf.bench_solve_fused),
        ("joint_solve", perf.bench_joint_solve),
        ("fleet_fused", perf.bench_fleet_fused),
        ("trace_sim_full", perf.bench_sim_throughput),
        ("cluster_replay", perf.bench_cluster_replay),
        ("kernel_pocd_mc", perf.bench_pocd_kernel),
        ("kernel_pocd_mc_all", perf.bench_pocd_kernel_all),
        ("kernel_flash_attention", perf.bench_flash_attention),
        ("workload_synthesize", perf.bench_workload_synthesize),
        ("strategy_dispatch", perf.bench_strategy_dispatch),
        ("strategy_hedge",
         lambda: perf.bench_new_strategy("hedge")),
        ("strategy_adaptive",
         lambda: perf.bench_new_strategy("adaptive")),
        ("fleet_sharded", perf.bench_fleet_sharded),
        ("fleet_chunked", perf.bench_fleet_chunked),
        ("fleet_chaos", perf.bench_fleet_chaos),
        ("serve_throughput", perf.bench_serve_throughput),
    ]


def _git_head() -> str:
    """Short HEAD hash, with a -dirty marker so recorded provenance never
    points at a commit that cannot reproduce the measured code."""
    try:
        head = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=REPO_ROOT,
            capture_output=True, text=True, check=True).stdout.strip()
        dirty = subprocess.run(
            ["git", "status", "--porcelain"], cwd=REPO_ROOT,
            capture_output=True, text=True, check=True).stdout.strip()
        return f"{head}-dirty" if dirty else head
    except Exception:
        return "unknown"


def load_tracker(path: Path) -> dict:
    if path.exists():
        return json.loads(path.read_text())
    return {"entries": {}}


def write_perf_tracker(perf_results, record_smoke: bool = False,
                       smoke: bool = False) -> None:
    """Refresh BENCH_perf.json, preserving recorded provenance.

    Full runs rewrite the headline us_per_call/derived next to the frozen
    baseline; ``record_smoke`` (with --smoke) rewrites only the per-entry
    smoke reference the CI gate compares against.
    """
    path = REPO_ROOT / "BENCH_perf.json"
    tracker = load_tracker(path)
    entries = tracker.setdefault("entries", {})
    head = _git_head()
    for r in perf_results:
        entry = entries.setdefault(r["name"], {})
        if smoke:
            if record_smoke:
                entry["smoke"] = {"commit": head,
                                  "us_per_call": r["us_per_call"],
                                  "derived": r["derived"]}
            continue
        entry["us_per_call"] = r["us_per_call"]
        entry["derived"] = r["derived"]
        entry["commit"] = head
        if r.get("stages"):
            # per-stage wall-clock attribution from a --trace run
            entry["stages"] = r["stages"]
        base = BASELINE.get(r["name"])
        if base is not None:
            base = dict(base)
            commit = base.pop("commit", BASELINE_COMMIT)
            label = base.pop("label", BASELINE_LABEL)
            entry["baseline"] = {"commit": commit, "label": label, **base}
            entry["speedup_vs_baseline"] = round(
                base["us_per_call"] / max(r["us_per_call"], 1e-9), 2)
    path.write_text(json.dumps(tracker, indent=1, sort_keys=True) + "\n")


def check_regressions(perf_results, tracker: dict, tolerance: float,
                      smoke: bool) -> list:
    """Compare fresh us_per_call against the tracked reference of the same
    size class (smoke entries for --smoke runs, headline otherwise).
    Returns a list of human-readable failure lines."""
    failures = []
    for r in perf_results:
        entry = tracker.get("entries", {}).get(r["name"], {})
        ref = entry.get("smoke") if smoke else entry
        if not ref or "us_per_call" not in ref:
            # a bench without a reference is a coverage hole, not a pass:
            # record one in the same change that adds/renames the bench
            record_how = ("--smoke --record-smoke" if smoke
                          else "a full benchmark run")
            failures.append(
                f"{r['name']}: no recorded "
                f"{'smoke ' if smoke else ''}reference — record one with "
                f"{record_how} and commit the refreshed tracker")
            continue
        limit = ref["us_per_call"] * (1.0 + tolerance)
        ratio = r["us_per_call"] / ref["us_per_call"]
        provenance = ref.get("commit", "unrecorded commit")
        if r["us_per_call"] > limit:
            failures.append(
                f"{r['name']}: {r['us_per_call']:.1f} us/call is "
                f"{ratio:.2f}x the reference "
                f"{ref['us_per_call']:.1f} us/call "
                f"(recorded at {provenance}; tolerance {tolerance:.0%})")
        else:
            print(f"check: {r['name']}: {ratio:.2f}x reference "
                  f"(recorded at {provenance}) — ok")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="perf benches only, reduced sizes, no JSON rewrite")
    ap.add_argument("--check", metavar="TRACKER_JSON", default=None,
                    help="compare against the tracked references in this "
                         "file and exit non-zero on regression")
    ap.add_argument("--tolerance", type=float, default=0.25,
                    help="allowed fractional us_per_call slowdown before "
                         "--check fails (default 0.25)")
    ap.add_argument("--out", metavar="PATH", default=None,
                    help="write the fresh results as JSON (CI artifact)")
    ap.add_argument("--record-smoke", action="store_true",
                    help="with --smoke: record this run as the smoke "
                         "reference in BENCH_perf.json")
    ap.add_argument("--retries", type=int, default=2,
                    help="re-measure benches that fail --check up to this "
                         "many times, keeping the best time (default 2)")
    ap.add_argument("--devices", type=int, default=0,
                    help="> 0 forces N XLA host devices (CPU) so the "
                         "fleet benches exercise a real multi-device "
                         "mesh; applied before JAX is imported")
    ap.add_argument("--only", metavar="NAMES", default=None,
                    help="comma-separated subset of perf benches to run "
                         "(with --smoke --record-smoke: recalibrate just "
                         "those smoke references)")
    ap.add_argument("--trace", action="store_true",
                    help="run each perf bench under the repro.obs span "
                         "tracer and record its per-stage breakdown into "
                         "the entry provenance (incompatible with --check: "
                         "fencing changes dispatch overlap)")
    args = ap.parse_args()

    if args.trace and args.check:
        sys.exit("--trace adds block_until_ready fences, so its timings "
                 "are not comparable to untraced references; run the gate "
                 "and the traced breakdown as separate invocations")

    if args.devices > 0:
        import os
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}")

    # snapshot the reference BEFORE any tracker rewrite below, or a full
    # run's --check would compare the fresh numbers against themselves
    reference = None
    if args.check:
        check_path = Path(args.check)
        if not check_path.exists():
            sys.exit(f"--check reference {check_path} not found "
                     f"(a missing file must not pass the gate vacuously)")
        reference = load_tracker(check_path)

    from repro import compile_cache
    compile_cache.enable()

    from . import perf

    results = []
    if not args.smoke:
        from . import paper_figures as pf
        results.append(_run("fig2_strategies_utility_gain", pf.fig2_strategies))
        results.append(_run("table1_tau_est_best_utility", pf.table1_tau_est))
        results.append(_run("table2_tau_kill_best_utility", pf.table2_tau_kill))
        results.append(_run("fig3_theta_utility_vs_mantri", pf.fig3_theta))
        results.append(_run("fig4_beta_mean_pocd", pf.fig4_beta))
        results.append(_run("fig5_rhist_mode_shift", pf.fig5_r_histogram))

    # --- framework perf (us_per_call = one solver/sim/kernel invocation) ---
    selected = perf_benches(perf, args.smoke)
    if args.only:
        only = {s.strip() for s in args.only.split(",") if s.strip()}
        known = {n for n, _ in selected}
        unknown = sorted(only - known)
        if unknown:
            sys.exit(f"--only names not in this run's bench set: "
                     f"{', '.join(unknown)} (available: "
                     f"{', '.join(sorted(known))})")
        selected = [(n, fn) for n, fn in selected if n in only]
    perf_results = []
    for name, fn in selected:
        if args.trace:
            from repro.obs import trace as obs_trace
            from repro.obs.export import stage_breakdown
            obs_trace.enable(fresh=True)
        dt, rate = fn()
        row = {"name": name, "us_per_call": dt * 1e6,
               "derived": rate, "rows": None}
        if args.trace:
            obs_trace.disable()
            row["stages"] = stage_breakdown()
        perf_results.append(row)
    results.extend(perf_results)

    failures = []
    if args.check:
        failures = check_regressions(perf_results, reference, args.tolerance,
                                     args.smoke)
        for _ in range(args.retries):
            if not failures:
                break
            # transient noise (GC pause, neighbor load) looks like a
            # regression on a single observation: re-measure the failing
            # benches and keep the best time seen before ruling
            failing = {line.split(":", 1)[0] for line in failures}
            print(f"check: re-measuring after transient failure: "
                  f"{sorted(failing)}")
            by_name = dict(perf_benches(perf, args.smoke))
            for r in perf_results:
                if r["name"] in failing:
                    dt, rate = by_name[r["name"]]()
                    if dt * 1e6 < r["us_per_call"]:
                        r["us_per_call"] = dt * 1e6
                        r["derived"] = rate
            failures = check_regressions(
                [r for r in perf_results if r["name"] in failing],
                reference, args.tolerance, args.smoke)

    # tracker rewrite comes after the gate ruling: a failing run must not
    # persist its regressed numbers as the next run's reference
    if not failures:
        if not args.smoke:
            out_dir = Path("artifacts")
            out_dir.mkdir(exist_ok=True)
            (out_dir / "bench_results.json").write_text(
                json.dumps(results, indent=1, default=str))
            write_perf_tracker(perf_results)
        elif args.record_smoke:
            write_perf_tracker(perf_results, record_smoke=True, smoke=True)

    # artifact + CSV come after the retry loop so they record the numbers
    # the gate actually ruled on
    if args.out:
        out_path = Path(args.out)
        out_path.parent.mkdir(parents=True, exist_ok=True)
        out_path.write_text(json.dumps(
            {"smoke": args.smoke, "commit": _git_head(), "results": results},
            indent=1, default=str) + "\n")

    print("name,us_per_call,derived")
    for r in results:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")

    if args.check:
        if failures:
            print("\nBENCHMARK REGRESSION GATE FAILED:", file=sys.stderr)
            for line in failures:
                print(f"  {line}", file=sys.stderr)
            sys.exit(1)
        print(f"benchmark gate: {len(perf_results)} benches within "
              f"{args.tolerance:.0%} of reference")


if __name__ == "__main__":
    main()
