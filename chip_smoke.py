#!/usr/bin/env python3
"""Run the Chronos solve -> replay -> serve path once on a TPU and check it.

    python chip_smoke.py             # phases (a)-(g) on one chip
    python chip_smoke.py --chips 4   # the four-chip mesh path only

One process, no children. Every phase goes through the entry points a
user calls (`repro.simulate` / `RunConfig`, the strategy-IR solve, the
fleet runners) at deployment size, checks its outputs against the
repo's own references, and prints one line: sizes, the comparison, and
its wall time, labelled `smoke_wall_s` (one cold run, compile included;
not a benchmark). The last line of standard output is the JSON record
`{"ok": true, "device": {...}}`.

Phases on one chip:
  (a) solve     Algorithm 1 on paper-hadoop (2,700 jobs) for every
                analytic strategy: the Pallas grid kernel against the
                XLA reference, on the chip.
  (b) flat      simulate() over the full paper-hadoop trace, every
                registered strategy.
  (c) capacity  the same trace on 2,000 slots; on 300 jobs the jitted
                replay against the host-orchestrated reference.
  (d) fleet     diurnal-burst at 10^5 jobs streamed in 8,192-job chunks:
                fused chunk programs against the staged pipeline.
  (e) chaos     (d) with an injected chunk failure and a corrupted
                payload, retried, against the fault-free run.
  (f) budget    multi-tenant-sla under a budget inside clone's binding
                band: the joint solve must be feasible and binding.
  (g) serve     request-storm at 10^5 requests with online refits: PoCD
                lift over no-hedge.

With --chips 4: `run_all_fleet` on ("rep", "job") meshes of shape (1, 4)
and (2, 2) against the no-mesh run, and mesh-sharded serving windows
against unsharded ones; all bitwise.

Exits nonzero, printing no result, when JAX finds no TPU or a phase
fails.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

THETA = 1e-4
#: phase (a) tolerances on utility / PoCD / cost at r*. Both backends
#: evaluate the same closures in f32 on the chip; they differ only where
#: Mosaic's and XLA's exp / log / pow approximations round differently
#: (pow feeds the 128-node S-Restart quadrature). These are the bounds
#: tests/test_grid_solve.py holds the interpret-mode kernel to on CPU.
U_TOL = dict(rtol=1e-4, atol=1e-5)
P_TOL = dict(rtol=1e-5, atol=1e-7)
C_TOL = dict(rtol=1e-4, atol=1e-5)


class PhaseFailed(Exception):
    pass


def need(ok, what: str) -> None:
    if not ok:
        raise PhaseFailed(what)


def report(phase: str, t0: float, **fields) -> None:
    kv = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {kv} smoke_wall_s={time.perf_counter() - t0:.3f}",
          flush=True)


def same_run(a, b):
    """Bitwise equality of two runs' per-job columns and scalars."""
    import numpy as np
    for f in ("job_met", "job_completion", "job_cost"):
        if not np.array_equal(np.asarray(getattr(a.result, f)),
                              np.asarray(getattr(b.result, f))):
            return False
    if float(a.result.pocd) != float(b.result.pocd):
        return False
    if float(a.result.mean_cost) != float(b.result.mean_cost):
        return False
    return all(np.array_equal(np.asarray(getattr(a, f)),
                              np.asarray(getattr(b, f)))
               for f in ("r_opt", "theory_pocd", "theory_cost")
               if hasattr(a, f))


def check_outcomes(outs, expect) -> str:
    """PoCD in [0, 1] and finite costs for every strategy of a run."""
    import numpy as np
    need(set(outs) == set(expect),
         f"strategies run {sorted(outs)} != {sorted(expect)}")
    for name, o in outs.items():
        pocd = float(o.result.pocd)
        need(0.0 <= pocd <= 1.0, f"{name}: PoCD {pocd} outside [0, 1]")
        need(np.isfinite(float(o.result.mean_cost))
             and np.isfinite(np.asarray(o.result.job_cost)).all(),
             f"{name}: non-finite cost")
    return ",".join(f"{n}:{float(o.result.pocd):.4f}"
                    for n, o in outs.items())


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------


def phase_solve(n_jobs=2700, r_max=9):
    import jax.numpy as jnp
    import numpy as np
    from repro.sim import SimParams
    from repro.sim.runner import jobspecs_of
    from repro.strategies import names, solve_jobs_jit
    from repro.workloads import make_jobset

    t0 = time.perf_counter()
    specs = jobspecs_of(make_jobset("paper-hadoop", n_jobs=n_jobs),
                        SimParams(), jnp.float32(THETA), jnp.float32(0.0))
    ties, worst = {}, {"u": 0.0, "p": 0.0, "c": 0.0}
    for s in names(kind="optimized"):
        hlo = solve_jobs_jit.lower(s, specs, r_max,
                                   backend="pallas").compile().as_text()
        need("tpu_custom_call" in hlo,
             f"{s}: the pallas solve compiled without a Mosaic kernel")
        r_p, ch_p, u_p, p_p, c_p, sat_p = (
            np.asarray(a) for a in solve_jobs_jit(s, specs, r_max,
                                                  backend="pallas"))
        r_x, ch_x, u_x, p_x, c_x, sat_x = (
            np.asarray(a) for a in solve_jobs_jit(s, specs, r_max,
                                                  backend="xla"))
        diff = (r_p != r_x) | (ch_p != ch_x)
        tie = diff & np.isclose(u_p, u_x, **U_TOL)
        need(np.array_equal(diff, tie),
             f"{s}: r*/choice differ off a utility tie at jobs "
             f"{np.flatnonzero(diff & ~tie)[:8].tolist()}")
        ties[s] = int(diff.sum())
        ok = ~diff
        need(np.array_equal(sat_p[ok], sat_x[ok]), f"{s}: sat differs")
        for tag, a, b, tol in (("u", u_p, u_x, U_TOL), ("p", p_p, p_x, P_TOL),
                               ("c", c_p, c_x, C_TOL)):
            np.testing.assert_allclose(a[ok], b[ok], err_msg=f"{s}:{tag}",
                                       **tol)
            fin = ok & np.isfinite(b) & (b != 0)
            if fin.any():
                rel = np.abs(a[fin] - b[fin]) / np.abs(b[fin])
                worst[tag] = max(worst[tag], float(rel.max()))
    report("a solve", t0, jobs=n_jobs, r_max=r_max, strategies=len(ties),
           tpu_custom_call="yes", r_or_choice_ties=ties,
           max_rel_diff=worst, tol=dict(u=U_TOL, p=P_TOL, c=C_TOL))


def phase_flat(key, n_jobs=None):
    from repro import RunConfig, simulate
    from repro.strategies import names
    from repro.workloads import make_jobset

    t0 = time.perf_counter()
    jobs = make_jobset("paper-hadoop", n_jobs=n_jobs)
    outs, r_min = simulate(key, jobs, cfg=RunConfig())
    pocds = check_outcomes(outs, names())
    report("b flat", t0, jobs=jobs.n_jobs, tasks=jobs.total_tasks,
           strategies=len(outs), pocd=pocds, r_min=f"{r_min:.4f}")


def phase_capacity(key, n_jobs=None, slots=2000, n_small=300):
    import numpy as np
    from repro import RunConfig, simulate
    from repro.cluster.engine import build_strategy_table, replay
    from repro.sim import SimParams
    from repro.strategies import names
    from repro.workloads import make_jobset

    t0 = time.perf_counter()
    jobs = make_jobset("paper-hadoop", n_jobs=n_jobs)
    outs, _ = simulate(key, jobs, cfg=RunConfig(slots=slots))
    pocds = check_outcomes(outs, names())
    for name, o in outs.items():
        u = float(o.queue.utilization)
        need(0.0 <= u <= 1.0 + 1e-6, f"{name}: utilization {u}")
    small = make_jobset("paper-hadoop", n_jobs=n_small)
    checked = []
    for s in ("sresume", "hadoop_s", "adaptive"):
        table, race = build_strategy_table(key, small, s, SimParams(),
                                           theta=THETA, max_r=8)
        rh, rel_h, st_h = replay(table, race, small, slots, backend="host")
        rj, rel_j, st_j = replay(table, race, small, slots, backend="jit")
        for a, b, what in ((st_h, st_j, "starts"), (rel_h, rel_j, "releases"),
                           (rh.task_completion, rj.task_completion,
                            "completion"),
                           (rh.task_machine, rj.task_machine, "machine")):
            need(np.array_equal(np.asarray(a), np.asarray(b)),
                 f"capacity replay {s}: jit != host ({what})")
        checked.append(s)
    report("c capacity", t0, jobs=jobs.n_jobs, tasks=jobs.total_tasks,
           slots=slots, strategies=len(outs), pocd=pocds,
           jit_eq_host_bitwise=f"{'+'.join(checked)}@{n_small}jobs")


def fleet_trace(n_jobs):
    from repro.workloads import make_trace
    return make_trace("diurnal-burst", n_jobs=n_jobs)


def phase_fleet(key, n_jobs=100_000, chunk_jobs=8192,
                strategies=("sresume", "adaptive")):
    """Returns the fused fault-free runs for phase (e)."""
    from repro import RunConfig, simulate
    from repro.fleet import run_fleet_strategy
    from repro.sim import SimParams
    from repro.sim.runner import strategy_keys

    t0 = time.perf_counter()
    trace = fleet_trace(n_jobs)
    fused, _ = simulate(key, trace, cfg=RunConfig(
        strategies=strategies, r_min_from_ns=False, chunk_jobs=chunk_jobs))
    keys = strategy_keys(key, strategies)
    for s in strategies:
        staged = run_fleet_strategy(keys[s], trace, s, SimParams(),
                                    theta=THETA, chunk_jobs=chunk_jobs,
                                    fused=False)
        need(same_run(fused[s], staged), f"fleet {s}: fused != staged")
    check_outcomes(fused, strategies)
    report("d fleet", t0, jobs=n_jobs, tasks=int(trace.n_tasks.sum()),
           chunk_jobs=chunk_jobs, strategies="+".join(strategies),
           fused_eq_staged="bitwise")
    return fused


def phase_chaos(key, clean, n_jobs=100_000, chunk_jobs=8192,
                strategy="sresume"):
    from repro.chaos import ChaosContext, FaultEvent, FaultPlan
    from repro.fleet import run_fleet_strategy
    from repro.sim import SimParams
    from repro.sim.runner import strategy_keys

    t0 = time.perf_counter()
    plan = FaultPlan(events=(FaultEvent("chunk_fail", 1),
                             FaultEvent("corrupt", 2)), seed=7)
    ctx = ChaosContext(plan, backoff_base=0.0)
    out = run_fleet_strategy(strategy_keys(key, (strategy,))[strategy],
                             fleet_trace(n_jobs), strategy, SimParams(),
                             theta=THETA, chunk_jobs=chunk_jobs, chaos=ctx)
    retries = sum(kind == "retry" for _, kind, _ in ctx.records)
    need(retries == 2, f"chaos: expected 2 retries, saw {ctx.records}")
    need(same_run(out, clean[strategy]),
         f"chaos {strategy}: retried run != fault-free run")
    report("e chaos", t0, jobs=n_jobs, chunk_jobs=chunk_jobs,
           strategy=strategy, faults="chunk_fail@1,corrupt@2",
           retries=retries, eq_fault_free="bitwise")


def phase_budget(key, n_jobs=None, r_max=9):
    import jax.numpy as jnp
    import numpy as np
    from repro import RunConfig, simulate
    from repro.coupled import utility_cost_grids
    from repro.sim import SimParams
    from repro.sim.runner import jobspecs_of
    from repro.strategies import get
    from repro.workloads import make_jobset

    t0 = time.perf_counter()
    jobs = make_jobset("multi-tenant-sla", n_jobs=n_jobs)
    specs = jobspecs_of(jobs, SimParams(), jnp.float32(THETA),
                        jnp.float32(0.0))
    U, E = utility_cost_grids(get("clone"), specs, r_max)
    cost = np.asarray(E) * np.asarray(specs.C)[:, None]
    i_free = np.argmax(np.asarray(U), axis=1)
    lo = float(cost.min(axis=1).sum())
    hi = float(np.take_along_axis(cost, i_free[:, None], 1).sum())
    need(lo < hi, f"budget band is empty: [{lo}, {hi}]")
    budget = 0.5 * (lo + hi)
    outs, _ = simulate(key, jobs, cfg=RunConfig(
        strategies=("clone",), r_min_from_ns=False, budget=budget,
        max_r=r_max - 1))
    info = outs["clone"].coupled
    need(bool(info.feasible) and bool(info.binding),
         f"budget {budget}: feasible={info.feasible} "
         f"binding={info.binding}")
    need(float(info.spend) <= budget, f"spend {info.spend} > {budget}")
    check_outcomes(outs, ("clone",))
    report("f budget", t0, jobs=jobs.n_jobs, budget=f"{budget:.1f}",
           band=f"[{lo:.1f},{hi:.1f}]", spend=f"{float(info.spend):.1f}",
           feasible=True, binding=True, lam=float(info.lam))


def phase_serve(key, n_requests=100_000, refit_every=500):
    from repro import RunConfig, simulate
    from repro.serve import make_requests

    t0 = time.perf_counter()
    reqs = make_requests("request-storm", n_requests=n_requests, seed=0)
    outs, _ = simulate(key, reqs, cfg=RunConfig(
        serve=True, strategies=("hadoop_ns", "sresume"), theta=1e-3,
        window=256, refit_every=refit_every, probe_every=5))
    on, base = outs["sresume"], outs["hadoop_ns"]
    lift = float(on.result.pocd) - float(base.result.pocd)
    need(on.n_refits >= 3, f"serve: only {on.n_refits} refits")
    need(lift > 0.0, f"serve: no PoCD lift over no-hedge ({lift})")
    need(on.epoch_strategies[0] == "hadoop_ns"
         and on.epoch_strategies[-1] == "sresume",
         f"serve: epochs ran {on.epoch_strategies[:1]} ... "
         f"{on.epoch_strategies[-1:]}")
    report("g serve", t0, requests=n_requests, refit_every=refit_every,
           refits=on.n_refits, pocd_no_hedge=f"{float(base.result.pocd):.4f}",
           pocd_online=f"{float(on.result.pocd):.4f}", lift=f"{lift:.4f}")


# ---------------------------------------------------------------------------
# four-chip phases
# ---------------------------------------------------------------------------


def phase_mesh_fleet(key, n_jobs=20_000, chunk_jobs=8192,
                     strategies=("hadoop_ns", "adaptive")):
    from repro import RunConfig, simulate
    from repro.fleet import fleet_mesh

    t0 = time.perf_counter()
    trace = fleet_trace(n_jobs)
    cfg = RunConfig(strategies=strategies, reps=2, chunk_jobs=chunk_jobs)
    ref, _ = simulate(key, trace, cfg=cfg)
    for shape in ((1, 4), (2, 2)):
        out, _ = simulate(key, trace, cfg=cfg.replace(
            mesh=fleet_mesh(shape=shape)))
        for s in strategies:
            need(same_run(ref[s], out[s]),
                 f"mesh {shape} {s}: != no-mesh run")
    report("mesh fleet", t0, jobs=n_jobs, reps=2, chunk_jobs=chunk_jobs,
           strategies="+".join(strategies), shapes="(1,4),(2,2)",
           eq_no_mesh="bitwise")


def phase_mesh_serve(key, n_requests=20_000):
    from repro import RunConfig, simulate
    from repro.fleet import fleet_mesh
    from repro.serve import make_requests

    t0 = time.perf_counter()
    reqs = make_requests("request-storm", n_requests=n_requests, seed=0)
    cfg = RunConfig(serve=True, strategies=("hadoop_ns", "adaptive"),
                    theta=1e-3, window=256, refit_every=500, probe_every=5)
    ref, _ = simulate(key, reqs, cfg=cfg)
    out, _ = simulate(key, reqs, cfg=cfg.replace(mesh=fleet_mesh(devices=4)))
    for s in ref:
        need(same_run(ref[s], out[s]), f"sharded serve {s}: != unsharded")
    report("mesh serve", t0, requests=n_requests, devices=4,
           strategies="+".join(ref), eq_unsharded="bitwise")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases (a)-(g); 4: the mesh path only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    from repro import compile_cache

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU visible (platform "
              f"{devs[0].platform!r}); nothing is run", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} "
              f"device(s) visible", file=sys.stderr)
        return 1
    cache = compile_cache.enable()
    print(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
          f"count={len(devs)} jax={jax.__version__} compile_cache={cache}",
          flush=True)

    key = jax.random.PRNGKey(args.seed)
    runs = {}
    if args.chips == 4:
        phases = [("mesh fleet", lambda: phase_mesh_fleet(key)),
                  ("mesh serve", lambda: phase_mesh_serve(key))]
    else:
        phases = [("a solve", phase_solve),
                  ("b flat", lambda: phase_flat(key)),
                  ("c capacity", lambda: phase_capacity(key)),
                  ("d fleet", lambda: runs.update(clean=phase_fleet(key))),
                  ("e chaos", lambda: phase_chaos(key, runs["clean"])),
                  ("f budget", lambda: phase_budget(key)),
                  ("g serve", lambda: phase_serve(key))]
    failed = []
    for name, run in phases:
        try:
            run()
        except Exception:   # report it, run the rest, exit nonzero below
            traceback.print_exc()
            print(f"[{name}] FAILED", flush=True)
            failed.append(name)
    if "repro.launch.roofline" in sys.modules:
        print("the LLM roofline scaffolding was imported", flush=True)
        failed.append("imports")
    if failed:
        print(f"chip_smoke: FAILED: {', '.join(failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
