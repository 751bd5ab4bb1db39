#!/usr/bin/env python3
"""Split a traced unit's host time over the program's own spans.

    python3 bench/span_split.py --workload <cell> --seed <n> [--seed <n> ...]

While the JAX profiler collects, every `repro.obs.trace.span` of the
program is a host annotation in the trace, on the clock of the device
ops. For each seed this sets the cell up as `run.py` does, warms up with
one unit, traces one unit under `bench.window` (the profiler options of
a `--trace 1` run), and prints one JSON line:

    unit_s      the unit's wall time on the host clock
    window_s, busy_s, modules
                as `trace_reduce.reduce` gives them
    spans       {name: {"count", "time_s", "self_s", "idle_s"}} over the
                program spans inside the window (`program_spans`)
    split       the online loop's per-epoch numbers (`epoch_split`), for
                cells that serve in epochs

Program spans are told apart from the runtime's own host events
(`TpuClient::LinearizeIntoImpl`, `PjitFunction(...)`, `Wait for usage
holds`) and from the benchmark's `bench.*` annotations by name: two or
more dot-separated words of lower-case letters, digits and `_`, each
starting with a letter, with an optional `[...]` suffix (`serve.epoch`,
`d2h.wait`, `cluster.replay[sresume]`). Runs only on a TPU.
"""
from __future__ import annotations

import argparse
import bisect
import gc
import json
import re
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

import harness  # noqa: E402
import trace_reduce as tr  # noqa: E402

PROGRAM_SPAN = re.compile(
    r"^(?!bench\.)[a-z][a-z0-9_]*(\.[a-z][a-z0-9_]*)+(\[[^\]]*\])?$")


def _idle_between(gaps):
    """f(a, b): the length of the idle `gaps` (sorted, disjoint) that
    falls inside [a, b]."""
    starts = [s for s, _ in gaps]
    before = [0.0]
    for s, e in gaps:
        before.append(before[-1] + e - s)

    def upto(t):
        i = bisect.bisect_right(starts, t)
        if i == 0:
            return 0.0
        s, e = gaps[i - 1]
        return before[i - 1] + min(t, e) - s

    return lambda a, b: upto(b) - upto(a)


def _line_events(line):
    return [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9) for e in line.events]


def program_spans(planes) -> dict:
    """{name: {"count", "time_s", "self_s", "idle_s"}} of the program
    spans inside `bench.window`. `self_s` is a span's duration less that of
    the program spans nested in it on its thread; `idle_s` is chip 0's idle
    time inside it that no nested program span covers, so every idle gap
    goes to the innermost program span over it."""
    planes = list(planes)
    lines = [line for p in planes if p.name.startswith("/host:")
             for line in p.lines]
    windows = [(s, e) for line in lines for n, s, e in _line_events(line)
               if n == tr.WINDOW]
    if not windows:
        raise ValueError(f"no {tr.WINDOW!r} annotation in the trace")
    lo, hi = windows[0]
    devices = sorted((int(m.group(1)), p) for p in planes
                     if (m := tr._DEVICE.match(p.name)))
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    ops = tr._clip(tr._events(devices[0][1], "XLA Ops"), lo, hi)
    idle = _idle_between(tr.gaps([(s, e) for _, s, e in ops], lo, hi))
    out: dict = {}
    for line in lines:
        evs = sorted(((n, s, e) for n, s, e in _line_events(line)
                      if lo <= s and e <= hi and PROGRAM_SPAN.match(n)),
                     key=lambda x: (x[1], -x[2]))
        dur = [e - s for _, s, e in evs]
        dur_idle = [idle(s, e) for _, s, e in evs]
        own, own_idle = list(dur), list(dur_idle)
        stack = []
        for i, (_, s, e) in enumerate(evs):
            while stack and not (evs[stack[-1]][1] <= s
                                 and e <= evs[stack[-1]][2]):
                stack.pop()
            if stack:
                own[stack[-1]] -= dur[i]
                own_idle[stack[-1]] -= dur_idle[i]
            stack.append(i)
        for (n, _, _), t, t_self, t_idle in zip(evs, dur, own, own_idle):
            m = out.setdefault(n, {"count": 0, "time_s": 0.0,
                                   "self_s": 0.0, "idle_s": 0.0})
            m["count"] += 1
            m["time_s"] += t
            m["self_s"] += t_self
            m["idle_s"] += t_idle
    return out


def epoch_split(reduced: dict, spans: dict) -> dict:
    """Per-epoch numbers of one traced online-serving unit, from
    `trace_reduce.reduce`'s dict and `program_spans`; {} when the unit
    served no epochs. Milliseconds, counts and shares:

        serve_epoch_host_ms   mean `serve.epoch`
        epoch_solve_host_ms   mean `serve.solve` (one per warm epoch)
        serve_window_host_ms  mean `serve.window`
        combine_host_ms       `serve.combine` time / epochs
        governor_host_ms      `serve.governor` time / epochs
        d2h_wait_ms           `d2h.wait` time / epochs
        d2h_per_epoch         `d2h.wait` count / epochs
        programs_per_epoch    XLA module executions / epochs
        epoch_children_share  share of `serve.epoch` inside nested spans
        unit_idle_share       chip 0's idle time left to `serve.trace`
                              itself, over the window's idle time
    """
    epoch = spans.get("serve.epoch")
    if not epoch:
        return {}
    n = epoch["count"]
    none = {"count": 0, "time_s": 0.0, "self_s": 0.0, "idle_s": 0.0}
    get = lambda name: spans.get(name, none)
    mean_ms = lambda name: (1e3 * get(name)["time_s"] / get(name)["count"]
                            if get(name)["count"] else None)
    idle_s = reduced["window_s"] - reduced["busy_s"]
    return {
        "serve_epoch_host_ms": mean_ms("serve.epoch"),
        "epoch_solve_host_ms": mean_ms("serve.solve"),
        "serve_window_host_ms": mean_ms("serve.window"),
        "combine_host_ms": 1e3 * get("serve.combine")["time_s"] / n,
        "governor_host_ms": 1e3 * get("serve.governor")["time_s"] / n,
        "d2h_wait_ms": 1e3 * get("d2h.wait")["time_s"] / n,
        "d2h_per_epoch": get("d2h.wait")["count"] / n,
        "programs_per_epoch": sum(m["count"]
                                  for m in reduced["modules"].values()) / n,
        "epoch_children_share": 1.0 - epoch["self_s"] / epoch["time_s"],
        "unit_idle_share": (get("serve.trace")["idle_s"] / idle_s
                            if idle_s > 0 else None),
    }


def measure(cell, seed: int) -> dict:
    """One traced unit of `cell` after set-up and one warm-up unit."""
    import jax
    cell.seed = seed
    state = cell.path.setup(cell)
    cell.path.unit(state, harness.unit_key(seed, harness.WARM_UNIT))
    gc.collect()
    shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(harness.TRACE_DIR), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        window = harness.run_window(cell.path, state, seed, 0.0, 1)
    jax.profiler.stop_trace()
    cell.path.free(state)
    data = jax.profiler.ProfileData.from_file(
        str(tr.find_xplane(harness.TRACE_DIR)))
    reduced = tr.reduce(data.planes, cell.chips)
    spans = program_spans(data.planes)
    shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
    return {"seed": seed, "unit_s": window["window_s"],
            "window_s": reduced["window_s"], "busy_s": reduced["busy_s"],
            "modules": reduced["modules"], "spans": spans,
            "split": epoch_split(reduced, spans)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    args = ap.parse_args(argv)
    try:
        cell = harness.load_cell(args.workload)
        import jax
        devices = jax.devices()
        if devices[0].platform != "tpu":
            raise harness.BenchError(
                f"no TPU visible (platform {devices[0].platform!r})")
        from repro import compile_cache
    except (harness.BenchError, ImportError) as e:
        print(f"span_split: {e}", file=sys.stderr)
        return 2
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    for seed in args.seed:
        out = measure(cell, seed)
        out["device"] = {"kind": devices[0].device_kind,
                         "count": cell.chips}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
