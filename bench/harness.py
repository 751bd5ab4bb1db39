"""The benchmark harness: one cell, one seed, one measured window.

Everything a cell needs is found by name under `bench/`:

    BENCHMARK.json            the cell's configuration, traffic and chips,
                              and the metrics it reports
    configs/<config>.json     the deployment (sizes, classes, precision)
    traffic/<traffic>.json    the traffic mix the generator reads
    workloads/<cell>.json     the path that drives the cell, its options,
                              and the limits of its correctness numbers
    paths/<path>.py           setup / unit / free / compare of one entry
                              point of the program
    end_to_end/<metric>.py    value(record) of an end-to-end metric
    layer_metrics/<metric>.py value(reduced_trace, record) of a per-layer
                              metric, None when there is nothing to read

A run builds the cell's inputs from the seed, warms up with one unit,
then calls whole units back to back and closes the window at the first
unit boundary after `seconds`. A unit is one complete call into the
program's entry point, with its own key folded from the seed.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"
#: unit index of the warm-up call: never one of the window's units
WARM_UNIT = 1 << 30
#: units of a window that the reference recomputes, drawn from the seed
CHECK_UNITS = 1
#: a traced run's window: one unit. The profiler records every op of
#: every loop iteration (4 M events, 190 MB for one capacity unit) and
#: silently drops what overflows its buffers, which a longer window
#: would read as an idle device
TRACE_UNITS = 1


class BenchError(Exception):
    """A cell, configuration, path or metric that cannot be found or used."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    options: dict
    path: object
    end_to_end: list
    per_layer: list
    seed: int = 0
    readers: dict = field(default_factory=dict)


def read_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"no such file: {path.relative_to(ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import one file of `bench/` by path (names may hold dots)."""
    if not path.is_file():
        raise BenchError(f"no such file: {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    known = ", ".join(sorted(e["name"] for e in entries))
    raise BenchError(f"unknown {what} {name!r}; known: {known}")


def load_cell(name: str, seed: int = 0, benchmark: dict | None = None
              ) -> Cell:
    """Resolve a cell of BENCHMARK.json and every file it names."""
    bm = read_json(ROOT / "BENCHMARK.json") if benchmark is None \
        else benchmark
    w = _by_name(bm["workloads"], name, "workload")
    conf = _by_name(bm["configs"], w["config"], "configuration")
    config = read_json(ROOT / conf["file"])
    traffic = read_json(BENCH / "traffic" / f"{w['traffic']}.json")
    options = read_json(BENCH / "workloads" / f"{name}.json")
    path = load_module(BENCH / "paths" / f"{options['path']}.py",
                       options["path"])
    e2e = [m for m in bm["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    cell = Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, options=options, path=path, end_to_end=e2e,
                per_layer=per_layer, seed=seed)
    for m in e2e:
        cell.readers[m["name"]] = load_module(
            BENCH / "end_to_end" / f"{m['name']}.py", m["name"])
    for m in per_layer:
        cell.readers[m["name"]] = load_module(
            BENCH / "layer_metrics" / f"{m['name']}.py", m["name"])
    return cell


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def unit_key(seed: int, i: int):
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed), i)


class CompileCounter:
    """Counts executables compiled or loaded from the cache while on."""

    def __init__(self):
        import jax
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if self.on and event.endswith("backend_compile_duration"):
            self.count += 1

    def _event(self, event, **kw):
        if self.on and event.endswith("/cache_hits"):
            self.count += 1


def run_window(path, state, seed: int, seconds: float,
               max_units: int | None = None) -> dict:
    """Whole units back to back until the first boundary after `seconds`,
    or after `max_units` units."""
    units = []
    t0 = time.perf_counter()
    while True:
        out = path.unit(state, unit_key(seed, len(units)))
        out["end_s"] = time.perf_counter() - t0
        units.append(out)
        if out["end_s"] >= seconds or len(units) == max_units:
            break
    return {"units": units, "window_s": units[-1]["end_s"]}


def device_info(devices, used: int) -> dict:
    peaks = []
    for d in devices[:used]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": used, "memory_peak_bytes": max(peaks)}


def check(cell: Cell, state, window: dict, seed: int) -> dict:
    """The cell's compared numbers: the worst over a seeded sample of the
    window's units, each against the float64 reference."""
    n = len(window["units"])
    pick = sorted(np.random.default_rng(seed).choice(
        n, min(CHECK_UNITS, n), replace=False))
    worst: dict = {}
    for i in pick:
        got = cell.path.compare(state, unit_key(seed, int(i)),
                                window["units"][i]["out"])
        for name, v in got.items():
            worst[name] = max(worst.get(name, 0.0), float(v))
    limits = cell.options["limits"]
    missing = set(limits) ^ set(worst)
    if missing:
        raise BenchError(f"{cell.name}: compared numbers and limits "
                         f"differ: {sorted(missing)}")
    return {name: {"value": worst[name], "limit": float(limits[name])}
            for name in sorted(limits)}


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices,
        t_process: float, log=print) -> dict:
    """One measured run of a cell; returns the result line's object."""
    import jax
    counter = CompileCounter()
    cell.seed = seed
    state = cell.path.setup(cell)
    cell.path.unit(state, unit_key(seed, WARM_UNIT))      # warm-up
    gc.collect()
    counter.on = True
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    setup_s = time.perf_counter() - t_process
    with jax.profiler.TraceAnnotation("bench.window"):
        window = run_window(cell.path, state, seed, seconds,
                            TRACE_UNITS if trace else None)
    if trace:
        jax.profiler.stop_trace()
    counter.on = False
    ends = [0.0] + [u["end_s"] for u in window["units"]]
    log(f"window: {len(window['units'])} units in "
        f"{window['window_s']:.3f} s (each "
        f"{', '.join(f'{b - a:.3f}' for a, b in zip(ends, ends[1:]))} s), "
        f"compiles in window: {counter.count}")
    device = device_info(devices, cell.chips)
    cell.path.free(state)
    gc.collect()
    record = dict(window, setup_s=setup_s)
    metrics = {}
    reduced = None
    if trace:
        import trace_reduce
        reduced = trace_reduce.reduce_dir(TRACE_DIR, cell.chips)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        for m in cell.per_layer:
            v = cell.readers[m["name"]].value(reduced, record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            v = cell.readers[m["name"]].value(record)
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    checks = check(cell, state, window, seed)
    attempted = int(sum(u["work"] for u in window["units"]))
    out = {"correct": all(c["value"] <= c["limit"]
                          for c in checks.values()),
           "attempted": attempted, "failed": 0, "metrics": metrics,
           "device": device}
    if reduced is not None:
        out["breakdown"] = reduced["breakdown"]
    out["checks"] = checks
    return out


# ---------------------------------------------------------------------------
# arithmetic shared by the metric readers
# ---------------------------------------------------------------------------


def rate(record: dict) -> float:
    """Work of the window's whole units over the window's time."""
    return sum(u["work"] for u in record["units"]) / record["window_s"]


def p95_over_requests(latencies) -> float:
    """95th percentile over every request (not over batches or chunks)."""
    x = np.concatenate([np.asarray(a, np.float64) for a in latencies])
    return float(np.percentile(x, 95))


def log_stderr(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
