"""Reduce a profiler trace of the window to the numbers the metrics read.

Input: the `.xplane.pb` that `jax.profiler` writes under
`<dir>/plugins/profile/<time>/`. Output (`reduce`):

    window_s    length of the host annotation `bench.window`
    busy_s      union of the device's op intervals inside the window,
                averaged over the chips used
    modules     {module: {"time_s", "count"}}: device time per XLA
                program (its `jit_` prefix and `(id)` suffix dropped),
                summed over the chips used
    kernels     {op: time_s}: device self time per op name (HLO text
                dropped; ops nested in a loop or branch are not counted
                in it), summed likewise
    breakdown   {"device_ops": the 10 ops that took most time,
                 "idle_gaps": the 10 longest idle gaps of chip 0, each
                 named by the innermost host annotation around its middle}
"""
from __future__ import annotations

import re
from pathlib import Path

WINDOW = "bench.window"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_MODULE_ID = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir) -> Path:
    files = sorted(Path(trace_dir).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def union_length(intervals) -> float:
    """Total length covered by (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo, hi):
    """(start, end) of the idle stretches of [lo, hi] between intervals."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def module_name(name: str) -> str:
    name = _MODULE_ID.sub("", name)
    return name[4:] if name.startswith("jit_") else name


def op_name(name: str) -> str:
    """An op event's name without its HLO text: `%fusion.3 = f32[...]
    fusion(...)` -> `fusion.3`."""
    return name.split(" = ", 1)[0].lstrip("%")


def self_times(ops):
    """[(name, self seconds)] of op events, where an op's self time is its
    duration less that of the ops nested directly in it (a `while` op
    holds its body's ops on the same line)."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    own = [e - s for _, s, e in ops]
    stack = []
    for i, (_, s, e) in enumerate(ops):
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(n, t) for (n, _, _), t in zip(ops, own)]


def _events(plane, line_name):
    for line in plane.lines:
        if line.name == line_name:
            return [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                    for e in line.events]
    return []


def _clip(events, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def reduce(planes, chips: int) -> dict:
    """The numbers above from a list of xplane planes (ProfileData.planes)."""
    planes = list(planes)
    host_events = []
    for p in planes:
        if p.name.startswith("/host:"):
            for line in p.lines:
                host_events += [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                                for e in line.events]
    windows = [(s, e) for n, s, e in host_events if n == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    lo, hi = windows[0]
    devices = sorted((int(m.group(1)), p) for p in planes
                     if (m := _DEVICE.match(p.name)))[:chips]
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    busy, modules, kernels, idle = [], {}, {}, []
    for i, plane in devices:
        ops = _clip(_events(plane, "XLA Ops"), lo, hi)
        spans = [(s, e) for _, s, e in ops]
        busy.append(union_length(spans))
        for name, t in self_times(ops):
            name = op_name(name)
            kernels[name] = kernels.get(name, 0.0) + t
        for name, s, e in _clip(_events(plane, "XLA Modules"), lo, hi):
            m = modules.setdefault(module_name(name),
                                   {"time_s": 0.0, "count": 0})
            m["time_s"] += e - s
            m["count"] += 1
        if not idle:
            idle = gaps(spans, lo, hi)
    inside = [(n, s, e) for n, s, e in host_events
              if s >= lo and e <= hi and n != WINDOW]
    named = [(n, s, e) for n, s, e in inside if not n.startswith("$")]

    def around(t):
        best = None
        for n, s, e in named:
            if s <= t <= e and (best is None or e - s < best[1]):
                best = (n, e - s)
        return best[0] if best else "no host annotation"

    top_gaps = sorted(idle, key=lambda g: g[0] - g[1])[:10]
    return {
        "window_s": hi - lo,
        "busy_s": sum(busy) / len(busy),
        "modules": modules,
        "kernels": kernels,
        "breakdown": {
            "device_ops": sorted(([n, t] for n, t in kernels.items()),
                                 key=lambda x: -x[1])[:10],
            "idle_gaps": [[around(0.5 * (s + e)), e - s]
                          for s, e in top_gaps],
        },
    }


def reduce_dir(trace_dir, chips: int) -> dict:
    import jax
    data = jax.profiler.ProfileData.from_file(str(find_xplane(trace_dir)))
    return reduce(data.planes, chips)


def module_time(reduced: dict, pattern: str):
    """(device seconds, executions) of the programs whose name contains
    `pattern`, or None when none ran in the window."""
    hits = [m for name, m in reduced["modules"].items() if pattern in name]
    if not hits:
        return None
    return sum(m["time_s"] for m in hits), sum(m["count"] for m in hits)
