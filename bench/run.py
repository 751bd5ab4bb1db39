#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs only on a TPU: with no TPU, or fewer chips than the cell asks for,
it exits nonzero and prints no result. `--trace 0` reports the cell's
end-to-end metrics, `--trace 1` its per-layer metrics from a profiler
trace of a window of one unit. Every run checks the window's outputs against the
plain reference and prints each compared number beside its limit, last
on standard error and last in the result line.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

import harness  # noqa: E402


def find_chips(n: int):
    """The TPU devices, or an error: no CPU fallback."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise harness.BenchError(
            f"no TPU visible (platform {devices[0].platform!r})")
    if len(devices) < n:
        raise harness.BenchError(
            f"the cell needs {n} chips, {len(devices)} visible")
    return devices


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = harness.load_cell(args.workload, args.seed)
        devices = find_chips(cell.chips)
        from repro import compile_cache
    except (harness.BenchError, ImportError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    import jax
    cache = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    harness.log_stderr(f"device: {devices[0].device_kind} x{len(devices)}, "
                       f"compile cache {cache}")
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         devices, T_PROCESS, log=harness.log_stderr)
    for name, c in result["checks"].items():
        harness.log_stderr(f"check {name} = {c['value']!r} "
                           f"(limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
