#!/usr/bin/env python3
"""Readings behind a cell's correctness limits, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--out FILE]

For each seed, in one process: the program's own unit at the cell's size
compared with the float64 reference (the sound reading), and the control,
the reference computed in bfloat16 put in the program's place and
compared the same way (the reading that has to fail). One JSON line per
seed and side. The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

import harness  # noqa: E402

LOWER = "bfloat16"


def readings(cell, seed: int) -> list:
    """[sound, control] numbers of one seed, each with its limits' verdict."""
    import ml_dtypes
    path = cell.path
    cell.seed = seed
    state = path.setup(cell)
    key = harness.unit_key(seed, 0)
    t0 = time.perf_counter()
    got = path.unit(state, key)["out"]
    unit_s = time.perf_counter() - t0
    path.free(state)
    out = []
    for side, outputs in (("program", got),
                          ("control", path.reference(
                              state, key, getattr(ml_dtypes, LOWER)))):
        t0 = time.perf_counter()
        nums = path.compare(state, key, outputs)
        out.append({"workload": cell.name, "seed": seed, "side": side,
                    "numbers": nums, "unit_s": unit_s,
                    "compare_s": time.perf_counter() - t0,
                    "fails": sorted(k for k, v in nums.items()
                                    if v > cell.options["limits"][k])})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    from repro import compile_cache
    compile_cache.enable()
    sink = open(args.out, "a") if args.out else None
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            for line in readings(cell, seed):
                text = json.dumps(line)
                print(text, flush=True)
                if sink:
                    sink.write(text + "\n")
                    sink.flush()
    finally:
        if sink:
            sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
