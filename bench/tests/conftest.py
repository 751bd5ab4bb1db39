"""Shared set-up of the benchmark's own tests (run: python -m pytest bench/tests).

They run on XLA:CPU at tiny sizes: `tiny_cell` loads a cell of
BENCHMARK.json and shrinks its trace, so one unit takes well under a
second. No test here needs, or looks for, a chip.
"""
from __future__ import annotations

import os
import sys
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import pytest  # noqa: E402

import harness  # noqa: E402

#: trace sizes of the tiny cells: jobs (and slots) or requests
TINY = {
    "hadoop-capacity-sresume": {"n_jobs": 24, "hours": 0.3, "slots": 40},
    "storm-online-sresume": {"n_jobs": 1500},
}


@pytest.fixture
def tiny_cell():
    def make(name: str, seed: int = 3):
        cell = harness.load_cell(name, seed)
        cell.config.update(TINY[name])
        return cell
    return make


@pytest.fixture
def run_tiny():
    """One run of a tiny cell through the harness, minus the chip check."""
    import jax

    def run(cell, seed: int = 3):
        return harness.run(cell, seed, 0.05, False, jax.devices(),
                           time.perf_counter(), log=lambda m: None)
    return run
