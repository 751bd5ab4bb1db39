"""The correctness check must fail what it exists to catch.

The control, the reference computed in bfloat16 in the program's place,
has to fail at least one compared number. And a run of the harness with
the timed path broken underneath (a fault planted in the program for the
length of one test) has to come out not correct, once for each fault a
cell can have: state left unchanged, half of the batch left out, one
answer altered where it is produced. The cells run on one chip, so the
exchange between chips does not exist here.
"""
from __future__ import annotations

import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

import harness


def _control_fails(cell, seed):
    state = cell.path.setup(cell)
    key = harness.unit_key(seed, 0)
    nums = cell.path.compare(state, key, cell.path.reference(
        state, key, ml_dtypes.bfloat16))
    limits = cell.options["limits"]
    return sorted(k for k, v in nums.items() if v > limits[k])


@pytest.mark.parametrize("name", ["hadoop-capacity-sresume",
                                  "storm-online-sresume"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_is_not_correct(name, seed, tiny_cell):
    assert _control_fails(tiny_cell(name, seed), seed)


@pytest.fixture
def planted(monkeypatch):
    """Plant a fault for one test; compiled programs are dropped on both
    sides so that no cached program outlives it."""
    import jax

    def plant(target, name, value):
        jax.clear_caches()
        monkeypatch.setattr(target, name, value)
    yield plant
    jax.clear_caches()


# -- capacity: the slot-pool replay -------------------------------------------


def _pool_never_updates(state, x):
    rel, h, act = x
    return state, rel


def _half_the_jobs(aggregate):
    def agg(jobs, completion, machine):
        keep = jnp.arange(completion.shape[0]) < completion.shape[0] // 2
        res = aggregate(jobs, completion * keep, machine * keep)
        half = jobs.n_jobs // 2
        return res._replace(pocd=res.job_met[:half].astype("float32").mean())
    return agg


def _one_answer_altered(aggregate):
    def agg(jobs, completion, machine):
        res = aggregate(jobs, completion, machine)
        return res._replace(job_completion=res.job_completion.at[0].multiply(
            1.5))
    return agg


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_capacity_fault_is_not_correct(fault, tiny_cell, run_tiny, planted):
    from repro.cluster import engine, events
    if fault == "state_unchanged":
        planted(events, "_pool_step", _pool_never_updates)
    elif fault == "half_batch":
        planted(engine, "aggregate", _half_the_jobs(engine.aggregate))
    else:
        planted(engine, "aggregate", _one_answer_altered(engine.aggregate))
    out = run_tiny(tiny_cell("hadoop-capacity-sresume"))
    assert not out["correct"], out["checks"]


# -- serve: the online loop -----------------------------------------------------


def _half_window(serve_window):
    def sw(key, rids, *cols, **kw):
        c, m = serve_window(key, rids, *cols, **kw)
        n = c.shape[0] // 2
        return np.concatenate([c[:n], np.zeros_like(c[n:])]), m
    return sw


def _one_request_altered(serve_window, rid=1001):
    def sw(key, rids, *cols, **kw):
        c, m = serve_window(key, rids, *cols, **kw)
        hit = np.asarray(rids) == rid
        return np.where(hit, c + 0.05, c), m
    return sw


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_serve_fault_is_not_correct(fault, tiny_cell, run_tiny, planted):
    from repro.obs import tail
    from repro.serve import loop
    if fault == "state_unchanged":
        planted(tail.TailGovernor, "observe", lambda self, x: None)
    elif fault == "half_batch":
        planted(loop, "serve_window", _half_window(loop.serve_window))
    else:
        planted(loop, "serve_window", _one_request_altered(
            loop.serve_window))
    out = run_tiny(tiny_cell("storm-online-sresume"))
    assert not out["correct"], out["checks"]
