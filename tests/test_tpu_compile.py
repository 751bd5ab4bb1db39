"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached.

Interpret mode (every other kernel test) never hands a kernel to Mosaic,
so block-shape, layout and VMEM faults only show when the TPU compiler
sees it. These tests compile at deployment widths: the fused Algorithm-1
grid kernel (paper-hadoop's 2,700 jobs, a partial tile, and one 4,096-job
fleet chunk at r_max 65), the Monte-Carlo sweep kernel, and one fused
fleet chunk program. Nothing runs; a pass is not a chip run.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports this
file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.utility import JobSpec
from repro.strategies import get


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("strategy,n_jobs,r_max", [
    ("clone", 2700, 9),
    ("srestart", 2700, 9),
    ("sresume", 2700, 9),
    ("adaptive", 2700, 9),
    ("srestart", 4096, 65),
])
def test_grid_solve_compiles_for_v5e(one_chip, strategy, n_jobs, r_max):
    from repro.kernels.grid_solve import grid_solve_pallas
    jobs = JobSpec(*(_shape(one_chip, (n_jobs,)) for _ in JobSpec._fields))
    solve = jax.jit(lambda j: grid_solve_pallas(get(strategy), j, r_max,
                                                interpret=False))
    _assert_mosaic(solve.lower(jobs).compile())


def test_pocd_mc_all_compiles_for_v5e(one_chip):
    from repro.kernels.pocd_mc import MODES, pocd_mc_all_pallas
    J, N, R = 1024, 32, 6
    col = _shape(one_chip, (J,))
    sweep = jax.jit(lambda u, t, b, d, r: pocd_mc_all_pallas(
        u, t, b, d, r, interpret=False))
    _assert_mosaic(sweep.lower(
        _shape(one_chip, (J, N, R)), col, col, col,
        _shape(one_chip, (len(MODES), J), jnp.int32)).compile())


def test_fused_fleet_chunk_compiles_for_v5e(one_chip, monkeypatch):
    """One fused solve -> gather -> replay chunk program with the grid
    kernel compiled (not interpreted) inside it."""
    from repro.fleet import runner
    from repro.fleet.blocks import (block_layout, make_blocks,
                                    stack_task_column)
    from repro.kernels import ops
    from repro.sim import SimParams
    from repro.sim.runner import jobspecs_of
    from repro.workloads import make_jobset

    # the kernel wrapper asks jax.default_backend(), which is the CPU
    # here; steer it to the Mosaic path for this compile only
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    jax.clear_caches()
    p = SimParams()
    cjobs = runner.chunk_jobset(
        runner.job_columns(make_jobset("paper-hadoop", n_jobs=512)), 0, 512)
    layout = block_layout(cjobs, 64, pad_blocks_to=1, tasks_pad=0,
                          min_blocks=0)
    args = (jax.random.PRNGKey(0), jnp.arange(1, dtype=jnp.int32),
            make_blocks(cjobs, 64, layout=layout),
            jobspecs_of(cjobs, p, jnp.float32(1e-4), jnp.float32(0.0)),
            stack_task_column(layout, np.asarray(cjobs.job_id),
                              cjobs.n_jobs, np.int32))
    shapes = jax.tree.map(lambda x: _shape(one_chip, x.shape, x.dtype), args)
    try:
        compiled = runner._fleet_fused.lower(
            *shapes, strategy="sresume", p=p, max_r=8, oracle=True,
            mesh=None, backend="pallas").compile()
    finally:
        jax.clear_caches()
    _assert_mosaic(compiled)


@pytest.mark.parametrize("strategy", ["sresume", "hadoop_s"])
def test_capacity_replay_compiles_for_v5e(one_chip, strategy):
    """The whole capacity program (table build, two dispatch passes,
    metrics) for the full paper-hadoop trace: ~863k tasks, two sorts of
    up to 3.5M units. Its code must not grow with the trace: flattening
    (T, A) tables by reshape and XLA's long 1-D sort each compiled to
    minutes and 100+ MB of code here."""
    from repro.cluster import engine
    from repro.sim import SimParams
    from repro.sim.trace import jobset_arrays
    from repro.workloads import make_jobset

    jobs = make_jobset("paper-hadoop")
    J = jobs.n_jobs
    arrays = jax.tree.map(lambda x: _shape(one_chip, x.shape, x.dtype),
                          jobset_arrays(jobs))
    col = _shape(one_chip, (J,))
    ints = _shape(one_chip, (J,), jnp.int32)
    scalar = _shape(one_chip, ())
    solved = (ints, ints, col, col) if get(strategy).optimized else \
        (None,) * 4
    compiled = engine._cluster_core.lower(
        _shape(one_chip, (2,), jnp.uint32), arrays, scalar, scalar,
        *solved, None, n_jobs=J, strategy=strategy, p=SimParams(),
        slots=2000, discipline="fifo", passes=2, max_r=8, oracle=True,
        reps=1, width=4 if get(strategy).optimized else None).compile()
    code = compiled.memory_analysis().generated_code_size_in_bytes
    assert code < 32 * 2**20, code
