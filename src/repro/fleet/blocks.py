"""Job-block decomposition: a flat JobSet as fixed-shape shardable blocks.

The flat task layout (`sim/trace.py`) is ragged per job, so the job axis
cannot be sharded directly. `make_blocks` partitions a JobSet into
contiguous blocks of `block_jobs` jobs — every task of a job lands in its
job's block, so within-job segment reductions (hadoop_s rank, mantri mean)
stay shard-local — and pads each block to one uniform shape:

  * per-job rows:  (G_pad, Jb) with Jb = block_jobs + 1; row Jb - 1 is a
    reserved dummy job that absorbs every padding task, so padding can
    never pollute a real job's segment even when a block is full;
  * per-task rows: (G_pad, Tb) with Tb = the max per-block task count
    (or an externally fixed `tasks_pad`, so chunked streaming reuses one
    compiled shape across chunks).

`block_id` carries the GLOBAL block index (chunk offset included): the
runner folds it into the PRNG key, which is what makes draws independent
of the mesh shape, the block padding, and the chunk split. Global job j
lives at block j // block_jobs, row j % block_jobs — `gather_index`
rebuilds trace order without a stored map.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from ..sim.trace import JobSet

#: benign Pareto parameters for padding rows: finite draws, never read.
_FILL = {"t_min": 1.0, "beta": 2.0, "D": 1.0}

#: the per-block task axis is padded to a multiple of this
_TASK_TILE = 1024


class FleetBlocks(NamedTuple):
    """Block-stacked JobSet arrays; leading axis G_pad shards over "job"."""
    block_id: jnp.ndarray     # (G_pad,) int32 global block index
    job_valid: jnp.ndarray    # (G_pad, Jb) bool — real job rows
    n_tasks: jnp.ndarray      # (G_pad, Jb) int32
    t_min: jnp.ndarray        # (G_pad, Jb) f32
    beta: jnp.ndarray         # (G_pad, Jb) f32
    D: jnp.ndarray            # (G_pad, Jb) f32
    arrival: jnp.ndarray      # (G_pad, Jb) f32
    C: jnp.ndarray            # (G_pad, Jb) f32
    job_class: jnp.ndarray    # (G_pad, Jb) int32
    theta_scale: jnp.ndarray  # (G_pad, Jb) f32
    job_id: jnp.ndarray       # (G_pad, Tb) int32 block-LOCAL job row
    task_valid: jnp.ndarray   # (G_pad, Tb) bool — real task rows
    task_t_min: jnp.ndarray   # (G_pad, Tb) f32
    task_beta: jnp.ndarray    # (G_pad, Tb) f32
    task_D: jnp.ndarray       # (G_pad, Tb) f32

    @property
    def n_blocks(self) -> int:
        return int(self.block_id.shape[0])

    @property
    def jobs_per_block(self) -> int:
        return int(self.n_tasks.shape[1]) - 1


def block_jobset(blk) -> JobSet:
    """View one block (leaves sliced to (Jb,) / (Tb,)) as a JobSet."""
    return JobSet(
        n_jobs=blk.n_tasks.shape[0], n_tasks=blk.n_tasks, t_min=blk.t_min,
        beta=blk.beta, D=blk.D, arrival=blk.arrival, C=blk.C,
        job_class=blk.job_class, theta_scale=blk.theta_scale,
        job_id=blk.job_id, task_t_min=blk.task_t_min,
        task_beta=blk.task_beta, task_D=blk.task_D)


def block_task_counts(n_tasks, block_jobs: int) -> np.ndarray:
    """(G,) task count per block for per-job counts `n_tasks` (host side).

    Used by the chunked streamer to fix one global Tb before any chunk is
    materialized, so every chunk traces the same compiled shapes.
    """
    n_tasks = np.asarray(n_tasks, np.int64)
    J = int(n_tasks.shape[0])
    G = -(-J // block_jobs)
    pad = G * block_jobs - J
    return np.pad(n_tasks, (0, pad)).reshape(G, block_jobs).sum(axis=1)


def gather_index(n_jobs: int, block_jobs: int) -> np.ndarray:
    """(J,) flat index of job j inside the (G_pad * Jb) stacked job rows —
    the inverse of the `make_blocks` row placement (host-side numpy; the
    epilogue that consumes it is host-side by design, see runner.py)."""
    j = np.arange(n_jobs)
    jb = block_jobs + 1
    return (j // block_jobs) * jb + (j % block_jobs)


class BlockLayout(NamedTuple):
    """The host-side block decomposition geometry, computed once per
    chunk and shared by `make_blocks` and every `stack_task_column` call
    (so the r_task/choice_task columns can never desynchronize from the
    block layout they index into)."""
    block_jobs: int           # B
    n_blocks: int             # G (real)
    n_blocks_padded: int      # G_pad
    tasks_per_block: int      # Tb
    counts: np.ndarray        # (G,) tasks per real block
    g_j: np.ndarray           # (J,) block of job j
    row_j: np.ndarray         # (J,) row of job j inside its block
    g_t: np.ndarray           # (T,) block of flat task t
    off_t: np.ndarray         # (T,) row of flat task t inside its block

    def stack_jobs(self, x, fill, dtype) -> np.ndarray:
        out = np.full((self.n_blocks_padded, self.block_jobs + 1), fill,
                      dtype)
        out[self.g_j, self.row_j] = np.asarray(x)
        return out

    def stack_tasks(self, x, fill, dtype) -> np.ndarray:
        out = np.full((self.n_blocks_padded, self.tasks_per_block), fill,
                      dtype)
        out[self.g_t, self.off_t] = np.asarray(x)
        return out


def block_layout(jobs: JobSet, block_jobs: int, pad_blocks_to: int = 1,
                 tasks_pad: int = 0, min_blocks: int = 0) -> BlockLayout:
    """Compute the decomposition geometry (host-side numpy, O(J + T)).

    pad_blocks_to: round the block count up to a multiple of the mesh's
        "job" extent; padded blocks hold only dummy rows and are masked.
    tasks_pad: minimum Tb (0 = this JobSet's own max block task count) —
        chunked streaming passes the global maximum here.
    min_blocks: minimum G_pad — chunked streaming passes the per-chunk
        block count here so a short final chunk reuses the same shape.
    """
    if block_jobs < 1:
        raise ValueError(f"block_jobs must be >= 1, got {block_jobs}")
    J = jobs.n_jobs
    B = int(block_jobs)
    G = -(-J // B)
    G_pad = max(-(-G // pad_blocks_to) * pad_blocks_to, int(min_blocks))

    n_tasks = np.asarray(jobs.n_tasks, np.int64)
    counts = block_task_counts(n_tasks, B)
    # a whole number of 1024-element tiles: a TPU compiler turns every
    # gather or scatter over (G, Tb) task rows into code that grows with
    # Tb (~10 s per op at 128 x 38,480) unless Tb is tile-aligned (<1 s).
    # Padding rows draw benign values that are masked, and a real task's
    # draws depend only on its own index, so results do not change.
    Tb = -(-max(int(counts.max()), int(tasks_pad), 1) // _TASK_TILE) \
        * _TASK_TILE

    j = np.arange(J)
    # tasks are job-contiguous, so block g's tasks are the flat slice
    # [task_start[g], task_start[g] + counts[g])
    job_start = np.concatenate([[0], np.cumsum(n_tasks)])
    blk_start = job_start[np.arange(G) * B]
    task_job = np.asarray(jobs.job_id, np.int64)
    g_t = task_job // B
    return BlockLayout(
        block_jobs=B, n_blocks=G, n_blocks_padded=G_pad,
        tasks_per_block=Tb, counts=counts, g_j=j // B, row_j=j % B,
        g_t=g_t, off_t=np.arange(jobs.total_tasks) - blk_start[g_t])


def make_blocks(jobs: JobSet, block_jobs: int, pad_blocks_to: int = 1,
                tasks_pad: int = 0, block_offset: int = 0,
                min_blocks: int = 0,
                layout: BlockLayout = None) -> FleetBlocks:
    """Decompose a JobSet into padded fixed-shape blocks (host-side numpy).

    See `block_layout` for the geometry parameters; `block_offset` is the
    global index of this JobSet's first block (chunk start). Passing a
    precomputed `layout` skips recomputing it.
    """
    if layout is None:
        layout = block_layout(jobs, block_jobs, pad_blocks_to, tasks_pad,
                              min_blocks)
    B = layout.block_jobs
    G, G_pad = layout.n_blocks, layout.n_blocks_padded
    Tb = layout.tasks_per_block
    T = jobs.total_tasks
    n_tasks = np.asarray(jobs.n_tasks, np.int64)
    task_job = np.asarray(jobs.job_id, np.int64)
    stack_jobs, stack_tasks = layout.stack_jobs, layout.stack_tasks

    # dummy job row Jb - 1: absorbs every padding task of its block; its
    # n_tasks is the padding count so per-job means stay well defined
    nt = stack_jobs(n_tasks, 0, np.int32)
    pad_tasks = Tb - np.pad(layout.counts, (0, G_pad - G))
    nt[:, B] = np.maximum(pad_tasks, 1).astype(np.int32)

    job_valid = stack_jobs(np.ones(jobs.n_jobs, bool), False, bool)

    return FleetBlocks(
        block_id=jnp.asarray(
            (block_offset + np.arange(G_pad)).astype(np.int32)),
        job_valid=jnp.asarray(job_valid),
        n_tasks=jnp.asarray(nt),
        t_min=jnp.asarray(stack_jobs(jobs.t_min, _FILL["t_min"], np.float32)),
        beta=jnp.asarray(stack_jobs(jobs.beta, _FILL["beta"], np.float32)),
        D=jnp.asarray(stack_jobs(jobs.D, _FILL["D"], np.float32)),
        arrival=jnp.asarray(stack_jobs(jobs.arrival, 0.0, np.float32)),
        C=jnp.asarray(stack_jobs(jobs.C, 0.0, np.float32)),
        job_class=jnp.asarray(stack_jobs(jobs.job_class, 0, np.int32)),
        theta_scale=jnp.asarray(stack_jobs(jobs.theta_scale, 1.0,
                                           np.float32)),
        job_id=jnp.asarray(stack_tasks(task_job % B, B, np.int32)),
        task_valid=jnp.asarray(stack_tasks(np.ones(T, bool), False, bool)),
        task_t_min=jnp.asarray(stack_tasks(jobs.task_t_min, _FILL["t_min"],
                                           np.float32)),
        task_beta=jnp.asarray(stack_tasks(jobs.task_beta, _FILL["beta"],
                                          np.float32)),
        task_D=jnp.asarray(stack_tasks(jobs.task_D, _FILL["D"],
                                       np.float32)),
    )


def stack_task_column(layout: BlockLayout, x, fill, dtype) -> jnp.ndarray:
    """Stack one extra flat per-task column (e.g. r_task) on a layout."""
    return jnp.asarray(layout.stack_tasks(x, fill, dtype))
