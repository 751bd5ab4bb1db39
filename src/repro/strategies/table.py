"""Attempt-table encoding — the unit every strategy lowers to under capacity.

`AttemptTable` is the flat per-attempt-unit schema the cluster replay
(`repro.cluster.events`) schedules: one row per potential attempt of a task,
each row encoding its whole analytic lifecycle (release offset, duration,
slot-hold cap, win eligibility). It lives here — in the strategy IR package —
because it is the *target* of every `StrategySpec.build_table` lowering;
`repro.cluster` re-exports it unchanged.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
from jax import lax


class AttemptTable(NamedTuple):
    """Flat per-attempt-unit arrays, (U,) each. U = total_tasks * width."""
    task_id: jnp.ndarray      # int32 — flat task index
    job_id: jnp.ndarray       # int32
    rel_offset: jnp.ndarray   # f32 — ARRIVAL offset from the primary's start
    dur: jnp.ndarray          # f32 — time from start to FINISH
    hold_cap: jnp.ndarray     # f32 — KILL: slot-hold if the unit loses
    can_win: jnp.ndarray      # bool — may its FINISH complete the task?
    active: jnp.ndarray       # bool — does this unit ever dispatch?
    is_primary: jnp.ndarray   # bool


def row_major(T: int, A: int):
    """(task, attempt) of each row of a (T, A) grid flattened row-major.

    Tables flatten by gathering at these indices, not by `reshape`: a
    TPU compiler lays a (T, A) array with A attempts on lanes padded to
    128, and its flattening reshape compiles to code that grows with T
    (minutes for a 10^6-task trace), while the gather compiles in well
    under a second and moves the same values.
    """
    i = lax.iota(jnp.int32, T * A)
    return i // A, i % A


def assemble(jobs, rel, dur, hold_cap, can_win, active) -> AttemptTable:
    """Flatten (T, A) per-attempt arrays into a (T*A,) AttemptTable.

    Layout contract (relied on by the replay's primary-slice fast path):
    row t*A + a is attempt a of task t, and attempt 0 is the primary.
    Inputs may be (T, A), (T, 1) or (1, A).
    """
    T, A = dur.shape
    task_id, att = row_major(T, A)

    def flat(x):
        rows, cols = x.shape
        return x[task_id if rows > 1 else 0, att if cols > 1 else 0]

    return AttemptTable(
        task_id=task_id, job_id=jobs.job_id[task_id],
        rel_offset=flat(rel).astype(jnp.float32),
        dur=flat(dur).astype(jnp.float32),
        hold_cap=flat(hold_cap).astype(jnp.float32),
        can_win=flat(can_win), active=flat(active), is_primary=att == 0)
