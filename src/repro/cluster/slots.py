"""Finite slot-pool accounting and dispatch disciplines.

The pool is the only mutable state of the event scan: `free[i]` is the
absolute time at which slot i next becomes idle. To keep the per-event cost
sublinear in the slot count, slots are stored as a two-level (G, g) grid with
a cached per-group minimum — finding the earliest-idle slot is an argmin over
G group minima followed by an argmin within the winning group (O(G + g)
instead of O(K), a ~5x end-to-end speedup at K = 2048; the decomposition is
exact, not approximate).

Disciplines decide the order in which queued attempt-units are offered a
slot:

  * FIFO — dispatch in release-time order. With identical slots this is the
    exact G/G/K recursion (start_i = max(release_i, earliest idle slot)).
  * EDF  — strict non-preemptive earliest-deadline-first: units sorted by
    absolute job deadline, ties broken by release. A unit with an early
    deadline but a late release blocks later-deadline units (strict priority,
    not work-conserving) — see DESIGN.md §10.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from ..sim.sorting import stable_argsort

DISCIPLINES = ("fifo", "edf")


class SlotPool(NamedTuple):
    """Two-level grid of slot next-idle times + cached group minima."""
    free: jnp.ndarray   # (G, g) absolute next-idle time per slot
    gmin: jnp.ndarray   # (G,)   cached min over each group row


def make_pool(slots: int, t0: float = 0.0) -> SlotPool:
    """A pool of `slots` idle-at-t0 slots, padded to a (G, g) grid.

    Padding slots are pinned at +inf so the argmin never selects them.
    """
    if slots <= 0:
        raise ValueError(f"slots must be positive, got {slots}")
    G = max(int(np.sqrt(slots)), 1)
    g = -(-slots // G)  # ceil
    free = np.full((G * g,), np.inf, np.float32)
    free[:slots] = t0
    free = free.reshape(G, g)
    return SlotPool(free=jnp.asarray(free), gmin=jnp.asarray(free.min(axis=1)))


def dispatch_order(discipline: str, release: np.ndarray,
                   deadline_abs: np.ndarray) -> np.ndarray:
    """Permutation that sorts attempt-units into dispatch order (host path)."""
    if discipline == "fifo":
        return np.argsort(release, kind="stable")
    if discipline == "edf":
        return np.lexsort((release, deadline_abs))
    raise ValueError(f"unknown discipline {discipline!r}; "
                     f"expected one of {DISCIPLINES}")


def dispatch_key_order(discipline: str, release, deadline_abs,
                       inactive=None):
    """Traceable twin of `dispatch_order`: both disciplines reduce to one
    stable lexicographic key sort, so dispatch ordering happens inside jit
    with no host round-trip. Ties break by unit index (stable), matching the
    host path's argsort/lexsort exactly. With `inactive` (bool mask), the
    most-significant key of inactive units is forced to +inf so active units
    pack into a dispatch-ordered prefix — the static-shape replacement for
    host-side flatnonzero compaction (exact because active releases and
    deadlines are always finite; one fewer stable sort pass than an extra
    boolean key)."""
    if discipline == "fifo":
        key = release
        if inactive is not None:
            key = jnp.where(inactive, jnp.inf, key)
        return stable_argsort(key)
    if discipline == "edf":
        key = deadline_abs
        if inactive is not None:
            key = jnp.where(inactive, jnp.inf, key)
        return stable_argsort(key, release)
    raise ValueError(f"unknown discipline {discipline!r}; "
                     f"expected one of {DISCIPLINES}")


def utilization(busy_time, slots: int, span):
    """Fraction of slot-time spent occupied over the makespan.

    Deliberately unclamped: billed occupancy never exceeding slots * span is
    an engine invariant (tests assert it), and a clamp would hide any
    double-billing regression.
    """
    return busy_time / jnp.maximum(slots * span, 1e-9)
