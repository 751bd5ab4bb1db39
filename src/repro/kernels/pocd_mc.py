"""Pallas TPU kernel: Monte-Carlo PoCD/cost estimation — the paper's
evaluation hot spot as a literal on-chip MapReduce.

Map: transform per-attempt uniforms into Pareto execution times, per-task
minimum over active attempts (the speculative race). Reduce: per-job
all-tasks-before-deadline indicator + total machine time. One grid step
processes a tile of 128 jobs. Jobs sit on the lane axis: the wrapper
moves the (J, N, R) uniforms to (R, N, J), so a tile is R (N, 128) slabs
with tasks on sublanes, and the slot race and the task reduction are
leading-axis and sublane reductions that leave lane-dense (1, Jt) rows.

Two entry points share the strategy bodies (`_strategy_outcome`):

  * `pocd_mc_pallas`     — one mode per launch.
  * `pocd_mc_all_pallas` — all three modes in ONE grid pass: the
    uniform -> Pareto transform (the exp/log half of the FLOPs) is computed
    once and reused, where three separate launches would redo it per mode.

Neither requires J to divide the job tile: the grid covers ceil(J / tile)
steps, lanes are independent, and Pallas drops the out-of-range part of
the last block, so callers never pad the uniforms.

Used by the governor's empirical PoCD cross-check and by benchmarks; the
ragged-trace path uses the segment-reduction JAX implementation (sim/), and
`ref.py` holds the pure-jnp oracle this kernel is tested against.

Strategy semantics match sim/strategies.py exactly:
  clone    — r+1 attempts from t=0; killed clones bill tau_kill each.
  srestart — original + r restarts at tau_est for stragglers (T1 > D).
  sresume  — original killed at tau_est; r+1 resumed attempts process the
             remaining (1-phi) work with a t_min startup floor.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..strategies import get, names

JOB_TILE = 128
# Every strategy with a Pallas tile body is a kernel mode; the tile
# closures live on the specs (repro.strategies.chronos). MODES is an
# import-time snapshot of the registry — it sizes static (n_modes, J)
# kernel shapes — so a tile-armed strategy must be registered before this
# module is first imported to join the fused sweep.
MODES = tuple(n for n in names() if get(n).tile_outcome is not None)


def _strategy_outcome(att, t_min, tau_est, tau_kill, D, r, *, mode: str,
                      phi: float):
    """(completion, machine), both (N, Jt), from shared Pareto draws.

    att: (R, N, Jt) attempt times; t_min/tau_est/tau_kill/D/r: (1, Jt)
    rows. The body is the mode's spec `tile_outcome`.
    """
    spec = get(mode)
    if spec.tile_outcome is None:
        raise ValueError(f"strategy {mode!r} has no Pallas tile body; "
                         f"kernel modes: {MODES}")
    return spec.tile_outcome(att, t_min, tau_est, tau_kill, D, r, phi=phi)


def _kernel(u_ref, par_ref, r_ref, met_ref, cost_ref, *, modes,
            tau_est_frac: float, tau_kill_gap_frac: float, phi: float):
    """Shared Pareto transform, then one (met, cost) row per mode."""
    t_min, beta, D = (par_ref[k:k + 1, :] for k in range(3))  # (1, Jt)
    att = t_min * jnp.exp(-jnp.log(u_ref[...]) / beta)  # Pareto u^(-1/beta)
    tau_est = tau_est_frac * t_min
    tau_kill = tau_est + tau_kill_gap_frac * t_min
    for m, mode in enumerate(modes):
        completion, machine = _strategy_outcome(
            att, t_min, tau_est, tau_kill, D, r_ref[m:m + 1, :], mode=mode,
            phi=phi)
        met_ref[m:m + 1, :] = jnp.min(
            jnp.where(completion <= D, 1.0, 0.0), axis=0, keepdims=True)
        cost_ref[m:m + 1, :] = jnp.sum(machine, axis=0, keepdims=True)


def _call(u, t_min, beta, D, r_rows, modes, *, tau_est_frac,
          tau_kill_gap_frac, phi, interpret):
    """One launch over all jobs: u (J, N, R), r_rows (len(modes), J)."""
    J, N, R = u.shape
    M = len(modes)
    kernel = functools.partial(_kernel, modes=modes,
                               tau_est_frac=tau_est_frac,
                               tau_kill_gap_frac=tau_kill_gap_frac, phi=phi)
    att_in = jnp.transpose(u, (2, 1, 0))                    # (R, N, J)
    par = jnp.stack([t_min, beta, D]).astype(jnp.float32)  # (3, J)
    row_spec = pl.BlockSpec((M, JOB_TILE), lambda i: (0, i))
    met, cost = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(J, JOB_TILE),),
        in_specs=[
            pl.BlockSpec((R, N, JOB_TILE), lambda i: (0, 0, i)),
            pl.BlockSpec((3, JOB_TILE), lambda i: (0, i)),
            row_spec,
        ],
        out_specs=[row_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((M, J), jnp.float32)] * 2,
        interpret=interpret,
    )(att_in, par, r_rows.astype(jnp.int32))
    return met, cost


def pocd_mc_pallas(u, t_min, beta, D, r, *, mode="clone", tau_est_frac=0.3,
                   tau_kill_gap_frac=0.5, phi=0.25, interpret=True):
    """u: (J, N, R) uniforms; per-job t_min/beta/D (J,), r (J,) int32.

    Returns (met (J,) f32, cost (J,) f32). Any J: no padding required.
    """
    met, cost = _call(u, t_min, beta, D, r[None], (mode,),
                      tau_est_frac=tau_est_frac,
                      tau_kill_gap_frac=tau_kill_gap_frac, phi=phi,
                      interpret=interpret)
    return met[0], cost[0]


def pocd_mc_all_pallas(u, t_min, beta, D, r_modes, *, tau_est_frac=0.3,
                       tau_kill_gap_frac=0.5, phi=0.25, interpret=True):
    """Fused sweep: u (J, N, R) uniforms shared across modes, r_modes
    (n_modes, J) int32 with one r* row per mode in `MODES` order.

    Returns (met (n_modes, J), cost (n_modes, J)) — one kernel launch, one
    Pareto transform, three strategy evaluations.
    """
    J = u.shape[0]
    assert r_modes.shape == (len(MODES), J), r_modes.shape
    return _call(u, t_min, beta, D, r_modes, MODES,
                 tau_est_frac=tau_est_frac,
                 tau_kill_gap_frac=tau_kill_gap_frac, phi=phi,
                 interpret=interpret)
