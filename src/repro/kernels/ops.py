"""jit'd public wrappers for the Pallas kernels.

On CPU (this container) the kernels execute in interpret mode — the kernel
body runs in Python for correctness validation; on TPU pass interpret=False
(the default flips automatically on TPU backends).
"""
from __future__ import annotations

import functools

import jax

from .pocd_mc import MODES as MODES  # re-export: tests use ops.MODES
from .pocd_mc import pocd_mc_pallas, pocd_mc_all_pallas
from .flash_attention import flash_attention
from .grid_solve import grid_solve_pallas


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("mode", "tau_est_frac",
                                             "tau_kill_gap_frac", "phi"))
def pocd_mc(u, t_min, beta, D, r, mode="clone", tau_est_frac=0.3,
            tau_kill_gap_frac=0.5, phi=0.25):
    """Monte-Carlo PoCD + cost for a batch of uniform-N jobs.

    Returns (met (J,), cost (J,)). Any J: a partial last job tile needs
    no padding copy of the uniforms.
    """
    return pocd_mc_pallas(u, t_min, beta, D, r, mode=mode,
                          tau_est_frac=tau_est_frac,
                          tau_kill_gap_frac=tau_kill_gap_frac, phi=phi,
                          interpret=_default_interpret())


@functools.partial(jax.jit, static_argnames=("tau_est_frac",
                                             "tau_kill_gap_frac", "phi"))
def pocd_mc_all(u, t_min, beta, D, r_modes, tau_est_frac=0.3,
                tau_kill_gap_frac=0.5, phi=0.25):
    """Fused Monte-Carlo sweep over all strategy modes in one grid pass.

    r_modes: (len(MODES), J) int32 — per-mode r* rows in `MODES` order
    (clone, srestart, sresume). Shares one uniform -> Pareto transform
    across modes; returns (met (M, J), cost (M, J)).
    """
    return pocd_mc_all_pallas(u, t_min, beta, D, r_modes,
                              tau_est_frac=tau_est_frac,
                              tau_kill_gap_frac=tau_kill_gap_frac, phi=phi,
                              interpret=_default_interpret())


@functools.partial(jax.jit, static_argnames=("strategy", "r_max",
                                             "interpret"))
def grid_solve_fused(strategy, jobs, r_max, interpret=None):
    """Fused Algorithm-1 grid solve (kernels/grid_solve.py) on a batched
    JobSpec. Returns (r_opt, choice, utility, pocd, cost, sat), all (J,);
    the strategy-IR `grid_solve`/`solve_jobs` dispatch here under
    backend="pallas". `interpret=None` flips off interpret mode on TPU.
    """
    from ..strategies import get
    from .grid_solve import grid_solve_pallas
    if interpret is None:
        interpret = _default_interpret()
    return grid_solve_pallas(get(strategy), jobs, r_max,
                             interpret=interpret)


@functools.partial(jax.jit, static_argnames=("causal", "softcap", "block_q",
                                             "block_k"))
def attention(q, k, v, causal=True, softcap=None, block_q=128, block_k=128):
    """Flash attention forward. q: (B,H,S,D); k/v: (B,K,S,D)."""
    return flash_attention(q, k, v, causal=causal, softcap=softcap,
                           block_q=block_q, block_k=block_k,
                           interpret=_default_interpret())
