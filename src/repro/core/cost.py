"""Expected machine running time E[T] per strategy — paper Theorems 2, 4, 6.

Cost = C * E[T] where E[T] is the total (virtual) machine time consumed by all
attempts of all N tasks of a job. Formulas are implemented exactly as derived
in the paper (Section IV + Appendix), with two engineering notes:

* Thm 4 (S-Restart) contains an integral with no elementary closed form,
    I(r) = int_{D-tau}^{inf} (D/(w+tau))^beta (t_min/w)^(beta r) dw.
  We evaluate it with fixed Gauss-Legendre quadrature after the substitution
  w = (D - tau)/u, u in (0, 1], which maps the infinite domain to the unit
  interval and concentrates nodes near the (integrable) endpoint. The
  integrand decays like u^(beta(r+1) - 2), integrable for beta(r+1) > 1.
  Differentiable in r (r enters only through exponents).

* Thm 6 (S-Resume) models each resumed attempt's execution time as
  max(t_min, (1-phi) * T), T ~ Pareto(t_min, beta): a resumed attempt still
  pays the t_min startup/processing floor. This is the reading under which the
  paper's Eq. (21)-(22) and Thm 5 are *mutually consistent* (P(max(t_min,
  (1-phi)T) > D - tau) equals Thm 5's term whenever D - tau >= t_min), and it
  is what our simulator implements in theory-matched mode.

Singularities at beta*r == 1 / beta*(r+1) == 1 are the genuine divergence of a
Pareto min-mean (Lemma 1 needs n*beta > 1); callers keep parameters away from
them (the optimizer works on integer r with beta > 1).
"""
from __future__ import annotations

from contextlib import contextmanager

import numpy as np
import jax.numpy as jnp

from .pareto import truncated_mean_below

_GL_NODES, _GL_WEIGHTS = np.polynomial.legendre.leggauss(128)
# Map from [-1, 1] to (0, 1).
_GL_U = jnp.asarray((_GL_NODES + 1.0) / 2.0, dtype=jnp.float32)
_GL_W = jnp.asarray(_GL_WEIGHTS / 2.0, dtype=jnp.float32)
# active (nodes, weights) — rebound by `quadrature_inputs` when the Thm-4
# integral is evaluated inside a Pallas kernel body, where the node arrays
# must enter as kernel operands (Pallas forbids captured consts)
_GL_ACTIVE = (_GL_U, _GL_W)


@contextmanager
def quadrature_inputs(u, w):
    """Scoped override of the Gauss-Legendre (nodes, weights) arrays.

    The fused grid-solve kernel (kernels/grid_solve.py) passes the
    quadrature vectors as kernel operands and traces the cost closures
    under this context. It passes them shaped (K, 1, 1): the quadrature
    axis then leads and broadcasts against the kernel's 2-D (r, job)
    tiles with no in-kernel reshape (Mosaic has no lane -> sublane shape
    cast). The node and weight values are the module constants.
    """
    global _GL_ACTIVE
    prev = _GL_ACTIVE
    _GL_ACTIVE = (u, w)
    try:
        yield
    finally:
        _GL_ACTIVE = prev


def _p_straggler(t_min, beta, D):
    """P(T_{j,1} > D) = (t_min / D)^beta."""
    return jnp.power(t_min / D, beta)


# ---------------------------------------------------------------------------
# Theorem 2 — Clone
# ---------------------------------------------------------------------------


def cost_clone(r, t_min, beta, D, N, tau_kill):
    """E_Clone[T] = N * [ r*tau_kill + t_min * beta(r+1) / (beta(r+1) - 1) ].

    r killed attempts each bill tau_kill; the winner bills the min of r+1
    attempts (Lemma 1 with n = r+1). D enters only through the optimizer.
    """
    nb = beta * (r + 1.0)
    e_win = t_min * nb / (nb - 1.0)
    return N * (r * tau_kill + e_win)


# ---------------------------------------------------------------------------
# Theorem 4 — Speculative-Restart
# ---------------------------------------------------------------------------


def _srestart_integral(r, t_min, beta, D, tau_est):
    """I(r) = int_{D-tau}^{inf} (D/(w+tau))^beta * (t_min/w)^(beta r) dw."""
    u, gl_w = _GL_ACTIVE
    params = (r, t_min, beta, D, tau_est)
    if u.ndim == 1:
        # (K,) nodes on a new trailing axis of the parameters
        r_, t_, b_, D_, tau_ = (jnp.asarray(x)[..., None] for x in params)
        axis = -1
    else:
        # kernel form: (K, 1, ..., 1) nodes lead the parameters' axes
        r_, t_, b_, D_, tau_ = params
        axis = 0
    Dm_ = jnp.maximum(D_ - tau_, t_)
    w_ = Dm_ / u
    f = jnp.power(D_ / (w_ + tau_), b_) * jnp.power(t_ / w_, b_ * r_)
    # dw = Dm / u^2 du
    return jnp.sum(f * (Dm_ / (u * u)) * gl_w, axis=axis)


def _srestart_cond_above(r, t_min, beta, D, tau_est, tau_kill):
    """E(T_j | T_{j,1} > D) per Eq. (16), continuous in r (valid at r = 0).

    Dm is clamped at t_min: the paper's formula assumes D - tau >= t_min
    (Appendix); below that, restarted attempts can't beat the window anyway
    and the clamped expression remains the correct machine-time model.
    """
    br = beta * r
    Dm = jnp.maximum(D - tau_est, t_min)
    head = tau_est + r * (tau_kill - tau_est)
    # int_{t_min}^{D-tau} (t_min/w)^(beta r) dw, written to be finite at br=1 via
    # the standard power-integral formula (callers avoid br == 1 exactly).
    # t_min^(br) / Dm^(br-1) is computed in log space: for large r the naive
    # powers overflow f32 even though the ratio underflows to 0.
    ratio = jnp.exp(br * jnp.log(t_min / Dm) + jnp.log(Dm))
    part1 = (t_min - ratio) / (br - 1.0)
    part2 = _srestart_integral(r, t_min, beta, D, tau_est)
    return head + part1 + part2 + t_min


def cost_srestart(r, t_min, beta, D, N, tau_est, tau_kill):
    """E_S-Restart[T] (Theorem 4), N tasks."""
    p_s = _p_straggler(t_min, beta, D)
    e_fast = truncated_mean_below(t_min, beta, D)
    e_slow = _srestart_cond_above(r, t_min, beta, D, tau_est, tau_kill)
    return N * (e_fast * (1.0 - p_s) + e_slow * p_s)


# ---------------------------------------------------------------------------
# Theorem 6 — Speculative-Resume
# ---------------------------------------------------------------------------


def cost_sresume(r, t_min, beta, D, N, tau_est, tau_kill, phi_est):
    """E_S-Resume[T] (Theorem 6), N tasks.

    Straggler branch: original bills tau_est, r of the r+1 resumed attempts
    bill (tau_kill - tau_est) each, the winner bills
    E[max(t_min, (1-phi) * min_{r+1} T)] = t_min + t_min (1-phi)^(beta(r+1)) / (beta(r+1)-1).
    """
    p_s = _p_straggler(t_min, beta, D)
    e_fast = truncated_mean_below(t_min, beta, D)
    nb = beta * (r + 1.0)
    e_win = t_min + t_min * jnp.power(1.0 - phi_est, nb) / (nb - 1.0)
    e_slow = tau_est + r * (tau_kill - tau_est) + e_win
    return N * (e_fast * (1.0 - p_s) + e_slow * p_s)

# Name-keyed dispatch lives in the strategy IR: `repro.strategies.get(name)`
# carries each strategy's cost closure (this module's closed forms for the
# paper trio); `core.utility.cost_of` is the JobSpec-level entry.
