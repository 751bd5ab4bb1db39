"""Pallas TPU kernel: fused Algorithm-1 grid solve — PoCD, cost, utility,
and the per-job argmax in ONE pass over the (job x r) grid.

The XLA reference path (`strategies.spec.grid_solve`, backend="xla")
evaluates the utility grid, argmaxes it, and then re-evaluates pocd/cost
at r* as three separate fusion islands with the (J, r_max) grid
materialized between them. This kernel keeps one job tile's grid entirely
in VMEM: an (r_max, Jt) utility surface is built from the spec's analytic
closed forms (the same `utility_of` / `pocd_of_spec` / `cost_of_spec`
closures — there is deliberately no second copy of the math), reduced to
r* along the sublane axis, and pocd/cost/utility at r* written out, so the
grid never touches HBM.

Composite strategies (spec.components, e.g. `adaptive`) fold their
sub-strategy `choose` argmax into the same pass: per-sub utility surfaces
are built in registers, U = elementwise max over subs (exactly
U_adaptive(r) = max_s U_s(r)), and the winning sub id at r* is selected
with where-masks — `jnp.take_along_axis`, which the XLA closures use, has
no Mosaic lowering, so the fold is the kernel-side form of the same math
and is tested identical on r*/choice.

Tile geometry: r on the sublane axis, jobs on the lane axis,
JOB_TILE=128 jobs per grid step. The wrapper stacks the JobSpec columns
into one lane-dense (N_COLS, J) operand, so each column enters the kernel
as a (1, Jt) row that broadcasts against the (r_max, Jt) utility surface
with no in-kernel reshape; the six outputs leave as two (3, J) slabs (one
f32, one i32). S-Restart's Thm-4 cost integral evaluates a 128-node
Gauss-Legendre quadrature on a leading axis: its intermediate is
(128, r_max, Jt) f32, 4.5 MiB at r_max=65 (r_max pads to 72 sublanes);
it compiles for v5e within the default scoped-VMEM limit
(tests/test_tpu_compile.py). A partial last tile needs no mask: lanes
are independent, and Pallas drops the out-of-range part of a block.

Saturation: Algorithm 1's grid is exact only when r_max exceeds the
certified bound (`core.optimizer.r_upper_bound`); an argmax landing on
the last grid point is the one observable symptom of a too-small grid.
The kernel (and the XLA reference) return `sat = (r* == r_max - 1)` per
job so callers can warn/assert instead of silently truncating r*.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import cost as core_cost
from ..core.utility import JobSpec
from ..strategies import cost_of_spec, get, pocd_of_spec, utility_of

JOB_TILE = 128
#: JobSpec field order == row order of the stacked (N_COLS, J) operand
N_COLS = len(JobSpec._fields)


def _sub_specs(spec):
    """The specs whose utility surfaces the kernel evaluates: the spec's
    `components` for a composite (meta) strategy, else the spec itself."""
    if spec.components:
        return tuple(get(n) for n in spec.components)
    return (spec,)


def _first_argmax(vals):
    """Elementwise index of the first maximum over a list of same-shape
    arrays, NaN counting as largest: `jnp.argmax(jnp.stack(vals), 0)`
    without the stack (the XLA closures' form)."""
    best, idx = vals[0], jnp.zeros(vals[0].shape, jnp.int32)
    for k, v in enumerate(vals[1:], start=1):
        take = (v > best) | (jnp.isnan(v) & ~jnp.isnan(best))
        best = jnp.where(take, v, best)
        idx = jnp.where(take, k, idx)
    return idx


def _kernel(cols_ref, gl_u_ref, gl_w_ref, f_ref, i_ref, *, strategy: str,
            r_max: int):
    # (1, Jt) job rows broadcast against the (r_max, Jt) grid
    job = JobSpec(*(cols_ref[k:k + 1, :] for k in range(N_COLS)))
    Jt = cols_ref.shape[1]
    spec = get(strategy)
    subs = _sub_specs(spec)

    # Thm-4's Gauss-Legendre nodes enter as (K, 1, 1) operands (Pallas
    # forbids captured consts); the closures read them through this scope
    with core_cost.quadrature_inputs(gl_u_ref[...], gl_w_ref[...]):
        row = jax.lax.broadcasted_iota(jnp.int32, (r_max, Jt), 0)
        rs = row.astype(jnp.float32)
        u = utility_of(subs[0], rs, job)
        for s in subs[1:]:
            u = jnp.maximum(u, utility_of(s, rs, job))  # max_s U_s(r)
        # r* = first argmax over the sublane axis (NaN counts as largest,
        # as in jnp.argmax)
        top = jnp.max(u, axis=0, keepdims=True)
        hit = (u == top) | jnp.isnan(u)
        i = jnp.min(jnp.where(hit, row, r_max), axis=0, keepdims=True)
        u_star = jnp.max(jnp.where(row == i, u, -jnp.inf), axis=0,
                         keepdims=True)

        # pocd/cost at the solved r, evaluated at the scalar r* as the
        # XLA reference does
        rf = i.astype(jnp.float32)                     # (1, Jt)
        if len(subs) == 1:
            choice = jnp.zeros_like(i)
            p_star = pocd_of_spec(spec, rf, job)
            c_star = cost_of_spec(spec, rf, job)
        else:
            choice = _first_argmax([utility_of(s, rf, job) for s in subs])
            p_star = pocd_of_spec(subs[0], rf, job)
            c_star = cost_of_spec(subs[0], rf, job)
            for k, s in enumerate(subs[1:], start=1):
                hit_k = choice == k
                p_star = jnp.where(hit_k, pocd_of_spec(s, rf, job), p_star)
                c_star = jnp.where(hit_k, cost_of_spec(s, rf, job), c_star)
    sat = (i >= r_max - 1).astype(jnp.int32)

    f_ref[0:1, :] = u_star
    f_ref[1:2, :] = p_star
    f_ref[2:3, :] = c_star
    i_ref[0:1, :] = i
    i_ref[1:2, :] = choice
    i_ref[2:3, :] = sat


def grid_solve_pallas(spec, jobs, r_max: int, *, interpret=True):
    """Fused Algorithm-1 solve of a batched JobSpec on the named spec.

    jobs: batched JobSpec (stacked (J,) leaves). Returns
    (r_opt i32, choice i32, utility, pocd, cost, sat i32), all (J,) —
    `choice` is the composite sub-strategy pick (zeros for pure specs),
    `sat` flags jobs whose argmax saturated at the grid edge.
    """
    cols = jnp.stack([jnp.asarray(c, jnp.float32) for c in jobs])
    J = int(cols.shape[1])
    gl_u, gl_w = (x.reshape(-1, 1, 1) for x in core_cost._GL_ACTIVE)
    K = int(gl_u.shape[0])
    kernel = functools.partial(_kernel, strategy=spec.name,
                               r_max=int(r_max))
    gl_spec = pl.BlockSpec((K, 1, 1), lambda i: (0, 0, 0))  # every tile
    out_spec = pl.BlockSpec((3, JOB_TILE), lambda i: (0, i))
    f, i = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(J, JOB_TILE),),
        in_specs=[pl.BlockSpec((N_COLS, JOB_TILE), lambda i: (0, i)),
                  gl_spec, gl_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((3, J), jnp.float32),
                   jax.ShapeDtypeStruct((3, J), jnp.int32)],
        interpret=interpret,
    )(cols, gl_u, gl_w)
    return i[0], i[1], f[0], f[1], f[2], i[2]
