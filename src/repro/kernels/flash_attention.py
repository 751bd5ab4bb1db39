"""Pallas TPU kernel: tiled online-softmax (flash) attention forward.

The framework's serving/training compute hot spot. Grid is
(batch*q_heads, q_blocks, kv_blocks) with kv as the innermost "arbitrary"
(sequential) dimension: running max/sum/acc live in VMEM scratch and the
output block is written on the last kv step — the canonical TPU flash
pattern (HBM->VMEM streaming of K/V tiles, MXU-aligned 128-multiples).

Supports causal masking, GQA (kv head = q head // q_per_kv via index_map),
and attention-logit softcapping (gemma2). `ref.py` holds the jnp oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            causal: bool, sm_scale: float, block_q: int, block_k: int,
            softcap):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                       # (bq, d)
    k = k_ref[0]                       # (bk, d)
    v = v_ref[0]
    s = jnp.dot(q.astype(jnp.float32), k.astype(jnp.float32).T) * sm_scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                        (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                        (block_q, block_k), 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

    m_prev = m_scr[...]                # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(p, v.astype(jnp.float32))
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, softcap=None, block_q=128,
                    block_k=128, interpret=True):
    """q: (B, H, Sq, D); k, v: (B, K, Sk, D) with H % K == 0.

    Returns (B, H, Sq, D) in q.dtype. Sq % block_q == Sk % block_k == 0.
    """
    B, H, Sq, D = q.shape
    _, K, Sk, _ = k.shape
    assert H % K == 0 and Sq % block_q == 0 and Sk % block_k == 0
    g = H // K
    sm_scale = D ** -0.5
    grid = (B * H, Sq // block_q, Sk // block_k)

    kernel = functools.partial(_kernel, causal=causal, sm_scale=sm_scale,
                               block_q=block_q, block_k=block_k,
                               softcap=softcap)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D),
                         lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda bh, qi, ki, g=g: (bh // g, ki, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda bh, qi, ki, g=g: (bh // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),    # running max
            pltpu.VMEM((block_q, 1), jnp.float32),    # running sum
            pltpu.VMEM((block_q, D), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q.reshape(B * H, Sq, D), k.reshape(B * K, Sk, D),
      v.reshape(B * K, Sk, D)).reshape(B, H, Sq, D)
