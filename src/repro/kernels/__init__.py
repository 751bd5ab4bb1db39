"""Pallas TPU kernels (validated in interpret mode on CPU, compiled for
v5e in tests/test_tpu_compile.py):
pocd_mc — the paper's Monte-Carlo evaluation hot spot as an on-chip MapReduce;
grid_solve — Algorithm 1's (job x r) utility grid + argmax fused in one pass;
flash_attention — tiled online-softmax attention for the serving/train path.
Each has a jit wrapper in ops.py and a pure-jnp oracle (ref.py, or the
XLA reference path in strategies.spec for grid_solve).
"""
from . import ops, ref
from .ops import MODES, pocd_mc, pocd_mc_all, attention, grid_solve_fused
