"""A stable argsort whose compile cost does not grow with its length.

XLA's own sort of one long 1-D array is slow to compile for a TPU: about
30 s from 10^5 elements up (v5e compiler, measured ahead of time on the
host CPU), and every dispatch pass of the capacity replay and every
Hadoop / Mantri launch rank holds one. This module sorts the same keys as
a bitonic network over a `(rows, ROW)` view instead:

  * runs of `ROW` elements are ordered by XLA's batched row sort, which
    compiles in about 2 s at any row count;
  * the merge stages whose compare distance spans rows are dense
    row-rolls, compare and select, walked by `lax.fori_loop`s, so the
    program has one copy of each stage whatever the length.

A key is mapped to a uint32 whose unsigned order is JAX's sort order
(-0.0 == 0.0, NaNs equal and last), and the element index is the second
key, so all pairs are distinct and the network's result is the one
stable order. Several keys are sorted least significant first, one
stable pass each (a row sort of three operands compiles several times
slower than two passes of two): `stable_argsort(a, b)` equals
`jnp.lexsort((b, a))` element for element.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

#: length of the runs the batched row sort orders
ROW = 1024

_ALL = 0xFFFFFFFF


def _sortable(x):
    """uint32 whose unsigned order is the order `lax.sort` gives `x`."""
    if jnp.issubdtype(x.dtype, jnp.floating):
        x = x.astype(jnp.float32)
        x = jnp.where(x == 0, jnp.float32(0.0), x)
        x = jnp.where(jnp.isnan(x), jnp.float32(jnp.nan), x)
        b = lax.bitcast_convert_type(x, jnp.uint32)
        return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(0x80000000))
    if x.dtype == jnp.bool_:
        return x.astype(jnp.uint32)
    b = lax.bitcast_convert_type(x.astype(jnp.int32), jnp.uint32)
    return b ^ jnp.uint32(0x80000000)


def _row_sort(cols, descending):
    """Sort each row ascending, or descending where `descending` (rows, 1)."""
    flip = jnp.where(descending, jnp.uint32(_ALL), jnp.uint32(0))
    out = lax.sort(tuple(c ^ flip for c in cols), dimension=1,
                   num_keys=len(cols))
    return tuple(c ^ flip for c in out)


def stable_argsort(*keys):
    """int32 permutation sorting by `keys`, most significant first, ties
    broken by index: `jnp.lexsort(keys[::-1])`, element for element.

    XLA:CPU keeps its own sort (several times faster there than the
    network); every other backend lowers the network."""
    return lax.platform_dependent(
        *keys, cpu=lambda *k: jnp.lexsort(k[::-1]).astype(jnp.int32),
        default=network_argsort)


def network_argsort(*keys):
    """`stable_argsort` by the bitonic network, on any backend."""
    perm = _argsort1(keys[-1])
    for k in keys[-2::-1]:
        perm = perm[_argsort1(k[perm])]
    return perm


def _argsort1(key):
    """Stable argsort of one key by the bitonic network (module doc)."""
    n = key.shape[0]
    if n < 2:
        return jnp.arange(n, dtype=jnp.int32)
    N = 1 << (n - 1).bit_length()
    R = min(ROW, N)
    # the index is the second key: padding (index >= n) sorts after every
    # element, whatever its key
    cols = [jnp.concatenate([_sortable(key),
                             jnp.full((N - n,), _ALL, jnp.uint32)]),
            lax.iota(jnp.uint32, N)]
    cols = [c.reshape(N // R, R) for c in cols]
    row = lax.broadcasted_iota(jnp.int32, (N // R, 1), 0)

    # bitonic phase k = R: alternate the direction of neighbouring runs
    cols = _row_sort(cols, (row & 1) == 1)

    def stage(t, carry):
        (k, i), kr = carry
        s = kr >> (t + 1)                       # compare distance in rows
        lower = (row & s) == 0
        pk, pi = (jnp.where(lower, jnp.roll(c, -s, axis=0),
                            jnp.roll(c, s, axis=0)) for c in (k, i))
        want_min = lower == ((row & kr) == 0)
        keep = ((k < pk) | ((k == pk) & (i < pi))) == want_min
        return (jnp.where(keep, k, pk), jnp.where(keep, i, pi)), kr

    def phase(m, cols):
        kr = jnp.int32(1) << m                  # bitonic size k = kr * R
        cols, _ = lax.fori_loop(0, m, stage, (cols, kr))
        return _row_sort(cols, (row & kr) != 0)

    cols = lax.fori_loop(1, (N // R).bit_length(), phase, tuple(cols))
    return lax.bitcast_convert_type(cols[1].reshape(-1)[:n], jnp.int32)
