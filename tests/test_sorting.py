"""The bitonic network argsort (`repro.sim.sorting`) against XLA's sort.

Both must give the one stable order, element for element: the capacity
replay's dispatch order and the Hadoop / Mantri launch ranks read it, and
a TPU lowers the network where the CPU keeps XLA's sort.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.sim import sorting

_SPECIAL = np.array([0.0, -0.0, 1.5, np.inf, -np.inf, np.nan, -np.nan, 2.0,
                     -3.0], np.float32)


def _keys(n, seed=0):
    rng = np.random.default_rng(seed)
    return {"special": rng.choice(_SPECIAL, n),
            "ints": rng.integers(-3, 4, n).astype(np.int32),
            "normal": rng.normal(size=n).astype(np.float32)}


@pytest.mark.parametrize("n", [1, 2, 3, 100, 1024, 1025, 5000])
@pytest.mark.parametrize("names", [("special",), ("normal",),
                                   ("ints", "special"), ("ints", "normal")])
def test_network_argsort_matches_lexsort(n, names):
    k = _keys(n)
    keys = tuple(jnp.asarray(k[m]) for m in names)
    got = jax.jit(sorting.network_argsort)(*keys)
    want = jnp.lexsort(keys[::-1])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("row", [4, 16])
def test_network_argsort_spans_many_rows(monkeypatch, row):
    """Small rows force every cross-row merge stage to run."""
    monkeypatch.setattr(sorting, "ROW", row)
    k = _keys(777, seed=1)
    a, b = jnp.asarray(k["ints"]), jnp.asarray(k["special"])
    np.testing.assert_array_equal(
        np.asarray(sorting.network_argsort(a, b)),
        np.asarray(jnp.lexsort((b, a))))


def test_stable_argsort_is_int32_lexsort_on_cpu():
    k = _keys(300, seed=2)
    a, b = jnp.asarray(k["ints"]), jnp.asarray(k["normal"])
    got = jax.jit(sorting.stable_argsort)(a, b)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.lexsort((b, a))))
