"""bitonic_sort kernel vs oracle: shape sweeps + property test.

The network sorts (key, value) pairs lexicographically; the values repeat
often enough among equal keys that the second key decides."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:                       # deterministic fallback
    from repro.testing import given, settings
    from repro.testing import strategies as st

from repro.kernels.bitonic_sort import ops, ref


def _pairs(rng, shape, lo, hi):
    keys = rng.integers(lo, hi, size=shape).astype(np.int32)
    keys[..., ::4] = keys[..., :1]            # equal keys: the value decides
    vals = rng.integers(0, 256, size=shape).astype(np.int32)
    return jnp.asarray(keys), jnp.asarray(vals)


def _assert_sorted_pairs(got, want):
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("b,l", [(1, 128), (4, 100), (2, 1000), (3, 4096),
                                 (1, 7), (8, 129), (1, 8192)])
def test_sort_sweep(b, l):
    rng = np.random.default_rng(b * 100 + l)
    keys, vals = _pairs(rng, (b, l), -2**30, 2**31 - 2)
    _assert_sorted_pairs(ops.sort_batch(keys, vals),
                         ref.sort_ref(keys, vals))


def test_sort_vmap():
    rng = np.random.default_rng(9)
    keys, vals = _pairs(rng, (5, 512), 0, 2**31 - 2)
    _assert_sorted_pairs(jax.vmap(ops.sort1d)(keys, vals),
                         ref.sort_ref(keys, vals))


@pytest.mark.slow
@settings(max_examples=20, deadline=None)
@given(st.lists(st.tuples(st.integers(-2**31, 2**31 - 2),
                          st.integers(0, 3)), min_size=1, max_size=300))
def test_sort_is_ordered_permutation(xs):
    keys = jnp.asarray(np.array([k for k, _ in xs], np.int32))
    vals = jnp.asarray(np.array([v for _, v in xs], np.int32))
    k, v = (np.asarray(a).tolist() for a in ops.sort1d(keys, vals))
    assert sorted(xs) == list(zip(k, v))


def test_duplicates_and_sentinels():
    keys = jnp.asarray(np.array([5, 5, 5, 0x7FFFFFFF, -1, 0x7FFFFFFF, 5],
                                np.int32))
    vals = jnp.asarray(np.array([3, 1, 255, 255, 0, 2, 1], np.int32))
    _assert_sorted_pairs(ops.sort1d(keys, vals), ref.sort_ref(keys, vals))
