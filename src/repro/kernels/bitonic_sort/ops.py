"""Public wrappers for the bitonic sort kernel + its stage-engine backend."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import stages
from repro.kernels.bitonic_sort.bitonic_sort import MAX_BLOCK, bitonic_sort

_PAD = jnp.int32(0x7FFFFFFF)


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def sort_batch(keys: jnp.ndarray, values: jnp.ndarray):
    """keys, values: (B, L) int32 -> each row's (key, value) pairs sorted
    ascending, as two (B, L) arrays."""
    B, L = keys.shape
    Lp = max(128, _next_pow2(L))
    if Lp > MAX_BLOCK:
        # beyond one VMEM block: fall back to XLA sort (documented limit;
        # the distributed pipeline shards anchors well below this).
        return jax.lax.sort((keys, values), dimension=1, num_keys=2)
    if Lp != L:
        pad = jnp.full((B, Lp - L), _PAD, jnp.int32)
        keys = jnp.concatenate([keys, pad], axis=1)
        values = jnp.concatenate([values, pad], axis=1)
    k, v = bitonic_sort(keys.astype(jnp.int32), values.astype(jnp.int32))
    return k[:, :L], v[:, :L]


def sort1d(t: jnp.ndarray, q: jnp.ndarray):
    """(L,) int32 pairs sorted ascending by (t, q).  vmap-safe via
    expand/squeeze."""
    k, v = sort_batch(t.reshape(1, -1), q.reshape(1, -1))
    return k[0], v[0]


def _sort_pallas(state, cfg, index):
    """Stage backend: anchor sort on the bitonic Sorter/Merger kernel."""
    return stages.sort_with(state, cfg, index, sorter=sort1d)


# ``sort1d`` doubles as the fast-path sorter primitive: under the
# select-then-sort ladder (core/pipeline.chain_phase) it receives the (W,)
# selected pairs and sorts a 128/512-lane block instead of the padded full
# E*H block.
stages.register_backend("sort", stages.PALLAS, _sort_pallas,
                        primitive=sort1d)
