"""Bitonic sort network as a Pallas TPU kernel.

MARS sorts anchors with an in-controller bitonic Sorter (<=128 elements)
feeding a streaming bitonic Merger (paper Section 6.4).  On TPU the same
network maps onto vector registers: the compare-exchange partner at XOR
distance j is one of two lane rotations —

    x[i ^ j]  ==  x[i + j]  if bit j of i is clear,  else  x[i - j]

(``lanes.roll_left`` / ``roll_right``, XLU rotates — no gather), and the
min/max select runs on the VPU.  Stages with k <= 128 correspond to MARS's
Sorter-128 blocks; the k > 128 stages are the Merger's merge passes — one
kernel expresses both units.

The network sorts (key, value) pairs lexicographically: an anchor's t
with its q as the second key, so positions keep all 31 bits.  Block
layout: ``lanes.row_block`` rows of keys and of values per program, two
(RB, L) int32 blocks in VMEM, L a power of two (<= 8192 -> 32 KiB per
row).  Ascending sort; pad both with INT32_MAX.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels as K
from repro.kernels import lanes

MAX_BLOCK = 8192


def _kernel(k_ref, v_ref, ko_ref, vo_ref):
    k, v = k_ref[...], v_ref[...]                    # (RB, L) int32 each
    L = k.shape[1]
    lane = lanes.lane_iota(k)

    def partner(x, is_lo, j):
        return jnp.where(is_lo, lanes.roll_left(x, j), lanes.roll_right(x, j))

    s = 2
    while s <= L:
        j = s // 2
        while j >= 1:
            is_lo = (lane & j) == 0
            pk, pv = partner(k, is_lo, j), partner(v, is_lo, j)
            # ascending run (bit s of i clear) or the final whole-block merge
            take_min = is_lo if s == L else ((lane & s) == 0) == is_lo
            below = (pk < k) | ((pk == k) & (pv < v))
            above = (pk > k) | ((pk == k) & (pv > v))
            take = (take_min & below) | (~take_min & above)
            k, v = jnp.where(take, pk, k), jnp.where(take, pv, v)
            j //= 2
        s *= 2
    ko_ref[...] = k
    vo_ref[...] = v


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitonic_sort(keys: jnp.ndarray, values: jnp.ndarray,
                 interpret: bool | None = None):
    """keys, values: (B, L) int32, L power of two <= MAX_BLOCK.  Sorts each
    row's (key, value) pairs ascending (grid over row blocks; each row =
    one Sorter/Merger stream)."""
    if interpret is None:
        interpret = K.INTERPRET
    B, L = keys.shape
    assert L & (L - 1) == 0 and L <= MAX_BLOCK, L
    rb = lanes.row_block(B)
    kp, vp = lanes.pad_rows(keys, rb), lanes.pad_rows(values, rb)
    block = pl.BlockSpec((rb, L), lambda b: (b, 0))
    ko, vo = pl.pallas_call(
        _kernel,
        grid=(kp.shape[0] // rb,),
        in_specs=[block, block],
        out_specs=[block, block],
        out_shape=[jax.ShapeDtypeStruct(kp.shape, jnp.int32)] * 2,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(kp, vp)
    return ko[:B], vo[:B]
