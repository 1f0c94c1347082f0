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

Block layout: ``lanes.row_block`` rows of anchor keys per program, (RB, L)
int32 in VMEM, L a power of two (<= 8192 -> 32 KiB per row).  Ascending
sort; pad with INT32_MAX.
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


def _kernel(x_ref, out_ref):
    x = x_ref[...]                                   # (RB, L) int32
    L = x.shape[1]
    lane = lanes.lane_iota(x)
    k = 2
    while k <= L:
        j = k // 2
        while j >= 1:
            is_lo = (lane & j) == 0
            p = jnp.where(is_lo, lanes.roll_left(x, j), lanes.roll_right(x, j))
            # ascending run (bit k of i clear) or the final whole-block merge
            take_min = is_lo if k == L else ((lane & k) == 0) == is_lo
            x = jnp.where(take_min, jnp.minimum(x, p), jnp.maximum(x, p))
            j //= 2
        k *= 2
    out_ref[...] = x


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitonic_sort(keys: jnp.ndarray, interpret: bool | None = None):
    """keys: (B, L) int32, L power of two <= MAX_BLOCK.  Sorts each row
    ascending (grid over row blocks; each row = one Sorter/Merger stream)."""
    if interpret is None:
        interpret = K.INTERPRET
    B, L = keys.shape
    assert L & (L - 1) == 0 and L <= MAX_BLOCK, L
    rb = lanes.row_block(B)
    xp = lanes.pad_rows(keys, rb)
    out = pl.pallas_call(
        _kernel,
        grid=(xp.shape[0] // rb,),
        in_specs=[pl.BlockSpec((rb, L), lambda b: (b, 0))],
        out_specs=pl.BlockSpec((rb, L), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct(xp.shape, jnp.int32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(xp)
    return out[:B]
