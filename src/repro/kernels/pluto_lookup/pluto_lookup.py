"""pLUTo-style LUT lookup as an MXU one-hot matmul sweep.

MARS's Querying Unit (paper Section 6.3 / pLUTo) answers `out[i] =
table[idx[i]]` by sweeping DRAM rows: activate each candidate row, compare
its index against the keys latched in the source row buffer, and let gated
sense amplifiers copy matching values out.  The TPU-native analogue keeps
the table in VMEM tiles and expresses the same row sweep as a matmul:

    out = onehot(idx - tile_offset) @ table_tile            (MXU)

accumulated over table tiles (the grid's inner dimension).  Because f32
matmuls are only exact below 2^24, 32-bit table values are split into two
16-bit halves and recombined — two matmuls per tile, both exact.

Block layout: queries (1, BQ) int32, table tile (1, BT) int32,
output (1, BQ) int32 accumulated across the table-tile grid axis.

``pluto_lookup_rows`` is the packed-row variant (the cheap-phase fast
path): the table holds W-word rows ((W, N) int32) and ONE sweep answers
every query with its whole row — exactly pLUTo's row-wide activation,
where the gated sense amplifiers copy the full DRAM row, not one word.
The W x 2 16-bit half-planes fold into a single (BT, 2W) operand so each
tile still costs one one-hot matmul.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels as K

BQ = 256          # queries per block (2 sublanes x 128 lanes)
BT = 512          # table entries per block


def _kernel(idx_ref, table_ref, out_ref):
    ti = pl.program_id(1)                      # table-tile index

    @pl.when(ti == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    idx = idx_ref[...]                         # (1, BQ) int32
    tab = table_ref[...]                       # (1, BT) int32
    offset = ti * BT
    local = idx - offset                       # (1, BQ)
    # one-hot match matrix (BQ, BT): row-sweep compare of pLUTo
    lanes = jax.lax.broadcasted_iota(jnp.int32, (BQ, BT), 1)
    onehot = (local.reshape(BQ, 1) == lanes).astype(jnp.float32)
    # split 32-bit values into exact f32 halves (<= 2^16)
    hi = jnp.right_shift(tab, 16).astype(jnp.float32).reshape(BT, 1)
    lo = jnp.bitwise_and(tab, 0xFFFF).astype(jnp.float32).reshape(BT, 1)
    got_hi = jax.lax.dot(onehot, hi, precision=jax.lax.Precision.HIGHEST)
    got_lo = jax.lax.dot(onehot, lo, precision=jax.lax.Precision.HIGHEST)
    val = (got_hi.astype(jnp.int32) << 16) | got_lo.astype(jnp.int32)
    out_ref[...] += val.reshape(1, BQ)


def _kernel_rows(idx_ref, table_ref, out_ref, *, W: int):
    ti = pl.program_id(1)                      # table-tile index

    @pl.when(ti == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    idx = idx_ref[...]                         # (1, BQ) int32
    tab = table_ref[...]                       # (W, BT) int32
    offset = ti * BT
    local = idx - offset                       # (1, BQ)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (BQ, BT), 1)
    onehot = (local.reshape(BQ, 1) == lanes).astype(jnp.float32)
    # all W rows' 16-bit halves as one (BT, 2W) operand: one matmul per tile
    hi = jnp.right_shift(tab, 16).astype(jnp.float32)          # (W, BT)
    lo = jnp.bitwise_and(tab, 0xFFFF).astype(jnp.float32)
    planes = jnp.concatenate([hi, lo], axis=0).T               # (BT, 2W)
    got = jax.lax.dot(onehot, planes, precision=jax.lax.Precision.HIGHEST)
    val = ((got[:, :W].astype(jnp.int32) << 16)
           | got[:, W:].astype(jnp.int32))                     # (BQ, W)
    out_ref[...] += val.T                                      # (W, BQ)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pluto_lookup_rows(table: jnp.ndarray, idx: jnp.ndarray,
                      interpret: bool | None = None) -> jnp.ndarray:
    """table: (W, N) int32 packed rows, idx: (Q,) int32 in [0, N).
    Returns (W, Q) int32 — every word of each queried row from ONE table
    sweep.  N and Q are padded to BT/BQ multiples by ops.lookup."""
    if interpret is None:
        interpret = K.INTERPRET
    Q, (W, N) = idx.shape[0], table.shape
    assert Q % BQ == 0 and N % BT == 0, (Q, N)
    grid = (Q // BQ, N // BT)
    out = pl.pallas_call(
        functools.partial(_kernel_rows, W=W),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, BQ), lambda qi, ti: (0, qi)),
            pl.BlockSpec((W, BT), lambda qi, ti: (0, ti)),
        ],
        out_specs=pl.BlockSpec((W, BQ), lambda qi, ti: (0, qi)),
        out_shape=jax.ShapeDtypeStruct((W, Q), jnp.int32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(idx.reshape(1, Q), table)
    return out


@functools.partial(jax.jit, static_argnames=("interpret",))
def pluto_lookup(table: jnp.ndarray, idx: jnp.ndarray,
                 interpret: bool | None = None) -> jnp.ndarray:
    """table: (N,) int32, idx: (Q,) int32 in [0, N). Returns (Q,) int32.

    N and Q are padded to BT/BQ multiples by ops.lookup; call through there.
    """
    if interpret is None:
        interpret = K.INTERPRET
    Q, N = idx.shape[0], table.shape[0]
    assert Q % BQ == 0 and N % BT == 0, (Q, N)
    grid = (Q // BQ, N // BT)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, BQ), lambda qi, ti: (0, qi)),
            pl.BlockSpec((1, BT), lambda qi, ti: (0, ti)),
        ],
        out_specs=pl.BlockSpec((1, BQ), lambda qi, ti: (0, qi)),
        out_shape=jax.ShapeDtypeStruct((1, Q), jnp.int32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(idx.reshape(1, Q), table.reshape(1, N))
    return out.reshape(Q)
