"""Banded chaining DP as a Pallas TPU kernel.

MARS runs the chaining dynamic program on word-serial Arithmetic Units next
to the anchors in SSD-DRAM (paper Section 6.4).  The TPU analogue keeps a
block of reads' sorted anchors resident in VMEM and walks them with a
fori_loop: the anchor walk is the sequential axis, the reads of the block
are the sublanes and the band (B predecessors) is the lane dimension.

Band state is a RING BUFFER: the carried loop state is the four (RB, B)
band planes (f/diag/t/q of the last B anchors); anchor i occupies slot
i % B and each step overwrites that one fixed slot with a lane-mask select.
Anchor i's (t, q, valid) are read with a lane-masked sum and its (f, diag)
written with a lane-masked select on the (RB, A) output planes — Mosaic has
no dynamic lane indexing.  argmax ties resolve to the OLDEST band anchor via
the explicit age rank k = (slot - i) mod B, matching the age-ordered window
of core/chaining.chain_dp{,_reference} bit for bit.

Block layout: ``lanes.row_block`` reads per program; q/t/valid (RB, A)
int32 blocks.  The arithmetic matches core/chaining.chain_dp exactly (same
jnp ops).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels as K
from repro.kernels import lanes

NEG = -1e9
_SENT = -(1 << 30)


def _kernel(q_ref, t_ref, v_ref, f_ref, d_ref, *, B: int,
            max_gap: int, gap_cost: float, skip_cost: float,
            anchor_score: float):
    q, t, v = q_ref[...], t_ref[...], v_ref[...]    # (RB, A) int32
    rb, A = q.shape
    lane_a = lanes.lane_iota(q)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rb, B), 1)

    def pick(x, i):                                  # x[:, i] as (RB, 1)
        return jnp.sum(jnp.where(lane_a == i, x, 0), axis=1, keepdims=True)

    def step(i, carry):
        bf, bd, bt, bq, f, d = carry
        ti, qi, vi = pick(t, i), pick(q, i), pick(v, i) != 0
        dt = ti - bt
        dq = qi - bq
        ok = (dt > 0) & (dq > 0) & (dt <= max_gap) & (dq <= max_gap)
        gap = jnp.abs(dt - dq).astype(jnp.float32)
        skip = jnp.minimum(dt, dq).astype(jnp.float32)
        cand = bf - gap_cost * gap - skip_cost * skip
        cand = jnp.where(ok & (bf > NEG / 2), cand, NEG)
        best = jnp.max(cand, axis=1, keepdims=True)
        # oldest-first tie-break: age rank k=0 is the oldest band slot
        k = (lane - i) % B
        kbest = jnp.min(jnp.where(cand == best, k, B), axis=1, keepdims=True)
        dbest = jnp.sum(jnp.where((cand == best) & (k == kbest), bd, 0),
                        axis=1, keepdims=True)
        fi = anchor_score + jnp.maximum(best, 0.0)
        fi = jnp.where(vi, fi, NEG)
        di = jnp.where(best > 0.0, dbest, ti - qi)
        at_i = lane_a == i
        wr = lane == i % B
        return (jnp.where(wr, fi, bf), jnp.where(wr, di, bd),
                jnp.where(wr, ti, bt), jnp.where(wr, qi, bq),
                jnp.where(at_i, fi, f), jnp.where(at_i, di, d))

    init = (jnp.full((rb, B), NEG, jnp.float32),
            jnp.zeros((rb, B), jnp.int32),
            jnp.full((rb, B), _SENT, jnp.int32),
            jnp.full((rb, B), _SENT, jnp.int32),
            jnp.zeros((rb, A), jnp.float32),
            jnp.zeros((rb, A), jnp.int32))
    *_, f, d = jax.lax.fori_loop(0, A, step, init)
    f_ref[...] = f
    d_ref[...] = d


@functools.partial(jax.jit,
                   static_argnames=("B", "max_gap", "gap_cost", "skip_cost",
                                    "anchor_score", "interpret"))
def chain_dp_kernel(q: jnp.ndarray, t: jnp.ndarray, valid: jnp.ndarray, *,
                    B: int, max_gap: int, gap_cost: float, skip_cost: float,
                    anchor_score: float, interpret: bool | None = None):
    """q, t: (R, A) int32 sorted anchors; valid: (R, A) bool.

    Returns (f (R, A) f32, diag0 (R, A) int32).
    """
    if interpret is None:
        interpret = K.INTERPRET
    R, A = q.shape
    rb = lanes.row_block(R)
    q, t, v = (lanes.pad_rows(x, rb) for x in (q, t, valid.astype(jnp.int32)))
    rp = q.shape[0]
    kern = functools.partial(_kernel, B=B, max_gap=max_gap,
                             gap_cost=gap_cost, skip_cost=skip_cost,
                             anchor_score=anchor_score)
    block = pl.BlockSpec((rb, A), lambda r: (r, 0))
    f, d = pl.pallas_call(
        kern,
        grid=(rp // rb,),
        in_specs=[block, block, block],
        out_specs=[block, block],
        out_shape=[
            jax.ShapeDtypeStruct((rp, A), jnp.float32),
            jax.ShapeDtypeStruct((rp, A), jnp.int32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(q, t, v)
    return f[:R], d[:R]
