"""Event detection (signal -> events) as a Pallas TPU kernel.

Implements MARS's fixed-point event-detection stage (paper Sections 5.2 +
6.2): the early-quantized int16 signal is segmented with the integer
(sqrt-free) t-statistic boundary test and reduced to per-segment means.

TPU mapping of the near-DRAM Arithmetic Unit:
  * word-serial window sums  -> lane-rotated adds on the VPU (w <= 8 shifts);
  * per-sample boundary test -> branch-free integer compare vector;
  * the peak-pick            -> shifted max-accumulation;
  * event-id assignment      -> Hillis-Steele prefix sum (log2 S shift-adds);
  * segment mean reduction   -> one-hot matmul on the MXU:
        sums = x (1,S) @ onehot(eid) (S,E).

Block layout: ``lanes.row_block`` reads per program — signal (RB, S) int32
Q-format in VMEM, outputs (RB, E) f32 means and (RB, 1) int32 event counts.
``detect_rows`` is the block body, shared with the fused cheap-phase kernel.
All arithmetic matches core/events.py (the pure-jnp oracle) bit-for-bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels as K
from repro.kernels import lanes

_NEG = -3.0e38  # python float: jnp scalars would be captured as constants
_HIGHEST = jax.lax.Precision.HIGHEST


def detect_rows(x: jnp.ndarray, *, E: int, w: int, tau2: int, eps: int,
                peak_r: int, frac_bits: int):
    """x: (RB, S) int32 Q-format signal rows.

    Returns (means (RB, E) f32 normalized units, n_events (RB, 1) int32)."""
    rb, S = x.shape

    # ---- windowed sums (truncated windows at the borders == zero fill) ----
    xx = x * x
    sum_r = jnp.zeros_like(x)
    sq_r = jnp.zeros_like(x)
    sum_l = jnp.zeros_like(x)
    sq_l = jnp.zeros_like(x)
    for d in range(w):
        sum_r = sum_r + lanes.shift_left(x, d, 0)        # x[i+d]
        sq_r = sq_r + lanes.shift_left(xx, d, 0)
        sum_l = sum_l + lanes.shift_right(x, d + 1, 0)   # x[i-1-d]
        sq_l = sq_l + lanes.shift_right(xx, d + 1, 0)

    # ---- integer boundary test (events.boundary_mask_fixed) ----
    diff = (sum_r - sum_l) >> 2
    ssd_l = w * sq_l - sum_l * sum_l
    ssd_r = w * sq_r - sum_r * sum_r
    lhs = diff * diff * w
    rhs = tau2 * (((ssd_l + ssd_r) >> 4) + eps)
    above = lhs > rhs
    score = lhs.astype(jnp.float32) / (rhs.astype(jnp.float32) + 1.0)

    # ---- peak pick: windowed max via shifts ----
    wmax = score
    for d in range(1, peak_r + 1):
        wmax = jnp.maximum(wmax, lanes.shift_left(score, d, _NEG))
        wmax = jnp.maximum(wmax, lanes.shift_right(score, d, _NEG))
    lmax = score
    for d in range(1, peak_r + 1):
        lmax = jnp.maximum(lmax, lanes.shift_right(score, d, _NEG))
    boundary = (score >= wmax) & (score >= lmax) & above

    # ---- event ids: inclusive prefix sum (nondecreasing: max == last) ----
    eid = lanes.prefix_sum(boundary.astype(jnp.int32))
    n_events = jnp.minimum(jnp.max(eid, axis=1, keepdims=True) + 1, E)
    eid = jnp.minimum(eid, E - 1)                       # (RB, S)

    # ---- segment means: one-hot matmul on the MXU, one row at a time ----
    bins = jax.lax.broadcasted_iota(jnp.int32, (S, E), 1)
    xf = x.astype(jnp.float32)                          # exact: |x| < 2^12
    ones = jnp.ones((1, S), jnp.float32)
    rows = []
    for r in range(rb):
        onehot = (eid[r:r + 1].reshape(S, 1) == bins).astype(jnp.float32)
        sums = jax.lax.dot(xf[r:r + 1], onehot, precision=_HIGHEST)
        cnts = jax.lax.dot(ones, onehot, precision=_HIGHEST)
        rows.append(sums / jnp.maximum(cnts, 1.0) / float(1 << frac_bits))
    means = rows[0] if rb == 1 else jnp.concatenate(rows, axis=0)
    return means, n_events


def _kernel(xq_ref, means_ref, nev_ref, **params):
    means, n_events = detect_rows(xq_ref[...], **params)
    means_ref[...] = means
    nev_ref[...] = n_events


@functools.partial(jax.jit,
                   static_argnames=("E", "w", "tau2", "eps", "peak_r",
                                    "frac_bits", "interpret"))
def event_detect_fixed(xq: jnp.ndarray, *, E: int, w: int, tau2: int,
                       eps: int, peak_r: int, frac_bits: int,
                       interpret: bool | None = None):
    """xq: (R, S) int16/int32 Q-format quantized signal.

    Returns (means (R, E) f32 normalized units, n_events (R,) int32).
    """
    if interpret is None:
        interpret = K.INTERPRET
    R, S = xq.shape
    rb = lanes.row_block(R)
    xp = lanes.pad_rows(xq.astype(jnp.int32), rb)
    rp = xp.shape[0]
    kern = functools.partial(_kernel, E=E, w=w, tau2=tau2, eps=eps,
                             peak_r=peak_r, frac_bits=frac_bits)
    means, nev = pl.pallas_call(
        kern,
        grid=(rp // rb,),
        in_specs=[pl.BlockSpec((rb, S), lambda r: (r, 0))],
        out_specs=[
            pl.BlockSpec((rb, E), lambda r: (r, 0)),
            pl.BlockSpec((rb, 1), lambda r: (r, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rp, E), jnp.float32),
            jax.ShapeDtypeStruct((rp, 1), jnp.int32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(xp)
    return means[:R], nev[:R].reshape(R)
