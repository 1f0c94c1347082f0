"""Fused cheap-phase mega-kernel: detect -> quantize -> seed -> query -> vote.

One `pl.pallas_call` executes the whole cheap phase for one read per grid
step without leaving the kernel.  The quantized signal row is staged into
VMEM by the grid pipeline; event means, quantized symbols and seed keys
live in registers/scratch instead of round-tripping through HBM between
stage launches; and the two index tables stay in `pl.ANY` memory and are
streamed tile-by-tile through VMEM scratch with double-buffered
`pltpu.make_async_copy` DMA — the `emit_pipeline` idiom spelled out by
hand: while tile t is being probed (one-hot matmul gather, split into exact
hi/lo 16-bit f32 planes), the DMA for tile t+1 is already in flight.  This
mirrors the HotTileCache's host->device prefetch one level down, and
MARS's flash-load/compute overlap one level up.

The two tables are (2, N) int32 row pairs, so each is ONE sweep: the
bucket table holds [bucket_start[b], bucket_start[b+1]] (both boundaries
of bucket b) and the entry table is the packed entry plane
[key|cnt, t_pos].  A read's queries lie on the lane axis — E buckets, then
E*H entry slots — and each probe is a (4, bt) @ (bt, Q) matmul against the
transposed one-hot, so no value ever changes layout between lanes and
sublanes.

The math is copied operation-for-operation from the per-stage path so the
fusion is bit-identical:

    detect     kernels/event_detect/event_detect.py::detect_rows (shared)
    quantize   core/quantization.py::quantize_events_fixed
    seed       core/hashing.py::pack_seeds (+ mix32, minimizer_mask)
    query      core/seeding.py::query_index / unpack_entries / match_entries
    vote       core/vote.py::vote_filter

The vote histogram is built ``_VOTE_CHUNK`` bins at a time with integer
lane/sublane reductions, so no (E*H, vote_bins) one-hot is ever whole.
Tile width is `DEFAULT_TILE`, sized for scoped VMEM on Mosaic; tests
pass smaller tiles.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels as K
from repro.core import hashing
from repro.core.vote import DIAG_SHIFT
from repro.kernels import lanes
from repro.kernels.event_detect.event_detect import detect_rows

_HIGHEST = jax.lax.Precision.HIGHEST
_VOTE_CHUNK = 256       # vote bins per histogram pass

# Column order of the fused kernel's per-read counter plane.
COUNTER_COLS = (
    "n_events", "n_seeds", "n_bucket_probes", "n_hits_raw",
    "n_hits_postfreq", "n_hits_exact", "n_votes_cast",
    "n_anchors_postvote", "n_votes_clipped",
)


@dataclasses.dataclass(frozen=True)
class FusedTile:
    """Block-shape choice for the mega-kernel.

    bt — index-tile width in entries for the double-buffered DMA sweeps;
    the entry sweep's one-hot is (bt, E*H) f32 in VMEM.
    """
    bt: int


# One geometry for Mosaic and interpret mode: on Mosaic the entry sweep's
# (bt, E*H) one-hot and its compare stay within scoped VMEM at D1 widths
# (E*H = 3072); in interpret mode the CPU parity suite still walks the
# multi-tile and partial-tail paths.
DEFAULT_TILE = FusedTile(bt=512)


def _repeat(x, h):
    """(1, E) -> (1, E*h): each lane repeated h times, in order."""
    e = x.shape[1]
    return jnp.broadcast_to(x.reshape(1, e, 1), (1, e, h)).reshape(1, e * h)


def _sweep_gather(src_ref, buf, sem, n_tiles, bt, q_row):
    """Double-buffered DMA sweep-gather over a (2, n_tiles*bt) table.

    Streams the table tile-by-tile from `pl.ANY` memory into the 2-slot
    VMEM scratch `buf`, starting the copy of tile t+1 before probing tile
    t (hand-rolled `pltpu.emit_pipeline`).  Each tile is probed with a
    one-hot f32 matmul gather, exact because the int32 values are split
    into hi/lo 16-bit planes (<= 2^16 in f32) and out-of-tile queries
    contribute zero columns.

    q_row: (1, Q) int32 global column indices (pre-clipped in range).
    Returns (2, Q) int32: both table words of every queried column.
    """
    q = q_row.shape[1]

    def dma(slot, t):
        return pltpu.make_async_copy(
            src_ref.at[:, pl.ds(t * bt, bt)], buf.at[slot], sem.at[slot])

    dma(0, 0).start()
    rows = jax.lax.broadcasted_iota(jnp.int32, (bt, q), 0)

    def body(t, acc):
        slot = jax.lax.rem(t, 2)

        @pl.when(t + 1 < n_tiles)
        def _():
            dma(1 - slot, t + 1).start()

        dma(slot, t).wait()
        tab = buf[slot]                                   # (2, bt) int32
        onehot = (q_row - t * bt == rows).astype(jnp.float32)   # (bt, Q)
        planes = jnp.concatenate(
            [jnp.right_shift(tab, 16).astype(jnp.float32),
             jnp.bitwise_and(tab, 0xFFFF).astype(jnp.float32)], axis=0)
        return acc + jax.lax.dot(planes, onehot, precision=_HIGHEST)

    acc = jax.lax.fori_loop(0, n_tiles, body,
                            jnp.zeros((4, q), jnp.float32))
    return (jnp.left_shift(acc[0:2].astype(jnp.int32), 16)
            | acc[2:4].astype(jnp.int32))


def _kernel(xq_ref, bs_ref, ent_ref, tpos_ref, hit_ref, cnt_ref,
            bs_buf, ent_buf, bs_sem, ent_sem, *,
            n_ev_max, hits, tw, tau2, eps, peak_r, frac_bits,
            seed_w, seed_q, minimizer_r, levels, clip_q, step_q,
            n_buckets, n_entries, thresh_freq, use_freq, use_vote,
            vlog2, nbins, thresh_vote, bt, nt_bs, nt_ent):
    e, h = n_ev_max, hits
    eh = e * h
    i32 = jnp.int32

    # ---- detect (event_detect.detect_rows on this read's row) ------------
    means, nev = detect_rows(xq_ref[...], E=e, w=tw, tau2=tau2, eps=eps,
                             peak_r=peak_r, frac_bits=frac_bits)

    # ---- quantize (quantization.quantize_events_fixed) -------------------
    eq = jnp.round(means * (1 << frac_bits)).astype(i32)  # (1, E)
    iota_e = lanes.lane_iota(eq)
    v = (iota_e < nev).astype(i32)
    n = jnp.maximum(jnp.sum(v, axis=1, keepdims=True), 1)
    mean = jnp.sum(eq * v, axis=1, keepdims=True) // n
    dlt = eq - mean
    d2 = dlt >> 1
    var = (jnp.sum(d2 * d2 * v, axis=1, keepdims=True) // n) << 2
    std = jax.lax.fori_loop(
        0, 24, lambda _, g: (g + var // jnp.maximum(g, 1)) // 2,
        jnp.maximum(var, 1))
    std = jnp.maximum(std, 1)
    z_q = jnp.clip((dlt << frac_bits) // std, -clip_q, clip_q - 1)
    sym = jnp.clip((z_q + clip_q) // max(step_q, 1), 0, levels - 1)

    # ---- seed (hashing.pack_seeds + mix32 + minimizer_mask) --------------
    su = sym.astype(jnp.uint32)
    key = jnp.zeros_like(su)
    for j in range(seed_w):
        key = (key << seed_q) | lanes.roll_left(su, j)   # circular, as jnp.roll
    key = hashing.mix32(key)
    seed_valid = (iota_e + seed_w) <= nev
    if minimizer_r > 0:
        # unsigned min in the signed domain (flip the sign bit): Mosaic has
        # no unsigned vector min
        kv = jnp.where(seed_valid,
                       jax.lax.bitcast_convert_type(key, i32) ^ (-1 << 31),
                       (1 << 31) - 1)
        wmin = kv
        for d in range(1, minimizer_r + 1):
            wmin = jnp.minimum(wmin, lanes.shift_left(kv, d, (1 << 31) - 1))
            wmin = jnp.minimum(wmin, lanes.shift_right(kv, d, (1 << 31) - 1))
        seed_valid = seed_valid & (kv == wmin)

    # ---- query (seeding.query_index on the two streamed tables) ----------
    mask_u = jnp.uint32(n_buckets - 1)
    bucket = (key & mask_u).astype(i32)                   # (1, E)
    se = _sweep_gather(bs_ref, bs_buf, bs_sem, nt_bs, bt, bucket)
    start = se[0:1]
    cnt_bucket = se[1:2] - start

    jh = lanes.lane_iota(jnp.zeros((1, eh), i32)) % h      # slot within seed
    idx = jnp.minimum(_repeat(start, h) + jh, n_entries - 1)
    ent = _sweep_gather(ent_ref, ent_buf, ent_sem, nt_ent, bt, idx)
    word0, t_pos = ent[0:1], ent[1:2]                     # (1, E*H)

    # unpack_entries + match_entries on the flattened (1, E*H) slots
    pu = jax.lax.bitcast_convert_type(word0, jnp.uint32)
    key_rep = jax.lax.bitcast_convert_type(
        _repeat(jax.lax.bitcast_convert_type(key, i32), h), jnp.uint32)
    got_key = (pu & ~mask_u) | (key_rep & mask_u)
    key_cnt = (pu & mask_u).astype(i32)
    valid_rep = _repeat(seed_valid.astype(i32), h) != 0
    in_bucket = jh < _repeat(cnt_bucket, h)
    key_match = got_key == key_rep
    raw_hit = in_bucket & key_match & valid_rep
    hit_v = raw_hit & (key_cnt <= thresh_freq) if use_freq else raw_hit

    fm = (key_match & in_bucket).astype(i32)
    first_match = (fm == 1) & (lanes.prefix_sum(fm, seg=h) == 1)

    n_seeds = jnp.sum(seed_valid.astype(i32), axis=1, keepdims=True)
    probes = jnp.sum(jnp.minimum(cnt_bucket, h) * seed_valid,
                     axis=1, keepdims=True)
    raw = jnp.sum(raw_hit.astype(i32), axis=1, keepdims=True)
    hit_i = hit_v.astype(i32)
    postfreq = jnp.sum(hit_i, axis=1, keepdims=True)
    exact = jnp.sum(jnp.where(first_match & valid_rep, key_cnt, 0),
                    axis=1, keepdims=True)

    # ---- vote (vote.vote_filter: per-read histogram, bin chunk by chunk) -
    if use_vote:
        q_pos = lanes.lane_iota(t_pos) // h
        shifted = (t_pos - q_pos) + DIAG_SHIFT
        clipped = jnp.maximum(shifted, 0)
        n_clip = jnp.sum(jnp.where(shifted < 0, hit_i, 0),
                         axis=1, keepdims=True)
        w1 = (clipped >> vlog2) % nbins
        w2 = ((clipped >> vlog2) + 1) % nbins
        vc = math.gcd(nbins, _VOTE_CHUNK)
        bins = jax.lax.broadcasted_iota(i32, (vc, eh), 0)

        def chunk(c, acc):
            v1, v2 = acc
            m1 = w1 == bins + c * vc                      # (vc, E*H)
            m2 = w2 == bins + c * vc
            votes = jnp.sum(jnp.where(m1, hit_i, 0) + jnp.where(m2, hit_i, 0),
                            axis=1, keepdims=True)        # (vc, 1)
            return (v1 + jnp.sum(jnp.where(m1, votes, 0), axis=0,
                                 keepdims=True),
                    v2 + jnp.sum(jnp.where(m2, votes, 0), axis=0,
                                 keepdims=True))

        zero = jnp.zeros((1, eh), i32)
        v1, v2 = jax.lax.fori_loop(0, nbins // vc, chunk, (zero, zero))
        keep = hit_v & (jnp.maximum(v1, v2) >= thresh_vote)
        n_cast = 2 * postfreq
    else:
        keep = hit_v
        n_cast = jnp.zeros((1, 1), i32)
        n_clip = jnp.zeros((1, 1), i32)
    n_anchors = jnp.sum(keep.astype(i32), axis=1, keepdims=True)

    tpos_ref[...] = t_pos
    hit_ref[...] = keep.astype(i32)
    col = lanes.lane_iota(jnp.zeros((1, len(COUNTER_COLS)), i32))
    cnt = jnp.zeros((1, len(COUNTER_COLS)), i32)
    for k, c in enumerate((nev, n_seeds, probes, raw, postfreq, exact,
                           n_cast, n_anchors, n_clip)):
        cnt = jnp.where(col == k, c.astype(i32), cnt)
    cnt_ref[...] = cnt


@functools.partial(
    jax.jit,
    static_argnames=("n_ev_max", "hits", "tw", "tau2", "eps", "peak_r",
                     "frac_bits", "seed_w", "seed_q", "minimizer_r",
                     "levels", "clip_q", "step_q", "n_buckets", "n_entries",
                     "thresh_freq", "use_freq", "use_vote", "vlog2", "nbins",
                     "thresh_vote", "tile", "interpret"))
def cheap_fused_fixed(xq, bucket_bounds, entries_packed, *,
                      n_ev_max, hits, tw, tau2, eps, peak_r, frac_bits,
                      seed_w, seed_q, minimizer_r, levels, clip_q, step_q,
                      n_buckets, n_entries, thresh_freq, use_freq, use_vote,
                      vlog2, nbins, thresh_vote, tile, interpret=None):
    """Launch the mega-kernel, one read per grid step.

    xq             (R, S)      int32 Q-format signal
    bucket_bounds  (2, NBpad)  int32 [start, end] of each bucket
    entries_packed (2, Npad)   int32, NBpad and Npad % tile.bt == 0
    Returns t_pos (R, E*H) i32, hit (R, E*H) i32, counters (R, 9) i32.
    """
    if interpret is None:
        interpret = K.INTERPRET
    r, s = xq.shape
    bt = tile.bt
    assert (bucket_bounds.shape[1] % bt == 0
            and entries_packed.shape[1] % bt == 0)
    eh = n_ev_max * hits
    nc = len(COUNTER_COLS)
    kern = functools.partial(
        _kernel, n_ev_max=n_ev_max, hits=hits, tw=tw, tau2=tau2, eps=eps,
        peak_r=peak_r, frac_bits=frac_bits, seed_w=seed_w, seed_q=seed_q,
        minimizer_r=minimizer_r, levels=levels, clip_q=clip_q,
        step_q=step_q, n_buckets=n_buckets, n_entries=n_entries,
        thresh_freq=thresh_freq, use_freq=use_freq, use_vote=use_vote,
        vlog2=vlog2, nbins=nbins, thresh_vote=thresh_vote, bt=bt,
        nt_bs=bucket_bounds.shape[1] // bt,
        nt_ent=entries_packed.shape[1] // bt)

    # (R, 1, X) operands with the read axis squeezed out of each block: the
    # kernel sees one read's (1, X) row, a legal Mosaic block for any R
    def row(width):
        return pl.BlockSpec((None, 1, width), lambda i: (i, 0, 0))

    t_pos, hit, cnt = pl.pallas_call(
        kern,
        grid=(r,),
        in_specs=[row(s),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[row(eh), row(eh), row(nc)],
        out_shape=[
            jax.ShapeDtypeStruct((r, 1, eh), jnp.int32),
            jax.ShapeDtypeStruct((r, 1, eh), jnp.int32),
            jax.ShapeDtypeStruct((r, 1, nc), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, 2, bt), jnp.int32),
            pltpu.VMEM((2, 2, bt), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(xq.reshape(r, 1, s), bucket_bounds, entries_packed)
    return t_pos.reshape(r, eh), hit.reshape(r, eh), cnt.reshape(r, nc)
