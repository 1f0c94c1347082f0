"""Fused cheap-phase mega-kernel: detect -> quantize -> seed -> query -> vote.

One `pl.pallas_call` executes the whole cheap phase for one read per grid
step without leaving the kernel.  The quantized signal row is staged into
VMEM by the grid pipeline; event means, quantized symbols and seed keys
live in registers/scratch instead of round-tripping through HBM between
stage launches.

The query gathers what the read probes, not the whole index: E bucket
bound pairs and two table rows a seed.  The body is shared by two
launches, and only the query step differs; the index's size chooses
(``TABLE_BYTES_MAX``, kernels/cheap_fused/ops.py).  Both read the device
index as ``index.index_arrays`` lays it out once at upload: the bucket
bounds [start; end] as a (2, B) int32 table and the packed entry rows as
a (2, N) one, each in whole 128-entry tiles, the entry table padded past
its end with copies of its last row.

``cheap_fused_fixed`` selects the rows by one-hot over the table's rows
(1/128 of its entries).  Both index tables stay in VMEM for the whole
launch (a full block with a constant index map, fetched
once), laid out by `index_planes` as byte planes of 128-entry rows, each
row one column of a (planes * 128, n_rows) bf16 matrix.  A lookup is two
levels: a one-hot matmul over the rows brings each query's row onto its
lane (exact: one 0..255 byte times 1 per output, accumulated in f32),
then an iota mask over the row's 128 sublanes picks the entry.  The
bucket bounds [start; end] are one row lookup per seed; a seed's H entry
rows lie in rows start // 128 and the next, so each seed gathers those
two rows, shifts them up by start % 128 and spreads the first H onto its
H anchor slots.  Slots past the table read copies of its last row, as
``min(start + j, n_entries - 1)`` does.  Reads' queries stay on
the lane axis (E buckets, then E*H entry slots), so no value changes
layout between lanes and sublanes.

``cheap_fused_hbm`` leaves both tables in HBM, where their (2, 128)
tiles are what a DMA may fetch.  A read's bucket ids go to
SMEM; one DMA a seed brings its bucket's tile of bounds, all E in flight
together; the starts picked from them go to SMEM; one DMA a seed brings
the entry tile holding its start, and one more the next tile when its H
entries run past it.  Transposed, the tiles are the rows the VMEM launch
gathers, and the same shift and spread follow.  The grid is pipelined by
one read: read i + 1's fetches are in flight while read i votes.

The math is copied operation-for-operation from the per-stage path so the
fusion is bit-identical:

    detect     kernels/event_detect/event_detect.py::detect_rows (shared)
    quantize   core/quantization.py::quantize_events_fixed
    seed       core/hashing.py::pack_seeds (+ mix32, minimizer_mask)
    query      core/seeding.py::query_index / unpack_entries / match_entries
    vote       core/vote.py::vote_filter

The vote histogram is built ``_VOTE_CHUNK`` bins at a time with integer
lane/sublane reductions, so no (E*H, vote_bins) one-hot is ever whole.
"""
from __future__ import annotations

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

_VOTE_CHUNK = 256       # vote bins per histogram pass
_ROW = 128              # index entries per table row: one lane tile
_BYTE = 8

# Column order of the fused kernel's per-read counter plane.
COUNTER_COLS = (
    "n_events", "n_seeds", "n_bucket_probes", "n_hits_raw",
    "n_hits_postfreq", "n_hits_exact", "n_votes_cast",
    "n_anchors_postvote", "n_votes_clipped",
)


def _repeat(x, h):
    """(1, E) -> (1, E*h): each lane repeated h times, in order."""
    e = x.shape[1]
    return jnp.broadcast_to(x.reshape(1, e, 1), (1, e, h)).reshape(1, e * h)


def _n_bytes(v: int) -> int:
    """Byte planes that hold every value in [0, v]."""
    return max(1, -(-v.bit_length() // _BYTE))


def _byte_planes(words, n_bytes):
    """(W, n) int32 table, n % _ROW == 0 -> (W * n_bytes * _ROW, n_rows)
    bf16: byte p of word w of entry r * _ROW + l sits at row
    (w * n_bytes + p) * _ROW + l, column r (n_rows padded to a lane
    multiple; pad columns are never selected)."""
    w, n = words.shape
    shifts = _BYTE * jnp.arange(n_bytes, dtype=jnp.int32).reshape(1, -1, 1, 1)
    x = (words.reshape(w, 1, n // _ROW, _ROW) >> shifts) & 0xFF
    x = x.transpose(0, 1, 3, 2).reshape(w * n_bytes * _ROW, n // _ROW)
    return jnp.pad(x, ((0, 0), (0, -x.shape[1] % _ROW))).astype(jnp.bfloat16)


def index_planes(bucket_bounds, entries_packed):
    """The VMEM launch's layout of the device index (``index.index_arrays``:
    [start; end] bounds and the entry rows, both in whole 128-entry tiles):
    (bucket_planes, entry_planes).  Bucket bounds hold values <= the
    entry count, so they take only the bytes it needs; entry words take
    four."""
    return (_byte_planes(bucket_bounds, _n_bytes(entries_packed.shape[1])),
            _byte_planes(entries_packed, 4))


def _columns(planes, col):
    """planes (K, C) bf16, col (1, Q) int32 in [0, C) -> (K, Q) f32:
    column col[q] of planes, by an exact one-hot matmul."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (planes.shape[1], col.shape[1]),
                                    0)
    onehot = (iota == col).astype(jnp.bfloat16)
    return jax.lax.dot(planes, onehot, preferred_element_type=jnp.float32)


def _blocks(x, rows):
    """Split axis 0 into blocks of ``rows``."""
    return [x[k:k + rows] for k in range(0, x.shape[0], rows)]


def _pick(block, on):
    """Per column, the one row of ``block`` where ``on`` holds (1, Q)."""
    return jnp.sum(jnp.where(on, block, 0), axis=0, keepdims=True)


def _word(planes):
    """Byte planes, least significant first (f32 or int32) -> int32."""
    return functools.reduce(jnp.bitwise_or, (
        p.astype(jnp.int32) << (_BYTE * k) for k, p in enumerate(planes)))


def _bytes(word):
    """int32 -> its four byte planes, least significant first."""
    return [(word >> (_BYTE * k)) & 0xFF for k in range(4)]


def _shift_up(win, off):
    """(L, Q) int32, off (1, Q) in [0, _ROW) -> rows win[i + off[q], q] for
    i + off[q] < L (a per-lane shift in log2(_ROW) static sublane rolls)."""
    n = win.shape[0]
    for k in range(_ROW.bit_length() - 1):
        rolled = pltpu.roll(win, n - (1 << k), 0)        # win[i + 2^k]
        win = jnp.where(((off >> k) & 1) == 1, rolled, win)
    return win


def _spread(planes, h):
    """(K, E) bf16 -> (K, E*h) f32 with column e*h + j equal to column e
    (each column repeated h times, by an exact one-hot matmul)."""
    e = planes.shape[1]
    shape = (e, e * h)
    rep = (jax.lax.broadcasted_iota(jnp.int32, shape, 1) // h
           == jax.lax.broadcasted_iota(jnp.int32, shape, 0))
    return jax.lax.dot(planes, rep.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)


def _front(xq, *, n_ev_max, tw, tau2, eps, peak_r, frac_bits, seed_w,
           seed_q, minimizer_r, levels, clip_q, step_q):
    """Detect, quantize and seed one read's (1, S) row: (n_events (1, 1),
    keys (1, E) uint32, seed_valid (1, E) bool)."""
    e = n_ev_max
    i32 = jnp.int32

    # ---- detect (event_detect.detect_rows on this read's row) ------------
    means, nev = detect_rows(xq, E=e, w=tw, tau2=tau2, eps=eps,
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
    return nev, key, seed_valid


def _query_vmem(bs_ref, ent_ref, bucket, bound_bytes):
    """The probed bounds and entry rows from the VMEM byte planes:
    (start (1, E), end (1, E), [keycnt, t_pos] rows (2 * _ROW, E) each,
    the two table rows from start // _ROW on, one column a seed)."""
    e = bucket.shape[1]
    # bucket bounds: the bucket's table row by matmul, then its lane
    cols = _blocks(_columns(bs_ref[...], bucket // _ROW), _ROW)
    at = jax.lax.broadcasted_iota(jnp.int32, (_ROW, e), 0) == bucket % _ROW
    start, end = (_word([_pick(c, at) for c in cols[w:w + bound_bytes]])
                  for w in (0, bound_bytes))
    ent = ent_ref[...]
    lo = _blocks(_columns(ent, start // _ROW), _ROW)
    hi = _blocks(_columns(ent, start // _ROW + 1), _ROW)
    rows = [jnp.concatenate([_word(lo[w:w + 4]), _word(hi[w:w + 4])])
            for w in (0, 4)]
    return start, end, rows


def _to_smem(vec, stage_ref, smem_ref, sem):
    """A (1, E) int32 vector into SMEM, for scalar DMA addresses."""
    stage_ref[...] = vec
    copy = pltpu.make_async_copy(stage_ref, smem_ref, sem)
    copy.start()
    copy.wait()


def _tile_copies(src_hbm, dst_ref, smem_ref, sem, hits=None):
    """(start, wait) of one DMA a seed: for seed i, the (2, _ROW) tile of
    ``src_hbm`` that holds entry ``smem_ref[0, i]``, into
    ``dst_ref[:, i, :]``; with ``hits``, the tile after it, for the seeds
    whose ``hits``-entry window runs into it.  ``start`` puts every seed's
    fetch in flight, ``wait`` waits for them all."""
    e = smem_ref.shape[1]

    def copy(i, tile):
        lo = pl.multiple_of(tile * _ROW, _ROW)
        return pltpu.make_async_copy(src_hbm.at[:, pl.ds(lo, _ROW)],
                                     dst_ref.at[:, i, :], sem)

    def wanted(i):
        at = smem_ref[0, i]
        if hits is None:
            return at // _ROW, True
        return at // _ROW + 1, at % _ROW + hits > _ROW

    def start_one(i, carry):
        tile, want = wanted(i)
        pl.when(want)(lambda: copy(i, tile).start())
        return carry

    def wait_one(i, carry):
        # every copy has the same shape, so the same byte count
        pl.when(wanted(i)[1])(lambda: copy(0, 0).wait())
        return carry

    return (lambda: jax.lax.fori_loop(0, e, start_one, 0),
            lambda: jax.lax.fori_loop(0, e, wait_one, 0))


def _slots(rows, start, h):
    """A seed's H entries lie in rows start // _ROW and the next: shift
    the two rows up by start % _ROW, keep H, and spread seed e's H entries
    onto its slots e*H .. e*H + H-1, byte plane by byte plane.  Returns
    the (1, E*H) words [keycnt, t_pos]."""
    eh = start.shape[1] * h
    win = [_shift_up(r, start % _ROW)[:h] for r in rows]  # 2 x (H, E)
    spread = _blocks(_spread(jnp.concatenate(
        [b for w in win for b in _bytes(w)]).astype(jnp.bfloat16), h), h)
    jh = lanes.lane_iota(jnp.zeros((1, eh), jnp.int32)) % h
    at = jax.lax.broadcasted_iota(jnp.int32, (h, eh), 0) == jh
    return [_word([_pick(c, at) for c in spread[w:w + 4]]) for w in (0, 4)]


def _back(key, seed_valid, nev, start, end, word0, t_pos, tpos_ref, hit_ref,
          cnt_ref, *, hits, n_buckets, thresh_freq, use_freq, use_vote, vlog2,
          nbins, thresh_vote):
    """Match and vote one read's E*H anchor slots, given its bucket bounds
    and the entry words of its slots, and write its outputs and
    counters."""
    e, h = key.shape[1], hits
    eh = e * h
    i32 = jnp.int32
    cnt_bucket = end - start
    jh = lanes.lane_iota(jnp.zeros((1, eh), i32)) % h      # slot within seed

    # unpack_entries + match_entries on the flattened (1, E*H) slots
    mask_u = jnp.uint32(n_buckets - 1)
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


def _kernel_vmem(xq_ref, bs_ref, ent_ref, tpos_ref, hit_ref, cnt_ref, *,
                 front, back, bound_bytes):
    nev, key, seed_valid = _front(xq_ref[...], **front)
    bucket = (key & jnp.uint32(back["n_buckets"] - 1)).astype(jnp.int32)
    start, end, rows = _query_vmem(bs_ref, ent_ref, bucket, bound_bytes)
    _back(key, seed_valid, nev, start, end, *_slots(rows, start, back["hits"]),
          tpos_ref, hit_ref, cnt_ref, **back)


def _kernel_hbm(x0_ref, xn_ref, bounds_hbm, ent_hbm, tpos_ref, hit_ref,
                cnt_ref, stage, ids, starts, bnd, win_lo, win_hi, state, sem,
                *, front, back):
    """The query from HBM, one read a grid step, pipelined by one read:
    read i's entry tiles are in flight when step i begins, and step i seeds
    read i + 1 and puts its bound and entry fetches in flight around read
    i's slot gather and vote.  Each seed's bucket lies in one 128-bucket
    tile of the (2, n_buckets) [start; end] table, and its H entries in
    the 128-entry tile of the (2, n_entries) entry table that holds its
    start, and in the next one when they run past it.

    ``state`` rows: read i's key, seed_valid, start, end and n_events
    (rows 0-4), then read i + 1's key, seed_valid and n_events (5-7)."""
    i, last = pl.program_id(0), pl.num_programs(0) - 1
    h, e = back["hits"], front["n_ev_max"]
    i32 = jnp.int32
    bounds = _tile_copies(bounds_hbm, bnd, ids, sem.at[1])
    tiles = [_tile_copies(ent_hbm, win_lo, starts, sem.at[2]),
             _tile_copies(ent_hbm, win_hi, starts, sem.at[3], h)]

    def bucket_of(key):
        return (key & jnp.uint32(back["n_buckets"] - 1)).astype(i32)

    def put(row, x):
        state[row:row + 1, :] = jnp.broadcast_to(x.astype(i32), (1, e))

    def seed(x, rows):
        """Seed a read, save it in ``rows``, fetch its bound tiles."""
        nev, key, valid = _front(x, **front)
        for r, v in zip(rows, (jax.lax.bitcast_convert_type(key, i32),
                               valid, nev)):
            put(r, v)
        _to_smem(bucket_of(key), stage, ids, sem.at[0])
        bounds[0]()

    def locate(key):
        """Its bucket bounds from the fetched tiles (saved as read i + 1's
        start and end), then its entry tiles in flight."""
        bounds[1]()
        bucket = bucket_of(jax.lax.bitcast_convert_type(key, jnp.uint32))
        at = jax.lax.broadcasted_iota(i32, (_ROW, e), 0) == bucket % _ROW
        start, end = (_pick(bnd[w].T[:, :e], at) for w in (0, 1))
        put(2, start)
        put(3, end)
        _to_smem(start, stage, starts, sem.at[0])
        for t in tiles:
            t[0]()

    @pl.when(i == 0)
    def _():
        seed(x0_ref[...], (0, 1, 4))
        locate(state[0:1, :])

    key, valid, start, end = (state[r:r + 1, :] for r in range(4))
    nev = state[4:5, 0:1]

    @pl.when(i < last)
    def _():
        seed(xn_ref[...], (5, 6, 7))

    for t in tiles:
        t[1]()
    rows = [jnp.concatenate([win_lo[w].T, win_hi[w].T])[:, :e]
            for w in (0, 1)]
    word0, t_pos = _slots(rows, start, h)

    @pl.when(i < last)
    def _():
        state[0:2, :] = state[5:7, :]
        state[4:5, :] = state[7:8, :]
        locate(state[0:1, :])

    _back(jax.lax.bitcast_convert_type(key, jnp.uint32), valid != 0, nev,
          start, end, word0, t_pos, tpos_ref, hit_ref, cnt_ref, **back)


def table_bytes(*tables):
    return sum(t.size * t.dtype.itemsize for t in tables)


def _vmem_bytes(*tables):
    """Scoped VMEM for the launch: the resident tables, room as large again
    for the one-hots over their rows, and the rest of the kernel's working
    set (under 3 MB at D1 widths)."""
    return 2 * table_bytes(*tables) + (16 << 20)


# Largest tables the VMEM launch can hold within a TPU v5e's 128 MiB of
# VMEM (2.8e6 entries at 2^18 buckets, 46 MiB, compile; 4e6 entries do
# not); past it the HBM launch runs.
TABLE_BYTES_MAX = 48 << 20


_STATIC = ("n_ev_max", "hits", "tw", "tau2", "eps", "peak_r", "frac_bits",
           "seed_w", "seed_q", "minimizer_r", "levels", "clip_q", "step_q",
           "n_buckets", "thresh_freq", "use_freq", "use_vote", "vlog2",
           "nbins", "thresh_vote", "interpret")
_FRONT = ("n_ev_max", "tw", "tau2", "eps", "peak_r", "frac_bits", "seed_w",
          "seed_q", "minimizer_r", "levels", "clip_q", "step_q")


def _launch(kern, xq, x_maps, tables, table_specs, scratch, vmem_bytes,
            interpret, params):
    """One read per grid step: the shared front and back around the
    query step ``kern`` chooses.  ``x_maps`` give the grid step's signal
    rows (as many operands).  Returns t_pos (R, E*H) i32, hit (R, E*H)
    i32, counters (R, 9) i32."""
    if interpret is None:
        interpret = K.INTERPRET
    hits = params["hits"]
    assert hits <= _ROW, "a seed's window spans at most two table rows"
    r, s = xq.shape
    eh = params["n_ev_max"] * hits
    nc = len(COUNTER_COLS)
    front = {k: params[k] for k in _FRONT}
    back = {k: v for k, v in params.items() if k not in _FRONT}

    # (R, 1, X) operands with the read axis squeezed out of each block: the
    # kernel sees one read's (1, X) row, a legal Mosaic block for any R
    def row(width, index=lambda i: i):
        return pl.BlockSpec((None, 1, width), lambda i: (index(i), 0, 0))

    x = xq.reshape(r, 1, s)
    t_pos, hit, cnt = pl.pallas_call(
        functools.partial(kern, front=front, back=back),
        grid=(r,),
        in_specs=[row(s, m) for m in x_maps] + table_specs,
        out_specs=[row(eh), row(eh), row(nc)],
        out_shape=[
            jax.ShapeDtypeStruct((r, 1, eh), jnp.int32),
            jax.ShapeDtypeStruct((r, 1, eh), jnp.int32),
            jax.ShapeDtypeStruct((r, 1, nc), jnp.int32),
        ],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_bytes),
        interpret=interpret,
    )(*[x] * len(x_maps), *tables)
    return t_pos.reshape(r, eh), hit.reshape(r, eh), cnt.reshape(r, nc)


@functools.partial(jax.jit, static_argnames=_STATIC)
def cheap_fused_fixed(xq, bucket_planes, entry_planes, *, interpret=None,
                      **params):
    """Launch the mega-kernel with both index tables resident in VMEM.

    xq             (R, S)  int32 Q-format signal
    bucket_planes, entry_planes   bf16, the index as `index_planes` lays
                   it out
    params         the kernel's static shapes and thresholds (``_STATIC``)
    """
    # the same whole table at every step: fetched once, one buffer
    def whole(x):
        return pl.BlockSpec(x.shape, lambda i: (0, 0),
                            pipeline_mode=pl.Buffered(1))

    kern = functools.partial(
        _kernel_vmem, bound_bytes=bucket_planes.shape[0] // (2 * _ROW))
    return _launch(kern, xq, [lambda i: i], (bucket_planes, entry_planes),
                   [whole(bucket_planes), whole(entry_planes)], [],
                   _vmem_bytes(bucket_planes, entry_planes), interpret,
                   params)


@functools.partial(jax.jit, static_argnames=_STATIC)
def cheap_fused_hbm(xq, bounds, entries, *, interpret=None, **params):
    """Launch the mega-kernel with both index tables left in HBM, as
    ``index.index_arrays`` lays them out: each read DMAs its E bucket-bound tiles, then
    the entry tiles its E windows lie in, into VMEM scratch, while the
    read before it votes (``_kernel_hbm``).  Same operands,
    outputs and results as ``cheap_fused_fixed``."""
    e = params["n_ev_max"]
    e8 = -(-e // 8) * 8
    last = xq.shape[0] - 1
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    scratch = [pltpu.VMEM((1, e), jnp.int32),             # vector -> SMEM
               pltpu.SMEM((1, e), jnp.int32),             # bucket ids
               pltpu.SMEM((1, e), jnp.int32),             # bucket starts
               pltpu.VMEM((2, e8, _ROW), jnp.int32),      # bound tiles
               pltpu.VMEM((2, e8, _ROW), jnp.int32),      # entry tiles
               pltpu.VMEM((2, e8, _ROW), jnp.int32),      # and the next
               pltpu.VMEM((8, e), jnp.int32),             # two reads' seeds
               pltpu.SemaphoreType.DMA((4,))]
    # the first read's row, and the next read's at every step
    x_maps = [lambda i: 0, lambda i: jnp.minimum(i + 1, last)]
    return _launch(_kernel_hbm, xq, x_maps, (bounds, entries), [hbm, hbm],
                   scratch, _vmem_bytes(), interpret, params)
