"""Plain reference of the MARS read mapper, independent of the program.

The same semantics as the program's chunk program in the configuration's
``ms_fixed`` mode, written out once and straightforwardly: it imports
nothing of ``repro`` and takes nothing the program made.  It builds its own
index from the genome's events and maps each read on its own:

    detect    median/MAD normalization, early Q-format quantization,
              integer two-sample t-test, peak picking, segment means
    quantize  integer z-score of the event means into 2^q symbols
    seed      w consecutive symbols packed and mixed (murmur3 finalizer)
    query     the first H entries of the seed's bucket, (key, pos) order;
              keep matching keys that occur at most thresh_freq times
    vote      anchors vote for two overlapping diagonal windows in a
              mod-hashed bin table; keep anchors of windows with enough
              votes
    sort      anchors on the (t, q) pair, q clamped to 8 bits, the
              first max_anchors kept; t is a whole int32 position
    chain     banded DP (look-back of chain_band anchors, oldest wins
              ties), best and second-best chain, the mapping decision

Index building runs in numpy on the host; the per-read program runs in
``jax.numpy`` on whatever device JAX has, so that the program and the
reference round their float operations on the same hardware.  Per read it
returns ``t_start``, ``score``, ``mapped``, ``n_events`` and the counters
the program sums per chunk.
"""
from __future__ import annotations

import functools

import numpy as np

SIGNAL_CLIP = 8.0          # normalized signal is clipped to +-8 sigma
MAD_SCALE = 1.4826
NORM_EPS = 1e-6
ISQRT_STEPS = 24           # fixed Newton steps of the integer sqrt
DIAG_SHIFT = 1 << 20       # projected starts are shifted non-negative
NEG = -1e9                 # score of an anchor that starts no chain
SENTINEL = -(1 << 30)      # position of the band slots before anchor 0
Q_BITS = 8                 # a read position q is clamped to 2^Q_BITS - 1
INVALID_KEY = 0x7FFFFFFF   # t of an anchor not kept: it sorts last
# positions are int32, and so is a diagonal t - q + DIAG_SHIFT
MAX_CONCAT_EVENTS = (1 << 31) - DIAG_SHIFT

COUNTERS = ("n_events", "n_seeds", "n_bucket_probes", "n_hits_raw",
            "n_hits_postfreq", "n_hits_exact", "n_votes_cast",
            "n_anchors_postvote", "n_sorted", "n_dp_pairs")


def check_params(p: dict) -> None:
    """The reference implements the configuration's fixed-point mode with
    both filters and no minimizer winnowing; anything else is refused."""
    need = dict(fixed_point=True, early_quantization=True,
                use_freq_filter=True, use_vote_filter=True,
                minimizer_radius=0, min_dwell=1)
    bad = {k: p.get(k) for k, v in need.items() if p.get(k, v) != v}
    if bad:
        raise ValueError(f"reference covers ms_fixed only; got {bad}")


# --------------------------------------------------------------------------- #
# Index (host, numpy)
# --------------------------------------------------------------------------- #
def mix32(x: np.ndarray) -> np.ndarray:
    """murmur3's 32-bit finalizer."""
    m = np.uint64(0xFFFFFFFF)
    x = x.astype(np.uint64) & m
    x ^= x >> np.uint64(16)
    x = (x * np.uint64(0x85EBCA6B)) & m
    x ^= x >> np.uint64(13)
    x = (x * np.uint64(0xC2B2AE35)) & m
    x ^= x >> np.uint64(16)
    return x.astype(np.uint32)


def genome_symbols(events: np.ndarray, p: dict) -> np.ndarray:
    """Global z-normalization of the genome's events into 2^q symbols."""
    ev = events.astype(np.float64)
    z = (ev - ev.mean()) / (ev.std() + NORM_EPS)
    clip = p["quant_clip_sigma"]
    levels = 1 << p["quant_bits"]
    step = 2.0 * clip / levels
    sym = np.floor((np.clip(z, -clip, clip - 1e-4) + clip) / step)
    return np.clip(sym.astype(np.int64), 0, levels - 1)


def build_index(events_concat: np.ndarray, n_events: int, p: dict) -> dict:
    """Every seed of the double genome, except those spanning the strand
    junction, as entries sorted by (bucket, key, position), each with the
    number of times its key occurs."""
    if events_concat.shape[0] > MAX_CONCAT_EVENTS:
        raise ValueError(
            f"double genome of {events_concat.shape[0]} events: int32 "
            f"positions and diagonals hold at most {MAX_CONCAT_EVENTS}")
    w, q = p["seed_width"], p["quant_bits"]
    sym = genome_symbols(events_concat, p)
    n = sym.shape[0] - w + 1
    packed = np.zeros(n, np.uint64)
    for j in range(w):
        packed = (packed << np.uint64(q)) | sym[j:j + n].astype(np.uint64)
    keys = mix32(packed)
    pos = np.arange(n, dtype=np.int64)
    keep = ~((pos > n_events - w) & (pos < n_events))
    keys, pos = keys[keep], pos[keep]
    _, inv, counts = np.unique(keys, return_inverse=True, return_counts=True)
    cnt = counts[inv]
    bucket = keys & np.uint32((1 << p["hash_bits"]) - 1)
    order = np.lexsort((pos, keys, bucket))
    return dict(bucket=bucket[order].astype(np.int32),
                key=keys[order], pos=pos[order].astype(np.int32),
                cnt=cnt[order].astype(np.int32))


# --------------------------------------------------------------------------- #
# Per-read program (jax.numpy)
# --------------------------------------------------------------------------- #
def sort_anchors(t_pos, q_pos, keep, n_keep: int):
    """The kept anchors sorted by the (t, q) pair, q clamped to Q_BITS,
    and the first ``n_keep`` of them: (t, q, valid).  A slot past the
    kept anchors reads t = ``INVALID_KEY >> Q_BITS``, q = ``2^Q_BITS - 1``
    and valid False: the program's sentinels, from which a read with no
    anchor takes its ``t_start``."""
    import jax
    import jax.numpy as jnp

    q_max = (1 << Q_BITS) - 1
    t = jnp.where(keep, t_pos, INVALID_KEY).reshape(-1)
    q = jnp.where(keep, jnp.minimum(q_pos, q_max), q_max).reshape(-1)
    st, sq = jax.lax.sort((t, q), num_keys=2)
    st, sq = st[:n_keep], sq[:n_keep]
    sv = st != INVALID_KEY
    return jnp.where(sv, st, INVALID_KEY >> Q_BITS), sq, sv


def _map_read(signal, index, p):
    import jax
    import jax.numpy as jnp

    S, E, H = signal.shape[0], p["max_events"], p["max_hits_per_seed"]
    f = p["frac_bits"]
    i32, f32 = jnp.int32, jnp.float32
    half = f32(0.5)

    # detect: normalization and early quantization
    xs = jnp.sort(signal)
    m1, m2 = (S - 1) // 2, S // 2
    med = xs[m1] * half + xs[m2] * half
    dev = jnp.sort(jnp.abs(signal - med))
    mad = dev[m1] * half + dev[m2] * half
    x = (signal - med) / (MAD_SCALE * mad + NORM_EPS)
    xq = jnp.round(jnp.clip(x, -SIGNAL_CLIP, SIGNAL_CLIP) * (1 << f))
    xq = xq.astype(jnp.int16).astype(i32)

    # detect: integer t-test, left window x[i-w..i-1], right x[i..i+w-1]
    w = p["tstat_window"]
    xp = jnp.pad(xq, (w, w))
    sum_l = sum(xp[w - k:w - k + S] for k in range(1, w + 1))
    sq_l = sum(xp[w - k:w - k + S] ** 2 for k in range(1, w + 1))
    sum_r = sum(xp[w + k:w + k + S] for k in range(w))
    sq_r = sum(xp[w + k:w + k + S] ** 2 for k in range(w))
    diff = (sum_r - sum_l) >> 2
    lhs = diff * diff * w
    tau2 = int(round(p["tstat_threshold"] ** 2))
    eps = 1 << (2 * f - 8)
    ssd = (w * sq_l - sum_l * sum_l) + (w * sq_r - sum_r * sum_r)
    rhs = tau2 * ((ssd >> 4) + eps)
    score = lhs.astype(f32) / (rhs.astype(f32) + 1.0)
    r = p["peak_window"]
    sp = jnp.pad(score, (r, r), constant_values=-jnp.inf)
    around = jnp.max(jnp.stack([sp[k:k + S] for k in range(2 * r + 1)]), 0)
    left = jnp.max(jnp.stack([sp[k:k + S] for k in range(r + 1)]), 0)
    boundary = (lhs > rhs) & (score >= around) & (score >= left)

    # detect: segment means
    eid_raw = jnp.cumsum(boundary.astype(i32))
    eid = jnp.minimum(eid_raw, E - 1)
    sums = jax.ops.segment_sum(xq, eid, num_segments=E)
    cnts = jax.ops.segment_sum(jnp.ones_like(xq), eid, num_segments=E)
    means = sums.astype(f32) / jnp.maximum(cnts.astype(f32), 1.0)
    means = means / float(1 << f)
    n_ev = jnp.minimum(eid_raw[-1] + 1, E)

    # quantize: integer z-score of the event means
    ev_ok = jnp.arange(E) < n_ev
    v = ev_ok.astype(i32)
    e = jnp.round(means * (1 << f)).astype(i32)
    n = jnp.maximum(v.sum(), 1)
    d = e - (e * v).sum() // n
    d2 = d >> 1
    var = ((d2 * d2 * v).sum() // n) << 2
    s = jnp.maximum(var, 1)
    for _ in range(ISQRT_STEPS):
        s = (s + var // jnp.maximum(s, 1)) // 2
    std = jnp.maximum(s, 1)
    clip_q = int(round(p["quant_clip_sigma"] * (1 << f)))
    levels = 1 << p["quant_bits"]
    z = jnp.clip((d << f) // std, -clip_q, clip_q - 1)
    sym = jnp.clip((z + clip_q) // ((2 * clip_q) // levels), 0, levels - 1)

    # seed: w symbols per key, mixed
    sw, q = p["seed_width"], p["quant_bits"]
    su = jnp.pad(sym.astype(jnp.uint32), (0, sw))
    key = jnp.zeros(E, jnp.uint32)
    for j in range(sw):
        key = (key << q) | su[j:j + E]
    key = key ^ (key >> 16)
    key = key * jnp.uint32(0x85EBCA6B)
    key = key ^ (key >> 13)
    key = key * jnp.uint32(0xC2B2AE35)
    key = key ^ (key >> 16)
    seed_ok = jnp.arange(E) + sw <= n_ev

    # query: the first H entries of each seed's bucket
    b = (key & jnp.uint32((1 << p["hash_bits"]) - 1)).astype(i32)
    lo = jnp.searchsorted(index["bucket"], b, side="left").astype(i32)
    hi = jnp.searchsorted(index["bucket"], b, side="right").astype(i32)
    j = jnp.arange(H, dtype=i32)
    slot = jnp.minimum(lo[:, None] + j, index["bucket"].shape[0] - 1)
    in_bucket = j < (hi - lo)[:, None]
    same = (index["key"][slot] == key[:, None]) & in_bucket
    kcnt = index["cnt"][slot]
    raw = same & seed_ok[:, None]
    hit = raw & (kcnt <= p["thresh_freq"])
    first = same & (jnp.cumsum(same.astype(i32), axis=1) == 1)
    t_pos = index["pos"][slot]

    # vote: two overlapping windows per anchor, mod-hashed bins
    q_pos = jnp.broadcast_to(jnp.arange(E, dtype=i32)[:, None], (E, H))
    diag = jnp.maximum(t_pos - q_pos + DIAG_SHIFT, 0)
    nb = p["vote_bins"]
    w1 = (diag >> p["voting_window_log2"]) % nb
    w2 = ((diag >> p["voting_window_log2"]) + 1) % nb
    hv = hit.astype(i32)
    votes = jnp.zeros(nb, i32).at[w1].add(hv).at[w2].add(hv)
    keep = hit & (jnp.maximum(votes[w1], votes[w2]) >= p["thresh_voting"])

    # sort: anchors by (t, q), the first A kept
    A, B = p["max_anchors"], p["chain_band"]
    st, sq, sv = sort_anchors(t_pos, q_pos, keep, A)

    # chain: banded DP over the sorted anchors
    ft = jnp.full(A + B, NEG, f32)
    dt_ = jnp.zeros(A + B, i32)
    tp = jnp.concatenate([jnp.full(B, SENTINEL, i32), st])
    qp = jnp.concatenate([jnp.full(B, SENTINEL, i32), sq])
    gap_cost, skip_cost = p["gap_cost"], p["skip_cost"]
    max_gap = p["max_gap"]

    def step(carry, i):
        fa, da = carry
        fw = jax.lax.dynamic_slice(fa, (i,), (B,))     # anchors i-B .. i-1
        dw = jax.lax.dynamic_slice(da, (i,), (B,))
        dt = st[i] - jax.lax.dynamic_slice(tp, (i,), (B,))
        dq = sq[i] - jax.lax.dynamic_slice(qp, (i,), (B,))
        ok = (dt > 0) & (dq > 0) & (dt <= max_gap) & (dq <= max_gap)
        gap = jnp.abs(dt - dq).astype(f32)
        skip = jnp.minimum(dt, dq).astype(f32)
        cand = fw - gap_cost * gap - skip_cost * skip
        cand = jnp.where(ok & (fw > NEG / 2), cand, NEG)
        bj = jnp.argmax(cand)                          # oldest on ties
        best = cand[bj]
        fi = jnp.where(sv[i], p["anchor_score"] + jnp.maximum(best, 0.0), NEG)
        di = jnp.where(best > 0.0, dw[bj], st[i] - sq[i])
        return (fa.at[i + B].set(fi), da.at[i + B].set(di)), None

    (fa, da), _ = jax.lax.scan(step, (ft, dt_), jnp.arange(A))
    fs, ds = fa[B:], da[B:]
    fv = jnp.where(sv, fs, NEG)
    i1 = jnp.argmax(fv)
    s1, d1 = fv[i1], ds[i1]
    far = jnp.abs(ds - d1) > (1 << p["voting_window_log2"])
    s2 = jnp.maximum(jnp.max(jnp.where(sv & far, fs, NEG)), 0.0)
    mapped = (s1 >= p["min_chain_score"]) & (s1 >= p["map_ratio"] * s2)

    n_keep = keep.sum().astype(i32)
    n_sorted = jnp.minimum(n_keep, A)
    counters = dict(
        n_events=n_ev.astype(i32),
        n_seeds=seed_ok.sum().astype(i32),
        n_bucket_probes=(jnp.minimum(hi - lo, H) * seed_ok).sum().astype(i32),
        n_hits_raw=raw.sum().astype(i32),
        n_hits_postfreq=hit.sum().astype(i32),
        n_hits_exact=jnp.where(first & seed_ok[:, None], kcnt,
                               0).sum().astype(i32),
        n_votes_cast=(2 * hit.sum()).astype(i32),
        n_anchors_postvote=n_keep,
        n_sorted=n_sorted,
        n_dp_pairs=n_sorted * B)
    return (jnp.maximum(d1, 0).astype(i32), s1, mapped, n_ev.astype(i32),
            counters)


@functools.lru_cache(maxsize=None)
def _block_fn(items: tuple):
    import jax
    p = dict(items)
    return jax.jit(jax.vmap(lambda s, ix: _map_read(s, ix, p),
                            in_axes=(0, None)))


def map_reads(signals: np.ndarray, index: dict, p: dict,
              block: int = 256) -> dict:
    """Map (n, S) raw signals against a ``build_index`` index.  Returns
    host arrays: t_start, score, mapped, n_events and one array per
    counter, each (n,)."""
    import jax
    import jax.numpy as jnp

    check_params(p)
    fn = _block_fn(tuple(sorted((k, v) for k, v in p.items()
                                if not isinstance(v, (list, dict)))))
    dev_index = {k: jnp.asarray(v) for k, v in index.items()}
    n = signals.shape[0]
    outs = []
    for lo in range(0, n, block):
        part = np.asarray(signals[lo:lo + block], np.float32)
        m = part.shape[0]
        if m < block:
            part = np.concatenate(
                [part, np.repeat(part[-1:], block - m, axis=0)])
        t, s, mp, ne, c = fn(jnp.asarray(part), dev_index)
        outs.append(jax.tree.map(lambda a: np.asarray(a)[:m],
                                 (t, s, mp, ne, c)))
    res = dict(
        t_start=np.concatenate([o[0] for o in outs]),
        score=np.concatenate([o[1] for o in outs]),
        mapped=np.concatenate([o[2] for o in outs]),
        n_events=np.concatenate([o[3] for o in outs]))
    for k in COUNTERS:
        res[k] = np.concatenate([o[4][k] for o in outs])
    return res
