"""Seeding (paper Fig. 1, mapping step 2): hash-table query + frequency filter.

Online, jit-compiled.  For each seed key we gather up to H entries from its
bucket, mask collisions (stored key != query key) and apply the exact
frequency filter (entries_cnt > thresh_freq -> drop, Section 5.1).

The bucket gathers are the operation MARS maps onto its pLUTo-based Querying
Units; the optimized pipeline path routes them through the `pluto_lookup`
Pallas kernel (kernels/pluto_lookup) instead of jnp.take.

Packed-entry fast path: the online index stores the entries as (2, N) int32
ROWS (``entries_packed``, core/index.py) — word 0 packs [key-distinguisher |
count], word 1 holds t_pos — so ``query_index`` issues exactly TWO gathers
per chunk: ONE row gather of the bucket bounds ``bucket_bounds`` (2, B)
[start; end] and ONE entry-row gather that
returns both words per probed entry (the pLUTo kernel reads the packed row
in a single table sweep, like pLUTo's row-wide sense amplifiers; the
unpacked layout needed three separate entry-table sweeps).  The unpacked
four-gather implementation survives as ``query_index_reference`` (parity
oracle + the "pre" side of the cheap-phase microbenchmark); both accept
per-read (E,) keys or a whole chunk (R, E) — batched calls lower to single
whole-chunk gathers (ONE pLUTo kernel sweep on the Pallas backend instead
of per-read unit batches).

Injectable ``gather(table, idx)`` contract: 1-D (N,) tables return
``idx``-shaped values (as before); the 2-D (2, N) row tables (bucket
bounds, packed entries) return (2, *idx.shape) — both words per index.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import MarsConfig


def _take_clip(table: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Default gather, hoisted to module level so every trace shares ONE
    callable instead of a fresh per-call lambda (stable jaxpr identity).
    2-D (2, N) packed-row tables gather along the entry axis and return
    both row words, (2, *idx.shape)."""
    return jnp.take(table, idx, axis=table.ndim - 1, mode="clip")


def unpack_entries(packed: jnp.ndarray, keys: jnp.ndarray, cfg: MarsConfig):
    """Split gathered packed-entry words back into (got_key, key_cnt).

    packed: (..., H) int32 — the [key & ~bucket_mask | cnt] half of the
    entry plane; keys: (...,) uint32 query keys.  Every in-bucket entry's
    low hash_bits equal the bucket id, i.e. the query key's own low bits —
    so the stored low bits are redundant and their field holds the count.
    Reconstruction ``(packed & ~mask) | (query_key & mask)`` equals the full
    stored key exactly for in-bucket entries; out-of-bucket slots are masked
    by ``match_entries``'s in_bucket test before the comparison matters.
    """
    mask = jnp.uint32(cfg.n_buckets - 1)
    pu = jax.lax.bitcast_convert_type(packed, jnp.uint32)
    got_key = (pu & ~mask) | (keys[..., None] & mask)
    key_cnt = (pu & mask).astype(jnp.int32)
    return got_key, key_cnt


def match_entries(keys: jnp.ndarray, valid: jnp.ndarray,
                  got_key: jnp.ndarray, key_cnt: jnp.ndarray,
                  cnt_bucket: jnp.ndarray, cfg: MarsConfig):
    """The post-gather query math, shared by the replicated-table path below
    and the partitioned-index backends (core/distributed.py) so the filter
    rules and counter semantics live in ONE place.

    keys/valid: (..., E); got_key/key_cnt: (..., E, H) gathered entry planes;
    cnt_bucket: (..., E).  Leading batch axes are allowed (the batched chunk
    program); reductions are per read.  ``valid`` is the seed mask for THIS
    table — the full seed mask on a replicated table, seed mask & partition
    ownership on a partitioned one (each seed's bucket lives in exactly one
    partition, so the per-partition scalars sum to the replicated-table
    values).

    Returns (hit_valid (..., E, H), probes, raw, exact int32 per-read
    counters): post-frequency-filter hits, bucket probes (capped at H per
    seed), raw pre-filter hits, and the uncapped exact hit count —
    occurrences of each matched key in the whole reference (entries_cnt),
    counted once per seed; what an unbounded software baseline (RawHash2)
    would chain over.
    """
    H = cfg.max_hits_per_seed
    red = (-2, -1)                                           # per-read axes
    j = jnp.arange(H, dtype=jnp.int32)                       # (H,)
    in_bucket = j < cnt_bucket[..., None]
    key_match = got_key == keys[..., None]
    raw_hit = in_bucket & key_match & valid[..., None]

    if cfg.use_freq_filter:
        hit_valid = raw_hit & (key_cnt <= cfg.thresh_freq)
    else:
        hit_valid = raw_hit

    first_match = key_match & in_bucket & (jnp.cumsum(
        (key_match & in_bucket).astype(jnp.int32), axis=-1) == 1)
    probes = (jnp.minimum(cnt_bucket, H) * valid).sum(-1)
    raw = raw_hit.sum(red)
    exact = jnp.where(first_match & valid[..., None], key_cnt, 0).sum(red)
    return hit_valid, probes, raw, exact


def _query_counters(valid, hit_valid, probes, raw, exact) -> Dict:
    return dict(
        n_seeds=valid.sum(-1),
        n_bucket_probes=probes,
        n_hits_raw=raw,
        n_hits_postfreq=hit_valid.sum((-2, -1)),
        n_hits_exact=exact,
    )


def query_index(keys: jnp.ndarray, valid: jnp.ndarray,
                index: Dict[str, jnp.ndarray], cfg: MarsConfig,
                gather=None) -> Tuple[jnp.ndarray, jnp.ndarray, Dict]:
    """keys: (E,) or (R, E) uint32, valid: same-shape bool.

    Returns (t_pos (..., E, H) int32, hit_valid (..., E, H) bool, counters
    dict — scalars per read, (R,)-vectors for batched input).  `gather(table,
    idx)` is injectable so the Pallas pLUTo kernel can be swapped in;
    defaults to jnp.take.

    Dispatches on the index pytree layout: the packed single-plane layout
    (``index_arrays``) takes the two-gather fast path; the legacy unpacked
    dict falls through to ``query_index_reference``.
    """
    if "entries_packed" not in index:
        return query_index_reference(keys, valid, index, cfg, gather=gather)
    if gather is None:
        gather = _take_clip
    H = cfg.max_hits_per_seed
    mask = jnp.uint32(cfg.n_buckets - 1)
    bucket = (keys & mask).astype(jnp.int32)

    # gather 1: both bucket boundaries ([start; end] of bucket b) in one
    # (2, ...) row lookup
    start_end = gather(index["bucket_bounds"], bucket)       # (2, ..., E)
    start, end = start_end[0], start_end[1]
    cnt_bucket = end - start

    j = jnp.arange(H, dtype=jnp.int32)
    idx = start[..., None] + j                               # (..., E, H)
    # the table's padding repeats its last entry (index.pad_entries)
    n_entries = index["entries_packed"].shape[-1]
    idx_c = jnp.minimum(idx, n_entries - 1)

    # gather 2: ONE packed-row lookup returns both entry words
    ent = gather(index["entries_packed"], idx_c)             # (2, ..., E, H)
    got_key, key_cnt = unpack_entries(ent[0], keys, cfg)
    t_pos = ent[1]

    hit_valid, probes, raw, exact = match_entries(
        keys, valid, got_key, key_cnt, cnt_bucket, cfg)
    return t_pos, hit_valid, _query_counters(valid, hit_valid, probes, raw,
                                             exact)


def query_index_reference(keys: jnp.ndarray, valid: jnp.ndarray,
                          index: Dict[str, jnp.ndarray], cfg: MarsConfig,
                          gather=None) -> Tuple[jnp.ndarray, jnp.ndarray,
                                                Dict]:
    """Pre-fast-path query over the UNPACKED index layout
    (``index_arrays_unpacked``): four separate table gathers.  Parity oracle
    + the "pre" side of the cheap-phase microbenchmark.  Same signature and
    batch semantics as ``query_index``.
    """
    if gather is None:
        gather = _take_clip
    H = cfg.max_hits_per_seed
    mask = jnp.uint32(cfg.n_buckets - 1)
    bucket = (keys & mask).astype(jnp.int32)

    start_end = gather(index["bucket_start"],
                       jnp.stack([bucket, bucket + 1]))      # (2, ..., E)
    start, end = start_end[0], start_end[1]
    cnt_bucket = end - start

    j = jnp.arange(H, dtype=jnp.int32)
    idx = start[..., None] + j                               # (..., E, H)
    n_entries = index["entries_key"].shape[0]
    idx_c = jnp.minimum(idx, n_entries - 1)

    got_key = gather(index["entries_key"], idx_c)            # (..., E, H)
    t_pos = gather(index["entries_pos"], idx_c)
    key_cnt = gather(index["entries_cnt"], idx_c)

    hit_valid, probes, raw, exact = match_entries(
        keys, valid, got_key, key_cnt, cnt_bucket, cfg)
    return t_pos, hit_valid, _query_counters(valid, hit_valid, probes, raw,
                                             exact)
