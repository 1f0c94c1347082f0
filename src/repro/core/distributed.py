"""Distributed query backends: the partitioned-index mapper as stage-engine
`query` implementations.

MARS distributes raw reads across flash channels and queries index
partitions sequentially, overlapping partition loads with compute
(paper Section 6.3).  The TPU mapping:

  * reads are sharded over ALL mesh axes (every chip maps its own reads —
    the "channel stripe");
  * the reference index is range-partitioned by bucket over the 'model'
    axis (``core/index.partition_index``: partition p owns buckets
    [p*B/n, (p+1)*B/n));
  * `query:ring` rotates each read's seed keys (and accumulated hits +
    counter partials) around the 'model' axis with collective_permute; at
    step k a chip queries its resident partition with keys that originated
    k ranks upstream.  After n_model steps every seed has visited every
    partition and its hits are home — the collective is overlapped with
    query compute exactly like MARS overlaps flash loads with PIM work.
  * `query:a2a` rotates ONLY the keys; each shard accumulates hits for
    every source rank locally and ONE all_to_all returns them home — the
    (E,H) hit payload crosses the wire once instead of n_model times.

There is NO separate per-read program here: the backends are registered
`query` stages, so ``stages.resolve_plan(cfg, "ring"|"a2a")`` plus
``pipeline.map_chunk_sharded`` run the IDENTICAL chunk program as the
single-device path — cheap phase, compaction-gated chaining fast path,
width ladder, and the exact ``stages.CHUNK_COUNTER_SCHEMA`` (per-read
counter partials ride home with the hits, so pad-row masking via
``n_valid`` works in the distributed path too).

The two layouts the sharded chunk program places its arguments with live
here too: ``mapping_chunk_shardings`` (reads over every mesh axis, index
replicated) and ``partitioned_index_shardings``.
``partition_index`` lives next to Index construction in ``core/index.py``
and is re-exported here.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import seeding, stages
from repro.core.config import MarsConfig
from repro.core.index import (INDEX_AXIS, PARTITIONED_INDEX_KEYS,  # noqa: F401 (re-export)
                              partition_index)


# --------------------------------------------------------------------------- #
# Device-local query of one resident partition (per read, vmap-safe)
# --------------------------------------------------------------------------- #
def _query_partition(keys: jnp.ndarray, valid: jnp.ndarray,
                     part: Dict[str, jnp.ndarray], my_part: jnp.ndarray,
                     n_parts: int, cfg: MarsConfig):
    """keys: (E,) uint32, valid: (E,) bool; ``part`` is THIS device's
    partition (leading axis squeezed).

    Returns (t_pos (E,H), hit (E,H), probes, raw, exact) for the seeds whose
    bucket lives in this partition: ``hit`` is post-frequency-filter, and
    the three scalars are this partition's int32 share of the read's
    n_bucket_probes / n_hits_raw / n_hits_exact counters.  The filter and
    counter math itself is ``seeding.match_entries`` with the seed mask
    restricted to owned seeds — each seed's bucket lives in exactly one
    partition, so the per-partition partials sum to the replicated-table
    counters exactly.
    """
    H = cfg.max_hits_per_seed
    bl_log = cfg.hash_bits - int(np.log2(n_parts))
    bucket = (keys & jnp.uint32(cfg.n_buckets - 1)).astype(jnp.int32)
    owner = bucket >> bl_log
    local_b = bucket & ((1 << bl_log) - 1)
    mine = (owner == my_part) & valid

    # the same two fused gathers as seeding.query_index, against the
    # resident partition's packed planes
    bstart = part["p_bucket_start"]
    start_end = jnp.take(bstart, jnp.stack([local_b, local_b + 1]), axis=0,
                         mode="clip")                        # (2,E)
    start, end = start_end[0], start_end[1]
    cnt_bucket = end - start
    j = jnp.arange(H, dtype=jnp.int32)[None, :]
    idx = start[:, None] + j                                 # (E,H)
    n_entries = part["p_entries_packed"].shape[-1]
    idx_c = jnp.minimum(idx, n_entries - 1)
    ent = jnp.take(part["p_entries_packed"], idx_c, axis=1,
                   mode="clip")                              # (2,E,H)
    got_key, key_cnt = seeding.unpack_entries(ent[0], keys, cfg)
    t_pos = ent[1]

    hit, probes, raw, exact = seeding.match_entries(
        keys, mine, got_key, key_cnt, cnt_bucket, cfg)
    return t_pos, hit, probes, raw, exact


def _partition_view(index: Dict[str, jnp.ndarray], cfg: MarsConfig):
    """Squeeze the local (1, ...) shard of a partitioned index and recover
    the (static) partition count from the local bucket range."""
    missing = [k for k in PARTITIONED_INDEX_KEYS if k not in index]
    if missing:
        raise ValueError(
            f"partitioned query backend needs index keys "
            f"{PARTITIONED_INDEX_KEYS} (core/index.partition_index); "
            f"missing {missing} — got {sorted(index)}")
    if index["p_bucket_start"].ndim != 2 or index["p_bucket_start"].shape[0] != 1:
        raise ValueError(
            "partitioned index must arrive as ONE resident partition per "
            "device (leading partition axis sharded over the mesh "
            f"'{INDEX_AXIS}' axis); got local p_bucket_start shape "
            f"{index['p_bucket_start'].shape}")
    part = {k: index[k][0] for k in PARTITIONED_INDEX_KEYS}
    bl = part["p_bucket_start"].shape[0] - 1
    n_parts = cfg.n_buckets // bl
    return part, n_parts


# --------------------------------------------------------------------------- #
# The `query` stage backends
# --------------------------------------------------------------------------- #
def _query_ring(state: stages.State, cfg: MarsConfig, index) -> stages.State:
    """Ring schedule (paper Section 6.3 analogue): keys, accumulated packed
    hits and counter partials all rotate around the index axis; after
    n_parts steps everything is back on the read's home device."""
    part, n_parts = _partition_view(index, cfg)
    keys, valid = state["keys"], state["seed_valid"]
    E, H = keys.shape[0], cfg.max_hits_per_seed
    my_rank = jax.lax.axis_index(INDEX_AXIS)
    perm = [(i, (i + 1) % n_parts) for i in range(n_parts)]

    def rot(x):
        return jax.lax.ppermute(x, INDEX_AXIS, perm)

    def step(carry, _):
        keys_r, valid_r, packed, probes, raw, exact = carry
        tp, hv, pr, rw, ex = _query_partition(keys_r, valid_r, part,
                                              my_rank, n_parts, cfg)
        # hit -> t_pos+1, miss -> 0: ONE int32 plane on the wire instead of
        # separate int32 + bool planes; each (e,h) slot is hit by at most
        # one partition, so max-combining is exact.
        packed = jnp.maximum(packed, jnp.where(hv, tp + 1, 0))
        carry = (keys_r, valid_r, packed, probes + pr, raw + rw, exact + ex)
        return tuple(rot(x) for x in carry), None

    z = jnp.zeros((), jnp.int32)
    carry = (keys, valid, jnp.zeros((E, H), jnp.int32), z, z, z)
    (_, _, packed, probes, raw, exact), _ = jax.lax.scan(
        step, carry, None, length=n_parts)
    # after n_parts rotations everything is back home
    return _finish_query(state, cfg, packed, probes, raw, exact)


def _query_a2a(state: stages.State, cfg: MarsConfig, index) -> stages.State:
    """All-to-all schedule: only (keys, valid) rotate; hits and counter
    partials accumulate locally per source rank and ONE all_to_all returns
    them home — the (E,H) hit payload crosses the wire once instead of
    n_parts times."""
    part, n_parts = _partition_view(index, cfg)
    keys, valid = state["keys"], state["seed_valid"]
    E, H = keys.shape[0], cfg.max_hits_per_seed
    my_rank = jax.lax.axis_index(INDEX_AXIS)
    perm = [(i, (i + 1) % n_parts) for i in range(n_parts)]

    def step(carry, k):
        keys_r, valid_r, pbuf, sbuf = carry
        tp, hv, pr, rw, ex = _query_partition(keys_r, valid_r, part,
                                              my_rank, n_parts, cfg)
        packed = jnp.where(hv, tp + 1, 0)
        src = jnp.mod(my_rank - k, n_parts)      # originating rank
        pbuf = jax.lax.dynamic_update_slice(pbuf, packed[None], (src, 0, 0))
        sbuf = jax.lax.dynamic_update_slice(
            sbuf, jnp.stack([pr, rw, ex])[None], (src, 0))
        keys_r = jax.lax.ppermute(keys_r, INDEX_AXIS, perm)
        valid_r = jax.lax.ppermute(valid_r, INDEX_AXIS, perm)
        return (keys_r, valid_r, pbuf, sbuf), None

    pbuf0 = jnp.zeros((n_parts, E, H), jnp.int32)
    sbuf0 = jnp.zeros((n_parts, 3), jnp.int32)
    (_, _, pbuf, sbuf), _ = jax.lax.scan(
        step, (keys, valid, pbuf0, sbuf0), jnp.arange(n_parts))
    # send each source rank its hits + counter partials
    packed = jax.lax.all_to_all(pbuf, INDEX_AXIS, 0, 0).max(axis=0)
    scal = jax.lax.all_to_all(sbuf, INDEX_AXIS, 0, 0).sum(axis=0)
    return _finish_query(state, cfg, packed, scal[0], scal[1], scal[2])


def _finish_query(state, cfg: MarsConfig, packed, probes, raw, exact):
    """Unpack the combined hit plane and emit the exact query-stage counter
    schema of seeding.query_index."""
    hit_valid = packed > 0
    t_pos = jnp.maximum(packed - 1, 0)
    q_pos = jnp.broadcast_to(
        jnp.arange(cfg.max_events, dtype=jnp.int32)[:, None], t_pos.shape)
    counters = dict(
        n_seeds=state["seed_valid"].sum(),
        n_bucket_probes=probes,
        n_hits_raw=raw,
        n_hits_postfreq=hit_valid.sum(),
        n_hits_exact=exact,
    )
    return {**state, "q_pos": q_pos, "t_pos": t_pos, "hit_valid": hit_valid,
            "counters": {**state["counters"], **counters}}


stages.register_backend("query", "ring", _query_ring,
                        index_kind="partitioned")
stages.register_backend("query", "a2a", _query_a2a,
                        index_kind="partitioned")


# --------------------------------------------------------------------------- #
# Argument layouts of the sharded chunk program
# --------------------------------------------------------------------------- #
def mapping_chunk_shardings(mesh: Mesh):
    """Layouts for the sharded map_chunk path (core/pipeline.py): raw reads
    sharded over EVERY mesh axis (the MARS "channel stripe" — each chip
    maps its own reads), and a replicated index on all chips (a
    partitioned one takes ``partitioned_index_shardings``).

    Returns (signals sharding for (R, S), replicated index sharding)."""
    return (NamedSharding(mesh, P(tuple(mesh.axis_names), None)),
            NamedSharding(mesh, P()))


def partitioned_index_shardings(mesh: Mesh):
    """Shardings for the ``core/index.partition_index`` pytree: the leading
    partition axis of every leaf over ``index.INDEX_AXIS``, so each chip
    holds exactly its resident bucket-range partition (the flash-partition
    layout of paper Section 6.3)."""
    return {k: NamedSharding(mesh, P(INDEX_AXIS))
            for k in PARTITIONED_INDEX_KEYS}
