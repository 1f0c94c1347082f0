"""End-to-end MARS read-mapping pipeline (paper Fig. 1 / Fig. 7 dataflow).

The per-read program is the stage graph of ``core/stages.py`` — the same
fine-grained tasks the MARS Control Unit sequences (Section 6.1.3):

    (1) event detection: signal-to-event conversion (1a) + quantization (1b)
    (2) seeding: hash-value generation (c), frequency filter (d),
        hash-table query (e), seed-and-vote filter (f)
    (3) chaining: bucket/sort (g,h) + dynamic programming (i)

Backend selection (reference jnp vs accelerated Pallas) flows ONLY through
the stage registry: ``map_chunk`` takes a static, hashable *plan* resolved
by ``stages.resolve_plan`` — no per-stage callables.  ``use_kernels=True``
routes every stage through its registered Pallas backend (falling back to
reference where a kernel does not support the config).

Everything is static-shape and jit-compiled; ``map_chunk`` vmaps the
per-read program over a chunk of reads (a "channel stripe" in MARS terms)
and ``map_chunk_sharded`` runs the identical program under ``shard_map``
with reads sharded over the mesh and the index replicated — bit-identical
outputs, counters combined with integer psum.  Counter outputs follow the
uniform schema ``stages.CHUNK_COUNTER_SCHEMA`` consumed by the analytic
SSD performance model (ssd_model.py via workload.py).

Pad rows (chunks shorter than the static chunk size) are masked out of
every counter and of ``mapped`` via the traced ``n_valid`` argument, so
workload counts never inflate on non-multiple-of-chunk inputs.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import chaining, driver, seeding, stages, vote
from repro.core.config import MarsConfig
from repro.core.index import Index, index_arrays


class MapOutput(NamedTuple):
    t_start: jnp.ndarray    # (R,) int32 double-genome event coords
    score: jnp.ndarray      # (R,) f32
    mapped: jnp.ndarray     # (R,) bool
    n_events: jnp.ndarray   # (R,) int32
    counters: Dict[str, jnp.ndarray]


def map_read(signal: jnp.ndarray, index: Dict[str, jnp.ndarray],
             cfg: MarsConfig, plan: Optional[stages.Plan] = None):
    """signal: (S,) f32 -> (ChainResult, counters) via the stage engine."""
    if plan is None:
        plan = stages.resolve_plan(cfg, stages.REFERENCE)
    return stages.execute_read(signal, index, cfg, plan)


# --------------------------------------------------------------------------- #
# Cheap-phase fast path (batch-level detect / query / vote)
# --------------------------------------------------------------------------- #
def cheap_phase_vmap(signals: jnp.ndarray, index: Dict[str, jnp.ndarray],
                     cfg: MarsConfig, plan: stages.Plan):
    """The per-read cheap phase: vmap CHEAP_STAGES (detect..vote) over a
    chunk through the state-dict stage bodies.  Fallback for plans whose
    cheap stages have no batch-level expression, and the parity comparand
    for ``cheap_phase`` (tests/test_cheap_fastpath.py)."""
    def one(signal):
        state = stages.execute_stages({"signal": signal, "counters": {}},
                                      index, cfg, plan, stages.CHEAP_STAGES)
        return (state["q_pos"], state["t_pos"], state["hit_valid"],
                state["counters"])
    return jax.vmap(one)(signals)


def cheap_phase(signals: jnp.ndarray, index: Dict[str, jnp.ndarray],
                cfg: MarsConfig, plan: stages.Plan, use_fused: bool = True):
    """The cheap phase (detect..vote) over a chunk, batch-level where the
    plan allows (``stages.cheap_primitives``).

    Returns (q_pos (R,E,H), t_pos (R,E,H), hit_valid (R,E,H), per-read
    counters dict) — everything the chaining phase and the chunk counter
    schema need.  ``counters["n_anchors_postvote"]`` is the per-read
    post-filter anchor count the compaction gate keys on.

    Dispatch ladder, most-fused first: (1) the whole-phase mega-kernel
    (``stages.register_fused_cheap``) when the plan's cheap stages match one
    — detect..vote in ONE kernel launch, the probed index rows gathered
    from VMEM, or fetched from HBM by DMA for an index past the VMEM line
    (kernels/cheap_fused);
    (2) the per-stage batch level below;
    (3) ``cheap_phase_vmap``.  ``use_fused=False`` pins level (2) — the
    fused-vs-per-stage microbenchmark pair and parity tests use it.

    Batch level means: detect runs ONCE per chunk (the Pallas event_detect
    kernel's native grid, no unit-batch vmap), the hash-table query issues
    two whole-chunk fused gathers against the packed index (one pLUTo sweep
    each on the Pallas backend), and the vote filter accumulates the whole
    chunk in one segment-sum.  Quantize/seed (pure per-read arithmetic) and
    non-gather query backends (ring/a2a) run their registered stage bodies
    under vmap, so the math stays in ONE place — outputs and counters are
    bit-identical to ``cheap_phase_vmap``.
    """
    prims = stages.cheap_primitives(plan, cfg)
    if prims is None:
        return cheap_phase_vmap(signals, index, cfg, plan)

    if use_fused and prims.fused is not None and "t_pre_keys" not in index:
        return prims.fused(signals, index)

    if "t_pre_keys" in index:
        # the tiered traffic pre-pass already ran the plan's own
        # detect/quantize/seed over this exact chunk (core/tiered.py,
        # PREPASS_KEYS) — consume its outputs instead of recomputing.
        # Bit-identical by construction: same stages, same plan, same
        # padded signals.
        n_ev = index["t_pre_nev"]
        keys = index["t_pre_keys"]
        seed_valid = index["t_pre_valid"]
        counters = {"n_events": n_ev}
    else:
        if prims.detector is not None:
            means, n_ev = prims.detector(signals)
        else:
            def detect_one(signal):
                st = stages.execute_stages({"signal": signal,
                                            "counters": {}},
                                           index, cfg, plan, ("detect",))
                return st["events"], st["n_events"]
            means, n_ev = jax.vmap(detect_one)(signals)
        counters = {"n_events": n_ev}

        def quant_seed(ev, n):
            st = stages.execute_stages({"events": ev, "n_events": n,
                                        "counters": {}},
                                       index, cfg, plan,
                                       ("quantize", "seed"))
            return st["keys"], st["seed_valid"]
        keys, seed_valid = jax.vmap(quant_seed)(means, n_ev)

    if prims.query_fn is not None:
        def query_one(k, v):
            st = prims.query_fn({"keys": k, "seed_valid": v, "counters": {}},
                                cfg, index)
            return st["t_pos"], st["hit_valid"], st["counters"]
        t_pos, hit_valid, qc = jax.vmap(query_one)(keys, seed_valid)
    else:
        t_pos, hit_valid, qc = seeding.query_index(
            keys, seed_valid, index, cfg, gather=prims.gather)
    counters.update(qc)
    q_pos = jnp.broadcast_to(
        jnp.arange(cfg.max_events, dtype=jnp.int32)[None, :, None],
        t_pos.shape)

    hit_valid, vc = vote.vote_filter(q_pos, t_pos, hit_valid, cfg)
    counters.update(vc)
    return q_pos, t_pos, hit_valid, counters


def _chain_widths(cfg: MarsConfig, n_keys: int):
    """The select-then-sort width ladder: configured widths that actually
    shrink the sorted array, ascending, deduplicated."""
    full = min(cfg.max_anchors, n_keys)
    return tuple(sorted({w for w in cfg.chain_widths if 0 < w < full}))


def chain_phase(q_pos: jnp.ndarray, t_pos: jnp.ndarray, hit_valid: jnp.ndarray,
                cnt: jnp.ndarray, cfg: MarsConfig, prims) -> tuple:
    """The batched chaining phase (sort -> dp -> finalize) over N reads.

    Runs at the smallest width W of ``cfg.chain_widths`` that bounds every
    active read's post-vote anchor count (``cnt``), falling back to the
    original full-sort path when none does: with cnt <= W the W smallest
    (t, q) pairs are ALL surviving anchors, so select-then-sort at width W,
    the banded DP over W slots and best_chain over W slots are bit-identical
    to the full-width pipeline (the truncated tail holds only invalid
    sentinel slots, which the DP maps to (NEG, const) and best_chain masks).
    The width choice is a batch-level runtime branch (lax.cond), so only the
    chosen program executes.

    Returns (t_start (N,), score (N,), mapped (N,)) int32/f32/bool.
    """
    sorter, dp = prims
    t, q = jax.vmap(chaining.anchor_pairs)(q_pos, t_pos, hit_valid)
    select = chaining._SELECTORS[cfg.anchor_select]
    maxcnt = jnp.max(cnt)

    def finalize(st, sq):
        sq, st, sv = chaining.decode_pairs(st, sq)
        f, d = jax.vmap(dp)(sq, st, sv)
        res = jax.vmap(lambda ff, dd, vv: chaining.best_chain(ff, dd, vv, cfg)
                       )(f, d, sv)
        return res.t_start, res.score, res.mapped

    def run_full():
        st, sq = jax.vmap(sorter)(t, q)
        return finalize(st[:, : cfg.max_anchors], sq[:, : cfg.max_anchors])

    def run_at(width):
        hits = t_pos.shape[-1]
        return finalize(*jax.vmap(lambda a: sorter(*select(a, width, hits))
                                  )(t))

    out = run_full
    for w in reversed(_chain_widths(cfg, t.shape[1])):
        def out(w_=w, fallback=out):
            return jax.lax.cond(maxcnt <= w_,
                                functools.partial(run_at, w_), fallback)
    return out()


def _chain_outputs(q_pos, t_pos, hit_valid, cnt, cfg: MarsConfig, prims):
    """Read-compaction gating around ``chain_phase``.

    Only reads with anchors surviving the filters (``cnt > 0``) can reach
    ``min_chain_score`` — under the paper's configurations the vote filter
    already enforces reachability, since a surviving anchor implies a vote
    window with >= thresh_voting anchors and thresh_voting * anchor_score >=
    min_chain_score.  Zero-anchor reads are finalized directly with the
    closed-form ``empty_chain_result`` (bit-identical to what the chain
    phase computes for them).  The survivors are compacted into a
    capacity-bounded batch of C = ceil(chain_capacity_frac * R) slots and
    their results scattered back; when more than C reads survive, a runtime
    branch (lax.cond) falls back to chaining the whole chunk — every read is
    exact either way, so the branch choice is invisible (including across
    shard_map partitions that take different branches).
    """
    R = cnt.shape[0]
    empty = chaining.empty_chain_result(cfg)
    cap = min(R, max(1, math.ceil(R * cfg.chain_capacity_frac)))
    needs = cnt > 0

    def run_all():
        return chain_phase(q_pos, t_pos, hit_valid, cnt, cfg, prims)

    if cap >= R:
        return run_all()

    def run_compacted():
        order = jnp.argsort(~needs)          # stable: survivors first, in order
        idx = order[:cap]
        taken = needs[idx]
        t_c, s_c, m_c = chain_phase(
            q_pos[idx], t_pos[idx], hit_valid[idx],
            jnp.where(taken, cnt[idx], 0), cfg, prims)
        sidx = jnp.where(taken, idx, R)      # out-of-bounds rows -> dropped
        t0 = jnp.full((R,), empty.t_start, jnp.int32)
        s0 = jnp.full((R,), empty.score, jnp.float32)
        m0 = jnp.zeros((R,), bool)
        return (t0.at[sidx].set(t_c, mode="drop"),
                s0.at[sidx].set(s_c, mode="drop"),
                m0.at[sidx].set(m_c, mode="drop"))

    return jax.lax.cond(needs.sum() <= cap, run_compacted, run_all)


def _chunk_program(signals: jnp.ndarray, index: Dict[str, jnp.ndarray],
                   cfg: MarsConfig, plan: stages.Plan,
                   row_valid: jnp.ndarray) -> MapOutput:
    """The shared chunk body: run the stage graph over a chunk, mask pad rows
    out of the counters, and sum to the uniform per-chunk counter schema.

    With ``cfg.chain_compaction`` (default) the graph is split: CHEAP_STAGES
    vmap over every read, then the chaining phase runs via the filter-aware
    fast path (``_chain_outputs``).  The chain-stage counters are exact in
    closed form from the per-read post-vote anchor count (n_sorted =
    min(cnt, A); n_dp_pairs = n_sorted * B), so the counter schema is
    identical to the unpartitioned path.  Disabling compaction (or a plan
    whose chain stages expose no primitives) falls back to the original
    whole-graph vmap.
    """
    rv = row_valid
    prims = (stages.chain_primitives(plan, cfg)
             if cfg.chain_compaction else None)
    if prims is None:
        fn = lambda s: stages.execute_read(s, index, cfg, plan)
        res, counters = jax.vmap(fn)(signals)
        t_start, score, mapped = res.t_start, res.score, res.mapped
    else:
        q_pos, t_pos, hit_valid, counters = cheap_phase(
            signals, index, cfg, plan)
        cnt = counters["n_anchors_postvote"]
        n_sorted = jnp.minimum(cnt, cfg.max_anchors)
        counters = {**counters, "n_sorted": n_sorted,
                    "n_dp_pairs": n_sorted * cfg.chain_band}
        missing = stages.missing_counters(counters)
        if missing:
            raise RuntimeError(f"plan {plan} produced incomplete counters; "
                               f"missing {missing}")
        t_start, score, mapped = _chain_outputs(
            q_pos, t_pos, hit_valid, cnt, cfg, prims)
    # sum per-read counters over valid rows; per-stage DEBUG counters (e.g.
    # n_votes_clipped) are dropped so MapOutput.counters is exactly
    # CHUNK_COUNTER_SCHEMA — unchanged for every schema-keyed consumer
    summed = {k: jnp.where(rv, v, jnp.zeros_like(v)).sum().astype(jnp.int32)
              for k, v in counters.items()
              if k not in stages.DEBUG_COUNTER_SCHEMA}
    summed["n_reads"] = rv.sum().astype(jnp.int32)
    summed["n_samples"] = (rv.sum() * signals.shape[1]).astype(jnp.int32)
    return MapOutput(
        t_start=t_start, score=score, mapped=mapped & rv,
        n_events=jnp.where(rv, counters["n_events"], 0).astype(jnp.int32),
        counters=summed)


@functools.partial(jax.jit, static_argnames=("cfg", "use_kernels", "plan"))
def map_chunk(signals: jnp.ndarray, index: Dict[str, jnp.ndarray],
              cfg: MarsConfig, use_kernels: bool = False,
              n_valid=None, plan: Optional[stages.Plan] = None) -> MapOutput:
    """signals: (R, S) f32.  The jit'd mapping program for one chunk.

    ``plan`` (static) overrides backend selection; otherwise it resolves
    from the registry: every stage's Pallas backend when ``use_kernels``,
    reference backends when not.  ``n_valid`` (traced; defaults to R) masks
    trailing pad rows out of counters and the ``mapped`` flags.

    Contract: plan choice is result-invisible — every plan produces
    bit-identical per-read outputs and the returned ``counters`` dict
    carries exactly ``stages.CHUNK_COUNTER_SCHEMA`` (docs/COUNTERS.md),
    so cost models and benchmarks can compare backends on one schema.
    """
    if plan is None:
        plan = stages.resolve_plan(
            cfg, stages.PALLAS if use_kernels else stages.REFERENCE)
    if stages.plan_index_kind(plan) == "partitioned":
        raise ValueError(
            f"plan {plan} uses a partitioned-index query backend; run it "
            "through map_chunk_sharded with a mesh (partitions live on the "
            "'model' axis)")
    R = signals.shape[0]
    if n_valid is None:
        row_valid = jnp.ones((R,), bool)
    else:
        row_valid = jnp.arange(R) < n_valid
    return _chunk_program(signals, index, cfg, plan, row_valid)


# --------------------------------------------------------------------------- #
# Sharded chunk mapping (shard_map over the read axis)
# --------------------------------------------------------------------------- #
@functools.lru_cache(maxsize=None)
def _sharded_chunk_fn(cfg: MarsConfig, mesh, plan: stages.Plan,
                      index_keys: Optional[Tuple[str, ...]] = None):
    from jax.sharding import PartitionSpec as P

    axes = tuple(mesh.axis_names)

    def body(signals, index, n_valid):
        # local shard: (R_loc, S); reconstruct global row ids for masking
        shard_id = jnp.int32(0)
        for a in axes:
            shard_id = shard_id * mesh.shape[a] + jax.lax.axis_index(a)
        r_loc = signals.shape[0]
        row_valid = (shard_id * r_loc + jnp.arange(r_loc)) < n_valid
        out = _chunk_program(signals, index, cfg, plan, row_valid)
        counters = {k: jax.lax.psum(v, axes) for k, v in out.counters.items()}
        return out.t_start, out.score, out.mapped, out.n_events, counters

    # index layout follows the plan's query backend: the whole table on
    # every device, or one bucket-range partition per INDEX_AXIS rank
    # (query:ring / query:a2a, core/distributed.py)
    if stages.plan_index_kind(plan) == "partitioned":
        from repro.core.index import INDEX_AXIS, PARTITIONED_INDEX_KEYS
        index_spec = {k: P(INDEX_AXIS) for k in PARTITIONED_INDEX_KEYS}
    elif index_keys is not None:
        # tiered view carrying the traffic pre-pass's per-read planes
        # (core/tiered.PREPASS_KEYS): those shard over the read axis like
        # the signals so cheap_phase reuse survives the mesh; the tile
        # planes stay replicated
        per_read = {"t_pre_keys": P(axes, None),
                    "t_pre_valid": P(axes, None),
                    "t_pre_nev": P(axes)}
        index_spec = {k: per_read.get(k, P()) for k in index_keys}
    else:
        index_spec = P()
    counter_spec = {k: P() for k in stages.CHUNK_COUNTER_SCHEMA}
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(axes, None), index_spec, P()),
                       out_specs=(P(axes), P(axes), P(axes), P(axes),
                                  counter_spec),
                       check_vma=False)
    return jax.jit(fn)


def sharded_chunk_fn(cfg: MarsConfig, mesh, plan: stages.Plan):
    """The jit'd sharded chunk program for a resolved plan:
    ``fn(signals (R,S), index pytree, n_valid) -> (t_start, score, mapped,
    n_events, counters)``.  Public accessor for callers that need the raw
    program rather than ``map_chunk_sharded``'s host conveniences — e.g.
    the legacy distributed-mapper wrapper and abstract ``.lower`` dry-runs
    (launch/dryrun.py), where device_put on ShapeDtypeStructs is
    impossible.  Cached per (cfg, mesh, plan)."""
    return _sharded_chunk_fn(cfg, mesh, plan)


def _prepass_index_keys(index) -> Optional[Tuple[str, ...]]:
    """The index pytree's key set when it carries per-read traffic-pre-pass
    planes (tiered reuse_prepass under a mesh) — the sharded chunk fn needs
    per-key in_specs for those; None for every other index layout."""
    try:
        keys = tuple(sorted(index))
    except TypeError:
        return None
    return keys if "t_pre_keys" in keys else None


def map_chunk_sharded(signals: jnp.ndarray, index: Dict[str, jnp.ndarray],
                      cfg: MarsConfig, mesh, use_kernels: bool = False,
                      n_valid=None,
                      plan: Optional[stages.Plan] = None) -> MapOutput:
    """Data-parallel ``map_chunk``: reads sharded over EVERY mesh axis (the
    MARS "channel stripe"), counters psum-combined.  The index is either
    replicated (default plans) or, for the `query:ring` / `query:a2a`
    backends, the ``partition_index`` pytree with one bucket-range
    partition resident per 'model' rank — either way the chunk program is
    IDENTICAL to the single-device path.

    Per-read programs are independent and each seed's bucket lives in
    exactly one partition, so outputs are bit-identical to the
    single-device path; integer counter sums are associative, so the psum
    is exact.  R must divide evenly over the mesh.
    """
    if plan is None:
        plan = stages.resolve_plan(
            cfg, stages.PALLAS if use_kernels else stages.REFERENCE)
    R = signals.shape[0]
    n_dev = int(np.prod(tuple(mesh.shape.values())))
    if R % n_dev != 0:
        raise ValueError(f"chunk of {R} reads does not shard over {n_dev} "
                         f"devices; pad the chunk to a multiple")
    from repro.core.index import INDEX_AXIS
    if (stages.plan_index_kind(plan) == "partitioned"
            and INDEX_AXIS not in mesh.axis_names):
        raise ValueError(f"plan {plan} partitions the index over the "
                         f"'{INDEX_AXIS}' axis, absent from mesh "
                         f"{mesh.axis_names}")
    from repro.distributed.sharding import mapping_chunk_shardings
    sig_sh, _ = mapping_chunk_shardings(mesh)
    signals = jax.device_put(signals, sig_sh)
    nv = jnp.int32(R if n_valid is None else n_valid)
    t, s, m, ne, counters = _sharded_chunk_fn(
        cfg, mesh, plan, _prepass_index_keys(index))(signals, index, nv)
    return MapOutput(t_start=t, score=s, mapped=m, n_events=ne,
                     counters=counters)


# --------------------------------------------------------------------------- #
# Host-side driver + accuracy scoring
# --------------------------------------------------------------------------- #
class Mapper:
    """Convenience host wrapper: owns the index arrays, resolves the
    backend plan once, and streams chunks through the unified driver.

    ``backend`` names a registry backend ("reference"/"pallas", or the
    partitioned-index query schedules "ring"/"a2a"); the legacy
    ``use_kernels=True`` flag is shorthand for backend="pallas".  With a
    ``mesh`` the chunks run through ``map_chunk_sharded`` instead; plans
    whose query backend is partitioned build the ``partition_index``
    arrays (one bucket-range partition per 'model' rank) instead of the
    replicated table, and REQUIRE a mesh with a 'model' axis.

    backend="tiered" keeps the index OUT OF CORE: the packed planes are
    split into ``tiles`` host-resident bucket-range tiles and only the
    tiles each chunk's seed traffic touches are paged into a
    ``cache_slots``-slot device cache (core/tiered.py), prefetching the
    next chunk's tiles while the current chunk computes.  Results are
    bit-identical to the resident table for every cache size and eviction
    order; the cache object (``self.cache``) carries hit/miss/paged-bytes
    telemetry.  ``index`` may also be a pre-built ``TieredIndex`` (e.g.
    from the streaming ``build_index_streaming``), in which case ``tiles``
    is ignored.  ``reuse_prepass`` (default) forwards the traffic
    pre-pass's detect/quantize/seed outputs to the main pass so that work
    runs once per chunk, not twice — bit-identical to recomputing, on the
    sharded path too (the sharded chunk program's index in_specs shard the
    per-read pre-pass planes over the read axis).

    ``fault_plan`` (tiered backend only) attaches a seeded
    ``core/faults.FaultPlan`` injection harness to the cache's page-in
    path; ``cache_retries`` / ``cache_backoff`` bound the checksummed
    retry loop (core/tiered.py).  A plan injecting nothing is
    byte-identical to no plan at all.  ``cache_replicas=K`` pins the K
    hottest tiles (by cumulative seed traffic) into extra replica slots
    — result-invisible, skewed-traffic residency (HotTileCache docs).
    """

    def __init__(self, index: Index, cfg: Optional[MarsConfig] = None,
                 use_kernels: bool = False, backend: Optional[str] = None,
                 mesh=None, tiles: int = 8, cache_slots: int = 4,
                 cache_policy: str = "lru", cache_seed: int = 0,
                 fault_plan=None, cache_retries: int = 3,
                 cache_backoff: float = 1.0, reuse_prepass: bool = True,
                 cache_replicas: int = 0):
        self.index = index
        self.cfg = cfg or index.cfg
        self.backend = backend or (
            stages.PALLAS if use_kernels else stages.REFERENCE)
        self.plan = stages.resolve_plan(self.cfg, self.backend)
        self.mesh = mesh
        self.cache = None
        if (fault_plan is not None
                and stages.plan_index_kind(self.plan) != "tiered"):
            raise ValueError(
                f"fault_plan hooks the tiered backend's tile page-in path; "
                f"backend {self.backend!r} resolves to index kind "
                f"{stages.plan_index_kind(self.plan)!r} (no page-in to "
                "inject into)")
        if stages.plan_index_kind(self.plan) == "tiered":
            from repro.core.index import TieredIndex, tier_index
            from repro.core.tiered import HotTileCache
            ti = (index if isinstance(index, TieredIndex)
                  else tier_index(index, tiles))
            self.cache = HotTileCache(ti, cache_slots, mesh=mesh,
                                      policy=cache_policy, seed=cache_seed,
                                      faults=fault_plan,
                                      max_retries=cache_retries,
                                      backoff_base=cache_backoff,
                                      reuse_prepass=reuse_prepass,
                                      replicas=cache_replicas)
            self.arrays = None
        elif stages.plan_index_kind(self.plan) == "partitioned":
            from repro.core.index import INDEX_AXIS, partition_index
            from repro.distributed.sharding import partitioned_index_shardings
            if mesh is None or INDEX_AXIS not in mesh.axis_names:
                raise ValueError(
                    f"backend {self.backend!r} partitions the index over "
                    f"the '{INDEX_AXIS}' axis; pass a mesh with one")
            parts = partition_index(index, mesh.shape[INDEX_AXIS])
            shardings = partitioned_index_shardings(mesh)
            self.arrays = {k: jax.device_put(jnp.asarray(v), shardings[k])
                           for k, v in parts.items()}
        else:
            self.arrays = {k: jnp.asarray(v)
                           for k, v in index_arrays(index).items()}
            if mesh is not None:
                from repro.distributed.sharding import mapping_chunk_shardings
                _, rep = mapping_chunk_shardings(mesh)
                self.arrays = {k: jax.device_put(v, rep)
                               for k, v in self.arrays.items()}

    # cfg fields known NOT to shape the index arrays — the only ones
    # with_cfg may change.  An allowlist so a future index-shaping field
    # fails closed instead of silently querying a stale resident table.
    _NON_INDEX_CFG_FIELDS = frozenset((
        "signal_len", "max_events", "tstat_window", "tstat_threshold",
        "peak_window", "min_dwell", "max_hits_per_seed",
        "use_freq_filter", "thresh_freq", "use_vote_filter",
        "thresh_voting", "voting_window_log2", "vote_bins",
        "max_anchors", "chain_band", "max_gap", "gap_cost", "skip_cost",
        "anchor_score", "min_chain_score", "map_ratio",
        "chain_compaction", "chain_capacity_frac", "chain_widths",
        "anchor_select",
    ))

    def with_cfg(self, cfg: MarsConfig) -> "Mapper":
        """A Mapper over the SAME device-resident index arrays with a
        different config (the plan re-resolves; the index upload — or
        partitioning — is not repeated).  Realtime mapping uses this for
        its per-prefix-length pipeline specializations; only fields that do
        not shape the index (signal_len, max_events, thresholds, ...) may
        change."""
        import copy
        import dataclasses
        changed = [f.name for f in dataclasses.fields(MarsConfig)
                   if (getattr(cfg, f.name) != getattr(self.cfg, f.name)
                       and f.name not in self._NON_INDEX_CFG_FIELDS)]
        if changed:
            raise ValueError(
                f"with_cfg changes fields {changed} not known to leave the "
                "index unchanged; build a new Mapper (the resident index "
                "arrays could be stale)")
        m = copy.copy(self)
        m.cfg = cfg
        m.plan = stages.resolve_plan(cfg, self.backend)
        return m

    def chunk_fn(self):
        """The (signals, n_valid) -> MapOutput program for driver.stream_map
        consumers that bring their own chunk source (e.g. the launcher's
        SignalReader)."""
        if self.cache is not None:
            cache, cfg, plan = self.cache, self.cfg, self.plan
            if self.mesh is not None:
                def fn(sig, nv):
                    view = cache.prepare(sig, cfg, plan)
                    return map_chunk_sharded(jnp.asarray(sig), view, cfg,
                                             self.mesh, n_valid=nv, plan=plan)
                return fn

            def fn(sig, nv):
                view = cache.prepare(sig, cfg, plan)
                return map_chunk(jnp.asarray(sig), view, cfg, n_valid=nv,
                                 plan=plan)
            return fn
        if self.mesh is not None:
            return lambda sig, nv: map_chunk_sharded(
                jnp.asarray(sig), self.arrays, self.cfg, self.mesh,
                n_valid=nv, plan=self.plan)
        return lambda sig, nv: map_chunk(jnp.asarray(sig), self.arrays,
                                         self.cfg, n_valid=nv, plan=self.plan)

    def map_signals(self, signals: np.ndarray, chunk: int = 64) -> MapOutput:
        prefetch = None
        if self.cache is not None:
            cache, cfg, plan = self.cache, self.cfg, self.plan
            # page the NEXT chunk's tiles while this chunk computes — the
            # software analogue of MARS's flash-load/compute overlap
            prefetch = lambda sig, nv: cache.prefetch(sig, cfg, plan)
        stream = driver.stream_map(self.chunk_fn(),
                                   driver.array_chunks(signals, chunk),
                                   prefetch=prefetch)
        return driver.collect(stream)

    def serve(self, **kw):
        """A continuous-batching ``ServeDriver`` over this mapper: many
        concurrent client streams packed into this pipeline's chunks
        (core/server.py).  Results are bit-identical to ``map_signals``
        on each stream's reads for any interleaving."""
        from repro.core.server import ServeDriver
        return ServeDriver(self, **kw)


def score_accuracy(out: MapOutput, true_pos: np.ndarray,
                   true_strand: np.ndarray, mappable: np.ndarray,
                   n_bases: np.ndarray, n_ref_events: int,
                   tol: int = 100) -> Dict[str, float]:
    """Precision/recall/F1 against simulator ground truth (UNCALLED
    pafstats-style; paper Section 8.1)."""
    t = np.asarray(out.t_start).astype(np.int64)
    strand = (t >= n_ref_events).astype(np.int8)
    span = np.maximum(np.asarray(n_bases).astype(np.int64), 1)
    fwd = np.where(strand == 0, t, n_ref_events - 1 - ((t - n_ref_events) + span - 1))
    mapped = np.asarray(out.mapped)
    correct = (np.abs(fwd - true_pos) <= tol) & (strand == true_strand)
    tp = int(np.sum(mapped & mappable & correct))
    fp = int(np.sum(mapped & ~(mappable & correct)))
    fn = int(np.sum(~mapped & mappable))
    prec = tp / max(tp + fp, 1)
    rec = tp / max(tp + fn, 1)
    f1 = 2 * prec * rec / max(prec + rec, 1e-9)
    return dict(precision=prec, recall=rec, f1=f1, tp=tp, fp=fp, fn=fn)
