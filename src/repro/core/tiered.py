"""Out-of-core tiered query backend: host-resident bucket-range tiles paged
into a fixed-slot device cache keyed on per-chunk bucket traffic.

MARS keeps the reference index in flash and overlaps partition loads with
compute (paper Section 6.3); GenStore/MegIS win by shrinking what crosses
the storage boundary at all.  This module is the host/device software
analogue over the stage engine:

  * the index lives on the host as a ``core/index.TieredIndex`` — the
    packed planes split into power-of-two bucket-range tiles (plain numpy,
    optionally memory-mapped);
  * ``HotTileCache`` owns a fixed number of device tile *slots*.  Before a
    chunk runs, a tiny jitted pre-pass (the plan's own detect/quantize/seed
    stages) histograms the chunk's seed traffic per tile; exactly the
    touched tiles are paged in, evicting by LRU over per-slot touch
    counts (``policy="random"`` exists so tests can prove results are
    eviction-order-independent).  A chunk touching more tiles than slots
    falls back to a transient wide view (every needed tile, padded to a
    power-of-two slot count) — correctness never depends on cache size,
    only traffic does;
  * ``query:tiered`` is a registered `query` stage backend
    (``Backend.index_kind = "tiered"``), so ``stages.resolve_plan`` +
    ``map_chunk`` / ``map_chunk_sharded`` / ``ServeDriver`` pick it up with
    zero pipeline copies.  The per-seed math routes every bucket through
    its tile's slot with the same two fused gathers as
    ``seeding.query_index`` and the shared ``seeding.match_entries``
    filter/counter math, so results are bit-identical to the resident
    table for every cache size and eviction order (non-resident slots are
    reachable only by invalid seeds, which ``match_entries`` masks; hit
    positions are packed ring-style so garbage slots never leak).

Cache-traffic telemetry (hits / misses / paged bytes / retries /
corruptions) rides the ``stages.DEBUG_COUNTER_SCHEMA`` — the chunk
program drops those names before summing, so ``CHUNK_COUNTER_SCHEMA`` and
every consumer keyed on it stay byte-identical; host-side totals live on
the cache object (``hits`` / ``misses`` / ``paged_bytes`` / ``hit_rate``
/ ``retries`` / ``corruptions``) for the microbenchmark cache group.

Fault tolerance: every page-in is verified against the tile's build-time
CRC32 (``core/index.tile_checksum``).  A failed or corrupted read is
retried with exponential backoff (accounted in virtual time,
``vtime_penalty``) up to ``max_retries`` times; an exhausted budget
raises a loud ``faults.TileReadError`` — a corrupted tile can never
silently serve hits.  The seeded injection harness (``core/faults.py``)
hooks exactly this boundary and is a no-op when absent.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import faults as faults_mod
from repro.core import seeding, stages
from repro.core.config import MarsConfig
from repro.core.index import TieredIndex, tile_checksum

# The pytree keys of a device tile-cache view (what the `query:tiered`
# stage body consumes).  Shapes for a cache of n_view slots over n_tiles
# tiles (bl = buckets per tile, emax = padded entries per tile):
#
#   t_bucket_start   (n_view, bl + 1) int32   per-slot local prefix offsets
#   t_entries_packed (2, n_view, emax) int32  per-slot packed entry rows
#   t_tile_slot      (n_tiles,) int32         tile -> slot, -1 non-resident
#   t_cache_stats    (5,) int32               this chunk's (hits, misses,
#                                             paged bytes, page-in retries,
#                                             checksum mismatches) telemetry
TIERED_INDEX_KEYS = ("t_bucket_start", "t_entries_packed", "t_tile_slot",
                     "t_cache_stats")

# Optional view planes carrying the traffic pre-pass's detect->quantize->
# seed outputs forward to the main pass (HotTileCache(reuse_prepass=True),
# the default off the sharded path): the chunk program consumes them
# instead of recomputing the cheap prefix on the host's critical path.
#
#   t_pre_keys  (R, E) uint32   seed keys        t_pre_valid (R, E) bool
#   t_pre_nev   (R,)   int32    per-read event counts
PREPASS_KEYS = ("t_pre_keys", "t_pre_valid", "t_pre_nev")


# --------------------------------------------------------------------------- #
# The `query:tiered` stage backend
# --------------------------------------------------------------------------- #
def _cache_view(index: Dict[str, jnp.ndarray]):
    missing = [k for k in TIERED_INDEX_KEYS if k not in index]
    if missing:
        raise ValueError(
            f"tiered query backend needs a HotTileCache view with keys "
            f"{TIERED_INDEX_KEYS} (core/tiered.HotTileCache.prepare); "
            f"missing {missing} — got {sorted(index)}")
    return index


def query_tiered(keys: jnp.ndarray, valid: jnp.ndarray,
                 index: Dict[str, jnp.ndarray], cfg: MarsConfig):
    """Query seed keys against the device tile-cache view.

    keys: (E,) uint32 (or batched (R, E)), valid: same-shape bool.  Every
    VALID seed's tile must be resident (``HotTileCache.prepare`` guarantees
    it); seeds whose tile is not resident are treated as invalid, so a
    garbage slot can never contribute a hit or a counter.  Returns
    (t_pos, hit_valid, counters) with ``seeding.query_index`` semantics;
    t_pos is packed ring-style (0 for non-hits), which the downstream
    stages provably never distinguish (the ring/a2a backends' parity).
    """
    view = _cache_view(index)
    H = cfg.max_hits_per_seed
    bstart = view["t_bucket_start"]          # (n_view, bl + 1)
    ent = view["t_entries_packed"]           # (2, n_view, emax)
    tile_slot = view["t_tile_slot"]          # (n_tiles,)
    blp1 = bstart.shape[1]
    emax = ent.shape[-1]
    n_tiles = tile_slot.shape[0]
    tile_log = int(np.log2(cfg.n_buckets // n_tiles))

    bucket = (keys & jnp.uint32(cfg.n_buckets - 1)).astype(jnp.int32)
    tile = bucket >> tile_log
    local_b = bucket & ((1 << tile_log) - 1)
    slot = jnp.take(tile_slot, tile, mode="clip")            # (..., E)
    valid = valid & (slot >= 0)

    # the same two fused gathers as seeding.query_index, routed through the
    # resident slot planes (flattened so one gather serves every slot);
    # non-resident (slot -1) indices clamp to 0 — deterministic garbage,
    # fully masked by the residency-anded `valid` above
    flat_b = slot * blp1 + local_b
    start_end = jnp.take(bstart.reshape(-1),
                         jnp.stack([flat_b, flat_b + 1]), mode="clip")
    start, end = start_end[0], start_end[1]
    cnt_bucket = end - start

    j = jnp.arange(H, dtype=jnp.int32)
    eidx = jnp.minimum(start[..., None] + j, emax - 1)       # (..., E, H)
    flat_e = slot[..., None] * emax + eidx
    ent2 = jnp.take(ent.reshape(2, -1), flat_e, axis=1, mode="clip")
    got_key, key_cnt = seeding.unpack_entries(ent2[0], keys, cfg)

    hit_valid, probes, raw, exact = seeding.match_entries(
        keys, valid, got_key, key_cnt, cnt_bucket, cfg)
    t_pos = jnp.where(hit_valid, ent2[1], 0)
    counters = seeding._query_counters(valid, hit_valid, probes, raw, exact)
    return t_pos, hit_valid, counters


def _query_tiered(state: stages.State, cfg: MarsConfig, index) -> stages.State:
    t_pos, hit_valid, c = query_tiered(state["keys"], state["seed_valid"],
                                       index, cfg)
    q_pos = jnp.broadcast_to(
        jnp.arange(cfg.max_events, dtype=jnp.int32)[:, None], t_pos.shape)
    # chunk-level cache telemetry rides the DEBUG schema (dropped by the
    # chunk program before summing — CHUNK_COUNTER_SCHEMA is unchanged)
    s = index["t_cache_stats"]
    c = {**c, "n_tile_hits": s[0], "n_tile_misses": s[1],
         "n_tile_paged_bytes": s[2], "n_tile_retries": s[3],
         "n_tile_corruptions": s[4]}
    return {**state, "q_pos": q_pos, "t_pos": t_pos, "hit_valid": hit_valid,
            "counters": {**state["counters"], **c}}


stages.register_backend("query", "tiered", _query_tiered, index_kind="tiered")


# --------------------------------------------------------------------------- #
# Per-chunk tile-traffic pre-pass
# --------------------------------------------------------------------------- #
@functools.lru_cache(maxsize=None)
def _prepass_fn(cfg: MarsConfig, plan: stages.Plan, n_tiles: int):
    """The jitted traffic probe: run the plan's own detect/quantize/seed
    stages over a chunk and histogram valid seeds per tile.  The keys it
    computes are bit-identical to the chunk program's own cheap phase, so
    the resident set it pages in covers every seed the real query will
    issue (pad rows included — their lanes stay bit-identical too).
    Cached per (cfg, plan, n_tiles): the serving prefix ladder reuses one
    compiled probe per stage config."""
    tile_log = int(np.log2(cfg.n_buckets // n_tiles))
    subset = ("detect", "quantize", "seed")

    def fn(signals):
        def one(signal):
            st = stages.execute_stages({"signal": signal, "counters": {}},
                                       {}, cfg, plan, subset)
            return st["keys"], st["seed_valid"], st["n_events"]
        keys, valid, n_ev = jax.vmap(one)(signals)
        tile = ((keys & jnp.uint32(cfg.n_buckets - 1)).astype(jnp.int32)
                >> tile_log)
        hist = jnp.zeros((n_tiles,), jnp.int32).at[tile].add(
            valid.astype(jnp.int32), mode="drop")
        # the probe's detect/quantize/seed outputs ride along so the main
        # pass can reuse them instead of recomputing (PREPASS_KEYS)
        return hist, keys, valid, n_ev.astype(jnp.int32)
    return jax.jit(fn)


# --------------------------------------------------------------------------- #
# The traffic-keyed device cache
# --------------------------------------------------------------------------- #
class HotTileCache:
    """Fixed device tile slots over a host-resident ``TieredIndex``.

    ``prepare(signals, cfg, plan)`` runs the traffic pre-pass, pages the
    chunk's touched tiles into slots (evicting per ``policy``) and returns
    the device view dict for ``map_chunk`` / ``map_chunk_sharded``.  The
    view's arrays are immutable snapshots (functional updates), so a
    prefetch for chunk i+1 never disturbs chunk i's in-flight program —
    that is what lets ``driver.stream_map`` page next-chunk tiles while the
    current chunk computes.  ``prefetch`` memoizes the prepared view by
    signal-array identity; the matching ``prepare`` call pops it.

    policy: "lru" (least-recent chunk serial, then touch count — empty
    slots first) or "random" (seeded; the eviction-order parity tests).
    A chunk needing more tiles than slots gets a transient wide view of
    every needed tile (power-of-two slot count, so compile shapes stay
    bounded); the persistent slots are untouched and misses are charged
    for the non-resident tiles — the cache-of-1 thrash regime.

    replicas: K extra slots pinned to the top-K hottest tiles by the
    cumulative traffic histogram (``tile_traffic()``) — the MegIS-style
    skewed-workload optimization: hot-bucket tiles absorbing most probes
    stay resident no matter how cold traffic churns the primary slots.
    Replicas are loaded through the same CRC-verified path, hold
    byte-identical tile planes, win the tile->slot routing, and are
    never eviction victims; results are bit-identical to ``replicas=0``
    for every cache size × K (tests/test_tiered.py).  Replica paging is
    accounted separately (``replica_loads`` / ``replica_bytes``) so
    hit/miss telemetry still describes the primary working set.

    Telemetry (cumulative, host ints): ``hits`` / ``misses`` (tile
    touches found/not found resident), ``paged_bytes`` (host->device bytes
    for missed tiles), ``retries`` (page-in re-reads), ``corruptions``
    (checksum mismatches caught), ``n_chunks``; ``hit_rate`` derives.
    Per-chunk values ride the view's ``t_cache_stats`` into the DEBUG
    counters.

    Every page-in is CRC-verified against the build-time per-tile checksum
    and retried with exponential backoff (``backoff_base * 2**k`` virtual
    time units, accumulated in ``vtime_penalty``) up to ``max_retries``
    times; exhaustion raises ``faults.TileReadError`` — never a silent
    wrong answer.  ``faults`` attaches a seeded ``core/faults.FaultPlan``
    injection harness at exactly this boundary; a plan that injects
    nothing (``FaultPlan.enabled`` false) is dropped entirely, so the
    happy path is byte-identical with or without it.
    """

    def __init__(self, tiered: TieredIndex, n_slots: int, mesh=None,
                 policy: str = "lru", seed: int = 0,
                 faults: Optional[faults_mod.FaultPlan] = None,
                 max_retries: int = 3, backoff_base: float = 1.0,
                 reuse_prepass: bool = True, replicas: int = 0):
        if n_slots < 1:
            raise ValueError(f"need at least one cache slot; got {n_slots}")
        if policy not in ("lru", "random"):
            raise ValueError(f"unknown eviction policy {policy!r}; "
                             "use 'lru' or 'random'")
        if max_retries < 0:
            raise ValueError(f"max_retries must be >= 0; got {max_retries}")
        if backoff_base < 0:
            raise ValueError(f"backoff_base must be >= 0; "
                             f"got {backoff_base}")
        if replicas < 0:
            raise ValueError(f"replicas must be >= 0 extra hot-tile slots; "
                             f"got {replicas}")
        self.max_retries = int(max_retries)
        self.backoff_base = float(backoff_base)
        self._inj = (faults_mod.FaultInjector(faults)
                     if faults is not None and faults.enabled else None)
        self._prefetch_serial = 0
        self.tiered = tiered
        self.n_slots = min(int(n_slots), tiered.n_tiles)
        self.mesh = mesh
        # the pre-pass's detect/quantize/seed outputs feed the main pass on
        # the sharded path too: the sharded chunk program's index in_specs
        # shard the per-read PREPASS_KEYS planes over the read axis while
        # the tile planes stay replicated (pipeline._sharded_chunk_fn)
        self.reuse_prepass = bool(reuse_prepass)
        self.policy = policy
        self._rng = np.random.default_rng(seed)
        self._rep = None
        if mesh is not None:
            from repro.core.distributed import mapping_chunk_shardings
            _, self._rep = mapping_chunk_shardings(mesh)
        # Replica slots sit AFTER the n_slots primary slots: each holds a
        # byte-identical copy of one of the top-K hottest tiles (by the
        # cumulative traffic histogram), is never an eviction victim, and
        # wins the tile->slot routing over the tile's primary copy.  All
        # view gathers therefore read the same words either way —
        # replication is result-invisible by construction; what it buys is
        # residency: a hot tile stays servable while cold traffic churns
        # the primary slots.
        self.n_replicas = min(int(replicas), tiered.n_tiles)
        self.n_total = self.n_slots + self.n_replicas
        blp1 = tiered.buckets_per_tile + 1
        self._slot_tile = np.full(self.n_total, -1, np.int64)
        self._slot_last = np.zeros(self.n_total, np.int64)   # chunk serial
        self._slot_touch = np.zeros(self.n_total, np.int64)  # seed traffic
        self._tile_traffic = np.zeros(tiered.n_tiles, np.int64)
        self._serial = 0
        self._dev_bstart = self._put(jnp.zeros((self.n_total, blp1),
                                               jnp.int32))
        self._dev_ent = self._put(jnp.zeros((2, self.n_total, tiered.emax),
                                            jnp.int32))
        self._ready: Dict[int, Dict] = {}    # id(signals) -> prepared view
        self._keep: Dict[int, object] = {}   # keeps ids unique until popped
        self.reset_stats()

    def _put(self, x):
        return x if self._rep is None else jax.device_put(x, self._rep)

    # -------------------------------------------------------------- stats
    def reset_stats(self) -> None:
        self.hits = 0
        self.misses = 0
        self.paged_bytes = 0
        self.n_chunks = 0
        self.retries = 0          # page-in re-reads (failures + mismatches)
        self.corruptions = 0      # checksum mismatches caught at page-in
        self.vtime_penalty = 0.0  # virtual time lost to spikes + backoff
        self.replica_loads = 0    # hot-tile copies paged into replica slots
        self.replica_bytes = 0    # host->device bytes those copies cost
        self._chunk_retries = 0
        self._chunk_corruptions = 0

    @property
    def hit_rate(self) -> float:
        return self.hits / max(self.hits + self.misses, 1)

    @property
    def cache_nbytes(self) -> int:
        return self.n_total * self.tiered.tile_nbytes

    def tile_traffic(self) -> np.ndarray:
        """Cumulative per-tile seed-traffic histogram (a copy) — the
        replication policy's input, and the skew statistic the cost
        model's ``skewed_serving`` term consumes."""
        return self._tile_traffic.copy()

    # ---------------------------------------------------------- prefetch
    def prefetch(self, signals, cfg: MarsConfig, plan: stages.Plan) -> None:
        """Page the tiles a future chunk needs NOW (called by the driver
        loop on chunk i+1 while chunk i computes).  The prepared view is
        handed back by the ``prepare`` call for the same signals object."""
        key = id(signals)
        if key in self._ready:
            return
        serial = self._prefetch_serial
        self._prefetch_serial += 1
        if self._inj is not None:
            self._inj.check_prefetch(serial)
        # build the view BEFORE memoizing: a failed page-in must not leak
        # a dangling `_keep` pin or a half-built `_ready` entry
        view = self._prepare(signals, cfg, plan)
        self._keep[key] = signals
        self._ready[key] = view

    def prepare(self, signals, cfg: MarsConfig,
                plan: stages.Plan) -> Dict[str, jnp.ndarray]:
        """The device view for this chunk: every tile its valid seeds touch
        is resident.  Pops a prefetched view when one exists."""
        key = id(signals)
        view = self._ready.pop(key, None)
        self._keep.pop(key, None)
        if view is not None:
            return view
        return self._prepare(signals, cfg, plan)

    # ---------------------------------------------------------- internals
    def _read_tile(self, t: int, attempt: int):
        """One raw page-in attempt: contiguous int32 copies of the tile's
        planes (the 'DMA' — copies so an injected corruption can never
        reach the host index), routed through the fault injector when one
        is attached.  Raises ``TransientTileError`` on an injected read
        failure; latency spikes land in ``vtime_penalty``."""
        ti = self.tiered
        bstart = np.ascontiguousarray(ti.tile_bucket_start[t],
                                      dtype=np.int32)
        ent = np.ascontiguousarray(ti.tile_entries_packed[t],
                                   dtype=np.int32)
        if self._inj is not None:
            bstart, ent, lat = self._inj.tile_read(t, attempt, bstart, ent)
            if lat:
                self.vtime_penalty += lat
        return bstart, ent

    def _fetch_tile(self, t: int):
        """Page in one tile, verified: read -> CRC32 check -> (bstart, ent)
        or bounded retry with exponential backoff (virtual-time accounted).
        Every read failure / checksum mismatch is counted; an exhausted
        budget raises ``TileReadError`` loudly — a corrupted tile never
        serves hits silently."""
        t = int(t)
        expect = self.tiered.checksum(t)
        last: Optional[Exception] = None
        for attempt in range(self.max_retries + 1):
            if attempt:
                self.retries += 1
                self._chunk_retries += 1
                self.vtime_penalty += self.backoff_base * 2.0 ** (attempt - 1)
            try:
                bstart, ent = self._read_tile(t, attempt)
            except faults_mod.TransientTileError as e:
                last = e
                continue
            if tile_checksum(bstart, ent) == expect:
                return bstart, ent
            self.corruptions += 1
            self._chunk_corruptions += 1
            last = faults_mod.TileReadError(
                f"checksum mismatch paging tile {t} "
                f"(attempt {attempt}, expected {expect:#010x})")
        raise faults_mod.TileReadError(
            f"tile {t} page-in failed after {self.max_retries + 1} "
            f"attempts: {last}") from last

    def _refresh_replicas(self) -> None:
        """Keep the replica slots holding the current top-K hottest tiles
        (highest cumulative traffic, ties to the lower tile id).  Loads go
        through the same CRC-verified ``_fetch_tile`` path, so a replica's
        planes are byte-identical to the host tile — routing through a
        replica slot gathers exactly the words the primary would."""
        if not self.n_replicas:
            return
        traffic = self._tile_traffic
        hot = np.nonzero(traffic > 0)[0]
        hot = hot[np.lexsort((hot, -traffic[hot]))][:self.n_replicas]
        for j, t in enumerate(hot):
            s = self.n_slots + j
            if self._slot_tile[s] == int(t):
                continue
            self._write_slot(s, *self._fetch_tile(int(t)))
            self._slot_tile[s] = int(t)
            self._slot_touch[s] = 0
            self.replica_loads += 1
            self.replica_bytes += self.tiered.tile_nbytes

    def _prepare(self, signals, cfg, plan):
        ti = self.tiered
        hist_d, keys, valid, n_ev = _prepass_fn(cfg, plan, ti.n_tiles)(
            jnp.asarray(signals))
        hist = np.asarray(hist_d)
        needed = np.nonzero(hist > 0)[0]
        self._serial += 1
        self.n_chunks += 1
        self._chunk_retries = 0
        self._chunk_corruptions = 0
        self._tile_traffic += hist
        self._refresh_replicas()
        if needed.size <= self.n_slots:
            view = self._ensure_resident(needed, hist)
        else:
            view = self._overflow_view(needed, hist)
        if self.reuse_prepass:
            # hand the probe's outputs to the chunk program (PREPASS_KEYS):
            # bit-identical to the cheap phase it would recompute, since
            # both run the plan's own detect/quantize/seed stages
            if self.mesh is not None:
                # per-read planes shard over the read axis like the signals
                # (the sharded chunk program's index in_specs expect it)
                from jax.sharding import NamedSharding, PartitionSpec
                axes = tuple(self.mesh.axis_names)
                sh2 = NamedSharding(self.mesh, PartitionSpec(axes, None))
                sh1 = NamedSharding(self.mesh, PartitionSpec(axes))
                keys = jax.device_put(keys, sh2)
                valid = jax.device_put(valid, sh2)
                n_ev = jax.device_put(n_ev, sh1)
            view = dict(view, t_pre_keys=keys, t_pre_valid=valid,
                        t_pre_nev=n_ev)
        return view

    def _victim(self, needed: set) -> int:
        """A PRIMARY slot whose tile is not needed this chunk; empty slots
        first, then least-recently-used / least-trafficked (or random).
        Replica slots are never victims — that is the replication win:
        hot tiles stay resident while cold traffic churns the primaries."""
        cands = [s for s in range(self.n_slots)
                 if self._slot_tile[s] not in needed]
        empties = [s for s in cands if self._slot_tile[s] < 0]
        if empties:
            return empties[0]
        if self.policy == "random":
            return int(self._rng.choice(cands))
        return min(cands, key=lambda s: (self._slot_last[s],
                                         self._slot_touch[s], s))

    def _load_slot(self, s: int, t: int) -> None:
        # fetch (verify + retry) BEFORE touching device state: a failed
        # page-in raises here and leaves every persistent slot unchanged
        self._write_slot(s, *self._fetch_tile(t))
        self._slot_tile[s] = t
        self._slot_touch[s] = 0

    def _write_slot(self, s: int, bstart, ent) -> None:
        """Functional update of slot ``s``'s planes.  The updated arrays
        keep the cache's sharding (replicated over the mesh, if any)."""
        self._dev_bstart = self._put(
            self._dev_bstart.at[s].set(self._put(jnp.asarray(bstart))))
        self._dev_ent = self._put(
            self._dev_ent.at[:, s, :].set(self._put(jnp.asarray(ent))))

    def _view(self, bstart, ent, tile_slot, chunk_hits, chunk_misses):
        paged = chunk_misses * self.tiered.tile_nbytes
        self.hits += chunk_hits
        self.misses += chunk_misses
        self.paged_bytes += paged
        stats = jnp.asarray([chunk_hits, chunk_misses,
                             min(paged, np.iinfo(np.int32).max),
                             self._chunk_retries,
                             self._chunk_corruptions], jnp.int32)
        return dict(t_bucket_start=bstart, t_entries_packed=ent,
                    t_tile_slot=self._put(jnp.asarray(tile_slot)),
                    t_cache_stats=self._put(stats))

    def _ensure_resident(self, needed, hist):
        nset = set(int(t) for t in needed)
        resident = {int(t): s for s, t in enumerate(self._slot_tile)
                    if t >= 0}
        missing = [t for t in nset if t not in resident]
        for t in sorted(missing):
            self._load_slot(self._victim(nset), t)
        slot_of = {int(t): s for s, t in enumerate(self._slot_tile)}
        for t in nset:
            s = slot_of[t]
            self._slot_last[s] = self._serial
            self._slot_touch[s] += int(hist[t])
        tile_slot = np.full(self.tiered.n_tiles, -1, np.int32)
        for s, t in enumerate(self._slot_tile):
            if t >= 0:
                tile_slot[int(t)] = s
        return self._view(self._dev_bstart, self._dev_ent, tile_slot,
                          len(nset) - len(missing), len(missing))

    def _overflow_view(self, needed, hist):
        """More tiles touched than slots: a transient view holding every
        needed tile (padded to a power-of-two slot count — bounded compile
        shapes).  Persistent slots are left as-is; misses are charged for
        the tiles that were not resident."""
        ti = self.tiered
        n_need = int(needed.size)
        n_view = 1 << (n_need - 1).bit_length()
        blp1 = ti.buckets_per_tile + 1
        bstart = np.zeros((n_view, blp1), np.int32)
        ent = np.zeros((2, n_view, ti.emax), np.int32)
        tile_slot = np.full(ti.n_tiles, -1, np.int32)
        for i, t in enumerate(needed):
            bstart[i], ent[:, i, :] = self._fetch_tile(t)
            tile_slot[int(t)] = i
        resident = {int(t) for t in self._slot_tile if t >= 0}
        hits = sum(1 for t in needed if int(t) in resident)
        for s, t in enumerate(self._slot_tile):
            if int(t) in set(int(x) for x in needed):
                self._slot_last[s] = self._serial
                self._slot_touch[s] += int(hist[int(t)])
        return self._view(self._put(jnp.asarray(bstart)),
                          self._put(jnp.asarray(ent)), tile_slot,
                          hits, n_need - hits)
