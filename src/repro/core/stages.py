"""Stage-graph execution engine for the MARS RSGA pipeline.

The MARS Control Unit (paper Section 6.1.3) sequences fine-grained tasks —
event detection, quantization, seeding, hash-table query, seed-and-vote,
anchor sort, chaining DP — across heterogeneous in-storage units.  This
module is the software analogue: the per-read program is an explicit graph
of named ``Stage``s, each with one or more registered ``Backend``s
(a pure-jnp *reference* implementation and, where a Pallas kernel exists,
an accelerated *pallas* one).  Backend selection is resolved per-config
into a static, hashable *plan* — no per-stage callables ever thread
through ``map_read``/``map_chunk``.

Dataflow state is a flat dict of arrays keyed by the names below; every
stage consumes/produces a documented subset:

    signal      (S,)   f32   raw read samples            [input]
    events      (E,)   f32   event means                 [detect]
    n_events    ()     i32   valid event count           [detect]
    symbols     (E,)   i32   quantized event symbols     [quantize]
    keys        (E,)   u32   seed hash keys              [seed]
    seed_valid  (E,)   bool  valid seed mask             [seed]
    q_pos       (E,H)  i32   query positions of anchors  [query]
    t_pos       (E,H)  i32   target positions of anchors [query]
    hit_valid   (E,H)  bool  surviving anchors           [query, vote]
    sq, st, sv  (A,)         sorted anchors + validity   [sort]
    f, diag0    (A,)         DP chain scores/start diags [dp]
    result      ChainResult  mapping decision            [finalize]
    counters    dict         uniform counter schema (COUNTER_SCHEMA)

Registering an accelerated backend (each kernel's ``ops.py`` does this at
import; ``resolve_plan`` imports them lazily):

    from repro.core import stages
    stages.register_backend("query", stages.PALLAS, my_backend_fn,
                            supports=lambda cfg: True)

Backends unavailable for a config (``supports`` false) or unregistered
fall back to the reference implementation, so a plan always covers every
stage.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable, Dict, Optional, Tuple

import jax.numpy as jnp

from repro.core import chaining, events, hashing, quantization, seeding, vote
from repro.core.config import MarsConfig

State = Dict[str, Any]

# Execution order of the per-read program (paper Fig. 1 steps 1a-3i).
STAGE_ORDER: Tuple[str, ...] = (
    "detect",     # (1a/1b) signal -> event means
    "quantize",   # (1b)    event means -> symbols
    "seed",       # (2c)    symbols -> hash keys (+ minimizer winnowing)
    "query",      # (2d/2e) hash-table gather + frequency filter
    "vote",       # (2f)    seed-and-vote filter
    "sort",       # (3g/3h) anchor sort (bitonic Sorter/Merger)
    "dp",         # (3i)    banded chaining DP
    "finalize",   #         best/second-best chain -> mapping decision
)

# The filter-aware split used by the chunk program (core/pipeline.py): the
# cheap phase runs on every read; the chaining phase runs only on the
# compacted batch of reads that still have anchors after the filters.
CHEAP_STAGES: Tuple[str, ...] = STAGE_ORDER[:5]   # detect .. vote
CHAIN_STAGES: Tuple[str, ...] = STAGE_ORDER[5:]   # sort, dp, finalize

# Canonical backend names.
REFERENCE = "reference"
PALLAS = "pallas"

# Modules that register accelerated backends (imported lazily the first
# time a plan asks for them, so importing core never pulls in Pallas).
# The "ring"/"a2a" entries are the distributed query backends: the same
# chunk program over a bucket-range-partitioned index, with the partition
# schedule (collective-permute ring / one all-to-all) as just another
# registered `query` implementation (core/distributed.py).
_BACKEND_MODULES: Dict[str, Tuple[str, ...]] = {
    PALLAS: (
        "repro.kernels.event_detect.ops",
        "repro.kernels.pluto_lookup.ops",
        "repro.kernels.bitonic_sort.ops",
        "repro.kernels.chain_dp.ops",
        # whole-phase fused cheap kernel (registers through
        # register_fused_cheap, not the per-stage registry)
        "repro.kernels.cheap_fused.ops",
    ),
    "ring": ("repro.core.distributed",),
    "a2a": ("repro.core.distributed",),
    # out-of-core query over host-resident bucket-range tiles + the
    # traffic-keyed hot-tile device cache (core/tiered.py)
    "tiered": ("repro.core.tiered",),
}
_loaded_backend_modules = set()

# Uniform counter schema: every map_chunk output carries exactly these
# per-chunk counters (plus n_reads / n_samples added by the chunk program).
# workload.from_counters / ssd_model consume them by name.  The full
# contract — which counters are closed-form, the debug-counters-never-
# change-the-chunk-schema rule, and the consumer table — is
# docs/COUNTERS.md.
COUNTER_SCHEMA: Tuple[str, ...] = (
    "n_events", "n_seeds", "n_bucket_probes", "n_hits_raw",
    "n_hits_postfreq", "n_hits_exact", "n_votes_cast",
    "n_anchors_postvote", "n_sorted", "n_dp_pairs",
)
CHUNK_COUNTER_SCHEMA: Tuple[str, ...] = COUNTER_SCHEMA + (
    "n_reads", "n_samples")

# Per-stage DEBUG counters: diagnostics a stage may emit alongside the
# uniform schema (e.g. the vote filter's clip-guard tally).  The chunk
# program DROPS them from MapOutput.counters so CHUNK_COUNTER_SCHEMA —
# and every consumer keyed on it (workload, ssd_model, psum specs) —
# stays exactly as-is; read them by running the stage (or cheap_phase)
# directly.  See docs/COUNTERS.md for the full contract.
DEBUG_COUNTER_SCHEMA: Tuple[str, ...] = (
    "n_votes_clipped",
    # tiered-index hot-tile cache traffic (core/tiered.py): per-chunk tile
    # hits / misses / host->device paged bytes (int32, clamped; exact
    # host-side totals live on HotTileCache)
    "n_tile_hits", "n_tile_misses", "n_tile_paged_bytes",
    # fault-tolerant paging (core/tiered.py + core/faults.py): per-chunk
    # page-in re-reads and checksum mismatches caught before retry/raise
    "n_tile_retries", "n_tile_corruptions",
)


@dataclasses.dataclass(frozen=True)
class Backend:
    """One implementation of one stage.

    fn(state, cfg, index) -> new state dict.  ``supports`` gates configs
    the implementation cannot serve (e.g. the fixed-point event-detect
    kernel under a float config); unsupported backends resolve to the
    reference implementation instead.

    ``primitive`` is the stage's underlying array-level kernel, exposed so
    batch-level fast paths can call it outside the per-read state-dict
    protocol.  The chaining fast path (core/pipeline.py) runs sort/dp on a
    compacted read batch at a reduced anchor width; the cheap-phase fast
    path runs detect once per chunk and routes the query gathers through
    one whole-chunk lookup:

        sort:   primitive(keys (L,) int32) -> sorted keys (L,)
        dp:     primitive(q, t, valid (A,), cfg) -> (f (A,) f32, d (A,) i32)
        detect: primitive(signals (R,S) f32, cfg) -> (means (R,E) f32,
                n_events (R,) i32) — batch-level, no unit-batch vmap
        query:  primitive(table (N,), idx (...,)) -> values (...,) — the
                entry-plane gather (pLUTo lookup)

    ``index_kind`` declares the index layout the backend consumes:
    "replicated" (the plain ``index_arrays`` dict, whole table on every
    device), "partitioned" (the ``partition_index`` dict with a leading
    partition axis, range-partitioned by bucket over the mesh 'model'
    axis), or "tiered" (the out-of-core hot-tile cache view from
    ``core/tiered.HotTileCache.prepare`` — host-resident bucket-range
    tiles paged into fixed device slots).  ``plan_index_kind`` lets the
    chunk drivers pick matching shard_map in_specs.
    """
    stage: str
    name: str
    fn: Callable[[State, MarsConfig, Dict[str, jnp.ndarray]], State]
    supports: Optional[Callable[[MarsConfig], bool]] = None
    primitive: Optional[Callable] = None
    index_kind: str = "replicated"


_REGISTRY: Dict[Tuple[str, str], Backend] = {}


def register_backend(stage: str, name: str, fn,
                     supports=None, replace: bool = False,
                     primitive=None, index_kind: str = "replicated") -> None:
    """Register ``fn`` as backend ``name`` for ``stage``.

    ``fn(state, cfg, index) -> state`` must be bit-exact to the stage's
    reference backend — same state keys, same values, and the exact
    COUNTER_SCHEMA counter increments (extra diagnostics are allowed only
    as DEBUG_COUNTER_SCHEMA keys, which the chunk program drops; see
    docs/COUNTERS.md).  ``supports(cfg)`` gates eligibility (unsupported
    configs fall back to reference in resolve_plan); ``primitive``
    optionally exposes a batch-level entry point the cheap phase can fuse;
    ``index_kind`` declares the index layout the backend consumes
    (replicated / partitioned / tiered).
    """
    if stage not in STAGE_ORDER:
        raise ValueError(f"unknown stage {stage!r}; stages: {STAGE_ORDER}")
    if index_kind not in ("replicated", "partitioned", "tiered"):
        raise ValueError(f"unknown index_kind {index_kind!r}")
    key = (stage, name)
    if key in _REGISTRY and not replace:
        raise ValueError(f"backend {key} already registered")
    _REGISTRY[key] = Backend(stage=stage, name=name, fn=fn, supports=supports,
                             primitive=primitive, index_kind=index_kind)


def get_backend(stage: str, name: str) -> Backend:
    return _REGISTRY[(stage, name)]


def registered_backends(stage: str) -> Tuple[str, ...]:
    return tuple(sorted(n for (s, n) in _REGISTRY if s == stage))


def _ensure_backend_loaded(name: str) -> None:
    if name in _loaded_backend_modules:
        return
    # resolve_plan may run inside a jit trace (map_chunk with plan=None);
    # module-level jnp constants in the kernel packages must be created
    # eagerly, not staged as tracers of the surrounding trace
    import jax
    with jax.ensure_compile_time_eval():
        for mod in _BACKEND_MODULES.get(name, ()):
            importlib.import_module(mod)
    _loaded_backend_modules.add(name)


Plan = Tuple[Tuple[str, str], ...]


def resolve_plan(cfg: MarsConfig, backend: str = REFERENCE) -> Plan:
    """Resolve the per-stage backend choice for one config.

    Returns a hashable ((stage, backend_name), ...) tuple in STAGE_ORDER —
    usable as a static jit argument.  Stages without the requested backend
    (or whose backend does not support ``cfg``) fall back to reference.
    """
    _ensure_backend_loaded(backend)
    known = ({REFERENCE} | set(_BACKEND_MODULES)
             | {n for _, n in _REGISTRY})
    if backend not in known:
        raise ValueError(f"unknown backend {backend!r}; known: "
                         f"{sorted(known)}")
    plan = []
    for stage in STAGE_ORDER:
        b = _REGISTRY.get((stage, backend))
        if b is None or (b.supports is not None and not b.supports(cfg)):
            b = _REGISTRY[(stage, REFERENCE)]
        plan.append((stage, b.name))
    return tuple(plan)


def plan_index_kind(plan: Plan) -> str:
    """The index layout ``plan`` consumes: "replicated" (index_arrays dict,
    whole table everywhere) or "partitioned" (partition_index dict, bucket
    ranges over the mesh 'model' axis).  Only the query stage touches the
    index, so its backend decides."""
    return _REGISTRY[("query", dict(plan)["query"])].index_kind


def execute_stages(state: State, index: Dict[str, jnp.ndarray],
                   cfg: MarsConfig, plan: Plan,
                   subset: Tuple[str, ...]) -> State:
    """Run the stages of ``plan`` named in ``subset`` (in plan order) over an
    existing state dict.  The chunk program uses this to split the per-read
    graph into the cheap phase (CHEAP_STAGES, every read) and the chaining
    phase (CHAIN_STAGES, compacted reads only)."""
    for stage, bname in plan:
        if stage in subset:
            state = _REGISTRY[(stage, bname)].fn(state, cfg, index)
    return state


def execute_read(signal: jnp.ndarray, index: Dict[str, jnp.ndarray],
                 cfg: MarsConfig, plan: Plan):
    """Run the per-read stage graph.  signal: (S,) f32.

    Returns (ChainResult, counters) with counters exactly COUNTER_SCHEMA.
    """
    state: State = {"signal": signal, "counters": {}}
    state = execute_stages(state, index, cfg, plan, STAGE_ORDER)
    counters = state["counters"]
    missing = missing_counters(counters)
    if missing:
        raise RuntimeError(f"plan {plan} produced incomplete counters; "
                           f"missing {missing}")
    return state["result"], counters


def chain_primitives(plan: Plan, cfg: MarsConfig):
    """Resolve the (sorter, dp) array-level primitives of ``plan``'s chaining
    stages for the batched fast path, or None when the plan's chain stages
    cannot be expressed through primitives (a registered backend without a
    ``primitive`` and a non-reference finalize must go through the per-read
    stage bodies instead).

    Returns (sorter(t, q)->(t, q) sorted by the pair, dp(q, t, valid)->(f, d))
    — both per-read, vmap-safe.
    """
    p = dict(plan)
    if p["finalize"] != REFERENCE:
        return None
    prims = []
    for stage in ("sort", "dp"):
        b = _REGISTRY[(stage, p[stage])]
        if b.name != REFERENCE and b.primitive is None:
            return None
        prims.append(b.primitive)
    sorter = prims[0] if prims[0] is not None else chaining.sort_pairs
    if prims[1] is not None:
        dp_prim = prims[1]
        dp = lambda q, t, v: dp_prim(q, t, v, cfg)
    else:
        dp = lambda q, t, v: chaining.chain_dp(q, t, v, cfg)
    return sorter, dp


@dataclasses.dataclass(frozen=True)
class FusedCheapBackend:
    """A whole-phase fused implementation of CHEAP_STAGES.

    fn(signals (R,S), index, cfg) -> (q_pos, t_pos, hit_valid, counters) —
    the exact ``pipeline.cheap_phase`` contract, produced by ONE kernel
    launch instead of per-stage programs, for an index of any size.
    ``supports`` gates configs the kernel cannot serve; unsupported configs
    resolve to the per-stage plan (pipeline.cheap_phase's existing
    dispatch ladder).
    """
    name: str
    fn: Callable
    supports: Optional[Callable[[MarsConfig], bool]] = None


_FUSED_CHEAP: Dict[str, FusedCheapBackend] = {}


def register_fused_cheap(name: str, fn, supports=None,
                         replace: bool = False) -> None:
    """Register a whole-phase fused cheap kernel under backend ``name``.

    The fused kernel engages only for plans whose detect AND query stages
    resolved to ``name`` with quantize/seed/vote at reference — i.e. the
    per-stage programs it replaces are exactly the ones it fuses, so parity
    is against the plan's own math, never a different backend's.
    """
    if name in _FUSED_CHEAP and not replace:
        raise ValueError(f"fused cheap backend {name!r} already registered")
    _FUSED_CHEAP[name] = FusedCheapBackend(name=name, fn=fn,
                                           supports=supports)


def fused_cheap_backend(plan: Plan,
                        cfg: MarsConfig) -> Optional[FusedCheapBackend]:
    """Resolve ``plan``'s whole-phase fused kernel, or None when the plan's
    cheap stages are not the exact per-stage shape the fusion covers (or the
    kernel's ``supports`` gate rejects ``cfg``)."""
    p = dict(plan)
    b = _FUSED_CHEAP.get(p["detect"])
    if b is None or p["query"] != b.name:
        return None
    if any(p[s] != REFERENCE for s in ("quantize", "seed", "vote")):
        return None
    if b.supports is not None and not b.supports(cfg):
        return None
    return b


@dataclasses.dataclass(frozen=True)
class CheapPrimitives:
    """Resolved batch-level implementations of a plan's cheap phase
    (core/pipeline.cheap_phase).

    ``detector``: batch detect (signals (R,S)) -> (means, n_events), or None
    for the reference math (the per-read detect stage body, vmapped).
    ``gather``: entry-plane gather for a whole-chunk ``seeding.query_index``
    call, or None for jnp.take.  ``query_fn``: set instead of ``gather``
    when the query backend is not gather-expressible (the partitioned-index
    ring/a2a schedules) — the registered stage body, vmapped per read.
    ``fused``: the whole-phase mega-kernel (register_fused_cheap) when the
    plan's cheap stages match one — signals in, (q_pos, t_pos, hit_valid,
    counters) out, no per-stage launches at all.
    """
    detector: Optional[Callable] = None
    gather: Optional[Callable] = None
    query_fn: Optional[Callable] = None
    fused: Optional[Callable] = None


def cheap_primitives(plan: Plan, cfg: MarsConfig) -> Optional[CheapPrimitives]:
    """Resolve the batch-level cheap-phase program for ``plan``, or None when
    the plan's cheap stages cannot be expressed at batch level (a registered
    non-reference quantize/seed/vote backend, or a non-reference detect
    backend without a batch primitive) — those plans fall back to the
    per-read vmap of the stage bodies.
    """
    p = dict(plan)
    for stage in ("quantize", "seed", "vote"):
        if p[stage] != REFERENCE:
            return None
    det = _REGISTRY[("detect", p["detect"])]
    if det.name != REFERENCE and det.primitive is None:
        return None
    det_prim = det.primitive
    detector = (None if det.name == REFERENCE
                else (lambda signals: det_prim(signals, cfg)))
    fused_b = fused_cheap_backend(plan, cfg)
    fused = (None if fused_b is None
             else (lambda signals, index: fused_b.fn(signals, index, cfg)))
    q = _REGISTRY[("query", p["query"])]
    if q.name == REFERENCE:
        return CheapPrimitives(detector=detector, fused=fused)
    if q.primitive is not None:
        return CheapPrimitives(detector=detector, gather=q.primitive,
                               fused=fused)
    return CheapPrimitives(detector=detector, query_fn=q.fn, fused=fused)


def missing_counters(counters: Dict[str, Any]) -> Tuple[str, ...]:
    return tuple(k for k in COUNTER_SCHEMA if k not in counters)


# --------------------------------------------------------------------------- #
# Parametrized stage bodies.  Reference backends call these with the jnp
# default; kernel ops.py modules call them with their accelerated primitive
# (gather / sorter / dp / detector) — keeping the math in ONE place.
# --------------------------------------------------------------------------- #
def detect_with(state: State, cfg: MarsConfig, index, detector=None) -> State:
    if detector is None:
        ev, n_ev, _ = events.detect_events(state["signal"], cfg)
    else:
        ev, n_ev = detector(state["signal"])
    return {**state, "events": ev, "n_events": n_ev,
            "counters": {**state["counters"], "n_events": n_ev}}


def quantize_ref(state: State, cfg: MarsConfig, index) -> State:
    ev_valid = jnp.arange(cfg.max_events) < state["n_events"]
    sym = quantization.quantize_events(state["events"], ev_valid, cfg)
    return {**state, "symbols": sym}


def seed_ref(state: State, cfg: MarsConfig, index) -> State:
    keys, valid = hashing.pack_seeds(state["symbols"], state["n_events"], cfg)
    valid = hashing.minimizer_mask(keys, valid, cfg.minimizer_radius)
    return {**state, "keys": keys, "seed_valid": valid}


def query_with(state: State, cfg: MarsConfig, index, gather=None) -> State:
    t_pos, hit_valid, c = seeding.query_index(
        state["keys"], state["seed_valid"], index, cfg, gather=gather)
    q_pos = jnp.broadcast_to(
        jnp.arange(cfg.max_events, dtype=jnp.int32)[:, None], t_pos.shape)
    return {**state, "q_pos": q_pos, "t_pos": t_pos, "hit_valid": hit_valid,
            "counters": {**state["counters"], **c}}


def vote_ref(state: State, cfg: MarsConfig, index) -> State:
    hit_valid, c = vote.vote_filter(state["q_pos"], state["t_pos"],
                                    state["hit_valid"], cfg)
    return {**state, "hit_valid": hit_valid,
            "counters": {**state["counters"], **c}}


def sort_with(state: State, cfg: MarsConfig, index, sorter=None) -> State:
    sq, st, sv = chaining.sort_anchors(state["q_pos"], state["t_pos"],
                                       state["hit_valid"], cfg, sorter=sorter)
    n_sorted = jnp.minimum(state["hit_valid"].sum(), cfg.max_anchors)
    return {**state, "sq": sq, "st": st, "sv": sv,
            "counters": {**state["counters"], "n_sorted": n_sorted}}


def dp_with(state: State, cfg: MarsConfig, index, dp=None) -> State:
    if dp is None:
        f, diag0 = chaining.chain_dp(state["sq"], state["st"], state["sv"],
                                     cfg)
    else:
        f, diag0 = dp(state["sq"], state["st"], state["sv"])
    n_dp_pairs = state["sv"].sum() * cfg.chain_band
    return {**state, "f": f, "diag0": diag0,
            "counters": {**state["counters"], "n_dp_pairs": n_dp_pairs}}


def finalize_ref(state: State, cfg: MarsConfig, index) -> State:
    res = chaining.best_chain(state["f"], state["diag0"], state["sv"], cfg)
    return {**state, "result": res}


def _detect_ref(state, cfg, index):
    return detect_with(state, cfg, index, detector=None)


def _query_ref(state, cfg, index):
    return query_with(state, cfg, index, gather=None)


def _sort_ref(state, cfg, index):
    return sort_with(state, cfg, index, sorter=None)


def _dp_ref(state, cfg, index):
    return dp_with(state, cfg, index, dp=None)


register_backend("detect", REFERENCE, _detect_ref)
register_backend("quantize", REFERENCE, quantize_ref)
register_backend("seed", REFERENCE, seed_ref)
register_backend("query", REFERENCE, _query_ref)
register_backend("vote", REFERENCE, vote_ref)
register_backend("sort", REFERENCE, _sort_ref)
register_backend("dp", REFERENCE, _dp_ref)
register_backend("finalize", REFERENCE, finalize_ref)
