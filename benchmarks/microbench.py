"""Persistent per-stage-group microbenchmark of the mapping pipeline.

Times warmed-up, jit-compiled wall clock for one ``map_chunk`` workload,
split by stage group:

    cheap         the shipped cheap phase (batch-level detect/query/vote,
                  packed-entry gathers) over the whole chunk
    cheap_pre     the pre-fast-path cheap phase on the SAME signals:
                  per-read vmap with two-median normalization, scatter
                  segment means, unpacked four-gather query and per-read
                  vote scatters (for the pallas backend: the unit-batch
                  vmapped detect kernel)
    detect/query/vote (+ _pre)   the cheap phase's stage groups timed
                  individually on the pipeline's real intermediate data
    chain_fast    the filter-aware chaining fast path of core/pipeline.py
                  (read compaction + select-then-sort width ladder +
                  ring-buffer banded DP) on the cheap phase's real outputs
    chain_pre     the pre-fast-path chaining implementation on the SAME
                  inputs: full E*H anchor sort + dynamic-slice banded DP
                  (chaining.sort_anchors_reference / chain_dp_reference)
    map_chunk     the full fused chunk program (fast path on)
    map_chunk_pre the full chunk program with chain_compaction disabled
    serving_fast  continuous-batching multi-stream serving (ServeDriver):
                  many short streams packed across stream boundaries into
                  full chunks
    serving_pre   the single-tenant serving baseline on the SAME streams:
                  each stream mapped separately through the driver loop,
                  so every stream pays its own padded partial chunk
    cache         the out-of-core tiered-index group (top-level ``cache``
                  key, not per-backend): the same reads through the
                  ``query:tiered`` hot-tile cache (host-resident tiles,
                  a device cache several times smaller than the index,
                  prefetching driver loop) vs the fully-resident table,
                  plus the cache's hit-rate / paged-bytes telemetry
    fused         the whole-phase mega-kernel group (top-level ``fused``
                  key): the cheap phase through kernels/cheap_fused (ONE
                  kernel launch, probed index rows gathered) vs the same
                  pallas plan's per-stage program
                  (``pipeline.cheap_phase(use_fused=False)``)
    fairness      the multi-tenant fair-serving group (top-level
                  ``fairness`` key): one flooded two-tenant trace served
                  with vs without per-tenant shed budgets
                  (``ServeDriver(tenant_budgets=...)``); the gated metric
                  is the well-behaved tenant's victim count (sheds +
                  rejects), measured on the VIRTUAL clock — fully
                  deterministic, no wall time involved

``scripts/bench_pipeline.py`` drives this and appends the results to
``BENCH_pipeline.json`` at the repo root so every PR records the perf
trajectory (see EXPERIMENTS.md).

All timings are min-over-repeats of a blocking call AFTER a warm-up call,
so compile time is excluded and cache effects are steady-state.

Quick-profile honesty rule: the interpret-mode pallas groups may run on a
REDUCED read grid (``run(pallas_reduced_reads=...)``) to keep CI bench
wall time bounded; every reduced record carries explicit ``grid_reads`` /
``grid_reduced`` markers, and pre/fast pairs always share the same grid so
the gated RATIOS stay honest.
"""
from __future__ import annotations

import subprocess
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import MarsConfig, build_index, chaining, seeding, stages
from repro.core import events, pipeline, vote
from repro.core.index import index_arrays, index_arrays_unpacked
from repro.signal import simulate


def git_sha() -> str:
    try:
        return subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                              capture_output=True, text=True,
                              timeout=10).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def hardware_key() -> Dict[str, object]:
    """The hardware/software fingerprint stamped into every measured
    profile and gate record, so numbers measured on different machines are
    never silently compared (absolute ms are machine-bound; the gate's
    pre/fast ratios are not)."""
    import os
    import platform
    return dict(machine=platform.machine(), system=platform.system(),
                cpu_count=os.cpu_count() or 0,
                python=platform.python_version(), jax=jax.__version__,
                jax_backend=jax.default_backend())


def time_fn(fn, *args, repeats: int = 5) -> float:
    """Min-of-repeats wall seconds for ``fn(*args)``; one warm-up call first
    (compiles + primes caches)."""
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def make_workload(n_reads: int = 32, ref_events: int = 20_000,
                  junk_frac: float = 0.5, seed: int = 0):
    """One benchmark chunk: a synthetic reference + a read mix where
    ``junk_frac`` of the reads are unmappable noise (the population the
    filters — and therefore the compaction gate — are built for)."""
    cfg = MarsConfig(hash_bits=14).with_mode("ms_fixed")
    ref = simulate.make_reference(ref_events, seed=seed)
    reads = simulate.sample_reads(ref, n_reads, signal_len=cfg.signal_len,
                                  seed=seed + 1, junk_frac=junk_frac)
    idx = build_index(ref.events_concat, ref.n_events, cfg)
    arrays = {k: jnp.asarray(v) for k, v in index_arrays(idx).items()}
    arrays["_unpacked"] = {k: jnp.asarray(v)
                           for k, v in index_arrays_unpacked(idx).items()}
    arrays["_index"] = idx                  # host Index (tiered-cache group)
    return cfg, jnp.asarray(reads.signals), arrays


def _split_arrays(arrays):
    """(packed online pytree, unpacked oracle pytree) from make_workload's
    arrays dict — the jit-facing packed dict must not carry the oracle or
    the host-side "_"-prefixed extras."""
    unpacked = arrays.get("_unpacked")
    packed = {k: v for k, v in arrays.items() if not k.startswith("_")}
    if unpacked is None:
        if "entries_key" not in packed:
            raise ValueError(
                "cheap-phase microbenchmark needs the unpacked oracle "
                "planes: use make_workload (which embeds them under "
                "'_unpacked') or pass index_arrays_unpacked output")
        unpacked = packed                # caller brought an unpacked dict
    return packed, unpacked


def _chain_programs(cfg: MarsConfig, signals, arrays, backend: str):
    """Jit the cheap phase and the pre/fast chaining programs of one
    backend; returns (cheap_call, fast_call, pre_call) where the chain
    calls are argless closures over the cheap phase's real outputs."""
    arrays, _ = _split_arrays(arrays)
    plan = stages.resolve_plan(cfg, backend)
    prims = stages.chain_primitives(plan, cfg)
    if prims is None:
        raise ValueError(
            f"backend {backend!r} resolves to a plan whose chain stages "
            "expose no primitives; the chaining microbenchmark cannot "
            f"time it (plan: {plan})")
    sorter, dp = prims

    cheap_j = jax.jit(
        lambda s: pipeline.cheap_phase(s, arrays, cfg, plan))
    q_pos, t_pos, hv, counters = cheap_j(signals)
    cnt = counters["n_anchors_postvote"]

    fast_j = jax.jit(lambda qp, tp, h, c: pipeline._chain_outputs(
        qp, tp, h, c, cfg, prims))

    def pre_read(qp, tp, h):
        # the pre-fast-path chain program: full-width sort + the
        # dynamic-slice reference DP ("pre" side of the speedup claim).
        # For accelerated backends the sort still runs on the backend's
        # sorter (full width); the reference DP is the pre-PR algorithm.
        sq, st, sv = chaining.sort_anchors_reference(qp, tp, h, cfg,
                                                     sorter=sorter)
        if backend == stages.REFERENCE:
            f, d = chaining.chain_dp_reference(sq, st, sv, cfg)
        else:
            f, d = dp(sq, st, sv)
        res = chaining.best_chain(f, d, sv, cfg)
        return res.t_start, res.score, res.mapped

    pre_j = jax.jit(lambda qp, tp, h: jax.vmap(pre_read)(qp, tp, h))

    return (lambda: cheap_j(signals),
            lambda: fast_j(q_pos, t_pos, hv, cnt),
            lambda: pre_j(q_pos, t_pos, hv))


def _cheap_programs(cfg: MarsConfig, signals, arrays, backend: str):
    """Jit the pre/fast cheap-phase programs of one backend, whole-phase and
    per stage group (detect / query / vote), all on the pipeline's real
    intermediate data.

    Returns (fast_calls, pre_calls): dicts keyed "cheap"/"detect"/"query"/
    "vote" of argless closures.  The "pre" side reconstructs the pre-fast-
    path configuration: per-read vmap, two-median normalization + scatter
    segment means (``events.detect_events_reference``; for the pallas
    backend the unit-batch vmapped kernel), unpacked four-gather query
    (``seeding.query_index_reference``) and per-read vote scatters
    (``vote.vote_filter_reference``).
    """
    packed, unpacked = _split_arrays(arrays)
    plan = stages.resolve_plan(cfg, backend)
    prims = stages.cheap_primitives(plan, cfg)
    if prims is None:
        raise ValueError(f"backend {backend!r} has no batch-level cheap "
                         f"phase to time (plan: {plan})")
    gather = prims.gather

    # ---- detect ----
    if prims.detector is not None:
        det_fast = jax.jit(prims.detector)
        det_prim = stages.get_backend("detect", backend).primitive
        det_pre = jax.jit(jax.vmap(
            lambda s: tuple(x[0] for x in det_prim(s[None], cfg))))
    else:
        det_fast = jax.jit(jax.vmap(
            lambda s: events.detect_events(s, cfg)[:2]))
        det_pre = jax.jit(jax.vmap(
            lambda s: events.detect_events_reference(s, cfg)[:2]))

    # real intermediate data for the later stage groups
    q_pos, t_pos, hit_valid, counters = jax.jit(
        lambda s: pipeline.cheap_phase(s, packed, cfg, plan))(signals)
    means, _n = det_fast(signals)

    def quant_seed(ev, n):
        st = stages.execute_stages({"events": ev, "n_events": n,
                                    "counters": {}},
                                   packed, cfg, plan, ("quantize", "seed"))
        return st["keys"], st["seed_valid"]
    keys, seed_valid = jax.jit(jax.vmap(quant_seed))(
        means, counters["n_events"])

    # ---- query ----
    query_fast = jax.jit(lambda k, v: seeding.query_index(
        k, v, packed, cfg, gather=gather))
    query_pre = jax.jit(jax.vmap(lambda k, v: seeding.query_index_reference(
        k, v, unpacked, cfg, gather=gather)))

    # ---- vote ----
    vote_fast = jax.jit(lambda q, t, h: vote.vote_filter(q, t, h, cfg))
    vote_pre = jax.jit(jax.vmap(
        lambda q, t, h: vote.vote_filter_reference(q, t, h, cfg)))

    # ---- whole cheap phase ----
    cheap_fast = jax.jit(lambda s: pipeline.cheap_phase(s, packed, cfg, plan))

    def cheap_pre_read(signal):
        ev, n, _ = (events.detect_events_reference(signal, cfg)
                    if prims.detector is None else
                    tuple(x[0] for x in det_prim(signal[None], cfg)) + (None,))
        st = stages.execute_stages({"events": ev, "n_events": n,
                                    "counters": {}},
                                   packed, cfg, plan, ("quantize", "seed"))
        tp, hv, _c = seeding.query_index_reference(
            st["keys"], st["seed_valid"], unpacked, cfg, gather=gather)
        qp = jnp.broadcast_to(
            jnp.arange(cfg.max_events, dtype=jnp.int32)[:, None], tp.shape)
        hv, _c2 = vote.vote_filter_reference(qp, tp, hv, cfg)
        return qp, tp, hv
    cheap_pre = jax.jit(jax.vmap(cheap_pre_read))

    fast_calls = {
        "cheap": lambda: cheap_fast(signals),
        "detect": lambda: det_fast(signals),
        "query": lambda: query_fast(keys, seed_valid),
        "vote": lambda: vote_fast(q_pos, t_pos, hit_valid),
    }
    pre_calls = {
        "cheap": lambda: cheap_pre(signals),
        "detect": lambda: det_pre(signals),
        "query": lambda: query_pre(keys, seed_valid),
        "vote": lambda: vote_pre(q_pos, t_pos, hit_valid),
    }
    return fast_calls, pre_calls


def _interleaved(fast_c, pre_c, rounds: int):
    """Paired pre/fast timing: both programs per round, so machine-speed
    swings between rounds hit both equally.  Returns (min fast, min pre,
    median per-round pre/fast ratio) — the median paired ratio is stable
    to a few % where separately-measured absolute times swing ~40% on a
    shared CPU."""
    jax.block_until_ready(fast_c())
    jax.block_until_ready(pre_c())
    tf = tp = float("inf")
    ratios = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        jax.block_until_ready(fast_c())
        tf_k = time.perf_counter() - t0
        t0 = time.perf_counter()
        jax.block_until_ready(pre_c())
        tp_k = time.perf_counter() - t0
        tf, tp = min(tf, tf_k), min(tp, tp_k)
        ratios.append(tp_k / tf_k)
    return tf, tp, float(np.median(ratios))


def bench_backend(cfg: MarsConfig, signals, arrays, backend: str,
                  repeats: int = 5,
                  include_serving: bool = True) -> Dict[str, float]:
    """Stage-group timings (seconds) for one registry backend.

    ``include_serving=False`` skips the serving pre/post group — on the
    pallas backend it runs the interpret-mode kernels through the whole
    driver loop many times (~tens of seconds) and the quick profile does
    not gate on it."""
    cheap_c, fast_c, pre_c = _chain_programs(cfg, signals, arrays, backend)
    packed, _ = _split_arrays(arrays)
    plan = stages.resolve_plan(cfg, backend)
    chunk_j = lambda: pipeline.map_chunk(signals, packed, cfg, plan=plan)
    cfg_pre = cfg.replace(chain_compaction=False)
    plan_pre = stages.resolve_plan(cfg_pre, backend)
    chunk_pre_j = lambda: pipeline.map_chunk(signals, packed, cfg_pre,
                                             plan=plan_pre)

    tf, tp, ratio = _interleaved(fast_c, pre_c, rounds=max(3 * repeats, 15))
    groups = {
        "cheap": time_fn(cheap_c, repeats=repeats),
        "chain_fast": tf,
        "chain_pre": tp,
        "chain_speedup": ratio,
        "map_chunk": time_fn(chunk_j, repeats=repeats),
        "map_chunk_pre": time_fn(chunk_pre_j, repeats=repeats),
    }

    # cheap-phase pre/post groups (pre side is expensive on the pallas
    # backend — the unit-batch vmapped kernel — so fewer rounds)
    cf, cp = _cheap_programs(cfg, signals, arrays, backend)
    ctf, ctp, cratio = _interleaved(cf["cheap"], cp["cheap"],
                                    rounds=max(repeats, 3))
    groups.update(cheap_fast=ctf, cheap_pre=ctp, cheap_speedup=cratio)
    for g in ("detect", "query", "vote"):
        gtf, gtp, gratio = _interleaved(cf[g], cp[g], rounds=max(repeats, 3))
        groups.update({f"{g}_fast": gtf, f"{g}_pre": gtp,
                       f"{g}_speedup": gratio})

    # serving pre/post group (continuous batching across streams)
    if include_serving:
        groups.update(bench_serving(cfg, signals, arrays, backend,
                                    repeats=repeats))
    else:
        groups["serving_skipped"] = True
    return groups


# --------------------------------------------------------------------------- #
# Serving (continuous batching across streams)
# --------------------------------------------------------------------------- #
class _PlanMapper:
    """Minimal Mapper stand-in over pre-built index arrays: exactly the
    ``cfg`` + ``chunk_fn()`` surface ServeDriver needs (no Index object,
    no device re-upload per construction)."""

    def __init__(self, arrays, cfg: MarsConfig, plan):
        self.arrays, self.cfg, self.plan = arrays, cfg, plan

    def chunk_fn(self):
        return lambda sig, nv: pipeline.map_chunk(
            jnp.asarray(sig), self.arrays, self.cfg, n_valid=nv,
            plan=self.plan)


def _serving_programs(cfg: MarsConfig, signals, arrays, backend: str,
                      stream_len: int = 2, chunk: int = 8):
    """(fast_call, pre_call, mapper, streams): the serving pre/post pair on
    one fixed multi-stream workload.

    The workload is R reads split into R/stream_len single-tenant streams
    (short streams — the sequencer-channel shape).  ``pre`` maps each
    stream separately through the unified driver loop, so every stream
    pays its own padded partial chunk (the single-tenant driver this PR
    replaces); ``fast`` serves the identical reads through ServeDriver,
    which packs ready reads across stream boundaries into full chunks.
    Outputs are bit-identical (tests/test_server.py); the speedup is the
    padding the packer eliminates."""
    from repro.core import driver
    from repro.core.server import ServeDriver

    arrays, _ = _split_arrays(arrays)
    plan = stages.resolve_plan(cfg, backend)
    mapper = _PlanMapper(arrays, cfg, plan)
    fn = mapper.chunk_fn()
    n = (signals.shape[0] // stream_len) * stream_len
    streams = [np.asarray(signals[i:i + stream_len], np.float32)
               for i in range(0, n, stream_len)]

    def pre_call():
        return [driver.collect(driver.stream_map(
            fn, driver.array_chunks(s, chunk))) for s in streams]

    def fast_call():
        sd = ServeDriver(mapper, chunk=chunk)
        for si, s in enumerate(streams):
            sd.submit(f"s{si}", s)
        sd.drain()
        return [sd.results(f"s{si}").t_start for si in range(len(streams))]

    return fast_call, pre_call, mapper, streams


def bench_serving(cfg: MarsConfig, signals, arrays, backend: str,
                  repeats: int = 5, offered_load: float = 0.7,
                  chunk: int = 8) -> Dict[str, float]:
    """The serving pre/post group: interleaved single-tenant vs
    continuous-batching timings, plus wall-clock streams/sec and the
    virtual-time p99 latency at a fixed offered load (Poisson arrivals at
    ``offered_load`` x chunk capacity)."""
    from repro.core.server import ServeDriver

    fast_c, pre_c, mapper, streams = _serving_programs(
        cfg, signals, arrays, backend, chunk=chunk)
    tf, tp, ratio = _interleaved(fast_c, pre_c, rounds=max(repeats, 3))
    out = {"serving_fast": tf, "serving_pre": tp, "serving_speedup": ratio,
           "serving_streams": len(streams), "serving_chunk": chunk}

    # throughput + tail latency at fixed offered load (virtual clock:
    # 1 unit = one full-length chunk service)
    rng = np.random.default_rng(0)
    n = len(streams) * streams[0].shape[0]
    times = np.cumsum(rng.exponential(1.0 / (offered_load * chunk), n))
    flat = np.concatenate(streams)
    trace = [(float(times[k]), f"s{k % len(streams)}", flat[k])
             for k in range(n)]

    def serve():
        sd = ServeDriver(mapper, chunk=chunk)
        return sd, sd.serve_trace(trace)

    serve()                                   # warm-up
    t0 = time.perf_counter()
    sd, reports = serve()
    wall = time.perf_counter() - t0
    p99 = float(np.max([r.p99_latency for r in reports.values()]))
    out.update(serving_offered_load=offered_load,
               serving_wall_s=wall,
               serving_streams_per_sec=len(streams) / wall,
               serving_reads_per_sec=n / wall,
               serving_p99_virtual=p99,
               serving_pad_rows=sd.n_pad_rows,
               serving_chunks=sd.n_chunks)
    return out


def bench_serving_ratio(cfg: MarsConfig, signals, arrays,
                        backend: str = stages.REFERENCE,
                        rounds: int = 25) -> Dict[str, float]:
    """The serving twin of ``bench_chain_ratio``: interleaved single-tenant
    (pre) vs continuous-batching (fast) rounds over the same streams,
    median paired ratio as the machine-speed-independent gate estimator."""
    fast_c, pre_c, _, _ = _serving_programs(cfg, signals, arrays, backend)
    tf, tp, ratio = _interleaved(fast_c, pre_c, rounds)
    return {"serving_fast_min": tf, "serving_pre_min": tp, "rounds": rounds,
            "serving_speedup_median": ratio}


# --------------------------------------------------------------------------- #
# Fairness (multi-tenant shed budgets)
# --------------------------------------------------------------------------- #
def _fairness_runs(cfg: MarsConfig, signals, arrays, backend: str,
                   chunk: int = 8):
    """One flooded two-tenant trace, served twice: ``run(False)`` is the
    budget-free legacy driver, ``run(True)`` adds per-tenant shed budgets.

    acme: two short in-budget streams (half the bench reads); flood: one
    stream of ``5*chunk`` identical reads at HIGHER priority with an
    empty budget — the starvation shape of tests/test_tenants.py, where
    the legacy shed rule serves the flooder first and sheds acme.  All
    arrivals and sheds live on the driver's virtual clock, so both runs
    are deterministic: the gated ratio never moves with machine speed."""
    from repro.core.server import ServeDriver, TenantBudget

    arrays_p, _ = _split_arrays(arrays)
    plan = stages.resolve_plan(cfg, backend)
    mapper = _PlanMapper(arrays_p, cfg, plan)
    acme = np.asarray(signals[:max(signals.shape[0] // 2, 2)], np.float32)
    flood = np.repeat(np.asarray(signals[-1:], np.float32), 5 * chunk,
                      axis=0)
    budgets = (TenantBudget("acme", rate=float(chunk)),
               TenantBudget("flood", rate=0.0, burst=1.0))

    def run(with_budgets: bool) -> "ServeDriver":
        sd = ServeDriver(mapper, chunk=chunk, shed=True, shed_window=2.0,
                         cost_model="sim",
                         tenant_budgets=budgets if with_budgets else None)
        half = acme.shape[0] // 2
        sd.submit("a0", acme[:half], tenant="acme", t=0.0)
        sd.submit("a1", acme[half:], tenant="acme", t=0.0)
        sd.submit("f0", flood, tenant="flood", priority=1, t=0.0)
        sd.drain()
        return sd

    return run


def _acme_victims(sd) -> int:
    # n_rejected is the total not-served count (closed-loop sheds are a
    # subset of it), so it IS the victim count — no double counting
    return sum(sd.stream(s).n_rejected for s in ("a0", "a1"))


def bench_fairness(cfg: MarsConfig, signals, arrays,
                   backend: str = stages.REFERENCE,
                   chunk: int = 8) -> Dict[str, object]:
    """The fairness pre/post group: the flooded trace without (pre) and
    with (fast) per-tenant shed budgets.  The headline metric is the
    well-behaved tenant's victim count — its reads not served (shed or
    rejected) — which budgets drive to zero by charging the flooder's
    own overflow instead (tests/test_tenants.py asserts the isolation
    bit-exactly)."""
    run = _fairness_runs(cfg, signals, arrays, backend, chunk=chunk)
    legacy, fair = run(False), run(True)
    vl, vf = _acme_victims(legacy), _acme_victims(fair)
    tr = fair.tenant_report()
    return {"fairness_acme_victims_legacy": vl,
            "fairness_acme_victims_fair": vf,
            "fairness_shed_total_legacy": int(legacy.n_shed),
            "fairness_shed_total_fair": int(fair.n_shed),
            "fairness_flood_shed_fair": int(tr["flood"].n_shed),
            "fairness_flood_over_budget": int(tr["flood"].n_over_budget),
            "fairness_speedup": (1.0 + vl) / (1.0 + vf),
            "fairness_chunk": chunk, "fairness_backend": backend}


def bench_fairness_ratio(cfg: MarsConfig, signals, arrays,
                         backend: str = stages.REFERENCE,
                         rounds: int = 1) -> Dict[str, object]:
    """The fairness twin of ``bench_chain_ratio`` for the regression gate:
    ``(1 + legacy acme victims) / (1 + budgeted acme victims)`` on the
    flooded trace.  Unlike the timing gates this is a VIRTUAL-clock count
    ratio — deterministic by construction, so one round suffices and the
    gate can never be machine-noise flaky."""
    run = _fairness_runs(cfg, signals, arrays, backend)
    vl, vf = _acme_victims(run(False)), _acme_victims(run(True))
    return {"fairness_acme_victims_legacy": vl,
            "fairness_acme_victims_fair": vf,
            "rounds": 1, "deterministic": True,
            "fairness_speedup_median": (1.0 + vl) / (1.0 + vf)}


def _cache_programs(cfg: MarsConfig, signals, arrays, n_tiles: int = 16,
                    cache_slots: int = 4, chunk: int = 8):
    """(tiered_call, resident_call, tiered_mapper): the SAME read stream
    mapped through the out-of-core tiered backend (host-resident tiles,
    ``cache_slots``-slot device cache, prefetching driver loop —
    core/tiered.py) vs the fully-resident table.  The index spans
    ``n_tiles`` tiles, several times the cache, so the tiered side really
    pages; outputs are bit-identical (tests/test_tiered.py), the timing
    difference is the paging + traffic-pre-pass overhead the hot-tile
    cache has to keep small."""
    idx = arrays.get("_index")
    if idx is None:
        raise ValueError(
            "cache microbenchmark needs the host Index: use make_workload "
            "(which embeds it under '_index')")
    tiered = pipeline.Mapper(idx, cfg, backend="tiered", tiles=n_tiles,
                             cache_slots=cache_slots)
    resident = pipeline.Mapper(idx, cfg)
    sig = np.asarray(signals, np.float32)
    return (lambda: tiered.map_signals(sig, chunk=chunk),
            lambda: resident.map_signals(sig, chunk=chunk), tiered)


def bench_cache(cfg: MarsConfig, signals, arrays, repeats: int = 5,
                n_tiles: int = 16, cache_slots: int = 4,
                chunk: int = 8) -> Dict[str, float]:
    """The tiered-index cache group: interleaved tiered-vs-resident
    timings plus the cache's traffic telemetry (hit rate, host->device
    paged bytes) on an index several times the cache size."""
    fast_c, pre_c, mapper = _cache_programs(cfg, signals, arrays, n_tiles,
                                            cache_slots, chunk)
    tf, tp, ratio = _interleaved(fast_c, pre_c, rounds=max(repeats, 3))
    cache = mapper.cache
    cache.reset_stats()
    fast_c()                               # one counted steady-state pass
    return {
        "cache_tiered": tf, "cache_resident": tp, "cache_speedup": ratio,
        "cache_hit_rate": cache.hit_rate,
        "cache_hits": cache.hits, "cache_misses": cache.misses,
        "cache_paged_bytes": cache.paged_bytes,
        "cache_n_tiles": n_tiles, "cache_slots": cache.n_slots,
        "cache_tile_nbytes": cache.tiered.tile_nbytes,
        "cache_nbytes": cache.cache_nbytes,
        "cache_index_nbytes": cache.tiered.nbytes,
    }


def bench_cache_ratio(cfg: MarsConfig, signals, arrays,
                      backend: str = stages.REFERENCE,
                      rounds: int = 25) -> Dict[str, float]:
    """The cache twin of ``bench_chain_ratio``: interleaved resident (pre)
    vs tiered-with-small-cache (fast) rounds over the same reads, median
    paired ratio as the machine-speed-independent gate estimator.  The
    ratio is below 1 (out-of-core paging costs something); the gate
    catches it getting WORSE."""
    del backend                            # tiered vs resident is the pair
    fast_c, pre_c, _ = _cache_programs(cfg, signals, arrays)
    tf, tp, ratio = _interleaved(fast_c, pre_c, rounds)
    return {"cache_fast_min": tf, "cache_pre_min": tp, "rounds": rounds,
            "cache_speedup_median": ratio}


def _fused_programs(cfg: MarsConfig, signals, arrays):
    """(fast_call, pre_call): the whole-phase fused mega-kernel
    (kernels/cheap_fused — ONE launch, detect..vote resident, probed index
    rows gathered from VMEM) vs the SAME pallas plan's per-stage
    batch program (``pipeline.cheap_phase(use_fused=False)``: separate
    detect kernel, pLUTo gathers and segment-sum vote with every
    intermediate materialized between launches).  Outputs are bit-identical
    (tests/kernels/test_cheap_fused.py); the timing difference is the
    launch + HBM round-trip overhead the fusion removes."""
    packed, _ = _split_arrays(arrays)
    plan = stages.resolve_plan(cfg, stages.PALLAS)
    prims = stages.cheap_primitives(plan, cfg)
    if prims is None or prims.fused is None:
        raise ValueError(
            f"plan {plan} resolves no fused cheap kernel "
            "(stages.register_fused_cheap); the fused microbenchmark "
            "cannot time it")
    fast_j = jax.jit(
        lambda s: pipeline.cheap_phase(s, packed, cfg, plan))
    pre_j = jax.jit(
        lambda s: pipeline.cheap_phase(s, packed, cfg, plan,
                                       use_fused=False))
    return (lambda: fast_j(signals)), (lambda: pre_j(signals))


# Default read-grid cap for the fused gate phase: the pre side runs the
# full per-stage interpret-mode pallas program, so the gate trims the grid
# to keep `run_tier1.sh --bench` wall time bounded (the reduction is
# recorded in the gate record; both sides share the grid).
FUSED_GATE_READS = 8


def bench_fused(cfg: MarsConfig, signals, arrays,
                repeats: int = 5) -> Dict[str, float]:
    """The fused mega-kernel group: interleaved fused-vs-per-stage cheap
    phase on the pallas plan, plus the grid markers."""
    fast_c, pre_c = _fused_programs(cfg, signals, arrays)
    tf, tp, ratio = _interleaved(fast_c, pre_c, rounds=max(repeats, 3))
    return {"fused_fast": tf, "fused_pre": tp, "fused_speedup": ratio,
            "fused_n_reads": int(signals.shape[0]),
            "fused_mode": ("interpret" if jax.default_backend() == "cpu"
                           else jax.default_backend())}


def bench_fused_ratio(cfg: MarsConfig, signals, arrays,
                      backend: str = stages.PALLAS,
                      rounds: int = 25,
                      n_reads: int = FUSED_GATE_READS) -> Dict[str, float]:
    """The fused twin of ``bench_chain_ratio``: interleaved per-stage-pallas
    (pre) vs mega-kernel (fast) rounds over the same reads, median paired
    ratio as the machine-speed-independent gate estimator."""
    del backend              # the fused/per-stage pair IS the pallas backend
    if n_reads and n_reads < signals.shape[0]:
        signals = signals[:n_reads]
    fast_c, pre_c = _fused_programs(cfg, signals, arrays)
    tf, tp, ratio = _interleaved(fast_c, pre_c, rounds)
    return {"fused_fast_min": tf, "fused_pre_min": tp, "rounds": rounds,
            "n_reads": int(signals.shape[0]),
            "fused_speedup_median": ratio}


def bench_chain_ratio(cfg: MarsConfig, signals, arrays,
                      backend: str = stages.REFERENCE,
                      rounds: int = 25) -> Dict[str, float]:
    """Machine-speed-independent chaining measurement for the regression
    gate.

    Absolute ms are not comparable across runs on a shared/containerized
    CPU (whole-process speed swings ~1.5x), so the pre and fast chain
    programs are timed in INTERLEAVED rounds — each round yields a paired
    pre/fast ratio under the same instantaneous machine state — and the
    MEDIAN of the per-round ratios is the estimator (stable to ~3% across
    processes where min-of-N absolute times swing ~40%)."""
    _, fast_c, pre_c = _chain_programs(cfg, signals, arrays, backend)
    tf, tp, ratio = _interleaved(fast_c, pre_c, rounds)
    return {"chain_fast_min": tf, "chain_pre_min": tp, "rounds": rounds,
            "chain_speedup_median": ratio}


def bench_cheap_ratio(cfg: MarsConfig, signals, arrays,
                      backend: str = stages.REFERENCE,
                      rounds: int = 25) -> Dict[str, float]:
    """The cheap-phase twin of ``bench_chain_ratio``: interleaved pre/fast
    whole-cheap-phase rounds, median paired ratio as the gate estimator."""
    fast_calls, pre_calls = _cheap_programs(cfg, signals, arrays, backend)
    tf, tp, ratio = _interleaved(fast_calls["cheap"], pre_calls["cheap"],
                                 rounds)
    return {"cheap_fast_min": tf, "cheap_pre_min": tp, "rounds": rounds,
            "cheap_speedup_median": ratio}


def run(n_reads: int = 32, ref_events: int = 20_000, junk_frac: float = 0.5,
        repeats: int = 5, backends=(stages.REFERENCE, stages.PALLAS),
        seed: int = 0, pallas_serving: bool = True,
        pallas_reduced_reads: int = 0) -> Dict:
    """One full profile record.  ``pallas_reduced_reads`` > 0 caps the
    pallas backend's bench groups (and the fused group) to that many reads
    — the interpret-mode per-read "pre" programs dominate bench wall time
    — with the reduction marked in the record (``grid_reads`` /
    ``grid_reduced``) so the recorded ratios stay honest: the pre/fast
    pair of every group shares one grid."""
    cfg, signals, arrays = make_workload(n_reads, ref_events, junk_frac, seed)
    rec = {
        "git_sha": git_sha(),
        "machine": hardware_key(),
        "workload": dict(n_reads=n_reads, ref_events=ref_events,
                         junk_frac=junk_frac, repeats=repeats, seed=seed,
                         signal_len=cfg.signal_len,
                         max_anchors=cfg.max_anchors,
                         chain_band=cfg.chain_band,
                         chain_widths=list(cfg.chain_widths),
                         chain_capacity_frac=cfg.chain_capacity_frac),
        "backends": {},
    }
    reduced = (0 < pallas_reduced_reads < n_reads)
    sig_pallas = signals[:pallas_reduced_reads] if reduced else signals
    for b in backends:
        inc = pallas_serving or b != stages.PALLAS
        sig_b = sig_pallas if b == stages.PALLAS else signals
        rec["backends"][b] = bench_backend(cfg, sig_b, arrays, b,
                                           repeats=repeats,
                                           include_serving=inc)
        rec["backends"][b].update(grid_reads=int(sig_b.shape[0]),
                                  grid_reduced=bool(sig_b.shape[0]
                                                    < n_reads))
    rec["cache"] = bench_cache(cfg, signals, arrays, repeats=repeats)
    rec["fused"] = bench_fused(cfg, sig_pallas, arrays, repeats=repeats)
    rec["fairness"] = bench_fairness(cfg, signals, arrays)
    return rec
