"""The benchmark harness: one run of one cell, from set-up to result line.

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``); per-layer metrics are read by
``bench/metrics/<metric>.py``.  Everything here is general: a cell, a mix
or a metric is added by adding files.

A run:

1. set-up (``setup_s``, from process start): the genome and the read pool
   from the seed, the program's own index build and upload, and one warm-up
   pass through every program shape the window uses;
2. the measured window: a closed loop through ``driver.stream_map`` (the
   loop ``Mapper.map_signals`` runs) or an open loop of Poisson arrivals
   through ``ServeDriver.submit`` / ``drain``;
3. the device's peak memory, then the program's state freed;
4. the check: every answer the window produced against the plain
   reference (``bench/reference.py``), run on the same device.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import pathlib
import resource
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import reference, tracing, traffic

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = pathlib.Path(__file__).resolve().parent
OUT = ROOT / ".bench_out"

# Reads are compared exactly: every compared number is a count of
# disagreements, and its limit is 0 (PERF.md, "How correct is decided").
CHECKS = ("rows_missing", "t_start_mismatch", "score_mismatch",
          "mapped_mismatch", "n_events_mismatch", "counter_mismatch")

clock = time.perf_counter


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at the program's fixed place
    (``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache/`` in the
    checkout), keeping every program however fast it compiles, so that
    only a cell's first run in a checkout compiles.  Returns the
    directory."""
    import jax
    from repro.launch import compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return compile_cache.enable()


# --------------------------------------------------------------------------- #
# Cells
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    spec: dict                 # the whole BENCHMARK.json

    @property
    def params(self) -> dict:
        return self.config["params"]

    @property
    def chunk(self) -> int:
        return int(self.traffic.get("chunk", self.config["chunk"]))


def load_spec(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    spec = load_spec(root)
    wl = {w["name"]: w for w in spec["workloads"]}
    if name not in wl:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(wl)}")
    w = wl[name]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / cfg["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic" /
                      f"{w['traffic']}.json").read_text())
    return Cell(name=name, config=config, traffic=mix, chips=w["chips"],
                spec=spec)


def end_to_end_metrics(spec: dict, cell: str) -> List[dict]:
    return [m for m in spec["end_to_end"]
            if cell in m.get("workloads", [cell])]


def per_layer_metrics(spec: dict, cell: str) -> List[dict]:
    e2e = {m["name"] for m in end_to_end_metrics(spec, cell)}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def metric_reader(name: str) -> Callable:
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------------------- #
# Set-up
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class Setup:
    cfg: object                # repro MarsConfig
    genome: traffic.Genome
    pool: traffic.ReadPool
    mapper: object             # repro Mapper


def mars_config(params: dict):
    from repro.core import MarsConfig
    p = dict(params)
    if "chain_widths" in p:
        p["chain_widths"] = tuple(p["chain_widths"])
    return MarsConfig(**p)


def set_up(cell: Cell, seed: int, program_params: Optional[dict] = None
           ) -> Setup:
    """The cell's data from the seed and the program's mapper over its own
    index.  ``program_params`` overrides configuration keys for the
    program alone (the control); the reference keeps the cell's."""
    import jax
    from repro.core import Mapper, build_index
    cfg = mars_config({**cell.params, **(program_params or {})})
    t = [clock()]
    with span("bench.generate"):
        genome = traffic.make_genome_for(cell.config, seed)
        pool = traffic.make_pool(genome, cell.traffic, cfg.signal_len, seed)
    t.append(clock())
    index = build_index(genome.events_concat, genome.n_events, cfg)
    t.append(clock())
    mapper = Mapper(index, cfg, use_kernels=cell.config["plan"] == "pallas")
    jax.block_until_ready(mapper.arrays)
    t.append(clock())
    log(f"[setup] generate {t[1] - t[0]:.3f} s, index build "
        f"{t[2] - t[1]:.3f} s, upload {t[3] - t[2]:.3f} s")
    log(f"[setup] {cell.name}: genome {cell.config['genome_len']} bp, "
        f"index {index.n_entries} entries, plan {dict(mapper.plan)}, "
        f"pool {pool.signals.shape[0]} reads "
        f"({int(np.sum(pool.kind == 1))} junk, "
        f"{int(np.sum(pool.kind == 2))} off-target), chunk {cell.chunk}")
    return Setup(cfg=cfg, genome=genome, pool=pool, mapper=mapper)


# --------------------------------------------------------------------------- #
# Windows
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class Window:
    start: float               # clock() at the window's start
    seconds: float             # from the start to the last answer
    rows: np.ndarray           # pool row of every answered read
    out: Dict[str, np.ndarray]  # per read: t_start, score, mapped, n_events
    counters: List[dict]       # per chunk: program counters
    counter_rows: List[np.ndarray]  # per chunk: pool rows it held
    attempted: int
    failed: int
    extra: dict


def _concat(outs) -> Dict[str, np.ndarray]:
    fields = ("t_start", "score", "mapped", "n_events")
    if not outs:
        return {f: np.zeros(0) for f in fields}
    return {f: np.concatenate([np.asarray(getattr(o, f)) for o in outs])
            for f in fields}


class StallWatch:
    """The window's longest gaps between two steps of its loop, and what
    the process did in each: CPU seconds, context switches given up and
    taken away, major page faults and seconds of garbage collection.  A
    gap with little CPU time was spent waiting (the device, the kernel's
    scheduler); one with CPU time as long as itself was spent computing."""

    def __init__(self, threshold_s: float = 0.4):
        self.threshold_s = threshold_s
        self.gaps: List[dict] = []
        self.gc: List[tuple] = []           # (start, seconds) of each run
        self._gc_t = 0.0
        self._last = (clock(), resource.getrusage(resource.RUSAGE_SELF))
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t = clock()
        else:
            self.gc.append((self._gc_t, clock() - self._gc_t))

    def tick(self) -> None:
        now, ru = clock(), resource.getrusage(resource.RUSAGE_SELF)
        t, ru0 = self._last
        if now - t > self.threshold_s:
            self.gaps.append(dict(
                at_s=t, gap_s=now - t,
                cpu_s=(ru.ru_utime + ru.ru_stime
                       - ru0.ru_utime - ru0.ru_stime),
                nvcsw=ru.ru_nvcsw - ru0.ru_nvcsw,
                nivcsw=ru.ru_nivcsw - ru0.ru_nivcsw,
                majflt=ru.ru_majflt - ru0.ru_majflt,
                gc_s=sum(d for g, d in self.gc if t <= g < now)))
        self._last = (now, ru)

    def close(self, t0: float) -> List[dict]:
        gc.callbacks.remove(self._on_gc)
        worst = sorted(self.gaps, key=lambda g: -g["gap_s"])[:5]
        return [dict(g, at_s=g["at_s"] - t0) for g in worst]


def batch_window(fn, pool: traffic.ReadPool, chunk: int, seconds: float,
                 min_chunks: int = 1) -> Window:
    """Closed loop: chunks cycle the read pool until ``seconds`` have
    passed (and at least ``min_chunks`` were sent); the window ends when
    the last dispatched chunk is answered."""
    from repro.core import driver
    P = pool.signals.shape[0]
    held: List[np.ndarray] = []
    outs, counters, t_done = [], [], []
    watch = StallWatch()
    t0 = clock()

    def source():
        ci = 0
        while ci < min_chunks or clock() - t0 < seconds:
            with span("bench.chunk_source"):
                rows = (ci * chunk + np.arange(chunk)) % P
                sig = pool.signals[rows]
            held.append(rows)
            watch.tick()
            yield ci, chunk, sig
            ci += 1

    with span(tracing.WINDOW_SPAN), span("driver.stream_map"):
        for ci, n_valid, out in driver.stream_map(fn, source()):
            t_done.append(clock() - t0)
            outs.append(out)
            counters.append(out.counters)
    n = len(outs) * chunk
    return Window(start=t0, seconds=t_done[-1],
                  rows=np.concatenate(held)[:n], out=_concat(outs),
                  counters=counters, counter_rows=held[:len(outs)],
                  attempted=n, failed=len(held) * chunk - n,
                  extra=dict(t_done=np.array(t_done),
                             stalls=watch.close(t0)))


def stamped_serve_driver(mapper, chunk: int):
    """``ServeDriver`` that stamps the wall clock on every read when its
    chunk's results are routed to their streams."""
    from repro.core.server import ServeDriver

    class Stamped(ServeDriver):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.done_at: Dict[tuple, float] = {}

        def _route(self, ci, n_valid, out):
            slots = self._inflight[ci][1]
            super()._route(ci, n_valid, out)
            now = clock()
            for s in slots:
                self.done_at[(s.stream, s.idx)] = now

    return Stamped(mapper, chunk=chunk)


def served_window(mapper, pool: traffic.ReadPool, mix: dict, chunk: int,
                  seconds: float, seed: int) -> Window:
    """Open loop: Poisson arrivals at the mix's fixed rate, each read
    submitted once it is due on the wall clock; between submissions the
    driver drains its queue.  Arrivals start ``settle_s`` (from the mix)
    before the window, so that the window sees the queue in its steady
    state; the window holds the reads due in its ``seconds``."""
    settle = float(mix.get("settle_s", 0.0))
    due, chan, rows = traffic.arrivals(mix, settle + seconds, seed)
    due = due - settle
    n = due.shape[0]
    sd = stamped_serve_driver(mapper, chunk)
    keys: List[Optional[tuple]] = [None] * n
    late = np.zeros(n)
    i = 0
    window = None                  # the window's span, opened at its start
    chunks0 = pads0 = 0            # the driver's counts when it opens
    watch = StallWatch()
    t0 = clock() + settle
    while i < n:
        now = clock() - t0
        watch.tick()
        if now >= 0 and window is None:
            window = span(tracing.WINDOW_SPAN)
            window.__enter__()
            chunks0, pads0 = sd.n_chunks, sd.n_pad_rows
        if due[i] > now:
            with span("bench.wait_arrival"):
                time.sleep(due[i] - now)
            continue
        j = int(np.searchsorted(due, now, side="right"))
        with span("serve.submit"):
            for k in range(i, j):
                sid = f"ch{chan[k]}"
                keys[k] = (sid, len(sd.stream(sid).t_start))
                sd.submit(sid, pool.signals[rows[k]])
                late[k] = clock() - t0 - due[k]
        i = j
        with span("serve.drain"):
            sd.drain()
    if window is not None:
        window.__exit__(None, None, None)
    done = np.array([sd.done_at.get(k, math.nan) for k in keys]) - t0
    ok = ~np.isnan(done)
    # a read the driver refused (its bounded queue) has failed; a read it
    # admitted and never answered is lost, and counts against correctness
    admitted = np.array([sd.stream(k[0]).admitted[k[1]] for k in keys])
    res = {sid: sd.results(sid) for sid in sd.stream_ids()}
    fields = ("t_start", "score", "mapped", "n_events")
    out = {f: np.array([getattr(res[k[0]], f)[k[1]] for k, o in
                        zip(keys, ok) if o]) for f in fields}
    inside = due >= 0
    lat_ms = (done[inside & ok] - due[inside & ok]) * 1e3
    return Window(
        start=t0, seconds=float(np.nanmax(done)) if ok.any() else 0.0,
        rows=rows[ok], out=out, counters=[dict(sd.counters)],
        counter_rows=[rows[ok]], attempted=int(inside.sum()),
        failed=int(np.sum(inside & ~ok)),
        extra=dict(latency_ms=lat_ms, late_s=late[inside], due=due[inside],
                   done=done[inside], n_chunks=sd.n_chunks - chunks0,
                   n_pad_rows=sd.n_pad_rows - pads0, chunk=chunk,
                   stalls=watch.close(t0),
                   missing=int(np.sum(admitted & ~ok)),
                   rejected=sum(sd.stream(s).n_rejected
                                for s in sd.stream_ids())))


def backlog(due: np.ndarray, done: np.ndarray, t: float) -> int:
    """Reads due by ``t`` and not answered by ``t``."""
    return int(np.sum(due <= t) - np.sum(done <= t))


def run_window(cell: Cell, setup: Setup, seconds: float, seed: int,
               mapper=None) -> Window:
    mapper = mapper or setup.mapper
    if cell.traffic["loop"] == "closed":
        return batch_window(mapper.chunk_fn(), setup.pool, cell.chunk,
                            seconds)
    return served_window(mapper, setup.pool, cell.traffic, cell.chunk,
                         seconds, seed)


def warm_up(cell: Cell, setup: Setup, mapper=None) -> None:
    """Every program shape the window uses, compiled and run once."""
    mapper = mapper or setup.mapper
    if cell.traffic["loop"] == "closed":
        batch_window(mapper.chunk_fn(), setup.pool, cell.chunk, 0.0,
                     min_chunks=2)
    else:
        sd = stamped_serve_driver(mapper, cell.chunk)
        for k in range(cell.chunk + 1):
            sd.submit(f"ch{k % 4}", setup.pool.signals[k])
        sd.drain()


# --------------------------------------------------------------------------- #
# Correctness
# --------------------------------------------------------------------------- #
def reference_answers(cell: Cell, setup: Setup, rows: np.ndarray) -> dict:
    """The plain reference's answers for the given pool rows (unique),
    computed on the device after the program's state is gone."""
    p = dict(cell.params)
    idx = reference.build_index(setup.genome.events_concat,
                                setup.genome.n_events, p)
    uniq = np.unique(rows)
    ref = reference.map_reads(setup.pool.signals[uniq], idx, p)
    return {"rows": uniq, **ref}


def compare(win: Window, ref: dict, signal_len: int) -> Dict[str, int]:
    """Counts of disagreements between the window's answers and the
    reference's, over every answered read and every chunk's counters."""
    pos = np.searchsorted(ref["rows"], win.rows)
    checks = {"rows_missing": win.extra.get("missing", win.failed)}
    for f in ("t_start", "score", "mapped", "n_events"):
        got = np.asarray(win.out[f])
        want = ref[f][pos]
        if f == "t_start":
            want = want.astype(np.int64)
            got = got.astype(np.int64)
        checks[f"{f}_mismatch"] = int(np.sum(got != want))
    bad = 0
    for c, r in zip(win.counters, win.counter_rows):
        p = np.searchsorted(ref["rows"], r)
        for k in reference.COUNTERS:
            bad += int(int(c[k]) != int(ref[k][p].sum()))
        bad += int(int(c["n_reads"]) != len(r))
        bad += int(int(c["n_samples"]) != len(r) * signal_len)
    checks["counter_mismatch"] = bad
    return checks


# --------------------------------------------------------------------------- #
# One run
# --------------------------------------------------------------------------- #
def device_info(n: int) -> dict:
    import jax
    devs = jax.devices()[:n]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


def load_peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


class CompileCounter:
    """Counts traces and compilations JAX reports, to show that none
    happens inside the measured window, and the persistent cache's hits
    and misses, to show that set-up finds every program in the cache."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.times: List[float] = []
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:
            self.times.append(clock())

    def since(self, t: float) -> int:
        return sum(x >= t for x in self.times)

    def _on_event(self, event, **kw):
        for k in self.cache:
            if event == f"/jax/compilation_cache/cache_{k}":
                self.cache[k] += 1


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, wrap: Optional[Callable] = None,
        program_params: Optional[dict] = None) -> dict:
    """One run of ``cell``; returns the result line's object.  ``wrap``
    takes the mapper and returns the one the timed path drives, and
    ``program_params`` runs the program with other configuration keys;
    both exist for the checks of the comparison itself
    (``bench/control.py``, ``tests/bench``)."""
    import jax

    compiles = CompileCounter()
    log(f"[setup] {clock() - t_start:.3f} s to start JAX and read the cell")
    setup = set_up(cell, seed, program_params)
    mapper = setup.mapper if wrap is None else wrap(setup.mapper)
    t_warm = clock()
    warm_up(cell, setup, mapper)
    log(f"[setup] warm-up {clock() - t_warm:.3f} s; compile cache "
        f"{compiles.cache['hits']} hits, {compiles.cache['misses']} misses")

    trace_dir = OUT / "trace" / cell.name
    if trace:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    # set-up's objects leave the collector's generations, so that a
    # collection inside the window walks only what the window makes
    gc.collect()
    gc.freeze()
    win = run_window(cell, setup, seconds, seed, mapper)
    gc.unfreeze()
    n_compiles = compiles.since(win.start)
    if trace:
        jax.profiler.stop_trace()
    setup_s = win.start - t_start
    log(f"[setup] {setup_s:.3f} s to the window's start")
    diagnose(win, seconds)
    dev = device_info(cell.chips)
    log(f"[window] {win.attempted} reads attempted, {win.failed} failed, "
        f"{win.seconds:.3f} s to the last answer; {n_compiles} traces or "
        f"compilations inside the window; peak device memory "
        f"{dev['memory_peak_bytes']} bytes")

    acc = traffic.score_accuracy(
        win.out["t_start"], win.out["mapped"], setup.pool.true_pos[win.rows],
        setup.pool.true_strand[win.rows], setup.pool.mappable[win.rows],
        setup.pool.n_bases[win.rows], setup.genome.n_events)
    log(f"[accuracy] P={acc['precision']:.4f} R={acc['recall']:.4f} "
        f"F1={acc['f1']:.4f} over {win.rows.shape[0]} answered reads")
    for g in win.extra["stalls"]:
        log("[stall] " + json.dumps(g))
    if "latency_ms" in win.extra and len(win.extra["latency_ms"]):
        q = (50, 90, 95, 99, 99.9)
        pct = np.percentile(win.extra["latency_ms"], q)
        log("[latency] " + ", ".join(f"p{k} {v:.3f} ms"
                                     for k, v in zip(q, pct)))
    if "late_s" in win.extra:
        late = win.extra["late_s"]
        log(f"[generator] lateness p50 {np.median(late) * 1e3:.3f} ms, "
            f"max {late.max() * 1e3:.3f} ms; {win.extra['rejected']} "
            f"reads rejected")

    metrics = {}
    if not trace:
        metrics = end_to_end(cell, win, setup, setup_s)
    else:
        metrics, summary = per_layer(cell, win, setup, dev, trace_dir)

    # the program's state goes before the reference runs on the device
    del mapper
    setup.mapper = None
    gc.collect()
    t_ref = clock()
    ref = reference_answers(cell, setup, win.rows)
    checks = compare(win, ref, setup.cfg.signal_len)
    log(f"[reference] {ref['rows'].shape[0]} distinct reads in "
        f"{clock() - t_ref:.3f} s")

    result = {
        "correct": all(v <= 0 for v in checks.values()),
        "attempted": int(win.attempted),
        "failed": int(win.failed),
        "metrics": metrics,
        "device": dev,
    }
    if trace:
        result["device"].update(busy_s=summary["busy_s"],
                                window_s=summary["window_s"])
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    if dev["platform"] == "tpu":
        load_peaks(dev["kind"])
    result["checks"] = {k: {"value": int(checks[k]), "limit": 0}
                        for k in CHECKS}
    for k in CHECKS:
        log(f"check {k} = {checks[k]} (limit 0)")
    return result


def diagnose(win: Window, seconds: float) -> None:
    """How the end-to-end numbers settle over the window: each read again
    over its first half and three quarters (standard error only)."""
    for frac in (0.5, 0.75):
        cut = frac * seconds
        if "t_done" in win.extra:
            t = win.extra["t_done"]
            k = int(np.sum(t <= cut))
            if k:
                log(f"[settle] first {frac:.2f}: {k} chunks, "
                    f"{k / t[k - 1]:.4f} chunks/s")
        elif "due" in win.extra:
            due, done = win.extra["due"], win.extra["done"]
            sel = (due < cut) & ~np.isnan(done)
            lat = (done[sel] - due[sel]) * 1e3
            if lat.size:
                log(f"[settle] first {frac:.2f}: p50 "
                    f"{np.percentile(lat, 50):.3f} ms, p90 "
                    f"{np.percentile(lat, 90):.3f} ms")


def end_to_end(cell: Cell, win: Window, setup: Setup,
               setup_s: float) -> dict:
    vals = {"setup_s": setup_s}
    if cell.traffic["loop"] == "closed":
        samples = win.attempted * setup.cfg.signal_len
        vals["map_msamples_per_s"] = samples / win.seconds / 1e6
    else:
        lat = win.extra["latency_ms"]
        vals["read_latency_p50_ms"] = float(np.percentile(lat, 50))
        vals["read_latency_p90_ms"] = float(np.percentile(lat, 90))
    out = {}
    for m in end_to_end_metrics(cell.spec, cell.name):
        out[m["name"]] = {"value": vals[m["name"]], "unit": m["unit"]}
    return out


def per_layer(cell: Cell, win: Window, setup: Setup, dev: dict,
              trace_dir: pathlib.Path):
    files = sorted(trace_dir.rglob("*.xplane.pb"))
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    record = tracing.load(str(files[-1]))
    summary = tracing.summarize(record)
    OUT.mkdir(parents=True, exist_ok=True)
    ctx = dict(record=record, summary=summary, window=win,
               params=cell.params, peaks=load_peaks(dev["kind"]),
               reads=win.attempted - win.failed)
    out = {}
    for m in per_layer_metrics(cell.spec, cell.name):
        v = metric_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out, summary
