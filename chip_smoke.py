"""Smoke run of the MARS read mapper on a TPU, through the user entry points.

Dataset D1 (SARS-CoV-2) at its published genome length, 29,903 bp, in the
paper's MARS pipeline (``ms_fixed``): the index holds about 6e4 packed
entries and 2^18 + 1 bucket starts; 4,096 simulated reads of 1,024 samples
are mapped in chunks of 256.

One chip (no arguments), in one process:

  1. device check: JAX's first device is a TPU and the Pallas kernels are
     compiled by Mosaic, not interpreted;
  2. batch mapping, reference plan (``repro.launch.map_reads``);
  3. the same with ``--use-kernels``: the plan must resolve every kernel
     stage to Pallas and engage the fused cheap-phase kernel, and its
     results and counters must be bit-identical to phase 2;
  4. the tiered index (16 host tiles behind a 4-slot device cache),
     bit-identical to phase 2;
  5. serving: 8 streams x 64 reads from two tenants through ``ServeDriver``;
     each stream's results equal ``map_signals`` on that stream alone.

``--chips 4`` runs only the four-chip comparison: the partitioned index
over the ``model`` axis of a (1, 4) mesh with ``query:a2a`` and
``query:ring``, and the reference plan sharded over a 4-way ``data`` mesh,
each bit-identical to the single-device reference.

Any mismatch raises.  The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU the script exits non-zero before mapping anything.

    python chip_smoke.py
    python chip_smoke.py --chips 4
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import time

import jax
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out" / "chip_smoke"
DATASET, MODE = "D1", "ms_fixed"
N_READS, CHUNK = 4096, 256
STREAMS, READS_PER_STREAM = 8, 64


def _setup(n_reads):
    from repro.core import build_index
    from repro.signal import datasets
    spec = datasets.DATASETS[DATASET]
    cfg = datasets.config_for(spec).with_mode(MODE)
    ref, reads = datasets.build(spec, cfg, n_reads)
    index = build_index(ref.events_concat, ref.n_events, cfg)
    print(f"[setup] {DATASET} genome={spec.genome_len}bp "
          f"index={index.n_entries} entries, {cfg.n_buckets + 1} bucket "
          f"starts; reads={n_reads} x {cfg.signal_len} samples")
    return cfg, index, reads


def _timed_map(mapper, signals, chunk, tag):
    t0 = time.perf_counter()
    out = mapper.map_signals(signals, chunk=chunk)
    np.asarray(out.t_start)                # wait for the device
    dt = time.perf_counter() - t0
    print(f"[{tag}] map_signals {len(signals)} reads in {dt:.3f}s "
          f"({len(signals) / dt:.1f} reads/s)")
    return out


def assert_same(got, want, tag, counters=True):
    """Per-read outputs (and, for whole jobs, every chunk counter) equal."""
    from repro.core import stages
    for field in ("t_start", "score", "mapped", "n_events"):
        g = np.asarray(getattr(got, field))
        w = np.asarray(getattr(want, field))
        if g.shape != w.shape or not np.array_equal(g, w):
            bad = int(np.sum(g != w)) if g.shape == w.shape else "shape"
            raise AssertionError(f"{tag}: {field} differs from the "
                                 f"reference ({bad} reads)")
    if counters:
        for k in stages.CHUNK_COUNTER_SCHEMA:
            if int(got.counters[k]) != int(want.counters[k]):
                raise AssertionError(
                    f"{tag}: counter {k} = {int(got.counters[k])}, "
                    f"reference {int(want.counters[k])}")
    print(f"[{tag}] bit-identical to the reference "
          f"({len(np.asarray(want.t_start))} reads"
          f"{', all counters' if counters else ''})")


def launcher(use_kernels, n_reads, chunk):
    """Phases 2/3: the batch launcher as a user runs it."""
    from repro.launch import map_reads
    tag = "pallas" if use_kernels else "reference"
    wd = OUT / f"map_{tag}"
    shutil.rmtree(wd, ignore_errors=True)
    argv = ["--dataset", DATASET, "--mode", MODE, "--reads", str(n_reads),
            "--chunk", str(chunk), "--workdir", str(wd)]
    print(f"[{tag}] python -m repro.launch.map_reads {' '.join(argv)}"
          + (" --use-kernels" if use_kernels else ""))
    return map_reads.main(argv + (["--use-kernels"] if use_kernels else []))


def check_pallas_plan(cfg):
    """Phase 3's precondition: nothing falls back to the reference."""
    from repro.core import stages
    plan = stages.resolve_plan(cfg, stages.PALLAS)
    p = dict(plan)
    fell_back = [s for s in ("detect", "query", "sort", "dp")
                 if p[s] != stages.PALLAS]
    if fell_back:
        raise AssertionError(f"pallas plan fell back to the reference for "
                             f"{fell_back}: {plan}")
    if stages.fused_cheap_backend(plan, cfg) is None:
        raise AssertionError("the fused cheap-phase kernel does not engage")
    print(f"[pallas] plan {plan}; fused cheap-phase kernel engaged")


def serve(mapper, reads, chunk, streams, per_stream):
    """Phase 5: interleaved multi-tenant streams through ServeDriver."""
    sd = mapper.serve(chunk=chunk)
    sig = reads.signals[:streams * per_stream]
    t0 = time.perf_counter()
    for i in range(per_stream):
        for k in range(streams):
            sd.submit(f"s{k}", sig[k * per_stream + i], tenant=f"t{k % 2}")
    sd.drain()
    dt = time.perf_counter() - t0
    print(f"[serve] {len(sig)} reads, {streams} streams, 2 tenants in "
          f"{dt:.3f}s ({len(sig) / dt:.1f} reads/s, {sd.n_chunks} chunks)")
    for k in range(streams):
        want = mapper.map_signals(sig[k * per_stream:(k + 1) * per_stream],
                                  chunk=chunk)
        assert_same(sd.results(f"s{k}"), want, f"serve s{k}", counters=False)


def one_chip(n_reads=N_READS, chunk=CHUNK, streams=STREAMS,
             per_stream=READS_PER_STREAM):
    from repro.core import Mapper
    cfg, index, reads = _setup(n_reads)

    acc_ref = launcher(False, n_reads, chunk)
    ref = _timed_map(Mapper(index, cfg), reads.signals, chunk, "reference")

    check_pallas_plan(cfg)
    acc_pal = launcher(True, n_reads, chunk)
    pallas = Mapper(index, cfg, use_kernels=True)
    assert_same(_timed_map(pallas, reads.signals, chunk, "pallas"), ref,
                "pallas")
    if acc_pal != acc_ref:
        raise AssertionError(f"launcher accuracy differs: pallas {acc_pal} "
                             f"vs reference {acc_ref}")

    tiered = Mapper(index, cfg, backend="tiered", tiles=16, cache_slots=4)
    assert_same(_timed_map(tiered, reads.signals, chunk, "tiered"), ref,
                "tiered")
    c = tiered.cache
    print(f"[tiered] cache hits={c.hits} misses={c.misses} "
          f"paged_bytes={c.paged_bytes} hit_rate={c.hit_rate:.3f}")

    serve(pallas, reads, chunk, streams, per_stream)


def four_chips(n_reads=N_READS, chunk=CHUNK):
    from repro.core import Mapper
    from repro.launch.mesh import make_mesh
    cfg, index, reads = _setup(n_reads)
    ref = _timed_map(Mapper(index, cfg), reads.signals, chunk, "reference")

    model_mesh = make_mesh((1, 4), ("data", "model"))
    for backend in ("a2a", "ring"):
        m = Mapper(index, cfg, backend=backend, mesh=model_mesh)
        for k, arr in m.arrays.items():
            where = sorted((s.index[0].start or 0, s.device.id)
                           for s in arr.addressable_shards)
            if len({dev for _, dev in where}) != 4:
                raise AssertionError(f"{backend}: {k} partitions sit on "
                                     f"{where}, not four distinct devices")
            print(f"[{backend}] {k} {arr.shape}: (partition, device) "
                  f"{where}")
        assert_same(_timed_map(m, reads.signals, chunk, backend), ref,
                    backend)

    data_mesh = make_mesh((4,), ("data",))
    m = Mapper(index, cfg, mesh=data_mesh)
    assert_same(_timed_map(m, reads.signals, chunk, "sharded-reference"),
                ref, "sharded-reference")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (first device: "
                 f"{devices[0].platform}); nothing was run")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
                 f"devices; JAX found {len(devices)}")
    sys.path.insert(0, str(ROOT / "src"))
    from repro import kernels
    from repro.launch import compile_cache
    if kernels.INTERPRET:
        raise AssertionError("Pallas kernels would run in interpret mode")
    cache = compile_cache.enable()
    d = devices[0]
    print(f"[device] {d.platform} kind={d.device_kind!r} "
          f"count={len(devices)} compile_cache={cache}")
    OUT.mkdir(parents=True, exist_ok=True)

    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    print(f"[done] all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
