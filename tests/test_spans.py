"""The program's profiler spans (``driver.dispatch`` / ``driver.fetch`` in
``driver.stream_map``, ``serve.pack`` / ``serve.route`` in
``ServeDriver``): one of each per chunk, read back from a trace the
profiler writes here, and outputs equal to an unprofiled run's."""
import pathlib
import sys

import jax
import numpy as np

from repro.core import Mapper, ServeDriver, driver

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import tracing  # noqa: E402

FIELDS = ("t_start", "score", "mapped", "n_events")


def _profiled(tmp_path, fn):
    """``fn()``'s result and the spans of the trace taken around it."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        got = fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    return got, tracing.load(str(path))["spans"]


def _named(spans, name):
    return sorted((s for s in spans if s[0] == name), key=lambda s: s[1])


def test_stream_map_spans_one_per_chunk(tmp_path, small_index, cfg_fixed,
                                        small_reads):
    mapper = Mapper(small_index, cfg_fixed)
    fn = mapper.chunk_fn()
    sig = small_reads.signals
    want = driver.collect(driver.stream_map(fn, driver.array_chunks(sig, 4)))
    got, spans = _profiled(tmp_path, lambda: list(
        driver.stream_map(fn, driver.array_chunks(sig, 4))))
    n = -(-sig.shape[0] // 4)
    assert [ci for ci, _, _ in got] == list(range(n))
    dispatch = _named(spans, "driver.dispatch")
    fetch = _named(spans, "driver.fetch")
    assert len(dispatch) == n and len(fetch) == n
    # double buffer: chunk k+1 is dispatched before chunk k is fetched
    for k in range(n - 1):
        assert dispatch[k + 1][1] < fetch[k][1]
    # observation only: the profiled stream equals the unprofiled one
    got = driver.collect(iter(got))
    for f in FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(want, f)),
                                      np.asarray(getattr(got, f)))
    assert want.counters == got.counters


def test_serve_driver_spans_pair_by_chunk(tmp_path, small_index, cfg_fixed,
                                          small_reads):
    mapper = Mapper(small_index, cfg_fixed)
    sig = small_reads.signals
    sd = ServeDriver(mapper, chunk=4)

    def waves():
        for lo, hi in ((0, 6), (6, 7), (7, 16)):
            for k in range(lo, hi):
                sd.submit(f"s{k % 3}", sig[k])
            sd.drain()

    _, spans = _profiled(tmp_path, waves)
    n = sd.n_chunks
    assert n == 6          # 6 reads take two chunks, 1 one, 9 three
    pack = _named(spans, "serve.pack")
    route = _named(spans, "serve.route")
    assert len(pack) == n and len(route) == n
    assert len(_named(spans, "driver.dispatch")) == n
    assert len(_named(spans, "driver.fetch")) == n
    for p, r in zip(pack, route):
        assert p[1] < r[1] + r[2]
    # the reads' results equal the batch mapper's
    want = mapper.map_signals(sig, chunk=4)
    for s in range(3):
        res = sd.results(f"s{s}")
        np.testing.assert_array_equal(res.mapped,
                                      np.asarray(want.mapped)[s::3])
        np.testing.assert_array_equal(res.t_start,
                                      np.asarray(want.t_start)[s::3])
