"""Unified streaming host driver: ONE copy of the chunk/pad/concat logic.

Every host-side consumer of the jit pipeline — ``Mapper.map_signals``,
real-time early-termination mapping (realtime.py) and the end-to-end
launcher (launch/map_reads.py) — used to carry its own chunking loop.
They all share this module now:

  * ``array_chunks`` produces fixed-size, zero-padded
    (chunk_idx, n_valid, signals) triples from an in-memory array; a
    streaming ``SignalReader`` yields the same triples directly;
  * ``stream_map`` is the double-buffered device loop: chunk i+1 is
    dispatched to the device *before* blocking on chunk i's host transfer,
    so host padding/serialization overlaps device compute (the host-side
    analogue of MARS's flash-load/compute overlap, Section 6.3);
  * ``collect`` folds the streamed per-chunk outputs into one MapOutput;
  * ``ProgressLog`` is the append-only JSONL checkpoint (with periodic
    compaction) used for resume-after-restart mapping jobs.

Pad rows are masked inside ``map_chunk`` via ``n_valid`` (counters never
see them) and trimmed from the per-read outputs here.
"""
from __future__ import annotations

import json
import os
import pathlib
from typing import Callable, Dict, Iterable, Iterator, List, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

# (chunk_idx, n_valid, padded signals (chunk, S) f32)
Chunk = Tuple[int, int, np.ndarray]


def pad_rows(part: np.ndarray, chunk: int) -> np.ndarray:
    """Zero-pad the leading axis to the static chunk size."""
    if part.shape[0] == chunk:
        return part
    pad = np.zeros((chunk - part.shape[0],) + part.shape[1:], part.dtype)
    return np.concatenate([part, pad])


def array_chunks(signals: np.ndarray, chunk: int,
                 start_chunk: int = 0) -> Iterator[Chunk]:
    """Fixed-size chunks over an in-memory (R, S) array."""
    signals = np.asarray(signals, np.float32)
    n = signals.shape[0]
    n_chunks = (n + chunk - 1) // chunk
    for ci in range(start_chunk, n_chunks):
        part = signals[ci * chunk:(ci + 1) * chunk]
        yield ci, part.shape[0], pad_rows(part, chunk)


def stream_map(map_fn: Callable[[np.ndarray, int], "MapOutput"],
               chunks: Iterable[Chunk],
               prefetch: Callable[[np.ndarray, int], None] = None,
               ) -> Iterator[Tuple[int, int, "MapOutput"]]:
    """Double-buffered device loop.

    ``map_fn(signals, n_valid)`` must be an async-dispatching jit program
    (map_chunk / map_chunk_sharded).  The next chunk is dispatched before
    the previous chunk's results are pulled to the host, so device compute
    overlaps host-side reading/padding/serialization.  Yields
    (chunk_idx, n_valid, MapOutput) with per-read fields on the host,
    trimmed to ``n_valid`` rows.

    With ``prefetch`` the loop additionally reads ONE chunk ahead: right
    after chunk i is dispatched, ``prefetch(signals, n_valid)`` runs on
    chunk i+1 so host->device staging (the tiered-index hot-tile cache,
    core/tiered.py) overlaps chunk i's compute.  Without it the pull order
    is unchanged — live chunk sources (the serving driver's ready queue)
    depend on the exact pull timing.

    Each chunk opens two profiler spans: ``driver.dispatch`` around
    ``map_fn`` (the host-to-device copy and the jit launch) and
    ``driver.fetch`` around the pull of its results (waiting for the
    device, then the transfers to the host), closed before the yield.

    A ``prefetch`` exception does NOT abandon the chunk already in flight
    on the device: the loop stops reading ahead, drains every dispatched
    chunk through the iterator, and re-raises the failure once at the end
    of the stream.
    """
    def _emit(p):
        with TraceAnnotation("driver.fetch"):
            return _to_host(*p)

    pending = None
    exc = None
    if prefetch is None:
        for ci, n_valid, sig in chunks:
            with TraceAnnotation("driver.dispatch"):
                out = map_fn(sig, n_valid)  # async dispatch
            if pending is not None:
                yield _emit(pending)
            pending = (ci, n_valid, out)
    else:
        it = iter(chunks)
        nxt = next(it, None)
        if nxt is not None:
            try:
                prefetch(nxt[2], nxt[1])
            except Exception as e:          # nothing in flight yet
                exc, nxt = e, None
        while nxt is not None:
            ci, n_valid, sig = nxt
            with TraceAnnotation("driver.dispatch"):
                out = map_fn(sig, n_valid)  # async dispatch
            nxt = next(it, None)
            if nxt is not None:
                try:
                    prefetch(nxt[2], nxt[1])  # stage next chunk's tiles
                except Exception as e:
                    # chunk ci is mid-flight on the device: let it finish
                    # and yield, surface the prefetch failure at the tail
                    exc, nxt = e, None
            if pending is not None:
                yield _emit(pending)
            pending = (ci, n_valid, out)
    if pending is not None:
        yield _emit(pending)
    if exc is not None:
        raise exc


def _to_host(ci: int, n_valid: int, out) -> Tuple[int, int, "MapOutput"]:
    from repro.core.pipeline import MapOutput
    host = MapOutput(
        t_start=np.asarray(out.t_start)[:n_valid],
        score=np.asarray(out.score)[:n_valid],
        mapped=np.asarray(out.mapped)[:n_valid],
        n_events=np.asarray(out.n_events)[:n_valid],
        counters={k: int(v) for k, v in out.counters.items()})
    return ci, n_valid, host


def collect(stream: Iterable[Tuple[int, int, "MapOutput"]]) -> "MapOutput":
    """Fold a stream_map stream into one host MapOutput (concat per-read
    fields, sum counters).  An empty stream still carries the full
    zero-valued ``stages.CHUNK_COUNTER_SCHEMA`` so downstream consumers
    (workload.from_counters / ssd_model) work on a zero-read job."""
    from repro.core.pipeline import MapOutput
    parts: List = []
    counters: Dict[str, int] = {}
    for _, _, out in stream:
        parts.append(out)
        for k, v in out.counters.items():
            counters[k] = counters.get(k, 0) + int(v)
    if not parts:
        from repro.core.stages import CHUNK_COUNTER_SCHEMA
        z = np.zeros(0)
        return MapOutput(t_start=z.astype(np.int32), score=z.astype(np.float32),
                         mapped=z.astype(bool), n_events=z.astype(np.int32),
                         counters={k: 0 for k in CHUNK_COUNTER_SCHEMA})
    return MapOutput(
        t_start=np.concatenate([p.t_start for p in parts]),
        score=np.concatenate([p.score for p in parts]),
        mapped=np.concatenate([p.mapped for p in parts]),
        n_events=np.concatenate([p.n_events for p in parts]),
        counters=counters)


# --------------------------------------------------------------------------- #
# Resumable progress checkpointing
# --------------------------------------------------------------------------- #
class ProgressLog:
    """Append-only JSONL progress log with periodic compaction.

    Each mapped chunk appends ONE line ``{"next": ci+1, "rows": [...]}`` —
    O(chunk) per chunk instead of re-serializing the full result list
    (the old checkpoint was O(n^2) over a run).  Every ``compact_every``
    lines the log is rewritten as a single consolidated base line
    (atomic tmp+rename), bounding file size and resume parse time.
    """

    def __init__(self, path, compact_every: int = 64):
        self.path = pathlib.Path(path)
        self.compact_every = compact_every
        self.rows: List = []
        self.next_chunk = 0
        self._lines = 0

    def load(self) -> Tuple[int, List]:
        """Replay the log.  Returns (next_chunk, rows).

        A malformed line (a crash mid-append leaves a partial final line)
        stops the replay there: everything before it is consistent, and
        the chunk whose append was cut short is simply remapped.
        """
        self.rows, self.next_chunk, self._lines = [], 0, 0
        if self.path.exists():
            good = 0                       # bytes of consistent prefix
            with open(self.path, "rb") as f:
                for raw in f:
                    if not raw.endswith(b"\n"):
                        break              # torn tail (no terminator)
                    line = raw.decode("utf-8", "replace").strip()
                    if line:
                        try:
                            rec = json.loads(line)
                        except json.JSONDecodeError:
                            break
                        if rec.get("base"):
                            self.rows = [tuple(r) for r in rec["rows"]]
                        else:
                            self.rows.extend(tuple(r) for r in rec["rows"])
                        self.next_chunk = rec["next"]
                        self._lines += 1
                    good += len(raw)
            if good < self.path.stat().st_size:
                with open(self.path, "r+b") as f:
                    f.truncate(good)       # drop the torn tail; its chunk
                                           # is simply remapped
        return self.next_chunk, self.rows

    def append(self, next_chunk: int, rows: List) -> None:
        rows = [tuple(r) for r in rows]
        with open(self.path, "a") as f:
            f.write(json.dumps({"next": next_chunk, "rows": rows}) + "\n")
        self.rows.extend(rows)
        self.next_chunk = next_chunk
        self._lines += 1
        if self._lines >= self.compact_every:
            self.compact()

    def compact(self) -> None:
        tmp = self.path.with_name(self.path.name + ".tmp")
        tmp.write_text(json.dumps(
            {"next": self.next_chunk, "rows": self.rows, "base": True}) + "\n")
        os.replace(tmp, self.path)
        self._lines = 1

    def clear(self) -> None:
        self.path.unlink(missing_ok=True)
        self.rows, self.next_chunk, self._lines = [], 0, 0
