"""Reduction of the program's own host spans, on the profiler's clock.

``bench/tracing.py`` reduces the device operations and gives each idle gap
to the span open at its midpoint.  The program opens a span per chunk
(``driver.dispatch``, ``driver.fetch``, ``serve.pack``, ``serve.route``),
and one gap of a few milliseconds can run through several of them, so
here every idle nanosecond is given to its own span:

* ``owners``: the trace's host spans as a timeline of (start, end, name),
  each piece owned by the innermost (latest-starting) open span other
  than ``bench.window``;
* ``idle_by_span``: idle ns of the device inside a window, by owner;
* ``paired``: the k-th span of one name matched with the k-th of
  another, which pairs a chunk's spans (chunks are routed in the order
  they were packed and dispatched).
"""
from __future__ import annotations

import collections
import heapq
from typing import Dict, List, Sequence, Tuple

from bench import tracing

NONE = "none"


def owners(spans: Sequence[Sequence]) -> List[Tuple[int, int, str]]:
    """Sorted, non-overlapping (start, end, name) pieces of the time some
    span other than the window is open, each named by the latest-starting
    span open over it (the one listed first among equal starts, as in
    ``tracing.gap_owner``)."""
    live = [(sp[1], sp[1] + sp[2], k, sp[0]) for k, sp in enumerate(spans)
            if sp[0] != tracing.WINDOW_SPAN and sp[2] > 0]
    edges = sorted({t for s, e, _, _ in live for t in (s, e)})
    starts = sorted(live)
    heap: List[tuple] = []         # (-start, k, end, name): innermost first
    out: List[Tuple[int, int, str]] = []
    i = 0
    for t0, t1 in zip(edges, edges[1:]):
        while i < len(starts) and starts[i][0] <= t0:
            s, e, k, name = starts[i]
            heapq.heappush(heap, (-s, k, e, name))
            i += 1
        while heap and heap[0][2] <= t0:
            heapq.heappop(heap)
        if heap:
            name = heap[0][3]
            if out and out[-1][2] == name and out[-1][1] == t0:
                out[-1] = (out[-1][0], t1, name)
            else:
                out.append((t0, t1, name))
    return out


def split_gaps(gaps: Sequence[Tuple[int, int]],
               pieces: Sequence[Tuple[int, int, str]]) -> Dict[str, int]:
    """ns of the sorted ``gaps`` covered by each owner of the sorted
    ``pieces``; the rest under "none"."""
    tot: Dict[str, int] = collections.Counter()
    j = 0
    for a, b in gaps:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        covered, k = 0, j
        while k < len(pieces) and pieces[k][0] < b:
            s, e, name = pieces[k]
            d = min(e, b) - max(s, a)
            if d > 0:
                tot[name] += d
                covered += d
            k += 1
        if b - a > covered:
            tot[NONE] += b - a - covered
    return dict(tot)


def idle_by_span(record: dict, lo: int, hi: int) -> Dict[str, float]:
    """Idle ns of the device inside [lo, hi] by the span that owned it
    (``owners``), averaged over the traced devices."""
    pieces = owners(record["spans"])
    devices = sorted(record["ops"])
    tot: Dict[str, float] = collections.Counter()
    for d in devices:
        gaps = tracing.idle_gaps(record["ops"][d], lo, hi)
        for name, ns in split_gaps(gaps, pieces).items():
            tot[name] += ns / len(devices)
    return dict(tot)


def paired(record: dict, first: str, second: str
           ) -> List[Tuple[Sequence, Sequence]]:
    """The k-th ``first`` span (by start) with the k-th ``second``, over
    the whole trace."""
    def named(name):
        return sorted((sp for sp in record["spans"] if sp[0] == name),
                      key=lambda sp: sp[1])
    return list(zip(named(first), named(second)))


def idle_pct(record: dict, names: Sequence[str]):
    """100 x the window's idle time owned by ``names``, over the window;
    None when the trace holds none of these spans (a program without
    them)."""
    if not any(sp[0] in names for sp in record["spans"]):
        return None
    lo, hi = tracing.window(record)
    idle = idle_by_span(record, lo, hi)
    return 100.0 * sum(idle.get(n, 0.0) for n in names) / (hi - lo)
