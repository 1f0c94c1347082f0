"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` turns the profiler's ``.xplane.pb`` into a neutral record: the
device operations of each TPU (name, start, duration in ns, from the
"XLA Ops" line) and the host spans the harness opened
(``jax.profiler.TraceAnnotation``), all on the profiler's one clock.
Everything else here works on that record, so that the reduction can be
checked on a small recorded trace without a chip:

* ``busy_ns``: the union of device-operation intervals inside a window;
* ``idle_gaps``: the complement, as (start, end) intervals;
* ``gap_owner``: the innermost host span open at a gap's midpoint, which
  says what the host was doing while the device waited;
* ``op_totals``: device self time per operation name inside a window.
"""
from __future__ import annotations

import collections
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

# Host spans the harness opens; other host events are the runtime's own.
SPAN_PREFIXES = ("bench.", "driver.", "serve.")
WINDOW_SPAN = "bench.window"
DEVICE_OPS_LINE = "XLA Ops"


def load(path: str) -> dict:
    """{"ops": {device: [[name, start_ns, dur_ns], ...]},
    "spans": [[name, start_ns, dur_ns], ...]} from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: Dict[str, list] = {}
    spans: List[list] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name != DEVICE_OPS_LINE:
                    continue
                ops[plane.name] = [
                    [op_name(ev.name), int(ev.start_ns),
                     int(ev.duration_ns)] for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIXES):
                        spans.append([ev.name, int(ev.start_ns),
                                      int(ev.duration_ns)])
    return {"ops": ops, "spans": spans}


def op_name(text: str) -> str:
    """The HLO instruction's name from the trace's event text, which is the
    whole instruction ("%cheap_fused_fixed.1 = (s32[...]) custom-call(...)")."""
    return text.split(" = ", 1)[0].lstrip("%")


def window(record: dict) -> Tuple[int, int]:
    """(start, end) ns of the harness's measured-window span."""
    w = [s for s in record["spans"] if s[0] == WINDOW_SPAN]
    if len(w) != 1:
        raise ValueError(f"trace holds {len(w)} {WINDOW_SPAN!r} spans, not 1")
    return w[0][1], w[0][1] + w[0][2]


def _clipped(ops: Iterable[Sequence], lo: int, hi: int):
    for op in ops:
        s, e = max(op[1], lo), min(op[1] + op[2], hi)
        if e > s:
            yield op[0], s, e


def merged(ops: Iterable[Sequence], lo: int, hi: int) -> List[Tuple[int, int]]:
    """The union of the operations' intervals inside [lo, hi], sorted."""
    out: List[List[int]] = []
    for _, s, e in sorted(_clipped(ops, lo, hi), key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ops, lo: int, hi: int) -> int:
    return sum(e - s for s, e in merged(ops, lo, hi))


def idle_gaps(ops, lo: int, hi: int) -> List[Tuple[int, int]]:
    gaps, t = [], lo
    for s, e in merged(ops, lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def gap_owner(gap: Tuple[int, int], spans: Sequence[Sequence]) -> str:
    """The innermost (latest-starting) harness span, other than the window
    itself, that is open at the gap's midpoint; "none" when none is."""
    mid = (gap[0] + gap[1]) / 2
    best: Optional[Sequence] = None
    for sp in spans:
        if sp[0] == WINDOW_SPAN:
            continue
        if sp[1] <= mid <= sp[1] + sp[2] and (best is None or sp[1] > best[1]):
            best = sp
    return best[0] if best is not None else "none"


def op_totals(ops, lo: int, hi: int) -> Dict[str, int]:
    """Self time per operation name inside [lo, hi]: an operation's time
    less the part its nested operations cover (a conditional or a loop
    holds the operations of its body on the same trace line)."""
    tot: Dict[str, int] = collections.Counter()
    stack: List[list] = []          # [name, start, end, nested ns]

    def close(item):
        tot[item[0]] += item[2] - item[1] - item[3]

    for name, s, e in sorted(_clipped(ops, lo, hi),
                             key=lambda x: (x[1], x[1] - x[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([name, s, e, 0])
    while stack:
        close(stack.pop())
    return dict(tot)


def summarize(record: dict, top: int = 10) -> dict:
    """Busy and window seconds averaged over the traced devices, and the
    breakdown the result line carries: the device operations that took
    most time and the idle time by what the host was doing (each list at
    most ``top`` entries, seconds, summed over devices / averaged)."""
    lo, hi = window(record)
    devices = sorted(record["ops"])
    if not devices:
        raise ValueError("trace holds no TPU device operations")
    n = len(devices)
    busy = sum(busy_ns(record["ops"][d], lo, hi) for d in devices) / n
    totals: Dict[str, float] = collections.Counter()
    idle: Dict[str, float] = collections.Counter()
    for d in devices:
        for name, t in op_totals(record["ops"][d], lo, hi).items():
            totals[name] += t / n
        for g in idle_gaps(record["ops"][d], lo, hi):
            idle[gap_owner(g, record["spans"])] += (g[1] - g[0]) / n
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy * 1e-9,
        "device_ops": [[k, v * 1e-9] for k, v in
                       sorted(totals.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v * 1e-9] for k, v in
                      sorted(idle.items(), key=lambda kv: -kv[1])[:top]],
    }


def op_seconds(record: dict, match) -> Optional[float]:
    """Device seconds (averaged over devices) of the operations for which
    ``match(op)`` holds inside the window; None when no operation does."""
    lo, hi = window(record)
    devices = sorted(record["ops"])
    tot, found = 0, False
    for d in devices:
        for op in record["ops"][d]:
            if match(op):
                s, e = max(op[1], lo), min(op[1] + op[2], hi)
                if e > s:
                    tot += e - s
                    found = True
    return tot * 1e-9 / len(devices) if found else None
