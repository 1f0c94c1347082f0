"""Chaining (paper Fig. 1, mapping step 3): anchor sort + banded DP.

Anchors are sorted by (t_pos, q_pos) — MARS does this on the in-controller
bitonic Sorter/Merger; the optimized pipeline path routes the sort through
the `bitonic_sort` Pallas kernel, the reference path uses lax.sort.  The DP
is minimap2-style with a fixed look-back band B (MARS's Arithmetic Units are
word-serial, so RawHash2's bounded-predecessor heuristic maps directly).

    f[i] = w + max(0, max_{j in band, colinear} f[j] - beta*|dt - dq|
                                              - alpha*min(dt, dq))

The best chain's projected start (t_start - q_start) is the mapping position.

Anchors are sorted on the (t, q) PAIR, lexicographically: t is a whole
int32 double-genome position (below ``index.MAX_CONCAT_EVENTS`` = 2^31 -
2^20) and q the read's event index, clamped to 8 bits as the plain
reference clamps it; ``build_index`` refuses ``max_events`` past 256, so
the clamp never changes a q.  The sorters carry q as a payload of t (``lax.sort``
on two keys; the Pallas bitonic network compares pairs).  A slot holding no
anchor sorts last (t = ``INVALID_T``) and decodes to the sentinel pair
(``EMPTY_T``, ``EMPTY_Q``) from which a read with no anchor takes its
``t_start``.

Fast path (this module + core/pipeline.py): MARS's filters exist so that
most reads reach chaining with few (often zero) anchors.  The chaining fast
path exploits that:

  * ``select_smallest_count`` / ``select_smallest_topk`` pull the W smallest
    pairs out of the (E*H,) pair arrays so the sorter runs on W pairs
    instead of E*H ("select-then-sort" — the Pallas bitonic backend then
    sorts a W-slot block instead of the padded full block);
  * ``chain_dp`` carries only the B-slot band window as a ring buffer
    (fixed-position rotate/update) instead of dynamic-slicing a full
    (A+B,) array every scan step — the whole-array gather/scatter the old
    scan made vmap materialize per read is gone;
  * zero-anchor reads short-circuit to ``empty_chain_result`` (exactly what
    the full pipeline computes for them — see the proof in the docstring).

``sort_anchors_reference`` and ``chain_dp_reference`` keep the pre-fast-path
implementations: they are the parity oracles for the tests and the "pre"
side of benchmarks/microbench.py.
"""
from __future__ import annotations

from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.config import MarsConfig

NEG = -1e9
_SENT = -(1 << 30)
# t of a slot that holds no anchor: above every position, so it sorts last
INVALID_T = 0x7FFFFFFF
Q_MAX = (1 << 8) - 1           # largest q (index.check_index_limits)
# the pair an empty slot decodes to (the chain phase's sentinels)
EMPTY_T = INVALID_T >> 8
EMPTY_Q = Q_MAX


class ChainResult(NamedTuple):
    t_start: jnp.ndarray     # () int32 — double-genome coords
    score: jnp.ndarray       # () f32
    score2: jnp.ndarray      # () f32 second-best (distinct location)
    mapped: jnp.ndarray      # () bool
    n_anchors: jnp.ndarray   # () int32 anchors entering the DP


# --------------------------------------------------------------------------- #
# Sort pairs / selection
# --------------------------------------------------------------------------- #
def anchor_pairs(q_pos: jnp.ndarray, t_pos: jnp.ndarray, valid: jnp.ndarray):
    """Flatten (E,H) anchors into (E*H,) sort pairs (t, q), q clamped to
    ``Q_MAX``; invalid anchors become (``INVALID_T``, ``Q_MAX``) and sort
    last.  The flattening is q-major (q_pos is the event index: slot
    e*H + h holds event e), so slot order already breaks ties in t by q,
    and a slot's q follows from its index (``slot_q``)."""
    v = valid.reshape(-1)
    t = jnp.where(v, t_pos.reshape(-1).astype(jnp.int32), INVALID_T)
    q = jnp.minimum(q_pos.reshape(-1), Q_MAX).astype(jnp.int32)
    return t, jnp.where(v, q, Q_MAX)


def slot_q(idx: jnp.ndarray, hits: int) -> jnp.ndarray:
    """The q ``anchor_pairs`` gives anchor slot ``idx`` of an (E, hits)
    grid, worked out from the index instead of gathered."""
    return jnp.minimum(idx // hits, Q_MAX).astype(jnp.int32)


def sort_pairs(t: jnp.ndarray, q: jnp.ndarray):
    """(t, q) sorted lexicographically: the reference sorter."""
    return jax.lax.sort((t, q), num_keys=2)


def decode_pairs(st: jnp.ndarray, sq: jnp.ndarray):
    """Sorted pairs -> (sq, st, sv); empty slots read (EMPTY_T, EMPTY_Q)."""
    sv = st != INVALID_T
    return sq, jnp.where(sv, st, EMPTY_T), sv


def select_smallest_count(t: jnp.ndarray, width: int, hits: int):
    """The valid pairs of ``anchor_pairs``' (E*hits,) t compacted to
    (width,) arrays (t, q), in slot order, padded with (``INVALID_T``,
    ``Q_MAX``).

    Gather-based (cumsum + searchsorted): no scatter, so it vmaps into one
    batched gather of t; q comes from the slot index.  EXACT equivalent of
    ``sort_pairs(t, q)[:width]`` as a multiset iff the number of valid
    pairs is <= width — callers guarantee that with a batch-level
    ``n_anchors_postvote`` bound (core/pipeline.py) before taking this
    path.
    """
    valid = t != INVALID_T
    cum = jnp.cumsum(valid.astype(jnp.int32))
    idx = jnp.searchsorted(cum, jnp.arange(1, width + 1, dtype=jnp.int32),
                           side="left")
    idx = jnp.minimum(idx, t.shape[0] - 1)
    live = jnp.arange(width) < cum[-1]
    return (jnp.where(live, t[idx], INVALID_T),
            jnp.where(live, slot_q(idx, hits), Q_MAX))


def select_smallest_topk(t: jnp.ndarray, width: int, hits: int):
    """The ``width`` smallest pairs via ``lax.top_k`` on the negated t.
    top_k puts the lower slot first among equal values, and slots are
    q-major (``anchor_pairs``), so ties in t fall to the smaller q: exact
    for ANY valid count.  On TPU top_k is a fast sampled-select, on CPU
    XLA lowers it to an O(n*k) pass — cfg.anchor_select picks the
    strategy."""
    neg, idx = jax.lax.top_k(-t, width)
    st = -neg
    return st, jnp.where(st != INVALID_T, slot_q(idx, hits), Q_MAX)


_SELECTORS = {
    "count": select_smallest_count,
    "topk": select_smallest_topk,
}


def sort_anchors(q_pos: jnp.ndarray, t_pos: jnp.ndarray, valid: jnp.ndarray,
                 cfg: MarsConfig, sorter=None, width: int = None):
    """Sort (E,H) anchors by (t_pos, q_pos) with invalids last and keep the
    first ``max_anchors``.  ``sorter(t, q) -> (t, q)`` is injectable (Pallas
    bitonic kernel); default ``sort_pairs``.

    ``width=None`` sorts all E*H pairs (the original full-sort behaviour).
    ``width=W`` is the select-then-sort fast path: the W smallest pairs are
    selected first (strategy ``cfg.anchor_select``) and the sorter runs on
    the (W,) selection only — bit-identical to the full sort's first W slots
    provided the post-filter anchor count is <= W ("count" strategy) or
    unconditionally ("topk" strategy).
    """
    if sorter is None:
        sorter = sort_pairs
    t, q = anchor_pairs(q_pos, t_pos, valid)
    if width is None:
        st, sq = sorter(t, q)
        st, sq = st[: cfg.max_anchors], sq[: cfg.max_anchors]
    else:
        st, sq = sorter(*_SELECTORS[cfg.anchor_select](t, width,
                                                       t_pos.shape[-1]))
    return decode_pairs(st, sq)


def sort_anchors_reference(q_pos, t_pos, valid, cfg: MarsConfig, sorter=None):
    """Pre-fast-path behaviour: always full-sort all E*H keys (parity oracle
    + "pre" side of the chaining microbenchmark)."""
    return sort_anchors(q_pos, t_pos, valid, cfg, sorter=sorter, width=None)


# --------------------------------------------------------------------------- #
# Banded DP
# --------------------------------------------------------------------------- #
def chain_dp(q: jnp.ndarray, t: jnp.ndarray, valid: jnp.ndarray,
             cfg: MarsConfig):
    """Banded DP over sorted anchors — ring-buffer band window.

    q, t: (A,) int32 sorted by (t, q); valid: (A,) bool.
    Returns (f (A,) f32 chain scores, diag0 (A,) int32 start diag of the best
    chain ending at each anchor).

    The carried state is ONLY the B-slot band (f/diag/t/q of the last B
    anchors), held in a ring buffer: anchor i lives in slot i % B and each
    step overwrites exactly one fixed-position slot with a lane-mask select —
    no dynamic_slice gather of an (A+B,) array per step (which vmap turned
    into a whole-array gather/scatter per read in the old scan; see
    ``chain_dp_reference``).  Outputs stream out as scan ys.

    Bit-identical to ``chain_dp_reference``: the band holds the same values
    (only slot order differs — a rotation), the float expressions are
    verbatim the same, and argmax ties resolve to the OLDEST anchor in both
    (the reference window is age-ordered; here the explicit age rank
    ``k = (slot - i) mod B`` reproduces that tie-break).
    """
    A, B = q.shape[0], cfg.chain_band
    lane = jnp.arange(B)

    def step(carry, x):
        bf, bd, bt, bq = carry
        ti, qi, vi, i = x
        dt = ti - bt
        dq = qi - bq
        ok = (dt > 0) & (dq > 0) & (dt <= cfg.max_gap) & (dq <= cfg.max_gap)
        gap = jnp.abs(dt - dq).astype(jnp.float32)
        skip = jnp.minimum(dt, dq).astype(jnp.float32)
        cand = bf - cfg.gap_cost * gap - cfg.skip_cost * skip
        cand = jnp.where(ok & (bf > NEG / 2), cand, NEG)
        best = jnp.max(cand)
        # oldest-first tie-break: age rank k=0 is the oldest band slot
        k = (lane - i) % B
        kbest = jnp.min(jnp.where(cand == best, k, B))
        dbest = jnp.sum(jnp.where((cand == best) & (k == kbest), bd, 0))
        ext = best > 0.0
        fi = cfg.anchor_score + jnp.maximum(best, 0.0)
        fi = jnp.where(vi, fi, NEG)
        di = jnp.where(ext, dbest, ti - qi)
        wr = lane == i % B
        carry = (jnp.where(wr, fi, bf), jnp.where(wr, di, bd),
                 jnp.where(wr, ti, bt), jnp.where(wr, qi, bq))
        return carry, (fi, di)

    init = (jnp.full((B,), NEG, jnp.float32), jnp.zeros((B,), jnp.int32),
            jnp.full((B,), _SENT, jnp.int32), jnp.full((B,), _SENT, jnp.int32))
    _, (f, d) = jax.lax.scan(step, init, (t, q, valid, jnp.arange(A)))
    return f, d


def chain_dp_reference(q: jnp.ndarray, t: jnp.ndarray, valid: jnp.ndarray,
                       cfg: MarsConfig):
    """Pre-fast-path DP: carries full (A+B,) f/diag arrays and dynamic-slices
    the band window each step.  Kept as the parity oracle for ``chain_dp``
    and the "pre" side of the chaining microbenchmark."""
    A, B = q.shape[0], cfg.chain_band
    # pad the carried state with B sentinel slots in front
    f0 = jnp.full(A + B, NEG, jnp.float32)
    d0 = jnp.zeros(A + B, jnp.int32)
    tp = jnp.concatenate([jnp.full(B, _SENT, jnp.int32), t])
    qp = jnp.concatenate([jnp.full(B, _SENT, jnp.int32), q])

    def step(carry, i):
        f, d = carry
        ti, qi, vi = t[i], q[i], valid[i]
        fw = jax.lax.dynamic_slice(f, (i,), (B,))
        dw = jax.lax.dynamic_slice(d, (i,), (B,))
        tw = jax.lax.dynamic_slice(tp, (i,), (B,))
        qw = jax.lax.dynamic_slice(qp, (i,), (B,))
        dt = ti - tw
        dq = qi - qw
        ok = (dt > 0) & (dq > 0) & (dt <= cfg.max_gap) & (dq <= cfg.max_gap)
        gap = jnp.abs(dt - dq).astype(jnp.float32)
        skip = jnp.minimum(dt, dq).astype(jnp.float32)
        cand = fw - cfg.gap_cost * gap - cfg.skip_cost * skip
        cand = jnp.where(ok & (fw > NEG / 2), cand, NEG)
        bj = jnp.argmax(cand)
        best = cand[bj]
        ext = best > 0.0
        fi = cfg.anchor_score + jnp.maximum(best, 0.0)
        fi = jnp.where(vi, fi, NEG)
        di = jnp.where(ext, dw[bj], ti - qi)
        f = jax.lax.dynamic_update_slice(f, fi[None], (i + B,))
        d = jax.lax.dynamic_update_slice(d, di[None], (i + B,))
        return (f, d), None

    (f, d), _ = jax.lax.scan(step, (f0, d0), jnp.arange(A))
    return f[B:], d[B:]


# --------------------------------------------------------------------------- #
# Finalize
# --------------------------------------------------------------------------- #
def best_chain(f: jnp.ndarray, diag0: jnp.ndarray, valid: jnp.ndarray,
               cfg: MarsConfig) -> ChainResult:
    """Best + second-best (distinct window) chain -> mapping decision."""
    fv = jnp.where(valid, f, NEG)
    i1 = jnp.argmax(fv)
    s1 = fv[i1]
    d1 = diag0[i1]
    far = jnp.abs(diag0 - d1) > cfg.voting_window
    fv2 = jnp.where(valid & far, f, NEG)
    s2 = jnp.maximum(jnp.max(fv2), 0.0)
    mapped = (s1 >= cfg.min_chain_score) & (s1 >= cfg.map_ratio * s2)
    t_start = jnp.maximum(d1, 0).astype(jnp.int32)
    return ChainResult(t_start=t_start, score=s1, score2=s2, mapped=mapped,
                       n_anchors=valid.sum().astype(jnp.int32))


def empty_chain_result(cfg: MarsConfig) -> ChainResult:
    """The EXACT ChainResult the full sort+dp+finalize pipeline produces for
    a read with zero valid anchors — in closed form.

    With no valid anchors every sorted slot holds ``INVALID_T``; the DP
    gives every slot f = NEG (invalid) and diag = t - q of the decoded
    sentinel pair (its huge t fails the ``dt <= max_gap`` colinearity test against
    every predecessor, so no extension can fire).  best_chain then sees an
    all-NEG score vector: argmax lands on slot 0, the second-best window is
    empty, and the result is a constant independent of A.  The read-
    compaction gate (core/pipeline.py) uses this to finalize filtered-out
    reads without running the chaining phase.
    """
    d = EMPTY_T - EMPTY_Q
    return ChainResult(
        t_start=jnp.int32(max(d, 0)),
        score=jnp.float32(NEG),
        score2=jnp.float32(0.0),
        mapped=jnp.asarray(False),
        n_anchors=jnp.int32(0),
    )


def chain_anchors(q_pos: jnp.ndarray, t_pos: jnp.ndarray, valid: jnp.ndarray,
                  cfg: MarsConfig, sorter=None, dp=None) -> (ChainResult, Dict):
    sq, st, sv = sort_anchors(q_pos, t_pos, valid, cfg, sorter=sorter)
    if dp is None:
        f, d0 = chain_dp(sq, st, sv, cfg)
    else:
        f, d0 = dp(sq, st, sv)
    res = best_chain(f, d0, sv, cfg)
    counters = dict(
        n_sorted=jnp.minimum(valid.sum(), cfg.max_anchors),
        n_dp_pairs=sv.sum() * cfg.chain_band,
    )
    return res, counters
