"""Signal-to-event conversion (event detection).

Implements the two-sample t-statistic segmentation used by RawHash2 /
scrappie, with two arithmetic paths:

* float path (RH2 baseline / MS-CPU_Float): f32 throughout;
* fixed-point path (MARS, Section 5.2): the raw signal is quantized EARLY
  (robust-normalized then converted to Q7.8 int16) and segmentation runs in
  integer arithmetic (int32/int64 accumulators, sqrt-free boundary test).

Static shapes: each read yields exactly `max_events` event slots plus a
validity count.  Segment means are computed as a one-hot segment-sum — the
same formulation the `event_detect` Pallas kernel maps onto the MXU.

Cheap-phase fast path: the float normalization sorts the signal once and
takes both the median and the MAD from the shared sorted array
(``robust_normalize``); the fixed-point segment reduction replaces the two
segment-sum scatters with cumsum-at-boundary gathers (``segment_means``).
Both are bit-identical to the plain implementations, kept as the parity
oracles ``robust_normalize_reference`` / ``segment_means_reference``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.config import MarsConfig

_EPS = 1e-6

# Early-quantization clip: normalized signals are clipped to +-SIGNAL_CLIP
# sigmas before the Q-format conversion, so |xq| <= SIGNAL_CLIP * 2^frac_bits
# — the static amplitude bound the integer boundary test's overflow check
# (fixed_tstat_bounds) is derived from.
SIGNAL_CLIP = 8.0


# --------------------------------------------------------------------------- #
# Normalization + early quantization (paper Section 5.2)
# --------------------------------------------------------------------------- #
def robust_normalize_reference(signal: jnp.ndarray) -> jnp.ndarray:
    """Pre-fast-path per-read median/MAD normalization: two full
    ``jnp.median`` sorts per read.  Parity oracle + the "pre" side of the
    cheap-phase microbenchmark."""
    med = jnp.median(signal, axis=-1, keepdims=True)
    mad = jnp.median(jnp.abs(signal - med), axis=-1, keepdims=True)
    scale = 1.4826 * mad + _EPS
    return (signal - med) / scale


def _kth_of_two_sorted(a: jnp.ndarray, b: jnp.ndarray, k: int):
    """The k-th smallest (0-based) of the union of two ascending 1-D runs.

    Taking i elements from ``a`` and k+1-i from ``b``, the largest taken is
    max(a[i-1], b[k-i]) (-inf for an empty side); the k-th smallest is the
    least of these over every feasible i.  k and both lengths are static, so
    the candidates are static slices: one maximum and one min reduction,
    with no gather, loop or sort.
    """
    lo, hi = max(0, k + 1 - b.shape[0]), min(k + 1, a.shape[0])
    ae, be = (jnp.pad(v, (1, 0), constant_values=-jnp.inf) for v in (a, b))
    return jnp.min(jnp.maximum(ae[lo:hi + 1], be[k + 1 - hi:k + 2 - lo][::-1]))


def _robust_normalize_row(signal: jnp.ndarray) -> jnp.ndarray:
    """One-sort median/MAD of a 1-D signal, bit-identical to the reference.

    The median interpolation mirrors jnp.quantile's "linear" method at
    q=0.5 exactly (lo*0.5 + hi*0.5 — for odd S, lo == hi).  |x - med| over
    the sorted signal is two sorted runs (descending-left, ascending-right
    of the median), so the MAD's two ranks are selected from the pair of
    runs (``_kth_of_two_sorted``) instead of a second full sort.
    """
    S = signal.shape[0]
    m1, m2 = (S - 1) // 2, S // 2
    half = jnp.float32(0.5)
    xs = jnp.sort(signal)
    med = xs[m1] * half + xs[m2] * half
    h = S // 2
    dev_lo = (med - xs[:h])[::-1]        # ascending: xs[:h] <= med
    dev_hi = xs[h:] - med                # ascending: xs[h:] >= med
    lo = _kth_of_two_sorted(dev_lo, dev_hi, m1)
    hi = _kth_of_two_sorted(dev_lo, dev_hi, m2)
    mad = lo * half + hi * half
    scale = 1.4826 * mad + _EPS
    return (signal - med) / scale


def robust_normalize(signal: jnp.ndarray) -> jnp.ndarray:
    """Per-read median/MAD normalization (f32).  signal: (..., S).

    One shared sort per read: the MAD's two ranks are selected from the
    sorted signal instead of sorting |x - med| again.  Bit-identical to
    ``robust_normalize_reference`` (asserted by tests/test_cheap_fastpath).
    """
    shape = signal.shape
    rows = signal.reshape(-1, shape[-1])
    out = jax.vmap(_robust_normalize_row)(rows)
    return out.reshape(shape)


def quantize_signal_fixed(signal_norm: jnp.ndarray, frac_bits: int,
                          clip: float = SIGNAL_CLIP) -> jnp.ndarray:
    """Early quantization: normalized f32 -> Q(15-f).f int16."""
    scaled = jnp.clip(signal_norm, -clip, clip) * (1 << frac_bits)
    return jnp.round(scaled).astype(jnp.int16)


def dequantize_fixed(x: jnp.ndarray, frac_bits: int) -> jnp.ndarray:
    return x.astype(jnp.float32) / (1 << frac_bits)


# --------------------------------------------------------------------------- #
# t-statistic boundary detection
# --------------------------------------------------------------------------- #
def _windowed_sums(x: jnp.ndarray, w: int):
    """Left/right window sums of x and x^2 at each position.

    x: (S,).  Returns (sum_l, sum_r, sq_l, sq_r), each (S,), where
    sum_l[i] = sum(x[i-w:i]) and sum_r[i] = sum(x[i:i+w]) (zero-padded at
    the borders).  Works for float32 or int32.
    """
    S = x.shape[0]
    zero = jnp.zeros((1,), x.dtype)
    c = jnp.concatenate([zero, jnp.cumsum(x)])              # (S+1,)
    c2 = jnp.concatenate([zero, jnp.cumsum(x * x)])
    idx = jnp.arange(S)
    lo = jnp.maximum(idx - w, 0)
    hi = jnp.minimum(idx + w, S)
    sum_l = c[idx] - c[lo]
    sum_r = c[hi] - c[idx]
    sq_l = c2[idx] - c2[lo]
    sq_r = c2[hi] - c2[idx]
    return sum_l, sum_r, sq_l, sq_r


def tstat_float(x: jnp.ndarray, w: int) -> jnp.ndarray:
    """|mean_r - mean_l| / sqrt(var_l/w + var_r/w).  x: (S,) f32."""
    sum_l, sum_r, sq_l, sq_r = _windowed_sums(x, w)
    wf = float(w)
    mean_l, mean_r = sum_l / wf, sum_r / wf
    var_l = jnp.maximum(sq_l / wf - mean_l**2, 0.0)
    var_r = jnp.maximum(sq_r / wf - mean_r**2, 0.0)
    denom = jnp.sqrt((var_l + var_r) / wf + _EPS)
    return jnp.abs(mean_r - mean_l) / denom


def boundary_mask_float(x: jnp.ndarray, cfg: MarsConfig) -> jnp.ndarray:
    """Peak-picked boundary mask (S,) bool, float path."""
    t = tstat_float(x, cfg.tstat_window)
    return _peak_pick(t, t > cfg.tstat_threshold, cfg)


def fixed_tstat_bounds(cfg: MarsConfig):
    """Static worst-case int32 magnitudes of the integer boundary test.

    Derived from the early-quantization amplitude bound
    M = SIGNAL_CLIP * 2^frac_bits (|xq| <= M by construction):

        sq      <= w * M^2            (windowed sum of squares)
        |diff|  <= (2*w*M) >> 2       (prescaled window-sum difference)
        lhs     <= diff^2 * w
        |ssd|   <= w^2 * M^2          (w*sq - sum^2, both sides)
        rhs     <= tau2 * ((2*w^2*M^2) >> 4 + eps)

    Returns a dict of the four bounds; every one must stay below 2^31 for
    the int32 arithmetic of ``boundary_mask_fixed`` (and the `event_detect`
    Pallas kernel, which evaluates the identical expressions) to be exact.
    The cumsums inside ``_windowed_sums`` may wrap — two's-complement
    differences recover the window sums exactly as long as the window sums
    themselves fit, which the ``sq`` bound guarantees.
    """
    w = cfg.tstat_window
    M = int(SIGNAL_CLIP * (1 << cfg.frac_bits))
    tau2 = int(round(cfg.tstat_threshold ** 2))
    eps = 1 << max(2 * cfg.frac_bits - 8, 0)
    diff = (2 * w * M) >> 2
    return dict(
        sq=w * M * M,
        ssd=2 * w * w * M * M,
        lhs=diff * diff * w,
        rhs=tau2 * (((2 * w * w * M * M) >> 4) + eps),
    )


def fixed_tstat_in_range(cfg: MarsConfig) -> bool:
    """True iff the integer boundary test cannot overflow int32 for cfg."""
    return max(fixed_tstat_bounds(cfg).values()) < (1 << 31)


def check_fixed_tstat_range(cfg: MarsConfig) -> None:
    """Static overflow guard for the fixed-point boundary test.

    ``diff * diff * w`` grows as tstat_window^3 x (Q-format amplitude)^2 —
    beyond the bound it silently wraps int32 and flips boundary decisions.
    Fail fast at trace time instead (tests/test_cheap_fastpath pins the
    boundary: tstat_window=12 is the largest safe window at frac_bits=8).
    """
    if fixed_tstat_in_range(cfg):
        return
    w_max = 0
    while fixed_tstat_in_range(cfg.replace(tstat_window=w_max + 1)):
        w_max += 1
    bounds = fixed_tstat_bounds(cfg)
    worst = max(bounds, key=bounds.get)
    raise ValueError(
        f"fixed-point boundary test overflows int32 for tstat_window="
        f"{cfg.tstat_window} at frac_bits={cfg.frac_bits} ({worst} bound "
        f"{bounds[worst]:#x} >= 2^31); the largest safe tstat_window for "
        f"this config is {w_max} — lower tstat_window/frac_bits or use the "
        "float path (fixed_point=False)")


def boundary_mask_fixed(xq: jnp.ndarray, cfg: MarsConfig) -> jnp.ndarray:
    """Integer (sqrt-free) boundary test on int16 Q-format signal.

    Compare  (sum_r - sum_l)^2 * w  >  tau^2 * (ssd_l + ssd_r)
    where ssd = w*sq - sum^2 (scaled sum of squared deviations), in int32
    with a >>2 / >>4 prescale on the two sides to stay in range — equivalent
    to tstat > tau without division or sqrt, matching what a word-serial
    Arithmetic Unit would evaluate (add/mul/compare only).  Configs whose
    worst case exceeds int32 are rejected statically
    (``check_fixed_tstat_range``).
    """
    check_fixed_tstat_range(cfg)
    w = cfg.tstat_window
    x32 = xq.astype(jnp.int32)
    sum_l, sum_r, sq_l, sq_r = _windowed_sums(x32, w)
    diff = (sum_r - sum_l) >> 2                            # prescale 1/4
    ssd_l = w * sq_l - sum_l * sum_l                       # w^2 * var_l
    ssd_r = w * sq_r - sum_r * sum_r
    # tstat^2 = diff^2*w / (ssd_l + ssd_r)  (after w^2 cancellation);
    # both sides carry the same 1/16 prescale.
    tau2 = int(round(cfg.tstat_threshold ** 2))
    eps = 1 << (2 * cfg.frac_bits - 8)                     # small int epsilon
    lhs = diff * diff * w
    rhs = tau2 * (((ssd_l + ssd_r) >> 4) + eps)
    # score for peak picking: use lhs/rhs ratio in float only for argmax (the
    # comparison itself is integer); monotone transform keeps peaks aligned.
    score = lhs.astype(jnp.float32) / (rhs.astype(jnp.float32) + 1.0)
    return _peak_pick(score, lhs > rhs, cfg)


def _peak_pick(score: jnp.ndarray, above: jnp.ndarray,
               cfg: MarsConfig) -> jnp.ndarray:
    """Local-max suppression: keep i if above[i] and score[i] is the max in
    a +-peak_window neighborhood (ties broken toward the left)."""
    r = cfg.peak_window
    S = score.shape[0]
    win = 2 * r + 1
    padded = jnp.pad(score, (r, r), constant_values=-jnp.inf)
    # windowed max via reduce_window
    wmax = jax.lax.reduce_window(padded, -jnp.inf, jax.lax.max, (win,), (1,),
                                 "valid")
    # tie-break: position of first occurrence — accept if strictly greater
    # than everything to the left in the window.
    lmax = jax.lax.reduce_window(padded[:S + r], -jnp.inf, jax.lax.max,
                                 (r + 1,), (1,), "valid")  # max over [i-r, i]
    is_peak = (score >= wmax) & (score >= lmax) & above
    if cfg.min_dwell <= 1:
        # the peak window already enforces spacing; skip the sequential pass
        # (this is the TPU-kernel-friendly default — measured accuracy is
        # identical, see EXPERIMENTS Accuracy notes).
        return is_peak
    # enforce min dwell: suppress boundaries closer than min_dwell using a
    # prefix-scan over positions (greedy left-to-right).
    def scan_fn(last, inp):
        i, p = inp
        keep = p & (i - last >= cfg.min_dwell)
        last = jnp.where(keep, i, last)
        return last, keep
    idx = jnp.arange(S)
    _, kept = jax.lax.scan(scan_fn, -cfg.min_dwell, (idx, is_peak))
    return kept


# --------------------------------------------------------------------------- #
# Segment means: one-hot segment-sum (oracle) / cumsum-at-boundary gathers
# --------------------------------------------------------------------------- #
def segment_means_reference(x: jnp.ndarray, boundaries: jnp.ndarray,
                            valid_len: int, max_events: int):
    """Pre-fast-path segment reduction: two ``segment_sum`` scatters.
    Parity oracle + the "pre" side of the cheap-phase microbenchmark.

    x: (S,) signal, boundaries: (S,) bool.  Returns (means (E,), n_events,
    counts).  Event id at sample i = cumsum(boundaries)[i] clipped to E-1;
    samples past valid_len are dropped.  Means = segsum(x)/segsum(1) —
    identical math to the Pallas kernel's one-hot matmul.
    """
    S = x.shape[0]
    sample_valid = jnp.arange(S) < valid_len
    eid = jnp.cumsum(boundaries.astype(jnp.int32))
    eid = jnp.minimum(eid, max_events - 1)
    eid_masked = jnp.where(sample_valid, eid, max_events)   # overflow bin
    xf = x.astype(jnp.float32)
    sums = jax.ops.segment_sum(jnp.where(sample_valid, xf, 0.0), eid_masked,
                               num_segments=max_events + 1)[:max_events]
    cnts = jax.ops.segment_sum(sample_valid.astype(jnp.float32), eid_masked,
                               num_segments=max_events + 1)[:max_events]
    means = sums / jnp.maximum(cnts, 1.0)
    n_events = jnp.minimum(eid[valid_len - 1] + 1, max_events)
    return means, n_events, cnts


def segment_means(x: jnp.ndarray, boundaries: jnp.ndarray, valid_len: int,
                  max_events: int, max_abs: int = None):
    """Segment reduction via cumsum-at-boundary gathers (no scatters).

    Same contract as ``segment_means_reference``.  The event-id array is
    nondecreasing, so each event's sample range is [starts[e], starts[e+1])
    with ``starts = searchsorted(eid, 0..E)``, and per-event sums are
    differences of ONE prefix sum — gathers only, which vmap into a single
    batched gather across a chunk instead of per-read scatters.

    Bit-identical to the reference for integer-valued ``x`` whose whole-
    signal prefix sum stays exact in f32: the caller must certify the
    static amplitude bound ``max_abs`` (for the MARS fixed-point path,
    SIGNAL_CLIP * 2^frac_bits) and ``S * max_abs`` must stay below 2^24.
    Anything else — float signals (whose scatter addition order must be
    preserved exactly), an uncertified bound, or a signal long/loud enough
    to round the prefix sum — falls back to the scatter-based reference.
    """
    if (not jnp.issubdtype(x.dtype, jnp.integer) or max_abs is None
            or x.shape[0] * max_abs >= (1 << 24)):
        return segment_means_reference(x, boundaries, valid_len, max_events)
    S = x.shape[0]
    sample_valid = jnp.arange(S) < valid_len
    eid = jnp.cumsum(boundaries.astype(jnp.int32))
    eid = jnp.minimum(eid, max_events - 1)
    g = jnp.where(sample_valid, eid, max_events)            # nondecreasing
    starts = jnp.searchsorted(
        g, jnp.arange(max_events + 1, dtype=jnp.int32), side="left")
    xf = jnp.where(sample_valid, x.astype(jnp.float32), 0.0)
    c = jnp.concatenate([jnp.zeros((1,), jnp.float32), jnp.cumsum(xf)])
    sums = c[starts[1:]] - c[starts[:-1]]
    cnts = (starts[1:] - starts[:-1]).astype(jnp.float32)
    means = sums / jnp.maximum(cnts, 1.0)
    n_events = jnp.minimum(eid[valid_len - 1] + 1, max_events)
    return means, n_events, cnts


def detect_events(signal: jnp.ndarray, cfg: MarsConfig):
    """Full per-read event detection.  signal: (S,) f32 raw.

    Returns (event_means (E,) f32 in normalized units, n_events i32,
    counts (E,) f32).  Dispatches on cfg.early_quantization / fixed_point.
    """
    x = robust_normalize(signal)
    if cfg.early_quantization and cfg.fixed_point:
        xq = quantize_signal_fixed(x, cfg.frac_bits)
        b = boundary_mask_fixed(xq, cfg)
        means, n, cnts = segment_means(
            xq.astype(jnp.int32), b, signal.shape[0], cfg.max_events,
            max_abs=int(SIGNAL_CLIP * (1 << cfg.frac_bits)))
        means = means / float(1 << cfg.frac_bits)
    elif cfg.early_quantization:
        # early quantization, float compute: quantize/dequantize to model the
        # precision loss, then float segmentation.
        xq = dequantize_fixed(quantize_signal_fixed(x, cfg.frac_bits),
                              cfg.frac_bits)
        b = boundary_mask_float(xq, cfg)
        means, n, cnts = segment_means(xq, b, signal.shape[0], cfg.max_events)
    else:
        b = boundary_mask_float(x, cfg)
        means, n, cnts = segment_means(x, b, signal.shape[0], cfg.max_events)
    return means, n, cnts


detect_events_batch = jax.vmap(detect_events, in_axes=(0, None),
                               out_axes=(0, 0, 0))


def detect_events_reference(signal: jnp.ndarray, cfg: MarsConfig):
    """Pre-fast-path ``detect_events``: two-sort median/MAD normalization +
    scatter-based segment reduction.  Parity oracle and the "pre" side of
    the cheap-phase microbenchmark (benchmarks/microbench.py)."""
    x = robust_normalize_reference(signal)
    if cfg.early_quantization and cfg.fixed_point:
        xq = quantize_signal_fixed(x, cfg.frac_bits)
        b = boundary_mask_fixed(xq, cfg)
        means, n, cnts = segment_means_reference(
            xq.astype(jnp.int32), b, signal.shape[0], cfg.max_events)
        means = means / float(1 << cfg.frac_bits)
    elif cfg.early_quantization:
        xq = dequantize_fixed(quantize_signal_fixed(x, cfg.frac_bits),
                              cfg.frac_bits)
        b = boundary_mask_float(xq, cfg)
        means, n, cnts = segment_means_reference(xq, b, signal.shape[0],
                                                 cfg.max_events)
    else:
        b = boundary_mask_float(x, cfg)
        means, n, cnts = segment_means_reference(x, b, signal.shape[0],
                                                 cfg.max_events)
    return means, n, cnts
