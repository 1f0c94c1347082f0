"""The algorithm's work in the cheap phase (detect -> vote), from shapes.

Counted per read from the configuration's static shapes alone, never from
an implementation's tile sweep or gather schedule, so the same yardstick
judges the fused whole-index sweep and any later gather-based query:

bytes
    the raw f32 signal read in (S x 4);
    per seed probe (E of them) the bucket's two boundary words (2 x 4) and
    H packed two-word entry rows (H x 8);
    the outputs written: t_pos and the hit mask per anchor slot
    (E x H x 4 each) and the per-read counter row (9 x 4).

operations (integer and float, counted alike)
    normalization: two selections of the median, S log2 S compares each,
    and 4 operations per sample to normalize, clip, scale and round;
    the t-test: 16 operations per sample (four window sums and squares,
    difference, two scaled deviations, both sides, compare);
    peak picking: 2 r + 2 compares per sample;
    segment means: 2 operations per sample and one divide per event;
    event quantization: 10 operations per event and the 24-step integer
    square root at 3 operations a step;
    seeding: 2 w packing and 8 mixing operations per seed;
    query: 6 operations per anchor slot (bounds, key compare, count
    compare, masks);
    vote: 8 operations per anchor slot (diagonal, two windows, two
    histogram adds, two reads, compare).

The least time is the larger of bytes over HBM bandwidth and operations
over the chip's peak; ``bound`` says which one binds.
"""
from __future__ import annotations

import math

COUNTER_WORDS = 9


def cheap_bytes(p: dict) -> int:
    S, E, H = p["signal_len"], p["max_events"], p["max_hits_per_seed"]
    return S * 4 + E * (2 * 4 + H * 8) + 2 * E * H * 4 + COUNTER_WORDS * 4


def cheap_ops(p: dict) -> int:
    S, E, H = p["signal_len"], p["max_events"], p["max_hits_per_seed"]
    r, w = p["peak_window"], p["seed_width"]
    per_sample = 4 + 16 + (2 * r + 2) + 2
    select = 2 * S * int(math.log2(S))
    quant = 10 * E + 24 * 3
    seed = E * (2 * w + 8)
    slots = E * H * (6 + 8)
    return select + S * per_sample + E + quant + seed + slots


def least_seconds(p: dict, reads: int, peaks: dict):
    """(seconds, bound) of ``reads`` reads' cheap phase at the roofline."""
    t_mem = reads * cheap_bytes(p) / peaks["hbm_bytes_per_s"]
    t_ops = reads * cheap_ops(p) / peaks["bf16_flops_per_s"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
