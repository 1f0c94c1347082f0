"""Public wrapper for the fused cheap-phase mega-kernel.

Host graph: normalize + early-quantize the signals (same split as the
event_detect wrapper), launch the mega-kernel once, then rebuild the
cheap-phase (q_pos, t_pos, hit_valid, counters) contract.  The launch
follows the index's size: up to ``TABLE_BYTES_MAX`` of byte planes
(`index_planes`) the tables stay in VMEM (``cheap_fused_fixed``); past it
they stay in HBM as the index dict holds them, and each read fetches what
it probes by DMA (``cheap_fused_hbm``).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.core import events as ev
from repro.core import stages
from repro.core.config import MarsConfig
from repro.kernels.cheap_fused.cheap_fused import (
    COUNTER_COLS, TABLE_BYTES_MAX, cheap_fused_fixed, cheap_fused_hbm,
    index_planes, table_bytes)


def cheap_fused(signals: jnp.ndarray, index: Dict[str, jnp.ndarray],
                cfg: MarsConfig):
    """signals: (R, S) f32 raw; index: the packed online index view.

    Returns (q_pos, t_pos, hit_valid, counters) — the exact
    ``pipeline.cheap_phase`` contract, bit-identical to the per-stage
    pallas program for every config the `supports` gate admits, from
    either launch.
    """
    assert cfg.fixed_point and cfg.early_quantization, (
        "mega-kernel implements the MARS fixed-point path")
    tables = (index["bucket_bounds"], index["entries_packed"])
    if table_bytes(*jax.eval_shape(index_planes, *tables)) <= TABLE_BYTES_MAX:
        launch, tables = cheap_fused_fixed, index_planes(*tables)
    else:
        launch = cheap_fused_hbm
    x = ev.robust_normalize(signals)
    xq = ev.quantize_signal_fixed(x, cfg.frac_bits).astype(jnp.int32)
    r = xq.shape[0]
    e, h = cfg.max_events, cfg.max_hits_per_seed

    clip_q = int(round(cfg.quant_clip_sigma * (1 << cfg.frac_bits)))
    t_pos, hit, cnt = launch(
        xq, *tables,
        n_ev_max=e, hits=h, tw=cfg.tstat_window,
        tau2=int(round(cfg.tstat_threshold ** 2)),
        eps=1 << (2 * cfg.frac_bits - 8),
        peak_r=cfg.peak_window, frac_bits=cfg.frac_bits,
        seed_w=cfg.seed_width, seed_q=cfg.quant_bits,
        minimizer_r=cfg.minimizer_radius, levels=cfg.quant_levels,
        clip_q=clip_q, step_q=(2 * clip_q) // cfg.quant_levels,
        n_buckets=cfg.n_buckets,
        thresh_freq=cfg.thresh_freq, use_freq=cfg.use_freq_filter,
        use_vote=cfg.use_vote_filter, vlog2=cfg.voting_window_log2,
        nbins=cfg.vote_bins, thresh_vote=cfg.thresh_voting)

    t_pos = t_pos.reshape(r, e, h)
    hit_valid = hit.reshape(r, e, h).astype(bool)
    counters = {name: cnt[:, i] for i, name in enumerate(COUNTER_COLS)}
    q_pos = jnp.broadcast_to(
        jnp.arange(e, dtype=jnp.int32)[None, :, None], t_pos.shape)
    return q_pos, t_pos, hit_valid, counters


def _fused_supports(cfg: MarsConfig) -> bool:
    """Same admission rule as the event_detect kernel it subsumes: the
    integer boundary test must fit int32 for this config."""
    return (cfg.fixed_point and cfg.early_quantization
            and ev.fixed_tstat_in_range(cfg))


stages.register_fused_cheap(stages.PALLAS, cheap_fused,
                            supports=_fused_supports)
