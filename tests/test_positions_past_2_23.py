"""The program against the plain reference past 2^23 double-genome events.

Anchors sort on the (t, q) pair with t a whole int32 position, so the
program answers as the plain reference (``bench/reference.py``) does at
any position the index guard admits.  Raising every index position by a
multiple of the vote table's span (2^voting_window_log2 x vote_bins)
leaves every vote where it was, so both map a read the same way at shifts
of 2^23, 2^24 and 2^30: the chain phase's sort on such anchors, and
``map_chunk`` end to end on the reference plan and on the Pallas plan's
HBM launch, agree with the reference on every answer and counter.
"""
import json
import pathlib
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MarsConfig, build_index, chaining, map_chunk, stages
from repro.core.index import index_arrays

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import reference, traffic  # noqa: E402

PARAMS = json.loads((ROOT / "tests" / "bench" / "data" /
                     "tiny_config.json").read_text())["params"]
CFG = MarsConfig(**dict(PARAMS, chain_widths=tuple(PARAMS["chain_widths"])))
N_READS = 16
SHIFTS = [2 ** 23, 2 ** 24, 2 ** 30]


@pytest.fixture(scope="module")
def reads():
    """A few seeded reads of a test-sized genome, with the program's and
    the reference's index of it."""
    genome = traffic.make_genome(20000, traffic.rng_for(3, traffic.GENOME))
    signals = traffic.sample_reads(genome, N_READS, PARAMS["signal_len"],
                                   traffic.rng_for(3, traffic.POOL))[0]
    program = index_arrays(build_index(genome.events_concat,
                                       genome.n_events, CFG))
    ref = reference.build_index(genome.events_concat, genome.n_events,
                                PARAMS)
    return signals, program, ref


@pytest.mark.parametrize("shift", SHIFTS)
def test_chain_sort_matches_reference_past_2_23(shift):
    """The program's anchor sort (full width and select-then-sort, both
    selections) equals the reference's on q-major anchors past 2^23, with
    equal positions left for q to order."""
    rng = np.random.default_rng(shift % 97)
    E, H = CFG.max_events, CFG.max_hits_per_seed
    t = rng.integers(shift, shift + 4096, size=(E, H)).astype(np.int32)
    t[::3] = t[::3, :1]
    q = np.broadcast_to(np.arange(E, dtype=np.int32)[:, None], (E, H))
    for n_keep in (40, 600):
        keep = np.zeros(E * H, bool)
        keep[rng.choice(E * H, n_keep, replace=False)] = True
        keep = keep.reshape(E, H)
        st, sq, sv = reference.sort_anchors(jnp.asarray(t), jnp.asarray(q),
                                            jnp.asarray(keep),
                                            CFG.max_anchors)
        want = [np.asarray(x) for x in (sq, st, sv)]
        for select, width in (("count", None), ("count", 64), ("topk", 64)):
            if width is not None and n_keep > width and select == "count":
                continue
            got = chaining.sort_anchors(
                jnp.asarray(q), jnp.asarray(t), jnp.asarray(keep),
                CFG.replace(anchor_select=select), width=width)
            for g, w in zip(got, want):
                np.testing.assert_array_equal(np.asarray(g), w[:g.shape[0]])


def _mapped(signals, arrays, plan):
    out = map_chunk(jnp.asarray(signals), arrays, CFG, plan=plan)
    return ({f: np.asarray(getattr(out, f))
             for f in ("t_start", "score", "mapped", "n_events")},
            {k: int(v) for k, v in out.counters.items()})


@pytest.mark.parametrize("shift", SHIFTS)
@pytest.mark.parametrize("backend", [stages.REFERENCE, stages.PALLAS])
def test_map_chunk_matches_reference_past_2_23(reads, shift, backend,
                                               monkeypatch):
    """``map_chunk`` against an index shifted past 2^23 answers every read
    and counts every counter as the reference does against the same
    shift; the Pallas plan runs its fused kernel's HBM launch."""
    from repro.kernels.cheap_fused import ops
    signals, program, ref = reads
    monkeypatch.setattr(ops, "TABLE_BYTES_MAX", 0)
    packed = program["entries_packed"].copy()
    packed[1] += shift
    got, counters = _mapped(signals, dict(program, entries_packed=packed),
                            stages.resolve_plan(CFG, backend))
    want = reference.map_reads(signals, dict(ref, pos=ref["pos"] + shift),
                               PARAMS, block=N_READS)
    assert want["mapped"].sum() >= N_READS // 2
    assert (want["t_start"][want["mapped"]] >= shift).all()
    for f, v in got.items():
        np.testing.assert_array_equal(v, want[f], err_msg=f)
    for k in reference.COUNTERS:
        assert counters[k] == int(want[k].sum()), k
