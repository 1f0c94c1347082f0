"""Seeded traffic for the benchmark: genomes, read pools and arrivals.

One general generator reads a traffic mix's parameters (``bench/traffic/
<mix>.json``) and a configuration's genome length, and makes everything a
run needs from ``--seed``.  The pore model and the read simulator are
copies of the program's own generators (``repro.core.pore_model``,
``repro.signal.simulate``), kept here so that a change to the program
cannot change the benchmark's inputs; ``tests/bench`` pins a digest of
their output.

A read pool mixes three kinds of reads, shuffled by the seed:

* on-target reads sampled from both strands of the indexed genome;
* junk reads of random signal (``junk_frac``), which map nowhere;
* off-target reads sampled from a background genome that is not in the
  index (``offtarget_frac``, ``background_len``).
"""
from __future__ import annotations

import dataclasses

import numpy as np

# --------------------------------------------------------------------------- #
# Pore model (copy of repro.core.pore_model)
# --------------------------------------------------------------------------- #
K = 6                      # k-mer length of the pore model
N_KMERS = 4 ** K
LEVEL_MEAN = 100.0         # pA
LEVEL_SPAN = 60.0          # levels uniform in [70, 130]
_M64 = np.uint64(0xFFFFFFFFFFFFFFFF)


def _splitmix64(x: np.ndarray) -> np.ndarray:
    x = (x + np.uint64(0x9E3779B97F4A7C15)) & _M64
    z = ((x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)) & _M64
    z = ((z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)) & _M64
    return z ^ (z >> np.uint64(31))


def pore_table(seed: int = 7) -> np.ndarray:
    """(4096,) float32 expected current level of every 6-mer."""
    idx = (np.arange(N_KMERS, dtype=np.uint64)
           + np.uint64(seed) * np.uint64(N_KMERS))
    u = (_splitmix64(idx) >> np.uint64(11)).astype(np.float64) / float(1 << 53)
    return (LEVEL_MEAN - LEVEL_SPAN / 2 + u * LEVEL_SPAN).astype(np.float32)


def kmer_ids(bases: np.ndarray) -> np.ndarray:
    n = bases.shape[0] - K + 1
    if n <= 0:
        return np.zeros((0,), np.int32)
    ids = np.zeros(n, dtype=np.int64)
    for j in range(K):
        ids = ids * 4 + bases[j:j + n].astype(np.int64)
    return ids.astype(np.int32)


def revcomp(bases: np.ndarray) -> np.ndarray:
    return (3 - bases)[::-1]


# --------------------------------------------------------------------------- #
# Genome and read simulator (copy of repro.signal.simulate)
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class Genome:
    events_fwd: np.ndarray     # (Le,) f32 expected levels, forward strand
    events_rc: np.ndarray      # (Le,) f32 expected levels, reverse strand

    @property
    def n_events(self) -> int:
        return int(self.events_fwd.shape[0])

    @property
    def events_concat(self) -> np.ndarray:
        """The double genome: forward ++ reverse-complement events."""
        return np.concatenate([self.events_fwd, self.events_rc])


def make_genome(length: int, rng: np.random.Generator) -> Genome:
    bases = rng.integers(0, 4, size=length, dtype=np.int8)
    table = pore_table()
    return Genome(events_fwd=table[kmer_ids(bases)],
                  events_rc=table[kmer_ids(revcomp(bases))])


def _signal_for_levels(levels, signal_len, dwell_lo, dwell_hi, noise_sigma,
                       rng):
    dwells = rng.integers(dwell_lo, dwell_hi + 1, size=levels.shape[0])
    reps = np.repeat(levels, dwells)
    n_bases = levels.shape[0]
    if reps.shape[0] < signal_len:
        reps = np.concatenate(
            [reps, np.full(signal_len - reps.shape[0], reps[-1])])
    else:
        n_bases = int(np.searchsorted(np.cumsum(dwells), signal_len,
                                      side="right")) + 1
        reps = reps[:signal_len]
    sig = reps + rng.normal(0.0, noise_sigma, size=signal_len)
    return sig.astype(np.float32), n_bases


def sample_reads(genome: Genome, n_reads: int, signal_len: int,
                 rng: np.random.Generator, dwell=(5, 11),
                 noise_sigma: float = 1.5):
    """``n_reads`` reads from both strands of ``genome``: (signals (n, S)
    f32, true_pos (n,) forward-strand start in events, true_strand (n,),
    n_bases (n,))."""
    Le = genome.n_events
    span = signal_len // dwell[0] + K + 2
    signals = np.zeros((n_reads, signal_len), np.float32)
    true_pos = np.zeros(n_reads, np.int64)
    strand = np.zeros(n_reads, np.int8)
    n_bases = np.zeros(n_reads, np.int64)
    for i in range(n_reads):
        s = int(rng.integers(0, 2))
        start = int(rng.integers(0, Le - span))
        src = genome.events_fwd if s == 0 else genome.events_rc
        sig, nb = _signal_for_levels(src[start:start + span], signal_len,
                                     dwell[0], dwell[1], noise_sigma, rng)
        signals[i], n_bases[i], strand[i] = sig, nb, s
        true_pos[i] = start if s == 0 else Le - 1 - (start + nb - 1)
    return signals, true_pos, strand, n_bases


def junk_signals(n_reads: int, signal_len: int, rng: np.random.Generator):
    return rng.normal(LEVEL_MEAN, LEVEL_SPAN / 4,
                      size=(n_reads, signal_len)).astype(np.float32)


# --------------------------------------------------------------------------- #
# The general generator
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class ReadPool:
    signals: np.ndarray        # (P, S) f32 raw signal
    true_pos: np.ndarray       # (P,) forward-strand start, -1 if unmappable
    true_strand: np.ndarray    # (P,) 0 fwd / 1 rev
    n_bases: np.ndarray        # (P,) bases each read consumed
    kind: np.ndarray           # (P,) 0 on-target, 1 junk, 2 off-target

    @property
    def mappable(self) -> np.ndarray:
        return self.kind == 0


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per purpose; any non-negative seed."""
    return np.random.default_rng([int(seed), int(stream)])


GENOME, POOL, BACKGROUND, ORDER, ARRIVALS = range(5)


def make_genome_for(config: dict, seed: int) -> Genome:
    return make_genome(int(config["genome_len"]), rng_for(seed, GENOME))


def make_pool(genome: Genome, traffic: dict, signal_len: int,
              seed: int) -> ReadPool:
    """The traffic mix's read pool, from the seed.  Every seed gives the
    same counts of each kind of read, in another order."""
    P = int(traffic["pool_reads"])
    n_junk = int(round(float(traffic.get("junk_frac", 0.0)) * P))
    n_off = int(round(float(traffic.get("offtarget_frac", 0.0)) * P))
    n_on = P - n_junk - n_off
    rng = rng_for(seed, POOL)
    on = sample_reads(genome, n_on, signal_len, rng)
    parts = [(on[0], on[1], on[2], on[3], np.zeros(n_on, np.int8))]
    if n_junk:
        parts.append((junk_signals(n_junk, signal_len, rng),
                      np.full(n_junk, -1), np.zeros(n_junk, np.int8),
                      np.zeros(n_junk, np.int64), np.ones(n_junk, np.int8)))
    if n_off:
        bg = make_genome(int(traffic["background_len"]),
                         rng_for(seed, BACKGROUND))
        off = sample_reads(bg, n_off, signal_len, rng)
        parts.append((off[0], np.full(n_off, -1), off[2], off[3],
                      np.full(n_off, 2, np.int8)))
    cols = [np.concatenate(c) for c in zip(*parts)]
    perm = rng_for(seed, ORDER).permutation(P)
    return ReadPool(*(c[perm] for c in cols))


def arrivals(traffic: dict, seconds: float, seed: int):
    """Open-loop Poisson arrivals over ``seconds``: (due times (n,) in s
    from the window's start, channel of each read (n,), pool row of each
    read (n,)).  Channels and pool rows are drawn uniformly."""
    rng = rng_for(seed, ARRIVALS)
    rate = float(traffic["rate_per_s"])
    n_max = int(rate * seconds * 1.5 + 64)
    due = np.cumsum(rng.exponential(1.0 / rate, size=n_max))
    while due[-1] < seconds:
        due = np.concatenate(
            [due, due[-1] + np.cumsum(rng.exponential(1.0 / rate, n_max))])
    due = due[due < seconds]
    chan = rng.integers(0, int(traffic["channels"]), size=due.shape[0])
    rows = rng.integers(0, int(traffic["pool_reads"]), size=due.shape[0])
    return due, chan, rows


# --------------------------------------------------------------------------- #
# Accuracy against the simulator's truth (copy of pipeline.score_accuracy)
# --------------------------------------------------------------------------- #
def score_accuracy(t_start, mapped, true_pos, true_strand, mappable, n_bases,
                   n_ref_events: int, tol: int = 100) -> dict:
    """Precision / recall / F1 of mapped reads against the simulator's
    truth: a mapped read is right when its strand matches and its
    forward-strand start lies within ``tol`` events of the truth."""
    t = np.asarray(t_start).astype(np.int64)
    strand = (t >= n_ref_events).astype(np.int8)
    span = np.maximum(np.asarray(n_bases).astype(np.int64), 1)
    fwd = np.where(strand == 0, t,
                   n_ref_events - 1 - ((t - n_ref_events) + span - 1))
    mapped = np.asarray(mapped, bool)
    right = (np.abs(fwd - true_pos) <= tol) & (strand == true_strand)
    tp = int(np.sum(mapped & mappable & right))
    fp = int(np.sum(mapped & ~(mappable & right)))
    fn = int(np.sum(~mapped & mappable))
    prec = tp / max(tp + fp, 1)
    rec = tp / max(tp + fn, 1)
    f1 = 2 * prec * rec / max(prec + rec, 1e-9)
    return dict(precision=prec, recall=rec, f1=f1, tp=tp, fp=fp, fn=fn)
