"""Find the knee of a served cell: the highest offered rate it sustains.

    python3 bench/sweep.py --workload sarscov2_d1.served --seed 5 \
        --seconds 10 --rates 600,800,1000,1200

One process, one set-up; for each offered rate, one open-loop window of
``--seconds`` through the cell's ``ServeDriver``.  Prints one JSON line per
rate: the completed rate (reads answered inside the window per second),
the backlog (reads due and not answered) at the window's middle and at its
end, and the p50 / p99 latency.  The knee is the highest rate whose backlog
grows by no more than one chunk from the middle to the end and whose
completed rate stays within 3% of the offer; the cell's traffic file then
fixes its rate at about four fifths of it.  Every answer is checked
against the plain reference as in a run.
"""
import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import numpy as np
    from bench import harness
    harness.enable_compile_cache()

    cell = harness.load_cell(args.workload)
    if cell.traffic["loop"] != "open":
        raise SystemExit(f"{args.workload} is not an open-loop cell")
    setup = harness.set_up(cell, args.seed)
    harness.warm_up(cell, setup)
    wins = []
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.traffic, rate_per_s=rate)
        t0 = time.perf_counter()
        win = harness.served_window(setup.mapper, setup.pool, mix,
                                    cell.chunk, args.seconds, args.seed)
        due, done, T = win.extra["due"], win.extra["done"], args.seconds
        lat = win.extra["latency_ms"]
        row = {"offered_per_s": rate,
               "completed_per_s": float(np.sum(done <= T)) / T,
               "backlog_mid": harness.backlog(due, done, T / 2),
               "backlog_end": harness.backlog(due, done, T),
               "p50_ms": float(np.percentile(lat, 50)),
               "p99_ms": float(np.percentile(lat, 99)),
               "pad_rows_pct": 100.0 * win.extra["n_pad_rows"] / (
                   win.extra["n_chunks"] * cell.chunk),
               "rejected": win.extra["rejected"],
               "wall_s": time.perf_counter() - t0}
        wins.append(win)
        print(json.dumps(row), flush=True)
    setup.mapper = None
    ref = harness.reference_answers(
        cell, setup, np.concatenate([w.rows for w in wins]))
    bad = {k: sum(harness.compare(w, ref, setup.cfg.signal_len)[k]
                  for w in wins) for k in harness.CHECKS}
    print(json.dumps({"checks": bad}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
