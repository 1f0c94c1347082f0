"""Readings of the comparison that decides ``correct``, on the chip.

    python3 bench/control.py --workload sarscov2_d1.batch \
        --seeds 11,12,13 --control-seeds 21,22,23 --seconds 10

For each ``--seeds`` seed, one whole run of the program as the cell states
it (the lower readings of every compared number); for each
``--control-seeds`` seed, one run of the control: the program with its
signal quantized one precision below the configuration's, Q3.4 (int8
range) in place of Q7.8 (``frac_bits`` 8 -> 4), compared with the same
reference (the upper readings).  One JSON line per run, then a summary:
per compared number the largest program reading and the smallest control
reading.  All runs share one process and its compiled programs.
"""
import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
CONTROL = {"frac_bits": 4}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import harness
    harness.enable_compile_cache()

    cell = harness.load_cell(args.workload)
    readings = {"program": [], "control": []}
    runs = [("program", s, None) for s in args.seeds.split(",") if s] + \
        [("control", s, CONTROL) for s in args.control_seeds.split(",") if s]
    for side, seed, params in runs:
        r = harness.run(cell, int(seed), args.seconds, False,
                        time.perf_counter(), program_params=params)
        checks = {k: v["value"] for k, v in r["checks"].items()}
        readings[side].append(checks)
        print(json.dumps({"side": side, "seed": int(seed),
                          "correct": r["correct"], "checks": checks,
                          "metrics": r["metrics"]}), flush=True)
    summary = {}
    for k in harness.CHECKS:
        summary[k] = {
            "lower": max((c[k] for c in readings["program"]), default=None),
            "upper": min((c[k] for c in readings["control"]), default=None)}
    print(json.dumps({"workload": args.workload, "summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
