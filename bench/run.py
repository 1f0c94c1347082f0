"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload sarscov2_d1.batch --seed 7 \
        --seconds 10 --trace 0

Loads the cell named in ``BENCHMARK.json``, sets up from ``--seed``,
measures for ``--seconds`` and checks every answer against the plain
reference.  The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``: each compared
number beside its limit).  With ``--trace 0`` the metrics are the cell's
end-to-end metrics; with ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.  JAX's persistent compilation cache lives in
``JAX_COMPILATION_CACHE_DIR`` when that is set, else in ``.jax_cache/`` at
the root of the checkout.
"""
import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import harness
    cache = harness.enable_compile_cache()
    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench/run.py: JAX found no TPU (first device: "
              f"{devices[0].platform}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench/run.py: {args.workload} needs {cell.chips} chips; JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    from repro import kernels
    if kernels.INTERPRET:
        raise RuntimeError("Pallas kernels would run in interpret mode")
    harness.log(f"[device] {devices[0].device_kind} x {len(devices)}, "
                f"compile cache {cache}")
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         T_START)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
