"""The fused cheap-phase kernel's share of its roofline.

The least time the chip could take for the cheap phase of the reads
mapped in the traced window (``bench/roofline.py``: the algorithm's bytes
and operations from shapes, against ``bench/peaks.json``) over the device
time of the kernel's operations in the trace.  Nothing to read when the
trace shows no operation of the kernel.
"""
import sys

from bench import roofline

# The kernel's custom call, as the device trace names it.
KERNEL = "cheap_fused"


def read(ctx):
    from bench import tracing
    t = tracing.op_seconds(ctx["record"], lambda op: KERNEL in op[0])
    if not t or not ctx["reads"]:
        return None
    least, bound = roofline.least_seconds(ctx["params"], ctx["reads"],
                                          ctx["peaks"])
    print(f"[roofline] cheap_fused: {least:.6g} s least ({bound} bound) "
          f"over {t:.6g} s of kernel time", file=sys.stderr)
    return 100.0 * least / t
