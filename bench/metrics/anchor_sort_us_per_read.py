"""Device microseconds a read in the anchor sort.

The device time of the bitonic sort kernel's operations (its jitted
launcher, ``bitonic_sort``, names them in the trace) over the reads mapped
in the traced window.  Nothing to read when no such operation ran.
"""

# The anchor sort's kernel, as the device trace names it.
KERNEL = "bitonic_sort"


def read(ctx):
    from bench import tracing
    t = tracing.op_seconds(ctx["record"], lambda op: KERNEL in op[0])
    if not t or not ctx["reads"]:
        return None
    return t * 1e6 / ctx["reads"]
