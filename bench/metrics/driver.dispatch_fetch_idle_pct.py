"""Share of the traced window in which the device sat idle while the
streaming driver dispatched a chunk (``driver.dispatch``: the copy to the
device and the jit launch) or fetched one (``driver.fetch``: waiting for
it, then its outputs and counters to the host).  Each idle nanosecond
goes to the innermost program span open over it (``bench/spans.py``).
Nothing to read in a program without these spans."""
from bench import spans


def read(ctx):
    return spans.idle_pct(ctx["record"], ("driver.dispatch", "driver.fetch"))
