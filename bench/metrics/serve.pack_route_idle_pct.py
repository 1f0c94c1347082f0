"""Share of the traced window in which the device sat idle while
``ServeDriver`` packed a chunk (``serve.pack``) or routed a chunk's
results to their streams (``serve.route``), each idle nanosecond to the
innermost program span open over it (``bench/spans.py``).  Nothing to
read in a program without these spans."""
from bench import spans


def read(ctx):
    return spans.idle_pct(ctx["record"], ("serve.pack", "serve.route"))
