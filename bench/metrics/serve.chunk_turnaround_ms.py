"""Median time from the start of a chunk's packing (``serve.pack``) to the
end of the routing of its results (``serve.route``, the k-th with the
k-th), over the chunks packed inside the traced window.  Against
``read_latency_p50_ms`` it splits a read's latency into the wait before
its chunk is packed and the time after.  Nothing to read in a program
without these spans."""
import numpy as np

from bench import spans, tracing


def read(ctx):
    rec = ctx["record"]
    lo, hi = tracing.window(rec)
    ms = [(r[1] + r[2] - p[1]) * 1e-6
          for p, r in spans.paired(rec, "serve.pack", "serve.route")
          if lo <= p[1] < hi]
    return float(np.median(ms)) if ms else None
