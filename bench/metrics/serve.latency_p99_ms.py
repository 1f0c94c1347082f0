"""99th percentile of the served reads' latency over the window, from due
time to result on the host: the tail the host's rare stalls decide."""
import numpy as np


def read(ctx):
    lat = ctx["window"].extra.get("latency_ms")
    if lat is None or not len(lat):
        return None
    return float(np.percentile(lat, 99))
