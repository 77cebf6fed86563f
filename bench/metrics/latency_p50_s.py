"""Median latency of every request of the window, submit to outputs
ready on the device."""
import numpy as np


def read(ctx):
    latencies = [x for t in ctx.ticks for x in t.latencies]
    if not latencies:
        return None
    return float(np.percentile(latencies, 50))
