"""Median client latency (send to last byte) of every statement sent in the
window, in milliseconds."""
import numpy as np


def read(ctx):
    lat = ctx.latencies_s()
    return float(np.percentile(lat, 50)) * 1e3 if len(lat) else None
