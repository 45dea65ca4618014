"""Mean client latency less the mean time inside ``QueryService.statement``
or ``.query``, over the traced window, in milliseconds: HTTP, JSON and the
hand-off to the query pool."""
import numpy as np


def read(ctx):
    if ctx.probe is None:
        return None
    inside = ctx.probe.statement_seconds(*ctx.traced)
    lat = ctx.latencies_s(traced=True)
    if not len(inside) or not len(lat):
        return None
    return (float(np.mean(lat)) - float(np.mean(inside))) * 1e3
