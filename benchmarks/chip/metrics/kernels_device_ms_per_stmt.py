"""Device busy time (union of the TPU's op intervals in the trace) per
statement answered in the traced window, in milliseconds."""


def read(ctx):
    if ctx.trace is None or not ctx.traced_count() or ctx.trace["busy_s"] <= 0:
        return None
    return ctx.trace["busy_s"] * 1e3 / ctx.traced_count()
