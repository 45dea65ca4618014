"""Bytes of the saved store's files, less the measure sidecar segments,
per table row: the paper's own metric (index size)."""


def read(ctx):
    return ctx.index_bytes / ctx.n_rows if ctx.n_rows else None
