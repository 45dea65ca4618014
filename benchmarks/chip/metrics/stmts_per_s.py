"""Statements answered over the window's seconds (all clients): every
statement sent in ``--seconds``, over the time from the first send to the
last answer."""


def read(ctx):
    return len(ctx.window) / ctx.window_s if ctx.window_s > 0 else None
