"""Share of the memory roofline reached by the executor's n-ary reductions.

Bytes: 4 x ``block_cols`` for every DIRTY tile flag passed to
``kops.logical_reduce`` in the traced window, the operand tiles any
implementation must read once (clean tiles need no read).  Time: the
device's busy time in the same window, every op counted.  Share = bytes /
peak HBM bytes per second / busy seconds, in percent.  Nothing is returned
where no reduction read a dirty tile or the device was never busy.
"""


def read(ctx):
    if ctx.trace is None or ctx.probe is None:
        return None
    nbytes = ctx.probe.reduce_bytes(*ctx.traced)
    busy = ctx.trace["busy_s"]
    if not nbytes or busy <= 0:
        return None
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / busy
