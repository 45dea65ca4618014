"""Group-bitmap run intervals mapped into the filter's coordinates per
shard-level grouped aggregate: the program's always-on counters
``executor.group_intervals`` over ``executor.group_aggs``
(``repro.core.trace.counters()``), over the whole process, so the warm-up's
one statement per template counts too, on both sides alike.  Nothing where
the program keeps no such counters."""


def read(ctx):
    try:
        from repro.core import trace
    except ImportError:
        return None
    c = trace.counters()
    aggs = c.get("executor.group_aggs", 0)
    if not aggs or "executor.group_intervals" not in c:
        return None
    return c["executor.group_intervals"] / aggs
