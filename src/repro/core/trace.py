"""Spans and counters of the served path: where a statement's host time goes.

Tracing is off by default.  Off, ``span(name)`` is one check of a module flag
that returns a shared no-op context manager: nothing is allocated and JAX is
never imported.  ``enable()`` turns it on; each span is then recorded twice:

* in memory (``spans()``): name, span id, parent span id, statement id,
  thread id, start and end on ``time.monotonic()`` — the clock the
  benchmark's load generator stamps its requests with — and the CPU seconds
  its thread spent inside it (``time.thread_time()``; the rest of the span
  waited: for the GIL, the device or I/O), in a bounded deque that keeps
  the newest ``MAX_SPANS``;
* as a ``jax.profiler.TraceAnnotation`` of the same name, so a profiler
  trace shows the span on its own clock beside the device's operations.

A statement id is minted by ``statement()`` (the HTTP handler and
``QueryService`` open one per statement, and one per batch); spans opened
under it carry it.  ``bind(fn)`` carries the caller's statement id and
parent span into a pool thread, so the spans of one statement form one tree
across the query and shard threads; its ``wait`` span covers the time from
the hand-off to the start of the run.  Workers forked by a
``ShardProcessPool`` (backend ``ewah`` only) record nothing: tracing is
turned off in them.

Counters (``add``) are always on: integer sums under one lock, read with
``counters()``.  The served path keeps three: ``kops.h2d_bytes`` (operand
words and tile flags copied to the device), ``kops.d2h_bytes`` (kernel
results fetched back) and ``kops.dirty_tile_bytes`` (4 bytes x
``block_cols`` per DIRTY operand tile flag handed to ``logical_reduce``,
the operand bytes any implementation must read).  ``summary()`` adds, per
span name, the count, total seconds, self seconds (a span's duration less
the part of its interval that its child spans cover) and CPU seconds.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

MAX_SPANS = 1 << 17

SpanRecord = collections.namedtuple(
    "SpanRecord", "name id parent stmt thread t0 t1 cpu")

_on = False
_annotation = None            # jax.profiler.TraceAnnotation, once enabled
_spans: "collections.deque[tuple]" = collections.deque(maxlen=MAX_SPANS)
_span_ids = itertools.count(1)
_stmt_ids = itertools.count(1)
_counters: Dict[str, int] = {}
counter_lock = threading.Lock()


class _Local(threading.local):
    stmt: Optional[int] = None
    span: Optional[int] = None


_local = _Local()


class _NoSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NO_SPAN = _NoSpan()


class _Span:
    __slots__ = ("name", "id", "parent", "stmt", "ann", "t0", "c0")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        loc = _local
        self.parent, self.stmt = loc.span, loc.stmt
        self.id = loc.span = next(_span_ids)
        self.ann = _annotation(self.name)
        self.ann.__enter__()
        self.t0 = time.monotonic()
        self.c0 = time.thread_time()
        return self

    def __exit__(self, *exc):
        t1 = time.monotonic()
        cpu = time.thread_time() - self.c0
        self.ann.__exit__(*exc)
        _local.span = self.parent
        _spans.append((self.name, self.id, self.parent, self.stmt,
                       threading.get_ident(), self.t0, t1, cpu))
        return False


class _Statement:
    __slots__ = ("outer",)

    def __enter__(self):
        self.outer = _local.stmt
        if self.outer is None:
            _local.stmt = next(_stmt_ids)
        return self

    def __exit__(self, *exc):
        _local.stmt = self.outer
        return False


def enable() -> None:
    """Record spans from now on (imports ``jax.profiler`` on first use)."""
    global _on, _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    _on = True


def disable() -> None:
    """Stop recording; spans already open still close and are kept."""
    global _on
    _on = False


def enabled() -> bool:
    return _on


def reset() -> None:
    """Forget the recorded spans (counters keep counting)."""
    _spans.clear()


def span(name: str):
    """Context manager timing one phase of the served path."""
    if not _on:
        return _NO_SPAN
    return _Span(name)


def statement():
    """Context manager for one statement: mints a statement id unless this
    thread is already inside one (a handler's statement spans the
    service's)."""
    if not _on:
        return _NO_SPAN
    return _Statement()


def bind(fn: Callable, wait: Optional[str] = None,
         run: Optional[str] = None) -> Callable:
    """``fn`` for one run in another thread, under the caller's statement id
    and current span.  ``wait`` names a span from this call to the start of
    the run (the time spent queued for a pool thread); ``run`` a span around
    the run.  Returns ``fn`` itself while tracing is off."""
    if not _on:
        return fn
    stmt, parent = _local.stmt, _local.span
    if wait is not None:
        wait_id = next(_span_ids)
        # entered here, left in the pool thread: the profiler records the
        # span from this call's time to the start of the run
        ann = _annotation(wait)
        ann.__enter__()
        t0 = time.monotonic()

    def bound(*args, **kwargs):
        loc = _local
        if wait is not None:
            t1 = time.monotonic()
            ann.__exit__(None, None, None)
            _spans.append((wait, wait_id, parent, stmt,
                           threading.get_ident(), t0, t1, 0.0))
        outer = loc.stmt, loc.span
        loc.stmt, loc.span = stmt, parent
        try:
            if run is None:
                return fn(*args, **kwargs)
            with span(run):
                return fn(*args, **kwargs)
        finally:
            loc.stmt, loc.span = outer

    return bound


def add(name: str, n: int) -> None:
    """Add ``n`` to counter ``name`` (exact under threads)."""
    with counter_lock:
        _counters[name] = _counters.get(name, 0) + int(n)


def counters() -> Dict[str, int]:
    with counter_lock:
        return dict(_counters)


def spans() -> List[SpanRecord]:
    """The recorded spans, oldest first."""
    return [SpanRecord._make(r) for r in list(_spans)]


def _covered(intervals: List[Tuple[float, float]], a: float,
             b: float) -> float:
    """Length of ``[a, b]`` covered by the union of ``intervals``."""
    total, reach = 0.0, a
    for s, e in sorted(intervals):
        s, e = max(s, reach), min(e, b)
        if e > s:
            total += e - s
            reach = e
    return total


def summary(records: Optional[List[SpanRecord]] = None) -> Dict:
    """Counters, and per span name its count, total, self and CPU seconds,
    over ``records`` (default: every recorded span)."""
    recs = spans() if records is None else records
    kids: Dict[int, List[Tuple[float, float]]] = {}
    for r in recs:
        if r.parent is not None:
            kids.setdefault(r.parent, []).append((r.t0, r.t1))
    by_name: Dict[str, Dict] = {}
    for r in recs:
        d = r.t1 - r.t0
        c = kids.get(r.id)
        s = by_name.setdefault(r.name, {"count": 0, "total_s": 0.0,
                                        "self_s": 0.0, "cpu_s": 0.0})
        s["count"] += 1
        s["total_s"] += d
        s["self_s"] += d - _covered(c, r.t0, r.t1) if c else d
        s["cpu_s"] += r.cpu
    return {"enabled": _on, "counters": counters(), "spans": by_name}
