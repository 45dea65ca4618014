"""Spans and counters from outside the program, and the trace reduction.

``Probe`` installs thin wrappers on the program's public entry points for a
traced run and takes them off again:

* ``_Handler.do_POST`` (HTTP) -> span ``http``;
* ``QueryService.statement`` and ``QueryService.query`` -> span
  ``service.statement``, with its host-clock duration;
* ``kernels.ops.logical_reduce`` -> span ``kops.logical_reduce``, and the
  operand bytes any implementation must read: 4 bytes x ``block_cols`` for
  every DIRTY tile flag passed in (clean tiles need no read).

Spans go into the profiler's trace as ``jax.profiler.TraceAnnotation``, on
the host's clock beside the device's operations.  ``simplify`` keeps the
part of an ``.xplane.pb`` that the reduction reads; ``reduce_trace`` turns
it into device busy time, the idle share, the top device operations and the
longest idle gaps named by what the host was doing in them.
"""
from __future__ import annotations

import inspect
import json
import re
import threading
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

WINDOW_SPAN = "bench.window"
# host spans, most specific first: an idle gap is named by the first one
# that covers at least half of it
HOST_SPANS = ("kops.logical_reduce", "service.statement", "http")
NO_SPAN = "between_statements"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+(\s|$)")
OPS_LINE = "XLA Ops"


class Probe:
    """Wrappers around the program's entry points (``install``/``remove``)."""

    def __init__(self):
        self.lock = threading.Lock()
        self.statements: List[tuple] = []      # (t0, t1) monotonic
        self.reduces: List[tuple] = []         # (t0, operand bytes)
        self._undo: List[tuple] = []

    def _patch(self, owner, name, make):
        orig = getattr(owner, name)
        setattr(owner, name, make(orig))
        self._undo.append((owner, name, orig))

    def install(self) -> "Probe":
        import jax
        from repro.kernels import ops as kops
        from repro.kernels import word_logical as wl
        from repro.serve import query_api

        ann = jax.profiler.TraceAnnotation
        probe = self

        def span(name):
            def make(orig):
                def wrapped(*a, **kw):
                    with ann(name):
                        return orig(*a, **kw)
                return wrapped
            return make

        def timed(orig):
            def wrapped(*a, **kw):
                t0 = time.monotonic()
                try:
                    with ann("service.statement"):
                        return orig(*a, **kw)
                finally:
                    t1 = time.monotonic()
                    with probe.lock:
                        probe.statements.append((t0, t1))
            return wrapped

        def reduce(orig):
            # bound by name, so the wrapper outlives a change to the
            # signature; without tile flags it counts no bytes
            sig = inspect.signature(orig)

            def wrapped(*a, **kw):
                t0 = time.monotonic()
                args = sig.bind(*a, **kw)
                args.apply_defaults()
                flags = args.arguments.get("row_flags")
                cols = args.arguments.get("block_cols")
                if flags is not None and cols is not None:
                    dirty = int(np.count_nonzero(
                        np.asarray(flags) == wl.DIRTY))
                    with probe.lock:
                        probe.reduces.append((t0, 4 * int(cols) * dirty))
                with ann("kops.logical_reduce"):
                    return orig(*a, **kw)
            return wrapped

        self._patch(query_api._Handler, "do_POST", span("http"))
        self._patch(query_api.QueryService, "statement", timed)
        self._patch(query_api.QueryService, "query", timed)
        self._patch(kops, "logical_reduce", reduce)
        return self

    def remove(self) -> None:
        for owner, name, orig in reversed(self._undo):
            setattr(owner, name, orig)
        self._undo.clear()

    def statement_seconds(self, t0: float, t1: float) -> List[float]:
        with self.lock:
            return [b - a for a, b in self.statements if t0 <= b <= t1]

    def reduce_bytes(self, t0: float, t1: float) -> Optional[int]:
        with self.lock:
            sel = [n for t, n in self.reduces if t0 <= t <= t1]
        return sum(sel) if sel else None


# -- the trace ---------------------------------------------------------------
def simplify(xplane_path: str) -> Dict:
    """The events the reduction reads, as plain JSON: the TPU planes' op
    events, and the benchmark's host spans (``[name, start_ns, dur_ns]``)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    planes = []
    keep = set(HOST_SPANS) | {WINDOW_SPAN}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = [{"name": ln.name,
                      "events": [[ev.name, ev.start_ns, ev.duration_ns]
                                 for ev in ln.events]}
                     for ln in plane.lines]
        elif plane.name.startswith("/host:"):
            lines = []
            for ln in plane.lines:
                evs = [[ev.name, ev.start_ns, ev.duration_ns]
                       for ev in ln.events if ev.name in keep]
                if evs:
                    lines.append({"name": ln.name, "events": evs})
        else:
            continue
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def union(starts: np.ndarray, ends: np.ndarray):
    """Merge intervals into sorted disjoint ``(starts, ends)``."""
    if not len(starts):
        return starts, ends
    o = np.argsort(starts, kind="stable")
    s, e = starts[o], ends[o]
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), dtype=bool)
    new[1:] = s[1:] > reach[:-1]
    idx = np.flatnonzero(new)
    return s[idx], np.append(reach[idx[1:] - 1], reach[-1])


def covered(us: np.ndarray, ue: np.ndarray, a: np.ndarray,
            b: np.ndarray) -> np.ndarray:
    """Length of each ``[a, b)`` covered by the disjoint sorted union."""
    if not len(us):
        return np.zeros(len(a))
    pref = np.concatenate(([0.0], np.cumsum(ue - us)))

    def below(x):
        i = np.searchsorted(us, x, side="right") - 1
        i0 = np.maximum(i, 0)
        inside = np.clip(x - us[i0], 0, ue[i0] - us[i0])
        return np.where(i >= 0, pref[i0] + inside, 0.0)

    return below(b) - below(a)


def _events(lines: Sequence[Dict], name: Optional[str] = None):
    ev = [e for ln in lines for e in ln["events"]
          if name is None or e[0] == name]
    s = np.asarray([e[1] for e in ev], dtype=np.float64)
    d = np.asarray([e[2] for e in ev], dtype=np.float64)
    return ev, s, s + d


def reduce_trace(trace: Dict, top: int = 10) -> Optional[Dict]:
    """Busy and idle time of the device over the window span.

    ``busy_s`` is the union of the device's op intervals inside the window,
    averaged over the TPU planes; ``idle_gaps`` lists the ``top`` longest
    gaps, each named by the host span that covers at least half of it
    (``HOST_SPANS`` order), or ``between_statements``.  Returns None where
    the trace holds no window span."""
    host = [ln for p in trace["planes"] if not DEVICE_PLANE.match(p["name"])
            for ln in p["lines"]]
    _, ws, we = _events(host, WINDOW_SPAN)
    if not len(ws):
        return None
    w0, w1 = float(ws.min()), float(we.max())
    devices = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    busy, ops = [], {}
    ga = gb = np.empty(0)
    for plane in devices:
        lines = [ln for ln in plane["lines"] if ln["name"] == OPS_LINE] \
            or [ln for ln in plane["lines"] if "step" not in ln["name"].lower()]
        ev, s, e = _events(lines)
        s, e = np.clip(s, w0, w1), np.clip(e, w0, w1)
        for (name, _, _), a, b in zip(ev, s, e):
            if b > a:
                ops[name] = ops.get(name, 0.0) + (b - a) * 1e-9
        us, ue = union(s[e > s], e[e > s])
        busy.append(float((ue - us).sum()) * 1e-9)
        if plane is devices[0]:
            ga, gb = np.append(w0, ue), np.append(us, w1)
    # the first device's gaps, named by the host spans
    keep = gb > ga
    ga, gb = ga[keep], gb[keep]
    label = np.full(len(ga), NO_SPAN, dtype=object)
    open_ = np.ones(len(ga), dtype=bool)
    for name in HOST_SPANS:
        _, s, e = _events(host, name)
        us, ue = union(s, e)
        hit = open_ & (covered(us, ue, ga, gb) * 2 >= (gb - ga))
        label[hit] = name
        open_ &= ~hit
    length = (gb - ga) * 1e-9
    by_span: Dict[str, float] = {}
    for lab, ln in zip(label, length):
        by_span[lab] = by_span.get(lab, 0.0) + float(ln)
    order = np.argsort(-length, kind="stable")[:top]
    return {
        "busy_s": float(np.mean(busy)) if busy else 0.0,
        "window_s": (w1 - w0) * 1e-9,
        "device_ops": sorted(([k, v] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[str(label[i]), float(length[i])] for i in order],
        "idle_by_span": by_span,
    }


def save(trace: Dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(trace, f)
