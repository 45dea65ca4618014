"""Spans and counters of the served path (``repro.core.trace``)."""
import json
import sys
import threading
import urllib.request

import numpy as np
import pytest

from repro.core import ShardedIndex, synth, trace
from repro.core.executor import Executor
from repro.serve.query_api import QueryService, serve_in_thread

# one count over an OR of three values of column 0: on backend ``kernel``
# every shard sends the OR to ``logical_reduce``
COUNT_OR = {"select": {"count": True},
            "where": {"op": "in", "col": "a", "values": [0, 1, 2]}}


@pytest.fixture(autouse=True)
def tracing_off_after():
    trace.disable()
    trace.reset()
    yield
    trace.disable()
    trace.reset()


@pytest.fixture(scope="module")
def table():
    rng = np.random.default_rng(7)
    t, _ = synth.factorize(synth.uniform_table(4 * 2048, 3, r=2, rng=rng))
    return t


@pytest.fixture(scope="module")
def service(table):
    idx = ShardedIndex.build(table, shard_rows=2048, k=1,
                             column_names=["a", "b", "c"])
    assert idx.n_shards == 4
    svc = QueryService(idx, backend="kernel", pool_workers=4,
                       cache_entries=0)
    for cache in idx._result_caches:
        cache.capacity = 0
    yield svc
    svc.close()


class _Boom:
    def __init__(self, *a, **kw):
        raise AssertionError("a TraceAnnotation was made while tracing off")


def test_off_records_nothing_and_makes_no_annotation(service, table,
                                                     monkeypatch):
    import jax.profiler
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Boom)
    monkeypatch.setattr(trace, "_annotation", _Boom)
    fn = len
    assert trace.bind(fn, wait="w", run="r") is fn
    d0 = Executor.kernel_dispatches
    out = service.statement(COUNT_OR)
    assert out["count"] == int(np.isin(table[:, 0], [0, 1, 2]).sum())
    assert Executor.kernel_dispatches - d0 == 4
    assert trace.spans() == []
    assert trace.summary()["spans"] == {}


def _by_id(spans):
    return {s.id: s for s in spans}


def _ancestors(s, ids):
    while s.parent is not None and s.parent in ids:
        s = ids[s.parent]
        yield s


def test_statement_spans_form_one_tree_across_threads(service, table):
    trace.enable()
    assert trace.enabled()
    c0 = trace.counters()
    out = service.statement(COUNT_OR)
    trace.disable()
    assert not trace.enabled()
    assert out["count"] == int(np.isin(table[:, 0], [0, 1, 2]).sum())
    spans = trace.spans()
    ids = _by_id(spans)
    names = [s.name for s in spans]
    assert len({s.stmt for s in spans}) == 1 and spans[0].stmt is not None
    assert len({s.thread for s in spans}) >= 3  # caller, query, shards
    for name, n in (("service.parse", 1), ("service.queue_wait", 1),
                    ("service.execute", 1), ("shard.queue_wait", 4),
                    ("shard.run", 4), ("planner.plan", 4),
                    ("executor.kernel", 4), ("executor.fetch", 4),
                    ("executor.reencode", 4), ("executor.aggregate", 4),
                    ("kops.transfer_in", 4), ("kops.launch", 4)):
        assert names.count(name) == n, name
    assert names.count("executor.load") == names.count("executor.densify")
    assert names.count("executor.load") == 12  # 3 operands x 4 shards
    (execute,) = [s for s in spans if s.name == "service.execute"]
    (qwait,) = [s for s in spans if s.name == "service.queue_wait"]
    assert qwait.parent is None and execute.parent is None
    assert qwait.t1 <= execute.t0
    for s in spans:
        if s.name in ("shard.run", "shard.queue_wait"):
            assert s.parent == execute.id
        if s.name in ("planner.plan", "executor.load", "executor.kernel",
                      "executor.aggregate"):
            assert ids[s.parent].name == "shard.run"
            assert ids[s.parent].thread == s.thread
        if s.name in ("kops.transfer_in", "kops.launch", "executor.fetch"):
            assert ids[s.parent].name == "executor.kernel"
        if s.parent is not None and s.name != "shard.queue_wait":
            p = ids[s.parent]
            assert p.t0 <= s.t0 and s.t1 <= p.t1, (s, p)
        assert execute in _ancestors(s, ids) or s.parent is None
    for s in spans:  # a queue wait burns no CPU of the span's thread
        assert 0 <= s.cpu <= s.t1 - s.t0 + 1e-3
        if s.name.endswith("queue_wait"):
            assert s.cpu == 0
    # self time: the duration less the union of the children's intervals
    kids = sorted((s.t0, s.t1) for s in spans if s.parent == execute.id)
    covered, reach = 0.0, execute.t0
    for a, b in kids:
        a, b = max(a, reach), min(b, execute.t1)
        if b > a:
            covered, reach = covered + b - a, b
    summ = trace.summary()["spans"]
    assert summ["service.execute"]["count"] == 1
    assert summ["service.execute"]["self_s"] == pytest.approx(
        execute.t1 - execute.t0 - covered)
    assert 0 <= summ["service.execute"]["self_s"] \
        < summ["service.execute"]["total_s"]
    fetch = summ["executor.fetch"]  # a leaf: all of it is self time
    assert fetch["self_s"] == pytest.approx(fetch["total_s"])
    assert summ["executor.densify"]["cpu_s"] == pytest.approx(
        sum(s.cpu for s in spans if s.name == "executor.densify"))
    c1 = trace.counters()
    # 4 shards x one bucketed 1024-word result fetched
    assert c1["kops.d2h_bytes"] - c0.get("kops.d2h_bytes", 0) == 4 * 4096


def test_http_statement_spans_share_one_id(service):
    srv, port = serve_in_thread(service)
    try:
        trace.enable()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/query",
            data=json.dumps(COUNT_OR).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            json.loads(resp.read())
        trace.disable()
    finally:
        srv.shutdown()
        srv.server_close()
    spans = trace.spans()
    assert len({s.stmt for s in spans}) == 1
    names = [s.name for s in spans]
    # the handler's parse (body, JSON) and the service's parse_statement
    assert names.count("service.parse") == 2
    assert names.count("service.respond") == 1
    assert "service.execute" in names
    stats = service.stats()["trace"]
    assert stats["enabled"] is False
    assert stats["spans"]["service.respond"]["count"] == 1
    assert "kops.h2d_bytes" in stats["counters"]


def test_counters_exact_under_threads():
    n, per = 16, 2000  # more threads than cores
    c0 = trace.counters().get("test.n", 0)
    d0 = Executor.kernel_dispatches
    start = threading.Barrier(n)

    def work():
        start.wait()
        for _ in range(per):
            trace.add("test.n", 1)
            Executor._count_dispatch()

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert trace.counters()["test.n"] - c0 == n * per
    assert Executor.kernel_dispatches - d0 == n * per


def test_h2d_bytes_are_the_bytes_put_on_the_device():
    from repro.kernels import ops as kops
    from repro.kernels.word_logical import DIRTY
    rng = np.random.default_rng(3)
    mat = rng.integers(0, 2**32, size=(5, 2048), dtype=np.uint32)
    mat[1, :1024] = 0  # one clean tile
    ref = np.bitwise_or.reduce(mat, axis=0)

    def delta(fn):
        c0 = trace.counters()
        out = np.asarray(fn())
        c1 = trace.counters()
        return out, {k: c1.get(k, 0) - c0.get(k, 0)
                     for k in ("kops.h2d_bytes", "kops.dirty_tile_bytes")}

    # 5 rows pad to 8 with OR's identity row, put on the device once per
    # width and kept: the first call's bytes hold it, the next call's not
    kops._identity_row.cache_clear()
    for held in (mat[:1].nbytes, 0):
        out, d = delta(lambda: kops.logical_reduce(mat, op="or"))
        np.testing.assert_array_equal(out, ref)
        assert d == {"kops.h2d_bytes": mat.nbytes + held,
                     "kops.dirty_tile_bytes": 0}

    flags = kops.np_row_flags(mat)
    out, d = delta(lambda: kops.logical_reduce(mat, op="or",
                                               row_flags=flags))
    np.testing.assert_array_equal(out, ref)
    # the 8 rows' (8, 2) flags go to the device at one byte a flag
    assert d["kops.h2d_bytes"] == mat.nbytes + 8 * 2
    assert d["kops.dirty_tile_bytes"] == \
        4 * 1024 * int(np.count_nonzero(flags == DIRTY)) == 4 * 1024 * 9

    a, b = mat[:2], mat[2:4]
    out, d = delta(lambda: kops.word_logical(a, b, "and"))
    np.testing.assert_array_equal(out, a & b)
    assert d["kops.h2d_bytes"] == a.nbytes + b.nbytes


def _runs(x):
    """Run id of each row: maximal runs of one value."""
    return np.concatenate(([0], np.cumsum(x[1:] != x[:-1])))


def test_group_sweep_span_and_counters(table):
    idx = ShardedIndex.build(table, shard_rows=2048, k=1,
                             column_names=["a", "b", "c"])
    svc = QueryService(idx, backend="ewah", pool_workers=4, cache_entries=0)
    for cache in idx._result_caches:
        cache.capacity = 0
    cube = {"select": {"count": True, "by": ["a", "b", "c"]},
            "where": {"op": "range", "col": "c", "lo": 0, "hi": 1}}
    empty = {"select": {"count": True, "by": ["a", "b", "c"]},
             "where": {"op": "and", "args": [
                 {"op": "eq", "col": "a", "value": 0},
                 {"op": "eq", "col": "a", "value": 1}]}}
    names = ("executor.group_aggs", "executor.group_intervals",
             "executor.group_segments")
    # what the sweep must count, from the stored rows: every run of every
    # value of every grouping column is mapped, and an elementary segment
    # starts at each selected row whose predecessor among the selected rows
    # lies in another run of some column
    intervals = segments = 0
    for sh in idx.shards:
        rows = sh.reconstruct_rows()
        runs = np.stack([_runs(rows[:, j]) for j in range(3)], axis=1)
        intervals += sum(len(np.unique(runs[:, j])) for j in range(3))
        sel = runs[rows[:, 2] <= 1]
        if len(sel):
            segments += 1 + int((sel[1:] != sel[:-1]).any(axis=1).sum())
    try:
        c0 = trace.counters()
        trace.enable()
        out = svc.statement(cube)
        trace.disable()
        c1 = trace.counters()
        svc.statement(empty)
        c2 = trace.counters()
    finally:
        svc.close()
    assert np.asarray(out["counts"]).sum() == int((table[:, 2] <= 1).sum())
    assert [c1.get(k, 0) - c0.get(k, 0) for k in names] == [
        4, intervals, segments]
    # an all-false filter runs the aggregate and sweeps nothing
    assert [c2.get(k, 0) - c1.get(k, 0) for k in names] == [4, 0, 0]
    spans = trace.spans()
    ids = _by_id(spans)
    sweeps = [s for s in spans if s.name == "executor.group_sweep"]
    assert len(sweeps) == 4
    for s in sweeps:
        assert ids[s.parent].name == "executor.aggregate"
        assert ids[ids[s.parent].parent].name == "shard.run"
    loads = [s for s in spans if s.name == "executor.load"
             and ids[s.parent].name == "executor.group_sweep"]
    assert loads  # the group bitmaps are read inside the sweep
