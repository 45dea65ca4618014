"""Distributed scatter/gather tier: wire protocol framing + CRC detection,
deterministic fault injection, coordinator robustness policy (replica
failover, hedged requests, eviction + re-placement, graceful degradation),
bit-identity with the single-process ``ShardedIndex``, rolling reload, and
the HTTP mounting of the cluster coordinator.

Workers here run as in-process ``WorkerServer`` threads over real TCP
sockets — the full wire path without subprocess startup cost (the
multi-process topology is exercised by ``benchmarks/bench_cluster.py`` and
the CI cluster smoke job via ``repro.launch.cluster``)."""
import socket
import time

import numpy as np
import pytest

from repro.core import BitmapIndex, ShardedIndex, col, lex_sort, synth
from repro.core import query as q
from repro.distributed import wire
from repro.distributed.cluster import (ClusterService, Policy,
                                       round_robin_placement)
from repro.serve.query_api import QueryService, expr_to_json
from repro.serve.worker_api import ShardWorker, WorkerServer

BACKEND = "ewah"  # deterministic + no jit warmup inside socket deadlines


# -- fixtures ---------------------------------------------------------------

@pytest.fixture(scope="module")
def store(tmp_path_factory):
    rng = np.random.default_rng(7)
    t = synth.uniform_table(4000, 3, r=2, rng=rng)
    table, _ = synth.factorize(t)
    table = table[lex_sort(table)]
    names = [f"dim{i}" for i in range(table.shape[1])]
    idx = ShardedIndex.build(table, shard_rows=640, k=2, column_names=names)
    d = str(tmp_path_factory.mktemp("cluster-store"))
    idx.save(d)
    return table, idx, d


@pytest.fixture()
def cluster(store):
    """3 worker servers + a started coordinator (no background monitor:
    tests drive probes explicitly, so there is no timing dependence)."""
    _table, _idx, d = store
    servers = [WorkerServer(ShardWorker(d, [], backend=BACKEND)).start()
               for _ in range(3)]
    svc = ClusterService(d, [s.address for s in servers], replication=2,
                         policy=Policy(deadline_s=5.0, retries=2,
                                       backoff_s=0.01, hedge_after_s=0.15),
                         backend=BACKEND)
    svc.start(monitor=False)
    yield servers, svc
    svc.close()
    for s in servers:
        s.stop()


EXPRS = [
    col("dim0") == 1,
    (col(0) == 1) & ~(col(1) == 2),
    ((col(0) == 0) | (col(2) == 3)) & (col(1) >= 1),
    col(2).isin([0, 2, 5]),
]


# -- wire protocol ----------------------------------------------------------

def test_wire_msg_roundtrip():
    obj = {"op": "gcount", "shards": [0, 2], "nested": {"a": [1, 2]}}
    arrays = {"g0": np.arange(7, dtype=np.int64),
              "w2": np.array([5, 0xFFFFFFFF], dtype=np.uint32),
              "empty": np.empty(0, dtype=np.int64)}
    out, arrs = wire.decode_msg(wire.encode_msg(obj, arrays))
    assert out == obj
    assert set(arrs) == set(arrays)
    for k in arrays:
        assert arrs[k].dtype == arrays[k].dtype
        np.testing.assert_array_equal(arrs[k], arrays[k])


def test_wire_decode_rejects_malformed():
    with pytest.raises(wire.WireError):
        wire.decode_msg(b"\x01")  # no JSON header
    with pytest.raises(wire.WireError):
        wire.decode_msg(b"\xff\xff\xff\xff{}")  # JSON overruns payload
    # array section shorter than its declared length
    payload = wire.encode_msg({"x": 1}, {"a": np.arange(8, dtype=np.int64)})
    with pytest.raises(wire.WireError):
        wire.decode_msg(payload[:-4])


def test_frame_roundtrip_and_corruption_detected():
    a, b = socket.socketpair()
    try:
        payload = wire.encode_msg({"hello": 1},
                                  {"v": np.arange(100, dtype=np.int64)})
        wire.send_frame(a, wire.KIND_RESP, payload)
        kind, got = wire.recv_frame(b, deadline=time.monotonic() + 5)
        assert kind == wire.KIND_RESP and got == payload

        # a corrupt-injected frame (byte flipped after the CRC) must raise,
        # never hand back a half-validated payload
        inj = wire.FaultInjector(seed=1, corrupt=1.0)
        assert wire.send_frame(a, wire.KIND_RESP, payload,
                               injector=inj) == "corrupt"
        with pytest.raises(wire.WireCorruptError):
            wire.recv_frame(b, deadline=time.monotonic() + 5)
    finally:
        a.close()
        b.close()


def test_frame_size_cap():
    a, b = socket.socketpair()
    try:
        wire.send_frame(a, wire.KIND_REQ, b"x" * 4096)
        with pytest.raises(wire.WireTooLargeError):
            wire.recv_frame(b, deadline=time.monotonic() + 5, max_bytes=100)
    finally:
        a.close()
        b.close()


def test_fault_injector_deterministic():
    cfg = dict(seed=42, drop=0.2, delay=0.2, corrupt=0.2, disconnect=0.1)
    seq1 = [wire.FaultInjector(**cfg).action() for _ in range(1)]  # warm
    i1, i2 = wire.FaultInjector(**cfg), wire.FaultInjector(**cfg)
    s1 = [i1.action() for _ in range(200)]
    s2 = [i2.action() for _ in range(200)]
    assert s1 == s2
    assert set(s1) > {None}  # some faults actually fired
    # round-trips through the remote-control config unchanged
    i3 = wire.FaultInjector.from_config(i1.to_config())
    assert i3.to_config() == i1.to_config()
    assert wire.FaultInjector.from_config(None) is None


# -- placement --------------------------------------------------------------

def test_round_robin_placement():
    p = round_robin_placement(6, 3, replication=2)
    assert all(len(r) == 2 and len(set(r)) == 2 for r in p)
    loads = [sum(1 for r in p if w in r) for w in range(3)]
    assert max(loads) - min(loads) <= 1  # balanced
    # replication clamps to the worker count; hot shards get one extra
    assert all(len(r) == 2 for r in round_robin_placement(4, 2, 5))
    hot = round_robin_placement(4, 3, 2, hot_shards=[1])
    assert len(hot[1]) == 3 and len(hot[0]) == 2


# -- bit-identity with the single-process index ------------------------------

def test_cluster_matches_mono(store, cluster):
    table, idx, _d = store
    _servers, svc = cluster
    mono = QueryService(idx, backend=BACKEND)
    for e in EXPRS:
        c = svc.count(e)
        assert c["exact"] and c["missing_shards"] == []
        assert c["covered_rows"] == idx.n_rows
        assert c["count"] == mono.count(e)["count"]
        g = svc.group_count("dim1", e)
        assert g["exact"]
        assert g["counts"] == mono.group_count("dim1", e)["counts"]
        t = svc.top_k("dim2", 3, e)
        assert t["top"] == mono.top_k("dim2", 3, e)["top"]
        r = svc.query(e)
        m = mono.query(e)
        assert r["count"] == m["count"] and r["rows"] == m["rows"]
        names = [f"dim{i}" for i in range(table.shape[1])]
        assert r["rows"] == q.naive_eval_rows(
            table, e, names)[:svc.max_rows].tolist()


def test_cluster_statement_and_cache(store, cluster):
    _table, idx, _d = store
    _servers, svc = cluster
    mono = QueryService(idx, backend=BACKEND)
    st = {"select": {"top_k": {"col": "dim2", "k": 4}},
          "where": expr_to_json(EXPRS[1])}
    assert svc.statement(st)["top"] == mono.statement(st)["top"]
    again = svc.statement(st)
    assert again["cached"] is True and again["exact"] is True
    svc.invalidate_cache()
    assert svc.statement(st)["cached"] is False


def test_coordinator_is_read_only(cluster):
    _servers, svc = cluster
    for call in (lambda: svc.ingest([[0, 0, 0]]),
                 lambda: svc.delete(EXPRS[0]),
                 lambda: svc.compact()):
        with pytest.raises(ValueError):
            call()


# -- chaos: crash, failover, re-placement, degradation -----------------------

def test_worker_crash_replica_failover(store, cluster):
    """Killing one worker leaves every query exact: replicas answer, and
    after eviction its shards are re-placed — no coordinator restart."""
    _table, idx, _d = store
    servers, svc = cluster
    ref = svc.count(EXPRS[2])["count"]
    servers[0].stop()  # hard crash
    svc.cache.clear()
    out = svc.count(EXPRS[2])
    assert out["count"] == ref and out["exact"]
    assert out["missing_shards"] == []
    # drive probes until the dead worker is evicted and shards re-placed
    for _ in range(svc.policy.fail_threshold + 1):
        svc.probe_all()
    stats = svc.stats()
    assert stats["workers"][0]["up"] is False
    assert stats["counters"]["evictions"] >= 1
    # every shard keeps >= 2 live replicas (re-placement restored r=2)
    live = {w for w in range(3) if stats["workers"][w]["up"]}
    for reps in stats["placement"]:
        assert len([w for w in reps if w in live]) >= 2
    svc.cache.clear()
    out = svc.count(EXPRS[2])
    assert out["count"] == ref and out["exact"]


def test_repair_is_level_triggered_not_eviction_edge(store):
    """A shard left under-replicated because no healthy candidate existed
    at eviction time is repaired on a later probe round, once a worker
    recovers.  Regression: repair used to run only on the eviction edge,
    so evicting B while A was still marked down stranded B-only shards
    under-replicated forever even after A came back."""
    _table, _idx, d = store
    servers = [WorkerServer(ShardWorker(d, [], backend=BACKEND)).start()
               for _ in range(3)]
    svc = ClusterService(d, [s.address for s in servers], replication=2,
                         policy=Policy(deadline_s=5.0, retries=2,
                                       backoff_s=0.01, fail_threshold=1),
                         backend=BACKEND)
    svc.start(monitor=False)
    try:
        # mark worker 0 down without killing it (probe failure via a fault
        # would not work: health ops bypass the injector — use the direct
        # path instead)
        svc._note_failure(0, "simulated outage")
        assert svc.stats()["workers"][0]["up"] is False
        # now worker 1 dies for real; at this instant only worker 2 is
        # healthy, so shards replicated on {0, 1} cannot reach r=2 yet
        servers[1].stop()
        for _ in range(2):
            svc.probe_all()
        stats = svc.stats()
        live = {w for w in range(3) if stats["workers"][w]["up"]}
        # worker 0 answered its probe: readmitted; worker 1 stays evicted
        assert live == {0, 2}
        # the probe round's repair pass restored full replication using
        # the recovered worker — including shards whose eviction-time
        # repair had no candidate
        for reps in stats["placement"]:
            assert len([w for w in reps if w in live]) >= 2
        svc.cache.clear()
        out = svc.count(EXPRS[0])
        assert out["exact"] and out["missing_shards"] == []
    finally:
        svc.close()
        servers[0].stop()
        servers[2].stop()


def test_all_replicas_down_degrades_structurally(store):
    """With no replicas left for some shards the query still answers:
    exact=False, the missing shards listed, coverage quantified — and the
    partial result is never cached."""
    _table, idx, d = store
    servers = [WorkerServer(ShardWorker(d, [], backend=BACKEND)).start()
               for _ in range(2)]
    # fail_threshold high: no eviction, so no re-placement can heal the
    # hole — this test wants the degraded path, not the failover path
    svc = ClusterService(d, [s.address for s in servers], replication=1,
                         policy=Policy(deadline_s=1.0, retries=1,
                                       backoff_s=0.01, fail_threshold=10 ** 6),
                         backend=BACKEND)
    svc.start(monitor=False)
    try:
        whole = svc.count(None)
        assert whole["exact"] and whole["count"] == idx.n_rows
        servers[0].stop()
        svc.cache.clear()
        out = svc.count(None)
        dead = [s for s, reps in enumerate(svc.placement) if reps == [0]]
        assert out["exact"] is False
        assert out["missing_shards"] == dead
        rows = np.diff(idx.offsets)
        assert out["covered_rows"] == idx.n_rows - sum(
            int(rows[s]) for s in dead)
        assert out["count"] == out["covered_rows"]  # count(None) == rows seen
        assert out["cached"] is False
        # degraded results are recomputed, not remembered
        assert svc.count(None)["cached"] is False
        g = svc.group_count("dim0", None)
        assert g["exact"] is False and g["missing_shards"] == dead
    finally:
        svc.close()
        servers[1].stop()


def test_corrupt_responses_detected_and_retried(store, cluster):
    """A worker whose responses get bit-flipped (CRC mismatch on the wire)
    never pollutes an answer — the coordinator retries elsewhere."""
    _table, idx, _d = store
    servers, svc = cluster
    ref = QueryService(idx, backend=BACKEND).count(EXPRS[1])["count"]
    servers[1].worker.fault = wire.FaultInjector(seed=3, corrupt=1.0)
    for _ in range(3):
        svc.cache.clear()
        out = svc.count(EXPRS[1])
        assert out["count"] == ref and out["exact"]
    assert svc.stats()["counters"]["failures"] >= 1
    assert servers[1].worker.fault.counts["corrupt"] >= 1


def test_slow_worker_hedged(store):
    """A worker delaying every data response past the hedge delay loses to
    the speculative request sent to its replica — exact answers at the
    backup's latency, no deadline misses."""
    _table, idx, d = store
    servers = [WorkerServer(ShardWorker(d, [], backend=BACKEND)).start()
               for _ in range(3)]
    svc = ClusterService(d, [s.address for s in servers], replication=2,
                         policy=Policy(deadline_s=5.0, retries=1,
                                       hedge_after_s=0.05, hedge_min_s=0.02),
                         backend=BACKEND)
    svc.start(monitor=False)
    try:
        ref = QueryService(idx, backend=BACKEND).count(EXPRS[0])["count"]
        servers[2].worker.fault = wire.FaultInjector(seed=5, delay=1.0,
                                                     delay_s=0.4)
        for _ in range(2):
            out = svc.count(EXPRS[0])
            assert out["count"] == ref and out["exact"]
            svc.cache.clear()
        counters = svc.stats()["counters"]
        assert counters["hedges"] >= 1
        assert counters["hedge_wins"] >= 1
    finally:
        svc.close()
        for s in servers:
            s.stop()


def test_remote_fault_control(cluster):
    """The coordinator can install and clear a seeded injector on a live
    worker — the chaos harness's remote control."""
    servers, svc = cluster
    out = svc.set_fault(1, {"seed": 9, "drop": 0.5})
    assert out["ok"] and servers[1].worker.fault.seed == 9
    out = svc.set_fault(1, None)
    assert out["ok"] and servers[1].worker.fault is None


# -- rolling reload ----------------------------------------------------------

def test_rolling_reload_refreshes_changed_shard(store, tmp_path):
    """Replacing one shard file on disk + reload_from_dir re-serves the new
    data; workers reopen only the changed file (fingerprint diff)."""
    rng = np.random.default_rng(11)
    t = synth.uniform_table(2000, 3, r=2, rng=rng)
    table, _ = synth.factorize(t)
    table = table[lex_sort(table)]
    idx = ShardedIndex.build(table, shard_rows=640, k=2,
                             column_names=["a", "b", "c"])
    d = str(tmp_path / "roll")
    idx.save(d)
    servers = [WorkerServer(ShardWorker(d, [], backend=BACKEND)).start()
               for _ in range(2)]
    svc = ClusterService(d, [s.address for s in servers], replication=2,
                         policy=Policy(deadline_s=5.0), backend=BACKEND)
    svc.start(monitor=False)
    try:
        e = col("a") == 0
        before = svc.count(e)["count"]
        # rewrite shard 1 with every row forced to a == 0: the count of
        # (a == 0) must grow by the shard's non-zero rows after reload
        lo, hi = int(idx.offsets[1]), int(idx.offsets[2])
        rows = table[lo:hi].copy()
        rows[:, 0] = 0
        new_shard = BitmapIndex.build(
            rows, k=2, column_names=["a", "b", "c"],
            cards=[idx.card(c) for c in range(3)])
        idx.replace_shard_file(d, 1, new_shard)
        out = svc.reload_from_dir()
        assert 1 in out["reloaded"]
        after = svc.count(e)
        assert after["exact"]
        assert after["count"] == QueryService(idx,
                                              backend=BACKEND).count(e)["count"]
        assert after["count"] != before  # the new data is actually served
        # a second reload is a no-op: fingerprints unchanged
        assert svc.reload_from_dir()["reloaded"] == []
    finally:
        svc.close()
        for s in servers:
            s.stop()


# -- worker surface ----------------------------------------------------------

def test_worker_assign_retire_missing(store):
    _table, idx, d = store
    w = ShardWorker(d, [0, 1], backend=BACKEND)
    out, _arrs = w.handle({"op": "count", "shards": [0, 1, 2],
                           "where": None}, {})
    assert sorted(map(int, out["parts"])) == [0, 1]
    assert out["missing"] == [2]  # unheld shard reported, not fabricated
    assert w.assign([2])["opened"] == [2]
    out, _arrs = w.handle({"op": "count", "shards": [2], "where": None}, {})
    assert out["missing"] == []
    assert w.retire([0])["retired"] == [0]
    assert sorted(w.shards) == [1, 2]
    with pytest.raises(ValueError):
        w.handle({"op": "frobnicate"}, {})
    rep = w.scrub()
    assert rep["ok"] and rep["n_corrupt_segments"] == 0


def test_worker_server_error_frame(store):
    _table, _idx, d = store
    srv = WorkerServer(ShardWorker(d, [0], backend=BACKEND)).start()
    try:
        sock = socket.create_connection((srv.host, srv.port), timeout=5)
        with pytest.raises(wire.WorkerError):
            wire.call(sock, {"op": "nope"}, deadline=time.monotonic() + 5)
        # the connection survives a bad request: next call still works
        out, _ = wire.call(sock, {"op": "health"},
                           deadline=time.monotonic() + 5)
        assert out["ok"]
        sock.close()
    finally:
        srv.stop()


# -- HTTP mounting -----------------------------------------------------------

def test_cluster_http_front_end(store, cluster):
    import json
    import urllib.error
    import urllib.request

    from repro.serve.query_api import serve_in_thread
    table, idx, _d = store
    _servers, svc = cluster
    srv, port = serve_in_thread(svc, max_body_bytes=64 << 10)
    try:
        base = f"http://127.0.0.1:{port}"

        def post(path, payload):
            req = urllib.request.Request(
                f"{base}{path}", data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req) as resp:
                return json.loads(resp.read())

        e = EXPRS[1]
        out = post("/query", {"select": {"count": True},
                              "where": expr_to_json(e)})
        assert out["count"] == QueryService(
            idx, backend=BACKEND).count(e)["count"]
        assert out["exact"] and out["missing_shards"] == []
        with urllib.request.urlopen(f"{base}/stats") as resp:
            stats = json.loads(resp.read())
        assert stats["n_shards"] == idx.n_shards
        assert len(stats["workers"]) == 3
        scrub = post("/admin/scrub", {})
        assert scrub["ok"] is True
        # read-only coordinator: mutation endpoints answer 400
        with pytest.raises(urllib.error.HTTPError) as err:
            post("/ingest", {"rows": [[0, 0, 0]]})
        assert err.value.code == 400
    finally:
        srv.shutdown()


@pytest.mark.parametrize("backend", ["auto", "kernel"])
def test_local_cluster_workers_are_host_only(tmp_path, backend):
    # several worker processes on one host cannot share its accelerator:
    # the request fails before any store is read or any worker spawned
    from repro.launch.cluster import LocalCluster
    with pytest.raises(ValueError, match="host-only"):
        LocalCluster(str(tmp_path / "no-store"), n_workers=2,
                     backend=backend)
