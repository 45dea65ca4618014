"""Live ingest subsystem: WAL framing and crash replay, delta indexes,
compressed tombstones, base+delta+tombstone query equivalence against a
NumPy row oracle, compaction, and concurrent HTTP mutation."""
import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.core import expr as E
from repro.core import store, wal as walmod
from repro.core.dataset import Dataset
from repro.core.expr import col
from repro.core.ingest import Compactor, DeltaIndex, LiveIndex
from repro.core.shard import ShardedIndex

CARDS = [7, 5, 9]
NAMES = ["region", "day", "user"]


def make_table(n, rng, cards=CARDS):
    return np.stack([rng.integers(0, c, n) for c in cards], axis=1)


def make_base(n=600, shard_rows=256, seed=0, sort=True):
    rng = np.random.default_rng(seed)
    t = make_table(n, rng)
    if sort:
        t = t[np.lexsort(t.T[::-1])]
    return t, ShardedIndex.build(t, shard_rows=shard_rows, cards=CARDS,
                                 column_names=NAMES)


class Oracle:
    """Plain NumPy rows + alive mask, mutated in lockstep with a LiveIndex.

    Deletes snapshot the rows that exist *at delete time* — later appends
    matching the same predicate stay alive, exactly like the tombstones.
    """

    def __init__(self, table):
        self.rows = np.array(table, copy=True)
        self.alive = np.ones(len(table), dtype=bool)

    def append(self, rows):
        self.rows = np.concatenate([self.rows, rows])
        self.alive = np.concatenate(
            [self.alive, np.ones(len(rows), dtype=bool)])

    def delete(self, pred):
        self.alive &= ~pred(self.rows)

    def count(self, pred=None):
        m = self.alive if pred is None else self.alive & pred(self.rows)
        return int(m.sum())

    def group(self, c, pred=None, card=None):
        m = self.alive if pred is None else self.alive & pred(self.rows)
        return np.bincount(self.rows[m][:, c], minlength=card)


# -- DeltaIndex ---------------------------------------------------------------

def test_delta_index_incremental_matches_batch():
    rng = np.random.default_rng(1)
    rows = make_table(1000, rng)
    d = DeltaIndex(CARDS, column_names=NAMES, partition_rows=256)
    for s in range(0, len(rows), 137):          # ragged arrival chunks
        d.append(rows[s:s + 137])
    assert d.n_rows == len(rows)
    assert np.array_equal(d.rows(), rows)
    idx = d.index()
    # sealed partitions + recompiled tail answer like a one-shot build
    from repro.core.statement import execute, run
    e = (col(0) == 3) | (col(1) == 1)
    want = (rows[:, 0] == 3) | (rows[:, 1] == 1)
    assert execute(idx, e).count() == int(want.sum())
    assert np.array_equal(
        run(idx, ("gcount", 2, e)),
        np.bincount(rows[want][:, 2], minlength=CARDS[2]))
    # the compiled view is memoized per version, invalidated by append
    assert d.index() is idx
    d.append(rows[:50])
    assert d.index() is not idx
    assert d.index().n_rows == len(rows) + 50


def test_delta_index_rejects_bad_shapes():
    d = DeltaIndex(CARDS)
    with pytest.raises(ValueError):
        d.append(np.zeros((4, 2), dtype=np.int64))
    with pytest.raises(ValueError):
        d.append(np.zeros(4, dtype=np.int64))


# -- WAL framing --------------------------------------------------------------

def test_wal_roundtrip_and_replay(tmp_path):
    path = str(tmp_path / "w.log")
    rows = make_table(40, np.random.default_rng(2))
    e = (col("region") == 2) & ~(col(1) == 3)
    with walmod.WAL(path) as w:
        w.log_epoch(0)
        w.log_append(rows)
        w.log_delete(e)
    frames, valid = walmod.replay(path)
    assert valid == os.path.getsize(path)
    decoded = [walmod.decode_frame(k, p) for k, p in frames]
    assert decoded[0] == ("epoch", 0)
    assert decoded[1][0] == "append"
    assert np.array_equal(decoded[1][1], rows)
    assert decoded[2] == ("delete", e)


def test_wal_torn_tail_is_truncated(tmp_path):
    path = str(tmp_path / "w.log")
    rows = make_table(40, np.random.default_rng(3))
    with walmod.WAL(path) as w:
        w.log_epoch(0)
        w.log_append(rows)
        w.log_append(rows)
    size = os.path.getsize(path)
    # tear the last frame mid-payload (crash during write)
    with open(path, "r+b") as f:
        f.truncate(size - 100)
    frames, valid = walmod.replay(path)
    assert len(frames) == 2 and valid < size - 100 + 1
    # a corrupt (bit-flipped) tail frame is dropped the same way
    with open(path, "r+b") as f:
        f.seek(valid - 7)
        b = f.read(1)
        f.seek(valid - 7)
        f.write(bytes([b[0] ^ 0x40]))
    frames2, valid2 = walmod.replay(path)
    assert len(frames2) == 1 and valid2 < valid
    # reopening as a WAL truncates to the valid prefix and appends cleanly
    with walmod.WAL(path) as w:
        assert w.n_frames == 1
        w.log_append(rows)
    assert len(walmod.replay(path)[0]) == 2


# -- crash recovery (acceptance: replay to the exact pre-crash state) ---------

def test_live_index_replays_bit_identically_after_crash(tmp_path):
    d = str(tmp_path / "idx")
    rng = np.random.default_rng(4)
    table, base = make_base(seed=4)
    store.save_sharded(base, d, meta={"cards": CARDS, "k": 1,
                                      "allocation": "alpha"})
    live = LiveIndex(store.load_sharded(d), dir_path=d, sync=False)
    live.append(make_table(90, rng))
    live.delete(col("day") == 2)
    live.append(make_table(33, rng))
    live.delete((col(0) == 1) | (col(2) == 4))
    probe = (col("region") == 3) | ~(col("user") == 0)
    want_bm = live.execute(probe)
    want_n = live.count(probe)
    want_g = live.group_count("day", probe)
    # crash: no close/flush beyond the per-frame writes; just reopen
    recovered = LiveIndex(store.load_sharded(d), dir_path=d, sync=False)
    assert recovered.n_rows == live.n_rows
    assert recovered.execute(probe) == want_bm          # bit-identical
    assert recovered.count(probe) == want_n
    assert np.array_equal(recovered.group_count("day", probe), want_g)
    live.close()
    recovered.close()


def test_live_index_torn_tail_replays_valid_prefix(tmp_path):
    d = str(tmp_path / "idx")
    rng = np.random.default_rng(5)
    table, base = make_base(seed=5)
    store.save_sharded(base, d, meta={"cards": CARDS})
    wal_path = os.path.join(d, "wal-00000.log")

    live = LiveIndex(store.load_sharded(d), dir_path=d, sync=False)
    live.append(make_table(64, rng))
    live.delete(col(1) == 1)
    cut = os.path.getsize(wal_path)  # end of the acknowledged prefix
    live.append(make_table(32, rng))  # the frame the crash will tear
    live.close()
    with open(wal_path, "r+b") as f:
        f.truncate(cut + 11)  # mid-header of the torn frame

    # reference: a service that never saw the torn frame at all
    ref = LiveIndex(store.load_sharded(d),
                    wal_path=str(tmp_path / "ref.log"), sync=False)
    ref.append(walmod.decode_frame(*walmod.replay(wal_path)[0][1])[1])
    ref.delete(col(1) == 1)

    recovered = LiveIndex(store.load_sharded(d), dir_path=d, sync=False)
    probe = (col(0) == 2) | (col(2) == 5)
    assert recovered.n_rows == ref.n_rows
    assert recovered.execute(probe) == ref.execute(probe)
    assert np.array_equal(recovered.group_count(2, probe),
                          ref.group_count(2, probe))
    # the torn bytes are gone: appending next reuses the truncated offset
    assert recovered.wal.n_frames == 3
    recovered.close()
    ref.close()


def test_live_index_rejects_stale_wal(tmp_path):
    d = str(tmp_path / "idx")
    _, base = make_base(seed=6)
    store.save_sharded(base, d, meta={"cards": CARDS, "epoch": 3})
    with walmod.WAL(os.path.join(d, "wal-00003.log")) as w:
        w.log_epoch(1)  # from another epoch entirely
    with pytest.raises(walmod.WALError):
        LiveIndex(store.load_sharded(d), dir_path=d)


# -- property test: (base ⊔ delta) AND NOT tombstones vs row oracle ----------

def test_live_index_matches_numpy_oracle():
    rng = np.random.default_rng(7)
    table, base = make_base(n=800, seed=7)
    live = LiveIndex(base)  # in-memory: no WAL needed for the algebra
    oracle = Oracle(table)
    preds = [
        (col(0) == 3, lambda r: r[:, 0] == 3),
        ((col(1) == 1) | (col(2) == 6), lambda r: (r[:, 1] == 1) | (r[:, 2] == 6)),
        (~(col(0) == 2), lambda r: r[:, 0] != 2),
        (col("day").between(1, 3) & (col(0) == 5),
         lambda r: (r[:, 1] >= 1) & (r[:, 1] <= 3) & (r[:, 0] == 5)),
    ]
    for step in range(24):
        op = rng.integers(0, 3)
        if op == 0:
            rows = make_table(int(rng.integers(1, 120)), rng)
            live.append(rows)
            oracle.append(rows)
        elif op == 1:
            e, p = preds[int(rng.integers(0, len(preds)))]
            assert live.delete(e) == oracle.count(p)
            oracle.delete(p)
        else:
            e, p = preds[int(rng.integers(0, len(preds)))]
            assert live.count(e) == oracle.count(p)
        # full sweep every few steps: execute + count + group_count
        if step % 6 == 5:
            assert live.count() == oracle.count()
            for c in range(3):
                assert np.array_equal(
                    live.group_count(c),
                    oracle.group(c, card=CARDS[c]))
            for e, p in preds:
                assert live.execute(e).count() == oracle.count(p)
                assert np.array_equal(
                    live.group_count(2, e),
                    oracle.group(2, p, card=CARDS[2]))


# -- compaction ---------------------------------------------------------------

def test_compaction_equals_from_scratch_build(tmp_path):
    d = str(tmp_path / "idx")
    rng = np.random.default_rng(8)
    ds = Dataset.from_rows(make_table(2000, rng), NAMES, sort="lex",
                           shards=2, cards=CARDS)
    ds.save(d)
    ds = Dataset.open(d, live=True)
    ds.append(make_table(100, rng))
    ds.delete(col("day") == 3)
    n_before = ds.n_rows
    info = ds.compact()
    live = ds.index
    assert info["epoch"] == 1 and live.pending_rows == 0
    assert live.delta.n_rows == 0 and live.tombstone_rows == 0
    assert live.n_rows == live.base.n_rows == n_before
    assert info["reapplied_frames"] == 0

    # the compacted store holds exactly the surviving rows
    survivors = _reconstruct_rows(d)
    assert len(survivors) == n_before
    assert not (survivors[:, 1] == 3).any()

    # size parity: compacted store within 5% of a from-scratch sorted build
    scratch = Dataset.from_rows(survivors, NAMES, sort=ds.sort_order,
                                shards=2, cards=CARDS)
    assert abs(live.base.size_words - scratch.size_words) \
        <= max(0.05 * scratch.size_words, 8)

    # query parity post-compaction
    e = (col(0) == 4) | (col(2) == 2)
    want = int(((survivors[:, 0] == 4) | (survivors[:, 2] == 2)).sum())
    assert ds.query().where(e).count() == want
    ds.index.close()

    # the store reopens at the new epoch with an empty WAL
    meta = store.manifest_meta(d)
    assert meta["epoch"] == 1 and meta["wal"] == "wal-00001.log"
    ds2 = Dataset.open(d)
    assert ds2.n_rows == n_before
    assert ds2.query().where(e).count() == want
    ds2.index.close()


def _reconstruct_rows(dir_path):
    """Row multiset of a store directory via the per-shard interval scatter."""
    idx = ShardedIndex.load(dir_path, mmap=False)
    return np.concatenate([sh.reconstruct_rows() for sh in idx.shards])


def test_compaction_drops_old_epoch_files(tmp_path):
    d = str(tmp_path / "idx")
    rng = np.random.default_rng(9)
    ds = Dataset.from_rows(make_table(700, rng), NAMES, sort="lex",
                           shards=2, cards=CARDS)
    ds.save(d)
    ds = Dataset.open(d, live=True)
    ds.append(make_table(64, rng))
    ds.compact()
    ds.append(make_table(32, rng))
    ds.compact()
    ds.index.close()
    names = sorted(os.listdir(d))
    assert names == ["e00002-shard-00000.ridx", "e00002-shard-00001.ridx",
                     "manifest.json", "wal-00002.log"]


def test_compactor_thread_drains_debt(tmp_path):
    rng = np.random.default_rng(10)
    _, base = make_base(seed=10)
    live = LiveIndex(base, wal_path=str(tmp_path / "w.log"), sync=False)
    live.append(make_table(50, rng))
    comp = Compactor(live, interval=0.02, min_pending_rows=10)
    fired = threading.Event()
    comp.on_compact = lambda info: fired.set()
    comp.start()
    try:
        assert fired.wait(10.0)
        assert live.pending_rows == 0 and live.compactions >= 1
        assert comp.stats()["runs"] >= 1
        assert comp.stats()["last_error"] is None
        # below threshold: no further compaction
        live.append(make_table(3, rng))
        assert comp.maybe_compact() is None
    finally:
        comp.stop()
        live.close()


# -- serving: concurrent HTTP ingest/delete during queries --------------------

@pytest.fixture()
def live_server(tmp_path):
    rng = np.random.default_rng(11)
    from repro.serve.query_api import QueryService, serve_in_thread
    d = str(tmp_path / "idx")
    table = make_table(3000, rng)
    Dataset.from_rows(table, NAMES, sort="lex", shards=2,
                      cards=CARDS).save(d)
    svc = QueryService.from_dir(d, live=True, cache_ttl=None)
    srv, port = serve_in_thread(svc)
    yield table, svc, f"http://127.0.0.1:{port}"
    srv.shutdown()
    svc.close()


def _post(base, path, obj):
    req = urllib.request.Request(base + path, data=json.dumps(obj).encode(),
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req) as r:
        return json.loads(r.read())


def test_http_ingest_delete_compact(live_server):
    table, svc, base = live_server
    rng = np.random.default_rng(12)
    extra = make_table(128, rng)
    out = _post(base, "/ingest", {"rows": extra.tolist()})
    assert out["ok"] and out["appended"] == 128
    out = _post(base, "/delete",
                {"where": {"op": "eq", "col": "day", "value": 1}})
    full = np.concatenate([table, extra])
    alive = full[:, 1] != 1
    assert out["removed"] == int((~alive).sum())
    q = {"select": {"count": True},
         "where": {"op": "eq", "col": "region", "value": 2}}
    want = int(((full[:, 0] == 2) & alive).sum())
    assert _post(base, "/query", q)["count"] == want
    # stats exposes the live layer
    with urllib.request.urlopen(base + "/stats") as r:
        stats = json.loads(r.read())
    assert stats["live"]["delta_rows"] == 128
    assert stats["live"]["tombstone_rows"] == out["removed"]
    # compact over HTTP, then the same statement still answers identically
    cp = _post(base, "/admin/compact", {})
    assert cp["ok"] and cp["epoch"] == 1
    assert _post(base, "/query", q)["count"] == want
    with urllib.request.urlopen(base + "/stats") as r:
        stats = json.loads(r.read())
    assert stats["live"]["delta_rows"] == 0
    assert stats["live"]["epoch"] == 1
    # malformed mutations are 400s, not crashes
    for path, body in (("/ingest", {}), ("/ingest", {"rows": [[1, 2]]}),
                       ("/delete", {}), ("/delete", {"where": {"op": "x"}})):
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(base, path, body)
        assert ei.value.code == 400


def test_http_concurrent_mutations_during_queries(live_server):
    table, svc, base = live_server
    stop = threading.Event()
    errors = []

    def ingester():
        rng = np.random.default_rng(13)
        while not stop.is_set():
            try:
                _post(base, "/ingest",
                      {"rows": make_table(16, rng).tolist()})
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)
                return

    def deleter():
        v = 0
        while not stop.is_set():
            try:
                _post(base, "/delete", {"where": {
                    "op": "and", "args": [
                        {"op": "eq", "col": "user", "value": v % CARDS[2]},
                        {"op": "eq", "col": "day", "value": v % CARDS[1]}]}})
                v += 1
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)
                return

    threads = [threading.Thread(target=ingester),
               threading.Thread(target=deleter)]
    for t in threads:
        t.start()
    try:
        # queries keep answering consistently while mutations land:
        # count(A) + count(NOT A) == count(*) must hold on every snapshot
        a = {"op": "eq", "col": "region", "value": 3}
        for _ in range(40):
            na = _post(base, "/query", {"select": {"count": True},
                                        "where": {"op": "not", "arg": a}})
            ca = _post(base, "/query", {"select": {"count": True},
                                        "where": a})
            total = _post(base, "/query", {"select": {"count": True}})
            # mutations may land between the three statements; the live row
            # count only moves by whole batches, so re-check coarsely:
            assert ca["count"] >= 0 and na["count"] >= 0
            assert total["count"] > 0
        # quiesce, then the invariant must hold exactly
        stop.set()
        for t in threads:
            t.join(30)
        assert not errors
        na = _post(base, "/query", {"select": {"count": True},
                                    "where": {"op": "not", "arg": a}})["count"]
        ca = _post(base, "/query", {"select": {"count": True},
                                    "where": a})["count"]
        total = _post(base, "/query", {"select": {"count": True}})["count"]
        assert ca + na == total
        gc = _post(base, "/query", {"select": {"group_count": "region"}})
        assert sum(gc["counts"]) == total
    finally:
        stop.set()
        for t in threads:
            t.join(30)


# -- compaction concurrent with mutations (WAL tail re-application) ----------

def test_compact_reapplies_wal_tail(tmp_path):
    """Mutations framed after the compaction snapshot survive the swap."""
    d = str(tmp_path / "idx")
    rng = np.random.default_rng(14)
    table = make_table(1200, rng)
    Dataset.from_rows(table, NAMES, sort="lex", shards=2,
                      cards=CARDS).save(d)
    live = Dataset.open(d, live=True).index
    pre = make_table(40, rng)
    live.append(pre)

    mid_rows = make_table(24, rng)
    barrier = threading.Barrier(2)

    def racer():
        barrier.wait()
        live.append(mid_rows)           # may land while compact() rebuilds
        live.delete(col("day") == 4)

    t = threading.Thread(target=racer)
    t.start()
    barrier.wait()
    info = live.compact()
    t.join(30)

    # the new-epoch WAL holds exactly the post-snapshot frames, and they
    # were re-applied onto the new base at swap time
    history = [walmod.decode_frame(k, p)
               for k, p in walmod.replay(live.wal.path)[0]]
    assert history[0] == ("epoch", 1)
    assert info["reapplied_frames"] == len(history) - 1

    # end state is interleaving-independent: the racer's append
    # happens-before its delete, so the delete saw every row
    allr = np.concatenate([table, pre, mid_rows])
    alive = allr[:, 1] != 4
    assert live.n_rows == int(alive.sum())
    assert np.array_equal(live.group_count("day"),
                          np.bincount(allr[alive][:, 1],
                                      minlength=CARDS[1]))
    probe = col(0) == 2
    assert live.count(probe) == int(((allr[:, 0] == 2) & alive).sum())
    # the recovered-from-disk view agrees bit for bit
    reopened = Dataset.open(d).index
    assert isinstance(reopened, LiveIndex)
    assert reopened.execute(probe) == live.execute(probe)
    reopened.close()
    live.close()


# -- Dataset façade -----------------------------------------------------------

def test_dataset_live_facade(tmp_path):
    rng = np.random.default_rng(15)
    table = make_table(900, rng)
    ds = Dataset.from_rows(table, NAMES, sort="lex", shards=2, cards=CARDS)
    d = str(tmp_path / "idx")
    ds.save(d)
    ds = Dataset.open(d)
    assert not isinstance(ds.index, LiveIndex)   # read-only until mutated
    extra = make_table(60, rng)
    assert ds.append(extra) == 60
    assert isinstance(ds.index, LiveIndex)
    removed = ds.delete(col("region") == 1)
    full = np.concatenate([table, extra])
    alive = full[:, 0] != 1
    assert removed == int((~alive).sum())
    assert ds.n_rows == int(alive.sum())
    # pending mutations block save/shard until compaction
    with pytest.raises(RuntimeError):
        ds.save(str(tmp_path / "other"))
    with pytest.raises(RuntimeError):
        ds.shard(3)
    ds.compact()
    re = ds.shard(3)
    assert re.n_shards == 3 and re.n_rows == int(alive.sum())
    want = int(((full[:, 2] == 4) & alive).sum())
    assert re.query().where(col("user") == 4).count() == want
    assert ds.query().where(col("user") == 4).count() == want
    ds.index.close()
    # a fresh open sees the compacted state and stays live (WAL present)
    ds2 = Dataset.open(d)
    assert isinstance(ds2.index, LiveIndex)
    assert ds2.query().where(col("user") == 4).count() == want
    ds2.index.close()


# -- durability knob ----------------------------------------------------------

def test_wal_fsync_knob(tmp_path, monkeypatch):
    """``fsync`` gates the per-frame ``os.fsync``; default stays off (page-
    cache flush only) and the legacy ``sync=`` alias wins when given."""
    calls = []
    real_fsync = os.fsync
    monkeypatch.setattr(os, "fsync", lambda fd: (calls.append(fd),
                                                 real_fsync(fd))[1])
    p = str(tmp_path / "durable.log")
    w = walmod.WAL(p)
    assert w.sync is False
    w.log_epoch(1)
    w.log_append(np.zeros((2, 3), dtype=np.int64))
    assert calls == []  # throughput mode: no disk barrier per append
    w.close()

    w = walmod.WAL(p, fsync=True)
    assert w.sync is True
    n0 = len(calls)
    w.log_append(np.ones((1, 3), dtype=np.int64))
    w.log_delete(col(0) == 1)
    assert len(calls) == n0 + 2  # one barrier per acknowledged frame
    w.close()

    # both modes replay identically
    frames, _ = walmod.replay(p)
    assert [k for k, _ in frames] == [walmod.KIND_EPOCH, walmod.KIND_APPEND,
                                      walmod.KIND_APPEND, walmod.KIND_DELETE]

    # alias compatibility: explicit sync= wins over fsync=
    assert walmod.WAL(p, fsync=True, sync=False).sync is False
    assert walmod.WAL(p, sync=True).sync is True


def test_live_index_fsync_plumbs_through(tmp_path):
    _, base = make_base(seed=21)
    live = LiveIndex(base, wal_path=str(tmp_path / "w.log"), fsync=True)
    assert live.sync is True and live.wal.sync is True
    live.close()
    live = LiveIndex(base, wal_path=str(tmp_path / "w2.log"))
    assert live.sync is False and live.wal.sync is False
    live.close()


# -- compaction error path ----------------------------------------------------

def _store_backed_live(tmp_path, seed=22):
    rng = np.random.default_rng(seed)
    d = str(tmp_path / "cidx")
    table, base = make_base(seed=seed)
    store.save_sharded(base, d, meta={"cards": CARDS, "k": 1,
                                      "allocation": "alpha"})
    live = LiveIndex(store.load_sharded(d), dir_path=d, sync=False)
    live.append(make_table(80, rng))
    live.delete(col("day") == 1)
    return d, live, rng


def test_failed_compaction_leaves_state_untouched(tmp_path, monkeypatch):
    """An injected store-write failure mid-compaction must not move the
    manifest, the WAL, or any serving result — and the next compact()
    (store healed) succeeds from exactly that state."""
    d, live, rng = _store_backed_live(tmp_path)
    probe = (col("region") == 3) | ~(col("user") == 0)
    want_n = live.count(probe)
    want_g = live.group_count("day", probe)
    want_rows = live.n_rows
    with open(os.path.join(d, store.MANIFEST_NAME), "rb") as f:
        manifest_before = f.read()
    wal_path, wal_frames = live.wal.path, live.wal.n_frames
    epoch_before = live.epoch

    def boom(*a, **kw):
        raise OSError("disk full (injected)")

    monkeypatch.setattr(store, "save_sharded", boom)
    with pytest.raises(OSError, match="injected"):
        live.compact()

    # the old stack is still the live truth, bit for bit
    assert live.epoch == epoch_before
    assert live.wal.path == wal_path and live.wal.n_frames == wal_frames
    with open(os.path.join(d, store.MANIFEST_NAME), "rb") as f:
        assert f.read() == manifest_before
    assert live.count(probe) == want_n
    assert np.array_equal(live.group_count("day", probe), want_g)
    # the half-built next-epoch WAL was retired: a crashed attempt leaves
    # no file a retry (or a warm start) could double-replay
    assert not [n for n in os.listdir(d)
                if n.startswith("wal-") and
                os.path.join(d, n) != wal_path]
    # mutations keep landing against the old stack
    live.append(make_table(5, rng))
    assert live.n_rows == want_rows + 5

    # heal the store: the retry compacts the accumulated state
    monkeypatch.undo()
    info = live.compact()
    assert info["epoch"] == epoch_before + 1
    assert live.count(probe) == live.count(probe)  # serving still coherent
    recovered = LiveIndex(store.load_sharded(d), dir_path=d, sync=False)
    assert recovered.n_rows == live.n_rows
    assert recovered.count(probe) == live.count(probe)
    live.close()
    recovered.close()


def test_compactor_records_error_and_retries(tmp_path, monkeypatch):
    """The background compactor survives a failing compact(): the error is
    surfaced via stats(), the thread stays alive, and the next cycle
    retries and drains the debt once the fault clears."""
    d, live, _rng = _store_backed_live(tmp_path, seed=23)
    fail = {"on": True}
    real = store.save_sharded

    def flaky(*a, **kw):
        if fail["on"]:
            raise OSError("injected store failure")
        return real(*a, **kw)

    monkeypatch.setattr(store, "save_sharded", flaky)
    comp = Compactor(live, interval=0.02, min_pending_rows=1)
    fired = threading.Event()
    comp.on_compact = lambda info: fired.set()
    comp.start()
    try:
        deadline = time.monotonic() + 10
        while comp.stats()["last_error"] is None:
            assert time.monotonic() < deadline, "error never surfaced"
            time.sleep(0.01)
        st = comp.stats()
        assert "injected store failure" in st["last_error"]
        assert st["alive"] and st["runs"] == 0
        assert live.compactions == 0  # nothing half-applied
        fail["on"] = False
        assert fired.wait(10.0), "retry never succeeded"
        assert live.compactions >= 1 and live.pending_rows == 0
    finally:
        comp.stop()
        live.close()
