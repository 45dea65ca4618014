"""Durable store + spill-to-disk sort: round-trips, corruption, atomicity.

Covers the storage contract end to end: a saved index reopened with
``mmap=True`` answers every query bit-identically to the in-memory build;
truncated / bit-flipped / wrong-version files are rejected; a shard file is
replaced atomically under a concurrent reader; the spilled external sort
produces the exact ``lex_sort`` permutation with bounded buffering; and the
serving layer warm-starts and reloads from the store directory.
"""
import json
import os
import struct
import threading
import time

import numpy as np
import pytest

from repro.core import (BitmapIndex, IndexBuilder, ShardedIndex, SortStats,
                        col, execute, external_merge_sort_perm,
                        external_sorted_chunks, lex_sort, load, load_sharded,
                        save, save_sharded, synth, write_shard_file)
from repro.core.lru import LRUCache
from repro.core.store import (MAGIC, PAYLOAD_START, StoreCorruptError,
                              StoreError, StoreVersionError, _PREAMBLE,
                              scrub, scrub_sharded)
from repro.serve.query_api import QueryService, expr_to_json

NAMES = ["region", "day", "user"]


def make_table(n, seed=0):
    rng = np.random.default_rng(seed)
    ranked, uniq = synth.factorize(synth.census_like_table(n, rng))
    return ranked[lex_sort(ranked)], [len(u) for u in uniq]


def queries():
    return [
        col("region") == 1,
        (col("region") == 2) & col("day").between(0, 6),
        col("user").isin([0, 3, 7]) | ~(col("day") == 2),
        ~(col("region").isin([0, 1]) & (col("user") == 5)),
    ]


@pytest.fixture(scope="module")
def built():
    table, cards = make_table(12_000)
    idx = BitmapIndex.build(table, k=2, cards=cards, partition_rows=4096,
                            column_names=NAMES)
    return table, cards, idx


# ---------------------------------------------------------------------------
# Single-file store round trips.
# ---------------------------------------------------------------------------

def test_round_trip_bit_identity(built, tmp_path):
    table, cards, idx = built
    path = str(tmp_path / "idx.ridx")
    save(idx, path)
    mem = load(path, mmap=False)
    mm = load(path, mmap=True)
    for loaded in (mem, mm):
        assert loaded.n_rows == idx.n_rows
        assert loaded.size_words == idx.size_words
        assert loaded.column_names == NAMES
        assert np.array_equal(loaded.partition_bounds, idx.partition_bounds)
        for c in range(len(idx.columns)):
            for p in range(idx.n_partitions):
                for b, bm in enumerate(idx.columns[c].bitmaps[p]):
                    got = loaded.columns[c].bitmaps[p][b]
                    assert got.n_bits == bm.n_bits
                    assert np.array_equal(got.words, bm.words), (c, p, b)
    for e in queries():
        ref = execute(idx, e)
        assert execute(mem, e) == ref
        assert execute(mm, e) == ref


def test_mmap_load_is_zero_copy(built, tmp_path):
    _, _, idx = built
    path = str(tmp_path / "idx.ridx")
    save(idx, path)
    mm = load(path, mmap=True)
    bm = mm.columns[0].bitmaps[0][0]
    # the words array is a read-only view into the file mapping, not a copy
    chain = []
    base = bm.words
    while isinstance(base, np.ndarray):
        chain.append(base)
        base = base.base
    assert any(isinstance(a, np.memmap) for a in chain)
    assert not bm.words.flags.writeable
    with pytest.raises(ValueError):
        bm.words[0] = 1


def test_streaming_builder_store_path(built, tmp_path):
    table, cards, idx = built
    path = str(tmp_path / "streamed.ridx")
    builder = IndexBuilder(cards, k=2, partition_rows=4096,
                           column_names=NAMES, store_path=path)
    for chunk in external_sorted_chunks(table, 2048):
        builder.append(chunk)
    streamed = builder.finish()
    # nothing was retained in the builder's in-memory column structures
    assert all(len(c.bitmaps) == 0 for c in builder.columns)
    assert streamed.size_words == idx.size_words
    for e in queries():
        assert execute(streamed, e) == execute(idx, e)


def test_store_empty_index(tmp_path):
    # zero rows, still a valid durable index with full column metadata
    idx = IndexBuilder([4, 9], k=1, column_names=["a", "b"]).finish()
    path = str(tmp_path / "empty.ridx")
    save(idx, path)
    loaded = load(path, mmap=True)
    assert loaded.n_rows == 0
    assert loaded.n_partitions == 0
    assert loaded.column_names == ["a", "b"]
    assert [c.encoder.card for c in loaded.columns] == [4, 9]


def test_store_single_value_columns(tmp_path):
    # cardinality-1 columns produce all-ones bitmaps; round-trip exactly
    table = np.zeros((100, 2), dtype=np.int64)
    idx = BitmapIndex.build(table, k=1, cards=[1, 1])
    path = str(tmp_path / "ones.ridx")
    save(idx, path)
    loaded = load(path, mmap=True)
    assert loaded.equality_bitmap(0, 0).count() == 100
    assert loaded.size_words == idx.size_words


# ---------------------------------------------------------------------------
# Corruption / version rejection.
# ---------------------------------------------------------------------------

def _saved(built, tmp_path):
    _, _, idx = built
    path = str(tmp_path / "c.ridx")
    save(idx, path)
    return path


def test_truncated_file_rejected(built, tmp_path):
    path = _saved(built, tmp_path)
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(size - 16)
    with pytest.raises(StoreCorruptError):
        load(path, mmap=True)
    with pytest.raises(StoreCorruptError):
        load(path, mmap=False)
    with open(path, "r+b") as f:
        f.truncate(PAYLOAD_START // 2)  # shorter than the preamble
    with pytest.raises(StoreCorruptError):
        load(path)


def test_flipped_payload_byte_rejected(built, tmp_path):
    path = _saved(built, tmp_path)
    with open(path, "r+b") as f:
        f.seek(PAYLOAD_START + 5)
        byte = f.read(1)
        f.seek(PAYLOAD_START + 5)
        f.write(bytes([byte[0] ^ 0xFF]))
    with pytest.raises(StoreCorruptError):
        load(path, mmap=False)  # default verify=True on the in-memory path
    with pytest.raises(StoreCorruptError):
        load(path, mmap=True, verify=True)


def test_flipped_header_byte_rejected(built, tmp_path):
    path = _saved(built, tmp_path)
    with open(path, "rb") as f:
        _, _, _, hdr_off, _, _ = _PREAMBLE.unpack(f.read(_PREAMBLE.size))
    with open(path, "r+b") as f:
        f.seek(hdr_off + 3)
        byte = f.read(1)
        f.seek(hdr_off + 3)
        f.write(bytes([byte[0] ^ 0xFF]))
    # header CRC is always checked, even on the trusting mmap path
    with pytest.raises(StoreCorruptError):
        load(path, mmap=True)


def test_version_mismatch_rejected(built, tmp_path):
    path = _saved(built, tmp_path)
    with open(path, "r+b") as f:
        f.seek(len(MAGIC))
        f.write(struct.pack("<I", 99))
    with pytest.raises(StoreVersionError):
        load(path)
    with open(path, "r+b") as f:
        f.seek(0)
        f.write(b"NOTANIDX")
    with pytest.raises(StoreVersionError):
        load(path)


# ---------------------------------------------------------------------------
# Sharded layout: manifest round trip + atomic replacement under a reader.
# ---------------------------------------------------------------------------

@pytest.fixture()
def sharded_dir(built, tmp_path):
    table, cards, _ = built
    sh = ShardedIndex.build(table, shard_rows=4096, k=2, cards=cards,
                            column_names=NAMES)
    d = str(tmp_path / "shards")
    sh.save(d)
    return table, cards, sh, d


def test_sharded_round_trip(sharded_dir):
    table, cards, sh, d = sharded_dir
    for mmap in (True, False):
        loaded = ShardedIndex.load(d, mmap=mmap)
        assert loaded.n_shards == sh.n_shards
        assert loaded.column_names == NAMES
        assert np.array_equal(loaded.offsets, sh.offsets)
        for e in queries():
            assert loaded.execute(e) == sh.execute(e)


def test_sharded_missing_manifest(tmp_path):
    with pytest.raises(StoreError):
        load_sharded(str(tmp_path / "nowhere"))


def test_write_shard_file_requires_manifest(built, tmp_path):
    _, _, idx = built
    with pytest.raises(StoreError):
        write_shard_file(str(tmp_path), 0, idx)


def test_atomic_replace_under_concurrent_reader(sharded_dir):
    """Readers loading mid-swap must always see a whole, valid store file.

    A writer thread flips shard 0 between two valid contents via the atomic
    write-temp + rename path while readers continuously reopen the
    directory; every load must succeed (a torn file would fail checksum or
    bounds validation) and answer with one of the two legal results.
    """
    table, cards, sh, d = sharded_dir
    rows = table[:4096].copy()
    variant = rows.copy()
    variant[:, 0] = 0
    shard_a = sh.shards[0]
    shard_b = IndexBuilder(cards, k=2, column_names=NAMES) \
        .append(variant).finish()
    e = col("region") == 0
    legal = set()
    for first in (shard_a, shard_b):
        probe = ShardedIndex.load(d)
        probe.replace_shard(0, first)
        legal.add(probe.execute(e).count())
    assert len(legal) == 2  # the two variants are distinguishable

    stop = threading.Event()
    errors = []

    def writer():
        i = 0
        while not stop.is_set():
            write_shard_file(d, 0, shard_b if i % 2 == 0 else shard_a)
            i += 1

    t = threading.Thread(target=writer)
    t.start()
    try:
        deadline = time.monotonic() + 2.0
        loads = 0
        while time.monotonic() < deadline:
            try:
                idx = ShardedIndex.load(d, mmap=True)
                count = idx.execute(e).count()
                assert count in legal, count
                loads += 1
            except Exception as exc:  # noqa: BLE001 - recorded for the assert
                errors.append(exc)
                break
    finally:
        stop.set()
        t.join()
    assert not errors, errors
    assert loads > 0


# ---------------------------------------------------------------------------
# Spill-to-disk external sort.
# ---------------------------------------------------------------------------

def test_spill_sort_matches_lex_sort(tmp_path):
    table, _ = make_table(9_000, seed=3)
    rng = np.random.default_rng(7)
    shuffled = table[rng.permutation(len(table))]
    stats = SortStats()
    perm = external_merge_sort_perm(shuffled, 1024,
                                    spill_dir=str(tmp_path / "runs"),
                                    stats=stats)
    assert np.array_equal(perm, lex_sort(shuffled))
    assert stats.n_runs == -(-len(table) // 1024)
    assert stats.spilled_bytes == len(table) * 16  # uint64 key + int64 perm
    assert len(stats.run_files) == 2 * stats.n_runs
    for f in stats.run_files:
        assert os.path.exists(f)


def test_spill_sort_ties_and_col_order(tmp_path):
    rng = np.random.default_rng(5)
    # heavy ties: tiny cardinalities so runs overlap a lot
    table = rng.integers(0, 3, size=(5000, 3))
    for order in (None, [2, 0, 1]):
        perm_mem = external_merge_sort_perm(table, 512, col_order=order)
        perm_disk = external_merge_sort_perm(
            table, 512, col_order=order,
            spill_dir=str(tmp_path / f"o{order is None}"))
        assert np.array_equal(perm_mem, perm_disk)
        assert np.array_equal(perm_disk, lex_sort(table, order))


def test_spill_chunks_stream_off_runs(tmp_path):
    table, _ = make_table(7_000, seed=9)
    rng = np.random.default_rng(1)
    shuffled = table[rng.permutation(len(table))]
    got = list(external_sorted_chunks(shuffled, 1000, out_rows=1500,
                                      spill_dir=str(tmp_path / "runs")))
    assert [len(c) for c in got[:-1]] == [1500] * (len(got) - 1)
    assert np.array_equal(np.concatenate(got), shuffled[lex_sort(shuffled)])


def test_spill_merge_window_bounds_buffering(tmp_path):
    table, _ = make_table(8_000, seed=2)
    stats = SortStats()
    external_merge_sort_perm(table, 1000, spill_dir=str(tmp_path / "runs"),
                             merge_block_rows=128, stats=stats)
    assert stats.merge_block_rows == 128
    # merge-phase windows: n_runs * block keys + one yielded block
    budget = stats.n_runs * 128 * 8 + 128 * 8
    run_budget = 1000 * 16  # run-generation buffers: chunk keys + perm
    assert stats.peak_buffer_bytes <= max(budget, run_budget)


def test_spill_handles_unpackable_keys(tmp_path):
    # key space >= 2^64: the run files spill the raw key *columns* and the
    # merge compares rows lexicographically — identical permutation to the
    # in-memory sort (this used to raise; wide keys forced in-memory runs)
    rng = np.random.default_rng(9)
    table = rng.integers(0, 1 << 40, size=(400, 3), dtype=np.int64)
    table[::7] = table[0]  # duplicate rows: tie order must stay stable
    perm = external_merge_sort_perm(table, 60, spill_dir=str(tmp_path / "r"))
    assert np.array_equal(perm, lex_sort(table))
    assert any(f.endswith(".keys") for f in os.listdir(tmp_path / "r"))
    got = list(external_sorted_chunks(table, 60, out_rows=128,
                                      spill_dir=str(tmp_path / "r2")))
    assert np.array_equal(np.concatenate(got), table[lex_sort(table)])


def test_spill_multipass_merge_matches_flat(tmp_path):
    table, _ = make_table(9_000, seed=3)
    rng = np.random.default_rng(7)
    shuffled = table[rng.permutation(len(table))]
    flat = external_merge_sort_perm(shuffled, 1024,
                                    spill_dir=str(tmp_path / "flat"))
    stats = SortStats()
    multi = external_merge_sort_perm(shuffled, 1024,
                                     spill_dir=str(tmp_path / "multi"),
                                     merge_fan_in=2, stats=stats)
    # reduction passes change the file plan, never the permutation
    assert np.array_equal(multi, flat)
    assert stats.merge_passes >= 2              # 9 runs at fan-in 2
    assert stats.n_runs == -(-len(table) // 1024)  # reports INITIAL runs
    # the streaming-chunks front end honours the fan-in too
    got = np.concatenate(list(external_sorted_chunks(
        shuffled, 1000, out_rows=1500, spill_dir=str(tmp_path / "c"),
        merge_fan_in=3)))
    assert np.array_equal(got, shuffled[flat])


def test_merge_fan_in_resolution():
    from repro.core.sorting import _AUTO_MULTIPASS_RUNS, _resolve_fan_in
    # default: flat single-pass merge below the runaway backstop
    assert _resolve_fan_in(None, 1024, 128, 9) is None
    assert _resolve_fan_in(None, 1024, 128, _AUTO_MULTIPASS_RUNS + 1) == 8
    assert _resolve_fan_in("auto", 1024, 128, 9) == 8
    assert _resolve_fan_in(4, 1024, 128, 9) == 4
    with pytest.raises(ValueError):
        _resolve_fan_in(1, 1024, 128, 9)


def test_spill_small_table_no_spill(tmp_path):
    # n <= chunk_rows: sorts in memory, no run files written
    table = np.random.default_rng(0).integers(0, 5, size=(50, 2))
    d = tmp_path / "unused"
    perm = external_merge_sort_perm(table, 100, spill_dir=str(d))
    assert np.array_equal(perm, lex_sort(table))
    assert not d.exists()


# ---------------------------------------------------------------------------
# TTL cache + warm-start serving.
# ---------------------------------------------------------------------------

def test_lru_ttl_lazy_expiry():
    now = [0.0]
    c = LRUCache(capacity=8, ttl=1.0, clock=lambda: now[0])
    c.put("a", 1)
    assert c.get("a") == 1
    now[0] = 0.9
    assert c.get("a") == 1
    now[0] = 2.0
    assert c.get("a") is None  # expired lazily on lookup
    st = c.stats()
    assert st["expired"] == 1 and st["misses"] == 1 and st["hits"] == 2
    assert st["entries"] == 0 and st["bytes"] == 0
    # re-put restarts the clock
    c.put("a", 2)
    now[0] = 2.5
    assert c.get("a") == 2


def test_lru_ttl_disabled_by_default():
    c = LRUCache(capacity=2)
    c.put("a", 1)
    assert c.get("a") == 1
    assert c.stats()["ttl"] is None and c.stats()["expired"] == 0


def test_service_cache_ttl(sharded_dir, monkeypatch):
    _, _, _, d = sharded_dir
    svc = QueryService.from_dir(d, cache_ttl=30.0)
    try:
        now = [0.0]
        monkeypatch.setattr(svc.cache, "_clock", lambda: now[0])
        q = {"op": "eq", "col": "region", "value": 1}
        assert not svc.query(q)["cached"]
        assert svc.query(q)["cached"]
        now[0] = 31.0
        assert not svc.query(q)["cached"]
        st = svc.stats()["cache"]
        assert st["expired"] == 1 and st["ttl"] == 30.0
    finally:
        svc.close()


def test_service_warm_start_and_reload(sharded_dir):
    table, cards, sh, d = sharded_dir
    svc = QueryService.from_dir(d)
    try:
        q = {"op": "and", "args": [
            {"op": "eq", "col": "region", "value": 1},
            {"op": "range", "col": "day", "lo": 0, "hi": 6}]}
        ref = svc.query(q)
        # bit-identical to serving the in-memory index
        mem_svc = QueryService(sh)
        assert mem_svc.query(q)["rows"] == ref["rows"]
        mem_svc.close()

        # no change on disk -> no shard swapped
        assert svc.reload_from_dir() == {"reloaded": [], "full": False,
                                         "n_shards": sh.n_shards}

        # out-of-band reindex of shard 0, then reload picks up exactly it
        variant = table[:4096].copy()
        variant[:, 0] = 0
        new_shard = IndexBuilder(cards, k=2, column_names=NAMES) \
            .append(variant).finish()
        write_shard_file(d, 0, new_shard)
        out = svc.reload_from_dir()
        assert out["reloaded"] == [0] and not out["full"]
        assert svc.query({"op": "eq", "col": "region", "value": 0})["count"] \
            >= 4096
    finally:
        svc.close()


def test_service_kernel_backend_serves_store_on_threads(sharded_dir):
    """A store-backed service whose backend reaches the kernels fans out
    on threads (one process holds the device) instead of forking workers
    that would raise ForkSafetyError, and answers as the EWAH path does."""
    from concurrent.futures import ThreadPoolExecutor
    from repro.core.executor import Executor
    _table, _cards, _sh, d = sharded_dir
    kern = QueryService.from_dir(d, backend="kernel")
    host = QueryService.from_dir(d, backend="ewah", shard_processes=0)
    try:
        assert isinstance(kern._shard_pool, ThreadPoolExecutor)
        before = Executor.kernel_dispatches
        for e in queries():
            got, want = kern.query(expr_to_json(e)), host.query(
                expr_to_json(e))
            assert got["count"] == want["count"]
            assert got["rows"] == want["rows"]
        assert Executor.kernel_dispatches > before
        q = expr_to_json(col("day") <= 3)
        assert kern.group_count("region", q)["counts"] == \
            host.group_count("region", q)["counts"]
    finally:
        kern.close()
        host.close()


@pytest.mark.parametrize("backend", ["auto", "kernel"])
def test_service_refuses_shard_processes_with_kernel_backend(sharded_dir,
                                                             backend):
    _table, _cards, _sh, d = sharded_dir
    with pytest.raises(ValueError, match="backend='ewah'"):
        QueryService.from_dir(d, backend=backend, shard_processes=2)


def test_service_watcher_picks_up_shard_swap(sharded_dir):
    """The --watch-interval poller: an out-of-band shard-file replacement is
    swapped in with no /admin/reload call, and the *sibling* shards'
    local result caches stay warm across the swap."""
    import time
    table, cards, sh, d = sharded_dir
    svc = QueryService.from_dir(d)
    try:
        e = (col("region") == 1) & (col("day") != 2)
        svc.query(expr_to_json(e))  # prime every shard-local LRU
        warm = [c["entries"] for c in svc.index.cache_stats()]
        assert all(n > 0 for n in warm)
        gen0 = svc.index.generation

        variant = table[:4096].copy()
        variant[:, 0] = 0
        new_shard = IndexBuilder(cards, k=2, column_names=NAMES) \
            .append(variant).finish()
        write_shard_file(d, 0, new_shard)

        svc.start_watcher(interval=0.05)
        deadline = time.monotonic() + 15
        while svc.index.generation == gen0 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert svc.index.generation > gen0, "watcher never reloaded"
        after = [c["entries"] for c in svc.index.cache_stats()]
        assert after[0] == 0                      # swapped shard: cold
        assert after[1:] == warm[1:]              # siblings: still warm
        # the served answer reflects the replaced shard immediately
        assert svc.query({"op": "eq", "col": "region",
                          "value": 0})["count"] >= 4096
        # idempotent + stoppable
        svc.start_watcher(interval=0.05)
        svc.stop_watcher()
        assert svc._watcher is None
    finally:
        svc.close()


def test_service_check_reload_noop_when_current(sharded_dir):
    _, _, _, d = sharded_dir
    svc = QueryService.from_dir(d)
    try:
        assert svc.check_reload() is None  # nothing changed: cheap no-op
    finally:
        svc.close()


def test_service_replace_shard_persists_to_dir(sharded_dir):
    """A dir-backed service's ``replace_shard`` must write the shard file
    first (atomically): the directory is what mmap pool workers re-open and
    what a restart serves, so memory and disk may never diverge."""
    table, cards, _, d = sharded_dir
    svc = QueryService.from_dir(d)
    try:
        variant = table[:4096].copy()
        variant[:, 0] = 0
        new_shard = IndexBuilder(cards, k=2, column_names=NAMES) \
            .append(variant).finish()
        svc.replace_shard(0, new_shard)
        live = svc.query({"op": "eq", "col": "region", "value": 0})["count"]
        # a cold open of the directory answers identically to the live index
        reopened = ShardedIndex.load(d, mmap=True)
        assert reopened.execute(col("region") == 0).count() == live >= 4096
        # and reload sees nothing stale to swap
        assert svc.reload_from_dir()["reloaded"] == []
    finally:
        svc.close()


def test_replace_shard_file_validates_before_writing(sharded_dir):
    """A shard the live index would reject must never reach the directory."""
    _, _, sh, d = sharded_dir
    bad = BitmapIndex.build(np.zeros((4096, 2), dtype=np.int64),
                            k=1, cards=[1, 1])  # wrong column count
    before = os.path.getmtime(os.path.join(d, "shard-00000.ridx"))
    with pytest.raises(ValueError):
        sh.replace_shard_file(d, 0, bad)
    assert os.path.getmtime(os.path.join(d, "shard-00000.ridx")) == before
    assert ShardedIndex.load(d).n_shards == sh.n_shards  # dir still valid


def test_service_reload_requires_dir(built):
    _, _, idx = built
    svc = QueryService(idx)
    try:
        with pytest.raises(ValueError):
            svc.reload_from_dir()
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# scrub: explicit full-CRC audit, usable while the file is mmap-served.
# ---------------------------------------------------------------------------

def test_scrub_clean_file(built, tmp_path):
    path = _saved(built, tmp_path)
    rep = scrub(path)
    assert rep["ok"] is True
    assert rep["corrupt"] == []
    assert rep["n_segments"] > 0


def test_scrub_reports_corruption_not_fatal(built, tmp_path):
    path = _saved(built, tmp_path)
    with open(path, "r+b") as f:
        f.seek(PAYLOAD_START + 5)
        byte = f.read(1)
        f.seek(PAYLOAD_START + 5)
        f.write(bytes([byte[0] ^ 0xFF]))
    # the trusting mmap open still succeeds (header intact) — scrub is the
    # audit that catches what zero-copy loading deliberately skips
    idx = load(path, mmap=True)
    rep = scrub(path)  # runs fine alongside the live mmap handle
    assert rep["ok"] is False
    assert len(rep["corrupt"]) >= 1
    bad = rep["corrupt"][0]
    assert bad["reason"] == "checksum mismatch"
    assert {"col", "partition", "bitmap", "offset", "n_words"} <= set(bad)
    assert idx.n_rows > 0  # the serving handle was not disturbed


def test_scrub_unreadable_file_is_an_error_entry(tmp_path):
    rep = scrub(str(tmp_path / "nope.ridx"))
    assert rep["ok"] is False and "error" in rep
    bad = tmp_path / "junk.ridx"
    bad.write_bytes(b"garbage that is not a store file at all")
    rep = scrub(str(bad))
    assert rep["ok"] is False and "error" in rep


def test_scrub_sharded_isolates_the_bad_shard(sharded_dir):
    _table, _cards, sh, d = sharded_dir
    rep = scrub_sharded(d)
    assert rep["ok"] is True and rep["n_shards"] == sh.n_shards
    assert rep["n_corrupt_segments"] == 0
    victim = os.path.join(d, rep["shards"][1]["file"])
    with open(victim, "r+b") as f:
        f.seek(PAYLOAD_START + 9)
        byte = f.read(1)
        f.seek(PAYLOAD_START + 9)
        f.write(bytes([byte[0] ^ 0xFF]))
    rep = scrub_sharded(d)
    assert rep["ok"] is False
    assert rep["n_corrupt_segments"] >= 1
    # corruption is attributed to shard 1 only; siblings stay clean
    assert rep["shards"][1]["ok"] is False
    assert all(s["ok"] for i, s in enumerate(rep["shards"]) if i != 1)


def test_scrub_http_endpoint(sharded_dir):
    import json
    import urllib.request

    from repro.serve.query_api import serve_in_thread
    _table, _cards, _sh, d = sharded_dir
    svc = QueryService.from_dir(d)
    srv, port = serve_in_thread(svc)
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/admin/scrub", data=b"{}")
        with urllib.request.urlopen(req) as resp:
            out = json.loads(resp.read())
        assert out["ok"] is True and out["n_shards"] == _sh.n_shards
    finally:
        srv.shutdown()
        svc.close()


# ---------------------------------------------------------------------------
# v4 measure sidecar: round trip, corruption rejection, version discipline.
# ---------------------------------------------------------------------------

def _measured_index(n=6000, seed=5):
    from repro.core.dataset import _attach_measures
    table, cards = make_table(n, seed)
    rng = np.random.default_rng(seed)
    sales = rng.integers(0, 10_000, len(table)).astype(np.int64)
    price = rng.random(len(table)) * 9.5
    idx = BitmapIndex.build(table, k=2, cards=cards, partition_rows=2048,
                            column_names=NAMES)
    _attach_measures(idx, {"sales": sales, "price": price})
    return table, idx, sales, price


def test_measure_sidecar_round_trip(tmp_path):
    from repro.core.store import VERSION_MEASURES, _PREAMBLE as PRE
    table, idx, sales, price = _measured_index()
    path = str(tmp_path / "m.ridx")
    save(idx, path)
    with open(path, "rb") as f:
        _, version, *_ = PRE.unpack(f.read(PRE.size))
    assert version == VERSION_MEASURES
    for mmap_mode in (True, False):
        re = load(path, mmap=mmap_mode)
        assert sorted(re.measure_names) == ["price", "sales"]
        assert np.array_equal(np.asarray(re.measure("sales")), sales)
        assert np.array_equal(np.asarray(re.measure("price")), price)
    # mmap'd sidecar views are zero-copy and read-only
    arr = load(path, mmap=True).measure("sales")
    assert isinstance(arr, np.memmap) or not arr.flags.writeable


def test_measure_free_build_stays_pre_v4(tmp_path):
    from repro.core.store import VERSION_MEASURES, _PREAMBLE as PRE
    table, cards = make_table(3000, 2)
    idx = BitmapIndex.build(table, k=2, cards=cards, column_names=NAMES)
    path = str(tmp_path / "plain.ridx")
    save(idx, path)
    with open(path, "rb") as f:
        _, version, _, off, ln, _ = PRE.unpack(f.read(PRE.size))
        f.seek(off)
        meta = json.loads(f.read(ln).decode())
    assert version < VERSION_MEASURES
    assert "measures" not in meta
    # and saving the same index twice is byte-identical (deterministic)
    path2 = str(tmp_path / "plain2.ridx")
    save(idx, path2)
    with open(path, "rb") as a, open(path2, "rb") as b:
        assert a.read() == b.read()


def _rewrite_header(path, mutate):
    """Re-JSON the header with ``mutate`` applied and a *valid* CRC, so the
    corruption under test is the semantic cross-check, not the checksum."""
    import zlib

    from repro.core.store import _PREAMBLE as PRE
    with open(path, "r+b") as f:
        magic, version, flags, off, ln, _ = PRE.unpack(f.read(PRE.size))
        f.seek(off)
        meta = json.loads(f.read(ln).decode())
        mutate(meta)
        hdr = json.dumps(meta).encode()
        f.seek(off)
        f.write(hdr)
        f.truncate(off + len(hdr))
        f.seek(0)
        f.write(PRE.pack(magic, version, flags, off, len(hdr),
                         zlib.crc32(hdr) & 0xFFFFFFFF))


def test_measure_row_count_mismatch_rejected(tmp_path):
    """Satellite: a v4 file whose measure TOC row count disagrees with the
    bitmap row count must be refused, not silently mis-sliced."""
    _table, idx, _sales, _price = _measured_index()
    path = str(tmp_path / "bad.ridx")
    save(idx, path)

    def shrink_partition(meta):
        meta["measures"]["sales"]["toc"][0][1] -= 1

    _rewrite_header(path, shrink_partition)
    with pytest.raises(StoreCorruptError, match="sidecar disagrees"):
        load(path, mmap=True)

    save(idx, path)

    def drop_partition(meta):
        meta["measures"]["sales"]["toc"].pop()

    _rewrite_header(path, drop_partition)
    with pytest.raises(StoreCorruptError):
        load(path, mmap=True)


def test_measure_payload_corruption_detected(tmp_path):
    _table, idx, _sales, _price = _measured_index()
    path = str(tmp_path / "flip.ridx")
    save(idx, path)
    # flip a byte inside the sidecar (after every bitmap segment): the
    # verifying load refuses it and scrub attributes it to the measure
    size = os.path.getsize(path)
    from repro.core.store import _PREAMBLE as PRE
    with open(path, "r+b") as f:
        _, _, _, hdr_off, _, _ = PRE.unpack(f.read(PRE.size))
        f.seek(hdr_off - 16)  # sidecar is the tail of the payload
        byte = f.read(1)
        f.seek(hdr_off - 16)
        f.write(bytes([byte[0] ^ 0xFF]))
    with pytest.raises(StoreCorruptError):
        load(path, mmap=False)
    rep = scrub(path)
    assert rep["ok"] is False
    assert any("measure" in c for c in rep["corrupt"])


def test_sharded_measure_round_trip_and_scrub(tmp_path):
    from repro.core.dataset import _attach_measures
    table, cards = make_table(8000, 4)
    rng = np.random.default_rng(4)
    sales = rng.integers(0, 500, len(table)).astype(np.int64)
    sh = ShardedIndex.build(table, shard_rows=2048, k=2, cards=cards,
                            column_names=NAMES)
    _attach_measures(sh, {"sales": sales})
    d = str(tmp_path / "mshards")
    sh.save(d)
    re = load_sharded(d)
    got = np.concatenate([np.asarray(s.measure("sales")) for s in re.shards])
    assert np.array_equal(got, sales)
    assert scrub_sharded(d)["ok"] is True
