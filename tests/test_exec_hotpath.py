"""Execution hot path: bucketed kernels, cost model, shard-parallel, caches."""
import json
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from repro.core import BitmapIndex, ShardedIndex, lex_sort, synth
from repro.core import cost_model as cm
from repro.core.executor import Executor
from repro.core.statement import QueryBatch, execute
from repro.core.expr import col
from repro.core.lru import LRUCache
from repro.core.planner import explain, plan


@pytest.fixture(scope="module")
def sorted_table():
    rng = np.random.default_rng(11)
    table = synth.census_like_table(6000, rng)
    ranked, _ = synth.factorize(table)
    return ranked[lex_sort(ranked)]


# -- kernel bucketing -------------------------------------------------------

def test_bucket_cols_powers_of_two():
    from repro.kernels import ops as kops
    assert kops.bucket_cols(1) == 1024
    assert kops.bucket_cols(1024) == 1024
    assert kops.bucket_cols(1025) == 2048
    assert kops.bucket_cols(9000) == 16384
    assert kops.bucket_cols(16384) == 16384
    # buckets collapse the shape universe: everything in (1024, 2048] shares
    for c in (1030, 1500, 2047, 2048):
        assert kops.bucket_cols(c) == 2048


def test_logical_reduce_bucketed_matches_numpy():
    from repro.kernels import ops as kops
    rng = np.random.default_rng(0)
    for L in (1, 2, 3, 7, 12):
        for c in (33, 700, 1500):
            mat = rng.integers(0, 2**32, (L, c), dtype=np.uint32)
            for op, fn in (("and", np.bitwise_and), ("or", np.bitwise_or),
                           ("xor", np.bitwise_xor)):
                got = np.asarray(kops.logical_reduce(mat, op=op))
                assert got.shape == (c,)
                assert np.array_equal(got, fn.reduce(mat, axis=0)), (L, c, op)


def test_logical_reduce_with_cached_row_flags():
    from repro.kernels import ops as kops
    rng = np.random.default_rng(1)
    c = 2000
    cp = kops.bucket_cols(c)
    for L in (2, 3, 5, 16):  # small L: flags still used (rows pad inside)
        mat = rng.integers(0, 2**32, (L, cp), dtype=np.uint32)
        mat[:, c:] = 0          # bucket padding
        mat[L // 2] = 0         # a clean-zero operand row
        mat[L - 1] = 0xFFFFFFFF
        rf = kops.np_row_flags(mat)
        for op in ("and", "or", "xor"):
            plain = np.asarray(kops.logical_reduce(mat, op=op))
            flagged = np.asarray(kops.logical_reduce(mat, op=op, row_flags=rf))
            assert np.array_equal(plain, flagged), (L, op)


def test_logical_reduce_is_one_program_per_row_bucket():
    """Operand counts that pad to one power of two share one compiled
    program, and a call launches exactly one."""
    import jax
    from repro.core import trace
    from repro.kernels import ops as kops
    rng = np.random.default_rng(4)
    # a block width no other test uses, so the first call is the compile
    bc = 512
    compiles = []

    def on_duration(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        for L in (5, 7):  # both pad to 8 rows
            mat = rng.integers(0, 2**32, (L, 4 * bc), dtype=np.uint32)
            mat[0, :bc] = 0
            rf = kops.np_row_flags(mat, bc)
            p0 = trace.counters().get("kops.programs", 0)
            got = np.asarray(kops.logical_reduce(mat, op="or", block_cols=bc,
                                                 row_flags=rf))
            assert trace.counters()["kops.programs"] - p0 == 1
            assert np.array_equal(got, np.bitwise_or.reduce(mat, axis=0))
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert len(compiles) == 1


def test_np_row_flags_values():
    from repro.kernels import ops as kops
    from repro.kernels.word_logical import CLEAN0, CLEAN1, DIRTY
    w = np.zeros((3, 2048), np.uint32)
    w[1] = 0xFFFFFFFF
    w[2, 5] = 123
    f = kops.np_row_flags(w)
    assert f.shape == (3, 2)
    assert (f[0] == CLEAN0).all() and (f[1] == CLEAN1).all()
    assert f[2, 0] == DIRTY and f[2, 1] == CLEAN0


# -- cost model -------------------------------------------------------------

def test_cost_model_roundtrip(tmp_path):
    m = cm.CostModel(dense_threshold=0.33, calibrated=True, source="calibrated")
    p = m.save(tmp_path / "cost.json")
    loaded = cm.CostModel.load(p)
    assert loaded.dense_threshold == 0.33 and loaded.calibrated
    data = json.loads(p.read_text())
    assert data["dense_threshold"] == 0.33


def test_cost_model_env_path_and_executor_consumption(tmp_path, monkeypatch,
                                                      sorted_table):
    path = tmp_path / "cm.json"
    monkeypatch.setenv(cm.ENV_PATH, str(path))
    cm.CostModel(dense_threshold=0.123, calibrated=True).save(path)
    try:
        model = cm.get_default(refresh=True)
        assert model.dense_threshold == 0.123
        idx = BitmapIndex.build(sorted_table)
        assert Executor(idx).dense_threshold == 0.123
        # explicit override still wins
        assert Executor(idx, dense_threshold=0.9).dense_threshold == 0.9
        # planner reads the same model for its kernel hints
        node = plan(idx, col(0).isin((0, 1)) | col(1).isin((0, 1)))
        assert "w" in explain(node)
    finally:
        cm.set_default(None)  # do not leak into other tests


def test_calibrate_produces_monotone_samples():
    m = cm.calibrate(n_words=1 << 10, n_operands=4,
                     densities=(0.1, 0.8), repeats=1)
    assert m.calibrated and len(m.samples) == 2
    # either a measured crossover in (0, 1], or inf = "kernel never wins"
    assert 0 < m.dense_threshold <= 1.0 or m.dense_threshold == float("inf")
    for s in m.samples:
        assert s["ewah_us"] > 0 and s["kernel_us"] > 0
    # the sentinel round-trips through persistence (json Infinity)
    import tempfile, os
    p = m.save(os.path.join(tempfile.mkdtemp(), "cm.json"))
    assert cm.CostModel.load(p).dense_threshold == m.dense_threshold


# -- executor caches --------------------------------------------------------

def test_const_bitmap_memoized_in_operand_cache(sorted_table):
    idx = BitmapIndex.build(sorted_table)
    cache = {}
    ex = Executor(idx, cache=cache)
    e = col(0).isin(tuple(range(int(sorted_table[:, 0].max()) + 1)))  # -> ALL
    r1 = ex.run(plan(idx, e))
    key = ("const", idx.n_rows, True)
    assert key in cache
    first = cache[key]
    r2 = ex.run(plan(idx, e))
    assert cache[key] is first  # reused, not rebuilt
    assert r1 == r2 and r1.count() == idx.n_rows


def test_dense_operand_cache_holds_bucketed_words_and_flags(sorted_table):
    from repro.kernels import ops as kops
    idx = BitmapIndex.build(sorted_table)
    cache = {}
    ex = Executor(idx, backend="kernel", cache=cache)
    e = (col(0) == 1) & (col(1) == 2)
    out = ex.run(plan(idx, e))
    dense_keys = [k for k in cache if k[0] == "dense"]
    assert dense_keys, "kernel path must populate the dense operand cache"
    n_words = -(-idx.n_rows // 32)
    for k in dense_keys:
        w, f = cache[k]
        assert len(w) == k[-1] == kops.bucket_cols(n_words)
        assert f.shape == (len(w) // 1024,)
    ref = execute(idx, e, backend="ewah")
    assert out == ref


def test_kernel_result_densified_again_from_its_runlist(monkeypatch):
    """An unsorted table's fragmented bitmaps through the kernel backend:
    the same words as the EWAH backend, and an OR re-encoded from the
    kernel's words reaches its parent's kernel from its memoized run-list,
    with no second decode of its marker stream."""
    from repro.core import ewah as ewah_mod
    rng = np.random.default_rng(17)
    n = 40_000
    table = np.stack([rng.integers(0, 51, n), rng.integers(0, 11, n),
                      rng.integers(0, 4, n)], axis=1)
    idx = BitmapIndex.build(table, k=1)
    assert idx.bitmap(0, 3).size_words > (n // 32) // 2  # fragmented
    e = col(0).isin((3, 9, 17, 30)) & (col(1) <= 5) & (col(2) == 1)

    reencoded, densified, cold = [], [], []
    reencode, pad, decode = (Executor._reencode, Executor._pad_and_flags,
                             ewah_mod._decode_words)

    def spy_reencode(words, like):
        out = reencode(words, like)
        reencoded.append(out)
        return out

    def spy_pad(bm, cp):
        densified.append(bm)
        return pad(bm, cp)

    def spy_decode(words):
        cold.append(words)
        return decode(words)

    monkeypatch.setattr(Executor, "_reencode", staticmethod(spy_reencode))
    monkeypatch.setattr(Executor, "_pad_and_flags", staticmethod(spy_pad))
    monkeypatch.setattr(ewah_mod, "_decode_words", spy_decode)
    got = Executor(idx, backend="kernel").run(plan(idx, e))
    ref = execute(idx, e, backend="ewah")
    assert np.array_equal(got.words, ref.words)
    want = (np.isin(table[:, 0], (3, 9, 17, 30)) & (table[:, 1] <= 5)
            & (table[:, 2] == 1))
    assert got.count() == int(want.sum())

    fed = [bm for bm in densified if any(bm is r for r in reencoded)]
    assert fed, "no kernel result was an operand of a parent kernel"
    for bm in fed:
        assert bm._rl is not None
        assert not any(w is bm.words for w in cold)


# -- shard-parallel execution ----------------------------------------------

@pytest.fixture(scope="module")
def sharded(sorted_table):
    return ShardedIndex.build(sorted_table, shard_rows=1600, k=1)


def test_shard_parallel_matches_sequential(sharded, sorted_table):
    mono = BitmapIndex.build(sorted_table)
    exprs = [(col(0) == 1) & (col(1) <= 3),
             col(0).isin((0, 2)) | (col(2) == 1),
             ~(col(1) == 0) & (col(0) >= 1)]
    with ThreadPoolExecutor(max_workers=4) as pool:
        for e in exprs:
            seq = sharded.execute(e)
            par = sharded.execute(e, pool=pool)
            ref = execute(mono, e)
            assert np.array_equal(seq.to_bool(), ref.to_bool())
            assert seq == par
            assert np.array_equal(seq.words, par.words)


def test_shard_local_result_cache_hits_and_replace_invalidation(sorted_table):
    sh = ShardedIndex.build(sorted_table, shard_rows=1600, k=1)
    e = (col(0) == 1) & (col(1) <= 3)
    first = sh.execute(e)
    stats0 = sh.cache_stats()
    assert all(s["misses"] >= 1 for s in stats0)
    second = sh.execute(e)
    assert second == first
    stats1 = sh.cache_stats()
    assert all(s["hits"] >= 1 for s in stats1)
    # rebuild one shard: only that slice's cache drops
    rows = np.diff(sh.offsets)
    start = int(sh.offsets[1])
    cards = [sh.card(c) for c in range(sh.n_columns)]
    rebuilt = BitmapIndex.build(sorted_table[start:start + int(rows[1])],
                                cards=cards, k=1)
    sh.replace_shard(1, rebuilt)
    assert sh.cache_stats()[1]["entries"] == 0
    assert sh.cache_stats()[0]["entries"] >= 1
    third = sh.execute(e)
    assert third == first  # same data -> same result


def test_replace_shard_validates(sharded, sorted_table):
    bad = BitmapIndex.build(sorted_table[:, :2], k=1)  # wrong column count
    with pytest.raises(ValueError):
        sharded.replace_shard(0, bad)
    with pytest.raises(IndexError):
        sharded.replace_shard(99, sharded.shards[0])


def test_shard_process_pool_bit_identical():
    # fork-based pool in a fresh interpreter: forking after this test
    # process has imported jax (other test modules do) is not fork-safe
    import subprocess
    import sys
    code = """
import numpy as np
from repro.core import ShardedIndex, synth, lex_sort, col
from repro.core.shard import ShardProcessPool

rng = np.random.default_rng(5)
table, _ = synth.factorize(synth.census_like_table(20_000, rng))
table = table[lex_sort(table)]
sh = ShardedIndex.build(table, shard_rows=4992, k=1)
pool = ShardProcessPool(sh, workers=2)
try:
    for e in [(col(0) == 1) & (col(1) <= 3), col(2) >= 2, ~(col(0) == 0)]:
        seq = sh.execute(e, backend="ewah")
        par = sh.execute(e, backend="ewah", pool=pool)
        assert np.array_equal(seq.words, par.words)
        assert seq.n_bits == par.n_bits
    # generation bump (replace_shard) must re-fork, not serve stale shards
    cards = [sh.card(c) for c in range(sh.n_columns)]
    from repro.core import BitmapIndex
    start, stop = int(sh.offsets[1]), int(sh.offsets[2])
    sh.replace_shard(1, BitmapIndex.build(table[start:stop], cards=cards, k=1))
    e = col(1) <= 3
    assert np.array_equal(sh.execute(e, backend="ewah", pool=pool).words,
                          sh.execute(e, backend="ewah").words)
finally:
    pool.shutdown()
print("OK")
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300,
                         env={**__import__('os').environ,
                              "PYTHONPATH": "src"})
    assert out.returncode == 0, out.stderr
    assert "OK" in out.stdout


def test_query_batch_with_pool(sharded, sorted_table):
    mono = BitmapIndex.build(sorted_table)
    exprs = [col(0) == v for v in range(3)]
    with ThreadPoolExecutor(max_workers=4) as pool:
        outs = QueryBatch(exprs).execute(sharded, pool=pool)
    refs = QueryBatch(exprs).execute(mono)
    for o, r in zip(outs, refs):
        assert np.array_equal(o.to_bool(), r.to_bool())


# -- byte-budget LRU --------------------------------------------------------

def test_lru_byte_budget_eviction():
    c = LRUCache(capacity=100, max_bytes=100, sizeof=len)
    c.put("a", b"x" * 40)
    c.put("b", b"x" * 40)
    assert c.stats()["bytes"] == 80
    c.put("c", b"x" * 40)  # 120 bytes -> evict LRU ("a")
    assert c.get("a") is None
    assert c.get("b") is not None and c.get("c") is not None
    assert c.stats()["bytes"] == 80
    assert c.stats()["evictions"] == 1


def test_lru_oversized_entry_and_replacement():
    c = LRUCache(capacity=10, max_bytes=50, sizeof=len)
    c.put("big", b"x" * 500)   # larger than the whole budget
    assert c.get("big") is None
    c.put("k", b"x" * 30)
    c.put("k", b"x" * 10)      # replacement updates accounting
    assert c.stats()["bytes"] == 10
    assert len(c) == 1


def test_payload_nbytes_counts_a_memoized_runlist():
    """A kernel result (``from_words``) carries its run-list: the result
    caches' byte budget counts it beside the words."""
    from repro.core.ewah import EWAH
    from repro.core.lru import payload_nbytes
    rng = np.random.default_rng(3)
    words = np.where(rng.random(4096) < 0.5,
                     rng.integers(1, 1 << 31, 4096), 0).astype(np.uint32)
    memo = EWAH.from_words(words, 4096 * 32)
    bare = EWAH(memo.words, memo.n_bits)
    rl = memo._rl
    held = (rl.bounds.nbytes + rl.kinds.nbytes + rl.lit_starts.nbytes
            + rl.lits.nbytes)
    assert bare.memo_nbytes == 0 and payload_nbytes(bare) == bare.size_bytes
    assert memo.memo_nbytes == held > memo.size_bytes
    assert payload_nbytes(memo) == memo.size_bytes + held
    c = LRUCache(capacity=4, max_bytes=memo.size_bytes + held - 1,
                 sizeof=payload_nbytes)
    c.put("k", memo)  # over the budget once the run-list counts
    assert c.get("k") is None


def test_lru_disabled_and_unbounded():
    off = LRUCache(capacity=0)
    off.put("k", 1)
    assert off.get("k") is None
    unbounded = LRUCache()
    for i in range(1000):
        unbounded.put(i, i)
    assert len(unbounded) == 1000
