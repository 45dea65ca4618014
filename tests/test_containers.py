"""Adaptive hybrid containers: bit-identity vs the run-list oracle.

Every container-path operation must produce results *bit-identical* to the
plain EWAH run-list implementation (the oracle that predates containers and
stays in place): the container layer is a physical encoding choice, never a
semantic one.  The property tests push random and adversarial bit
distributions — shuffled (high-entropy positions, the paper's unsorted fact
table), alternating (the EWAH worst case: no word-aligned runs), clustered
(sorted-table-like runs, the case that must *collapse back* to plain
run-list) — through every binary / n-ary op pair and the store round trip.
"""
import os

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import containers as C
from repro.core.containers import (CHUNK_BITS, Containers, T_ARRAY, T_DENSE,
                                   T_EMPTY, T_FULL, T_RUN,
                                   containers_from_positions,
                                   containers_to_runlist, runlist_to_containers,
                                   worthwhile)
from repro.core.cost_model import CostModel, calibrate_containers
from repro.core.ewah import EWAH, and_many, binary_op, or_many
from repro.core.expr import col
from repro.core.index import IndexBuilder
from repro.core.shard import (ForkSafetyError, ShardedIndex, ShardProcessPool,
                              _guard_backend)
from repro.core import store as index_store

N_BITS = 3 * CHUNK_BITS + 12345  # >3 chunks with a ragged bit-padded tail


# -- position generators: the distributions under test -----------------------
def _shuffled(rng, n_bits, frac):
    n = max(1, int(n_bits * frac))
    return np.unique(rng.integers(0, n_bits, n))


def _alternating(rng, n_bits, stride):
    start = int(rng.integers(0, stride))
    return np.arange(start, n_bits, stride, dtype=np.int64)


def _clustered(rng, n_bits, n_runs):
    pieces = []
    for _ in range(n_runs):
        s = int(rng.integers(0, n_bits))
        e = min(n_bits, s + int(rng.integers(1, n_bits // max(n_runs, 1) + 2)))
        pieces.append(np.arange(s, e, dtype=np.int64))
    return np.unique(np.concatenate(pieces)) if pieces \
        else np.array([], np.int64)


def _positions(rng, n_bits, flavor):
    if flavor == "empty":
        return np.array([], dtype=np.int64)
    if flavor == "full":
        return np.arange(n_bits, dtype=np.int64)
    if flavor == "sparse":
        return _shuffled(rng, n_bits, 0.0005)
    if flavor == "mid":
        return _shuffled(rng, n_bits, 0.05)
    if flavor == "dense":
        return _shuffled(rng, n_bits, 0.6)
    if flavor == "alternating":
        return _alternating(rng, n_bits, int(rng.integers(2, 5)))
    if flavor == "clustered":
        return _clustered(rng, n_bits, int(rng.integers(1, 8)))
    raise AssertionError(flavor)


FLAVORS = ["empty", "full", "sparse", "mid", "dense", "alternating",
           "clustered"]


def _pair(a_flavor, b_flavor, seed, n_bits=N_BITS):
    rng = np.random.default_rng(seed)
    pa = _positions(rng, n_bits, a_flavor)
    pb = _positions(rng, n_bits, b_flavor)
    a = EWAH.from_positions(pa, n_bits)           # plain run-list oracle
    b = EWAH.from_positions(pb, n_bits)
    ca = EWAH.from_positions(pa, n_bits)
    cb = EWAH.from_positions(pb, n_bits)
    ca.to_containers(force=True)
    cb.to_containers(force=True)
    return a, b, ca, cb


# -- binary ops: every container-type pairing vs the oracle ------------------
@settings(max_examples=30, deadline=None)
@given(st.sampled_from(FLAVORS), st.sampled_from(FLAVORS),
       st.sampled_from(["and", "or", "xor", "andnot"]),
       st.integers(0, 10_000))
def test_binary_matches_oracle(fa, fb, op, seed):
    a, b, ca, cb = _pair(fa, fb, seed)
    want = binary_op(a, b, op)
    for lhs, rhs in ((ca, cb), (ca, b), (a, cb)):  # cont x cont / mixed
        got = binary_op(lhs, rhs, op)
        assert got == want
        # bit-identity of the *encoding*, not just the bits: lazy word
        # emission must reproduce the oracle's canonical EWAH stream
        assert np.array_equal(got.words, want.words)


@settings(max_examples=15, deadline=None)
@given(st.sampled_from(["and", "or"]), st.integers(0, 10_000),
       st.integers(2, 5))
def test_nary_matches_oracle(op, seed, k):
    rng = np.random.default_rng(seed)
    flavors = [FLAVORS[int(rng.integers(0, len(FLAVORS)))] for _ in range(k)]
    plains, conts = [], []
    for i, f in enumerate(flavors):
        p = _positions(rng, N_BITS, f)
        plains.append(EWAH.from_positions(p, N_BITS))
        c = EWAH.from_positions(p, N_BITS)
        if i % 2 == 0:  # mixed operand lists promote the rest on the fly
            c.to_containers(force=True)
        conts.append(c)
    fn = and_many if op == "and" else or_many
    want, got = fn(plains), fn(conts)
    assert got == want
    assert np.array_equal(got.words, want.words)


@settings(max_examples=20, deadline=None)
@given(st.sampled_from(FLAVORS), st.sampled_from(FLAVORS),
       st.integers(0, 10_000))
def test_and_count_matches_oracle(fa, fb, seed):
    a, b, ca, cb = _pair(fa, fb, seed)
    want = binary_op(a, b, "and").count()
    assert ca.and_count(cb) == want
    assert ca.and_count(b) == want
    assert a.and_count(cb) == want


@settings(max_examples=20, deadline=None)
@given(st.sampled_from(FLAVORS), st.integers(0, 10_000))
def test_count_and_set_bits_match(flavor, seed):
    rng = np.random.default_rng(seed)
    pos = _positions(rng, N_BITS, flavor)
    plain = EWAH.from_positions(pos, N_BITS)
    cont = EWAH.from_positions(pos, N_BITS)
    cont.to_containers(force=True)
    assert cont.count() == plain.count() == len(pos)
    assert np.array_equal(cont.set_bits(), pos)
    assert np.array_equal(cont.to_words(), plain.to_words())


# -- conversion laws ---------------------------------------------------------
@settings(max_examples=20, deadline=None)
@given(st.sampled_from(FLAVORS), st.integers(0, 10_000))
def test_runlist_containers_runlist_roundtrip(flavor, seed):
    rng = np.random.default_rng(seed)
    pos = _positions(rng, N_BITS, flavor)
    bm = EWAH.from_positions(pos, N_BITS)
    rl = bm.runlist()
    cont = runlist_to_containers(rl, N_BITS)
    back = containers_to_runlist(cont)
    assert np.array_equal(back.bounds, rl.bounds)
    assert np.array_equal(back.kinds, rl.kinds)
    assert np.array_equal(back.lits, rl.lits)


@settings(max_examples=20, deadline=None)
@given(st.sampled_from(FLAVORS), st.integers(0, 10_000))
def test_from_positions_equals_runlist_conversion(flavor, seed):
    rng = np.random.default_rng(seed)
    pos = _positions(rng, N_BITS, flavor)
    via_rl = runlist_to_containers(
        EWAH.from_positions(pos, N_BITS).runlist(), N_BITS)
    direct = containers_from_positions(pos, N_BITS)
    assert np.array_equal(direct.types, via_rl.types)
    assert np.array_equal(direct.counts, via_rl.counts)
    da = EWAH._from_containers(direct, N_BITS)
    db = EWAH._from_containers(via_rl, N_BITS)
    assert np.array_equal(da.words, db.words)


def test_sorted_clustered_collapses_to_plain():
    # the acceptance rule behind the <=5% sorted-table gate: a bitmap of
    # word-aligned runs gains nothing from chunking, so from_positions
    # with container="auto" keeps it a plain run-list bitmap
    pos = np.arange(40_000, 120_000)
    bm = EWAH.from_positions(pos, N_BITS, container="auto")
    assert bm._cont is None
    assert bm.container_summary() == "ewah"
    # while a shuffled sparse bitmap becomes container-backed
    rng = np.random.default_rng(0)
    bm2 = EWAH.from_positions(_shuffled(rng, N_BITS, 0.001), N_BITS,
                              container="auto")
    assert bm2._cont is not None
    assert worthwhile(bm2._cont)


def test_chunk_type_selection_spans_all_types():
    rng = np.random.default_rng(7)
    # build one bitmap whose chunks exercise every container type
    pieces = [
        np.array([], np.int64),                          # chunk 0: EMPTY
        np.arange(CHUNK_BITS, 2 * CHUNK_BITS),           # chunk 1: FULL
        2 * CHUNK_BITS + np.unique(
            rng.integers(0, CHUNK_BITS, 300)),           # chunk 2: ARRAY
        3 * CHUNK_BITS + np.unique(
            rng.integers(0, CHUNK_BITS, 40_000)),        # chunk 3: DENSE
        4 * CHUNK_BITS + np.arange(1000, 60_000),        # chunk 4: RUN
    ]
    pos = np.concatenate(pieces)
    n_bits = 5 * CHUNK_BITS
    cont = containers_from_positions(pos, n_bits)
    assert list(cont.types) == [T_EMPTY, T_FULL, T_ARRAY, T_DENSE, T_RUN]
    assert cont.type_summary() == "mixed"
    bm = EWAH._from_containers(cont, n_bits)
    assert bm == EWAH.from_positions(pos, n_bits)


# -- store round trip: every container type + mixed bitmaps ------------------
@settings(max_examples=15, deadline=None)
@given(st.sampled_from(FLAVORS), st.integers(0, 10_000))
def test_serialize_roundtrip(flavor, seed):
    rng = np.random.default_rng(seed)
    pos = _positions(rng, N_BITS, flavor)
    cont = runlist_to_containers(
        EWAH.from_positions(pos, N_BITS).runlist(), N_BITS)
    words = cont.serialize()
    back = Containers.deserialize(np.asarray(words), N_BITS)
    assert np.array_equal(back.types, cont.types)
    assert np.array_equal(back.counts, cont.counts)
    a = EWAH._from_containers(cont, N_BITS)
    b = EWAH._from_containers(back, N_BITS)
    assert np.array_equal(a.words, b.words)


def test_store_roundtrip_mixed_containers(tmp_path):
    rng = np.random.default_rng(3)
    table = rng.integers(0, 32, size=(50_000, 2))
    builder = IndexBuilder([32, 32], k=1, container="auto")
    idx = builder.append(table).finish()
    kinds = {bm.container_summary()
             for ci in idx.columns for part in ci.bitmaps for bm in part}
    assert kinds - {"ewah"}, kinds  # containers actually in play
    path = str(tmp_path / "idx.ridx")
    index_store.save(idx, path)
    for mmap in (False, True):
        idx2 = index_store.load(path, mmap=mmap)
        for ci, ci2 in zip(idx.columns, idx2.columns):
            for part, part2 in zip(ci.bitmaps, ci2.bitmaps):
                for bm, bm2 in zip(part, part2):
                    assert bm2.container_summary() == bm.container_summary()
                    assert bm2 == bm
                    assert np.array_equal(bm2.words, bm.words)


def test_store_mmap_views_are_zero_copy(tmp_path):
    rng = np.random.default_rng(4)
    table = rng.integers(0, 32, size=(60_000, 1))
    idx = IndexBuilder([32], k=1, container="auto").append(table).finish()
    path = str(tmp_path / "one.ridx")
    index_store.save(idx, path)
    idx2 = index_store.load(path, mmap=True)
    checked = 0
    for part, part2 in zip(idx.columns[0].bitmaps, idx2.columns[0].bitmaps):
        for bm, bm2 in zip(part, part2):
            if bm2._cont is None:
                continue
            types = np.asarray(bm2._cont.types)
            for i in np.flatnonzero(types == T_ARRAY):
                t, _cnt, payload = bm2._cont.chunk(int(i))
                assert t == T_ARRAY
                # uint16 view over the mapped file, not a copied array
                assert payload.dtype == np.uint16
                assert not payload.flags.owndata
                checked += 1
            assert bm2 == bm
    assert checked > 0  # array containers actually occurred


def _patch_preamble_version(path: str, version: int) -> None:
    import struct
    with open(path, "r+b") as f:
        raw = bytearray(f.read(index_store._PREAMBLE.size))
        struct.pack_into("<I", raw, 8, version)  # after the 8-byte magic
        f.seek(0)
        f.write(bytes(raw))


def test_old_format_v1_store_still_loads(tmp_path):
    # a pre-container (version-1, 3-element TOC) file must keep loading:
    # a containers-free v2 store is byte-identical to v1 except for the
    # preamble version field, so patching it down *is* an old-format file
    rng = np.random.default_rng(5)
    table = rng.integers(0, 8, size=(4096, 2))
    idx = IndexBuilder([8, 8], k=1).append(table).finish()  # plain run-list
    path = str(tmp_path / "v1.ridx")
    index_store.save(idx, path)
    assert index_store.VERSION == 2
    _patch_preamble_version(path, 1)
    idx2 = index_store.load(path, mmap=False)
    for ci, ci2 in zip(idx.columns, idx2.columns):
        for part, part2 in zip(ci.bitmaps, ci2.bitmaps):
            for bm, bm2 in zip(part, part2):
                assert bm2 == bm


def test_future_version_rejected(tmp_path):
    rng = np.random.default_rng(6)
    idx = IndexBuilder([4], k=1).append(
        rng.integers(0, 4, size=(128, 1))).finish()
    path = str(tmp_path / "v9.ridx")
    index_store.save(idx, path)
    _patch_preamble_version(path, 9)
    with pytest.raises(index_store.StoreVersionError):
        index_store.load(path)


# -- kernel-facing row flags -------------------------------------------------
@settings(max_examples=15, deadline=None)
@given(st.sampled_from(FLAVORS), st.integers(0, 10_000))
def test_container_row_flags_match_np_row_flags(flavor, seed):
    from repro.kernels import ops as kops
    rng = np.random.default_rng(seed)
    pos = _positions(rng, N_BITS, flavor)
    bm = EWAH.from_positions(pos, N_BITS)
    bm.to_containers(force=True)
    cp = kops.bucket_cols(bm.n_words_uncompressed)
    w = bm.to_words()
    w = np.pad(w, (0, cp - len(w)))
    assert np.array_equal(kops.container_row_flags(bm._cont, cp),
                          kops.np_row_flags(w))


# -- cost model --------------------------------------------------------------
def test_choose_container_matches_conversion():
    model = CostModel()
    rng = np.random.default_rng(8)
    for flavor in FLAVORS:
        pos = _positions(rng, CHUNK_BITS, flavor)
        cont = containers_from_positions(pos, CHUNK_BITS)
        t, cnt, _p = cont.chunk(0)
        rl = EWAH.from_positions(pos, CHUNK_BITS).runlist()
        stats = {"count": len(pos), "n_words": cont.chunk_nw(0),
                 "run_words": C._run_words_exact(rl)}
        name = {T_EMPTY: "empty", T_FULL: "full", T_ARRAY: "array",
                T_DENSE: "dense", T_RUN: "run"}[int(t)]
        assert model.choose_container(stats) == name, flavor


def test_cost_model_json_backward_compatible(tmp_path):
    # a pre-container JSON (no array_cutoff field) must load with defaults
    import json
    p = tmp_path / "cm.json"
    p.write_text(json.dumps({"dense_threshold": 0.25, "calibrated": True,
                             "source": "calibrated", "machine": "x",
                             "n_words": 1, "n_operands": 2, "samples": []}))
    cm = CostModel.load(p)
    assert cm.dense_threshold == 0.25
    assert cm.array_cutoff == 4096
    assert cm.containers_calibrated is False
    # and a calibrated model round-trips through save/load
    cm2 = calibrate_containers(counts=(256, 1024), repeats=1, base=cm)
    assert cm2.containers_calibrated
    assert 0 < cm2.array_cutoff <= 4096
    p2 = cm2.save(tmp_path / "cm2.json")
    cm3 = CostModel.load(p2)
    assert cm3.array_cutoff == cm2.array_cutoff
    assert len(cm3.container_samples) == 2


# -- fork safety (ShardProcessPool regression) -------------------------------
def test_guard_backend_passthrough_in_parent():
    assert _guard_backend("kernel") == "kernel"  # parent process untouched
    assert _guard_backend("auto") == "auto"


def test_fork_workers_never_touch_jax():
    if "fork" not in __import__("multiprocessing").get_all_start_methods():
        pytest.skip("no fork on this platform")
    rng = np.random.default_rng(9)
    table = rng.integers(0, 8, size=(2048, 2))
    idx = ShardedIndex.build(table, shard_rows=512)
    pool = ShardProcessPool(idx, workers=2)
    try:
        probes = pool.run_shards(("probe",), range(idx.n_shards),
                                 backend="ewah")
        assert all(p["fork_worker"] for p in probes)
        assert all(p["pid"] != os.getpid() for p in probes)
        assert all(p["backend"] == "ewah" for p in probes)
        # any backend that can reach jax is a loud error in a worker —
        # never rewritten to ewah, never retried
        for backend in ("auto", "kernel"):
            with pytest.raises(ForkSafetyError):
                pool.run_shards(("probe",), [0], backend=backend)
        assert not issubclass(ForkSafetyError, RuntimeError)
        e = (col(0) == 3) & (col(1) != 2)
        assert idx.execute(e, backend="ewah", pool=pool) == idx.execute(e)
    finally:
        pool.shutdown()
