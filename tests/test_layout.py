"""Self-tuning physical layout: streaming advisor, frequency remaps,
``Dataset.optimize()``.

The invariant under test everywhere: the layout is *physical only*.  Row
order and value encoding move; every query answer — ``rows()`` ids resolved
back to values, ``reconstruct_rows``, ``group_by`` counts, equality
bitmaps, WAL-replayed mutations — stays in original value ranks, through
``compact()``, ``optimize()``, and save/open on both remap-free (v2) and
remap-carrying (v3) store headers.
"""
import os

import numpy as np
import pytest

from repro.core import (Dataset, LayoutDecision, LayoutStats, SortStats,
                        advise_order, col, order_columns_freq_aware,
                        remap_from_counts, synth, validate_remap)
from repro.core import store
from repro.core.encoding import ColumnEncoder

NAMES = ["region", "sku", "user"]


def skewed_table(n=4000, seed=0):
    """Uniform lead + label-shuffled Zipf column + uniform tail: the Zipf
    column's dictionary ranks are decorrelated from frequency, so the
    advisor's remap for it is guaranteed non-identity."""
    rng = np.random.default_rng(seed)
    zipf = (rng.zipf(1.6, n) - 1) % 300
    shuf = rng.permutation(300)
    t = np.stack([rng.integers(0, 32, n), shuf[zipf],
                  rng.integers(0, 50, n)], axis=1).astype(np.int64)
    return t, [32, 300, 50]


def sorted_rows(t):
    """Row-multiset key: lexicographically sorted row tuples."""
    t = np.asarray(t)
    return t[np.lexsort(t.T[::-1])]


def assert_same_answers(ds, table, cards):
    """Every read path must answer in original value ranks."""
    # full reconstruction is the original table as a multiset
    shards = ds.index.shards if hasattr(ds.index, "shards") else [ds.index]
    recon = np.vstack([sh.reconstruct_rows() for sh in shards])
    assert np.array_equal(sorted_rows(recon), sorted_rows(table))
    # group-by counts == the NumPy oracle, indexed by original rank
    for c, name in enumerate(NAMES):
        got = ds.query().group_by(name).count()
        assert np.array_equal(got, np.bincount(table[:, c],
                                               minlength=cards[c]))
    # equality bitmaps take original ranks (hot and cold value of the
    # remapped column)
    for v in (int(table[0, 1]), int(table[-1, 1])):
        want = int((table[:, 1] == v).sum())
        assert ds.query().where(col("sku") == v).count() == want
    # rows() ids point at rows whose values match the predicate
    v = int(table[0, 0])
    ids = ds.query().where(col("region") == v).rows()
    assert len(ids) == int((table[:, 0] == v).sum())
    assert np.all(recon[ids, 0] == v) or np.all(
        np.sort(recon[:, 0][ids]) == v)  # ids index the *stored* order


# -- advisor ----------------------------------------------------------------

def test_advise_order_regimes():
    # every column repeats >= a word: highest card leads
    assert advise_order(32_000, [10, 100, 1000]) == [2, 1, 0]
    # a near-key column (mean freq < 32) trails even though its card is max
    assert advise_order(32_000, [10, 100, 30_000]) == [1, 0, 2]
    # nothing eligible: ascending card (classic d1..dn)
    assert advise_order(100, [50, 90, 70]) == [0, 2, 1]


def test_streaming_order_matches_materialized_rule():
    rng = np.random.default_rng(2)
    t, _ = synth.factorize(synth.census_like_table(3000, rng))
    cards = [int(t[:, c].max()) + 1 for c in range(t.shape[1])]
    assert advise_order(len(t), cards) == order_columns_freq_aware(t, cards)


def test_remap_from_counts_dict_and_array():
    want = [2, 0, 1, 3]  # value 1 hottest -> rank 0, 2 next, 0 -> 2
    rm = remap_from_counts(4, {0: 5, 1: 100, 2: 50})
    assert rm.tolist() == want
    rm2 = remap_from_counts(4, np.array([5, 100, 50, 0]))
    assert rm2.tolist() == want
    # identity collapses to None (store header stays remap-free)
    assert remap_from_counts(3, {0: 9, 1: 5, 2: 1}) is None


def test_validate_remap_rejects_non_permutations():
    with pytest.raises(ValueError):
        validate_remap([0, 0, 1], 3)
    with pytest.raises(ValueError):
        validate_remap([0, 1], 3)
    assert validate_remap([0, 1, 2], 3) is None
    assert validate_remap([2, 0, 1], 3).tolist() == [2, 0, 1]


def test_layout_stats_streaming_parity_with_full_table():
    t, cards = skewed_table()
    whole = LayoutStats().observe(t)
    chunked = LayoutStats()
    for s in range(0, len(t), 257):  # uneven chunks on purpose
        chunked.observe(t[s:s + 257])
    assert chunked.cards() == whole.cards() == cards
    assert chunked.order(cards) == whole.order(cards)
    ra, rb = chunked.remaps(cards), whole.remaps(cards)
    assert ra is not None and rb is not None
    for a, b in zip(ra, rb):
        assert (a is None) == (b is None)
        if a is not None:
            assert np.array_equal(a, b)


def test_layout_stats_eviction_keeps_heavy_hitters():
    t, cards = skewed_table()
    tight = LayoutStats(capacity=64).observe(t)
    assert tight.snapshot()["histogram_exact"][1] is False
    rm = tight.remaps(cards)[1]
    exact = LayoutStats().observe(t).remaps(cards)[1]
    # the hottest values' new ranks survive eviction untouched
    hot = np.argsort(np.bincount(t[:, 1], minlength=300))[::-1][:8]
    assert np.array_equal(rm[hot], exact[hot])


def test_encoder_remap_is_a_pure_relabeling():
    rm = validate_remap([2, 0, 1], 3)
    enc = ColumnEncoder(3, k=2, remap=rm)
    plain = ColumnEncoder(3, k=2)
    for v in range(3):
        assert np.array_equal(enc.codes(np.array([v])),
                              plain.codes(np.array([int(rm[v])])))


# -- build paths: materialized vs streaming ---------------------------------

@pytest.mark.parametrize("k", [1, 2])
def test_from_rows_remap_answers_unchanged(k):
    t, cards = skewed_table()
    ds = Dataset.from_rows(t, NAMES, cards=cards, sort="lex", k=k,
                           remap=True)
    assert ds.layout is not None and 1 in ds.layout.remapped_columns
    assert_same_answers(ds, t, cards)


def test_from_chunks_picks_same_layout_without_materializing(tmp_path):
    t, cards = skewed_table(n=6000)
    ref = Dataset.from_rows(t, NAMES, cards=cards, sort="lex", remap=True,
                            partition_rows=1024)
    stats = SortStats()
    ds = Dataset.from_chunks(
        (t[s:s + 500] for s in range(0, len(t), 500)), NAMES, cards=cards,
        spill_dir=str(tmp_path), sort="lex", remap=True, chunk_rows=1024,
        partition_rows=1024, sort_stats=stats)
    # identical decision: same order, same remaps, frozen pre-sort
    assert ds.sort_order == ref.sort_order
    for a, b in zip(ds.layout.remaps, ref.layout.remaps):
        assert (a is None) == (b is None)
        if a is not None:
            assert np.array_equal(a, b)
    # identical physical result
    assert ds.index.size_words == ref.index.size_words
    # and the sort never held the table: peak merge buffer is bounded by
    # the merge block, far under the 6000-row table
    assert 0 < stats.peak_buffer_bytes < t.nbytes
    assert stats.n_runs >= 2
    assert_same_answers(ds, t, cards)


# -- store round trip: v2 stays v2, remaps ride v3 --------------------------

def _file_version(path):
    with open(path, "rb") as f:
        return store._PREAMBLE.unpack(f.read(store._PREAMBLE.size))[1]


def test_store_version_bumps_only_for_remaps(tmp_path):
    t, cards = skewed_table()
    plain_dir, remap_dir = str(tmp_path / "v2"), str(tmp_path / "v3")
    Dataset.from_rows(t, NAMES, cards=cards, sort="lex",
                      remap=False).save(plain_dir)
    Dataset.from_rows(t, NAMES, cards=cards, sort="lex",
                      remap=True).save(remap_dir)
    for d, want in ((plain_dir, store.VERSION),
                    (remap_dir, store.VERSION_REMAP)):
        for name in store.manifest_shards(d):
            assert _file_version(os.path.join(d, name)) == want


@pytest.mark.parametrize("remap", [False, True])
def test_save_open_preserves_layout_and_answers(tmp_path, remap):
    t, cards = skewed_table()
    d = str(tmp_path / "ds")
    Dataset.from_rows(t, NAMES, cards=cards, sort="lex", k=2, remap=remap,
                      shards=2).save(d)
    ds = Dataset.open(d)
    if remap:
        assert ds.layout is not None and 1 in ds.layout.remapped_columns
        assert "remapped_columns=" in ds.explain(col("sku") == 1)
    assert_same_answers(ds, t, cards)
    import json
    with open(os.path.join(d, store.MANIFEST_NAME)) as f:
        assert json.load(f)["version"] == store.VERSION  # manifest unchanged
    meta = store.manifest_meta(d)
    if remap:
        dec = LayoutDecision.from_meta(meta["layout"])
        assert 1 in dec.remapped_columns
        assert dec.stats["n_rows"] == len(t)


# -- live ingest: WAL replay + relayout compaction --------------------------

def test_wal_replay_and_relayout_compaction_keep_original_values(tmp_path):
    t, cards = skewed_table()
    d = str(tmp_path / "live")
    Dataset.from_rows(t, NAMES, cards=cards, sort="lex", k=2,
                      remap=True).save(d)
    ds = Dataset.open(d, live=True)
    extra = np.array([[3, 7, 11], [5, 299, 0], [3, 7, 11]], dtype=np.int64)
    ds.append(extra)
    ds.delete(col("user") == 13)
    merged = np.vstack([t[t[:, 2] != 13], extra[extra[:, 2] != 13]])
    want = np.bincount(merged[:, 1], minlength=cards[1])
    assert np.array_equal(ds.query().group_by("sku").count(), want)
    ds.index.close()

    # crash-replay: reopen replays the WAL against the remapped base
    ds2 = Dataset.open(d, live=True)
    assert np.array_equal(ds2.query().group_by("sku").count(), want)

    # relayout compaction re-runs the advisor over the merged rows and the
    # answers still come back in original ranks
    info = ds2.compact(relayout=True)
    assert info["n_rows"] == len(merged)
    assert ds2.layout is not None and 1 in ds2.layout.remapped_columns
    assert np.array_equal(ds2.query().group_by("sku").count(), want)
    ds2.index.close()

    # and the compacted store reopens cold with the same answers
    ds3 = Dataset.open(d, live=False)
    assert np.array_equal(ds3.query().group_by("sku").count(), want)


# -- optimize() -------------------------------------------------------------

def test_optimize_rewrites_store_in_place(tmp_path):
    t, cards = skewed_table(n=6000)
    d = str(tmp_path / "opt")
    Dataset.from_rows(t, NAMES, cards=cards, sort="none", k=2, shards=2,
                      container="run").save(d)
    ds = Dataset.open(d)
    before = ds.index.size_words
    info = ds.optimize(col_order="auto", remap=True)
    assert info["size_words_before"] == before
    assert info["opt_epoch"] == 1
    assert info["size_words_after"] == ds.index.size_words < before
    assert 1 in info["remapped_columns"]
    # within 2% of (here: identical to) a from-scratch sorted+remap build
    scratch = Dataset.from_rows(t, NAMES, cards=cards, sort="lex", k=2,
                                shards=2, container="run", remap=True)
    assert ds.index.size_words <= int(scratch.index.size_words * 1.02)
    assert_same_answers(ds, t, cards)
    # the rewrite is durable: a cold reopen sees the optimized layout
    ds2 = Dataset.open(d)
    assert 1 in ds2.layout.remapped_columns
    assert store.manifest_meta(d)["opt_epoch"] == 1
    assert_same_answers(ds2, t, cards)
    # old shard files are gone, only the oNNNNN- generation remains
    names = store.manifest_shards(d)
    assert all(n.startswith("o00001-") for n in names)
    assert sorted(os.listdir(d)) == sorted(
        names + [store.MANIFEST_NAME])
    # epochs increment across repeated optimizes
    assert ds2.optimize(col_order="auto", remap=True)["opt_epoch"] == 2


def test_optimize_explicit_order_and_guards(tmp_path):
    t, cards = skewed_table()
    d = str(tmp_path / "opt2")
    Dataset.from_rows(t, NAMES, cards=cards, sort="none").save(d)
    ds = Dataset.open(d)
    info = ds.optimize(col_order=[1, 0, 2], remap=False)
    assert ds.sort_order == [1, 0, 2] and info["remapped_columns"] == []
    assert_same_answers(ds, t, cards)
    # live dataset with pending mutations must refuse
    ds.append(np.array([[0, 0, 0]], dtype=np.int64))
    with pytest.raises(RuntimeError, match="pending mutations"):
        ds.optimize()
    ds.index.close()

# -- serving: /admin/optimize + layout/cost-model provenance in /stats ------

def _post(base, path, body=None):
    import json
    import urllib.request
    req = urllib.request.Request(
        base + path, data=json.dumps(body or {}).encode(),
        headers={"Content-Type": "application/json"})
    return json.loads(urllib.request.urlopen(req).read())


def test_service_optimize_rolls_store_and_reports_layout(tmp_path):
    import json
    import urllib.request
    from repro.serve.query_api import QueryService, serve_in_thread
    t, cards = skewed_table(n=6000)
    d = str(tmp_path / "srv")
    Dataset.from_rows(t, NAMES, cards=cards, sort="none", k=2,
                      shards=2).save(d)
    svc = QueryService.from_dir(d, shard_processes=0)
    srv, port = serve_in_thread(svc)
    base = f"http://127.0.0.1:{port}"
    try:
        q = {"op": "eq", "col": "sku", "value": int(t[0, 1])}
        before = _post(base, "/query", {"query": q})
        out = _post(base, "/admin/optimize", {})
        assert out["ok"] and out["opt_epoch"] == 1
        assert out["reloaded"] == [0, 1]
        assert out["size_words_after"] < out["size_words_before"]
        after = _post(base, "/query", {"query": q})
        assert after["count"] == before["count"]
        stats = json.loads(urllib.request.urlopen(base + "/stats").read())
        assert stats["layout"]["order"] == out["order"]
        assert stats["layout"]["remaps"] is not None
        cm = stats["cost_model"]
        assert set(cm) >= {"dense_threshold", "calibrated", "source",
                           "machine", "machine_match", "array_cutoff"}
        # in-memory services must refuse (no directory to rewrite)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(base, "/admin/optimize", {"col_order": "bogus"})
        assert ei.value.code == 400
    finally:
        srv.shutdown()
        svc.close()


def test_service_optimize_live_folds_pending_then_rewrites(tmp_path):
    from repro.core.ingest import LiveIndex
    from repro.serve.query_api import QueryService
    t, cards = skewed_table(n=3000)
    d = str(tmp_path / "live-srv")
    Dataset.from_rows(t, NAMES, cards=cards, sort="none", k=1,
                      shards=2).save(d)
    svc = QueryService.from_dir(d, shard_processes=0, live=True)
    try:
        svc.ingest([[3, 7, 11], [5, 299, 0]])
        svc.delete({"op": "eq", "col": "user", "value": 13})
        want = svc.count()["count"]
        out = svc.optimize()
        assert out.get("live") is True
        assert isinstance(svc.index, LiveIndex)
        assert svc.count()["count"] == want
        assert svc.stats()["layout"]["remaps"] is not None
        # still mutable after the swap
        svc.ingest([[1, 2, 3]])
        assert svc.count()["count"] == want + 1
    finally:
        svc.close()


# -- cost-model satellites --------------------------------------------------

def test_calibrate_resolves_interpret_from_backend(monkeypatch):
    from repro.core import cost_model
    from repro.kernels import ops as kops
    calls = []
    real = kops.interpret_mode

    def spy():
        calls.append(real())
        return calls[-1]

    monkeypatch.setattr(kops, "interpret_mode", spy)
    m = cost_model.calibrate(n_words=1 << 8, n_operands=2,
                             densities=(0.05, 0.9), repeats=1)
    # the CPU backend resolves to interpret mode through the one helper,
    # for the source label and for every kernel dispatch of the sweep
    assert calls and all(calls)
    assert len(calls) > 1
    assert m.calibrated and m.source == "calibrated-interpret"
    assert m.machine_match


def test_cost_model_machine_match_flags_foreign_calibration(tmp_path,
                                                            monkeypatch,
                                                            caplog):
    import logging
    from repro.core import cost_model
    foreign = cost_model.CostModel(dense_threshold=0.25, calibrated=True,
                                   source="calibrated",
                                   machine="some-other-host")
    assert not foreign.machine_match
    p = tmp_path / "cm.json"
    foreign.save(p)
    monkeypatch.setenv(cost_model.ENV_PATH, str(p))
    with caplog.at_level(logging.WARNING, logger="repro.core.cost_model"):
        m = cost_model.get_default(refresh=True)
    try:
        assert m.dense_threshold == 0.25  # still applied...
        assert not m.machine_match        # ...but flagged
        assert any("stale" in r.message for r in caplog.records)
    finally:
        monkeypatch.delenv(cost_model.ENV_PATH)
        cost_model.set_default(None)
        cost_model.get_default(refresh=True)
