"""Dataset façade + aggregation statements: group-by vs the NumPy oracle,
sharded partial-count merging, HTTP statement round trips, shared
subexpression accounting."""
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.core import (BitmapIndex, Dataset, QueryBatch, ShardedIndex, col,
                        lex_sort, synth)
from repro.core.statement import run
from repro.core.executor import Executor
from repro.core.planner import Planner, plan
from repro.serve.query_api import (expr_to_json, parse_statement,
                                   serve_in_thread)

NAMES = ["region", "day", "user"]


@pytest.fixture(scope="module")
def tables():
    # moderate cardinalities (~60-90 per column): group-by fan-outs stay
    # CI-sized while still exercising every code path; the lifecycle test
    # below uses a census-shaped table for the realistic skew
    rng = np.random.default_rng(0)
    table, _ = synth.factorize(synth.uniform_table(8000, 3, r=2, rng=rng))
    return {"sorted": table[lex_sort(table)],
            "unsorted": table[rng.permutation(len(table))]}


@pytest.fixture(scope="module")
def census():
    rng = np.random.default_rng(1)
    table, _ = synth.factorize(synth.census_like_table(6000, rng))
    return table


def bincount_oracle(table, c, mask=None, card=None):
    rows = table if mask is None else table[mask]
    return np.bincount(rows[:, c], minlength=card)


# -- statement API vs the oracle --------------------------------------------

@pytest.mark.parametrize("name", ["sorted", "unsorted"])
@pytest.mark.parametrize("k", [1, 2])
def test_group_by_matches_bincount_oracle(tables, name, k):
    table = tables[name]
    ds = Dataset.from_rows(table, NAMES, sort="none", k=k)
    v = int(table[7, 0])
    mask = table[:, 0] == v
    q = ds.query().where(col("region") == v)
    assert q.count() == int(mask.sum())
    for c, cname in enumerate(NAMES):
        got = q.group_by(cname).count()
        want = bincount_oracle(table, c, mask, ds.card(cname))
        assert got.dtype == np.int64
        assert np.array_equal(got, want), (name, k, cname)
        # unfiltered group-by == plain bincount
        assert np.array_equal(ds.query().group_by(cname).count(),
                              bincount_oracle(table, c, None, ds.card(cname)))


def test_group_by_with_complex_filter(tables):
    table = tables["sorted"]
    ds = Dataset.from_rows(table, NAMES, sort="none", k=1)
    e = ((col("region") == int(table[5, 0]))
         | ~col("day").isin([int(table[0, 1]), int(table[9, 1])]))
    mask = ((table[:, 0] == table[5, 0])
            | ~np.isin(table[:, 1], [table[0, 1], table[9, 1]]))
    q = ds.query().where(e)
    assert q.count() == int(mask.sum())
    got = q.group_by("user").count()
    assert np.array_equal(got, bincount_oracle(table, 2, mask, ds.card(2)))


def test_where_chaining_ands(tables):
    table = tables["sorted"]
    ds = Dataset.from_rows(table, NAMES, sort="none")
    v0, v1 = int(table[3, 0]), int(table[3, 1])
    chained = ds.query().where(col(0) == v0).where(col(1) == v1)
    mask = (table[:, 0] == v0) & (table[:, 1] == v1)
    assert chained.count() == int(mask.sum())
    assert np.array_equal(chained.rows(), np.flatnonzero(mask))
    # limit pushes down into a truncated interval decode, same prefix
    want = np.flatnonzero(mask)
    for lim in (0, 1, 3, len(want), len(want) + 10):
        assert np.array_equal(chained.rows(limit=lim), want[:lim])


def test_top_k(tables):
    table = tables["sorted"]
    ds = Dataset.from_rows(table, NAMES, sort="none")
    counts = bincount_oracle(table, 1, None, ds.card("day"))
    top = ds.query().top_k("day", 5)
    assert len(top) == min(5, int((counts > 0).sum()))
    # descending counts, ties by ascending rank; values match the oracle
    want = sorted(((int(c), v) for v, c in enumerate(counts) if c),
                  key=lambda t: (-t[0], t[1]))[:5]
    assert [(v, c) for c, v in want] == top
    assert ds.query().top_k("day", 0) == []


# -- sorted dataset end to end (the acceptance flow) -------------------------

def test_sorted_dataset_lifecycle(census, tmp_path):
    table = census
    ds = Dataset.from_rows(table, NAMES, sort="lex", shards=4)
    st = table[ds.row_perm]
    assert np.array_equal(np.sort(st, axis=0)[:, 0], np.sort(table[:, 0]))
    v = int(st[0, 0])
    mask = st[:, 0] == v
    want = bincount_oracle(st, 1, mask, ds.card("day"))

    # acceptance: open(dir).query().group_by(c).count() == bincount oracle
    ds.save(str(tmp_path / "idx"))
    warm = Dataset.open(str(tmp_path / "idx"))
    assert warm.n_shards == 4
    assert warm.sort_order == ds.sort_order
    got = warm.query().where(col("region") == v).group_by("day").count()
    assert np.array_equal(got, want)
    assert warm.query().where(col("region") == v).count() == int(mask.sum())


def test_spilled_build_matches_in_memory(tables, tmp_path):
    table = tables["unsorted"]
    mem = Dataset.from_rows(table, NAMES, sort="lex", chunk_rows=3000)
    spl = Dataset.from_rows(table, NAMES, sort="lex", chunk_rows=3000,
                            shards=3, spill_dir=str(tmp_path / "runs"))
    assert spl.table is None  # rows never retained on the spill path
    st = table[mem.row_perm]
    v = int(st[0, 0])
    q_mem = mem.query().where(col(0) == v)
    q_spl = spl.query().where(col(0) == v)
    assert q_mem.count() == q_spl.count()
    assert np.array_equal(q_mem.group_by("user").count(),
                          q_spl.group_by("user").count())
    # no retained rows, yet shard() still works: the compressed index is
    # re-cut at 32-bit word boundaries (ShardedIndex.reshard), no rebuild
    recut = spl.shard(2)
    assert recut.n_shards == 2 and recut.table is None
    assert recut.n_rows == spl.n_rows
    assert recut.query().where(col(0) == v).count() == q_spl.count()
    assert np.array_equal(recut.query().group_by("user").count(),
                          spl.query().group_by("user").count())


def test_from_chunks(tables, tmp_path):
    table = tables["unsorted"]
    chunks = [table[s:s + 2500] for s in range(0, len(table), 2500)]
    for spill in (None, str(tmp_path / "c")):
        ds = Dataset.from_chunks(iter(chunks), NAMES, spill_dir=spill)
        st = table[lex_sort(table, ds.sort_order)]
        v = int(st[0, 0])
        assert ds.n_rows == len(table)
        assert ds.query().where(col(0) == v).count() == \
            int((table[:, 0] == v).sum())


# -- sharded vs single-index equality ---------------------------------------

def test_sharded_counts_equal_single_index(tables):
    table = tables["sorted"]
    mono = BitmapIndex.build(table, k=2, column_names=NAMES)
    sh = ShardedIndex.build(table, shard_rows=2016, k=2, column_names=NAMES)
    e = (col("region") == int(table[5, 0])) | (col("day") == int(table[3, 1]))
    assert run(sh, ("count", e)) == run(mono, ("count", e))
    assert run(sh, ("count", None)) == run(mono, ("count", None)) \
        == len(table)
    for c in range(3):
        assert np.array_equal(run(sh, ("gcount", c, e)),
                              run(mono, ("gcount", c, e)))
        assert np.array_equal(run(sh, ("gcount", c, None)),
                              run(mono, ("gcount", c, None)))
    # second round is served from the shard-local LRUs, same answers
    assert run(sh, ("count", e)) == run(mono, ("count", e))
    assert any(c["hits"] > 0 for c in sh.cache_stats())


def test_sharded_aggregates_never_concat_bitmaps(tables, monkeypatch):
    """Aggregates merge per-shard partial counts; the global result bitmap
    that ``execute`` concatenates must never exist."""
    import repro.core.statement as statement_mod  # where partials merge
    table = tables["sorted"]
    sh = ShardedIndex.build(table, shard_rows=2016, k=1, column_names=NAMES)

    def boom(parts):
        raise AssertionError("aggregate concatenated a global bitmap")

    monkeypatch.setattr(statement_mod, "concat_bitmaps", boom)
    e = col("region") == int(table[5, 0])
    mask = table[:, 0] == table[5, 0]
    assert sh.count(e) == int(mask.sum())
    assert np.array_equal(sh.group_count("day", e),
                          bincount_oracle(table, 1, mask, sh.card(1)))
    with pytest.raises(AssertionError):
        sh.execute(e)  # row queries do concatenate — the patch is live


# -- shared-subexpression accounting (QueryBatch satellite) ------------------

def test_executor_shares_subexpressions(tables):
    table = tables["sorted"]
    idx = BitmapIndex.build(table, k=1)
    shared = (col(0) == int(table[5, 0])) | (col(0) == int(table[9, 0]))
    plans = [plan(idx, shared & (col(1) == int(table[i, 1])))
             for i in (0, 50, 99)]
    ex = Executor(idx)
    for p in plans:
        ex.run(p)
    # the OR subtree evaluated once; the two later statements hit the memo
    assert ex.sub_hits >= 2
    # commutatively reordered subtree lands on the same canonical plan key
    swapped = (col(0) == int(table[9, 0])) | (col(0) == int(table[5, 0]))
    ex.run(plan(idx, swapped & (col(2) == int(table[0, 2]))))
    assert ex.sub_hits >= 3


def test_query_batch_computes_shared_subtree_once(tables, monkeypatch):
    import repro.core.executor as exec_mod
    table = tables["sorted"]
    idx = BitmapIndex.build(table, k=1)
    shared = (col(0) == int(table[5, 0])) | (col(0) == int(table[9, 0]))
    exprs = [shared & (col(1) == int(table[i, 1])) for i in (0, 50, 99)]
    calls = []
    orig = exec_mod.or_many
    monkeypatch.setattr(exec_mod, "or_many",
                        lambda bms: (calls.append(len(bms)), orig(bms))[1])
    outs = QueryBatch(exprs).execute(idx)
    assert len(calls) == 1  # the shared OR ran once for the whole batch
    for e, bm in zip(exprs, outs):
        want = ((np.isin(table[:, 0], [table[5, 0], table[9, 0]]))
                & (table[:, 1] == int(e.operands[-1].value)))
        assert np.array_equal(bm.set_bits(), np.flatnonzero(want))


def test_group_by_shares_filter_across_fanout(tables):
    """The group-by fan-out evaluates its filter once: every per-value AND
    reuses the same filter bitmap through the operand cache."""
    table = tables["sorted"]
    idx = BitmapIndex.build(table, k=1)
    e = (col(0) == int(table[5, 0])) | (col(0) == int(table[9, 0]))
    ex = Executor(idx)
    node = Planner(idx).plan_group_count(1, e)
    ex.run_group_count(node)
    ex.run_group_count(node)  # second statement: filter comes from cache
    assert ex.sub_hits >= 1


# -- HTTP statement round trip ----------------------------------------------

def test_http_statement_roundtrip(tables):
    table = tables["sorted"]
    ds = Dataset.from_rows(table, NAMES, sort="none", shards=3)
    svc = ds.serve(pool_workers=2)
    srv, port = serve_in_thread(svc)
    try:
        base = f"http://127.0.0.1:{port}"

        def post(payload):
            req = urllib.request.Request(
                f"{base}/query", data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req) as resp:
                return json.loads(resp.read())

        v = int(table[5, 0])
        e = col("region") == v
        mask = table[:, 0] == v
        out = post({"select": {"count": True}, "where": expr_to_json(e)})
        assert out["select"] == "count" and out["count"] == int(mask.sum())
        assert post({"select": {"count": True},
                     "where": expr_to_json(e)})["cached"] is True
        g = post({"select": {"group_count": "day"}, "where": expr_to_json(e)})
        assert g["counts"] == bincount_oracle(table, 1, mask,
                                              ds.card("day")).tolist()
        t = post({"select": {"top_k": {"col": "day", "k": 3}},
                  "where": expr_to_json(e)})
        assert len(t["top"]) == 3
        assert t["top"][0][1] == max(g["counts"])
        # no where clause: whole-table aggregates
        assert post({"select": {"count": True}})["count"] == len(table)
        # malformed statements -> 400
        for bad in ({"select": {"nope": 1}},
                    {"select": {"count": False}},
                    {"select": {"top_k": {"col": "day"}}},
                    {"select": {"group_count": "no_such_col"}}):
            with pytest.raises(urllib.error.HTTPError) as err:
                post(bad)
            assert err.value.code == 400
    finally:
        srv.shutdown()
        svc.close()


def test_parse_statement():
    st = parse_statement(
        {"select": {"top_k": {"col": "day", "k": 7}},
         "where": {"op": "eq", "col": 0, "value": 1}})
    assert (st["kind"], st["col"], st["k"]) == ("top_k", "day", 7)
    assert st["measure"] is None
    assert st["where"] == (col(0) == 1)
    assert parse_statement({"select": {"count": True}})["kind"] == "count"
    for bad in ({}, {"select": []}, {"select": {"count": True, "x": 1}},
                # bool is a subclass of int: a typo'd copy of the count
                # shape must not resolve to column 1
                {"select": {"group_count": True}},
                {"select": {"top_k": {"col": False, "k": 3}}}):
        with pytest.raises(ValueError):
            parse_statement(bad)


def test_service_statement_cache_invalidation(tables):
    table = tables["sorted"]
    ds = Dataset.from_rows(table, NAMES, sort="none")
    svc = ds.serve(pool_workers=2)
    try:
        e = col("region") == int(table[5, 0])
        first = svc.count(expr_to_json(e))
        assert first["cached"] is False
        assert svc.count(expr_to_json(e))["cached"] is True
        svc.invalidate_cache()
        assert svc.count(expr_to_json(e))["cached"] is False
        g1 = svc.group_count("day", expr_to_json(e))
        assert svc.group_count("day", expr_to_json(e))["cached"] is True
        assert g1["counts"] == bincount_oracle(
            table, 1, table[:, 0] == table[5, 0], ds.card("day")).tolist()
    finally:
        svc.close()
