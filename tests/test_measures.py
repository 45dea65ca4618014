"""Measure sidecar + compressed-domain OLAP statements.

Covers the measure subsystem end to end against NumPy row oracles:

* property suite — sum/avg/min/max over ``set_intervals()`` slices vs a
  boolean-mask oracle, across clustered (sorted-table-like), scattered and
  container-backed bitmaps, including the empty-filter and all-rows edges;
* Dataset statements — scalar aggregates, two-column group-by, measure
  declaration validation, measure survival through save/open, ``shard()``,
  ``optimize()`` and live ``compact()``;
* top-k tie-breaking — identical deterministic orderings (count desc, rank
  asc) on the monolithic, sharded and cluster paths, for count- and
  sum-ranked top-k (the satellite regression);
* result-cache byte sizing — aggregate tuples and grouped matrices are
  accounted by ``payload_nbytes``, not sized as 0;
* the SQL-ish front door and the statement JSON grammar;
* cluster degradation — grouped aggregates under a killed worker stay
  exact via replicas, and report ``exact=False`` + ``covered_rows`` once
  coverage is genuinely lost.
"""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import measures as M
from repro.core.containers import containers_from_positions
from repro.core.dataset import Dataset, top_k_from_counts, top_k_from_values
from repro.core.ewah import EWAH
from repro.core.lru import payload_kind, payload_nbytes
from repro.serve.query_api import (QueryService, nan_to_none, parse_sql,
                                   parse_statement)

NAMES = ["region", "day", "user"]


def make(n=4000, seed=3, shards=0):
    rng = np.random.default_rng(seed)
    rows = np.column_stack([rng.integers(0, 7, n), rng.integers(0, 11, n),
                            rng.integers(0, 29, n)]).astype(np.int64)
    sales = rng.integers(-50, 1000, n).astype(np.int64)
    price = rng.random(n) * 20.0 - 5.0
    ds = Dataset.from_rows(rows, NAMES, shards=shards,
                           measures={"sales": sales, "price": price})
    # from_rows sorts the table; oracles must see the *stored* row order,
    # so read rows and measure values back from the index itself
    idx_shards = getattr(ds.index, "shards", [ds.index])
    stored = np.concatenate([sh.reconstruct_rows() for sh in idx_shards])
    meas = {name: np.concatenate(
        [np.asarray(sh.measures[name]) for sh in idx_shards])
        for name in ("sales", "price")}
    return ds, stored, meas


# ---------------------------------------------------------------------------
# Property suite: interval-sliced reduction vs boolean-mask oracle.
# ---------------------------------------------------------------------------

def _mask(rng, n, density, clustered):
    if density <= 0.0:
        return np.zeros(n, dtype=bool)
    if density >= 1.0:
        return np.ones(n, dtype=bool)
    if clustered:
        # sorted-table-like: a few long runs
        mask = np.zeros(n, dtype=bool)
        n_runs = int(rng.integers(1, 6))
        for _ in range(n_runs):
            a = int(rng.integers(0, n))
            b = min(n, a + int(rng.integers(1, max(2, int(n * density)))))
            mask[a:b] = True
        return mask
    return rng.random(n) < density


def _check_reduction(vals, mask, bm):
    starts, ends = bm.set_intervals()
    s, cnt, mn, mx = M.reduce_intervals(vals, starts, ends)
    assert cnt == int(mask.sum())
    if cnt == 0:
        assert s == 0 and mn is None and mx is None
        return
    sel = vals[mask]
    if vals.dtype == np.int64:
        # int64 sums wrap exactly like NumPy's — bit-exact comparison
        assert s == int(sel.sum()) and mn == int(sel.min()) \
            and mx == int(sel.max())
    else:
        assert s == pytest.approx(float(sel.sum()), rel=1e-12, abs=1e-9)
        assert mn == float(sel.min()) and mx == float(sel.max())


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 2**31 - 1),
       st.sampled_from([0.0, 0.01, 0.1, 0.5, 0.9, 1.0]),
       st.sampled_from(["int", "float"]),
       st.booleans(), st.booleans())
def test_interval_reduction_matches_mask_oracle(seed, density, kind,
                                                clustered, container):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 2500))
    mask = _mask(rng, n, density, clustered)
    vals = rng.integers(-10**6, 10**6, n).astype(np.int64) if kind == "int" \
        else rng.random(n) * 100.0 - 50.0
    if container:
        cont = containers_from_positions(np.flatnonzero(mask), n)
        bm = EWAH._from_containers(cont, n)
    else:
        bm = EWAH.from_bool(mask)
    _check_reduction(vals, mask, bm)


def test_interval_reduction_edges():
    vals = np.arange(10, dtype=np.int64)
    # empty filter
    _check_reduction(vals, np.zeros(10, bool), EWAH.from_bool(np.zeros(10, bool)))
    # all rows
    _check_reduction(vals, np.ones(10, bool), EWAH.from_bool(np.ones(10, bool)))
    # int64 overflow wraps like NumPy, never raises
    big = np.full(4, 2**62, dtype=np.int64)
    bm = EWAH.from_bool(np.ones(4, bool))
    s, cnt, _, _ = M.reduce_intervals(big, *bm.set_intervals())
    with np.errstate(over="ignore"):
        assert s == int(big.sum()) and cnt == 4


# ---------------------------------------------------------------------------
# Dataset statements vs NumPy row oracle (mono + sharded).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shards", [0, 3])
def test_scalar_aggs_match_oracle(shards):
    from repro.core import col
    ds, stored, meas = make(shards=shards)
    mask = stored[:, 0] == 2
    q = ds.query().where(col("region") == 2)
    assert q.sum("sales") == int(meas["sales"][mask].sum())
    assert q.min("sales") == int(meas["sales"][mask].min())
    assert q.max("sales") == int(meas["sales"][mask].max())
    assert q.avg("price") == pytest.approx(meas["price"][mask].mean())
    # unfiltered
    assert ds.query().sum("sales") == int(meas["sales"].sum())
    # unknown measure
    with pytest.raises(KeyError):
        ds.query().sum("bogus")


@pytest.mark.parametrize("shards", [0, 4])
def test_two_column_group_by_matches_oracle(shards):
    from repro.core import col
    ds, stored, meas = make(shards=shards)
    g = ds.query().group_by("day", "region")
    sums = g.sum("sales")
    oracle = np.zeros((11, 7), dtype=np.int64)
    np.add.at(oracle, (stored[:, 1], stored[:, 0]), meas["sales"])
    assert np.array_equal(np.asarray(sums), oracle)
    cnt = np.zeros((11, 7), dtype=np.int64)
    np.add.at(cnt, (stored[:, 1], stored[:, 0]), 1)
    assert np.array_equal(np.asarray(g.count()), cnt)
    # filtered two-column min (float measure; empty cells -> NaN)
    mask = stored[:, 2] < 5
    gm = ds.query().where(col("user") < 5).group_by("day", "region")
    mins = np.asarray(gm.min("price"))
    for a in range(11):
        for b in range(7):
            cell = mask & (stored[:, 1] == a) & (stored[:, 0] == b)
            if cell.any():
                assert mins[a, b] == pytest.approx(meas["price"][cell].min())
            else:
                assert np.isnan(mins[a, b])


def test_measures_survive_save_open_shard_optimize(tmp_path):
    ds, stored, meas = make(n=2000, shards=3)
    total = int(meas["sales"].sum())
    d = str(tmp_path / "store")
    ds.save(d)
    re = Dataset.open(d, live=False)
    assert re.measure_names == ["price", "sales"] or \
        sorted(re.measure_names) == ["price", "sales"]
    assert re.query().sum("sales") == total
    # reshard keeps the sidecar aligned
    re2 = re.shard(2)
    assert re2.query().sum("sales") == total
    assert np.array_equal(np.asarray(re2.query().group_by("region").sum("sales")),
                          np.asarray(ds.query().group_by("region").sum("sales")))
    # physical-layout rewrite permutes rows with their measure values
    out = Dataset.open(d, live=False).optimize()
    assert out is not None
    opt = Dataset.open(d, live=False)
    assert opt.query().sum("sales") == total
    assert np.array_equal(np.asarray(opt.query().group_by("region").sum("sales")),
                          np.asarray(ds.query().group_by("region").sum("sales")))


def test_live_append_measures_and_compact(tmp_path):
    from repro.core import ShardedIndex, col
    from repro.core.ingest import LiveIndex
    ds, stored, meas = make(n=1200, shards=2)
    d = str(tmp_path / "live")
    ds.save(d)
    live = LiveIndex(ShardedIndex.load(d), dir_path=d)
    new_rows = np.array([[1, 2, 3], [6, 10, 28]], dtype=np.int64)
    live.append(new_rows, measures={"sales": np.array([100, 200]),
                                    "price": np.array([1.5, 2.5])})
    # all-or-nothing: an append without the declared measures is rejected
    with pytest.raises(ValueError):
        live.append(new_rows)
    with pytest.raises(ValueError):
        live.append(new_rows, measures={"sales": np.array([1, 2])})
    assert live.agg("sales", None)[0] == int(meas["sales"].sum()) + 300
    g = live.group_agg("sales", ["region"], (col("day") == 2))
    oracle = np.zeros(7, dtype=np.int64)
    m2 = stored[:, 1] == 2
    np.add.at(oracle, stored[m2, 0], meas["sales"][m2])
    oracle[1] += 100
    assert np.array_equal(M.finalize_group("sum", g), oracle)
    live.compact()
    assert live.agg("sales", None)[0] == int(meas["sales"].sum()) + 300
    assert np.array_equal(
        M.finalize_group("sum", live.group_agg("sales", ["region"],
                                               (col("day") == 2))), oracle)
    live.close()
    # WAL-free reopen serves the compacted sidecar
    re = LiveIndex(ShardedIndex.load(d), dir_path=d)
    assert re.agg("sales", None)[0] == int(meas["sales"].sum()) + 300
    re.close()


# ---------------------------------------------------------------------------
# Top-k tie-breaking determinism across mono / sharded / cluster.
# ---------------------------------------------------------------------------

def _tied_dataset(shards=0):
    # 6 region values, each appearing exactly 300 times, measure all-ones:
    # counts AND sums tie everywhere, so any nondeterminism shows instantly
    reps = 300
    rows = np.column_stack([
        np.repeat(np.arange(6), reps),
        np.tile(np.arange(10), 180),
        np.tile(np.arange(30), 60),
    ]).astype(np.int64)
    ones = np.ones(len(rows), dtype=np.int64)
    return Dataset.from_rows(rows, NAMES, shards=shards,
                             measures={"sales": ones})


def test_top_k_ties_deterministic_mono_vs_sharded():
    mono = _tied_dataset(0)
    shd = _tied_dataset(4)
    for measure in (None, "sales"):
        t_mono = mono.query().top_k("region", 4, measure=measure)
        t_shd = shd.query().top_k("region", 4, measure=measure)
        # all six groups tie; deterministic rule = ascending rank
        assert [r for r, _ in t_mono] == [0, 1, 2, 3]
        assert t_mono == t_shd


def test_top_k_ties_deterministic_cluster(tmp_path):
    from repro.distributed.cluster import ClusterService, Policy
    from repro.serve.worker_api import ShardWorker, WorkerServer
    ds = _tied_dataset(4)
    d = str(tmp_path / "tied")
    ds.index.save(d)
    servers = [WorkerServer(ShardWorker(d, [], backend="ewah")).start()
               for _ in range(2)]
    svc = ClusterService(d, [s.address for s in servers], replication=2,
                         policy=Policy(deadline_s=5.0, backoff_s=0.01),
                         backend="ewah")
    svc.start(monitor=False)
    try:
        expect = ds.query().top_k("region", 4)
        got = svc.top_k("region", 4)
        assert [tuple(t) for t in got["top"]] == expect
        expect_m = ds.query().top_k("region", 4, measure="sales")
        got_m = svc.top_k("region", 4, measure="sales")
        assert [tuple(t) for t in got_m["top"]] == expect_m
    finally:
        svc.close()
        for s in servers:
            s.stop()


def test_top_k_helpers_tie_break_and_zero_exclusion():
    counts = np.array([5, 5, 0, 5, 2], dtype=np.int64)
    assert top_k_from_counts(counts, 4) == [(0, 5), (1, 5), (3, 5), (4, 2)]
    vals = np.array([7, 7, 9, 7, 0], dtype=np.int64)
    # rank 2 wins on value; the 7s tie -> ascending rank; count-0 groups
    # are excluded even when their value ties
    cts = np.array([1, 1, 1, 1, 0], dtype=np.int64)
    assert top_k_from_values(vals, cts, 5) == [(2, 9), (0, 7), (1, 7), (3, 7)]


# ---------------------------------------------------------------------------
# Result-cache byte sizing for aggregate shapes (satellite).
# ---------------------------------------------------------------------------

def test_payload_nbytes_accounts_aggregate_shapes():
    # scalar agg tuple: plain python numbers -> 0 payload bytes
    assert payload_nbytes((1234, 10, -5, 999)) == 0
    assert payload_kind((1234, 10, -5, 999)) == "scalar"
    # tuple carrying arrays (pruned top-k partials) sizes the arrays
    a = np.zeros(100, dtype=np.int64)
    assert payload_nbytes((a, 3)) == a.nbytes
    assert payload_kind((a, 3)) == "agg"
    # grouped aggregate dict: every matrix counted, metadata free
    g = {"cols": (0, 1), "shape": (11, 7), "measure": "sales",
         "dtype": "<i8", "counts": np.zeros(77, dtype=np.int64),
         "sums": np.zeros(77, dtype=np.int64),
         "mins": np.zeros(77, dtype=np.int64),
         "maxs": np.zeros(77, dtype=np.int64)}
    assert payload_nbytes(g) == 4 * 77 * 8
    assert payload_kind(g) == "agg"
    # nesting (dict of lists of arrays) recurses
    assert payload_nbytes({"parts": [a, a]}) == 2 * a.nbytes


def test_service_caches_group_matrices_within_budget():
    ds, stored, meas = make(n=1500, shards=0)
    svc = QueryService(ds.index, cache_entries=64, cache_bytes=1 << 20)
    r1 = svc.group_agg("sum", "sales", ["day", "region"])
    r2 = svc.group_agg("sum", "sales", ["day", "region"])
    assert not r1["cached"] and r2["cached"]
    assert r1["values"] == r2["values"]
    st_ = svc.stats()["cache"]
    assert st_["bytes"] > 0  # the matrices are not sized as 0
    svc.close()


# ---------------------------------------------------------------------------
# Statement grammar + SQL front door.
# ---------------------------------------------------------------------------

def test_parse_statement_measure_forms():
    st_ = parse_statement({"select": {"sum": "sales"}})
    assert st_["kind"] == "agg" and st_["op"] == "sum" \
        and st_["measure"] == "sales"
    st_ = parse_statement({"select": {"avg": "price", "by": ["day", "region"]}})
    assert st_["kind"] == "group_agg" and st_["by"] == ["day", "region"]
    st_ = parse_statement({"select": {"count": True, "by": "day"}})
    assert st_["kind"] == "group_agg" and st_["op"] == "count" \
        and st_["measure"] is None and st_["by"] == ["day"]
    st_ = parse_statement({"select": {"top_k": {"col": "region", "k": 3,
                                                "measure": "sales"}}})
    assert st_["kind"] == "top_k" and st_["measure"] == "sales"
    # limit rewrites single-column count/sum group-bys into top-k
    st_ = parse_statement({"select": {"sum": "sales", "by": ["region"]},
                           "limit": 5})
    assert st_["kind"] == "top_k" and st_["col"] == "region" \
        and st_["k"] == 5 and st_["measure"] == "sales"
    st_ = parse_statement({"select": {"group_count": "region"}, "limit": 2})
    assert st_["kind"] == "top_k" and st_["k"] == 2 and st_["measure"] is None


@pytest.mark.parametrize("bad", [
    {"select": {"sum": 5}},                                  # non-string measure
    {"select": {"sum": "s", "by": []}},                      # no group col
    {"select": {"avg": "p", "by": ["region"]}, "limit": 3},  # no avg ranking
    {"select": {"sum": "s"}, "limit": 3},                    # scalar limit
    {"select": {"group_count": "region", "by": ["day"]}},    # by + group_count
    {"select": {"count": True, "limit": "x"}},               # two select keys
    {"select": {"sum": "s", "avg": "p"}},                    # two statements
])
def test_parse_statement_rejects_malformed(bad):
    with pytest.raises(ValueError):
        parse_statement(bad)


def test_parse_sql_translates_grammar():
    obj = parse_sql("SELECT sum(sales) FROM t WHERE region = 2 "
                    "GROUP BY day, region")
    assert obj == {"select": {"sum": "sales", "by": ["day", "region"]},
                   "where": {"op": "eq", "col": "region", "value": 2}}
    obj = parse_sql("SELECT count(*) FROM f WHERE a IN (1, 2) "
                    "AND b BETWEEN 3 AND 6 OR NOT c = 0 LIMIT 4")
    assert obj["where"]["op"] == "or"
    assert obj["limit"] == 4
    obj = parse_sql("select avg(price) from t")  # keywords case-insensitive
    assert obj == {"select": {"avg": "price"}}
    for bad in ["SELECT median(x) FROM t", "SELECT sum(s)", "",
                "SELECT count(*) FROM t GROUP BY a,",
                "SELECT count(*) FROM t WHERE a = 1 garbage"]:
        with pytest.raises(ValueError):
            parse_sql(bad)


def test_sql_statement_matches_json_statement():
    ds, stored, meas = make(n=2000, shards=3)
    svc = QueryService(ds.index)
    try:
        via_sql = svc.sql("SELECT sum(sales) FROM t WHERE region = 1 "
                          "GROUP BY day LIMIT 3")
        via_json = svc.statement({
            "select": {"sum": "sales", "by": ["day"]},
            "where": {"op": "eq", "col": "region", "value": 1}, "limit": 3})
        assert via_sql["top"] == via_json["top"]
        mask = stored[:, 0] == 1
        oracle = np.zeros(11, dtype=np.int64)
        np.add.at(oracle, stored[mask, 1], meas["sales"][mask])
        expect = top_k_from_values(oracle, np.bincount(
            stored[mask, 1], minlength=11).astype(np.int64), 3)
        assert [tuple(t) for t in via_sql["top"]] == expect
    finally:
        svc.close()


def test_nan_to_none():
    assert nan_to_none([1.0, float("nan"), [float("nan"), 2]]) == \
        [1.0, None, [None, 2]]


# ---------------------------------------------------------------------------
# Cluster degradation for measure statements.
# ---------------------------------------------------------------------------

def test_cluster_measure_degradation(tmp_path):
    from repro.distributed.cluster import ClusterService, Policy
    from repro.serve.worker_api import ShardWorker, WorkerServer
    ds, stored, meas = make(n=3000, seed=9, shards=4)
    d = str(tmp_path / "clu")
    ds.index.save(d)
    servers = [WorkerServer(ShardWorker(d, [], backend="ewah")).start()
               for _ in range(2)]
    svc = ClusterService(d, [s.address for s in servers], replication=2,
                         policy=Policy(deadline_s=3.0, retries=1,
                                       backoff_s=0.01, hedge_after_s=0.1),
                         backend="ewah")
    svc.start(monitor=False)
    try:
        total = int(meas["sales"].sum())
        r = svc.agg("sum", "sales")
        assert r["exact"] and r["value"] == total
        oracle = np.zeros((11, 7), dtype=np.int64)
        np.add.at(oracle, (stored[:, 1], stored[:, 0]), meas["sales"])
        r = svc.group_agg("sum", "sales", ["day", "region"])
        assert r["exact"] and np.array_equal(np.asarray(r["values"]), oracle)
        with pytest.raises(KeyError):
            svc.agg("sum", "bogus")
        # kill one worker: replication=2 across 2 workers still covers all
        # shards through the survivor, so results stay exact
        servers[0].stop()
        svc.invalidate_cache()
        r = svc.group_agg("sum", "sales", ["day", "region"])
        assert np.array_equal(np.asarray(r["values"]), oracle)
        assert r["exact"]
        # kill the last worker: every shard is missing -> degraded result,
        # never cached, coverage reported
        servers[1].stop()
        svc.invalidate_cache()
        svc.policy.deadline_s = 0.5
        svc.policy.retries = 0
        r = svc.agg("sum", "sales")
        assert not r["exact"]
        assert r["missing_shards"] == list(range(4))
        assert r["covered_rows"] == 0 and r["value"] == 0
        r = svc.group_agg("count", None, ["region"])
        assert not r["exact"] and sum(r["counts"]) == 0
    finally:
        svc.close()
        for s in servers:
            s.stop()
