"""Grouped aggregates over three and more columns against a NumPy row scan.

One sweep of elementary segments serves any number of grouping columns.
These tests hold it to a plain row scan on seeded tables cut into 3 shards,
for every op, with and without a filter (an all-false one included), on
the ``ewah`` and ``kernel`` backends (the kernels interpreted on the CPU),
through each front door: ``QueryService.statement`` (JSON and SQL),
``Dataset.query().group_by`` and a ``ClusterService`` over a local worker
fleet.  The one- and two-column answers are pinned to what the former
one- and two-column branches of the executor returned.  The sweep's two
ways to build a column's catalog, mapping its bitmaps' intervals and
ranking it at the filter's rows, are each held to the row scan on
unsorted and lex-sorted tables, and the counters show which one ran.
"""
import numpy as np
import pytest

from repro.core import ShardedIndex, col, trace
from repro.core.dataset import Dataset
from repro.core.ewah import EWAH
from repro.core.executor import (Executor, _bin_segments, _interval_catalog,
                                 _rank_catalog, execute_group_agg)
from repro.core.measures import (empty_group_agg, finalize_group, gather,
                                 interval_positions, merge_group_aggs)
from repro.core.planner import PGroupAgg, PPinned, Planner
from repro.serve.query_api import QueryService, expr_to_json, parse_sql

NAMES = ["a", "b", "c", "d", "e"]
CARDS = [4, 3, 5, 2, 6]
OPS = ("count", "sum", "avg", "min", "max")
FILTERS = {
    "none": None,
    "some": (col("e") <= 3) & ~(col("d") == 1),
    "all_false": (col("e") == 0) & (col("e") == 1),
}
# the same filters as row masks
MASKS = {
    "none": lambda r: np.ones(len(r), dtype=bool),
    "some": lambda r: (r[:, 4] <= 3) & ~(r[:, 3] == 1),
    "all_false": lambda r: np.zeros(len(r), dtype=bool),
    "e_le_3": lambda r: r[:, 4] <= 3,
}


def make_table(n=2000, seed=11):
    """Rows in runs (sorted on coarse keys), an int64 and a float64 measure
    whose sums are exact in any order (multiples of 1/4)."""
    rng = np.random.default_rng(seed)
    rows = np.column_stack([rng.integers(0, c, n) for c in CARDS])
    rows = rows[np.lexsort((rows[:, 2] // 2, rows[:, 0] // 2))]
    meas = {"m": rng.integers(-500, 5000, n).astype(np.int64),
            "p": rng.integers(-40, 400, n) / 4.0}
    return rows.astype(np.int64), meas


def row_scan(rows, meas, by, op, measure, filt):
    """The dense row-major cube of ``op`` by the columns ``by`` over the
    rows that filter ``filt`` selects: NaN where avg/min/max has no row."""
    mask = MASKS[filt](rows)
    shape = [CARDS[NAMES.index(c)] for c in by]
    cell = np.ravel_multi_index(
        tuple(rows[mask, NAMES.index(c)] for c in by), shape)
    size = int(np.prod(shape))
    counts = np.bincount(cell, minlength=size)
    if op == "count":
        return counts.reshape(shape)
    vals = meas[measure][mask]
    if op == "sum":
        out = np.zeros(size, dtype=vals.dtype)
        np.add.at(out, cell, vals)
        return out.reshape(shape)
    out = np.full(size, np.nan)
    for g in np.unique(cell):
        v = vals[cell == g]
        out[g] = {"avg": np.mean, "min": np.min, "max": np.max}[op](v)
    return out.reshape(shape)


@pytest.fixture(scope="module")
def table():
    return make_table()


@pytest.fixture(scope="module")
def sharded(table):
    rows, meas = table
    idx = ShardedIndex.build(rows, shard_rows=704, k=1, column_names=NAMES,
                             measures=meas)
    assert idx.n_shards == 3
    return idx


def _as_array(nested):
    return np.asarray([np.nan if v is None else v
                       for v in np.ravel(np.asarray(nested, dtype=object))])


def _check(got, expect, op):
    got = np.asarray(got)
    if op in ("count", "sum"):
        np.testing.assert_array_equal(got, expect)
    else:
        np.testing.assert_allclose(got, expect, rtol=1e-12, equal_nan=True)


@pytest.mark.parametrize("backend", ["ewah", "kernel"])
@pytest.mark.parametrize("by", [["a", "b", "c"], ["e", "c", "a", "b"]],
                         ids=["3cols", "4cols"])
@pytest.mark.parametrize("filt", list(FILTERS))
def test_statement_cube_matches_row_scan(table, sharded, backend, by, filt):
    rows, meas = table
    expr = FILTERS[filt]
    svc = QueryService(sharded, backend=backend, cache_entries=0)
    try:
        for op in OPS:
            measure = None if op == "count" else \
                ("p" if op == "avg" else "m")
            sel = {"count": True} if op == "count" else {op: measure}
            body = {"select": {**sel, "by": by}}
            if expr is not None:
                body["where"] = expr_to_json(expr)
            out = svc.statement(body)
            shape = [CARDS[NAMES.index(c)] for c in by]
            assert out["shape"] == shape
            expect_counts = row_scan(rows, meas, by, "count", None, filt)
            np.testing.assert_array_equal(np.asarray(out["counts"]),
                                          expect_counts)
            if op != "count":
                got = _as_array(out["values"]).reshape(shape)
                _check(got, row_scan(rows, meas, by, op, measure, filt), op)
                if op != "sum":  # empty cells serialize as null
                    flat = np.ravel(np.asarray(out["values"], dtype=object))
                    empty = np.ravel(expect_counts) == 0
                    assert all(v is None for v in flat[empty])
            if filt == "all_false":
                assert not np.asarray(out["counts"]).any()
    finally:
        svc.close()


@pytest.mark.parametrize("op", OPS)
def test_sql_group_by_three_columns(table, sharded, op):
    rows, meas = table
    svc = QueryService(sharded, backend="ewah", cache_entries=0)
    try:
        fn = "count(*)" if op == "count" else f"{op}(m)"
        sql = f"SELECT {fn} FROM t WHERE e BETWEEN 0 AND 3 GROUP BY a, b, c"
        assert parse_sql(sql)["select"]["by"] == ["a", "b", "c"]
        out = svc.sql(sql)
        key = "counts" if op == "count" else "values"
        got = _as_array(out[key]).reshape(out["shape"])
        _check(got, row_scan(rows, meas, ["a", "b", "c"], op, "m", "e_le_3"),
               op)
        via_json = svc.statement({
            "select": ({"count": True} if op == "count" else {op: "m"})
            | {"by": ["a", "b", "c"]},
            "where": {"op": "range", "col": "e", "lo": 0, "hi": 3}})
        assert via_json[key] == out[key]
    finally:
        svc.close()


@pytest.mark.parametrize("by", [("a", "b", "c"), ("d", "e", "a", "c")],
                         ids=["3cols", "4cols"])
@pytest.mark.parametrize("filt", list(FILTERS))
def test_dataset_group_by_cube(table, by, filt):
    rows, meas = table
    ds = Dataset.from_rows(rows, NAMES, shards=3, measures=meas)
    # from_rows sorts the table: the row scan reads the stored order
    stored = np.concatenate([sh.reconstruct_rows() for sh in ds.index.shards])
    smeas = {k: np.concatenate([np.asarray(sh.measures[k])
                                for sh in ds.index.shards]) for k in meas}
    expr = FILTERS[filt]
    q = ds.query() if expr is None else ds.query().where(expr)
    g = q.group_by(*by)
    np.testing.assert_array_equal(
        g.count(), row_scan(stored, smeas, list(by), "count", None, filt))
    _check(g.sum("m"), row_scan(stored, smeas, list(by), "sum", "m", filt),
           "sum")
    for op in ("avg", "min", "max"):
        _check(getattr(g, op)("p"),
               row_scan(stored, smeas, list(by), op, "p", filt), op)


def test_cluster_cube_matches_row_scan(table, sharded, tmp_path):
    from repro.launch.cluster import LocalCluster
    rows, meas = table
    d = str(tmp_path / "store")
    sharded.save(d)
    by = ["c", "a", "b"]
    expr = FILTERS["some"]
    with LocalCluster(d, n_workers=2, replication=2, start_monitor=False,
                      startup_timeout_s=120.0) as cluster:
        svc = cluster.service
        for op in OPS:
            measure = None if op == "count" else "m"
            out = svc.group_agg(op, measure, by, expr_to_json(expr))
            assert out["exact"] and out["missing_shards"] == []
            assert out["shape"] == [5, 4, 3]
            key = "counts" if op == "count" else "values"
            _check(_as_array(out[key]).reshape(out["shape"]),
                   row_scan(rows, meas, by, op, measure, "some"), op)
        st = svc.statement({"select": {"sum": "m", "by": by},
                            "where": expr_to_json(expr)})
        np.testing.assert_array_equal(
            np.asarray(st["values"]),
            row_scan(rows, meas, by, "sum", "m", "some"))


@pytest.mark.parametrize("shape", [(4, 3, 5), (2, 4, 3, 5)],
                         ids=["3cols", "4cols"])
def test_merge_and_finalize_keep_the_cube_shape(shape):
    """Shard partials of a cube merge elementwise and finalize flat; the
    row-major reshape by ``shape`` puts every cell where the row scan
    does."""
    rng = np.random.default_rng(len(shape))
    size = int(np.prod(shape))
    cols = tuple(range(len(shape)))
    parts, cells, vals = [], [], []
    for _ in range(3):
        part = empty_group_agg(cols, shape, "m", "<i8")
        cell = rng.integers(0, size, 40)
        val = rng.integers(-100, 100, 40)
        np.add.at(part["counts"], cell, 1)
        np.add.at(part["sums"], cell, val)
        np.minimum.at(part["mins"], cell, val)
        np.maximum.at(part["maxs"], cell, val)
        parts.append(part)
        cells.append(cell)
        vals.append(val)
    parts.append(empty_group_agg(cols, shape, "m", "<i8"))  # a row-less shard
    merged = merge_group_aggs(parts)
    assert merged["shape"] == shape
    cell, val = np.concatenate(cells), np.concatenate(vals)
    idx = np.unravel_index(cell, shape)
    counts = np.zeros(shape, dtype=np.int64)
    np.add.at(counts, idx, 1)
    np.testing.assert_array_equal(
        finalize_group("count", merged).reshape(shape), counts)
    sums = np.zeros(shape, dtype=np.int64)
    np.add.at(sums, idx, val)
    np.testing.assert_array_equal(
        finalize_group("sum", merged).reshape(shape), sums)
    mins = np.full(shape, np.inf)
    np.minimum.at(mins, idx, val)
    mins[counts == 0] = np.nan
    np.testing.assert_array_equal(
        finalize_group("min", merged).reshape(shape), mins)
    avg = finalize_group("avg", merged).reshape(shape)
    np.testing.assert_allclose(avg[counts > 0], (sums / np.maximum(
        counts, 1))[counts > 0])
    assert np.isnan(avg[counts == 0]).all()


# what the executor's former one- and two-column branches returned on this
# table: 3 shards, filter b <= 1, measure m
PINNED_TABLE_SEED = 20261018
PINNED = {
    ("a",): {"counts": [177, 129, 191, 182],
             "sums": [40172, 28749, 42779, 38336],
             "mins": [-42, -48, -48, -49], "maxs": [498, 493, 497, 499]},
    ("a", "b"): {
        "counts": [86, 91, 0, 66, 63, 0, 111, 80, 0, 81, 101, 0],
        "sums": [21062, 19110, 0, 13961, 14788, 0, 25950, 16829, 0, 16727,
                 21609, 0],
        "mins": [-40, -42, None, -48, -45, None, -35, -48, None, -44, -49,
                 None],
        "maxs": [490, 498, None, 493, 484, None, 497, 495, None, 499, 497,
                 None]},
}


@pytest.mark.parametrize("by", list(PINNED), ids=["1col", "2cols"])
def test_sweep_keeps_one_and_two_column_answers(by):
    rng = np.random.default_rng(PINNED_TABLE_SEED)
    n = 1000
    rows = np.column_stack([rng.integers(0, c, n) for c in (4, 3)])
    rows = rows[np.lexsort((rows[:, 1] // 2, rows[:, 0] // 3))]
    m = rng.integers(-50, 500, n).astype(np.int64)
    idx = ShardedIndex.build(rows.astype(np.int64), shard_rows=352, k=1,
                             column_names=["a", "b"], measures={"m": m})
    assert idx.n_shards == 3
    g = execute_group_agg(idx, "m", list(by), col("b") <= 1, backend="ewah")
    want = PINNED[by]
    assert g["counts"].tolist() == want["counts"]
    assert g["sums"].tolist() == want["sums"]
    for op in ("min", "max"):
        np.testing.assert_array_equal(
            finalize_group(op, g),
            np.asarray([np.nan if v is None else v for v in want[op + "s"]],
                       dtype=np.float64))


# -- the sweep's two catalog builders ----------------------------------------

BUILD_NAMES = ["x", "y", "z"]
BUILD_CARDS = [6, 4, 5]
BUILD_ROWS = 3000
BUILD_MASKS = {
    "nothing": lambda rng: np.zeros(BUILD_ROWS, dtype=bool),
    "one_row": lambda rng: np.arange(BUILD_ROWS) == 1234,
    "every_row": lambda rng: np.ones(BUILD_ROWS, dtype=bool),
    "scattered_3pct": lambda rng: rng.random(BUILD_ROWS) < 0.03,
}


@pytest.fixture(scope="module")
def build_tables():
    """(rows, measure, index) per table order and k, built once: uniform
    rows in drawing order, or sorted lexicographically, cut into 3
    shards; int64 measure values whose sums need every bit."""
    made = {}

    def get(order, k):
        if (order, k) not in made:
            rng = np.random.default_rng(20261019)
            rows = np.column_stack([rng.integers(0, c, BUILD_ROWS)
                                    for c in BUILD_CARDS]).astype(np.int64)
            if order == "lex":
                rows = rows[np.lexsort(rows.T[::-1])]
            m = rng.integers(-10**12, 10**12, BUILD_ROWS)
            idx = ShardedIndex.build(rows, shard_rows=1024, k=k,
                                     column_names=BUILD_NAMES,
                                     measures={"m": m})
            assert idx.n_shards == 3
            made[order, k] = rows, m, idx
        return made[order, k]
    return get


def _cube_by(builder, idx, by, mask):
    """The cube of measure ``m`` by ``by`` over the rows ``mask`` selects,
    every column's catalog built by ``builder`` ("interval", "rank" or
    ``None`` for the executor's own choice), merged over the shards."""
    parts, off = [], 0
    for sh in idx.shards:
        sel = mask[off:off + sh.n_rows]
        off += sh.n_rows
        node = Planner(sh).plan_group_agg("m", by, None)
        ex = Executor(sh, backend="ewah")
        if builder is None:
            parts.append(ex.run_group_agg(PGroupAgg(
                "m", node.cols, node.groups, PPinned(EWAH.from_bool(sel)))))
            continue
        cards = tuple(len(g) for g in node.groups)
        out = empty_group_agg(node.cols, cards, "m", "<i8")
        fs, fe = EWAH.from_bool(sel).set_intervals()
        F = int((fe - fs).sum())
        if F:
            cols = [[ex._run(gn) for gn in g] for g in node.groups]
            if builder == "rank":
                pos = interval_positions(fs, fe)
                cats = [_rank_catalog(bms, pos) for bms in cols]
            else:
                cats = [_interval_catalog(bms, fs, fe)[:2] for bms in cols]
            _bin_segments(cats, cards, F, gather(sh.measure("m"), fs, fe),
                          out)
        parts.append(out)
    return merge_group_aggs(parts)


@pytest.mark.parametrize("mask", list(BUILD_MASKS))
@pytest.mark.parametrize("by", [["y"], ["z", "x"], ["x", "y", "z"]],
                         ids=["1col", "2cols", "3cols"])
@pytest.mark.parametrize("k", [1, 2], ids=["k1", "k2"])
@pytest.mark.parametrize("order", ["unsorted", "lex"])
def test_catalog_builders_match_row_scan(build_tables, order, k, by, mask):
    rows, m, idx = build_tables(order, k)
    sel = BUILD_MASKS[mask](np.random.default_rng(7))
    shape = [BUILD_CARDS[BUILD_NAMES.index(c)] for c in by]
    size = int(np.prod(shape))
    cell = np.ravel_multi_index(
        tuple(rows[sel, BUILD_NAMES.index(c)] for c in by), shape)
    counts = np.bincount(cell, minlength=size)
    sums = np.zeros(size, dtype=np.int64)
    np.add.at(sums, cell, m[sel])
    mins, maxs = np.full(size, np.inf), np.full(size, -np.inf)
    np.minimum.at(mins, cell, m[sel])
    np.maximum.at(maxs, cell, m[sel])
    mins[counts == 0] = maxs[counts == 0] = np.nan
    for builder in ("interval", "rank", None):
        got = _cube_by(builder, idx, by, sel)
        assert got["shape"] == tuple(shape), builder
        np.testing.assert_array_equal(got["counts"], counts, err_msg=builder)
        assert got["sums"].dtype == np.int64
        np.testing.assert_array_equal(got["sums"], sums, err_msg=builder)
        np.testing.assert_array_equal(finalize_group("min", got), mins,
                                      err_msg=builder)
        np.testing.assert_array_equal(finalize_group("max", got), maxs,
                                      err_msg=builder)


@pytest.mark.parametrize("order", ["unsorted", "lex"])
def test_the_builder_chosen_shows_in_the_counters(order):
    """An unsorted table's literal-heavy columns are ranked at a scattered
    filter's rows; a lex-sorted table's runs keep the interval path, and
    so does every unfiltered column (ranking would touch every row).
    Every aggregate that selects rows adds catalog entries."""
    rng = np.random.default_rng(11)
    n, cards = 20000, (8, 6, 5, 6, 6)
    rows = np.column_stack([rng.integers(0, c, n) for c in cards])
    if order == "lex":
        rows = rows[np.lexsort(rows.T[::-1])]
    idx = ShardedIndex.build(rows.astype(np.int64), shard_rows=10016, k=1,
                             column_names=list("abcde"),
                             measures={"m": rng.integers(0, 100, n)})
    filt = (col("d") == 0) & (col("e") == 1)
    names = ("executor.group_aggs", "executor.group_intervals",
             "executor.group_rows_ranked")
    for sh in idx.shards:
        for expr in (filt, None):
            node = Planner(sh).plan_group_agg("m", ["a", "b", "c"], expr)
            c0 = trace.counters()
            got = Executor(sh, backend="ewah").run_group_agg(node)
            c1 = trace.counters()
            aggs, intervals, ranked = (c1.get(k, 0) - c0.get(k, 0)
                                       for k in names)
            F = int(got["counts"].sum())
            assert F > 0 and aggs == 1 and intervals > 0
            ranks = order == "unsorted" and expr is not None
            assert ranked == (3 * F if ranks else 0), (order, expr)
