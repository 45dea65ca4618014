"""The Star Schema Benchmark cell: its generator against the specification,
the reference's three-column cubes, and a whole run at a tiny size."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import reference  # noqa: E402
import run  # noqa: E402
from generators import ssb_lineorder as ssb  # noqa: E402
from generators import tpch_lineitem as li  # noqa: E402
from traffic import Traffic  # noqa: E402

ROOT = Path(__file__).resolve().parents[3]
CELL = "ssb-arrival.q31-q42-cubes"
# TPC-H 4.2.3: the five nations of each region, by nation key
REGION_NATIONS = {0: {0, 5, 14, 15, 16}, 1: {1, 2, 3, 17, 24},
                  2: {8, 9, 12, 18, 21}, 3: {6, 7, 19, 22, 23},
                  4: {4, 10, 11, 13, 20}}


@pytest.fixture(scope="module")
def spec():
    return run.load_spec(CELL, ROOT)


@pytest.fixture(scope="module")
def drawn(spec):
    cfg = spec["config"]
    return cfg, ssb.draw(cfg, [7, 0, 0], rows=40_000), \
        ssb.generate(cfg, [7, 0, 0], rows=40_000)


def test_nation_region_map_is_tpch():
    for region, nations in REGION_NATIONS.items():
        assert set(np.flatnonzero(ssb.NATION_REGION == region)) == nations


def test_year_ranks():
    days = np.asarray([0, 365, 366, 2191, 2192, li.ORDERDATE_MAX])
    assert ssb.year_rank(days).tolist() == [0, 0, 1, 5, 6, 6]


def test_domains_and_lines_per_order(drawn):
    cfg, t, g = drawn
    cols = g["columns"]
    assert list(cols) == cfg["columns"]
    assert set(g["measures"]) == set(cfg["measures"])
    for name, card in cfg["domains"].items():
        # every rank of every domain appears at 40,000 rows
        assert set(np.unique(cols[name])) == set(range(card)), name
    n = len(cols["c_nation"])
    assert all(len(v) == n for v in list(cols.values()) +
               list(g["measures"].values()))
    ok = t["lo_orderkey"]
    assert (np.diff(ok) >= 0).all()  # orderkey order
    per_order = np.bincount(ok)[1:]
    assert per_order.min() == 1 and per_order.max() == 7
    assert 3.8 < per_order.mean() < 4.2
    # one customer and one date per order
    first = np.r_[0, np.flatnonzero(np.diff(ok)) + 1]
    for key in ("lo_custkey", "lo_orderdate"):
        v = t[key]
        assert (v == np.repeat(v[first], per_order)).all(), key
    assert len(t["c_nation"]) == ssb.CUSTOMERS_PER_SF
    assert len(t["s_nation"]) == ssb.SUPPLIERS_PER_SF
    assert len(t["p_mfgr"]) == ssb.PARTS
    assert t["lo_orderdate"].min() >= li.STARTDATE
    assert t["lo_orderdate"].max() <= li.ORDERDATE_MAX


def test_join_consistency(drawn):
    _, t, g = drawn
    c = g["columns"]
    assert (c["c_region"] == ssb.NATION_REGION[c["c_nation"]]).all()
    assert (c["s_region"] == ssb.NATION_REGION[c["s_nation"]]).all()
    assert (c["p_category"] // 5 == c["p_mfgr"]).all()
    assert (c["c_nation"] == t["c_nation"][t["lo_custkey"] - 1]).all()
    assert (c["s_nation"] == t["s_nation"][t["lo_suppkey"] - 1]).all()
    assert (c["p_mfgr"] == t["p_mfgr"][t["lo_partkey"] - 1]).all()
    assert (c["p_category"] == t["p_category"][t["lo_partkey"] - 1]).all()
    # d_year is the calendar year of the order date
    import datetime as dt
    d0 = dt.date(1992, 1, 1)
    for day in np.unique(t["lo_orderdate"])[::97]:
        year = (d0 + dt.timedelta(days=int(day))).year
        assert ssb.year_rank(np.asarray([day]))[0] == year - 1992


def test_measures_follow_the_formulas(drawn):
    _, t, g = drawn
    m = g["measures"]
    retail = li.retail_price_cents(t["lo_partkey"])
    assert (t["lo_extendedprice"] == t["lo_quantity"] * retail).all()
    assert (m["lo_revenue"] == t["lo_extendedprice"] *
            (100 - t["lo_discount"]) // 100).all()
    assert (m["lo_supplycost"] == 6 * retail // 10).all()
    assert t["lo_discount"].min() == 0 and t["lo_discount"].max() == 10
    assert t["lo_quantity"].min() == 1 and t["lo_quantity"].max() == 50
    assert m["lo_revenue"].dtype == np.int64


def test_same_seed_same_table(spec):
    cfg = spec["config"]
    a = run.generate(cfg, 2**33 + 7, 3000)
    b = run.generate(cfg, 2**33 + 7, 3000)
    c = run.generate(cfg, 2**33 + 8, 3000)
    assert all(np.array_equal(a["columns"][k], b["columns"][k])
               for k in cfg["columns"])
    assert not np.array_equal(a["measures"]["lo_revenue"],
                              c["measures"]["lo_revenue"])


def test_reference_three_column_cube_is_a_loop_over_cells():
    rng = np.random.default_rng(5)
    n = 600
    cols = {"x": rng.integers(0, 3, n), "y": rng.integers(0, 4, n),
            "z": rng.integers(0, 2, n), "w": rng.integers(0, 5, n)}
    meas = {"v": rng.integers(-20, 100, n)}
    ref = reference.Reference(cols, meas)
    where = {"op": "range", "col": "w", "lo": 1, "hi": 3}
    mask = (cols["w"] >= 1) & (cols["w"] <= 3)
    for op in ("sum", "avg", "min", "max"):
        got = ref.answer({"select": {op: "v", "by": ["y", "x", "z"]},
                          "where": where})
        assert got["shape"] == [4, 3, 2]
        for iy in range(4):
            for ix in range(3):
                for iz in range(2):
                    sel = mask & (cols["y"] == iy) & (cols["x"] == ix) & \
                        (cols["z"] == iz)
                    vals = meas["v"][sel]
                    assert got["counts"][iy][ix][iz] == int(sel.sum())
                    if op == "sum":
                        want = int(vals.sum())
                    elif not len(vals):
                        want = None
                    else:
                        want = {"avg": float(vals.mean()),
                                "min": int(vals.min()),
                                "max": int(vals.max())}[op]
                    cell = got["values"][iy][ix][iz]
                    if op == "avg" and want is not None:
                        assert cell == pytest.approx(want)
                    else:
                        assert cell == want


def test_every_template_matches_the_program(spec, tmp_path):
    from repro.core import ShardedIndex
    from repro.serve.query_api import QueryService
    cfg = spec["config"]
    t = run.generate(cfg, 3, 4000)
    names = cfg["columns"]
    mat = np.stack([t["columns"][c] for c in names], axis=1)
    index = ShardedIndex.build(mat, shard_rows=-(-len(mat) // 128) * 32,
                               k=cfg["k"], column_names=names,
                               measures=t["measures"])
    svc = QueryService(index, backend="ewah", cache_entries=0)
    ref = reference.Reference(t["columns"], t["measures"])
    traffic = Traffic(spec["mix"], cfg["domains"])
    assert traffic.deck_size(0) == 10 and traffic.deck_size(1) == 20
    try:
        seen = set()
        for i in range(3 * len(traffic.block)):
            tpl, body = traffic.statement(9, i)
            seen.add(tpl)
            got = svc.statement(body)
            assert got["shape"] == [25, 25, 7] or got["shape"] == [7, 25, 25]
            assert reference.matches(ref.answer(body), got), (tpl, body)
        assert seen == {"q31_revenue", "q42_revenue", "q42_supplycost"}
    finally:
        svc.close()


@pytest.mark.parametrize("trace", [False, True])
def test_run_of_the_ssb_cell(spec, trace, tmp_path):
    res = run.run_cell(spec, 2**33 + 5, 1.5, trace, rows=3000,
                       compile_cache=False, warm=False, run_dir=tmp_path)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    m = res["metrics"]
    if trace:
        assert set(m) == {"executor.group_intervals_per_agg",
                          "executor.group_segments_per_agg"}
        assert all(v["value"] > 0 for v in m.values())
    else:
        assert set(m) == {"stmts_per_s", "stmt_p50_ms", "setup_s"}


def test_a_program_without_cubes_stops_the_run(spec, monkeypatch):
    ssb.require_cubes()  # this program groups by three columns
    from repro.serve import query_api

    def refuse(obj):
        raise ValueError("'by' must list 1 or 2 grouping columns")

    monkeypatch.setattr(query_api, "parse_statement", refuse)
    with pytest.raises(SystemExit, match="cannot group by three columns"):
        run.generate(spec["config"], 5, 100)
