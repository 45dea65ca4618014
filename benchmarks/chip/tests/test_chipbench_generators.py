"""The table generators against their sources' constants."""
import datetime as dt
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import run  # noqa: E402
from generators import tpch_lineitem as li  # noqa: E402

ROOT = Path(__file__).resolve().parents[3]


def _days(y, m, d):
    return (dt.date(y, m, d) - dt.date(1992, 1, 1)).days


def test_lineitem_spec_dates():
    # 1998-12-31 - 151 days, and TPC-H CURRENTDATE
    assert li.ORDERDATE_MAX == _days(1998, 8, 2)
    assert li.CURRENTDATE == _days(1995, 6, 17)


@pytest.fixture(scope="module")
def lineitem():
    cfg = run.load_spec("lineitem-arrival.dense-filters", ROOT)["config"]
    return cfg, li.generate(cfg, [5, 0, 0], rows=40_000)


def test_lineitem_row_count_and_domains(lineitem):
    cfg, t = lineitem
    c, m = t["columns"], t["measures"]
    n = len(c["l_quantity"])
    # 10,000 orders of U[1, 7] lines: 4 lines an order on average
    assert 3.8 * 10_000 < n < 4.2 * 10_000
    assert set(c) == set(cfg["columns"]) and set(m) == set(cfg["measures"])
    for name, card in cfg["domains"].items():
        assert 0 <= c[name].min() and c[name].max() < card, name
    assert c["l_quantity"].min() == 1 and c["l_quantity"].max() == 50
    assert set(np.unique(c["l_discount"])) == set(range(11))
    assert c["l_shipdate"].min() >= 1
    assert c["l_shipdate"].max() <= _days(1998, 12, 1)
    assert set(np.unique(c["l_shipmode"])) == set(range(7))
    assert set(np.unique(c["l_shipinstruct"])) == set(range(4))
    # linestatus O exactly when shipped after CURRENTDATE
    assert np.array_equal(c["l_linestatus"] == 0,
                          c["l_shipdate"] > li.CURRENTDATE)
    # returnflag N exactly when the receipt can fall after CURRENTDATE:
    # receipt = ship + U[1, 30], so every N ships after CURRENTDATE - 30
    n_flag = c["l_returnflag"] == 2
    assert (c["l_shipdate"][n_flag] > li.CURRENTDATE - 30).all()
    assert (c["l_shipdate"][~n_flag] < li.CURRENTDATE).all()


def test_lineitem_prices(lineitem):
    _, t = lineitem
    price = t["measures"]["l_extendedprice"]
    q = t["measures"]["l_quantity"]
    retail = price // q
    assert np.array_equal(price, q * retail)
    assert retail.min() >= 90000 and retail.max() <= 90000 + 20000 + 99900
    assert li.retail_price_cents(np.asarray([1, 1000, 199999])).tolist() == [
        90100, 90100, 90000 + 19999 + 99900]


def test_lineitem_sf1_order_count():
    assert li.ORDERS_PER_SF == 1_500_000


def test_lex_config_is_the_arrival_table_sorted():
    arrival = run.load_spec("lineitem-arrival.dense-filters", ROOT)["config"]
    lex = run.load_spec("lineitem-lex.dense-filters", ROOT)["config"]
    for key in ("generator", "scale_factor", "orders", "columns", "measures",
                "domains", "k", "shards", "reduced"):
        assert lex[key] == arrival[key], key
    assert arrival["sort"] is None and lex["sort"] == lex["columns"]
    assert arrival["service"]["backend"] == "auto"
    assert lex["service"]["backend"] == "kernel"
    a = run.generate(arrival, 2**31 + 9, 3000)
    b = run.generate(lex, 2**31 + 9, 3000)
    for name in arrival["columns"]:
        assert np.array_equal(a["columns"][name], b["columns"][name])
