"""Star Schema Benchmark ``lineorder`` with its dimension attributes on it.

O'Neil, O'Neil, Chen, Revilak, "The Star Schema Benchmark and Augmented
Fact Table Indexing" (TPCTC 2009), written from the specification:

* ``customer``: 30,000 x SF rows, ``C_NATIONKEY`` uniform over TPC-H's 25
  nations, ``C_REGION`` the nation's region;
* ``supplier``: 2,000 x SF rows, nation and region the same way;
* ``part``: 200,000 rows at SF1, ``P_MFGR`` MFGR#1..5 uniform,
  ``P_CATEGORY`` MFGR#mn with n in 1..5 uniform (25 values, 5 per
  manufacturer), ``P_RETAILPRICE`` TPC-H's formula of the part key;
* ``lineorder``: 1,500,000 x SF orders of U[1, 7] lines, in orderkey order;
  per order ``LO_CUSTKEY`` and ``LO_ORDERDATE`` (uniform over 1992-01-01 ..
  1998-08-02, TPC-H's order dates); per line ``LO_PARTKEY``,
  ``LO_SUPPKEY``, ``LO_QUANTITY`` U[1, 50], ``LO_DISCOUNT`` U[0, 10];
  ``LO_EXTENDEDPRICE`` = quantity x ``P_RETAILPRICE``, ``LO_REVENUE`` =
  extendedprice x (100 - discount) / 100, ``LO_SUPPLYCOST`` = 6 x
  ``P_RETAILPRICE`` / 10.

The paper's augmented fact table: each dimension attribute a query filters
or groups on is copied onto its ``lineorder`` row through the foreign key
at load time, and the index over those copies is a bitmap-join index.
Stored as ranks: a region or nation as its TPC-H key, ``d_year`` as years
since 1992, ``p_mfgr`` as m - 1 and ``p_category`` as 5 (m - 1) + (n - 1);
money in integer cents (revenue and supply cost rounded down).
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from generators.tpch_lineitem import (ORDERDATE_MAX, STARTDATE,
                                      retail_price_cents)

ORDERS_PER_SF = 1_500_000
CUSTOMERS_PER_SF = 30_000
SUPPLIERS_PER_SF = 2_000
PARTS = 200_000               # at SF1: 200,000 x floor(1 + log2 SF)
LINES_PER_ORDER = (1, 7)
MFGRS = 5
CATEGORIES_PER_MFGR = 5

# TPC-H 4.2.3 NATION: N_REGIONKEY of nation keys 0..24
# (0 AFRICA, 1 AMERICA, 2 ASIA, 3 EUROPE, 4 MIDDLE EAST)
NATION_REGION = np.asarray([0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0,
                            0, 1, 2, 3, 4, 2, 3, 3, 1], dtype=np.int64)
# 1 January of 1992..1999, days since 1992-01-01
YEAR_STARTS = np.asarray([0, 366, 731, 1096, 1461, 1827, 2192, 2557])


def year_rank(days: np.ndarray) -> np.ndarray:
    """Years since 1992 of a date given as days since 1992-01-01."""
    return np.searchsorted(YEAR_STARTS, days, side="right") - 1


def draw(cfg: Dict, seed_words, rows: Optional[int] = None) -> Dict:
    """The dimension tables (indexed by key - 1) and ``lineorder``'s own
    columns, drawn from the seed: dimensions first, then the orders in
    orderkey order, at ``cfg["scale_factor"]`` or about ``rows`` rows
    (whole orders); the dimensions keep their sizes at the scale factor."""
    sf = cfg["scale_factor"]
    orders = int(ORDERS_PER_SF * sf) if rows is None else max(rows // 4, 1)
    rng = np.random.default_rng(seed_words)
    c_nation = rng.integers(0, 25, size=int(CUSTOMERS_PER_SF * sf))
    s_nation = rng.integers(0, 25, size=int(SUPPLIERS_PER_SF * sf))
    p_mfgr = rng.integers(0, MFGRS, size=PARTS)
    p_category = p_mfgr * CATEGORIES_PER_MFGR + rng.integers(
        0, CATEGORIES_PER_MFGR, size=PARTS)
    lines = rng.integers(LINES_PER_ORDER[0], LINES_PER_ORDER[1] + 1,
                         size=orders)
    custkey = np.repeat(rng.integers(1, len(c_nation) + 1, size=orders),
                        lines)
    orderdate = np.repeat(
        rng.integers(STARTDATE, ORDERDATE_MAX + 1, size=orders), lines)
    n = len(orderdate)
    partkey = rng.integers(1, PARTS + 1, size=n)
    suppkey = rng.integers(1, len(s_nation) + 1, size=n)
    quantity = rng.integers(1, 51, size=n)
    discount = rng.integers(0, 11, size=n)
    retail = retail_price_cents(partkey)
    extendedprice = quantity * retail
    return {
        "c_nation": c_nation, "s_nation": s_nation, "p_mfgr": p_mfgr,
        "p_category": p_category,
        "lo_orderkey": np.repeat(np.arange(1, orders + 1), lines),
        "lo_custkey": custkey, "lo_orderdate": orderdate,
        "lo_partkey": partkey, "lo_suppkey": suppkey,
        "lo_quantity": quantity, "lo_discount": discount,
        "lo_extendedprice": extendedprice,
        "lo_revenue": extendedprice * (100 - discount) // 100,
        "lo_supplycost": 6 * retail // 10,
    }


def require_cubes() -> None:
    """Stop the run before it generates anything where the program's
    statement parser refuses a three-column ``by``: every statement of the
    cell's mix groups by three columns, and each would be answered with
    HTTP 400."""
    from repro.serve.query_api import parse_statement
    try:
        parse_statement({"select": {"count": True, "by": ["a", "b", "c"]}})
    except ValueError as exc:
        raise SystemExit(f"the program cannot group by three columns: {exc}")


def generate(cfg: Dict, seed_words, rows: Optional[int] = None) -> Dict:
    """Columns and measures of the augmented ``lineorder`` (see ``draw``):
    the load-time join copies each dimension attribute onto its rows
    through the foreign key."""
    require_cubes()
    t = draw(cfg, seed_words, rows)
    cn = t["c_nation"][t["lo_custkey"] - 1]
    sn = t["s_nation"][t["lo_suppkey"] - 1]
    part = t["lo_partkey"] - 1
    columns = {
        "c_region": NATION_REGION[cn], "c_nation": cn,
        "s_region": NATION_REGION[sn], "s_nation": sn,
        "d_year": year_rank(t["lo_orderdate"]),
        "p_mfgr": t["p_mfgr"][part], "p_category": t["p_category"][part],
    }
    measures = {k: t[k] for k in ("lo_revenue", "lo_supplycost")}
    return {"columns": {k: v.astype(np.int64) for k, v in columns.items()},
            "measures": {k: v.astype(np.int64) for k, v in measures.items()}}
