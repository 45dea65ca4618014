"""TPC-H ``lineitem`` as DBGEN writes it, in arrival (orderkey) order.

Column domains from the TPC-H specification, section 4.2.3:

* ``O_ORDERDATE`` uniform over [1992-01-01, 1998-12-31 - 151 days], one per
  order; each order has U[1, 7] lines, in orderkey order (DBGEN does not
  sort orders by date);
* ``L_SHIPDATE`` = orderdate + U[1, 121]; ``L_RECEIPTDATE`` = shipdate +
  U[1, 30];
* ``L_RETURNFLAG`` R or A (even odds) when receiptdate <= CURRENTDATE
  (1995-06-17), else N; ``L_LINESTATUS`` O when shipdate > CURRENTDATE,
  else F;
* ``L_QUANTITY`` U[1, 50]; ``L_DISCOUNT`` U[0.00, 0.10] in steps of 0.01;
  ``L_SHIPINSTRUCT`` one of 4 strings, ``L_SHIPMODE`` one of 7;
* ``L_PARTKEY`` U[1, SF * 200,000]; ``L_EXTENDEDPRICE`` = quantity *
  P_RETAILPRICE(partkey), with P_RETAILPRICE = (90000 + ((partkey / 10)
  mod 20001) + 100 * (partkey mod 1000)) / 100.

Stored as ranks: dates as days since 1992-01-01, discount in hundredths,
quantity as itself, each text column as its position in the spec's list;
the extended price in integer cents.
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

RETURNFLAGS = ("R", "A", "N")
LINESTATUS = ("O", "F")
SHIPINSTRUCT = ("DELIVER IN PERSON", "COLLECT COD", "NONE",
                "TAKE BACK RETURN")
SHIPMODE = ("REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB")

# days since 1992-01-01
STARTDATE = 0
ORDERDATE_MAX = 2405          # 1998-08-02 = 1998-12-31 - 151 days
CURRENTDATE = 1263            # 1995-06-17
LINES_PER_ORDER = (1, 7)
ORDERS_PER_SF = 1_500_000
PARTS_PER_SF = 200_000


def retail_price_cents(partkey: np.ndarray) -> np.ndarray:
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def generate(cfg: Dict, seed_words, rows: Optional[int] = None) -> Dict:
    """Columns and measures of lineitem at ``cfg["scale_factor"]``, or of
    about ``rows`` rows (whole orders) when given."""
    sf = cfg["scale_factor"]
    orders = int(ORDERS_PER_SF * sf) if rows is None else max(rows // 4, 1)
    rng = np.random.default_rng(seed_words)
    lines = rng.integers(LINES_PER_ORDER[0], LINES_PER_ORDER[1] + 1,
                         size=orders)
    orderdate = np.repeat(
        rng.integers(STARTDATE, ORDERDATE_MAX + 1, size=orders), lines)
    n = len(orderdate)
    shipdate = orderdate + rng.integers(1, 122, size=n)
    receiptdate = shipdate + rng.integers(1, 31, size=n)
    returnflag = np.where(receiptdate <= CURRENTDATE,
                          rng.integers(0, 2, size=n), 2)
    linestatus = np.where(shipdate > CURRENTDATE, 0, 1)
    quantity = rng.integers(1, 51, size=n)
    discount = rng.integers(0, 11, size=n)
    shipinstruct = rng.integers(0, len(SHIPINSTRUCT), size=n)
    shipmode = rng.integers(0, len(SHIPMODE), size=n)
    partkey = rng.integers(1, int(PARTS_PER_SF * sf) + 1, size=n)
    columns = {
        "l_returnflag": returnflag, "l_linestatus": linestatus,
        "l_shipmode": shipmode, "l_shipinstruct": shipinstruct,
        "l_discount": discount, "l_quantity": quantity,
        "l_shipdate": shipdate,
    }
    measures = {
        "l_extendedprice": quantity * retail_price_cents(partkey),
        "l_quantity": quantity.copy(),
    }
    return {"columns": {k: v.astype(np.int64) for k, v in columns.items()},
            "measures": {k: v.astype(np.int64) for k, v in measures.items()}}
