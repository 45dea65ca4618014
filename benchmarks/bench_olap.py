"""OLAP measure benchmark: compressed-domain aggregates vs decompress-then-
NumPy.

The tentpole claim of the measure sidecar: sum/avg/min/max and multi-column
group-by evaluate *in the compressed domain* — the filter's run intervals
slice the mmap-able measure arrays directly (``reduce_intervals``:
``add.reduceat`` over contiguous slices), and grouped aggregates intersect
value-bitmap intervals per group — with no row ids materialized and no
dimension column decoded.  The baseline any row-oriented engine pays:
decompress the filter bitmap to row positions, gather the measure by fancy
indexing (scalar case), and for group-bys first *decode the dimension
columns back out of the bitmaps* before a NumPy ``add.at`` histogram.

Asserted (and recorded in ``BENCH_olap.json``, a CI artifact):

* every compressed-domain aggregate is **bit-exact** against the NumPy
  star-schema row oracle (boolean masks over the sorted fact table);
* on the sorted table, the compressed-domain filtered SUM and the
  two-column grouped SUM each beat decompress-then-NumPy by >= 2x;
* the sharded path returns the identical scalar/matrix, and sum-ranked
  top-k agrees between the monolithic and shard-pruned implementations.

    PYTHONPATH=src python benchmarks/bench_olap.py [--tiny] \
        [--out BENCH_olap.json]
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core import Dataset, col, execute
from repro.core import statement
from repro.core.measures import finalize_group

try:  # package-style and script-style execution both work
    from .common import emit
except ImportError:  # pragma: no cover
    from common import emit


def _make(n: int, rng: np.random.Generator):
    """Star-schema-shaped fact table: 3 dimension columns + 2 measures."""
    t = np.stack([rng.integers(0, 8, n),
                  (rng.pareto(1.2, n) * 12).astype(np.int64) % 48,
                  (rng.pareto(1.2, n) * 80).astype(np.int64) % 512],
                 axis=1)
    sales = rng.integers(0, 10_000, n).astype(np.int64)
    ds = Dataset.from_rows(t, ["region", "day", "user"], sort="lex", k=1,
                           measures={"sales": sales})
    return ds


def decode_column(index, c: int) -> np.ndarray:
    """Decompress one dimension column out of its value bitmaps — what a
    row engine must do before it can group on a bitmap-stored column."""
    out = np.empty(index.n_rows, dtype=np.int64)
    for b in range(index.card(c)):
        out[index.bitmap(c, b).set_bits()] = b
    return out


def baseline_sum(index, vals: np.ndarray, e) -> int:
    """Decompress-then-NumPy: filter bitmap -> row ids -> gather + sum."""
    ids = execute(index, e).set_bits()
    return int(vals[ids].sum())


def baseline_group_sum(index, vals: np.ndarray, ca: int, cb: int,
                       e) -> np.ndarray:
    """Decode both dimension columns from bitmaps, then ``np.add.at``."""
    a = decode_column(index, ca)
    b = decode_column(index, cb)
    ids = execute(index, e).set_bits()
    out = np.zeros((index.card(ca), index.card(cb)), dtype=np.int64)
    np.add.at(out, (a[ids], b[ids]), vals[ids])
    return out


def _median_time(fn, repeats: int = 5) -> float:
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def run(n: int = 200_000, out_path: str = "BENCH_olap.json") -> dict:
    rng = np.random.default_rng(0)
    ds = _make(n, rng)
    ds_sh = ds.shard(4)
    st = ds.table
    idx = ds.index
    vals = np.asarray(idx.measures["sales"])
    results: dict = {"n_rows": n,
                     "cards": [ds.card(c) for c in range(3)],
                     "sort_order": ds.sort_order}

    # -- filtered scalar SUM -------------------------------------------------
    region = int(st[n // 2, 0])  # a populous region (one long sorted run)
    e = col("region") == region
    mask = st[:, 0] == region
    oracle = int(vals[mask].sum())

    assert ds.query().where(e).sum("sales") == oracle
    assert baseline_sum(idx, vals, e) == oracle
    assert ds_sh.query().where(e).sum("sales") == oracle

    comp_s = _median_time(lambda: statement.run(idx, ("agg", "sales", e)))
    base_s = _median_time(lambda: baseline_sum(idx, vals, e))
    sum_speedup = base_s / comp_s
    results["sum"] = {
        "selected_rows": int(mask.sum()),
        "compressed_s": round(comp_s, 6),
        "decompress_numpy_s": round(base_s, 6),
        "speedup": round(sum_speedup, 2),
    }
    emit("olap_sum_compressed", comp_s * 1e6,
         f"{sum_speedup:.1f}x_vs_decompress")

    # avg/min/max ride the same partials — assert exactness, skip timing
    assert ds.query().where(e).avg("sales") == oracle / int(mask.sum())
    assert ds.query().where(e).min("sales") == int(vals[mask].min())
    assert ds.query().where(e).max("sales") == int(vals[mask].max())

    # -- two-column grouped SUM ----------------------------------------------
    ca, cb = 1, 0  # day x region
    g_oracle = np.zeros((ds.card(ca), ds.card(cb)), dtype=np.int64)
    np.add.at(g_oracle, (st[mask, ca], st[mask, cb]), vals[mask])

    comp = np.asarray(ds.query().where(e).group_by("day", "region")
                      .sum("sales"))
    assert np.array_equal(comp, g_oracle), "compressed group sum != oracle"
    assert np.array_equal(baseline_group_sum(idx, vals, ca, cb, e), g_oracle)
    assert np.array_equal(
        np.asarray(ds_sh.query().where(e).group_by("day", "region")
                   .sum("sales")), g_oracle)

    gcomp_s = _median_time(lambda: statement.run(
        idx, statement.group_task("sales", [ca, cb], idx.resolve_column, e)))
    gbase_s = _median_time(lambda: baseline_group_sum(idx, vals, ca, cb, e))
    g_speedup = gbase_s / gcomp_s
    results["group_sum_2col"] = {
        "shape": [ds.card(ca), ds.card(cb)],
        "compressed_s": round(gcomp_s, 6),
        "decompress_numpy_s": round(gbase_s, 6),
        "speedup": round(g_speedup, 2),
    }
    emit("olap_group_sum_compressed", gcomp_s * 1e6,
         f"{g_speedup:.1f}x_vs_decompress")

    # -- shard-pruned top-k agreement ----------------------------------------
    agg = statement.run(idx, ("gagg", "sales", (2,), None))
    from repro.core.dataset import top_k_from_values
    expect = top_k_from_values(finalize_group("sum", agg),
                               np.asarray(agg["counts"]), 10)
    pruned = ds_sh.query().top_k("user", 10, measure="sales")
    assert pruned == expect, "shard-pruned top-k disagrees with full merge"
    topk_s = _median_time(
        lambda: ds_sh.query().top_k("user", 10, measure="sales"))
    results["top_k_measure"] = {"k": 10, "sharded_warm_s": round(topk_s, 6)}
    emit("olap_top_k_measure_sharded", topk_s * 1e6, "k_10")

    # -- gates ---------------------------------------------------------------
    assert sum_speedup >= 2.0, (
        f"compressed-domain SUM must beat decompress-then-NumPy >= 2x on "
        f"the sorted table: {comp_s * 1e3:.2f}ms vs {base_s * 1e3:.2f}ms")
    assert g_speedup >= 2.0, (
        f"compressed-domain 2-col grouped SUM must beat decompress-then-"
        f"NumPy >= 2x: {gcomp_s * 1e3:.2f}ms vs {gbase_s * 1e3:.2f}ms")

    with open(out_path, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {out_path}")
    return results


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CI-sized run (fast, same asserts)")
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--out", default="BENCH_olap.json")
    args = ap.parse_args()
    n = args.rows or (50_000 if args.tiny else 200_000)
    run(n, out_path=args.out)


if __name__ == "__main__":
    main()
