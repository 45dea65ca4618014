"""Aggregation benchmark: compressed-domain group-by vs decompress-then-
histogram.

The tentpole claim of the statement API: on a sorted fact table, a
``group_by(col).count()`` answered *in the compressed domain* — the filter
evaluated once, every value bitmap intersected by run-interval arithmetic
(memoized ``set_intervals`` + two vectorized ``searchsorted`` passes over
all groups at once), counts merged per shard — beats the baseline that
decompresses bitmaps to dense words and popcounts ``filter & value`` per
group, because sorted columns compress to a handful of runs while the dense
path touches every word of every bitmap.

Asserted (and recorded in ``BENCH_agg.json``, a CI artifact):

* compressed-domain group-by (warm) is faster than decompress-then-
  histogram on the sorted table, for a mid- and a high-cardinality column;
* all three group-by implementations (compressed, dense, NumPy ``bincount``
  row oracle) agree bit-for-bit;
* sharded partial-count merging returns the same vector as the monolithic
  index.

    PYTHONPATH=src python benchmarks/bench_aggregates.py [--tiny] \
        [--out BENCH_agg.json]
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core import Dataset, col, execute, synth
from repro.core import statement

try:  # package-style and script-style execution both work
    from .common import emit
except ImportError:  # pragma: no cover
    from common import emit

def _popcount(words):
    return int(np.bitwise_count(words).sum(dtype=np.int64))


def _make_table(n: int, rng: np.random.Generator) -> np.ndarray:
    """3 columns: low cardinality (selective filters), mid and high
    cardinality (the group-by dimensions)."""
    t = np.stack([rng.integers(0, 8, n),
                  (rng.pareto(1.2, n) * 12).astype(np.int64) % 64,
                  (rng.pareto(1.2, n) * 80).astype(np.int64) % 1024],
                 axis=1)
    table, _ = synth.factorize(t)
    return table


def dense_group_count(index, c: int, e) -> np.ndarray:
    """Decompress-then-histogram baseline: materialize the filter as dense
    words, then AND + popcount every value bitmap's dense words."""
    filt_words = execute(index, e).to_words()
    card = index.card(c)
    out = np.empty(card, dtype=np.int64)
    for b in range(card):
        out[b] = _popcount(filt_words & index.bitmap(c, b).to_words())
    return out


def _median_time(fn, repeats: int = 5) -> float:
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def run(n: int = 200_000, out_path: str = "BENCH_agg.json") -> dict:
    rng = np.random.default_rng(0)
    table = _make_table(n, rng)
    names = ["region", "bucket", "user"]
    ds = Dataset.from_rows(table, names, sort="lex", k=1)
    ds_sh = ds.shard(4)
    st = ds.table
    results: dict = {"n_rows": n,
                     "cards": [ds.card(c) for c in range(3)],
                     "sort_order": ds.sort_order,
                     "group_by": {}}

    e = col("region") == int(st[n // 2, 0])  # a populous region
    mask = st[:, 0] == int(st[n // 2, 0])
    for cname in ("bucket", "user"):
        c = names.index(cname)
        card = ds.card(cname)
        oracle = np.bincount(st[mask, c], minlength=card)

        compressed = ds.query().where(e).group_by(cname).count()
        dense = dense_group_count(ds.index, c, e)
        sharded = ds_sh.query().where(e).group_by(cname).count()
        assert np.array_equal(compressed, oracle), cname
        assert np.array_equal(dense, oracle), cname
        assert np.array_equal(sharded, oracle), cname

        t0 = time.perf_counter()
        statement.run(ds.index, ("gcount", c, e))  # includes interval decodes
        cold_s = time.perf_counter() - t0
        comp_s = _median_time(
            lambda: ds.query().where(e).group_by(cname).count())
        dense_s = _median_time(lambda: dense_group_count(ds.index, c, e))
        # repeat statements hit the shard-local LRUs: the serving steady
        # state, recorded as the warm figure it is
        shard_warm_s = _median_time(
            lambda: ds_sh.query().where(e).group_by(cname).count())
        count_s = _median_time(lambda: ds.query().where(e).count())

        speedup = dense_s / comp_s
        results["group_by"][cname] = {
            "card": card,
            "selected_rows": int(mask.sum()),
            "compressed_cold_s": round(cold_s, 6),
            "compressed_s": round(comp_s, 6),
            "dense_s": round(dense_s, 6),
            "sharded_warm_s": round(shard_warm_s, 6),
            "count_s": round(count_s, 6),
            "speedup_vs_dense": round(speedup, 2),
        }
        emit(f"group_by_{cname}_compressed", comp_s * 1e6,
             f"{speedup:.1f}x_vs_dense")
        emit(f"group_by_{cname}_dense", dense_s * 1e6, f"card_{card}")
        assert speedup > 1.0, (
            f"compressed-domain group-by over {cname} (card {card}) must "
            f"beat decompress-then-histogram on the sorted table: "
            f"{comp_s * 1e3:.2f}ms vs {dense_s * 1e3:.2f}ms")

    with open(out_path, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {out_path}")
    return results


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CI-sized run (fast, same asserts)")
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--out", default="BENCH_agg.json")
    args = ap.parse_args()
    n = args.rows or (50_000 if args.tiny else 200_000)
    run(n, out_path=args.out)


if __name__ == "__main__":
    main()
