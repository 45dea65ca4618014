"""Benchmark orchestrator — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (see benchmarks/common.py).
"""
from __future__ import annotations

import time


def main() -> None:
    t0 = time.time()
    from . import (bench_bitmap_profile, bench_block_sort, bench_column_order,
                   bench_logical_ops, bench_sort_compression)
    print("name,us_per_call,derived")
    bench_sort_compression.run()
    bench_column_order.run()
    bench_bitmap_profile.run()
    bench_block_sort.run()
    bench_logical_ops.run()
    print(f"\n[benchmarks] total {time.time()-t0:.0f}s")


if __name__ == '__main__':
    main()
