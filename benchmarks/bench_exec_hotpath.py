"""Query-execution hot path: vectorized EWAH, bucketed kernels, shard fan-out.

Measures the three legs of the PR-3 overhaul end to end and *asserts* the
contracts rather than eyeballing them:

1. **EWAH n-ary throughput** — ``and_many``/``or_many`` on the vectorized
   run-list path vs the retained ``_SegCursor`` reference fold, over real
   bitmaps of a lexicographically sorted fact table.  Asserts word-identical
   outputs and >= 3x speedup.
2. **Bucketed Pallas compilation** — cold vs warm ``logical_reduce`` latency
   across operand word counts that share one power-of-two bucket (one
   compile serves all of them) vs per-shape padding (one compile *each*).
   Asserts warm latency is flat within the bucket and correctness vs NumPy.
3. **Shard-parallel execution** — sequential vs ``ShardProcessPool`` (and a
   thread pool for reference) on >= 4 shards, all ``backend="ewah"``: the
   forked workers are host-only, and they fork before this process imports
   jax.  Asserts bit-identical results
   always; asserts parallel < sequential when the machine demonstrably has
   multi-core headroom (a 2-process CPU-scaling pre-check — on a 1-core or
   quota-throttled box *nothing* can run below sequential, and pretending
   otherwise would just make the benchmark flaky).
4. **Cost-model calibration** — runs the EWAH-vs-kernel sweep and records
   the measured crossover the executor/planner consume.

Emits CSV rows (like the other benchmarks) and writes ``BENCH_exec.json``:

    PYTHONPATH=src python benchmarks/bench_exec_hotpath.py [--tiny] \
        [--out BENCH_exec.json]
"""
from __future__ import annotations

import argparse
import itertools
import json
import multiprocessing
import sys
import time

import numpy as np

from repro.core import (BitmapIndex, ShardedIndex, col, execute, lex_sort,
                        synth)
from repro.core import cost_model as cm
from repro.core.ewah import and_many, binary_op, or_many
from repro.core.shard import ShardProcessPool

try:  # package-style and script-style execution both work
    from .common import emit
except ImportError:  # pragma: no cover
    from common import emit


def _best_of(fn, repeats=5):
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _make_table(n: int, rng: np.random.Generator) -> np.ndarray:
    t = np.stack([rng.integers(0, 7, n),
                  (rng.pareto(1.5, n) * 40).astype(np.int64) % 500,
                  rng.integers(0, 5000, n)], axis=1)
    table, _ = synth.factorize(t)
    return table[lex_sort(table)]


# -- 1. EWAH n-ary throughput ------------------------------------------------

def bench_ewah_nary(table: np.ndarray, results: dict) -> None:
    idx = BitmapIndex.build(table, k=1)
    # operand sets straight from the sorted index: the last sort column's
    # bitmaps are the fragmented (literal-fringe) ones where op cost lives
    frag_col = len(idx.columns) - 1
    or_ops = [idx.bitmap(frag_col, b)
              for b in range(min(32, idx.card(frag_col)))]
    n_cols = len(idx.columns)
    and_ops = [or_many([idx.bitmap(c, b) for b in range(0, idx.card(c), 2)
                        if b < idx.card(c)][:20])
               for c in range(n_cols)]
    and_ops += [or_many([idx.bitmap(c, b) for b in range(1, idx.card(c), 2)
                         if b < idx.card(c)][:20])
                for c in range(n_cols)]
    for bm in or_ops + and_ops:
        bm.runlist()  # decode once up front, as the executor's cache does

    def ref_and():
        acc = and_ops[0]
        for bm in and_ops[1:]:
            acc = binary_op(acc, bm, "and")
        return acc

    def ref_or():
        items = list(or_ops)
        while len(items) > 1:
            items = [binary_op(items[i], items[i + 1], "or")
                     if i + 1 < len(items) else items[i]
                     for i in range(0, len(items), 2)]
        return items[0]

    out = {}
    for name, ref_fn, vec_fn, ops in (
            ("nary_and", ref_and, lambda: and_many(and_ops), and_ops),
            ("nary_or", ref_or, lambda: or_many(or_ops), or_ops)):
        ref_bm, vec_bm = ref_fn(), vec_fn()
        assert np.array_equal(ref_bm.words, vec_bm.words), \
            f"{name}: vectorized path diverged from the cursor oracle"
        ref_s, vec_s = _best_of(ref_fn), _best_of(vec_fn)
        speedup = ref_s / vec_s
        out[name] = {"operands": len(ops),
                     "cursor_us": round(ref_s * 1e6, 1),
                     "vectorized_us": round(vec_s * 1e6, 1),
                     "speedup": round(speedup, 2),
                     "bit_identical": True}
        emit(f"exec_{name}_vectorized", vec_s * 1e6,
             f"cursor_us={ref_s * 1e6:.0f} speedup={speedup:.1f}x")
        assert speedup >= 3.0, \
            f"{name}: vectorized speedup {speedup:.2f}x < 3x over the cursor path"
    results["ewah"] = out


# -- 2. bucketed kernel compilation ------------------------------------------

def bench_kernel_buckets(results: dict, tiny: bool) -> None:
    from repro.kernels import ops as kops
    rng = np.random.default_rng(2)
    base = 2048 if tiny else 8192
    word_counts = [int(base * f) for f in (1.1, 1.4, 1.7, 2.0)]
    buckets = {kops.bucket_cols(c) for c in word_counts}
    assert len(buckets) == 1, (word_counts, buckets)
    L = 8
    mats = [rng.integers(0, 2**32, (L, c), dtype=np.uint32)
            for c in word_counts]
    cold, warm = [], []
    for mat in mats:
        run = lambda: np.asarray(kops.logical_reduce(mat, op="and"))  # noqa: E731
        got = None

        def run_keep():
            nonlocal got
            got = run()
        cold.append(_best_of(run_keep, repeats=1))
        warm.append(_best_of(run, repeats=3))
        assert np.array_equal(got, np.bitwise_and.reduce(mat, axis=0))
    # per-shape padding for comparison: every count compiles its own kernel
    unbucketed_cold = [
        _best_of(lambda: np.asarray(kops.logical_reduce(m, op="and",
                                                        bucket=False)),
                 repeats=1)
        for m in mats]
    flat_ratio = max(warm) / min(warm)
    out = {"bucket_words": next(iter(buckets)),
           "word_counts": word_counts,
           "cold_us": [round(c * 1e6, 1) for c in cold],
           "warm_us": [round(w * 1e6, 1) for w in warm],
           "unbucketed_cold_us": [round(c * 1e6, 1) for c in unbucketed_cold],
           "warm_flat_ratio": round(flat_ratio, 2),
           "bit_identical": True}
    emit("exec_kernel_bucket_warm", float(np.mean(warm)) * 1e6,
         f"cold_first_us={cold[0] * 1e6:.0f} flat_ratio={flat_ratio:.2f}")
    # one compile serves the whole bucket: later first-calls stay near warm
    # latency, far below the first (compiling) call
    assert max(cold[1:]) < cold[0], \
        f"bucketing did not amortize the compile: {out['cold_us']}"
    # warm latency is flat across word counts within the bucket (same
    # compiled program, same padded shape; generous bound for CI noise)
    assert flat_ratio < 8.0, f"warm latency not flat in bucket: {out['warm_us']}"
    results["kernel_buckets"] = out


# -- 3. shard-parallel execution ---------------------------------------------

def _cpu_scaling_probe(work_s: float = 0.25) -> float:
    """Measured speedup of 2 forked CPU-bound processes vs 1 — the machine's
    real multi-core headroom (containers often quota-throttle below nproc)."""
    def burn(barrier, out):
        barrier.wait()
        t0 = time.perf_counter()
        x = 0
        deadline = t0 + work_s
        while time.perf_counter() < deadline:
            x += sum(range(1000))
        out.put(time.perf_counter() - t0)

    ctx = multiprocessing.get_context("fork")

    def run(n):
        barrier = ctx.Barrier(n + 1)
        q = ctx.Queue()
        ps = [ctx.Process(target=burn, args=(barrier, q)) for _ in range(n)]
        for p in ps:
            p.start()
        barrier.wait()
        t0 = time.perf_counter()
        for p in ps:
            p.join()
        wall = time.perf_counter() - t0
        for p in ps:
            p.close()
        return wall

    solo = run(1)
    duo = run(2)
    return 2 * solo / duo


def bench_shards(table: np.ndarray, results: dict, tiny: bool) -> None:
    n = len(table)
    n_shards = 8
    shard_rows = max(-(-n // n_shards) // 32 * 32, 32)
    sharded = ShardedIndex.build(table, shard_rows=shard_rows, k=1,
                                 cache_entries=0)  # raw latency, no result cache
    mono = BitmapIndex.build(table, k=1)
    card2 = sharded.card(2)
    exprs = [(col(0) == 1) & (col(1) <= 50),
             col(1).isin(tuple(range(30))) | (col(0) == 3),
             (col(2) <= card2 // 5) & (col(0) >= 2),
             ~(col(1) == 0) & (col(0) <= 4)]
    # executors memoize shared *subtrees* in the operand caches (the
    # QueryBatch subexpression-sharing path), so repeating literally
    # identical statements would time dictionary lookups, not execution.
    # Each timing round therefore uses structurally distinct statements
    # drawn from one fixed leaf pool: leaf operands stay warm (that part of
    # the cache is the intended steady state) while every round's n-ary
    # reductions really run.
    card1 = sharded.card(1)
    pool_hi = min(200, card1 - 1)

    def make_exprs(r: int):
        # deterministic per-round variation: every subtree's canonical key
        # is fresh for far more rounds than the benchmark uses, while all
        # leaves stay inside a bounded pool the warm rounds cover
        sel = tuple(sorted({(r * 31 + 17 * i) % pool_hi for i in range(30)}))
        return [(col(0) == 1) & (col(1) <= 40 + (r * 13) % (pool_hi - 40)),
                col(1).isin(sel) | (col(0) == 3),
                (col(2) <= card2 // 5 + (r * 11) % 50) & (col(0) >= 2),
                ~(col(1) == (r * 3) % 100) & (col(0) <= 4)]

    rounds = itertools.count()
    caches = [{} for _ in sharded.shards]
    # host-only forks (here and in the scaling probe): never from a process
    # that may already hold a device
    assert "jax" not in sys.modules, "fork shard workers before jax loads"
    proc_pool = ShardProcessPool(sharded, workers=2)
    from concurrent.futures import ThreadPoolExecutor
    thread_pool = ThreadPoolExecutor(max_workers=4)
    try:
        # bit-identity across every execution strategy, then warm all paths
        for e in exprs + make_exprs(next(rounds)):
            ref = execute(mono, e, backend="ewah")
            seq = sharded.execute(e, backend="ewah", caches=caches)
            par = sharded.execute(e, backend="ewah", pool=proc_pool)
            thr = sharded.execute(e, backend="ewah", pool=thread_pool)
            assert np.array_equal(ref.to_bool(), seq.to_bool())
            assert np.array_equal(seq.words, par.words), "process pool diverged"
            assert np.array_equal(seq.words, thr.words), "thread pool diverged"
        # map() has no shard->worker affinity: run enough warm rounds that
        # every worker has loaded every shard's leaf operands before timing
        for _ in range(7):
            for e in make_exprs(next(rounds)):
                sharded.execute(e, backend="ewah", caches=caches)
                sharded.execute(e, backend="ewah", pool=proc_pool)

        # every strategy times the SAME three statement rounds — the rounds
        # differ from each other (so subtree memos can't short-circuit the
        # work) but not across strategies (so the ratios compare execution
        # strategies, not workloads)
        timed_rounds = [make_exprs(next(rounds)) for _ in range(3)]

        def timed(run_one):
            it = iter(timed_rounds)
            return _best_of(lambda: [run_one(e) for e in next(it)], repeats=3)

        seq_s = timed(lambda e: sharded.execute(e, backend="ewah",
                                                caches=caches))
        par_s = timed(lambda e: sharded.execute(e, backend="ewah",
                                                pool=proc_pool))
        thr_s = timed(lambda e: sharded.execute(e, backend="ewah",
                                                pool=thread_pool))
    finally:
        proc_pool.shutdown()
        thread_pool.shutdown(wait=False)
    scaling = _cpu_scaling_probe(0.1 if tiny else 0.25)
    out = {"n_shards": sharded.n_shards,
           "sequential_us": round(seq_s * 1e6, 1),
           "process_pool_us": round(par_s * 1e6, 1),
           "thread_pool_us": round(thr_s * 1e6, 1),
           "speedup": round(seq_s / par_s, 2),
           "cpu_scaling_2proc": round(scaling, 2),
           "bit_identical": True}
    emit("exec_shard_parallel", par_s * 1e6,
         f"sequential_us={seq_s * 1e6:.0f} speedup={seq_s / par_s:.2f}x "
         f"cpu_scaling={scaling:.2f}x")
    if scaling >= 1.25:
        assert par_s < seq_s, \
            (f"shard-parallel ({par_s * 1e3:.0f}ms) not below sequential "
             f"({seq_s * 1e3:.0f}ms) despite {scaling:.2f}x CPU headroom")
        out["parallel_below_sequential"] = True
    else:
        # quota-throttled / single-core box: no execution strategy can beat
        # sequential; record the fact instead of asserting the impossible
        out["parallel_below_sequential"] = bool(par_s < seq_s)
        out["note"] = (f"cpu scaling probe {scaling:.2f}x < 1.25x: machine "
                       "has no multi-core headroom, latency assert skipped")
    results["shards"] = out


# -- 4. cost-model calibration -----------------------------------------------

def bench_cost_model(results: dict, tiny: bool) -> None:
    import math
    model = cm.calibrate(n_words=1 << (10 if tiny else 13), n_operands=6,
                         densities=(0.05, 0.2, 0.5, 0.8),
                         repeats=2)
    threshold = model.dense_threshold
    results["cost_model"] = {
        # keep the artifact strict-JSON: inf ("kernel never wins") -> null
        "dense_threshold": threshold if math.isfinite(threshold) else None,
        "kernel_ever_wins": math.isfinite(threshold),
        "calibrated": model.calibrated,
        "samples": model.samples,
    }
    emit("exec_cost_model_threshold",
         (threshold if math.isfinite(threshold) else -1.0) * 1e6,
         f"samples={len(model.samples)}")


def run(n_rows: int, tiny: bool, out_path: str) -> dict:
    rng = np.random.default_rng(0)
    table = _make_table(n_rows, rng)
    results: dict = {"n_rows": n_rows, "tiny": tiny}
    bench_ewah_nary(table, results)
    # shard forks must happen before anything imports jax (one process on
    # the device; bench_shards asserts it)
    bench_shards(table, results, tiny)
    bench_kernel_buckets(results, tiny)
    bench_cost_model(results, tiny)
    with open(out_path, "w") as fh:
        json.dump(results, fh, indent=2)
    print(f"[bench_exec_hotpath] wrote {out_path}", flush=True)
    return results


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CI-sized run (same asserts, smaller data)")
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--out", default="BENCH_exec.json")
    args = ap.parse_args(argv)
    n = args.rows if args.rows is not None else (120_000 if args.tiny
                                                 else 1_000_000)
    res = run(n, args.tiny, args.out)
    sh = res["shards"]
    thr = res["cost_model"]["dense_threshold"]
    print(f"[bench_exec_hotpath] nary_and {res['ewah']['nary_and']['speedup']}x, "
          f"nary_or {res['ewah']['nary_or']['speedup']}x, "
          f"shard-parallel {sh['speedup']}x "
          f"(cpu scaling {sh['cpu_scaling_2proc']}x), "
          f"threshold {'inf (kernel never wins)' if thr is None else f'{thr:.3f}'}",
          flush=True)


if __name__ == "__main__":
    main()
