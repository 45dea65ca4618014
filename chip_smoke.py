"""Chip smoke test: serve a 10M-row sorted bitmap store from one TPU chip.

Drives the system's main path once, in this one process, through the
entry points a user calls:

1. build the census-shaped demo fact table (``query_api.demo_table``) from
   a fixed seed, lex-sorted, indexed with k=2 and cut into 4 shards;
2. save it (``ShardedIndex.save``) under the checkout and reopen it mmap'd
   with ``QueryService.from_dir(..., backend="kernel")``;
3. serve it over HTTP (``serve_in_thread``) and POST a row query whose
   plan has an n-ary AND and OR, a ``Not`` that lowers to ANDNOT, a
   ``count`` and a ``group_count`` — once cold (compiles included), then
   again with other constants (the same kernel shapes, no result-cache
   hits);
4. check every answer bit for bit against the row-scan oracle
   (``query.naive_eval``) and against the same statements on the ``ewah``
   backend, and that the kernel path really ran compiled: kernel
   dispatches above zero, ``interpret_mode()`` False, and a lowered
   ``word_logical`` holding ``tpu_custom_call``;
5. run the kernels no statement reaches yet (``popcount_total``,
   ``popcount_rows``, ``bitpack``) once at a shard's width against NumPy.

Phase times and counters go to earlier lines; the last line is
``{"ok": true, "device": {...}}``.  Every phase runs wherever the script
runs, but off a TPU it exits 1 at the end without that line, so a tiny CPU
rehearsal exercises the whole path:

    python chip_smoke.py                                  # one TPU chip
    JAX_PLATFORMS=cpu python chip_smoke.py --rows 200000  # rehearsal, exits 1

The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, else
``.jax_cache/`` in the checkout; cache hits and misses are printed, so a
second run shows the kernels read back from it.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

STORE_DIR = ROOT / "chip_smoke_store"
SHARDS = 4


def _post(port: int, body: dict) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/query", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=600) as resp:
        return json.loads(resp.read())


def _statements(table, round_: int):
    """The four statement shapes; ``round_`` shifts their constants."""
    from repro.core import col
    region = 7 + 11 * round_
    days = [v + 6 * round_ for v in (0, 1, 2, 3, 5)]
    return [
        ("rows_and_or", "rows",
         col("day").isin(days) & (col("region") == region)),
        ("rows_andnot", "rows",
         (col("region") == region + 1) & ~(col("day") == 2 + round_)),
        ("count", "count",
         col("day").isin(days[:3]) | (col("region") == region + 2)),
        ("group_count", "group_count",
         col("day").between(4 * round_, 4 * round_ + 10)),
    ]


def _run_statement(port, host, table, kind, e):
    """POST one statement, check it; returns (seconds, answer summary)."""
    from repro.core.query import naive_eval
    from repro.serve.query_api import DEMO_COLUMNS, expr_to_json
    mask = naive_eval(table, e, DEMO_COLUMNS)
    where = expr_to_json(e)
    t0 = time.perf_counter()
    if kind == "rows":
        got = _post(port, {"query": where, "explain": True})
        dt = time.perf_counter() - t0
        want = host.query(e)
        assert not got["truncated"], "row answer truncated"
        assert got["rows"] == want["rows"], "kernel rows != ewah rows"
        assert got["rows"] == mask.nonzero()[0].tolist(), \
            "kernel rows != naive_eval"
        return dt, got["count"], got["plan"]
    if kind == "count":
        got = _post(port, {"select": {"count": True}, "where": where})
        dt = time.perf_counter() - t0
        assert got["count"] == host.count(e)["count"], "count != ewah"
        assert got["count"] == int(mask.sum()), "count != naive_eval"
        return dt, got["count"], None
    got = _post(port, {"select": {"group_count": "region"}, "where": where})
    dt = time.perf_counter() - t0
    import numpy as np
    naive = np.bincount(table[mask, 0], minlength=len(got["counts"]))
    assert got["counts"] == host.group_count("region", e)["counts"], \
        "group_count != ewah"
    assert got["counts"] == naive.tolist(), "group_count != naive_eval"
    return dt, sum(got["counts"]), None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=10_000_000,
                    help="fact-table rows (smaller only for a CPU rehearsal)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.executor import Executor
    from repro.core.shard import ShardProcessPool
    from repro.kernels import ops as kops
    from repro.kernels import word_logical as wl
    from repro.serve.query_api import (QueryService, demo_index, demo_table,
                                       serve_in_thread)

    cache_dir = kops.use_compile_cache()
    cache_events = {"hits": 0, "misses": 0}

    def on_event(event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"[smoke] device {device}; compile cache {cache_dir}", flush=True)
    times = {}

    t0 = time.perf_counter()
    table = demo_table(args.rows, np.random.default_rng(args.seed))
    index = demo_index(table, shards=SHARDS)
    times["build_s"] = time.perf_counter() - t0

    shutil.rmtree(STORE_DIR, ignore_errors=True)
    t0 = time.perf_counter()
    index.save(str(STORE_DIR))
    times["save_s"] = time.perf_counter() - t0
    del index
    store_bytes = sum(p.stat().st_size for p in STORE_DIR.iterdir())

    srv = svc = host = None
    try:
        t0 = time.perf_counter()
        svc = QueryService.from_dir(str(STORE_DIR), backend="kernel",
                                    max_rows=args.rows)
        times["open_s"] = time.perf_counter() - t0
        # the same store on the host path, for the per-statement comparison;
        # shard_processes=0: this process starts no children
        host = QueryService.from_dir(str(STORE_DIR), backend="ewah",
                                     shard_processes=0, max_rows=args.rows)
        assert not isinstance(svc._shard_pool, ShardProcessPool)
        srv, port = serve_in_thread(svc)
        print(f"[smoke] {args.rows} rows in {svc.index.n_shards} shards, "
              f"{store_bytes} store bytes, serving on port {port}",
              flush=True)

        dispatches0 = Executor.kernel_dispatches
        for round_, phase in ((0, "first_queries_s"), (1, "warm_queries_s")):
            total = 0.0
            for name, kind, e in _statements(table, round_):
                dt, answer, plan = _run_statement(port, host, table, kind, e)
                ops = {ln.split()[0] for ln in (plan or "").splitlines()
                       if ln.strip()}
                if name == "rows_and_or":
                    assert {"AND", "OR"} <= ops, plan
                if name == "rows_andnot":
                    assert "ANDNOT" in ops, plan
                total += dt
                print(f"[smoke] {phase[:-2]} {name}: {dt:.6f} s, "
                      f"answer {answer}, matches naive_eval and ewah",
                      flush=True)
            times[phase] = total
        dispatches = Executor.kernel_dispatches - dispatches0
    finally:
        if srv is not None:
            srv.shutdown()
            srv.server_close()
        for s in (svc, host):
            if s is not None:
                s.close()
        shutil.rmtree(STORE_DIR, ignore_errors=True)

    # the kernels the served path does not reach yet, against NumPy
    from repro.core.bitpack import pack_matrix
    rng = np.random.default_rng(args.seed)
    words = rng.integers(0, 2**32, size=(8, kops.bucket_cols(
        -(-args.rows // (32 * SHARDS)))), dtype=np.uint32)
    bits = rng.random((4096, 256)) < 0.5
    kernel_checks = {
        "popcount_total": int(kops.popcount_total(words))
        == int(np.bitwise_count(words).sum()),
        "popcount_rows": np.array_equal(
            np.asarray(kops.popcount_rows(words)),
            np.bitwise_count(words).sum(axis=1)),
        "bitpack": np.array_equal(np.asarray(kops.bitpack(bits)).T,
                                  pack_matrix(bits)),
    }
    print(f"[smoke] kernels at {words.shape} match NumPy: {kernel_checks}",
          flush=True)

    interpret = kops.interpret_mode()
    words = jax.ShapeDtypeStruct((wl.BLOCK_ROWS, wl.BLOCK_COLS), jnp.uint32)
    flags = jax.ShapeDtypeStruct((1, 1), jnp.int32)
    lowered = wl.word_logical.lower(words, words, flags, flags, op="and",
                                    interpret=interpret).as_text()
    custom_call = "tpu_custom_call" in lowered
    print(f"[smoke] phase times {json.dumps(times)}", flush=True)
    print(f"[smoke] kernel dispatches {dispatches}; interpret {interpret}; "
          f"tpu_custom_call in lowered word_logical {custom_call}; "
          f"compile cache hits {cache_events['hits']} misses "
          f"{cache_events['misses']}", flush=True)

    failures = [f"{k} differs from NumPy"
                for k, ok in kernel_checks.items() if not ok]
    if dispatches <= 0:
        failures.append("no statement reached the kernels")
    if interpret:
        failures.append("kernels resolved to interpret mode")
    if not custom_call:
        failures.append("lowered word_logical holds no tpu_custom_call")
    if device["platform"] != "tpu":
        failures.append(f"no TPU: JAX runs on {device['platform']}")
    if failures:
        print("[smoke] FAILED: " + "; ".join(failures), file=sys.stderr,
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
