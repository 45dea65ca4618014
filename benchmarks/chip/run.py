"""Chip benchmark of the bitmap index service: one cell, one run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``configs/<config>.json``, its table generator in ``generators/``) and a
traffic mix (``traffic/<mix>.json``).  One process holds the chip and:

1. generates the table from ``--seed``;
2. sorts it (where the configuration stores it sorted), builds the sharded
   index, saves it, and reopens it mmap'd with ``QueryService.from_dir``;
3. serves it over HTTP (``serve_in_thread``);
4. warms up: every kernel shape the cell's statements can reach, then one
   statement of each template, then drops the result caches;
5. starts ``loadgen.py``, a child process that never imports JAX, which
   keeps the mix's clients busy for ``--seconds`` (closed loop); the window
   closes when the last statement sent has been answered;
6. checks a seeded sample of the window's answers against the row-scan
   reference (``reference.py``) and prints the result line last.

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the profiler traces the window, wrappers (``tracing.Probe``)
take spans and counters, and the metrics are the per-layer ones.  Every
metric is read by ``metrics/<name>.py``.  Off a TPU, or with fewer chips
than the cell asks for, the run stops before building anything and exits 2;
``--rows`` (rehearsals only) runs every phase at that row count on any
backend and exits 1 at the end without a result line.

Compiled programs persist in ``.chipbench/jax_cache`` of the checkout; the
cost model is pinned to a path that holds no file, so the routing is the
program's default on every machine.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import urllib.request  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
RUN_DIR = ROOT / ".chipbench"
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import reference as reference_mod  # noqa: E402
import tracing  # noqa: E402
from traffic import Traffic, _seed_words, file_stem, load_mix  # noqa: E402

CHECK_MAX = 200          # answers compared per run (a seeded sample)
DRAIN_S = 60.0           # how long answers in flight at the close are awaited
CONTROL_SHARE = 1000     # the control misses the table's last rows/1000 rows
_SAMPLE_STREAM = 4


def pin_environment() -> None:
    """Before JAX is imported: the compile cache at a fixed path inside the
    checkout, and the cost model at a path that holds no file."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(RUN_DIR / "jax_cache")
    os.environ["REPRO_COST_MODEL"] = str(RUN_DIR / "no_cost_model.json")


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def load_spec(workload: str, root: Path = ROOT) -> Dict:
    """The cell, its configuration and mix, and its metrics, by name, from
    ``BENCHMARK.json`` under ``root`` and the benchmark directory it names
    first in ``paths``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench_dir = root / bench["paths"][0]
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or workload in m["workloads"]]

    return {"cell": cell, "dir": bench_dir,
            "config": json.loads((root / conf["file"]).read_text()),
            "mix": load_mix(cell["traffic"], bench_dir),
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"]),
            "run_seconds": bench["run_seconds"]}


def _module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generate(cfg: Dict, seed: int, rows: Optional[int] = None,
             bench_dir: Path = HERE) -> Dict:
    gen = _module(bench_dir / "generators" / f"{cfg['generator']}.py")
    return gen.generate(cfg, _seed_words(seed) + [0], rows)


def store_index_bytes(store: Path) -> int:
    """Bytes of a saved store, less every measure sidecar segment."""
    import struct
    pre = struct.Struct("<8sIIQQI")
    total = 0
    for f in store.iterdir():
        total += f.stat().st_size
        if f.suffix != ".ridx":
            continue
        with open(f, "rb") as fh:
            _, _, _, off, n, _ = pre.unpack(fh.read(pre.size))
            fh.seek(off)
            meta = json.loads(fh.read(n))
        for spec in (meta.get("measures") or {}).values():
            item = np.dtype(spec["dtype"]).itemsize
            total -= sum(row[1] for row in spec["toc"]) * item
    return total


class Context:
    """What the metric readers see."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def latencies_s(self, traced: bool = False) -> np.ndarray:
        recs = self.window
        if traced:
            a, b = self.traced
            recs = [r for r in recs if a <= r["t1"] <= b]
        return np.asarray([r["t1"] - r["t0"] for r in recs])

    def traced_count(self) -> int:
        a, b = self.traced
        return sum(1 for r in self.window if a <= r["t1"] <= b)


def read_metrics(specs: List[Dict], ctx: Context,
                 bench_dir: Path = HERE) -> Dict:
    out = {}
    for m in specs:
        mod = _module(bench_dir / "metrics" / f"{file_stem(m['name'])}.py")
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def _post(port: int, body: Dict) -> Dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/query", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=600) as resp:
        return json.loads(resp.read())


def warm_kernels(svc, max_operands: int) -> int:
    """Every shape the executor's kernel calls can take on this store: an
    n-ary AND/OR of 1..``max_operands`` bucketed shard operands (with their
    tile flags) and the ANDNOT of two reductions.  Returns the calls made."""
    from repro.kernels import ops as kops
    calls = 0
    widths = sorted({kops.bucket_cols(-(-sh.n_rows // 32))
                     for sh in svc.index.shards if sh.n_rows})
    for cp in widths:
        for op in ("and", "or"):
            for n in range(1, max_operands + 1):
                mat = np.zeros((n, cp), dtype=np.uint32)
                out = kops.logical_reduce(mat, op=op,
                                          row_flags=kops.np_row_flags(mat))
                np.asarray(out)
                calls += 1
        a = kops.logical_reduce(np.zeros((1, cp), np.uint32), op="and")
        np.asarray(kops.word_logical(a[None, :], a[None, :], "andnot"))
        calls += 1
    return calls


def warm_statements(port: int, traffic: Traffic, clients: int):
    """Each template once, at the high end of its draws (the kernel shapes
    are warmed by ``warm_kernels``): the statement paths run once before the
    window."""
    todo = traffic.warmup(ends=("hi",))
    lock = threading.Lock()

    def worker():
        while True:
            with lock:
                if not todo:
                    return
                _, body = todo.pop()
            _post(port, body)

    threads = [threading.Thread(target=worker) for _ in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def drop_result_caches(svc) -> None:
    """Empty the service's result cache and the sharded index's shard-local
    result caches, which ``invalidate_cache`` leaves warm: the window then
    meets no result that a warm-up statement left behind.  Where the
    configuration turns the service's cache off (``cache_entries`` 0), the
    shards' caches are turned off too, so every statement is executed."""
    svc.invalidate_cache()
    off = svc.cache.capacity == 0
    for cache in getattr(svc.index, "_result_caches", ()):
        cache.clear()
        if off:
            cache.capacity = 0


def sample_records(records: List[Dict], seed: int, k: int) -> List[Dict]:
    """Up to ``k`` answered records, drawn from the seed round-robin over
    templates so every statement kind is checked."""
    rng = np.random.default_rng(_seed_words(seed) + [_SAMPLE_STREAM])
    by_tpl: Dict[str, List[Dict]] = {}
    for r in records:
        by_tpl.setdefault(r["tpl"], []).append(r)
    queues = [[v[j] for j in rng.permutation(len(v))]
              for _, v in sorted(by_tpl.items())]
    out = []
    while len(out) < k and any(queues):
        for q in queues:
            if q and len(out) < k:
                out.append(q.pop())
    return out


def run_cell(spec: Dict, seed: int, seconds: float, trace: bool,
             rows: Optional[int] = None, control: int = 0,
             compile_cache: bool = True, warm: bool = True,
             run_dir: Path = RUN_DIR) -> Dict:
    """One run of the cell; returns the result line as a dict.

    ``control`` > 0 judges the control in the program's place: a stale copy
    that misses the table's last rows/``control`` rows.  ``compile_cache``,
    ``warm`` and ``run_dir`` (where the store and the trace are written) let
    tests drive a run at a tiny size."""
    import jax
    from repro.core import ShardedIndex, lex_sort
    from repro.core import cost_model
    from repro.core.executor import Executor
    from repro.kernels import ops as kops
    from repro.serve.query_api import QueryService, serve_in_thread

    cfg, cell = spec["config"], spec["cell"]
    if compile_cache:
        kops.use_compile_cache()
    compiles = {"n": 0, "on": False}

    def on_duration(event, duration, **_kw):
        if compiles["on"] and event == "/jax/core/compile/backend_compile_duration":
            compiles["n"] += 1

    def on_event(event, **_kw):
        if compiles["on"] and event == "/jax/compilation_cache/cache_hits":
            compiles["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    dev = jax.devices()[0]
    cm = cost_model.get_default()
    log(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"cost model dense_threshold {cm.dense_threshold} ({cm.source}); "
        f"compile cache {jax.config.jax_compilation_cache_dir}")

    phases: Dict[str, float] = {}
    build: Dict[str, float] = {}
    t = time.monotonic()
    table = generate(cfg, seed, rows, spec["dir"])
    cols = table["columns"]
    names = list(cfg["columns"])
    mat = np.stack([cols[c] for c in names], axis=1)
    measures = table["measures"] or None
    n_rows = len(mat)
    phases["generate_s"] = time.monotonic() - t
    if cfg.get("sort"):
        t = time.monotonic()
        perm = lex_sort(mat, [names.index(c) for c in cfg["sort"]])
        build["sort_s"] = time.monotonic() - t
        mat = mat[perm]
        if measures:
            measures = {k: v[perm] for k, v in measures.items()}
    shards = int(cfg["shards"])
    t = time.monotonic()
    index = ShardedIndex.build(
        mat, shard_rows=max(-(-n_rows // (32 * shards)) * 32, 32),
        k=int(cfg["k"]), column_names=names, measures=measures)
    build["index_s"] = time.monotonic() - t
    del mat
    store = run_dir / "store" / file_stem(cell["name"])
    shutil.rmtree(store, ignore_errors=True)
    store.parent.mkdir(parents=True, exist_ok=True)
    t = time.monotonic()
    index.save(str(store))
    phases["save_s"] = time.monotonic() - t
    del index
    index_bytes = store_index_bytes(store)
    service = dict(cfg["service"])
    traffic = Traffic(spec["mix"], cfg["domains"])

    probe = records = None
    srv = svc = proc = None
    try:
        t = time.monotonic()
        svc = QueryService.from_dir(str(store), **service)
        srv, port = serve_in_thread(svc)
        phases["open_s"] = time.monotonic() - t
        t = time.monotonic()
        n_warm = 0
        if warm and service.get("backend", "auto") != "ewah":
            n_warm = warm_kernels(svc, traffic.max_operands(int(cfg["k"])))
        phases["warm_kernels_s"] = time.monotonic() - t
        t = time.monotonic()
        if warm:
            warm_statements(port, traffic, traffic.clients)
        drop_result_caches(svc)
        phases["warm_statements_s"] = time.monotonic() - t
        log(f"{n_rows} rows, {len(svc.index.shards)} shards, "
            f"{index_bytes} index bytes; build {json.dumps(build)}; "
            f"{json.dumps(phases)}; {n_warm} kernel warm-up calls")

        if trace:
            import jax.profiler as jp
            probe = tracing.Probe().install()
            trace_dir = run_dir / "trace" / file_stem(cell["name"])
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jp.ProfileOptions()
            opts.python_tracer_level = 0
            jp.start_trace(str(trace_dir), profiler_options=opts)
            window_ann = jp.TraceAnnotation(tracing.WINDOW_SPAN)
            window_ann.__enter__()
        d0 = Executor.kernel_dispatches
        c0 = svc.stats()["cache"]
        compiles["on"] = True
        proc = subprocess.Popen(
            [sys.executable, str(HERE / "loadgen.py"), "--port", str(port),
             "--mix", str(spec["dir"] / "traffic" /
                         f"{file_stem(cell['traffic'])}.json"), "--domains",
             json.dumps(cfg["domains"]), "--seed", str(seed),
             "--seconds", str(seconds), "--drain", str(DRAIN_S)],
            stdout=subprocess.PIPE, text=True, cwd=str(HERE))
        t0 = json.loads(proc.stdout.readline())["start"]
        setup_s = t0 - T_START
        # no statement starts after `seconds`; the window closes once the
        # last one sent is answered, so all the work sent counts, over all
        # the time it took
        out, _ = proc.communicate(timeout=seconds + DRAIN_S + 60)
        lines = [json.loads(ln) for ln in out.splitlines() if ln.strip()]
        records = [r for r in lines if "i" in r]
        t_end = next((r["end"] for r in lines if "end" in r),
                     time.monotonic())
        dispatches = Executor.kernel_dispatches - d0
        c1 = svc.stats()["cache"]
        if trace:
            window_ann.__exit__(None, None, None)
            jp.stop_trace()
        compiles["on"] = False
        stats = dev.memory_stats() or {}
        memory_peak = int(stats.get("peak_bytes_in_use", 0))
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait()
        if probe is not None:
            probe.remove()
        if srv is not None:
            srv.shutdown()
            srv.server_close()
        if svc is not None:
            svc.close()
        svc = None
        gc.collect()
        shutil.rmtree(store, ignore_errors=True)

    log(f"compilations inside the window: {compiles['n']} (expected 0)")
    window = [r for r in records if r["t1"] is not None
              and r["status"] == 200]
    failed = [r for r in records if r["err"] is not None
              or (r["status"] is not None and r["status"] != 200)]
    unanswered = [r for r in records if r["t1"] is None and r["err"] is None]
    answered = [r for r in records if r["status"] == 200]
    by_tpl: Dict[str, int] = {}
    for r in window:
        by_tpl[r["tpl"]] = by_tpl.get(r["tpl"], 0) + 1
    log(f"window: {len(window)} statements, "
        f"{sum(1 for r in window if (r['resp'] or {}).get('cached'))} "
        f"answered from the result cache; by template {json.dumps(by_tpl)}")

    trace_red = None
    if trace:
        xplanes = sorted(trace_dir.glob("**/*.xplane.pb"))
        simple = tracing.simplify(str(xplanes[-1]))
        log("trace planes: " + "; ".join(
            f"{pl['name']}: " + ", ".join(
                f"{ln['name']} ({len(ln['events'])})" for ln in pl["lines"])
            for pl in simple["planes"]))
        tracing.save(simple, str(run_dir / "trace" /
                                 f"{file_stem(cell['name'])}.json"))
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_red = tracing.reduce_trace(simple)
        log(f"trace: {json.dumps(trace_red)}")

    # -- correctness, after the program's state is gone --------------------
    t = time.monotonic()
    ref = reference_mod.Reference(cols, table["measures"], cfg.get("sort"),
                                  max_rows=service.get("max_rows", 10_000))
    ctl = reference_mod.Reference(
        cols, table["measures"], cfg.get("sort"),
        max_rows=service.get("max_rows", 10_000),
        drop_last=max(n_rows // control, 1)) if control else None
    wrong = []
    checked = sample_records(answered, seed, CHECK_MAX)
    for r in checked:
        _, body = traffic.statement(seed, r["i"])
        got = ctl.answer(body) if control else r["resp"]
        if not reference_mod.matches(ref.answer(body), got):
            wrong.append(r["tpl"])
    log(f"reference: {len(checked)} answers in "
        f"{time.monotonic() - t:.3f} s; wrong by template "
        f"{json.dumps({k: wrong.count(k) for k in sorted(set(wrong))})}")

    peaks = json.loads((spec["dir"] / "peaks.json").read_text())["devices"]
    ctx = Context(
        window=window, window_s=t_end - t0, setup_s=setup_s,
        index_bytes=index_bytes, n_rows=n_rows, build=build,
        dispatches=dispatches,
        cache_delta=(c1["hits"] - c0["hits"], c1["misses"] - c0["misses"]),
        probe=probe, trace=trace_red, traced=(t0, t_end),
        peaks=peaks.get(dev.device_kind))
    if trace and dev.platform == "tpu" and ctx.peaks is None:
        raise SystemExit(f"no peaks for device kind {dev.device_kind!r} "
                         "in peaks.json")
    metrics = read_metrics(spec["per_layer"] if trace else spec["end_to_end"],
                           ctx, spec["dir"])
    checks = {
        "wrong_answers": {"value": len(wrong), "limit": 0},
        "failed_statements": {"value": len(failed) + len(unanswered),
                              "limit": 0},
        "checked_answers": {"value": len(checked), "limit": 1},
    }
    correct = (len(wrong) == 0 and not failed and not unanswered
               and len(checked) >= 1)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": len(records),
              "failed": len(failed) + len(unanswered), "metrics": metrics,
              "device": device}
    if trace_red is not None:
        device["busy_s"] = trace_red["busy_s"]
        device["window_s"] = trace_red["window_s"]
        result["breakdown"] = {"device_ops": trace_red["device_ops"],
                               "idle_gaps": trace_red["idle_gaps"]}
    result["checks"] = checks
    return result


def check_lines(checks: Dict) -> List[str]:
    return [f"check {k}: {v['value']} "
            f"({'at least' if k == 'checked_answers' else 'at most'} "
            f"{v['limit']})" for k, v in checks.items()]


def device_ok(chips: int) -> Optional[str]:
    """Why this process cannot run the cell on the chip, or None."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return f"no TPU: JAX runs on {devs[0].platform}"
    if len(devs) < chips:
        return f"{len(devs)} TPU chips, the cell asks for {chips}"
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rows", type=int, default=None,
                    help="rehearsal only: table rows; never prints a result")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="judge the control (a stale copy missing the last "
                         f"rows/{CONTROL_SHARE} rows) in the program's place")
    args = ap.parse_args(argv)
    pin_environment()
    spec = load_spec(args.workload)
    why = device_ok(int(spec["cell"]["chips"]))
    if why is not None and args.rows is None:
        log(f"refused: {why}")
        return 2
    result = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                      rows=args.rows,
                      control=CONTROL_SHARE if args.control else 0)
    lines = check_lines(result["checks"])
    if why is not None:
        log(f"rehearsal result {json.dumps(result)}")
        for ln in lines:
            log(ln)
        log(f"refused: {why}")
        return 1
    for ln in lines:
        print(ln, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # the result line is the last output: no interpreter teardown (the
    # service's pool threads, the TPU runtime) may print after it
    os._exit(code)
