"""One statement path: every statement kind answers the same on every
fan-out — a ``BitmapIndex``, a 4-shard ``ShardedIndex`` on threads, a
``LiveIndex`` after appends and deletes, and a 2-worker ``ClusterService``
over socket workers — and equals a NumPy row scan.  Each case sends one
wire statement through ``statement(obj)`` on all four."""
import numpy as np
import pytest

from repro.core import BitmapIndex, ShardedIndex, col, statement
from repro.core.expr import to_wire
from repro.core.ingest import LiveIndex
from repro.distributed.cluster import ClusterService, Policy
from repro.serve.query_api import QueryService
from repro.serve.worker_api import ShardWorker, WorkerServer

NAMES = ["a", "b", "c", "d"]
CARDS = [5, 4, 3, 6]
FILTER = (col("a") <= 2) & ~(col("b") == 1)
DELETE = (col("c") == 2) & (col("a") == 0)
CLUSTER_ONLY = ("exact", "missing_shards", "covered_rows")

STATEMENTS = {
    "count": {"count": True},
    "group_count": {"group_count": "b"},
    "sum": {"sum": "m"},
    "avg": {"avg": "m"},
    "min": {"min": "m"},
    "max": {"max": "m"},
    "group_sum_1col": {"sum": "m", "by": ["a"]},
    "group_avg_3cols": {"avg": "m", "by": ["a", "b", "c"]},
    "top_k_count": {"top_k": {"col": "d", "k": 3}},
    "top_k_sum": {"top_k": {"col": "d", "k": 3, "measure": "m"}},
}


def _rows(rng, n):
    return np.column_stack([rng.integers(0, c, n) for c in CARDS]) \
        .astype(np.int64), rng.integers(0, 1000, n).astype(np.int64)


@pytest.fixture(scope="module")
def services(tmp_path_factory):
    """The four fan-outs over one logical table, and that table's rows."""
    rng = np.random.default_rng(17)
    base_rows, base_m = _rows(rng, 1500)
    more_rows, more_m = _rows(rng, 300)
    base = ShardedIndex.build(base_rows, shard_rows=384, cards=CARDS,
                              column_names=NAMES, measures={"m": base_m})
    live = LiveIndex(base)
    live.append(more_rows, measures={"m": more_m})
    live.delete(DELETE)
    rows = np.concatenate([base_rows, more_rows])
    m = np.concatenate([base_m, more_m])
    keep = ~((rows[:, 2] == 2) & (rows[:, 0] == 0))
    rows, m = rows[keep], m[keep]

    mono = BitmapIndex.build(rows, cards=CARDS, column_names=NAMES)
    mono.measures = {"m": m}
    sharded = ShardedIndex.build(rows, shard_rows=-(-len(rows) // 128) * 32,
                                 cards=CARDS, column_names=NAMES,
                                 measures={"m": m})
    assert sharded.n_shards == 4
    d = str(tmp_path_factory.mktemp("statement-store"))
    sharded.save(d)
    servers = [WorkerServer(ShardWorker(d, [], backend="ewah")).start()
               for _ in range(2)]
    cluster = ClusterService(d, [s.address for s in servers], replication=2,
                             policy=Policy(deadline_s=5.0, retries=2,
                                           backoff_s=0.01,
                                           hedge_after_s=0.15),
                             backend="ewah")
    cluster.start(monitor=False)
    svcs = {"bitmap": QueryService(mono, backend="ewah"),
            "sharded": QueryService(sharded, backend="ewah"),
            "live": QueryService(live, backend="ewah"),
            "cluster": cluster}
    yield svcs, rows, m
    for svc in svcs.values():
        svc.close()
    for s in servers:
        s.stop()


def _scan(sel, rows, m, mask):
    """The answer's fields, by a row scan of the rows ``mask`` keeps."""
    r, v = rows[mask], m[mask]
    kind = next(k for k in sel if k != "by")
    if kind == "count" and "by" not in sel:
        return {"count": int(mask.sum())}
    if kind == "group_count":
        c = NAMES.index(sel[kind])
        return {"counts": np.bincount(r[:, c], minlength=CARDS[c]).tolist()}
    if kind == "top_k":
        c = NAMES.index(sel[kind]["col"])
        counts = np.bincount(r[:, c], minlength=CARDS[c])
        w = counts if "measure" not in sel[kind] else \
            np.bincount(r[:, c], weights=v, minlength=CARDS[c]).astype(int)
        ranked = sorted((g for g in range(CARDS[c]) if counts[g]),
                        key=lambda g: (-w[g], g))
        return {"top": [[g, int(w[g])] for g in ranked[:sel[kind]["k"]]]}
    if "by" not in sel:
        if not len(v):
            value = 0 if kind == "sum" else None
        else:
            value = {"sum": int(v.sum()), "avg": float(v.sum()) / len(v),
                     "min": int(v.min()), "max": int(v.max())}[kind]
        return {"value": value, "count": int(mask.sum())}
    cs = [NAMES.index(c) for c in sel["by"]]
    shape = [CARDS[c] for c in cs]
    cell = np.ravel_multi_index(tuple(r[:, c] for c in cs), shape)
    size = int(np.prod(shape))
    counts = np.bincount(cell, minlength=size)
    sums = np.zeros(size, dtype=np.int64)
    np.add.at(sums, cell, v)
    if kind == "sum":
        values = sums.tolist()
    else:
        values = [float(s) / n if n else None for s, n in zip(sums, counts)]
    return {"shape": shape,
            "counts": counts.reshape(shape).tolist(),
            "values": np.asarray(values, dtype=object).reshape(shape).tolist()}


@pytest.mark.parametrize("filtered", [False, True], ids=["all", "where"])
@pytest.mark.parametrize("name", list(STATEMENTS))
def test_every_fan_out_gives_the_row_scan_answer(services, name, filtered):
    svcs, rows, m = services
    obj = {"select": STATEMENTS[name]}
    mask = np.ones(len(rows), dtype=bool)
    if filtered:
        obj["where"] = to_wire(FILTER)
        mask = (rows[:, 0] <= 2) & (rows[:, 1] != 1)
    answers = {fan: svc.statement(obj) for fan, svc in svcs.items()}
    assert answers["cluster"]["exact"]
    assert answers["cluster"]["missing_shards"] == []
    assert answers["cluster"]["covered_rows"] == len(rows)
    bare = {fan: {k: v for k, v in a.items()
                  if k != "cached" and k not in CLUSTER_ONLY}
            for fan, a in answers.items()}
    for fan in ("sharded", "live", "cluster"):
        assert bare[fan] == bare["bitmap"], fan
    want = _scan(obj["select"], rows, m, mask)
    assert {k: bare["bitmap"][k] for k in want} == want


@pytest.mark.parametrize("task", [
    ("expr", FILTER), ("count", None), ("gcount", 1, FILTER),
    ("agg", "m", FILTER), ("gagg", "m", (0, 2), None),
    ("gtop", "m", 3, 2, FILTER), ("gvals", None, 3, (0, 4), FILTER)],
    ids=lambda t: t[0] if isinstance(t, tuple) else None)
def test_worker_codec_round_trips_every_task(services, task):
    # what a worker would answer for one shard, through the wire codec
    svcs, _rows_, _m = services
    shard = svcs["sharded"].index.shards[1]
    task = statement.decode_task(statement.encode_task(task))
    part = statement.run_on_shard(shard, task, backend="ewah")
    arrays = {}
    back = statement.decode_partial(
        statement.encode_partial(part, "p1", arrays), arrays)
    if isinstance(part, dict):
        assert back.keys() == part.keys()
        for k, v in part.items():
            assert np.array_equal(back[k], v)
            assert np.asarray(back[k]).dtype == np.asarray(v).dtype
    elif isinstance(part, np.ndarray):
        assert np.array_equal(back, part) and back.dtype == part.dtype
    else:
        assert back == part
