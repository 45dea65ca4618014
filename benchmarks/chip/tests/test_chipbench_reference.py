"""The row-scan reference against the program, on tiny stores, for every
statement kind of both mixes and the other kinds the reference knows."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import reference  # noqa: E402
import run  # noqa: E402
from traffic import Traffic  # noqa: E402

ROOT = Path(__file__).resolve().parents[3]
CELLS = ("lineitem-arrival.dense-filters", "lineitem-lex.dense-filters")


def _service(spec, rows, backend, tmp_path):
    from repro.core import ShardedIndex, lex_sort
    from repro.serve.query_api import QueryService
    cfg = spec["config"]
    t = run.generate(cfg, 3, rows)
    names = cfg["columns"]
    mat = np.stack([t["columns"][c] for c in names], axis=1)
    measures = t["measures"] or None
    if cfg.get("sort"):
        perm = lex_sort(mat, [names.index(c) for c in cfg["sort"]])
        mat = mat[perm]
        measures = measures and {k: v[perm] for k, v in measures.items()}
    index = ShardedIndex.build(mat, shard_rows=-(-len(mat) // 128) * 32,
                               k=cfg["k"], column_names=names,
                               measures=measures)
    index.save(str(tmp_path / "store"))
    # shard_processes=0: the test process forks no shard workers
    svc = QueryService.from_dir(str(tmp_path / "store"), backend=backend,
                                max_rows=50, shard_processes=0)
    ref = reference.Reference(t["columns"], t["measures"], cfg.get("sort"),
                              max_rows=50)
    return svc, ref


def _ask(svc, body):
    return svc.query(body["query"]) if "query" in body else svc.statement(body)


@pytest.mark.parametrize("cell", CELLS)
def test_every_template_matches_the_program(cell, tmp_path):
    spec = run.load_spec(cell, ROOT)
    svc, ref = _service(spec, 3000, "ewah", tmp_path)
    traffic = Traffic(spec["mix"], spec["config"]["domains"])
    try:
        seen = set()
        for i in range(8 * len(traffic.block)):
            tpl, body = traffic.statement(9, i)
            seen.add(tpl)
            assert reference.matches(ref.answer(body), _ask(svc, body)), \
                (tpl, body)
        for tpl, body in traffic.warmup():
            assert reference.matches(ref.answer(body), _ask(svc, body)), \
                (tpl, body)
        assert seen == {t["name"] for t in spec["mix"]["templates"]}
    finally:
        svc.close()


@pytest.mark.parametrize("cell", CELLS)
def test_cell_backend_matches_the_reference(cell, tmp_path):
    # the cell's own backend (kernels interpreted on the CPU), one
    # statement of each template
    spec = run.load_spec(cell, ROOT)
    svc, ref = _service(spec, 2000, spec["config"]["service"]["backend"],
                        tmp_path)
    traffic = Traffic(spec["mix"], spec["config"]["domains"])
    try:
        for tpl, body in traffic.warmup()[::2]:
            assert reference.matches(ref.answer(body), _ask(svc, body)), tpl
    finally:
        svc.close()


OTHER = [
    {"select": {"avg": "l_extendedprice"},
     "where": {"op": "eq", "col": "l_shipmode", "value": 3}},
    {"select": {"min": "l_extendedprice"},
     "where": {"op": "eq", "col": "l_discount", "value": 99}},
    {"select": {"max": "l_quantity"},
     "where": {"op": "not", "arg": {"op": "eq", "col": "l_linestatus",
                                    "value": 0}}},
    {"select": {"sum": "l_extendedprice", "by": ["l_returnflag",
                                                 "l_linestatus"]},
     "where": {"op": "range", "col": "l_shipdate", "hi": 2400}},
    {"select": {"count": True, "by": ["l_shipmode"]}},
    {"select": {"avg": "l_quantity", "by": ["l_returnflag",
                                            "l_linestatus"]},
     "where": {"op": "range", "col": "l_shipdate", "lo": 2500}},
    {"select": {"top_k": {"col": "l_shipmode", "k": 3,
                          "measure": "l_extendedprice"}}},
    {"select": {"top_k": {"col": "l_discount", "k": 4}},
     "where": {"op": "in", "col": "l_quantity", "values": [1, 2, 70]}},
    {"select": {"group_count": "l_linestatus"},
     "where": {"op": "or", "args": [
         {"op": "eq", "col": "l_shipinstruct", "value": 1},
         {"op": "range", "col": "l_quantity", "lo": -5, "hi": 3}]}},
    {"query": {"op": "eq", "col": "l_returnflag", "value": 0}},
]


def test_other_statement_kinds_match_the_program(tmp_path):
    spec = run.load_spec("lineitem-arrival.dense-filters", ROOT)
    svc, ref = _service(spec, 3000, "ewah", tmp_path)
    try:
        for body in OTHER:
            assert reference.matches(ref.answer(body), _ask(svc, body)), body
    finally:
        svc.close()


def test_control_breaks_visibility():
    cols = {"a": np.arange(100) % 7}
    ref = reference.Reference(cols, max_rows=1000)
    ctl = reference.Reference(cols, max_rows=1000, drop_last=1)
    body = {"select": {"count": True},
            "where": {"op": "eq", "col": "a", "value": 99 % 7}}
    assert ref.answer(body) == {"count": 15}
    assert not reference.matches(ref.answer(body), ctl.answer(body))


@pytest.mark.parametrize("cell", CELLS)
def test_warm_up_leaves_no_result_behind(cell, tmp_path):
    # after drop_result_caches a warm-up statement is executed again; where
    # the configuration turns the service's cache off, no result is kept
    spec = run.load_spec(cell, ROOT)
    svc, _ = _service(spec, 2000, "ewah", tmp_path)
    svc.cache.capacity = spec["config"]["service"].get("cache_entries", 256)
    traffic = Traffic(spec["mix"], spec["config"]["domains"])
    try:
        _, body = traffic.warmup()[0]
        _ask(svc, body)
        run.drop_result_caches(svc)
        assert all(s["entries"] == 0 for s in svc.index.cache_stats())
        assert _ask(svc, body)["cached"] is False
        _ask(svc, body)
        kept = sum(s["entries"] for s in svc.index.cache_stats())
        assert (kept == 0) == (svc.cache.capacity == 0)
    finally:
        svc.close()
