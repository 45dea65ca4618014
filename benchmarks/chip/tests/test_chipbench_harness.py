"""The harness finds cells, mixes and metrics by name, and a whole run at a
tiny size comes out correct, traced and untraced."""
import json
import shutil
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import run  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]

TEST_MIX = {"clients": 2, "templates": [
    {"name": "one_mode", "weight": 1,
     "draw": {"m": {"int": [0, "max:l_shipmode"]}},
     "body": {"select": {"count": True},
              "where": {"op": "eq", "col": "l_shipmode", "value": "$m"}}}]}
TEST_METRIC = '''"""Statements completed in the window (test only)."""


def read(ctx):
    return len(ctx.window)
'''


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    """A checkout where a later change added a mix, a cell and a per-layer
    metric as new files and entries, editing no file already there."""
    root = tmp_path_factory.mktemp("checkout")
    dest = root / "benchmarks" / "chip"
    shutil.copytree(BENCH, dest, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    (dest / "traffic" / "one_mode.json").write_text(json.dumps(TEST_MIX))
    (dest / "metrics" / "test_only_statements.py").write_text(TEST_METRIC)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": "lineitem-arrival.one-mode",
        "config": "tpch-lineitem-sf1-arrival-k1", "traffic": "one-mode",
        "chips": 1, "why": "test only"})
    bench["per_layer"].append({
        "name": "test_only.statements", "unit": "stmt", "better": "higher",
        "source": "host_clock", "layer": "service", "moves": "stmts_per_s",
        "workloads": ["lineitem-arrival.one-mode"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_new_mix_is_found_by_name(added):
    spec = run.load_spec("lineitem-arrival.one-mode", added)
    assert spec["mix"] == TEST_MIX
    assert spec["config"]["name"] == "tpch-lineitem-sf1-arrival-k1"
    assert "test_only.statements" in [m["name"] for m in spec["per_layer"]]


@pytest.mark.parametrize("trace", [False, True])
def test_run_of_an_added_cell(added, trace, tmp_path):
    spec = run.load_spec("lineitem-arrival.one-mode", added)
    res = run.run_cell(spec, 2**33 + 5, 1.5, trace, rows=2000,
                       compile_cache=False, warm=False,
                       run_dir=tmp_path)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    m = res["metrics"]
    if trace:
        # the per-layer metrics that list their cells do not list this one
        assert set(m) == {"test_only.statements"}
        assert m["test_only.statements"]["value"] > 0
        assert res["device"]["window_s"] > 0
    else:
        assert set(m) == {"stmts_per_s", "stmt_p50_ms", "setup_s"}
        assert all(v["value"] > 0 for v in m.values())


def test_bench_file_shape():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert bench["command"] == ["python3", "benchmarks/chip/run.py"]
    assert bench["paths"] == ["benchmarks/chip"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (BENCH / "metrics" / f"{run.file_stem(m['name'])}.py").exists()
    for w in bench["workloads"]:
        spec = run.load_spec(w["name"], ROOT)
        assert spec["mix"]["templates"]
        gen = BENCH / "generators" / f"{spec['config']['generator']}.py"
        assert gen.exists()
