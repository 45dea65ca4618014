"""The trace reduction on a small trace kept beside the tests."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import tracing  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def test_union_and_coverage():
    s, e = tracing.union(np.array([5.0, 0.0, 2.0, 10.0]),
                         np.array([6.0, 3.0, 4.0, 11.0]))
    assert s.tolist() == [0.0, 5.0, 10.0] and e.tolist() == [4.0, 6.0, 11.0]
    got = tracing.covered(s, e, np.array([0.0, 3.5, 6.0]),
                          np.array([11.0, 5.5, 10.0]))
    assert got.tolist() == [6.0, 1.0, 0.0]


def test_small_trace():
    red = tracing.reduce_trace(json.loads(
        (DATA / "trace_small.json").read_text()))
    # window [1000, 11000) ns; XLA Ops clipped to it, in union: [1000,
    # 1200) of the concatenate, [2100, 2600) of word_logical, [2700, 2800)
    # of the pad, [9000, 9400), and [10900, 11000) of the slice
    assert red["window_s"] == pytest.approx(10000e-9)
    assert red["busy_s"] == pytest.approx(1300e-9)
    ops = dict(red["device_ops"])
    assert ops["word_logical"] == pytest.approx(1000e-9)
    assert ops["concatenate"] == pytest.approx(200e-9)
    assert ops["slice"] == pytest.approx(100e-9)
    assert [k for k, _ in red["device_ops"]][0] == "word_logical"
    # gaps, each named by the first span (most specific first) covering
    # half of it: [2800, 9000) statements cover 1800, http 4700 -> http;
    # [9400, 10900) http covers 1100 -> http; [1200, 2100) the statement
    # covers 500 -> service.statement; [2600, 2700) inside the reduction
    gaps = red["idle_gaps"]
    assert [round(g * 1e9) for _, g in gaps] == [6200, 1500, 900, 100]
    assert [n for n, _ in gaps] == ["http", "http", "service.statement",
                                   "kops.logical_reduce"]
    assert sum(red["idle_by_span"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"])


def test_no_window_span_reads_nothing():
    assert tracing.reduce_trace({"planes": []}) is None
