"""The roofline's byte count on hand-built tile flags, and its share."""
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import run  # noqa: E402
import tracing  # noqa: E402

HERE = Path(__file__).resolve().parents[1]


def test_probe_counts_dirty_tiles_only():
    from repro.kernels import ops as kops
    from repro.kernels import word_logical as wl
    orig = kops.logical_reduce
    mat = np.zeros((3, 4096), dtype=np.uint32)
    mat[0, :1024] = 5
    mat[1, 1024:3072] = 0xFFFFFFFF
    mat[2, 2048:2050] = 1
    flags = kops.np_row_flags(mat)
    # hand-built view of the same flags: row 0 one dirty tile, row 1 all
    # clean (two all-ones tiles), row 2 one dirty tile
    assert np.count_nonzero(flags == wl.DIRTY) == 2
    hand = np.full((3, 4), wl.CLEAN0, dtype=np.int32)
    hand[0, 0] = hand[2, 2] = wl.DIRTY
    hand[1, 1:3] = wl.CLEAN1
    assert np.array_equal(flags, hand)
    probe = tracing.Probe().install()
    try:
        assert kops.logical_reduce is not orig
        out = np.asarray(kops.logical_reduce(mat, op="or", row_flags=hand))
        kops.logical_reduce(mat, op="and")  # no flags: nothing counted
    finally:
        probe.remove()
    assert kops.logical_reduce is orig
    assert np.array_equal(out, mat[0] | mat[1] | mat[2])
    assert probe.reduce_bytes(0, float("inf")) == 2 * 4 * 1024


def test_roofline_share_and_silence():
    mod = run._module(HERE / "metrics" / "logical_reduce_roofline.py")

    class FakeProbe:
        def __init__(self, n):
            self.n = n

        def reduce_bytes(self, a, b):
            return self.n

    peaks = {"hbm_bytes_per_s": 819e9}
    ctx = run.Context(probe=FakeProbe(819_000_000), traced=(0, 1),
                      trace={"busy_s": 0.004, "window_s": 1.0}, peaks=peaks)
    # 0.8 GB at 819 GB/s is 1 ms of the 4 ms busy
    assert abs(mod.read(ctx) - 25.0) < 1e-9
    ctx.probe = FakeProbe(None)
    assert mod.read(ctx) is None
    ctx.probe = FakeProbe(10)
    ctx.trace = {"busy_s": 0.0, "window_s": 1.0}
    assert mod.read(ctx) is None
