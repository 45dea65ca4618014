"""Each cell: whole runs at a tiny size with the timed path broken underneath, and the
control in the program's place: each must come out not correct."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import run  # noqa: E402

ROOT = Path(__file__).resolve().parents[3]
CELLS = ("lineitem-arrival.dense-filters", "lineitem-lex.dense-filters")


def _run(cell, tmp_path, control=0):
    spec = run.load_spec(cell, ROOT)
    return run.run_cell(spec, 2**32 + 77, 1.5, False, rows=2500,
                        control=control, compile_cache=False, warm=False,
                        run_dir=tmp_path)


def _flip_kernel_answer(monkeypatch):
    """A word of every kernel reduction altered where it is produced."""
    import jax.numpy as jnp
    from repro.kernels import ops as kops
    orig = kops.logical_reduce

    def flipped(*a, **kw):
        out = orig(*a, **kw)
        return out.at[0].set(out[0] ^ jnp.uint32(1))

    monkeypatch.setattr(kops, "logical_reduce", flipped)


def _drop_a_shard(monkeypatch):
    """The last shard's partial left out of the merge (shard 0's taken in
    its place)."""
    from repro.core.shard import ShardedIndex
    orig = ShardedIndex._fan_out

    def fan_out(self, *a, **kw):
        parts = orig(self, *a, **kw)
        return parts[:-1] + parts[:1]

    monkeypatch.setattr(ShardedIndex, "_fan_out", fan_out)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_reaches_the_kernels(cell, tmp_path):
    from repro.core.executor import Executor
    d0 = Executor.kernel_dispatches
    res = _run(cell, tmp_path)
    assert res["correct"], res["checks"]
    assert res["checks"]["wrong_answers"]["value"] == 0
    assert Executor.kernel_dispatches > d0


@pytest.mark.parametrize("fault", [_flip_kernel_answer, _drop_a_shard])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cell, fault, monkeypatch, tmp_path):
    fault(monkeypatch)
    res = _run(cell, tmp_path)
    assert not res["correct"]
    c = res["checks"]
    assert c["wrong_answers"]["value"] + c["failed_statements"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell, tmp_path):
    # at this size the stale copy misses 2% of the rows (50 of them)
    res = _run(cell, tmp_path, control=50)
    assert not res["correct"]
    assert res["checks"]["wrong_answers"]["value"] > 0
    assert np.isfinite(res["metrics"]["stmts_per_s"]["value"])
