"""The run refuses a backend that is not a TPU: there is no fallback."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import run  # noqa: E402


def test_cpu_backend_is_refused(monkeypatch, capsys):
    # main pins these for its own process; give them back afterwards
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "unused")
    monkeypatch.setenv("REPRO_COST_MODEL", "unused")
    rc = run.main(["--workload", "lineitem-arrival.dense-filters",
                   "--seed", "3000000019", "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc == 2
    assert out == ""
    assert "refused: no TPU" in err


def test_device_reason_names_the_backend():
    assert run.device_ok(1).startswith("no TPU: JAX runs on cpu")
