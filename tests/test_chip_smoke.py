"""chip_smoke.py's phases at reduced sizes on the CPU (kernels
interpreted), and its refusal to report a result off a TPU."""
import importlib.util
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_planner_phase_reduced(smoke, capsys):
    smoke.phase_planner(big=12, small=8)
    out = capsys.readouterr().out
    assert out.count("bit-equal=True") == 2


def test_online_phase_reduced(smoke, capsys):
    smoke.phase_online(M=200)
    assert "violations=0" in capsys.readouterr().out


def test_sweep_phase_interpreted(smoke, capsys):
    smoke.phase_sweep(M=24, interpret=True)
    assert "match the oracle" in capsys.readouterr().out


def test_served_phase_reduced(smoke, capsys, monkeypatch, tmp_path):
    # a set JAX_COMPILATION_CACHE_DIR leaves JAX's cache as configured at
    # import (off here), so the CLI writes nothing into the checkout
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    smoke.phase_served(argv=["--arch", "minitron-4b", "--users", "6",
                             "--seq", "16", "--beta", "10", "30"])
    out = capsys.readouterr().out
    assert out.count("[served]") == 2
    assert "peak_bytes_in_use=not reported" in out


def test_entry_point_refuses_non_tpu(smoke, capsys):
    assert smoke.main() != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "no TPU" in captured.err
