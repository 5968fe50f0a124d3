"""The command fails, and prints no result, where it finds no TPU, and in
a directory that holds only the benchmark's own files."""
import json
import os
import shutil
import subprocess
import sys

import benchtest_util

ROOT = benchtest_util.ROOT
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    cmd = [sys.executable] + SPEC["command"][1:] + [
        "--workload", SPEC["workloads"][0]["name"], "--seed", "3",
        "--seconds", "1", "--trace", "0"]
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def _no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if "metrics" in json.loads(line):
                return False
        except ValueError:
            pass
    return True


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in SPEC["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)
