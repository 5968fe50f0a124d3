"""Configurations, mixes and per-layer metrics are found by file name,
and BENCHMARK.json keeps the contract's shape."""
import json
import re
import shutil
from types import SimpleNamespace

import benchtest_util
import pytest

from bench import run

ROOT = benchtest_util.ROOT
SPEC = run.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_cell_resolves_its_files():
    for cell in SPEC["workloads"]:
        c, config, mix = run.cell_parts(SPEC, cell["name"])
        assert config["name"] == cell["config"]
        assert mix["mode"] in ("online", "waves")
        assert set(config["limits"]) >= {"plan_energy_gap", "deadline_excess"}


def test_every_metric_has_a_reader():
    for m in SPEC["per_layer"]:
        assert callable(run.reader(m["name"]))
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}


def test_names_and_paths_keep_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/")
        assert json.loads((ROOT / c["file"]).read_text())["reduced"] == \
            c["reduced"]
    for m in SPEC["end_to_end"]:
        assert m["bound"] <= 0.25
    for x in SPEC["configs"] + SPEC["workloads"]:
        assert 1 <= len(x["why"]) <= 200 and "\n" not in x["why"]
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    assert {m["name"] for m in SPEC["end_to_end"]} == {
        "flush_ms.p50", "flush_ms.p95", "req_per_s", "setup_s"}


def test_each_cell_reports_a_per_layer_metric():
    for cell in SPEC["workloads"]:
        assert run.metrics_of(SPEC, cell, True)
        assert len(run.metrics_of(SPEC, cell, False)) == 4


def test_a_new_cell_is_files_and_entries_alone(tmp_path):
    """A mix, a configuration and a metric added as files, named in a copy
    of BENCHMARK.json, are found with no code change."""
    for d in ("bench/configs", "bench/mixes", "bench/metrics"):
        shutil.copytree(ROOT / d, tmp_path / d)
    (tmp_path / "bench/mixes/waves-8.json").write_text(json.dumps(
        {"mode": "waves", "wave_users": 8, "beta": [0.0, 5.0]}))
    cfg = json.loads((ROOT / "bench/configs/mnv2-paper.json").read_text())
    cfg["name"] = "mnv2-copy"
    (tmp_path / "bench/configs/mnv2-copy.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/metrics/waves_seen.py").write_text(
        "def read(run):\n    return float(len(run.flushes))\n")
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append(dict(spec["configs"][1], name="mnv2-copy",
                                file="bench/configs/mnv2-copy.json"))
    spec["workloads"].append({"name": "mnv2-8.og", "config": "mnv2-copy",
                              "traffic": "waves-8", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "waves_seen", "unit": "count",
                              "better": "higher", "source": "host_clock",
                              "layer": "planner service",
                              "moves": "req_per_s",
                              "workloads": ["mnv2-8.og"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    spec = run.load_spec(tmp_path)
    cell, config, mix = run.cell_parts(spec, "mnv2-8.og", tmp_path)
    assert config["name"] == "mnv2-copy" and mix["wave_users"] == 8
    names = [m["name"] for m in run.metrics_of(spec, cell, True)]
    assert names == ["waves_seen"]
    read = run.reader("waves_seen", tmp_path)
    assert read(SimpleNamespace(flushes=[1, 2, 3])) == 3.0


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        run.cell_parts(SPEC, "no-such-cell")
