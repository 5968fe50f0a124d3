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


def _digest(d):
    return {str(p.relative_to(d)): p.read_bytes() for p in sorted(d.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


#: two segments of the program's existing layer kinds
WINDOWED_PLAN = [[[{"kind": "attn", "ffn": "dense", "window": None}], 1],
                 [[{"kind": "swa", "ffn": "dense", "window": 8}], 1]]


def test_a_model_configuration_is_files_and_entries_alone(tmp_path):
    """A decoder with another layer plan (full attention, then a sliding
    window of 8 at 32-token prompts), added as a configuration, its own
    reference module, a mix and entries in a copy of BENCHMARK.json, is
    served, checked and counted with no edit under bench/."""
    from repro.core import profile_from_arch
    from bench import deploy, flops, sut
    from bench.peaks import peaks
    before = _digest(ROOT / "bench")
    ref = tmp_path / "bench" / "reference"
    ref.mkdir(parents=True)
    shutil.copy(ROOT / "tests" / "bench" / "windowed_reference.py",
                ref / "windowed.py")
    cfg = json.loads((ROOT / "bench/configs/minitron4b-coinf.json"
                      ).read_text())
    model = dict(cfg["model"], **benchtest_util.TINY_MODEL, arch="windowed",
                 reference="windowed", plan=WINDOWED_PLAN)
    cfg.update(name="windowed", reduced=[], model=model,
               task={"kind": "dense_prefill", "seq": 32, "act_bytes": 2})
    del cfg["departs"]
    (tmp_path / "bench/configs").mkdir()
    (tmp_path / "bench/configs/windowed.json").write_text(json.dumps(cfg))
    mix = json.loads((ROOT / "bench/mixes/busy.json").read_text())
    (tmp_path / "bench/mixes").mkdir()
    (tmp_path / "bench/mixes/busy-4.json").write_text(
        json.dumps(dict(mix, devices=4)))
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append(dict(spec["configs"][0], name="windowed",
                                file="bench/configs/windowed.json",
                                reduced=[], why="x"))
    spec["workloads"].append({"name": "windowed.busy", "config": "windowed",
                              "traffic": "busy-4", "chips": 1, "why": "x"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    spec = run.load_spec(tmp_path)

    # served and checked
    seed = 2 ** 35 + 11
    x = run.execute(spec, "windowed.busy", seed, 0.5, False,
                    require_tpu=False, root=tmp_path)
    assert sum(f.n for f in x["flushes"]) > 0
    nums = run.compare(x, seed)
    assert nums["logit_err"]["value"] <= nums["logit_err"]["limit"], nums
    assert all(c["value"] <= c["limit"] for c in nums.values()
               if c["limit"] is not None), nums
    # the window is computed on both sides: without it the reference differs
    full = dict(model, plan=[[[{"kind": "attn", "ffn": "dense",
                                "window": None}], 2]])
    ans = x["answers"]
    from bench import check
    err = check.logits(full, x["params"], ans["kept"], ans["tokens"],
                       root=tmp_path)["logit_err"]
    assert err > 100 * nums["logit_err"]["limit"]
    shapes = x["params"]["segments"]
    assert [len(seg) for seg in shapes] == [1, 1]

    # counted: the profile is the program's, the FLOPs are per layer
    P = deploy.task_profile(cfg, tmp_path)
    want = profile_from_arch(sut.arch_config(model), seq=32)
    assert P.A.tobytes() == want.A.tobytes()
    assert P.O.tobytes() == want.O.tobytes()
    B, S, d, H, hd = 3, 32, 128, 4, 32
    matmul = 2 * B * S * (2 * d * H * hd + 2 * d * 2 * hd + 2 * d * 256)
    pairs = [S * (S + 1) // 2, sum(min(q + 1, 8) for q in range(S))]
    for i in range(2):
        assert flops.layer_flops(model, i, B, S, tmp_path) == \
            matmul + 2 * 2 * B * H * hd * pairs[i]
    assert flops.forward_flops(model, B, S, tmp_path) == \
        2 * matmul + 2 * 2 * B * H * hd * sum(pairs) + 2 * B * S * d * 512
    by = flops.layers_roofline_s(model, B, S, peaks("TPU v5 lite"), tmp_path)
    assert by["compute"] + by["memory"] > 0
    assert _digest(ROOT / "bench") == before


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        run.cell_parts(SPEC, "no-such-cell")
