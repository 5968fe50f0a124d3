"""Configurations, mixes and per-layer metrics are found by file name,
and BENCHMARK.json keeps the contract's shape."""
import json
import shutil
from types import SimpleNamespace

import benchtest_util
import pytest
import spec_checks

from bench import run

ROOT = benchtest_util.ROOT
SPEC = run.load_spec()


def test_every_cell_resolves_its_files():
    spec_checks.cells_resolve_their_files(SPEC, ROOT)


def test_every_metric_has_a_reader():
    spec_checks.metrics_have_readers(SPEC, ROOT)


def test_names_and_paths_keep_the_contract():
    spec_checks.names_and_paths_keep_the_contract(SPEC, ROOT)


def test_each_cell_reports_a_per_layer_metric():
    spec_checks.cells_report_a_per_layer_metric(SPEC, ROOT)


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


def test_a_served_model_joins_the_real_spec_as_files_and_entries(tmp_path):
    """The repository's own BENCHMARK.json and bench/, joined by a served
    configuration of another layer plan as new files (its reference
    module and configuration) and entries (its cell on busy, named in
    every per-layer list that names minitron4b.busy), pass every check
    the tests run on the spec: the old formulas on their own
    configurations' cells, the generic ones on every served cell."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "tests" / "bench" / "windowed_reference.py",
                tmp_path / "bench" / "reference" / "extra_windowed.py")
    cfg = json.loads((ROOT / "bench/configs/minitron4b-coinf.json"
                      ).read_text())
    # embedding and head 2·V·d, and two layers of attention 2·d·(H + KV)·hd,
    # MLP 2·d·ff and two norm scales of d: 131,072 + 2 · 114,944; the final
    # norm is left out, as in every configuration's params
    params = 360_960
    cfg.update(name="extra-windowed", reduced=[],
               model=dict(cfg["model"], **benchtest_util.TINY_MODEL,
                          arch="windowed", reference="extra_windowed",
                          plan=WINDOWED_PLAN, params=params))
    del cfg["departs"]
    (tmp_path / "bench/configs/extra-windowed.json").write_text(
        json.dumps(cfg))
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "extra-windowed", "source": "x",
                            "file": "bench/configs/extra-windowed.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "extra-windowed.busy",
                              "config": "extra-windowed", "traffic": "busy",
                              "chips": 1, "why": "x"})
    for m in spec["per_layer"]:
        if "minitron4b.busy" in m.get("workloads", []):
            m["workloads"].append("extra-windowed.busy")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    spec = run.load_spec(tmp_path)
    assert "extra-windowed.busy" in spec_checks.served_cells(spec, tmp_path)
    assert "extra-windowed.busy" not in spec_checks.old_formula_cells(spec)
    spec_checks.run_all(spec, tmp_path)


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        run.cell_parts(SPEC, "no-such-cell")
