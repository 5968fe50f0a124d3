"""Checks of a ``BENCHMARK.json`` and the files it names, each a function
of ``(spec, root)`` or, for one cell, ``(spec, cell, root)``.  The tests
run them on the repository's own spec, and on a copy of it that a further
served configuration joins as new files plus entries."""
from __future__ import annotations

import json
import math
import re

import benchtest_util  # noqa: F401  (puts the checkout on sys.path)
import jax
import numpy as np

from bench import deploy, flops, run, traffic
from bench.reference import model_module

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
#: the configurations the old formulas below were written for
OLD_FORMULA_CONFIGS = ("minitron4b-coinf", "mnv2-paper")
MINITRON_FILE = "bench/configs/minitron4b-coinf.json"


# ---- the spec's contract and each cell's files --------------------------------
def cells_resolve_their_files(spec: dict, root) -> None:
    for cell in spec["workloads"]:
        _, config, mix = run.cell_parts(spec, cell["name"], root)
        assert config["name"] == cell["config"]
        assert mix["mode"] in ("online", "waves")
        assert set(config["limits"]) >= {"plan_energy_gap", "deadline_excess"}


def metrics_have_readers(spec: dict, root) -> None:
    for m in spec["per_layer"]:
        assert callable(run.reader(m["name"], root))
        assert m["moves"] in {e["name"] for e in spec["end_to_end"]}


def names_and_paths_keep_the_contract(spec: dict, root) -> None:
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in spec[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in spec["configs"]:
        assert c["file"].startswith("bench/")
        assert json.loads((root / c["file"]).read_text())["reduced"] == \
            c["reduced"]
    for m in spec["end_to_end"]:
        assert m["bound"] <= 0.25
    for x in spec["configs"] + spec["workloads"]:
        assert 1 <= len(x["why"]) <= 200 and "\n" not in x["why"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    assert {m["name"] for m in spec["end_to_end"]} == {
        "flush_ms.p50", "flush_ms.p95", "req_per_s", "setup_s"}


def cells_report_a_per_layer_metric(spec: dict, root) -> None:
    for cell in spec["workloads"]:
        assert run.metrics_of(spec, cell, True)
        assert len(run.metrics_of(spec, cell, False)) == 4


SPEC_CHECKS = (cells_resolve_their_files, metrics_have_readers,
               names_and_paths_keep_the_contract,
               cells_report_a_per_layer_metric)


# ---- which cells ------------------------------------------------------------
def served_cells(spec: dict, root) -> list:
    """Cells whose configuration serves a model."""
    return [c["name"] for c in spec["workloads"]
            if "model" in run.cell_parts(spec, c["name"], root)[1]]


def old_formula_cells(spec: dict) -> list:
    """Cells of the configurations the old formulas describe."""
    return [c["name"] for c in spec["workloads"]
            if c["config"] in OLD_FORMULA_CONFIGS]


# ---- the old formulas, for the configurations they were written for ----------
def old_dense_prefill(model: dict, seq: int, act_bytes: int):
    """The harness's profile of a dense decoder before counts were per
    layer."""
    d, H, KV = model["d_model"], model["num_heads"], model["num_kv_heads"]
    hd = model["head_dim"]
    qkv = 2.0 * seq * d * (H * hd + 2 * KV * hd)
    out = 2.0 * seq * H * hd * d
    attn = 2.0 * 2.0 * seq * (seq / 2.0) * H * hd
    mlp = 2.0 * seq * d * model["d_ff"] * (3 if model["gated_mlp"] else 2)
    L = model["num_layers"]
    A = [0.0] + [qkv + out + attn + mlp] * L
    O = [float(seq * 4)] + [float(seq * d * act_bytes)] * L
    A[-1] += 2.0 * seq * d * model["vocab_size"]
    O[-1] = float(seq * model["vocab_size"] * act_bytes)
    return np.asarray(A), np.asarray(O)


def profile_and_fleet_are_the_old_ones(spec: dict, cell: str, root) -> None:
    _, config, mix = run.cell_parts(spec, cell, root)
    assert config["name"] in OLD_FORMULA_CONFIGS, cell
    P = deploy.task_profile(config, root)
    if "model" in config:
        task = config["task"]
        A, O = old_dense_prefill(config["model"], task["seq"],
                                 task["act_bytes"])
    else:
        old = deploy.mobilenet_v2_profile(config["task"]["input_res"],
                                          config["task"]["act_bytes"])
        A, O = old.A, old.O
    assert P.A.tobytes() == A.tobytes() and P.O.tobytes() == O.tobytes()
    old_p = deploy.Profile(P.name, A, O, np.ones_like(A), np.ones_like(A))
    beta = traffic.device_betas(mix, 2 ** 34 + 3) if mix["mode"] == "online" \
        else traffic.wave_betas(mix, 2 ** 34 + 3, 0)
    E = deploy.edge_profile(P, config["edge"])
    new_fl = deploy.fleet(P, E, config["fleet"], beta)
    old_fl = deploy.fleet(old_p, deploy.edge_profile(old_p, config["edge"]),
                          config["fleet"], beta)
    for k in new_fl:
        assert new_fl[k].tobytes() == old_fl[k].tobytes(), k


def minitron_cells_run_on_the_transformer(spec: dict, root) -> None:
    """Every served cell of minitron4b-coinf runs that file's model on
    ``bench/reference/transformer.py``, and there are two or more."""
    mini = json.loads((root / MINITRON_FILE).read_text())["model"]
    transformer = str((root / "bench/reference/transformer.py").resolve())
    cells = [c["name"] for c in spec["workloads"]
             if c["config"] == "minitron4b-coinf"]
    assert len(cells) >= 2 and set(cells) <= set(served_cells(spec, root))
    for cell in cells:
        model = run.cell_parts(spec, cell, root)[1]["model"]
        assert model == mini, cell
        assert model_module(model, root).__file__ == transformer, cell


# ---- every served cell, from its reference module -----------------------------
def weight_tree_holds_params(spec: dict, cell: str, root) -> None:
    """The module's weight tree holds the file's ``params`` and the final
    norm's ``d_model`` scales, which ``params`` leaves out."""
    m = run.cell_parts(spec, cell, root)[1]["model"]
    assert "params" in m, f"{cell}: the configuration states no params"
    leaves = jax.tree_util.tree_leaves(
        model_module(m, root).shapes(m),
        is_leaf=lambda x: isinstance(x, tuple))
    assert sum(math.prod(s) for s in leaves) == m["params"] + m["d_model"]


def profile_is_the_modules_block_flops(spec: dict, cell: str, root) -> None:
    """Block i + 1 of the task profile is layer i's ``block_flops``, the
    head's FLOPs added to the last."""
    config = run.cell_parts(spec, cell, root)[1]
    m, seq = config["model"], config["task"]["seq"]
    mod = model_module(m, root)
    A = [mod.block_flops(m, i, seq) for i in range(m["num_layers"])]
    A[-1] += mod.head_flops(m, 1, seq)
    P = deploy.task_profile(config, root)
    assert P.A.tobytes() == np.asarray([0.0] + A).tobytes()


def forward_flops_are_layer_sums(spec: dict, cell: str, root) -> None:
    _, config, mix = run.cell_parts(spec, cell, root)
    m, seq = config["model"], mix["prompt_tokens"]
    for batch in (1, 2, 6, 16):
        layers = [flops.layer_flops(m, i, batch, seq, root)
                  for i in range(m["num_layers"])]
        assert flops.forward_flops(m, batch, seq, root) == \
            math.fsum(layers) + flops.head_flops(m, batch, seq, root), batch


SERVED_CHECKS = (weight_tree_holds_params, profile_is_the_modules_block_flops,
                 forward_flops_are_layer_sums)


def run_all(spec: dict, root) -> None:
    """Every check above on ``spec``: the contract, the old formulas on
    their configurations' cells, and the generic checks on every served
    cell."""
    for check in SPEC_CHECKS:
        check(spec, root)
    for cell in old_formula_cells(spec):
        profile_and_fleet_are_the_old_ones(spec, cell, root)
    minitron_cells_run_on_the_transformer(spec, root)
    for cell in served_cells(spec, root):
        for check in SERVED_CHECKS:
            check(spec, cell, root)
