"""The control (the reference in the nearest precision below the
configuration's, put in the program's place) comes out not correct."""
import json

import benchtest_util
import ml_dtypes
import numpy as np
import pytest

from bench import check, deploy, traffic, weights
from bench.reference import transformer as tf

ROOT = benchtest_util.ROOT
MNV2 = json.loads((ROOT / "bench/configs/mnv2-paper.json").read_text())
MINI = json.loads((ROOT / "bench/configs/minitron4b-coinf.json").read_text())


@pytest.mark.parametrize("seed", [3, 2 ** 40 + 9, 77])
def test_planner_control_fails_at_a_flush_of_the_cells_size(seed):
    """A flush of the 10k cell's size (~300 users), planned in bfloat16."""
    P = deploy.task_profile(MNV2)
    E = deploy.edge_profile(P, MNV2["edge"])
    mix = json.loads((ROOT / "bench/mixes/online-10k.json").read_text())
    fl = deploy.fleet(P, E, MNV2["fleet"], traffic.device_betas(mix, seed))
    sub = deploy.subset(fl, np.arange(300))
    sweep = deploy.f_sweep(E, MNV2["planner"]["rho"])
    keys = tuple(MNV2["planner"]["online"])
    plan = check.ref.jdob(P, E, sub, 2e-3, sweep, keys, ml_dtypes.bfloat16)
    gap, excess = check._score(P, E, sub, 2e-3, sweep, keys,
                               check._as_answer(plan, 0.0))
    lim = MNV2["limits"]
    assert gap > lim["plan_energy_gap"] or excess > lim["deadline_excess"]


@pytest.mark.parametrize("seed", [5, 2 ** 36 + 1])
def test_grouping_control_fails_on_a_wave(seed):
    mix = json.loads((ROOT / "bench/mixes/waves-40.json").read_text())
    mix["wave_users"] = 24                  # a test-sized wave
    one_wave = {"waves": [{"energy": 0.0, "groups": [list(range(24))],
                           "plans": []}]}
    nums = check.waves(MNV2, mix, seed, one_wave, control=ml_dtypes.bfloat16)
    lim = MNV2["limits"]
    assert nums["plan_energy_gap"] > lim["plan_energy_gap"] or \
        nums["deadline_excess"] > lim["deadline_excess"]


@pytest.mark.parametrize("seed", [4, 2 ** 37 + 3, 91])
def test_forward_control_fails(seed):
    """Three-pass bfloat16 in place of float32 at ``highest``: the served
    cell's logit comparison fails it (a small model on the CPU; the chip
    readings at published widths are in PERF.md)."""
    model = dict(MINI["model"], **benchtest_util.TINY_MODEL)
    w = weights.make(model, seed)
    tok = traffic.rng(seed, 1).integers(0, model["vocab_size"], (8, 32)
                                        ).astype(np.int32)
    want = np.asarray(tf.logits(w, tf.hidden(w, tok, model), model))
    kept = {i: want[i] for i in range(8)}
    toks = {i: tok[i] for i in range(8)}
    assert check.logits(model, w, kept, toks)["logit_err"] == 0.0
    err = check.logits(model, w, kept, toks, "bf16x3")["logit_err"]
    assert err > MINI["limits"]["logit_err"]
