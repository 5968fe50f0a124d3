"""A served model's weights, task profile, fleet and FLOP counts come from
the reference module its configuration names, and for the cells already
in BENCHMARK.json they are what the harness computed before it looked the
module up: the old formulas are written out here."""
import json

import benchtest_util
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import deploy, flops, run, traffic, weights
from bench.reference import model_module
from bench.reference import transformer as tf

ROOT = benchtest_util.ROOT
SPEC = run.load_spec()
MINI = json.loads((ROOT / "bench/configs/minitron4b-coinf.json").read_text())
DENSE_PATHS = ["['embed']['w']", "['final_norm']", "['lm_head']['w']"] + [
    f"['segments'][0][0]['{k}']" for k in (
        "norm1", "norm2", "w_down", "w_up", "wk", "wo", "wq", "wv")]


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


def old_dense_weights(model: dict, seed: int):
    """The harness's weights of a dense decoder before their shapes came
    from the reference module: leaf i of the sorted tree from the key
    folded with i."""
    key = jax.random.key(int(traffic.rng(seed, 99).integers(0, 2 ** 31)))
    dtype = jnp.dtype(model["weight_dtype"])
    L, d, V = model["num_layers"], model["d_model"], model["vocab_size"]
    H, KV, hd, ff = (model["num_heads"], model["num_kv_heads"],
                     model["head_dim"], model["d_ff"])
    shapes = {"['embed']['w']": (V, d), "['final_norm']": (d,),
              "['lm_head']['w']": (d, V),
              "['segments'][0][0]['norm1']": (L, d),
              "['segments'][0][0]['norm2']": (L, d),
              "['segments'][0][0]['w_down']": (L, ff, d),
              "['segments'][0][0]['w_up']": (L, d, ff),
              "['segments'][0][0]['wk']": (L, d, KV * hd),
              "['segments'][0][0]['wo']": (L, H * hd, d),
              "['segments'][0][0]['wq']": (L, d, H * hd),
              "['segments'][0][0]['wv']": (L, d, KV * hd)}

    @jax.jit
    def draw(key):
        out = {}
        for i, path in enumerate(DENSE_PATHS):
            z = jax.random.normal(jax.random.fold_in(key, i), shapes[path],
                                  jnp.float32)
            out[path] = ((1.0 + 0.05 * z) if "norm" in path
                         else model["init_scale"] * z).astype(dtype)
        return out
    return draw(key)


def _served_cells():
    return [c["name"] for c in SPEC["workloads"]
            if "model" in run.cell_parts(SPEC, c["name"])[1]]


def test_a_configuration_without_reference_names_the_transformer():
    assert model_module(MINI["model"]) is tf
    assert model_module(dict(MINI["model"], reference="transformer")) is tf


@pytest.mark.parametrize("name", ["no_such_model", "../transformer",
                                  "bad-name"])
def test_a_reference_that_is_not_a_module_file_is_an_error(name):
    with pytest.raises((ValueError, ModuleNotFoundError)):
        model_module(dict(MINI["model"], reference=name))


@pytest.mark.parametrize("cell", [c["name"] for c in SPEC["workloads"]])
def test_each_cells_profile_and_fleet_are_the_old_ones(cell):
    _, config, mix = run.cell_parts(SPEC, cell)
    P = deploy.task_profile(config)
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


def test_served_cells_are_minitron_on_the_transformer():
    assert _served_cells() == ["minitron4b.busy", "minitron4b.sparse"]
    for cell in _served_cells():
        assert run.cell_parts(SPEC, cell)[1]["model"] == MINI["model"]


def test_dense_weight_tree_keeps_its_leaf_paths_and_order():
    flat, _ = jax.tree_util.tree_flatten_with_path(
        tf.shapes(MINI["model"]), is_leaf=lambda x: isinstance(x, tuple))
    assert [jax.tree_util.keystr(p) for p, _ in flat] == DENSE_PATHS


@pytest.mark.parametrize("seed", [3, 2 ** 33 + 5])
def test_dense_weights_are_the_old_draws_bit_for_bit(seed):
    model = dict(MINI["model"], **benchtest_util.TINY_MODEL)
    got = weights.make(model, seed)
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    want = old_dense_weights(model, seed)
    assert [jax.tree_util.keystr(p) for p, _ in flat] == DENSE_PATHS
    for p, leaf in flat:
        w = want[jax.tree_util.keystr(p)]
        assert leaf.dtype == w.dtype and leaf.shape == w.shape
        assert np.asarray(leaf).tobytes() == np.asarray(w).tobytes()


@pytest.mark.parametrize("batch", [1, 2, 6, 16])
def test_minitron_counts_are_per_layer_sums_of_one_layer(batch):
    m = MINI["model"]
    one = flops.layer_flops(m, 0, batch, 32)
    assert {flops.layer_flops(m, i, batch, 32) for i in range(32)} == {one}
    assert flops.forward_flops(m, batch, 32) == \
        m["num_layers"] * one + flops.head_flops(m, batch, 32)
