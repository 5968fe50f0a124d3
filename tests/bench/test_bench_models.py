"""A served model's weights, task profile, fleet and FLOP counts come from
the reference module its configuration names.  For the configurations
they were written for (minitron4b-coinf and mnv2-paper) they are what the
harness computed before it looked the module up: the old formulas are
written out here and in ``spec_checks``.  Every served cell is also
checked against its own module."""
import json

import benchtest_util
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import spec_checks

from bench import flops, run, traffic, weights
from bench.reference import model_module
from bench.reference import transformer as tf

ROOT = benchtest_util.ROOT
SPEC = run.load_spec()
MINI = json.loads((ROOT / "bench/configs/minitron4b-coinf.json").read_text())
DENSE_PATHS = ["['embed']['w']", "['final_norm']", "['lm_head']['w']"] + [
    f"['segments'][0][0]['{k}']" for k in (
        "norm1", "norm2", "w_down", "w_up", "wk", "wo", "wq", "wv")]


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


SERVED = spec_checks.served_cells(SPEC, ROOT)


def test_a_configuration_without_reference_names_the_transformer():
    assert model_module(MINI["model"]) is tf
    assert model_module(dict(MINI["model"], reference="transformer")) is tf


@pytest.mark.parametrize("name", ["no_such_model", "../transformer",
                                  "bad-name"])
def test_a_reference_that_is_not_a_module_file_is_an_error(name):
    with pytest.raises((ValueError, ModuleNotFoundError)):
        model_module(dict(MINI["model"], reference=name))


@pytest.mark.parametrize("cell", spec_checks.old_formula_cells(SPEC))
def test_each_cells_profile_and_fleet_are_the_old_ones(cell):
    spec_checks.profile_and_fleet_are_the_old_ones(SPEC, cell, ROOT)


def test_served_cells_are_minitron_on_the_transformer():
    spec_checks.minitron_cells_run_on_the_transformer(SPEC, ROOT)


@pytest.mark.parametrize("cell", SERVED)
def test_each_served_weight_tree_holds_the_files_params(cell):
    spec_checks.weight_tree_holds_params(SPEC, cell, ROOT)


@pytest.mark.parametrize("cell", SERVED)
def test_each_served_profile_is_the_modules_block_flops(cell):
    spec_checks.profile_is_the_modules_block_flops(SPEC, cell, ROOT)


@pytest.mark.parametrize("cell", SERVED)
def test_each_served_forward_flops_are_layer_sums(cell):
    spec_checks.forward_flops_are_layer_sums(SPEC, cell, ROOT)


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
