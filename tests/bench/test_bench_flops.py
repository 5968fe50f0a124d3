"""FLOP and byte counts of one minitron-4b layer and its head against
hand counts, and the roofline bound."""
import json

import benchtest_util
import pytest

from bench import flops
from bench.peaks import peaks
from bench.reference import transformer as tf

MODEL = json.loads((benchtest_util.ROOT / "bench" / "configs" /
                    "minitron4b-coinf.json").read_text())["model"]
LAYERS = range(MODEL["num_layers"])


def test_layer_params_match_the_published_count():
    # q 3072x3072, k and v 3072x1024 each, o 3072x3072, up and down
    # 3072x9216 each, two norm scales
    hand = (3072 * 3072 + 2 * 3072 * 1024 + 3072 * 3072
            + 2 * 3072 * 9216 + 2 * 3072)
    assert {tf.layer_params(MODEL, i) for i in LAYERS} == {hand}
    assert hand == 81_795_072
    total = 32 * hand + 2 * 3072 * 256000
    assert total == MODEL["params"] == 4_190_306_304


def test_layer_flops_and_bytes_by_hand():
    B, S = 8, 32
    matmul = 2 * B * S * (3072 * 3072 * 2 + 2 * 3072 * 1024 + 2 * 3072 * 9216)
    attn = 2 * 2 * B * 24 * 128 * (S * (S + 1) // 2)
    weights = 81_795_072 * 2                            # bf16
    acts = 2 * B * S * 3072 * 4                         # float32 in and out
    for i in LAYERS:
        assert flops.layer_flops(MODEL, i, B, S) == matmul + attn
        assert flops.layer_bytes(MODEL, i, B, S) == weights + acts


def test_head_and_forward():
    B, S = 3, 32
    assert flops.head_flops(MODEL, B, S) == 2 * B * S * 3072 * 256000
    assert flops.forward_flops(MODEL, B, S) == \
        32 * flops.layer_flops(MODEL, 0, B, S) + flops.head_flops(MODEL, B, S)


def test_roofline_bound_switches_with_batch():
    v5e = peaks("TPU v5 lite")
    t1, bound1 = flops.roofline_s(flops.layer_flops(MODEL, 0, 1, 32),
                                  flops.layer_bytes(MODEL, 0, 1, 32), v5e)
    t16, bound16 = flops.roofline_s(flops.layer_flops(MODEL, 0, 16, 32),
                                    flops.layer_bytes(MODEL, 0, 16, 32), v5e)
    assert bound1 == "memory" and bound16 == "compute"
    assert t1 == pytest.approx(flops.layer_bytes(MODEL, 0, 1, 32) / 819e9)
    assert t16 == pytest.approx(flops.layer_flops(MODEL, 0, 16, 32) / 197e12)


@pytest.mark.parametrize("batch", [1, 3, 8, 16])
def test_a_forward_pass_of_layer_steps_is_one_layer_times_the_depth(batch):
    """The per-layer sum is what one layer's count times 32 gave, to the
    bit: the readers read what they read before counts were per layer."""
    v5e = peaks("TPU v5 lite")
    t, bound = flops.roofline_s(flops.layer_flops(MODEL, 0, batch, 32),
                                flops.layer_bytes(MODEL, 0, batch, 32), v5e)
    other = "memory" if bound == "compute" else "compute"
    by = flops.layers_roofline_s(MODEL, batch, 32, v5e)
    assert by[bound] == t * 32 and by[other] == 0.0


def test_layer_calls_skip_empty_parts():
    assert flops.layer_calls([(0, 5), (2, 3), (1, 0)]).tolist() == [5, 2, 3, 1]


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks("TPU v99")
