"""Ahead-of-time compiles of the main path's device programs for a TPU
v5e that is described, not attached: the Pallas sweep kernel, the J-DOB
solve grid, the fused grouping-DP scan and one minitron-4b decoder layer
at published widths.  What Mosaic or XLA would refuse on the chip fails
here; nothing runs, so no result or time is checked."""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ARCHS
from repro.core import (PlannerService, make_edge_profile, make_fleet,
                        mobilenet_v2_profile)
from repro.core.jdob import (BatchedPlanner, _og_scan, jdob_plan_batched,
                             og_plan_fused)
from repro.kernels.jdob_sweep import jdob_sweep_kernel
from repro.kernels.ops import jdob_sweep_inputs
from repro.models import RunCtx, init_params
from repro.serving.engine import _layer_step, layer_refs


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def paper_setup():
    profile = mobilenet_v2_profile()
    return profile, make_edge_profile(profile)


def _on(sharding, tree):
    """Shapes and dtypes of ``tree``, placed on the described chip."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=sharding), tree)


@pytest.mark.parametrize("M", [96, 1000])
def test_sweep_kernel_compiles(one_chip, paper_setup, M):
    profile, edge = paper_setup
    fleet = make_fleet(M, profile, edge, beta=(0.0, 10.0), seed=0)
    args = _on(one_chip, jdob_sweep_inputs(profile, fleet, edge))
    compiled = jax.jit(functools.partial(jdob_sweep_kernel, interpret=False)
                       ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_jdob_plan_batched_compiles_at_m96_bucket(one_chip, paper_setup):
    profile, edge = paper_setup
    m_pad, g_pad = max(PlannerService(profile, edge).level_shapes(96))
    planner = BatchedPlanner(profile, edge)
    f32 = np.dtype(np.float32)
    users = {k: jax.ShapeDtypeStruct((g_pad, m_pad), f32)
             for k in ("zeta", "ku", "fm_min", "fm_max", "rate", "p_up",
                       "T")}
    args = ({**planner.blocks, **users}, planner.f_sweep,
            jax.ShapeDtypeStruct((g_pad,), f32),
            jax.ShapeDtypeStruct((g_pad, m_pad), np.dtype(bool)))
    compiled = jdob_plan_batched.lower(
        *_on(one_chip, args), n_partitions=profile.N + 1,
        sort_key="gamma").compile()
    assert compiled.as_text()


class _Captured(Exception):
    pass


class _CaptureScanArgs:
    """Stands in for the planner's executable cache: records the fused
    scan's arguments and statics, then stops before compiling."""

    def lookup_general(self, args, key, compile_fn, stats=None):
        self.args, self.statics = args, dict(key[1:])
        raise _Captured


def test_og_scan_compiles_at_32_levels(one_chip, paper_setup):
    profile, edge = paper_setup
    fleet = make_fleet(32, profile, edge, beta=(0.0, 10.0), seed=0)
    fleet = fleet.subset(np.argsort(fleet.deadline, kind="stable"))
    capture = _CaptureScanArgs()
    with pytest.raises(_Captured):
        og_plan_fused(BatchedPlanner(profile, edge, cache=capture), fleet)
    with jax.enable_x64(True):
        args = _on(one_chip, capture.args)
        assert args[5].dtype == jnp.int64    # binary64 bits of e_all
        compiled = _og_scan.lower(*args, **capture.statics).compile()
    assert compiled.as_text()


def test_minitron_decoder_layer_compiles_bf16(one_chip):
    cfg = ARCHS["minitron-4b"]
    params = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    spec, s, e, _ = layer_refs(cfg)[0]
    stacked = _on(one_chip, params["segments"][s][e])
    assert stacked["wq"].shape == (32, cfg.d_model, cfg.d_model)
    assert stacked["wq"].dtype == jnp.bfloat16
    ctx = RunCtx(cfg, compute_dtype=jnp.float32, ssm_chunk=16, kv_chunk=64)
    h = jax.ShapeDtypeStruct((6, 32, cfg.d_model), jnp.float32,
                             sharding=one_chip)
    r = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = _layer_step.lower(spec, ctx, stacked, r, h, None).compile()
    assert compiled.memory_analysis().output_size_in_bytes == \
        6 * 32 * cfg.d_model * 4
