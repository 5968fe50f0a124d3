"""Weights of a dense decoder, made on the device from the seed in one
jitted call, in the type they are served in.

The tree has the layout the served executor reads: ``embed.w`` (V, d),
``segments[0][0]`` holding every layer's arrays stacked on a leading
layer axis, ``final_norm`` (d,) and ``lm_head.w`` (d, V).  Matrices are
N(0, ``init_scale``²); norm scales are 1 + N(0, 0.05²), so that a norm
that ignored its scale would show.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.traffic import rng

_NORM_SD = 0.05


def shapes(model: dict) -> dict:
    """Leaf shapes, stacked layer arrays first dimension L."""
    L, d, V = model["num_layers"], model["d_model"], model["vocab_size"]
    H, KV, hd, ff = (model["num_heads"], model["num_kv_heads"],
                     model["head_dim"], model["d_ff"])
    layer = {"norm1": (L, d), "wq": (L, d, H * hd), "wk": (L, d, KV * hd),
             "wv": (L, d, KV * hd), "wo": (L, H * hd, d),
             "w_up": (L, d, ff), "w_down": (L, ff, d), "norm2": (L, d)}
    if model["gated_mlp"]:
        layer["w_gate"] = (L, d, ff)
    return {"embed": {"w": (V, d)}, "segments": [[layer]],
            "final_norm": (d,), "lm_head": {"w": (d, V)}}


@functools.partial(jax.jit, static_argnames=("spec",))
def _init(key, spec):
    model = dict(spec)
    dtype = jnp.dtype(model["weight_dtype"])
    flat, tree = jax.tree.flatten(shapes(model),
                                  is_leaf=lambda x: isinstance(x, tuple))
    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(
        shapes(model), is_leaf=lambda x: isinstance(x, tuple))[0]]
    leaves = []
    for i, (path, shape) in enumerate(zip(paths, flat)):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if "norm" in path:
            leaves.append((1.0 + _NORM_SD * z).astype(dtype))
        else:
            leaves.append((model["init_scale"] * z).astype(dtype))
    return jax.tree.unflatten(tree, leaves)


def make(model: dict, seed: int):
    """The weights for ``seed`` (the same seed gives the same weights)."""
    key = jax.random.key(int(rng(seed, 99).integers(0, 2 ** 31)))
    spec = tuple(sorted((k, v) for k, v in model.items()
                        if not isinstance(v, (list, dict))))
    return _init(key, spec)
