"""Weights of a served model, made on the device from the seed in one
jitted call, in the type they are served in.

The tree's layout is the one the served executor reads, and its leaf
shapes come from the model's reference module (``shapes(model)``, see
``bench/reference``).  Matrices are N(0, ``init_scale``²); leaves whose
path names a norm are 1 + N(0, 0.05²), so that a norm that ignored its
scale would show.  Leaf ``i`` of the flattened tree draws from the seed's
key folded with ``i``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference import model_module
from bench.traffic import rng

_NORM_SD = 0.05


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


@functools.partial(jax.jit, static_argnames=("leaves", "tree", "dtype",
                                             "scale"))
def _init(key, leaves, tree, dtype, scale):
    out = []
    for i, (path, shape) in enumerate(leaves):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if "norm" in path:
            out.append((1.0 + _NORM_SD * z).astype(dtype))
        else:
            out.append((scale * z).astype(dtype))
    return jax.tree.unflatten(tree, out)


def make(model: dict, seed: int, root=None):
    """The weights for ``seed`` (the same seed gives the same weights)."""
    key = jax.random.key(int(rng(seed, 99).integers(0, 2 ** 31)))
    shapes = model_module(model, root).shapes(model)
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes,
                                                      is_leaf=_is_shape)
    leaves = tuple((jax.tree_util.keystr(p), tuple(s)) for p, s in flat)
    return _init(key, leaves, tree, jnp.dtype(model["weight_dtype"]),
                 model["init_scale"])
