"""Reference module of a decoder whose layer plan mixes full-attention and
sliding-window layers, each with a dense MLP.

The tests copy it to ``bench/reference/windowed.py`` under a scratch root
to show that a model of another layer plan joins the benchmark as new
files alone.  It keeps the interface of ``bench/reference/__init__.py``
and reuses ``transformer``'s equations; a ``swa`` layer's query at
position q attends to the keys at q - window + 1 .. q.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.reference import transformer as tf

F32 = tf.F32
CONTROL_BELOW = tf.CONTROL_BELOW


def _layers(model: dict) -> list:
    """(segment, element, repeat, spec) of each layer in execution order."""
    return [(s, e, r, spec)
            for s, (pattern, reps) in enumerate(model["plan"])
            for r in range(reps)
            for e, spec in enumerate(pattern)]


def _window(model: dict, i: int):
    spec = _layers(model)[i][3]
    return spec.get("window") if spec["kind"] == "swa" else None


def check_equations(model: dict) -> None:
    tf.check_equations({k: v for k, v in model.items() if k != "plan"})
    if model["gated_mlp"]:
        raise ValueError("reference: only the two-matrix GELU MLP")
    for pattern, _ in model["plan"]:
        for spec in pattern:
            if spec["kind"] not in ("attn", "swa") or spec["ffn"] != "dense":
                raise ValueError(f"reference: layer {spec!r} is not "
                                 "implemented")


def shapes(model: dict) -> dict:
    tree = tf.shapes(dict(model, num_layers=1))
    layer = tree["segments"][0][0]
    tree["segments"] = [[{k: (reps,) + v[1:] for k, v in layer.items()}
                         for _ in pattern]
                        for pattern, reps in model["plan"]]
    return tree


# ---- counts -----------------------------------------------------------------
def layer_flops(model: dict, i: int, batch: int, seq: int) -> float:
    w = _window(model, i) or seq
    pairs = sum(min(q + 1, w) for q in range(seq))
    matmul = 2.0 * batch * seq * (tf.layer_params(model, i)
                                  - 2 * model["d_model"])
    return matmul + 2.0 * 2.0 * batch * model["num_heads"] \
        * model["head_dim"] * pairs


layer_bytes = tf.layer_bytes
head_flops = tf.head_flops
head_bytes = tf.head_bytes


def block_flops(model: dict, i: int, seq: int) -> float:
    """As ``transformer.block_flops``, with a window shorter than the
    prompt counted as ``window`` keys for every query."""
    w = _window(model, i)
    d, H, KV = model["d_model"], model["num_heads"], model["num_kv_heads"]
    hd = model["head_dim"]
    keys = w if w is not None and w < seq else seq / 2.0
    qkv = 2.0 * seq * d * (H * hd + 2 * KV * hd)
    out = 2.0 * seq * H * hd * d
    attn = 2.0 * 2.0 * seq * keys * H * hd
    mlp = 2.0 * seq * d * model["d_ff"] * (3 if model["gated_mlp"] else 2)
    return qkv + out + attn + mlp


# ---- the forward pass -------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("cfg", "mode", "window"))
def layer(h, w, cfg, mode, window):
    c = dict(cfg)
    B, S, _ = h.shape
    H, KV, hd = c["num_heads"], c["num_kv_heads"], c["head_dim"]
    f = lambda k: w[k].astype(F32)
    x = tf.rms_norm(h, w["norm1"], c["norm_eps"])
    q = tf.mm("bsd,de->bse", x, f("wq"), mode).reshape(B, S, H, hd)
    k = tf.mm("bsd,de->bse", x, f("wk"), mode).reshape(B, S, KV, hd)
    v = tf.mm("bsd,de->bse", x, f("wv"), mode).reshape(B, S, KV, hd)
    q, k = tf.rotary(q, c["rope_theta"]), tf.rotary(k, c["rope_theta"])
    k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
    s = tf.mm("bqhd,bkhd->bhqk", q, k, mode) / math.sqrt(hd)
    back = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
    seen = (back >= 0) & (back < (window or S))
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    o = tf.mm("bhqk,bkhd->bqhd", p, v, mode).reshape(B, S, H * hd)
    h = h + tf.mm("bse,ed->bsd", o, f("wo"), mode)
    x = tf.rms_norm(h, w["norm2"], c["norm_eps"])
    a = tf.gelu_tanh(tf.mm("bsd,df->bsf", x, f("w_up"), mode))
    return h + tf.mm("bsf,fd->bsd", a, f("w_down"), mode)


def hidden(weights, tokens, model: dict, mode: str = "highest"):
    check_equations(model)
    cfg = tuple(sorted((k, v) for k, v in model.items()
                       if not isinstance(v, (list, dict))))
    h = jnp.take(weights["embed"]["w"], jnp.asarray(tokens), axis=0
                 ).astype(F32)
    for i, (s, e, r, _) in enumerate(_layers(model)):
        w = {k: a[r] for k, a in weights["segments"][s][e].items()}
        h = layer(h, w, cfg, mode, _window(model, i))
    return h


logits = tf.logits
