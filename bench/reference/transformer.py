"""Plain forward pass of the served dense decoder, layer by layer.

Follows the equations the configuration states, and raises on any it
does not implement (``check_equations``): RMSNorm (ε from the
file) with a scale; grouped-query causal attention with rotary embeddings
(the two halves of each head rotated, base ``rope_theta``); a two-matrix
MLP with tanh-approximated GELU (or SiLU-gated with three matrices);
residual adds; final RMSNorm and an untied LM head.  Weights are read as
stored and cast to float32; activations stay float32.

The model is one segment of ``num_layers`` full-attention layers with a
dense MLP each: a configuration that states another ``plan`` is refused.
Beside the forward pass, this module gives the weight tree's shapes and the
FLOP and byte counts of each layer and the head (the interface
``bench/reference/__init__.py`` names).

``mode`` picks the matmul arithmetic: ``"highest"`` is float32 (the
reference), ``"bf16x3"`` the three-pass bfloat16 product that TPUs call
``high`` (the control: hi·hi + hi·lo + lo·hi, spelled out so that it means
the same on every backend).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
BF16 = jnp.bfloat16
MODES = ("highest", "bf16x3")
#: the matmul arithmetic one step below each stated ``matmul_precision``
CONTROL_BELOW = {"float32": "bf16x3"}
#: the equations this reference implements, by configuration key
EQUATIONS = {"norm": ("rms",), "rotary_fraction": (1.0,)}
ACTIVATION = {False: "gelu_tanh", True: "silu"}


def check_equations(model: dict) -> None:
    """Raise unless the configuration states equations this reference
    implements (a configuration that asks for others must not be checked
    against these)."""
    plan = model.get("plan")
    if plan is not None and (len(plan) != 1 or any(
            e.get("kind", "attn") != "attn" or e.get("ffn", "dense") != "dense"
            or e.get("window") is not None for e in plan[0][0])):
        raise ValueError(f"reference: plan={plan!r} is not implemented "
                         "(only full-attention layers with a dense MLP)")
    for key, ok in EQUATIONS.items():
        if model[key] not in ok:
            raise ValueError(f"reference: {key}={model[key]!r} is not "
                             f"implemented (only {ok})")
    want = ACTIVATION[bool(model["gated_mlp"])]
    if model["mlp_activation"] != want:
        raise ValueError(f"reference: mlp_activation="
                         f"{model['mlp_activation']!r} with gated_mlp="
                         f"{model['gated_mlp']} is not implemented "
                         f"(only {want!r})")
    if model["matmul_precision"] not in CONTROL_BELOW:
        raise ValueError(f"reference: no control below matmul_precision="
                         f"{model['matmul_precision']!r}")


def shapes(model: dict) -> dict:
    """Leaf shapes of the weight tree the executor reads: ``embed.w`` (V, d),
    ``segments[0][0]`` holding every layer's arrays stacked on a leading
    layer axis, ``final_norm`` (d,) and ``lm_head.w`` (d, V)."""
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


# ---- counts -----------------------------------------------------------------
# A matmul of (m, k)·(k, n) is 2·m·k·n operations; attention's scores and
# weighted sum are computed for the causal half of the (S, S) square
# (diagonal included); elementwise work is not counted.  Bytes are the
# weights read once in their stored type plus the float32 hidden states
# read and written.  Every layer is alike, so ``i`` only names it.
def layer_params(model: dict, i: int) -> int:
    d, hd = model["d_model"], model["head_dim"]
    attn = d * (model["num_heads"] + 2 * model["num_kv_heads"]) * hd \
        + model["num_heads"] * hd * d
    mlp = d * model["d_ff"] * (3 if model["gated_mlp"] else 2)
    return attn + mlp + 2 * d


def head_params(model: dict) -> int:
    return model["d_model"] * model["vocab_size"] + model["d_model"]


def _weight_bytes(model: dict) -> int:
    return jnp.dtype(model["weight_dtype"]).itemsize


def layer_flops(model: dict, i: int, batch: int, seq: int) -> float:
    """Layer ``i`` over ``batch`` sequences of ``seq`` tokens."""
    matmul = 2.0 * batch * seq * (layer_params(model, i)
                                  - 2 * model["d_model"])
    attn = 2.0 * 2.0 * batch * model["num_heads"] * model["head_dim"] \
        * seq * (seq + 1) / 2.0
    return matmul + attn


def layer_bytes(model: dict, i: int, batch: int, seq: int) -> float:
    w = layer_params(model, i) * _weight_bytes(model)
    return w + 2.0 * batch * seq * model["d_model"] * 4


def head_flops(model: dict, batch: int, seq: int) -> float:
    return 2.0 * batch * seq * model["d_model"] * model["vocab_size"]


def head_bytes(model: dict, batch: int, seq: int) -> float:
    return (head_params(model) * _weight_bytes(model)
            + batch * seq * (model["d_model"] + model["vocab_size"]) * 4)


def block_flops(model: dict, i: int, seq: int) -> float:
    """Per-sample FLOPs of layer ``i`` at prefill of ``seq`` tokens as the
    task profile states them: projections, attention over half of the
    (S, S) square, and the MLP (the program's ``profile_from_arch``)."""
    d, H, KV = model["d_model"], model["num_heads"], model["num_kv_heads"]
    hd = model["head_dim"]
    qkv = 2.0 * seq * d * (H * hd + 2 * KV * hd)
    out = 2.0 * seq * H * hd * d
    attn = 2.0 * 2.0 * seq * (seq / 2.0) * H * hd
    mlp = 2.0 * seq * d * model["d_ff"] * (3 if model["gated_mlp"] else 2)
    return qkv + out + attn + mlp


# ---- the forward pass -------------------------------------------------------
def mm(spec: str, a, b, mode: str):
    if mode == "highest":
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=F32)
    if mode != "bf16x3":
        raise ValueError(f"unknown matmul mode {mode!r}")

    def split(x):
        # rounded by reduce_precision, which compilers keep: a round trip
        # through bfloat16 may be dropped as excess precision
        hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        return hi.astype(BF16), (x - hi).astype(BF16)

    (ah, al), (bh, bl) = split(a), split(b)
    dot = functools.partial(jnp.einsum, spec, preferred_element_type=F32)
    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def rotary(x, theta: float):
    """x: (B, S, heads, hd); rotates (x1, x2) halves by position·freq."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freq = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("cfg", "mode"))
def layer(h, w, cfg, mode):
    """One decoder layer on float32 hidden states h (B, S, d)."""
    c = dict(cfg)
    B, S, _ = h.shape
    H, KV, hd = c["num_heads"], c["num_kv_heads"], c["head_dim"]
    f = lambda k: w[k].astype(F32)
    x = rms_norm(h, w["norm1"], c["norm_eps"])
    q = mm("bsd,de->bse", x, f("wq"), mode).reshape(B, S, H, hd)
    k = mm("bsd,de->bse", x, f("wk"), mode).reshape(B, S, KV, hd)
    v = mm("bsd,de->bse", x, f("wv"), mode).reshape(B, S, KV, hd)
    q, k = rotary(q, c["rope_theta"]), rotary(k, c["rope_theta"])
    rep = H // KV                      # query head i reads kv head i // rep
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = mm("bqhd,bkhd->bhqk", q, k, mode) / math.sqrt(hd)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    o = mm("bhqk,bkhd->bqhd", p, v, mode).reshape(B, S, H * hd)
    h = h + mm("bse,ed->bsd", o, f("wo"), mode)
    x = rms_norm(h, w["norm2"], c["norm_eps"])
    if c["gated_mlp"]:
        a = jax.nn.silu(mm("bsd,df->bsf", x, f("w_gate"), mode)) \
            * mm("bsd,df->bsf", x, f("w_up"), mode)
    else:
        a = gelu_tanh(mm("bsd,df->bsf", x, f("w_up"), mode))
    return h + mm("bsf,fd->bsd", a, f("w_down"), mode)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def head(h, norm, w, eps, mode):
    return mm("bsd,dv->bsv", rms_norm(h, norm, eps), w.astype(F32), mode)


def hidden(weights, tokens, model: dict, mode: str = "highest"):
    """Final hidden states (before the final norm) for tokens (B, S)."""
    check_equations(model)
    cfg = tuple(sorted((k, v) for k, v in model.items()
                       if not isinstance(v, (list, dict))))
    h = jnp.take(weights["embed"]["w"], jnp.asarray(tokens), axis=0
                 ).astype(F32)
    stacked = weights["segments"][0][0]
    for i in range(model["num_layers"]):
        h = layer(h, {k: a[i] for k, a in stacked.items()}, cfg, mode)
    return h


def logits(weights, h, model: dict, mode: str = "highest", cols=None):
    """LM-head logits (B, S, V) of hidden states h, or only the vocabulary
    columns ``cols`` = (start, stop)."""
    w = weights["lm_head"]["w"]
    if cols is not None:
        w = w[:, cols[0]:cols[1]]
    return head(h, weights["final_norm"], w, model["norm_eps"], mode)
