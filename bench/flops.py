"""Operations and bytes of the served decoder's steps, from shapes.

Counted as the algorithm needs them: a matmul of (m, k)·(k, n) is 2·m·k·n
operations, attention's scores and weighted sum are computed for the causal
half of the (S, S) square, and elementwise work is not counted.  Bytes are
the weights read once in their stored type plus the float32 hidden states
read and written.
"""
from __future__ import annotations

import numpy as np

WEIGHT_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def layer_params(m: dict) -> int:
    d, hd = m["d_model"], m["head_dim"]
    attn = d * (m["num_heads"] + 2 * m["num_kv_heads"]) * hd \
        + m["num_heads"] * hd * d
    mlp = d * m["d_ff"] * (3 if m["gated_mlp"] else 2)
    return attn + mlp + 2 * d


def head_params(m: dict) -> int:
    return m["d_model"] * m["vocab_size"] + m["d_model"]


def layer_flops(m: dict, batch: int, seq: int) -> float:
    """One decoder layer over ``batch`` sequences of ``seq`` tokens."""
    matmul = 2.0 * batch * seq * (layer_params(m) - 2 * m["d_model"])
    attn = 2.0 * 2.0 * batch * m["num_heads"] * m["head_dim"] \
        * seq * (seq + 1) / 2.0
    return matmul + attn


def layer_bytes(m: dict, batch: int, seq: int) -> float:
    w = layer_params(m) * WEIGHT_BYTES[m["weight_dtype"]]
    return w + 2.0 * batch * seq * m["d_model"] * 4


def head_flops(m: dict, batch: int, seq: int) -> float:
    return 2.0 * batch * seq * m["d_model"] * m["vocab_size"]


def head_bytes(m: dict, batch: int, seq: int) -> float:
    return (head_params(m) * WEIGHT_BYTES[m["weight_dtype"]]
            + batch * seq * (m["d_model"] + m["vocab_size"]) * 4)


def forward_flops(m: dict, batch: int, seq: int) -> float:
    """Every layer and the head: the model FLOPs of one forward pass."""
    return m["num_layers"] * layer_flops(m, batch, seq) \
        + head_flops(m, batch, seq)


def roofline_s(flops: float, nbytes: float, peak: dict):
    """Least time on the chip and which bound sets it."""
    t_c, t_m = flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def layer_calls(flush_sizes) -> np.ndarray:
    """Batch size of every layer-step call a list of executed flushes made:
    ``flush_sizes`` holds (local users, offloaded users) per flush, and
    each non-empty part runs every layer once at its own batch size."""
    out = [b for pair in flush_sizes for b in pair if b > 0]
    return np.asarray(out, np.int64)
