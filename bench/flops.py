"""Operations and bytes of the served decoder's steps, from shapes.

Each layer's and the head's counts come from the model's reference module
(``bench/reference/<model.reference>.py``, see ``bench/reference``), which
counts them as the algorithm needs them: a matmul of (m, k)·(k, n) is
2·m·k·n operations and elementwise work is not counted.  This module sums
them over a forward pass and sets them against the chip's peaks.  Sums
over the layers are ``math.fsum``'s correctly rounded ones, so L equal
layers sum to the float product L·count.
"""
from __future__ import annotations

import math

import numpy as np

from bench.reference import model_module


def layer_flops(m: dict, i: int, batch: int, seq: int, root=None) -> float:
    """Layer ``i`` over ``batch`` sequences of ``seq`` tokens."""
    return model_module(m, root).layer_flops(m, i, batch, seq)


def layer_bytes(m: dict, i: int, batch: int, seq: int, root=None) -> float:
    return model_module(m, root).layer_bytes(m, i, batch, seq)


def head_flops(m: dict, batch: int, seq: int, root=None) -> float:
    return model_module(m, root).head_flops(m, batch, seq)


def forward_flops(m: dict, batch: int, seq: int, root=None) -> float:
    """Every layer and the head: the model FLOPs of one forward pass."""
    mod = model_module(m, root)
    return math.fsum(mod.layer_flops(m, i, batch, seq)
                     for i in range(m["num_layers"])) \
        + mod.head_flops(m, batch, seq)


def roofline_s(flops: float, nbytes: float, peak: dict):
    """Least time on the chip and which bound sets it."""
    t_c, t_m = flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def layers_roofline_s(m: dict, batch: int, seq: int, peak: dict,
                      root=None) -> dict:
    """Least time of every layer step of one forward pass at ``batch``,
    split by the bound that sets each: {"compute": s, "memory": s}."""
    mod = model_module(m, root)
    by = {"compute": [], "memory": []}
    for i in range(m["num_layers"]):
        t, bound = roofline_s(mod.layer_flops(m, i, batch, seq),
                              mod.layer_bytes(m, i, batch, seq), peak)
        by[bound].append(t)
    return {bound: math.fsum(ts) for bound, ts in by.items()}


def layer_calls(flush_sizes) -> np.ndarray:
    """Batch size of every layer-step call a list of executed flushes made:
    ``flush_sizes`` holds (local users, offloaded users) per flush, and
    each non-empty part runs every layer once at its own batch size."""
    out = [b for pair in flush_sizes for b in pair if b > 0]
    return np.asarray(out, np.int64)
