"""Block-partitioned execution engine for co-inference.

The paper's runtime counterpart: a request's DNN pass is split at the J-DOB
partition point ñ — the "device" computes blocks 1..ñ, ships the boundary
activation, and the edge executes blocks ñ+1..N *batched* across users
(greedy batching).  This module runs that split on the real JAX models so
tests can assert the co-inference output equals the monolithic forward.

Weights stay in the model's stacked per-segment form and are held once:
a layer is a reference ``(spec, segment, element, repeat)`` into them, and
the jitted layer step slices its repeat out on device.  Weights may be
stored narrower than the compute dtype (bf16 at published widths); the
cast happens inside the compiled layer and head steps, where XLA fuses it
into the matmuls, so no full-precision copy of a layer, the embedding or
the head is ever held.

The steps run at ``float32`` matmul precision: a float32 compute dtype
then means float32 products on a TPU too, whose default precision would
round each float32 operand to one bf16 pass.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, LayerSpec
from repro.models import RunCtx
from repro.models.model import _apply_elem, rms_norm


def layer_refs(cfg: ArchConfig) -> list[tuple[LayerSpec, int, int, int]]:
    """Per-layer ``(spec, segment, element, repeat)`` references into the
    stacked segment params, in execution order."""
    return [(spec, s, e, r)
            for s, (pattern, reps) in enumerate(cfg.plan)
            for r in range(reps)
            for e, spec in enumerate(pattern)]


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_step(spec: LayerSpec, ctx: RunCtx, stacked, r, h, vision):
    """One decoder layer: repeat ``r`` of the stacked element params."""
    p = jax.tree.map(lambda x: x[r], stacked)
    B, S = h.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    aux = dict(load_balance=jnp.zeros((), jnp.float32),
               router_z=jnp.zeros((), jnp.float32))
    h, _ = _apply_elem(spec, p, h, ctx, positions, vision, aux)
    return h


@functools.partial(jax.jit, static_argnums=(0,))
def _head_step(ctx: RunCtx, norm, w, h):
    """Final norm + LM head; ``w`` is (d, V), or the (V, d) embedding
    table for tied embeddings.  Inside the compiled step XLA fuses the
    transpose and the weight cast into the matmul, so no compute-dtype
    copy of the head is materialized."""
    cd = ctx.compute_dtype
    if ctx.cfg.tie_embeddings:
        w = w.T
    h = rms_norm(h, norm, ctx.cfg.norm_eps)
    return (h.astype(cd) @ w.astype(cd)).astype(jnp.float32)


@dataclasses.dataclass
class BlockwiseExecutor:
    """Runs arbitrary block ranges of a model — the engine the paper's
    offloading needs (device prefix / edge suffix)."""
    cfg: ArchConfig
    params: Any
    ctx: RunCtx = None

    def __post_init__(self):
        self.ctx = self.ctx or RunCtx(self.cfg, compute_dtype=jnp.float32,
                                      ssm_chunk=16, kv_chunk=64)
        self.layers = layer_refs(self.cfg)

    def embed(self, tokens):
        h = jnp.take(self.params["embed"]["w"], tokens, axis=0)
        return h.astype(self.ctx.stream)

    def run_blocks(self, h, lo: int, hi: int, *, vision=None):
        """Apply layers [lo, hi) to hidden states h (B, S, d)."""
        segs = self.params["segments"]
        with jax.default_matmul_precision("float32"):
            for spec, s, e, r in self.layers[lo:hi]:
                h = _layer_step(spec, self.ctx, segs[s][e], r, h, vision)
        return h

    def head(self, h):
        w = self.params["embed" if self.cfg.tie_embeddings
                        else "lm_head"]["w"]
        with jax.default_matmul_precision("float32"):
            return _head_step(self.ctx, self.params["final_norm"], w, h)

    def full_forward(self, tokens, *, vision=None):
        return self.head(self.run_blocks(self.embed(tokens), 0,
                                         len(self.layers), vision=vision))
