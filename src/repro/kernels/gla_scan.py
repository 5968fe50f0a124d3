"""Chunked gated-linear-attention scan Pallas TPU kernel.

Serves Mamba2 (SSD) and mLSTM (DESIGN.md §6): one (batch·head) per grid
row, sequential grid over chunks; the (Dk × Dv) recurrent state lives in
VMEM scratch and persists across the chunk dimension.  Within a chunk the
intra-term is a (c × c) masked matmul (MXU) and the inter-term applies the
carried state — the exact blocked algorithm of
:func:`repro.models.ssm.gla_chunked`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(q_ref, k_ref, v_ref, ld_ref, y_ref, s_out_ref, state_ref, *,
            chunk: int, nc: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    q = q_ref[0].astype(jnp.float32)            # (c, Dk)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)            # (c, Dv)
    ld = ld_ref[0].astype(jnp.float32)          # (c, 1)
    cum = jnp.cumsum(ld, axis=0)                # (c, 1)

    # intra-chunk: att[t,s] = exp(cum_t - cum_s) (q_t · k_s),  s <= t
    att = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = cum - cum.reshape(1, chunk)         # (t, s)
    att = jnp.where(t_idx >= s_idx, att * jnp.exp(decay), 0.0)
    y = jax.lax.dot(att, v, preferred_element_type=jnp.float32)

    # inter-chunk: y_t += exp(cum_t) q_t · S
    S = state_ref[...]
    y = y + jax.lax.dot(q * jnp.exp(cum), S,
                        preferred_element_type=jnp.float32)

    # state update: S' = exp(total) S + Σ_s exp(total - cum_s) k_s v_sᵀ
    total = cum[chunk - 1]
    kw = k * jnp.exp(total - cum)
    state_ref[...] = (S * jnp.exp(total)
                      + jax.lax.dot_general(
                          kw, v, (((0,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32))
    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _finish():
        s_out_ref[0] = state_ref[...].astype(s_out_ref.dtype)


def gla_scan(q, k, v, log_decay, *, chunk: int = 256,
             interpret: bool = False):
    """q, k: (BH, L, Dk); v: (BH, L, Dv); log_decay: (BH, L).
    Returns (y (BH, L, Dv), state (BH, Dk, Dv))."""
    bh, L, dk = q.shape
    dv = v.shape[-1]
    chunk = min(chunk, L)
    assert L % chunk == 0
    nc = L // chunk
    kernel = functools.partial(_kernel, chunk=chunk, nc=nc)
    ld = log_decay[..., None]
    return pl.pallas_call(
        kernel,
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, chunk, dk), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, chunk, dv), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, ci: (b, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dv), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, dk, dv), lambda b, ci: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, L, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, ld)
