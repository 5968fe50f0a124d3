"""Decode (single-token) attention Pallas TPU kernel.

One query row per (batch·head) attends over the KV cache in ``block_k``
tiles; partial-softmax accumulators persist in VMEM scratch across the
sequential kv grid dimension.  Handles cache-validity masking (``pos``)
for both full and ring caches.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            sm_scale: float, block_k: int, nk: int, ring: bool):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    pos = pos_ref[0]
    q = q_ref[0].astype(jnp.float32) * sm_scale            # (1, hd)
    k = k_ref[0].astype(jnp.float32)                       # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (1, bk)
    slot = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    L = nk * block_k
    if ring:
        valid = slot < jnp.minimum(pos + 1, L)
    else:
        valid = slot <= pos
    s = jnp.where(valid, s, _NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    v = v_ref[0].astype(jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def decode_attention_kernel(q, k, v, pos, *, ring: bool = False,
                            block_k: int = 512, n_rep: int = 1,
                            interpret: bool = False):
    """q: (B·H, 1, hd); k, v: (B·KV, L, hd), H = KV·n_rep; pos: () int32.
    GQA-native kv index map — the cache streams once per kv head.
    ``ring=True``: every slot < min(pos+1, L) is valid (ring cache).
    Returns (B·H, 1, hd)."""
    bh, _, hd = q.shape
    L = k.shape[1]
    assert k.shape[0] * n_rep == bh
    block_k = min(block_k, L)
    assert L % block_k == 0
    nk = L // block_k
    kernel = functools.partial(_kernel, sm_scale=1.0 / math.sqrt(hd),
                               block_k=block_k, nk=nk, ring=ring)
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (1,))
    kv_map = lambda b, ki, pos: (b // n_rep, ki, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, nk),
        in_specs=[
            pl.BlockSpec((1, 1, hd), lambda b, ki, pos: (b, 0, 0)),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, hd), lambda b, ki, pos: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, hd), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, 1, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(pos_arr, q, k, v)
