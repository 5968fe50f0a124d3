"""Flash attention (prefill) Pallas TPU kernel.

Tiling: q blocks of ``block_q`` × kv blocks of ``block_k``; the online-
softmax accumulators (acc, m, l) live in VMEM scratch and persist across the
innermost (kv) grid dimension, which TPU executes sequentially.  MXU-aligned
defaults (block 128/256, head_dim padded to 128 by the ops wrapper when
needed).  Causal + optional sliding-window masking; fully-masked kv blocks
are skipped with ``pl.when`` (no MXU work issued).

Validated on CPU with interpret=True against :mod:`repro.kernels.ref`.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            sm_scale: float, causal: bool, window: int | None,
            block_q: int, block_k: int, nk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = ki * block_k
    # block-level skip: kv block entirely in the future (causal) or
    # entirely outside the window
    live = True
    if causal:
        live = k_start <= q_start + block_q - 1
    if window is not None:
        live = jnp.logical_and(live,
                               q_start - (k_start + block_k - 1) < window)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * sm_scale        # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                   # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= q_pos - k_pos < window
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        acc_ref[...] = (acc_ref[...] * alpha
                        + jax.lax.dot(p, v,
                                      preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: int | None = None, block_q: int = 256,
                    block_k: int = 512, n_rep: int = 1,
                    interpret: bool = False):
    """q: (B·H, Sq, hd); k, v: (B·KV, Sk, hd) with H = KV·n_rep.

    GQA-native: the kv BlockSpec index map folds the query head onto its
    kv group (``b // n_rep``) — K/V tiles stream HBM→VMEM once per kv
    head, not once per query head (§Perf A1 at the kernel level).
    Returns (B·H, Sq, hd)."""
    bh, sq, hd = q.shape
    sk = k.shape[1]
    assert k.shape[0] * n_rep == bh
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0
    nq, nk = sq // block_q, sk // block_k
    sm_scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(
        _kernel, sm_scale=sm_scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, nk=nk)

    kv_map = lambda b, qi, ki: (b // n_rep, ki, 0)
    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
