"""jit'd public wrappers around the Pallas kernels.

Shape plumbing: the model layers use (B, S, H, hd) GQA tensors; the kernels
take head-folded (B·H, S, hd).  The kernels compile for Mosaic on a TPU
and run with ``interpret=True`` everywhere else (the default resolves via
:func:`default_interpret`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .decode_attention import decode_attention_kernel
from .flash_attention import flash_attention
from .gla_scan import gla_scan
from .jdob_sweep import LANES, jdob_sweep_kernel


def default_interpret() -> bool:
    """Kernels compile for Mosaic on a TPU and interpret everywhere else."""
    return jax.default_backend() != "tpu"


def _fold_heads(q, k, v):
    """(B,S,H,hd)/(B,S,KV,hd) -> head-folded (B·H,...)/(B·KV,...).  K/V are
    NOT broadcast — the kernels' GQA index maps stream each kv head once
    (§Perf A1 at the kernel level)."""
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(
        b * x.shape[2], x.shape[1], hd)
    return fold(q), fold(k), fold(v), (b, h, h // kv)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention_op(q, k, v, *, causal=True, window=None, block_q=256,
                       block_k=512, interpret=None):
    """Drop-in for :func:`repro.models.layers.blockwise_attention`."""
    interpret = default_interpret() if interpret is None else interpret
    qf, kf, vf, (b, h, rep) = _fold_heads(q, k, v)
    o = flash_attention(qf, kf, vf, causal=causal, window=window,
                        block_q=block_q, block_k=block_k, n_rep=rep,
                        interpret=interpret)
    sq, hd = q.shape[1], q.shape[3]
    return o.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("ring", "block_k", "interpret"))
def decode_attention_op(q, k_cache, v_cache, pos, *, ring=False,
                        block_k=512, interpret=None):
    """Drop-in for :func:`repro.models.layers.decode_attention`."""
    interpret = default_interpret() if interpret is None else interpret
    qf, kf, vf, (b, h, rep) = _fold_heads(q, k_cache, v_cache)
    o = decode_attention_kernel(qf, kf, vf, pos, ring=ring, block_k=block_k,
                                n_rep=rep, interpret=interpret)
    return o.reshape(b, h, 1, q.shape[3]).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def gla_scan_op(q, k, v, log_decay, *, chunk=256, interpret=None):
    """Drop-in for :func:`repro.models.ssm.gla_chunked` (zero init state).
    q,k: (B,L,H,Dk); v: (B,L,H,Dv); log_decay: (B,L,H)."""
    interpret = default_interpret() if interpret is None else interpret
    b, L, h, dk = q.shape
    dv = v.shape[-1]
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, L, x.shape[-1])
    ldf = log_decay.transpose(0, 2, 1).reshape(b * h, L)
    y, s = gla_scan(fold(q), fold(k), fold(v), ldf, chunk=chunk,
                    interpret=interpret)
    y = y.reshape(b, h, L, dv).transpose(0, 2, 1, 3)
    return y, s.reshape(b, h, dk, dv)


def jdob_sweep_inputs(profile, fleet, edge, t_free=0.0, rho=0.03e9):
    """Host side of the sweep (Alg. 1's per-ñ sort) in the (GHz, s, J)
    scaling of :mod:`repro.core.jdob`: the argument tuple of
    :func:`~repro.kernels.jdob_sweep.jdob_sweep_kernel`."""
    from repro.core.jdob import _GHZ, make_f_sweep
    N = profile.N
    M = fleet.M
    v = profile.v() / _GHZ
    u = profile.u()
    phi_b, phi_s = edge.phi_coeffs(profile)
    psi_b, psi_s = edge.psi_coeffs(profile)
    phi_b, phi_s = phi_b / _GHZ, phi_s / _GHZ
    psi_b, psi_s = psi_b * _GHZ ** 2, psi_s * _GHZ ** 2
    fsw = make_f_sweep(edge, rho) / _GHZ
    K = len(fsw)

    f_loc = np.clip(fleet.zeta * v[-1] * _GHZ / fleet.deadline / _GHZ,
                    fleet.f_min / _GHZ, fleet.f_max / _GHZ)
    e_loc = fleet.kappa * _GHZ ** 2 * u[-1] * f_loc ** 2

    th = np.full((N + 1, M), np.inf, np.float32)
    sufft = np.zeros((N + 1, M), np.float32)
    our = np.zeros((N + 1, M), np.float32)
    eup = np.zeros((N + 1, M), np.float32)
    elo = np.zeros((N + 1, M), np.float32)
    zet = np.zeros((N + 1, M), np.float32)
    kus = np.zeros((N + 1, M), np.float32)
    fmn = np.zeros((N + 1, M), np.float32)
    fmx = np.zeros((N + 1, M), np.float32)
    scal = np.zeros((N + 1, 8), np.float32)
    for nt in range(N):
        gamma = profile.O[nt] / fleet.rate + fleet.zeta * v[nt] * _GHZ \
            / fleet.f_max
        order = np.argsort(-gamma, kind="stable")
        g_s = gamma[order]
        T_s = fleet.deadline[order]
        st = np.minimum.accumulate(T_s[::-1])[::-1]
        b_in = M - np.arange(M)
        denom = st - g_s
        phi_i = phi_b[nt] + phi_s[nt] * b_in
        th[nt] = np.where(denom > 0, phi_i / np.where(denom > 0, denom, 1.0),
                          np.inf)
        sufft[nt] = st
        our[nt] = (profile.O[nt] / fleet.rate)[order]
        eup[nt] = (profile.O[nt] / fleet.rate * fleet.p_up)[order]
        elo[nt] = e_loc[order]
        zet[nt] = fleet.zeta[order]
        kus[nt] = (fleet.kappa * _GHZ ** 2)[order]
        fmn[nt] = (fleet.f_min / _GHZ)[order]
        fmx[nt] = (fleet.f_max / _GHZ)[order]
        scal[nt] = [phi_b[nt], phi_s[nt], psi_b[nt], psi_s[nt], v[nt], u[nt],
                    t_free, 0.0]
    f_rows = np.broadcast_to(fsw.astype(np.float32), (N + 1, K))

    # users on lanes, padded to whole vregs: a padded user never joins the
    # batch (th = +inf) and contributes zero energy
    Mp = -(-M // LANES) * LANES
    pad = lambda a, fill=0.0: jnp.asarray(
        np.pad(a, ((0, 0), (0, Mp - M)), constant_values=fill)[:, None, :])
    return (pad(th, np.inf), pad(sufft), pad(our), pad(eup), pad(elo),
            pad(zet), pad(kus), pad(fmn), pad(fmx),
            jnp.asarray(scal[:, None, :]), jnp.asarray(f_rows[:, :, None]))


def jdob_sweep_op(profile, fleet, edge, t_free=0.0, rho=0.03e9,
                  interpret=None):
    """The paper's (ñ × f_e) energy grid on-device.  Host does Alg.1's
    sort; kernel does Alg.2's sweep.  Returns an (N+1, K) float32 grid
    whose row N is +inf (local branch handled in closed form by the
    caller)."""
    interpret = default_interpret() if interpret is None else interpret
    grid = jdob_sweep_kernel(
        *jdob_sweep_inputs(profile, fleet, edge, t_free=t_free, rho=rho),
        interpret=interpret)
    grid = np.array(grid[:, :, 0])
    grid[profile.N] = np.inf
    return grid


def jdob_sweep_schedule(profile, fleet, edge, t_free=0.0, rho=0.03e9,
                        interpret=None):
    """Inner group solver backed by the Pallas sweep kernel: the (ñ × f_e)
    grid runs on-device (:func:`jdob_sweep_op`), the host argmin picks the
    winning partition, and that single-ñ problem is re-evaluated through
    the jitted core so the returned :class:`~repro.core.jdob.Schedule`
    carries the core's exact float64 energies/offload sets/DVFS
    frequencies.  Signature-compatible with
    :func:`~repro.core.jdob.jdob_schedule`, so it routes through
    :func:`~repro.core.grouping.optimal_grouping` as an ``inner`` — the
    planner-service spec lookup returns None for it, which is correct:
    each grid IS the group's whole partition sweep, so the sequential
    reference fold is the matching outer loop.  The grid math is float32
    with a plain row sum (vs the core's ``_pow2_sum`` fold), so on a
    near-exact tie between partitions the two backends may pick different
    ñ; the winner's energy always comes from the core re-solve."""
    from repro.core.jdob import jdob_schedule
    grid = jdob_sweep_op(profile, fleet, edge, t_free=t_free, rho=rho,
                         interpret=interpret)
    per_nt = grid.min(axis=1)
    nt = int(per_nt.argmin())
    if not np.isfinite(per_nt[nt]):
        nt = profile.N          # all-local: the core's closed-form branch
    return jdob_schedule(profile, fleet, edge, t_free=t_free, rho=rho,
                         partitions=[nt])
