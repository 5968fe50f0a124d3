"""The paper's Alg. 2 inner sweep as a Pallas kernel.

Computes the (partition ñ × edge-frequency) energy grid of J-DOB on-device:
one grid row per partition point; the (K × M) membership/DVFS/energy
evaluation is a dense VMEM-resident block (the greedy batching set update is
the ``th <= f`` comparison — valid because the threshold sequence is
non-increasing, the paper's key structural result).  The host-side sort
(Alg. 1 line 5) happens in the ops wrapper; the kernel consumes per-ñ
sorted arrays.  Mirrors the single-group slice of
:func:`repro.core.jdob.jdob_plan_batched` (same GHz/s/J scaled units);
oracle = :func:`repro.core.jdob.jdob_energy_grid` via
:mod:`repro.kernels.ref`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_INF = jnp.inf
#: lane width of a TPU vreg: the user axis is padded to a multiple of it
LANES = 128


def _kernel(th_ref, sufft_ref, our_ref, eup_ref, eloc_ref, zeta_ref, ku_ref,
            fmin_ref, fmax_ref, scal_ref, f_ref, o_ref):
    # one partition row per grid step; users on lanes, sweep frequencies
    # on sublanes: (1, 1, M) user rows broadcast against the (1, K, 1)
    # frequency column into the (1, K, M) evaluation block
    th = th_ref[...]                                 # (1, 1, M)
    sufft = sufft_ref[...]
    our = our_ref[...]                               # O_ñ / R_m  (s)
    eup = eup_ref[...]                               # uplink energy (J)
    eloc = eloc_ref[...]                             # local-opt energy (J)
    zeta = zeta_ref[...]
    ku = ku_ref[...]
    fmin = fmin_ref[...]
    fmax = fmax_ref[...]
    s = scal_ref[...]                                # (1, 1, 8)
    phi_b, phi_s, psi_b, psi_s, v_nt, u_nt, t_free = (
        s[:, :, i:i + 1] for i in range(7))          # (1, 1, 1) each
    f = f_ref[...]                                   # (1, K, 1)

    # greedy batching membership per sweep frequency (paper Alg.2 l.7-12)
    memb = th <= f                                   # (1, K, M)
    B_o = jnp.sum(jnp.where(memb, 1.0, 0.0), axis=2, keepdims=True)
    has = B_o > 0
    l_o = jnp.min(jnp.where(memb, sufft, _INF), axis=2, keepdims=True)
    phi = phi_b + phi_s * B_o                        # (1, K, 1)
    psi = psi_b + psi_s * B_o
    gpu_ok = f * (l_o - t_free) >= phi               # Eq. 6
    slack = l_o - our - phi / f                      # (1, K, M)
    gamma_off = jnp.where(slack > 0,
                          zeta * v_nt / jnp.maximum(slack, 1e-30),
                          _INF)                      # Eq. 19
    fdev = jnp.clip(gamma_off, fmin, fmax)           # Eq. 20
    dev_bad = memb & ~(gamma_off <= fmax * (1 + 1e-9))
    e_user = jnp.where(memb, ku * u_nt * fdev ** 2 + eup, eloc)  # Eq. 21
    energy = (jnp.sum(e_user, axis=2, keepdims=True)
              + jnp.where(has, psi * f ** 2, 0.0))
    n_bad = jnp.sum(jnp.where(dev_bad, 1.0, 0.0), axis=2, keepdims=True)
    feas = has & gpu_ok & (n_bad == 0)
    o_ref[...] = jnp.where(feas, energy, _INF)


def jdob_sweep_kernel(th, sufft, our, eup, eloc, zeta, ku, fmin, fmax,
                      scal, f_sweep, *, interpret: bool = False):
    """All (NP, 1, M) inputs sorted per-ñ by the paper's γ ordering, M a
    multiple of :data:`LANES` (pad users with ``th = +inf`` and zero
    energies: never members, zero contribution); scal: (NP, 1, 8);
    f_sweep: (NP, K, 1).  Returns the (NP, K, 1) energy grid
    (+inf = infeasible).  Every block spans the full last two dims of
    its array, which is what Mosaic's (8, 128) tiling rule asks of a
    block that is not tile-aligned; rows are independent, so the row
    grid is ``parallel``."""
    NP, _, M = th.shape
    K = f_sweep.shape[1]
    assert M % LANES == 0, f"pad the user axis to a multiple of {LANES}"
    row = lambda n: (n, 0, 0)
    mspec = pl.BlockSpec((1, 1, M), row)
    return pl.pallas_call(
        _kernel,
        grid=(NP,),
        in_specs=[mspec] * 9 + [pl.BlockSpec((1, 1, 8), row),
                                pl.BlockSpec((1, K, 1), row)],
        out_specs=pl.BlockSpec((1, K, 1), row),
        out_shape=jax.ShapeDtypeStruct((NP, K, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(th, sufft, our, eup, eloc, zeta, ku, fmin, fmax, scal, f_sweep)
