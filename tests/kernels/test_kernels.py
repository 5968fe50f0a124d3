"""Per-kernel validation: interpret=True vs the pure-jnp ref.py oracles,
swept over shapes and dtypes (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_edge_profile, make_fleet, mobilenet_v2_profile
from repro.kernels import (decode_attention_op, flash_attention_op,
                           gla_scan_op, jdob_sweep_op)
from repro.kernels.ref import (decode_attention_ref, flash_attention_ref,
                               gla_scan_ref, jdob_sweep_ref)

TOL = {jnp.float32: dict(atol=2e-5, rtol=2e-5),
       jnp.bfloat16: dict(atol=3e-2, rtol=3e-2)}


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,sq,sk,h,kv,hd,bq,bk,window", [
    (1, 64, 64, 4, 4, 32, 16, 16, None),
    (2, 128, 128, 4, 2, 64, 32, 64, None),       # GQA
    (2, 64, 64, 8, 1, 16, 64, 32, None),         # MQA
    (1, 128, 128, 2, 2, 128, 32, 32, 32),        # sliding window
    (1, 32, 32, 2, 2, 8, 32, 32, None),          # single block
])
def test_flash_attention_sweep(dtype, b, sq, sk, h, kv, hd, bq, bk, window):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(ks[0], (b, sq, h, hd), dtype)
    k = _rand(ks[1], (b, sk, kv, hd), dtype)
    v = _rand(ks[2], (b, sk, kv, hd), dtype)
    got = flash_attention_op(q, k, v, window=window, block_q=bq, block_k=bk,
                             interpret=True)
    want = flash_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), **TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,L,h,kv,hd,bk,pos,ring", [
    (2, 64, 4, 2, 32, 16, 40, False),
    (1, 128, 8, 8, 64, 64, 127, False),
    (2, 32, 4, 1, 16, 32, 100, True),            # ring cache, wrapped
    (1, 64, 2, 2, 128, 16, 10, True),            # ring cache, not yet full
    (2, 64, 4, 4, 16, 64, 0, False),             # first token
])
def test_decode_attention_sweep(dtype, b, L, h, kv, hd, bk, pos, ring):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(ks[0], (b, 1, h, hd), dtype)
    k = _rand(ks[1], (b, L, kv, hd), dtype)
    v = _rand(ks[2], (b, L, kv, hd), dtype)
    got = decode_attention_op(q, k, v, jnp.asarray(pos), ring=ring,
                              block_k=bk, interpret=True)
    want = decode_attention_ref(q, k, v, jnp.asarray(pos), ring=ring)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), **TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,L,h,dk,dv,chunk", [
    (2, 32, 2, 16, 16, 8),
    (1, 64, 4, 8, 24, 16),                       # Dk != Dv (mLSTM normalizer)
    (2, 128, 1, 64, 64, 128),                    # one chunk
    (1, 48, 2, 32, 32, 16),
])
def test_gla_scan_sweep(dtype, b, L, h, dk, dv, chunk):
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = _rand(ks[0], (b, L, h, dk), dtype)
    k = (_rand(ks[1], (b, L, h, dk), jnp.float32) * 0.3).astype(dtype)
    v = _rand(ks[2], (b, L, h, dv), dtype)
    ld = -jax.nn.softplus(jax.random.normal(ks[3], (b, L, h)))
    y1, s1 = gla_scan_op(q, k, v, ld, chunk=chunk, interpret=True)
    y2, s2 = gla_scan_ref(q, k, v, ld)
    # accumulation error grows with chunk width (the 128-wide single-chunk
    # case legitimately reaches ~4e-5 abs in float32 vs the step
    # recurrence); narrower chunks keep the tight seed tolerance
    tol = dict(TOL[dtype])
    if chunk >= 64:
        tol["atol"] = max(tol["atol"], 8e-5)
    np.testing.assert_allclose(y1.astype(jnp.float32),
                               y2.astype(jnp.float32), **tol)
    np.testing.assert_allclose(s1, s2, atol=1e-2 if dtype == jnp.bfloat16
                               else 1e-4, rtol=1e-2)


@pytest.mark.parametrize("M,beta,seed,t_free", [
    (4, 2.13, 0, 0.0), (8, (0.0, 10.0), 3, 1e-3), (12, 30.25, 1, 0.0),
    (1, 5.0, 2, 0.0),
])
def test_jdob_sweep_kernel_vs_grid(M, beta, seed, t_free):
    prof = mobilenet_v2_profile()
    edge = make_edge_profile(prof)
    fleet = make_fleet(M, prof, edge, beta=beta, seed=seed)
    got = jdob_sweep_op(prof, fleet, edge, t_free=t_free, interpret=True)
    want = jdob_sweep_ref(prof, fleet, edge, t_free=t_free)
    finite = np.isfinite(want)
    assert (np.isfinite(got) == finite).all()
    if finite.any():
        np.testing.assert_allclose(got[finite], want[finite], rtol=1e-4)
    # and the argmin (the selected strategy) coincides
    if finite.any():
        assert np.unravel_index(np.argmin(got), got.shape) == \
            np.unravel_index(np.argmin(want), want.shape)

