"""The executor's host output pool: ``run_partitioned`` writes each flush
into pooled memory, hands a buffer out again only once nothing refers to
the output that held it, and returns the same logits as a fresh
``np.zeros`` would hold."""
import dataclasses
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core import Schedule
from repro.models import init_params
from repro.serving import BlockwiseExecutor, Request
from repro.serving.outputs import HostOutputs
from repro.serving.server import run_partitioned


@pytest.fixture(scope="module")
def ex():
    cfg = ARCHS["glm4-9b"].reduced()
    return BlockwiseExecutor(cfg, init_params(cfg, jax.random.PRNGKey(0)))


def _flush(ex, offload, seed=0, partition=1):
    rng = np.random.default_rng(seed)
    reqs = [Request(user=m, deadline=1.0,
                    tokens=rng.integers(0, ex.cfg.vocab_size, 8,
                                        dtype=np.int32))
            for m in range(len(offload))]
    sched = Schedule(feasible=True, energy=0.0, partition=partition,
                     f_edge=1e9, offload=np.asarray(offload, bool),
                     f_device=np.ones(len(offload)), t_free_end=0.0, terms={},
                     per_user_energy=np.zeros(len(offload)))
    return reqs, sched


def _run(ex, offload, seed=0):
    reqs, sched = _flush(ex, offload, seed)
    return run_partitioned(ex, ex.cfg.vocab_size, reqs, sched)


def test_interleaved_rows_equal_monolithic_and_the_fresh_buffer_way(ex):
    offload = [True, False, True, False, False]
    reqs, sched = _flush(ex, offload, seed=1)
    out = run_partitioned(ex, ex.cfg.vocab_size, reqs, sched)
    assert isinstance(out, np.ndarray) and out.flags.writeable
    assert out.shape == (5, 8, ex.cfg.vocab_size) and out.dtype == np.float32
    tokens = jnp.asarray(np.stack([r.tokens for r in reqs]))
    np.testing.assert_allclose(out, np.asarray(ex.full_forward(tokens)),
                               atol=1e-4, rtol=1e-4)
    # the same dispatches scattered into zeroed memory: the same bits
    h = ex.embed(tokens)
    n, off = len(ex.layers), sched.offload
    want = np.zeros(out.shape, np.float32)
    want[~off] = np.asarray(ex.head(ex.run_blocks(h[~off], 0, n)))
    want[off] = np.asarray(ex.head(ex.run_blocks(
        ex.run_blocks(h[off], 0, 1), 1, n)))
    np.testing.assert_array_equal(out, want)
    out[1::2] = 0.0                       # the caller may write its result


def test_row_view_survives_later_calls(ex):
    row = _run(ex, [True, False, True])[1]
    kept = row.copy()
    for seed in range(3):
        _run(ex, [False, True, True], seed=10 + seed)
    np.testing.assert_array_equal(row, kept)


def test_dropped_output_is_reused_by_the_next_call(ex):
    _run(ex, [True, False])               # its output dies at once
    taken, reused = ex.outputs.taken, ex.outputs.reused
    out = _run(ex, [False, True])
    assert (ex.outputs.taken, ex.outputs.reused) == (taken + 1, reused + 1)
    del out
    _run(ex, [True])                      # a smaller output fits too
    assert ex.outputs.reused == reused + 2


def test_outputs_held_at_once_never_share_memory(ex):
    held = [_run(ex, [s % 2 == 0, True, False][:1 + s % 3], seed=s)
            for s in range(4)]
    for i, a in enumerate(held):
        for b in held[i + 1:]:
            assert not np.shares_memory(a, b)


def test_offload_mask_must_cover_the_batch(ex):
    reqs, sched = _flush(ex, [True, False])
    with pytest.raises(ValueError, match="offload mask"):
        run_partitioned(ex, ex.cfg.vocab_size, reqs,
                        dataclasses.replace(sched, offload=np.ones(3, bool)))


def test_pool_keeps_one_free_buffer_and_trims_live_ones():
    pool = HostOutputs()
    big, fresh = pool.take((4, 1024, 256))       # 4 MiB
    assert not fresh
    big[:] = 1.0
    held = [pool.take((1, 1024, 256))[0] for _ in range(3)]
    assert pool.reused == 0
    del big
    small, reused = pool.take((1, 1024, 256))
    assert reused                                # big's mapping, 1 MiB used
    small[:] = 2.0
    buf, = [b for b in pool._bufs if np.shares_memory(b.base, small)]
    assert buf.resident == 4 << 20
    pool.take((1, 8))                            # small still held: trimmed
    assert buf.resident == 1 << 20
    np.testing.assert_array_equal(small, 2.0)
    del held, small
    last, _ = pool.take((1, 8))
    assert sum(b.free for b in pool._bufs) == 1  # one spare beside ``last``
    assert len(pool._bufs) == 2


def test_pool_under_threads_hands_no_live_memory_out_twice():
    pool = HostOutputs()
    errors = []

    def work(k):
        try:
            for i in range(200):
                out, _ = pool.take((1 + (k + i) % 3, 4096))
                out[:] = k
                if not (out == k).all():
                    errors.append((k, i))
        except Exception as e:                   # reported by the assert
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[:3]
    assert pool.taken == 16 * 200 and pool.reused > 0
