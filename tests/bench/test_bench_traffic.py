"""The traffic generator: the same stream for the same seed, the same
load for every seed, and no device drawn while it holds a request."""
import benchtest_util  # noqa: F401
import numpy as np
import pytest

from bench import traffic

MIX = {"mode": "online", "devices": 16, "beta": [10.0, 30.0],
       "rate_hz": 400.0, "hold_frac": 0.3, "prompt_tokens": 32, "block": 512}
BIG_SEED = 2 ** 33 + 12345


def _stream(seed, mix=MIX):
    T = (1.0 + traffic.device_betas(mix, seed)) * 4e-3
    return T, traffic.OnlineStream(mix, T, seed, vocab=1000)


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED])
def test_same_seed_same_stream(seed):
    (_, a), (_, b) = _stream(seed), _stream(seed)
    for _ in range(3):
        x, y = a.next_block(), b.next_block()
        assert np.array_equal(x.times, y.times)
        assert np.array_equal(x.devices, y.devices)
        assert np.array_equal(x.tokens, y.tokens)


def test_other_seed_same_load_other_order():
    Ta, a = _stream(1)
    Tb, b = _stream(2)
    assert np.array_equal(np.sort(Ta), np.sort(Tb))        # same β set
    assert not np.array_equal(Ta, Tb)
    xa, xb = a.next_block(), b.next_block()
    ga, gb = np.diff(xa.times), np.diff(xb.times)
    assert not np.array_equal(xa.devices, xb.devices)
    # the offered rate is the same to a few percent (holds defer a few)
    assert xa.times[-1] == pytest.approx(xb.times[-1], rel=0.05)
    assert ga.min() >= 0 and gb.min() >= 0


@pytest.mark.parametrize("seed", [3, BIG_SEED])
def test_no_device_drawn_while_it_holds_a_request(seed):
    T, s = _stream(seed)
    hold = MIX["hold_frac"] * T
    times, devs = [], []
    for _ in range(4):
        blk = s.next_block()
        times.extend(blk.times)
        devs.extend(blk.devices)
    last = {}
    for t, d in zip(times, devs):
        if d in last:
            assert t >= last[d] + hold[d] - 1e-15
        last[d] = t
    assert np.all(np.diff(times) >= 0)


def test_saturated_pool_waits_for_a_free_device():
    mix = dict(MIX, devices=2, rate_hz=1e6)
    T, s = _stream(5, mix)
    blk = s.next_block()
    # two devices, each held for hold_frac·T: arrivals cannot come faster
    assert np.diff(blk.times).mean() >= 0.4 * (0.3 * T.min())


def test_wave_betas_per_wave_and_seed():
    mix = {"mode": "waves", "wave_users": 40, "beta": [0.0, 10.0]}
    a = traffic.wave_betas(mix, BIG_SEED, 0)
    assert np.array_equal(a, traffic.wave_betas(mix, BIG_SEED, 0))
    assert not np.array_equal(a, traffic.wave_betas(mix, BIG_SEED, 1))
    assert a.shape == (40,) and a.min() >= 0 and a.max() <= 10
