"""The one traffic generator: reads a mix file's parameters and yields a
seeded request stream.

Two modes:

* ``online`` — an open-loop Poisson stream over a fleet of ``devices``.
  Inter-arrival gaps come in blocks of ``block``: each block holds the
  same exponential quantiles at rate ``rate_hz``, in an order drawn from
  the seed, so every seed offers the same load.  Each device's deadline
  factor β is one of ``devices`` evenly spaced points of ``beta`` (the
  same set for every seed, assigned to devices by the seed).  A device is
  not drawn again while it holds a request: for ``hold_frac`` of its
  deadline after its last arrival (the slack policy flushes a request at
  most ``1 − keep_frac`` of its deadline after it arrives).  When every
  device holds one, the arrival waits for the first to free up.
  ``prompt_tokens`` > 0 gives each request that many token ids.
* ``waves`` — ``wave_users`` users arrive together; each wave draws its
  own β ~ U(``beta``) from the seed.

The same seed gives the same stream.  Seeds are any whole number: they
enter numpy's generator as a list of words, so values past 32 bits work.
"""
from __future__ import annotations

import dataclasses

import numpy as np

#: sub-stream tags: one independent generator per purpose and block
_BETA, _GAPS, _DEVICES, _TOKENS, _WAVES = range(5)


def rng(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  *tags])


def stratified(lo: float, hi: float, n: int, gen) -> np.ndarray:
    """``n`` evenly spaced points of U(lo, hi), in an order drawn by ``gen``."""
    return gen.permutation(lo + (hi - lo) * (np.arange(n) + 0.5) / n)


def device_betas(mix: dict, seed: int) -> np.ndarray:
    lo, hi = mix["beta"]
    return stratified(lo, hi, mix["devices"], rng(seed, _BETA))


@dataclasses.dataclass
class Arrivals:
    """One generated chunk: request ids, arrival times (s), devices, and
    token ids (``None`` without prompts)."""

    ids: np.ndarray
    times: np.ndarray
    devices: np.ndarray
    tokens: np.ndarray | None


class OnlineStream:
    """Lazily generated arrivals of an ``online`` mix, one block per
    :meth:`next_block` call."""

    def __init__(self, mix: dict, deadlines: np.ndarray, seed: int,
                 vocab: int = 0):
        assert mix["mode"] == "online"
        self.mix = mix
        self.seed = seed
        self.vocab = vocab
        self.block = int(mix["block"])
        n = self.block
        self._gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / mix["rate_hz"]
        self.hold = mix["hold_frac"] * np.asarray(deadlines, np.float64)
        self.free_at = np.zeros(len(deadlines))
        self._dev_rng = rng(seed, _DEVICES)
        self.t = 0.0
        self.k = 0

    def next_block(self) -> Arrivals:
        n, M = self.block, len(self.free_at)
        gaps = rng(self.seed, _GAPS, self.k).permutation(self._gaps)
        times = np.empty(n)
        devs = np.empty(n, np.int64)
        free_at, hold, gen = self.free_at, self.hold, self._dev_rng
        draws = gen.integers(0, M, size=4 * n)
        d = 0
        t = self.t
        for i in range(n):
            t += gaps[i]
            tries = 0
            while True:                        # uniform over free devices
                if d == len(draws):
                    draws, d = gen.integers(0, M, size=4 * n), 0
                dev = draws[d]
                d += 1
                if free_at[dev] <= t:
                    break
                tries += 1
                if tries % 64 == 0 and free_at.min() > t:
                    t = float(free_at.min())   # every device holds one
            free_at[dev] = t + hold[dev]
            times[i], devs[i] = t, dev
        self.t = t
        tokens = None
        if self.mix.get("prompt_tokens", 0):
            tokens = rng(self.seed, _TOKENS, self.k).integers(
                0, self.vocab, size=(n, self.mix["prompt_tokens"]),
                dtype=np.int32)
        ids = self.k * n + np.arange(n)
        self.k += 1
        return Arrivals(ids, times, devs, tokens)


def wave_betas(mix: dict, seed: int, wave: int) -> np.ndarray:
    """β of every user of wave ``wave`` of a ``waves`` mix."""
    assert mix["mode"] == "waves"
    lo, hi = mix["beta"]
    return rng(seed, _WAVES, wave).uniform(lo, hi, mix["wave_users"])
