"""Seeded open-loop arrival schedules.

``u64`` is copied from ``repro.loadgen.arrivals``: a stateless
splitmix64-style counter hash, bit-identical on every platform, into
which a seed of any size (beyond 32 bits too) folds exactly.
It keys every other random stream of the benchmark.

The Poisson schedule keeps the work of a run fixed across seeds.  Its
``n`` gaps are the exponential distribution's quantiles at
``(i + 0.5) / n``, in an order that the seed draws: every seed offers
the same set of gaps, so the same number of requests lands in the
window, and only their order (where the bursts fall) changes.
"""

from __future__ import annotations

import numpy as np

_M64 = (1 << 64) - 1
_P1 = 0x9E3779B97F4A7C15      # golden-ratio increment (splitmix64)
_P2 = 0xBF58476D1CE4E5B9
_P3 = 0x94D049BB133111EB


def u64(seed: int, *counters: int) -> int:
    """Stateless 64-bit draw for (seed, counters...)."""
    z = (seed * _P1) & _M64
    for i, c in enumerate(counters):
        z = (z + (c + 1) * ((_P2 + 2 * i) & _M64)) & _M64
    z ^= z >> 30
    z = (z * _P2) & _M64
    z ^= z >> 27
    z = (z * _P3) & _M64
    return z ^ (z >> 31)


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent numpy generator for one named stream of a seed."""
    return np.random.default_rng(u64(seed, stream))


def poisson_times_s(seed: int, rate_per_s: float, seconds: float
                    ) -> np.ndarray:
    """Arrival offsets (s) from the window's start, all below
    ``seconds``: ``round(rate * seconds)`` requests whose gaps are the
    exponential quantiles of mean ``1 / rate`` in seeded order, scaled
    so that the gaps sum to ``seconds``."""
    n = max(1, round(rate_per_s * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = gaps[rng(seed, 0xA221).permutation(n)] * (seconds / gaps.sum())
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
