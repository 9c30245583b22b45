"""The general traffic generator: everything a run offers, from the seed.

A traffic mix is a data file, ``traffic/<name>.json``, read here.  Its
``loop`` names the module that offers the work (``serve`` or ``train``)
and ``kernel`` the module under ``work/`` that counts a launch's work;
the other keys are parameters:

* ``arrivals``: ``poisson`` (open loop at ``rate_per_s``) or ``backlog``
  (closed loop that keeps ``backlog`` x ``max_batch`` requests queued);
* ``t_steps``: each request's window length;
* ``pool``: distinct procedural digits drawn from the seed;
* ``sample``: requests whose answers are compared with the reference;
* ``chunk``, ``pool_chunks``, ``check_steps``, ``window_checks``: the
  training stream and the calls of it compared.

Digits come from the copy of ``repro.data.digits`` beside this file
(mostly zero pixels, like MNIST).  Payload pools are built in set-up, so
the window only stamps and submits.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from chip import arrivals, digits

HERE = Path(__file__).resolve().parent


def load_mix(name: str) -> dict:
    with open(HERE / "traffic" / f"{name}.json") as f:
        return json.load(f)


def digit_pool(seed: int, n: int) -> tuple[np.ndarray, np.ndarray]:
    """uint8 intensities [n, 784] and int32 labels of procedural digits."""
    x, y = digits.make_digits(n, seed=arrivals.u64(seed, 0xD161))
    return np.clip(np.round(x * 255.0), 0, 255).astype(np.uint8), y


def counter_seeds(seed: int, stream: int, n: int) -> np.ndarray:
    """uint32 counter seeds (below 2**31, so int32 carries them too)."""
    return arrivals.rng(seed, stream).integers(
        0, 1 << 31, n, dtype=np.int64).astype(np.uint32)


def pool_picks(seed: int, stream: int, n: int, pool: int) -> np.ndarray:
    return arrivals.rng(seed, stream).integers(0, pool, n)


def arrival_times(mix: dict, seed: int, seconds: float) -> np.ndarray:
    """Offsets (s) from the window's start of every request offered."""
    if mix["arrivals"] != "poisson":
        raise ValueError(f"no schedule for arrivals={mix['arrivals']!r}")
    return arrivals.poisson_times_s(seed, mix["rate_per_s"], seconds)
