"""YCSB's zipfian request distribution, scrambled.

Ranks follow Gray et al.'s generator ("Quickly generating billion-record
synthetic databases", SIGMOD 1994), as YCSB's ``ZipfianGenerator`` draws
them: with ``zeta(n) = sum_{i=1..n} i^-theta``, a uniform ``u`` gives rank 0
when ``u * zeta(n) < 1``, rank 1 when it is below ``1 + 0.5^theta``, and
otherwise ``int(n * (eta * u - eta + 1)^alpha)``.  Rank 0 is drawn with
probability exactly ``1 / zeta(n)``: 6.47% for ``n = 2^20``, ``theta = 0.99``.

The stream sends a rank to a key through a permutation of ``[0, n)`` drawn
from the seed, so the hot keys are spread over the store as YCSB's
``ScrambledZipfianGenerator`` spreads them.  YCSB's own scrambler hashes a
rank drawn over 10^10 items modulo ``n``, which flattens the head (1/26.47
on the hottest key); this law keeps the zipfian over ``n`` itself.
"""

from __future__ import annotations

import functools

import numpy as np

from potbench.keylaws import _distinct


@functools.lru_cache(maxsize=None)
def zeta(n: int, theta: float) -> float:
    return float(np.sum(np.arange(1, n + 1, dtype=np.float64) ** -theta))


def ranks(u: np.ndarray, n: int, theta: float, zetan: float) -> np.ndarray:
    """Gray et al.'s rank for each uniform ``u`` in [0, 1)."""
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / n) ** (1.0 - theta)) / (1.0 - zeta(2, theta) / zetan)
    uz = u * zetan
    r = (n * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    r = np.where(uz < 1.0 + 0.5 ** theta, 1, r)
    r = np.where(uz < 1.0, 0, r)
    return np.clip(r, 0, n - 1)


def draw(rng: np.random.Generator, n_keys: int, shape: tuple[int, int],
         params: dict) -> np.ndarray:
    theta = float(params["theta"])
    zetan = zeta(n_keys, theta)
    return _distinct.rows(lambda m: ranks(rng.random(m), n_keys, theta, zetan),
                          shape)
