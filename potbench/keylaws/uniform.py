"""Uniform ranks, distinct within a transaction (YCSB
``requestdistribution=uniform``; Block-STM's p2p sender and receiver)."""

from __future__ import annotations

import numpy as np

from potbench.keylaws import _distinct


def draw(rng: np.random.Generator, n_keys: int, shape: tuple[int, int],
         params: dict) -> np.ndarray:
    return _distinct.rows(lambda n: rng.integers(0, n_keys, size=n), shape)
