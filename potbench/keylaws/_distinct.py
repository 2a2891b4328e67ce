"""Rows of keys that are distinct within each row, redrawn where they clash."""

from __future__ import annotations

import numpy as np


def rows(sample, shape: tuple[int, int]) -> np.ndarray:
    """Fill a ``shape`` array row by row from ``sample(n) -> (n,) keys``,
    redrawing the later duplicate of any key that repeats within a row.
    Deterministic in the draws ``sample`` makes."""
    n, k = shape
    out = sample(n * k).reshape(n, k).astype(np.int64)
    for j in range(1, k):
        while True:
            clash = (out[:, :j] == out[:, j:j + 1]).any(axis=1)
            m = int(clash.sum())
            if m == 0:
                break
            out[clash, j] = sample(m)
    return out
