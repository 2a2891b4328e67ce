"""The key laws draw what YCSB draws."""

import numpy as np
import pytest

from potbench.keylaws import _distinct, uniform, zipfian

N = 1 << 20
THETA = 0.99
DRAWS = 2_000_000


def _tolerance(p: float) -> float:
    return 4 * np.sqrt(p * (1 - p) / DRAWS)


def test_zipfian_head_at_2_20_keys():
    """Gray et al.'s generator, as YCSB's ZipfianGenerator runs it: the
    hottest rank draws exactly 1/zeta(2^20) = 6.47%.  Its top ten draw
    20.14% (the generator's closed form for ranks below 10), not exact
    Zipf's 19.14%: ranks from 2 on come from Gray's approximation."""
    zetan = zipfian.zeta(N, THETA)
    assert zetan == pytest.approx(15.4463, abs=1e-3)
    r = zipfian.ranks(np.random.default_rng(1).random(DRAWS), N, THETA, zetan)
    counts = np.bincount(r, minlength=N)
    hot = 1 / zetan
    assert hot == pytest.approx(0.0647, abs=1e-4)
    assert abs(counts[0] / DRAWS - hot) < _tolerance(hot)
    eta = (1 - (2 / N) ** (1 - THETA)) / (1 - zipfian.zeta(2, THETA) / zetan)
    top10 = ((10 / N) ** (1 - THETA) - 1 + eta) / eta
    assert top10 == pytest.approx(0.2014, abs=1e-4)
    assert abs(counts[:10].sum() / DRAWS - top10) < _tolerance(top10)
    exact = sum(i ** -THETA for i in range(1, 11)) / zetan
    assert exact == pytest.approx(0.1914, abs=1e-4)
    assert counts[:10].sum() / DRAWS - exact > 2 * _tolerance(exact)


@pytest.mark.parametrize("law", [zipfian, uniform])
def test_rows_are_distinct_and_in_range(law):
    rng = np.random.default_rng(2)
    keys = law.draw(rng, 4096, (2000, 10), {"theta": THETA})
    assert keys.shape == (2000, 10)
    assert keys.min() >= 0 and keys.max() < 4096
    assert all(len(set(row)) == 10 for row in keys.tolist())


def test_distinct_redraws_only_clashes():
    """The first draw of each row stands; a later key that repeats one
    before it in its row is drawn again."""
    seq = iter([7, 7, 3, 9, 5, 4])

    def sample(n):
        return np.asarray([next(seq) for _ in range(n)])
    assert _distinct.rows(sample, (2, 2)).tolist() == [[7, 5], [3, 9]]
