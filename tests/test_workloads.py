"""Zipf address draws of the workload generators: every skew the
contention knob accepts returns promptly, and the inverse-CDF law used
for small skews is the clamped Zipf law numpy samples."""

import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import workloads as W

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("skew", [5e-324, 2.220446049250313e-16, 1e-10,
                                  1e-7, 5e-4, 0.99])
def test_zipf_addrs_small_skews_return(skew):
    """numpy's own zipf sampler never returns for skews near 1e-10, and a
    stall in C code ignores signals: draw in a child with a time limit."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    code = ("from repro.core import workloads as W; import numpy as np; "
            f"wl = W.counters(n_txns=64, n_objects=4096, n_reads=2, "
            f"n_writes=2, skew={skew!r}, seed=7); "
            "a = np.asarray(wl.batch.addrs); print(a.min(), a.max())")
    r = subprocess.run([sys.executable, "-c", code], env=env, timeout=60,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    lo, hi = map(int, r.stdout.split())
    assert 0 <= lo <= hi < 4096


@pytest.mark.parametrize("n_objects", [2, 24])
@pytest.mark.parametrize("skew", [0.8, 1.5])
def test_zipf_cdf_matches_numpy_law(n_objects, skew):
    """Where numpy's sampler is accurate, its clamped address frequencies
    agree with the inverse-CDF law within 5 standard errors."""
    m = 200_000
    rng = np.random.default_rng(1)
    drawn = np.minimum(rng.zipf(1.0 + skew, size=m) - 1, n_objects - 1)
    freq = np.bincount(drawn, minlength=n_objects) / m
    cdf = W._zipf_clamped_cdf(n_objects, skew)
    p = np.diff(cdf, prepend=0.0) / cdf[-1]
    for addr in (0, n_objects - 1):
        se = np.sqrt(p[addr] * (1 - p[addr]) / m)
        assert abs(freq[addr] - p[addr]) < 5 * se, (addr, freq[addr], p[addr])


def test_zipf_cdf_continuous_as_skew_vanishes():
    """The law tends to the 1/k law on [1, 2**63 - 1] as skew -> 0."""
    n = 64
    tiny = W._zipf_clamped_cdf(n, 1e-300)
    p = np.diff(tiny, prepend=0.0) / tiny[-1]
    harmonic = 1.0 / np.arange(1, n)
    total = harmonic.sum() + np.log(2.0 ** 63 / n)   # sum_{k>=n} 1/k
    np.testing.assert_allclose(p[:-1], harmonic / total, rtol=1e-3)
    np.testing.assert_allclose(
        W._zipf_clamped_cdf(n, 1e-12) / W._zipf_clamped_cdf(n, 1e-12)[-1],
        tiny / tiny[-1], rtol=1e-6)
