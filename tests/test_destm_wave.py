"""Wave-speculative DeSTM retries == the serial token walk, bit for bit
(PR 10).

The wave-validity invariant (see repro/core/destm.py): a wave may commit
a token-order prefix of its re-executed members iff each committed row
(i) classifies identically once earlier wave members' speculative writes
are swapped for their actual re-executed writes, and (ii) logged no read
of an address an earlier prefix row commits this trip.  Both checks are
conservative only toward shrinking the prefix, and the first conflicting
row always commits, so:

* store image, fingerprint, and EVERY trace field except the wave
  observables (``retry_waves`` / ``waves_per_round``) are bitwise equal
  between ``wave=True`` and ``wave=False`` — and both match the PoGL
  serial oracle's store;
* ``retry_waves`` (wave) <= retry events (serial walk, = Σ retries for
  DeSTM), with equality exactly on fully serial conflict chains.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import example, given, settings, st

from repro.core import (RMW, READ, WRITE, RoundRobinSequencer, fingerprint,
                        make_batch, make_store)
from repro.core import workloads as W
from repro.core.destm import _destm_execute
from repro.core.engine import ExecTrace
from repro.core.pogl import pogl_execute

# the wave observables are the ONLY fields allowed to differ
WAVE_FIELDS = {"retry_waves", "waves_per_round"}

_destm = jax.jit(_destm_execute,
                 static_argnames=("n_lanes", "max_rounds", "incremental",
                                  "compact", "wave"))


def _seq_for(wl, lanes=None, n_lanes=None):
    lanes = wl.lanes.tolist() if lanes is None else lanes
    seqr = RoundRobinSequencer(n_root_lanes=n_lanes or wl.n_lanes)
    return jnp.asarray(seqr.order_for(lanes), jnp.int32)


def _run_both(store, batch, seq, lanes, n_lanes):
    sW, tW = _destm(store, batch, seq, lanes, n_lanes, wave=True)
    sS, tS = _destm(store, batch, seq, lanes, n_lanes, wave=False)
    return sW, tW, sS, tS


def _assert_wave_equals_serial(store, batch, seq, lanes, n_lanes, ctx=""):
    sW, tW, sS, tS = _run_both(store, batch, seq, lanes, n_lanes)
    assert int(fingerprint(sW)) == int(fingerprint(sS)), ctx
    np.testing.assert_array_equal(np.asarray(sW.values),
                                  np.asarray(sS.values), err_msg=ctx)
    np.testing.assert_array_equal(np.asarray(sW.versions),
                                  np.asarray(sS.versions), err_msg=ctx)
    assert int(sW.gv) == int(sS.gv), ctx
    for f in dataclasses.fields(ExecTrace):
        if f.name in WAVE_FIELDS:
            continue
        np.testing.assert_array_equal(
            np.asarray(getattr(tW, f.name)), np.asarray(getattr(tS, f.name)),
            err_msg=f"{ctx}: trace field {f.name} diverged")
    events = int(tS.retry_waves)
    waves = int(tW.retry_waves)
    # the serial walk's trips ARE the retry events
    assert events == int(np.asarray(tS.retries).sum()), ctx
    assert waves <= events, f"{ctx}: waves {waves} > events {events}"
    # per-round counts dominate the same way, round by round
    wS, wW = tS.wave_counts(), tW.wave_counts()
    assert wS.shape == wW.shape and (wW <= wS).all(), ctx
    return sW, tW, tS


def _wl(k: int, contention: str, seed: int = 0, n_lanes: int = 4):
    n_lanes = min(n_lanes, k)
    if contention == "low":
        return W.counters(n_txns=k, n_objects=max(64, 8 * k), n_reads=2,
                          n_writes=2, n_lanes=n_lanes, skew=0.0, seed=seed)
    return W.counters(n_txns=k, n_objects=max(4, k // 4), n_reads=2,
                      n_writes=2, n_lanes=n_lanes, skew=1.0, seed=seed)


# ------------------------------------------------- wave == serial == oracle
@pytest.mark.parametrize("k", [1, 2, 64])
@pytest.mark.parametrize("contention", ["low", "high"])
@pytest.mark.parametrize("n_lanes", [1, 8])
def test_wave_equals_serial_walk(k, contention, n_lanes):
    wl = _wl(k, contention, seed=3 * k + n_lanes, n_lanes=n_lanes)
    store = make_store(wl.n_objects)
    seq = _seq_for(wl)
    lanes = jnp.asarray(wl.lanes, jnp.int32)
    sW, _, _ = _assert_wave_equals_serial(
        store, wl.batch, seq, lanes, wl.n_lanes,
        f"k={k} {contention} lanes={wl.n_lanes}")
    # anchor both modes to the serial oracle
    assert int(fingerprint(sW)) == int(fingerprint(
        pogl_execute(store, wl.batch, seq)))


def test_single_lane_degenerate():
    # one lane: one member per round, never a conflict, never a wave
    wl = _wl(12, "high", seed=5, n_lanes=1)
    store = make_store(wl.n_objects)
    seq = _seq_for(wl)
    lanes = jnp.asarray(wl.lanes, jnp.int32)
    _, tW, tS = _assert_wave_equals_serial(store, wl.batch, seq, lanes, 1,
                                           "single lane")
    assert int(tW.retry_waves) == int(tS.retry_waves) == 0
    assert int(np.asarray(tW.retries).sum()) == 0


def test_fully_serial_chain_wave_equals_events():
    # every txn RMWs the same object: within a round, each member
    # conflicts with ALL earlier members, so each wave resolves exactly
    # one row — waves == retry events (the equality edge of the bound)
    n_lanes, per_lane = 6, 2
    progs = [[(RMW, 0, False, 1)]
             for _ in range(n_lanes * per_lane)]
    batch = make_batch(progs)
    lanes = [i % n_lanes for i in range(n_lanes * per_lane)]
    seq = _seq_for(None, lanes=lanes, n_lanes=n_lanes)
    store = make_store(16)
    _, tW, tS = _assert_wave_equals_serial(
        store, batch, seq, jnp.asarray(lanes, jnp.int32), n_lanes,
        "serial chain")
    events = int(tS.retry_waves)
    assert events == (n_lanes - 1) * per_lane  # all but the token head
    assert int(tW.retry_waves) == events       # no wave win on a chain


def test_disjoint_pairs_one_wave_per_round():
    # lanes (2i, 2i+1) blind-WRITE object i: each round has 4
    # independent pairwise write-write conflicts.  The serial walk pays
    # one retry event per pair; one wave re-executes all 4 losers at
    # once, and with empty read sets every re-execution is trivially
    # serial-valid, so the whole prefix commits in a single wave.  (RMW
    # pairs would NOT collapse: the loser must read its partner's value,
    # which commits in the same trip — after the wave's snapshot — so
    # the execution-validity check correctly rejects it to next wave.)
    n_lanes = 8
    progs = [[(WRITE, i // 2, False, i + 1)] for i in range(n_lanes)]
    batch = make_batch(progs)
    lanes = list(range(n_lanes))
    seq = _seq_for(None, lanes=lanes, n_lanes=n_lanes)
    store = make_store(16)
    sW, tW, tS = _assert_wave_equals_serial(
        store, batch, seq, jnp.asarray(lanes, jnp.int32), n_lanes,
        "disjoint pairs")
    assert int(tS.retry_waves) == n_lanes // 2   # one event per pair
    assert int(tW.retry_waves) == 1              # one wave clears them all
    # last-writer-wins per pair: the loser's value lands
    got = np.asarray(sW.values)[:n_lanes // 2, 0]
    np.testing.assert_array_equal(got, [2, 4, 6, 8])


def test_wave_counts_accessor_trims_to_rounds():
    wl = _wl(24, "high", seed=9, n_lanes=8)
    store = make_store(wl.n_objects)
    _, tW = _destm(store, wl.batch, _seq_for(wl),
                   jnp.asarray(wl.lanes, jnp.int32), wl.n_lanes, wave=True)
    counts = tW.wave_counts()
    assert counts.shape == (int(tW.rounds),)
    assert (counts >= 0).all()                   # -1 padding trimmed off
    assert counts.sum() == int(tW.retry_waves)


def test_session_wave_counts():
    from repro.core import PotSession
    wl = _wl(16, "high", seed=13, n_lanes=4)
    s = PotSession(wl.n_objects, engine="destm", n_lanes=4)
    s.submit(wl.batch, wl.lanes)
    (counts,) = s.wave_counts()
    assert counts.shape == (int(s.traces[0].rounds),)
    # pcc has no token walk: empty arrays, same accessor
    s2 = PotSession(wl.n_objects, engine="pcc", n_lanes=4)
    s2.submit(wl.batch, wl.lanes)
    assert s2.wave_counts()[0].size == 0


# ------------------------------------------------------- hypothesis property
@settings(max_examples=12, deadline=None)
@given(st.integers(0, 10_000), st.floats(0.0, 1.8),
       st.sampled_from([2, 5, 8]))
# a skew too small to lift the Zipf exponent off 1.0 must draw uniformly
@example(seed=0, skew=5e-324, n_lanes=2)
# ... and one just above it must not stall numpy's Zipf sampler
@example(seed=0, skew=1e-10, n_lanes=2)
def test_wave_equals_serial_property(seed, skew, n_lanes):
    # random retry graphs: skewed hot sets drive random conflict shapes
    wl = W.counters(n_txns=24, n_objects=24, n_reads=2, n_writes=2,
                    n_lanes=n_lanes, skew=skew, seed=seed)
    store = make_store(wl.n_objects)
    _assert_wave_equals_serial(
        store, wl.batch, _seq_for(wl), jnp.asarray(wl.lanes, jnp.int32),
        wl.n_lanes, f"seed={seed} skew={skew:.2f} lanes={n_lanes}")
