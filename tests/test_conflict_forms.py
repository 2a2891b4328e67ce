"""The conflict table's TPU formulation gives the engines the same
decisions as the off-TPU one.

On TPU the (K, K) conflict table and its strips come from the Pallas
kernels that compare the (K, 2L) footprint lists against the (L, K)
write lists; off the TPU the engines take the first-writer scatter-min
formulation and the ops their dense fallbacks.  Here the TPU branch is
forced (``ops._on_tpu``) with the Pallas kernels interpreted, and every
engine runs one small zipfian batch both ways: commit log, commit
rounds, store, versions, ``gv`` and round count must agree bit for bit.
The ops the engines call on that branch are checked against their dense
fallbacks the same way.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (destm_execute, get_engine, make_store, occ_execute,
                        pcc_execute, protocol)
from repro.core import workloads as W
from repro.core.tstore import StoreLayout
from repro.core.txn import gather_live_indices
from repro.kernels import conflict as C
from repro.kernels import ops

KERNELS = ("conflict_matrix_bits_delta", "conflict_matrix_bits_pair",
           "conflict_matrix_bits_addr_delta", "conflict_matrix_bits_addr_pair")


@pytest.fixture
def tpu_branch(monkeypatch):
    """A switch: once called, ops takes its TPU branch and the Pallas
    kernels it calls run in interpret mode.  Jit caches are dropped on
    both sides, so no trace of one branch serves the other."""
    def force():
        monkeypatch.setattr(ops, "_on_tpu", lambda: True)
        for name in KERNELS:
            kernel = getattr(C, name)
            monkeypatch.setattr(
                C, name,
                lambda *a, interpret, _k=kernel: _k(*a, interpret=True))
        jax.clear_caches()
    yield force
    jax.clear_caches()


def _zipf_batch(seed):
    return W.counters(n_txns=48, n_objects=64, n_reads=2, n_writes=2,
                      n_lanes=8, skew=0.99, seed=seed)


def _run(engine, wl, store):
    seq = jnp.arange(1, wl.batch.n_txns + 1, dtype=jnp.int32)
    lanes = jnp.asarray(wl.lanes, jnp.int32)
    if engine == "pcc":
        return pcc_execute(store, wl.batch, seq)
    if engine == "occ":
        return occ_execute(store, wl.batch, jnp.argsort(seq))
    if engine == "destm":
        return destm_execute(store, wl.batch, seq, lanes, wl.n_lanes)
    # pcc at pipeline depth > 0: a seed speculated against the store
    # before another batch committed, re-based onto the store after it
    before, _ = pcc_execute(store, _zipf_batch(99).batch, seq)
    seed = protocol.spec_execute(store, wl.batch)
    return get_engine("pcc").raw_spec(before, wl.batch, seq, lanes,
                                      wl.n_lanes, seed)


@pytest.mark.parametrize("engine", ["pcc", "occ", "destm", "pcc_seeded"])
def test_engines_tpu_table_equals_off_tpu(tpu_branch, engine):
    wl = _zipf_batch(seed=5)
    store = make_store(wl.n_objects, init=np.arange(wl.n_objects) % 5)
    out_b, tr_b = _run(engine, wl, store)
    tpu_branch()
    out_a, tr_a = _run(engine, wl, store)
    assert int(tr_a.rounds) > 1, "the batch should contend"
    for name, a, b in (("commit_pos", tr_a.commit_pos, tr_b.commit_pos),
                       ("commit_round", tr_a.commit_round,
                        tr_b.commit_round),
                       ("rounds", tr_a.rounds, tr_b.rounds),
                       ("values", out_a.values, out_b.values),
                       ("versions", out_a.versions, out_b.versions),
                       ("gv", out_a.gv, out_b.gv)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{engine}: {name}")


def test_round_state_carries_bitsets_only_when_sharded(tpu_branch):
    """One chip: a (K, K) table and no packed words.  Sharded store:
    (S, K, w) words per shard beside the table."""
    tpu_branch()
    wl = _zipf_batch(seed=1)
    k, n = wl.batch.n_txns, wl.n_objects
    store = make_store(n)
    rs = protocol.init_round_state(wl.batch, store.values, store.versions)
    assert rs.conflict.shape == (k, k)
    assert rs.foot_bits is None and rs.write_bits is None

    layout = StoreLayout(n, 4)
    c = layout.shard_size
    rs = protocol.init_round_state(
        wl.batch, jnp.zeros((4, c, 1), jnp.int32), jnp.zeros((4, c), jnp.int32),
        layout=layout)
    w = layout.words_per_shard
    assert rs.foot_bits.shape == rs.write_bits.shape == (4, k, w)


def _lists(k, l, n_objects, seed):
    rng = np.random.default_rng(seed)
    mk = lambda: (jnp.asarray(rng.integers(0, n_objects, (k, l)), jnp.int32),
                  jnp.asarray(rng.integers(0, l + 1, (k,)), jnp.int32))
    return (*mk(), *mk())


@pytest.mark.parametrize("n_live", [0, 1, 7, 40])
def test_address_strips_equal_masked_delta(tpu_branch, n_live):
    """The table's two round updates on the TPU branch — the masked
    delta kernel and the compact rungs' (C, K) / (K, C) strips — equal
    the dense recompute-and-select: live rows and columns fresh, the
    rest carried from ``old``."""
    k, l, n_objects = 40, 4, 300
    ra, rn, wa, wn = _lists(k, l, n_objects, seed=n_live)
    rng = np.random.default_rng(n_live + 1)
    old = jnp.asarray(rng.random((k, k)) < 0.2)
    live = np.zeros(k, bool)
    live[rng.choice(k, n_live, replace=False)] = True
    idx, valid = gather_live_indices(jnp.asarray(live), max(1, n_live))
    dense = np.asarray(ops._conflict_matrix_dense(ra, rn, wa, wn, n_objects))
    exp = np.where(live[:, None] | live[None, :], dense, np.asarray(old))
    tpu_branch()
    masked = ops.conflict_matrix_delta_addrs(ra, rn, wa, wn, old,
                                             jnp.asarray(live), n_objects)
    strips = ops.conflict_matrix_delta_compact_addrs(ra, rn, wa, wn, old,
                                                     idx, valid, n_objects)
    np.testing.assert_array_equal(np.asarray(masked), exp)
    np.testing.assert_array_equal(np.asarray(strips), exp)


@pytest.mark.parametrize("reads_only", [False, True])
def test_cross_conflicts_tpu_equals_dense(tpu_branch, reads_only):
    """DeSTM's reader × writer strip from two different result blocks:
    the address kernel on the TPU branch equals the dense bit-ops."""
    ra, rn, wa, wn = _lists(13, 3, 200, seed=2)
    _, _, xa, xn = _lists(9, 3, 200, seed=3)
    exp = np.asarray(ops.cross_conflicts(ra, rn, wa, wn, xa, xn, 200,
                                         reads_only=reads_only))
    tpu_branch()
    got = np.asarray(ops.cross_conflicts(ra, rn, wa, wn, xa, xn, 200,
                                         reads_only=reads_only))
    assert got.shape == (13, 9) and exp.any()
    np.testing.assert_array_equal(got, exp)
