"""Sharded store == dense store, bit for bit (PR 5).

The shard decomposition invariant: with the address space partitioned
into S contiguous range shards, conflict(t, u) is the OR over shards of
per-shard conflicts, write-back splits into S independent scatters, and
every commit decision stays in global rank space — so S is a pure
layout knob.  Layers under test:

* the store layout itself — shard/unshard round-trips, padding for
  non-dividing S, layout-blind fingerprints;
* per-shard packed footprints + OR-reduced conflict tables (full,
  masked-row delta, and compact-strip paths) against the dense
  formulation's verdicts;
* ``fused_write_back`` / ``apply_writes`` sharded scatters against the
  dense scatter;
* every engine (pcc / occ / destm / pogl), masked and compact-ladder
  paths, at S in {2, 8} / K in {1, 2, 64} / high + low contention:
  store images, versions, fingerprints and full traces bitwise equal
  to the dense run;
* ``PotSession(shards=...)`` over a bucketed ragged stream: fingerprints
  and ``replay_log()`` equal the dense session's, replay round-trips;
* the ``shard_map`` mesh path on a real 8-device host-platform mesh
  (subprocess, like test_moe_shardmap) — also exercised by
  ``scripts/ci.sh --shard-smoke``.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import (PotSession, RoundRobinSequencer, ShardedStore,
                        StoreLayout, destm_execute, dense_image,
                        fingerprint, make_store, occ_execute, pcc_execute,
                        shard_store, unshard_store)
from repro.core import protocol
from repro.core import workloads as W
from repro.core.pogl import _pogl_raw
from repro.core.tstore import flat_values
from repro.core.txn import run_all
from repro.kernels import ops as kernel_ops

ENGINES = ("pcc", "occ", "destm")
TRACE_FIELDS = ("commit_round", "commit_pos", "first_round", "retries",
                "mode", "wait_rounds", "rounds", "exec_ops",
                "validation_words", "promotions", "barrier_ops",
                "wave_trips", "live_txns", "live_slots", "walked_slots",
                "live_per_round")


def _wl(k, contention, seed=0):
    if contention == "low":
        return W.counters(n_txns=k, n_objects=max(64, 8 * k), n_reads=2,
                          n_writes=2, n_lanes=min(8, k), skew=0.0,
                          seed=seed)
    return W.counters(n_txns=k, n_objects=max(4, k // 4), n_reads=2,
                      n_writes=2, n_lanes=min(8, k), skew=1.0, seed=seed)


def _seq_for(wl):
    seqr = RoundRobinSequencer(n_root_lanes=wl.n_lanes)
    return jnp.asarray(seqr.order_for(wl.lanes.tolist()), jnp.int32)


def _run(engine, store, wl, **kw):
    seq = _seq_for(wl)
    lanes = jnp.asarray(wl.lanes, jnp.int32)
    if engine == "pcc":
        return pcc_execute(store, wl.batch, seq, **kw)
    if engine == "occ":
        return occ_execute(store, wl.batch, jnp.argsort(seq), **kw)
    if engine == "destm":
        return destm_execute(store, wl.batch, seq, lanes, wl.n_lanes, **kw)
    raise ValueError(engine)


def _assert_stores_equal(dense, sharded, msg=""):
    np.testing.assert_array_equal(
        np.asarray(dense_image(dense)), np.asarray(dense_image(sharded)),
        err_msg=f"values diverged {msg}")
    dv = np.asarray(unshard_store(sharded).versions) \
        if isinstance(sharded, ShardedStore) else np.asarray(sharded.versions)
    np.testing.assert_array_equal(np.asarray(dense.versions), dv,
                                  err_msg=f"versions diverged {msg}")
    assert int(dense.gv) == int(sharded.gv), msg
    assert int(fingerprint(dense)) == int(fingerprint(sharded)), msg


def _assert_traces_equal(a, b, msg=""):
    for f in TRACE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
            err_msg=f"trace field {f} diverged {msg}")


# ------------------------------------------------------------ store layout
class TestStoreLayout:
    def test_shard_round_trip(self):
        store = make_store(100, slot=2,
                           init=np.arange(200).reshape(100, 2))
        for s in (2, 3, 7, 8):
            sh = shard_store(store, s)
            assert sh.shards == s
            assert sh.shard_size == -(-100 // s)
            back = unshard_store(sh)
            np.testing.assert_array_equal(np.asarray(store.values),
                                          np.asarray(back.values))
            np.testing.assert_array_equal(np.asarray(store.versions),
                                          np.asarray(back.versions))
            assert int(fingerprint(sh)) == int(fingerprint(store))

    def test_one_shard_no_mesh_stays_dense(self):
        # shards=1 without a mesh IS the dense layout: no ShardedStore is
        # created (it would route (1, C, slot) arrays through the dense
        # code paths), and engines run it as the dense store
        store = make_store(32)
        assert shard_store(store, 1) is store
        assert unshard_store(store) is store
        assert not isinstance(make_store(32, shards=1), ShardedStore)
        wl = _wl(8, "med", seed=4)
        out_a, tr_a = _run("pcc", make_store(wl.n_objects), wl)
        out_b, tr_b = _run("pcc",
                           shard_store(make_store(wl.n_objects), 1), wl)
        _assert_stores_equal(out_a, out_b, "shards=1")
        _assert_traces_equal(tr_a, tr_b, "shards=1")

    def test_make_store_sharded(self):
        sh = make_store(64, shards=4)
        assert isinstance(sh, ShardedStore)
        assert sh.values.shape == (4, 16, 1)
        assert sh.layout == StoreLayout(64, 4)
        assert isinstance(make_store(64), type(unshard_store(sh)))

    def test_flat_values_is_the_dense_image(self):
        store = make_store(10, init=np.arange(10))
        sh = shard_store(store, 4)  # C=3, padded to 12
        flat = flat_values(sh.values, sh.layout)
        assert flat.shape == (12, 1)
        np.testing.assert_array_equal(np.asarray(flat[:10]),
                                      np.asarray(store.values))

    def test_layout_address_map(self):
        lay = StoreLayout(10, 4)   # C = 3
        addrs = jnp.arange(10)
        np.testing.assert_array_equal(
            np.asarray(lay.shard_of(addrs) * lay.shard_size
                       + lay.offset_of(addrs)), np.arange(10))
        assert int(lay.shard_of(jnp.asarray(9))) == 3
        assert lay.padded_objects == 12 and lay.words_per_shard == 1

    def test_mesh_validation(self):
        store = make_store(16)
        with pytest.raises(ValueError):
            PotSession(store=shard_store(store, 2), shards=4)
        # jax.make_mesh's default Explicit axes break the sharded scatter
        explicit = jax.make_mesh((1,), ("shard",), axis_types=(
            jax.sharding.AxisType.Explicit,))
        with pytest.raises(ValueError, match="Auto axis"):
            shard_store(store, 1, mesh=explicit)
        with pytest.raises(ValueError):
            PotSession(16, bucket_ladder="golden")


# --------------------------------------------- per-shard conflict analysis
class TestShardedConflict:
    def _bits(self, wl, layout):
        store = make_store(wl.n_objects)
        res = run_all(wl.batch, store.values)
        return res, kernel_ops.packed_footprints_sharded(
            res.raddrs, res.rn, res.waddrs, res.wn, layout)

    @pytest.mark.parametrize("shards", [2, 3, 8])
    def test_or_reduced_table_matches_dense(self, shards):
        wl = _wl(32, "med", seed=11)
        layout = StoreLayout(wl.n_objects, shards)
        res, (foot, write) = self._bits(wl, layout)
        got = kernel_ops.conflict_matrix_sharded(foot, write)
        exp = kernel_ops._conflict_matrix_dense(
            res.raddrs, res.rn, res.waddrs, res.wn, wl.n_objects)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))

    @pytest.mark.parametrize("shards", [2, 8])
    def test_delta_matches_recompute(self, shards):
        # simulate rounds: shrinking live sets over changing store images
        wl = _wl(24, "med", seed=3)
        layout = StoreLayout(wl.n_objects, shards)
        rng = np.random.default_rng(5)
        values = jnp.asarray(
            rng.integers(0, 100, (wl.n_objects, 1)), jnp.int32)
        res = run_all(wl.batch, values)
        foot, write = kernel_ops.packed_footprints_sharded(
            res.raddrs, res.rn, res.waddrs, res.wn, layout)
        table = kernel_ops.conflict_matrix_sharded(foot, write)
        for n_live in (12, 5, 1, 0):
            live = np.zeros(24, bool)
            live[rng.choice(24, n_live, replace=False)] = True
            live = jnp.asarray(live)
            values = jnp.asarray(
                rng.integers(0, 100, (wl.n_objects, 1)), jnp.int32)
            res = run_all(wl.batch, values)
            foot, write = kernel_ops.update_packed_footprints_sharded(
                foot, write, res.raddrs, res.rn, res.waddrs, res.wn,
                live, layout)
            table = kernel_ops.conflict_matrix_delta_sharded(
                foot, write, table, live, layout)
            fresh_foot, fresh_write = kernel_ops.packed_footprints_sharded(
                res.raddrs, res.rn, res.waddrs, res.wn, layout)
            fresh = kernel_ops.conflict_matrix_sharded(fresh_foot,
                                                       fresh_write)
            refresh = np.asarray(live)[:, None] | np.asarray(live)[None, :]
            # refreshed entries fresh, stale entries carried
            np.testing.assert_array_equal(
                np.asarray(table)[refresh], np.asarray(fresh)[refresh])
            # live rows' packed words match a from-scratch pack
            for a, b in ((foot, fresh_foot), (write, fresh_write)):
                np.testing.assert_array_equal(
                    np.asarray(a)[:, np.asarray(live)],
                    np.asarray(b)[:, np.asarray(live)])

    @pytest.mark.parametrize("shards", [2, 8])
    def test_compact_strips_match_masked_delta(self, shards):
        from repro.core.txn import gather_live_indices
        wl = _wl(24, "med", seed=9)
        layout = StoreLayout(wl.n_objects, shards)
        rng = np.random.default_rng(13)
        values = jnp.asarray(
            rng.integers(0, 50, (wl.n_objects, 1)), jnp.int32)
        res0 = run_all(wl.batch, values)
        foot, write = kernel_ops.packed_footprints_sharded(
            res0.raddrs, res0.rn, res0.waddrs, res0.wn, layout)
        table = kernel_ops.conflict_matrix_sharded(foot, write)
        live = np.zeros(24, bool)
        live[rng.choice(24, 6, replace=False)] = True
        live = jnp.asarray(live)
        values2 = jnp.asarray(
            rng.integers(0, 50, (wl.n_objects, 1)), jnp.int32)
        res = run_all(wl.batch, values2)
        idx, valid = gather_live_indices(live, 8)
        cres = jax.tree.map(lambda a: a[idx], res)
        cfoot, cwrite = kernel_ops.update_packed_footprints_compact_sharded(
            foot, write, cres.raddrs, jnp.where(valid, cres.rn, 0),
            cres.waddrs, jnp.where(valid, cres.wn, 0), idx, valid, layout)
        got = kernel_ops.conflict_matrix_delta_compact_sharded(
            cfoot, cwrite, table, idx, valid, layout)
        mfoot, mwrite = kernel_ops.update_packed_footprints_sharded(
            foot, write, res.raddrs, res.rn, res.waddrs, res.wn, live,
            layout)
        exp = kernel_ops.conflict_matrix_delta_sharded(
            mfoot, mwrite, table, live, layout)
        np.testing.assert_array_equal(np.asarray(cfoot), np.asarray(mfoot))
        np.testing.assert_array_equal(np.asarray(cwrite),
                                      np.asarray(mwrite))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))


# -------------------------------------------------- write-back primitives
class TestShardedWriteBack:
    @pytest.mark.parametrize("shards", [2, 3, 8])
    def test_fused_write_back_matches_dense(self, shards):
        k, length, n_obj, slot = 16, 5, 37, 2
        rng = np.random.default_rng(shards)
        waddrs = jnp.asarray(rng.integers(0, n_obj, (k, length)), jnp.int32)
        wvals = jnp.asarray(rng.integers(0, 99, (k, length, slot)),
                            jnp.int32)
        wn = jnp.asarray(rng.integers(0, length + 1, (k,)), jnp.int32)
        committing = jnp.asarray(rng.random(k) < 0.6)
        rank = jnp.asarray(rng.permutation(k), jnp.int32)
        seq_nos = rank + 5
        dense = make_store(n_obj, slot=slot)
        sh = shard_store(dense, shards)
        dv, dver = protocol.fused_write_back(
            dense.values, dense.versions, waddrs, wvals, wn, committing,
            rank, seq_nos)
        sv, sver = protocol.fused_write_back(
            sh.values, sh.versions, waddrs, wvals, wn, committing, rank,
            seq_nos, sh.layout)
        c = sh.shard_size
        np.testing.assert_array_equal(
            np.asarray(dv),
            np.asarray(sv.reshape(-1, slot)[:n_obj]))
        np.testing.assert_array_equal(
            np.asarray(dver), np.asarray(sver.reshape(-1)[:n_obj]))
        # padding rows stay untouched
        assert not np.asarray(sver.reshape(-1)[n_obj:]).any()
        assert c * shards >= n_obj

    @pytest.mark.parametrize("shards", [2, 8])
    def test_apply_writes_matches_dense(self, shards):
        length, n_obj = 6, 21
        rng = np.random.default_rng(41 + shards)
        for trial in range(5):
            waddrs = jnp.asarray(rng.integers(0, n_obj, (length,)),
                                 jnp.int32)
            wvals = jnp.asarray(rng.integers(0, 99, (length, 1)), jnp.int32)
            wn = jnp.asarray(rng.integers(0, length + 1), jnp.int32)
            dense = make_store(n_obj)
            sh = shard_store(dense, shards)
            dv, dver = protocol.apply_writes(
                dense.values, dense.versions, waddrs, wvals, wn, 7)
            sv, sver = protocol.apply_writes(
                sh.values, sh.versions, waddrs, wvals, wn, 7, sh.layout)
            np.testing.assert_array_equal(
                np.asarray(dv), np.asarray(sv.reshape(-1, 1)[:n_obj]))
            np.testing.assert_array_equal(
                np.asarray(dver), np.asarray(sver.reshape(-1)[:n_obj]))


# ------------------------------------------------------- engine equality
@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("contention", ["low", "med"])
@pytest.mark.parametrize("k", [1, 2, 64])
@pytest.mark.parametrize("shards", [2, 8])
def test_engine_sharded_equals_dense(engine, contention, k, shards):
    wl = _wl(k, contention, seed=13 * k + shards)
    dense = make_store(wl.n_objects)
    sh = shard_store(dense, shards)
    out_d, tr_d = _run(engine, dense, wl)
    out_s, tr_s = _run(engine, sh, wl)
    assert isinstance(out_s, ShardedStore)
    _assert_stores_equal(out_d, out_s, f"{engine} K={k} {contention} "
                                       f"S={shards}")
    _assert_traces_equal(tr_d, tr_s, f"{engine} K={k} {contention} "
                                     f"S={shards}")


@pytest.mark.parametrize("engine", ENGINES)
def test_engine_sharded_masked_path(engine):
    # compact=False: the masked (non-ladder) loop must also be sharded-
    # invariant, and rebuild (incremental=False) too
    wl = _wl(32, "med", seed=2)
    dense = make_store(wl.n_objects)
    sh = shard_store(dense, 4)
    for kw in (dict(compact=False), dict(incremental=False)):
        out_d, tr_d = _run(engine, dense, wl, **kw)
        out_s, tr_s = _run(engine, sh, wl, **kw)
        _assert_stores_equal(out_d, out_s, f"{engine} {kw}")
        _assert_traces_equal(tr_d, tr_s, f"{engine} {kw}")


def test_pogl_sharded_equals_dense():
    wl = _wl(16, "med", seed=21)
    seq = _seq_for(wl)
    lanes = jnp.asarray(wl.lanes, jnp.int32)
    dense = make_store(wl.n_objects)
    for shards in (2, 8):
        out_d, tr_d = _pogl_raw(dense, wl.batch, seq, lanes, wl.n_lanes)
        out_s, tr_s = _pogl_raw(shard_store(dense, shards), wl.batch, seq,
                                lanes, wl.n_lanes)
        _assert_stores_equal(out_d, out_s, f"pogl S={shards}")
        _assert_traces_equal(tr_d, tr_s, f"pogl S={shards}")


@settings(max_examples=15, deadline=None)
@given(k=st.integers(1, 24), shards=st.sampled_from([2, 3, 5, 8]),
       skew=st.sampled_from([0.0, 1.0]), seed=st.integers(0, 99))
def test_pcc_sharded_equals_dense_property(k, shards, skew, seed):
    wl = W.counters(n_txns=k, n_objects=max(8, 2 * k), n_reads=2,
                    n_writes=2, n_lanes=min(4, k), skew=skew, seed=seed)
    dense = make_store(wl.n_objects)
    out_d, tr_d = _run("pcc", dense, wl)
    out_s, tr_s = _run("pcc", shard_store(dense, shards), wl)
    _assert_stores_equal(out_d, out_s)
    _assert_traces_equal(tr_d, tr_s)


# --------------------------------------------------------------- session
@pytest.mark.parametrize("engine", ENGINES)
def test_session_sharded_stream_bitwise(engine):
    rng = np.random.default_rng(17)
    batches, lanes = [], []
    for i in range(8):
        kk = int(rng.integers(1, 33))
        wl = W.counters(n_txns=kk, n_objects=101, n_reads=2, n_writes=2,
                        n_lanes=min(4, kk), skew=0.8, seed=200 + i)
        batches.append(wl.batch)
        lanes.append(wl.lanes.tolist())
    ref = PotSession(101, engine=engine, n_lanes=4)
    ref.run_stream(batches, lanes)
    for shards in (2, 8):
        s = PotSession(101, engine=engine, n_lanes=4, shards=shards)
        s.run_stream(batches, lanes)
        assert s.fingerprint() == ref.fingerprint(), (engine, shards)
        assert s.replay_log() == ref.replay_log(), (engine, shards)
        assert s.gv == ref.gv


def test_session_sharded_replay_round_trip():
    wl = W.counters(n_txns=24, n_objects=64, n_lanes=4, skew=0.9, seed=31)
    rec = PotSession(64, engine="occ", n_lanes=4, shards=4)
    rec.submit(wl.batch, wl.lanes.tolist())
    replay = PotSession(64, engine="occ", n_lanes=4, shards=4,
                        sequencer=rec.replay_sequencer())
    replay.submit(wl.batch, wl.lanes.tolist())
    assert replay.fingerprint() == rec.fingerprint()
    assert replay.replay_log() == rec.replay_log()


def test_session_dense_bucket_ladder():
    """The bucket_ladder='dense' satellite: {1,2,4,8} + multiples of 8
    below/instead of pow2 rungs — same outcome, tighter padding, compile
    count still bounded by the ladder."""
    from repro.core.session import dense_bucket
    assert [dense_bucket(k) for k in (1, 2, 3, 5, 8, 9, 16, 17, 24, 30)] \
        == [1, 2, 4, 8, 8, 16, 16, 24, 24, 32]
    rng = np.random.default_rng(23)
    batches, lanes = [], []
    for i in range(16):
        kk = int(rng.integers(1, 33))
        wl = W.counters(n_txns=kk, n_objects=64, n_reads=2, n_writes=2,
                        n_lanes=min(4, kk), skew=0.5, seed=300 + i)
        batches.append(wl.batch)
        lanes.append(wl.lanes.tolist())
    pow2 = PotSession(64, engine="pcc", n_lanes=4)
    pow2.run_stream(batches, lanes)
    dense = PotSession(64, engine="pcc", n_lanes=4, bucket_ladder="dense")
    dense.run_stream(batches, lanes)
    assert dense.fingerprint() == pow2.fingerprint()
    assert dense.replay_log() == pow2.replay_log()
    # every dense bucket K is on the ladder; padding never exceeds 7 rows
    # above 8 (vs up to K-1 for pow2), and the compile count stays within
    # the K<=32 dense ladder {1,2,4,8,16,24,32} x L rungs
    for (bk, _bl), _ in dense.bucket_counts().items():
        assert bk in (1, 2, 4, 8) or bk % 8 == 0, bk
    assert dense.compile_count() <= 7
    # the dense ladder walks no more padded rows than the pow2 one
    pad_dense = sum((bk - b.n_txns) for b, (bk, _) in
                    zip(batches, map(dense._bucket_shape, batches)))
    pad_pow2 = sum((bk - b.n_txns) for b, (bk, _) in
                   zip(batches, map(pow2._bucket_shape, batches)))
    assert pad_dense <= pad_pow2


# ------------------------------------------------------- shard_map mesh
MESH_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from repro.core import (PotSession, RoundRobinSequencer, fingerprint,
                        make_store, pcc_execute, shard_store)
from repro.core import workloads as W
from repro.launch.mesh import make_host_mesh

wl = W.counters(n_txns=24, n_objects=80, n_reads=2, n_writes=2,
                n_lanes=4, skew=0.9, seed=6)
seq = jnp.asarray(RoundRobinSequencer(n_root_lanes=4)
                  .order_for(wl.lanes.tolist()), jnp.int32)
dense = make_store(wl.n_objects)
out_d, tr_d = pcc_execute(dense, wl.batch, seq)
for s in (1, 2, 8):
    # s=1 with a mesh: a single-shard ShardedStore must still route
    # through the shard_map path (regression: generic shards=len(devices))
    mesh = make_host_mesh(s, "shard")
    out_s, tr_s = pcc_execute(shard_store(dense, s, mesh=mesh),
                              wl.batch, seq)
    assert int(fingerprint(out_s)) == int(fingerprint(out_d)), s
    assert np.array_equal(np.asarray(tr_s.commit_pos),
                          np.asarray(tr_d.commit_pos)), s
# session-level: mesh store threads through the cached jitted step
sess = PotSession(80, engine="pcc", n_lanes=4, shards=8,
                  mesh=make_host_mesh(8, "shard"))
sess.submit(wl.batch, wl.lanes.tolist())
ref = PotSession(80, engine="pcc", n_lanes=4)
ref.submit(wl.batch, wl.lanes.tolist())
assert sess.fingerprint() == ref.fingerprint()
assert sess.replay_log() == ref.replay_log()
print("MESH_OK")
"""


def test_shard_map_mesh_equals_dense():
    """The per-shard write-back under jax.shard_map on a REAL 8-device
    host-platform mesh reproduces the dense store bitwise (subprocess,
    as in test_moe_shardmap; the CI twin is scripts/ci.sh
    --shard-smoke)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", MESH_CODE],
                       capture_output=True, text=True, cwd=repo,
                       env={**os.environ, "PYTHONPATH": "src"})
    assert "MESH_OK" in r.stdout, (r.stdout[-1000:], r.stderr[-3000:])


TWINS_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec
from repro.core import StoreLayout, run_all
from repro.core import workloads as W
from repro.core.txn import gather_live_indices
from repro.kernels import ops
from repro.launch.mesh import make_host_mesh

n, k = 90, 24
mesh = make_host_mesh(4, "shard")
layout = StoreLayout(n, 4, mesh)
wl = W.counters(n_txns=k, n_objects=n, n_reads=2, n_writes=2, n_lanes=4,
                skew=0.5, seed=2)
rng = np.random.default_rng(0)
res = run_all(wl.batch, jnp.asarray(rng.integers(0, n, (n, 1)), jnp.int32))
dense = np.asarray(ops._conflict_matrix_dense(res.raddrs, res.rn,
                                              res.waddrs, res.wn, n))
foot, write = ops.packed_footprints_sharded(res.raddrs, res.rn, res.waddrs,
                                            res.wn, StoreLayout(n, 4))
# each shard's words on its own device, as the sharded store holds them
on_mesh = NamedSharding(mesh, PartitionSpec("shard"))
foot, write = jax.device_put((foot, write), on_mesh)
full = ops.conflict_matrix_sharded(foot, write, mesh)
assert (np.asarray(full) == dense).all()
stale = jnp.zeros((k, k), bool)
live = jnp.asarray(rng.random(k) < 0.4)
delta = jax.jit(ops.conflict_matrix_delta_sharded, static_argnums=4)(
    foot, write, stale, live, layout)
lv = np.asarray(live)
assert (np.asarray(delta) == (dense & (lv[:, None] | lv[None, :]))).all()
idx, valid = gather_live_indices(live, 16)
compact = jax.jit(ops.conflict_matrix_delta_compact_sharded,
                  static_argnums=5)(foot, write, stale, idx, valid, layout)
assert (np.asarray(compact) == np.asarray(delta)).all()
versions = jnp.asarray(rng.integers(0, 100, (n,)), jnp.int32)
snap = jnp.asarray(60, jnp.int32)
stacked = jax.device_put(jnp.pad(versions, (0, layout.padded_objects - n))
                         .reshape(4, layout.shard_size), on_mesh)
got = ops.spec_read_invalid_sharded(res.raddrs, res.rn, stacked, snap, layout)
want = ops.spec_read_invalid(res.raddrs, res.rn, versions, snap, n)
assert (np.asarray(got) == np.asarray(want)).all()
print("TWINS_OK")
"""


def test_shard_map_conflict_twins_equal_dense():
    """The per-shard conflict twins under jax.shard_map on a 4-device
    host-platform mesh — one shard's words per device, verdicts
    OR-reduced across the mesh axis — equal the dense verdicts: full
    table, masked delta, compact strips and the cross-batch validation
    strip."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", TWINS_CODE],
                       capture_output=True, text=True, cwd=repo,
                       env={**os.environ, "PYTHONPATH": "src"})
    assert "TWINS_OK" in r.stdout, (r.stdout[-1000:], r.stderr[-3000:])
