"""Cross-batch speculative pipelining == the serial stream, bit for bit
(PR 7), plus the OCC blocked wave solve.

The pipelining invariant: with ranks globally consecutive across batches
and version stamps globally monotone (gv0 + commit position + 1),
``versions > snap_gv`` is the exact post-snapshot dirty predicate, and a
speculated row whose logged read set misses every dirty address replays
bit-identically (row purity + induction along its read chain).  So a
``PotSession(pipeline_depth=D)`` stream must equal the serial ``D=0``
run on store fingerprints, full ExecTraces (every pre-existing field)
and ``replay_log()`` — for any engine, bucket ladder, shard count and
ingress budget schedule; the speculation cost may only surface in the
new ``spec_*`` observables.  Layers under test:

* the validation strip kernels (``kernels.ops.spec_dirty_words`` /
  ``spec_read_invalid`` and their sharded OR-over-shards twins) against
  a dense NumPy oracle;
* ``protocol.seed_round_state``: a seeded engine call equals the
  unseeded call on stores the speculation snapshot is stale against;
* pipelined sessions over ragged bucketed streams, all four engines
  seeded (pcc / occ since PR 7, destm / pogl since PR 10), D in {1, 2},
  shards in {1, 8}, both bucket ladders, ingress ``serve``;
* ``protocol.wave_commit(block=B)``: decision-identical to B=1 with
  fewer `while_loop` trips on a deep neighbor conflict chain.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import example, given, settings, st

from repro.core import (READ, WRITE, IngressPool, PotSession,
                        RoundRobinSequencer, fingerprint, make_batch,
                        make_store)
from repro.core import protocol
from repro.core import workloads as W
from repro.core.engine import ExecTrace
from repro.core.occ import _occ_execute
from repro.core.pcc import _pcc_execute
from repro.core.txn import run_all
from repro.kernels import ops as kernel_ops

ENGINES = ("pcc", "occ", "pogl", "destm")
N_OBJ = 96


def _wl(k, skew, seed):
    return W.counters(n_txns=k, n_objects=N_OBJ, n_reads=3, n_writes=3,
                      n_lanes=8, skew=skew, seed=seed)


def _stream(n_batches=5, skew=0.8, seed=0):
    """A ragged stream: several distinct (K, L) shapes, shared hot set."""
    ks = (13, 16, 7, 32, 9, 24)
    wls = [_wl(ks[i % len(ks)], skew, seed + 100 + i)
           for i in range(n_batches)]
    return [w.batch for w in wls], [w.lanes for w in wls]


def _assert_traces_match(serial, pipelined, msg=""):
    """Every pre-existing trace field bitwise equal; serial spec_* zero."""
    assert len(serial) == len(pipelined), msg
    for i, (a, b) in enumerate(zip(serial, pipelined)):
        for f in dataclasses.fields(ExecTrace):
            x, y = np.asarray(getattr(a, f.name)), \
                np.asarray(getattr(b, f.name))
            if f.name.startswith("spec_"):
                assert x.sum() == 0, f"serial {f.name} nonzero {msg}"
                continue
            np.testing.assert_array_equal(
                x, y, err_msg=f"batch {i} field {f.name} diverged {msg}")


def _run_sessions(engine, depth, shards, ladder="pow2", n_batches=5,
                  skew=0.8, seed=0):
    batches, lanes = _stream(n_batches, skew, seed)
    kw = dict(engine=engine, n_lanes=8, shards=shards,
              bucket_ladder=ladder)
    s0 = PotSession(N_OBJ, **kw)
    t0 = s0.run_stream(batches, lanes)
    s1 = PotSession(N_OBJ, pipeline_depth=depth, **kw)
    t1 = s1.run_stream(batches, lanes)
    return s0, t0, s1, t1


# ------------------------------------------------- validation strip kernels
class TestValidationStrip:
    def _case(self, seed, k=24, skew=1.0):
        rng = np.random.default_rng(seed)
        wl = _wl(k, skew, seed)
        values = jnp.asarray(
            rng.integers(0, 50, size=(N_OBJ, 1)), jnp.int32)
        res = run_all(wl.batch, values)
        # a random post-snapshot version image: snap_gv 5, some stamps
        # above it (dirty), some at/below (clean)
        versions = jnp.asarray(rng.integers(0, 12, size=(N_OBJ,)),
                               jnp.int32)
        return res, versions, jnp.asarray(5, jnp.int32)

    def _oracle(self, res, versions, snap_gv):
        raddrs, rn = np.asarray(res.raddrs), np.asarray(res.rn)
        dirty = np.asarray(versions) > int(snap_gv)
        k, length = raddrs.shape
        out = np.zeros((k,), bool)
        for t in range(k):
            out[t] = bool(dirty[raddrs[t, :rn[t]]].any())
        return out

    def test_dirty_words_pack_convention(self):
        versions = jnp.zeros((70,), jnp.int32).at[jnp.asarray([0, 33, 69])
                                                  ].set(9)
        words = np.asarray(kernel_ops.spec_dirty_words(
            versions, jnp.asarray(0, jnp.int32), 70))
        assert words.shape == (3,)   # ceil(70/32)
        assert words[0] == 1                     # bit 0 of word 0
        assert words[1] == (1 << 1)              # addr 33 -> word 1 bit 1
        assert np.uint32(words[2]) == np.uint32(1) << 5   # addr 69

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_dense_matches_numpy_oracle(self, seed):
        res, versions, snap_gv = self._case(seed)
        got = np.asarray(kernel_ops.spec_read_invalid(
            res.raddrs, res.rn, versions, snap_gv, N_OBJ))
        np.testing.assert_array_equal(got,
                                      self._oracle(res, versions, snap_gv))

    @pytest.mark.parametrize("shards", [2, 8])
    def test_sharded_matches_dense(self, shards):
        from repro.core import StoreLayout
        res, versions, snap_gv = self._case(3)
        layout = StoreLayout(N_OBJ, shards)
        # stack the dense versions into the sharded (S, C) image
        pad = layout.padded_objects - N_OBJ
        vs = jnp.pad(versions, (0, pad)).reshape(layout.shards,
                                                 layout.shard_size)
        got = np.asarray(kernel_ops.spec_read_invalid_sharded(
            res.raddrs, res.rn, vs, snap_gv, layout))
        np.testing.assert_array_equal(got,
                                      self._oracle(res, versions, snap_gv))

    def test_everything_clean_when_no_dirty_writes(self):
        res, versions, _ = self._case(4)
        snap = jnp.asarray(int(np.asarray(versions).max()), jnp.int32)
        got = np.asarray(kernel_ops.spec_read_invalid(
            res.raddrs, res.rn, versions, snap, N_OBJ))
        assert not got.any()


# ------------------------------------------------------ seeded engine calls
class TestSeededEngines:
    @pytest.mark.parametrize("engine", ["pcc", "occ"])
    @pytest.mark.parametrize("shards", [1, 4])
    def test_seeded_equals_unseeded(self, engine, shards):
        fn = _pcc_execute if engine == "pcc" else _occ_execute
        wl1, wl2 = _wl(16, 1.0, 1), _wl(16, 1.0, 2)
        seq = jnp.arange(1, 17, dtype=jnp.int32)
        arg = seq if engine == "pcc" else jnp.argsort(seq)
        store0 = make_store(N_OBJ, shards=shards)
        s1, _ = fn(store0, wl1.batch, arg)
        s2, t2 = fn(s1, wl2.batch, arg)
        # speculate batch 2 against the PRE-batch-1 snapshot (stale)
        seed = protocol.spec_execute(store0, wl2.batch)
        s2b, t2b = fn(s1, wl2.batch, arg, seed=seed)
        np.testing.assert_array_equal(
            np.asarray(s2.values).reshape(-1),
            np.asarray(s2b.values).reshape(-1))
        np.testing.assert_array_equal(
            np.asarray(s2.versions).reshape(-1),
            np.asarray(s2b.versions).reshape(-1))
        assert int(s2.gv) == int(s2b.gv)
        _assert_traces_match([t2], [t2b], f"{engine} S={shards}")
        assert int(t2b.spec_executed) == 16
        assert int(t2b.spec_rounds) == (int(t2b.spec_invalidated) > 0)

    @pytest.mark.parametrize("engine", ["destm", "pogl"])
    def test_seeded_equals_unseeded_lane_engines(self, engine):
        # destm / pogl go through the registry's uniform raw signature
        # (they need lanes); same stale-seed setup as above
        from repro.core.engine import get_engine
        eng = get_engine(engine)
        wl1, wl2 = _wl(16, 1.0, 1), _wl(16, 1.0, 2)
        seq = jnp.arange(1, 17, dtype=jnp.int32)
        lanes = jnp.asarray(wl2.lanes, jnp.int32)
        store0 = make_store(N_OBJ)
        s1, _ = eng.raw(store0, wl1.batch,
                        seq, jnp.asarray(wl1.lanes, jnp.int32), 8)
        s2, t2 = eng.raw(s1, wl2.batch, seq, lanes, 8)
        seed = protocol.spec_execute(store0, wl2.batch)  # stale snapshot
        s2b, t2b = eng.raw_spec(s1, wl2.batch, seq, lanes, 8, seed)
        np.testing.assert_array_equal(np.asarray(s2.values),
                                      np.asarray(s2b.values))
        np.testing.assert_array_equal(np.asarray(s2.versions),
                                      np.asarray(s2b.versions))
        assert int(s2.gv) == int(s2b.gv)
        _assert_traces_match([t2], [t2b], engine)
        assert int(t2b.spec_executed) == 16

    def test_fresh_seed_invalidates_nothing(self):
        wl = _wl(16, 0.5, 7)
        seq = jnp.arange(1, 17, dtype=jnp.int32)
        store = make_store(N_OBJ)
        seed = protocol.spec_execute(store, wl.batch)  # current snapshot
        _, trace = _pcc_execute(store, wl.batch, seq, seed=seed)
        assert int(trace.spec_invalidated) == 0
        assert int(trace.spec_rounds) == 0
        assert int(trace.spec_executed) == 16


# ------------------------------------------------------- pipelined sessions
class TestPipelinedSession:
    @pytest.mark.parametrize("engine", ENGINES)
    @pytest.mark.parametrize("depth", [1, 2])
    def test_stream_equals_serial(self, engine, depth):
        s0, t0, s1, t1 = _run_sessions(engine, depth, shards=1)
        assert s0.fingerprint() == s1.fingerprint()
        assert s0.replay_log() == s1.replay_log()
        assert int(s0.store.gv) == int(s1.store.gv)
        _assert_traces_match(t0, t1, f"{engine} D={depth}")
        # all four engines are seeded and must record the overlap
        assert sum(int(t.spec_executed) for t in t1) > 0

    @pytest.mark.parametrize("shards", [8])
    @pytest.mark.parametrize("engine", ["pcc", "occ"])
    def test_sharded_stream_equals_serial(self, engine, shards):
        s0, t0, s1, t1 = _run_sessions(engine, 2, shards=shards)
        assert s0.fingerprint() == s1.fingerprint()
        assert s0.replay_log() == s1.replay_log()
        _assert_traces_match(t0, t1, f"{engine} S={shards}")

    def test_dense_ladder_stream_equals_serial(self):
        s0, t0, s1, t1 = _run_sessions("pcc", 2, shards=1, ladder="dense")
        assert s0.fingerprint() == s1.fingerprint()
        assert s0.replay_log() == s1.replay_log()
        _assert_traces_match(t0, t1, "dense ladder")

    def test_low_contention_speculation_survives(self):
        # disjoint-ish batches: most speculated rows must stay valid
        batches, lanes = _stream(4, skew=0.0, seed=50)
        s = PotSession(4096, engine="pcc", n_lanes=8, pipeline_depth=1)
        s0 = PotSession(4096, engine="pcc", n_lanes=8)
        wls = [W.counters(n_txns=16, n_objects=4096, n_reads=2,
                          n_writes=2, n_lanes=8, skew=0.0, seed=i)
               for i in range(4)]
        t1 = s.run_stream([w.batch for w in wls], [w.lanes for w in wls])
        t0 = s0.run_stream([w.batch for w in wls], [w.lanes for w in wls])
        assert s.fingerprint() == s0.fingerprint()
        executed = sum(int(t.spec_executed) for t in t1)
        invalidated = sum(int(t.spec_invalidated) for t in t1)
        assert executed > 0 and invalidated < executed

    def test_depth_zero_is_serial_path(self):
        s = PotSession(N_OBJ, engine="pcc", pipeline_depth=0)
        assert not s._pipelined and s._spec_step is None

    def test_negative_depth_rejected(self):
        with pytest.raises(ValueError):
            PotSession(N_OBJ, pipeline_depth=-1)

    def test_submit_flushes_pending_window(self):
        # interleave run_stream and submit: submit must see the window
        # fully drained (run_stream flushes; the submit-side flush is a
        # guard) and the combined history must equal the serial one
        batches, lanes = _stream(3, seed=9)
        extra = _wl(11, 0.8, 999)
        s1 = PotSession(N_OBJ, engine="pcc", n_lanes=8, pipeline_depth=2)
        s1.run_stream(batches, lanes)
        s1.submit(extra.batch, extra.lanes)
        s0 = PotSession(N_OBJ, engine="pcc", n_lanes=8)
        s0.run_stream(batches, lanes)
        s0.submit(extra.batch, extra.lanes)
        assert s1.fingerprint() == s0.fingerprint()
        assert s1.replay_log() == s0.replay_log()

    def test_replay_round_trip(self):
        batches, lanes = _stream(4, seed=3)
        s1 = PotSession(N_OBJ, engine="pcc", n_lanes=8, pipeline_depth=2)
        s1.run_stream(batches, lanes)
        replay = PotSession(N_OBJ, engine="pcc", n_lanes=8,
                            sequencer=s1.replay_sequencer())
        replay.run_stream(batches)
        assert replay.fingerprint() == s1.fingerprint()


# ----------------------------------------------------------- ingress serve
class TestPipelinedServe:
    def _fill(self, pool, n=60, seed=11):
        rng = np.random.default_rng(seed)
        for i in range(n):
            prog = ((READ, int(rng.integers(0, N_OBJ)), False, 0),
                    (WRITE, int(rng.integers(0, N_OBJ)), False, i + 1))
            pool.admit(prog, lane=int(rng.integers(0, 6)),
                       fee=int(rng.integers(0, 5)))

    @pytest.mark.parametrize("budgets", [(16,), (5, 9, 3, 31)])
    def test_serve_equals_serial_across_budgets(self, budgets):
        pool0, pool1 = IngressPool(), IngressPool()
        self._fill(pool0)
        self._fill(pool1)
        s0 = PotSession(N_OBJ, engine="pcc", n_lanes=8)
        s1 = PotSession(N_OBJ, engine="pcc", n_lanes=8, pipeline_depth=2)
        for b in budgets:
            s0.serve(pool0, budget=b)
            s1.serve(pool1, budget=b)
        assert s0.fingerprint() == s1.fingerprint()
        assert s0.replay_log() == s1.replay_log()


# ------------------------------------------------------- blocked wave solve
class TestBlockedWaveCommit:
    def _chain(self, k=48):
        """Neighbor conflict chain: txn i reads i-1's write target — the
        wave fixpoint resolves one conflict layer per query, so its
        depth is O(chain length) at block=1."""
        progs = [[(READ, (i - 1) % N_OBJ, False, 0), (WRITE, i, False, 1)]
                 for i in range(k)]
        return make_batch(progs)

    @pytest.mark.parametrize("block", [2, 8])
    def test_decisions_identical_any_block(self, block):
        batch = self._chain()
        store = make_store(N_OBJ)
        res = run_all(batch, store.values)
        rank = jnp.arange(batch.n_txns, dtype=jnp.int32)
        pending = jnp.ones((batch.n_txns,), bool)
        conflict = protocol.conflict_table(res, N_OBJ, use_matrix=True)
        c1, t1 = protocol.wave_commit(res, conflict, pending, rank, N_OBJ)
        cb, tb = protocol.wave_commit(res, conflict, pending, rank, N_OBJ,
                                      block=block)
        np.testing.assert_array_equal(np.asarray(c1), np.asarray(cb))
        assert int(tb) < int(t1)   # deep chain: trips cut by ~block

    def test_occ_engine_blocked_equals_unblocked(self):
        batch = self._chain()
        store = make_store(N_OBJ)
        arrival = jnp.arange(batch.n_txns, dtype=jnp.int32)
        s1, t1 = _occ_execute(store, batch, arrival, wave_block=1)
        s8, t8 = _occ_execute(store, batch, arrival, wave_block=8)
        np.testing.assert_array_equal(np.asarray(s1.values),
                                      np.asarray(s8.values))
        np.testing.assert_array_equal(np.asarray(s1.versions),
                                      np.asarray(s8.versions))
        for f in ("commit_pos", "retries", "rounds", "commit_round"):
            np.testing.assert_array_equal(np.asarray(getattr(t1, f)),
                                          np.asarray(getattr(t8, f)),
                                          err_msg=f)
        assert int(t8.wave_trips) < int(t1.wave_trips)

    def test_disjoint_wave_single_trip_any_block(self):
        # disjoint txns: fixpoint converges on the first check at any B
        progs = [[(WRITE, i, False, 1)] for i in range(8)]
        batch = make_batch(progs)
        store = make_store(N_OBJ)
        res = run_all(batch, store.values)
        rank = jnp.arange(8, dtype=jnp.int32)
        pending = jnp.ones((8,), bool)
        for block in (1, 8):
            c, trips = protocol.wave_commit(res, None, pending, rank,
                                            N_OBJ, block=block)
            assert np.asarray(c).all()
            assert int(trips) == 1


# ------------------------------------------------------- hypothesis property
@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([1, 2]),
       st.sampled_from(["pcc", "occ", "destm", "pogl"]),
       st.floats(0.0, 1.5))
# a skew too small to lift the Zipf exponent off 1.0 must draw uniformly
@example(seed=0, depth=1, engine="pcc", skew=5e-324)
# ... and one just above it must not stall numpy's Zipf sampler
@example(seed=0, depth=1, engine="pcc", skew=1e-10)
def test_pipelined_equals_serial_property(seed, depth, engine, skew):
    s0, t0, s1, t1 = _run_sessions(engine, depth, shards=1,
                                   n_batches=4, skew=skew, seed=seed)
    assert s0.fingerprint() == s1.fingerprint()
    assert s0.replay_log() == s1.replay_log()
    _assert_traces_match(t0, t1, f"{engine} D={depth} seed={seed}")
