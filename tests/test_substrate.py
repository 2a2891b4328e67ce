"""Substrate tests: checkpoint/restart, data pipeline, ordered reduction,
elastic scaling, straggler invariance, serving determinism."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import checkpoint as ck
from repro.configs import get_smoke_config
from repro.data.pipeline import DataConfig, batch_at
from repro.models import lm
from repro.optim import ordered_ring_reduce, ordered_tree_sum
from repro.runtime.elastic import ElasticLaneManager, ScalingEvent
from repro.runtime.straggler import commit_deadline_policy, simulate_arrivals
from repro.runtime.shardings import SMOKE
from repro.serve.session import Session
from repro.train import make_train_step
from repro.train.train_step import init_state


# ------------------------------------------------------------ checkpoint
class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        cfg = get_smoke_config("stablelm_12b")
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        state = init_state(params)
        ck.save(str(tmp_path), 7, state, extra={"data_step": 7})
        assert ck.latest_step(str(tmp_path)) == 7
        restored, extra = ck.restore(str(tmp_path), 7, state)
        assert extra == {"data_step": 7}
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_atomic_no_partial(self, tmp_path):
        state = {"w": jnp.ones((4, 4))}
        ck.save(str(tmp_path), 1, state)
        # a .tmp dir from a crashed save must not count as a checkpoint
        os.makedirs(os.path.join(str(tmp_path), "step_2.tmp_0"))
        assert ck.latest_step(str(tmp_path)) == 1

    def test_prune(self, tmp_path):
        state = {"w": jnp.ones((2,))}
        for s in (1, 2, 3, 4, 5):
            ck.save(str(tmp_path), s, state)
        ck.prune(str(tmp_path), keep=2)
        assert ck.latest_step(str(tmp_path)) == 5
        assert sorted(os.listdir(str(tmp_path))) == ["step_4", "step_5"]

    def test_restart_reproduces_run_bitwise(self, tmp_path):
        """Train 4 steps straight vs train 2 + checkpoint + restore +
        train 2: identical parameters (deterministic restart)."""
        cfg = get_smoke_config("stablelm_12b")
        params = lm.init_params(jax.random.PRNGKey(1), cfg)
        dcfg = DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=4)
        step = jax.jit(make_train_step(cfg, SMOKE, mode="pot",
                                       remat=False))

        s_a = init_state(params)
        for i in range(4):
            s_a, _ = step(s_a, batch_at(dcfg, i))

        s_b = init_state(params)
        for i in range(2):
            s_b, _ = step(s_b, batch_at(dcfg, i))
        ck.save(str(tmp_path), 2, s_b, extra={"data_step": 2})
        s_c, extra = ck.restore(str(tmp_path), 2, s_b)
        for i in range(extra["data_step"], 4):
            s_c, _ = step(s_c, batch_at(dcfg, i))

        for a, b in zip(jax.tree.leaves(s_a.params),
                        jax.tree.leaves(s_c.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------------ data
class TestData:
    def test_deterministic_per_step(self):
        cfg = DataConfig(vocab=1000, seq_len=32, global_batch=8)
        a = batch_at(cfg, 5)
        b = batch_at(cfg, 5)
        np.testing.assert_array_equal(np.asarray(a["tokens"]),
                                      np.asarray(b["tokens"]))

    def test_steps_differ(self):
        cfg = DataConfig(vocab=1000, seq_len=32, global_batch=8)
        a = batch_at(cfg, 1)["tokens"]
        b = batch_at(cfg, 2)["tokens"]
        assert not np.array_equal(np.asarray(a), np.asarray(b))

    def test_host_sharding_disjoint_and_deterministic(self):
        base = DataConfig(vocab=500, seq_len=16, global_batch=8, n_hosts=2)
        h0 = batch_at(base, 3)
        h1 = batch_at(DataConfig(vocab=500, seq_len=16, global_batch=8,
                                 n_hosts=2, host_id=1), 3)
        assert h0["tokens"].shape == (4, 16)
        assert not np.array_equal(np.asarray(h0["tokens"]),
                                  np.asarray(h1["tokens"]))

    def test_labels_are_shifted_tokens(self):
        cfg = DataConfig(vocab=100, seq_len=8, global_batch=2)
        b = batch_at(cfg, 0)
        np.testing.assert_array_equal(np.asarray(b["labels"][:, :-1]),
                                      np.asarray(b["tokens"][:, 1:]))
        assert (np.asarray(b["labels"][:, -1]) == -1).all()


# -------------------------------------------------------- ordered reduce
class TestOrderedReduce:
    def test_tree_sum_matches_sum(self):
        x = jnp.asarray(np.random.default_rng(0).normal(size=(7, 13)),
                        jnp.float32)
        got = ordered_tree_sum(x)
        # fixed tree order != jnp's reduction order: agreement is only up
        # to f32 associativity (~2.5e-6 rel on this draw), same bound the
        # ring-reduce test uses.  Bitwise determinism is asserted by
        # test_tree_sum_fixed_order, not here.
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(x.sum(0)), rtol=1e-5)

    def test_tree_sum_fixed_order(self):
        """Same values, same order -> bitwise equal across calls."""
        x = jnp.asarray(np.random.default_rng(1).normal(size=(8, 64)),
                        jnp.float32)
        a = np.asarray(ordered_tree_sum(x))
        b = np.asarray(ordered_tree_sum(x))
        assert a.tobytes() == b.tobytes()

    def test_ring_reduce_multidevice(self):
        """Needs >1 device: spawn a subprocess with 8 host devices to keep
        this process at 1 device (see conftest note in the brief)."""
        import subprocess
        import sys
        code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.optim import ordered_ring_reduce
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(8)
x = jnp.arange(8 * 24, dtype=jnp.float32).reshape(8, 24) / 7.0
f = jax.shard_map(lambda y: ordered_ring_reduce(y[0], "data")[None],
              mesh=mesh, in_specs=P("data", None),
              out_specs=P("data", None), check_vma=False)
got = np.asarray(f(x))
want = np.asarray(x.sum(0))
for i in range(8):
    np.testing.assert_allclose(got[i], want, rtol=1e-5)
print("OK")
"""
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True,
                           env={**os.environ, "PYTHONPATH": "src"},
                           cwd=os.path.dirname(os.path.dirname(
                               os.path.abspath(__file__))))
        assert "OK" in r.stdout, r.stderr[-2000:]


# ----------------------------------------------------- elastic/straggler
class TestRuntime:
    def test_elastic_join_leave_deterministic(self):
        ev = [ScalingEvent(at_round=1, action="join", lane_id=7),
              ScalingEvent(at_round=3, action="leave", lane_id=0)]
        a = ElasticLaneManager(2, [ScalingEvent(**vars(e)) for e in ev])
        b = ElasticLaneManager(2, [ScalingEvent(**vars(e)) for e in ev])
        for mgr in (a, b):
            mgr.advance_to(1)
        assert a.live_lanes() == b.live_lanes()
        a.advance_to(3)
        b.advance_to(3)
        assert a.live_lanes() == b.live_lanes()
        assert 0 not in a.live_lanes() and 7 in a.live_lanes()

    def test_straggler_arrivals_seeded(self):
        a = simulate_arrivals(32, n_stragglers=4, seed=9)
        b = simulate_arrivals(32, n_stragglers=4, seed=9)
        np.testing.assert_array_equal(a, b)

    def test_deadline_policy(self):
        assert commit_deadline_policy(5, 4) == "fast"
        assert commit_deadline_policy(8, 4, max_stale=8) == "validate"
        assert commit_deadline_policy(20, 4, max_stale=8) == "rebase"

    def test_pot_invariant_to_straggler_arrivals(self):
        """The core claim: PCC output does not depend on arrival order."""
        import jax
        from repro.core import (RoundRobinSequencer, fingerprint,
                                make_store, pcc_execute)
        from repro.core import workloads as W
        wl = W.vacation_like(n_txns=24, n_objects=128, n_lanes=4, seed=3)
        store = make_store(wl.n_objects)
        seq = np.asarray(RoundRobinSequencer(
            n_root_lanes=4).order_for(wl.lanes.tolist()))
        fps = set()
        for s in range(4):
            arr = simulate_arrivals(24, n_stragglers=6, seed=s)
            batch_p = jax.tree.map(lambda a: a[arr], wl.batch)
            out, _ = pcc_execute(store, batch_p,
                                 jnp.asarray(seq[arr], jnp.int32))
            fps.add(int(fingerprint(out)))
        assert len(fps) == 1


# ----------------------------------------------------------------- serve
class TestServe:
    def test_session_replicas_identical(self):
        cfg = get_smoke_config("stablelm_12b")
        params = lm.init_params(jax.random.PRNGKey(5), cfg)

        def run_replica():
            s = Session(cfg, params, n_slots=4, max_seq=32)
            for i in range(4):
                s.add_request(i, first_token=i + 1)
            toks = s.generate(6)
            return toks, s.fingerprint()

        t1, f1 = run_replica()
        t2, f2 = run_replica()
        np.testing.assert_array_equal(t1, t2)
        assert f1 == f2

    def test_page_versions_record_commit_order(self):
        cfg = get_smoke_config("stablelm_12b")
        params = lm.init_params(jax.random.PRNGKey(6), cfg)
        s = Session(cfg, params, n_slots=2, max_seq=32)
        s.add_request(0, 3)
        s.add_request(1, 4)
        s.step()
        vers = np.asarray(s.page_versions)
        assert set(vers[vers > 0]) == {1, 2}  # sequence numbers, §3.1
