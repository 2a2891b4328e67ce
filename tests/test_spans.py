"""Profiler spans on the served path.

``IngressPool.drain`` runs in ``pot.drain`` (with ``pot.drain.pack`` and
``pot.drain.upload`` inside), ``PotSession._serve_formed`` in ``pot.step``
(with ``pot.step.pad`` and ``pot.step.record`` inside).  A small pool is
served under ``jax.profiler`` on the CPU; the spans must nest, pair each
formed batch's drain with its step by the ``batch`` stat, count what the
pool and the bucket ladder count, and leave the committed store and the
replay log as they are without the profiler.  The reduction in
``potbench/program_spans.py`` reads them; the CPU trace has no TPU plane,
so its idle numbers are ``None``.
"""

import collections
import os
import sys

import jax
import numpy as np
import pytest

from repro.core import READ, RMW, WRITE, IngressPool, PotSession

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from potbench import program_spans, trace  # noqa: E402

N_OBJECTS = 64
N_TXNS = 45
BUDGET = 12


def _pool() -> IngressPool:
    """45 transactions of 1-4 instructions on 5 client lanes."""
    rng = np.random.default_rng(7)
    pool = IngressPool()
    for i in range(N_TXNS):
        addrs = rng.choice(N_OBJECTS, size=1 + i % 4, replace=False)
        ops = (RMW, READ, WRITE)
        pool.admit([(ops[j % 3], int(a), False, i + 1)
                    for j, a in enumerate(addrs)], lane=i % 5)
    return pool


def _serve(log_dir=None):
    pool, sess = _pool(), PotSession(N_OBJECTS, n_lanes=4)
    if log_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            sess.serve(pool, budget=BUDGET)
            jax.block_until_ready(sess.store)
    finally:
        if log_dir is not None:
            jax.profiler.stop_trace()
    return pool, sess


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    log_dir = tmp_path_factory.mktemp("profile")
    pool, sess = _serve(log_dir)
    xplane = trace.find_xplane(str(log_dir))
    events = [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
              for plane in trace.load(xplane).planes
              if plane.name == trace.HOST_PLANE
              for line in plane.lines for ev in line.events
              if ev.name.startswith(program_spans.PREFIX)]
    return pool, sess, xplane, events


def _named(events, name):
    return [ev for ev in events if ev[0] == name]


def test_spans_nest(served):
    *_, events = served
    for parent, children in (("pot.drain", ("pot.drain.pack",
                                            "pot.drain.upload")),
                             ("pot.step", ("pot.step.pad",
                                           "pot.step.record"))):
        outer = _named(events, parent)
        for child in children:
            inner = _named(events, child)
            assert inner, child
            for _, s, e, _ in inner:
                assert any(s0 <= s and e <= e0 for _, s0, e0, _ in outer), \
                    child


def test_batch_ids_pair_drain_and_step(served):
    pool, _, _, events = served
    drains = _named(events, "pot.drain")
    formed = [st["batch"] for _, _, _, st in drains if st["k"] > 0]
    steps = [st["batch"] for _, _, _, st in _named(events, "pot.step")]
    assert formed == list(range(1, N_TXNS + 1, BUDGET))
    assert collections.Counter(steps) == collections.Counter(formed)
    # serve's last drain finds the pool empty and takes nothing
    assert [st["k"] for *_, st in drains][-1] == 0
    assert len(drains) == pool.stats.drain_calls


def test_counts_match_the_pool_and_the_ladder(served):
    pool, sess, _, events = served
    assert sum(st["k"] for *_, st in _named(events, "pot.drain")) \
        == pool.stats.drained == N_TXNS
    pads = [st for *_, st in _named(events, "pot.step.pad")]
    assert sum(st["k"] for st in pads) == N_TXNS
    padded = sum(bk * n for (bk, _), n in sess.bucket_counts().items())
    assert sum(st["bk"] - st["k"] for st in pads) == padded - N_TXNS > 0


def test_profiler_leaves_the_results_alone(served):
    _, traced, _, _ = served
    _, plain = _serve()
    for field in ("values", "versions"):
        np.testing.assert_array_equal(np.asarray(getattr(plain.store, field)),
                                      np.asarray(getattr(traced.store,
                                                         field)))
    assert int(plain.store.gv) == int(traced.store.gv)
    assert plain.replay_log() == traced.replay_log()


def test_reduction_on_a_cpu_trace(served):
    pool, sess, xplane, events = served
    red = program_spans.reduce(xplane)
    sp = red["spans"]
    assert set(sp) == {"pot.drain", "pot.drain.pack", "pot.drain.upload",
                       "pot.step", "pot.step.pad", "pot.step.record"}
    assert sp["pot.drain"]["count"] == pool.stats.drain_calls
    assert sp["pot.step"]["count"] == sp["pot.step.pad"]["count"] == 4
    assert sp["pot.drain"]["stats"] == {"k": N_TXNS}
    assert "batch" not in sp["pot.step"]["stats"]
    # self time is the parent less its children
    drain = sp["pot.drain"]
    assert drain["self_s"] == pytest.approx(
        drain["total_s"] - sp["pot.drain.pack"]["total_s"]
        - sp["pot.drain.upload"]["total_s"], abs=1e-9)
    assert all(0 < v["self_s"] <= v["total_s"] <= red["window_s"]
               for v in sp.values())
    # no TPU plane: nothing to say about the device's idle time
    assert red["idle_s"] is None and red["idle_outside_s"] is None
    assert red["gaps"] == [] and all(v["idle_s"] is None for v in sp.values())
    nums = program_spans.numbers(red)
    assert nums["session.idle_share"] is None
    assert nums["device.idle_share"] is None
    padded = sum(bk * n for (bk, _), n in sess.bucket_counts().items())
    assert nums["session.pad_rows_share"] == pytest.approx(
        (padded - N_TXNS) / padded)
    assert nums["ingress.drain_us_per_txn"] == pytest.approx(
        nums["ingress.select_us_per_txn"] + nums["ingress.pack_us_per_txn"]
        + nums["ingress.upload_us_per_txn"])


def test_overlaps_of_intervals():
    idle = np.asarray([[0, 2], [5, 6], [8, 12]], np.float64)
    spans = np.asarray([[1, 5.5], [6, 8], [-3, 20], [9, 10], [13, 14]],
                       np.float64)
    np.testing.assert_allclose(program_spans._overlaps(idle, spans),
                               [1.5, 0, 7, 1, 0])
    np.testing.assert_allclose(
        program_spans._idle(np.asarray([[1, 3], [2, 4], [6, 7]], np.float64),
                            0, 10),
        [[0, 1], [4, 6], [7, 10]])
