"""Deterministic replica failover under injected faults (PR 9).

Properties:
  F1  FaultPlan is deterministic: fault points are (formed-batch index,
      phase) positions in the order — validated, replayable, and (in
      "raise" mode) observable in-process.
  F2  Kill-and-restore: a replica killed at ANY fault point — including
      mid-snapshot with a torn tmp dir — restores from its latest
      COMPLETE snapshot plus the shared arrival-journal suffix and
      produces bitwise-identical store fingerprints, ExecTraces
      (speculation observables aside, per the PR 7 invariant) and
      replay_log() to an uninterrupted replica.  Driven both in-process
      ("raise" mode) and as a real subprocess SIGKILL (-9).
  F3  Elastic failover: worker join/leave events are sequenced,
      snapshot-visible state — a replica restored across a scaling
      event numbers lanes identically (destm: lane placement is
      load-bearing).
  F4  The metrics CSV carries the failover observables
      (snapshots_taken / restored_from / recovery_batches).

The acceptance matrix — engines {pcc, occ} x shards {1, 8} x
pipeline_depth {0, 2} x two drain-budget schedules, phases cycling
admit/drain/execute/snapshot(+torn) — is expensive (every config
compiles its own engine steps), so tier-1 runs a fixed subset and
``scripts/ci.sh --failover-smoke`` runs the full matrix via
``REPRO_FAILOVER_FULL=1``.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import (FaultInjected, FaultPlan, IngressPool, PotSession,
                        run_replica, trace_digest)
from repro.core import workloads as W
from repro.core.checkpoint import snapshot_ids
from repro.core.ingress import programs_from_batch

FULL = os.environ.get("REPRO_FAILOVER_FULL") == "1"
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_OBJECTS = 64
N_LANES = 6


def _journal(n_txns=60, seed=3):
    wl = W.counters(n_txns=n_txns, n_objects=N_OBJECTS, n_reads=2,
                    n_writes=2, n_lanes=N_LANES, skew=0.7, seed=seed)
    pool = IngressPool(capacity=512)
    for i, p in enumerate(programs_from_batch(wl.batch)):
        pool.admit(p, lane=i % N_LANES, fee=i % 5)
    return pool.arrival_journal()


JOURNAL = _journal()


def _assert_recovered(rec_fp, rec_log, rec_digests, base):
    assert rec_fp == base.session.fingerprint()
    assert rec_log == base.session.replay_log()
    bd = [trace_digest(t) for t in base.session.traces]
    assert rec_digests == bd[len(bd) - len(rec_digests):]


# ------------------------------------------------------------- F1 plans
def test_fault_plan_validates_its_schedule():
    with pytest.raises(ValueError, match="phase"):
        FaultPlan(kill_batch=1, kill_phase="commit")
    with pytest.raises(ValueError, match="action"):
        FaultPlan(kill_batch=1, action="explode")
    with pytest.raises(ValueError, match="torn"):
        FaultPlan(kill_batch=1, kill_phase="execute", torn=True)


def test_fault_plan_fires_only_at_its_point():
    plan = FaultPlan(kill_batch=2, kill_phase="drain", action="raise")
    plan.fire(0, "drain")
    plan.fire(2, "execute")
    assert not plan.matches(1, "drain") and plan.matches(2, "drain")
    with pytest.raises(FaultInjected, match="batch 2, phase 'drain'"):
        plan.fire(2, "drain")
    # the empty plan never fires
    FaultPlan().fire(0, "drain")


# -------------------------------------------------- F2 kill-and-restore
# (engine, shards, pipeline_depth, budgets, kill_batch, phase, torn)
_SCHED_A, _SCHED_B = (7, 11), (16,)
MATRIX = []
_PHASES = [("drain", False), ("execute", False), ("snapshot", False),
           ("snapshot", True)]
for _i, (_e, _s, _d, _b) in enumerate(
        (e, s, d, b) for e in ("pcc", "occ") for s in (1, 8)
        for d in (0, 2) for b in (_SCHED_A, _SCHED_B)):
    _ph, _torn = _PHASES[_i % len(_PHASES)]
    # snapshot-phase faults must land ON a snapshot point: with
    # snapshot_every=2 those are even formed-batch counts (2, 4, ...)
    # regardless of schedule; drain/execute faults land mid-stream
    # (schedule A forms 7 batches of 60 txns, schedule B forms 4)
    _kill = 4 if (_ph == "snapshot" or _b == _SCHED_A) else 3
    MATRIX.append((_e, _s, _d, _b, _kill, _ph, _torn))

# tier-1 subset: both engines, both layouts, both depths, both
# schedules, a torn and a non-torn phase all appear at least once
TIER1 = {("pcc", 1, 0, _SCHED_A), ("occ", 8, 2, _SCHED_B),
         ("pcc", 8, 2, _SCHED_B), ("occ", 1, 0, _SCHED_A)}


def _full_only(engine, shards, depth, budgets):
    if not FULL and (engine, shards, depth, budgets) not in TIER1:
        pytest.skip("full failover matrix runs under REPRO_FAILOVER_FULL=1 "
                    "(scripts/ci.sh --failover-smoke)")


@pytest.mark.parametrize("engine,shards,depth,budgets,kill,phase,torn",
                         MATRIX)
def test_kill_and_restore_in_process(tmp_path, engine, shards, depth,
                                     budgets, kill, phase, torn):
    """F2 in 'raise' mode: the whole acceptance matrix, in-process."""
    _full_only(engine, shards, depth, budgets)
    kw = dict(n_objects=N_OBJECTS, engine=engine, n_lanes=N_LANES,
              shards=shards, pipeline_depth=depth, budgets=budgets)
    base = run_replica(JOURNAL, directory=str(tmp_path / "base"),
                       snapshot_every=0, **kw)
    vdir = str(tmp_path / "victim")
    plan = FaultPlan(kill_batch=kill, kill_phase=phase, torn=torn,
                     action="raise")
    with pytest.raises(FaultInjected):
        run_replica(JOURNAL, directory=vdir, snapshot_every=2,
                    fault_plan=plan, **kw)
    rec = run_replica(JOURNAL, directory=vdir, snapshot_every=2,
                      resume=True, **kw)
    assert rec.session.restored_from >= 0
    _assert_recovered(rec.session.fingerprint(), rec.session.replay_log(),
                      [trace_digest(t) for t in rec.session.traces], base)


def test_torn_snapshot_leaves_latest_complete_invariant(tmp_path):
    """The torn tmp dir is invisible (never renamed): the victim's
    snapshot directory still serves its latest COMPLETE snapshot, and
    recovery restores from it — not from the torn turd."""
    kw = dict(n_objects=N_OBJECTS, engine="pcc", n_lanes=N_LANES,
              budgets=(7, 11))
    vdir = str(tmp_path / "victim")
    plan = FaultPlan(kill_batch=4, kill_phase="snapshot", torn=True,
                     action="raise")
    with pytest.raises(FaultInjected):
        run_replica(JOURNAL, directory=vdir, snapshot_every=2,
                    fault_plan=plan, **kw)
    # snapshot 0 (after batch 2) committed; snapshot 1 (after batch 4)
    # died mid-commit: only a .tmp turd remains
    assert snapshot_ids(vdir) == [0]
    assert any("tmp" in name for name in os.listdir(vdir))
    base = run_replica(JOURNAL, directory=str(tmp_path / "base"),
                       snapshot_every=0, **kw)
    rec = run_replica(JOURNAL, directory=vdir, snapshot_every=2,
                      resume=True, **kw)
    assert rec.session.restored_from == 0
    _assert_recovered(rec.session.fingerprint(), rec.session.replay_log(),
                      [trace_digest(t) for t in rec.session.traces], base)


def test_kill_before_any_snapshot_cold_starts(tmp_path):
    """A victim killed before its first snapshot leaves nothing: resume
    falls back to a cold start from the arrival journal alone."""
    kw = dict(n_objects=N_OBJECTS, engine="pcc", n_lanes=N_LANES,
              budgets=(7, 11))
    vdir = str(tmp_path / "victim")
    plan = FaultPlan(kill_batch=0, kill_phase="admit", action="raise")
    with pytest.raises(FaultInjected):
        run_replica(JOURNAL, directory=vdir, snapshot_every=2,
                    fault_plan=plan, **kw)
    assert snapshot_ids(vdir) == []
    base = run_replica(JOURNAL, directory=str(tmp_path / "base"),
                       snapshot_every=0, **kw)
    rec = run_replica(JOURNAL, directory=vdir, snapshot_every=2,
                      resume=True, **kw)
    assert rec.session.restored_from == -1      # never restored: cold
    _assert_recovered(rec.session.fingerprint(), rec.session.replay_log(),
                      [trace_digest(t) for t in rec.session.traces], base)


# ------------------------------------------------- F2 subprocess SIGKILL
def _subprocess_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    # the victim / recovery processes share the checkout's persistent
    # compile cache (repro.launch.compile_cache) — the matrix is
    # compile-bound otherwise; cache every step, however quick
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    return env


def _run_driver(cfg, cfg_path, out_path, env):
    with open(cfg_path, "w") as f:
        json.dump(cfg, f)
    return subprocess.run(
        [sys.executable, "-m", "repro.core.checkpoint",
         str(cfg_path), str(out_path)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=420)


SUBPROC_CASES = [
    ("pcc", 1, 0, (7, 11), 4, "execute", False),
    ("occ", 8, 2, (16,), 4, "snapshot", True),
]
if FULL:
    SUBPROC_CASES += [
        ("pcc", 8, 2, (7, 11), 4, "snapshot", True),
        ("occ", 1, 0, (16,), 3, "drain", False),
        ("pcc", 1, 2, (16,), 2, "drain", False),
        ("occ", 8, 0, (7, 11), 4, "execute", False),
        ("pcc", 8, 0, (16,), 0, "admit", False),
        ("occ", 1, 2, (7, 11), 2, "snapshot", False),
    ]


@pytest.mark.parametrize("engine,shards,depth,budgets,kill,phase,torn",
                         SUBPROC_CASES)
def test_sigkill_and_restore_subprocess(tmp_path, engine, shards, depth,
                                        budgets, kill, phase, torn):
    """F2 for real: the victim process takes an actual SIGKILL at its
    deterministic fault point (torn case: after corrupting the staged
    snapshot mid-commit); a fresh process restores and reconverges."""
    env = _subprocess_env()
    kw = dict(n_objects=N_OBJECTS, engine=engine, n_lanes=N_LANES,
              shards=shards, pipeline_depth=depth, budgets=list(budgets))
    base = run_replica(JOURNAL, directory=str(tmp_path / "base"),
                       snapshot_every=0, **kw)

    vdir = str(tmp_path / "victim")
    cfg_path, out_path = tmp_path / "cfg.json", tmp_path / "out.json"
    victim = dict(kw, journal=JOURNAL, directory=vdir, snapshot_every=2,
                  fault={"kill_batch": kill, "kill_phase": phase,
                         "torn": torn})
    r = _run_driver(victim, cfg_path, out_path, env)
    assert r.returncode == -9, (r.returncode, r.stderr[-2000:])
    assert not out_path.exists()

    recovery = dict(kw, journal=JOURNAL, directory=vdir, snapshot_every=2,
                    resume=True)
    r = _run_driver(recovery, cfg_path, out_path, env)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(out_path.read_text())
    assert out["pool_depth"] == 0
    _assert_recovered(out["fingerprint"], out["replay_log"],
                      out["trace_digests"], base)


# ------------------------------------------------- F3 elastic failover
ELASTIC_EVENTS = [[2, "join", None, 0], [5, "leave", 2, 0]]


def test_elastic_failover_numbers_lanes_identically(tmp_path):
    """destm's lane placement decides round membership, so this fails
    loudly if a restored replica renumbers lanes across the join/leave
    events the victim already applied."""
    kw = dict(n_objects=N_OBJECTS, engine="destm", n_lanes=4,
              budgets=(7, 11), elastic_events=ELASTIC_EVENTS)
    base = run_replica(JOURNAL, directory=str(tmp_path / "base"),
                       snapshot_every=0, **kw)
    assert base.session.elastic is not None
    vdir = str(tmp_path / "victim")
    plan = FaultPlan(kill_batch=4, kill_phase="execute", action="raise")
    with pytest.raises(FaultInjected):
        run_replica(JOURNAL, directory=vdir, snapshot_every=2,
                    fault_plan=plan, **kw)
    rec = run_replica(JOURNAL, directory=vdir, snapshot_every=2,
                      resume=True, **kw)
    # the restored manager is byte-for-byte the uninterrupted one:
    # same events (with their assigned lane ids), same round cursor
    assert rec.session.elastic.state_dict() == \
        base.session.elastic.state_dict()
    assert rec.session.elastic.live_lanes() == \
        base.session.elastic.live_lanes()
    _assert_recovered(rec.session.fingerprint(), rec.session.replay_log(),
                      [trace_digest(t) for t in rec.session.traces], base)


def test_serve_accepts_elastic_manager():
    """PotSession.serve(elastic=...) wires scaling events through the
    ordinary serve loop — same stream as a plain serve when no event
    fires inside it, different (but deterministic) lane placement when
    one does."""
    from repro.runtime.elastic import ElasticLaneManager, ScalingEvent
    pool, _ = IngressPool.replay(JOURNAL)
    mgr = ElasticLaneManager(4, [ScalingEvent(2, "join", None, 0)])
    s = PotSession(N_OBJECTS, engine="pcc", n_lanes=4)
    s.serve(pool, budget=9, elastic=mgr)
    assert s.elastic is mgr and s.batches_formed > 2
    assert mgr._round == s.batches_formed
    assert 4 in mgr.live_lanes()        # the joined worker lane

    # two replicas serving the same journal + schedule agree bitwise
    pool2, _ = IngressPool.replay(JOURNAL)
    mgr2 = ElasticLaneManager(4, [ScalingEvent(2, "join", None, 0)])
    s2 = PotSession(N_OBJECTS, engine="pcc", n_lanes=4)
    s2.serve(pool2, budget=9, elastic=mgr2)
    assert s2.fingerprint() == s.fingerprint()
    assert s2.replay_log() == s.replay_log()


# ------------------------------------------------- F4 metrics columns
def test_metrics_csv_carries_failover_observables(tmp_path):
    from repro.core import make_store, run_all
    from repro.core import metrics as M

    kw = dict(n_objects=N_OBJECTS, engine="pcc", n_lanes=N_LANES,
              budgets=(7, 11))
    run_replica(JOURNAL, directory=str(tmp_path), snapshot_every=2, **kw)
    rec = run_replica(JOURNAL, directory=str(tmp_path), snapshot_every=2,
                      resume=True, **kw)
    session, pool = rec.session, rec.pool
    wl = W.counters(n_txns=12, n_objects=N_OBJECTS, n_lanes=4, seed=4)
    trace = session.submit(wl.batch, wl.lanes.tolist())
    res = run_all(wl.batch, make_store(N_OBJECTS).values)
    rep = M.report_from_trace("pcc", trace, wl.batch,
                              np.asarray(res.rn), np.asarray(res.wn),
                              session=session, pool=pool)
    assert rep.snapshots_taken == session.snapshots_taken >= 1
    assert rep.restored_from == session.restored_from >= 0
    assert rep.recovery_batches == session.recovery_batches >= 1
    row, header = rep.row(), M.HEADER
    assert len(row.split(",")) == len(header.split(","))
    for col in ("snapshots_taken", "restored_from", "recovery_batches"):
        assert col in header.split(",")
    # a never-restored session reports the defaults
    fresh = M.report_from_trace("pcc", trace, wl.batch,
                                np.asarray(res.rn), np.asarray(res.wn),
                                session=PotSession(N_OBJECTS))
    assert (fresh.snapshots_taken, fresh.restored_from,
            fresh.recovery_batches) == (0, -1, 0)
