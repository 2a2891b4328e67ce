"""Crash-consistent session checkpoints + deterministic replica failover.

Pot's core promise — one deterministic serialization order — is exactly
what makes fault tolerance cheap (paper §1; Aviram et al. in PAPERS.md):
a replica that crashes anywhere in the stream can rejoin bit-exactly,
because everything it lost is a pure function of (last snapshot, the
shared arrival journal suffix).  This module assembles the pieces the
earlier PRs built — the replayable ingress journal (PR 6), rank-space
sequencing, the layout-polymorphic store (PR 5), the speculative window
(PR 7) — into a crash-consistent runtime layer:

- **Session snapshots** (:func:`save_snapshot` / :func:`restore_session`,
  surfaced as ``PotSession.snapshot`` / ``PotSession.restore``): the
  complete resumable state of a ``PotSession`` — the committed store
  image (dense, or one ``.npz`` per shard, so an S-sharded snapshot
  restores into any S'), ``gv``, the sequencer cursor, the submit /
  formed-batch counters, bucket/compile bookkeeping, the materialized
  replay log, the elastic lane-manager state, and the ingress pool's
  event journal (whose non-drain prefix IS the cursor into the shared
  arrival journal).  The speculative window is always *flushed into*
  the snapshot — speculation is never persisted.

- **Atomic commit protocol** (:func:`atomic_dir`): write everything into
  ``<final>.tmp``, fsync every file and the directory, then atomically
  rename — a crash at ANY point leaves either the previous snapshots or
  a ``.tmp`` turd that restore never looks at.  This is the one
  crash-safety implementation in the repo; ``repro.ckpt.checkpoint``
  (the trainer checkpoint) commits through the same helper.

- **Self-verification**: every snapshot manifest carries per-file
  sha256 digests, the store fingerprint, and a *chained* snapshot
  digest (``sha256(parent_chain || core)``), so a restore proves the
  snapshot complete and uncorrupted — and provably part of one lineage
  — before serving (:func:`load_snapshot`; :func:`latest_snapshot`
  walks back to the newest snapshot that verifies).

- **Deterministic fault injection** (:class:`FaultPlan`): fault points
  are (formed-batch index, phase) positions in the *order* — never
  wall-clock, never RNG — so a fault schedule is as replayable as the
  execution it kills.  ``action="sigkill"`` delivers a real SIGKILL
  (the subprocess harness in tests/test_failover.py);
  ``action="raise"`` raises :class:`FaultInjected` for in-process
  tests.  Torn-write injection corrupts the snapshot tmp directory
  mid-commit (before the rename), proving the latest-complete-snapshot
  invariant.

- **The replica loop** (:func:`run_replica`): admit-journal in, batches
  formed under a deterministic budget schedule, snapshot every N
  batches, faults fired between admit/drain/execute/snapshot steps.
  ``resume=True`` restores from the newest complete snapshot (or cold
  starts when none exists), re-applies the arrival-journal suffix, and
  continues — the **recovery invariant**::

      restore(latest snapshot) + drain(arrival journal suffix)
          ==  the uninterrupted stream, bit for bit

  (store fingerprints, ``ExecTrace``s — speculation observables aside,
  exactly as in PR 7 — and ``replay_log()``), at any snapshot point,
  any drain-budget schedule, any ``pipeline_depth``.

Run one replica from the command line (the subprocess harness)::

    python -m repro.core.checkpoint <config.json> <out.json>
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import os
import shutil
import signal

import jax.numpy as jnp
import numpy as np

from repro.core.ingress import EV_DRAIN, IngressPool, JournalError
from repro.core.sequencer import sequencer_from_state, sequencer_state
from repro.core.tstore import TStore, fingerprint as store_fingerprint
from repro.core.tstore import shard_images

SNAP_PREFIX = "snap_"
SNAP_FORMAT = 1
MANIFEST = "manifest.json"

# fault phases, in the order they occur inside one replica-loop turn
PH_ADMIT, PH_DRAIN, PH_EXECUTE, PH_SNAPSHOT = (
    "admit", "drain", "execute", "snapshot")
PHASES = (PH_ADMIT, PH_DRAIN, PH_EXECUTE, PH_SNAPSHOT)


class SnapshotError(RuntimeError):
    """A snapshot is missing, incomplete, corrupted, or off-chain."""


# --------------------------------------------------------------------------
# the atomic tmp/fsync/rename commit protocol (shared with repro.ckpt)
# --------------------------------------------------------------------------
def fsync_dir(path: str) -> None:
    """fsync a directory fd so the rename itself is durable."""
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def fsync_tree(path: str) -> None:
    """fsync every regular file under ``path``, then the dirs themselves."""
    for root, _dirs, files in os.walk(path):
        for name in files:
            fd = os.open(os.path.join(root, name), os.O_RDONLY)
            try:
                os.fsync(fd)
            finally:
                os.close(fd)
        fsync_dir(root)


@contextlib.contextmanager
def atomic_dir(final: str, *, suffix: str = ".tmp"):
    """Atomically materialize the directory ``final``.

    Yields a ``final + suffix`` staging directory to write into.  On
    clean exit: every file is fsynced, an existing ``final`` is
    replaced, the staging dir is renamed into place, and the parent dir
    is fsynced — so a crash at ANY point leaves either the old state or
    a ``*.tmp*`` turd that readers skip, never a half-written ``final``.
    On exception the staging dir is left in place (exactly what a real
    crash leaves behind); it is replaced by the next attempt.
    """
    tmp = final + suffix
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    yield tmp
    fsync_tree(tmp)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    fsync_dir(os.path.dirname(final) or ".")


def _sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _core_digest(manifest: dict) -> str:
    """The chained-digest payload: the fields that pin a snapshot's
    identity (execution outcome + exact file contents)."""
    core = {k: manifest[k] for k in
            ("format", "snapshot_id", "gv", "n_txns", "store_fingerprint",
             "replay_log", "files")}
    return hashlib.sha256(
        json.dumps(core, sort_keys=True).encode()).hexdigest()


def chain_digest(parent: str, manifest: dict) -> str:
    """chain = sha256(parent_chain || core): links snapshot k to k-1, so
    a snapshot directory proves it belongs to one replica lineage."""
    return hashlib.sha256(
        (parent + _core_digest(manifest)).encode()).hexdigest()


# --------------------------------------------------------------------------
# trace canonicalization (cross-process comparison / future receipts)
# --------------------------------------------------------------------------
def trace_digest(trace, *, include_spec: bool = False) -> str:
    """Canonical sha256 of an ExecTrace — comparable across processes.

    ``spec_*`` observables are excluded by default: they surface *when*
    speculative work ran (which legitimately differs around a restore
    point, where the window restarts empty), while every other field is
    bit-identical between replicas by the PR 7 pipelining invariant.
    """
    h = hashlib.sha256()
    for f in dataclasses.fields(trace):
        if not include_spec and f.name.startswith("spec_"):
            continue
        arr = np.asarray(getattr(trace, f.name))
        h.update(f.name.encode())
        h.update(str(arr.dtype).encode())
        h.update(str(arr.shape).encode())
        h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()


# --------------------------------------------------------------------------
# snapshot save / load / verify
# --------------------------------------------------------------------------
def _snap_path(directory: str, snapshot_id: int) -> str:
    return os.path.join(directory, f"{SNAP_PREFIX}{snapshot_id:08d}")


def snapshot_ids(directory: str) -> list[int]:
    """Ids of the *committed* snapshots in ``directory``, ascending
    (staging ``*.tmp*`` dirs — crash turds — are never listed)."""
    if not os.path.isdir(directory):
        return []
    out = []
    for name in os.listdir(directory):
        if not name.startswith(SNAP_PREFIX) or "tmp" in name:
            continue
        tail = name[len(SNAP_PREFIX):]
        if tail.isdigit():
            out.append(int(tail))
    return sorted(out)


def save_snapshot(session, directory: str, *, pool: IngressPool | None = None,
                  _torn_hook=None) -> str:
    """Write one crash-consistent snapshot of ``session`` (and the pool
    feeding it) under ``directory``; returns the committed path.

    The speculative window is flushed first (speculation is never
    persisted), the replay log is materialized, and everything commits
    through :func:`atomic_dir`.  ``_torn_hook(tmp)``, when given, runs
    after all files are staged and *before* the atomic rename — the
    fault-injection seam for torn-write tests.
    """
    session._spec_flush()
    log = session.replay_log()
    store = session.store
    snap_id = session._next_snapshot_id
    final = _snap_path(directory, snap_id)
    os.makedirs(directory, exist_ok=True)

    images = shard_images(store)
    sharded = isinstance(store, TStore) is False
    manifest = {
        "format": SNAP_FORMAT,
        "snapshot_id": snap_id,
        "engine": session.engine.name,
        "n_objects": int(store.n_objects),
        "slot": int(store.slot),
        "shards": len(images) if sharded else 1,
        "gv": int(store.gv),
        "n_txns": int(session.n_txns),
        "n_batches": len(session.traces),
        "batches_formed": int(session.batches_formed),
        "n_lanes": int(session.n_lanes),
        "bucket": bool(session.bucket),
        "bucket_ladder": session.bucket_ladder,
        "pipeline_depth": int(session.pipeline_depth),
        "replay_log": [int(t) for t in log],
        "bucket_counts": [[int(k), int(l), int(c)] for (k, l), c
                          in sorted(session._bucket_counts.items())],
        "sequencer": sequencer_state(session.sequencer),
        "elastic": (session.elastic.state_dict()
                    if session.elastic is not None else None),
        "pool_journal": (_journal_to_json(pool.journal())
                         if pool is not None else None),
        "snapshots_taken": int(session.snapshots_taken) + 1,
        "restored_from": int(session.restored_from),
        "store_fingerprint": int(store_fingerprint(store)),
        "parent_digest": session._chain_digest,
    }

    with atomic_dir(final) as tmp:
        files: dict[str, str] = {}
        if sharded:
            for i, (vals, vers) in enumerate(images):
                name = f"shard_{i}.npz"
                np.savez(os.path.join(tmp, name),
                         values=np.asarray(vals), versions=np.asarray(vers))
                files[name] = _sha256_file(os.path.join(tmp, name))
        else:
            np.savez(os.path.join(tmp, "store.npz"),
                     values=np.asarray(store.values),
                     versions=np.asarray(store.versions))
            files["store.npz"] = _sha256_file(os.path.join(tmp, "store.npz"))
        manifest["files"] = files
        manifest["chain_digest"] = chain_digest(session._chain_digest,
                                                manifest)
        with open(os.path.join(tmp, MANIFEST), "w") as f:
            json.dump(manifest, f)
        if _torn_hook is not None:
            _torn_hook(tmp)

    session.snapshots_taken += 1
    session._chain_digest = manifest["chain_digest"]
    session._next_snapshot_id = snap_id + 1
    return final


def load_snapshot(path: str) -> tuple[dict, np.ndarray, np.ndarray]:
    """Load + self-verify one snapshot directory.

    Returns ``(manifest, values, versions)`` with the store already
    reassembled into its dense (O, slot) / (O,) image.  Raises
    :class:`SnapshotError` unless the snapshot proves itself complete:
    per-file sha256 digests match, the reassembled store re-hashes to
    the manifest's fingerprint, and the chain digest recomputes.
    """
    mpath = os.path.join(path, MANIFEST)
    try:
        with open(mpath) as f:
            manifest = json.load(f)
    except (OSError, ValueError) as e:
        raise SnapshotError(f"unreadable manifest in {path}: {e}") from e
    if manifest.get("format") != SNAP_FORMAT:
        raise SnapshotError(
            f"unknown snapshot format {manifest.get('format')!r} in {path}")
    for name, digest in manifest["files"].items():
        fpath = os.path.join(path, name)
        if not os.path.exists(fpath):
            raise SnapshotError(f"snapshot {path} is missing {name}")
        actual = _sha256_file(fpath)
        if actual != digest:
            raise SnapshotError(
                f"snapshot {path} file {name} is corrupted: sha256 "
                f"{actual[:12]}… != manifest {digest[:12]}…")
    if chain_digest(manifest["parent_digest"], manifest) \
            != manifest["chain_digest"]:
        raise SnapshotError(f"snapshot {path} chain digest does not verify")

    parts = []
    if "store.npz" in manifest["files"]:
        with np.load(os.path.join(path, "store.npz")) as data:
            parts.append((data["values"], data["versions"]))
    else:
        for i in range(manifest["shards"]):
            with np.load(os.path.join(path, f"shard_{i}.npz")) as data:
                parts.append((data["values"], data["versions"]))
    values = np.concatenate([p[0] for p in parts], axis=0)
    versions = np.concatenate([p[1] for p in parts], axis=0)
    o = manifest["n_objects"]
    if values.shape != (o, manifest["slot"]) or versions.shape != (o,):
        raise SnapshotError(
            f"snapshot {path} store image has shape {values.shape}, "
            f"manifest says ({o}, {manifest['slot']})")
    dense = TStore(values=jnp.asarray(values), versions=jnp.asarray(versions),
                   gv=jnp.asarray(manifest["gv"], jnp.int32))
    fp = int(store_fingerprint(dense))
    if fp != manifest["store_fingerprint"]:
        raise SnapshotError(
            f"snapshot {path} store image re-hashes to 0x{fp:08x}, "
            f"manifest says 0x{manifest['store_fingerprint']:08x}")
    return manifest, values, versions


def latest_snapshot(directory: str) -> str | None:
    """Path of the newest snapshot in ``directory`` that *verifies* —
    the latest-complete-snapshot invariant: torn staging dirs are
    invisible (never renamed) and a corrupted committed snapshot is
    skipped in favor of its predecessor.  None when nothing verifies.
    """
    for snap_id in reversed(snapshot_ids(directory)):
        path = _snap_path(directory, snap_id)
        try:
            load_snapshot(path)
        except SnapshotError:
            continue
        return path
    return None


def _journal_to_json(journal) -> list:
    """Journal events as JSON-clean nested lists (tuples round-trip
    through json as lists; IngressPool validation accepts both)."""
    def clean(x):
        if isinstance(x, (list, tuple)):
            return [clean(v) for v in x]
        if isinstance(x, dict):
            return {k: clean(v) for k, v in x.items()}
        if isinstance(x, (np.integer,)):
            return int(x)
        return x
    return [clean(ev) for ev in journal]


def arrival_cursor(journal) -> int:
    """How far into the *shared arrival journal* a pool journal has
    consumed: its non-drain events are exactly the arrival prefix."""
    return sum(1 for ev in journal if ev[0] != EV_DRAIN)


def restore_session(directory: str, *, step: int | None = None,
                    arrival_journal=None, engine: str | None = None,
                    shards: int | None = None, mesh=None,
                    bucket: bool | None = None,
                    bucket_ladder: str | None = None,
                    pipeline_depth: int | None = None,
                    sequencer=None, donate: bool = True):
    """Rebuild a ``(PotSession, IngressPool | None)`` from a snapshot.

    Picks the newest *complete* snapshot under ``directory`` (or exactly
    ``snap_<step>`` when ``step`` is given), self-verifies it
    (:func:`load_snapshot`), and reconstructs the full session state:
    store (resharded into ``shards``/``mesh`` if overridden — snapshots
    are layout-portable), sequencer cursor, replay log, submit/formed
    counters, bucket bookkeeping, elastic lane manager, and the ingress
    pool replayed from its journaled cursor.  With ``arrival_journal``
    (the shared replication feed), the suffix of admissions the snapshot
    had not yet seen is applied to the restored pool, so draining the
    restored replica converges to the uninterrupted stream bit-exactly.

    Overrides (``engine``, ``shards``, ``bucket_ladder``,
    ``pipeline_depth``, ...) default to the snapshot's own values.
    """
    from repro.core.session import PotSession
    from repro.runtime.elastic import ElasticLaneManager

    if step is not None:
        path = _snap_path(directory, step)
        manifest, values, versions = load_snapshot(path)
    else:
        path = latest_snapshot(directory)
        if path is None:
            raise SnapshotError(
                f"no complete snapshot under {directory!r}")
        manifest, values, versions = load_snapshot(path)

    target_shards = shards if shards is not None else manifest["shards"]
    store = TStore(values=jnp.asarray(values),
                   versions=jnp.asarray(versions),
                   gv=jnp.asarray(manifest["gv"], jnp.int32))
    if sequencer is None:
        sequencer = sequencer_from_state(manifest["sequencer"])
    session = PotSession(
        store=store,
        engine=engine if engine is not None else manifest["engine"],
        sequencer=sequencer,
        n_lanes=manifest["n_lanes"],
        donate=donate,
        bucket=bucket if bucket is not None else manifest["bucket"],
        bucket_ladder=(bucket_ladder if bucket_ladder is not None
                       else manifest["bucket_ladder"]),
        shards=target_shards if target_shards > 1 or mesh is not None else 1,
        mesh=mesh,
        pipeline_depth=(pipeline_depth if pipeline_depth is not None
                        else manifest["pipeline_depth"]))

    # resume the session's host-side cursors exactly where the snapshot
    # left them: future batches continue the same global history
    session._n_txns = manifest["n_txns"]
    session._log = list(manifest["replay_log"])
    session._log_batches = 0          # traces list restarts empty …
    session._log_txns = manifest["n_txns"]   # … but ids keep their offset
    session._bucket_counts = {(k, l): c
                              for k, l, c in manifest["bucket_counts"]}
    session._batches_formed = manifest["batches_formed"]
    session.snapshots_taken = manifest["snapshots_taken"]
    session.restored_from = manifest["snapshot_id"]
    session._chain_digest = manifest["chain_digest"]
    session._next_snapshot_id = manifest["snapshot_id"] + 1
    if manifest["elastic"] is not None:
        session.elastic = ElasticLaneManager.from_state(manifest["elastic"])

    pool = None
    if manifest["pool_journal"] is not None:
        pool, _ = IngressPool.replay(manifest["pool_journal"])
        if arrival_journal is not None:
            arrival_journal = list(arrival_journal)
            cursor = arrival_cursor(manifest["pool_journal"])
            if cursor > len(arrival_journal):
                raise JournalError(
                    f"snapshot consumed {cursor} arrival events but the "
                    f"shared journal has only {len(arrival_journal)} — "
                    "journals diverged or the feed was truncated")
            pool.apply(arrival_journal[cursor:])
    return session, pool


# --------------------------------------------------------------------------
# deterministic fault injection
# --------------------------------------------------------------------------
class FaultInjected(RuntimeError):
    """Raised by a ``FaultPlan(action="raise")`` at its fault point."""

    def __init__(self, batch: int, phase: str):
        super().__init__(f"injected fault at batch {batch}, phase {phase!r}")
        self.batch, self.phase = batch, phase


@dataclasses.dataclass
class FaultPlan:
    """A deterministic crash schedule over the replica loop.

    Fault points are positions in the ORDER — (formed-batch index,
    phase) — never wall-clock and never RNG, so a fault plan replays as
    deterministically as the execution it interrupts.  Phases fire
    between the loop's steps: ``admit`` (after the journal is applied,
    before the first drain), ``drain`` (before forming batch k),
    ``execute`` (after forming, before executing batch k), ``snapshot``
    (before the snapshot that follows batch k).  With ``torn=True`` the
    snapshot-phase fault corrupts the staged tmp directory mid-commit
    (truncating the payload before the atomic rename) and THEN dies —
    the torn-write case the latest-complete-snapshot invariant covers.

    ``action``: ``"sigkill"`` (default) delivers a real ``SIGKILL`` to
    the current process — the subprocess harness; ``"raise"`` raises
    :class:`FaultInjected` for in-process tests.
    """

    kill_batch: int | None = None
    kill_phase: str = PH_EXECUTE
    torn: bool = False
    action: str = "sigkill"

    def __post_init__(self):
        if self.kill_phase not in PHASES:
            raise ValueError(f"unknown fault phase {self.kill_phase!r}; "
                             f"pick one of {PHASES}")
        if self.action not in ("sigkill", "raise"):
            raise ValueError(f"unknown fault action {self.action!r}")
        if self.torn and self.kill_phase != PH_SNAPSHOT:
            raise ValueError("torn=True only makes sense at the "
                             "'snapshot' phase (it corrupts the staged "
                             "snapshot mid-commit)")

    def matches(self, batch: int, phase: str) -> bool:
        return self.kill_batch is not None and batch == self.kill_batch \
            and phase == self.kill_phase

    def _die(self, batch: int, phase: str):
        if self.action == "raise":
            raise FaultInjected(batch, phase)
        os.kill(os.getpid(), signal.SIGKILL)   # pragma: no cover

    def fire(self, batch: int, phase: str) -> None:
        """Die iff (batch, phase) is the planned fault point.  The torn
        variant does not fire here — it runs as :meth:`torn_hook` inside
        the snapshot commit instead."""
        if self.matches(batch, phase) and not self.torn:
            self._die(batch, phase)

    def torn_hook(self, tmp: str) -> None:
        """The mid-commit fault: truncate the staged store payload and
        mangle the manifest, then die before the atomic rename — the
        staging dir is left exactly as a torn write would leave it."""
        for name in sorted(os.listdir(tmp)):
            path = os.path.join(tmp, name)
            size = os.path.getsize(path)
            with open(path, "r+b") as f:
                f.truncate(max(size // 2, 1))
        self._die(self.kill_batch if self.kill_batch is not None else -1,
                  PH_SNAPSHOT)


# --------------------------------------------------------------------------
# the replica loop
# --------------------------------------------------------------------------
@dataclasses.dataclass
class ReplicaRun:
    """What one :func:`run_replica` call produced (host-side views)."""

    session: object                     # the PotSession
    pool: IngressPool
    fingerprints: list[int]             # store fingerprint after each
    #                                     executed batch (in record order)

    def summary(self) -> dict:
        """JSON-clean cross-process comparison payload."""
        s = self.session
        return {
            "fingerprint": int(s.fingerprint()),
            "fingerprints": [int(f) for f in self.fingerprints],
            "replay_log": [int(t) for t in s.replay_log()],
            "trace_digests": [trace_digest(t) for t in s.traces],
            "n_batches": len(s.traces),
            "batches_formed": int(s.batches_formed),
            "n_txns": int(s.n_txns),
            "gv": int(s.gv),
            "pool_depth": len(self.pool),
            "restored_from": int(s.restored_from),
            "snapshots_taken": int(s.snapshots_taken),
            "recovery_batches": int(s.recovery_batches),
            "chain_digest": s._chain_digest,
            "elastic": (s.elastic.state_dict()
                        if s.elastic is not None else None),
        }


def run_replica(arrival_journal, *, directory: str, n_objects: int,
                slot: int = 1, engine: str = "pcc", n_lanes: int = 8,
                shards: int = 1, mesh=None, pipeline_depth: int = 0,
                bucket_ladder: str = "pow2", budgets=(16,),
                snapshot_every: int = 2, elastic_events=None,
                fault_plan: FaultPlan | None = None, resume: bool = False,
                record_fingerprints: bool = True) -> ReplicaRun:
    """Serve one replica from a shared arrival journal, snapshotting as
    it goes — the deterministic failover loop.

    Cold start (``resume=False`` or no complete snapshot yet): replay
    the arrival journal into a fresh pool and serve it with a fresh
    session.  Warm start (``resume=True`` with a complete snapshot):
    :func:`restore_session` + the arrival-journal suffix.  Either way
    the loop is a pure function of (journal, budgets, snapshot_every,
    elastic_events): batch k always drains with ``budgets[k %
    len(budgets)]`` and a snapshot commits after every
    ``snapshot_every``-th formed batch (0 disables) — so a restarted
    replica re-enters the SAME schedule at the position the snapshot
    recorded, and its stream is bit-identical to the uninterrupted run.

    ``fault_plan`` fires between steps (see :class:`FaultPlan`).
    """
    from repro.core.session import PotSession
    from repro.runtime.elastic import ElasticLaneManager, ScalingEvent

    plan = fault_plan if fault_plan is not None else FaultPlan()
    budgets = tuple(int(b) for b in budgets)
    if not budgets:
        raise ValueError("budgets must name at least one drain budget")
    arrival_journal = list(arrival_journal)

    session = pool = None
    if resume:
        try:
            session, pool = restore_session(
                directory, arrival_journal=arrival_journal, mesh=mesh)
        except SnapshotError:
            session = pool = None     # nothing committed yet: cold start
    if session is None:
        pool, _ = IngressPool.replay(arrival_journal)
        session = PotSession(n_objects, slot=slot, engine=engine,
                             n_lanes=n_lanes, shards=shards, mesh=mesh,
                             bucket_ladder=bucket_ladder,
                             pipeline_depth=pipeline_depth)
        if elastic_events:
            session.elastic = ElasticLaneManager(
                n_lanes, [ScalingEvent(*ev) for ev in elastic_events])

    fingerprints: list[int] = []

    def _executed(traces):
        # one fingerprint per loop step that committed work: at D=0 this
        # is exactly the per-batch store sequence; pipelined runs emit
        # one per window drain (positions shift, values stay on the
        # committed-batch boundaries)
        if record_fingerprints and traces:
            fingerprints.append(int(session.fingerprint()))

    plan.fire(session.batches_formed, PH_ADMIT)
    while True:
        b = session.batches_formed
        plan.fire(b, PH_DRAIN)
        fb = pool.drain(budgets[b % len(budgets)])
        if fb is None:
            break
        plan.fire(b, PH_EXECUTE)
        _executed(session._serve_formed(fb, ladder=fb.ladder))
        done = session.batches_formed
        if snapshot_every and done % snapshot_every == 0:
            hook = None
            if plan.matches(done, PH_SNAPSHOT) and plan.torn:
                hook = plan.torn_hook
            else:
                plan.fire(done, PH_SNAPSHOT)
            session.snapshot(directory, pool=pool, _torn_hook=hook)
            if record_fingerprints:
                # the snapshot flushed the speculative window: record
                # the store state the snapshot actually captured
                fingerprints.append(int(session.fingerprint()))
    _executed(session._spec_flush())
    return ReplicaRun(session=session, pool=pool, fingerprints=fingerprints)


# --------------------------------------------------------------------------
# subprocess harness entry point
# --------------------------------------------------------------------------
def _main(argv) -> int:     # pragma: no cover - exercised via subprocess
    """``python -m repro.core.checkpoint <config.json> <out.json>``:
    run one replica per the JSON config, write its summary atomically.

    Config keys = :func:`run_replica` kwargs plus ``journal`` (the
    arrival journal as nested lists) and optional ``fault`` (a
    :class:`FaultPlan` field dict).  A victim run simply never writes
    its out file — SIGKILL is the point.
    """
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cfg_path, out_path = argv
    with open(cfg_path) as f:
        cfg = json.load(f)
    journal = cfg.pop("journal")
    fault = cfg.pop("fault", None)
    plan = FaultPlan(**fault) if fault else None
    run = run_replica(journal, fault_plan=plan, **cfg)
    payload = run.summary()
    with atomic_dir(out_path + ".d") as tmp:
        with open(os.path.join(tmp, "out.json"), "w") as f:
            json.dump(payload, f)
    shutil.move(os.path.join(out_path + ".d", "out.json"), out_path)
    shutil.rmtree(out_path + ".d", ignore_errors=True)
    return 0


if __name__ == "__main__":   # pragma: no cover
    import sys
    raise SystemExit(_main(sys.argv[1:]))
