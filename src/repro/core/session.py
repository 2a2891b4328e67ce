"""PotSession — the streaming execution layer over the unified engine API.

A session owns the three pieces Pot threads through time:

- the **store** (committed TStore image + ``gv``), carried across batches
  so a stream of batches behaves like one long preordered history;
- the **sequencer**, which keeps assigning globally increasing sequence
  numbers (round-robin over lanes by default, or any sequencer from
  :mod:`repro.core.sequencer`);
- a **cached jitted step** for its engine, with the incoming store
  buffers *donated* — on accelerators the committed image is updated in
  place instead of copied every batch.

**Shape bucketing** (PR 4): a ragged stream of batch shapes would force
one XLA compile per distinct ``(K, L)`` — a serving-workload killer.
``submit`` therefore pads every batch up to the next power-of-two bucket
with *vacant* NOP rows (``n_ins == 0``; sequence numbers past every real
row's), so the jitted step compiles once per (engine, bucket): a 32-shape
ragged stream compiles at most ladder-size (= log₂ range) steps.  The
engines guarantee vacant rows never commit — no store write, no version
stamp, no ``gv`` advance, ``commit_pos == -1`` — so fingerprints and
``replay_log()`` are bit-identical to the unpadded run (asserted in
tests/test_compact_bucket.py).  The returned traces are sliced back to
the real K, so callers never see padding.  Observables:
``compile_count()`` (distinct compiled step shapes this session
triggered) and ``bucket_counts()`` (batches per bucket).

Usage::

    session = PotSession(n_objects=1024, engine="pcc", n_lanes=8)
    for batch in batches:
        trace = session.submit(batch, lanes)       # one ExecTrace each
    session.fingerprint()                          # determinism check
    log = session.replay_log()                     # global commit order
    session.compile_count()                        # <= #buckets, not #shapes

**Deterministic ingress** (PR 6): a session can also be fed by an
:class:`~repro.core.ingress.IngressPool` — the admission + priority-
drain front-end that *forms* batches from single-transaction arrivals.
``serve(pool, budget=...)`` drains the pool to empty; the pool's drain
order is the preordered sequence (the formed batches carry their own
globally consecutive sequence numbers) and the shape bucket follows the
pool's occupancy-driven ladder recommendation::

    pool = IngressPool(capacity=4096)
    for program, lane, fee in arrivals:
        pool.admit(program, lane=lane, fee=fee)
    session.serve(pool, budget=64)

The recorded log feeds straight back into a new session for
record/replay debugging (paper §2.1)::

    replay = PotSession(n_objects=1024, engine="pcc",
                        sequencer=session.replay_sequencer())
    replay.run_stream(batches)                     # bitwise-identical

**Cross-batch speculative pipelining** (PR 7): with
``pipeline_depth=D >= 1``, ``run_stream`` / ``serve`` keep a window of
up to D batches *speculatively executed* ahead of the committed store:
each enqueued batch runs its round-0 read phase + conflict analysis
against the CURRENT store image (``protocol.spec_execute`` — a pure
read, overlappable with the predecessor batches' tail rounds), and when
its turn comes the engine re-bases that seed onto the now-committed
store: rows whose read set hit a post-snapshot write (the version-stamp
dirty predicate ``versions > snap_gv``) re-execute through the ordinary
compact ladder; everything else is already bit-identical to a fresh
round 0.  Ranks are globally consecutive across batches (the sequencer
/ ingress drain order), so the validation stays in rank space and the
pipelined stream's stores, fingerprints, traces and ``replay_log()``
are bit-identical to the serial ``D=0`` run by construction (asserted
in tests/test_pipeline.py and ``scripts/ci.sh --pipeline-smoke``); the
speculation cost is surfaced only in the new ``ExecTrace.spec_*``
observables.  ``D=0`` (default) is exactly the pre-PR path.  Since
PR 10 every registry engine has a seeded entry point (``raw_spec``),
so pipelining covers all four; an out-of-registry engine registered
without one still silently serves the (bit-identical) serial path.

**Crash-consistent checkpoints** (PR 9): ``snapshot(dir, pool=...)`` /
``PotSession.restore(dir, arrival_journal=...)`` round-trip the complete
resumable state — store image, ``gv``, sequencer cursor, submit / formed
counters, bucket bookkeeping, replay log, elastic lane-manager state,
and the ingress journal cursor — through the atomic, self-verifying
snapshot format of :mod:`repro.core.checkpoint`.  The recovery
invariant: restore(latest snapshot) + drain(arrival-journal suffix) is
bit-identical to the uninterrupted stream at any snapshot point, any
drain-budget schedule, any ``pipeline_depth`` (the speculative window
is flushed into the snapshot, never persisted speculatively).

Every engine runs through the same ``submit`` — there is no per-engine
signature anywhere above this layer.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import protocol
from repro.core.engine import EngineDef, ExecTrace, get_engine
from repro.core.sequencer import ReplaySequencer, RoundRobinSequencer
from repro.core.tstore import TStore, make_store, shard_store
from repro.core.tstore import fingerprint as store_fingerprint
from repro.core.txn import TxnBatch, next_pow2, pad_batch

# per-transaction ExecTrace fields, sliced back to the real K after a
# bucketed submit (everything else is scalar or per-round)
_PER_TXN_FIELDS = ("commit_round", "commit_pos", "first_round", "retries",
                   "mode", "wait_rounds")


def dense_bucket(k: int) -> int:
    """The denser small-K bucket ladder (ROADMAP open item): {1, 2, 4, 8}
    below 8, then multiples of 8 — serving tails with many mid-size
    batches pad to the next 8 instead of the next power of two (e.g.
    K=17 runs at 24, not 32), trading a few more compiled rungs for
    much less vacant-row padding per batch."""
    if k <= 8:
        return next_pow2(k)
    return -(-k // 8) * 8


@functools.lru_cache(maxsize=None)
def _jitted_step(engine_name: str, donate: bool):
    """One compiled step per (engine, donation) — shared by all sessions
    so repeated sessions reuse compilation caches."""
    eng = get_engine(engine_name)
    return jax.jit(eng.raw, static_argnums=(4,),
                   donate_argnums=(0,) if donate else ())


@functools.lru_cache(maxsize=None)
def _jitted_spec_step(engine_name: str, donate: bool):
    """The seeded twin of :func:`_jitted_step` (``eng.raw_spec``): same
    donation, the extra trailing ``seed`` argument traced."""
    eng = get_engine(engine_name)
    return jax.jit(eng.raw_spec, static_argnums=(4,),
                   donate_argnums=(0,) if donate else ())


# the speculative round-0 step: reads the store, never donates it — the
# same buffers are consumed later by the real (seeded) step
_spec_execute_step = jax.jit(protocol.spec_execute)


class PotSession:
    """Deterministic transactional execution over a stream of batches.

    Args:
      n_objects: size of a fresh store (ignored if ``store`` is given).
      slot / init: forwarded to :func:`make_store` for the fresh store.
      store: an existing TStore to adopt.  The session takes ownership:
        with ``donate=True`` its buffers are consumed by the first step.
      engine: engine name (``"pcc"`` / ``"pogl"`` / ``"destm"`` /
        ``"occ"``, ``"pot"`` aliases ``"pcc"``) or an
        :class:`~repro.core.engine.EngineDef`.
      sequencer: any object with ``order_for(keys) -> (K,) seq numbers``;
        defaults to a ``RoundRobinSequencer`` over ``n_lanes`` lanes.
      n_lanes: lane count (round-robin width, DeSTM round width).
      donate: donate the store buffers to the jitted step (in-place
        update on backends that support it).
      bucket: pad ragged batch shapes up to power-of-two buckets with
        vacant NOP rows so the jitted step compiles per bucket, not per
        exact shape (bit-identical outcome; see the module docstring).
        False submits exact shapes (one compile each — the pre-PR4
        behavior, kept for benchmarking the recompile cost).
      bucket_ladder: the K-axis bucket family.  ``"pow2"`` (default)
        rounds K up to the next power of two; ``"dense"`` uses the
        denser serving-tail ladder {1, 2, 4, 8} ∪ multiples of 8
        (ROADMAP open item) — less padding waste per small/mid batch at
        the cost of more rungs (compile count still bounded by the
        ladder size; asserted in tests).  The L axis always buckets to
        powers of two.
      shards: partition the store's address space into S contiguous
        range shards (:class:`~repro.core.tstore.ShardedStore`):
        per-shard conflict analysis and S independent write-back
        scatters, with every commit decision still taken in global rank
        space — fingerprints, traces and ``replay_log()`` are
        bit-identical to ``shards=1`` (the dense store).
      mesh: optional 1-axis ``jax.sharding.Mesh`` of exactly ``shards``
        devices (``repro.launch.mesh.make_host_mesh(S, "shard")``); when
        given, each device holds one shard and runs that shard's
        write-back scatter and conflict kernels under ``jax.shard_map``.
        The mesh travels on the store pytree as a static field, so it
        threads through the cached jitted step with no signature change.
      pipeline_depth: speculate up to D batches ahead of the committed
        store in ``run_stream`` / ``serve`` (cross-batch pipelining —
        see the module docstring).  Bit-identical to the serial stream
        for any D; 0 (default) is exactly the pre-PR serial path, as is
        any out-of-registry engine without a seeded entry point
        (``raw_spec is None`` — all four registry engines have one).
    """

    def __init__(self, n_objects: int | None = None, *, slot: int = 1,
                 init=None, store: TStore | None = None,
                 engine: str | EngineDef = "pcc", sequencer=None,
                 n_lanes: int = 1, donate: bool = True,
                 bucket: bool = True, bucket_ladder: str = "pow2",
                 shards: int = 1, mesh=None, pipeline_depth: int = 0,
                 elastic=None):
        if store is None:
            if n_objects is None:
                raise ValueError("PotSession needs n_objects or store")
            store = make_store(n_objects, slot=slot, init=init,
                               shards=shards, mesh=mesh)
        elif shards > 1 or mesh is not None:
            if not isinstance(store, TStore):
                raise ValueError(
                    "pass either an already-sharded store OR shards=/"
                    "mesh= with a dense store, not both")
            store = shard_store(store, shards, mesh=mesh)
        if bucket_ladder not in ("pow2", "dense"):
            raise ValueError(
                f"bucket_ladder must be 'pow2' or 'dense', "
                f"got {bucket_ladder!r}")
        self.bucket_ladder = bucket_ladder
        self.store = store
        self.engine = engine if isinstance(engine, EngineDef) \
            else get_engine(engine)
        self.n_lanes = n_lanes
        self.sequencer = sequencer if sequencer is not None \
            else RoundRobinSequencer(n_root_lanes=n_lanes)
        self.bucket = bucket
        self._step = _jitted_step(self.engine.name, donate)
        if pipeline_depth < 0:
            raise ValueError("pipeline_depth must be >= 0")
        self.pipeline_depth = pipeline_depth
        # pipelining needs the engine's seeded entry point; without one
        # the session silently serves the (bit-identical) serial path
        self._pipelined = (pipeline_depth > 0
                           and self.engine.raw_spec is not None)
        self._spec_step = (_jitted_spec_step(self.engine.name, donate)
                           if self._pipelined else None)
        # speculation window: (batch, seq, lane_ids, seed, k, bk) tuples
        # enqueued ahead of the committed store, oldest first
        self._window: list[tuple] = []
        self.traces: list[ExecTrace] = []
        # replay log cache, materialized lazily (device->host sync happens
        # in replay_log(), never on the hot submit path)
        self._log: list[int] = []
        self._log_batches = 0      # traces already folded into _log
        self._log_txns = 0         # Σ n_txns of those traces (id offset)
        self._n_txns = 0
        # compile-cache observables: step shapes this session triggered
        # (one XLA compile each) and batches submitted per bucket
        self._bucket_counts: dict[tuple[int, int], int] = {}
        # elastic worker pool (runtime.elastic.ElasticLaneManager or
        # None): scaling events apply at formed-batch boundaries and
        # client lanes map onto live worker lanes — snapshot-visible
        # state, so a restored replica numbers lanes identically
        self.elastic = elastic
        # failover bookkeeping (PR 9): formed-batch cursor (the budget-
        # schedule index a restored replica re-enters at), snapshot
        # chain state, and the restore observables the metrics CSV
        # surfaces (snapshots_taken / restored_from / recovery_batches)
        self._batches_formed = 0
        self.snapshots_taken = 0
        self.restored_from = -1       # snapshot id, or -1 (never restored)
        self._chain_digest = ""       # last committed snapshot's chain
        self._next_snapshot_id = 0

    # ------------------------------------------------------------- stream
    def _bucket_shape(self, batch: TxnBatch,
                      ladder: str | None = None) -> tuple[int, int]:
        """The (K, L) step shape a batch runs at: the exact shape when not
        bucketing, else K rounded up along the bucket ladder (pow2, or
        the denser {1, 2, 4, 8} ∪ 8·n serving ladder) and L to the next
        power of two.  ``ladder`` overrides the session default per batch
        (the ingress pool's occupancy-driven recommendation)."""
        if not self.bucket:
            return batch.n_txns, batch.max_ins
        ladder = ladder if ladder is not None else self.bucket_ladder
        return (dense_bucket(batch.n_txns) if ladder == "dense"
                else next_pow2(batch.n_txns)), next_pow2(batch.max_ins)

    def submit(self, batch: TxnBatch, lanes: Sequence | None = None
               ) -> ExecTrace:
        """Sequence and execute one batch against the session store.

        ``lanes`` is the per-txn sequencing key — lane ids for the
        round-robin sequencer, txn names for an ``ExplicitSequencer``,
        ignored by a ``ReplaySequencer``.  Defaults to one lane.

        With bucketing on, the batch is padded to its shape bucket with
        vacant NOP rows (sequence numbers past every real one, so they
        never commit) before hitting the jitted step; the returned trace
        is sliced back to the batch's real K rows.
        """
        k = batch.n_txns
        keys = list(lanes) if lanes is not None else [0] * k
        if len(keys) != k:
            raise ValueError(f"batch has {k} txns, got {len(keys)} lanes")
        # submit is synchronous (returns THIS batch's trace), so any
        # speculation window left pending must execute first — order is
        # the sequencer's.  run_stream/serve always flush before
        # returning, so this is a no-op there.
        self._spec_flush()
        seq = np.asarray(self.sequencer.order_for(keys), np.int64)
        return self._submit_seq(batch, seq, self._lane_ids(keys))

    def _prepare(self, batch: TxnBatch, seq: np.ndarray,
                 lane_ids: np.ndarray, ladder: str | None = None):
        """Bucket accounting + vacant-row padding for one batch, shared
        by the serial step and the speculative enqueue: pads the batch
        to its (K, L) bucket and extends ``seq`` / ``lane_ids`` over the
        vacant rows (sequence numbers past every real one).  Returns
        ``(batch, seq, lane_ids, k, bk)`` with k the real row count.
        The padding runs in the profiler span ``pot.step.pad`` (stats
        ``k`` and ``bk``), the bookkeeping of :meth:`_record` in
        ``pot.step.record``."""
        k = batch.n_txns
        bk, bl = self._bucket_shape(batch, ladder)
        with jax.profiler.TraceAnnotation("pot.step.pad", k=k, bk=bk):
            seq = np.asarray(seq, np.int64)
            lane_ids = np.asarray(lane_ids, np.int64) % max(self.n_lanes, 1)
            self._bucket_counts[(bk, bl)] = \
                self._bucket_counts.get((bk, bl), 0) + 1
            if (bk, bl) != (k, batch.max_ins):
                batch = pad_batch(batch, bk, bl)
                base = seq.max() if k else 0
                seq = np.concatenate([seq, base + 1 + np.arange(bk - k)])
                lane_ids = np.concatenate(
                    [lane_ids, np.zeros((bk - k,), lane_ids.dtype)])
        return batch, seq, lane_ids, k, bk

    def _record(self, trace: ExecTrace, k: int, bk: int) -> ExecTrace:
        """Post-step bookkeeping: slice vacant rows back off and append
        the trace (kept on device — the commit order is recorded by
        keeping the trace, and replay_log() materializes it on demand,
        so no device->host sync on the streaming hot path)."""
        with jax.profiler.TraceAnnotation("pot.step.record"):
            if bk != k:   # slice vacant rows back off (lazy device ops)
                trace = dataclasses.replace(trace, **{
                    f: getattr(trace, f)[:k] for f in _PER_TXN_FIELDS})
            self._n_txns += k
            self.traces.append(trace)
        return trace

    def _submit_seq(self, batch: TxnBatch, seq: np.ndarray,
                    lane_ids: np.ndarray,
                    ladder: str | None = None) -> ExecTrace:
        """The core of ``submit`` with the sequence numbers already
        assigned — the entry point for batch formers that ARE the
        sequencer (the ingress pool's drain order): ``seq`` ranks the
        rows, ``lane_ids`` are engine-facing lanes (reduced mod
        ``n_lanes``), ``ladder`` optionally overrides the session's
        bucket family for this batch."""
        batch, seq, lane_ids, k, bk = self._prepare(batch, seq, lane_ids,
                                                    ladder)
        self.store, trace = self._step(
            self.store, batch, jnp.asarray(seq, jnp.int32),
            jnp.asarray(lane_ids, jnp.int32), self.n_lanes)
        return self._record(trace, k, bk)

    # ------------------------------------------ cross-batch speculation
    def _spec_enqueue(self, batch: TxnBatch, seq: np.ndarray,
                      lane_ids: np.ndarray,
                      ladder: str | None = None) -> None:
        """Speculatively execute one batch's round 0 against the CURRENT
        store image (a pure read — the store buffers stay owned by the
        pending window's drains) and append it to the window."""
        batch, seq, lane_ids, k, bk = self._prepare(batch, seq, lane_ids,
                                                    ladder)
        seed = _spec_execute_step(self.store, batch)
        self._window.append((batch, seq, lane_ids, seed, k, bk))

    def _spec_drain(self) -> ExecTrace:
        """Execute the window's oldest batch for real: the engine's
        seeded step validates the speculation against the now-current
        store and re-executes only invalidated rows."""
        batch, seq, lane_ids, seed, k, bk = self._window.pop(0)
        self.store, trace = self._spec_step(
            self.store, batch, jnp.asarray(seq, jnp.int32),
            jnp.asarray(lane_ids, jnp.int32), self.n_lanes, seed)
        return self._record(trace, k, bk)

    def _spec_flush(self) -> list[ExecTrace]:
        """Drain the whole speculation window (stream end / before any
        synchronous submit)."""
        out = []
        while self._window:
            out.append(self._spec_drain())
        return out

    def _serve_formed(self, fb, ladder: str | None = None
                      ) -> list[ExecTrace]:
        """Execute one ingress-formed batch (the unit step of ``serve``
        and of the failover replica loop in ``repro.core.checkpoint``).

        Advances the elastic lane manager to this formed-batch boundary
        (scaling events are positions in the order — a restored replica
        re-applies them identically) and maps client lanes onto live
        worker lanes; bumps the formed-batch cursor; then submits —
        through the speculation window when pipelined.  Returns the
        traces completed by this step (possibly none while the window
        fills).  The step runs in the profiler span ``pot.step``, whose
        stat ``batch`` is the formed batch's first sequence number, the
        same as on the drain's ``pot.drain`` span."""
        with jax.profiler.TraceAnnotation("pot.step", batch=int(fb.seq[0])):
            fb_ladder = ladder if ladder is not None else fb.ladder
            lanes = fb.lanes
            if self.elastic is not None:
                self.elastic.advance_to(self._batches_formed + 1)
                lanes = np.asarray([self.elastic.worker_for(int(l))
                                    for l in np.asarray(fb.lanes)], np.int64)
            self._batches_formed += 1
            if self._pipelined:
                self._spec_enqueue(fb.batch, fb.seq, lanes, ladder=fb_ladder)
                out = []
                while len(self._window) > self.pipeline_depth:
                    out.append(self._spec_drain())
                return out
            return [self._submit_seq(fb.batch, fb.seq, lanes,
                                     ladder=fb_ladder)]

    def serve(self, pool, budget: int = 64, *,
              max_batches: int | None = None,
              ladder: str | None = None, elastic=None) -> list[ExecTrace]:
        """Drain an :class:`~repro.core.ingress.IngressPool` through the
        session until it is empty (or ``max_batches``): the deterministic
        ingress serve loop.

        Each iteration asks the pool to *form* the next batch
        (``pool.drain(budget)``) and executes it.  The pool's drain
        order IS the preordered sequence — the formed batch carries its
        own globally consecutive sequence numbers, so the session's
        sequencer is neither consulted nor advanced.  The (K, L) shape
        bucket follows the pool's occupancy-driven ladder recommendation
        (``FormedBatch.ladder``) unless ``ladder`` pins one, closing the
        bucket auto-selection loop: mid-size drain tails steer the step
        shapes to the dense ladder, pow2-ish drains to pow2 — with
        bit-identical commits either way (padding is vacant rows).

        Two replica sessions serving pools fed the same arrival journal
        emit bit-identical stores, fingerprints and ``replay_log()``s
        for ANY budget schedules that drain the same prefix — and for
        any ``pipeline_depth`` (speculation changes when work runs, not
        what commits; the window drains fully before returning).

        ``elastic`` optionally attaches an
        :class:`~repro.runtime.elastic.ElasticLaneManager`: worker
        join/leave events apply at formed-batch boundaries and client
        lanes map onto live worker lanes (sequenced, snapshot-visible
        scaling — see ``_serve_formed``).
        """
        if elastic is not None:
            self.elastic = elastic
        traces: list[ExecTrace] = []
        formed = 0
        while max_batches is None or formed < max_batches:
            fb = pool.drain(budget)
            if fb is None:
                break
            formed += 1
            traces.extend(self._serve_formed(fb, ladder=ladder))
        traces.extend(self._spec_flush())
        return traces

    def run_stream(self, batches: Iterable[TxnBatch],
                   lanes: Sequence[Sequence] | None = None
                   ) -> list[ExecTrace]:
        """Submit a whole stream of batches; returns one trace each.

        The stream may be ragged — batches of arbitrary (K, L) shapes —
        and still compiles at most one step per shape bucket (the
        bucketed ``submit`` path; ``compile_count()`` proves it).

        With ``pipeline_depth=D >= 1`` this is the pipelined loop: each
        batch speculates against the current store at enqueue time and
        the window drains once it exceeds D — bit-identical traces in
        the same (submission) order, with the overlap surfaced in the
        ``spec_*`` trace fields."""
        batches = list(batches)
        lanes_list = list(lanes) if lanes is not None \
            else [None] * len(batches)
        if len(lanes_list) != len(batches):
            raise ValueError(
                f"{len(batches)} batches but {len(lanes_list)} lane lists")
        if not self._pipelined:
            return [self.submit(b, l) for b, l in zip(batches, lanes_list)]
        traces: list[ExecTrace] = []
        for b, l in zip(batches, lanes_list):
            k = b.n_txns
            keys = list(l) if l is not None else [0] * k
            if len(keys) != k:
                raise ValueError(
                    f"batch has {k} txns, got {len(keys)} lanes")
            seq = np.asarray(self.sequencer.order_for(keys), np.int64)
            self._spec_enqueue(b, seq, self._lane_ids(keys))
            while len(self._window) > self.pipeline_depth:
                traces.append(self._spec_drain())
        traces.extend(self._spec_flush())
        return traces

    def _lane_ids(self, keys) -> np.ndarray:
        """Engine-facing lane array: numeric keys mod n_lanes; symbolic
        sequencing keys (e.g. ExplicitSequencer names) map to lane 0."""
        try:
            ids = np.asarray(keys, dtype=np.int64)
        except (TypeError, ValueError):
            return np.zeros((len(keys),), np.int64)
        return ids % max(self.n_lanes, 1)

    # ------------------------------------------------------ introspection
    @property
    def n_txns(self) -> int:
        """Transactions committed by this session so far."""
        return self._n_txns

    @property
    def gv(self) -> int:
        """Global version = sequence number of the last commit."""
        return int(self.store.gv)

    @property
    def batches_formed(self) -> int:
        """Ingress-formed batches executed (or enqueued) by this session
        — the deterministic cursor a restored replica re-enters its
        budget/snapshot/scaling schedules at."""
        return self._batches_formed

    @property
    def recovery_batches(self) -> int:
        """Batches this session executed SINCE restoring from a
        snapshot (0 for a session that never restored) — the recovery-
        cost observable in the metrics CSV."""
        return len(self.traces) if self.restored_from >= 0 else 0

    # --------------------------------------------------- crash recovery
    def snapshot(self, directory: str, *, pool=None,
                 _torn_hook=None) -> str:
        """Commit one crash-consistent snapshot of this session (and the
        ingress ``pool`` feeding it) under ``directory`` — the complete
        resumable state, written atomically and self-verifying; the
        speculative window is flushed first (never persisted
        speculatively).  Returns the committed snapshot path.  See
        :func:`repro.core.checkpoint.save_snapshot`."""
        from repro.core import checkpoint as _ckpt
        return _ckpt.save_snapshot(self, directory, pool=pool,
                                   _torn_hook=_torn_hook)

    @classmethod
    def restore(cls, directory: str, **overrides
                ) -> "tuple[PotSession, object]":
        """Rebuild ``(session, pool)`` from the newest complete snapshot
        under ``directory`` (self-verified before serving); restoring
        mid-stream and draining the remaining arrival-journal suffix is
        bit-identical to the uninterrupted run.  Keyword overrides
        (``step=``, ``arrival_journal=``, ``shards=``, ``engine=``,
        ``bucket_ladder=``, ``pipeline_depth=``, ...) pass through to
        :func:`repro.core.checkpoint.restore_session`."""
        from repro.core import checkpoint as _ckpt
        return _ckpt.restore_session(directory, **overrides)

    def fingerprint(self) -> int:
        """Order-sensitive hash of the committed store image."""
        return int(store_fingerprint(self.store))

    def compile_count(self) -> int:
        """Distinct compiled step shapes this session has triggered — each
        one is an XLA compilation of the engine step.  With bucketing this
        is bounded by the bucket-ladder size regardless of how ragged the
        stream is; without it, every distinct (K, L) compiles.  (Shapes
        already compiled by an earlier same-engine session are served from
        jit's cache, so this is an upper bound on compiles actually paid.)
        """
        return len(self._bucket_counts)

    def bucket_counts(self) -> dict[tuple[int, int], int]:
        """Batches submitted per (K, L) step-shape bucket — the occupancy
        observable behind :meth:`compile_count`."""
        return dict(self._bucket_counts)

    def replay_log(self) -> list[int]:
        """Global commit order across the whole stream: entry i is the
        global txn id (batch offset + index) that committed i-th.

        Materialized lazily from the recorded traces (this is where the
        device->host sync happens); incremental, so repeated calls only
        pay for batches submitted since the last call.  Rows with
        ``commit_pos < 0`` (vacant bucket padding / uncommitted) are not
        part of the history and are skipped."""
        for trace in self.traces[self._log_batches:]:
            # global txn ids offset by the txns of all PRIOR batches (not
            # by log length: a batch can log fewer entries than its k if
            # rows never committed, and ids must not shift)
            offset = self._log_txns
            cp = np.asarray(trace.commit_pos)
            order = np.argsort(cp, kind="stable")
            order = order[cp[order] >= 0]
            self._log.extend(int(t) + offset for t in order)
            self._log_batches += 1
            self._log_txns += trace.n_txns
        return list(self._log)

    def live_counts(self) -> list[np.ndarray]:
        """Per-round live (re-executed) transaction counts, one array per
        submitted batch, trimmed to the rounds each batch actually ran.

        The observable behind the incremental round loop (PR 3): at low
        contention the counts collapse after round 0 because committed
        transactions stop re-executing; engines that predate the
        RoundState loop (legacy scans) return empty arrays.  Host-syncs
        the recorded traces — keep off the streaming hot path.
        """
        return [t.live_counts() for t in self.traces]

    def wave_counts(self) -> list[np.ndarray]:
        """Per-round retry-wave counts, one array per submitted batch,
        trimmed to the rounds each batch actually ran.

        The observable behind DeSTM's wave-speculative retries (PR 10):
        every wave trip re-executes ALL of a round's conflicting members
        and commits the maximal provably-serial token prefix, so the
        per-round wave counts sit at or below the serial walk's retry
        events (equality only on fully serial conflict chains).  Engines
        that do not record waves return empty arrays.  Host-syncs the
        recorded traces — keep off the streaming hot path.
        """
        return [t.wave_counts() for t in self.traces]

    def replay_sequencer(self) -> ReplaySequencer:
        """A sequencer that replays this session's commit order — feed it
        to a fresh ``PotSession`` with the same batches (paper §2.1)."""
        return ReplaySequencer(self.replay_log())
