"""Shared pieces of the concurrency-control engines.

Three layers:

**Scalar helpers** (`footprint_conflicts` / `mark_writes` /
`apply_writes`) — the per-transaction primitives used by the serial
paths (PoGL, PCC live promotion, DeSTM token-held retries) and by the
preserved scan engines in :mod:`repro.core.legacy_scan`.

**Incremental round state** (PR 3) — :class:`RoundState`, the
persistent execution state every engine threads through its
`lax.while_loop` rounds instead of rebuilding from scratch:

* the committed store image (``values`` / ``versions``);
* the cached per-transaction :class:`~repro.core.txn.TxnResult` —
  :func:`refresh_round_state` re-executes only the *live* rows
  (uncommitted/aborted transactions, via
  :func:`repro.core.txn.run_live`) and keeps the settled rows' cached
  results, so a low-contention round no longer pays a full-batch
  ``run_all`` on already-committed transactions;
* the carried conflict structure — the K×K ``conflict`` table: only
  the rows/columns of re-executed transactions are recomputed per
  round, on TPU by a masked Pallas kernel over the round's address
  lists (``kernels.conflict.conflict_matrix_bits_addr_delta``; on a
  sharded store over per-shard bit-packed footprints carried beside
  it); dense recompute-and-select fallback off-TPU.

Correctness rests on one invariant: an engine's commit decision only
ever *consumes* conflict entries and footprint rows of transactions
that are still pending — and every pending transaction is live, hence
refreshed.  Settled rows go stale in the cache but are masked out of
every reduction, so the incremental loop is bit-identical to the
from-scratch rebuild (``incremental=False`` on every engine, asserted
by tests and by ``scripts/ci.sh --incremental-smoke``).

**Gather-compacted rounds** (PR 4) — the masked executor still walks the
full static (K, L) grid even when only a handful of rows are live
(shapes are static under jit).  Engines therefore run their round loop
as a *cascade* over :func:`compact_ladder` widths: once the live set
fits a narrower rung C, the read phase gathers the live rows into a
(C, L) block, executes that
(:func:`refresh_round_state_compact` / the caller-ordered
:func:`refresh_round_state_gathered`), and scatters results — plus the
conflict table's refreshed row/column strips
(``kernels.ops.conflict_matrix_delta_compact_addrs``, two rectangular
strips instead of a K×K pass; on a sharded store also the per-shard
packed-footprint rows) — back to full-K positions.  Commit decisions
and the fused write-back stay in rank space, so the cascade is
bit-identical to the masked loop (``compact=False``; asserted by tests
and ``scripts/ci.sh --compact-smoke``); only the device work changes,
from K·L to C·L per round (``RoundState.walked_slots`` / ``ExecTrace.walked_slots``).
DeSTM's ≤ n_lanes rounds are the degenerate always-compact case: its
members run through :func:`refresh_round_state_gathered` in token
order at width n_lanes.  Vacant rows (``n_ins == 0`` — shape-bucket
padding from ``PotSession.submit``) never enter a live set and never
commit; :func:`prefix_commit` takes the ``real`` mask to enforce it.

**Shard-partitioned stores** (PR 5) — every function in this module is
layout-polymorphic over :class:`repro.core.tstore.StoreLayout`: with
the store partitioned into S contiguous range shards
(:class:`~repro.core.tstore.ShardedStore`), the read phase executes
against the flat view of the stacked shards (bit-identical — padding
rows are never addressed), the conflict analysis decomposes per shard
— (S, K, ceil(C/32)) packed footprints, per-shard tables OR-reduced
into the carried K×K ``conflict`` (kernels/ops.py ``*_sharded`` twins
of the full, masked-delta and compact-strip paths) — and
:func:`fused_write_back` splits into S *independent* scatters (one per
device under ``jax.shard_map`` when the layout carries a mesh, a vmap
over the shard axis otherwise).  The invariant making S a
pure layout knob: conflict(t, u) == OR over shards of per-shard
conflicts, and every commit decision stays in global rank space — so
sharded runs are bit-identical to dense ones (tests/
test_sharded_store.py, ``scripts/ci.sh --shard-smoke``).

**Vectorized commit pipeline** (PR 2) — the batched commit machinery
shared by PCC / OCC / DeSTM.  Instead of walking K transactions through
a `lax.scan` with an O(n_objects) bitmap probe and a `lax.cond`
write-back each (K sequential device steps per round), a round is three
batched stages:

1. conflict analysis — the carried ``RoundState.conflict`` table
   (:func:`conflict_table` builds the from-scratch equivalent);
2. a commit *decision* — :func:`prefix_commit` (the maximal in-order
   prefix, an `associative_scan` cumulative-AND: ≤⌈log₂K⌉ device
   steps) or :func:`wave_commit` (OCC's greedy arrival-order kernel, a
   fixpoint that converges in the conflict-chain depth, one batched
   step per iteration; its trip count is surfaced in
   ``ExecTrace.wave_trips``).  Both consume
   :func:`earlier_writer_conflicts`, which answers "does position p's
   footprint hit the writes of a marked position q < p" either as a
   masked row-reduction of the conflict matrix (TPU: regular,
   VPU-friendly, exactly the dense-bitset argument of validate.py) or
   as a first-writer-per-address scatter-min + gather (O(K·L) work —
   the right trade on backends where irregular gathers are cheap and
   K² dense work is not).  The two formulations are decision-identical
   (asserted in tests);
3. :func:`fused_write_back` — every committing transaction's deferred
   writes installed in ONE flattened scatter, the winner per address
   selected by (commit-position, write-slot) segment-max, subsuming
   both the per-transaction apply chain and per-transaction
   last-writer dedup.

All stages reproduce the scan engines' decisions bit-exactly
(tests/test_commit_pipeline.py asserts equality against
`legacy_scan` and a pure-NumPy reference on random batches).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core.tstore import StoreLayout, flat_values
from repro.core.txn import (TxnBatch, TxnResult, gather_live_indices,
                            next_pow2, run_compact, run_live,
                            scatter_result, scatter_rows)
from repro.kernels import ops as kernel_ops


def footprint_conflicts(written: jax.Array, raddrs, rn, waddrs, wn) -> jax.Array:
    """Does this txn's footprint overlap ``written`` (O,) bool?

    This is the validation step (paper Fig. 2b line 9): a read-write or
    write-write overlap with a transaction that committed after our read
    phase means the speculation is stale.
    """
    length = raddrs.shape[0]
    idx = jnp.arange(length)
    r_hit = jnp.any(jnp.where(idx < rn, written[raddrs], False))
    w_hit = jnp.any(jnp.where(idx < wn, written[waddrs], False))
    return r_hit | w_hit


def mark_writes(written: jax.Array, waddrs, wn) -> jax.Array:
    """written |= this txn's write set."""
    length = waddrs.shape[0]
    n_obj = written.shape[0]
    tgt = jnp.where(jnp.arange(length) < wn, waddrs, n_obj)
    return written.at[tgt].set(True, mode="drop")


def dedup_last_writer(waddrs, wn):
    """Mask selecting, per address, only the LAST write-set entry (a txn may
    write the same object twice; the later deferred write must win).

    Sort-based O(F log F): order the slots by address (stable, so equal
    addresses keep slot order) and keep a slot iff it is valid and the
    next slot in sorted order holds a different address.
    """
    length = waddrs.shape[0]
    idx = jnp.arange(length)
    valid = idx < wn
    # invalid slots sort behind every real address (addresses are object
    # ids, far below int32 max)
    key = jnp.where(valid, waddrs, jnp.iinfo(jnp.int32).max)
    order = jnp.argsort(key)
    sorted_key = key[order]
    nxt = jnp.concatenate([sorted_key[1:],
                           jnp.full((1,), -1, sorted_key.dtype)])
    last_of_run = sorted_key != nxt
    keep = jnp.zeros((length,), bool).at[order].set(last_of_run)
    return valid & keep


def _dedup_last_writer_reference(waddrs, wn):
    """Pre-PR2 all-pairs O(F²) formulation, kept as the behavioral oracle
    for :func:`dedup_last_writer` (tests/test_commit_pipeline.py)."""
    length = waddrs.shape[0]
    idx = jnp.arange(length)
    valid = idx < wn
    shadowed = (
        (waddrs[None, :] == waddrs[:, None])
        & (idx[None, :] > idx[:, None])
        & valid[None, :]
    ).any(axis=1)
    return valid & ~shadowed


def apply_writes(values, versions, waddrs, wvals, wn, seq_no,
                 layout: StoreLayout | None = None):
    """Write-back one committing txn: install deferred values and stamp the
    objects' versions with the txn's sequence number (paper §3.1: sequence
    numbers retrofitted as TL2 versions).

    Under a sharded ``layout`` the scatter splits per shard: address a
    lands in shard a // C at offset a % C — same values, same winners
    (a transaction's deduped writes hit distinct addresses), hence
    bit-identical to the dense scatter.
    """
    keep = dedup_last_writer(waddrs, wn)
    if layout is not None and layout.sharded:
        shard = jnp.where(keep, layout.shard_of(waddrs), layout.shards)
        off = layout.offset_of(waddrs)
        values = values.at[shard, off].set(wvals, mode="drop")
        versions = versions.at[shard, off].set(seq_no, mode="drop")
        return values, versions
    n_obj = values.shape[0]
    tgt = jnp.where(keep, waddrs, n_obj)
    values = values.at[tgt].set(wvals, mode="drop")
    versions = versions.at[tgt].set(seq_no, mode="drop")
    return values, versions


# --------------------------------------------------------------------------
# Vectorized commit pipeline
# --------------------------------------------------------------------------
#
# Everything below works in TRANSACTION space (storage order), with the
# serialization order threaded through as ``rank`` — rank[t] = the
# sequence position of txn t (engines compute it once per batch via
# engine.rank_from_order).  Staying in txn space keeps the hot per-round
# path free of (K, L) permutation gathers: the only order-dependent
# arrays are (K,) rank comparisons.


def _matrix_backend() -> bool:
    # one dispatch predicate shared with the kernel wrappers
    return kernel_ops._on_tpu()


def conflict_table(res, n_objects: int,
                   use_matrix: bool | None = None) -> jax.Array | None:
    """The round's K×K footprint-vs-write-set conflict matrix, in txn
    space: entry (i, j) = footprint(i) ∩ writes(j) ≠ ∅ (the paper's
    per-txn validation question asked for all ordered pairs at once).

    Materialized only on TPU, where a Pallas kernel
    (`kernels/conflict.py`) answers every pair in one regular pass of
    K²·2L·L address compares (the bit-packed form it replaced cost K²·W
    word-ANDs, W = ceil(O/32)).  Returns ``None`` elsewhere —
    :func:`earlier_writer_conflicts` then uses the first-writer
    scatter-min formulation, which gives identical verdicts with O(K·L)
    work (asserted in tests/test_commit_pipeline.py).
    """
    if use_matrix is None:
        use_matrix = _matrix_backend()
    if not use_matrix:
        return None
    return kernel_ops.conflict_matrix(
        res.raddrs, res.rn, res.waddrs, res.wn, n_objects)


# --------------------------------------------------------------------------
# Incremental round state (PR 3)
# --------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RoundState:
    """Persistent per-batch execution state threaded through an engine's
    `lax.while_loop` rounds.

    ``res`` caches every transaction's last speculative execution; a
    round calls :func:`refresh_round_state` with the mask of *live*
    (still-pending) transactions and only those rows re-execute — the
    paper's abort-and-retry, restricted to the transactions it actually
    applies to.  ``conflict`` is carried the same way: suffix footprints
    change only via re-execution, so only live rows/columns are
    recomputed — from the address lists in ``res`` (K²·2L·L compares
    at most), or, on a sharded store, from per-shard packed
    ``foot_bits`` / ``write_bits`` carried beside it.  ``live_txns`` /
    ``live_slots`` accumulate the actual re-execution work for the trace
    (the observable proving settled transactions are skipped).

    ``conflict``/``foot_bits``/``write_bits`` are ``None`` when the
    engine uses the scatter-min conflict formulation (off-TPU default)
    or carries no table at all (DeSTM's compact-block rounds), and the
    bitsets alone are ``None`` unless the store is sharded; the choice
    is static per trace, so the pytree structure is while_loop-stable.
    """

    values: jax.Array        # (O, S) committed store image
    versions: jax.Array      # (O,)
    res: TxnResult           # cached speculative executions (K rows)
    conflict: jax.Array | None    # (K, K) carried conflict table
    foot_bits: jax.Array | None   # (S, K, W_s) packed footprints (sharded)
    write_bits: jax.Array | None  # (S, K, W_s) packed write sets (sharded)
    live: jax.Array          # (K,) bool — rows refreshed this round
    live_txns: jax.Array     # () int32 — Σ rounds live count
    live_slots: jax.Array    # () int32 — Σ rounds live instruction slots
    walked_slots: jax.Array  # () int32 — Σ rounds executor width × L (the
    #   device slots the read phase actually walked; K·L per masked
    #   round, C·L per compact round — see ExecTrace.walked_slots)


def init_round_state(batch: TxnBatch, values: jax.Array,
                     versions: jax.Array, *,
                     track_conflict: bool = True,
                     use_matrix: bool | None = None,
                     layout: StoreLayout | None = None) -> RoundState:
    """A fresh RoundState with empty caches.

    ``track_conflict=False`` (DeSTM) carries no table — the engine asks
    its conflict questions on a compacted per-round block instead.
    ``use_matrix`` follows :func:`conflict_table`'s backend dispatch:
    when the scatter-min formulation is in use there is no table to
    carry either.  Cache rows start zeroed; the caller's invariant is
    that every row is refreshed (appears in a ``refresh_round_state``
    live mask) no later than the first round in which it is consumed —
    PCC/OCC satisfy it by making every pending transaction live, DeSTM
    by making exactly the round's members live (a member's row is only
    ever consumed in its own round).

    Under a sharded ``layout`` the conflict analysis is always the
    matrix formulation, partitioned per shard: ``foot_bits`` /
    ``write_bits`` carry (S, K, ceil(C/32)) packed words — each shard's
    bitset spans only its own C-object range — and ``conflict`` carries
    the OR-reduced K×K table the decisions consume (decision-identical
    to both dense formulations; see kernels/ops.py).
    """
    sharded = layout is not None and layout.sharded
    if use_matrix is None:
        use_matrix = _matrix_backend() or sharded
    k, length = batch.opcodes.shape
    slot = values.shape[-1]
    z = jnp.zeros
    res = TxnResult(
        raddrs=z((k, length), jnp.int32), rn=z((k,), jnp.int32),
        waddrs=z((k, length), jnp.int32),
        wvals=z((k, length, slot), jnp.int32), wn=z((k,), jnp.int32))
    conflict = foot_bits = write_bits = None
    if track_conflict and use_matrix:
        conflict = z((k, k), bool)
        if sharded:
            w = layout.words_per_shard
            foot_bits = z((layout.shards, k, w), jnp.int32)
            write_bits = z((layout.shards, k, w), jnp.int32)
    return RoundState(
        values=values, versions=versions, res=res, conflict=conflict,
        foot_bits=foot_bits, write_bits=write_bits,
        live=z((k,), bool), live_txns=z((), jnp.int32),
        live_slots=z((), jnp.int32), walked_slots=z((), jnp.int32))


def refresh_round_state(state: RoundState, batch: TxnBatch,
                        live: jax.Array,
                        layout: StoreLayout | None = None) -> RoundState:
    """One round's incremental read phase: re-execute the live rows
    against the current store image and delta-update the carried
    conflict structure.

    Post-conditions (tests/test_round_state.py):

    * ``res`` rows with ``live`` equal the same rows of a from-scratch
      ``run_all(batch, state.values)``; settled rows are carried
      bit-exactly;
    * ``conflict`` entries (i, j) with ``live[i] or live[j]`` equal the
      from-scratch table built from the merged ``res``; entries between
      two settled transactions keep last round's verdict (they are
      stale but, by the pending ⊆ live invariant, never consumed).

    Under a sharded ``layout``, execution runs against the flat view of
    the stacked shards (bit-identical — see ``tstore.flat_values``) and
    the conflict delta decomposes per shard, OR-reduced into the carried
    K×K table (kernels/ops.py sharded twins).
    """
    sharded = layout is not None and layout.sharded
    n_obj = layout.n_objects if layout is not None \
        else state.values.shape[0]
    res = run_live(batch, flat_values(state.values, layout), live,
                   state.res, n_objects=n_obj)
    conflict, foot_bits, write_bits = (
        state.conflict, state.foot_bits, state.write_bits)
    if conflict is not None:
        if sharded:                 # per-shard bitsets, OR-reduced table
            foot_bits, write_bits = \
                kernel_ops.update_packed_footprints_sharded(
                    foot_bits, write_bits, res.raddrs, res.rn,
                    res.waddrs, res.wn, live, layout)
            conflict = kernel_ops.conflict_matrix_delta_sharded(
                foot_bits, write_bits, conflict, live, layout)
        else:                       # address lists (dense off-TPU)
            conflict = kernel_ops.conflict_matrix_delta_addrs(
                res.raddrs, res.rn, res.waddrs, res.wn, conflict, live,
                n_obj)
    k, length = batch.opcodes.shape
    return RoundState(
        values=state.values, versions=state.versions, res=res,
        conflict=conflict, foot_bits=foot_bits, write_bits=write_bits,
        live=live,
        live_txns=state.live_txns + live.sum(dtype=jnp.int32),
        live_slots=state.live_slots
        + jnp.where(live, batch.n_ins, 0).sum(dtype=jnp.int32),
        walked_slots=state.walked_slots + jnp.asarray(k * length, jnp.int32))


def commit_round_state(state: RoundState, values: jax.Array,
                       versions: jax.Array) -> RoundState:
    """Fold a round's committed store image back into the carried state."""
    return dataclasses.replace(state, values=values, versions=versions)


# --------------------------------------------------------------------------
# Gather-compacted rounds (PR 4)
# --------------------------------------------------------------------------


def compact_ladder(k: int, min_width: int = 8, step: int = 4) -> list[int]:
    """The descending compact widths an engine's round cascade runs at:
    ``[k, p/step, p/step², ...]`` with ``p = next_pow2(k)``, stopping
    above ``min_width`` (where gather/scatter overhead would eat the
    saving).  Rung 0 is the full masked width (round 0's live set is the
    whole batch); each later rung is entered only once the live count
    fits it, so a rung-C round's device work is C·L, not K·L.  Shapes
    are static under jit, hence a *static* ladder of loop bodies rather
    than a per-round dynamic width; its length is O(log K), bounding
    compile cost.
    """
    widths = [k]
    c = next_pow2(k) // step
    while c >= min_width and c < k:
        widths.append(c)
        c //= step
    return widths


def run_compact_cascade(ladder: list[int], state, body_at, cond_at):
    """Drive an engine's round loop down the compact ladder: one
    `lax.while_loop` per rung, where ``body_at(width)`` builds the round
    body executing the read phase at that width and ``cond_at(next_width)``
    builds the loop predicate that additionally hands over to the next
    rung once the live set fits it (``next_width`` is 0 on the last rung —
    no hand-over, run to completion).  The carried ``state`` pytree must
    be rung-independent; only the body internals change width.  Shared by
    PCC and OCC so the hand-over rule lives in exactly one place."""
    for i, width in enumerate(ladder):
        nxt = ladder[i + 1] if i + 1 < len(ladder) else 0
        state = jax.lax.while_loop(cond_at(nxt), body_at(width), state)
    return state


def refresh_round_state_gathered(state: RoundState, batch: TxnBatch,
                                 idx: jax.Array, valid: jax.Array,
                                 layout: StoreLayout | None = None
                                 ) -> tuple[RoundState, TxnResult]:
    """One round's read phase over a caller-gathered compact block: execute
    rows ``batch[idx]`` (``valid`` masks gather padding, possibly with
    duplicate indices) at width C = ``idx.shape[0]`` and scatter the
    results — plus, when a conflict table is carried, the table's
    refreshed row/column strips (and on a sharded store the per-shard
    packed-footprint rows) — back to full-K positions.

    The caller chooses the gather order: :func:`refresh_round_state_compact`
    packs live rows ascending; DeSTM passes its round members in token
    order so the returned compact block feeds the token walk directly.

    Bit-identical post-conditions to :func:`refresh_round_state` with
    ``live = scatter(valid at idx)`` (asserted in
    tests/test_compact_bucket.py): row purity makes the compact execution
    equal the masked one row-for-row, and decisions downstream stay in
    rank space, so they cannot tell the two read phases apart.

    Returns ``(state, cres)`` — the compact (C, L) result block is
    exposed for engines that keep working at width C.
    """
    k, length = batch.opcodes.shape
    width = idx.shape[0]
    sharded = layout is not None and layout.sharded
    n_obj = layout.n_objects if layout is not None \
        else state.values.shape[0]
    cres = run_compact(batch, flat_values(state.values, layout), idx,
                       valid, n_objects=n_obj)
    res = scatter_result(state.res, cres, idx, valid, k)
    live = scatter_rows(jnp.zeros((k,), bool), valid, idx, valid)
    conflict, foot_bits, write_bits = (
        state.conflict, state.foot_bits, state.write_bits)
    if conflict is not None:
        if sharded:                 # per-shard strips, OR-reduced table
            foot_bits, write_bits = \
                kernel_ops.update_packed_footprints_compact_sharded(
                    foot_bits, write_bits, cres.raddrs, cres.rn,
                    cres.waddrs, cres.wn, idx, valid, layout)
            conflict = kernel_ops.conflict_matrix_delta_compact_sharded(
                foot_bits, write_bits, conflict, idx, valid, layout)
        else:                       # address strips (dense off-TPU)
            conflict = kernel_ops.conflict_matrix_delta_compact_addrs(
                res.raddrs, res.rn, res.waddrs, res.wn, conflict, idx,
                valid, n_obj)
    return RoundState(
        values=state.values, versions=state.versions, res=res,
        conflict=conflict, foot_bits=foot_bits, write_bits=write_bits,
        live=live,
        live_txns=state.live_txns + valid.sum(dtype=jnp.int32),
        live_slots=state.live_slots
        + jnp.where(valid, batch.n_ins[idx], 0).sum(dtype=jnp.int32),
        walked_slots=state.walked_slots
        + jnp.asarray(width * length, jnp.int32)), cres


def refresh_round_state_compact(state: RoundState, batch: TxnBatch,
                                live: jax.Array, width: int,
                                layout: StoreLayout | None = None
                                ) -> tuple[RoundState, TxnResult,
                                           jax.Array, jax.Array]:
    """One round's read phase at compact width C = ``width``: gather the
    live rows (ascending index) into a (C, L) block and refresh through
    :func:`refresh_round_state_gathered`.  Requires
    ``live.sum() <= width`` — the caller's rung invariant (engines only
    descend a :func:`compact_ladder` rung once the live count fits it).

    Returns ``(state, cres, idx, valid)``.
    """
    idx, valid = gather_live_indices(live, width)
    state, cres = refresh_round_state_gathered(state, batch, idx, valid,
                                               layout)
    return state, cres, idx, valid


# --------------------------------------------------------------------------
# Cross-batch speculative pipelining (PR 7)
# --------------------------------------------------------------------------
#
# While batch n's tail rounds commit, PotSession executes batch n+1
# against the store image snapshotted at enqueue time (spec_execute),
# capturing the round-0 read phase AND the conflict analysis as a
# SpecSeed.  When batch n+1's turn comes, the engine re-bases the seed
# onto the now-current store (seed_round_state): rows whose read set
# hit an address written after the snapshot (versions > snap_gv — the
# exact dirty predicate, version stamps being globally monotone
# sequence numbers) re-execute through the same compact-ladder
# machinery; every other row's cached result is already bit-identical
# to what a fresh round 0 would produce, because a row's execution is
# a pure function of its read values (read-your-writes is row-local
# and logged in raddrs, so chained indirect reads are covered by
# induction along the read chain).  The engine then charges round 0's
# ordinary work accounting without re-walking it, and everything
# downstream — commit decisions, write-back, trace — is the serial
# computation on bit-identical inputs.  Ranks stay globally consecutive
# across batches, so the validation never leaves rank space.


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SpecSeed:
    """A speculative round-0 execution of one batch against an earlier
    store snapshot: the cached results and conflict structure a seeded
    engine re-bases instead of re-walking (see module section above).
    ``conflict``/``foot_bits``/``write_bits`` mirror
    :class:`RoundState`'s backend-static optionality."""

    res: TxnResult                # (K rows) speculative executions
    conflict: jax.Array | None    # (K, K) speculative conflict table
    foot_bits: jax.Array | None   # packed footprints (sharded)
    write_bits: jax.Array | None  # packed write sets  (sharded)
    snap_gv: jax.Array            # () int32 — store.gv at the snapshot


def spec_execute(store, batch: TxnBatch) -> SpecSeed:
    """Speculatively run ``batch``'s round-0 read phase + conflict
    analysis against ``store``'s current image and capture it as a
    :class:`SpecSeed`.  Pure read — the store is not modified (and the
    session's jit of this function must NOT donate it)."""
    layout = store.layout
    rs = init_round_state(batch, store.values, store.versions,
                          layout=layout)
    rs = refresh_round_state(rs, batch, batch.n_ins > 0, layout)
    return SpecSeed(res=rs.res, conflict=rs.conflict,
                    foot_bits=rs.foot_bits, write_bits=rs.write_bits,
                    snap_gv=store.gv)


def speculation_invalid(res: TxnResult, versions: jax.Array,
                        snap_gv: jax.Array,
                        layout: StoreLayout | None = None) -> jax.Array:
    """(K,) bool — rows whose logged read set touches an address written
    after the snapshot (``versions > snap_gv``).  Read-set-only is
    sound: clean reads replay bit-identically (row purity), and a row's
    own writes need no check — its write set is a function of its reads.
    Conservative only where run_txn logs a read-your-writes read whose
    address happens to be dirty (a false re-execution, never a false
    accept)."""
    if layout is not None and layout.sharded:
        return kernel_ops.spec_read_invalid_sharded(
            res.raddrs, res.rn, versions, snap_gv, layout)
    n_obj = layout.n_objects if layout is not None else versions.shape[0]
    return kernel_ops.spec_read_invalid(res.raddrs, res.rn, versions,
                                        snap_gv, n_obj)


def seed_round_state(batch: TxnBatch, store, seed: SpecSeed,
                     compact: bool = True
                     ) -> tuple[RoundState, jax.Array, jax.Array]:
    """Re-base a :class:`SpecSeed` onto the current store: validate the
    speculated rows, re-execute only the invalidated ones (through the
    compact ladder when they fit a narrow rung), and return a
    RoundState whose ``res``/``conflict``/``foot_bits``/``write_bits``
    are bit-identical to a fresh round-0 refresh of the whole batch
    against ``store`` — with the work counters zeroed, so the engine's
    round 0 can charge its ordinary accounting on top and the trace
    stays bit-identical to the serial run (the re-execution cost is
    surfaced separately, via the returned counts).

    Returns ``(state, n_invalid, spec_rounds)`` — ``spec_rounds`` is 1
    iff any row re-executed, else 0.
    """
    layout = store.layout
    k = batch.n_txns
    rs = init_round_state(batch, store.values, store.versions,
                          layout=layout)
    rs = dataclasses.replace(rs, res=seed.res, conflict=seed.conflict,
                             foot_bits=seed.foot_bits,
                             write_bits=seed.write_bits)
    real = batch.n_ins > 0
    invalid = speculation_invalid(seed.res, store.versions, seed.snap_gv,
                                  layout) & real
    n_inv = invalid.sum(dtype=jnp.int32)
    # exactly-one-rung dispatch over the same ladder the engines cascade
    # down: the narrowest width the invalidated set fits re-executes it
    ladder = compact_ladder(k) if compact else [k]
    for i, width in enumerate(ladder):
        nxt = ladder[i + 1] if i + 1 < len(ladder) else 0
        sel = n_inv > nxt
        if width < k:
            sel = sel & (n_inv <= width)

        def refresh(r, width=width):
            if width >= k:
                return refresh_round_state(r, batch, invalid, layout)
            return refresh_round_state_compact(r, batch, invalid, width,
                                               layout)[0]

        rs = jax.lax.cond(sel, refresh, lambda r: r, rs)
    z = jnp.zeros
    rs = dataclasses.replace(
        rs, live=z((k,), bool), live_txns=z((), jnp.int32),
        live_slots=z((), jnp.int32), walked_slots=z((), jnp.int32))
    return rs, n_inv, (n_inv > 0).astype(jnp.int32)


def charge_round_state(state: RoundState, batch: TxnBatch,
                       live: jax.Array, width: int) -> RoundState:
    """The accounting-only twin of a round-0 refresh at ``width``: set
    the live mask and charge exactly the counters
    :func:`refresh_round_state` (full rung) or
    :func:`refresh_round_state_compact` (``live.sum() <= width``, where
    the gathered ``valid`` count equals ``live.sum()``) would — without
    touching ``res`` or the conflict structure, which a
    :func:`seed_round_state` re-base already made bit-identical."""
    length = batch.opcodes.shape[1]
    return dataclasses.replace(
        state, live=live,
        live_txns=state.live_txns + live.sum(dtype=jnp.int32),
        live_slots=state.live_slots
        + jnp.where(live, batch.n_ins, 0).sum(dtype=jnp.int32),
        walked_slots=state.walked_slots
        + jnp.asarray(width * length, jnp.int32))


def earlier_writer_conflicts(res, conflict, writer_mask: jax.Array,
                             rank: jax.Array, n_objects: int) -> jax.Array:
    """bad (K,) bool, txn space: does txn t's footprint (reads ∪ writes)
    hit the write set of any txn q with ``writer_mask[q]`` that comes
    earlier in the serialization order (rank[q] < rank[t])?

    This is the one conflict question every engine's commit decision
    reduces to (PCC: q pending this round; OCC: q currently committing;
    DeSTM: q a remaining round member).  Two exact formulations:

    * matrix path (``conflict`` present): a masked row-reduction of the
      precomputed K×K matrix — one batched step, perfectly regular (the
      TPU-native choice);
    * scatter path (``conflict`` is None): the *first marked writer per
      address* via one scatter-min over write slots, then a footprint
      gather — O(K·L) work with no K² term (the right trade where
      irregular gathers are cheap).
      ∃ marked q earlier writing address a  ⟺  first_writer[a] < rank.
    """
    if conflict is not None:
        earlier = writer_mask[None, :] & (rank[None, :] < rank[:, None])
        return (conflict & earlier).any(axis=1)
    k, length = res.waddrs.shape
    slot = jnp.arange(length)
    wvalid = (slot[None, :] < res.wn[:, None]) & writer_mask[:, None]
    first_writer = jnp.full((n_objects + 1,), k, jnp.int32).at[
        jnp.where(wvalid, res.waddrs, n_objects)
    ].min(jnp.where(wvalid, rank[:, None], k).astype(jnp.int32))
    rvalid = slot[None, :] < res.rn[:, None]
    r_hit = jnp.where(rvalid, first_writer[res.raddrs], k) < rank[:, None]
    svalid = slot[None, :] < res.wn[:, None]
    w_hit = jnp.where(svalid, first_writer[res.waddrs], k) < rank[:, None]
    return r_hit.any(axis=1) | w_hit.any(axis=1)


def cross_writer_conflicts(reader_res, writer_res, writer_mask: jax.Array,
                           rank: jax.Array, n_objects: int,
                           reads_only: bool = False) -> jax.Array:
    """bad (C,) bool: does reader row t's footprint (or, with
    ``reads_only``, its logged read set alone) hit the write set of a
    writer row q with ``writer_mask[q]`` and ``rank[q] < rank[t]``?

    The two-block generalization of :func:`earlier_writer_conflicts`
    for DeSTM's wave-speculative retries (PR 10), where the question
    crosses result blocks: a row's *speculative* footprint against a
    wave's *re-executed* write sets (classification agreement), and a
    wave row's re-executed read set against the block's resolved write
    sets (execution validity).  Verdicts come from the rectangular
    strip kernel (:func:`repro.kernels.ops.cross_conflicts`) masked to
    earlier-rank marked writers — rank space, like every commit
    decision."""
    mat = kernel_ops.cross_conflicts(
        reader_res.raddrs, reader_res.rn, reader_res.waddrs, reader_res.wn,
        writer_res.waddrs, writer_res.wn, n_objects, reads_only=reads_only)
    earlier = writer_mask[None, :] & (rank[None, :] < rank[:, None])
    return (mat & earlier).any(axis=1)


def prefix_commit(res, conflict, order: jax.Array, rank: jax.Array,
                  n_comm: jax.Array, n_objects: int,
                  real: jax.Array | None = None) -> jax.Array:
    """Maximal committing in-order prefix (PCC's ordered commit, §2.2.2).

    A pending position commits iff no position of this round's pending
    prefix up to and including it conflicts with an earlier *committing*
    transaction.  Under the prefix rule "conflicts with an earlier
    committing txn" equals "conflicts with ANY earlier pending txn":
    every pending position before the first conflict commits, and
    nothing after it does.  That collapses the old K-step scan into one
    batched conflict query plus a cumulative AND — ≤⌈log₂K⌉ device
    steps via `associative_scan`.

    n_comm: () int32 count of already-committed positions.  ``real``
    optionally masks out *vacant* rows (bucket padding, ``n_ins == 0`` —
    they sort after every real row and must never commit).  Returns
    committing (K,) bool in TXN space.
    """
    k = rank.shape[0]
    pending = rank >= n_comm
    if real is not None:
        pending = pending & real
    bad = earlier_writer_conflicts(res, conflict, pending, rank, n_objects)
    # positions before the pending window never break the chain
    ok_pos = jnp.where(jnp.arange(k) >= n_comm, ~bad[order], True)
    alive_pos = jax.lax.associative_scan(jnp.logical_and, ok_pos)
    return pending & alive_pos[rank]


def wave_commit(res, conflict, pending: jax.Array, rank: jax.Array,
                n_objects: int, block: int = 1) -> jax.Array:
    """OCC's arrival-order wave rule: c[t] = pending[t] ∧ ¬∃ earlier q:
    c[q] ∧ conflict[t, q] — the greedy kernel of the conflict DAG (no
    prefix rule: a conflicting txn aborts but later ones keep
    committing).

    Solved by fixpoint iteration from the optimistic start c = pending;
    each step is one batched conflict query, and the iteration provably
    reaches the unique solution in at most the conflict-chain depth:
    a txn's verdict is final once all its conflict predecessors'
    verdicts are, by induction along the order.

    ``block`` unrolls B conflict queries per `while_loop` trip (the
    blocked solve): on deep conflict chains the dominant cost is the
    per-trip loop overhead (condition sync + carried-state round trip),
    which the unroll divides by B.  Decision-identical for ANY block:
    the iterates F(c), F²(c), ... from c = pending converge monotonely
    layer-by-layer to the unique greedy solution, and a convergent
    sequence with F^B(c) == c must already sit AT the fixpoint (a
    B-periodic tail of a convergent sequence is constant), so the
    blocked convergence test never exits early on a non-solution and
    terminates once B·trips covers the chain depth.

    Returns ``(committing, trips)`` — ``trips`` () int32 counts
    `while_loop` trips (≥ 1; the final converging trip is included),
    i.e. ceil over B of the wave's conflict-chain depth + 1.  Engines
    accumulate it into ``ExecTrace.wave_trips`` so contention cost is
    observable per round.
    """

    def body(state):
        c, _, trips = state
        start = c
        for _ in range(block):
            blocked = earlier_writer_conflicts(res, conflict, c, rank,
                                               n_objects)
            c = pending & ~blocked
        return c, (c == start).all(), trips + 1

    c, _, trips = jax.lax.while_loop(
        lambda s: ~s[1], body,
        (pending, jnp.asarray(False), jnp.zeros((), jnp.int32)))
    return c, trips


def fused_write_back(values, versions, waddrs, wvals, wn, committing,
                     rank, seq_nos, layout: StoreLayout | None = None):
    """Install a whole round of commits in one flattened scatter.

    waddrs (K, L) / wvals (K, L, S) / wn (K,) / committing (K,) /
    rank (K,) / seq_nos (K,) are all in txn space; ``committing``
    selects the round's committers and ``seq_nos`` carries each txn's
    version stamp.  The winning writer per address is the one with the
    largest (rank, slot) priority — serialization-order-major, so a
    later committing transaction overwrites an earlier one, and
    slot-minor, so within one transaction the later deferred write
    shadows the earlier (subsuming :func:`dedup_last_writer`).
    Priorities are unique per slot, hence exactly one winner per
    address and a duplicate-free scatter.

    Under a sharded ``layout`` the round's scatter splits into S
    *independent* per-shard scatters (winner selection is per address,
    and an address lives in exactly one shard, so each shard's winners
    are decided from exactly the writes the dense scatter would route
    there — bit-identical).  With ``layout.mesh`` set, the S scatters
    run one-per-device under ``jax.shard_map``; otherwise they run as
    one vmap over the shard axis.
    """
    if layout is not None and layout.sharded:
        return _fused_write_back_sharded(
            values, versions, waddrs, wvals, wn, committing, rank,
            seq_nos, layout)
    # the dense store IS the one-shard case: shard 0 spanning the whole
    # address space (every executor address is < n_obj, so the shard
    # filter is a no-op) — one copy of the winner-selection logic
    return _shard_write_back(values, versions, 0, waddrs, wvals, wn,
                             committing, rank, seq_nos, values.shape[0])


def _shard_write_back(values_s, versions_s, shard, waddrs, wvals, wn,
                      committing, rank, seq_nos, shard_size: int):
    """One shard's slice of :func:`fused_write_back`: the (rank, slot)
    segment-max winner selection, restricted to the write slots whose
    address falls in this shard's range and rebased to shard-local
    offsets.  ``values_s`` (C, slot) / ``versions_s`` (C,).  THE single
    copy of the winner-selection logic — the dense scatter is the
    degenerate call with ``shard=0, shard_size=n_obj``."""
    c = values_s.shape[0]
    k, length = waddrs.shape
    slot = jnp.arange(length)
    valid = (committing[:, None] & (slot[None, :] < wn[:, None])
             & (waddrs // shard_size == shard))
    prio = (rank.astype(jnp.int32)[:, None] * length
            + slot[None, :].astype(jnp.int32))
    addr = jnp.where(valid, waddrs % shard_size, c).reshape(-1)
    flat_prio = jnp.where(valid, prio, -1).reshape(-1)
    best = jnp.full((c + 1,), -1, jnp.int32).at[addr].max(flat_prio)
    win = valid.reshape(-1) & (flat_prio == best[addr])
    tgt = jnp.where(win, addr, c)
    values_s = values_s.at[tgt].set(wvals.reshape(k * length, -1),
                                    mode="drop")
    versions_s = versions_s.at[tgt].set(
        jnp.repeat(jnp.asarray(seq_nos, jnp.int32), length), mode="drop")
    return values_s, versions_s


def _fused_write_back_sharded(values, versions, waddrs, wvals, wn,
                              committing, rank, seq_nos,
                              layout: StoreLayout):
    """S independent per-shard commit scatters (see fused_write_back).

    values (S, C, slot) / versions (S, C).  The mesh path shards the
    store axis one-shard-per-device and replicates the (K, L) round
    operands — each device installs exactly its own range's writes, no
    cross-device traffic beyond the broadcast of the round's operands.
    """
    wb = functools.partial(_shard_write_back,
                           shard_size=layout.shard_size)
    if layout.mesh is None:
        return jax.vmap(
            wb, in_axes=(0, 0, 0) + (None,) * 6)(
                values, versions, jnp.arange(layout.shards), waddrs,
                wvals, wn, committing, rank, seq_nos)

    axis = layout.mesh.axis_names[0]
    spec = jax.sharding.PartitionSpec

    def body(values_b, versions_b, waddrs, wvals, wn, committing, rank,
             seq_nos):
        v, ver = wb(values_b[0], versions_b[0], jax.lax.axis_index(axis),
                    waddrs, wvals, wn, committing, rank, seq_nos)
        return v[None], ver[None]

    return jax.shard_map(
        body, mesh=layout.mesh,
        in_specs=(spec(axis), spec(axis)) + (spec(),) * 6,
        out_specs=(spec(axis), spec(axis)),
    )(values, versions, waddrs, wvals, wn, committing, rank,
      jnp.asarray(seq_nos, jnp.int32))
