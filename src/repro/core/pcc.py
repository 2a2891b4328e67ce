"""Pot Concurrency Control (PCC) — the paper's contribution (§2.2), adapted
to a dataflow runtime.

Vectorized round-based prefix commit
------------------------------------
Each engine round is three *batched* stages (the shared commit pipeline,
:mod:`repro.core.protocol`), not a walk over transactions:

1. **Speculative read phase** — every pending transaction executes
   (vmapped) against the committed store image (deferred updates, logged
   footprints: OCC read phase, Fig. 2a/2b).  Since PR 3 this is the
   *masked* executor (``txn.run_live`` via ``protocol.RoundState``):
   only the pending suffix re-executes, committed transactions keep
   their cached results, and the conflict table is delta-updated rather
   than rebuilt — per-round live counts land in
   ``ExecTrace.live_per_round``.
2. **Batched conflict analysis** — the paper's per-transaction
   validation question asked for the whole batch at once
   (``protocol.earlier_writer_conflicts``): on TPU a masked
   row-reduction of the K×K footprint-conflict matrix
   (``kernels/conflict.py``, a tiled Pallas kernel comparing the
   read/write address lists), elsewhere a first-writer-per-
   address scatter-min with O(K·L) work — two decision-identical
   formulations of the same question.
3. **Prefix fixpoint + fused write-back** — the maximal committing
   in-order prefix (§2.2.2 "ordered commits" + §2.2.3 "multiple
   simultaneous fast transactions") is a cumulative AND over the
   matrix's masked row-reduction: ``protocol.prefix_commit`` resolves it
   in ≤⌈log₂K⌉ device steps via ``associative_scan``, where the old
   implementation scanned all K positions sequentially, probing an
   O(n_objects) bitmap per step.  The whole prefix's deferred writes
   then land in ONE flattened scatter (``protocol.fused_write_back``):
   the winning writer per address is selected by (commit-position,
   write-slot) priority, which subsumes both the per-transaction apply
   chain and per-transaction last-writer dedup.

The conflicting suffix re-executes next round against the new store
(abort & retry, overlapping its predecessors' commit wait exactly as
speculative transactions overlap waiting in the paper).

Transaction modes fall out structurally:

- the **head** of the pending prefix is the paper's *fast transaction*:
  nothing can commit before it, so row head of the matrix is all-clear
  by construction — it always commits (progress guarantee), with no
  validation work accounted;
- prefix members behind the head are *promoted* transactions
  (compatibility-checked fast commits / live promotion, §2.2.3);
- the remainder stay *speculative* and retry.  After the prefix
  commits, the next pending transaction re-executes serially against
  the fresh store and commits unconditionally (live promotion).

Determinism: the result depends only on (store, transactions, sequence
order) — never on arrival order, lane count, or timing.  ``pcc_execute``
takes an ``arrival`` permutation argument solely so tests can prove the
output is invariant to it.  The decisions are bit-identical to the
pre-vectorization scan (``repro.core.legacy_scan``, asserted in
tests/test_commit_pipeline.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import protocol
from repro.core.engine import (MODE_FAST, MODE_PREFIX, MODE_SPEC, MODE_UNSET,
                               EngineDef, ExecTrace, make_trace,
                               rank_from_order, register_engine)
from repro.core.tstore import TStore, flat_values, store_with
from repro.core.txn import TxnBatch, TxnResult, run_txn

# The old per-engine trace dataclass is now the canonical schema.
PccTrace = ExecTrace


def _pcc_execute(store: TStore, batch: TxnBatch, seq: jax.Array,
                 max_rounds: int | None = None,
                 live_promotion: bool = True,
                 incremental: bool = True,
                 compact: bool = True,
                 seed: "protocol.SpecSeed | None" = None
                 ) -> tuple[TStore, ExecTrace]:
    """Execute a batch of preordered transactions under PCC.

    Args:
      store: committed TStore.
      batch: K transactions (dynamic read/write sets).  Rows with
             ``n_ins == 0`` are *vacant* (shape-bucket padding from
             ``PotSession.submit``): never pending, never committed, no
             ``gv`` advance, ``commit_pos == -1``.  Their sequence
             numbers must sort after every real row's.
      seq:   (K,) int32 — 1-based sequence numbers from the sequencer
             (a permutation of 1..K).
      live_promotion: paper §2.2.3 — after the prefix commits, the next
             pending transaction has become the fast transaction (all
             predecessors committed); it re-executes against the updated
             store within the SAME round and commits unconditionally
             (its abort-and-retry-in-fast-mode path).  Halves the round
             count on conflict chains; False gives the Pot* ablation.
      incremental: re-execute only the pending suffix each round
             (masked ``run_live`` + carried conflict table via
             ``protocol.RoundState``); False rebuilds everything per
             round (the PR 2 behavior, kept for benchmarking and the
             incremental-smoke equivalence gate).  Decision-identical:
             committed transactions' rows are never consumed by the
             prefix decision, so both paths commit bit-identically.
      compact: run the round loop as a cascade over
             ``protocol.compact_ladder(K)`` widths — once the pending
             suffix fits a narrower rung, the read phase gathers it into
             a (C, L) block and executes THAT
             (``protocol.refresh_round_state_compact``), so the sparse
             tail of a contended batch pays device work proportional to
             the live set instead of K.  Decisions stay in rank space
             and are bit-identical to the masked loop (False; asserted
             by tests and ``scripts/ci.sh --compact-smoke``).  Only
             meaningful with ``incremental=True``.
      seed:  optional :class:`protocol.SpecSeed` — a speculative round-0
             execution of this batch against an EARLIER store snapshot
             (cross-batch pipelining, ``PotSession(pipeline_depth=...)``).
             ``protocol.seed_round_state`` re-bases it onto the current
             store (re-executing only rows whose read set went stale)
             and round 0 charges its ordinary work accounting without
             re-walking the batch — bit-identical store and trace to the
             unseeded call, except the ``spec_*`` observables.
    Returns:
      (new store, trace).  ``new_store.gv`` equals ``store.gv`` + the
      number of real (non-vacant) transactions.
    """
    k = batch.n_txns
    layout = store.layout     # static: dense or S contiguous range shards
    n_obj = layout.n_objects
    order = jnp.argsort(seq)  # order[p] = txn index at seq position p
    rank = rank_from_order(order)
    gv0 = store.gv
    seq_nos = gv0 + 1 + rank   # version stamp per txn (its seq position)
    real = batch.n_ins > 0     # vacant rows (bucket padding) never commit
    n_real = real.sum(dtype=jnp.int32)

    def round_body_at(width: int):
        full = width >= k

        def round_body(state):
            rs, gv, n_comm, rnd, tr = state

            # --- read phase: only pending txns re-execute; below the full
            # rung they execute gather-compacted at (width, L) ------------
            pending_t = real & (rank >= n_comm)
            live = pending_t if incremental else jnp.ones((k,), bool)

            def refresh(r):
                if full:
                    return protocol.refresh_round_state(r, batch, live,
                                                        layout)
                return protocol.refresh_round_state_compact(
                    r, batch, live, width, layout)[0]

            if seeded:
                # round 0's read phase already ran speculatively and was
                # re-based onto this store by seed_round_state — charge
                # the identical work accounting without re-walking
                rs = jax.lax.cond(
                    rnd == 0,
                    lambda r: protocol.charge_round_state(
                        r, batch, live, k if full else width),
                    refresh, rs)
            else:
                rs = refresh(rs)
            res: TxnResult = rs.res

            # --- carried conflict analysis + prefix fixpoint (txn space) -
            committing_t = protocol.prefix_commit(
                res, rs.conflict, order, rank, n_comm, n_obj, real)

            # --- fused write-back: the whole prefix in one scatter -------
            values, versions = protocol.fused_write_back(
                rs.values, rs.versions, res.waddrs, res.wvals, res.wn,
                committing_t, rank, seq_nos, layout)

            n_new = committing_t.sum(dtype=jnp.int32)
            gv = gv + n_new

            # ---- live promotion (paper §2.2.3): the first NON-committing
            # pending transaction is now the fast transaction — re-execute
            # it against the freshly-committed store and commit
            # unconditionally.
            promoted_pos = -jnp.ones((), jnp.int32)
            if live_promotion:
                head_pos = n_comm + n_new

                def promote(args):
                    values, versions, gv = args
                    t = order[jnp.clip(head_pos, 0, k - 1)]
                    row = jax.tree.map(lambda a: a[t], batch)
                    raddrs2, rn2, waddrs2, wvals2, wn2 = run_txn(
                        row, flat_values(values, layout), n_obj)
                    del raddrs2, rn2
                    values, versions = protocol.apply_writes(
                        values, versions, waddrs2, wvals2, wn2,
                        gv0 + head_pos + 1, layout)
                    return values, versions, gv + 1

                do_promote = head_pos < n_real
                values, versions, gv = jax.lax.cond(
                    do_promote, promote, lambda a: a,
                    (values, versions, gv))
                promoted_pos = jnp.where(do_promote, head_pos, -1)
                n_new = n_new + do_promote.astype(jnp.int32)

            # --- trace bookkeeping: all txn-space, all elementwise -------
            is_head_t = rank == n_comm
            promoted_t = rank == promoted_pos
            committing_all = committing_t | promoted_t
            mode_t = jnp.where(
                committing_all,
                jnp.where(is_head_t | promoted_t, MODE_FAST, MODE_PREFIX),
                jnp.where(pending_t, MODE_SPEC, MODE_UNSET))
            commit_round = jnp.maximum(tr["commit_round"],
                                       jnp.where(committing_all, rnd, -1))
            first_round = jnp.minimum(
                tr["first_round"],
                jnp.where(pending_t, rnd, jnp.iinfo(jnp.int32).max))
            retries = tr["retries"] + (pending_t & ~committing_all)
            mode = jnp.maximum(tr["mode"], mode_t)
            wait_rounds = tr["wait_rounds"] + (pending_t & ~committing_all)
            # validation: head (fast) validates nothing; everyone else
            # pending validates its read set this round (paper Fig. 2b
            # line 9 / 2c line 2) — a single masked reduction
            validation_words = tr["validation_words"] + jnp.where(
                pending_t & ~is_head_t, res.rn, 0).sum(dtype=jnp.int32)
            exec_ops = tr["exec_ops"] + jnp.where(
                pending_t, batch.n_ins, 0).sum(dtype=jnp.int32) \
                + jnp.where(promoted_t, batch.n_ins,
                            0).sum(dtype=jnp.int32)  # promotion re-exec
            promotions = tr["promotions"] + promoted_t.sum(dtype=jnp.int32)
            live_per_round = tr["live_per_round"].at[rnd].set(
                live.sum(dtype=jnp.int32))
            tr = dict(tr, commit_round=commit_round,
                      first_round=first_round, retries=retries, mode=mode,
                      wait_rounds=wait_rounds,
                      validation_words=validation_words, exec_ops=exec_ops,
                      promotions=promotions, live_per_round=live_per_round)
            rs = protocol.commit_round_state(rs, values, versions)
            return rs, gv, n_comm + n_new, rnd + 1, tr

        return round_body

    def cond_at(next_width: int):
        def cond(state):
            _, _, n_comm, rnd, _ = state
            go = (n_comm < n_real) & (rnd < limit)
            if next_width:
                # hand over to the narrower rung once the pending suffix
                # fits it
                go = go & (n_real - n_comm > next_width)
            return go

        return cond

    limit = max_rounds if max_rounds is not None else k + 1
    tr0 = dict(
        commit_round=jnp.full((k,), -1, jnp.int32),
        first_round=jnp.full((k,), jnp.iinfo(jnp.int32).max, jnp.int32),
        retries=jnp.zeros((k,), jnp.int32),
        mode=jnp.zeros((k,), jnp.int32),
        wait_rounds=jnp.zeros((k,), jnp.int32),
        validation_words=jnp.zeros((), jnp.int32),
        exec_ops=jnp.zeros((), jnp.int32),
        promotions=jnp.zeros((), jnp.int32),
        live_per_round=jnp.full((limit,), -1, jnp.int32),
    )
    seeded = seed is not None   # static per trace (None jits leaf-free)
    if seeded:
        rs0, spec_inv, spec_rnds = protocol.seed_round_state(
            batch, store, seed, compact=(incremental and compact))
    else:
        rs0 = protocol.init_round_state(batch, store.values,
                                        store.versions, layout=layout)
    ladder = (protocol.compact_ladder(k) if (incremental and compact)
              else [k])
    state = (rs0, store.gv, jnp.zeros((), jnp.int32),
             jnp.zeros((), jnp.int32), tr0)
    state = protocol.run_compact_cascade(ladder, state, round_body_at,
                                         cond_at)
    rs, gv, n_comm, rnd, tr = state

    trace = make_trace(
        k,
        commit_round=tr["commit_round"],
        first_round=jnp.where(real, tr["first_round"], -1),
        retries=tr["retries"], mode=tr["mode"],
        wait_rounds=tr["wait_rounds"], rounds=rnd,
        validation_words=tr["validation_words"], exec_ops=tr["exec_ops"],
        promotions=tr["promotions"],
        live_txns=rs.live_txns, live_slots=rs.live_slots,
        walked_slots=rs.walked_slots,
        live_per_round=tr["live_per_round"],
        # PCC commits in sequence order: position = rank in the order.
        # Vacant rows and rows a max_rounds cap left uncommitted
        # (commit_round < 0) are not part of the history: commit_pos -1
        commit_pos=jnp.where(real & (tr["commit_round"] >= 0), rank, -1),
        **(dict(spec_executed=n_real, spec_invalidated=spec_inv,
                spec_rounds=spec_rnds) if seeded else {}))
    return store_with(store, rs.values, rs.versions, gv), trace


pcc_execute = jax.jit(
    _pcc_execute,
    static_argnames=("max_rounds", "live_promotion", "incremental",
                     "compact"))


def _pcc_raw(store, batch, seq, lanes, n_lanes):
    del lanes, n_lanes  # PCC has no lane structure
    return _pcc_execute(store, batch, seq)


def _pcc_raw_spec(store, batch, seq, lanes, n_lanes, seed):
    del lanes, n_lanes
    return _pcc_execute(store, batch, seq, seed=seed)


register_engine(EngineDef(
    "pcc", _pcc_raw,
    doc="Pot Concurrency Control — ordered prefix commit + live promotion",
    raw_spec=_pcc_raw_spec))
