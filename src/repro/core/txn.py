"""Transactions with *dynamic* read/write sets, as a jittable bytecode VM.

The paper chooses OCC precisely because general TM transactions have
read/write sets that cannot be known a priori (aliasing, pointer chasing,
"the unstructured nature of the heap", §2.2).  We reproduce that property
in a dataflow runtime with a tiny bounded-length bytecode: the *indirect*
addressing mode makes an instruction's effective address depend on the
value returned by the previous read, so a transaction's footprint is only
discoverable by executing it — exactly the dynamic-set regime.

Opcodes
-------
NOP   — padding.
READ  — acc += M[eff]; logs eff in the read set.
WRITE — M[eff] := acc + operand (deferred); logs eff in the write set.
RMW   — READ then WRITE on the same address (read-modify-write).

Addressing: eff = addr                     (direct)
            eff = (addr + last_read) % O   (indirect — data dependent)

Reads observe the transaction's own deferred writes (read-your-writes, as
in Fig. 2a line 5/6 of the paper: "return the buffered value for o in the
write set, if existing").

Two properties of this VM carry the cross-batch speculation invariant
(PR 7, ``protocol.spec_execute`` / ``seed_round_state``): a row's
execution is a pure function of the values its logged reads observed
(so a speculated row whose reads all survive validation replays
bit-identically without re-running), and read-your-writes is row-local
(a row never observes another row's deferred writes, so the logged read
set is exactly the row's store footprint).  Invalidated rows re-execute
through the same ``run_live`` / ``run_live_compact`` executors the
round loops use — there is no separate speculation VM.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

NOP, READ, WRITE, RMW = 0, 1, 2, 3


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TxnBatch:
    """K transactions of up to L instructions each (stacked, masked)."""

    opcodes: jax.Array   # (K, L) int32
    addrs: jax.Array     # (K, L) int32
    indirect: jax.Array  # (K, L) bool
    operands: jax.Array  # (K, L) int32
    n_ins: jax.Array     # (K,)   int32 — live instruction count (= txn "cost")

    @property
    def n_txns(self) -> int:
        return self.opcodes.shape[0]

    @property
    def max_ins(self) -> int:
        return self.opcodes.shape[1]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TxnResult:
    """One speculative execution: logged footprint + deferred writes."""

    raddrs: jax.Array  # (K, L) int32 — read-set addresses (first rn valid)
    rn: jax.Array      # (K,)   int32
    waddrs: jax.Array  # (K, L) int32 — write-set addresses (first wn valid)
    wvals: jax.Array   # (K, L, S) int32 — deferred values (write buffer)
    wn: jax.Array      # (K,)   int32


def make_batch(progs: list[list[tuple]], max_ins: int | None = None) -> TxnBatch:
    """Build a TxnBatch from python programs: each a list of
    (opcode, addr, indirect, operand) tuples.

    NB: a row with ``n_ins == 0`` is a *vacant* row — since PR 4 the
    engines treat it as absent (never committed, no sequence position,
    no ``gv`` advance, ``commit_pos == -1``), because that is how
    ``PotSession`` encodes shape-bucket padding (:func:`pad_batch`).  An
    intentionally empty transaction should be a single NOP instruction
    (``[(NOP, 0, False, 0)]``), which commits normally with an empty
    footprint."""
    return upload_batch(pack_batch(progs, max_ins))


def pack_batch(progs: Sequence[Sequence[tuple]],
               max_ins: int | None = None) -> TxnBatch:
    """The host half of :func:`make_batch`: the same (K, L) arrays as
    NumPy arrays, filled from the programs on the host."""
    k = len(progs)
    length = max_ins or max((len(p) for p in progs), default=1)
    length = max(length, 1)
    op = np.zeros((k, length), np.int32)
    ad = np.zeros((k, length), np.int32)
    ind = np.zeros((k, length), bool)
    opr = np.zeros((k, length), np.int32)
    n = np.zeros((k,), np.int32)
    for i, p in enumerate(progs):
        n[i] = len(p)
        for j, (o, a, b, v) in enumerate(p):
            op[i, j], ad[i, j], ind[i, j], opr[i, j] = o, a, b, v
    return TxnBatch(opcodes=op, addrs=ad, indirect=ind, operands=opr,
                    n_ins=n)


def upload_batch(batch: TxnBatch) -> TxnBatch:
    """The device half of :func:`make_batch`: one host->device copy per
    field of a :func:`pack_batch` result."""
    return jax.tree.map(jnp.asarray, batch)


def run_txn(batch_row, values: jax.Array,
            n_objects: int | None = None) -> tuple:
    """Execute ONE transaction speculatively against a store image.

    ``batch_row`` — a TxnBatch pytree sliced to one transaction (arrays of
    shape (L,) / (L,)).  ``values`` — (O, S) committed store image.  Pure:
    returns the footprint + deferred writes, never mutates ``values``
    (deferred-update OCC read phase, Fig. 2a).

    ``n_objects`` — the real object count when ``values`` is a *padded*
    flat view (the sharded store's stacked shards reshape to
    S*ceil(O/S) >= O rows, see ``tstore.flat_values``).  Effective
    addresses are reduced mod ``n_objects``, so execution against the
    padded view is bit-identical to the dense (O, S) image: the padding
    rows are never addressed.  Defaults to ``values.shape[0]``.
    """
    n_obj = n_objects if n_objects is not None else values.shape[0]
    slot = values.shape[1]
    length = batch_row.opcodes.shape[0]

    def step(carry, t):
        acc, last, rn, wn, raddrs, waddrs, wvals = carry
        op = batch_row.opcodes[t]
        active = (t < batch_row.n_ins) & (op != NOP)
        eff = jnp.where(
            batch_row.indirect[t],
            jnp.abs(batch_row.addrs[t] + last) % n_obj,
            batch_row.addrs[t] % n_obj,
        )
        is_read = active & ((op == READ) | (op == RMW))
        is_write = active & ((op == WRITE) | (op == RMW))

        # read-your-writes: latest deferred write to eff, else memory
        idx = jnp.arange(length)
        match = (waddrs == eff) & (idx < wn)
        has_match = match.any()
        last_match = (length - 1) - jnp.argmax(match[::-1])
        buf_val = wvals[last_match]
        mem_val = values[eff]
        rval = jnp.where(has_match, buf_val, mem_val)  # (S,)

        acc = jnp.where(is_read, acc + rval, acc)
        last = jnp.where(is_read, rval[0], last)
        raddrs = jnp.where(is_read, raddrs.at[rn].set(eff), raddrs)
        rn = rn + is_read.astype(jnp.int32)

        wval = acc + batch_row.operands[t]
        waddrs = jnp.where(is_write, waddrs.at[wn].set(eff), waddrs)
        wvals = jnp.where(is_write, wvals.at[wn].set(wval), wvals)
        wn = wn + is_write.astype(jnp.int32)
        return (acc, last, rn, wn, raddrs, waddrs, wvals), None

    init = (
        jnp.zeros((slot,), jnp.int32),          # acc
        jnp.zeros((), jnp.int32),               # last read word
        jnp.zeros((), jnp.int32),               # rn
        jnp.zeros((), jnp.int32),               # wn
        jnp.zeros((length,), jnp.int32),        # raddrs
        jnp.zeros((length,), jnp.int32),        # waddrs
        jnp.zeros((length, slot), jnp.int32),   # wvals
    )
    (acc, last, rn, wn, raddrs, waddrs, wvals), _ = jax.lax.scan(
        step, init, jnp.arange(length))
    return raddrs, rn, waddrs, wvals, wn


def run_all(batch: TxnBatch, values: jax.Array,
            n_objects: int | None = None) -> TxnResult:
    """Speculatively execute every transaction in the batch (vmapped) against
    the same committed store image — one engine "round" of read phases.
    ``n_objects`` as in :func:`run_txn` (padded flat store views)."""
    raddrs, rn, waddrs, wvals, wn = jax.vmap(
        run_txn, in_axes=(0, None, None))(batch, values, n_objects)
    return TxnResult(raddrs=raddrs, rn=rn, waddrs=waddrs, wvals=wvals, wn=wn)


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (>= 1)."""
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def pad_batch(batch: TxnBatch, n_txns: int, max_ins: int) -> TxnBatch:
    """Pad a batch with vacant NOP rows / inert instruction columns up to
    (n_txns, max_ins) — the shape-bucketing primitive.

    Padded rows have ``n_ins == 0`` (the *vacant row* convention: engines
    treat them as absent — never pending, never committing, no sequence
    number consumed, ``commit_pos == -1``).  Padded columns are NOP slots
    past every row's ``n_ins``, so real rows execute bit-identically: the
    executor's instruction predicate ``t < n_ins`` is false on them.
    """
    k, length = batch.opcodes.shape
    pk, pl = n_txns - k, max_ins - length
    if pk < 0 or pl < 0:
        raise ValueError(
            f"pad_batch target ({n_txns}, {max_ins}) smaller than ({k}, "
            f"{length})")
    if pk == 0 and pl == 0:
        return batch
    pad2 = lambda a: jnp.pad(a, ((0, pk), (0, pl)))
    return TxnBatch(
        opcodes=pad2(batch.opcodes), addrs=pad2(batch.addrs),
        indirect=pad2(batch.indirect), operands=pad2(batch.operands),
        n_ins=jnp.pad(batch.n_ins, (0, pk)))


def run_live(batch: TxnBatch, values: jax.Array, live: jax.Array,
             cache: TxnResult | None = None,
             n_objects: int | None = None) -> TxnResult:
    """Masked re-execution: run only the *live* transactions, reuse cached
    rows for the settled ones.

    ``live`` (K,) bool selects the transactions whose speculation is stale
    (uncommitted/aborted rows that must re-read the new store image);
    settled rows keep their ``cache`` entry untouched.  Dead lanes run
    with ``n_ins`` masked to 0 so every instruction predicate is false —
    the vmapped scan still walks the (K, L) grid (shapes are static under
    jit) but a dead lane's instruction slots are inert, which is exactly
    the live-slot work model the engines account (``ExecTrace.live_slots``
    vs ``rounds * sum(n_ins)`` for a from-scratch ``run_all`` per round).

    A live row's result is bit-identical to the same row of
    ``run_all(batch, values)``: execution is per-transaction pure, so
    masking the other lanes cannot change it (asserted in
    tests/test_round_state.py).

    With ``cache=None`` dead rows come back zeroed (rn = wn = 0) — only
    valid when every consumer masks by ``live``, e.g. the first round of
    an engine loop where ``live`` is all-true.
    """
    masked = TxnBatch(
        opcodes=batch.opcodes, addrs=batch.addrs, indirect=batch.indirect,
        operands=batch.operands,
        n_ins=jnp.where(live, batch.n_ins, 0))
    fresh = run_all(masked, values, n_objects)
    if cache is None:
        return fresh

    def merge(new, old):
        mask = live.reshape(live.shape + (1,) * (new.ndim - 1))
        return jnp.where(mask, new, old)

    return jax.tree.map(merge, fresh, cache)


# --------------------------------------------------------------------------
# Gather-compacted execution (PR 4)
# --------------------------------------------------------------------------
#
# The masked executor above still walks the full static (K, L) grid even
# when only a handful of rows are live (shapes are static under jit).
# When live_count <= width << K, the compact path gathers the live rows
# into a bounded (width, L) block, executes THAT, and scatters the
# results back — device work proportional to the live set, not the batch
# capacity.  Row purity makes it bit-identical to the masked path: a
# transaction's execution depends only on its own program and the store
# image, never on which other rows share the vmap.


def gather_live_indices(live: jax.Array, width: int
                        ) -> tuple[jax.Array, jax.Array]:
    """Pack the live row indices into the first slots of a (width,) index
    vector: returns ``(idx, valid)`` where ``idx`` holds every live row's
    index (ascending) in its leading ``live.sum()`` slots and ``valid``
    flags them.  Requires ``live.sum() <= width`` — callers guarantee it
    by choosing ``width`` from the compact ladder (protocol.compact_ladder)
    and only descending a rung once the live count fits.
    """
    idx = jnp.argsort(jnp.where(live, 0, 1), stable=True)[:width]
    idx = idx.astype(jnp.int32)
    return idx, live[idx]


def run_compact(batch: TxnBatch, values: jax.Array, idx: jax.Array,
                valid: jax.Array,
                n_objects: int | None = None) -> TxnResult:
    """Execute the gathered rows ``batch[idx]`` against ``values`` at
    compact width C = idx.shape[0].  Rows with ``~valid`` (gather padding,
    possibly duplicate indices) run inert (``n_ins`` masked to 0) and come
    back with empty footprints.  Valid rows are bit-identical to the same
    rows of ``run_all(batch, values)``."""
    cbatch = jax.tree.map(lambda a: a[idx], batch)
    cbatch = TxnBatch(
        opcodes=cbatch.opcodes, addrs=cbatch.addrs,
        indirect=cbatch.indirect, operands=cbatch.operands,
        n_ins=jnp.where(valid, cbatch.n_ins, 0))
    return run_all(cbatch, values, n_objects)


def scatter_rows(dst: jax.Array, src: jax.Array, idx: jax.Array,
                 valid: jax.Array) -> jax.Array:
    """Scatter compact rows back to full width: row ``idx[c]`` of ``dst``
    takes row c of ``src`` where ``valid[c]``; other rows are untouched.

    THE sentinel-drop idiom of the compact path, kept in one place
    because its safety argument is subtle: invalid slots of ``idx`` may
    hold DUPLICATE indices (gather padding clips to valid range), so
    they must be routed to the out-of-bounds sentinel and dropped —
    never masked by a `where` on the gathered value, which would still
    scatter the duplicate and make the result order-dependent."""
    tgt = jnp.where(valid, idx, dst.shape[0])
    return dst.at[tgt].set(src, mode="drop")


def scatter_result(cache: TxnResult, cres: TxnResult, idx: jax.Array,
                   valid: jax.Array, n_rows: int) -> TxnResult:
    """Scatter compact result rows back to their full-width positions:
    row ``idx[c]`` of the output takes row c of ``cres`` where
    ``valid[c]``; every other row keeps its ``cache`` entry."""
    del n_rows  # every leaf's leading axis is the full width
    return jax.tree.map(
        lambda old, new: scatter_rows(old, new, idx, valid), cache, cres)


def run_live_compact(batch: TxnBatch, values: jax.Array, live: jax.Array,
                     cache: TxnResult, width: int,
                     n_objects: int | None = None
                     ) -> tuple[TxnResult, TxnResult, jax.Array, jax.Array]:
    """Compact equivalent of :func:`run_live`: gather the live rows into a
    (width, L) block, execute it, scatter back over ``cache``.

    Returns ``(merged, cres, idx, valid)`` — ``merged`` is bit-identical
    to ``run_live(batch, values, live, cache)`` whenever
    ``live.sum() <= width`` (asserted in tests); ``cres``/``idx``/``valid``
    expose the compact block for callers that keep working at width C
    (the incremental conflict-strip update, DeSTM's token walk).
    """
    idx, valid = gather_live_indices(live, width)
    cres = run_compact(batch, values, idx, valid, n_objects)
    merged = scatter_result(cache, cres, idx, valid, batch.n_txns)
    return merged, cres, idx, valid
