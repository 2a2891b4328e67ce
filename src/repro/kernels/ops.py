"""Jit'd public wrappers around the Pallas kernels.

Handle padding/reshaping so callers can pass arbitrary shapes; pick
interpret mode automatically (interpret=True off-TPU so the kernels
validate on CPU; compiled on real TPU backends).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import conflict as _conf
from repro.kernels import fused_adamw as _adamw
from repro.kernels import kv_commit as _kvc
from repro.kernels import validate as _val


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x, mult, axis, value=0):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _packed_intersects(foot_bits: jax.Array,
                       write_bits: jax.Array) -> jax.Array:
    """(M, N) bool, entry (i, j) = any_w(foot_bits[i, w] & write_bits[j, w])
    for packed (M, W) / (N, W) words: the rectangular bitset-intersection
    Pallas kernel on TPU, dense bit-ops off it."""
    m, n = foot_bits.shape[0], write_bits.shape[0]
    if _on_tpu():
        fb = _pad_to(_pad_to(foot_bits, _conf.BI, 0), _conf.BW, 1)
        wb = _pad_to(_pad_to(write_bits, _conf.BJ, 0), _conf.BW, 1)
        return _conf.conflict_matrix_bits_pair(
            fb, wb, interpret=False)[:m, :n]
    return ((foot_bits[:, None, :] & write_bits[None, :, :]) != 0
            ).any(axis=2)


def _slots(addrs: jax.Array, n: jax.Array, void: int) -> jax.Array:
    """(K, L) addresses with the slots at or past ``n`` set to ``void``."""
    valid = jnp.arange(addrs.shape[1])[None, :] < n[:, None]
    return jnp.where(valid, addrs, void)


def _foot_addrs(raddrs, rn, waddrs, wn) -> jax.Array:
    """(K, 2L) footprint lists, reads then writes, FOOT_VOID where
    invalid — the row operand of the address kernels."""
    return jnp.concatenate([_slots(raddrs, rn, _conf.FOOT_VOID),
                            _slots(waddrs, wn, _conf.FOOT_VOID)], axis=1)


def _write_addrs_t(waddrs, wn) -> jax.Array:
    """(L, K) write lists, WRITE_VOID where invalid, transposed so that
    write sets lie on lanes — the column operand of the address
    kernels."""
    return _slots(waddrs, wn, _conf.WRITE_VOID).T


def _addr_intersects(foot: jax.Array, write_t: jax.Array) -> jax.Array:
    """(M, N) bool, entry (i, j) = any(foot[i, a] == write_t[b, j]): the
    rectangular address-compare Pallas kernel, operands padded with
    their sentinels (TPU only)."""
    m, n = foot.shape[0], write_t.shape[1]
    f = _pad_to(foot, 8 if m <= _conf.BT_I else _conf.BT_I, 0,
                _conf.FOOT_VOID)
    w = _pad_to(write_t, _conf.BT_J, 1, _conf.WRITE_VOID)
    return _conf.conflict_matrix_bits_addr_pair(f, w, interpret=False)[:m, :n]


def validate(read_addrs: jax.Array, read_n: jax.Array,
             written_addrs: jax.Array, written_n: jax.Array,
             n_objects: int) -> jax.Array:
    """Read-set validation for K transactions against a written set.

    read_addrs (K, L) + read_n (K,); written_addrs (Lw,) + written_n ().
    Returns conflict (K,) bool.
    """
    k = read_addrs.shape[0]
    read_bits = _val.pack_addr_sets(read_addrs, read_n, n_objects)
    written_bits = _val.pack_addr_sets(
        written_addrs[None, :], written_n[None], n_objects)[0]
    read_bits = _pad_to(_pad_to(read_bits, _val.BK, 0), _val.BW, 1)
    written_bits = _pad_to(written_bits, _val.BW, 0)
    out = _val.validate_bitsets(read_bits, written_bits,
                                interpret=not _on_tpu())
    return out[:k]


def _conflict_matrix_dense(raddrs, rn, waddrs, wn, n_objects):
    """Reference fallback for :func:`conflict_matrix` off-TPU: dense 0/1
    footprint masks + one matmul (BLAS-batched on CPU, exact — counts are
    small integers in float32)."""
    k, length = raddrs.shape

    def dense(addrs, n):
        valid = jnp.arange(length)[None, :] < n[:, None]
        tgt = jnp.where(valid, addrs, n_objects)  # invalid -> shadow column
        mask = jnp.zeros((k, n_objects + 1), jnp.float32)
        mask = mask.at[jnp.arange(k)[:, None], tgt].set(1.0)
        return mask[:, :n_objects]

    wmask = dense(waddrs, wn)
    fmask = jnp.maximum(dense(raddrs, rn), wmask)
    return (fmask @ wmask.T) > 0.5


def conflict_matrix(raddrs: jax.Array, rn: jax.Array, waddrs: jax.Array,
                    wn: jax.Array, n_objects: int) -> jax.Array:
    """Batched pairwise conflict analysis: (K, K) bool where entry (i, j)
    means footprint(i) = reads(i) ∪ writes(i) intersects writes(j).

    raddrs/waddrs (K, L) masked by rn/wn (K,).  On TPU this is the tiled
    address-compare Pallas kernel (conflict.py); off-TPU it falls back to
    the dense-mask reference formulation (same verdicts, asserted in
    tests/test_kernels.py).
    """
    if not _on_tpu():
        return _conflict_matrix_dense(raddrs, rn, waddrs, wn, n_objects)
    return _addr_intersects(_foot_addrs(raddrs, rn, waddrs, wn),
                            _write_addrs_t(waddrs, wn))


def conflict_matrix_delta_compact(foot_bits: jax.Array,
                                  write_bits: jax.Array, old: jax.Array,
                                  idx: jax.Array, valid: jax.Array,
                                  n_objects: int) -> jax.Array:
    """Compacted variant of :func:`conflict_matrix_delta` (one shard's
    words under the sharded layout): instead of a masked pass over the
    full (K, K) grid, compute only the two strips the round actually
    changed — rows idx (the C live footprints against every write set,
    (C, K)) and columns idx (every footprint against the C live write
    sets, (K, C)) — and scatter them over last round's table.

    On TPU both strips come from the rectangular bitset-intersection
    Pallas kernel (conflict.conflict_matrix_bits_pair): O(C·K·W) device
    work instead of O(K²·W).  Off-TPU a dense bit-ops fallback with
    identical verdicts (asserted in tests).  ``foot_bits``/``write_bits``
    must ALREADY hold the refreshed live rows
    (:func:`update_packed_footprints_compact_sharded`).
    """
    row_strip = _packed_intersects(foot_bits[idx], write_bits)
    col_strip = _packed_intersects(foot_bits, write_bits[idx])
    return _scatter_strips(old, row_strip, col_strip, idx, valid)


def _scatter_strips(old, row_strip, col_strip, idx, valid):
    """Rows ``idx`` of ``old`` := row_strip (C, K), then columns ``idx``
    := col_strip (K, C); gather padding (``~valid``) is dropped."""
    from repro.core.txn import scatter_rows
    k = old.shape[0]
    new = scatter_rows(old, row_strip, idx, valid)
    # column twin of scatter_rows: same sentinel-drop contract, axis 1
    tgt = jnp.where(valid, idx, k)
    return new.at[:, tgt].set(col_strip, mode="drop")


def conflict_matrix_delta(foot_bits: jax.Array, write_bits: jax.Array,
                          old: jax.Array, live: jax.Array,
                          n_objects: int) -> jax.Array:
    """Incremental conflict-table update over carried packed footprints
    (one shard's words under the sharded layout): entry (i, j) is
    recomputed iff transaction i or j re-executed this round (``live``),
    otherwise last round's verdict is carried.

    On TPU this is the masked-row variant of the bitset-intersection
    Pallas kernel (conflict.conflict_matrix_bits_delta — dead blocks skip
    the intersection); elsewhere a dense recompute-and-select fallback
    with identical verdicts (asserted in tests/test_kernels.py).
    ``old`` is (K, K) bool, ``foot_bits``/``write_bits`` are the (K, W)
    packed sets ALREADY refreshed for live rows.
    """
    k = foot_bits.shape[0]
    if _on_tpu():
        rows = max(_conf.BI, _conf.BJ)
        fb = _pad_to(_pad_to(foot_bits, rows, 0), _conf.BW, 1)
        wb = _pad_to(_pad_to(write_bits, rows, 0), _conf.BW, 1)
        old_p = _pad_to(_pad_to(old.astype(jnp.int32), rows, 0), rows, 1)
        live_p = _pad_to(live.astype(jnp.int32), rows, 0)
        out = _conf.conflict_matrix_bits_delta(fb, wb, old_p, live_p,
                                               interpret=False)
        return out[:k, :k] != 0
    # dense fallback: full bitset "matmul", then carry stale entries
    fresh = _packed_intersects(foot_bits, write_bits)
    refresh = live[:, None].astype(bool) | live[None, :].astype(bool)
    return jnp.where(refresh, fresh, old)


def conflict_matrix_delta_addrs(raddrs: jax.Array, rn: jax.Array,
                                waddrs: jax.Array, wn: jax.Array,
                                old: jax.Array, live: jax.Array,
                                n_objects: int) -> jax.Array:
    """The carried one-chip conflict table's round update: entry (i, j)
    is recomputed iff transaction i or j re-executed this round
    (``live``), otherwise last round's verdict is carried — from the
    round's (K, L) lists (``res`` with the live rows already
    re-executed), with no packed state.

    On TPU the masked address-compare Pallas kernel
    (conflict.conflict_matrix_bits_addr_delta); elsewhere the dense
    recompute-and-select fallback, with identical verdicts.
    """
    k = raddrs.shape[0]
    if not _on_tpu():
        fresh = _conflict_matrix_dense(raddrs, rn, waddrs, wn, n_objects)
        refresh = live[:, None].astype(bool) | live[None, :].astype(bool)
        return jnp.where(refresh, fresh, old)
    rows = max(_conf.BT_I, _conf.BT_J)
    foot = _pad_to(_foot_addrs(raddrs, rn, waddrs, wn), rows, 0,
                   _conf.FOOT_VOID)
    write_t = _pad_to(_write_addrs_t(waddrs, wn), rows, 1, _conf.WRITE_VOID)
    old_p = _pad_to(_pad_to(old.astype(jnp.int32), rows, 0), rows, 1)
    live_p = _pad_to(live.astype(jnp.int32), rows, 0)
    out = _conf.conflict_matrix_bits_addr_delta(foot, write_t, old_p, live_p,
                                                interpret=False)
    return out[:k, :k] != 0


def conflict_matrix_delta_compact_addrs(raddrs: jax.Array, rn: jax.Array,
                                        waddrs: jax.Array, wn: jax.Array,
                                        old: jax.Array, idx: jax.Array,
                                        valid: jax.Array,
                                        n_objects: int) -> jax.Array:
    """Compacted variant of :func:`conflict_matrix_delta_addrs`: the
    (C, K) row strip and (K, C) column strip of rows ``idx`` (``valid``
    masks gather padding), from the merged (K, L) lists (``res`` after
    the compact block's scatter), by the rectangular address-compare
    kernel — O(C·K·2L·L) on TPU.  Off TPU, the dense
    recompute-and-select of the rows ``idx`` marks."""
    if not _on_tpu():
        k = raddrs.shape[0]
        live = jnp.zeros((k,), bool).at[jnp.where(valid, idx, k)].set(
            True, mode="drop")
        return conflict_matrix_delta_addrs(raddrs, rn, waddrs, wn, old,
                                           live, n_objects)
    foot = _foot_addrs(raddrs, rn, waddrs, wn)
    write_t = _write_addrs_t(waddrs, wn)
    row_strip = _addr_intersects(foot[idx], write_t)
    col_strip = _addr_intersects(foot, write_t[:, idx])
    return _scatter_strips(old, row_strip, col_strip, idx, valid)


# --------------------------------------------------------------------------
# Shard-partitioned conflict analysis (PR 5)
# --------------------------------------------------------------------------
#
# Under the sharded store layout (tstore.StoreLayout, S contiguous range
# shards of C = ceil(O/S) objects) the conflict table is built from
# footprints packed per shard: each shard packs only the addresses in
# its range into (K, ceil(C/32)) words — the bitset kernels' W axis
# shrinks by S — and the global conflict verdict is the OR over shards:
#
#     footprint(i) ∩ writes(j) ≠ ∅  ⟺  ∃s: foot_s(i) ∩ writes_s(j) ≠ ∅
#
# because the shards partition the address space.  Every function below
# OR-reduces S independent intersections (conflict_matrix_delta and
# conflict_matrix_delta_compact above on one shard's words) through
# :func:`_or_over_shards` — one bitset kernel per
# shard, on the shard's own device under ``jax.shard_map`` when the
# layout carries a mesh (a loop on one device otherwise), dense bit-ops
# off-TPU; verdicts are bit-identical to the dense formulation (asserted
# in tests/test_sharded_store.py).


def packed_footprints_sharded(raddrs: jax.Array, rn: jax.Array,
                              waddrs: jax.Array, wn: jax.Array, layout
                              ) -> tuple[jax.Array, jax.Array]:
    """Per-shard bit-packing of a batch's (footprint, write-set) address
    sets: (S, K, ceil(C/32)) int32 words each.  Shard s packs the slots
    whose address lies in [s*C, (s+1)*C), rebased to shard-local bits."""
    c = layout.shard_size
    length = raddrs.shape[1]
    slot = jnp.arange(length)[None, :]
    rvalid = slot < rn[:, None]
    wvalid = slot < wn[:, None]

    def per_shard(s):
        rb = _val.pack_addr_sets_masked(
            raddrs - s * c, rvalid & (raddrs // c == s), c)
        wb = _val.pack_addr_sets_masked(
            waddrs - s * c, wvalid & (waddrs // c == s), c)
        return rb | wb, wb

    return jax.vmap(per_shard)(jnp.arange(layout.shards))


def update_packed_footprints_sharded(foot_bits: jax.Array,
                                     write_bits: jax.Array,
                                     raddrs: jax.Array, rn: jax.Array,
                                     waddrs: jax.Array, wn: jax.Array,
                                     live: jax.Array, layout
                                     ) -> tuple[jax.Array, jax.Array]:
    """Carry the per-shard packed footprints across engine rounds:
    re-pack only the live (re-executed) rows — every shard's row strip
    for a live transaction — and keep the settled rows' words in all S
    shards.  Dead rows are packed with their counts masked to 0 and then
    dropped by the merge."""
    fresh_foot, fresh_write = packed_footprints_sharded(
        raddrs, jnp.where(live, rn, 0), waddrs, jnp.where(live, wn, 0),
        layout)
    keep = live[None, :, None]
    return (jnp.where(keep, fresh_foot, foot_bits),
            jnp.where(keep, fresh_write, write_bits))


def update_packed_footprints_compact_sharded(foot_bits: jax.Array,
                                             write_bits: jax.Array,
                                             raddrs: jax.Array,
                                             rn: jax.Array,
                                             waddrs: jax.Array,
                                             wn: jax.Array,
                                             idx: jax.Array,
                                             valid: jax.Array, layout
                                             ) -> tuple[jax.Array,
                                                        jax.Array]:
    """Compact variant of :func:`update_packed_footprints_sharded`: pack
    the gathered (C_rows, L) block (``txn.run_compact``'s rows ``idx``)
    per shard — O(S·C_rows·L) — and scatter each shard's row strip over
    the carried (S, K, W_s) words; ``valid`` masks gather padding, which
    is dropped rather than scattered."""
    from repro.core.txn import scatter_rows
    cfoot, cwrite = packed_footprints_sharded(
        raddrs, jnp.where(valid, rn, 0), waddrs, jnp.where(valid, wn, 0),
        layout)
    scatter = jax.vmap(scatter_rows, in_axes=(0, 0, None, None))
    return scatter(foot_bits, cfoot, idx, valid), \
        scatter(write_bits, cwrite, idx, valid)


def _or_over_shards(mesh, per_shard, stacked: tuple, *replicated):
    """OR-reduce the bool array ``per_shard(*shard_slices, *replicated)``
    over the S shards of the ``stacked`` (S, ...) arrays.

    Without a mesh the shards run as a loop on one device (peak memory
    one shard's working set).  With the store's 1-axis mesh of S devices
    the loop is one ``jax.shard_map``: each device runs ``per_shard`` on
    the shard it holds and the verdicts are OR-reduced across the axis
    (a psum of 0/1 counts) — the kernels see only device-local words, so
    no Pallas call is left for XLA to partition.  ``check_vma`` is off:
    the per-shard bodies (Pallas calls, the packing loop) build their
    outputs and loop carries from unvarying zeros."""
    if mesh is None:
        out = None
        for i in range(stacked[0].shape[0]):
            v = per_shard(*(x[i] for x in stacked), *replicated)
            out = v if out is None else out | v
        return out
    axis = mesh.axis_names[0]
    spec = jax.sharding.PartitionSpec
    n = len(stacked)

    def body(*args):
        v = per_shard(*(x[0] for x in args[:n]), *args[n:])
        return jax.lax.psum(v.astype(jnp.int32), axis) > 0

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec(axis),) * n + (spec(),) * len(replicated),
        out_specs=spec(), check_vma=False)(*stacked, *replicated)


def conflict_matrix_sharded(foot_bits: jax.Array, write_bits: jax.Array,
                            mesh=None) -> jax.Array:
    """(K, K) conflict table from per-shard packed sets (S, K, W_s): the
    OR over shards of each shard's bitset intersection (W axis = W_s,
    not W) — one device per shard under ``mesh``."""
    return _or_over_shards(mesh, _packed_intersects, (foot_bits, write_bits))


def conflict_matrix_delta_sharded(foot_bits: jax.Array,
                                  write_bits: jax.Array, old: jax.Array,
                                  live: jax.Array, layout) -> jax.Array:
    """Sharded twin of :func:`conflict_matrix_delta`: each shard runs the
    dense delta update on its own words against ``old``.  A stale entry
    is ``old`` in every shard, so it ORs to itself; a refreshed one ORs
    to the OR of the shard-fresh verdicts — the global verdict."""
    return _or_over_shards(
        layout.mesh,
        lambda fb, wb, old, live: conflict_matrix_delta(
            fb, wb, old, live, layout.shard_size),
        (foot_bits, write_bits), old, live)


def conflict_matrix_delta_compact_sharded(foot_bits: jax.Array,
                                          write_bits: jax.Array,
                                          old: jax.Array, idx: jax.Array,
                                          valid: jax.Array,
                                          layout) -> jax.Array:
    """Sharded twin of :func:`conflict_matrix_delta_compact`: each shard
    scatters its own (C, K) / (K, C) strips over ``old``; the OR over
    shards leaves untouched entries at ``old`` and makes every refreshed
    row/column the OR of the shard strips.  ``foot_bits``/``write_bits``
    (S, K, W_s) must already hold the refreshed live rows
    (:func:`update_packed_footprints_compact_sharded`)."""
    return _or_over_shards(
        layout.mesh,
        lambda fb, wb, old, idx, valid: conflict_matrix_delta_compact(
            fb, wb, old, idx, valid, layout.shard_size),
        (foot_bits, write_bits), old, idx, valid)


# --------------------------------------------------------------------------
# Cross-batch speculative validation (PR 7)
# --------------------------------------------------------------------------
#
# Cross-batch speculative pipelining (session.PotSession pipeline_depth)
# executes batch n+1 against the store image snapshotted BEFORE batch n
# committed.  Version stamps are globally monotone sequence numbers
# (every engine write-back stamps gv0 + commit position + 1), so an
# address was written after the snapshot iff versions[a] > snap_gv —
# the EXACT dirty predicate at any pipeline depth.  A speculated row
# stays valid iff none of its logged READ addresses is dirty: a row's
# execution is a pure function of its read values (read-your-writes is
# row-local), so clean reads replay bit-identically and the write set
# need not be checked.  The dirty set packs into ONE bitset row
# (word = a // 32, bit = a % 32 — validate.py's convention), turning
# the whole validation into a (K, 1) rectangular strip of the
# bitset-intersection Pallas kernel (conflict.conflict_matrix_bits_pair);
# off-TPU a dense gather fallback with identical verdicts (asserted in
# tests/test_pipeline.py).


def spec_dirty_words(versions: jax.Array, snap_gv: jax.Array,
                     n_objects: int) -> jax.Array:
    """Bit-pack the post-snapshot dirty set: word ``a // 32`` bit
    ``a % 32`` is set iff ``versions[a] > snap_gv``.  ``versions`` may
    be the dense (O,) array or the sharded (S, C) stack — the flat view
    lists addresses in order either way (contiguous range shards), and
    the padded tail rows of the last shard are never stamped (version
    0), hence never dirty.  Returns (ceil(O/32),) int32."""
    w = -(-n_objects // 32)
    dirty = versions.reshape(-1)[:n_objects] > snap_gv
    dirty = jnp.pad(dirty, (0, w * 32 - n_objects))
    weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    words = (dirty.reshape(w, 32).astype(jnp.uint32) * weights).sum(
        axis=1, dtype=jnp.uint32)
    return words.astype(jnp.int32)


def spec_dirty_words_sharded(versions: jax.Array, snap_gv: jax.Array,
                             layout) -> jax.Array:
    """Per-shard twin of :func:`spec_dirty_words`: shard s's words span
    only its own C-object range (shard-local bits, like
    ``packed_footprints_sharded``).  versions (S, C) -> (S, W_s) int32."""
    w = layout.words_per_shard
    c = layout.shard_size
    dirty = versions > snap_gv            # padding rows stamp 0: never dirty
    dirty = jnp.pad(dirty, ((0, 0), (0, w * 32 - c)))
    weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    words = (dirty.reshape(layout.shards, w, 32).astype(jnp.uint32)
             * weights).sum(axis=2, dtype=jnp.uint32)
    return words.astype(jnp.int32)


def spec_read_invalid(raddrs: jax.Array, rn: jax.Array,
                      versions: jax.Array, snap_gv: jax.Array,
                      n_objects: int) -> jax.Array:
    """Cross-batch read-set validation: (K,) bool, True where a row's
    logged read set hits an address written after the snapshot
    (``versions > snap_gv``).  On TPU the dirty words form a 1-row
    write set and the verdict is a (K, 1) strip of the rectangular
    bitset-intersection kernel; off-TPU a dense version gather."""
    if not _on_tpu():
        valid = jnp.arange(raddrs.shape[1])[None, :] < rn[:, None]
        dirty = versions.reshape(-1)[:n_objects] > snap_gv
        return (valid & dirty[raddrs]).any(axis=1)
    read_bits = _val.pack_addr_sets(raddrs, rn, n_objects)
    dwords = spec_dirty_words(versions, snap_gv, n_objects)
    return _packed_intersects(read_bits, dwords[None, :])[:, 0]


def spec_read_invalid_sharded(raddrs: jax.Array, rn: jax.Array,
                              versions: jax.Array, snap_gv: jax.Array,
                              layout) -> jax.Array:
    """Sharded twin of :func:`spec_read_invalid`: per-shard read bits
    against per-shard dirty words, OR-reduced — the PR 5 OR-over-shards
    invariant (shards partition the address space, so a dirty read hit
    lands in exactly one shard's strip)."""
    c = layout.shard_size
    slotv = jnp.arange(raddrs.shape[1])[None, :] < rn[:, None]
    dwords = spec_dirty_words_sharded(versions, snap_gv, layout)

    def per_shard(s, dwords_s, raddrs, slotv):
        rb = _val.pack_addr_sets_masked(
            raddrs - s * c, slotv & (raddrs // c == s), c)
        return _packed_intersects(rb, dwords_s[None, :])[:, 0]

    return _or_over_shards(
        layout.mesh, per_shard,
        (jnp.arange(layout.shards, dtype=jnp.int32), dwords),
        raddrs, slotv)


def cross_conflicts(reader_raddrs: jax.Array, reader_rn: jax.Array,
                    reader_waddrs: jax.Array, reader_wn: jax.Array,
                    writer_waddrs: jax.Array, writer_wn: jax.Array,
                    n_objects: int, reads_only: bool = False) -> jax.Array:
    """Rectangular reader × writer conflict strip: (R, C) bool where
    entry (i, j) means reader row i's footprint (reads ∪ writes, or the
    logged read set alone with ``reads_only`` — sound for execution
    validity by row purity, same argument as :func:`spec_read_invalid`)
    intersects writer row j's write set.

    The cross-result twin of :func:`conflict_matrix` behind DeSTM's
    wave-speculative retry validation (PR 10): the reader and writer
    verdicts come from DIFFERENT result blocks (speculative footprints
    vs a wave's re-executed write sets), so neither the carried table
    nor the delta strips apply.  On TPU the strip is one launch of the
    address-compare kernel (``conflict.conflict_matrix_bits_addr_pair``);
    off-TPU a dense bit-ops fallback with identical verdicts."""
    if _on_tpu():
        foot = (_slots(reader_raddrs, reader_rn, _conf.FOOT_VOID)
                if reads_only else
                _foot_addrs(reader_raddrs, reader_rn, reader_waddrs,
                            reader_wn))
        return _addr_intersects(foot,
                                _write_addrs_t(writer_waddrs, writer_wn))
    rbits = _val.pack_addr_sets(reader_raddrs, reader_rn, n_objects)
    if not reads_only:
        rbits = rbits | _val.pack_addr_sets(reader_waddrs, reader_wn,
                                            n_objects)
    wbits = _val.pack_addr_sets(writer_waddrs, writer_wn, n_objects)
    return _packed_intersects(rbits, wbits)


def adamw_update(p, m, v, g, *, step, lr=1e-3, b1=0.9, b2=0.999,
                 eps=1e-8, wd=0.01):
    """Fast-mode fused AdamW over an arbitrary-shaped parameter leaf."""
    shape = p.shape
    flat = lambda x: _pad_to(x.reshape(1, -1).astype(jnp.float32),
                             _adamw.BR * _adamw.BC, 1).reshape(
                                 _adamw.BR, -1)
    p2, m2, v2 = _adamw.fused_adamw(
        flat(p), flat(m), flat(v), flat(g), step=step, lr=lr, b1=b1,
        b2=b2, eps=eps, wd=wd, interpret=not _on_tpu())
    n = int(jnp.prod(jnp.asarray(shape)))
    unflat = lambda x: x.reshape(-1)[:n].reshape(shape)
    return unflat(p2), unflat(m2), unflat(v2)


def adamw_update_speculative(p, m, v, g, versions, rv, *, step, lr=1e-3,
                             b1=0.9, b2=0.999, eps=1e-8, wd=0.01):
    """Speculative fused AdamW: versions (R//BR, C//BC) int32, rv scalar."""
    return _adamw.fused_adamw_speculative(
        p, m, v, g, versions, rv, step=step, lr=lr, b1=b1, b2=b2,
        eps=eps, wd=wd, interpret=not _on_tpu())


def kv_cache_commit(cache, versions, rows, page_idx, row_idx, sn, commit):
    """Ordered paged-KV commit for one decode step (see kv_commit.py)."""
    return _kvc.kv_commit(cache, versions, rows, page_idx, row_idx, sn,
                          commit, interpret=not _on_tpu())
