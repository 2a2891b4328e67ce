"""K x K footprint-conflict matrix kernel (the batched validation pass
behind the vectorized commit pipeline, protocol.conflict_table).

The per-transaction validation loop (paper Fig. 2b line 9) probed one
(n_objects,) bitmap per transaction per commit step — K sequential device
steps per round.  The pipeline instead asks ONE batched question per
round: for every ordered pair (i, j), does transaction i's footprint
(read set + write set) intersect transaction j's write set?  With
bit-packed address sets (validate.pack_addr_sets) this is a boolean
"matmul" over W = ceil(n_objects/32) words:

    conflict[i, j] = any_w( foot_bits[i, w] & write_bits[j, w] )

TPU formulation: tile the (K, K) output into (BI, BJ) blocks and the
word axis into BW-word chunks; each grid step ANDs a (BI, BW) block of
footprints against a (BJ, BW) block of write sets and OR-accumulates the
(BI, BJ) any-hit tile across the W grid axis (same accumulate idiom as
validate.py, lifted from a vector to a matrix of verdicts).  The commit
decision then becomes a prefix fixpoint over this matrix
(protocol.prefix_commit / protocol.wave_commit) in O(log K) device steps
instead of a K-step `lax.scan`.

Incremental rounds (PR 3) carry the matrix across engine rounds instead
of rebuilding it: footprints change only via re-execution, so
conflict_matrix_bits_delta recomputes just the rows/columns of the
round's live transactions (masked-row variant of the same kernel —
blocks with no live row/column skip the intersection and carry last
round's tile).

Gather-compacted rounds (PR 4) shrink the delta further: with the C live
rows gathered into a compact block, the update is two *rectangular*
products (conflict_matrix_bits_pair) — the (C, K) row strip of live
footprints against every write set and the (K, C) column strip of every
footprint against the live write sets — scattered over the carried
table (ops.conflict_matrix_delta_compact): O(C·K·W) device work with no
K² term at all, vs the masked delta's K²-shaped grid whose dead blocks
skip work but still launch.

Address lists.  The bitset form pays for the whole address space: a
(K, K) table costs K²·W word-ANDs although a transaction touches only
its L slots (at O = 2^20 records, W = 32,768 words against L = 16).  The
same verdict compares the lists themselves:

    conflict[i, j] = any_{a < 2L, b < L}( foot[i, a] == write[j, b] )

with foot = reads then writes (2L slots) and the invalid slots replaced
by two sentinels that no address (and neither other) equals — K²·2L·L
compares, no (K, W) state, no lane reduction.  The address kernels
(conflict_matrix_bits_addr_delta / _addr_pair) tile the output into
(BT_I, BT_J) blocks, footprint rows on sublanes and write sets on lanes
(the write lists come transposed, (L, K)), and OR-accumulate one
broadcast compare per (a, b) slot pair: a loop over the L write slots
whose body compares all 2L footprint slots, so the kernel's code grows
with 2L, not 2L·L.  They build every one-chip table on TPU; the bitset
kernels above stay for the sharded layout (each shard ANDs only its own
range's words, ops.*_sharded) and for the cross-batch dirty-set strip
(ops.spec_read_invalid).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BI = 8     # footprint rows per block (sublane dimension)
BJ = 128   # write-set rows per block (lane dimension of the output tile)
BW = 128   # bitset words per block

BT_I = 128  # footprint rows per address tile (sublanes)
BT_J = 128  # write-set rows per address tile (lanes)
FOOT_VOID = -1    # an invalid footprint slot
WRITE_VOID = -2   # an invalid write slot: equals no address, nor FOOT_VOID


def _conflict_kernel(foot_ref, write_ref, out_ref):
    """One (BI, BJ) output tile: out[i, j] |= any_w(foot[i, w] & write[j, w])."""
    foot = foot_ref[...]                                   # (BI, BW)
    write = write_ref[...]                                 # (BJ, BW)
    hit = (foot[:, None, :] & write[None, :, :]) != 0      # (BI, BJ, BW)
    tile = hit.sum(axis=2) > 0                             # (BI, BJ)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = tile.astype(jnp.int32)

    @pl.when(pl.program_id(2) != 0)
    def _accum():
        out_ref[...] = out_ref[...] | tile.astype(jnp.int32)


def _conflict_delta_kernel(rowlive_ref, collive_ref, foot_ref, write_ref,
                           old_ref, out_ref):
    """Masked-row variant of :func:`_conflict_kernel` for the incremental
    round update: only entries whose row OR column transaction re-executed
    this round are recomputed; the rest of the tile is carried over from
    ``old_ref``.  Blocks with no live row/column skip the bitset
    intersection entirely (`pl.when` on the tile's refresh mask) — the
    device-work saving that makes carrying the table across rounds pay.
    """
    rl = rowlive_ref[...] != 0                             # (BI, 1)
    cl = collive_ref[...] != 0                             # (BJ, 1)
    refresh = rl | cl.reshape(1, -1)                       # (BI, BJ)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        # stale entries keep the carried verdict; refreshed entries start
        # from 0 and OR-accumulate across the word grid axis below
        out_ref[...] = jnp.where(refresh, 0, old_ref[...])

    @pl.when(refresh.sum() > 0)
    def _accum():
        foot = foot_ref[...]                               # (BI, BW)
        write = write_ref[...]                             # (BJ, BW)
        hit = (foot[:, None, :] & write[None, :, :]) != 0  # (BI, BJ, BW)
        tile = (hit.sum(axis=2) > 0).astype(jnp.int32)     # (BI, BJ)
        out_ref[...] = out_ref[...] | jnp.where(refresh, tile, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def conflict_matrix_bits_delta(foot_bits: jax.Array, write_bits: jax.Array,
                               old: jax.Array, live: jax.Array,
                               *, interpret: bool) -> jax.Array:
    """Incremental (K, K) conflict update: recompute only the rows and
    columns of live (re-executed) transactions, carry ``old`` elsewhere.

    foot_bits / write_bits (K, W) int32 must already hold the CURRENT
    round's packed sets (live rows refreshed, settled rows carried —
    see ops.update_packed_footprints_sharded); ``old`` (K, K) int32 is last
    round's table and ``live`` (K,) int32 flags the re-executed rows.
    Same padding contract as :func:`conflict_matrix_bits`.
    """
    k, w = foot_bits.shape
    assert k % BI == 0 and k % BJ == 0 and w % BW == 0, (k, w)
    grid = (k // BI, k // BJ, w // BW)
    live_col = live.astype(jnp.int32).reshape(k, 1)
    out = pl.pallas_call(
        _conflict_delta_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BI, 1), lambda i, j, v: (i, 0)),
            pl.BlockSpec((BJ, 1), lambda i, j, v: (j, 0)),
            pl.BlockSpec((BI, BW), lambda i, j, v: (i, v)),
            pl.BlockSpec((BJ, BW), lambda i, j, v: (j, v)),
            pl.BlockSpec((BI, BJ), lambda i, j, v: (i, j)),
        ],
        out_specs=pl.BlockSpec((BI, BJ), lambda i, j, v: (i, j)),
        out_shape=jax.ShapeDtypeStruct((k, k), jnp.int32),
        interpret=interpret,
    )(live_col, live_col, foot_bits, write_bits, old)
    return out


@functools.partial(jax.jit, static_argnames=("interpret",))
def conflict_matrix_bits_pair(foot_bits: jax.Array, write_bits: jax.Array,
                              *, interpret: bool) -> jax.Array:
    """Rectangular bitset intersection: out (M, N) bool with
    out[i, j] = any_w(foot_bits[i, w] & write_bits[j, w]), for
    foot_bits (M, W) vs write_bits (N, W) over DIFFERENT row sets.

    The gather-compacted round update (ops.conflict_matrix_delta_compact)
    asks exactly this twice per round: a (C, K) row strip — the C live
    footprints against every write set — and a (K, C) column strip — every
    footprint against the C live write sets — instead of the full (K, K)
    product.  M must be a multiple of BI, N of BJ, W of BW (callers pad).
    """
    m, w = foot_bits.shape
    n = write_bits.shape[0]
    assert m % BI == 0 and n % BJ == 0 and w % BW == 0, (m, n, w)
    grid = (m // BI, n // BJ, w // BW)
    out = pl.pallas_call(
        _conflict_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BI, BW), lambda i, j, v: (i, v)),
            pl.BlockSpec((BJ, BW), lambda i, j, v: (j, v)),
        ],
        out_specs=pl.BlockSpec((BI, BJ), lambda i, j, v: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(foot_bits, write_bits)
    return out != 0


@functools.partial(jax.jit, static_argnames=("interpret",))
def conflict_matrix_bits(foot_bits: jax.Array, write_bits: jax.Array,
                         *, interpret: bool) -> jax.Array:
    """conflict (K, K) bool — foot_bits (K, W) int32, write_bits (K, W) int32.

    K must be a multiple of lcm(BI, BJ) and W a multiple of BW (callers
    pad; see ops.conflict_matrix).  Row i / column j of the result refer
    to the same transaction ordering as the input rows.  The square case
    of :func:`conflict_matrix_bits_pair`.
    """
    return conflict_matrix_bits_pair(foot_bits, write_bits,
                                     interpret=interpret)


def _addr_hits(foot_ref, write_ref):
    """(M, N) int32 tile: 1 where any foot[i, a] equals any write_t[b, j].
    foot (M, A) holds row i's slots along lanes, write_t (B, N) column
    j's slots along sublanes; one broadcast compare per (a, b), in a loop
    over the B write slots whose body unrolls the A footprint slots."""
    foot = foot_ref[...]
    cols = [foot[:, a:a + 1] for a in range(foot.shape[1])]

    def slot(b, hit):
        row = write_ref[pl.ds(b, 1), :]                    # (1, N)
        for col in cols:
            hit = jnp.where(col == row, 1, hit)
        return hit

    hit = jnp.zeros((foot.shape[0], write_ref.shape[1]), jnp.int32)
    return jax.lax.fori_loop(0, write_ref.shape[0], slot, hit)


def _addr_pair_kernel(foot_ref, write_ref, out_ref):
    out_ref[...] = _addr_hits(foot_ref, write_ref)


def _addr_delta_kernel(rowlive_ref, collive_ref, foot_ref, write_ref,
                       old_ref, out_ref):
    """The refresh rule of :func:`_conflict_delta_kernel` over address
    lists: entries whose row or column is live are recomputed, the rest
    carry ``old``; a tile with neither skips the compares."""
    refresh = (rowlive_ref[...] != 0) | (collive_ref[...] != 0)  # (BI, BJ)
    out_ref[...] = old_ref[...]

    @pl.when(refresh.sum() > 0)
    def _refresh():
        hit = _addr_hits(foot_ref, write_ref)
        out_ref[...] = jnp.where(refresh, hit, out_ref[...])


def _addr_tiles(m: int, n: int) -> tuple[int, int]:
    bi, bj = min(BT_I, m), min(BT_J, n)
    assert m % bi == 0 and bi % 8 == 0 and n % bj == 0 and bj % 128 == 0, \
        (m, n)
    return bi, bj


@functools.partial(jax.jit, static_argnames=("interpret",))
def conflict_matrix_bits_addr_pair(foot_addrs: jax.Array,
                                   write_addrs_t: jax.Array,
                                   *, interpret: bool) -> jax.Array:
    """Rectangular address-list conflict strip: out (M, N) bool with
    out[i, j] = any_{a, b}(foot_addrs[i, a] == write_addrs_t[b, j]).

    foot_addrs (M, A) int32 (a row's reads then writes, FOOT_VOID in
    invalid slots) against write_addrs_t (B, N) int32 (write sets,
    transposed, WRITE_VOID in invalid slots).  M must be a multiple of
    8 and, above BT_I, of BT_I; N likewise of 128 and BT_J (callers
    pad with the sentinels).  The address twin of
    :func:`conflict_matrix_bits_pair`: same verdicts, K·N·A·B compares
    instead of K·N·W words.
    """
    m, a = foot_addrs.shape
    b, n = write_addrs_t.shape
    bi, bj = _addr_tiles(m, n)
    out = pl.pallas_call(
        _addr_pair_kernel,
        grid=(m // bi, n // bj),
        in_specs=[
            pl.BlockSpec((bi, a), lambda i, j: (i, 0)),
            pl.BlockSpec((b, bj), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bi, bj), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(foot_addrs, write_addrs_t)
    return out != 0


@functools.partial(jax.jit, static_argnames=("interpret",))
def conflict_matrix_bits_addr_delta(foot_addrs: jax.Array,
                                    write_addrs_t: jax.Array,
                                    old: jax.Array, live: jax.Array,
                                    *, interpret: bool) -> jax.Array:
    """Incremental (K, K) conflict update over address lists: entry
    (i, j) is recomputed iff ``live[i]`` or ``live[j]``, else ``old``.
    Bit-identical to :func:`conflict_matrix_bits_delta` on the packed
    sets of the same lists, stale entries included.

    foot_addrs (K, 2L), write_addrs_t (L, K) as in
    :func:`conflict_matrix_bits_addr_pair`; ``old`` (K, K) int32 is last
    round's table, ``live`` (K,) int32.  K must be a multiple of
    max(BT_I, BT_J), or below both and a multiple of 128.
    """
    k, a = foot_addrs.shape
    b = write_addrs_t.shape[0]
    bi, bj = _addr_tiles(k, k)
    live = live.astype(jnp.int32)
    out = pl.pallas_call(
        _addr_delta_kernel,
        grid=(k // bi, k // bj),
        in_specs=[
            pl.BlockSpec((bi, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bj), lambda i, j: (0, j)),
            pl.BlockSpec((bi, a), lambda i, j: (i, 0)),
            pl.BlockSpec((b, bj), lambda i, j: (0, j)),
            pl.BlockSpec((bi, bj), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bi, bj), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((k, k), jnp.int32),
        interpret=interpret,
    )(live.reshape(k, 1), live.reshape(1, k), foot_addrs, write_addrs_t,
      old)
    return out
