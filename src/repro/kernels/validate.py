"""Read-set validation kernel (TL2 validation phase, paper Fig. 3b 23-26).

TPU adaptation (DESIGN.md §2): the paper's validation loop gathers one
version word per read address — an irregular gather that is hostile to the
TPU memory system.  The TPU-native formulation is *dense bitset
validation*: read sets are bit-packed into (K, W) int32 words (W = ceil
(n_objects/32)) and the committed-writes-since-``rv`` set into (1, W);
a transaction conflicts iff any AND of its row with the written set is
non-zero.  This turns validation into a perfectly-tiled VPU reduction:
VMEM blocks of (BK, BW) words, OR-accumulated across the W grid axis.

The fast transaction (paper §2.2.3) skips this kernel launch entirely —
that is precisely its "no validation phase".
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BK = 8     # transactions per block (sublane dimension)
BW = 128   # bitset words per block (lane dimension)


def _validate_kernel(read_ref, written_ref, out_ref):
    """One (BK, BW) tile: conflict |= any(read & written) per row."""
    hit = (read_ref[...] & written_ref[...]) != 0          # (BK, BW) bool
    any_hit = hit.sum(axis=1, keepdims=True) > 0           # (BK, 1)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = any_hit.astype(jnp.int32)

    @pl.when(pl.program_id(1) != 0)
    def _accum():
        out_ref[...] = out_ref[...] | any_hit.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def validate_bitsets(read_bits: jax.Array, written_bits: jax.Array,
                     *, interpret: bool) -> jax.Array:
    """conflict (K,) bool — read_bits (K, W) int32, written_bits (W,) int32.

    K must be a multiple of BK and W a multiple of BW (callers pad; see
    ops.validate).
    """
    k, w = read_bits.shape
    assert k % BK == 0 and w % BW == 0, (k, w)
    grid = (k // BK, w // BW)
    out = pl.pallas_call(
        _validate_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BK, BW), lambda i, j: (i, j)),
            pl.BlockSpec((1, BW), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((BK, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((k, 1), jnp.int32),
        interpret=interpret,
    )(read_bits, written_bits.reshape(1, w))
    return out[:, 0] != 0


def pack_addr_sets(addrs: jax.Array, n: jax.Array, n_objects: int) -> jax.Array:
    """Bit-pack (K, L) masked address sets into (K, ceil(O/32)) int32.

    Pure-jnp helper (runs under jit); the scatter is regular enough for
    XLA — the hot reduction is the Pallas kernel above.
    """
    length = addrs.shape[1]
    valid = jnp.arange(length)[None, :] < n[:, None]
    return pack_addr_sets_masked(addrs, valid, n_objects)


def pack_addr_sets_masked(addrs: jax.Array, valid: jax.Array,
                          n_objects: int) -> jax.Array:
    """Bit-pack (K, L) address sets under an explicit (K, L) validity mask.

    The shard-partitioned packing primitive (PR 5): a shard packs only
    the slots whose address falls inside its range, so ``valid`` is not
    expressible as a per-row prefix count.  Addresses must already be
    shard-local (callers subtract the shard base); invalid slots may
    hold any value — they are routed to the out-of-range word and
    dropped.
    """
    k, length = addrs.shape
    w = -(-n_objects // 32)
    word = addrs // 32
    bit = (jnp.uint32(1) << (addrs % 32).astype(jnp.uint32)).astype(jnp.uint32)
    word = jnp.where(valid, word, w)  # out-of-range -> dropped

    def body(j, acc):
        cur = acc[jnp.arange(k), jnp.clip(word[:, j], 0, w - 1)]
        new = cur | jnp.where(valid[:, j], bit[:, j], jnp.uint32(0))
        return acc.at[jnp.arange(k), word[:, j]].set(new, mode="drop")

    bits = jax.lax.fori_loop(0, length, body,
                             jnp.zeros((k, w), jnp.uint32))
    return bits.astype(jnp.int32)
