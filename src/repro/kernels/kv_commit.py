"""Ordered KV-cache page commit kernel (serving-side Pot).

During batched decoding, request slots append their new token's K/V rows
to shared cache pages.  Under Pot, slot commits are preordered: the head
slot writes directly (fast), later slots' writes land in sequence order
and stamp the page version so speculative readers can validate
(kernels/validate.py).

TPU formulation: grid over *pages* (each page block visited exactly once —
no output-block revisit hazard); the per-slot routing metadata
(page_idx, row_idx, sn, commit) arrives as scalar-prefetch operands and
the kernel folds all S slots over its page in sequence order (grid-order-
independent, deterministic).  Slot rows live in a VMEM block; the fold is
S dynamic row updates — S is the decode batch (small), pages are the
large axis, so work is dominated by the single page-block pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kv_commit_kernel(page_idx_ref, row_idx_ref, sn_ref, commit_ref,
                      rows_ref, cache_ref, ver_ref,
                      cache_out_ref, ver_out_ref):
    p = pl.program_id(0)
    n_slots = rows_ref.shape[0]
    block = cache_ref[0]            # (page, H)
    ver = ver_ref[0, 0]             # ()

    def fold(s, carry):
        block, ver = carry
        hit = (page_idx_ref[s] == p) & (commit_ref[s] != 0)
        new_row = rows_ref[s][None].astype(block.dtype)   # (1, H)
        updated = jax.lax.dynamic_update_slice(
            block, new_row, (row_idx_ref[s], 0))
        block = jnp.where(hit, updated, block)
        ver = jnp.where(hit, sn_ref[s], ver)
        return block, ver

    block, ver = jax.lax.fori_loop(0, n_slots, fold, (block, ver))
    cache_out_ref[...] = block[None]
    ver_out_ref[...] = ver[None, None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def kv_commit(cache, versions, rows, page_idx, row_idx, sn, commit,
              *, interpret: bool):
    """Apply one decode step's slot commits to the paged KV cache.

    cache:    (P, page, H)  — paged cache (one head-group flattened to H)
    versions: (P,) int32    — page versions (sequence numbers, §3.1)
    rows:     (S, H)        — new K/V rows per slot
    page_idx: (S,) int32    — target page per slot
    row_idx:  (S,) int32    — row within the page
    sn:       (S,) int32    — slot sequence numbers (commit order: ascending)
    commit:   (S,) int32    — 1 to commit, 0 to skip (aborted/speculative)

    Slots must be supplied in sequence order; within a page the fold
    applies them in that order (last = highest sn wins, matching the
    ordered write-back of core/pcc.py).
    """
    n_pages, page, h = cache.shape
    s = rows.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_pages,),
        in_specs=[
            pl.BlockSpec((s, h), lambda i, *pref: (0, 0)),
            pl.BlockSpec((1, page, h), lambda i, *pref: (i, 0, 0)),
            pl.BlockSpec((1, 1), lambda i, *pref: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, page, h), lambda i, *pref: (i, 0, 0)),
            pl.BlockSpec((1, 1), lambda i, *pref: (i, 0)),
        ],
    )
    cache_out, ver_out = pl.pallas_call(
        _kv_commit_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(cache.shape, cache.dtype),
            jax.ShapeDtypeStruct((n_pages, 1), jnp.int32),
        ],
        interpret=interpret,
    )(page_idx, row_idx, sn, commit, rows, cache, versions.reshape(-1, 1))
    return cache_out, ver_out[:, 0]
