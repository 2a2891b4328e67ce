"""Deterministic ordered gradient reduction — Pot's ordered commits
applied to the data-parallel gradient transaction (DESIGN.md §3).

Float addition is non-associative: an all-reduce whose internal schedule
varies with timing/topology yields bitwise-different sums, so replicated
trainers diverge — the exact nondeterminism Pot removes from TM programs.
Here the sequencer's order is the lane (shard) index, and the reduction
follows a FIXED ring schedule implemented with ``lax.ppermute``:
shard i adds its contribution in ring position order, so the float
summation order is a pure function of the mesh, never of timing.

- ``ordered_ring_reduce``: reduce-scatter + all-gather over a named mesh
  axis (inside shard_map), 2(n-1) unrolled ppermute steps, summation
  order = ring order (bitwise deterministic).
- ``ordered_tree_sum``: fixed-order pairwise tree over a stacked leading
  axis (microbatch lanes inside one device) — the in-chip analog.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def ordered_ring_reduce(x: jax.Array, axis_name: str) -> jax.Array:
    """Bitwise-deterministic all-reduce(sum) over ``axis_name``.

    Must run inside shard_map.  x: the local shard's contribution.
    Equivalent to lax.psum(x, axis_name) with a fixed summation order.
    """
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    idx = jax.lax.axis_index(axis_name)
    perm_fwd = [(i, (i + 1) % n) for i in range(n)]

    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    chunks = flat.reshape(n, -1)

    # --- reduce-scatter: partial for chunk c starts at shard c and walks
    # the ring; the summation order of chunk c is c, c+1, ..., c-1 — a
    # fixed function of ring position (never of timing).
    acc = chunks[idx]
    for s in range(n - 1):
        acc = jax.lax.ppermute(acc, axis_name, perm_fwd)
        acc = acc + jnp.take(chunks, (idx - 1 - s) % n, axis=0)
    # shard i now holds the full sum of chunk (i + 1) % n.

    # --- all-gather the reduced chunks around the same ring
    gathered = jnp.zeros_like(chunks)
    gathered = gathered.at[(idx + 1) % n].set(acc)
    cur = acc
    for s in range(n - 1):
        cur = jax.lax.ppermute(cur, axis_name, perm_fwd)
        gathered = gathered.at[(idx - s) % n].set(cur)
    out = gathered.reshape(-1)
    if pad:
        out = out[:-pad]
    return out.reshape(x.shape)


def ordered_tree_sum(stacked: jax.Array) -> jax.Array:
    """Fixed-order pairwise-tree sum over axis 0 (lane order = sequence
    order).  Deterministic regardless of how XLA would schedule a plain
    ``sum``; used for microbatch-lane gradient commits inside a device."""
    n = stacked.shape[0]
    x = stacked
    while x.shape[0] > 1:
        m = x.shape[0]
        if m % 2 == 1:
            x = jnp.concatenate([x, jnp.zeros_like(x[:1])], axis=0)
            m += 1
        x = x[0::2] + x[1::2]
    return x[0]
