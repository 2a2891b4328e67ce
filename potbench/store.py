"""The store's initial words, made on the device in one jitted call.

The same hash of (seed, record, word) as ``reference.init_values``, in
jax.numpy; the reference evaluates it in NumPy, so a fault in either shows
as a mismatch."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_M1, _M2 = 0x85EBCA6B, 0xC2B2AE35


def _fmix(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(_M1)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(_M2)
    return x ^ (x >> 16)


@functools.partial(jax.jit, static_argnums=(2, 3))
def init_values_device(seed_lo, seed_hi, n_records: int, slot: int):
    """(n_records, slot) int32 initial words, each in [0, 2^16)."""
    s = _fmix(seed_lo ^ _fmix(seed_hi + jnp.uint32(0x165667B1)))
    c = _fmix(jnp.arange(slot, dtype=jnp.uint32) * jnp.uint32(0x27D4EB2F) ^ s)
    r = jnp.arange(n_records, dtype=jnp.uint32) * jnp.uint32(0x9E3779B1)
    return (_fmix(r[:, None] ^ c[None, :]) >> 16).astype(jnp.int32)
