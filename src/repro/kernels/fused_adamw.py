"""Fast-mode direct parameter commit: fused AdamW update kernel.

The paper's fast transaction merges read and write phases and installs
updates *in place* with no tracking (§2.2.3, Fig. 3c).  For the framework's
highest-volume transaction — committing a gradient into the parameter
store — the fast path is a fused optimizer update: one pass over
(p, m, v, g) producing (p', m', v') with all element-wise math fused, so
each parameter word moves HBM→VMEM→HBM exactly once.  Unfused XLA would
be 3 reads + 3 writes per state; the fusion is the direct-update win.

The *speculative* variant (``fused_adamw_speculative``) is the same
update guarded by TL2-style version validation: it carries the per-block
version word tile + the transaction's read version ``rv`` and applies the
update only where ``version <= rv`` (stale blocks are left untouched and
reported for retry).  The extra operands/scratch are exactly the paper's
"read set maintenance" — and the reason the fast path has a larger usable
VMEM tile budget (the ROT capacity story of Fig. 13, measured in
benchmarks/fig13_capacity.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BR = 256   # rows per block
BC = 256   # cols per block (lane multiple)


def _adamw_kernel(hp_ref, p_ref, m_ref, v_ref, g_ref,
                  po_ref, mo_ref, vo_ref):
    """hp = [lr, b1, b2, eps, wd, bc1, bc2, 0] as a (1, 8) f32 block."""
    lr, b1, b2, eps = hp_ref[0, 0], hp_ref[0, 1], hp_ref[0, 2], hp_ref[0, 3]
    wd, bc1, bc2 = hp_ref[0, 4], hp_ref[0, 5], hp_ref[0, 6]
    g = g_ref[...].astype(jnp.float32)
    m = b1 * m_ref[...] + (1.0 - b1) * g
    v = b2 * v_ref[...] + (1.0 - b2) * g * g
    mhat = m / bc1
    vhat = v / bc2
    p = p_ref[...]
    p = p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p)
    po_ref[...] = p
    mo_ref[...] = m
    vo_ref[...] = v


def _adamw_spec_kernel(hp_ref, ver_ref, p_ref, m_ref, v_ref, g_ref,
                       po_ref, mo_ref, vo_ref, abort_ref):
    """Speculative variant: validate block versions against rv before
    applying (rv passed as hp[0, 7]); stale blocks abort (left unchanged)."""
    rv = hp_ref[0, 7]
    stale = (ver_ref[...].astype(jnp.float32) > rv).sum() > 0

    lr, b1, b2, eps = hp_ref[0, 0], hp_ref[0, 1], hp_ref[0, 2], hp_ref[0, 3]
    wd, bc1, bc2 = hp_ref[0, 4], hp_ref[0, 5], hp_ref[0, 6]
    g = g_ref[...].astype(jnp.float32)
    m = b1 * m_ref[...] + (1.0 - b1) * g
    v = b2 * v_ref[...] + (1.0 - b2) * g * g
    mhat = m / bc1
    vhat = v / bc2
    p = p_ref[...]
    pn = p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p)

    ok = ~stale
    po_ref[...] = jnp.where(ok, pn, p)
    mo_ref[...] = jnp.where(ok, m, m_ref[...])
    vo_ref[...] = jnp.where(ok, v, v_ref[...])
    abort_ref[...] = jnp.full_like(abort_ref, stale.astype(jnp.int32))


def _hp_vector(lr, b1, b2, eps, wd, step, rv=0.0):
    step = jnp.asarray(step, jnp.float32)
    bc1 = 1.0 - jnp.power(jnp.float32(b1), step)
    bc2 = 1.0 - jnp.power(jnp.float32(b2), step)
    return jnp.stack([
        jnp.float32(lr), jnp.float32(b1), jnp.float32(b2), jnp.float32(eps),
        jnp.float32(wd), bc1, bc2, jnp.asarray(rv, jnp.float32),
    ]).reshape(1, 8)


@functools.partial(jax.jit,
                   static_argnames=("lr", "b1", "b2", "eps", "wd",
                                    "interpret"))
def fused_adamw(p, m, v, g, *, step, lr=1e-3, b1=0.9, b2=0.999, eps=1e-8,
                wd=0.01, interpret: bool):
    """Fast-mode (direct update) fused AdamW.  p/m/v f32 (R, C), g f32/bf16.

    R % BR == 0 and C % BC == 0 (ops.py pads/reshapes arbitrary pytrees).
    """
    r, c = p.shape
    assert r % BR == 0 and c % BC == 0, (r, c)
    hp = _hp_vector(lr, b1, b2, eps, wd, step)
    grid = (r // BR, c // BC)
    return pl.pallas_call(
        _adamw_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 8), lambda i, j: (0, 0)),
            pl.BlockSpec((BR, BC), lambda i, j: (i, j)),
            pl.BlockSpec((BR, BC), lambda i, j: (i, j)),
            pl.BlockSpec((BR, BC), lambda i, j: (i, j)),
            pl.BlockSpec((BR, BC), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((BR, BC), lambda i, j: (i, j)),
            pl.BlockSpec((BR, BC), lambda i, j: (i, j)),
            pl.BlockSpec((BR, BC), lambda i, j: (i, j)),
        ],
        out_shape=[jax.ShapeDtypeStruct((r, c), jnp.float32)] * 3,
        interpret=interpret,
    )(hp, p, m, v, g)


@functools.partial(jax.jit,
                   static_argnames=("lr", "b1", "b2", "eps", "wd",
                                    "interpret"))
def fused_adamw_speculative(p, m, v, g, versions, rv, *, step, lr=1e-3,
                            b1=0.9, b2=0.999, eps=1e-8, wd=0.01,
                            interpret: bool):
    """Speculative-mode update: per-block version validation against rv.

    versions: (R//BR, C//BC) int32 block versions.  Returns
    (p', m', v', abort (R//BR, C//BC) int32).
    """
    r, c = p.shape
    assert r % BR == 0 and c % BC == 0, (r, c)
    gr, gc = r // BR, c // BC
    hp = _hp_vector(lr, b1, b2, eps, wd, step, rv=rv)
    outs = pl.pallas_call(
        _adamw_spec_kernel,
        grid=(gr, gc),
        in_specs=[
            pl.BlockSpec((1, 8), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (i, j)),
            pl.BlockSpec((BR, BC), lambda i, j: (i, j)),
            pl.BlockSpec((BR, BC), lambda i, j: (i, j)),
            pl.BlockSpec((BR, BC), lambda i, j: (i, j)),
            pl.BlockSpec((BR, BC), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((BR, BC), lambda i, j: (i, j)),
            pl.BlockSpec((BR, BC), lambda i, j: (i, j)),
            pl.BlockSpec((BR, BC), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (i, j)),
        ],
        out_shape=[jax.ShapeDtypeStruct((r, c), jnp.float32)] * 3
        + [jax.ShapeDtypeStruct((gr, gc), jnp.int32)],
        interpret=interpret,
    )(hp, versions, p, m, v, g)
    return outs
