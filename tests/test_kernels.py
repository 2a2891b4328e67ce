"""Per-kernel allclose sweeps against the pure-jnp oracles (ref.py),
executed in interpret mode on CPU (kernels target TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.conflict import conflict_matrix_bits
from repro.kernels import conflict as conflict_mod
from repro.kernels.validate import BK, BW, pack_addr_sets, validate_bitsets


# ---------------------------------------------------------------- validate
@pytest.mark.parametrize("k,l,n_objects", [
    (1, 1, 32), (8, 4, 64), (13, 6, 300), (32, 16, 4096), (40, 3, 8192),
])
def test_validate_sweep(k, l, n_objects):
    rng = np.random.default_rng(k * 31 + l)
    ra = np.asarray(rng.integers(0, n_objects, (k, l)), np.int32)
    rn = np.asarray(rng.integers(0, l + 1, (k,)), np.int32)
    wa = np.asarray(rng.integers(0, n_objects, (max(2 * l, 4),)), np.int32)
    wn = int(rng.integers(0, len(wa) + 1))
    out = np.asarray(ops.validate(
        jnp.asarray(ra), jnp.asarray(rn), jnp.asarray(wa),
        jnp.asarray(wn, jnp.int32), n_objects))
    exp = np.array([
        bool(set(ra[i, :rn[i]].tolist()) & set(wa[:wn].tolist()))
        for i in range(k)])
    np.testing.assert_array_equal(out, exp)


def test_validate_kernel_vs_ref_dense():
    rng = np.random.default_rng(0)
    k, w = 4 * BK, 2 * BW
    read_bits = jnp.asarray(rng.integers(0, 2**31, (k, w)), jnp.int32)
    written = jnp.asarray(rng.integers(0, 2, (w,)) *
                          rng.integers(0, 2**31, (w,)), jnp.int32)
    out = validate_bitsets(read_bits, written, interpret=True)
    exp = ref.validate_bitsets_ref(read_bits, written)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


def test_fast_mode_skips_validation_semantics():
    """The head transaction needs no validation: with an empty written set
    nothing ever conflicts (progress guarantee of ordered commits)."""
    ra = jnp.asarray(np.arange(24).reshape(8, 3), jnp.int32)
    rn = jnp.full((8,), 3, jnp.int32)
    wa = jnp.zeros((4,), jnp.int32)
    out = ops.validate(ra, rn, wa, jnp.asarray(0, jnp.int32), 64)
    assert not np.asarray(out).any()


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 20), st.integers(1, 8), st.sampled_from([33, 64, 257]))
def test_validate_property(k, l, n_objects):
    rng = np.random.default_rng(k * 131 + l * 7 + n_objects)
    ra = np.asarray(rng.integers(0, n_objects, (k, l)), np.int32)
    rn = np.asarray(rng.integers(0, l + 1, (k,)), np.int32)
    wa = np.asarray(rng.integers(0, n_objects, (l,)), np.int32)
    wn = int(rng.integers(0, l + 1))
    out = np.asarray(ops.validate(
        jnp.asarray(ra), jnp.asarray(rn), jnp.asarray(wa),
        jnp.asarray(wn, jnp.int32), n_objects))
    exp = np.array([
        bool(set(ra[i, :rn[i]].tolist()) & set(wa[:wn].tolist()))
        for i in range(k)])
    np.testing.assert_array_equal(out, exp)


# --------------------------------------------------------- conflict matrix
def _packed(ra, rn, wa, wn, n_objects):
    """(footprint, write-set) bitsets, (K, ceil(O/32)) words each: the
    operands of the bitset kernels and of their ops wrappers."""
    write = pack_addr_sets(wa, wn, n_objects)
    return pack_addr_sets(ra, rn, n_objects) | write, write


def test_conflict_matrix_kernel_vs_bits_ref():
    rng = np.random.default_rng(5)
    k = max(conflict_mod.BI, conflict_mod.BJ)
    w = conflict_mod.BW
    foot = jnp.asarray(rng.integers(0, 2**31, (k, w)), jnp.int32)
    write = jnp.asarray((rng.random((k, w)) < 0.05) *
                        rng.integers(0, 2**31, (k, w)), jnp.int32)
    foot = foot | write  # footprints include the write set
    out = conflict_matrix_bits(foot, write, interpret=True)
    exp = ref.conflict_matrix_bits_ref(foot, write)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


def test_conflict_matrix_kernel_multiblock_accumulate():
    """Conflicts living in different word blocks must OR across the W grid
    axis (2 * BW words => two accumulation steps per tile)."""
    k = max(conflict_mod.BI, conflict_mod.BJ)
    w = 2 * conflict_mod.BW
    foot = np.zeros((k, w), np.int32)
    write = np.zeros((k, w), np.int32)
    foot[3, conflict_mod.BW + 7] = 1 << 11      # hit only in the 2nd block
    write[5, conflict_mod.BW + 7] = 1 << 11
    out = np.asarray(conflict_matrix_bits(
        jnp.asarray(foot), jnp.asarray(write), interpret=True))
    exp = np.zeros((k, k), bool)
    exp[3, 5] = True
    np.testing.assert_array_equal(out, exp)


@pytest.mark.parametrize("k,l,n_objects", [
    (1, 1, 32), (7, 4, 64), (20, 6, 300), (33, 3, 4096),
])
def test_conflict_matrix_op_vs_sets(k, l, n_objects):
    """ops.conflict_matrix (whichever backend path) == set intersection."""
    rng = np.random.default_rng(k * 13 + l)
    ra = np.asarray(rng.integers(0, n_objects, (k, l)), np.int32)
    rn = np.asarray(rng.integers(0, l + 1, (k,)), np.int32)
    wa = np.asarray(rng.integers(0, n_objects, (k, l)), np.int32)
    wn = np.asarray(rng.integers(0, l + 1, (k,)), np.int32)
    out = np.asarray(ops.conflict_matrix(
        jnp.asarray(ra), jnp.asarray(rn), jnp.asarray(wa), jnp.asarray(wn),
        n_objects))
    foot = [set(ra[i, :rn[i]].tolist()) | set(wa[i, :wn[i]].tolist())
            for i in range(k)]
    writes = [set(wa[j, :wn[j]].tolist()) for j in range(k)]
    exp = np.array([[bool(foot[i] & writes[j]) for j in range(k)]
                    for i in range(k)])
    np.testing.assert_array_equal(out, exp)


def test_conflict_matrix_paths_agree():
    """The dense-mask fallback and the bit-packed kernel formulation give
    identical verdicts."""
    rng = np.random.default_rng(11)
    k, l, n_objects = 17, 5, 130
    ra = jnp.asarray(rng.integers(0, n_objects, (k, l)), jnp.int32)
    rn = jnp.asarray(rng.integers(0, l + 1, (k,)), jnp.int32)
    wa = jnp.asarray(rng.integers(0, n_objects, (k, l)), jnp.int32)
    wn = jnp.asarray(rng.integers(0, l + 1, (k,)), jnp.int32)
    dense = np.asarray(ops._conflict_matrix_dense(ra, rn, wa, wn, n_objects))
    read_bits = pack_addr_sets(ra, rn, n_objects)
    write_bits = pack_addr_sets(wa, wn, n_objects)
    foot_bits = read_bits | write_bits
    rows = max(conflict_mod.BI, conflict_mod.BJ)
    pad_r = (-k) % rows
    pad_w = (-foot_bits.shape[1]) % conflict_mod.BW
    pad = lambda x: jnp.pad(x, ((0, pad_r), (0, pad_w)))
    packed = np.asarray(conflict_matrix_bits(
        pad(foot_bits), pad(write_bits), interpret=True))[:k, :k]
    np.testing.assert_array_equal(dense, packed)


@pytest.mark.parametrize("live_frac", [0.0, 0.3, 1.0])
def test_conflict_matrix_delta_kernel_vs_full(live_frac):
    """The masked-row delta kernel recomputes exactly the live rows and
    columns and carries the stale entries (incl. the all-dead and
    all-live extremes)."""
    rng = np.random.default_rng(int(live_frac * 10) + 1)
    k = max(conflict_mod.BI, conflict_mod.BJ)
    w = 2 * conflict_mod.BW   # two word blocks: delta must OR-accumulate
    mk = lambda d: jnp.asarray((rng.random((k, w)) < d) *
                               rng.integers(0, 2**31, (k, w)), jnp.int32)
    old_write = mk(0.05)
    old_foot = mk(0.2) | old_write
    old = conflict_matrix_bits(old_foot, old_write,
                               interpret=True).astype(jnp.int32)
    live = jnp.asarray(rng.random(k) < live_frac, jnp.int32)
    keep = live[:, None].astype(bool)
    new_write = jnp.where(keep, mk(0.05), old_write)
    new_foot = jnp.where(keep, mk(0.2) | new_write, old_foot)
    got = np.asarray(conflict_mod.conflict_matrix_bits_delta(
        new_foot, new_write, old, live, interpret=True)) != 0
    full = np.asarray(conflict_matrix_bits(new_foot, new_write,
                                           interpret=True))
    lv = np.asarray(live).astype(bool)
    exp = np.where(lv[:, None] | lv[None, :], full, np.asarray(old) != 0)
    np.testing.assert_array_equal(got, exp)


def test_conflict_matrix_pair_kernel_rectangular():
    """The rectangular pair kernel (the compacted delta's strip primitive)
    over different row sets == the pure-jnp reference."""
    rng = np.random.default_rng(21)
    m, n = 2 * conflict_mod.BI, conflict_mod.BJ
    w = 2 * conflict_mod.BW
    mk = lambda rows, d: jnp.asarray(
        (rng.random((rows, w)) < d) * rng.integers(0, 2**31, (rows, w)),
        jnp.int32)
    foot = mk(m, 0.2)
    write = mk(n, 0.05)
    out = np.asarray(conflict_mod.conflict_matrix_bits_pair(
        foot, write, interpret=True))
    exp = ((np.asarray(foot)[:, None, :]
            & np.asarray(write)[None, :, :]) != 0).any(axis=2)
    assert out.shape == (m, n)
    np.testing.assert_array_equal(out, exp)


def test_conflict_matrix_delta_compact_vs_masked_delta():
    """The compacted strip-scatter delta == the masked-row delta for a
    gathered live set (both backend paths share this op-level contract;
    off-TPU this exercises the dense strips)."""
    from repro.core.txn import gather_live_indices
    rng = np.random.default_rng(31)
    k, l, n_objects = 19, 4, 80
    mk = lambda: (jnp.asarray(rng.integers(0, n_objects, (k, l)), jnp.int32),
                  jnp.asarray(rng.integers(0, l + 1, (k,)), jnp.int32))
    ra, rn = mk()
    wa, wn = mk()
    foot, write = _packed(ra, rn, wa, wn, n_objects)
    old = jnp.asarray(rng.random((k, k)) < 0.2)
    for n_live in (0, 1, 7, k):
        live = np.zeros(k, bool)
        live[rng.choice(k, n_live, replace=False)] = True
        live = jnp.asarray(live)
        idx, valid = gather_live_indices(live, max(1, int(n_live)))
        ref = np.asarray(ops.conflict_matrix_delta(foot, write, old, live,
                                                   n_objects))
        got = np.asarray(ops.conflict_matrix_delta_compact(
            foot, write, old, idx, valid, n_objects))
        np.testing.assert_array_equal(got, ref, err_msg=f"n_live={n_live}")


def test_update_packed_footprints_compact_matches_masked():
    """The sharded store's packed carry: the compact update (gathered
    rows scattered back) equals the masked one."""
    from repro.core.tstore import StoreLayout
    from repro.core.txn import gather_live_indices
    rng = np.random.default_rng(44)
    k, l, n_objects = 14, 5, 90
    layout = StoreLayout(n_objects, 3)
    mk = lambda: (jnp.asarray(rng.integers(0, n_objects, (k, l)), jnp.int32),
                  jnp.asarray(rng.integers(0, l + 1, (k,)), jnp.int32))
    ra0, rn0 = mk()
    wa0, wn0 = mk()
    foot0, write0 = ops.packed_footprints_sharded(ra0, rn0, wa0, wn0, layout)
    ra1, rn1 = mk()
    wa1, wn1 = mk()
    live = jnp.asarray(rng.random(k) < 0.4)
    width = max(1, int(live.sum()))
    idx, valid = gather_live_indices(live, width)
    ref = ops.update_packed_footprints_sharded(foot0, write0, ra1, rn1, wa1,
                                               wn1, live, layout)
    got = ops.update_packed_footprints_compact_sharded(
        foot0, write0, ra1[idx], rn1[idx], wa1[idx], wn1[idx], idx, valid,
        layout)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(ref[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(ref[1]))


def test_update_packed_footprints_refreshes_live_rows_only():
    """The sharded store's packed carry re-packs the live rows in every
    shard and keeps the settled rows' words."""
    from repro.core.tstore import StoreLayout
    rng = np.random.default_rng(8)
    k, l, n_objects = 12, 5, 100
    layout = StoreLayout(n_objects, 3)
    mk = lambda: (jnp.asarray(rng.integers(0, n_objects, (k, l)), jnp.int32),
                  jnp.asarray(rng.integers(0, l + 1, (k,)), jnp.int32))
    ra0, rn0 = mk()
    wa0, wn0 = mk()
    foot0, write0 = ops.packed_footprints_sharded(ra0, rn0, wa0, wn0, layout)
    ra1, rn1 = mk()
    wa1, wn1 = mk()
    live = jnp.asarray(rng.random(k) < 0.4)
    foot, write = ops.update_packed_footprints_sharded(
        foot0, write0, ra1, rn1, wa1, wn1, live, layout)
    exp_foot, exp_write = ops.packed_footprints_sharded(ra1, rn1, wa1, wn1,
                                                        layout)
    lv = np.asarray(live)
    np.testing.assert_array_equal(np.asarray(foot)[:, lv],
                                  np.asarray(exp_foot)[:, lv])
    np.testing.assert_array_equal(np.asarray(write)[:, lv],
                                  np.asarray(exp_write)[:, lv])
    np.testing.assert_array_equal(np.asarray(foot)[:, ~lv],
                                  np.asarray(foot0)[:, ~lv])
    np.testing.assert_array_equal(np.asarray(write)[:, ~lv],
                                  np.asarray(write0)[:, ~lv])


def test_conflict_matrix_delta_op_dense_fallback():
    """ops.conflict_matrix_delta's dense fallback (the off-TPU path)
    matches the where-select semantics on unpadded shapes."""
    rng = np.random.default_rng(13)
    k, l, n_objects = 17, 4, 70
    mk = lambda: (jnp.asarray(rng.integers(0, n_objects, (k, l)), jnp.int32),
                  jnp.asarray(rng.integers(0, l + 1, (k,)), jnp.int32))
    ra, rn = mk()
    wa, wn = mk()
    foot, write = _packed(ra, rn, wa, wn, n_objects)
    old = jnp.asarray(rng.random((k, k)) < 0.2)
    live = jnp.asarray(rng.random(k) < 0.5)
    got = np.asarray(ops.conflict_matrix_delta(foot, write, old, live,
                                               n_objects))
    full = np.asarray(ops._conflict_matrix_dense(ra, rn, wa, wn, n_objects))
    lv = np.asarray(live)
    exp = np.where(lv[:, None] | lv[None, :], full, np.asarray(old))
    np.testing.assert_array_equal(got, exp)


# ------------------------------------------- conflict matrix: address lists
def _addr_case(k, l, n_objects, seed):
    """(K, L) read/write lists over ``n_objects`` with the awkward cases
    planted: empty rows (rn = wn = 0), a row with repeated addresses,
    and the addresses 0 and O - 1 (as a write, then as a read)."""
    rng = np.random.default_rng(seed)
    ra = rng.integers(0, n_objects, (k, l)).astype(np.int32)
    wa = rng.integers(0, n_objects, (k, l)).astype(np.int32)
    rn = rng.integers(0, l + 1, (k,)).astype(np.int32)
    wn = rng.integers(0, l + 1, (k,)).astype(np.int32)
    rn[:3] = 0
    wn[1:4] = 0
    ra[4], rn[4] = ra[4, 0], l                  # one address, repeated
    wa[5], wn[5] = wa[5, 0], l
    wa[6, 0], wn[6] = 0, max(wn[6], 1)
    ra[7, 0], rn[7] = 0, max(rn[7], 1)
    wa[8, 0], wn[8] = n_objects - 1, max(wn[8], 1)
    ra[9, l - 1], rn[9] = n_objects - 1, l
    return tuple(jnp.asarray(x) for x in (ra, rn, wa, wn))


def _addr_operands(ra, rn, wa, wn, rows=None, cols=None):
    """The address kernels' operands, padded with their sentinels to
    ``rows`` footprint rows and ``cols`` write-set columns."""
    foot = ops._foot_addrs(ra, rn, wa, wn)
    write_t = ops._write_addrs_t(wa, wn)
    if rows is not None:
        foot = jnp.pad(foot, ((0, rows - foot.shape[0]), (0, 0)),
                       constant_values=conflict_mod.FOOT_VOID)
    if cols is not None:
        write_t = jnp.pad(write_t, ((0, 0), (0, cols - write_t.shape[1])),
                          constant_values=conflict_mod.WRITE_VOID)
    return foot, write_t


def _bit_operands(ra, rn, wa, wn, n_objects):
    foot, write = _packed(ra, rn, wa, wn, n_objects)
    pad_w = (-foot.shape[1]) % conflict_mod.BW
    pad = lambda x: jnp.pad(x, ((0, 0), (0, pad_w)))
    return pad(foot), pad(write)


@pytest.mark.parametrize("k,l", [(256, 16), (1024, 4), (128, 8)])
@pytest.mark.parametrize("live_frac", ["none", "one_row", 0.1, 0.5, 1.0])
def test_conflict_addr_delta_kernel_equals_bitset_delta(k, l, live_frac):
    """The address-compare delta kernel gives the bitset delta kernel's
    table bit for bit (stale entries carried from the same ``old``), and
    both give the dense reference on every refreshed entry."""
    n_objects = 4000
    ra, rn, wa, wn = _addr_case(k, l, n_objects, seed=k + l)
    rng = np.random.default_rng(k * l)
    old = jnp.asarray(rng.random((k, k)) < 0.3, jnp.int32)
    if live_frac == "none":
        live = np.zeros(k, np.int32)
    elif live_frac == "one_row":
        live = np.zeros(k, np.int32)
        live[k // 3] = 1
    else:
        live = (rng.random(k) < live_frac).astype(np.int32)
    live = jnp.asarray(live)
    foot, write_t = _addr_operands(ra, rn, wa, wn)
    got = np.asarray(conflict_mod.conflict_matrix_bits_addr_delta(
        foot, write_t, old, live, interpret=True))
    fb, wb = _bit_operands(ra, rn, wa, wn, n_objects)
    exp = np.asarray(conflict_mod.conflict_matrix_bits_delta(
        fb, wb, old, live, interpret=True))
    np.testing.assert_array_equal(got, exp)
    dense = np.asarray(ops._conflict_matrix_dense(ra, rn, wa, wn, n_objects))
    lv = np.asarray(live).astype(bool)
    np.testing.assert_array_equal(
        got != 0, np.where(lv[:, None] | lv[None, :], dense,
                           np.asarray(old) != 0))


@pytest.mark.parametrize("k,l,c", [(256, 16, 64), (1024, 4, 16),
                                   (128, 8, 8)])
@pytest.mark.parametrize("strip", ["rows", "cols"])
def test_conflict_addr_pair_kernel_equals_bitset_pair(k, l, c, strip):
    """The rectangular address kernel on the compact rungs' strips —
    (C, K) live footprints against every write set, (K, C) every
    footprint against the live write sets — equals the bitset pair
    kernel and the dense reference."""
    n_objects = 4000
    ra, rn, wa, wn = _addr_case(k, l, n_objects, seed=3 * k + l)
    idx = jnp.asarray(np.random.default_rng(c).choice(k, c, replace=False),
                      jnp.int32)
    foot, write_t = _addr_operands(ra, rn, wa, wn)
    fb, wb = _bit_operands(ra, rn, wa, wn, n_objects)
    dense = np.asarray(ops._conflict_matrix_dense(ra, rn, wa, wn, n_objects))
    if strip == "rows":
        got = conflict_mod.conflict_matrix_bits_addr_pair(
            foot[idx], write_t, interpret=True)
        exp = conflict_mod.conflict_matrix_bits_pair(
            jnp.pad(fb[idx], ((0, (-c) % conflict_mod.BI), (0, 0))), wb,
            interpret=True)[:c]
        ref_strip = dense[np.asarray(idx)]
    else:
        cols = -(-c // 128) * 128
        got = conflict_mod.conflict_matrix_bits_addr_pair(
            foot, jnp.pad(write_t[:, idx], ((0, 0), (0, cols - c)),
                          constant_values=conflict_mod.WRITE_VOID),
            interpret=True)[:, :c]
        exp = conflict_mod.conflict_matrix_bits_pair(
            fb, jnp.pad(wb[idx], ((0, cols - c), (0, 0))),
            interpret=True)[:, :c]
        ref_strip = dense[:, np.asarray(idx)]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))
    np.testing.assert_array_equal(np.asarray(got), ref_strip)


# ------------------------------------------------------------- fused adamw
@pytest.mark.parametrize("shape", [(256, 256), (3, 700), (1, 1), (512, 512),
                                   (1000,)])
@pytest.mark.parametrize("gdtype", [jnp.float32, jnp.bfloat16])
def test_adamw_sweep(shape, gdtype):
    rng = np.random.default_rng(sum(shape))
    p = jnp.asarray(rng.normal(size=shape), jnp.float32)
    m = jnp.asarray(rng.normal(size=shape) * 0.1, jnp.float32)
    v = jnp.asarray(np.abs(rng.normal(size=shape)) * 0.01, jnp.float32)
    g = jnp.asarray(rng.normal(size=shape), gdtype)
    got = ops.adamw_update(p, m, v, g, step=7, lr=3e-4, wd=0.1)
    exp = ref.adamw_ref(p, m, v, g, step=7, lr=3e-4, wd=0.1)
    for a, b in zip(got, exp):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-5, atol=1e-7)


def test_adamw_no_nan_large_steps():
    p = jnp.ones((256, 256)) * 1e3
    m = jnp.zeros_like(p)
    v = jnp.zeros_like(p)
    g = jnp.ones_like(p) * 1e3
    p2, m2, v2 = ops.adamw_update(p, m, v, g, step=1)
    assert np.isfinite(np.asarray(p2)).all()


@pytest.mark.parametrize("stale_frac", [0.0, 0.5, 1.0])
def test_adamw_speculative_aborts_stale_blocks(stale_frac):
    rng = np.random.default_rng(int(stale_frac * 10))
    r = c = 512
    p = jnp.asarray(rng.normal(size=(r, c)), jnp.float32)
    m = jnp.zeros((r, c))
    v = jnp.zeros((r, c))
    g = jnp.asarray(rng.normal(size=(r, c)), jnp.float32)
    gr, gc = r // 256, c // 256
    versions = jnp.asarray(
        (rng.random((gr, gc)) < stale_frac).astype(np.int32) * 10, jnp.int32)
    rv = jnp.asarray(5, jnp.int32)
    got = ops.adamw_update_speculative(p, m, v, g, versions, rv, step=2)
    exp = ref.adamw_speculative_ref(p, m, v, g, versions, rv, step=2)
    for a, b in zip(got, exp):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-5, atol=1e-7)
    n_stale = int((np.asarray(versions) > 5).sum())
    assert int(np.asarray(got[3]).sum()) == n_stale
    if stale_frac == 1.0:  # everything aborted -> params untouched
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(p))


# --------------------------------------------------------------- kv commit
@pytest.mark.parametrize("p,page,h,s", [
    (4, 2, 8, 3), (8, 4, 16, 5), (16, 8, 128, 8), (2, 1, 8, 4),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kv_commit_sweep(p, page, h, s, dtype):
    rng = np.random.default_rng(p * 7 + s)
    cache = jnp.asarray(rng.normal(size=(p, page, h)), dtype)
    versions = jnp.asarray(rng.integers(0, 3, (p,)), jnp.int32)
    rows = jnp.asarray(rng.normal(size=(s, h)), jnp.float32)
    page_idx = jnp.asarray(rng.integers(0, p, (s,)), jnp.int32)
    row_idx = jnp.asarray(rng.integers(0, page, (s,)), jnp.int32)
    sn = jnp.arange(10, 10 + s, dtype=jnp.int32)
    commit = jnp.asarray(rng.integers(0, 2, (s,)), jnp.int32)
    got_c, got_v = ops.kv_cache_commit(cache, versions, rows, page_idx,
                                       row_idx, sn, commit)
    exp_c, exp_v = ref.kv_commit_ref(cache, versions, rows, page_idx,
                                     row_idx, sn, commit)
    np.testing.assert_allclose(np.asarray(got_c, np.float32),
                               np.asarray(exp_c, np.float32))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(exp_v))


def test_kv_commit_order_last_writer_wins():
    """Two slots commit to the same (page, row): the higher sequence number
    (later slot) must win — ordered commit semantics."""
    cache = jnp.zeros((2, 2, 8), jnp.float32)
    versions = jnp.zeros((2,), jnp.int32)
    rows = jnp.stack([jnp.full((8,), 1.0), jnp.full((8,), 2.0)])
    page_idx = jnp.asarray([1, 1], jnp.int32)
    row_idx = jnp.asarray([0, 0], jnp.int32)
    sn = jnp.asarray([5, 6], jnp.int32)
    commit = jnp.asarray([1, 1], jnp.int32)
    got_c, got_v = ops.kv_cache_commit(cache, versions, rows, page_idx,
                                       row_idx, sn, commit)
    assert float(got_c[1, 0, 0]) == 2.0
    assert int(got_v[1]) == 6


def test_kv_commit_speculative_slots_skipped():
    cache = jnp.zeros((2, 2, 8), jnp.float32)
    versions = jnp.zeros((2,), jnp.int32)
    rows = jnp.ones((1, 8), jnp.float32)
    got_c, got_v = ops.kv_cache_commit(
        cache, versions, rows, jnp.asarray([0], jnp.int32),
        jnp.asarray([0], jnp.int32), jnp.asarray([9], jnp.int32),
        jnp.asarray([0], jnp.int32))
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(cache))
    assert int(got_v[0]) == 0
