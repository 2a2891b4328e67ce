"""Compile the chip path for a described TPU v5e, with no chip attached.

The TPU's compiler refuses what interpret mode accepts (unaligned
slices, kernels that cannot be partitioned, programs that do not fit
the device), so the served path's kernels and steps are compiled here
at the smoke's real sizes: a 2^20-record store, K = 256 transactions.
Nothing runs; these tests say nothing about results or times.

The topology is described inside a module-scope fixture, never at
import: only one process may load the TPU library, and every test
worker imports this file.  The engines pick their kernel path with
``kernels.ops._on_tpu()``, which sees the CPU here, so each step test
steers it to the TPU branch with ``monkeypatch``.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core import get_engine
from repro.core.tstore import ShardedStore, TStore
from repro.core.txn import TxnBatch
from repro.kernels import conflict as C
from repro.kernels import ops
from repro.launch.mesh import make_host_mesh

N_RECORDS = 1 << 20
K, L = 256, 4
W = N_RECORDS // 32
KERNEL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back without one
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_branch(monkeypatch):
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _step_args(store, sharding):
    i32 = jnp.int32
    batch = TxnBatch(opcodes=_sds((K, L), i32, sharding),
                     addrs=_sds((K, L), i32, sharding),
                     indirect=_sds((K, L), jnp.bool_, sharding),
                     operands=_sds((K, L), i32, sharding),
                     n_ins=_sds((K,), i32, sharding))
    return (store, batch, _sds((K,), i32, sharding),
            _sds((K,), i32, sharding), 8)


def test_pair_kernel_compiles(one_chip):
    words = _sds((K, W), jnp.int32, one_chip)
    compiled = jax.jit(C.conflict_matrix_bits_pair,
                       static_argnames="interpret").lower(
        words, words, interpret=False).compile()
    assert KERNEL in compiled.as_text()


def test_delta_kernel_compiles(one_chip):
    words = _sds((K, W), jnp.int32, one_chip)
    compiled = jax.jit(C.conflict_matrix_bits_delta,
                       static_argnames="interpret").lower(
        words, words, _sds((K, K), jnp.int32, one_chip),
        _sds((K,), jnp.int32, one_chip), interpret=False).compile()
    assert KERNEL in compiled.as_text()


@pytest.mark.parametrize("k,length", [(256, 16), (1024, 4)])
def test_addr_kernels_compile(one_chip, k, length):
    """The address kernels at the served cells' shapes: YCSB's 256 txns
    of 16 slots, p2p's 1,024 of 4; the pair kernel on a 64-row strip."""
    i32 = jnp.int32
    delta = jax.jit(C.conflict_matrix_bits_addr_delta,
                    static_argnames="interpret").lower(
        _sds((k, 2 * length), i32, one_chip),
        _sds((length, k), i32, one_chip), _sds((k, k), i32, one_chip),
        _sds((k,), i32, one_chip), interpret=False).compile()
    pair = jax.jit(C.conflict_matrix_bits_addr_pair,
                   static_argnames="interpret").lower(
        _sds((64, 2 * length), i32, one_chip),
        _sds((length, k), i32, one_chip), interpret=False).compile()
    assert KERNEL in delta.as_text() and KERNEL in pair.as_text()


def test_spec_strip_compiles(one_chip, tpu_branch):
    """The (K x 128) strip of the cross-batch validation: K read sets
    against the one-row dirty bitmap padded to a 128-row block."""
    compiled = jax.jit(ops.spec_read_invalid, static_argnums=4).lower(
        _sds((K, L), jnp.int32, one_chip), _sds((K,), jnp.int32, one_chip),
        _sds((N_RECORDS,), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip), N_RECORDS).compile()
    assert KERNEL in compiled.as_text()


def test_pcc_step_compiles_one_chip(one_chip, tpu_branch):
    store = TStore(values=_sds((N_RECORDS, 1), jnp.int32, one_chip),
                   versions=_sds((N_RECORDS,), jnp.int32, one_chip),
                   gv=_sds((), jnp.int32, one_chip))
    compiled = jax.jit(get_engine("pcc").raw, static_argnums=4).lower(
        *_step_args(store, one_chip)).compile()
    text = compiled.as_text()
    # short transactions over 2^20 records: the conflict table comes from
    # the address kernels, and no (K, W) bitset is built or carried
    kernels = [line.split()[0] for line in text.splitlines()
               if KERNEL in line]
    assert any(k.startswith("%conflict_matrix_bits_addr") for k in kernels)
    assert f"s32[{K},{W}]" not in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2**30


def test_pcc_step_compiles_four_shards(topo, tpu_branch):
    """The sharded store's pcc step over a 2x2 mesh, one shard per chip:
    every Pallas call sits inside a shard_map, so XLA has no kernel to
    partition."""
    mesh = make_host_mesh(4, "shard", devices=topo.devices)
    shard = NamedSharding(mesh, PartitionSpec("shard"))
    rep = NamedSharding(mesh, PartitionSpec())
    c = N_RECORDS // 4
    store = ShardedStore(values=_sds((4, c, 1), jnp.int32, shard),
                         versions=_sds((4, c), jnp.int32, shard),
                         gv=_sds((), jnp.int32, rep),
                         n_objects=N_RECORDS, mesh=mesh)
    compiled = jax.jit(get_engine("pcc").raw, static_argnums=4).lower(
        *_step_args(store, rep)).compile()
    assert KERNEL in compiled.as_text()
    # the committed store stays one shard per chip
    assert compiled.output_shardings[0].values.is_equivalent_to(shard, 3)
