"""Transactional object store (TStore) + store-layout abstraction.

The TPU/JAX analog of the paper's shared mutable heap + TL2 metadata.
Since PR 5 the store is a *layout-polymorphic* pytree: the protocol
layer only ever talks to it through :class:`StoreLayout`, and two
concrete layouts implement it:

- :class:`TStore` — the dense layout (the S=1 degenerate case):

  * ``values``   (O, S) int32  — O objects, each a slot-vector of S words.
  * ``versions`` (O,)   int32  — per-object version = sequence number of
    the last committed writer (the paper retrofits sequence numbers as
    TL2 versions, §3.1 "Speculative STM transaction"); 0 = initial state.
  * ``gv``       ()     int32  — global version = sequence number of the
    last committed transaction (the paper's ``gv``/``sn_c``).

- :class:`ShardedStore` — the address space partitioned into S
  contiguous range shards of C = ceil(O/S) objects each (object ``a``
  lives in shard ``a // C`` at offset ``a % C``):

  * ``values``   (S, C, slot) int32 — stacked shard images (the last
    shard may carry padding rows past object O-1; they are never
    addressed, never written, and excluded from the fingerprint);
  * ``versions`` (S, C) int32; ``gv`` () int32 as above.

  Nothing in Pot's protocol requires one dense address space: the
  global serialization order lives in *rank* space (per transaction),
  while footprints, conflict analysis, and write-back all decompose
  per address — hence per shard.  ``ShardedStore`` is bit-identical to
  the dense store under every engine (same fingerprints, traces and
  replay logs; asserted in tests/test_sharded_store.py and
  ``scripts/ci.sh --shard-smoke``): the layout changes only *where*
  device work happens, never a decision.

The store is a pure pytree threaded through ``jax.lax`` control flow;
all engines (OCC / PCC / PoGL / DeSTM-analog) transform it
functionally.  ``DenseStore`` is an alias of ``TStore``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class StoreLayout:
    """Static description of how the object address space is laid out.

    ``shards`` contiguous ranges of ``shard_size`` objects each; global
    address ``a`` maps to ``(a // shard_size, a % shard_size)``.  The
    dense store is the ``shards == 1`` case.  ``mesh`` optionally names
    a 1-axis :class:`jax.sharding.Mesh` of exactly ``shards`` devices —
    when present, the stacked shards live one per device and the
    per-shard write-back scatters and conflict kernels run under
    ``jax.shard_map`` over it; when absent they run on a single device.
    Hashable (a static jit constant): it travels on the store pytree as
    a meta field, so the engine step specializes per layout.
    """

    n_objects: int
    shards: int = 1
    mesh: object | None = None   # jax.sharding.Mesh (hashable) or None

    @property
    def shard_size(self) -> int:
        """Objects per shard C = ceil(O/S); the last shard may pad."""
        return -(-self.n_objects // self.shards)

    @property
    def padded_objects(self) -> int:
        """S * C >= O — the flat length of the stacked shard images."""
        return self.shards * self.shard_size

    @property
    def sharded(self) -> bool:
        """True iff the store's arrays carry the stacked-shard axes.

        A 1-shard layout WITH a mesh still counts: its arrays are
        (1, C, slot) and its write-back runs under shard_map, so it
        must route through the sharded code paths (every
        :class:`ShardedStore` instance satisfies ``shards > 1 or mesh``
        — :func:`shard_store` returns the dense store otherwise)."""
        return self.shards > 1 or self.mesh is not None

    @property
    def words_per_shard(self) -> int:
        """Packed-bitset width per shard, ceil(C/32) — the conflict
        kernels' W axis shrinks by S under the sharded layout."""
        return -(-self.shard_size // 32)

    def shard_of(self, addr: jax.Array) -> jax.Array:
        return addr // self.shard_size

    def offset_of(self, addr: jax.Array) -> jax.Array:
        return addr % self.shard_size


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TStore:
    values: jax.Array    # (O, S) int32
    versions: jax.Array  # (O,)   int32
    gv: jax.Array        # ()     int32

    @property
    def n_objects(self) -> int:
        return self.values.shape[0]

    @property
    def slot(self) -> int:
        return self.values.shape[1]

    @property
    def layout(self) -> StoreLayout:
        return StoreLayout(self.n_objects, 1)


DenseStore = TStore  # the S=1 degenerate case of the layout abstraction


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("values", "versions", "gv"),
                   meta_fields=("n_objects", "mesh"))
@dataclasses.dataclass
class ShardedStore:
    """Range-partitioned store: S stacked shard images (see module doc).

    ``n_objects`` and ``mesh`` are static pytree *meta* fields: the real
    object count cannot be recovered from the (padded) array shapes, and
    the mesh must be a hashable jit constant for the shard_map path.
    """

    values: jax.Array    # (S, C, slot) int32
    versions: jax.Array  # (S, C)       int32
    gv: jax.Array        # ()           int32
    n_objects: int       # real object count (required: the padded array
    #   shapes cannot recover it, and a zero default would silently give
    #   shard_size == 0 addressing)
    mesh: object | None = None

    @property
    def shards(self) -> int:
        return self.values.shape[0]

    @property
    def shard_size(self) -> int:
        return self.values.shape[1]

    @property
    def slot(self) -> int:
        return self.values.shape[2]

    @property
    def layout(self) -> StoreLayout:
        return StoreLayout(self.n_objects, self.shards, self.mesh)


def flat_values(values: jax.Array, layout: StoreLayout | None) -> jax.Array:
    """The executor-facing flat (O_pad, slot) view of a store image.

    For the dense layout this is the image itself; for the sharded
    layout it is a free reshape of the stacked (S, C, slot) shards —
    contiguous-range partitioning means shard s's row c IS global
    object s*C + c, so the flat view needs no permutation.  Rows past
    ``layout.n_objects`` are padding and are never addressed (every
    effective address is reduced mod n_objects).
    """
    if layout is None or not layout.sharded:
        return values
    s, c, slot = values.shape
    return values.reshape(s * c, slot)


def store_with(store, values, versions, gv):
    """Rebuild a store of the same layout around new contents."""
    return dataclasses.replace(store, values=values, versions=versions,
                               gv=gv)


def make_store(n_objects: int, slot: int = 1, init=None, *,
               shards: int = 1, mesh=None) -> TStore | ShardedStore:
    """Create a fresh store. ``init`` is an optional (O, S) initial image.

    ``shards > 1`` returns a :class:`ShardedStore` over ``shards``
    contiguous address ranges (bit-identical semantics; see module doc).
    ``mesh`` optionally places one shard per device for the write-back
    scatters (requires a 1-axis mesh of exactly ``shards`` devices).
    """
    if init is None:
        values = jnp.zeros((n_objects, slot), dtype=jnp.int32)
    else:
        values = jnp.asarray(init, dtype=jnp.int32).reshape(n_objects, -1)
    dense = TStore(
        values=values,
        versions=jnp.zeros((n_objects,), dtype=jnp.int32),
        gv=jnp.zeros((), dtype=jnp.int32),
    )
    if shards == 1 and mesh is None:
        return dense
    return shard_store(dense, shards, mesh=mesh)


def shard_store(store: TStore, shards: int, mesh=None):
    """Partition a dense store into ``shards`` contiguous range shards.

    Pads the address space up to S * ceil(O/S) (padding rows are inert:
    never addressed, never written, excluded from the fingerprint).
    ``shards == 1`` without a mesh is the dense layout already — the
    store is returned unchanged, so every :class:`ShardedStore` that
    exists routes through the sharded code paths (see
    ``StoreLayout.sharded``).
    """
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    if shards == 1 and mesh is None:
        return store
    layout = StoreLayout(store.n_objects, shards, mesh)
    pad = layout.padded_objects - store.n_objects
    values = jnp.pad(store.values, ((0, pad), (0, 0))).reshape(
        shards, layout.shard_size, store.slot)
    versions = jnp.pad(store.versions, (0, pad)).reshape(
        shards, layout.shard_size)
    gv = store.gv
    if mesh is not None:
        sizes = tuple(mesh.shape.values())
        if len(sizes) != 1 or sizes[0] != shards:
            raise ValueError(
                f"mesh must have exactly one axis of size shards={shards}, "
                f"got axes {dict(mesh.shape)}")
        if any(t != jax.sharding.AxisType.Auto for t in mesh.axis_types):
            raise ValueError(
                f"the store mesh needs an Auto axis, got {mesh.axis_types}: "
                f"build it with repro.launch.mesh.make_host_mesh")
        # one shard per device: shard s's (C, slot) image on device s;
        # gv replicated, as the step returns it
        spec = jax.sharding.PartitionSpec
        values, versions = jax.device_put(
            (values, versions),
            jax.sharding.NamedSharding(mesh, spec(mesh.axis_names[0])))
        gv = jax.device_put(gv, jax.sharding.NamedSharding(mesh, spec()))
    return ShardedStore(values=values, versions=versions, gv=gv,
                        n_objects=store.n_objects, mesh=mesh)


def unshard_store(store) -> TStore:
    """Reassemble the dense image of a sharded store (drops padding).
    Idempotent: a dense store is returned unchanged."""
    if isinstance(store, TStore):
        return store
    o = store.n_objects
    return TStore(
        values=store.values.reshape(-1, store.slot)[:o],
        versions=store.versions.reshape(-1)[:o],
        gv=store.gv)


def shard_images(store) -> list[tuple[jax.Array, jax.Array]]:
    """Per-shard ``(values, versions)`` images, trimmed to real rows.

    The snapshot serialization form (repro.core.checkpoint): one image
    per shard — (C, slot) values + (C,) versions, with the last shard's
    padding rows dropped — whose concatenation IS the dense store image.
    A dense store yields its single full image.  Because the shards are
    contiguous address ranges, a snapshot written at S shards restores
    into any S' by concatenating and re-sharding.
    """
    if isinstance(store, TStore):
        return [(store.values, store.versions)]
    o, c = store.n_objects, store.shard_size
    out = []
    for s in range(store.shards):
        rows = min(o, (s + 1) * c) - min(o, s * c)
        out.append((store.values[s, :rows], store.versions[s, :rows]))
    return out


def dense_image(store) -> jax.Array:
    """The (O, slot) committed image of any store layout."""
    if isinstance(store, ShardedStore):
        return store.values.reshape(-1, store.slot)[:store.n_objects]
    return store.values


def fingerprint(store) -> jax.Array:
    """Order-sensitive FNV-1a (32-bit) fingerprint of the store image.

    Used by the determinism harness: two executions are "the same
    outcome" iff their fingerprints are bitwise equal.  Layout-blind:
    a sharded store hashes its dense image (padding excluded), so
    sharded and dense runs of the same history fingerprint identically.
    """
    data = dense_image(store).astype(jnp.uint32).reshape(-1)

    def step(h, x):
        h = (h ^ x) * jnp.uint32(0x01000193)
        return h, None

    h0 = jnp.uint32(0x811C9DC5)
    h, _ = jax.lax.scan(step, h0, data)
    return h
