"""Mesh construction: the one place meshes are built (production dry-run
meshes, the sharded store's device mesh, examples and tests).

Every axis is ``AxisType.Auto``: the store's sharded write-back and the
shard_map'd conflict kernels leave partitioning of the surrounding
program to XLA, which Explicit axes (``jax.make_mesh``'s default) refuse.

FUNCTIONS, not module-level constants: importing this module never
touches jax device state (smoke tests and benches must see 1 device)."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None) -> jax.sharding.Mesh:
    """An Auto-axis mesh of ``shape`` over ``devices`` (default: the
    first prod(shape) of ``jax.devices()``)."""
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n: int | None = None, axis: str = "data",
                   devices=None):
    """1-axis mesh over ``n`` devices (default: all of ``devices`` or of
    ``jax.devices()``) — the shape a :class:`ShardedStore` takes, with
    ``axis="shard"``."""
    devices = list(devices) if devices is not None else jax.devices()
    n = n or len(devices)
    return make_mesh((n,), (axis,), devices=devices[:n])
