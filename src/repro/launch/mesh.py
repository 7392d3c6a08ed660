"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 BEFORE first jax use,
and smoke tests must keep seeing 1 device.

Every mesh has ``Auto`` axes: arrays carry their layout in
``NamedSharding``s and GSPMD propagates it.  ``jax.make_mesh`` defaults to
``Explicit`` axes, under which the vocab-sharded embedding gather has no
unambiguous output sharding and fails to trace.  Activate a mesh for the
model's ``shard_hint``s with ``jax.set_mesh(mesh)``.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod mesh ("data", "model") or 2x16x16 multi-pod
    ("pod", "data", "model").  The pod axis carries model-level data
    parallelism (independent serving replicas / second-level gradient
    all-reduce), so adding pods scales capacity elastically."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Generic mesh builder for tests/examples (e.g. ("stage", "model")
    pipeline meshes, or small CPU meshes)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def data_axes(mesh) -> tuple:
    """The batch-sharding axes of a mesh: ("pod", "data") when a pod axis
    exists, else ("data",)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)
