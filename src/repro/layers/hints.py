"""Sharding hints usable from inside model code.

``shard_hint(x, *spec)`` applies a with_sharding_constraint iff a mesh is
active (``jax.set_mesh`` — the launchers set it); with no mesh it is the
identity, so model code stays mesh-agnostic.  Axis entries whose size does
not divide their dimension are dropped per dimension.  Any other failure
propagates: a hint that cannot be applied is an error, not a silent
replication.
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P


def mesh_axis_size(name: str) -> int:
    return dict(jax.sharding.get_abstract_mesh().shape).get(name, 1)


def data_axis_names() -> tuple:
    shape = jax.sharding.get_abstract_mesh().shape
    return tuple(a for a in ("pod", "data") if a in shape)


def _sanitize(x, entries) -> P:
    """Drop PER-DIM any axis entry whose size doesn't divide the dim
    (e.g. batch=1 at long_500k must not veto the sequence sharding)."""
    out = []
    for dim, entry in zip(x.shape, entries):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        total = 1
        for a in axes:
            total *= mesh_axis_size(a)
        out.append(entry if (total <= 1 or dim % total == 0) else None)
    return P(*out)


def shard_hint(x, *spec_entries):
    """with_sharding_constraint under the active mesh; identity when no
    mesh is active, with per-dimension divisibility fallback."""
    if jax.sharding.get_abstract_mesh().empty:
        return x
    spec = _sanitize(x, spec_entries)
    if all(e is None for e in spec):
        return x
    return jax.lax.with_sharding_constraint(x, spec)
