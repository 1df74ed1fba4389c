"""Mesh bookkeeping helpers.

These are the mesh arithmetic the sharded offload hooks
(`repro.core.hooks`) need: a linearized per-device shard index computed
*inside* a shard_map body, and the local (per-shard) shape implied by a
PartitionSpec.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp


def mesh_size(mesh) -> int:
    """Device count of a mesh; 1 for None (no mesh = one device)."""
    if mesh is None:
        return 1
    n = 1
    for s in mesh.shape.values():
        n *= int(s)
    return n


def axes_size(mesh, axes: Sequence[str]) -> int:
    """Product of the listed mesh axis sizes (1 for an empty list)."""
    n = 1
    for a in axes:
        n *= int(mesh.shape[a])
    return n


def linear_axis_index(mesh, axes: Sequence[str]):
    """Traced linearized index of the calling shard over `axes` (row
    major in the listed order). Only valid inside a shard_map body over
    a mesh where every listed axis is manual. Returns int32 0 for an
    empty axis list — callers use that as 'there is one shard'."""
    idx = jnp.zeros((), jnp.int32)
    for name in axes:
        idx = idx * mesh.shape[name] + jax.lax.axis_index(name)
    return idx


def spec_axes(spec) -> Tuple[str, ...]:
    """Mesh axis names a PartitionSpec mentions, in spec order."""
    out = []
    for entry in spec:
        if entry is None:
            continue
        for a in (entry if isinstance(entry, tuple) else (entry,)):
            out.append(a)
    return tuple(out)


def local_shape(global_shape: Tuple[int, ...], spec, mesh) \
        -> Tuple[int, ...]:
    """Per-shard block shape of a value sharded as `spec` on `mesh`."""
    dims = list(global_shape)
    for d, entry in enumerate(spec):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        dims[d] //= axes_size(mesh, axes)
    return tuple(dims)


def canonical_axis_entry(axes: Sequence[str]) -> Optional[Any]:
    """A PartitionSpec dim entry for `axes`: None when empty, the bare
    name for one axis (newer jax canonicalizes 1-tuples — doing it
    ourselves keeps specs comparable across versions), else the tuple."""
    axes = tuple(axes)
    if not axes:
        return None
    return axes[0] if len(axes) == 1 else axes
