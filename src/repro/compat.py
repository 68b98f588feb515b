"""jax API shims shared by the distributed runtime (single home).

- ``shard_map``: ``jax.shard_map`` with replication checking off — the
  engine's lane outputs are deliberately device-varying along the lane
  axes.
- ``axis_size``: the named-axis extent inside a mapped context.
- ``auto_axes``: a mesh with every axis in ``Auto`` sharding mode.
  ``jax.make_mesh`` makes ``Explicit`` axes, whose jit outputs carry their
  sharding in their type, and host-side indexing of such a result (the
  scheduler pulls one lane's prefix with ``rows[j, :n]``) then demands an
  ``out_sharding``.  The runtime places its arrays itself, so it runs on
  ``Auto`` axes.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
from jax.sharding import AxisType, Mesh


def shard_map(f: Callable, mesh: Any, in_specs: Any,
              out_specs: Any) -> Callable:
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def axis_size(axis: str):
    """Extent of a named mapped axis, inside shard_map/vmap."""
    return jax.lax.axis_size(axis)


def auto_axes(mesh: Mesh | None) -> Mesh | None:
    """``mesh`` with every axis in ``Auto`` mode (``None`` passes through)."""
    if mesh is None or all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))
