"""The one home of mesh, shard_map and mesh-context calls.

Every mesh / shard_map / mesh-context call in this repo goes through this
module, so each spelling lives in one place (the installed JAX, 0.9.0):

* ``make_mesh``    — ``jax.make_mesh`` with ``AxisType.Auto`` on every
  axis unless the caller passes ``axis_types``;
* ``shard_map``    — ``jax.shard_map``, its ``check_vma`` replication
  check exposed as ``check=``;
* ``use_mesh``     — ``jax.set_mesh``, the ambient-mesh context.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "shard_map", "use_mesh"]


def make_mesh(axis_shapes, axis_names, *, devices=None, axis_types="auto"):
    """``jax.make_mesh``. ``axis_types="auto"`` requests Auto sharding on
    every axis; pass an explicit tuple to forward one, or None to take
    JAX's default."""
    axis_shapes = tuple(int(s) for s in axis_shapes)
    axis_names = tuple(axis_names)
    if len(axis_shapes) != len(axis_names):
        raise ValueError(f"{len(axis_shapes)} axis sizes for "
                         f"{len(axis_names)} names")
    if axis_types == "auto":
        axis_types = (AxisType.Auto,) * len(axis_names)
    kw = {} if axis_types is None else {"axis_types": axis_types}
    if devices is not None:
        kw["devices"] = devices
    return jax.make_mesh(axis_shapes, axis_names, **kw)


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with ``check`` mapped onto ``check_vma``; this
    repo always passes False — the collectives here (a2a, psum of int
    payloads, ppermute schedules) trip the replication checker's
    conservatism."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def use_mesh(mesh):
    """Context manager installing ``mesh`` as the ambient mesh."""
    return jax.set_mesh(mesh)
