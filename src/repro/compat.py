"""The JAX calls whose spelling has drifted between releases, in one place.

The repository pins the installed JAX (``pyproject.toml``); when it moves,
only this module changes:

* ``make_mesh(shape, axes)`` — a mesh with explicit ``AxisType.Auto`` axes.
* ``shard_map(...)`` — ``jax.shard_map``; ``check_rep`` is forwarded as the
  current ``check_vma`` (replication/varying-type checking).
* ``axis_size(name)`` — static mesh-axis size inside ``shard_map``.
* ``enable_x64()`` — context in which 64-bit types are available.
"""
from __future__ import annotations

import jax

axis_size = jax.lax.axis_size


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with explicit Auto axis types."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def shard_map(f, *, mesh, in_specs, out_specs, check_rep: bool = True):
    """``jax.shard_map`` with ``check_rep`` spelled as ``check_vma``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_rep)


def enable_x64():
    """Context manager: trace with 64-bit types available."""
    return jax.enable_x64(True)
