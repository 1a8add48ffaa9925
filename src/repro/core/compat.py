"""Mesh and shard_map entry points with this repo's defaults.

Every call site goes through this module so the defaults are set in one
place: ``shard_map`` runs with ``check_vma=False`` (jmpi collectives manage
replication themselves), and meshes get explicit ``AxisType.Auto`` axes
(GSPMD-style sharding propagation, which every caller here relies on).
"""

from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with replication checks off by default."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with explicit Auto axis types (JAX's default is
    Explicit) over the first ``prod(axis_shapes)`` devices."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(tuple(axis_names)))
