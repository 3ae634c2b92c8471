"""The JAX mesh and manual-SPMD entry points the repo uses, defined once.

  * ``make_mesh`` / ``abstract_mesh`` — every mesh the repo builds comes
    from here, with ``AxisType.Auto`` on every axis.  ``jax.make_mesh``
    defaults to *Explicit* axes, under which ``with_sharding_constraint``
    acts as an assertion and gathers whose output sharding is ambiguous
    (the embedding lookup) raise ``ShardingTypeError``; the repo's plans
    are GSPMD hints, which is what Auto axes mean.
  * ``shard_map`` — all repo shard_maps are fully manual (ppermute /
    all_to_all schedules) and disable the replication check.
  * ``use_mesh`` — the ambient-mesh context manager for jit/constraints.
"""
from __future__ import annotations

import jax
from jax.sharding import AbstractMesh, AxisType


def _auto(axes):
    return (AxisType.Auto,) * len(axes)


def make_mesh(shape, axes, devices=None):
    """Device mesh of ``shape`` over ``axes``, every axis Auto."""
    return jax.make_mesh(tuple(shape), tuple(axes), _auto(axes),
                         devices=devices)


def abstract_mesh(shape, axes):
    """Device-free mesh for sharding analysis, every axis Auto."""
    return AbstractMesh(tuple(shape), tuple(axes), _auto(axes))


def shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


use_mesh = jax.set_mesh
