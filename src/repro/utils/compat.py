"""The two mesh entry points every module imports: ``shard_map`` and
``make_mesh``, thin calls of ``jax.shard_map`` / ``jax.make_mesh`` that fix
the conventions this repo uses (Auto axis types unless ``explicit``; the
replication check passed only when a caller sets it).
"""
from __future__ import annotations

import jax

__all__ = ["shard_map", "make_mesh"]


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool | None = None):
    """`jax.shard_map`; ``check_vma=None`` keeps jax's default."""
    kw = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kw)


def make_mesh(axis_shapes, axis_names, *, explicit: bool = False, devices=None):
    """`jax.make_mesh` with every axis Auto (or Explicit when ``explicit``),
    over ``devices`` when given (default: all of ``jax.devices()``)."""
    kind = jax.sharding.AxisType.Explicit if explicit else jax.sharding.AxisType.Auto
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(kind,) * len(axis_names), devices=devices,
    )
