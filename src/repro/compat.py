"""The single import surface for jax's sharding and mesh API.

Sharding, mesh and cost-analysis code imports these names from here, never
from ``jax.sharding`` / ``jax.experimental`` directly (the compat-drift
analysis rule pins this), so a future move of any of them is one edit in
this file. The wrappers fix the conventions the repo relies on: meshes
with explicit ``Auto`` axis types, an ambient mesh that reads as None when
absent, and cost analysis as a plain dict.
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

P = PartitionSpec

__all__ = ["Mesh", "NamedSharding", "PartitionSpec", "P", "make_mesh",
           "set_mesh", "get_abstract_mesh", "cost_analysis", "shard_map"]


def make_mesh(shape, axes, devices=None):
    """jax.make_mesh with every axis ``Auto`` (sharding propagated by XLA),
    over ``devices`` when given, else over all devices."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def set_mesh(mesh):
    """Context manager installing ``mesh`` as the ambient mesh."""
    return jax.set_mesh(mesh)


def get_abstract_mesh():
    """The ambient mesh, or None when no mesh context is active."""
    m = jax.sharding.get_abstract_mesh()
    return m if m is not None and m.axis_names else None


def cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a dict (empty when unavailable)."""
    return compiled.cost_analysis() or {}


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=check_vma)
