"""Production meshes. A FUNCTION (not a module-level constant) so importing
this module never touches jax device state."""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """v5e: 16x16 = 256 chips/pod; multi_pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes, *, devices=None):
    """Arbitrary mesh for tests/elastic restarts; ``devices`` (default: all
    visible) lets a mesh span the first few devices of a larger host."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices)


def set_mesh(mesh):
    """Context manager activating ``mesh``."""
    return jax.set_mesh(mesh)


def shard_map(fn, *, mesh, in_specs, out_specs):
    """jax.shard_map with replication checking off."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
