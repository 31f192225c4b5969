"""Production mesh builders and the repo's one shard_map spelling.

A FUNCTION, not a module constant — importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before first jax init).

``make_mesh_compat`` and ``shard_map`` keep their names so callers stay
put; they are thin spellings of ``jax.make_mesh`` with Auto axis types and
``jax.shard_map`` with replication checking off.
"""
from __future__ import annotations

import jax

__all__ = [
    "make_mesh_compat",
    "shard_map",
    "make_production_mesh",
    "make_local_mesh",
    "mesh_axes",
    "dp_axes",
]


def make_mesh_compat(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` over this process's devices, every axis Auto."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def shard_map(body, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking disabled (all bodies in
    this repo do their own collectives)."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_compat(shape, axes)


def make_local_mesh(n_data: int | None = None, n_model: int = 1):
    """Whatever this host has (tests / examples / elastic resume)."""
    n = len(jax.devices())
    n_data = n_data or max(n // n_model, 1)
    return make_mesh_compat((n_data, n_model), ("data", "model"))


def mesh_axes(mesh) -> dict:
    return dict(mesh.shape)


def dp_axes(mesh) -> tuple[str, ...]:
    """Batch-parallel axes: pod (if present) + data."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)
