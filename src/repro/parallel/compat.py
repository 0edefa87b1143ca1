"""Thin spellings of the JAX distribution API used across the repo.

Every module that shard_maps or inspects the ambient mesh imports from here,
so a future API rename is one edit.
"""

from __future__ import annotations

from typing import Tuple

import jax

__all__ = [
    "auto_mesh", "shard_map", "get_abstract_mesh", "mesh_axis_names_sizes",
    "axis_size",
]


def auto_mesh(shape, names):
    """``jax.make_mesh`` with ``Auto`` axes. JAX's default is ``Explicit``
    axes, under which ``with_sharding_constraint`` (``parallel/ctx.py``)
    asserts a sharding instead of imposing it."""
    return jax.make_mesh(
        shape, names, axis_types=(jax.sharding.AxisType.Auto,) * len(names)
    )


def axis_size(axis_name) -> jax.Array:
    """Size of a mapped mesh axis from inside shard_map."""
    return jax.lax.axis_size(axis_name)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map``."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check_vma
    )


def get_abstract_mesh():
    """The ambient mesh (entered via :func:`repro.launch.mesh.set_mesh`), or
    ``None`` when no mesh is active."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not getattr(mesh, "axis_names", None):
        return None
    return mesh


def mesh_axis_names_sizes(mesh) -> Tuple[Tuple[str, ...], Tuple[int, ...]]:
    """``(axis_names, axis_sizes)`` of a Mesh or AbstractMesh."""
    return tuple(mesh.axis_names), tuple(mesh.axis_sizes)
