"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* first init.
"""

from __future__ import annotations

import contextlib

import jax

from repro.parallel.compat import auto_mesh
from repro.parallel.sharding import MeshAxes, multi_pod_axes, single_pod_axes


@contextlib.contextmanager
def set_mesh(mesh):
    """Activate ``mesh`` as the ambient mesh (``jax.set_mesh``). All mesh
    activation in this repo goes through here."""
    with jax.set_mesh(mesh):
        yield mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 (512 chips, 2 pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def production_axes(*, multi_pod: bool = False) -> MeshAxes:
    return multi_pod_axes(2, 16, 16) if multi_pod else single_pod_axes(16, 16)


def make_mesh_from_axes(ax: MeshAxes):
    names = tuple(n for n, _ in ax.sizes)
    shape = tuple(s for _, s in ax.sizes)
    return auto_mesh(shape, names)
