"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run must be
able to set ``XLA_FLAGS`` before the first jax call.

Axes:
  * ``pod``   — pure data parallelism across pods (DCN); gradients cross it
                once per step,
  * ``data``  — batch / edge / row sharding (ICI),
  * ``model`` — tensor/expert/vocab/embedding-row parallelism (ICI).

``make_mesh`` is re-exported from :mod:`repro.compat` for callers that build
their own meshes.
"""

from __future__ import annotations

import jax

from repro.compat import make_mesh

__all__ = [
    "make_mesh", "make_production_mesh",
    "make_engine_mesh", "mesh_size", "data_axes", "model_axis",
]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_engine_mesh(n_devices: int | None = None, axis: str = "data"):
    """1-D mesh for the SPMD materialisation engine.

    ``n_devices`` smaller than the process's device count builds the mesh
    over a prefix of the devices — the device-count-invariance tests run
    1/2/4-shard engines inside one 4-device process this way.
    """
    devs = jax.devices()
    n = n_devices or len(devs)
    if n < len(devs):
        return make_mesh((n,), (axis,), devices=devs[:n])
    return make_mesh((n,), (axis,))


def mesh_size(mesh) -> int:
    """Total device count of a mesh (the engine's shard count on 1-D meshes)."""
    import numpy as np

    return int(np.prod(mesh.devices.shape))


def data_axes(mesh) -> tuple[str, ...]:
    """All batch-parallel axes of a mesh (pod + data when present)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def model_axis(mesh) -> str | None:
    return "model" if "model" in mesh.axis_names else None
