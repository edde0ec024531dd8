"""The one place the tree calls JAX's mesh and ambient-mesh API.

Everything in the tree that constructs a mesh or needs the ambient-mesh
machinery routes through this module; ``jax.sharding.AxisType`` must never
be referenced anywhere else (enforced by ``tests/test_compat_sharding.py``).
All meshes are Auto-typed (``AxisType.Auto`` on every axis), so the
logical sharding rules in :mod:`repro.sharding` decide placement.  Each
function is a thin call to the installed JAX; keeping them here keeps the
call sites unchanged if that API moves again.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh


def auto_axis_types(n_axes: int) -> Tuple:
    """``(AxisType.Auto,) * n_axes``."""
    return (AxisType.Auto,) * n_axes


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None) -> Mesh:
    """The one mesh factory: logical (shape, axes) -> Auto-typed Mesh.

    ``devices`` restricts construction to an explicit device list
    (defaults to all of ``jax.devices()``).
    """
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         devices=devices,
                         axis_types=auto_axis_types(len(axis_names)))


def mesh_from_devices(device_array, axis_names: Sequence[str]) -> Mesh:
    """Mesh from an explicit device ndarray (the elastic re-mesh path,
    where surviving rows of a failed mesh are re-assembled in place)."""
    names = tuple(axis_names)
    return Mesh(device_array, names, axis_types=auto_axis_types(len(names)))


def use_mesh(mesh: Mesh):
    """Context manager making ``mesh`` ambient (``jax.sharding.set_mesh``)
    for jit lowering and sharding constraints."""
    return jax.sharding.set_mesh(mesh)


def get_abstract_mesh() -> Optional[object]:
    """The ambient mesh, or None when there is none.  Query axis sizes via
    ``mesh_axis_sizes``."""
    try:
        mesh = jax.sharding.get_abstract_mesh()
    except Exception:
        return None
    if mesh is None or getattr(mesh, "empty", True):
        return None
    return mesh


def mesh_axis_sizes(mesh) -> dict:
    """{axis name: size} for abstract and physical meshes alike."""
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def cost_analysis_dict(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a dict (empty when XLA gives none)."""
    return compiled.cost_analysis() or {}
