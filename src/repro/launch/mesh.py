"""Production mesh construction.

Defined as functions (not module constants) so importing this module never
touches JAX device state.  Every mesh has ``Auto`` axes: the model code
anchors activations with ``with_sharding_constraint`` and leaves the rest
to the partitioner (``jax.make_mesh`` defaults to ``Explicit`` axes).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.config import MULTI_POD_MESH, SINGLE_POD_MESH, MeshConfig


def _auto_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def mesh_config(*, multi_pod: bool = False) -> MeshConfig:
    return MULTI_POD_MESH if multi_pod else SINGLE_POD_MESH


def make_mesh_from_config(mesh_cfg: MeshConfig):
    return _auto_mesh(mesh_cfg.shape, mesh_cfg.axis_names)


def make_smoke_mesh(n_data: int = 2, n_model: int = 2):
    """Tiny mesh for CPU integration tests (requires >=4 host devices)."""
    return _auto_mesh((n_data, n_model), ("data", "model"))
