"""Mesh construction for the launch and sharding layers.

Every mesh this repo builds has all axes ``Auto`` (GSPMD propagates
shardings freely) and is entered with ``jax.set_mesh``; these helpers keep
those two choices in one place for ``repro.launch`` and
``repro.dist.sharding``.
"""
from __future__ import annotations

from typing import Mapping, Sequence

import jax
import numpy as np
from jax.sharding import AbstractMesh, AxisType, Mesh


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]) -> Mesh:
    """``jax.make_mesh`` with all axes ``Auto``.

    Used for the production mesh (``repro.launch.mesh``) and for the
    CPU-backed fake meshes in tests/smoke runs (``XLA_FLAGS=
    --xla_force_host_platform_device_count=N`` before first jax init).
    """
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), names,
                         axis_types=(AxisType.Auto,) * len(names))


def mesh_of(devices: np.ndarray, axis_names: Sequence[str]) -> Mesh:
    """Wrap an explicit device array in a Mesh with ``Auto`` axes.

    This is the decentralized-mesh constructor: the caller reshapes the
    production device array to ``(clients, fsdp, model)`` so one K-GT-Minimax
    client owns each contiguous ``fsdp x model`` block (see
    ``repro.launch.mesh.make_decentralized_mesh``).
    """
    names = tuple(axis_names)
    return Mesh(devices, names, axis_types=(AxisType.Auto,) * len(names))


def use_mesh(mesh: Mesh):
    """Context manager entering ``mesh`` (``jax.set_mesh``): inside it, jit
    tracing and sharding-constraint resolution treat ``mesh`` as the
    ambient mesh."""
    return jax.set_mesh(mesh)


def abstract_mesh(axis_sizes: Mapping[str, int]) -> AbstractMesh:
    """Device-free :class:`jax.sharding.AbstractMesh` for spec-level work.

    Lets tests and planners build ``NamedSharding``\\s for meshes larger than
    the local device count (e.g. asserting the clients-axis placement of
    :func:`repro.dist.sharding.params_shardings` on a 1-CPU container).
    """
    return AbstractMesh(tuple(axis_sizes.values()), tuple(axis_sizes))
