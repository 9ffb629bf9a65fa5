"""Production and decentralized meshes.

``make_production_mesh`` is the launch-spec mesh (verbatim).  The
decentralized *logical* mesh reshapes the same device array to
("clients", "fsdp", "model"): one K-GT-Minimax client per contiguous block of
fsdp x model chips.  In the multi-pod mesh the clients axis spans the pod
boundary, so only the gossip exchange (once per K local steps — the paper's
entire point) crosses inter-pod links.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
from jax.experimental import mesh_utils
from jax.sharding import Mesh

from repro.configs.base import MeshConfig
from repro.dist import compat
from repro.dist.sharding import CLIENTS, FSDP, MODEL


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_decentralized_mesh(mcfg: MeshConfig) -> Mesh:
    """Reshape the production device array to (clients, fsdp, model)."""
    prod = make_production_mesh(multi_pod=mcfg.multi_pod)
    devices = prod.devices.reshape(mcfg.num_clients, mcfg.fsdp, mcfg.model)
    return compat.mesh_of(devices, (CLIENTS, FSDP, MODEL))


# Per-arch overrides of the decentralized layout: the 70B-class model needs a
# bigger per-client sub-mesh to fit fp32 tracking state in 16 GB HBM.
_ARCH_MESH = {
    "internvl2-76b": dict(num_clients=2, fsdp=8),
    "qwen1.5-32b": dict(num_clients=4, fsdp=4),
}


def decentralized_mesh_config(arch_id: str, *, multi_pod: bool = False) -> MeshConfig:
    kw = dict(_ARCH_MESH.get(arch_id, dict(num_clients=4, fsdp=4)))
    kw["model"] = 16
    if multi_pod:
        kw["num_clients"] *= 2  # clients axis spans the pod dimension
    return MeshConfig(multi_pod=multi_pod, **kw)


def local_mesh(n_devices: Optional[int] = None) -> Mesh:
    """Small mesh over whatever devices exist (tests / CPU examples).

    The clients axis walks the devices in the order of their interconnect
    (``mesh_utils.create_device_mesh``: on a v5e 2x2, chips 0, 1, 3, 2), so
    that clients adjacent on the axis, ring neighbors, are one link apart."""
    devs = jax.devices()[: n_devices or len(jax.devices())]
    devs = mesh_utils.create_device_mesh((len(devs), 1, 1), devices=devs)
    return compat.mesh_of(devs, (CLIENTS, FSDP, MODEL))


def fake_mesh(num_clients: int = 2, fsdp: int = 2, model: int = 2) -> Mesh:
    """CPU-backed fake decentralized mesh for compile-level tests.

    Requires ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (with
    N >= num_clients*fsdp*model) to be set before jax's first backend init —
    see ``repro.launch.smoke`` / ``scripts/smoke.sh``.
    """
    need = num_clients * fsdp * model
    if len(jax.devices()) < need:
        raise RuntimeError(
            f"fake_mesh needs {need} devices, have {len(jax.devices())}; set "
            "XLA_FLAGS=--xla_force_host_platform_device_count before jax init")
    return compat.make_mesh((num_clients, fsdp, model), (CLIENTS, FSDP, MODEL))
