"""Production mesh builders.

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import).

All construction routes through ``repro.compat.make_mesh`` (``Auto``
axes, the one mesh-API import site).
"""
from __future__ import annotations

import numpy as np

import jax

from repro.compat import Mesh, make_mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 (data, model) single pod, or 2x16x16 (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)}; set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "importing jax (launch/dryrun.py does this)."
        )
    return make_mesh(shape, axes, devices=devices[:n])


def make_test_mesh(
    shape: tuple[int, ...] = (1, 1), axes: tuple[str, ...] = ("data", "model")
) -> Mesh:
    """Small mesh over however many devices the test process has."""
    n = int(np.prod(shape))
    return make_mesh(shape, axes, devices=jax.devices()[:n])


def make_graph_mesh(num_devices: int | None = None) -> Mesh:
    """1-D edge-partitioning mesh for the sharded graph engine."""
    from repro.distributed.graph import graph_mesh

    return graph_mesh(num_devices)


def mesh_num_chips(mesh: Mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))
