"""JAX API shims: one import site for the mesh and shard_map APIs.

The repo targets the one installed JAX release (pinned in
``pyproject.toml``). Every mesh and every shard_map in the repo routes
through this module, so an API that moves in a later release is absorbed
HERE, never inline at a call site:

* ``jax.make_mesh`` defaults its axes to ``Explicit`` sharding; the
  engines here are written for ``Auto`` axes, so ``make_mesh`` asks for
  them unless the caller says otherwise.
* ``jax.shard_map`` with its ``check_vma`` replication check.

``Mesh`` is re-exported from here for the same reason: call sites write
``from repro.compat import Mesh`` so this stays the one direct
``jax.sharding`` import site (enforced by the compat-shim lint pass,
docs/lint.md).
"""
from __future__ import annotations

from typing import Any, Callable, Sequence

import numpy as np

import jax
from jax.sharding import AxisType, Mesh  # noqa: F401  (re-exported)


def auto_axis_types(ndim: int):
    """(AxisType.Auto,) * ndim -- the only mode this repo uses."""
    return (AxisType.Auto,) * ndim


def make_mesh(
    axis_shapes: Sequence[int],
    axis_names: Sequence[str],
    *,
    axis_types: tuple | None = None,
    devices: Sequence[Any] | None = None,
) -> Mesh:
    """``jax.make_mesh`` with ``Auto`` axes unless ``axis_types`` says
    otherwise.

    ``devices`` pins the mesh to an explicit device list IN THAT ORDER
    (jax.make_mesh may permute devices for ICI topology; tests and
    sub-meshes need determinism).
    """
    shape = tuple(int(s) for s in axis_shapes)
    names = tuple(axis_names)
    if axis_types is None:
        axis_types = auto_axis_types(len(shape))
    if devices is not None:
        dev = np.asarray(list(devices)[: int(np.prod(shape))]).reshape(shape)
        return Mesh(dev, names, axis_types=axis_types)
    return jax.make_mesh(shape, names, axis_types=axis_types)


def shard_map(
    f: Callable,
    *,
    mesh: Mesh,
    in_specs,
    out_specs,
    check_vma: bool = True,
) -> Callable:
    """``jax.shard_map`` (``check_vma=False`` turns off the replication
    check for collectives whose replication the tracer cannot prove)."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


def is_tracer(x: Any) -> bool:
    """True when ``x`` is a JAX tracer (i.e. we are inside a jit trace)."""
    return isinstance(x, jax.core.Tracer)
