"""compat-layer behaviour + deterministic (hypothesis-free) smoke coverage
of the core graph algorithms and their auto-dispatch wrappers. Runs on the
single-device test process; the 8-device paths live in test_multidev.py."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from conftest import random_succ
from repro import compat
from repro.core import connected_components, list_rank, shiloach_vishkin
from repro.core.serial import (
    canonicalize_labels,
    serial_connected_components,
    serial_list_rank,
)


def test_axis_type_sentinels_exist():
    assert compat.AxisType.Auto != compat.AxisType.Explicit
    assert compat.auto_axis_types(3) == (compat.AxisType.Auto,) * 3


def test_make_mesh_accepts_and_survives_axis_types():
    mesh = compat.make_mesh(
        (1, 1), ("data", "model"), axis_types=compat.auto_axis_types(2)
    )
    assert mesh.axis_names == ("data", "model")
    assert mesh.shape == {"data": 1, "model": 1}
    # jax.make_mesh alone would give Explicit axes; the shim defaults
    # to the Auto axes every engine here is written for.
    for m in (mesh, compat.make_mesh((1,), ("x",)),
              compat.make_mesh((1,), ("x",), devices=jax.devices()[:1])):
        assert set(m.axis_types) == {compat.AxisType.Auto}


def test_make_mesh_explicit_devices_keeps_order():
    devs = jax.devices()[:1]
    mesh = compat.make_mesh((1,), ("graph",), devices=devs)
    assert list(mesh.devices.flat) == devs


def test_shard_map_runs_on_one_device_mesh():
    mesh = compat.make_mesh((1,), ("x",), devices=jax.devices()[:1])
    out = compat.shard_map(
        lambda v: jax.lax.psum(v, "x"),
        mesh=mesh,
        in_specs=P("x"),
        out_specs=P("x"),
        check_vma=False,
    )(jnp.arange(4.0))
    np.testing.assert_allclose(np.asarray(out), np.arange(4.0))


def test_connected_components_dispatch_matches_serial():
    edges = np.array([[0, 1], [1, 2], [4, 5], [6, 6]], np.int32)
    n = 8
    ref = canonicalize_labels(serial_connected_components(edges, n))
    lab, rounds = connected_components(edges[:, 0], edges[:, 1], n)
    np.testing.assert_array_equal(canonicalize_labels(np.asarray(lab)), ref)
    assert int(rounds) >= 1
    lab2, _ = shiloach_vishkin(edges[:, 0], edges[:, 1], n)
    np.testing.assert_array_equal(np.asarray(lab), np.asarray(lab2))


def test_list_rank_dispatch_matches_serial():
    for n, p in [(40, 8), (257, 16)]:
        succ = random_succ(n, seed=n)
        ref = serial_list_rank(succ)
        got = np.asarray(list_rank(succ, p, seed=1))
        np.testing.assert_array_equal(got, ref)
