"""Ahead-of-time compiles for a described TPU v5e, no chip attached.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not present (``jax.experimental.topologies``). These tests
keep its verdicts on the graph path:

* the Pallas graph kernels are REFUSED at the sizes the engines would
  send them (1-D in-VMEM gathers; ``segment_sum`` at its default block),
  which is why every ``auto`` rule takes the XLA path
  (``repro.kernels``) -- if a later compiler accepts one, its test fails
  and the rule can be revisited;
* ``flash_attention`` compiles, so its TPU auto rule stands;
* the jitted XLA programs of the main path compile at the sizes
  ``chip_smoke.py`` runs and fit the chip's 16 GB.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, so a worker that is not
given this file must not touch it. All compiles stay in the test's own
process for the same reason.

The CPU-only test at the end checks that no ``auto`` rule picks a
refused kernel even when the backend reports a TPU.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A described chip's programs cannot be read back from the persistent
    # compile cache; keep it off around these compiles.
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _fits_chip(compiled):
    ma = compiled.memory_analysis()
    total = (
        ma.argument_size_in_bytes + ma.output_size_in_bytes
        + ma.temp_size_in_bytes - ma.alias_size_in_bytes
    )
    assert 0 < total < V5E_HBM_BYTES, total
    return total


# --------------------------------------------------------------------------
# Pallas kernels: the compiler's verdicts
# --------------------------------------------------------------------------


def _pointer_jump(s):
    from repro.kernels.pointer_jump.pointer_jump import pointer_jump_pallas

    p = 1 << 20  # the largest splitter list the old auto rule sent it
    return (
        lambda nxt, w: pointer_jump_pallas(nxt, w, iters=20, interpret=False),
        (_spec(s, (p,)), _spec(s, (p,))),
    )


def _edge_hook(mode):
    def build(s):
        from repro.kernels.edge_hook.edge_hook import edge_hook_pallas

        n, m = 1 << 19, 1 << 21  # labels at the old auto rule's VMEM cap
        return (
            lambda a, b, lab, prev, q, t: edge_hook_pallas(
                a, b, lab, prev, q, t, mode=mode, interpret=False
            ),
            (_spec(s, (m,)), _spec(s, (m,)), _spec(s, (n,)),
             _spec(s, (n,)), _spec(s, (n,)), _spec(s, ())),
        )

    return build


def _splitter_aggregate(s):
    from repro.kernels.splitter_aggregate.splitter_aggregate import (
        splitter_aggregate_pallas,
    )

    n, p = 1 << 24, 4096  # chip_smoke's list ranking: RS5 over 16M nodes
    return (
        lambda packed, sprank: splitter_aggregate_pallas(
            packed, sprank, interpret=False
        ),
        (_spec(s, (n, 2)), _spec(s, (p,))),
    )


def _segment_sum(s):
    from repro.kernels.segment_sum.segment_sum import (
        segment_sum_sorted_pallas,
    )

    m, ns = 1 << 20, 1 << 14
    nb = ns // 256
    return (
        lambda d, seg, st, cnt: segment_sum_sorted_pallas(
            d, seg, st, cnt, ns, block_e=512, block_s=256, max_steps=4,
            interpret=False,
        ),
        (_spec(s, (m, 128), jnp.float32), _spec(s, (m,)),
         _spec(s, (nb,)), _spec(s, (nb,))),
    )


@pytest.mark.parametrize(
    "build, error, match",
    [
        (_pointer_jump, NotImplementedError, "Only 2D gather"),
        (_edge_hook("sv2"), NotImplementedError, "Only 2D gather"),
        (_edge_hook("sv3"), NotImplementedError, "Only 2D gather"),
        (_splitter_aggregate, NotImplementedError, "Only 2D gather"),
        (_segment_sum, Exception, "does not match Mosaic layout"),
    ],
    ids=["pointer_jump", "edge_hook_sv2", "edge_hook_sv3",
         "splitter_aggregate", "segment_sum"],
)
def test_chip_compiler_refuses_graph_kernel(one_chip, build, error, match):
    fn, args = build(one_chip)
    with pytest.raises(error, match=match):
        _compile(fn, *args)


def test_flash_attention_kernel_compiles(one_chip):
    from repro.kernels.flash_attention.flash_attention import (
        flash_attention_pallas,
    )

    bh, seq, d = 16, 4096, 128
    q = _spec(one_chip, (bh, seq, d), jnp.bfloat16)
    compiled = _compile(
        lambda q, k, v: flash_attention_pallas(
            q, k, v, num_q_heads=bh, num_kv_heads=bh, causal=True,
            window=None, block_q=128, block_k=128, interpret=False,
        ),
        q, q, q,
    )
    assert "tpu_custom_call" in compiled.as_text()
    _fits_chip(compiled)


# --------------------------------------------------------------------------
# The main path's XLA programs at chip_smoke.py's sizes
# --------------------------------------------------------------------------


def test_sv_round_level_compiles_and_fits(one_chip):
    """The frontier CC engine's jitted level (``sv_round_fns`` in a
    while-loop) at the first bucket of a 4M-node, 32M-edge graph."""
    from repro.core.components import sv_round_bound
    from repro.core.frontier import _run_level

    n, m2 = 1 << 22, 1 << 26
    s = one_chip
    compiled = _compile(
        lambda a, b, D, Q, t, aux: _run_level(
            a, b, D, Q, t, aux, n=n, bound=sv_round_bound(n),
            shrink_at=m2 // 2, hook_impl="xla",
        ),
        _spec(s, (m2,)), _spec(s, (m2,)), _spec(s, (n,)), _spec(s, (n,)),
        _spec(s, ()), _spec(s, ()),
    )
    _fits_chip(compiled)


def test_splitter_walk_compiles_and_fits(one_chip):
    from repro.core.list_ranking import _random_splitter_core

    n, p = 1 << 24, 4096
    compiled = _compile(
        lambda succ, spl: _random_splitter_core(succ, spl, kernel_impl="xla"),
        _spec(one_chip, (n,)), _spec(one_chip, (p,)),
    )
    _fits_chip(compiled)


def test_dense_pagerank_compiles_and_fits(one_chip):
    from repro.core.pagerank import _pr_fixed

    n, m2 = 1 << 20, 1 << 23
    s = one_chip
    f32 = jnp.float32
    compiled = _compile(
        lambda a, b, w2, deg, t, r0, dmp, omd: _pr_fixed(
            a, b, w2, deg, t, r0, dmp, omd, num_iters=20
        ),
        _spec(s, (m2,)), _spec(s, (m2,)), _spec(s, (m2,), f32),
        _spec(s, (n,), f32), _spec(s, (n,), f32), _spec(s, (n,), f32),
        _spec(s, (), f32), _spec(s, (), f32),
    )
    _fits_chip(compiled)


def test_dense_bellman_ford_compiles_and_fits(one_chip):
    from repro.core.sssp import _bf_dense, _min_parents, sssp_round_bound

    n, m2 = 1 << 20, 1 << 23
    s = one_chip
    f32 = jnp.float32
    edges = (_spec(s, (m2,)), _spec(s, (m2,)), _spec(s, (m2,), f32))
    dist = _spec(s, (1, n), f32)
    relax = _compile(
        lambda a, b, w, d: _bf_dense(a, b, w, d, bound=sssp_round_bound(n)),
        *edges, dist,
    )
    parents = _compile(_min_parents, *edges, dist, _spec(s, (1,)))
    _fits_chip(relax)
    _fits_chip(parents)


# --------------------------------------------------------------------------
# CPU: no auto rule picks a refused kernel, even on a TPU backend
# --------------------------------------------------------------------------


def test_auto_rules_never_pick_refused_kernels(monkeypatch):
    """With ``on_tpu`` reporting a TPU, every ``auto`` rule of the graph
    path must still take XLA: the refused kernels are replaced by stubs
    that fail if called. Odd sizes keep earlier tests' jit caches from
    hiding a retrace."""
    import repro.kernels as kernels
    from repro.core import connected_components, list_rank
    from repro.core.list_ranking import random_splitter_rank
    from repro.core.serial import serial_connected_components
    from repro.data.graphs import random_succ
    from repro.distributed.graph import graph_mesh
    from repro.kernels.edge_hook import ops as eh_ops
    from repro.kernels.pointer_jump import ops as pj_ops
    from repro.kernels.segment_sum import ops as ss_ops
    from repro.kernels.splitter_aggregate import ops as sa_ops

    monkeypatch.setattr(kernels, "on_tpu", lambda: True)

    def refused(*_a, **_k):
        raise AssertionError("auto picked a kernel the chip refuses")

    for mod, name in ((pj_ops, "pointer_jump_pallas"),
                      (eh_ops, "edge_hook_pallas"),
                      (sa_ops, "splitter_aggregate_pallas"),
                      (ss_ops, "segment_sum_sorted_pallas")):
        monkeypatch.setattr(mod, name, refused)

    nxt = jnp.asarray(np.r_[np.arange(1, 37), 36].astype(np.int32))
    w = jnp.ones(37, jnp.int32).at[36].set(0)
    got = pj_ops.pointer_jump(nxt, w, impl="auto")
    want = pj_ops.pointer_jump(nxt, w, impl="xla")
    for g, x in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(x))

    a = jnp.asarray(np.array([0, 3, 5, 7], np.int32))
    b = jnp.asarray(np.array([1, 2, 6, 8], np.int32))
    lab = jnp.arange(39, dtype=jnp.int32)
    q = jnp.zeros(39, jnp.int32)
    for mode in ("sv2", "sv3"):
        got = eh_ops.edge_hook(a, b, lab, q, jnp.int32(1), mode=mode,
                               impl="auto")
        want = eh_ops.edge_hook(a, b, lab, q, jnp.int32(1), mode=mode,
                                impl="xla")
        for g, x in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(x))

    packed = jnp.asarray(np.stack([np.arange(41), np.arange(41) % 5],
                                  axis=1).astype(np.int32))
    sprank = jnp.arange(5, dtype=jnp.int32) * 10
    np.testing.assert_array_equal(
        np.asarray(sa_ops.splitter_aggregate(packed, sprank, impl="auto")),
        np.asarray(sa_ops.splitter_aggregate(packed, sprank, impl="xla")),
    )

    data = jnp.ones((43, 8), jnp.float32)
    seg = jnp.asarray(np.sort(np.arange(43) % 7).astype(np.int32))
    np.testing.assert_array_equal(
        np.asarray(ss_ops.segment_sum_sorted(data, seg, 7, impl="auto")),
        np.asarray(ss_ops.segment_sum_sorted(data, seg, 7, impl="xla")),
    )

    # The engine-level auto rules (single-device and sharded list
    # ranking, the hook phase of connected components).
    succ = random_succ(301, seed=3)
    ref = np.asarray(random_splitter_rank(succ, 11, kernel_impl="xla"))
    np.testing.assert_array_equal(
        np.asarray(random_splitter_rank(succ, 11, kernel_impl="auto")), ref
    )
    np.testing.assert_array_equal(
        np.asarray(list_rank(succ, 11, mesh=graph_mesh(1))), ref
    )
    edges = np.array([[0, 1], [2, 3], [3, 4], [9, 10]], np.int32)
    lab, _ = connected_components(edges[:, 0], edges[:, 1], 47,
                                  hook_impl="auto")
    np.testing.assert_array_equal(
        np.asarray(lab), serial_connected_components(edges, 47)
    )


def test_explicit_pallas_on_tpu_never_interprets(monkeypatch):
    """On a TPU backend ``impl="pallas"`` compiles the kernel for real
    (here the CPU backend refuses a non-interpreted Pallas call); it must
    never fall back to interpret mode."""
    import repro.kernels as kernels
    from repro.kernels.pointer_jump import ops as pj_ops

    monkeypatch.setattr(kernels, "on_tpu", lambda: True)
    nxt = jnp.asarray(np.r_[np.arange(1, 53), 52].astype(np.int32))
    with pytest.raises(Exception, match="(?i)interpret|TPU|Mosaic"):
        pj_ops.pointer_jump(nxt, jnp.ones(53, jnp.int32), impl="pallas")
