"""Frontier-compacted CC engine: bit-exactness vs the dense sv_run loop
across adversarial graph families, work accounting, the Afforest-style
sampling pre-pass, and edge dedup."""
import numpy as np
import pytest

from conftest import given, settings, st  # hypothesis or skip-stubs

from repro.core import (
    connected_components,
    dedup_edges,
    frontier_shiloach_vishkin,
    num_components,
    shiloach_vishkin,
)
from repro.core.serial import canonicalize_labels, serial_connected_components
from repro.ops.kiss import giant_dust_graph, list_graph, random_graph, tree_graph


def _star(n):
    return np.stack(
        [np.zeros(n - 1, np.int32), np.arange(1, n, dtype=np.int32)], axis=1
    )


def _adversarial_families():
    r = np.random.default_rng(7)
    return {
        "long-chain": (2000, list_graph(2000, 1, seed=1)),
        "star": (1500, _star(1500)),
        "giant+dust": (2000, giant_dust_graph(2000, 0.9, seed=2)),
        "empty": (17, np.zeros((0, 2), np.int32)),
        "all-self-loops": (9, np.stack([np.arange(9)] * 2, axis=1).astype(np.int32)),
        "tree": (1200, tree_graph(1200, 3, seed=3)),
        "random": (800, random_graph(800, 0.01, seed=4)),
        "dense-multigraph": (150, r.integers(0, 150, (3000, 2)).astype(np.int32)),
    }


@pytest.mark.parametrize(
    "family", sorted(_adversarial_families()), ids=lambda f: f
)
def test_bit_exact_vs_dense(family):
    n, edges = _adversarial_families()[family]
    ref, rounds_ref = shiloach_vishkin(edges[:, 0], edges[:, 1], n)
    lab, rounds = frontier_shiloach_vishkin(
        edges[:, 0], edges[:, 1], n, min_bucket=64
    )
    np.testing.assert_array_equal(np.asarray(lab), np.asarray(ref))
    assert int(rounds) == int(rounds_ref)


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 100), st.integers(0, 300), st.integers(0, 10_000))
def test_random_edge_lists_bit_exact(n, m, seed):
    r = np.random.default_rng(seed)
    edges = r.integers(0, n, size=(m, 2)).astype(np.int32)
    ref, rounds_ref = shiloach_vishkin(edges[:, 0], edges[:, 1], n)
    lab, rounds = frontier_shiloach_vishkin(
        edges[:, 0], edges[:, 1], n, min_bucket=16
    )
    np.testing.assert_array_equal(np.asarray(lab), np.asarray(ref))
    assert int(rounds) == int(rounds_ref)


def test_edges_touched_below_dense_on_chains():
    n = 4000
    edges = list_graph(n, 1, seed=5)
    _, rounds = shiloach_vishkin(edges[:, 0], edges[:, 1], n)
    _, _, stats = frontier_shiloach_vishkin(
        edges[:, 0], edges[:, 1], n, min_bucket=64, with_stats=True
    )
    dense = 2 * stats.m2 * int(rounds)
    assert stats.edges_touched < dense / 2
    sizes = [size for size, _ in stats.levels]
    assert sizes == sorted(sizes, reverse=True)  # buckets only shrink
    assert stats.rounds == int(rounds)


def test_afforest_prepass_partition_correct():
    for n, edges in [
        (2000, giant_dust_graph(2000, 0.9, seed=6)),
        (800, random_graph(800, 0.02, seed=7)),
        (1200, tree_graph(1200, 3, seed=8)),
    ]:
        ref = canonicalize_labels(serial_connected_components(edges, n))
        lab, _rounds, stats = frontier_shiloach_vishkin(
            edges[:, 0], edges[:, 1], n,
            sample_rounds=3, min_bucket=64, with_stats=True,
        )
        np.testing.assert_array_equal(
            canonicalize_labels(np.asarray(lab)), ref
        )
        assert stats.sample_rounds == 3
        assert 0.0 <= stats.largest_component_frac <= 1.0
        # the pre-pass resolves edges before full SV sees them
        assert stats.live_after_sample < stats.m2


def test_hook_kernel_path_bit_exact():
    n = 600
    edges = tree_graph(n, 3, seed=9)
    ref, rounds_ref = shiloach_vishkin(edges[:, 0], edges[:, 1], n)
    lab, rounds = frontier_shiloach_vishkin(
        edges[:, 0], edges[:, 1], n,
        min_bucket=64, hook_impl="pallas_interpret",
    )
    np.testing.assert_array_equal(np.asarray(lab), np.asarray(ref))
    assert int(rounds) == int(rounds_ref)


def test_dedup_edges():
    src = np.array([0, 1, 1, 2, 3, 3, 3], np.int32)
    dst = np.array([1, 0, 1, 3, 2, 2, 3], np.int32)  # dups + self-loops
    a, b = dedup_edges(src, dst)
    assert a.tolist() == [0, 2] and b.tolist() == [1, 3]
    # dedup changes neither labels nor rounds
    for dedup in (True, False):
        lab, rounds = shiloach_vishkin(src, dst, 5, dedup=dedup)
        assert num_components(lab) == 3  # {0,1}, {2,3}, {4}
        assert int(rounds) == 2


def test_dedup_edges_degenerate_inputs():
    # empty edge list
    a, b = dedup_edges(np.zeros(0, np.int32), np.zeros(0, np.int32))
    assert a.shape == (0,) and b.shape == (0,)
    assert a.dtype == np.int32 and b.dtype == np.int32
    lab, rounds = shiloach_vishkin(a, b, 4)
    assert num_components(lab) == 4 and int(rounds) == 1
    # all self-loops collapse to an empty walk
    loops = np.arange(7, dtype=np.int32)
    a, b = dedup_edges(loops, loops)
    assert a.shape == (0,)
    # n=1 single node, self-loop input
    a, b = dedup_edges(np.zeros(1, np.int32), np.zeros(1, np.int32))
    assert a.shape == (0,)
    lab, rounds = shiloach_vishkin(a, b, 1)
    assert num_components(lab) == 1 and int(rounds) == 1
    # orientation + duplicates collapse to one canonical edge
    a, b = dedup_edges(
        np.array([2, 1, 1, 2], np.int32), np.array([1, 2, 2, 1], np.int32)
    )
    assert a.tolist() == [1] and b.tolist() == [2]


def _dedup_edges_stacked(src, dst):
    """The row-wise ``np.unique(axis=0)`` dedup that ``dedup_edges``'s
    packed-key sort replaced, kept verbatim as its reference."""
    e = np.stack(
        [np.asarray(src).ravel(), np.asarray(dst).ravel()], axis=1
    ).astype(np.int64)
    lo, hi = e.min(axis=1), e.max(axis=1)
    keep = lo != hi
    u = np.unique(np.stack([lo[keep], hi[keep]], axis=1), axis=0)
    return u[:, 0].astype(np.int32), u[:, 1].astype(np.int32)


def _assert_dedup_matches_stacked(src, dst):
    a, b = dedup_edges(src, dst)
    ra, rb = _dedup_edges_stacked(src, dst)
    assert a.dtype == np.int32 and b.dtype == np.int32
    np.testing.assert_array_equal(a, ra)  # same edges, same order
    np.testing.assert_array_equal(b, rb)


_INT32_ENDS = [-2**31, -2**31 + 1, -2, -1, 0, 1, 2**31 - 2, 2**31 - 1]


@pytest.mark.parametrize("case", ["random", "int32_ends", "int64", "sequences"])
def test_dedup_edges_matches_stacked_unique(case):
    r = np.random.default_rng(11)
    if case == "int32_ends":  # every pair of ids at both ends of int32
        src, dst = (x.ravel() for x in np.meshgrid(_INT32_ENDS, _INT32_ENDS))
        src, dst = src.astype(np.int32), dst.astype(np.int32)
    else:  # duplicates, both orientations and self-loops
        src = r.integers(0, 50, 2000).astype(np.int32)
        dst = np.where(r.random(2000) < 0.1, src, r.integers(0, 50, 2000))
        dst = dst.astype(np.int32)
    if case == "int64":
        src, dst = src.astype(np.int64), dst.astype(np.int64)
    if case == "sequences":
        src, dst = src.tolist(), tuple(dst.tolist())
    _assert_dedup_matches_stacked(src, dst)


_EDGE_ID = st.one_of(
    st.sampled_from(_INT32_ENDS), st.integers(-2**31, 2**31 - 1),
    st.integers(0, 7),  # a small pool, so random pairs repeat
)


@settings(max_examples=200, deadline=None)
@given(
    st.lists(st.tuples(_EDGE_ID, _EDGE_ID), max_size=40),
    st.sampled_from([np.int32, np.int64, list, tuple]),
)
def test_dedup_edges_matches_stacked_unique_property(pairs, kind):
    # Each pair also comes reversed and as a self-loop on its first end.
    edges = pairs + [(v, u) for u, v in pairs] + [(u, u) for u, _ in pairs]
    src = [u for u, _ in edges]
    dst = [v for _, v in edges]
    if kind in (np.int32, np.int64):
        src, dst = np.array(src, kind), np.array(dst, kind)
    else:
        src, dst = kind(src), kind(dst)
    _assert_dedup_matches_stacked(src, dst)


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 40), st.integers(0, 120), st.integers(0, 10_000))
def test_dedup_never_changes_labels_or_rounds(n, m, seed):
    r = np.random.default_rng(seed)
    edges = r.integers(0, n, size=(m, 2)).astype(np.int32)
    lab_raw, rounds_raw = shiloach_vishkin(
        edges[:, 0], edges[:, 1], n, dedup=False
    )
    lab_dd, rounds_dd = shiloach_vishkin(
        edges[:, 0], edges[:, 1], n, dedup=True
    )
    np.testing.assert_array_equal(np.asarray(lab_dd), np.asarray(lab_raw))
    assert int(rounds_dd) == int(rounds_raw)
    # the frontier engine agrees under dedup too
    lab_f, rounds_f = frontier_shiloach_vishkin(
        edges[:, 0], edges[:, 1], n, min_bucket=16
    )
    np.testing.assert_array_equal(np.asarray(lab_f), np.asarray(lab_raw))
    assert int(rounds_f) == int(rounds_raw)


def test_all_self_loops_single_round():
    e = np.stack([np.arange(6)] * 2, axis=1).astype(np.int32)
    lab, rounds = frontier_shiloach_vishkin(e[:, 0], e[:, 1], 6)
    assert num_components(lab) == 6
    assert int(rounds) == 1  # dedup leaves an empty walk: one no-op round


def test_connected_components_engine_dispatch():
    n = 500
    edges = list_graph(n, 3, seed=10)
    ref, rounds_ref = shiloach_vishkin(edges[:, 0], edges[:, 1], n)
    for kwargs in (
        {},  # auto: single visible device -> frontier engine
        {"engine": "frontier"},
        {"engine": "dense"},
        {"engine": "frontier", "sample_rounds": 2},
    ):
        lab, rounds = connected_components(edges[:, 0], edges[:, 1], n, **kwargs)
        if kwargs.get("sample_rounds"):
            np.testing.assert_array_equal(
                canonicalize_labels(np.asarray(lab)),
                canonicalize_labels(np.asarray(ref)),
            )
        else:
            np.testing.assert_array_equal(np.asarray(lab), np.asarray(ref))
            assert int(rounds) == int(rounds_ref)
    with pytest.raises(ValueError):
        connected_components(edges[:, 0], edges[:, 1], n, engine="bogus")
    # an explicit mesh contradicts the single-device frontier engine
    from repro.distributed.graph import graph_mesh

    with pytest.raises(ValueError, match="single-device"):
        connected_components(
            edges[:, 0], edges[:, 1], n, engine="frontier", mesh=graph_mesh(1)
        )
    # engine="dense" + mesh routes to the sharded engine (the dense walk)
    lab, rounds = connected_components(
        edges[:, 0], edges[:, 1], n, engine="dense", mesh=graph_mesh(1)
    )
    np.testing.assert_array_equal(np.asarray(lab), np.asarray(ref))
    assert int(rounds) == int(rounds_ref)


def test_unknown_dispatch_strings_name_choices():
    """Unknown engine=/kernel_impl=/hook_impl= strings raise loudly,
    naming the valid set (they used to fall through silently)."""
    edges = list_graph(60, 2, seed=11)
    with pytest.raises(ValueError, match="'auto', 'frontier', 'dense'"):
        connected_components(edges[:, 0], edges[:, 1], 60, engine="bogus")
    with pytest.raises(ValueError, match="hook_impl.*'xla'"):
        shiloach_vishkin(edges[:, 0], edges[:, 1], 60, hook_impl="bogus")
    with pytest.raises(ValueError, match="hook_impl.*'pallas'"):
        frontier_shiloach_vishkin(
            edges[:, 0], edges[:, 1], 60, hook_impl="pallas_typo"
        )
    from repro.distributed.graph import sharded_shiloach_vishkin

    with pytest.raises(ValueError, match="exchange.*'dense', 'sparse'"):
        sharded_shiloach_vishkin(
            edges[:, 0], edges[:, 1], 60, exchange="sparse_typo"
        )


def test_auto_sampling_policy_on_dense_graphs():
    """ROADMAP decision: engine='auto' enables the Afforest pre-pass on
    edge-heavy graphs (m/n >= AUTO_SAMPLE_DENSITY); labels remain a
    correct partition, and sample_rounds=0 opts out bit-exactly."""
    from repro.core import AUTO_SAMPLE_DENSITY

    n = 300
    m = int(AUTO_SAMPLE_DENSITY * n) + 10
    r = np.random.default_rng(12)
    edges = r.integers(0, n, size=(m, 2)).astype(np.int32)
    ref, rounds_ref = shiloach_vishkin(edges[:, 0], edges[:, 1], n)
    # auto: pre-pass on -> partition-correct labels
    lab, _rounds = connected_components(edges[:, 0], edges[:, 1], n)
    np.testing.assert_array_equal(
        canonicalize_labels(np.asarray(lab)),
        canonicalize_labels(np.asarray(ref)),
    )
    # explicit sample_rounds=0 overrides the policy: bit-exact vs dense
    lab0, rounds0 = connected_components(
        edges[:, 0], edges[:, 1], n, sample_rounds=0
    )
    np.testing.assert_array_equal(np.asarray(lab0), np.asarray(ref))
    assert int(rounds0) == int(rounds_ref)
    # explicit engine= pins exact dense representatives too
    for engine in ("frontier", "dense"):
        labe, roundse = connected_components(
            edges[:, 0], edges[:, 1], n, engine=engine
        )
        np.testing.assert_array_equal(np.asarray(labe), np.asarray(ref))
        assert int(roundse) == int(rounds_ref)
    # sparse graphs stay below the threshold: bit-exact on auto
    sparse = list_graph(n, 3, seed=13)
    ref_s, rounds_s = shiloach_vishkin(sparse[:, 0], sparse[:, 1], n)
    lab_s, rounds_sa = connected_components(sparse[:, 0], sparse[:, 1], n)
    np.testing.assert_array_equal(np.asarray(lab_s), np.asarray(ref_s))
    assert int(rounds_sa) == int(rounds_s)
