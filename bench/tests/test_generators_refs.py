"""Generators at tiny scale, and each copied reference against the
library's own serial oracles on small graphs."""
import json

import numpy as np
import pytest

import components
import kronecker
import molecules
import sssp
import tree_analytics
from conftest import BENCH

G500 = json.loads((BENCH / "configs" / "graph500-s18.json").read_text())
MOL = json.loads((BENCH / "configs" / "ogb-mol-serve.json").read_text())


def g500(scale=8, **extra):
    return kronecker.generate(dict(G500, scale=scale, **extra), 2**31 + 77)


def test_kronecker_counts_and_ranges():
    g = g500(8)
    assert g["num_nodes"] == 256
    assert len(g["src"]) == len(g["dst"]) == 16 * 256
    assert g["src"].dtype == np.int32
    assert 0 <= min(g["src"].min(), g["dst"].min())
    assert max(g["src"].max(), g["dst"].max()) < 256


def test_kronecker_seed_permutes_one_structure():
    """Another seed shuffles the same graph: the same edges, labelling
    included, in another order."""
    p = dict(G500, scale=9)
    a, b = kronecker.generate(p, 5), kronecker.generate(p, 2**40 + 6)
    again = kronecker.generate(p, 5)
    assert np.array_equal(a["src"], again["src"])
    assert not np.array_equal(a["src"], b["src"])

    def edges(g):
        return np.sort(g["src"].astype(np.int64) * 512 + g["dst"])

    assert np.array_equal(edges(a), edges(b))


def test_kronecker_skew():
    """Kronecker graphs are scale-free: the top 1% of vertices hold far
    more than 1% of the edge ends."""
    g = g500(12)
    deg = np.sort(np.bincount(np.concatenate([g["src"], g["dst"]]),
                              minlength=g["num_nodes"]))[::-1]
    assert deg[: len(deg) // 100].sum() > 0.1 * deg.sum()


def test_kronecker_weights_and_roots():
    g = g500(8, weights=True, roots=64)
    w = g["weights"]
    assert w.dtype == np.float32 and len(w) == len(g["src"])
    assert np.all((w >= 0) & (w < 1))
    assert np.array_equal(w * 2**24, np.floor(w * 2**24))
    deg = np.bincount(np.concatenate([g["src"], g["dst"]]), minlength=256)
    assert len(g["roots"]) == 64 and np.all(deg[g["roots"]] > 0)
    assert len(np.unique(g["roots"])) == 64


def test_molecule_means_and_shape():
    rng = np.random.default_rng(2**33 + 1)
    m = molecules.generate(MOL, 20000, rng)
    nn, ptr = m["num_nodes"], m["edge_ptr"]
    assert abs(nn.mean() - 25.5) < 0.02 * 25.5
    assert abs(np.diff(ptr).mean() - 27.5) < 0.02 * 27.5
    assert nn.min() >= 2 and nn.max() <= 222
    for i in range(0, 20000, 997):
        s, d = m["src"][ptr[i]:ptr[i + 1]], m["dst"][ptr[i]:ptr[i + 1]]
        assert s.max() < nn[i] and d.max() < nn[i] and min(s.min(), d.min()) >= 0
        labels, _ = components.hook_compress(s, d, int(nn[i]))
        assert np.all(labels == 0)  # one molecule, one component


def test_arrivals_rate():
    rng = np.random.default_rng(9)
    due = molecules.arrivals(500.0, 20.0, rng, 25)
    assert np.all(np.diff(due) >= 0) and 0 <= due[0] and due[-1] < 20.0
    assert len(due) == 10000
    gaps = np.diff(due)
    assert abs(gaps.std() / gaps.mean() - 1) < 0.05  # exponential gaps
    # Every seed: the same gaps, in another order.
    other = molecules.arrivals(500.0, 20.0, np.random.default_rng(2**40 + 1), 25)
    assert not np.array_equal(due, other)
    def all_gaps(d):
        return np.sort(np.diff(np.concatenate([[0.0], d, [20.0]])))

    assert np.allclose(all_gaps(due), all_gaps(other))


def test_molecule_seeds_share_work():
    """Every seed offers the same atom and bond counts, in another order."""
    a = molecules.generate(MOL, 3000, np.random.default_rng(2**40 + 3))
    b = molecules.generate(MOL, 3000, np.random.default_rng(7))
    assert not np.array_equal(a["num_nodes"], b["num_nodes"])
    assert np.array_equal(np.sort(a["num_nodes"]), np.sort(b["num_nodes"]))
    assert np.array_equal(np.sort(np.diff(a["edge_ptr"])), np.sort(np.diff(b["edge_ptr"])))


def _random_graph(n, m, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, n, size=m), rng.integers(0, n, size=m)


@pytest.mark.parametrize("n,m,seed", [(1, 0, 0), (30, 10, 1), (200, 150, 2),
                                      (200, 400, 3), (500, 2000, 4)])
def test_hook_compress_matches_serial(n, m, seed):
    from repro.core.serial import serial_connected_components

    s, d = _random_graph(n, m, seed)
    got, _ = components.hook_compress(s, d, n)
    want = serial_connected_components(np.stack([s, d], axis=1), n)
    assert np.array_equal(got, want)


def test_canonical_and_control():
    s, d = _random_graph(300, 280, 5)
    ref, rounds = components.hook_compress(s, d, 300)
    perm = np.random.default_rng(0).permutation(300)
    assert np.array_equal(components.canonical(perm[ref], 300), ref)
    assert components.canonical(np.full(300, 300), 300) is None
    assert rounds >= 2
    short = components.control({"src": s, "dst": d, "num_nodes": 300}, [{}], ref)
    assert components.compare({"num_nodes": 300}, [{}], short, ref)[0]["wrong_labels"] > 0


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_circuit_walk_matches_tree_reference(seed):
    from repro.trees.reference import serial_tree_reference

    rng = np.random.default_rng(seed)
    n = 60
    # a forest: each node but a few roots attaches to a smaller id
    par = np.array([rng.integers(0, i) if i and rng.random() > 0.1 else -1
                    for i in range(n)])
    u = np.flatnonzero(par >= 0)
    v = par[u]
    order = rng.permutation(len(u))
    u, v = u[order], v[order]
    labels, _ = components.hook_compress(u, v, n)
    got = tree_analytics.circuit_walk(u, v, n, labels)
    want = serial_tree_reference(u, v, n)
    for k in tree_analytics.FIELDS:
        assert np.array_equal(got[k], want[k]), k


@pytest.mark.parametrize("seed", [0, 1])
def test_dijkstra_matches_serial(seed):
    from repro.core.serial import serial_dijkstra

    s, d = _random_graph(80, 200, seed)
    w = (np.random.default_rng(seed).integers(0, 1 << 24, 200) / 2**24).astype(np.float32)
    got_d, got_p = sssp.dijkstra(s, d, w, 80, 3)
    want_d, want_p = serial_dijkstra(np.stack([s, d], axis=1), w, 80, 3)
    assert np.array_equal(got_d, want_d)
    assert np.array_equal(got_p, want_p)
