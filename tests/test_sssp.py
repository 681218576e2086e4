"""SSSP on the frontier machinery (`repro.core.sssp`): distances AND
parent trees must be bit-exact vs the serial Dijkstra / Bellman-Ford
oracles across the adversarial families, batched multi-source must be
bit-exact vs solo runs, unit-weight reachability must agree with CC,
and the serve path must treat kind="sssp" waves like any other
(batched == solo, validated loudly)."""
import numpy as np
import pytest

from conftest import given, settings, st  # hypothesis or skip-stubs
from test_frontier import _adversarial_families
from test_serve_graph import _assert_matches_solo, _requests

from repro.core import (
    SSSP_ENGINES,
    bellman_ford,
    connected_components,
    frontier_bellman_ford,
    shortest_paths,
    sssp_round_bound,
)
from repro.core.components import ConvergenceError
from repro.core.serial import serial_bellman_ford, serial_dijkstra
from repro.data.graphs import graph_request_stream
from repro.serve import GraphRequest, GraphServeEngine


def _eighth_weights(edges, salt=0):
    """Deterministic weights in {0, 0.25, ..., 1.75}: zero-weight edges
    included on purpose (adversarial tie-breaks)."""
    r = np.random.default_rng(1000 + salt + len(edges))
    return (r.integers(0, 8, size=len(edges)) / 4.0).astype(np.float32)


def _assert_vs_oracles(edges, weights, n, source=0, **engine_kwargs):
    """Both engines == both serial oracles, distances AND parents."""
    od, op = serial_dijkstra(edges, weights, n, source)
    od2, op2 = serial_bellman_ford(edges, weights, n, source)
    np.testing.assert_array_equal(od, od2)
    np.testing.assert_array_equal(op, op2)
    src, dst = edges[:, 0], edges[:, 1]
    for engine in ("frontier", "dense"):
        kw = dict(engine_kwargs)
        if engine == "dense":
            kw.pop("min_bucket", None)
        d, p, rounds = shortest_paths(
            src, dst, weights, n, sources=source, engine=engine, **kw
        )
        np.testing.assert_array_equal(
            np.asarray(d), od, err_msg=f"dist {engine}"
        )
        np.testing.assert_array_equal(
            np.asarray(p), op, err_msg=f"parent {engine}"
        )
        assert int(rounds) <= sssp_round_bound(n)
    return od, op


@pytest.mark.parametrize(
    "family", sorted(_adversarial_families()), ids=lambda f: f
)
def test_bit_exact_vs_serial_oracles(family):
    """test_frontier's adversarial families, weighted with zero-weight
    edges included: frontier == dense == serial Dijkstra == serial BF,
    bit-for-bit on distances and parent trees."""
    n, edges = _adversarial_families()[family]
    _assert_vs_oracles(
        edges, _eighth_weights(edges), n, min_bucket=64
    )


def test_unit_weights_and_degenerate_graphs():
    """weights=None (BFS), the empty graph (all unreachable -> +inf /
    -1), a single-node graph, and all-self-loops (self-relaxes never
    parent)."""
    n, edges = _adversarial_families()["random"]
    _assert_vs_oracles(edges, None, n, min_bucket=64)
    # empty: everything but the source is unreachable
    d, p = _assert_vs_oracles(np.zeros((0, 2), np.int32), None, 17)
    assert d[0] == 0.0 and np.isinf(d[1:]).all()
    assert p[0] == 0 and (p[1:] == -1).all()
    # single node, no edges
    d, p = _assert_vs_oracles(np.zeros((0, 2), np.int32), None, 1)
    assert d.tolist() == [0.0] and p.tolist() == [0]
    # single node, self-loop edge
    loop = np.zeros((1, 2), np.int32)
    d, p = _assert_vs_oracles(loop, np.array([0.5], np.float32), 1)
    assert d.tolist() == [0.0] and p.tolist() == [0]
    # all-self-loops: like the empty graph
    n, edges = _adversarial_families()["all-self-loops"]
    d, p = _assert_vs_oracles(edges, _eighth_weights(edges), n)
    assert np.isinf(d[1:]).all() and (p[1:] == -1).all()


def test_zero_weight_component_min_parent_rule():
    """An all-zero-weight clique: every node is at distance 0 and every
    non-source node's parent is the MINIMUM optimal neighbor (the
    deterministic min-CRCW tie-break)."""
    n = 5
    a, b = np.triu_indices(n, k=1)
    edges = np.stack([a, b], axis=1).astype(np.int32)
    w = np.zeros(len(edges), np.float32)
    d, p = _assert_vs_oracles(edges, w, n, min_bucket=16)
    assert (d == 0.0).all()
    # every node except source 0 ties on ALL in-edges; min u wins
    assert p.tolist() == [0, 0, 0, 0, 0]
    # and from source 2 the same rule gives min-id parents again
    d2, p2, _ = shortest_paths(
        edges[:, 0], edges[:, 1], w, n, sources=2, engine="frontier"
    )
    od2, op2 = serial_dijkstra(edges, w, n, 2)
    np.testing.assert_array_equal(np.asarray(d2), od2)
    np.testing.assert_array_equal(np.asarray(p2), op2)
    assert np.asarray(p2).tolist() == [1, 0, 2, 0, 0]


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 24), st.integers(0, 60), st.integers(0, 10_000))
def test_batched_multi_source_equals_solo_property(n, m, seed):
    """Hypothesis: batched multi-source rows == per-source solo runs
    (duplicate sources allowed), on both engines."""
    r = np.random.default_rng(seed)
    edges = r.integers(0, n, size=(m, 2)).astype(np.int32)
    weights = None if seed % 3 == 0 else _eighth_weights(edges, salt=seed)
    S = int(r.integers(1, 4))
    srcs = r.integers(0, n, size=S).astype(np.int32)  # dups allowed
    for engine in ("frontier", "dense"):
        bd, bp, _ = shortest_paths(
            edges[:, 0], edges[:, 1], weights, n, sources=srcs,
            engine=engine,
        )
        bd, bp = np.asarray(bd), np.asarray(bp)
        assert bd.shape == (S, n) and bp.shape == (S, n)
        for i, s in enumerate(srcs):
            sd, sp, _ = shortest_paths(
                edges[:, 0], edges[:, 1], weights, n, sources=int(s),
                engine=engine,
            )
            np.testing.assert_array_equal(bd[i], np.asarray(sd))
            np.testing.assert_array_equal(bp[i], np.asarray(sp))


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 30), st.integers(0, 80), st.integers(0, 10_000))
def test_unit_weight_reachability_equals_cc_property(n, m, seed):
    """Hypothesis: unit-weight SSSP reachability == the CC
    same-component predicate -- dist[v] is finite iff v shares the
    source's component."""
    r = np.random.default_rng(seed)
    edges = r.integers(0, n, size=(m, 2)).astype(np.int32)
    source = int(r.integers(0, n))
    d, p, _ = shortest_paths(
        edges[:, 0], edges[:, 1], None, n, sources=source, engine="frontier"
    )
    lab, _ = connected_components(edges[:, 0], edges[:, 1], n)
    lab = np.asarray(lab)
    np.testing.assert_array_equal(
        np.isfinite(np.asarray(d)), lab == lab[source]
    )
    # parent sentinels agree with reachability too
    np.testing.assert_array_equal(np.asarray(p) == -1, lab != lab[source])


def test_engine_dispatch_and_validation():
    """Unknown engines name the choice set; min_bucket only fits the
    frontier engine; the frontier level loop refuses to trace; auto
    under jit falls back to the dense walk."""
    import jax

    n, edges = 40, np.array([[0, 1], [1, 2]], np.int32)
    with pytest.raises(ValueError, match="'auto', 'frontier', 'dense'"):
        shortest_paths(edges[:, 0], edges[:, 1], None, n, engine="fastest")
    with pytest.raises(TypeError, match="num_nodes"):
        shortest_paths(edges[:, 0], edges[:, 1])
    with pytest.raises(ValueError, match="min_bucket"):
        shortest_paths(
            edges[:, 0], edges[:, 1], None, n, engine="dense", min_bucket=8
        )
    with pytest.raises(ValueError, match="negative"):
        shortest_paths(
            edges[:, 0], edges[:, 1], np.array([1.0, -0.5]), n
        )
    with pytest.raises(ValueError, match="NaN"):
        shortest_paths(
            edges[:, 0], edges[:, 1], np.array([1.0, np.nan]), n
        )
    with pytest.raises(ValueError, match="sources"):
        shortest_paths(edges[:, 0], edges[:, 1], None, n, sources=n)
    assert SSSP_ENGINES == ("auto", "frontier", "dense")

    ref, _, _ = bellman_ford(edges[:, 0], edges[:, 1], None, n)

    @jax.jit
    def traced(s, d):
        dist, parent, _ = shortest_paths(s, d, None, n)  # auto -> dense
        return dist, parent

    td, tp = traced(edges[:, 0], edges[:, 1])
    np.testing.assert_array_equal(np.asarray(td), np.asarray(ref))

    @jax.jit
    def traced_frontier(s, d):
        return shortest_paths(s, d, None, n, engine="frontier")[0]

    with pytest.raises(ValueError, match="host-driven"):
        traced_frontier(edges[:, 0], edges[:, 1])


def test_convergence_sentinel_fires_on_round_bound():
    """max_rounds below the fixpoint raises ConvergenceError on both
    engines (host calls); the default bound always converges."""
    from repro.ops.kiss import list_graph

    n = 64
    edges = list_graph(n, 1, seed=21)
    for engine in ("frontier", "dense"):
        with pytest.raises(ConvergenceError, match="max_rounds"):
            shortest_paths(
                edges[:, 0], edges[:, 1], None, n, engine=engine,
                max_rounds=0,
            )
        with pytest.raises(ConvergenceError):
            shortest_paths(
                edges[:, 0], edges[:, 1], None, n, engine=engine,
                max_rounds=2,
            )
        d, _, rounds = shortest_paths(
            edges[:, 0], edges[:, 1], None, n, engine=engine
        )
        assert int(rounds) <= sssp_round_bound(n)
        assert np.isfinite(np.asarray(d)).all()


def test_frontier_stats_beat_dense_on_chains():
    """The work accounting the benchmark pins: on a long chain the
    frontier engine's relax visits stay far below the dense engine's
    m2 * rounds (only the advancing front relaxes), at the cost of one
    full-list mask gather per level."""
    from repro.ops.kiss import list_graph

    n = 1024
    edges = list_graph(n, 1, seed=22)
    w = _eighth_weights(edges)
    fd, fp, fr, fstats = frontier_bellman_ford(
        edges[:, 0], edges[:, 1], w, n, min_bucket=64, with_stats=True
    )
    dd, dp, dr, dstats = bellman_ford(
        edges[:, 0], edges[:, 1], w, n, with_stats=True
    )
    np.testing.assert_array_equal(np.asarray(fd), np.asarray(dd))
    np.testing.assert_array_equal(np.asarray(fp), np.asarray(dp))
    assert fstats.relax_visits < dstats.relax_visits / 2
    # one mask gather per level PLUS the terminal empty-frontier check
    assert fstats.mask_visits == fstats.m2 * (len(fstats.levels) + 1)
    assert dstats.mask_visits == 0 and dstats.m2 == fstats.m2
    assert fstats.num_sources == dstats.num_sources == 1


def _random_graph(n, m, seed):
    r = np.random.default_rng(seed)
    edges = r.integers(0, n, size=(m, 2)).astype(np.int32)
    return n, edges, r.random(m).astype(np.float32)


def _chain_graph(n):
    from repro.ops.kiss import list_graph

    edges = list_graph(n, 1, seed=22)
    return n, edges, _eighth_weights(edges)


@pytest.mark.parametrize("graph,min_bucket,dense", [
    (lambda: _chain_graph(256), 16, "none"),
    (lambda: _random_graph(300, 1000, 7), 64, "some"),
    (lambda: _random_graph(300, 1000, 7), 2048, "every"),
    (lambda: _random_graph(40, 100, 3), 1024, "every"),  # m2 < min_bucket
], ids=["chain-none", "random-some", "random-bucket-is-list",
        "small-m2-under-min-bucket"])
def test_dense_levels_relax_the_whole_list_exactly(graph, min_bucket, dense):
    """A level whose bucket is the whole arc list relaxes the list
    uncompacted: distances and parents stay bit-exact vs the dense walk
    and both serial oracles, every such level is counted, and the visit
    counters still read a bucket per level and a mask per read."""
    n, edges, w = graph()
    src, dst = edges[:, 0], edges[:, 1]
    fd, fp, _, stats = frontier_bellman_ford(
        src, dst, w, n, sources=5, min_bucket=min_bucket, with_stats=True
    )
    dd, dp, _ = bellman_ford(src, dst, w, n, sources=5)
    for want_d, want_p in [(np.asarray(dd), np.asarray(dp)),
                           serial_bellman_ford(edges, w, n, 5),
                           serial_dijkstra(edges, w, n, 5)]:
        np.testing.assert_array_equal(np.asarray(fd).view(np.uint32),
                                      np.asarray(want_d).view(np.uint32))
        np.testing.assert_array_equal(np.asarray(fp), want_p)
    sizes = [size for size, _ in stats.levels]
    hits = sum(size == stats.m2 for size in sizes)
    assert stats.dense_levels == hits
    assert {"none": hits == 0, "some": 0 < hits < len(sizes),
            "every": hits == len(sizes) > 0}[dense], stats.levels
    assert stats.relax_visits == sum(sizes)
    assert stats.mask_visits == stats.m2 * (len(sizes) + 1)


# ---------------------------------------------------------------- serve


def test_serve_sssp_batched_bit_exact_vs_solo():
    """kind="sssp" waves: packed multi-request multi-source results ==
    solo shortest_paths calls, on the dense (default) and pinned
    frontier serve engines."""
    stream = graph_request_stream(7, kind="sssp", seed=31)
    for eng_kw in ({}, {"engine": "frontier", "min_bucket": 32}):
        eng = GraphServeEngine(max_requests=3, **eng_kw)
        reqs = _requests(stream)
        for r in reqs:
            eng.submit(r)
        done = eng.run()
        assert len(done) == len(stream)
        for r in done:
            _assert_matches_solo(r, stream[r.uid])
        assert all(w.stage == "sssp" for w in eng.wave_records)
        assert all(w.src_cap >= 1 for w in eng.wave_records)


def test_serve_sssp_family_separated_from_cc_chain():
    """A mixed queue packs sssp requests only with other sssp requests
    (different device programs), preserving FIFO completion order."""
    stream = (
        graph_request_stream(2, kind="cc", seed=32)
        + graph_request_stream(2, kind="sssp", seed=33)
        + graph_request_stream(2, kind="analytics", family="tree", seed=34)
    )
    eng = GraphServeEngine(max_requests=16)
    for r in _requests(stream):
        eng.submit(r)
    done = eng.run()
    assert [r.uid for r in done] == list(range(len(stream)))
    assert [w.stage for w in eng.wave_records] == [
        "cc", "sssp", "analytics",
    ]
    for r in done:
        _assert_matches_solo(r, stream[r.uid])


def test_serve_sssp_submit_validation():
    eng = GraphServeEngine(max_sources=2)
    e = np.array([0, 1], np.int32), np.array([1, 2], np.int32)

    def req(uid, **kw):
        return GraphRequest(
            uid=uid, src=e[0], dst=e[1], num_nodes=3, kind="sssp", **kw
        )

    with pytest.raises(ValueError, match="finite"):
        eng.submit(req(0, weights=np.array([1.0, -2.0])))
    with pytest.raises(ValueError, match="finite"):
        eng.submit(req(1, weights=np.array([1.0, np.nan])))
    with pytest.raises(ValueError, match="length"):
        eng.submit(req(2, weights=np.array([1.0])))
    with pytest.raises(ValueError, match="sources outside"):
        eng.submit(req(3, sources=np.array([3])))
    with pytest.raises(ValueError, match="max_sources"):
        eng.submit(req(4, sources=np.array([0, 1, 2])))
    with pytest.raises(ValueError, match="sssp/pagerank kinds"):
        eng.submit(GraphRequest(
            uid=5, src=e[0], dst=e[1], num_nodes=3, kind="cc",
            weights=np.array([1.0, 1.0]),
        ))
    assert eng.queue == []  # nothing slipped through
    sharded = GraphServeEngine(engine="sharded_frontier")
    with pytest.raises(ValueError, match="single-device"):
        sharded.submit(req(6))
    hooked = GraphServeEngine(hook_impl="xla")
    with pytest.raises(ValueError, match="hook_impl"):
        hooked.submit(req(7))


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 5), st.integers(0, 10_000), st.integers(1, 4))
def test_serve_sssp_random_streams_property(num_requests, seed, width):
    """Hypothesis: sssp serving is bit-exact vs solo on random streams,
    including empty-edge and single-node requests."""
    r = np.random.default_rng(seed)
    stream = []
    for _ in range(num_requests):
        n = int(r.integers(1, 12))
        m = int(r.integers(0, 3 * n))
        stream.append({
            "src": r.integers(0, n, m).astype(np.int32),
            "dst": r.integers(0, n, m).astype(np.int32),
            "num_nodes": n,
            "kind": "sssp",
            "weights": (r.integers(0, 8, m) / 4.0).astype(np.float32),
            "sources": r.integers(0, n, int(r.integers(1, 3))).astype(
                np.int32
            ),
        })
    eng = GraphServeEngine(max_requests=width)
    reqs = _requests(stream)
    for q in reqs:
        eng.submit(q)
    done = eng.run()
    assert len(done) == len(stream)
    for q in done:
        _assert_matches_solo(q, stream[q.uid])


# ---------------------------------------------------------------------------
# Graph500 kernel 3 shapes: Kronecker graphs, one root per call
# ---------------------------------------------------------------------------


def _kronecker(scale, seed, edgefactor=16, a=0.57, b=0.19, c=0.19):
    """A Graph500-shaped Kronecker graph (duplicates and self-loops
    kept, vertex ids permuted) with weights that are multiples of
    2**-24 in [0, 1), and the vertices of degree >= 1."""
    r = np.random.default_rng(seed)
    m = edgefactor << scale
    ab = a + b
    ij = np.zeros((2, m), np.int64)
    for level in range(scale):
        ii = r.random(m) > ab
        jj = r.random(m) > np.where(ii, c / (1.0 - ab), a / ab)
        ij += np.stack([ii, jj]).astype(np.int64) << level
    src, dst = r.permutation(1 << scale)[ij].astype(np.int32)
    w = (r.integers(0, 1 << 24, m) * 2.0 ** -24).astype(np.float32)
    live = np.flatnonzero(np.bincount(np.r_[src, dst], minlength=1 << scale))
    return src, dst, w, live


def _plain_bellman_ford(src, dst, w, n, root):
    """Synchronous float32 Bellman-Ford over both orientations of every
    edge until a round changes nothing, and the min-id parent rule."""
    u, v, w2 = np.r_[src, dst], np.r_[dst, src], np.r_[w, w]
    dist = np.full(n, np.inf, np.float32)
    dist[root] = 0.0
    while True:
        nxt = dist.copy()
        np.minimum.at(nxt, v, dist[u] + w2)
        if np.array_equal(nxt, dist):
            break
        dist = nxt
    parent = np.full(n, n, np.int64)
    ok = (u != v) & (dist[u] + w2 == dist[v])
    np.minimum.at(parent, v[ok], u[ok])
    parent[(parent == n) | np.isinf(dist)] = -1
    parent[root] = root
    return dist, parent


@pytest.mark.parametrize("scale,seed,pick", [
    (8, 11, 0), (8, 11, 1), (9, 12, 2), (10, 13, 3), (10, 13, 4),
])
def test_kronecker_one_root_per_call_bit_exact(scale, seed, pick):
    """The default engine, one root per call, on a Graph500-shaped
    multigraph: distances bit for bit and parents equal to a plain
    float32 Bellman-Ford written here."""
    src, dst, w, live = _kronecker(scale, seed)
    n = 1 << scale
    root = int(np.random.default_rng(pick).choice(live))
    assert (src == dst).any()  # self-loops kept
    d, p, _ = shortest_paths(src, dst, w, n, sources=root)
    want_d, want_p = _plain_bellman_ford(src, dst, w, n, root)
    np.testing.assert_array_equal(np.asarray(d).view(np.uint32),
                                  want_d.view(np.uint32))
    np.testing.assert_array_equal(np.asarray(p), want_p)
