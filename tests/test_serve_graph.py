"""Wave-batched graph serving (`repro.serve.graph`): packed-batch
results must be bit-exact vs issuing each request alone with the same
engine knobs, compiles must be bucket-bounded, and admission must
reject impossible requests loudly."""
import numpy as np
import pytest

from conftest import given, settings, st  # hypothesis or skip-stubs

from repro.core import (
    connected_components,
    num_components,
    serve_graphs,
    spanning_forest,
    tree_analytics,
)
from repro.data.graphs import graph_request_stream
from repro.serve import GraphRequest, GraphServeEngine

FIELDS = ("parent", "depth", "subtree_size", "preorder", "postorder")


def _requests(stream):
    return [GraphRequest(uid=i, **g) for i, g in enumerate(stream)]


def _assert_matches_solo(req, g, *, engine="dense", mesh=None,
                         pagerank_iters=None):
    """Batched result == the same engine run on the request alone."""
    res = req.result
    assert req.done and res is not None
    if g["kind"] == "pagerank":
        # the serve path always runs the dense fixed-iteration engine;
        # its default count is pagerank_iter_bound at default knobs.
        from repro.core.pagerank import pagerank, pagerank_iter_bound

        iters = (
            pagerank_iters if pagerank_iters is not None
            else pagerank_iter_bound()
        )
        scores, _ = pagerank(
            g["src"], g["dst"], g.get("weights"), g["num_nodes"],
            engine="dense", num_iters=iters,
        )
        np.testing.assert_array_equal(res.scores, np.asarray(scores))
        assert res.labels is None and res.dist is None
        assert res.edge_u is None and res.parent is None
        return
    if g["kind"] == "sssp":
        # sssp engines are bit-exact across engines, so "dense" pins
        # the solo baseline regardless of what the wave ran.
        from repro.core import shortest_paths

        sources = g.get("sources")
        if sources is None:
            sources = np.zeros(1, np.int32)
        dist, pred, _ = shortest_paths(
            g["src"], g["dst"], g.get("weights"), g["num_nodes"],
            sources=np.atleast_1d(np.asarray(sources, np.int32)),
            engine="dense",
        )
        np.testing.assert_array_equal(res.dist, np.asarray(dist))
        np.testing.assert_array_equal(res.pred, np.asarray(pred))
        np.testing.assert_array_equal(
            res.sources, np.atleast_1d(np.asarray(sources, np.int32))
        )
        assert res.labels is None and res.edge_u is None
        return
    lab, _ = connected_components(
        g["src"], g["dst"], g["num_nodes"], engine=engine, mesh=mesh,
        dedup=False,
    )
    np.testing.assert_array_equal(res.labels, np.asarray(lab))
    assert res.num_components == num_components(lab)
    # stage promotion must not leak wave-mate-dependent extra fields
    if g["kind"] == "cc":
        assert res.edge_u is None and res.parent is None
    if g["kind"] == "forest":
        assert res.parent is None
    if g["kind"] in ("forest", "analytics"):
        forest = spanning_forest(
            g["src"], g["dst"], g["num_nodes"], engine=engine, mesh=mesh,
            dedup=False,
        )
        np.testing.assert_array_equal(res.edge_u, forest.edge_u)
        np.testing.assert_array_equal(res.edge_v, forest.edge_v)
    if g["kind"] == "analytics":
        ta = tree_analytics(
            g["src"], g["dst"], g["num_nodes"], engine=engine, mesh=mesh,
            dedup=False,
        )
        for k in FIELDS:
            np.testing.assert_array_equal(
                getattr(res, k), np.asarray(getattr(ta.computations, k)),
                err_msg=f"{k} uid={req.uid}",
            )


def test_batched_bit_exact_vs_solo_mixed_kinds():
    """Mixed cc/forest/analytics waves (stage promotion) over random
    graphs, trees, an empty-edge request, and a single-node request."""
    stream = (
        graph_request_stream(4, kind="cc", seed=1)
        + graph_request_stream(3, kind="forest", family="tree", seed=2)
        + graph_request_stream(4, kind="analytics", family="tree", seed=3)
    )
    z = np.zeros(0, np.int32)
    stream.append({"src": z, "dst": z, "num_nodes": 6, "kind": "analytics"})
    stream.append({"src": z, "dst": z, "num_nodes": 1, "kind": "cc"})
    np.random.default_rng(0).shuffle(stream)  # interleave kinds per wave

    eng = GraphServeEngine(max_requests=5)
    for r in _requests(stream):
        eng.submit(r)
    done = eng.run()
    assert len(done) == len(stream) and eng.waves == 3
    for r in done:
        _assert_matches_solo(r, stream[r.uid])


def test_bucket_compiles_bounded_and_reused():
    """Same-bucket waves reuse compiled programs: the bucket counter
    stays at 1 across many waves, and (when jax exposes jit cache
    sizes) the dense CC kernel really compiled once."""
    from repro.core.components import _sv_dense

    stream = graph_request_stream(12, kind="cc", seed=5)
    eng = GraphServeEngine(max_requests=3)
    cache0 = getattr(_sv_dense, "_cache_size", lambda: None)()
    for r in _requests(stream):
        eng.submit(r)
    eng.run()
    assert eng.waves == 4
    assert eng.bucket_compiles == len(
        {(w.stage, w.node_cap, w.edge_cap) for w in eng.wave_records}
    )
    assert sum(w.new_bucket for w in eng.wave_records) == eng.bucket_compiles
    caps = {(w.node_cap, w.edge_cap) for w in eng.wave_records}
    if cache0 is not None and len(caps) == eng.bucket_compiles:
        added = _sv_dense._cache_size() - cache0
        assert added <= eng.bucket_compiles, (
            "dense CC compiled more than once per bucket"
        )
    # deterministic accounting invariants
    assert eng.requests_per_wave == pytest.approx(3.0)
    assert 0.0 <= eng.node_pad_waste < 1.0
    assert 0.0 <= eng.edge_pad_waste < 1.0


def test_solo_wave_engine_is_identity_baseline():
    """max_requests=1 (the benchmark baseline) serves each request in
    its own wave and still matches direct engine calls."""
    stream = graph_request_stream(4, kind="analytics", family="tree", seed=7)
    eng = GraphServeEngine(max_requests=1)
    for r in _requests(stream):
        eng.submit(r)
    done = eng.run()
    assert eng.waves == len(stream)
    assert all(w.requests == 1 for w in eng.wave_records)
    for r in done:
        _assert_matches_solo(r, stream[r.uid])


def test_serve_graphs_core_dispatch():
    """repro.core.serve_graphs honours engine= like the other entry
    points (explicit frontier engine, still bit-exact)."""
    stream = graph_request_stream(5, kind="forest", seed=9)
    done = serve_graphs(
        _requests(stream), max_requests=4, engine="frontier", min_bucket=32
    )
    for r in done:
        _assert_matches_solo(r, stream[r.uid], engine="frontier")


def test_serve_graphs_mesh_path():
    """An explicit mesh routes every wave through the sharded engines,
    bit-exact vs solo sharded calls."""
    from repro.distributed.graph import graph_mesh

    mesh = graph_mesh(1)
    stream = graph_request_stream(4, kind="analytics", family="tree", seed=13)
    done = serve_graphs(_requests(stream), max_requests=4, mesh=mesh)
    for r in done:
        _assert_matches_solo(r, stream[r.uid], engine="auto", mesh=mesh)


def test_submit_validation():
    eng = GraphServeEngine(max_nodes=64, max_edges=64)
    z = np.zeros(0, np.int32)
    with pytest.raises(ValueError, match="kind"):
        eng.submit(GraphRequest(uid=0, src=z, dst=z, num_nodes=3,
                                kind="labels"))
    with pytest.raises(ValueError, match="num_nodes"):
        eng.submit(GraphRequest(uid=1, src=z, dst=z, num_nodes=0))
    with pytest.raises(ValueError, match="budget"):
        eng.submit(GraphRequest(uid=2, src=z, dst=z, num_nodes=65))
    with pytest.raises(ValueError, match="budget"):
        eng.submit(GraphRequest(
            uid=3, src=np.zeros(65, np.int32), dst=np.zeros(65, np.int32),
            num_nodes=4,
        ))
    with pytest.raises(ValueError, match="endpoints"):
        eng.submit(GraphRequest(
            uid=4, src=np.array([0], np.int32), dst=np.array([5], np.int32),
            num_nodes=4,
        ))
    with pytest.raises(ValueError, match="endpoints"):
        eng.submit(GraphRequest(
            uid=6, src=np.array([0], np.int32), dst=np.array([-1], np.int32),
            num_nodes=4,
        ))
    with pytest.raises(ValueError, match="mismatch"):
        eng.submit(GraphRequest(
            uid=5, src=np.array([0], np.int32), dst=z, num_nodes=4,
        ))
    assert eng.queue == []  # nothing slipped through
    with pytest.raises(ValueError, match="sample_rounds"):
        GraphServeEngine(sample_rounds=2)
    with pytest.raises(ValueError, match="engine"):
        GraphServeEngine(engine="fastest")


def test_pagerank_batched_bit_exact_vs_solo_and_oracle():
    """kind="pagerank" waves: every unpacked scores slice equals the
    solo dense run AND the serial NumPy oracle bit-for-bit."""
    from repro.core.serial import serial_pagerank

    stream = graph_request_stream(6, kind="pagerank", seed=31)
    eng = GraphServeEngine(max_requests=3)
    for r in _requests(stream):
        eng.submit(r)
    done = eng.run()
    assert len(done) == 6 and eng.waves == 2
    assert all(w.stage == "pagerank" for w in eng.wave_records)
    assert all(w.rounds == eng.pagerank_iters for w in eng.wave_records)
    for r in done:
        g = stream[r.uid]
        _assert_matches_solo(r, g, pagerank_iters=eng.pagerank_iters)
        oracle = serial_pagerank(
            np.stack([g["src"], g["dst"]], axis=1), g["weights"],
            g["num_nodes"], num_iters=eng.pagerank_iters,
        )
        np.testing.assert_array_equal(r.result.scores, oracle)


def test_three_way_family_boundary_fifo_stable():
    """The _FAMILY packing boundary over all three families: a wave
    closes AT the boundary in FIFO order (no reordering past it --
    later same-family requests are NOT pulled forward), each wave is
    family-pure, and stage promotion never crosses a family."""
    stream = (
        graph_request_stream(1, kind="cc", seed=61)
        + graph_request_stream(1, kind="analytics", family="tree", seed=62)
        + graph_request_stream(2, kind="sssp", seed=63)
        + graph_request_stream(2, kind="pagerank", seed=64)
        + graph_request_stream(1, kind="cc", seed=65)
        + graph_request_stream(1, kind="pagerank", seed=66)
    )
    eng = GraphServeEngine(max_requests=16)
    for r in _requests(stream):
        eng.submit(r)
    done = eng.run()
    assert len(done) == len(stream)
    # cc+analytics promote WITHIN the cc-chain family; every family
    # switch closes the wave, and the trailing cc / pagerank requests
    # are served in arrival order, not merged backwards.
    assert [w.stage for w in eng.wave_records] == [
        "analytics", "sssp", "pagerank", "cc", "pagerank"
    ]
    assert [w.requests for w in eng.wave_records] == [2, 2, 2, 1, 1]
    # completion order is FIFO (family boundaries never reorder)
    assert [r.uid for r in done] == list(range(len(stream)))
    for r in done:
        _assert_matches_solo(r, stream[r.uid],
                             pagerank_iters=eng.pagerank_iters)
    # no cross-family field leaks: the cc member of the promoted wave
    # got labels only; sssp rows got no scores; pagerank no labels.
    by_uid = {r.uid: r for r in done}
    assert by_uid[0].result.scores is None
    assert by_uid[0].result.dist is None
    assert by_uid[2].result.scores is None
    assert by_uid[4].result.labels is None


def test_pagerank_submit_validation():
    z = np.zeros(0, np.int32)
    eng = GraphServeEngine()
    with pytest.raises(ValueError, match="sssp-only"):
        eng.submit(GraphRequest(
            uid=0, src=z, dst=z, num_nodes=3, kind="pagerank",
            sources=np.zeros(1, np.int32),
        ))
    with pytest.raises(ValueError, match="finite"):
        eng.submit(GraphRequest(
            uid=1, src=np.array([0], np.int32), dst=np.array([1], np.int32),
            num_nodes=3, kind="pagerank",
            weights=np.array([-1.0], np.float32),
        ))
    with pytest.raises(ValueError, match="only consumed"):
        eng.submit(GraphRequest(
            uid=2, src=z, dst=z, num_nodes=3, kind="cc",
            weights=np.zeros(0, np.float32),
        ))
    assert eng.queue == []
    with pytest.raises(ValueError, match="pagerank_iters"):
        GraphServeEngine(pagerank_iters=0)
    with pytest.raises(ValueError, match="damping"):
        GraphServeEngine(damping=1.5)
    # engine knobs that cannot reach the dense pagerank engine reject
    # at submit, like the sssp path does
    knobbed = GraphServeEngine(engine="frontier", min_bucket=32)
    with pytest.raises(ValueError, match="not pagerank engine knobs"):
        knobbed.submit(GraphRequest(
            uid=3, src=z, dst=z, num_nodes=3, kind="pagerank",
        ))


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 6), st.integers(0, 10_000), st.integers(1, 4))
def test_mixed_family_streams_bit_exact_property(num_requests, seed, width):
    """Hypothesis over ALL THREE packing families interleaved: the
    family-boundary wave closes keep every request bit-exact vs its
    solo engine, including empty-edge and single-node requests."""
    r = np.random.default_rng(seed)
    kinds = ("cc", "analytics", "sssp", "pagerank")
    stream = []
    for _ in range(num_requests):
        n = int(r.integers(1, 14))
        m = int(r.integers(0, 4 * n))
        g = {
            "src": r.integers(0, n, m).astype(np.int32),
            "dst": r.integers(0, n, m).astype(np.int32),
            "num_nodes": n,
            "kind": kinds[int(r.integers(0, len(kinds)))],
        }
        if g["kind"] in ("sssp", "pagerank"):
            g["weights"] = (r.integers(0, 8, m) / 4.0).astype(np.float32)
        if g["kind"] == "sssp":
            g["sources"] = r.integers(
                0, n, int(r.integers(1, 3))
            ).astype(np.int32)
        stream.append(g)
    done = serve_graphs(_requests(stream), max_requests=width)
    assert sorted(req.uid for req in done) == list(range(num_requests))
    for req in done:
        _assert_matches_solo(req, stream[req.uid])


@settings(max_examples=12, deadline=None)
@given(st.integers(1, 6), st.integers(0, 10_000), st.integers(1, 4))
def test_random_streams_bit_exact_property(num_requests, seed, width):
    """Hypothesis: packed-batch serving is bit-exact vs per-request
    calls on random streams, including empty-edge and single-node
    requests."""
    r = np.random.default_rng(seed)
    stream = []
    for _ in range(num_requests):
        n = int(r.integers(1, 14))
        m = int(r.integers(0, 4 * n))
        stream.append({
            "src": r.integers(0, n, m).astype(np.int32),
            "dst": r.integers(0, n, m).astype(np.int32),
            "num_nodes": n,
            "kind": "analytics",
        })
    done = serve_graphs(_requests(stream), max_requests=width)
    for req in done:
        _assert_matches_solo(req, stream[req.uid])
