"""Wave-batched graph serving vs one-request-at-a-time.

Replays a KISS-deterministic stream of small independent graph requests
(``data/graphs.graph_request_stream`` -- the many-small-molecule-graphs
serving workload) through ``repro.serve.GraphServeEngine`` twice: once
wave-batched (``max_requests=16``) and once with ``max_requests=1``,
which is the same code path serving one request per wave -- the honest
one-request-at-a-time baseline (it still buckets, so the baseline's
compiles are amortized too; the win measured here is batching, not
compile caching).

Emits wall time per REQUEST plus the deterministic batching counters
the serve layer guarantees -- requests/wave, padded-slot waste
(node/edge), and bucket compiles (one set of compiled programs per
(stage, node_cap, edge_cap) bucket) -- which ``run.py --check``
guards against the committed ``BENCH_smoke.json`` in CI.
Wall-derived numbers (the speedup) are printed as comments only: the
counters in ``derived`` must be deterministic at a given scale.
"""
from __future__ import annotations

from benchmarks.common import SCALE, emit, time_fn
from repro.data.graphs import graph_request_stream
from repro.obs.metrics import derived_fragment
from repro.serve import GraphRequest, GraphServeEngine


def _serve(stream, max_requests: int, **knobs) -> GraphServeEngine:
    eng = GraphServeEngine(max_requests=max_requests, **knobs)
    for i, g in enumerate(stream):
        eng.submit(GraphRequest(uid=i, **g))
    eng.run()
    return eng


def run(num_requests: int | None = None) -> list[str]:
    R = num_requests or max(8, int(1600 * SCALE))
    lines = []
    for kind, family in (
        ("cc", "random"), ("analytics", "tree"), ("pagerank", "random"),
    ):
        stream = graph_request_stream(R, kind=kind, family=family, seed=11)
        t_batch = time_fn(lambda: _serve(stream, 16), iters=2)
        eng = _serve(stream, 16)
        # legacy counters first (pinned bit-identical by --check), then
        # the engine's unified metrics.snapshot() (repro.obs.metrics)
        lines.append(emit(
            f"graph_serve/batched/{kind}/{family}/req={R}",
            t_batch / R * 1e6,
            f"waves={eng.waves};req_per_wave={eng.requests_per_wave:.2f};"
            f"compiles={eng.bucket_compiles};"
            f"node_waste={eng.node_pad_waste:.3f};"
            f"edge_waste={eng.edge_pad_waste:.3f};"
            + derived_fragment(eng.metrics.snapshot()),
            spread=(t_batch.p10 / R * 1e6, t_batch.p90 / R * 1e6),
        ))
        t_solo = time_fn(lambda: _serve(stream, 1), iters=2)
        solo = _serve(stream, 1)
        lines.append(emit(
            f"graph_serve/solo/{kind}/{family}/req={R}",
            t_solo / R * 1e6,
            f"waves={solo.waves};compiles={solo.bucket_compiles}",
            spread=(t_solo.p10 / R * 1e6, t_solo.p90 / R * 1e6),
        ))
        print(
            f"# graph_serve {kind}/{family}: batched "
            f"{t_batch / R * 1e6:.0f} us/req vs solo "
            f"{t_solo / R * 1e6:.0f} us/req "
            f"({t_solo / max(t_batch, 1e-12):.2f}x)",
            flush=True,
        )

    # rank_engine="splitter" lane: served forests vary their tour-head
    # count per wave, and the splitter count is a compiled dimension of
    # the rank core -- tour_splitters' power-of-two capacity pad is
    # what keeps the compile count bucket-bounded. Pinned here as the
    # jit-cache DELTA of _random_splitter_core across the whole serve
    # run (a raw size would count earlier suites' shapes).
    from repro.core.list_ranking import _random_splitter_core

    stream = graph_request_stream(
        R, kind="analytics", family="tree", seed=13
    )
    cache0 = _random_splitter_core._cache_size()
    t_spl = time_fn(
        lambda: _serve(stream, 16, rank_engine="splitter"), iters=2
    )
    eng = _serve(stream, 16, rank_engine="splitter")
    rank_compiles = _random_splitter_core._cache_size() - cache0
    lines.append(emit(
        f"graph_serve/batched/analytics-splitter/tree/req={R}",
        t_spl / R * 1e6,
        f"waves={eng.waves};compiles={eng.bucket_compiles};"
        f"rank_compiles={rank_compiles}",
        spread=(t_spl.p10 / R * 1e6, t_spl.p90 / R * 1e6),
    ))
    return lines


if __name__ == "__main__":
    run()
