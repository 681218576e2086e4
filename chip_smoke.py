"""Bring-up check: the graph engines end to end on one TPU chip.

    python chip_smoke.py             # every phase, on one chip
    python chip_smoke.py --chips 4   # only the sharded phase, on four chips

Each phase runs through the public API (``repro.core``, ``repro.serve``)
with the engines the code picks by default, at sizes graph users call
real, and checks every output against an oracle that shares no code with
the engine under test. Each phase prints one JSON line: its sizes, the
host seconds to generate its inputs (``gen_s``), the first engine call
with its compiles (``setup_s``), a second, warm call on the same inputs
(``run_s``), the oracle check (``check_s``), and the device's bytes in
use where the backend reports them; the ``--chips 4`` phase calls each
engine once and prints the sharding of its outputs. The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

There is no fallback: without a TPU, or with another device count than
asked for, the script exits nonzero before any phase, and a phase that
fails its check or raises ends the script with a nonzero exit and no
result line. Everything runs in this one process (a chip belongs to one
process at a time).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

# Sizes (nodes / list length). Each is the size the phase's users run on
# one chip: a 16M-node list, 4M-node graphs with 32M edges, a 1M-node
# weighted graph, and a serving stream of molecule-sized requests.
LIST_N = 1 << 24
CC_N = 1 << 22
CC_EDGES_PER_NODE = 8  # = core.AUTO_SAMPLE_DENSITY: the Afforest pre-pass runs
WEIGHTED_N = 1 << 20
WEIGHTED_EDGES_PER_NODE = 4
PAGERANK_ITERS = 20
SERVE_PER_KIND = 16
SERVE_KINDS = ("cc", "analytics", "sssp", "pagerank")
MOLECULES = 4096  # --chips 4 tree-analytics batch (30 nodes, 64 edges each)
# --chips 4 graphs are smaller than CC_N: the sharded frontier engine
# compiles one program per bucket of its ladder, and for a v5e:2x2 one
# such program takes the compiler about 5 s at 2**20 arcs but 77 s at
# 2**26 arcs, so cold ladders at CC_N would take most of a run.
SHARDED_CC_N = 1 << 18

# PageRank on the chip need not be bit-exact with np.add.at: the TPU's
# scatter-add may fold contributions in another order, and its float32
# division may round differently. Each iteration sums <= ~40 float32
# terms per node (relative error <= 40 * 2**-24 ~ 2.4e-6) and damping
# 0.85 contracts older errors, so the steady-state error stays under
# 2.4e-6 / 0.15 ~ 1.6e-5; 1e-4 leaves margin while a lost or doubled
# edge contribution (>= ~1 / degree) still fails.
PAGERANK_RTOL = 1e-4


class SmokeFailure(RuntimeError):
    """An engine output disagreed with its oracle."""


def require(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def device_bytes() -> dict:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return {k: stats[k] for k in ("bytes_in_use", "peak_bytes_in_use")
            if k in stats}


def run_twice(fn):
    """(output, setup_s, run_s): the first call compiles, the second is
    warm; both block until the device is done. The two outputs must be
    identical -- every engine here is deterministic."""
    import jax

    (first, setup), (out, run) = _timed(fn), _timed(fn)
    for a, b in zip(jax.tree.leaves(first), jax.tree.leaves(out)):
        require(np.array_equal(a, b), "two identical calls disagreed")
    return jax.tree.map(np.asarray, out), setup, run


def _timed(fn):
    """(output, seconds) of one call, blocked until the device is done."""
    import jax

    t = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t


# --------------------------------------------------------------------------
# Inputs (KISS-generated from fixed seeds; host NumPy)
# --------------------------------------------------------------------------


def random_graph_edges(n: int, edges_per_node: int, seed: int) -> np.ndarray:
    from repro.ops.kiss import random_graph

    edges = random_graph(n, 2.0 * edges_per_node / (n - 1), seed=seed)
    require(len(edges) == edges_per_node * n, "generator edge count")
    return edges


def uniform_weights(m: int, seed: int) -> np.ndarray:
    """Float32 in [0, 1), multiples of 2**-24 (exact, never subnormal)."""
    from repro.ops.kiss import KissRng

    ints = KissRng(seed, 8192).uniform_ints((m,), 1 << 24)
    return (ints.astype(np.float64) / (1 << 24)).astype(np.float32)


# --------------------------------------------------------------------------
# Oracles independent of the engines
# --------------------------------------------------------------------------


def check_list_ranks(succ: np.ndarray, rank: np.ndarray) -> None:
    """rank[tail] = 0 and rank[j] = rank[succ[j]] + 1 elsewhere: on a
    single list from the head this is serial_list_rank, vectorised."""
    idx = np.arange(len(succ))
    tail = succ == idx
    require(tail.sum() == 1, "list must have one tail")
    require(rank[tail][0] == 0, "rank[tail] != 0")
    require(np.array_equal(rank[~tail], rank[succ[~tail]] + 1),
            "rank[j] != rank[succ[j]] + 1")


def numpy_components(edges: np.ndarray, n: int) -> np.ndarray:
    """Connected components by hook-and-compress in NumPy, sharing no
    code with the engines: every root hooks onto the smallest root across
    its crossing edges, then all paths compress; repeat until no edge
    crosses two roots. Parents only ever decrease, so each component ends
    labelled with its minimum node id (serial_connected_components'
    labels)."""
    a = edges[:, 0].astype(np.int64)
    b = edges[:, 1].astype(np.int64)
    parent = np.arange(n, dtype=np.int64)
    while True:
        ra, rb = parent[a], parent[b]
        cross = ra != rb
        if not cross.any():
            return parent
        a, b, ra, rb = a[cross], b[cross], ra[cross], rb[cross]
        np.minimum.at(parent, np.maximum(ra, rb), np.minimum(ra, rb))
        while True:
            pp = parent[parent]
            if np.array_equal(pp, parent):
                break
            parent = pp


def check_sssp(edges, weights, n, source, dist, parent) -> None:
    from repro.core.serial import serial_dijkstra

    want_d, want_p = serial_dijkstra(edges, weights, n, source)
    require(np.array_equal(dist, want_d), f"SSSP distances from {source}")
    require(np.array_equal(parent, want_p), f"SSSP parents from {source}")


def compare_pagerank(got: np.ndarray, want: np.ndarray) -> dict:
    diff = np.abs(got.astype(np.float64) - want.astype(np.float64))
    rel = diff / np.abs(want.astype(np.float64))
    out = {
        "bit_exact": bool(np.array_equal(got, want)),
        "max_abs_diff": float(diff.max()),
        "max_rel_diff": float(rel.max()),
        "rtol": PAGERANK_RTOL,
    }
    require(out["bit_exact"] or out["max_rel_diff"] <= PAGERANK_RTOL,
            f"PageRank off the oracle beyond rtol={PAGERANK_RTOL}: {out}")
    return out


# --------------------------------------------------------------------------
# One-chip phases
# --------------------------------------------------------------------------


def phase_list_rank(n: int = LIST_N) -> dict:
    from repro.core import list_rank
    from repro.ops.kiss import random_linked_list

    t = time.perf_counter()
    succ = random_linked_list(n, seed=1)
    gen = time.perf_counter() - t
    rank, setup, run = run_twice(lambda: list_rank(succ))
    t = time.perf_counter()
    check_list_ranks(succ, rank)
    return dict(phase="list_rank", n=n, gen_s=gen, setup_s=setup,
                run_s=run, check_s=time.perf_counter() - t,
                oracle="rank[j] = rank[succ[j]] + 1")


def phase_cc_random(n: int = CC_N) -> dict:
    from repro.core import connected_components
    from repro.core.serial import canonicalize_labels

    t = time.perf_counter()
    edges = random_graph_edges(n, CC_EDGES_PER_NODE, seed=2)
    gen = time.perf_counter() - t
    (labels, rounds), setup, run = run_twice(
        lambda: connected_components(edges[:, 0], edges[:, 1], n)
    )
    t = time.perf_counter()
    want = numpy_components(edges, n)
    # The Afforest pre-pass may pick other representatives: compare the
    # partitions.
    require(np.array_equal(canonicalize_labels(labels), want),
            "CC partition (random graph)")
    return dict(phase="cc_random", n=n, m=len(edges), rounds=int(rounds),
                components=int(len(np.unique(want))), gen_s=gen,
                setup_s=setup, run_s=run, check_s=time.perf_counter() - t,
                oracle="numpy hook-and-compress (serial union-find is too "
                       "slow at this size)")


def phase_cc_giant_dust(n: int = CC_N) -> dict:
    from repro.core import connected_components
    from repro.core.serial import serial_connected_components
    from repro.ops.kiss import giant_dust_graph

    t = time.perf_counter()
    edges = giant_dust_graph(n, seed=3)
    gen = time.perf_counter() - t
    (labels, rounds), setup, run = run_twice(
        lambda: connected_components(edges[:, 0], edges[:, 1], n)
    )
    t = time.perf_counter()
    want = serial_connected_components(edges, n)
    require(np.array_equal(labels, want), "CC labels (giant + dust)")
    return dict(phase="cc_giant_dust", n=n, m=len(edges),
                rounds=int(rounds), components=int(len(np.unique(want))),
                gen_s=gen, setup_s=setup, run_s=run,
                check_s=time.perf_counter() - t,
                oracle="serial_connected_components")


def weighted_graph(n: int = WEIGHTED_N):
    edges = random_graph_edges(n, WEIGHTED_EDGES_PER_NODE, seed=4)
    return edges, uniform_weights(len(edges), seed=5)


def phase_sssp(edges, weights, n: int = WEIGHTED_N) -> dict:
    from repro.core import shortest_paths

    (dist, parent, rounds), setup, run = run_twice(
        lambda: shortest_paths(edges[:, 0], edges[:, 1], weights, n,
                               sources=0)
    )
    t = time.perf_counter()
    check_sssp(edges, weights, n, 0, dist, parent)
    return dict(phase="sssp", n=n, m=len(edges), rounds=int(rounds),
                reached=int(np.isfinite(dist).sum()), setup_s=setup,
                run_s=run, check_s=time.perf_counter() - t,
                oracle="serial_dijkstra, bit for bit")


def phase_pagerank(edges, weights, n: int = WEIGHTED_N) -> dict:
    from repro.core import pagerank
    from repro.core.serial import serial_pagerank

    (scores, _iters), setup, run = run_twice(
        lambda: pagerank(edges[:, 0], edges[:, 1], weights, n,
                         engine="dense", num_iters=PAGERANK_ITERS)
    )
    t = time.perf_counter()
    want = serial_pagerank(edges, weights, n, num_iters=PAGERANK_ITERS)
    cmp = compare_pagerank(scores, want)
    return dict(phase="pagerank", n=n, m=len(edges), iters=PAGERANK_ITERS,
                setup_s=setup, run_s=run, check_s=time.perf_counter() - t,
                oracle="serial_pagerank", **cmp)


def check_served(req, g, pagerank_iters: int) -> dict | None:
    """One served request against the serial oracles; returns the
    PageRank comparison for pagerank requests."""
    from repro.core.serial import serial_connected_components, serial_pagerank
    from repro.trees.reference import serial_tree_reference

    res = req.result
    require(req.done and not req.failed and res is not None,
            f"request {req.uid} ({req.kind}) not served: {req.error}")
    n = g["num_nodes"]
    edges = np.stack([g["src"], g["dst"]], axis=1)
    if req.kind == "pagerank":
        want = serial_pagerank(edges, g["weights"], n, num_iters=pagerank_iters)
        return compare_pagerank(res.scores, want)
    if req.kind == "sssp":
        for row, s in enumerate(g["sources"]):
            check_sssp(edges, g["weights"], n, int(s), res.dist[row],
                       res.pred[row])
        return None
    labels = serial_connected_components(edges, n)
    require(np.array_equal(res.labels, labels), f"request {req.uid} labels")
    ncomp = len(np.unique(labels))
    require(res.num_components == ncomp, f"request {req.uid} components")
    if req.kind == "analytics":
        fu, fv = np.asarray(res.edge_u), np.asarray(res.edge_v)
        pairs = {(min(u, v), max(u, v)) for u, v in edges.tolist()}
        require(len(fu) == n - ncomp, f"request {req.uid} forest size")
        require(all((min(u, v), max(u, v)) in pairs
                    for u, v in zip(fu.tolist(), fv.tolist())),
                f"request {req.uid} forest edge not in graph")
        require(np.array_equal(
            serial_connected_components(np.stack([fu, fv], axis=1), n),
            labels), f"request {req.uid} forest does not span")
        ref = serial_tree_reference(fu, fv, n)
        for k, v in ref.items():
            require(np.array_equal(getattr(res, k), v),
                    f"request {req.uid} tree {k}")
    return None


def phase_serve() -> dict:
    from repro.data.graphs import graph_request_stream
    from repro.serve import GraphRequest, GraphServeEngine

    stream = []
    for i, kind in enumerate(SERVE_KINDS):
        stream += graph_request_stream(SERVE_PER_KIND, kind=kind, seed=10 + i)
    np.random.default_rng(0).shuffle(stream)  # mixed traffic

    def serve():
        eng = GraphServeEngine()
        for uid, g in enumerate(stream):
            eng.submit(GraphRequest(uid=uid, **g))
        return eng, eng.run()

    t = time.perf_counter()
    eng, done = serve()
    setup = time.perf_counter() - t
    t = time.perf_counter()
    eng, done = serve()
    run = time.perf_counter() - t
    t = time.perf_counter()
    require(len(done) == len(stream), "requests lost")
    pr = [c for r in done
          if (c := check_served(r, stream[r.uid], eng.pagerank_iters))]
    return dict(phase="serve", requests=len(stream), kinds=list(SERVE_KINDS),
                waves=eng.waves, bucket_compiles=eng.bucket_compiles,
                setup_s=setup, run_s=run, check_s=time.perf_counter() - t,
                oracle="serial CC / tree reference / dijkstra / pagerank",
                pagerank_bit_exact=all(c["bit_exact"] for c in pr),
                pagerank_max_rel_diff=max(c["max_rel_diff"] for c in pr))


# --------------------------------------------------------------------------
# --chips 4: the sharded engines against the one-chip engines
# --------------------------------------------------------------------------


def _sharding(x) -> str:
    return str(getattr(x, "sharding", "host"))


def phase_sharded(num_devices: int) -> list[dict]:
    from repro.core import (
        connected_components,
        list_rank,
        random_splitter_rank,
        tree_analytics,
    )
    from repro.data.graphs import molecule_batch
    from repro.distributed.graph import graph_mesh
    from repro.ops.kiss import giant_dust_graph, random_linked_list

    mesh = graph_mesh(num_devices)
    out = []

    def same(name, sharded_fn, single_fn, extra):
        """One call of each engine (compiles included in the seconds);
        the sharded outputs keep their device arrays so that their
        sharding is printed."""
        sharded, secs = _timed(sharded_fn)
        single, one_chip_secs = _timed(single_fn)
        t = time.perf_counter()
        require(all(np.array_equal(np.asarray(a), np.asarray(b))
                    for a, b in zip(sharded, single)),
                f"{name}: sharded result differs from the one-chip engine")
        rec = dict(phase=f"sharded_{name}", devices=num_devices, **extra,
                   setup_s=secs, one_chip_setup_s=one_chip_secs,
                   check_s=time.perf_counter() - t,
                   oracle="one-chip engine, bit for bit",
                   sharding=[_sharding(x) for x in sharded])
        emit(rec)
        out.append(rec)
        return sharded

    succ = random_linked_list(LIST_N, seed=1)
    ranks = same("list_rank", lambda: (list_rank(succ, mesh=mesh),),
                 lambda: (random_splitter_rank(succ),), dict(n=LIST_N))
    check_list_ranks(succ, np.asarray(ranks[0]))

    mol = molecule_batch(MOLECULES)
    n = len(mol["graph_ids"])
    fields = ("parent", "depth", "subtree_size", "preorder", "postorder")

    def analytics(**kw):
        ta = tree_analytics(mol["src"], mol["dst"], n, **kw)
        return tuple(getattr(ta.computations, k) for k in fields)

    same("tree_analytics", lambda: analytics(mesh=mesh),
         lambda: analytics(engine="frontier", rank_engine="wylie"),
         dict(n=n, m=len(mol["src"]), molecules=MOLECULES))

    n = SHARDED_CC_N
    graphs = {
        "cc_giant_dust": giant_dust_graph(n, seed=3),
        "cc_random": random_graph_edges(n, CC_EDGES_PER_NODE, seed=2),
    }
    for name, edges in graphs.items():
        src, dst = edges[:, 0], edges[:, 1]
        # The sharded frontier engine is bit-exact with the one-chip
        # frontier engine without the sampling pre-pass.
        same(name, lambda: connected_components(src, dst, n, mesh=mesh),
             lambda: connected_components(src, dst, n, engine="frontier"),
             dict(n=n, m=len(edges)))
    return out


# --------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1 (default): every one-chip phase; 4: only the "
                         "sharded engines against the one-chip engines")
    args = ap.parse_args(argv)

    cache = use_compile_cache()
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU -- JAX found platform {platform!r} "
              f"({devices[0].device_kind}); this check never falls back "
              "to the CPU", file=sys.stderr)
        return 2
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} needs exactly "
              f"{args.chips} visible TPU device(s), found {len(devices)} "
              "(the engines switch to their sharded variants when several "
              "devices are visible)", file=sys.stderr)
        return 2
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    emit({"phase": "start", "device": device, "compile_cache": cache,
          "jax": jax.__version__})

    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded(4)
    else:
        phases = [phase_list_rank, phase_cc_random, phase_cc_giant_dust]
        for phase in phases:
            emit(phase() | device_bytes())
        t = time.perf_counter()
        edges, weights = weighted_graph()
        gen = time.perf_counter() - t
        emit(phase_sssp(edges, weights) | {"gen_s": gen} | device_bytes())
        emit(phase_pagerank(edges, weights) | device_bytes())
        emit(phase_serve() | device_bytes())
    emit({"phase": "done", "total_s": time.perf_counter() - t0})
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
