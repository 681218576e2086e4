"""Shortest paths on the frontier machinery: frontier Bellman-Ford.

The first workload beyond connected components to ride the compacted
edge-frontier + ``next_pow2`` size-bucket loop of ``core/frontier.py``.
Gunrock's observation (PAPERS.md) is that the advance/filter frontier
loop expresses BFS, SSSP, and CC with only the per-edge functor
swapped; here the CC engine's hook-min-scatter becomes a
**relax-min-scatter** -- ``dist.at[:, b].min(dist[:, a] + w)`` -- which
is min-CRCW and therefore deterministic (RL002-clean) by construction.
BFS falls out as the unit-weight case (``weights=None``).

Two engines share the relax round:

* ``bellman_ford`` -- the dense walk: every oriented edge relaxes every
  round inside one ``lax.while_loop``; fully traceable, one compile per
  shape, the serve path's engine (``kind="sssp"`` waves).
* ``frontier_bellman_ford`` -- level-synchronous frontier relaxation:
  each level gathers only the edges OUT of nodes whose distance changed
  last round into a ``next_pow2``-bucketed buffer (padding with inert
  (0, 0) zero-weight self-loops) and relaxes just those. Unlike CC --
  where label equality is permanent, so the buffer shrinks
  monotonically -- a relaxed-quiet edge can wake up again when its
  source's distance later drops, so each level re-compacts **from the
  full edge list** (one O(m) boolean mask gather per level, against the
  S x bucket relax work it saves). A level whose bucket is the whole
  list (more than half the arcs live, or ``m2 <= min_bucket``) skips
  the compaction and relaxes the list itself: the compacted buffer
  would have the same shape, holding every live arc plus inert pads,
  and the quiet arcs relaxed in their place are inert too (see
  Exactness), so the level's outcome is bit-identical. The host sync
  per level is the same level-synchronous design as the CC frontier
  engine, with ``sssp.level`` spans attached at those already-paid
  sync points.

**Exactness.** Distances are the unique least fixpoint of the float32
Bellman relaxations ``dist[v] = min(dist[v], dist[u] + w)`` (float add
is monotonic and each candidate is a single add -- no accumulation-
order ambiguity), so dense, frontier, batched, and the serial oracles
(``core/serial.serial_dijkstra`` / ``serial_bellman_ford``) all produce
bit-identical distances. Skipping quiet edges never changes a round's
outcome (their contribution was already min'd in), so the frontier
engine's per-round distance evolution equals the dense engine's.
Parents are recovered by one deterministic post-pass: ``parent[v]`` is
the **minimum** u over non-self-loop edges with ``dist[u] + w ==
dist[v]`` (min-CRCW again), ``parent[source] = source``, unreachable
nodes get ``-1`` with ``dist = +inf``.

**Batched multi-source** shares one padded compile: sources are extra
rows of the ``(S, n)`` distance matrix, relaxed by the same scatter
(the Johnson all-pairs trick -- n independent sources as one batch).
Rows are independent, so batched results are bit-exact vs per-source
solo runs; the disjoint-union serve packing (``repro.serve.graph``)
builds on exactly this.

Negative weights are rejected up front: edges are walked in both
orientations (the repo-wide undirected convention), so any negative
edge is a negative cycle.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.components import ConvergenceError, check_choice
from repro.core.operators import (
    MIN,
    advance,
    bucket_size,
    compact_weighted,
    run_rebuild_loop,
)
from repro.obs import trace

Array = jax.Array

# shortest_paths(engine=) choices (RL004: registered as "sssp_engine"
# in tools/lint/passes/choice_set.py; docs/engines.md choice-matrix).
# "sharded_frontier" is absent on purpose: the relax scatter has no
# sharded counterpart yet (ROADMAP).
SSSP_ENGINES = ("auto", "frontier", "dense")

UNREACHABLE = -1  # parent sentinel for dist == +inf nodes


def sssp_round_bound(n: int) -> int:
    """Relax-round ceiling: a shortest path uses at most n - 1 edges,
    so n rounds always suffice (n - 1 improving + 1 confirming)."""
    return max(int(n), 1)


@dataclass
class SsspStats:
    """Work accounting for the SSSP engines (benchmarks/sssp_frontier).

    ``relax_visits`` counts edge-slot relax visits the way
    ``FrontierStats.edges_touched`` counts hook work: one per buffer
    slot per relax round (row-batched: the S source rows share each
    slot's gather/scatter lanes). The dense engine's same-metric cost
    is ``m2 * rounds``. ``mask_visits`` is the frontier engine's extra
    cost: one full-edge-list boolean gather per level to rebuild the
    frontier mask (quiet edges can wake up again -- see module
    docstring -- so compaction cannot be permanent like CC's).
    ``dense_levels`` counts the levels whose bucket was the whole list,
    relaxed uncompacted; they still add ``m2`` to ``relax_visits``.
    """

    rounds: int
    relax_visits: int  # compacted relax slots walked (see docstring)
    mask_visits: int  # full-list frontier-mask gathers, m2 per level
    m2: int  # oriented edge count (dense relaxes this per round)
    num_sources: int
    dense_levels: int = 0  # levels that relaxed the whole list uncompacted
    levels: list = field(default_factory=list)  # (bucket, live) per level

    def publish(self, registry=None, prefix: str = "sssp.frontier") -> None:
        """Publish into the metrics registry (``repro.obs.metrics``)."""
        from repro.obs.metrics import publish_stats

        publish_stats(self, prefix, registry)


def _prep_edges(src, dst, weights):
    """Both-orientation edge arrays (a, b, w2): the repo's undirected
    2m walk. ``weights=None`` means unit weights (BFS). Host-side
    inputs are validated (NaN / negative weights rejected; +inf is a
    legal "non-edge", the serve path's pad convention)."""
    if weights is not None and isinstance(
        weights, (np.ndarray, list, tuple)
    ):
        wh = np.asarray(weights, np.float32).ravel()
        if np.isnan(wh).any():
            raise ValueError("weights contain NaN")
        if (wh < 0).any():
            raise ValueError(
                "negative weights are unsupported: edges relax in both "
                "orientations (undirected), so a negative edge is a "
                "negative cycle"
            )
    src = jnp.asarray(src, jnp.int32).ravel()
    dst = jnp.asarray(dst, jnp.int32).ravel()
    if weights is None:
        w = jnp.ones(src.shape, jnp.float32)
    else:
        w = jnp.asarray(weights, jnp.float32).ravel()
    if w.shape != src.shape:
        raise ValueError(
            f"weights length {w.shape[0]} != edge count {src.shape[0]}"
        )
    a = jnp.concatenate([src, dst])
    b = jnp.concatenate([dst, src])
    w2 = jnp.concatenate([w, w])
    return a, b, w2


def _prep_sources(sources, n: int):
    """Normalized (sources int32 array, scalar?) pair. Scalar callers
    get (n,)-shaped results back; array callers the (S, n) batch."""
    scalar = np.ndim(sources) == 0
    srcs = np.atleast_1d(np.asarray(sources, np.int32))
    if srcs.size < 1:
        raise ValueError("need at least one source")
    if srcs.min() < 0 or srcs.max() >= n:
        raise ValueError(
            f"sources outside [0, {n}): {srcs[(srcs < 0) | (srcs >= n)]}"
        )
    return srcs, scalar


@partial(jax.jit, static_argnames=("n",))
def _init_dist(srcs, *, n):
    S = srcs.shape[0]
    dist = jnp.full((S, n), jnp.inf, jnp.float32)
    return dist.at[jnp.arange(S), srcs].set(0.0)


@partial(jax.jit, static_argnames=("bound",))
def _bf_dense(a, b, w, dist0, *, bound):
    """All-edges-every-round Bellman-Ford in one ``lax.while_loop``.
    Returns (dist, rounds, converged); ``converged`` is the fixpoint
    sentinel host callers turn into ``ConvergenceError``."""

    def cond(carry):
        _dist, s, changed = carry
        return jnp.logical_and(changed, s <= bound)

    def body(carry):
        dist, s, _changed = carry
        new = advance(dist, b, dist[:, a] + w, monoid=MIN)
        return new, s + 1, jnp.any(new < dist)

    dist, s, changed = jax.lax.while_loop(
        cond, body, (dist0, jnp.int32(1), jnp.bool_(True))
    )
    return dist, s - 1, jnp.logical_not(changed)


@jax.jit
def _min_parents(a, b, w, dist, srcs):
    """Deterministic parent recovery (one full-edge pass, after the
    distance fixpoint): ``parent[v] = min{u : dist[u] + w(u,v) ==
    dist[v], u != v}`` via min-CRCW scatter; sources point at
    themselves, unreachable nodes at ``UNREACHABLE``. At the fixpoint
    every reachable non-source node has at least one optimal incoming
    edge (float add is monotonic), so the min is never vacuous."""
    S, n = dist.shape
    opt = (dist[:, a] + w == dist[:, b]) & (a != b)[None, :]
    cand = jnp.where(opt, a[None, :], n)
    parent = advance(
        jnp.full((S, n), n, jnp.int32), b, cand, monoid=MIN
    )
    parent = jnp.where(parent < n, parent, UNREACHABLE)
    parent = jnp.where(jnp.isinf(dist), UNREACHABLE, parent)
    return parent.at[jnp.arange(S), srcs].set(srcs)


@jax.jit
def _edge_frontier(a, changed_nodes):
    """Edge slots whose (oriented) source node improved last round --
    the union over source rows, so one mask serves the whole batch."""
    return changed_nodes[a]


@jax.jit
def _relax_level(ca, cb, cw, dist):
    """One relax round over a compacted edge buffer (a MIN-monoid
    advance of the per-edge candidates). Returns the new distance
    matrix and the (n,) any-row node-improved mask that seeds the next
    level's frontier."""
    new = advance(dist, cb, dist[:, ca] + cw, monoid=MIN)
    return new, jnp.any(new < dist, axis=0)


def bellman_ford(
    src: Array,
    dst: Array,
    weights: Array | None,
    num_nodes: int,
    *,
    sources=0,
    max_rounds: int | None = None,
    with_stats: bool = False,
):
    """Dense Bellman-Ford: relax all 2m oriented edges per round until
    the distance fixpoint. Returns ``(dist, parent, rounds)`` --
    ``dist`` float32 with ``+inf`` for unreachable nodes, ``parent``
    int32 per ``_min_parents`` -- shaped ``(n,)`` for a scalar source,
    ``(S, n)`` for an array of sources (one batched compile; rows are
    bit-exact vs solo runs). ``with_stats`` appends ``SsspStats``.

    Hitting ``max_rounds`` before the fixpoint raises
    ``ConvergenceError`` (host calls; a jit trace keeps the documented
    return-at-bound -- a device value cannot raise). The default bound
    ``sssp_round_bound(n)`` always suffices.
    """
    from repro.compat import is_tracer

    n = num_nodes
    with trace.span("sssp.prep"):
        a, b, w2 = _prep_edges(src, dst, weights)
        srcs, scalar = _prep_sources(sources, n)
    m2 = int(a.shape[0])
    bound = max_rounds if max_rounds is not None else sssp_round_bound(n)
    # Whole-run device span; blocks at close on the same terminal sync
    # the convergence-sentinel read below already pays.
    with trace.span(
        "sssp.dense", device=True, n=n, m2=m2, sources=int(srcs.shape[0]),
        bound=bound,
    ) as sp:
        dist0 = _init_dist(jnp.asarray(srcs), n=n)
        dist, rounds, converged = _bf_dense(a, b, w2, dist0, bound=bound)
        parent = _min_parents(a, b, w2, dist, jnp.asarray(srcs))
        if not is_tracer(converged):
            sp.block_on(dist)
    if not is_tracer(converged):
        # Intentional terminal sync: the sentinel must be read before
        # wrong distances can escape (core.components.ConvergenceError).
        trace.count("host_sync")
        if not bool(converged):  # repro-lint: disable=host-sync
            raise ConvergenceError(
                f"bellman_ford hit max_rounds={bound} before the "
                f"distance fixpoint on {n} nodes; raise max_rounds (the "
                f"safe bound is sssp_round_bound(n)={sssp_round_bound(n)})"
            )
    if scalar:
        dist, parent = dist[0], parent[0]
    if with_stats:
        # Terminal readback only when stats are asked for.
        trace.count("host_sync")
        r = int(rounds)  # repro-lint: disable=host-sync
        stats = SsspStats(
            rounds=r, relax_visits=m2 * r, mask_visits=0, m2=m2,
            num_sources=int(srcs.shape[0]),
        )
        return (dist, parent, rounds, stats)
    return (dist, parent, rounds)


def frontier_bellman_ford(
    src: Array,
    dst: Array,
    weights: Array | None,
    num_nodes: int,
    *,
    sources=0,
    max_rounds: int | None = None,
    min_bucket: int = 1024,
    with_stats: bool = False,
):
    """Level-synchronous frontier Bellman-Ford: each level relaxes only
    the edges out of nodes whose distance improved last round, gathered
    into a ``next_pow2`` size bucket (shape-static compiles, the CC
    frontier engine's ladder). Distances and parents are bit-exact vs
    ``bellman_ford`` (see module docstring); return convention and the
    ``ConvergenceError`` sentinel match it too. The level loop is
    host-driven (one live-count sync per level -- the paper's
    level-synchronous design), so it cannot run inside ``jax.jit``.
    """
    n = num_nodes
    with trace.span("sssp.prep"):
        a, b, w2 = _prep_edges(src, dst, weights)
        srcs, scalar = _prep_sources(sources, n)
    m2 = int(a.shape[0])
    S = int(srcs.shape[0])
    bound = max_rounds if max_rounds is not None else sssp_round_bound(n)
    dist = _init_dist(jnp.asarray(srcs), n=n)
    # Level 0 frontier: the source rows' one-hot improvement mask.
    changed_nodes = (
        jnp.zeros((n,), bool).at[jnp.asarray(srcs)].set(True)
    )
    stats = SsspStats(
        rounds=0, relax_visits=0, mask_visits=0, m2=m2, num_sources=S
    )
    fmask = None
    # Spans attach at the per-level syncs the bucket ladder already
    # pays (the int() live-count reads), so tracing adds zero extra
    # device round-trips -- same policy as cc.frontier. The loop shape
    # is operators.run_rebuild_loop: unlike CC's permanent compaction,
    # every level re-masks the FULL edge list (a settled edge wakes up
    # when its source's distance later drops -- module docstring).
    with trace.span("sssp.frontier", n=n, m2=m2, sources=S) as run_sp:

        def live_edges():
            nonlocal fmask
            if m2 == 0:
                return 0
            fmask = _edge_frontier(a, changed_nodes)
            stats.mask_visits += m2
            trace.count("sssp.mask_edges", m2)
            # The level-synchronous sync: the host reads the live count
            # to pick the next power-of-two bucket.
            trace.count("host_sync")
            return int(jnp.sum(fmask.astype(jnp.int32)))  # repro-lint: disable=host-sync

        def relax(live):
            nonlocal dist, changed_nodes
            size = bucket_size(live, min_bucket=min_bucket, cap=m2)
            # A bucket as long as the list holds every arc anyway, so
            # the level relaxes the list itself: quiet arcs are inert
            # (module docstring), and the relax program is the same
            # shape the compacted buffer would have had.
            dense = size == m2
            with trace.span("sssp.level", bucket=size, live=live, dense=dense):
                arcs = (a, b, w2) if dense else compact_weighted(
                    a, b, w2, fmask, size=size)
                dist, changed_nodes = _relax_level(*arcs, dist)
            if dense:
                stats.dense_levels += 1
                trace.count("sssp.dense_levels")
            stats.relax_visits += size
            trace.count("sssp.relax_slots", size)
            stats.levels.append((size, live))

        def bound_hit(live, _rounds):
            # Frontier still live at the round bound: distances would
            # be wrong, so fail loudly (the convergence sentinel; see
            # core.components.ConvergenceError).
            raise ConvergenceError(
                f"frontier_bellman_ford hit its round bound "
                f"({bound}) with {live} frontier edges still live "
                f"on {n} nodes; raise max_rounds (the safe bound "
                f"is sssp_round_bound(n)={sssp_round_bound(n)})"
            )

        rounds = run_rebuild_loop(
            bound=bound, live_count=live_edges, run_level=relax,
            on_bound=bound_hit,
        )
        run_sp.tag(rounds=rounds, levels=len(stats.levels))
    stats.rounds = rounds
    # The post-pass over every arc; the span blocks on it only while
    # tracing is on (the last live-count read already synced the loop).
    with trace.span("sssp.parents", device=True) as par_sp:
        parent = par_sp.block_on(
            _min_parents(a, b, w2, dist, jnp.asarray(srcs))
        )
    if scalar:
        dist, parent = dist[0], parent[0]
    out = (dist, parent, jnp.int32(rounds))
    if with_stats:
        out = out + (stats,)
    return out


def shortest_paths(
    src,
    dst,
    weights=None,
    num_nodes: int | None = None,
    *,
    sources=0,
    max_rounds: int | None = None,
    engine: str = "auto",
    **kwargs,
):
    """Single/multi-source shortest paths with engine dispatch -- the
    ``connected_components`` convention for the SSSP workload. Returns
    ``(dist, parent, rounds)``: float32 distances (``+inf`` =
    unreachable), deterministic min-id parent tree (``parent[source] =
    source``, unreachable ``-1``), and the relax-round count. A scalar
    ``sources`` gives ``(n,)`` arrays, an array ``(S, n)`` -- all S
    sources share one padded compile and are bit-exact vs solo runs.
    ``weights=None`` means unit weights: BFS.

    ``engine=`` -- ``"auto"`` (default), ``"frontier"``, ``"dense"``
    (full matrix: ``docs/engines.md``, knob ``sssp_engine``):

    * ``"auto"``: the frontier engine, except under a ``jax.jit``
      trace, where the host-driven level loop is impossible and the
      fully-traceable dense walk runs instead.
    * ``"frontier"``: pin the level-synchronous frontier engine
      (``min_bucket=`` sizes its smallest bucket; rejects tracing).
    * ``"dense"``: the all-edges-every-round walk (the serve path's
      engine -- one compile per shape bucket).

    Both engines raise ``ConvergenceError`` when ``max_rounds`` cuts
    the relax loop before the distance fixpoint (host calls), and both
    support ``with_stats=True`` (``SsspStats`` relax/mask visit
    counters).
    """
    from repro.compat import is_tracer

    if num_nodes is None:
        raise TypeError("shortest_paths requires num_nodes")
    check_choice("sssp_engine", engine, SSSP_ENGINES)
    tracing = is_tracer(src) or is_tracer(dst) or is_tracer(weights)
    if engine == "auto":
        engine = "dense" if tracing else "frontier"
    if engine == "frontier" and tracing:
        raise ValueError(
            "the frontier SSSP engine's level loop is host-driven "
            "and cannot run inside jit; call it outside jit or use "
            "engine='dense'"
        )
    if engine == "dense" and "min_bucket" in kwargs:
        raise ValueError(
            "min_bucket= is a frontier-engine option; use "
            "engine='frontier' (or 'auto')"
        )
    run = frontier_bellman_ford if engine == "frontier" else bellman_ford
    # The root span of one library call: the prep, the engine's spans,
    # the parents pass and the host syncs of the call sit beneath it.
    with trace.span("sssp.call", n=num_nodes) as sp:
        if trace.enabled():
            sp.tag(engine=engine, m2=2 * int(np.size(src)),
                   sources=int(np.size(sources)))
        return run(
            src, dst, weights, num_nodes, sources=sources,
            max_rounds=max_rounds, **kwargs,
        )
