"""Tree computations as +-1-weighted ranks over the Euler tour.

The heavy lifting -- ordering the tour arcs -- is a LIST RANKING call,
dispatched through the exact engines ``list_rank`` uses (`wylie_rank`,
``random_splitter_rank``, or the sharded splitter engine, with the same
``kernel_impl=`` Pallas plumbing). Every tree quantity then falls out
of dense prefix sums over the ranked order, which is the Euler-tour
technique verbatim:

* an arc is **forward** (discovers its destination) iff it precedes its
  twin in the tour;
* ``parent[v]`` = source of the forward arc into v (``root_tree``);
* ``depth[v]`` = prefix sum of +1 (forward) / -1 (backward) weights at
  that arc;
* ``subtree_size[v]`` = half the (inclusive) span between the forward
  arc and its twin;
* ``preorder``/``postorder`` = prefix counts of forward/backward arcs.

All quantities are exact int32, so they are bit-identical across rank
engines. Forests batch for free: the tour of every tree ranks in ONE
multi-list call, per-tree prefix sums are isolated by construction
(each complete tour's +-1 weights sum to zero), and padded capacity
slots are inert self-loops -- the serving path for many concurrent
small-graph requests at one compiled shape.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.components import check_choice
from repro.core.operators import next_pow2
from repro.core.list_ranking import (
    KERNEL_IMPLS,
    WYLIE_PACK_MODES,
    max_splitters_for_linear_work,
    random_splitter_rank,
    select_splitters,
    wylie_rank,
)
from repro.obs import trace
from repro.trees.forest import SpanningForest, spanning_forest
from repro.trees.tour import EulerTour, euler_tour, tour_capacity

Array = jax.Array

RANK_ENGINES = ("auto", "wylie", "splitter")


def tour_splitters(
    tour: EulerTour, num_splitters: int | None = None, seed: int = 0
) -> np.ndarray:
    """Splitters for ranking a (multi-list) tour: every tour head plus
    random extras. Heads MUST be splitters -- a sub-list walk only
    covers arcs downstream of some splitter, and a list head has no
    upstream -- which is the one extra rule the forest case adds over
    ``select_splitters``'s single-list convention.

    The returned set is capacity-padded to the next power of two (with
    distinct, deterministically-chosen extra arc ids): the splitter
    COUNT is a compiled dimension of ``_random_splitter_core``, and
    the head count of a served forest varies per wave -- without the
    pad every distinct tour-head count costs one recompile per bucket
    (pinned by ``benchmarks/graph_serve.py``'s splitter lane). Extra
    splitters only refine the sub-list decomposition; ranks are exact
    integers either way. The pad ids must be DISTINCT from the
    existing set: a duplicate splitter would hand one arc two lane
    ids, making the lane scatter order-dependent."""
    L = tour.capacity
    if tour.num_arcs:
        # mask, don't slice: padded-edge-buffer tours interleave dead
        # self-loop arcs with the real ones (see ``euler_tour``)
        trace.count("host_sync", 2)
        heads = np.unique(
            np.asarray(tour.head_of_arc, dtype=np.int64)[
                np.asarray(tour.valid)
            ]
        )
    else:
        heads = np.zeros((0,), np.int64)
    p = num_splitters or min(4096, max_splitters_for_linear_work(max(L, 2)))
    p = min(max(p, 1), L)
    head0 = int(heads[0]) if len(heads) else 0
    extras = select_splitters(L, p, seed=seed, head=head0)
    spl = np.unique(np.concatenate([heads, extras.astype(np.int64)]))
    target = min(L, next_pow2(len(spl)))
    if target > len(spl):
        pool = np.setdiff1d(np.arange(L, dtype=np.int64), spl)
        spl = np.sort(np.concatenate([spl, pool[: target - len(spl)]]))
    return spl


def tour_ranks(
    tour: EulerTour,
    *,
    rank_engine: str = "auto",
    num_splitters: int | None = None,
    kernel_impl: str = "auto",
    pack_mode: str = "aos",
    seed: int = 0,
    mesh=None,
) -> Array:
    """Rank the tour's arcs: rank[j] = arcs from j to its tour's end.

    ``rank_engine="wylie"`` runs pointer jumping, ``"splitter"`` the
    random-splitter engine (single-device, or the sharded engine when a
    mesh is given / several devices are visible -- the same dispatch
    convention as ``repro.core.list_rank``, including ``kernel_impl``
    routing the RS4/RS5 phases through the Pallas kernels). ``"auto"``
    picks wylie on one device and the sharded splitter engine
    otherwise. Ranks are exact integers: every route is bit-identical.

    Every dispatch string is validated up front -- including knobs the
    chosen branch then ignores (wylie has no kernels) -- so a typo
    never silently measures the wrong engine.
    """
    check_choice("rank_engine", rank_engine, RANK_ENGINES)
    check_choice("kernel_impl", kernel_impl, KERNEL_IMPLS)
    check_choice("pack_mode", pack_mode, WYLIE_PACK_MODES)
    multi = mesh is not None or jax.device_count() > 1
    if rank_engine == "auto":
        rank_engine = "splitter" if multi else "wylie"
    if rank_engine == "wylie":
        if mesh is not None:
            raise ValueError(
                "wylie_rank is single-device; drop mesh= or use "
                "rank_engine='splitter'"
            )
        return wylie_rank(tour.succ, pack_mode=pack_mode)
    splitters = tour_splitters(tour, num_splitters=num_splitters, seed=seed)
    if multi:
        from repro.distributed.graph import sharded_random_splitter_rank

        return sharded_random_splitter_rank(
            tour.succ, splitters=splitters, mesh=mesh,
            kernel_impl=kernel_impl,
        )
    return random_splitter_rank(
        tour.succ, splitters=splitters, kernel_impl=kernel_impl
    )


@partial(jax.jit, static_argnames=("n",))
def _analytics(ranks, arc_src, arc_dst, twin, head_of_arc, valid, root_of,
               *, n):
    """All tree quantities from the arc ranks, in dense prefix ops.

    Everything is sized by the (static) capacity L, never by the traced
    real-arc count, so variable-size forests served at one ``pad_to``
    capacity share ONE compiled program: order-buffer slots past the
    real arcs hold garbage, but every read position (``gpos`` of a real
    arc) lies below them, and a cumsum prefix is unaffected by entries
    above it."""
    L = ranks.shape[0]
    ids = jnp.arange(L, dtype=jnp.int32)
    ranks = ranks.astype(jnp.int32)
    # Position within the arc's own tour (0-based; 0 on padded slots
    # because their head is themselves).
    pos = ranks[head_of_arc] - ranks

    # Per-tree tour length and the exclusive base offset of each tree in
    # the concatenated (root-id-ordered) global order.
    tree_of_arc = root_of[arc_src]
    tree_len = jnp.zeros((n,), jnp.int32).at[
        jnp.where(valid, tree_of_arc, n)
    ].max(pos + 1, mode="drop")
    base = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(tree_len)[:-1].astype(jnp.int32)]
    )
    gpos = base[tree_of_arc] + pos  # bijection: valid arcs -> [0, num_arcs)

    fwd = pos < pos[twin]  # forward = discovers its destination

    # The arc occupying each global tour slot, then the three prefix
    # families: +-1 depth weights, forward counts, backward counts.
    # Cross-tree isolation is automatic for depth (each complete tour
    # sums to 0); pre/post subtract their tree-start prefix.
    order = jnp.zeros((L,), jnp.int32).at[
        jnp.where(valid, gpos, L)
    ].set(ids, mode="drop")
    w_fwd = fwd[order].astype(jnp.int32)
    C = jnp.cumsum(2 * w_fwd - 1)
    F = jnp.cumsum(w_fwd)
    B = jnp.cumsum(1 - w_fwd)
    F_start = jnp.where(base > 0, F[jnp.maximum(base - 1, 0)], 0)
    B_start = jnp.where(base > 0, B[jnp.maximum(base - 1, 0)], 0)

    # The unique forward arc into each non-root node, and its twin out.
    in_arc = jnp.full((n,), -1, jnp.int32).at[
        jnp.where(fwd & valid, arc_dst, n)
    ].set(ids, mode="drop")
    has = in_arc >= 0
    ia = jnp.maximum(in_arc, 0)
    oa = twin[ia]
    nodes = jnp.arange(n, dtype=jnp.int32)

    parent = jnp.where(has, arc_src[ia], nodes)
    depth = jnp.where(has, C[gpos[ia]], 0)
    size_sub = jnp.where(
        has, (pos[oa] - pos[ia] + 1) // 2, tree_len[nodes] // 2 + 1
    )
    pre = jnp.where(has, F[gpos[ia]] - F_start[root_of], 0)
    post = jnp.where(
        has, B[gpos[oa]] - B_start[root_of] - 1, tree_len[nodes] // 2
    )
    return parent, depth, size_sub, pre, post


@dataclass
class TreeComputations:
    """Per-node tree quantities over a (forest) Euler tour; roots have
    ``parent[r] == r``, ``depth 0``, ``preorder 0``, and per-tree
    ``postorder == tree_size - 1``; isolated nodes are size-1 roots."""

    parent: Array  # (n,) int32
    depth: Array  # (n,) int32
    subtree_size: Array  # (n,) int32
    preorder: Array  # (n,) int32 per-tree DFS discovery index
    postorder: Array  # (n,) int32 per-tree DFS finish index
    ranks: Array  # (L,) the tour ranks everything derives from


def tree_computations(
    tour: EulerTour, *, ranks: Array | None = None, **rank_kwargs
) -> TreeComputations:
    """Run the whole tree-computation family over one ranked tour.

    ``ranks`` reuses an existing ``tour_ranks`` result; otherwise one is
    computed with ``rank_kwargs`` (``rank_engine=``, ``kernel_impl=``,
    ``mesh=``, ...).
    """
    n = tour.num_nodes
    if tour.capacity == 0 or tour.num_arcs == 0:
        # validate dispatch strings even on the trivial path
        check_choice(
            "rank_engine", rank_kwargs.get("rank_engine", "auto"),
            RANK_ENGINES,
        )
        check_choice(
            "kernel_impl", rank_kwargs.get("kernel_impl", "auto"),
            KERNEL_IMPLS,
        )
        ids = jnp.arange(n, dtype=jnp.int32)
        zeros = jnp.zeros((n,), jnp.int32)
        return TreeComputations(
            parent=ids, depth=zeros, subtree_size=zeros + 1,
            preorder=zeros, postorder=zeros,
            ranks=jnp.zeros((tour.capacity,), jnp.int32),
        )
    if ranks is None:
        ranks = tour_ranks(tour, **rank_kwargs)
    parent, depth, size_sub, pre, post = _analytics(
        ranks, tour.arc_src, tour.arc_dst, tour.twin, tour.head_of_arc,
        tour.valid, tour.root_of, n=n,
    )
    return TreeComputations(
        parent=parent, depth=depth, subtree_size=size_sub,
        preorder=pre, postorder=post, ranks=ranks,
    )


def root_tree(tour: EulerTour, **kwargs) -> Array:
    """Parent array of the rooted forest (roots point at themselves)."""
    return tree_computations(tour, **kwargs).parent


def depths(tour: EulerTour, **kwargs) -> Array:
    return tree_computations(tour, **kwargs).depth


def subtree_sizes(tour: EulerTour, **kwargs) -> Array:
    return tree_computations(tour, **kwargs).subtree_size


def preorder(tour: EulerTour, **kwargs) -> Array:
    return tree_computations(tour, **kwargs).preorder


def postorder(tour: EulerTour, **kwargs) -> Array:
    return tree_computations(tour, **kwargs).postorder


@dataclass
class TreeAnalytics:
    """End-to-end result: forest -> tour -> computations."""

    forest: SpanningForest
    tour: EulerTour
    computations: TreeComputations

    @property
    def parent(self) -> Array:
        return self.computations.parent

    @property
    def depth(self) -> Array:
        return self.computations.depth

    @property
    def subtree_size(self) -> Array:
        return self.computations.subtree_size


def tree_analytics(
    src,
    dst,
    num_nodes: int,
    *,
    engine: str = "auto",
    rank_engine: str = "auto",
    kernel_impl: str = "auto",
    num_splitters: int | None = None,
    pad_to: int | None = None,
    pad_edges_to: int | None = None,
    mesh=None,
    seed: int = 0,
    **cc_kwargs,
) -> TreeAnalytics:
    """One-shot pipeline on an arbitrary graph: CC + spanning forest,
    Euler tour, and the batched tree computations. Keywords (full
    matrix in ``docs/engines.md``):

    * ``engine=`` -- ``"auto"`` (default), ``"frontier"``, ``"dense"``,
      ``"sharded_frontier"``: the CC engine extracting the forest (as
      in ``connected_components``); ``**cc_kwargs`` forward to it.
    * ``rank_engine=`` -- ``"auto"`` (default), ``"wylie"``,
      ``"splitter"``: the list-ranking engine over the tour ("auto"
      picks wylie on one device, the sharded splitter engine when a
      mesh is given or several devices are visible).
    * ``kernel_impl=`` -- ``"auto"`` (default), ``"xla"``, ``"pallas"``,
      ``"pallas_interpret"``: Pallas routing for the splitter engine's
      RS4/RS5 phases (ignored by wylie, validated regardless).
    * ``num_splitters=`` (int, default: linear-work bound), ``seed=``
      (int, default 0) -- splitter selection.
    * ``pad_to=`` (int, default None) -- fixes the tour capacity so many
      variable-size requests compile once (see ``tour_capacity``); a
      forest of many small graphs (e.g. ``data/graphs.molecule_batch``)
      is one batched call.
    * ``pad_edges_to=`` (int, default None) -- pads the extracted
      forest-edge buffer to a fixed capacity before touring, so the
      tour/compute stages compile per CAPACITY instead of per live
      forest-edge count (the data-dependent quantity); this is what
      lets ``repro.serve.graph`` run every wave of a capacity bucket
      through one compiled program. Implies a tour capacity of
      ``2 * pad_edges_to`` unless ``pad_to`` raises it.
    * ``mesh=`` -- threads to BOTH the CC engine and the ranking engine
      (the all-sharded path end to end).

    All quantities are exact int32: results are bit-identical across
    every engine combination.
    """
    with trace.span("trees.forest"):
        forest = spanning_forest(
            src, dst, num_nodes, engine=engine, mesh=mesh, **cc_kwargs
        )
    edge_u, edge_v, num_edges = forest.edge_u, forest.edge_v, None
    if pad_edges_to is not None:
        f = forest.num_edges
        if f > pad_edges_to:
            raise ValueError(
                f"pad_edges_to={pad_edges_to} below the {f} forest edges"
            )
        num_edges = f
        edge_u = np.zeros((pad_edges_to,), np.int32)
        edge_v = np.zeros((pad_edges_to,), np.int32)
        edge_u[:f] = forest.edge_u
        edge_v[:f] = forest.edge_v
    with trace.span("trees.tour"):
        tour = euler_tour(
            edge_u, edge_v, num_nodes,
            labels=forest.labels, pad_to=pad_to, num_edges=num_edges,
        )
    rank_kwargs = dict(rank_engine=rank_engine, kernel_impl=kernel_impl,
                       num_splitters=num_splitters, seed=seed, mesh=mesh)
    ranks = None
    if tour.capacity and tour.num_arcs:  # else tree_computations' trivial path
        with trace.span("trees.rank"):
            ranks = tour_ranks(tour, **rank_kwargs)
    with trace.span("trees.compute"):
        comp = tree_computations(tour, ranks=ranks, **rank_kwargs)
    return TreeAnalytics(forest=forest, tour=tour, computations=comp)
