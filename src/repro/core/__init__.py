"""The paper's contribution: PRAM graph algorithms adapted for TPU."""
from repro.core.list_ranking import (
    wylie_rank,
    random_splitter_rank,
    select_splitters,
    even_splitters,
    max_splitters_for_linear_work,
    SplitterStats,
)
from repro.core.components import (
    shiloach_vishkin,
    label_propagation,
    sv_round_bound,
    num_components,
    dedup_edges,
    check_choice,
    ConvergenceError,
)
from repro.core.frontier import frontier_shiloach_vishkin, FrontierStats
from repro.core.sssp import (
    SSSP_ENGINES,
    SsspStats,
    bellman_ford,
    frontier_bellman_ford,
    shortest_paths,
    sssp_round_bound,
)
from repro.core.pagerank import (
    PAGERANK_ENGINES,
    PageRankStats,
    pagerank,
    pagerank_iter_bound,
)
from repro.core.pram import (
    striding_indices,
    partitioning_indices,
    strided_view,
    partitioned_view,
    lockstep_walk,
)
from repro.obs import trace


# Engine-specific tuning knobs: naming one pins the dispatch to that
# engine (regardless of device count), so the same call behaves
# identically on any machine -- the list_rank pack_mode convention.
# The sampling pre-pass (sample_rounds/seed) exists only on the
# single-device frontier engine; min_bucket and hook_impl are honoured
# by BOTH frontier engines (single-device and sharded), so with a mesh
# they steer toward engine="sharded_frontier" instead of raising.
_SAMPLING_KW = frozenset({"sample_rounds", "seed"})
_FRONTIER_KW = _SAMPLING_KW | {"min_bucket"}
_SINGLE_KW = _FRONTIER_KW | {"hook_impl"}
_SHARDED_KW = frozenset({"exchange", "sparse_capacity", "axis"})
_CC_ENGINES = ("auto", "frontier", "dense", "sharded_frontier")

# Sampling policy (ROADMAP decision, PR 3): when the auto dispatch
# lands on the frontier engine and the graph is edge-heavy -- at least
# AUTO_SAMPLE_DENSITY input edges per node -- the Afforest-style
# pre-pass is enabled automatically with AUTO_SAMPLE_ROUNDS rounds: on
# dense graphs the giant component(s) resolve at O(n)/round and the
# first compaction drops most of the edge walk, while the labels remain
# a correct partition (representatives may differ from the dense
# engine's -- the reason the pre-pass stays off for sparse graphs and
# for explicit ``engine=``). Pass ``sample_rounds=0`` (or any explicit
# value) to override, or ``engine="frontier"``/``"dense"`` to pin the
# exact dense-engine representatives.
AUTO_SAMPLE_DENSITY = 8.0
AUTO_SAMPLE_ROUNDS = 2


def _auto_sample_rounds(src, num_nodes):
    """Afforest pre-pass rounds for the auto dispatch: 0 unless the
    input is host-visible and edge-heavy (m/n >= AUTO_SAMPLE_DENSITY)."""
    shape = getattr(src, "shape", None)
    if shape is not None:
        m = shape[0] if len(shape) else 0
    else:
        m = len(src) if hasattr(src, "__len__") else 0
    if num_nodes > 0 and m / num_nodes >= AUTO_SAMPLE_DENSITY:
        return AUTO_SAMPLE_ROUNDS
    return 0


def connected_components(
    src, dst, num_nodes, *, max_rounds=None, mesh=None, engine="auto", **kwargs
):
    """Connected components with automatic engine dispatch.

    Returns ``(labels, rounds)`` -- identical on every path --
    ``labels[i]`` being the component root id. The full engine matrix
    (valid values, defaults, auto rules, exactness guarantees) lives in
    ``docs/engines.md``; summary:

    ``engine=`` -- one of ``"auto"`` (default), ``"frontier"``,
    ``"dense"``, ``"sharded_frontier"``:

    * ``"auto"``: an explicit ``mesh=`` picks the **sharded frontier**
      engine (each device compacts its own edge shard between rounds);
      otherwise one visible device runs the single-device
      frontier-compacted engine (``repro.core.frontier``) and several
      visible devices the edge-partitioned sharded engine
      (``repro.distributed.graph``). The two frontier engines' level
      loops are host-driven, so inside a ``jax.jit`` trace auto falls
      back to the fully-traceable dense walks.
    * ``"frontier"``: pin the single-device frontier engine (rejects
      ``mesh=``).
    * ``"dense"``: the all-edges-every-round escape hatch (single
      device: ``sv_run``; with a mesh or several devices: the sharded
      engine, which IS the dense walk).
    * ``"sharded_frontier"``: pin the per-shard frontier engine
      (``mesh=`` optional -- defaults to all visible devices).

    Engine kwargs (each steers the auto dispatch toward an engine that
    honours it; every string is validated against the sets in
    ``docs/engines.md``):

    * ``sample_rounds=`` (int, default 0) / ``seed=`` (int, default 0)
      -- the Afforest-style sampling pre-pass; single-device frontier
      engine only.
    * ``min_bucket=`` (int, default 1024) -- smallest frontier bucket;
      both frontier engines (per-device in the sharded one).
    * ``hook_impl=`` -- ``"xla"`` (default), ``"auto"``, ``"pallas"``,
      ``"pallas_interpret"``: the SV2/SV3 hook-phase implementation
      (``kernels/edge_hook``; ``"auto"`` takes the XLA phases, since
      the chip's compiler refuses the kernel); dense, frontier, and
      sharded-frontier engines (shard-local in the latter).
    * ``exchange=`` -- ``"dense"`` or ``"sparse"``: the cross-device
      label exchange; sharded engines only. Defaults: ``"dense"`` on
      the dense sharded engine, ``"sparse"`` on the sharded frontier
      engine. ``sparse_capacity=`` (int, default: frontier-sized with
      an ``n/8`` cap) bounds the per-device (index, label) buffer.
    * ``axis=`` (str, default ``"graph"``) -- mesh axis name carrying
      the edge partition; sharded engines only.
    * ``dedup=`` (bool, default True), ``record_hooks=`` (bool, default
      False), ``with_stats=`` (bool, default False) -- every engine;
      ``record_hooks`` appends the spanning-forest hook record (see
      ``repro.trees``) without changing labels or rounds.

    On the auto path, edge-heavy graphs (>= ``AUTO_SAMPLE_DENSITY``
    input edges per node) reaching the single-device frontier engine
    enable the sampling pre-pass automatically (``AUTO_SAMPLE_ROUNDS``
    rounds): labels stay a correct partition but representatives may
    differ from the dense engine's; pass ``sample_rounds=`` explicitly
    (0 disables) or pin ``engine=`` to opt out. Every other
    engine/kwarg combination is bit-exact in labels, round counts, and
    recorded hook forests against every other.
    """
    # The root span of one library call: every engine span, the dedup,
    # the uploads and the host syncs of the call sit beneath it.
    with trace.span("cc.call", n=num_nodes) as sp:
        if trace.enabled():
            sp.tag(m=len(src))
        return _dispatch_cc(src, dst, num_nodes, max_rounds, mesh, engine,
                            kwargs, sp)


def _dispatch_cc(src, dst, num_nodes, max_rounds, mesh, engine, kwargs, sp):
    """``connected_components``' engine choice and call, under its
    ``cc.call`` span ``sp``."""
    import jax

    from repro.compat import is_tracer

    check_choice("engine", engine, _CC_ENGINES)
    single_kw = _SINGLE_KW & kwargs.keys()
    sharded_kw = _SHARDED_KW & kwargs.keys()
    sampling_kw = _SAMPLING_KW & kwargs.keys()
    tracing = is_tracer(src) or is_tracer(dst)
    if sampling_kw and (
        sharded_kw or mesh is not None or engine == "sharded_frontier"
    ):
        trigger = (
            sorted(sharded_kw) if sharded_kw
            else "mesh=" if mesh is not None
            else "engine='sharded_frontier'"
        )
        raise ValueError(
            f"{sorted(sampling_kw)} are single-device frontier options "
            "(the sampling pre-pass has no sharded counterpart); drop "
            f"them or drop {trigger}"
        )
    if engine == "auto":
        if mesh is not None:
            # The sharded-frontier auto rule: an explicit mesh gets the
            # composed per-shard frontier engine. Its level loop is
            # host-driven, so a jit trace falls back to the traceable
            # dense sharded walk (which rejects the frontier knobs).
            engine = "_sharded" if tracing else "sharded_frontier"
        elif _FRONTIER_KW & kwargs.keys() and not sharded_kw:
            engine = "frontier"
        elif single_kw and not sharded_kw:
            # hook_impl alone: dense sv_run honours it too and is fully
            # traceable, so a jit trace falls back there
            engine = "dense" if tracing else "frontier"
        elif sharded_kw:
            # bucket/hook knobs + exchange knobs only meet in the
            # composed engine (default mesh over all visible devices)
            engine = (
                "sharded_frontier" if (single_kw and not tracing)
                else "_sharded"
            )
        elif jax.device_count() > 1:
            engine = "_sharded"
        else:
            engine = "dense" if tracing else "frontier"
        if engine == "frontier" and "sample_rounds" not in kwargs:
            auto_k = _auto_sample_rounds(src, num_nodes)
            if auto_k:
                kwargs["sample_rounds"] = auto_k
    sp.tag(engine=engine)
    if engine == "frontier":
        if sharded_kw:
            raise ValueError(
                f"{sorted(sharded_kw)} are sharded-engine options; drop "
                "them or use engine='auto'/'sharded_frontier'"
            )
        if mesh is not None:
            raise ValueError(
                "the frontier engine is single-device; drop mesh= or use "
                "engine='auto'/'sharded_frontier'"
            )
        if tracing:
            raise ValueError(
                "the frontier engine's shrink loop is host-driven and "
                "cannot run inside jit; call it outside jit or use "
                "engine='dense'"
            )
        return frontier_shiloach_vishkin(
            src, dst, num_nodes, max_rounds=max_rounds, **kwargs
        )
    if engine == "sharded_frontier":
        if tracing:
            raise ValueError(
                "the sharded frontier engine's level loop is host-driven "
                "and cannot run inside jit; call it outside jit or use "
                "engine='dense'"
            )
        from repro.distributed.graph import sharded_frontier_shiloach_vishkin

        return sharded_frontier_shiloach_vishkin(
            src, dst, num_nodes, mesh=mesh, max_rounds=max_rounds, **kwargs
        )
    if engine == "dense":
        fkw = _FRONTIER_KW & kwargs.keys()
        if fkw:
            raise ValueError(
                f"{sorted(fkw)} are frontier-engine options; use "
                "engine='frontier' or engine='sharded_frontier'"
            )
        if single_kw and (mesh is not None or sharded_kw):
            # only hook_impl can land here: the dense sharded engine has
            # no kernel hook path
            raise ValueError(
                f"{sorted(single_kw)} with a mesh needs "
                "engine='sharded_frontier' (the dense sharded engine "
                "walks every edge through plain XLA scatters)"
            )
        if single_kw or (mesh is None and not sharded_kw
                         and jax.device_count() == 1):
            # hook_impl pins the single-device sv_run loop on any machine
            return shiloach_vishkin(
                src, dst, num_nodes, max_rounds=max_rounds, **kwargs
            )
    elif single_kw:  # engine == "_sharded" off the auto path
        raise ValueError(
            f"{sorted(single_kw)} cannot run inside jit with a mesh: the "
            "frontier level loop is host-driven; call outside jit or "
            "drop them"
        )
    # multi-device (or sharded knobs): the sharded engine IS the dense walk
    from repro.distributed.graph import sharded_shiloach_vishkin

    return sharded_shiloach_vishkin(
        src, dst, num_nodes, mesh=mesh, max_rounds=max_rounds, **kwargs
    )


_SINGLE_ENGINE_KW = frozenset({"pack_mode"})


def list_rank(succ, num_splitters=None, *, mesh=None, **kwargs):
    """List ranking with automatic engine dispatch: the random-splitter
    engine on one device, its edge-partitioned sharded counterpart when
    a ``mesh=`` is given or several devices are visible. Returns the
    exact integer ranks (bit-identical on every path). The full matrix
    lives in ``docs/engines.md``; keywords:

    * ``num_splitters=`` (int, default: ``min(4096,
      max_splitters_for_linear_work(n))``) -- RS1 splitter count.
    * ``kernel_impl=`` -- ``"xla"``, ``"auto"``, ``"pallas"``,
      ``"pallas_interpret"``: routes the RS4/RS5 phases through the
      Pallas kernels; honoured by BOTH engines. Defaults: ``"xla"`` on
      the single-device engine, ``"auto"`` on the sharded one; "auto"
      keeps plain XLA on every backend, since the chip's compiler
      refuses both kernels (``repro.kernels``).
    * ``pack_mode=`` -- ``"aos"`` (default), ``"soa"``, ``"word64"``:
      single-device walk-state packing (Table 2); when given without a
      mesh it pins the single-device engine on any machine, combining
      it WITH a mesh raises.
    * ``splitters=``/``seed=``/``head=``/``max_steps=``/``with_stats=``
      -- forwarded to the chosen engine unchanged (same KISS streams on
      both, so default splitter selection agrees bit-exactly).

    Unknown dispatch strings raise naming the valid choices.
    """
    import jax

    from repro.core.list_ranking import KERNEL_IMPLS, PACK_MODES

    if "kernel_impl" in kwargs:
        check_choice("kernel_impl", kwargs["kernel_impl"], KERNEL_IMPLS)
    if "pack_mode" in kwargs:
        check_choice("pack_mode", kwargs["pack_mode"], PACK_MODES)
    single_only = _SINGLE_ENGINE_KW & kwargs.keys()
    if mesh is not None or (jax.device_count() > 1 and not single_only):
        if single_only:
            raise ValueError(
                f"{sorted(single_only)} are single-device options; drop "
                "them or drop mesh="
            )
        from repro.distributed.graph import sharded_random_splitter_rank

        return sharded_random_splitter_rank(
            succ, num_splitters, mesh=mesh, **kwargs
        )
    return random_splitter_rank(succ, num_splitters, **kwargs)


def spanning_forest(src, dst, num_nodes, **kwargs):
    """Spanning forest from CC hook decisions -- see
    ``repro.trees.spanning_forest`` (engine dispatch as above)."""
    from repro.trees import spanning_forest as _sf

    return _sf(src, dst, num_nodes, **kwargs)


def euler_tour(edge_u, edge_v, num_nodes, **kwargs):
    """Euler tour of a spanning forest -- see ``repro.trees.euler_tour``;
    the returned tour's ``succ`` feeds ``list_rank``/``wylie_rank``."""
    from repro.trees import euler_tour as _et

    return _et(edge_u, edge_v, num_nodes, **kwargs)


def root_tree(tour, **kwargs):
    """Parent array of a toured forest -- see ``repro.trees.root_tree``;
    ``rank_engine=``/``kernel_impl=``/``mesh=`` dispatch the underlying
    list ranking exactly like ``list_rank``."""
    from repro.trees import root_tree as _rt

    return _rt(tour, **kwargs)


def tree_analytics(src, dst, num_nodes, **kwargs):
    """One-shot graph -> forest -> tour -> tree computations pipeline --
    see ``repro.trees.tree_analytics``."""
    from repro.trees import tree_analytics as _ta

    return _ta(src, dst, num_nodes, **kwargs)


def serve_graphs(requests, **kwargs):
    """Serve many small graph requests wave-batched: one padded
    disjoint-union engine call per wave, bit-exact vs issuing each
    request alone -- see ``repro.serve.graph.GraphServeEngine``.

    ``requests`` is an iterable of ``repro.serve.GraphRequest``;
    ``kwargs`` are the engine knobs (``engine=`` / ``rank_engine=`` /
    ``kernel_impl=`` / ``mesh=`` dispatch exactly as in the functions
    above, plus the wave/bucket capacity knobs -- full matrix in
    ``docs/engines.md`` and ``docs/serving.md``). Returns the finished
    requests with ``result`` populated, in completion order.
    """
    from repro.serve.graph import GraphServeEngine

    eng = GraphServeEngine(**kwargs)
    for r in requests:
        eng.submit(r)
    return eng.run()


__all__ = [
    "connected_components",
    "list_rank",
    "spanning_forest",
    "euler_tour",
    "root_tree",
    "tree_analytics",
    "serve_graphs",
    "check_choice",
    "wylie_rank",
    "random_splitter_rank",
    "select_splitters",
    "even_splitters",
    "max_splitters_for_linear_work",
    "SplitterStats",
    "shiloach_vishkin",
    "frontier_shiloach_vishkin",
    "FrontierStats",
    "shortest_paths",
    "bellman_ford",
    "frontier_bellman_ford",
    "SsspStats",
    "SSSP_ENGINES",
    "sssp_round_bound",
    "pagerank",
    "pagerank_iter_bound",
    "PageRankStats",
    "PAGERANK_ENGINES",
    "label_propagation",
    "sv_round_bound",
    "ConvergenceError",
    "num_components",
    "dedup_edges",
    "striding_indices",
    "partitioning_indices",
    "strided_view",
    "partitioned_view",
    "lockstep_walk",
]
