"""Edge-partitioned multi-device graph engine (shard_map).

The single-device kernels in ``repro.core`` treat the whole TPU as one
PRAM; this module scales the paper's two headline algorithms across a
1-D device mesh using the partitioning scheme Gunrock-style systems use:
**edges are partitioned, labels are replicated**, and each round ends
with one associative label exchange.

* ``sharded_shiloach_vishkin`` -- each device min-hooks over its own
  edge shard into its replica of the label array ``D``; a ``pmin``
  exchange after SV2 (fused with a ``pmax`` of the activity stamps
  ``Q``) and another after SV3 make the merged replica bit-identical to
  the single-device min-CRCW scatter, because a min-scatter distributes
  over shard unions:  min_shards(min-scatter(shard)) ==
  min-scatter(all edges).  Short-cuts (SV1a/SV4) touch only replicated
  state and run redundantly with zero communication.  The round
  structure -- and therefore the paper's log_{3/2} n + 2 bound -- is
  unchanged; only WHO walks each edge moved.

  ``exchange="sparse"`` replaces the O(n) full-array merges with the
  **sparse frontier exchange**: each device all-gathers only the
  (index, label) pairs its own scatter changed this round, in a
  fixed-capacity buffer (default n/8), and every replica re-applies the
  union onto the shared pre-scatter base -- the same distributivity
  argument, restricted to the changed support, so still bit-exact. A
  pmax'd overflow count flips all replicas together to the dense pmin
  path when a round's frontier exceeds capacity (early rounds), cutting
  late-round exchange volume from O(n) to O(capacity);
  ``with_stats=True`` returns the measured per-round volumes.

* ``sharded_random_splitter_rank`` -- RS3's sub-list walks are
  partitioned over devices by splitter block (device d walks lanes
  [d*p/nd, (d+1)*p/nd)); each device scatter-writes (local_rank, owner)
  for the nodes its sub-lists cover, and since sub-lists partition the
  node set exactly one device writes each node: a single ``pmax``
  merges the stores losslessly.  RS4 all-gathers the p-lane splitter
  list (p is VMEM-sized by construction) and ranks it redundantly on
  every device -- the multi-device analogue of the paper's single-block
  ``__syncthreads`` fast path.  RS5's streaming aggregation is sharded
  back out over node blocks, so the output materialises already
  edge-partitioned (out_spec P(axis)).  ``kernel_impl`` routes RS4/RS5
  through the Pallas kernels (``kernels/pointer_jump``,
  ``kernels/splitter_aggregate``) inside each shard -- "auto" keeps
  plain XLA, since the chip's compiler refuses both kernels.

Both functions are bit-exact against their single-device counterparts
(asserted by ``tests/multidev_scripts.py sharded_cc / sharded_rank``),
and both report their per-round exchange volume so
``benchmarks/multidev_scaling.py`` can plot communication vs devices.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.compat import Mesh, is_tracer
from repro.core.components import (
    HOOK_IMPLS,
    ConvergenceError,
    _maybe_dedup,
    check_choice,
    init_hooks,
    sv_compress,
    sv_round_bound,
    sv_round_fns,
    sv_run,
)
from repro.core.list_ranking import (
    KERNEL_IMPLS,
    SplitterStats,
    _splitter_list_rank,
    aos_walk_fns,
    max_splitters_for_linear_work,
    select_splitters,
)
from repro.core.operators import compact_frontier, run_bucket_ladder
from repro.core.pram import lockstep_walk
from repro.obs import trace

Array = jax.Array

GRAPH_AXIS = "graph"

# Valid cross-device label-exchange modes for the sharded CC engines.
# The frontier-compacted sharded engine defaults to "sparse" (volumes
# are measured per round; late-round frontiers are tiny), the dense
# sharded engine to "dense" (it re-walks every edge anyway).
EXCHANGES = ("dense", "sparse")


def graph_mesh(num_devices: int | None = None, axis: str = GRAPH_AXIS) -> Mesh:
    """1-D mesh over the first ``num_devices`` devices (default: all)."""
    devs = jax.devices()
    nd = num_devices if num_devices is not None else len(devs)
    if nd > len(devs):
        raise ValueError(f"asked for {nd} devices, have {len(devs)}")
    return compat.make_mesh((nd,), (axis,), devices=devs[:nd])


def _resolve_axis(mesh: Mesh, axis: str) -> str:
    """Accept any 1-D mesh regardless of its axis name.

    The engine partitions along a single axis; a user-built 1-D mesh
    named anything (e.g. "data") works as-is, while multi-axis meshes
    must name which axis carries the edges.
    """
    if axis in mesh.axis_names:
        return axis
    if len(mesh.axis_names) == 1:
        return mesh.axis_names[0]
    raise ValueError(
        f"sharded graph engine needs a 1-D mesh or axis={axis!r} present; "
        f"got mesh axes {mesh.axis_names}"
    )


def _pad_to(x: jnp.ndarray, size: int, fill) -> jnp.ndarray:
    if x.shape[0] == size:
        return x
    return jnp.concatenate(
        [x, jnp.full((size - x.shape[0],), fill, x.dtype)]
    )


# ---------------------------------------------------------------------------
# Sharded Shiloach-Vishkin connected components
# ---------------------------------------------------------------------------


def _dense_merge_fns(axis, n):
    """The replicated-label exchanges: full pmin/pmax every round."""

    def merge_labels(d, base, aux, s):
        words, frontier = aux
        cnt = jnp.sum((d != base).astype(jnp.int32))
        aux = (words.at[s].add(n), frontier.at[s].max(jax.lax.pmax(cnt, axis)))
        return jax.lax.pmin(d, axis), aux

    def merge_stamps(q, base, aux, s):
        words, frontier = aux
        return jax.lax.pmax(q, axis), (words.at[s].add(n), frontier)

    return merge_labels, merge_stamps


def _sparse_merge_fns(axis, n, capacity):
    """Sparse frontier exchange: each device publishes only the (index,
    label) pairs its own min-scatter changed this round, in a
    fixed-capacity buffer; every replica applies the all-gathered pairs
    onto the common pre-scatter base. Because a min-scatter distributes
    over edge-shard unions, ``base.at[union of idx].min(vals)`` is
    bit-identical to ``pmin`` of the full arrays -- whenever every
    device's change count fits the buffer. One pmax'd scalar decides
    overflow uniformly across replicas, so all devices fall back to the
    dense pmin path together (``lax.cond`` stays collective-safe)."""
    C = capacity

    def publish_min(d, base, changed):
        idx = jnp.nonzero(changed, size=C, fill_value=n)[0].astype(jnp.int32)
        vals = jnp.where(idx < n, d[jnp.minimum(idx, n - 1)], n)
        idx_all = jax.lax.all_gather(idx, axis, axis=0, tiled=True)
        vals_all = jax.lax.all_gather(vals, axis, axis=0, tiled=True)
        return base.at[idx_all].min(vals_all, mode="drop")

    def merge_labels(d, base, aux, s):
        words, frontier = aux
        changed = d != base
        cnt_max = jax.lax.pmax(jnp.sum(changed.astype(jnp.int32)), axis)
        overflow = cnt_max > C
        merged = jax.lax.cond(
            overflow,
            lambda _: jax.lax.pmin(d, axis),
            lambda _: publish_min(d, base, changed),
            operand=None,
        )
        # 2C words (idx, label) when sparse, n when dense; +1 for the
        # pmax'd overflow count either way.
        aux = (
            words.at[s].add(jnp.where(overflow, n, 2 * C) + 1),
            frontier.at[s].max(cnt_max),
        )
        return merged, aux

    def merge_stamps(q, base, aux, s):
        words, frontier = aux
        changed = q != base
        cnt_max = jax.lax.pmax(jnp.sum(changed.astype(jnp.int32)), axis)
        overflow = cnt_max > C

        def sparse(_):
            idx = jnp.nonzero(changed, size=C, fill_value=n)[0].astype(
                jnp.int32
            )
            idx_all = jax.lax.all_gather(idx, axis, axis=0, tiled=True)
            # Every SV2 stamp this round is the same value s, so indices
            # alone carry the exchange (C words, not 2C).
            return base.at[idx_all].set(s, mode="drop")

        merged = jax.lax.cond(
            overflow, lambda _: jax.lax.pmax(q, axis), sparse, operand=None
        )
        aux = (words.at[s].add(jnp.where(overflow, n, C) + 1), frontier)
        return merged, aux

    return merge_labels, merge_stamps


@partial(
    jax.jit,
    static_argnames=(
        "num_nodes", "max_rounds", "mesh", "axis", "exchange", "capacity",
        "record_hooks",
    ),
)
def _sharded_sv(a, b, *, num_nodes, max_rounds, mesh, axis, exchange,
                capacity, record_hooks=False):
    n = num_nodes
    bound = max_rounds if max_rounds is not None else sv_round_bound(n)

    def block(a_loc, b_loc):
        # The round body itself lives in core.components.sv_run;
        # this engine only chooses who walks which edges and inserts the
        # two per-round exchanges: the label merge after each min-scatter
        # (exchange 1 fused with the activity-stamp merge -- monotone
        # round numbers, so max == "any device set it"), exchange 2 for
        # the SV3 hooks. Short-cuts run redundantly on replicated state.
        # ``exchange="sparse"`` swaps the full-array pmin/pmax for the
        # frontier-compacted (index, label) exchange.
        if exchange == "sparse":
            ml, mq = _sparse_merge_fns(axis, n, capacity)
        else:
            ml, mq = _dense_merge_fns(axis, n)
        aux0 = (jnp.zeros(bound + 2, jnp.int32), jnp.zeros(bound + 2, jnp.int32))
        # Hook recording merges with pmin: candidate winning-edge arrays
        # use sentinel n, so the per-phase two-step (u then v) pmin
        # reconstructs the lexicographically-min global winner even when
        # the winning edge lives on another device's shard.
        mh = (lambda arr: jax.lax.pmin(arr, axis)) if record_hooks else None
        return sv_run(
            a_loc, b_loc, n, bound,
            merge_labels=ml, merge_stamps=mq,
            aux0=aux0, return_aux=True,
            record_hooks=record_hooks, merge_hooks=mh,
        )

    # sv_run returns (D, rounds, converged[, hooks], aux) -- converged
    # is the replicated fixpoint sentinel (see ConvergenceError).
    out_specs = (P(), P(), P(), (P(), P()))
    if record_hooks:
        out_specs = (P(), P(), P(), (P(), P()), (P(), P()))
    return compat.shard_map(
        block,
        mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=out_specs,
        check_vma=False,
    )(a, b)


@dataclass
class CCExchangeStats:
    """Measured per-round exchange volume (``benchmarks/multidev_scaling``).

    ``words_per_round[r]`` is the int32 words one device sent in round
    r+1 across all three exchanges; ``frontier_per_round[r]`` is the
    largest per-device changed-label count pmax'd that round (the sparse
    payload the fixed-capacity buffer must hold to stay off the dense
    fallback)."""

    words_per_round: np.ndarray
    frontier_per_round: np.ndarray
    exchange: str
    capacity: int | None

    def publish(self, registry=None, prefix: str = "cc.sharded") -> None:
        """Publish into the metrics registry (``repro.obs.metrics``)."""
        from repro.obs.metrics import publish_stats

        publish_stats(self, prefix, registry)


def default_sparse_capacity(num_nodes: int) -> int:
    """Per-device (index, label) buffer: n/8 keeps a no-overflow round's
    label exchange at n/4 words vs the dense path's n."""
    return max(64, num_nodes // 8)


def sharded_shiloach_vishkin(
    src: Array | np.ndarray,
    dst: Array | np.ndarray,
    num_nodes: int,
    *,
    mesh: Mesh | None = None,
    axis: str = GRAPH_AXIS,
    max_rounds: int | None = None,
    exchange: str = "dense",
    sparse_capacity: int | None = None,
    dedup: bool = True,
    record_hooks: bool = False,
    with_stats: bool = False,
):
    """Multi-device connected components; bit-exact vs single-device.

    Edges (both orientations, as in the paper's 2m walk, minus
    self-loops and duplicates) are partitioned across the mesh; labels
    are replicated and merged twice per round. ``exchange="sparse"``
    sends only the (index, label) pairs each device changed (capacity
    ``sparse_capacity``, default n/8, dense fallback on overflow) --
    bit-exact either way. Returns (labels, rounds) exactly like
    ``shiloach_vishkin``, plus the ``(hook_u, hook_v)`` spanning-forest
    record when ``record_hooks`` (labels/rounds unchanged; the hook
    arrays are pmin-merged so they match the single-device record
    bit-exactly), plus a ``CCExchangeStats`` when ``with_stats``.
    """
    check_choice("exchange", exchange, EXCHANGES)
    mesh = mesh if mesh is not None else graph_mesh(axis=axis)
    axis = _resolve_axis(mesh, axis)
    nd = mesh.shape[axis]
    src, dst = _maybe_dedup(src, dst, dedup)  # no-op under a jit trace
    src = jnp.asarray(src).astype(jnp.int32)
    dst = jnp.asarray(dst).astype(jnp.int32)
    a = jnp.concatenate([src, dst])
    b = jnp.concatenate([dst, src])
    # Pad the edge shard to a device multiple with (0, 0) self-loops --
    # inert under both hook conditions (SV2 needs Db < Da, SV3 Da != Db).
    m2 = int(a.shape[0])
    mp = max(-(-m2 // nd) * nd, nd)
    a, b = _pad_to(a, mp, 0), _pad_to(b, mp, 0)
    capacity = (
        sparse_capacity if sparse_capacity is not None
        else default_sparse_capacity(num_nodes)
    )
    # Whole-run device span: blocks on the replicated labels at close,
    # the sync the sentinel read below pays anyway; nothing registers
    # under an outer jit trace, so the engine stays traceable.
    with trace.span(
        "cc.sharded", device=True, n=num_nodes, devices=nd,
        exchange=exchange,
    ) as sp:
        res = _sharded_sv(
            a, b, num_nodes=num_nodes, max_rounds=max_rounds, mesh=mesh,
            axis=axis, exchange=exchange, capacity=capacity,
            record_hooks=record_hooks,
        )
        if record_hooks:
            labels, rounds, converged, hooks, (words, frontier) = res
            out = (labels, rounds, hooks)
        else:
            labels, rounds, converged, (words, frontier) = res
            out = (labels, rounds)
        if not is_tracer(converged):
            sp.block_on(labels)
    if not is_tracer(converged):
        # Intentional terminal sync: the fixpoint sentinel must be read
        # before wrong labels can escape (labels are replicated, so the
        # flag is device-agreed). Traced callers keep the documented
        # return-at-bound behavior.
        if not bool(converged):  # repro-lint: disable=host-sync
            bound = (
                max_rounds if max_rounds is not None
                else sv_round_bound(num_nodes)
            )
            raise ConvergenceError(
                f"sharded_shiloach_vishkin hit max_rounds={bound} "
                f"before the label fixpoint on {num_nodes} nodes; raise "
                "max_rounds (the proven bound is sv_round_bound(n)="
                f"{sv_round_bound(num_nodes)})"
            )
    if not with_stats:
        return out
    # Opt-in stats materialization: with_stats=True is an explicit ask to
    # read the per-round traces back to host, after the loop converged.
    r = int(rounds)  # repro-lint: disable=host-sync
    stats = CCExchangeStats(
        words_per_round=np.asarray(words)[1 : r + 1],  # repro-lint: disable=host-sync
        frontier_per_round=np.asarray(frontier)[1 : r + 1],  # repro-lint: disable=host-sync
        exchange=exchange,
        capacity=capacity if exchange == "sparse" else None,
    )
    return out + (stats,)


def cc_exchange_words_per_round(
    num_nodes: int, *, stats: CCExchangeStats | None = None
):
    """int32 words a device sends per SV round.

    Without ``stats``: the dense replicated-label model,
    pmin(D2)+pmax(Q)+pmin(D3) = 3n, as a scalar. With ``stats`` (from
    ``sharded_shiloach_vishkin(..., with_stats=True)``): the measured
    per-round volumes, as an array -- for the sparse exchange this drops
    to O(frontier buffer) once the per-round change counts fit capacity.
    """
    if stats is not None:
        return stats.words_per_round
    return 3 * num_nodes


# ---------------------------------------------------------------------------
# Sharded frontier-compacted Shiloach-Vishkin (per-shard edge frontiers)
# ---------------------------------------------------------------------------


@partial(
    jax.jit,
    static_argnames=(
        "num_nodes", "bound", "shrink_at", "mesh", "axis", "exchange",
        "capacity", "hook_impl", "record_hooks",
    ),
)
def _sharded_frontier_level(
    a, b, D, Q, aux, s, *, num_nodes, bound, shrink_at, mesh, axis,
    exchange, capacity, hook_impl, record_hooks=False,
):
    """One bucket level of the sharded frontier engine: every device runs
    SV rounds over its own (compacted) edge shard at a fixed per-device
    buffer size, with the usual per-round label exchanges, until
    convergence, the round bound, or -- when ``shrink_at`` is set -- the
    globally largest per-device frontier drops to half the buffer.

    The shrink watermark is ``pmax`` of the per-shard live counts, read
    off the round body's own SV3 compare mask exactly like the
    single-device engine, and it rides in the loop carry so the
    ``while_loop`` predicate stays collective-free (every replica holds
    the identical pmax'd scalar -- the same uniformity argument as the
    sparse exchange's overflow cond). Node-indexed state (labels, stamps,
    hook records, exchange stats) is replicated and threads through
    levels untouched by compaction."""
    n = num_nodes

    def block(a_loc, b_loc, D, Q, aux, s):
        if exchange == "sparse":
            ml, mq = _sparse_merge_fns(axis, n, capacity)
        else:
            ml, mq = _dense_merge_fns(axis, n)
        mh = (lambda arr: jax.lax.pmin(arr, axis)) if record_hooks else None
        body = sv_round_fns(
            a_loc, b_loc, n, ml, mq, hook_impl=hook_impl,
            with_frontier=True, record_hooks=record_hooks, merge_hooks=mh,
        )
        m_loc = a_loc.shape[0]

        def wrapped(carry):
            D, Q, aux, s, changed, fmask, _live_max, rounds = carry
            D, Q, aux, s, changed, fmask = body(
                (D, Q, aux, s, changed, fmask)
            )
            live = jnp.sum(fmask.astype(jnp.int32))
            live_max = jax.lax.pmax(live, axis)
            return D, Q, aux, s, changed, fmask, live_max, rounds + 1

        def cond(carry):
            _D, _Q, _aux, s, changed, _fmask, live_max, _rounds = carry
            keep = jnp.logical_and(changed, s <= bound)
            if shrink_at is not None:
                keep = jnp.logical_and(keep, live_max > shrink_at)
            return keep

        init = (
            D, Q, aux, s, jnp.bool_(True), jnp.ones((m_loc,), jnp.bool_),
            jnp.int32(m_loc), jnp.int32(0),
        )
        D, Q, aux, s, changed, fmask, live_max, rounds = jax.lax.while_loop(
            cond, wrapped, init
        )
        return D, Q, aux, s, changed, fmask, live_max, rounds

    rep = jax.tree_util.tree_map(lambda _: P(), aux)
    return compat.shard_map(
        block,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(), P(), rep, P()),
        out_specs=(P(), P(), rep, P(), P(), P(axis), P(), P()),
        check_vma=False,
    )(a, b, D, Q, aux, s)


@partial(jax.jit, static_argnames=("size", "mesh", "axis"))
def _sharded_compact(a, b, fmask, *, size, mesh, axis):
    """Every device compacts its own edge shard into a ``size``-slot
    bucket (the global pmax'd live count's power-of-two ceiling) via the
    shard-local ``core.frontier.compact_frontier`` primitive -- zero
    cross-device traffic; shards stay where they are, only shrink."""
    return compat.shard_map(
        partial(compact_frontier, size=size),
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis)),
        check_vma=False,
    )(a, b, fmask)


@dataclass
class ShardedFrontierStats:
    """Work + exchange accounting for the sharded frontier engine.

    ``edges_touched`` counts **per-device** edge-slot visits with the
    same rules as ``core.frontier.FrontierStats`` (two hook passes per
    round over the local bucket, one bucket write per compaction); the
    dense sharded engine's same-metric cost is ``2 * ceil(m2 / nd) *
    rounds`` per device. ``words_per_round`` / ``frontier_per_round``
    are the measured exchange volumes, as in ``CCExchangeStats``;
    ``capacities`` lists the frontier-driven sparse buffer size chosen
    at each level (empty for the dense exchange)."""

    rounds: int
    edges_touched: int  # per-device edge-slot visits (see docstring)
    m2: int  # global oriented edge count after dedup
    num_devices: int
    levels: list = field(default_factory=list)  # (per-device bucket, rounds)
    exchange: str = "sparse"
    capacities: list = field(default_factory=list)  # per-level sparse cap
    words_per_round: np.ndarray | None = None
    frontier_per_round: np.ndarray | None = None

    def publish(
        self, registry=None, prefix: str = "cc.sharded_frontier"
    ) -> None:
        """Publish into the metrics registry (``repro.obs.metrics``)."""
        from repro.obs.metrics import publish_stats

        publish_stats(self, prefix, registry)


def frontier_sparse_capacity(
    num_nodes: int, bucket: int, user_capacity: int | None = None
) -> int:
    """Per-device sparse-exchange buffer for one frontier level.

    Sized from the live frontier: a device's min-scatter changes at most
    one label slot per local edge, so ``bucket`` (the per-device frontier
    buffer) is a hard bound on its per-round change count -- once the
    frontier undercuts the fixed ``default_sparse_capacity`` the buffer
    shrinks with it and overflow becomes impossible. Early levels (bucket
    above the fixed default) keep the default capacity with the dense
    fallback live, exactly like the dense sharded engine's sparse mode.
    An explicit ``user_capacity`` is honoured verbatim at every level
    (that keeps the overflow path forceable in tests)."""
    if user_capacity is not None:
        return user_capacity
    return max(64, min(bucket, default_sparse_capacity(num_nodes)))


def sharded_frontier_shiloach_vishkin(
    src: Array | np.ndarray,
    dst: Array | np.ndarray,
    num_nodes: int,
    *,
    mesh: Mesh | None = None,
    axis: str = GRAPH_AXIS,
    max_rounds: int | None = None,
    exchange: str = "sparse",
    sparse_capacity: int | None = None,
    min_bucket: int = 1024,
    hook_impl: str = "xla",
    dedup: bool = True,
    record_hooks: bool = False,
    with_stats: bool = False,
):
    """Frontier-compacted CC on the mesh: the composition of the sharded
    engine (edges partitioned, labels replicated, per-round exchanges)
    with the frontier engine (each device compacts its OWN edge shard to
    the active frontier between bucket levels).

    Bit-exact in labels, round counts, AND recorded hook forests against
    both ``sharded_shiloach_vishkin`` and the single-device engines: the
    round body is the shared ``sv_round_fns``, compaction keeps every
    unequal-label edge (label equality is permanent, so no future hook
    winner is ever dropped), and the inert (0, 0) self-loop padding in
    part-full buckets is invisible to both hook conditions.

    ``exchange="sparse"`` is the DEFAULT here (unlike the dense sharded
    engine): per-round volumes are measured, and the sparse buffer is
    sized from the live frontier per level (``frontier_sparse_capacity``)
    -- once the frontier fits the per-device bucket, overflow to the
    dense path is impossible by construction. ``hook_impl`` routes each
    shard's SV2/SV3 hook phases through the fused ``kernels/edge_hook``
    Pallas kernel (shard-local labels+stamps stay VMEM-resident; the
    merges see identical arrays either way). Returns ``(labels, rounds)``
    plus the ``(hook_u, hook_v)`` record when ``record_hooks``, plus a
    ``ShardedFrontierStats`` when ``with_stats``.

    Like the single-device frontier engine, the level loop is
    host-driven (bucket sizes are compiled shapes), so this engine
    cannot run under an outer ``jax.jit`` trace -- ``engine="auto"``
    falls back to the fully-traceable dense sharded walk there.
    """
    n = num_nodes
    check_choice("exchange", exchange, EXCHANGES)
    check_choice("hook_impl", hook_impl, HOOK_IMPLS)
    mesh = mesh if mesh is not None else graph_mesh(axis=axis)
    axis = _resolve_axis(mesh, axis)
    nd = mesh.shape[axis]
    src, dst = _maybe_dedup(src, dst, dedup)
    src = jnp.asarray(src, jnp.int32).ravel()
    dst = jnp.asarray(dst, jnp.int32).ravel()
    a = jnp.concatenate([src, dst])
    b = jnp.concatenate([dst, src])
    m2 = int(a.shape[0])
    bucket = max(-(-m2 // nd), 1)  # per-device edge-buffer size
    a, b = _pad_to(a, nd * bucket, 0), _pad_to(b, nd * bucket, 0)

    bound = max_rounds if max_rounds is not None else sv_round_bound(n)
    D = jnp.arange(n, dtype=jnp.int32)
    Q = jnp.zeros(n, jnp.int32)
    s = jnp.int32(1)
    exa = (jnp.zeros(bound + 2, jnp.int32), jnp.zeros(bound + 2, jnp.int32))
    aux = (init_hooks(n), exa) if record_hooks else exa
    stats = ShardedFrontierStats(
        rounds=0, edges_touched=0, m2=m2, num_devices=nd, exchange=exchange,
    )

    fmask = None
    live_max = None
    # Spans attach at the per-LEVEL syncs the shared shrink ladder
    # already pays; tags reuse those reads (docs/observability.md). The
    # ladder is the same operators.run_bucket_ladder the single-device
    # engine drives; only the closures differ -- the level runs inside
    # shard_map and the live watermark is the pmax'd per-device count.
    with trace.span(
        "cc.sharded_frontier", n=n, m2=m2, devices=nd, exchange=exchange,
    ) as run_sp:

        def sv_level(bucket_now, shrink_at):
            nonlocal D, Q, aux, s, fmask, live_max
            capacity = (
                frontier_sparse_capacity(n, bucket_now, sparse_capacity)
                if exchange == "sparse" else 0
            )
            if exchange == "sparse":
                stats.capacities.append(capacity)
            with trace.span(
                "cc.sharded_frontier.level", bucket=bucket_now,
                capacity=capacity,
            ) as sp:
                D, Q, aux, s, changed, fmask, live_max, rounds = (
                    _sharded_frontier_level(
                        a, b, D, Q, aux, s,
                        num_nodes=n, bound=bound, shrink_at=shrink_at,
                        mesh=mesh, axis=axis, exchange=exchange,
                        capacity=capacity, hook_impl=hook_impl,
                        record_hooks=record_hooks,
                    )
                )
                # Per-device visit accounting mirrors the single-device
                # engine: SV2 + SV3 passes over the local bucket (the
                # Pallas hook kernel pays a third, mask, pass), plus the
                # compaction write below.
                passes = 2 if hook_impl == "xla" else 3
                # Per-level host syncs (not per-round): the inner SV
                # iteration stays on device and the host reads one round
                # count / convergence flag / live max per LEVEL to drive
                # the shared shrink ladder -- same level-synchronous
                # design as frontier.py.
                level_rounds = int(rounds)  # repro-lint: disable=host-sync
                stats.edges_touched += passes * level_rounds * bucket_now
                stats.levels.append((bucket_now, level_rounds))
                converged = not bool(changed)  # repro-lint: disable=host-sync
                sp.tag(rounds=level_rounds, converged=converged)
            over = not converged and int(s) > bound  # repro-lint: disable=host-sync
            return converged, over

        def live_edges():
            # Shrink: every shard drops to the power-of-two bucket
            # covering the LARGEST per-device live count (one shared
            # compiled shape).
            return int(live_max)  # repro-lint: disable=host-sync

        def charge_shrink(new_bucket):
            stats.edges_touched += new_bucket

        def shrink(new_bucket):
            nonlocal a, b
            a, b = _sharded_compact(
                a, b, fmask, size=new_bucket, mesh=mesh, axis=axis
            )

        def bound_hit():
            raise ConvergenceError(
                f"sharded frontier SV hit its round bound ({bound}) before"
                f" the label fixpoint on {n} nodes across {nd} devices; the"
                " labels at the bound are NOT components -- raise"
                " max_rounds (the proven bound is sv_round_bound(n)="
                f"{sv_round_bound(n)})"
            )

        run_bucket_ladder(
            bucket=bucket, min_bucket=min_bucket, run_level=sv_level,
            live_count=live_edges, compact=shrink, on_shrink=charge_shrink,
            on_nonconverged=bound_hit,
        )
        D = sv_compress(D, n)
        # Terminal readback: the loop above already synced on s per level.
        rounds_total = int(s) - 1  # repro-lint: disable=host-sync
        run_sp.tag(rounds=rounds_total, levels=len(stats.levels))
    stats.rounds = rounds_total
    out = (D, jnp.int32(rounds_total))
    if record_hooks:
        hooks, exa = aux
        out = out + (hooks,)
    else:
        exa = aux
    if not with_stats:
        return out
    # Opt-in stats materialization after convergence (with_stats=True).
    words, frontier = exa
    stats.words_per_round = np.asarray(words)[1 : rounds_total + 1]  # repro-lint: disable=host-sync
    stats.frontier_per_round = np.asarray(frontier)[1 : rounds_total + 1]  # repro-lint: disable=host-sync
    return out + (stats,)


# ---------------------------------------------------------------------------
# Sharded random-splitter list ranking
# ---------------------------------------------------------------------------


@partial(
    jax.jit,
    static_argnames=(
        "n", "p", "pp", "npad", "max_steps", "mesh", "axis", "kernel_impl"
    ),
)
def _sharded_rs(
    succ, spl_pad, *, n, p, pp, npad, max_steps, mesh, axis, kernel_impl
):
    nd = mesh.shape[axis]
    lanes_per = pp // nd

    def block(succ, spl_all):
        dev = jax.lax.axis_index(axis)
        # RS1/RS2 (replicated): stop set + ownership seed from the full
        # splitter list; every device computes the identical init.
        spl = spl_all[:p]
        all_lanes = jnp.arange(p, dtype=jnp.int32)
        is_stop = jnp.zeros((n,), jnp.bool_).at[spl].set(True)
        packed = jnp.full((n, 2), -1, jnp.int32)
        packed = packed.at[:, 0].set(0)
        packed = packed.at[spl, 1].set(all_lanes)

        # RS3 (partitioned by splitter block): device d walks global
        # lanes [d*lanes_per, (d+1)*lanes_per). Padded lanes (id >= p)
        # are masked inert.
        lanes = dev.astype(jnp.int32) * lanes_per + jnp.arange(
            lanes_per, dtype=jnp.int32
        )
        valid = lanes < p
        spl_loc = jax.lax.dynamic_slice(
            spl_all, (dev * lanes_per,), (lanes_per,)
        )
        state = dict(
            store=(packed,),
            cur=spl_loc,
            nxt=succ[spl_loc],
            dist=jnp.ones((lanes_per,), jnp.int32),
        )
        # Walk predicate + scatter are the single-device ones (shared
        # code); only the lane ids are offset and padded lanes masked.
        active_fn, step_fn = aos_walk_fns(succ, is_stop, lanes, valid=valid)
        final, steps, converged = lockstep_walk(
            state, active_fn, step_fn, max_steps=max_steps
        )
        (pk,) = final["store"]

        # Merge the stores: sub-lists partition the nodes, so each node
        # was written by exactly one device (local >= 1 over init 0,
        # owner >= 0 over init -1) -> pmax is a lossless union. ONE
        # n-sized exchange for the whole walk phase.
        local = jax.lax.pmax(pk[:, 0], axis)
        owner = jax.lax.pmax(pk[:, 1], axis)

        # RS4 (gathered): the p-lane splitter list fits one device's
        # VMEM; all-gather the per-lane walk results and rank the list
        # redundantly on every replica -- with kernel_impl="pallas" all
        # O(log p) jumping steps run inside ONE kernels/pointer_jump
        # call per device (the paper's single-block fast path).
        dist_full = jax.lax.all_gather(final["dist"], axis, axis=0, tiled=True)[:p]
        nxt_full = jax.lax.all_gather(final["nxt"], axis, axis=0, tiled=True)[:p]
        spsucc = owner[nxt_full]
        is_term = spsucc == all_lanes
        w_adj = dist_full - is_term.astype(jnp.int32)
        iters = max(1, math.ceil(math.log2(max(p, 2))))
        if kernel_impl != "xla":
            from repro.kernels.pointer_jump.ops import pointer_jump

            r, nxt_final = pointer_jump(
                spsucc, jnp.where(is_term, 0, w_adj),
                iters=iters, impl=kernel_impl,
            )
            rank_sp = r + w_adj[nxt_final]
        else:
            rank_sp = _splitter_list_rank(w_adj, spsucc, iters)

        # RS5 (sharded back out): each device aggregates its node block;
        # the ranks come out already partitioned over the mesh. The
        # pallas path streams the block through kernels/splitter_aggregate
        # with the splitter table pinned in VMEM.
        blk = npad // nd
        own_blk = jax.lax.dynamic_slice(
            _pad_to(owner, npad, 0), (dev * blk,), (blk,)
        )
        loc_blk = jax.lax.dynamic_slice(
            _pad_to(local, npad, 0), (dev * blk,), (blk,)
        )
        if kernel_impl != "xla":
            from repro.kernels.splitter_aggregate.ops import splitter_aggregate

            packed_blk = jnp.stack([loc_blk, own_blk], axis=-1)
            rank_blk = splitter_aggregate(packed_blk, rank_sp, impl=kernel_impl)
        else:
            rank_blk = rank_sp[own_blk] - loc_blk

        steps = jax.lax.pmax(steps, axis)  # global trip count
        # Fixpoint sentinel: converged only if EVERY device's lanes
        # finished -- pmin of the per-device flags is the global AND.
        converged = jax.lax.pmin(converged.astype(jnp.int32), axis)
        return rank_blk, dist_full, steps, converged

    return compat.shard_map(
        block,
        mesh=mesh,
        in_specs=(P(), P()),
        out_specs=(P(axis), P(), P(), P()),
        check_vma=False,
    )(succ, spl_pad)


def sharded_random_splitter_rank(
    succ: Array | np.ndarray,
    num_splitters: int | None = None,
    *,
    splitters: np.ndarray | None = None,
    head: int = 0,
    seed: int = 0,
    mesh: Mesh | None = None,
    axis: str = GRAPH_AXIS,
    max_steps: int | None = None,
    kernel_impl: str = "auto",
    with_stats: bool = False,
):
    """Multi-device list ranking; bit-exact vs ``random_splitter_rank``.

    Splitter selection (RS1/RS2) is identical to the single-device path
    (same KISS streams, same seed), so the two implementations rank the
    same sub-lists and produce identical integer ranks.

    ``kernel_impl`` routes the RS4/RS5 phases through the Pallas kernels
    (``kernels/pointer_jump``, ``kernels/splitter_aggregate``) inside
    each device's shard: "auto" keeps the plain-XLA phases (the chip's
    compiler refuses both kernels, see ``repro.kernels``);
    "pallas"/"pallas_interpret" force the kernel path (interpreted
    off-TPU). All routes are bit-exact -- the phases are integer-exact
    in any implementation.
    """
    check_choice("kernel_impl", kernel_impl, KERNEL_IMPLS)
    if kernel_impl == "auto":
        kernel_impl = "xla"  # the chip's compiler refuses the kernels
    mesh = mesh if mesh is not None else graph_mesh(axis=axis)
    axis = _resolve_axis(mesh, axis)
    nd = mesh.shape[axis]
    succ = jnp.asarray(succ).astype(jnp.int32)
    n = int(succ.shape[0])
    if splitters is None:
        p = num_splitters or min(4096, max_splitters_for_linear_work(n))
        p = min(p, n)
        splitters = select_splitters(n, p, seed=seed, head=head)
    splitters = np.asarray(splitters)
    p = len(splitters)
    pp = max(-(-p // nd) * nd, nd)  # lane padding (masked inert)
    npad = max(-(-n // nd) * nd, nd)  # node padding for the RS5 out shard
    spl_pad = _pad_to(jnp.asarray(splitters, jnp.int32), pp, 0)
    with trace.span(
        "rank.splitter.sharded", device=True, n=n, p=p, devices=nd,
    ) as sp:
        rank_pad, sublens, steps, converged = _sharded_rs(
            succ,
            spl_pad,
            n=n,
            p=p,
            pp=pp,
            npad=npad,
            max_steps=max_steps,
            mesh=mesh,
            axis=axis,
            kernel_impl=kernel_impl,
        )
        rank = rank_pad[:n]
        if not is_tracer(converged):
            sp.block_on(rank)
    if max_steps is not None and not is_tracer(converged):
        # Host-driven callers get the fixpoint guarantee; a traced
        # caller cannot raise on a device value and keeps the
        # return-at-bound behavior.
        if not bool(converged):  # repro-lint: disable=host-sync
            raise ConvergenceError(
                f"sharded_random_splitter_rank hit max_steps={max_steps}"
                f" with unfinished lanes ({p} splitters, {n} nodes); the"
                " ranks are NOT valid -- raise max_steps"
            )
    if not with_stats:
        return rank
    # Opt-in stats materialization after the walk finished.
    stats = SplitterStats(
        splitters=np.asarray(splitters),  # repro-lint: disable=host-sync
        sublist_lengths=np.asarray(sublens),  # repro-lint: disable=host-sync
        walk_steps=int(steps),  # repro-lint: disable=host-sync
        expected_mean=n / p,
    )
    return rank, stats


def rank_exchange_words(n: int, p: int, num_devices: int) -> int:
    """int32 words a device sends for one sharded ranking call:
    pmax(local)+pmax(owner) (2n) + two lane all-gathers (2p)."""
    del num_devices  # replicated-label scheme: volume is device-local
    return 2 * n + 2 * p
