"""Gunrock-style advance / filter / compute operators for frontier engines.

The paper's PRAM algorithms share one irregular-access skeleton --
gather values along edges, combine, scatter back -- and the non-trivial
accelerator adaptations (frontier compaction, power-of-two size
buckets, deterministic min-scatters, host-driven level synchronization)
attach to that skeleton, not to any one algorithm. Gunrock (PAPERS.md,
arxiv 1701.01170) showed a small advance/filter/compute operator set
expresses BFS, SSSP, CC, PageRank and BC on GPUs; this module is that
operator set for the repo, and every frontier engine
(``core.frontier.frontier_shiloach_vishkin``,
``core.sssp.frontier_bellman_ford``,
``distributed.graph.sharded_frontier_shiloach_vishkin``,
``core.pagerank.pagerank``) is a composition over it.

Three operator groups (see docs/operators.md for the full contract):

* **advance** -- one gather-apply-scatter step over an edge buffer,
  with scatter collisions resolved by a pluggable commutative
  :class:`Monoid`. ``MIN`` (CC labels, SSSP distances) is idempotent
  min-CRCW: any collision order gives the same bits, the RL002
  scatter-determinism discipline. ``ADD`` (PageRank mass) is
  commutative but float-add is not associative, so its determinism
  contract is weaker: bit-stable for a fixed edge-slot order on a
  backend with deterministic scatter accumulation in ``np.add.at``'s
  order (XLA on the CPU), which is exactly what the serial oracle
  mirrors. On a TPU the scores stay within float32 rounding of it.
* **filter** -- the frontier machinery: ``next_pow2`` size buckets,
  ``compact_frontier`` / ``compact_weighted`` (gather the masked live
  edges into a fixed-size buffer padded with inert self-loops), and
  ``bucket_size`` tying them together. MIN-monoid frontiers come in two
  flavours: CC's compaction is **permanent** (label equality never
  un-happens) so the buffer only shrinks, while SSSP must **re-compact
  from the full edge list** every level (a settled edge wakes up when
  its source's distance later drops). ADD-monoid frontiers cannot skip
  edges at all -- every contribution is part of the sum -- so for
  PageRank the filter only gates *termination* (the tolerance mask),
  never the edge walk.
* **compute** -- a per-node map over node-indexed arrays; trivially
  parallel, no collisions.

plus the two **host drivers** the engines share: ``run_bucket_ladder``
(CC's shrinking power-of-two levels) and ``run_rebuild_loop`` (SSSP's
and PageRank's rebuild-every-level loop). Both are host-driven (bucket
sizes are compiled shapes -- they cannot run under ``jax.jit``), sync
with the device once per LEVEL (the paper's level-synchronous design),
and guarantee the ``ConvergenceError`` sentinel: a loop that stops
before its fixpoint raises rather than returning wrong results. Spans
(``repro.obs``) and stats stay in the engine-supplied closures so each
engine keeps its exact span vocabulary, pinned counters, and host-sync
pragma sites -- the drivers only own the loop structure, which is how
the refactor keeps every engine bit-exact by construction.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.components import ConvergenceError

Array = jax.Array


# ---------------------------------------------------------------------------
# advance: gather-apply-scatter with a pluggable commutative monoid
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Monoid:
    """A commutative monoid resolving ``advance`` scatter collisions.

    ``scatter(target, index, values)`` folds ``values`` into
    ``target[..., index]`` under the monoid's combine; ``identity`` is
    the pad value that makes a buffer slot inert (``+inf`` for min,
    ``0.0`` for add -- the compaction pads rely on this). The combine
    must be commutative (scatter collision order is unspecified);
    idempotent combines (min) are additionally order-free in float,
    non-idempotent ones (add) are bit-stable only per fixed edge-slot
    order -- see docs/operators.md for the exact contract.
    """

    name: str
    identity: float
    scatter: Callable[[Array, Array, Array], Array]


# ``...`` indexing keeps one scatter form for (n,) node vectors and
# (S, n) batched rows (sources/batch lead, node axis last everywhere).
MIN = Monoid(
    "min", float("inf"), lambda t, i, v: t.at[..., i].min(v)
)
ADD = Monoid(
    "add", 0.0, lambda t, i, v: t.at[..., i].add(v)
)


def advance(target: Array, index: Array, values: Array, *, monoid: Monoid):
    """One advance step: scatter ``values`` into ``target`` at ``index``
    (the last -- node -- axis), collisions resolved by ``monoid``.

    Callers gather/apply first (``values`` is already the per-edge
    candidate, e.g. ``dist[:, a] + w``), so this is the scatter half of
    gather-apply-scatter; keeping it a single primitive is what lets
    the RL002 lint reason about every frontier engine's determinism in
    one place. Traceable: safe inside ``jax.jit`` / ``lax`` loops and
    inside ``shard_map`` blocks (it only touches the buffer it is
    handed -- the shard-local rule, docs/operators.md).
    """
    return monoid.scatter(target, index, values)


# ---------------------------------------------------------------------------
# compute: per-node map
# ---------------------------------------------------------------------------


def compute(fn: Callable, *arrays: Array):
    """Per-node map: apply elementwise ``fn`` over node-indexed arrays.

    Trivially parallel (no collisions, no monoid); exists so operator
    compositions read as advance/filter/compute end to end."""
    return fn(*arrays)


# ---------------------------------------------------------------------------
# filter: power-of-two size buckets + frontier compaction
# ---------------------------------------------------------------------------


def next_pow2(x: int) -> int:
    """Smallest power of two >= x (1 for x <= 0): the bucket ladder every
    frontier engine -- single-device and sharded -- sizes its compacted
    edge buffers on, so compiled shapes stay static per level."""
    return 1 << max(x - 1, 0).bit_length() if x > 0 else 1


def bucket_size(live: int, *, min_bucket: int, cap: int | None = None) -> int:
    """The filter's bucket rule: the ``next_pow2`` ceiling of the live
    count, floored at ``min_bucket`` (tiny buckets recompile for no
    win) and clipped to ``cap`` (usually the full edge-buffer size --
    never compact into a bucket larger than the data)."""
    size = max(min_bucket, next_pow2(live))
    return size if cap is None else min(cap, size)


@partial(jax.jit, static_argnames=("size",))
def compact_frontier(a, b, fmask, *, size):
    """Gather the masked frontier into a ``size``-slot buffer, padding
    with inert (0, 0) self-loops. ``size`` must cover the mask count.

    This is the **shard-local compaction primitive**: it only ever looks
    at the edge buffer it is handed, so the sharded frontier engine
    (``repro.distributed.graph.sharded_frontier_shiloach_vishkin``) runs
    it unchanged inside ``shard_map`` -- each device compacts its own
    edge shard into a bucket sized by the global (pmax'd) live count, so
    every shard keeps one common compiled shape per level."""
    m = a.shape[0]
    idx = jnp.nonzero(fmask, size=size, fill_value=m)[0]
    valid = idx < m
    ic = jnp.minimum(idx, max(m - 1, 0))
    return jnp.where(valid, a[ic], 0), jnp.where(valid, b[ic], 0)


@partial(jax.jit, static_argnames=("size",))
def compact_weighted(a, b, w, fmask, *, size):
    """``compact_frontier`` with a weight lane: gather the masked
    frontier into a ``size``-slot buffer, padding with inert (0, 0)
    zero-weight self-loops (a self-relax can never improve, and 0.0 is
    the ADD identity, so the pads are inert under both monoids)."""
    m = a.shape[0]
    idx = jnp.nonzero(fmask, size=size, fill_value=m)[0]
    valid = idx < m
    ic = jnp.minimum(idx, max(m - 1, 0))
    return (
        jnp.where(valid, a[ic], 0),
        jnp.where(valid, b[ic], 0),
        jnp.where(valid, w[ic], 0.0),
    )


# ---------------------------------------------------------------------------
# host drivers: the two level-loop shapes every frontier engine runs
# ---------------------------------------------------------------------------


def run_bucket_ladder(
    *,
    bucket: int,
    min_bucket: int,
    run_level: Callable[[int, int | None], tuple[bool, bool]],
    live_count: Callable[[], int],
    compact: Callable[[int], None],
    on_shrink: Callable[[int], None] | None = None,
    on_nonconverged: Callable[[], None] | None = None,
) -> None:
    """The MONOTONE frontier loop (CC's shrinking bucket ladder): run
    levels at a fixed buffer size, shrink the buffer to the live
    frontier's ``next_pow2`` bucket between levels, never re-expand
    (compaction is permanent -- see docs/operators.md).

    ``run_level(bucket, shrink_at)`` runs one level and returns
    ``(converged, stop)``; ``shrink_at`` is the half-buffer watermark
    the level's device loop may exit early on (``None`` = run to
    convergence/bound: the bucket is already at ``min_bucket``, or a
    previous shrink attempt failed). ``live_count()`` reads the live
    frontier size (the per-level host sync -- only called when a shrink
    is still possible), ``on_shrink(new_bucket)`` is the stats hook
    charged before ``compact(new_bucket)`` rebuilds the buffer. A
    ladder that stops without converging calls ``on_nonconverged``
    (expected to raise the engine's own ``ConvergenceError``) and
    otherwise raises a generic one -- wrong labels never escape.
    """
    force_converge = False
    while True:
        shrink_at = (
            None if (bucket <= min_bucket or force_converge)
            else bucket // 2
        )
        converged, stop = run_level(bucket, shrink_at)
        if converged or stop:
            break
        live = live_count()
        new_bucket = max(min_bucket, next_pow2(live))
        if new_bucket >= bucket:  # can't shrink: run to convergence
            force_converge = True
            continue
        if on_shrink is not None:
            on_shrink(new_bucket)
        compact(new_bucket)
        bucket = new_bucket
    if not converged:
        if on_nonconverged is not None:
            on_nonconverged()
        raise ConvergenceError(
            "bucket ladder stopped before convergence"
        )


def run_rebuild_loop(
    *,
    bound: int,
    live_count: Callable[[], int],
    run_level: Callable[[int], None],
    on_bound: Callable[[int, int], None] | None = None,
) -> int:
    """The REBUILDING frontier loop (SSSP, PageRank): every level asks
    ``live_count()`` for the current live size (SSSP re-masks the FULL
    edge list -- settled edges wake up; PageRank counts above-tolerance
    nodes), stops at zero, and otherwise runs ``run_level(live)``.
    Returns the number of levels run.

    Hitting ``bound`` with a live frontier calls ``on_bound(live,
    rounds)`` (expected to raise the engine's ``ConvergenceError``) and
    otherwise raises a generic one -- the sentinel fires before wrong
    distances/scores can escape."""
    rounds = 0
    while True:
        live = live_count()
        if not live:
            return rounds
        if rounds >= bound:
            if on_bound is not None:
                on_bound(live, rounds)
            raise ConvergenceError(
                f"rebuild loop hit its round bound ({bound}) with "
                f"{live} live"
            )
        run_level(live)
        rounds += 1
