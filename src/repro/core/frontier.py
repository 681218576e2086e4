"""Frontier-compacted Shiloach-Vishkin connected components.

The dense engine (``components.sv_run``) walks all 2m edge orientations
every round, but an edge whose endpoints already share a label can never
hook again (labels of same-labeled nodes evolve identically under both
short-cuts and min-hooks), so after the first few rounds most of the 2m
walk is dead work -- the connected-components instance of the
frontier-centric operators Gunrock showed are THE key GPU graph-analytics
optimization. This engine compacts the edge list to the **active
frontier** (edges with ``D[a] != D[b]``) between rounds:

* the round body is ``components.sv_round_fns`` -- the SAME body the
  dense and sharded engines run, so hook semantics (min-CRCW
  resolution, Q stamps, the log_{3/2} n + 2 round bound) are
  bit-identical and, with ``sample_rounds=0``, labels AND round counts
  match ``sv_run`` exactly;
* compiled shapes stay static via **size-bucketed shrink levels**: each
  level runs a ``lax.while_loop`` at a fixed edge-buffer size and exits
  when the live count falls below half the buffer; the host then
  compacts into the next power-of-two bucket (padding with inert (0, 0)
  self-loops) and resumes the loop carry ``(D, Q, s)`` unchanged.

Optional **Afforest-style sampling pre-pass** (``sample_rounds=k > 0``),
after Sutton, Ben-Nun & Barak, "Optimizing Parallel Graph Connectivity
Computation via Subgraph Sampling" (IPDPS 2018): run k SV rounds that
hook each node through one sampled incident edge (one streaming scatter
pass builds all k samples), which resolves the giant component(s) at
O(n) cost per round; the first frontier compaction then drops every
edge internal to the largest component -- and to every other
already-resolved component -- before full SV runs on the residue. The
pre-pass changes which root represents each component (hooks happen in
a different order), so it is OFF by default; labels remain a correct
component partition and are canonicalization-equal to the dense
engine's.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.components import (
    HOOK_IMPLS,
    ConvergenceError,
    _maybe_dedup,
    check_choice,
    init_hooks,
    sv_compress,
    sv_round_bound,
    sv_round_fns,
)
from repro.core.operators import (  # noqa: F401  (re-exported: the
    bucket_size,  # filter primitives lived here before core/operators.py)
    compact_frontier,
    next_pow2,
    run_bucket_ladder,
)
from repro.obs import trace

Array = jax.Array


@dataclass
class FrontierStats:
    """Work accounting for the frontier engine (benchmarks/cc_frontier).

    ``edges_touched`` counts edge-slot visits the way the paper's
    Table 4 counts kernel work: each SV round walks its edge buffer
    TWICE (one SV2 pass, one SV3 pass), each compaction writes the new
    buffer once (the live mask is a by-product of the round's own
    D[a]/D[b] gathers), and the sampling pre-pass streams the full edge
    list once to build its (n, k) table. The dense engine's same-metric
    cost is ``2 * m2 * rounds``.
    """

    rounds: int  # total SV rounds (pre-pass included)
    edges_touched: int  # per-phase edge-slot visits (see docstring)
    m2: int  # oriented edge count after dedup (dense walks this per phase)
    levels: list = field(default_factory=list)  # (buffer_size, rounds) pairs
    sample_rounds: int = 0
    live_after_sample: int = 0  # frontier size after the pre-pass
    largest_component_frac: float = 0.0  # node share of the Afforest giant

    def publish(self, registry=None, prefix: str = "cc.frontier") -> None:
        """Publish into the metrics registry (``repro.obs.metrics``)."""
        from repro.obs.metrics import publish_stats

        publish_stats(self, prefix, registry)


@partial(
    jax.jit,
    static_argnames=("n", "bound", "shrink_at", "hook_impl", "record_hooks"),
)
def _run_level(a, b, D, Q, s, aux, *, n, bound, shrink_at, hook_impl,
               record_hooks=False):
    """Run SV rounds at one fixed buffer size until convergence, the
    round bound, or (when ``shrink_at`` is set) the frontier mask drops
    to half the buffer -- whichever comes first. The mask is the round
    body's own SV3 compare (``with_frontier=True``), so watching it
    costs no extra edge passes; it is a superset of the truly-live
    edges, which only delays a shrink, never breaks one. ``aux`` (the
    hook-recording state when ``record_hooks``) is node-indexed, so it
    threads through level changes untouched by compaction."""
    body = sv_round_fns(a, b, n, hook_impl=hook_impl, with_frontier=True,
                        record_hooks=record_hooks)
    m = a.shape[0]

    def wrapped(carry):
        D, Q, aux, s, changed, fmask, rounds = carry
        D, Q, aux, s, changed, fmask = body(
            (D, Q, aux, s, changed, fmask)
        )
        return D, Q, aux, s, changed, fmask, rounds + 1

    def cond(carry):
        _D, _Q, _aux, s, changed, fmask, _rounds = carry
        keep = jnp.logical_and(changed, s <= bound)
        if shrink_at is not None:
            live = jnp.sum(fmask.astype(jnp.int32))  # elementwise only
            keep = jnp.logical_and(keep, live > shrink_at)
        return keep

    init = (
        D, Q, aux, s, jnp.bool_(True), jnp.ones((m,), jnp.bool_),
        jnp.int32(0),
    )
    D, Q, aux, s, changed, fmask, rounds = jax.lax.while_loop(
        cond, wrapped, init
    )
    return D, Q, aux, s, changed, fmask, rounds


@partial(jax.jit, static_argnames=("n", "k"))
def _build_samples(a, b, perm, *, n, k):
    """ONE streaming scatter pass over the 2m edges fills an (n, k)
    sampled-neighbor table (last write wins over a seeded permutation)."""
    m = a.shape[0]
    slot = jnp.arange(m, dtype=jnp.int32) % k
    tbl = jnp.full((n, k), -1, jnp.int32)
    return tbl.at[a[perm], slot].set(b[perm])


@partial(jax.jit, static_argnames=("n", "record_hooks"))
def _sample_round(neigh, D, Q, s, aux, *, n, record_hooks=False):
    """One SV round hooking every node through one sampled neighbor;
    nodes without a sample become inert self-loops. Sampled arcs are
    real graph edges, so hook recording stays valid in the pre-pass."""
    sa = jnp.arange(n, dtype=jnp.int32)
    sb = jnp.where(neigh >= 0, neigh, sa)
    body = sv_round_fns(sa, sb, n, record_hooks=record_hooks)
    D, Q, aux, s, changed = body((D, Q, aux, s, jnp.bool_(True)))
    return D, Q, aux, s, changed


@partial(jax.jit, static_argnames=("n",))
def _largest_component_frac(D, *, n):
    counts = jnp.zeros((n,), jnp.int32).at[D].add(1)
    return jnp.max(counts).astype(jnp.float32) / n


def frontier_shiloach_vishkin(
    src: Array,
    dst: Array,
    num_nodes: int,
    *,
    max_rounds: int | None = None,
    dedup: bool = True,
    sample_rounds: int = 0,
    min_bucket: int = 1024,
    hook_impl: str = "xla",
    seed: int = 0,
    record_hooks: bool = False,
    with_stats: bool = False,
):
    """Connected components over a shrinking active-edge frontier.

    Bit-exact vs ``shiloach_vishkin`` (labels AND rounds) when
    ``sample_rounds=0``; with a sampling pre-pass the labels are a
    correct partition with possibly different representatives. Returns
    (labels, rounds), or (labels, rounds, FrontierStats) when
    ``with_stats`` -- ``stats.edges_touched`` counts every edge slot
    walked by a round plus one buffer pass per compaction/sampling,
    the number the dense engine pays ``2m * rounds`` for.

    ``record_hooks=True`` inserts the spanning-forest hook record
    ``(hook_u, hook_v)`` after rounds in the return tuple (labels AND
    round counts stay bit-identical -- recording only reads the round
    state). Compaction cannot drop a future winner: a winning edge has
    differently-labeled endpoints at hook time, label equality is
    permanent, and the frontier mask keeps every unequal-label edge.
    """
    n = num_nodes
    check_choice("hook_impl", hook_impl, HOOK_IMPLS)
    src, dst = _maybe_dedup(src, dst, dedup)
    with trace.span("cc.upload"):
        src = jnp.asarray(src, jnp.int32).ravel()
        dst = jnp.asarray(dst, jnp.int32).ravel()
        a = jnp.concatenate([src, dst])
        b = jnp.concatenate([dst, src])
    m2 = int(a.shape[0])

    bound = (max_rounds if max_rounds is not None else sv_round_bound(n))
    bound += sample_rounds
    D = jnp.arange(n, dtype=jnp.int32)
    Q = jnp.zeros(n, jnp.int32)
    s = jnp.int32(1)
    aux = (init_hooks(n), jnp.int32(0)) if record_hooks else jnp.int32(0)
    stats = FrontierStats(rounds=0, edges_touched=0, m2=m2,
                          sample_rounds=sample_rounds)

    if sample_rounds > 0 and m2 > 0:
        with trace.span("cc.frontier.sample", k=sample_rounds) as sample_sp:
            with trace.span("cc.frontier.sample.permute"):
                rng = np.random.default_rng(seed)
                perm = jnp.asarray(rng.permutation(m2).astype(np.int32))
            samples = _build_samples(a, b, perm, n=n, k=sample_rounds)
            stats.edges_touched += m2  # the sampling pass streams all edges
            for t in range(sample_rounds):
                D, Q, aux, s, _changed = _sample_round(
                    samples[:, t], D, Q, s, aux, n=n,
                    record_hooks=record_hooks,
                )
                stats.edges_touched += 2 * n  # SV2 + SV3, n sampled edges
            if with_stats:  # O(n) scatter + host sync: only when asked for
                trace.count("host_sync")
                # repro-lint: disable=host-sync  (opt-in stats readback)
                stats.largest_component_frac = float(
                    _largest_component_frac(D, n=n)
                )
            # Compact straight away: drops ALL edges internal to the giant
            # (and to every other component the pre-pass already resolved).
            live_mask = D[a] != D[b]
            # The level-synchronous sync (paper sec. 4): the host must see
            # the live count to pick the next power-of-two bucket.
            trace.count("host_sync")
            live = int(jnp.sum(live_mask.astype(jnp.int32)))  # repro-lint: disable=host-sync
            stats.live_after_sample = live
            stats.edges_touched += m2  # full-list live scan (pre-pass rounds
            # walked only the sampled edges, so this mask needs its own pass)
            size = bucket_size(live, min_bucket=min_bucket, cap=m2)
            a, b = compact_frontier(a, b, live_mask, size=size)
            m2_level = size
            sample_sp.tag(live=live)
    else:
        m2_level = m2

    fmask = None
    # Spans attach at the per-LEVEL syncs the shrink ladder already pays
    # (the int()/bool() reads below); tags reuse those reads, so tracing
    # adds zero device round-trips (docs/observability.md). The ladder
    # itself is operators.run_bucket_ladder -- the engine only supplies
    # the level/compaction closures, so counters and sync sites are
    # unchanged by construction.
    with trace.span("cc.frontier", n=n, m2=m2) as run_sp:

        def sv_level(bucket, shrink_at):
            nonlocal D, Q, aux, s, fmask
            with trace.span("cc.frontier.level", bucket=bucket) as sp:
                D, Q, aux, s, changed, fmask, rounds = _run_level(
                    a, b, D, Q, s, aux,
                    n=n, bound=bound, shrink_at=shrink_at,
                    hook_impl=hook_impl, record_hooks=record_hooks,
                )
                # SV2 + SV3 passes; the Pallas hook kernel doesn't export
                # its compare mask, so that path pays a third (mask) pass
                # per round.
                passes = 2 if hook_impl == "xla" else 3
                # Per-level host syncs, not per-round: _run_level keeps
                # the inner SV iteration on device (lax.while_loop) and
                # the host reads one round count / convergence flag /
                # live count per LEVEL to drive the shrink ladder -- the
                # paper's level-synchronous design.
                trace.count("host_sync")
                level_rounds = int(rounds)  # repro-lint: disable=host-sync
                stats.edges_touched += passes * level_rounds * bucket
                stats.levels.append((bucket, level_rounds))
                trace.count("host_sync")
                converged = not bool(changed)  # repro-lint: disable=host-sync
                sp.tag(rounds=level_rounds, converged=converged)
            if converged:
                return True, False
            trace.count("host_sync")
            return False, int(s) > bound  # repro-lint: disable=host-sync

        def live_edges():
            # Shrink: the masked frontier fits the next power-of-two
            # bucket.
            trace.count("host_sync")
            return int(jnp.sum(fmask.astype(jnp.int32)))  # repro-lint: disable=host-sync

        def charge_shrink(new_size):
            # The mask came out of this level's last SV3 pass; only the
            # gather-write of the surviving edges into the new buffer is
            # extra work.
            stats.edges_touched += new_size

        def shrink(new_size):
            nonlocal a, b
            a, b = compact_frontier(a, b, fmask, size=new_size)

        def bound_hit():
            # The level loop ran out of round budget with hooks still
            # flowing: labels would be wrong, so fail loudly (the
            # convergence sentinel; see core.components.ConvergenceError).
            raise ConvergenceError(
                f"frontier_shiloach_vishkin hit its round bound ({bound}"
                f"{f', incl. {sample_rounds} sampling rounds' if sample_rounds else ''})"
                f" before the label fixpoint on {n} nodes; raise max_rounds"
            )

        run_bucket_ladder(
            bucket=m2_level, min_bucket=min_bucket, run_level=sv_level,
            live_count=live_edges, compact=shrink, on_shrink=charge_shrink,
            on_nonconverged=bound_hit,
        )
        with trace.span("cc.compress"):
            D = sv_compress(D, n)
        # Terminal readback: the loop above already synced on s per level.
        trace.count("host_sync")
        rounds_total = int(s) - 1  # repro-lint: disable=host-sync
        run_sp.tag(rounds=rounds_total, levels=len(stats.levels))
    stats.rounds = rounds_total
    out = (D, jnp.int32(rounds_total))
    if record_hooks:
        hooks, _inner = aux
        out = out + (hooks,)
    if with_stats:
        out = out + (stats,)
    return out
