"""PageRank as an advance/filter/compute composition (~50 lines).

The proof-of-unlock for ``core/operators.py``: where CC and SSSP ride
the MIN monoid, PageRank is the repo's first ADD-monoid workload --
push-style mass propagation, ``r' = (1-d) * t + d * sum_{(u,v)} w(u,v)
* r[u] / deg(u)`` over the undirected 2m arc walk -- and the whole
algorithm is one ``advance`` (scatter-add of out-mass), one ``compute``
(per-node out-mass split), and the shared ``run_rebuild_loop`` driver.
An ADD frontier cannot skip edges (every contribution is part of the
sum -- see docs/operators.md), so the filter here gates *termination*
only: the tolerance mask ``|r' - r| > tol`` is the live set.

**Exactness.** Everything is float32, and every multiply is rounded
separately before the scatter-add folds contributions in edge-slot
order (the teleport term is the scatter's *base*, not a post-add --
that keeps XLA from contracting a multiply-add into an FMA, which
would unpin the serial oracle). ``core.serial.serial_pagerank``
mirrors the exact op sequence with ``np.add.at``, whose accumulation
order matches the XLA scatter-add on the CPU backend, so engine
scores are bit-identical to the oracle, iteration for iteration (on a
TPU they agree within float32 rounding, not bit for bit).
Per-node ``teleport`` vectors make the serve path's disjoint-union
packing decompose: a request's slice of the packed union sees exactly
its solo teleport mass, pad nodes carry zero and stay zero. Dangling
mass (weighted degree 0) leaks by design -- redistribution would
couple packed requests through a global sum.

Two engines share the iteration body (bit-identical trajectories):

* ``frontier`` -- the host tolerance loop on ``run_rebuild_loop``:
  iterate until no node moves more than ``tol``, ``ConvergenceError``
  at the iteration bound (``pagerank_iter_bound``).
* ``dense`` -- fixed ``num_iters`` iterations in one traceable
  ``lax.fori_loop``: one compile per shape, no per-iteration host
  sync, and -- because the iteration count is data-independent --
  batched disjoint unions stay bit-exact vs solo runs. This is the
  serve path's engine (``kind="pagerank"`` waves): damping and
  iteration count are wave-uniform engine knobs there, never
  per-request, precisely so packing cannot change any member's bits.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.components import ConvergenceError, check_choice
from repro.core.operators import ADD, advance, compute, run_rebuild_loop
from repro.obs import trace

Array = jax.Array

# pagerank(engine=) choices (RL004: registered as "pagerank_engine" in
# tools/lint/passes/choice_set.py; docs/engines.md choice-matrix).
PAGERANK_ENGINES = ("auto", "frontier", "dense")

DEFAULT_DAMPING = 0.85
DEFAULT_TOL = 1e-6


def pagerank_iter_bound(
    damping: float = DEFAULT_DAMPING, tol: float = DEFAULT_TOL
) -> int:
    """Iteration ceiling for the tolerance loop: per-node scores are
    bounded by the total mass (<= 1) and the update contracts by
    ``damping`` per iteration, so the residual undercuts ``tol`` within
    ``log(tol * (1 - damping)) / log(damping)`` iterations. Also the
    dense engine's default ``num_iters``."""
    if not 0.0 < damping < 1.0:
        raise ValueError(f"damping must be in (0, 1), got {damping}")
    if not tol > 0.0:
        raise ValueError(f"tol must be > 0, got {tol}")
    return max(
        int(math.ceil(math.log(tol * (1.0 - damping)) / math.log(damping)))
        + 1,
        1,
    )


@dataclass
class PageRankStats:
    """Work accounting (benchmarks/pagerank). ``edges_touched`` counts
    edge-slot visits like ``SsspStats.relax_visits``: the degree pass
    walks the 2m arcs once, then every iteration gathers + scatters all
    of them (an ADD frontier never compacts -- module docstring), so
    the total is ``m2 * (iterations + 1)`` on both engines."""

    iterations: int
    edges_touched: int
    m2: int  # oriented arc count (every iteration walks all of it)
    levels: list = field(default_factory=list)  # live (>tol) nodes per iter

    def publish(self, registry=None, prefix: str = "pagerank.frontier") -> None:
        """Publish into the metrics registry (``repro.obs.metrics``)."""
        from repro.obs.metrics import publish_stats

        publish_stats(self, prefix, registry)


def _prep_mass_edges(src, dst, weights):
    """Both-orientation (a, b, w2) arc arrays. Unlike SSSP's prep,
    +inf is rejected too: mass MULTIPLIES along edges, so a non-finite
    weight poisons every score it can reach (0 * inf = NaN)."""
    src = jnp.asarray(src, jnp.int32).ravel()
    dst = jnp.asarray(dst, jnp.int32).ravel()
    if weights is None:
        w = jnp.ones(src.shape, jnp.float32)
    else:
        wh = np.asarray(weights, np.float32).ravel()
        if not np.isfinite(wh).all():
            raise ValueError("pagerank weights must be finite")
        if (wh < 0).any():
            raise ValueError("pagerank weights must be >= 0")
        w = jnp.asarray(wh)
    if w.shape != src.shape:
        raise ValueError(
            f"weights length {w.shape[0]} != edge count {src.shape[0]}"
        )
    return (
        jnp.concatenate([src, dst]),
        jnp.concatenate([dst, src]),
        jnp.concatenate([w, w]),
    )


@jax.jit
def _degrees(a, w2, t):
    """Weighted out-degree per node (ADD-monoid advance of the weight
    lane; ``t`` only supplies the (n,) float32 shape)."""
    return advance(jnp.zeros_like(t), a, w2, monoid=ADD)


def _mass_step(a, b, w2, deg, t, r, dmp, omd):
    """One push iteration: compute per-node out-mass, advance it along
    every arc under ADD *onto the teleport base* ``(1-d) * t`` -- the
    base-not-post-add form that keeps every multiply separately rounded
    (no FMA contraction), which is what pins the NumPy oracle."""
    out = compute(
        lambda ri, di: jnp.where(di > 0, ri / di, 0.0), r, deg
    )
    return advance(omd * t, b, dmp * (out[a] * w2), monoid=ADD)


@jax.jit
def _pr_iterate(a, b, w2, deg, t, r, dmp, omd, tol):
    """One host-loop iteration: new scores + the tolerance filter mask
    (the ADD frontier's live set -- gates termination, not the walk)."""
    new = _mass_step(a, b, w2, deg, t, r, dmp, omd)
    return new, jnp.abs(new - r) > tol


@partial(jax.jit, static_argnames=("num_iters",))
def _pr_fixed(a, b, w2, deg, t, r0, dmp, omd, *, num_iters):
    """``num_iters`` iterations in one fori_loop: the traceable dense
    engine, bit-identical to the host loop's first ``num_iters`` steps."""
    return jax.lax.fori_loop(
        0,
        num_iters,
        lambda _, r: _mass_step(a, b, w2, deg, t, r, dmp, omd),
        r0,
    )


def _prep_teleport(teleport, n: int):
    if teleport is None:
        return jnp.full((n,), 1.0 / n, jnp.float32)
    th = np.asarray(teleport, np.float32).ravel()
    if th.shape != (n,):
        raise ValueError(f"teleport shape {th.shape} != ({n},)")
    if not np.isfinite(th).all() or (th < 0).any():
        raise ValueError("teleport mass must be finite and >= 0")
    return jnp.asarray(th)


def pagerank(
    src: Array,
    dst: Array,
    weights: Array | None = None,
    num_nodes: int | None = None,
    *,
    damping: float = DEFAULT_DAMPING,
    tol: float = DEFAULT_TOL,
    teleport: Array | None = None,
    num_iters: int | None = None,
    max_rounds: int | None = None,
    engine: str = "auto",
    with_stats: bool = False,
):
    """Weighted PageRank over the undirected 2m arc walk. Returns
    ``(scores, iterations)`` -- float32 scores, int32 iteration count
    -- plus ``PageRankStats`` when ``with_stats``. ``weights=None``
    means unit weights; ``teleport`` (default uniform ``1/n``) is the
    per-node restart mass. Dangling mass leaks (module docstring).

    ``engine=`` -- ``"auto"`` (default), ``"frontier"``, ``"dense"``
    (full matrix: ``docs/engines.md``, knob ``pagerank_engine``):

    * ``"auto"``: the frontier tolerance loop, except under a
      ``jax.jit`` trace, where the host-driven loop is impossible and
      the fully-traceable fixed-iteration dense engine runs instead.
    * ``"frontier"``: iterate until every node moves <= ``tol``;
      ``max_rounds`` (default ``pagerank_iter_bound(damping, tol)``)
      is the ``ConvergenceError`` bound. Rejects ``num_iters``.
    * ``"dense"``: exactly ``num_iters`` iterations (default
      ``pagerank_iter_bound(damping, tol)``), one compile per shape,
      no per-iteration sync -- the serve path's engine. ``max_rounds``
      below ``num_iters`` caps the iterations and then *checks*: a
      still-moving score vector raises ``ConvergenceError`` (the serve
      chaos harness's real nonconvergence sentinel; under a trace the
      check is skipped -- a device value cannot raise).
    """
    if num_nodes is None:
        raise TypeError("pagerank requires num_nodes")
    from repro.compat import is_tracer

    n = int(num_nodes)
    check_choice("pagerank_engine", engine, PAGERANK_ENGINES)
    bound = (
        max_rounds if max_rounds is not None
        else pagerank_iter_bound(damping, tol)
    )
    dmp = np.float32(damping)
    omd = np.float32(1.0) - dmp  # oracle computes 1 - d the same way
    tolv = np.float32(tol)
    a, b, w2 = _prep_mass_edges(src, dst, weights)
    m2 = int(a.shape[0])
    t = _prep_teleport(teleport, n)
    tracing = is_tracer(src) or is_tracer(dst) or is_tracer(weights)
    if engine == "auto":
        engine = "dense" if tracing else "frontier"
    deg = _degrees(a, w2, t)
    r = t  # iteration 0 state: all mass at its teleport slot
    stats = PageRankStats(iterations=0, edges_touched=m2, m2=m2)

    if engine == "dense":
        iters = (
            num_iters if num_iters is not None
            else pagerank_iter_bound(damping, tol)
        )
        run_iters = min(iters, bound) if max_rounds is not None else iters
        with trace.span(
            "pagerank.dense", device=True, n=n, m2=m2, iters=run_iters,
        ) as sp:
            r = _pr_fixed(a, b, w2, deg, t, r, dmp, omd,
                          num_iters=run_iters)
            if not is_tracer(r):
                sp.block_on(r)
        if max_rounds is not None and run_iters < iters and not is_tracer(r):
            # The budget cut the fixed schedule short: probe one extra
            # iteration and fail loudly if scores are still moving (the
            # convergence sentinel; core.components.ConvergenceError).
            _new, mask = _pr_iterate(a, b, w2, deg, t, r, dmp, omd, tolv)
            live = int(jnp.sum(mask.astype(jnp.int32)))  # repro-lint: disable=host-sync
            if live:
                raise ConvergenceError(
                    f"pagerank hit its iteration budget ({bound}) with "
                    f"{live} nodes still above tol={tol} on {n} nodes; "
                    f"raise max_rounds (the tolerance bound is "
                    f"pagerank_iter_bound={pagerank_iter_bound(damping, tol)})"
                )
        stats.iterations = run_iters
        stats.edges_touched += m2 * run_iters
        out = (r, jnp.int32(run_iters))
        return out + (stats,) if with_stats else out

    if tracing:
        raise ValueError(
            "the frontier PageRank engine's tolerance loop is "
            "host-driven and cannot run inside jit; call it outside "
            "jit or use engine='dense'"
        )
    if num_iters is not None:
        raise ValueError(
            "num_iters= is a dense-engine option (fixed schedule); the "
            "frontier engine iterates to tol -- use engine='dense'"
        )
    live_mask = None
    # Spans attach at the per-iteration syncs the tolerance loop
    # already pays (the int() live reads) -- same policy as cc.frontier.
    with trace.span("pagerank.frontier", n=n, m2=m2) as run_sp:

        def live_nodes():
            if live_mask is None:
                return n  # every node is live before the first push
            # The level-synchronous sync: the host reads the tolerance
            # filter's live count to decide termination.
            return int(jnp.sum(live_mask.astype(jnp.int32)))  # repro-lint: disable=host-sync

        def push_level(live):
            nonlocal r, live_mask
            with trace.span("pagerank.level", live=live):
                r, live_mask = _pr_iterate(
                    a, b, w2, deg, t, r, dmp, omd, tolv
                )
            stats.edges_touched += m2
            stats.levels.append(live)

        def bound_hit(live, _rounds):
            raise ConvergenceError(
                f"pagerank hit its iteration bound ({bound}) with "
                f"{live} nodes still above tol={tol} on {n} nodes; "
                f"raise max_rounds (the tolerance bound is "
                f"pagerank_iter_bound={pagerank_iter_bound(damping, tol)})"
            )

        iters = run_rebuild_loop(
            bound=bound, live_count=live_nodes, run_level=push_level,
            on_bound=bound_hit,
        )
        run_sp.tag(iterations=iters)
    stats.iterations = iters
    out = (r, jnp.int32(iters))
    return out + (stats,) if with_stats else out
