"""Spanning-forest extraction from Shiloach-Vishkin hook decisions.

Hooking-based connectivity produces a spanning forest as a by-product
(Hong, Dhulipala & Shun 2020): every hook event attaches one tree to
another through a real graph edge, a component of size c hooks exactly
c - 1 times, and min-CRCW hooks always point label-decreasing, so the
recorded edges are acyclic. ``repro.core.components.sv_round_fns``
records those winning edges when ``record_hooks=True`` (see
``init_hooks``); this module turns the raw ``(hook_u, hook_v)`` slots
into a compact forest object the tour layer consumes.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np

from repro.obs import trace


@dataclass
class SpanningForest:
    """A spanning forest of the input graph, one tree per component.

    ``edge_u``/``edge_v`` are the ``num_nodes - num_trees`` winning hook
    edges (each a real input edge); ``labels`` are the CC labels, i.e.
    the minimum node id of each component, which the tour layer uses as
    the canonical tree roots.
    """

    num_nodes: int
    labels: np.ndarray  # (n,) component root ids (min node id)
    rounds: int
    edge_u: np.ndarray  # (f,) forest edge endpoints
    edge_v: np.ndarray  # (f,)

    @property
    def num_edges(self) -> int:
        return int(self.edge_u.shape[0])

    @property
    def num_trees(self) -> int:
        return self.num_nodes - self.num_edges


def forest_from_hooks(
    hook_u, hook_v, labels, rounds, num_nodes: int
) -> SpanningForest:
    """Compact raw ``(hook_u, hook_v)`` slot arrays (sentinel n = never
    hooked) into a ``SpanningForest`` (host-side)."""
    if trace.enabled():  # one host sync per device value read below
        trace.count("host_sync", sum(
            isinstance(x, jax.Array) for x in (hook_u, hook_v, labels, rounds)
        ))
    hu = np.asarray(hook_u)
    hv = np.asarray(hook_v)
    mask = hu < num_nodes
    return SpanningForest(
        num_nodes=num_nodes,
        labels=np.asarray(labels),
        rounds=int(rounds),
        edge_u=hu[mask].astype(np.int32),
        edge_v=hv[mask].astype(np.int32),
    )


def spanning_forest(
    src,
    dst,
    num_nodes: int,
    *,
    max_rounds: int | None = None,
    mesh=None,
    engine: str = "auto",
    **kwargs,
) -> SpanningForest:
    """Connected components + spanning forest in one CC run.

    Thin wrapper over ``repro.core.connected_components(...,
    record_hooks=True)``: ``engine=`` (``"auto"`` default /
    ``"frontier"`` / ``"dense"`` / ``"sharded_frontier"``), ``mesh=``,
    ``max_rounds=``, and every engine kwarg (``min_bucket=``,
    ``hook_impl=``, ``exchange=``, ``sparse_capacity=``, ``axis=``,
    ``sample_rounds=``, ``seed=``, ``dedup=``) behave exactly as there
    -- see ``docs/engines.md`` for the full matrix -- and the
    labels/round counts are bit-identical to a plain CC call: hook
    recording only *reads* the round state. The recorded forest is
    itself engine-independent (ties break to the lexicographically
    smallest edge), except under a sampling pre-pass (``sample_rounds``)
    which hooks through sampled edges -- still a valid spanning forest,
    but a different one.
    """
    from repro.core import connected_components

    if kwargs.pop("record_hooks", True) is not True:
        raise ValueError("spanning_forest always records hooks")
    res = connected_components(
        src, dst, num_nodes, max_rounds=max_rounds, mesh=mesh,
        engine=engine, record_hooks=True, **kwargs,
    )
    labels, rounds, (hook_u, hook_v) = res[0], res[1], res[2]
    return forest_from_hooks(hook_u, hook_v, labels, rounds, num_nodes)
