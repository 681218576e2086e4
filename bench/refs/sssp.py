"""Plain reference for single-source shortest paths, and its control.

Binary-heap Dijkstra in float32, one edge added at a time (the order of
operations a relax-min engine performs, so distances are bit-exact),
and the deterministic parent rule ``parent[v] = min{u != v : dist[u] +
w(u, v) == dist[v]}``, ``parent[source] = source``, ``-1`` where
unreachable. The control stops the relaxation one settled vertex short
of the end: the last vertex Dijkstra would settle keeps the distance it
had when the heap still held it, as an early exit would leave it.

Dijkstra in Python is slow on large graphs, so only up to
``CHECKED_ROOTS`` distinct roots, drawn from the run's seed by order of
the calls, are compared.
"""
from __future__ import annotations

import heapq

import numpy as np

CHECKED_ROOTS = 3


def _arcs(src, dst, weights):
    u = np.concatenate([src, dst]).astype(np.int64)
    v = np.concatenate([dst, src]).astype(np.int64)
    w = np.concatenate([weights, weights]).astype(np.float32)
    return u, v, w


def dijkstra(src, dst, weights, n: int, source: int):
    """(dist, parent) from ``source``."""
    u, v, w = _arcs(src, dst, weights)
    adj: list[list] = [[] for _ in range(n)]
    for ui, vi, wi in zip(u.tolist(), v.tolist(), w):
        adj[ui].append((vi, wi))
    dist = np.full(n, np.inf, np.float32)
    dist[source] = np.float32(0.0)
    heap = [(np.float32(0.0), source)]
    done = np.zeros(n, bool)
    while heap:
        _, x = heapq.heappop(heap)
        if done[x]:
            continue
        done[x] = True
        for y, wy in adj[x]:
            nd = np.float32(dist[x] + wy)
            if nd < dist[y]:
                dist[y] = nd
                heapq.heappush(heap, (nd, y))
    return dist, parents(u, v, w, dist, source)


def parents(u, v, w, dist, source: int) -> np.ndarray:
    n = len(dist)
    parent = np.full(n, n, np.int64)
    ok = (u != v) & ((dist[u] + w).astype(np.float32) == dist[v])
    np.minimum.at(parent, v[ok], u[ok])
    parent[parent == n] = -1
    parent[np.isinf(dist)] = -1
    parent[source] = source
    return parent


def _roots(calls: list) -> list[int]:
    seen = []
    for kw in calls:
        r = int(kw["sources"])
        if r not in seen:
            seen.append(r)
        if len(seen) == CHECKED_ROOTS:
            break
    return seen


def reference(inputs: dict, calls: list) -> dict:
    return {r: dijkstra(inputs["src"], inputs["dst"], inputs["weights"],
                        inputs["num_nodes"], r) for r in _roots(calls)}


def bellman_ford_short(src, dst, weights, n: int, source: int):
    """(dist, parent) of Bellman-Ford one round short of its fixpoint."""
    u, v, w = _arcs(src, dst, weights)
    dist = np.full(n, np.inf, np.float32)
    dist[source] = np.float32(0.0)
    history = [dist]
    while True:
        nxt = dist.copy()
        np.minimum.at(nxt, v, (dist[u] + w).astype(np.float32))
        if np.array_equal(nxt, dist):
            break
        dist = nxt
        history.append(dist)
    short = history[max(len(history) - 2, 0)]
    return short, parents(u, v, w, short, source)


def control(inputs: dict, calls: list, ref) -> list:
    out = []
    for kw in calls:
        r = int(kw["sources"])
        if r in ref:
            d, p = bellman_ford_short(inputs["src"], inputs["dst"],
                                      inputs["weights"], inputs["num_nodes"], r)
        else:  # not compared
            d = p = None
        out.append((d, p, 0))
    return out


def compare(inputs: dict, calls: list, outputs: list, ref) -> tuple[dict, int]:
    """({"wrong_dist": most vertices whose distance differs in one
    checked call, "wrong_parent": likewise for parents}, calls with
    either)."""
    wd = wp = failed = 0
    for kw, out in zip(calls, outputs):
        r = int(kw["sources"])
        if r not in ref:
            continue
        d, p = np.asarray(out[0]), np.asarray(out[1])
        want_d, want_p = ref[r]
        bad_d = int(np.count_nonzero(d != want_d)) if d.shape == want_d.shape else len(want_d)
        bad_p = int(np.count_nonzero(p != want_p)) if p.shape == want_p.shape else len(want_p)
        wd, wp = max(wd, bad_d), max(wp, bad_p)
        failed += (bad_d + bad_p) > 0
    return {"wrong_dist": wd, "wrong_parent": wp}, failed
