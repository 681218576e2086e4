"""Plain reference for Graph500 kernel 3 at the benchmark's size, and
its control.

Distances come from a synchronous float32 Bellman-Ford in NumPy: each
round relaxes all 2m arcs (both orientations of every edge, duplicates
and self-loops kept) from the last round's distances with one
``np.minimum.at``, until a round changes nothing. Float addition is
monotonic and each candidate is a single addition, so this fixpoint is
the exact float32 answer of every relax-min order. Parents follow the
min-id rule of ``sssp.parents``: ``parent[v] = min{u != v : dist[u] +
w(u, v) == dist[v]}``, ``parent[source] = source``, ``-1`` where
unreachable. Nothing of the program is imported.

Every call of the window is compared. The first call of each of the
first ``CHECKED_ROOTS`` distinct roots, in call order, is compared with
the reference; every later call of a root is compared with that root's
first output, bit for bit. The first output of a root beyond those is
not checked: the reference takes a second or two a root on one core
at scale 18, and the run's budget holds no more. The roots run in threads, since NumPy
does much of this work outside the interpreter lock.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import sssp
from sssp import parents

CHECKED_ROOTS = 16

# The control: Bellman-Ford one round short of its fixpoint, for the
# checked roots, in the program's place.
control = sssp.control


def bellman_ford(u, v, w, n: int, source: int) -> np.ndarray:
    """float32 distances from ``source`` over the arcs ``(u, v, w)``."""
    dist = np.full(n, np.inf, np.float32)
    dist[source] = np.float32(0.0)
    while True:
        nxt = dist.copy()
        np.minimum.at(nxt, v, dist[u] + w)
        if np.array_equal(nxt, dist):
            return dist
        dist = nxt


def checked_roots(calls: list) -> list[int]:
    seen = []
    for kw in calls:
        r = int(kw["sources"])
        if r not in seen:
            seen.append(r)
            if len(seen) == CHECKED_ROOTS:
                break
    return seen


def reference(inputs: dict, calls: list) -> dict:
    """{root: (dist, parent)} for the checked roots."""
    n = inputs["num_nodes"]
    src, dst = inputs["src"], inputs["dst"]
    u = np.concatenate([src, dst]).astype(np.int64)
    v = np.concatenate([dst, src]).astype(np.int64)
    w = np.concatenate([inputs["weights"]] * 2).astype(np.float32)

    def solve(r):
        dist = bellman_ford(u, v, w, n, r)
        return dist, parents(u, v, w, dist, r)

    roots = checked_roots(calls)
    with ThreadPoolExecutor(max_workers=os.cpu_count()) as pool:
        return dict(zip(roots, pool.map(solve, roots)))


def _wrong(got, want, bits: bool) -> int:
    """Entries of ``got`` that differ from ``want`` (float32 bit
    patterns where ``bits``); all of them where the shapes differ."""
    if got is None or want is None:
        return 0 if got is want else len(want if got is None else got)
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        return max(got.size, want.size)
    if bits:
        got = got.astype(np.float32).view(np.uint32)
        want = want.astype(np.float32).view(np.uint32)
    return int(np.count_nonzero(got != want))


def compare(inputs: dict, calls: list, outputs: list, ref) -> tuple[dict, int]:
    """({"wrong_dist": most vertices whose distance differs in one call,
    "wrong_parent": likewise for parents}, calls with either)."""
    wd = wp = failed = 0
    first = {}
    for kw, out in zip(calls, outputs):
        r = int(kw["sources"])
        got = (out[0], out[1])
        if r in first:
            want = first[r]
        else:
            first[r] = got
            want = ref.get(r)
        if want is None:
            continue
        bad_d = _wrong(got[0], want[0], bits=True)
        bad_p = _wrong(got[1], want[1], bits=False)
        wd, wp = max(wd, bad_d), max(wp, bad_p)
        failed += (bad_d + bad_p) > 0
    return {"wrong_dist": wd, "wrong_parent": wp}, failed
