"""Plain reference for connected components, and its control.

Hook-and-compress in NumPy, sharing no code with the library: every
root hooks onto the smallest root across its crossing edges, then all
paths compress; repeat until no edge crosses two roots. Parents only
ever decrease, so each component ends labelled with its smallest vertex
id. The control is the same procedure stopped one round before its
fixpoint: the shortcut of a capped round count, which leaves labels
that are not yet a partition into components.

The numbers compared (``compare``) are exact counts, so their limit
is 0.
"""
from __future__ import annotations

import numpy as np


def hook_compress(src, dst, n: int, max_rounds: int | None = None):
    """(labels, rounds): labels after at most ``max_rounds`` hooking
    rounds (None: to the fixpoint), and the rounds that hooked."""
    a = np.asarray(src, np.int64).ravel()
    b = np.asarray(dst, np.int64).ravel()
    parent = np.arange(n, dtype=np.int64)
    rounds = 0
    while max_rounds is None or rounds < max_rounds:
        ra, rb = parent[a], parent[b]
        cross = ra != rb
        if not cross.any():
            break
        a, b, ra, rb = a[cross], b[cross], ra[cross], rb[cross]
        np.minimum.at(parent, np.maximum(ra, rb), np.minimum(ra, rb))
        while True:
            pp = parent[parent]
            if np.array_equal(pp, parent):
                break
            parent = pp
        rounds += 1
    return parent, rounds


def canonical(labels, n: int) -> np.ndarray | None:
    """Each vertex's label replaced by the smallest vertex id that
    carries the same label, so that two labellings of one partition
    compare equal; None where a label is no vertex id at all."""
    labels = np.asarray(labels).astype(np.int64).ravel()
    if labels.shape != (n,) or (n and (labels.min() < 0 or labels.max() >= n)):
        return None
    first = np.full(n, n, np.int64)
    np.minimum.at(first, labels, np.arange(n, dtype=np.int64))
    return first[labels]


def reference(inputs: dict, calls: list) -> np.ndarray:
    labels, _ = hook_compress(inputs["src"], inputs["dst"], inputs["num_nodes"])
    return labels


def control(inputs: dict, calls: list, ref) -> list:
    """The reference one round short of its fixpoint, in the library's
    output form ``(labels, rounds)``, once per call."""
    n = inputs["num_nodes"]
    _, rounds = hook_compress(inputs["src"], inputs["dst"], n)
    short, done = hook_compress(inputs["src"], inputs["dst"], n,
                                max_rounds=max(rounds - 1, 0))
    return [(short, done)] * len(calls)


def compare(inputs: dict, calls: list, outputs: list, ref) -> tuple[dict, int]:
    """({"wrong_labels": the most vertices any call labelled into the
    wrong component}, number of calls with any such vertex)."""
    n = inputs["num_nodes"]
    worst, failed = 0, 0
    for out in outputs:
        got = canonical(out[0], n)
        wrong = n if got is None else int(np.count_nonzero(got != ref))
        worst = max(worst, wrong)
        failed += wrong > 0
    return {"wrong_labels": worst}, failed
