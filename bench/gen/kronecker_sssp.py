"""Graph500 kernel 3 inputs: the Kronecker graph, its edge weights and
its search keys, all from the configuration's ``structure_seed``.

``kronecker.generate(params, structure_seed)`` makes the graph, each
edge's weight and the ``roots`` search keys in their order; the run's
seed then only permutes ``(src, dst, weights)`` together. Every seed
therefore times the same weighted multigraph and the same sequence of
roots, in another edge order: the same frontier levels and buckets,
since the distances and the live arc counts do not depend on the order.
"""
from __future__ import annotations

import numpy as np

import kronecker


def generate(params: dict, seed: int) -> dict:
    out = kronecker.generate(params, int(params["assumed"]["structure_seed"]))
    order = np.random.default_rng(seed).permutation(len(out["src"]))
    for k in ("src", "dst", "weights"):
        out[k] = out[k][order]
    return out
