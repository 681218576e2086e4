"""Graph500 Kronecker graphs, generated on the device.

The generator of the Graph500 specification (v3): each of the
``edgefactor * 2**scale`` edges picks one quadrant of the adjacency
matrix per level with probabilities A, B, C and D = 1 - A - B - C, then
the vertex ids are permuted and the edge list is shuffled. The quadrant
draws and the permutation come from the configuration's
``structure_seed``, the shuffle from the run's seed. Every seed therefore
gets the same graph in another edge order: the same shapes and the same
work, since the vertex labelling, which steers how hooking proceeds, is
fixed.

Optional traffic parameters (from the workload file):

* ``weights: true`` -- float32 edge weights, multiples of 2**-24 in
  [0, 1) (exact in float32, never subnormal), from the run's seed;
* ``roots: k`` -- k source vertices of degree >= 1, from the run's seed.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def key_from_seed(seed: int):
    """A PRNG key from any non-negative seed, wider ones included."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@partial(jax.jit, static_argnames=("scale", "edgefactor", "a", "b", "c",
                                   "weights"))
def _kronecker(struct_key, run_key, *, scale, edgefactor, a, b, c, weights):
    m = edgefactor << scale
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    ij = jnp.zeros((2, m), jnp.int32)
    *levels, kp = jax.random.split(struct_key, scale + 1)
    for level, k in enumerate(levels):
        ki, kj = jax.random.split(k)
        ii = jax.random.uniform(ki, (m,)) > ab
        jj = jax.random.uniform(kj, (m,)) > jnp.where(ii, c_norm, a_norm)
        ij = ij + (jnp.stack([ii, jj]).astype(jnp.int32) << level)
    ke, kw = jax.random.split(run_key)
    perm = jax.random.permutation(kp, 1 << scale).astype(jnp.int32)
    order = jax.random.permutation(ke, m)
    ij = perm[ij][:, order]
    w = None
    if weights:
        ints = jax.random.randint(kw, (m,), 0, 1 << 24)
        w = ints.astype(jnp.float32) * jnp.float32(2.0 ** -24)
    return ij, w


def generate(params: dict, seed: int) -> dict:
    """Host NumPy inputs of one run: ``src``, ``dst`` (int32),
    ``num_nodes``, and ``weights`` / ``roots`` where asked for."""
    scale = int(params["scale"])
    ij, w = _kronecker(
        key_from_seed(int(params["assumed"]["structure_seed"])),
        key_from_seed(seed),
        scale=scale, edgefactor=int(params["edgefactor"]),
        a=float(params["A"]), b=float(params["B"]), c=float(params["C"]),
        weights=bool(params.get("weights", False)),
    )
    ij = np.asarray(ij)
    n = 1 << scale
    out = {"src": ij[0].copy(), "dst": ij[1].copy(), "num_nodes": n}
    if w is not None:
        out["weights"] = np.asarray(w)
    k = int(params.get("roots", 0))
    if k:
        deg = np.bincount(ij[0], minlength=n) + np.bincount(ij[1], minlength=n)
        live = np.flatnonzero(deg > 0)
        rng = np.random.default_rng(seed)
        out["roots"] = rng.choice(live, size=min(k, len(live)),
                                  replace=False).astype(np.int32)
    return out
