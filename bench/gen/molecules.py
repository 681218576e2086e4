"""Molecule-shaped graph requests and their Poisson arrivals, in bulk.

Each molecule is a random spanning tree (atom j bonds to one of atoms
j-1, j-2, j-3, so chains with short branches) plus ring-closing bonds
from a random atom other than the first to its ancestor five bonds up
(or the first atom, where the chain is shorter). Atom counts follow a
rounded log-normal with the configuration's mean; ring closures are
Poisson with mean ``mean_bonds - mean_atoms + 1`` (the cycle rank that
makes the mean bond count right), at most ``atoms // 4``. The atom and
ring counts of a stream of a given length come from the configuration's
``structure_seed``, the same in every run; the run's seed orders them
and draws the bonds, so every seed offers the same work in another
order. Everything is drawn in a few vectorised NumPy passes, so tens of
thousands of requests cost milliseconds.
"""
from __future__ import annotations

import math

import numpy as np


def arrivals(rate: float, seconds: float, rng, structure_seed: int) -> np.ndarray:
    """Due times (seconds from the window's start) of a Poisson stream
    at ``rate`` requests per second over ``[0, seconds)``, conditioned on
    its mean count: given N arrivals in a window, a Poisson process
    places them as N sorted uniform draws. The N + 1 gaps between those
    draws are exchangeable, so they come from ``structure_seed`` and the
    run's seed orders them: every seed offers the same gaps, in another
    order."""
    count = int(round(rate * seconds))
    due = np.sort(np.random.default_rng(structure_seed).uniform(0.0, seconds, size=count))
    gaps = np.diff(np.concatenate([[0.0], due, [seconds]]))
    return np.cumsum(rng.permutation(gaps))[:count]


def generate(params: dict, count: int, rng) -> dict:
    """``count`` molecules as flat arrays: ``num_nodes`` (count,),
    ``edge_ptr`` (count + 1,) into ``src``/``dst`` (request-local atom
    ids, int32)."""
    a = params["assumed"]
    sizes = np.random.default_rng(int(a["structure_seed"]))
    sigma = float(a["sigma"])
    mu = math.log(float(params["mean_atoms"])) - sigma * sigma / 2
    n = np.rint(np.exp(sizes.normal(mu, sigma, size=count))).astype(np.int64)
    n = np.clip(n, int(a["min_atoms"]), int(a["max_atoms"]))
    rings_mean = float(params["mean_bonds"]) - float(params["mean_atoms"]) + 1
    rings = np.minimum(sizes.poisson(rings_mean, size=count), n // 4)
    order = rng.permutation(count)  # the run's seed orders the sizes
    n, rings = n[order], rings[order]

    # Spanning trees over all molecules at once, in global atom ids.
    node_off = np.concatenate([[0], np.cumsum(n)])
    total = int(node_off[-1])
    mol = np.repeat(np.arange(count), n)
    local = np.arange(total) - node_off[mol]
    back = 1 + np.floor(rng.random(total) * np.minimum(local, 3)).astype(np.int64)
    parent = np.where(local > 0, np.arange(total) - back, np.arange(total))

    # Ring closures: a random atom and its ancestor five bonds up.
    rmol = np.repeat(np.arange(count), rings)
    atom = node_off[rmol] + 1 + np.floor(
        rng.random(len(rmol)) * (n[rmol] - 1)).astype(np.int64)
    anc = atom
    for _ in range(5):
        anc = parent[anc]

    tree = local > 0
    emol = np.concatenate([mol[tree], rmol])
    eu = np.concatenate([np.arange(total)[tree], atom])
    ev = np.concatenate([parent[tree], anc])
    order = np.argsort(emol, kind="stable")
    emol, eu, ev = emol[order], eu[order], ev[order]
    base = node_off[emol]
    edge_ptr = np.concatenate([[0], np.cumsum((n - 1) + rings)])
    return {
        "num_nodes": n.astype(np.int32),
        "edge_ptr": edge_ptr.astype(np.int64),
        "src": (eu - base).astype(np.int32),
        "dst": (ev - base).astype(np.int32),
    }
