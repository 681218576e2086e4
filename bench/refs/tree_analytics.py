"""Plain reference for served tree-analytics requests, and its control.

A served ``kind="analytics"`` answer holds a request's component labels
and component count, a spanning forest of its bonds, and that forest's
parent, depth, subtree size, preorder and postorder, rooted at each
component's smallest atom id. A spanning forest is not unique, so the
forest is judged by what it must be (as many edges as atoms less
components, every edge a bond of the request, and spanning each
component), and the tree arrays are then computed from the served
forest by a serial walk of its Euler circuit.

Everything runs once over the disjoint union of the requests checked
(atom ids shifted by each request's offset): components, edges and
circuits never cross a request's boundary, so the union's answer,
shifted back, is each request's own.

The control is the reference in the program's place with labels one
hooking round short of each molecule's fixpoint, the shortcut of a
capped round count. The numbers compared are exact counts of requests,
so their limit is 0.
"""
from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from components import hook_compress  # bench/refs is on the path

FIELDS = ("parent", "depth", "subtree_size", "preorder", "postorder")


def circuit_walk(edge_u, edge_v, n: int, labels) -> dict:
    """Parent, depth, subtree size, preorder and postorder of a forest,
    rooted at ``labels`` (each component's root id), by walking each
    tree's Euler circuit arc by arc.

    Arcs are ``[u->v | v->u]``, stable-sorted by source; an arc's twin
    lies ``f`` arcs away, and the arc after ``x->y`` is the one after
    its twin ``y->x`` in y's circular adjacency."""
    u = np.asarray(edge_u, np.int64).ravel()
    v = np.asarray(edge_v, np.int64).ravel()
    f = len(u)
    parent = np.arange(n, dtype=np.int64)
    depth = np.zeros(n, np.int64)
    size = np.ones(n, np.int64)
    pre = np.zeros(n, np.int64)
    post = np.zeros(n, np.int64)
    out = dict(parent=parent, depth=depth, subtree_size=size,
               preorder=pre, postorder=post)
    if f == 0:
        return out
    root_of = np.asarray(labels, np.int64)
    asrc = np.concatenate([u, v])
    adst = np.concatenate([v, u])
    arcs = 2 * f
    order = np.argsort(asrc, kind="stable")
    inv = np.empty(arcs, np.int64)
    inv[order] = np.arange(arcs)
    counts = np.bincount(asrc, minlength=n)
    offsets = np.concatenate([[0], np.cumsum(counts)[:-1]])
    tpos = inv[(np.arange(arcs) + f) % arcs]
    grp_end = offsets[adst] + counts[adst]
    succ = order[np.where(tpos + 1 < grp_end, tpos + 1, offsets[adst])]

    src_l, dst_l, succ_l = asrc.tolist(), adst.tolist(), succ.tolist()
    in_pos = np.full(n, -1, np.int64)
    out_pos = np.full(n, -1, np.int64)
    for r in np.unique(root_of[asrc]).tolist():
        head = int(order[offsets[r]])
        pre_c = post_c = p = 0
        arc = head
        while True:
            a, b = src_l[arc], dst_l[arc]
            if in_pos[b] < 0 and b != r:  # forward arc: discover b
                parent[b] = a
                depth[b] = depth[a] + 1
                pre_c += 1
                pre[b] = pre_c
                in_pos[b] = p
            else:  # backward arc: finish a
                post[a] = post_c
                post_c += 1
                out_pos[a] = p
            p += 1
            arc = succ_l[arc]
            if arc == head:
                break
        post[r] = post_c  # the root finishes last
        size[r] = post_c + 1
    seen = in_pos >= 0
    size[seen] = (out_pos[seen] - in_pos[seen] + 1) // 2
    return out


def _union(mols: dict):
    """Global edge arrays and offsets of the disjoint union."""
    nn = mols["num_nodes"].astype(np.int64)
    off = np.concatenate([[0], np.cumsum(nn)])
    ptr = mols["edge_ptr"]
    emol = np.repeat(np.arange(len(nn)), np.diff(ptr))
    return nn, off, mols["src"] + off[emol], mols["dst"] + off[emol], emol


def reference(mols: dict) -> dict:
    """Labels (global ids) of every atom of every request."""
    nn, off, gs, gd, _ = _union(mols)
    labels, _ = hook_compress(gs, gd, int(off[-1]))
    return {"labels": labels}


def _per_request(bad_node, off, count):
    """Requests with any flagged atom."""
    hits = np.zeros(count, bool)
    if len(bad_node):
        idx = np.searchsorted(off, np.flatnonzero(bad_node), side="right") - 1
        hits[idx] = True
    return hits


def compare(mols: dict, results: list, ref: dict) -> tuple[dict, int]:
    """({"wrong_answers": delivered requests with any wrong field,
    "missing_answers": requests never answered}, failed requests).
    ``results[i]`` is request i's served result, or None."""
    nn, off, gs, gd, emol = _union(mols)
    count, total = len(nn), int(off[-1])
    want = ref["labels"]
    have = np.array([r is not None for r in results], bool)
    wrong = np.zeros(count, bool)

    def stitched(field, dtype=np.int64):
        """A per-atom field of every answered request, in global ids
        where it names atoms; atoms of unanswered requests keep -1."""
        out = np.full(total, -1, dtype)
        for i in np.flatnonzero(have):
            x = getattr(results[i], field)
            if x is None or np.shape(x) != (nn[i],):
                wrong[i] = True
                continue
            out[off[i]:off[i + 1]] = np.asarray(x)
        return out

    shift = np.repeat(off[:-1], nn)
    got = stitched("labels") + shift
    # Labels name the smallest atom of the component.
    wrong |= _per_request((got != want) & np.repeat(have, nn), off, count)
    comps = np.bincount(np.repeat(np.arange(count), nn),
                        weights=(want == np.arange(total)), minlength=count)
    ncomp = np.array([-1 if r is None else r.num_components for r in results])
    wrong |= have & (ncomp != comps.astype(np.int64))

    # The forest: n - c edges per request, all bonds, spanning.
    fu, fv, fmol = [], [], []
    for i in np.flatnonzero(have & ~wrong):
        eu, ev = results[i].edge_u, results[i].edge_v
        if eu is None or ev is None or len(eu) != len(ev) \
                or len(eu) != nn[i] - int(comps[i]):
            wrong[i] = True
            continue
        eu, ev = np.asarray(eu, np.int64), np.asarray(ev, np.int64)
        if len(eu) and (min(eu.min(), ev.min()) < 0
                        or max(eu.max(), ev.max()) >= nn[i]):
            wrong[i] = True
            continue
        fu.append(eu + off[i])
        fv.append(ev + off[i])
        fmol.append(np.full(len(eu), i))
    fu = np.concatenate(fu) if fu else np.zeros(0, np.int64)
    fv = np.concatenate(fv) if fv else np.zeros(0, np.int64)
    fmol = np.concatenate(fmol) if fmol else np.zeros(0, np.int64)
    bonds = np.unique(np.minimum(gs, gd) * total + np.maximum(gs, gd))
    keys = np.minimum(fu, fv) * total + np.maximum(fu, fv)
    wrong[fmol[~np.isin(keys, bonds)]] = True
    span, _ = hook_compress(fu, fv, total)
    ok_nodes = np.repeat(have & ~wrong, nn)
    wrong |= _per_request((span != want) & ok_nodes, off, count)

    # Tree arrays of the served forest, rooted at the smallest atoms.
    keep = ~wrong[fmol]
    tree = circuit_walk(fu[keep], fv[keep], total, want)
    tree["parent"] = tree["parent"] - shift
    ok_nodes = np.repeat(have & ~wrong, nn)
    for k in FIELDS:
        wrong |= _per_request((stitched(k) != tree[k]) & ok_nodes, off, count)
    wrong &= have
    missing = int(np.count_nonzero(~have))
    return ({"wrong_answers": int(np.count_nonzero(wrong)),
             "missing_answers": missing}, int(np.count_nonzero(wrong)) + missing)


def control(mols: dict, ref: dict) -> list:
    """Full answers from the reference (a union-find spanning forest
    and its circuit walk) whose labels stop one hooking round short of
    each molecule's own fixpoint."""
    nn, off, gs, gd, emol = _union(mols)
    count, total = len(nn), int(off[-1])
    want = ref["labels"]
    history = [np.arange(total, dtype=np.int64)]
    while True:
        nxt, hooked = hook_compress(gs, gd, total, max_rounds=len(history))
        if hooked < len(history):
            break
        history.append(nxt)
    mol = np.repeat(np.arange(count), nn)
    rounds = np.zeros(count, np.int64)
    for r in range(1, len(history)):
        moved = np.zeros(count, bool)
        moved[mol[history[r] != history[r - 1]]] = True
        rounds[moved] = r
    short = np.stack(history)[np.maximum(rounds - 1, 0)[mol], np.arange(total)]

    root = np.arange(total, dtype=np.int64)

    def find(x):
        while root[x] != x:
            root[x] = root[root[x]]
            x = root[x]
        return x

    fu, fv = [], []
    for a, b in zip(gs.tolist(), gd.tolist()):
        ra, rb = find(a), find(b)
        if ra != rb:
            root[max(ra, rb)] = min(ra, rb)
            fu.append(a)
            fv.append(b)
    fu, fv = np.asarray(fu, np.int64), np.asarray(fv, np.int64)
    tree = circuit_walk(fu, fv, total, want)
    fmol = np.searchsorted(off, fu, side="right") - 1
    out = []
    for i in range(count):
        lo, hi = off[i], off[i + 1]
        lab = short[lo:hi] - lo
        sel = fmol == i
        res = SimpleNamespace(
            labels=lab, num_components=int(np.count_nonzero(lab == np.arange(nn[i]))),
            edge_u=fu[sel] - lo, edge_v=fv[sel] - lo,
            parent=tree["parent"][lo:hi] - lo,
            **{k: tree[k][lo:hi] for k in FIELDS[1:]})
        out.append(res)
    return out
