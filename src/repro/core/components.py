"""Shiloach-Vishkin connected components on TPU (paper section 4).

The paper's seven CUDA kernels SV0..SV5 (Algorithm 4) become seven
functional phases inside one ``lax.while_loop`` round. Adaptations per
DESIGN.md section 2:

* arbitrary-CRCW concurrent writes -> deterministic **min-CRCW** scatter
  (``.at[].min``). Any arbitrary-write resolution is a valid hook; choosing
  the minimum keeps runs reproducible and still satisfies the paper's
  O(log_{3/2} n) + 2 round bound.
* the SV1a/SV1b kernel split (barrier between short-cutting and marking) is
  structural here: ``D_new`` is a fresh functional value, so the data race
  the paper warns about cannot occur. We keep the phases separate anyway so
  per-phase work counts match Table 4.
* SV5's parallel-OR through racing writes to one word becomes ``jnp.any``.

The round body is built once by ``sv_round_fns`` and shared by THREE
engines so their hook semantics stay bit-identical by construction:

* ``sv_run`` / ``shiloach_vishkin`` -- the dense single-device loop;
* ``repro.core.frontier.frontier_shiloach_vishkin`` -- the
  frontier-compacted engine (same body over a shrinking edge buffer);
* ``repro.distributed.graph.sharded_shiloach_vishkin`` -- the
  edge-partitioned engine (same body plus per-round label exchanges).

Cross-replica merges use the convention ``fn(arr, base, aux, s) ->
(arr, aux)``: ``base`` is the replicated pre-scatter array (what every
device agreed on before this phase's min-scatter), which is what lets
the sparse frontier exchange send only the (index, label) pairs that
changed; ``aux`` threads exchange statistics through the round loop.

``label_propagation`` is the simple O(diameter)-round alternative used as a
baseline in benchmarks (it wins on small-diameter random graphs, loses badly
on chains -- the same graph-family sensitivity as the paper's Figure 4).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import trace

Array = jax.Array


def sv_round_bound(n: int) -> int:
    """Paper/[14]: at most floor(log_{3/2} n) + 2 rounds."""
    return int(math.floor(math.log(max(n, 2)) / math.log(1.5))) + 2


class ConvergenceError(RuntimeError):
    """A bounded round/walk loop hit its bound without reaching a
    fixpoint. Labels past the bound would be WRONG (an un-hooked edge
    still straddles two components), so every host-driven engine raises
    this instead of returning them -- a silent bound-hit is exactly how
    a broken invariant (e.g. a nondeterministic scatter, guideline G3 /
    RL002) would otherwise leak wrong results. Fully traced callers
    (``jax.jit`` over the dense walks) cannot raise on a device value;
    they keep the documented return-at-bound behavior, and the serve
    path fails just the offending wave (``docs/serving.md``)."""


def _identity_merge(arr, base, aux, s):
    del base, s
    return arr, aux


def check_choice(kind: str, value, choices) -> None:
    """Reject unknown dispatch strings loudly, naming the valid set.

    Shared by every ``engine=`` / ``kernel_impl=`` / ``hook_impl=``
    switch so a typo fails at the call site instead of silently falling
    through to a default path."""
    if value not in choices:
        raise ValueError(
            f"unknown {kind} {value!r}; valid choices: "
            + ", ".join(repr(c) for c in choices)
        )


HOOK_IMPLS = ("xla", "auto", "pallas", "pallas_interpret")


def _lift_merge(fn):
    """Adapt an engine merge fn (which owns only its engine aux) to the
    nested ``(hooks, engine_aux)`` aux used when ``record_hooks`` is on,
    so no engine's merge functions need to know about hook recording."""

    def lifted(arr, base, aux, s):
        hooks, inner = aux
        arr, inner = fn(arr, base, inner, s)
        return arr, (hooks, inner)

    return lifted


def init_hooks(n: int):
    """Fresh hook-recording state: ``(hook_u, hook_v)``, sentinel ``n``.

    Slot r holds the endpoints of the graph edge that won the min-CRCW
    hook of tree r (the round r's label slot changed), or ``n`` if tree
    r never hooked (component roots). Each slot hooks at most once over
    a whole run -- once D[r] drops below r, no node carries label r
    again after the round's short-cuts -- so the arrays are write-once
    and the recorded pairs form a spanning forest: one edge per hook
    event, hooks always point label-decreasing (acyclic), and a
    component of size c hooks exactly c - 1 times."""
    return jnp.full((n,), n, jnp.int32), jnp.full((n,), n, jnp.int32)


def _hook_phase_fns(a: Array, b: Array, n: int, hook_impl: str):
    """SV2/SV3 hook phases over the edge arrays: either inline XLA
    gathers + min-scatters, or the fused ``kernels/edge_hook`` Pallas
    kernel (one VMEM-resident pass per edge tile)."""
    if hook_impl != "xla":
        from repro.kernels.edge_hook.ops import edge_hook

        def sv2(D1, D, Q, s):
            return edge_hook(a, b, D1, Q, s, labels_prev=D, mode="sv2",
                             impl=hook_impl)

        def sv3(D2, Q, s):
            D3, _ = edge_hook(a, b, D2, Q, s, mode="sv3", impl=hook_impl)
            # the fused kernel doesn't export its compare mask (yet);
            # frontier callers recompute it (see sv_round_fns)
            return D3, None

        return sv2, sv3

    def sv2(D1, D, Q, s):
        # SV2: hook edges from trees that did NOT shrink onto smaller roots.
        Da, Db = D1[a], D1[b]
        stagnant_a = Da == D[a]
        cond2 = jnp.logical_and(stagnant_a, Db < Da)
        tgt2 = jnp.where(cond2, Da, n)
        D2 = D1.at[tgt2].min(jnp.where(cond2, Db, n), mode="drop")
        # Every winning lane writes the SAME scalar stamp s: duplicate
        # targets commute, so plain set is deterministic here.
        Q2 = Q.at[jnp.where(cond2, Db, n)].set(s, mode="drop")  # repro-lint: disable=scatter-determinism
        return D2, Q2

    def sv3(D2, Q, s):
        # SV3: hook stagnant roots (no activity this round) onto any
        # neighboring tree, breaking label-order ties via min-CRCW.
        Da3, Db3 = D2[a], D2[b]
        root_a = D2[Da3] == Da3
        stagnant = Q[Da3] < s
        live = Da3 != Db3
        cond3 = stagnant & root_a & live
        tgt3 = jnp.where(cond3, Da3, n)
        # ``live`` rides along as the frontier mask: a superset of the
        # edges still able to hook after this round (label equality is
        # permanent), read off SV3's own gathers at zero extra passes.
        return D2.at[tgt3].min(jnp.where(cond3, Db3, n), mode="drop"), live

    return sv2, sv3


def sv_round_fns(
    a: Array,
    b: Array,
    n: int,
    merge_labels=None,
    merge_stamps=None,
    hook_impl: str = "xla",
    with_frontier: bool = False,
    record_hooks: bool = False,
    merge_hooks=None,
):
    """Build the SV1a..SV5 round body over edge arrays ``(a, b)``.

    Returns ``round_body(carry) -> carry`` with carry
    ``(D, Q, aux, s, changed)``. This is THE round body: every engine
    (dense, frontier-compacted, sharded) runs it unmodified, so hook
    semantics -- min-CRCW resolution, Q stamps, the round bound -- are
    bit-identical across engines by construction.

    ``with_frontier=True`` appends a per-edge frontier mask to the carry
    (``(D, Q, aux, s, changed, fmask)``): a superset of the edges still
    able to hook, read off the SV3 phase's own D[a]/D[b] gathers (the
    pre-hook compare), so the frontier engine's shrink decisions cost no
    extra edge passes on the XLA path. The Pallas hook kernel doesn't
    export its compare mask, so that path recomputes the mask post-round
    (one extra pass).

    ``record_hooks=True`` records, for every hook event, the graph edge
    that won the min-CRCW scatter (the spanning-forest by-product the
    ``repro.trees`` subsystem consumes). The aux slot then carries
    ``((hook_u, hook_v), engine_aux)`` -- see ``init_hooks`` -- and
    ``merge_labels``/``merge_stamps`` are lifted automatically to their
    engine_aux component, so engines opt in without changing their merge
    functions. Recording only READS the label/stamp state (after each
    phase's merge) and writes the side arrays, so labels, stamps, and
    round counts are bit-identical with recording on or off, on every
    engine, by construction. ``merge_hooks`` is the cross-replica
    reduction for the candidate arrays (identity on a single device,
    pmin in the sharded engine); it runs twice per phase -- once to
    agree on the winning ``u``, once for the matching ``v`` -- so the
    recorded pair is a real edge even when the winner is on another
    device's shard.
    """
    ml = merge_labels if merge_labels is not None else _identity_merge
    mq = merge_stamps if merge_stamps is not None else _identity_merge
    if record_hooks:
        ml, mq = _lift_merge(ml), _lift_merge(mq)
    mh = merge_hooks if merge_hooks is not None else (lambda arr: arr)
    sv2_hook, sv3_hook = _hook_phase_fns(a, b, n, hook_impl)

    def record_phase(hooks, cond, tgt, val, D_before, D_after):
        """Record the winning edge of every slot this phase hooked.

        A slot r hooked iff its merged label changed; the winners are
        the edges that (a) satisfied the phase's hook condition, (b)
        targeted r, and (c) wrote exactly the value that survived the
        min. Ties (several edges writing the min label) break to the
        lexicographically smallest (u, v): one min-scatter picks u, a
        second -- conditioned on the merged u -- picks its v, which
        keeps the pair an actual edge and makes the recorded forest
        deterministic and engine-independent."""
        hook_u, hook_v = hooks
        tc = jnp.minimum(tgt, n - 1)  # clamped: non-winners masked below
        hooked = D_after[tc] != D_before[tc]
        win = cond & (val == D_after[tc]) & hooked
        cu = jnp.full((n,), n, jnp.int32).at[
            jnp.where(win, tgt, n)
        ].min(a, mode="drop")
        cu = mh(cu)
        win_v = win & (a == cu[tc])
        cv = jnp.full((n,), n, jnp.int32).at[
            jnp.where(win_v, tgt, n)
        ].min(b, mode="drop")
        cv = mh(cv)
        return jnp.where(cu < n, cu, hook_u), jnp.where(cv < n, cv, hook_v)

    def round_body(carry):
        if with_frontier:
            D, Q, aux, s, _changed, _fmask = carry
        else:
            D, Q, aux, s, _changed = carry

        # SV1a: short-cut.
        D1 = D[D]
        # SV1b: mark roots whose tree shrank. (Concurrent writes of the same
        # value s -> plain scatter-set with OOB drop for unmarked lanes.)
        mark = D1 != D
        Q = Q.at[jnp.where(mark, D1, n)].set(s, mode="drop")  # repro-lint: disable=scatter-determinism
        q_base = Q  # replicated: the shrink marks are device-independent

        D2, Q = sv2_hook(D1, D, Q, s)
        D2, aux = ml(D2, D1, aux, s)
        Q, aux = mq(Q, q_base, aux, s)
        if record_hooks:
            hooks, inner = aux
            Da, Db = D1[a], D1[b]
            cond2 = jnp.logical_and(Da == D[a], Db < Da)
            hooks = record_phase(
                hooks, cond2, jnp.where(cond2, Da, n), Db, D1, D2
            )
            aux = (hooks, inner)

        D3, fmask = sv3_hook(D2, Q, s)
        D3, aux = ml(D3, D2, aux, s)
        if record_hooks:
            hooks, inner = aux
            Da3, Db3 = D2[a], D2[b]
            cond3 = (
                (Q[Da3] < s) & (D2[Da3] == Da3) & (Da3 != Db3)
            )
            hooks = record_phase(
                hooks, cond3, jnp.where(cond3, Da3, n), Db3, D2, D3
            )
            aux = (hooks, inner)

        # SV4: short-cut again.
        D4 = D3[D3]

        # SV5: parallel OR "did anything change this round?".
        changed = jnp.any(Q == s)
        if with_frontier:
            if fmask is None:  # kernel path: mask needs its own compare
                fmask = D4[a] != D4[b]
            return D4, Q, aux, s + 1, changed, fmask
        return D4, Q, aux, s + 1, changed

    return round_body


def sv_compress(D: Array, n: int) -> Array:
    """Full path compression so labels are true roots (the paper reads
    D directly; min-hooking can leave 2-level trees on the last round)."""
    comp_iters = max(1, math.ceil(math.log2(max(n, 2))))
    return jax.lax.fori_loop(0, comp_iters, lambda _, d: d[d], D)


def sv_run(
    a: Array,
    b: Array,
    n: int,
    bound: int,
    merge_labels=None,
    merge_stamps=None,
    *,
    hook_impl: str = "xla",
    aux0=None,
    return_aux: bool = False,
    record_hooks: bool = False,
    merge_hooks=None,
):
    """The SV0..SV5 round loop over edge arrays (a, b).

    ``merge_labels`` / ``merge_stamps`` are cross-replica reductions
    ``fn(arr, base, aux, s) -> (arr, aux)`` applied right after each
    min-scatter phase; identity on a single device, pmin/pmax (or the
    sparse frontier exchange) in the sharded engine. ``base`` is the
    replicated pre-scatter array and ``aux`` threads per-round exchange
    stats. Keeping the round body in ONE place is what guarantees the
    engines stay bit-identical -- a min-scatter distributes over
    edge-shard unions, so inserting the merges at these two points
    changes who walks each edge and nothing else.

    Returns ``(D, rounds, converged[, hooks][, aux])``. ``converged``
    is the fixpoint sentinel carried out of the while-loop: True iff
    the loop exited because a round made no change (the final carried
    ``changed`` flag), False iff it exited at ``bound`` with changes
    still flowing -- the case host-driven callers turn into
    ``ConvergenceError`` instead of returning wrong labels.

    ``record_hooks=True`` additionally returns the ``(hook_u, hook_v)``
    winning-hook-edge arrays (see ``init_hooks``; ``merge_hooks`` is
    their cross-replica pmin in the sharded engine) right after
    ``converged``.
    """
    # SV0: D(0)[j] = j, Q[j] = 0
    D0 = jnp.arange(n, dtype=jnp.int32)
    Q0 = jnp.zeros(n, jnp.int32)
    aux = aux0 if aux0 is not None else jnp.int32(0)
    if record_hooks:
        aux = (init_hooks(n), aux)

    round_body = sv_round_fns(
        a, b, n, merge_labels, merge_stamps, hook_impl=hook_impl,
        record_hooks=record_hooks, merge_hooks=merge_hooks,
    )

    def cond(carry):
        _D, _Q, _aux, s, changed = carry
        return jnp.logical_and(changed, s <= bound)

    D, _Q, aux, s, changed = jax.lax.while_loop(
        cond, round_body, (D0, Q0, aux, jnp.int32(1), jnp.bool_(True))
    )
    D = sv_compress(D, n)
    # The loop exits with changed=False at a fixpoint, or changed=True
    # when round `bound` still hooked something -- NOT converged.
    out = (D, s - 1, jnp.logical_not(changed))
    if record_hooks:
        hooks, aux = aux
        out = out + (hooks,)
    if return_aux:
        out = out + (aux,)
    return out


def dedup_edges(
    src: Array | np.ndarray, dst: Array | np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """Drop self-loops and duplicate undirected edges (host-side).

    Self-loops can never hook (SV2 needs Db < Da, SV3 Da != Db) and
    duplicates min-hook idempotently, so removing them changes neither
    labels nor round count -- it only shrinks the 2m edge walk.

    Returns the distinct ``(lo, hi)`` pairs, ``lo < hi``, as int32 in
    lexicographic order; ids must fit int32, the engines' label dtype.
    Each edge is packed into one int64 key, ``lo * 2**32 + (hi + 2**31)``,
    which sorts exactly as the pair does, negative ids included: one
    flat integer sort stands in for a row-wise (byte-compared) unique,
    and the int32 work arrays and in-place packing keep the fresh
    int64 buffers to two.
    """
    u = np.asarray(src, dtype=np.int32).ravel()
    v = np.asarray(dst, dtype=np.int32).ravel()
    lo, hi = np.minimum(u, v), np.maximum(u, v)
    keep = lo != hi
    key = lo[keep].astype(np.int64)
    key <<= 32
    key += hi[keep]
    key += 2**31
    key.sort()
    if key.size:  # keep the first key of each run of equal keys
        first = np.empty(key.size, bool)
        first[0] = True
        np.not_equal(key[1:], key[:-1], out=first[1:])
        key = key[first]
    # The low word holds hi + 2**31; as int32 that is hi with its sign
    # bit flipped, so flipping it back recovers hi.
    hi = key.astype(np.int32)
    hi ^= np.int32(-2**31)
    key >>= 32
    return key.astype(np.int32), hi


def _maybe_dedup(src, dst, dedup: bool):
    """Dedup host-side (numpy/list) edge inputs; pass device-resident or
    traced arrays through untouched -- dedup is label/round-neutral, so
    skipping it never changes results, and forcing a device-to-host sync
    on every call would dominate hot loops. Device-array callers who
    want the smaller walk dedup once via ``dedup_edges`` up front."""
    host = isinstance(src, (np.ndarray, list, tuple)) and isinstance(
        dst, (np.ndarray, list, tuple)
    )
    if not dedup or not host:
        return src, dst
    with trace.span("cc.dedup") as sp:
        a, b = dedup_edges(src, dst)
        if trace.enabled():
            sp.tag(m_in=np.size(src), m_out=a.size)
        return a, b


@partial(
    jax.jit,
    static_argnames=("num_nodes", "bound", "hook_impl", "record_hooks"),
)
def _sv_dense(src, dst, num_nodes, bound, hook_impl, record_hooks=False):
    a = jnp.concatenate([src, dst]).astype(jnp.int32)
    b = jnp.concatenate([dst, src]).astype(jnp.int32)
    return sv_run(
        a, b, num_nodes, bound, hook_impl=hook_impl,
        record_hooks=record_hooks,
    )


def shiloach_vishkin(
    src: Array,
    dst: Array,
    num_nodes: int,
    *,
    max_rounds: int | None = None,
    dedup: bool = True,
    hook_impl: str = "xla",
    record_hooks: bool = False,
):
    """Connected components. Edges are treated as undirected (both
    orientations are processed, matching the paper's 2m edge walk);
    self-loops and duplicate edges in host-side (numpy) inputs are
    dropped up front (``dedup=False`` restores the paper's raw walk for
    work-count experiments; device-resident inputs skip the host sync
    and can be pre-cleaned with ``dedup_edges``).

    Returns (labels, rounds). labels[i] is the component root id.
    ``record_hooks=True`` appends the spanning-forest hook record
    ``(hook_u, hook_v)`` (see ``init_hooks``) without changing labels
    or round counts; ``repro.trees.spanning_forest`` is the consumer.

    Hitting ``max_rounds`` without a fixpoint raises
    ``ConvergenceError`` instead of returning wrong labels (host calls
    only; under a ``jax.jit`` trace the sentinel cannot raise and the
    bounded result is returned as before). The default bound is the
    paper's proven ceiling, so the sentinel only ever fires on an
    explicit too-small ``max_rounds`` or a broken round invariant.
    """
    from repro.compat import is_tracer

    n = num_nodes
    check_choice("hook_impl", hook_impl, HOOK_IMPLS)
    bound = max_rounds if max_rounds is not None else sv_round_bound(n)
    src, dst = _maybe_dedup(src, dst, dedup)
    # The whole-run device span blocks on the labels at close -- the
    # same terminal sync the convergence-sentinel read below already
    # pays, so tracing adds no new device round-trip. Under an outer
    # jit trace nothing is registered to block on (tracer values), so
    # the function stays traceable.
    with trace.span("cc.dense", device=True, n=n, bound=bound) as sp:
        out = _sv_dense(
            jnp.asarray(src), jnp.asarray(dst), n, bound, hook_impl,
            record_hooks,
        )
        labels, rounds, converged = out[0], out[1], out[2]
        if not is_tracer(converged):
            sp.block_on(labels)
    if not is_tracer(converged):
        # Intentional terminal sync: the sentinel must be read before
        # wrong labels can escape (docstring above).
        trace.count("host_sync")
        if not bool(converged):  # repro-lint: disable=host-sync
            raise ConvergenceError(
                f"shiloach_vishkin hit max_rounds={bound} before the "
                f"label fixpoint on {n} nodes; raise max_rounds (the "
                f"proven bound is sv_round_bound(n)={sv_round_bound(n)})"
            )
    return (labels, rounds) + out[3:]


@partial(jax.jit, static_argnames=("num_nodes", "max_rounds"))
def label_propagation(
    src: Array, dst: Array, num_nodes: int, *, max_rounds: int | None = None
) -> tuple[Array, Array]:
    """Min-label propagation baseline: O(diameter) rounds, O(m) work/round."""
    n = num_nodes
    bound = max_rounds if max_rounds is not None else n
    a = jnp.concatenate([src, dst]).astype(jnp.int32)
    b = jnp.concatenate([dst, src]).astype(jnp.int32)
    D0 = jnp.arange(n, dtype=jnp.int32)

    def body(carry):
        D, s, _changed = carry
        Dn = D.at[b].min(D[a])
        Dn = Dn[Dn]  # pointer-jump accelerates long chains
        return Dn, s + 1, jnp.any(Dn != D)

    D, s, _ = jax.lax.while_loop(
        lambda c: jnp.logical_and(c[2], c[1] < bound),
        body,
        (D0, jnp.int32(0), jnp.bool_(True)),
    )
    D = sv_compress(D, n)
    return D, s


def num_components(labels: Array | np.ndarray) -> int:
    return int(len(np.unique(np.asarray(labels))))
