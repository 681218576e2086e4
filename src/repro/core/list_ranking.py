"""Parallel list ranking on TPU (paper section 3).

Two algorithms, as in the paper:

* ``wylie_rank`` -- Wylie's pointer jumping. O(n log n) work, O(log n)
  steps. Each step follows every node's pointer: two irregular gathers per
  step in SoA layout, or ONE row gather in AoS layout (the paper's 64-bit
  union packing of (rank, last), guideline G5).

* ``random_splitter_rank`` -- Reid-Miller's parallel random splitter
  algorithm (paper Algorithm 1/3). O(n + p log p) work. Five phases mapped
  from the paper's five kernels RS1..RS5:
    RS1/RS2  init + splitter selection (KISS RNG, one stream per lane),
    RS3      lockstep masked sub-list walk (the irregular-access hot spot),
    RS4      pointer jumping on the p-node splitter list (fits in VMEM ->
             single Pallas kernel, the paper's "single thread block +
             __syncthreads" fast path),
    RS5      streaming rank aggregation (the coalescing-friendly kernel; a
             blocked Pallas kernel keeps the splitter table VMEM-resident).

rank[j] = number of edges from j to the last list element (rank[last] = 0).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.components import check_choice
from repro.core.pram import lockstep_walk
from repro.ops.kiss import KissRng

Array = jax.Array

PACK_MODES = ("aos", "soa", "word64")
# wylie_rank's subset: pointer jumping has no word64-packed variant.
WYLIE_PACK_MODES = ("aos", "soa")
KERNEL_IMPLS = ("auto", "xla", "pallas", "pallas_interpret")


def max_splitters_for_linear_work(n: int) -> int:
    """Largest p with p*log2(p) <= n (paper: keeps total work O(n))."""
    p = max(2, n)
    while p * math.log2(max(p, 2)) > n and p > 2:
        p //= 2
    return p


# ---------------------------------------------------------------------------
# Wylie's algorithm (pointer jumping)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("pack_mode", "num_iters"))
def wylie_rank(
    succ: Array, *, pack_mode: str = "aos", num_iters: int | None = None
) -> Array:
    n = succ.shape[0]
    iters = num_iters if num_iters is not None else max(1, math.ceil(math.log2(max(n, 2))))
    lane = jnp.arange(n, dtype=succ.dtype)
    rank0 = (succ != lane).astype(jnp.int32)

    check_choice("pack_mode", pack_mode, WYLIE_PACK_MODES)
    if pack_mode == "soa":

        def body(_, st):
            rank, last = st
            # two independent irregular gathers per step
            return rank + rank[last], last[last]

        rank, _ = jax.lax.fori_loop(0, iters, body, (rank0, succ.astype(jnp.int32)))
        return rank

    if pack_mode == "aos":
        packed0 = jnp.stack([rank0, succ.astype(jnp.int32)], axis=-1)

        def body(_, packed):
            # ONE row gather fetches (rank[last], last[last]) together:
            # the paper's 64-bit union trick as an (n, 2) AoS row.
            row = jnp.take(packed, packed[:, 1], axis=0)
            return jnp.stack([packed[:, 0] + row[:, 0], row[:, 1]], axis=-1)

        packed = jax.lax.fori_loop(0, iters, body, packed0)
        return packed[:, 0]

    raise AssertionError("unreachable: pack_mode validated above")


# ---------------------------------------------------------------------------
# Reid-Miller's parallel random splitter algorithm
# ---------------------------------------------------------------------------


@dataclass
class SplitterStats:
    """Observables the paper reports in Tables 2/3."""

    splitters: np.ndarray  # (p,) node ids
    sublist_lengths: np.ndarray  # (p,) walk lengths (= RS4 weights)
    walk_steps: int  # lockstep trip count = max sub-list length
    expected_mean: float  # n / p (Table 3 "Mean")

    def publish(self, registry=None, prefix: str = "rank.splitter") -> None:
        """Publish into the metrics registry (``repro.obs.metrics``)."""
        from repro.obs.metrics import publish_stats

        publish_stats(self, prefix, registry)


def select_splitters(n: int, p: int, seed: int = 0, head: int = 0) -> np.ndarray:
    """RS2: one KISS stream per lane picks a splitter in its n/p block.

    Lane 0's pick is replaced by the list head so every node is covered
    (Reid-Miller's convention; the head starts the first sub-list).
    """
    if p < 1 or p > n:
        raise ValueError(f"need 1 <= p <= n, got p={p} n={n}")
    block = n // p
    rng = KissRng(seed, n_streams=p)
    offs = rng.next_u32().astype(np.int64) % max(block, 1)
    spl = np.minimum(np.arange(p, dtype=np.int64) * block + offs, n - 1)
    spl[0] = head
    # Ensure distinctness (head may collide with lane 0's block anyway).
    spl = np.unique(spl)
    if len(spl) < p:  # refill collisions deterministically
        missing = p - len(spl)
        pool = np.setdiff1d(np.arange(n, dtype=np.int64), spl, assume_unique=True)
        spl = np.concatenate([spl, pool[:missing]])
    return np.sort(spl)


def even_splitters(succ: np.ndarray, p: int, head: int = 0) -> np.ndarray:
    """Perfect splitters for the Table-3 control: every n/p-th list node."""
    n = len(succ)
    order = np.empty(n, dtype=np.int64)
    j = head
    for i in range(n):
        order[i] = j
        j = succ[j]
    return np.sort(order[:: max(n // p, 1)][:p])


def _splitter_list_rank(w_adj: Array, spsucc: Array, iters: int) -> Array:
    """RS4: weighted pointer jumping over the p-node splitter list.

    Returns final splitter ranks: rank_sp[s] = edges from s to the last
    list element. Terminal splitters (spsucc == self) carry their residual
    walk length in w_adj.
    """
    p = w_adj.shape[0]
    lanes = jnp.arange(p, dtype=spsucc.dtype)
    is_term = spsucc == lanes
    r = jnp.where(is_term, 0, w_adj)
    nxt = spsucc

    def body(_, st):
        r, nxt = st
        return r + r[nxt], nxt[nxt]

    r, nxt = jax.lax.fori_loop(0, iters, body, (r, nxt))
    # nxt now points at each chain's terminal; add its residual once.
    return r + w_adj[nxt]


def aos_walk_fns(succ: Array, is_stop: Array, lanes: Array, valid=None):
    """RS3 active/step functions for the AoS store.

    Shared by the single-device core and the sharded engine (which
    passes offset global lane ids plus a ``valid`` mask for padded
    lanes) -- one copy of the walk predicate and scatter keeps the two
    engines bit-identical by construction.
    """
    n = succ.shape[0]

    def active_fn(st):
        act = jnp.logical_and(~is_stop[st["nxt"]], st["nxt"] != st["cur"])
        return act if valid is None else jnp.logical_and(valid, act)

    def step_fn(st, active):
        (packed,) = st["store"]
        nxt, cur, dist = st["nxt"], st["cur"], st["dist"]
        tgt = jnp.where(active, nxt, n)  # OOB rows are dropped (branch-free)
        rows = jnp.stack([dist, lanes], axis=-1)
        packed = packed.at[tgt].set(rows, mode="drop")
        return dict(
            store=(packed,),
            cur=jnp.where(active, nxt, cur),
            nxt=jnp.where(active, succ[nxt], nxt),
            dist=dist + active.astype(jnp.int32),
        )

    return active_fn, step_fn


@partial(jax.jit, static_argnames=("pack_mode", "max_steps", "kernel_impl"))
def _random_splitter_core(
    succ: Array,
    splitters: Array,
    *,
    pack_mode: str = "aos",
    max_steps: int | None = None,
    kernel_impl: str = "xla",  # "pallas": RS4/RS5 via the Pallas kernels
):
    n = succ.shape[0]
    p = splitters.shape[0]
    succ = succ.astype(jnp.int32)
    splitters = splitters.astype(jnp.int32)
    lanes = jnp.arange(p, dtype=jnp.int32)

    is_stop = jnp.zeros((n,), jnp.bool_).at[splitters].set(True)

    if pack_mode == "soa":
        owner = jnp.full((n,), -1, jnp.int32).at[splitters].set(lanes)
        local = jnp.zeros((n,), jnp.int32)
        store = (owner, local)
    elif pack_mode in ("aos", "word64"):
        # AoS rows [local_rank, owner]; word64 packs the same pair into one
        # integer word when x64 is enabled (benchmarks only).
        packed = jnp.full((n, 2), -1, jnp.int32)
        packed = packed.at[:, 0].set(0)
        packed = packed.at[splitters, 1].set(lanes)
        store = (packed,)
    else:
        raise ValueError(f"unknown pack_mode {pack_mode!r}")

    # --- RS3: lockstep masked walk --------------------------------------
    state = dict(
        store=store,
        cur=splitters,
        nxt=succ[splitters],
        dist=jnp.ones((p,), jnp.int32),
    )

    if pack_mode == "soa":

        def active_fn(st):
            return jnp.logical_and(~is_stop[st["nxt"]], st["nxt"] != st["cur"])

        def step_fn(st, active):
            owner, local = st["store"]
            nxt, cur, dist = st["nxt"], st["cur"], st["dist"]
            tgt = jnp.where(active, nxt, n)  # OOB rows dropped (branch-free)
            owner = owner.at[tgt].set(lanes, mode="drop")
            local = local.at[tgt].set(dist, mode="drop")
            return dict(
                store=(owner, local),
                cur=jnp.where(active, nxt, cur),
                nxt=jnp.where(active, succ[nxt], nxt),
                dist=dist + active.astype(jnp.int32),
            )

    else:
        active_fn, step_fn = aos_walk_fns(succ, is_stop, lanes)

    final, steps, converged = lockstep_walk(
        state, active_fn, step_fn, max_steps=max_steps
    )

    if pack_mode == "soa":
        owner, local = final["store"]
    else:
        (packed,) = final["store"]
        local, owner = packed[:, 0], packed[:, 1]

    # --- RS4: rank the splitter linked list ------------------------------
    # The splitter list fits VMEM: with kernel_impl="pallas" ALL O(log p)
    # jumping steps run inside one Pallas kernel (the paper's single-block
    # __syncthreads() fast path; see kernels/pointer_jump).
    spsucc = owner[final["nxt"]]
    is_term = spsucc == lanes
    w_adj = final["dist"] - is_term.astype(jnp.int32)
    iters = max(1, math.ceil(math.log2(max(p, 2))))
    if kernel_impl != "xla":
        from repro.kernels.pointer_jump.ops import pointer_jump

        r, nxt_final = pointer_jump(
            spsucc, jnp.where(is_term, 0, w_adj),
            iters=iters, impl=kernel_impl,
        )
        rank_sp = r + w_adj[nxt_final]
    else:
        rank_sp = _splitter_list_rank(w_adj, spsucc, iters)

    # --- RS5: streaming aggregation (coalesced: pure striding access) ----
    if kernel_impl != "xla":
        from repro.kernels.splitter_aggregate.ops import splitter_aggregate

        if pack_mode == "soa":
            packed_rs5 = jnp.stack([local, owner], axis=-1)
        else:
            packed_rs5 = jnp.stack([packed[:, 0], packed[:, 1]], axis=-1)
        rank = splitter_aggregate(packed_rs5, rank_sp, impl=kernel_impl)
    elif pack_mode == "soa":
        rank = rank_sp[owner] - local
    else:
        # one row gather yields (local, owner) together
        rank = rank_sp[packed[:, 1]] - packed[:, 0]

    return rank, final["dist"], steps, converged


def random_splitter_rank(
    succ: Array | np.ndarray,
    num_splitters: int | None = None,
    *,
    splitters: np.ndarray | None = None,
    head: int = 0,
    seed: int = 0,
    pack_mode: str = "aos",
    max_steps: int | None = None,
    kernel_impl: str = "xla",
    with_stats: bool = False,
):
    """Rank a linked list with Reid-Miller's random splitter algorithm.

    ``kernel_impl`` routes the RS4/RS5 phases through the Pallas
    kernels: "auto" keeps plain XLA (the chip's compiler refuses both
    kernels, see ``repro.kernels``); "pallas"/"pallas_interpret" force
    the kernel path (interpreted off-TPU). Unknown strings raise (they
    used to fall through to the XLA path silently).

    If ``max_steps`` cuts the lockstep walk off before every lane
    reaches its splitter, the ranks would be wrong -- host calls raise
    ``ConvergenceError`` instead of returning them (under a ``jax.jit``
    trace the sentinel cannot raise; the bounded state is returned).
    """
    from repro.compat import is_tracer
    from repro.core.components import ConvergenceError

    check_choice("pack_mode", pack_mode, PACK_MODES)
    check_choice("kernel_impl", kernel_impl, KERNEL_IMPLS)
    if kernel_impl == "auto":
        kernel_impl = "xla"  # the chip's compiler refuses the kernels
    succ = jnp.asarray(succ)
    n = int(succ.shape[0])
    if splitters is None:
        p = num_splitters or min(4096, max_splitters_for_linear_work(n))
        p = min(p, n)
        splitters = select_splitters(n, p, seed=seed, head=head)
    splitters = np.asarray(splitters)
    rank, sublens, steps, converged = _random_splitter_core(
        succ, jnp.asarray(splitters), pack_mode=pack_mode,
        max_steps=max_steps, kernel_impl=kernel_impl,
    )
    if max_steps is not None and not is_tracer(converged):
        # Intentional terminal sync: the walk sentinel must be read
        # before truncated (wrong) ranks can escape.
        if not bool(converged):  # repro-lint: disable=host-sync
            raise ConvergenceError(
                f"random_splitter_rank walk hit max_steps={max_steps} "
                "with lanes still active; ranks would be truncated -- "
                "raise max_steps or add splitters"
            )
    if not with_stats:
        return rank
    # Opt-in stats materialization after the walk finished.
    stats = SplitterStats(
        splitters=np.asarray(splitters),  # repro-lint: disable=host-sync
        sublist_lengths=np.asarray(sublens),  # repro-lint: disable=host-sync
        walk_steps=int(steps),  # repro-lint: disable=host-sync
        expected_mean=n / len(splitters),
    )
    return rank, stats
