"""Public wrapper: pads, derives per-output-block edge ranges, dispatches.

The eb_start/eb_count tables are the TPU analogue of CSR row pointers at
block granularity; they are computed with jnp (O(num_blocks) searchsorted)
so the whole op stays jit-compatible.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.segment_sum.ref import segment_sum_sorted_ref
from repro.kernels.segment_sum.segment_sum import segment_sum_sorted_pallas


@partial(
    jax.jit,
    static_argnames=("num_segments", "impl", "block_e", "block_s", "max_steps"),
)
def segment_sum_sorted(
    data: jax.Array,
    seg_ids: jax.Array,
    num_segments: int,
    *,
    impl: str = "auto",
    block_e: int = 512,
    block_s: int = 256,
    max_steps: int | None = None,
) -> jax.Array:
    """Segment sum over rows already sorted by ``seg_ids``.

    Args:
        data: (m, d) float messages, sorted by segment.
        seg_ids: (m,) int32 sorted segment ids in [0, num_segments).
        num_segments: output rows.
        max_steps: static bound on edge blocks any output block spans; the
            default (all blocks) is safe but slow -- callers with degree
            bounds should pass ceil(max_in_degree_per_block / block_e) + 1.
    """
    # "auto" is the XLA path: the chip's compiler refuses the kernel at
    # the default block_e (kernels/__init__).
    if impl in ("auto", "xla"):
        return segment_sum_sorted_ref(data, seg_ids, num_segments)

    m, d = data.shape
    pad_m = (-m) % block_e
    pad_s = (-num_segments) % block_s
    ns_pad = num_segments + pad_s
    data_p = jnp.pad(data, ((0, pad_m), (0, 0)))
    # Padding rows get an out-of-range segment id -> one-hot rows of zeros.
    seg_p = jnp.pad(seg_ids, (0, pad_m), constant_values=ns_pad + block_s)
    mp = m + pad_m
    num_eb = mp // block_e
    num_ob = ns_pad // block_s

    # First/last edge touching each output block, via binary search over the
    # sorted ids sampled at block edges.
    block_first = seg_p[:: block_e]  # (num_eb,) first seg id in each block
    block_last = seg_p[block_e - 1 :: block_e]  # last seg id in each block
    ob_lo = jnp.arange(num_ob, dtype=jnp.int32) * block_s
    ob_hi = ob_lo + (block_s - 1)
    # edge block j intersects out block o iff block_first[j] <= ob_hi[o]
    # and block_last[j] >= ob_lo[o]; with sorted ids the j's are contiguous.
    eb_start = jnp.searchsorted(block_last, ob_lo, side="left").astype(jnp.int32)
    eb_end = jnp.searchsorted(block_first, ob_hi, side="right").astype(jnp.int32)
    eb_count = jnp.maximum(eb_end - eb_start, 0)
    eb_start = jnp.minimum(eb_start, num_eb - 1)

    steps = max_steps if max_steps is not None else num_eb
    out = segment_sum_sorted_pallas(
        data_p,
        seg_p.astype(jnp.int32),
        eb_start,
        eb_count,
        ns_pad,
        block_e=block_e,
        block_s=block_s,
        max_steps=steps,
        interpret=default_interpret() if impl == "pallas" else True,
    )
    return out[:num_segments]
