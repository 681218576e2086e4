"""Public wrapper: fused SV hook (Pallas kernel) or the unfused XLA phases.

``impl="auto"`` runs the XLA phases everywhere: the chip's compiler
refuses the kernel's 1-D in-VMEM gathers (``kernels/__init__``).
``"pallas"`` compiles the kernel on a TPU backend (interpreted
elsewhere); ``"pallas_interpret"`` runs the kernel body as plain JAX
ops for CPU validation.

The kernel is **shard-local by construction**: it reads only the edge
arrays it is handed and the replicated label/stamp state, so the
sharded frontier engine (``distributed/graph``, ``hook_impl=``) runs it
unchanged inside ``shard_map`` -- each device fuses the hook phases
over its own compacted edge bucket, and the per-round label exchanges
see identical arrays either way.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.edge_hook.edge_hook import edge_hook_pallas
from repro.kernels.edge_hook.ref import edge_hook_ref


@partial(jax.jit, static_argnames=("mode", "impl", "block_e"))
def edge_hook(
    a: jax.Array,
    b: jax.Array,
    labels: jax.Array,
    stamps: jax.Array,
    s: jax.Array,
    *,
    labels_prev: jax.Array | None = None,
    mode: str = "sv2",
    impl: str = "auto",
    block_e: int = 8192,
) -> tuple[jax.Array, jax.Array]:
    """Fused hook phase over all edges. Returns (labels_out, stamps_out).

    ``labels_prev`` (the pre-shortcut labels) is required for mode="sv2"
    (the stagnant-tree check); mode="sv3" ignores it.
    """
    prev = labels_prev if labels_prev is not None else labels
    if impl in ("auto", "xla"):
        return edge_hook_ref(a, b, labels, prev, stamps, s, mode=mode)
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(f"unknown impl {impl!r}")
    interpret = default_interpret() if impl == "pallas" else True
    m = a.shape[0]
    pad = (-m) % block_e if m else block_e
    # (0, 0) self-loop padding is inert under both hook conditions.
    a = jnp.concatenate([a.astype(jnp.int32), jnp.zeros(pad, jnp.int32)])
    b = jnp.concatenate([b.astype(jnp.int32), jnp.zeros(pad, jnp.int32)])
    return edge_hook_pallas(
        a, b, labels, prev, stamps, s,
        mode=mode, block_e=block_e, interpret=interpret,
    )
