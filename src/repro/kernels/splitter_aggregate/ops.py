"""Public wrapper for the RS5 aggregation kernel (pads + dispatches)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.splitter_aggregate.ref import splitter_aggregate_ref
from repro.kernels.splitter_aggregate.splitter_aggregate import (
    splitter_aggregate_pallas,
)


@partial(jax.jit, static_argnames=("impl", "block_n"))
def splitter_aggregate(
    packed: jax.Array,
    sprank: jax.Array,
    *,
    impl: str = "auto",
    block_n: int = 2048,
) -> jax.Array:
    # "auto" is the XLA path: the chip's compiler refuses the kernel
    # (kernels/__init__).
    if impl in ("auto", "xla"):
        return splitter_aggregate_ref(packed, sprank)
    n = packed.shape[0]
    pad = (-n) % block_n
    padded = jnp.pad(packed, ((0, pad), (0, 0)))  # owner 0 / local 0: harmless
    interpret = default_interpret() if impl == "pallas" else True
    out = splitter_aggregate_pallas(
        padded, sprank, block_n=block_n, interpret=interpret
    )
    return out[:n]
