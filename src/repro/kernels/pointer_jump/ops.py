"""Public wrapper: VMEM pointer jumping (Pallas kernel) or its XLA path."""
from __future__ import annotations

import math
from functools import partial

import jax

from repro.kernels import default_interpret
from repro.kernels.pointer_jump.pointer_jump import pointer_jump_pallas
from repro.kernels.pointer_jump.ref import pointer_jump_ref


@partial(jax.jit, static_argnames=("iters", "impl"))
def pointer_jump(
    nxt: jax.Array,
    w: jax.Array,
    *,
    iters: int | None = None,
    impl: str = "auto",
) -> tuple[jax.Array, jax.Array]:
    p = nxt.shape[0]
    iters = iters if iters is not None else max(1, math.ceil(math.log2(max(p, 2))))
    if impl == "auto":
        impl = "xla"  # the chip's compiler refuses the kernel (kernels/__init__)
    if impl == "pallas":
        return pointer_jump_pallas(nxt, w, iters=iters, interpret=default_interpret())
    if impl == "pallas_interpret":
        return pointer_jump_pallas(nxt, w, iters=iters, interpret=True)
    if impl == "xla":
        return pointer_jump_ref(nxt, w, iters=iters)
    raise ValueError(f"unknown impl {impl!r}")
