"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel directory holds:
  <name>.py  -- pl.pallas_call + explicit BlockSpec VMEM tiling
  ops.py     -- jit'd public wrapper (chooses pallas vs xla path)
  ref.py     -- pure-jnp oracle used by tests and by CPU dry-runs

Kernels are written for TPU as the target and validated with
``interpret=True`` on CPU (the kernel body runs as plain JAX ops).

The TPU v5e compiler refuses the graph kernels (``pointer_jump``,
``edge_hook``, ``splitter_aggregate``: their 1-D in-VMEM ``jnp.take``
raises ``NotImplementedError: Only 2D gather is supported``) and
``segment_sum`` at its default 512-row edge block (the operand layout
must match XLA's ``T(1024)`` tiling). Their ``impl="auto"`` therefore
always takes the XLA path; an explicit ``impl="pallas"`` on the chip
compiles the kernel and fails with the compiler's reason -- it never
falls back to interpret mode. ``tests/test_tpu_compile.py`` holds these
verdicts. ``flash_attention`` compiles and keeps its TPU auto rule.
"""
import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    """Interpret mode everywhere except a real TPU backend."""
    return not on_tpu()
