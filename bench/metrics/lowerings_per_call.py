"""JAX lowerings per call inside the window: the ``jax.lower`` spans
that ``repro.obs`` records from JAX's own compile events, over the
calls. Each is a program traced and lowered again (then compiled, or
fetched from the persistent cache) on the timed path."""
import program_spans


def read(ctx):
    calls = len(ctx.window.get("calls", ()))
    return program_spans.spans_per(ctx.spans, "jax.lower", calls)
