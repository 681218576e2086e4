"""Milliseconds per call in the SSSP parents post-pass (one pass over
every arc after the distance fixpoint): the ``sssp.parents`` spans of
``repro.obs``, which block on the pass in a traced run, over the calls.
Nothing where the program has no ``sssp.call`` span."""
import program_spans


def read(ctx):
    if not any(e["name"] == "sssp.call" for e in ctx.spans):
        return None
    calls = len(ctx.window.get("calls", ()))
    return program_spans.ms_per(ctx.spans, "sssp.parents", calls)
