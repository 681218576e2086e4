"""Frontier levels per SSSP call that relaxed the whole arc list
uncompacted: the ``sssp.dense_levels`` counts of the root ``sssp.call``
spans of ``repro.obs``, over the calls. Nothing where the program has
no ``sssp.call`` span, or levels without the ``dense`` tag (a program
that compacts at every level)."""
import program_spans


def read(ctx):
    if not any(e["name"] == "sssp.call" for e in ctx.spans):
        return None
    if not any("dense" in e.get("args", {}) for e in ctx.spans
               if e["name"] == "sssp.level"):
        return None
    calls = len(ctx.window.get("calls", ()))
    return program_spans.counted_per(ctx.spans, "sssp.call", "sssp.dense_levels",
                                     calls, roots=True)
