"""Edge slots relaxed per SSSP call: the ``sssp.relax_slots`` counts
(each level's bucket size) of the root ``sssp.call`` spans of
``repro.obs``, over the calls. Nothing where the program has no
``sssp.call`` span."""
import program_spans


def read(ctx):
    if not any(e["name"] == "sssp.call" for e in ctx.spans):
        return None
    calls = len(ctx.window.get("calls", ()))
    return program_spans.counted_per(ctx.spans, "sssp.call", "sssp.relax_slots",
                                     calls, roots=True)
