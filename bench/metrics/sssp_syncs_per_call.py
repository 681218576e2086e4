"""Device-to-host reads per SSSP call: the ``host_sync`` counts of the
root ``sssp.call`` spans of ``repro.obs`` (the live count of each
frontier level, and the last one that finds the frontier empty), over
the calls. Nothing where the program has no ``sssp.call`` span."""
import program_spans


def read(ctx):
    if not any(e["name"] == "sssp.call" for e in ctx.spans):
        return None
    calls = len(ctx.window.get("calls", ()))
    return program_spans.counted_per(ctx.spans, "sssp.call", "host_sync", calls,
                                     roots=True)
