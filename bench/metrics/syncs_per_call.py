"""Device-to-host reads per call: the ``host_sync`` counts of the root
``cc.call`` spans of ``repro.obs`` (one per device value the host
reads), over the calls."""
import program_spans


def read(ctx):
    calls = len(ctx.window.get("calls", ()))
    return program_spans.counted_per(ctx.spans, "cc.call", "host_sync", calls,
                                     roots=True)
