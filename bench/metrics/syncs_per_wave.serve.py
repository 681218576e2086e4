"""Device-to-host reads per wave: the ``host_sync`` counts of the
``serve.run`` spans of ``repro.obs`` in the window (each covers every
wave of one ``run()``), over the engine's ``waves`` counter."""
import program_spans


def read(ctx):
    return program_spans.counted_per(ctx.spans, "serve.run", "host_sync",
                                     ctx.window.get("waves"))
