"""Milliseconds per wave the host waits for a wave's device results:
the ``serve.wave.readback`` spans of ``repro.obs`` in the window, over
the engine's ``waves`` counter."""
import program_spans


def read(ctx):
    return program_spans.ms_per(ctx.spans, "serve.wave.readback",
                                ctx.window.get("waves"))
