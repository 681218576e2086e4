"""Milliseconds per wave in the tree-analytics pipeline's forest stage
(connected components with hook recording, and the read of the forest
to the host): the ``trees.forest`` spans of ``repro.obs`` in the window,
over the engine's ``waves`` counter."""
import program_spans


def read(ctx):
    waves = ctx.window.get("waves")
    return program_spans.ms_per(ctx.spans, "trees.forest", waves)
