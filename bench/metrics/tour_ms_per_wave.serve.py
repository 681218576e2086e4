"""Milliseconds per wave in the tree-analytics pipeline's Euler-tour
stage: the ``trees.tour`` spans of ``repro.obs`` in the window, over the
engine's ``waves`` counter."""
import program_spans


def read(ctx):
    waves = ctx.window.get("waves")
    return program_spans.ms_per(ctx.spans, "trees.tour", waves)
