"""Milliseconds per call in the host's edge dedup (``np.unique`` over
the edge rows): the ``cc.dedup`` spans of ``repro.obs`` in the window,
over the calls."""
import program_spans


def read(ctx):
    calls = len(ctx.window.get("calls", ()))
    return program_spans.ms_per(ctx.spans, "cc.dedup", calls)
