"""Frontier levels per call: the ``cc.frontier.level`` /
``sssp.level`` spans of ``repro.obs`` (each level is at least one host
round trip), over the calls in the window."""

LEVELS = ("cc.frontier.level", "sssp.level")


def read(ctx):
    calls = ctx.window.get("calls")
    levels = sum(e["name"] in LEVELS for e in ctx.spans)
    if not calls or not levels:
        return None
    return levels / len(calls)
