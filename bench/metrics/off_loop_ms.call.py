"""Milliseconds per call outside the engine's level loop: each call's
wall time less its ``cc.frontier`` / ``sssp.frontier`` span of
``repro.obs`` -- dispatch, dedup, transfers, the sampling pre-pass and
what follows the loop."""

LOOPS = ("cc.frontier", "sssp.frontier")


def read(ctx):
    calls = ctx.window.get("calls")
    loops = [e["dur"] for e in ctx.spans if e["name"] in LOOPS]
    if not calls or not loops:
        return None
    wall = sum(end - start for start, end in calls)
    return (wall - sum(loops) / 1e6) * 1e3 / len(calls)
