"""Median latency of every request due in the traced window, from its
due time to the return of the ``run()`` that delivered it (the serving
driver's own reading, recorded per layer)."""


def read(ctx):
    return ctx.window.get("metrics", {}).get("p50_ms")
