"""95th percentile latency of every request due in the traced window,
from its due time to the return of the ``run()`` that delivered it (the
serving driver's own reading; host stalls of seconds swing it, so it is
recorded per layer and judged by no bound)."""


def read(ctx):
    return ctx.window.get("metrics", {}).get("p95_ms")
