"""Share of the traced window in which no operation ran on the device
(``trace_reduce``), for every cell: ``idle_pct.call`` over back-to-back
library calls, ``idle_pct.serve`` under open-loop serving, where waiting
for arrivals counts as idle."""
import trace_reduce


def read(ctx):
    return trace_reduce.idle_pct(ctx.trace)
