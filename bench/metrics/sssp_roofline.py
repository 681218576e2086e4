"""Share of the HBM roofline that the shortest-paths calls reach: the
fewest bytes any implementation must move, at the chip's peak HBM
bandwidth, over the device's busy time in the traced window.

The bytes come from the input's shapes alone, never from what the
program did, so every version of the program is held to the same work:
each input edge read once (two int32 endpoints and a float32 weight,
12 B) and each vertex's distance and parent written once (float32 and
int32, 8 B).
"""


def floor_bytes(num_nodes: int, num_edges: int) -> int:
    return 12 * num_edges + 8 * num_nodes


def read(ctx):
    t, calls = ctx.trace, ctx.window.get("calls")
    if not t or not t["busy_s"] or not calls or not ctx.peaks:
        return None
    s = ctx.shapes
    least = floor_bytes(s["num_nodes"], s["num_edges"]) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least * len(calls) / t["busy_s"]
