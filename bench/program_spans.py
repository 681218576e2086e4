"""What the program's own ``repro.obs`` spans say about a traced window,
for the per-layer metrics that read them.

A program whose spans carry parent links (``args.span_id``) records
every span and counter these metrics read, so where its window holds
none of them a metric reads 0, and stays visible once a change takes
the work away. A program without parent links predates those spans:
there a metric reads None and is left out of the result line.
"""


def instrumented(spans) -> bool:
    return any("span_id" in e.get("args", {}) for e in spans)


def _spans(spans, name):
    return [e for e in spans if e["name"] == name and e.get("ph") == "X"]


def ms_per(spans, name: str, per: int):
    """Milliseconds in the spans called ``name``, over ``per``."""
    if not per or not instrumented(spans):
        return None
    return sum(e["dur"] for e in _spans(spans, name)) / 1e3 / per


def spans_per(spans, name: str, per: int):
    """Spans called ``name``, over ``per``."""
    if not per or not instrumented(spans):
        return None
    return len(_spans(spans, name)) / per


def counted_per(spans, name: str, counter: str, per: int, roots=False):
    """Counter ``counter`` summed over the spans called ``name`` (only
    those with no parent where ``roots``), over ``per``; a span's
    counts cover every span beneath it."""
    if not per or not instrumented(spans):
        return None
    return sum(e["args"].get("counts", {}).get(counter, 0)
               for e in _spans(spans, name)
               if not roots or e["args"].get("parent_id") == 0) / per
