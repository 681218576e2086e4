"""Per-phase summary table for an exported Chrome trace.

    python -m repro.obs.summarize trace.json
    python -m repro.obs.summarize trace.json --require serve.wave

Reads the ``{"traceEvents": [...]}`` JSON written by
``repro.obs.trace.export_chrome`` (a bare event list also works),
aggregates the complete events (``ph="X"``) by span name, and prints
count / total / self / mean / max wall time per phase and the phase's
summed counters (``args.counts``), widest total first -- the quick
answer to "where did the time go" without opening Perfetto. A span's
self time is its duration less that of its children, the spans whose
``args.parent_id`` names it.

``--require SUBSTR`` (repeatable) exits nonzero unless at least one
complete event's name contains the substring: CI's traced-smoke step
uses it to assert the serve lifecycle spans (wave, retry, bisection
probe) actually appeared in the trace.

Pure stdlib -- no jax, no repro imports -- so it runs anywhere the
JSON does.
"""
from __future__ import annotations

import argparse
import json
import sys


def load_events(path: str) -> list[dict]:
    """The event list from a Chrome-trace JSON file (object or list)."""
    with open(path) as f:
        payload = json.load(f)
    events = payload.get("traceEvents") if isinstance(payload, dict) else payload
    if not isinstance(events, list):
        raise ValueError(f"{path}: not a Chrome trace (no traceEvents list)")
    return events


def summarize(events: list[dict]) -> list[tuple]:
    """[(name, count, total_us, self_us, mean_us, max_us, counts)]
    sorted by total desc; ``counts`` sums the spans' ``args.counts``."""
    spans = [ev for ev in events if ev.get("ph") == "X"]
    children_us: dict = {}
    for ev in spans:
        parent = ev.get("args", {}).get("parent_id")
        if parent:
            children_us[parent] = (children_us.get(parent, 0.0)
                                   + float(ev.get("dur", 0.0)))
    agg: dict[str, list] = {}
    for ev in spans:
        args = ev.get("args", {})
        dur = float(ev.get("dur", 0.0))
        row = agg.setdefault(ev["name"], [0, 0.0, 0.0, 0.0, {}])
        row[0] += 1
        row[1] += dur
        row[2] += max(0.0, dur - children_us.get(args.get("span_id"), 0.0))
        row[3] = max(row[3], dur)
        for k, v in args.get("counts", {}).items():
            row[4][k] = row[4].get(k, 0) + v
    return sorted(
        (
            (name, int(cnt), total, own, total / cnt, mx, counts)
            for name, (cnt, total, own, mx, counts) in agg.items()
        ),
        key=lambda r: -r[2],
    )


def format_table(rows: list[tuple]) -> str:
    if not rows:
        return "(no complete spans in trace)"
    w = max(len(r[0]) for r in rows)
    lines = [
        f"{'span':<{w}}  {'count':>7}  {'total_ms':>10}  {'self_ms':>10}  "
        f"{'mean_us':>10}  {'max_us':>10}  counts"
    ]
    for name, cnt, total, own, mean, mx, counts in rows:
        tail = " ".join(f"{k}={v}" for k, v in sorted(counts.items()))
        lines.append(
            f"{name:<{w}}  {cnt:>7}  {total / 1e3:>10.3f}  "
            f"{own / 1e3:>10.3f}  {mean:>10.1f}  {mx:>10.1f}  {tail}"
        .rstrip())
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.obs.summarize", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("trace", help="Chrome-trace JSON from trace.export_chrome")
    ap.add_argument(
        "--require", action="append", default=[], metavar="SUBSTR",
        help="fail unless a complete span name contains SUBSTR "
             "(repeatable; CI's traced-smoke assertion)",
    )
    args = ap.parse_args(argv)

    events = load_events(args.trace)
    rows = summarize(events)
    print(format_table(rows))
    n_inst = sum(1 for ev in events if ev.get("ph") == "i")
    print(f"# {len(rows)} phases, {sum(r[1] for r in rows)} spans, "
          f"{n_inst} instant events")
    missing = [
        s for s in args.require if not any(s in r[0] for r in rows)
    ]
    if missing:
        print(f"# REQUIRE FAIL: no span matching {missing}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
