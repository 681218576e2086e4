"""Reduce a ``jax.profiler`` trace to device busy time, top device ops,
and idle gaps labelled by what the host was doing.

* Device ops: on a TPU, the events of the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane (its ``XLA Modules`` line where it has no op
  line); on the CPU backend (used by the tests), the host events that
  carry an ``hlo_op`` stat.
* The window: the span of the harness's ``bench.window`` annotation.
* Busy: the union of device-op intervals inside the window, averaged
  over the devices that ran any op.
* Idle gaps: the holes in that union on the first such device, summed
  by the name of the narrowest host interval that covers the gap's
  midpoint (``untracked`` where none does). Host intervals are the
  harness's annotations (``bench.*``), the host dispatch events of
  jitted programs (``PjitFunction(...)``) and the program's own
  ``repro.obs`` spans, moved onto the profiler's clock through the
  ``bench.anchor`` annotation, which the harness opens at the moment it
  records the ``bench.anchor`` instant event in ``repro.obs``.
"""
from __future__ import annotations

import bisect
import glob
import heapq
import os
from collections import defaultdict

TOP = 10
# Device-plane lines that hold device work, most detailed first.
DEVICE_LINES = ("XLA Ops", "XLA Modules")


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def read_planes(path: str, platform: str):
    """(device ops per device, host intervals, annotations) from one
    ``.xplane.pb``: ops as {device: [(start_ns, end_ns, name)]}, host
    intervals and annotations as [(start_ns, end_ns, name)]."""
    from jax.profiler import ProfileData

    ops = defaultdict(list)
    host, notes = [], []
    for plane in ProfileData.from_file(path).planes:
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                start, end = ev.start_ns, ev.start_ns + ev.duration_ns
                name = ev.name
                if on_device:
                    if platform != "cpu" and line.name in DEVICE_LINES:
                        ops[(plane.name, line.name)].append((start, end, name))
                    continue
                if platform == "cpu":
                    stats = dict(ev.stats)
                    if "hlo_op" in stats:
                        mod = stats.get("hlo_module", "")
                        ops[("cpu", "XLA Ops")].append((start, end, f"{mod}:{name}"))
                        continue
                if name.startswith("bench."):
                    notes.append((start, end, name))
                elif name.startswith("PjitFunction("):
                    host.append((start, end, name))
    # One line per device: its ops, named by the program that ran them,
    # or its programs where a device plane has no op line.
    best = {}
    for (plane, line), evs in ops.items():
        if plane not in best or DEVICE_LINES.index(line) < DEVICE_LINES.index(best[plane][0]):
            best[plane] = (line, evs)
    for plane, (line, evs) in best.items():
        modules = ops.get((plane, "XLA Modules"))
        if line == "XLA Ops" and modules:
            best[plane] = (line, in_modules(evs, modules))
    return {plane: evs for plane, (_, evs) in best.items()}, host, notes


def in_modules(ops, modules):
    """Each op as ``<program>:<op>``, the program being the module event
    that covers the op's start. A TPU op event is named by its whole HLO
    instruction; only the instruction's name (before `` = ``) is kept."""
    modules = sorted(modules)
    starts = [s for s, _, _ in modules]
    out = []
    for s, e, name in ops:
        i = bisect.bisect_right(starts, s) - 1
        program = modules[i][2] if i >= 0 and modules[i][1] >= s else "untracked"
        out.append((s, e, f"{program}:{name.split(' = ', 1)[0]}"))
    return out


def spans_on_clock(events, notes):
    """``repro.obs`` complete events as (start_ns, end_ns, name) on the
    profiler's clock, placed by the ``bench.anchor`` instant event and
    annotation; [] without an anchor."""
    anchor_ts = [e["ts"] for e in events if e["name"] == "bench.anchor"]
    anchor_ns = [s for s, _, n in notes if n == "bench.anchor"]
    if not anchor_ts or not anchor_ns:
        return []
    shift = anchor_ns[0] - anchor_ts[0] * 1e3
    return [(e["ts"] * 1e3 + shift, (e["ts"] + e["dur"]) * 1e3 + shift,
             e["name"]) for e in events if e.get("ph") == "X"]


def label_gaps(gaps, host):
    """The name of the narrowest host interval that covers each gap's
    midpoint, else ``untracked``: one sweep over both, in time order."""
    points = sorted(((g0 + g1) / 2, i) for i, (g0, g1) in enumerate(gaps))
    starts = sorted(host)
    ends = []  # heap of (end, index) of the intervals begun so far
    out = ["untracked"] * len(gaps)
    j = 0
    active = {}
    for t, i in points:
        while j < len(starts) and starts[j][0] <= t:
            s, e, name = starts[j]
            active[j] = (e - s, e, name)
            heapq.heappush(ends, (e, j))
            j += 1
        while ends and ends[0][0] < t:
            active.pop(heapq.heappop(ends)[1], None)
        if active:
            out[i] = min(active.values())[2]
    return out


def self_times(ops) -> dict:
    """Time per op name, each op's time less that of the ops nested in
    it: a TPU's op line holds a while loop and its body's ops both, so
    the times sum to the busy time and nothing counts twice."""
    out = defaultdict(float)
    stack = []  # (end, name) of the ops that enclose the current one
    for s, e, name in sorted(ops, key=lambda op: (op[0], -op[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]] -= min(e, stack[-1][0]) - s
        out[name] += e - s
        stack.append((e, name))
    return out


def _top(seconds: dict) -> list:
    return [[k, v] for k, v in sorted(seconds.items(), key=lambda kv: -kv[1])[:TOP]]


def idle_pct(reduced) -> float | None:
    """Share of the window in which no operation ran on the device, in
    percent."""
    if not reduced:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])


def reduce(path: str, platform: str, spans=()) -> dict:
    """{"window_s", "busy_s", "devices", "device_ops", "idle_gaps"} of
    the trace at ``path``; ``spans`` are ``repro.obs`` chrome events."""
    ops, host, notes = read_planes(path, platform)
    win = [(s, e) for s, e, n in notes if n == "bench.window"]
    if not win:
        raise ValueError("trace has no bench.window annotation")
    lo, hi = win[0]
    busy, by_name = [], defaultdict(float)
    first = None
    for dev in sorted(ops):
        evs = [(s, e, n) for s, e, n in ops[dev] if e > lo and s < hi]
        if not evs:
            continue
        merged = _union(_clip([(s, e) for s, e, _ in evs], lo, hi))
        busy.append(sum(e - s for s, e in merged))
        for n, t in self_times([(max(s, lo), min(e, hi), n) for s, e, n in evs]).items():
            by_name[n] += t * 1e-9
        if first is None:
            first = merged
    if not busy:
        # A traced run in which no device op shows is a trace this
        # reduction cannot read, never an idle device.
        raise ValueError(f"no device op of {platform!r} inside bench.window "
                         f"(lines read: {DEVICE_LINES})")
    gaps = defaultdict(float)
    labelled = host + notes + spans_on_clock(list(spans), notes)
    labelled = [(s, e, n) for s, e, n in labelled
                if n not in ("bench.window", "bench.anchor")]
    edges = [lo] + [x for iv in (first or []) for x in iv] + [hi]
    holes = [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2]) if g1 > g0]
    for (g0, g1), name in zip(holes, label_gaps(holes, labelled)):
        gaps[name] += (g1 - g0) * 1e-9
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": (sum(busy) / len(busy)) * 1e-9,
        "devices": len(busy),
        "device_ops": _top(by_name),
        "idle_gaps": _top(gaps),
    }
