"""The knee of an open-loop serving cell: the highest offered rate that
the engine sustains without a growing backlog.

    python bench/sweep_knee.py --workload mol-analytics --seed 1 --seconds 10 \
        --rates 200,400,800,1600

Runs the cell's driver at each rate in turn, in one process (warm-up
included at each rate; programs compiled for one rate are reused by the
next), and prints one JSON line per rate and a last line with the knee.

The backlog grows at a rate when either (a) the last request completes
more than ``DRAIN_SHARE`` of the window after the window closes, or
(b) the median latency of the requests due in the window's last quarter
is more than twice that of the first quarter, plus ``SLACK_MS``.
The cell's rate is then set to four fifths of the knee by hand, in its
workload file.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys

import numpy as np

import run as harness

DRAIN_SHARE = 0.05
SLACK_MS = 20.0


def backlog_grows(w: dict, seconds: float) -> bool:
    drain = w["last_completion_s"] - seconds
    due, lat = w["due"], w["latency_ms"]
    first = lat[due < seconds / 4]
    last = lat[due >= 3 * seconds / 4]
    if not len(first) or not len(last):
        return True
    return bool(drain > DRAIN_SHARE * seconds
                or np.median(last) > 2 * np.median(first) + SLACK_MS)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, requests per second")
    args = ap.parse_args(argv)

    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cell = harness.make_cell(spec, args.workload, args.seed, args.seconds,
                             (harness.BENCH,))
    try:
        devs = harness.devices(int(cell.entry["chips"]))
    except harness.NoChip as e:
        print(f"sweep_knee: {e}", file=sys.stderr)
        return 2
    module = cell.load("drivers", cell.workload["driver"])
    knee, over = None, 0
    for rate in sorted(float(r) for r in args.rates.split(",")):
        if over == 2:  # two rates in a row past the knee: the rest are too
            break
        driver = module.Driver(cell, rate=rate)
        driver.warm_up()
        w = driver.window(args.seconds, lambda name: contextlib.nullcontext())
        grows = backlog_grows(w, args.seconds)
        over = over + 1 if grows else 0
        if not grows:
            knee = rate
        print(json.dumps({"rate": rate, **w["metrics"],
                          "requests": w["attempted"],
                          "completed": w["completed"], "waves": w["waves"],
                          "drain_s": w["last_completion_s"] - args.seconds,
                          "generator_late_ms": w["generator_late_ms"],
                          "backlog_grows": grows}), flush=True)
    print(json.dumps({"knee_rps": knee, "device": devs[0].device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
