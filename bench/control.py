"""The control of a cell: the plain reference with one guarantee broken,
put in the program's place, judged by the cell's own comparison.

    python bench/control.py --workload g500-wcc --seeds 1,2,3 --seconds 10

For each seed it makes the cell's inputs at the cell's own size (the
whole request stream of a ``--seconds`` window, for a serving cell),
runs the reference's ``control`` on them, and prints the numbers the
comparison reads beside the cell's limits. A control must fail at
least one of them: that is what shows the comparison can fail. The
benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys

import run as harness


def readings(cell) -> dict:
    driver = cell.load("drivers", cell.workload["driver"]).Driver(cell)
    numbers = driver.control()
    limits = cell.workload["limits"]
    return {"seed": cell.seed, "numbers": numbers, "limits": limits,
            "fails": any(numbers[k] > limits[k] for k in limits)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    chips = {w["name"]: w["chips"] for w in spec["workloads"]}[args.workload]
    try:
        harness.devices(int(chips))
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    failed_all = True
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = harness.make_cell(spec, args.workload, seed, args.seconds,
                                 (harness.BENCH,))
        r = readings(cell)
        failed_all &= r["fails"]
        print(json.dumps(r), flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
