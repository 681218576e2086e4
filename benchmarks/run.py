# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV lines (benchmarks/common.emit).
#
#   python benchmarks/run.py                         # full sweep
#   python benchmarks/run.py --smoke                 # n <= 4096 compile check
#   python benchmarks/run.py --only cc_frontier,fig4_cc --json BENCH_cc.json
#   python benchmarks/run.py --smoke --check BENCH_smoke.json
#
# --json writes the emitted lines as a perf snapshot: a list of
# {suite, name, us_per_call, derived} records, so the repo's perf
# trajectory is diffable commit over commit.
#
# --check SNAPSHOT is the regression guard: it re-runs the snapshot's
# suites (unless --only narrows them) and compares every numeric
# ``key=value`` counter in the ``derived`` fields -- edge visits,
# exchange words, rounds, tree/arc counts -- against the snapshot
# within --check-tol relative tolerance. Wall times are never compared
# (CI machines vary); the counters are deterministic at a given scale,
# so the snapshot must have been produced at the same scale flags
# (CI checks a --smoke snapshot). A snapshot record whose (suite, name)
# is missing from the fresh run fails the check too: losing a counter
# silently is itself a regression.
from __future__ import annotations

import argparse
import json
import os
import traceback

SMOKE_SCALE = "0.005"  # largest suite base is 800_000 -> n=4000 caps the
# smoke lane at n <= 4096 while still compile-checking every perf path


def _parse_line(suite: str, line: str) -> dict:
    name, us, derived = line.split(",", 2)
    return {
        "suite": suite,
        "name": name,
        "us_per_call": float(us),
        "derived": derived,
    }


def _derived_counters(derived: str) -> dict:
    """Numeric key=value pairs from a derived field ("a=1;b=2.5;c=x").

    Keys starting with ``~`` (wall-time spread: ``~p10_us``/``~p90_us``
    from ``common.emit(..., spread=)``) are measurements, not
    deterministic counters -- they are excluded, so --check never
    compares them."""
    out = {}
    for part in derived.split(";"):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        k = k.strip()
        if k.startswith("~"):
            continue
        try:
            out[k] = float(v)
        except ValueError:
            continue
    return out


def check_records(
    snapshot: list[dict], fresh: list[dict], tol: float,
    suites_run: set[str] | None = None,
) -> list[str]:
    """Compare counters in ``fresh`` against ``snapshot``; returns a
    list of human-readable mismatch descriptions (empty = pass).
    Snapshot records from suites outside ``suites_run`` (an explicit
    --only narrowing) are skipped, not reported missing."""
    fresh_by_key = {(r["suite"], r["name"]): r for r in fresh}
    problems = []
    for rec in snapshot:
        if suites_run is not None and rec["suite"] not in suites_run:
            continue
        key = (rec["suite"], rec["name"])
        now = fresh_by_key.get(key)
        where = f"{rec['suite']}/{rec['name']}"
        if now is None:
            problems.append(
                f"{where}: record missing from fresh run"
                f"\n  snapshot derived: {rec['derived']}"
            )
            continue
        want = _derived_counters(rec["derived"])
        got = _derived_counters(now["derived"])
        for k, old in want.items():
            if k not in got:
                problems.append(
                    f"{where}: counter {k} disappeared "
                    f"(snapshot had {k}={old:g})"
                    f"\n  snapshot derived: {rec['derived']}"
                    f"\n  fresh    derived: {now['derived']}"
                )
                continue
            new = got[k]
            if abs(new - old) > tol * max(abs(old), 1.0):
                rel = (new - old) / abs(old) if old else float("inf")
                problems.append(
                    f"{where}: counter {k} expected {old:g}, got {new:g} "
                    f"(rel delta {rel:+.2%}, tol {tol:.0%})"
                    f"\n  snapshot derived: {rec['derived']}"
                    f"\n  fresh    derived: {now['derived']}"
                )
    return problems


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write the emitted records as a JSON perf snapshot")
    ap.add_argument("--smoke", action="store_true",
                    help=f"tiny inputs (REPRO_BENCH_SCALE={SMOKE_SCALE}): "
                         "compile-check every perf path in CI minutes")
    ap.add_argument("--only", metavar="SUITES", default=None,
                    help="comma-separated suite subset to run")
    ap.add_argument("--check", metavar="SNAPSHOT", default=None,
                    help="compare fresh derived counters against this "
                         "snapshot (same scale!); implies --only the "
                         "snapshot's suites unless --only is given")
    ap.add_argument("--check-tol", type=float, default=0.05,
                    help="relative tolerance for --check counters "
                         "(default 0.05)")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="enable repro.obs span tracing for the run and "
                         "write a Chrome-trace JSON here (inspect with "
                         "python -m repro.obs.summarize PATH)")
    args = ap.parse_args(argv)

    if args.smoke:  # must land before benchmarks.common reads the env
        os.environ["REPRO_BENCH_SCALE"] = SMOKE_SCALE

    from repro.launch.compile_cache import use_compile_cache

    print(f"# compile cache: {use_compile_cache()}", flush=True)

    if args.trace:
        from repro.obs import trace as obs_trace

        obs_trace.configure(trace="on")

    snapshot = None
    if args.check:
        with open(args.check) as f:
            snapshot = json.load(f)
        if args.only is None:
            args.only = ",".join(sorted({r["suite"] for r in snapshot}))

    from benchmarks import (
        cc_frontier,
        fig2_scaling,
        fig3_per_element,
        fig4_cc,
        fig5_parallelism,
        fig6_rounds,
        graph_serve,
        moe_dispatch,
        multidev_scaling,
        pagerank,
        roofline_table,
        serve_chaos,
        sssp_frontier,
        table2_packing,
        table3_splitters,
        tree_ops,
    )

    suites = [
        ("table2_packing", table2_packing.run),
        ("table3_splitters", table3_splitters.run),
        ("fig2_scaling", fig2_scaling.run),
        ("fig3_per_element", fig3_per_element.run),
        ("fig4_cc", fig4_cc.run),
        ("cc_frontier", cc_frontier.run),
        ("sssp_frontier", sssp_frontier.run),
        ("pagerank", pagerank.run),
        ("tree_ops", tree_ops.run),
        ("graph_serve", graph_serve.run),
        ("serve_chaos", serve_chaos.run),
        ("fig5_parallelism", fig5_parallelism.run),
        ("fig6_rounds", fig6_rounds.run),
        ("moe_dispatch", moe_dispatch.run),
        ("roofline_table", roofline_table.run),
        # reports this process's device count; run standalone for the
        # 8-fake-device scaling table (see module docstring)
        ("multidev_scaling", multidev_scaling.run),
    ]
    if args.only:
        wanted = {s.strip() for s in args.only.split(",")}
        unknown = wanted - {name for name, _ in suites}
        if unknown:
            raise SystemExit(f"unknown suites: {sorted(unknown)}")
        suites = [(name, fn) for name, fn in suites if name in wanted]

    print("name,us_per_call,derived")
    records, failures = [], []
    for name, fn in suites:
        print(f"# === {name} ===", flush=True)
        try:
            lines = fn() or []
            records.extend(_parse_line(name, ln) for ln in lines)
        except Exception:
            failures.append(name)
            traceback.print_exc()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(records, f, indent=1)
        print(f"# wrote {len(records)} records to {args.json}", flush=True)
    if args.trace:
        from repro.obs import trace as obs_trace

        n_events = obs_trace.export_chrome(args.trace)
        print(
            f"# wrote {n_events} trace events to {args.trace} "
            "(chrome://tracing / Perfetto; summarize with "
            f"python -m repro.obs.summarize {args.trace})",
            flush=True,
        )
    if snapshot is not None and not failures:
        ran = {name for name, _ in suites}
        problems = check_records(
            snapshot, records, args.check_tol, suites_run=ran
        )
        if problems:
            for p in problems:
                # continuation lines stay comment-prefixed so the
                # output remains a valid CSV-with-comments stream
                print("# CHECK FAIL " + p.replace("\n", "\n#"), flush=True)
            raise SystemExit(
                f"--check {args.check}: {len(problems)} counter "
                "regressions (see CHECK FAIL lines)"
            )
        compared = sum(r["suite"] in ran for r in snapshot)
        print(
            f"# check passed: {compared} records within "
            f"{args.check_tol:.0%} of {args.check}",
            flush=True,
        )
    if failures:
        raise SystemExit(f"benchmark suites failed: {failures}")


if __name__ == "__main__":
    main()
