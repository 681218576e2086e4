"""``dense_levels_per_call``: the SSSP levels per call that relaxed the
whole arc list uncompacted. Nothing for a program without the
``sssp.call`` span or the levels' ``dense`` tag; the root spans' count
on a synthetic window; ``SsspStats.dense_levels`` on real calls; and a
traced scale-8 ``g500-sssp`` run that reports it."""
import io
import json
from types import SimpleNamespace

import numpy as np
import pytest

import run as harness
from conftest import BENCH

NAME = "dense_levels_per_call"


def reader():
    return harness.metric_reader(NAME, (BENCH,)).read


def span(name, sid, parent, counts=None, **tags):
    args = {"span_id": sid, "parent_id": parent, **tags}
    if counts:
        args["counts"] = counts
    return {"name": name, "ph": "X", "ts": 0.0, "dur": 5.0, "args": args}


def ctx(spans, calls=2):
    return SimpleNamespace(window={"calls": [(0.0, 1.0)] * calls}, spans=spans)


def test_listed_with_the_sssp_cell_only():
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    (entry,) = [m for m in spec["per_layer"] if m["name"] == NAME]
    assert entry["workloads"] == ["g500-sssp"] and entry["moves"] == "call_s"
    assert spec["per_layer"][-1] is entry


@pytest.mark.parametrize("spans", [
    [span("sssp.level", 2, 1, bucket=64, live=3, dense=False)],
    [span("sssp.call", 1, 0, {"host_sync": 3}),
     span("sssp.level", 2, 1, bucket=64, live=3)],
], ids=["no-sssp-call", "levels-without-dense-tag"])
def test_reads_nothing_for_a_program_without_it(spans):
    assert reader()(ctx(spans)) is None


def test_counts_the_root_calls_over_the_calls():
    spans = [
        span("sssp.call", 1, 0, {"sssp.dense_levels": 6}),
        span("sssp.level", 2, 1, bucket=2**23, live=5_000_000, dense=True),
        span("sssp.call", 3, 0, {"sssp.dense_levels": 7}),
        span("sssp.call", 4, 3, {"sssp.dense_levels": 7}),  # nested: not a root
        span("sssp.call", 5, 0),
        span("sssp.level", 6, 5, bucket=1024, live=9, dense=False),
    ]
    assert reader()(ctx(spans, calls=4)) == pytest.approx(13 / 4)


def test_matches_the_engines_count_on_real_calls():
    from repro.core import shortest_paths
    from repro.obs import trace

    r = np.random.default_rng(7)
    src, dst = (r.integers(0, 300, 1000).astype(np.int32) for _ in range(2))
    w = r.random(1000).astype(np.float32)

    def call(**kw):
        return shortest_paths(src, dst, w, 300, sources=5, min_bucket=64, **kw)

    *_, stats = call(with_stats=True)
    assert stats.dense_levels > 0
    trace.reset()
    trace.configure(trace="on")
    try:
        for _ in range(2):
            call()
        spans = trace.chrome_trace()["traceEvents"]
    finally:
        trace.configure(trace="off")
        trace.reset()
    assert reader()(ctx(spans, calls=2)) == stats.dense_levels


def test_traced_cell_reports_it(tmp_path):
    config = json.loads((BENCH / "configs" / "graph500-s18-sssp.json").read_text())
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / "graph500-s18-sssp.json").write_text(
        json.dumps(dict(config, scale=8)))
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run(
        ["--workload", "g500-sssp", "--seed", str(2**33 + 29), "--seconds",
         "0.5", "--trace", "1"],
        search=(tmp_path, BENCH), require_tpu=False, out=out, err=err)
    res = json.loads(out.getvalue().splitlines()[-1])
    assert rc == 0 and res["correct"], err.getvalue()
    assert res["metrics"][NAME]["value"] > 0
    assert res["metrics"][NAME]["value"] <= res["metrics"]["levels_per_call"]["value"]
