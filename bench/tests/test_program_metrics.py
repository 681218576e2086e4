"""The per-layer metrics that read the program's own ``repro.obs``
spans and counters: 0 where an instrumented window holds none, None for
a program without parent links, the right value on a synthetic window,
and ``syncs_per_call`` against the hand count of a real call."""
import json
from types import SimpleNamespace

import numpy as np
import pytest

import run as harness
from conftest import BENCH

SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
NEW = ["dedup_ms.call", "syncs_per_call", "lowerings_per_call",
       "forest_ms_per_wave.serve", "tour_ms_per_wave.serve",
       "rank_ms_per_wave.serve", "readback_ms_per_wave.serve",
       "syncs_per_wave.serve"]


def reader(name):
    return harness.metric_reader(name, (BENCH,)).read


def span(name, dur_us, sid, parent, **counts):
    args = {"span_id": sid, "parent_id": parent}
    if counts:
        args["counts"] = counts
    return {"name": name, "ph": "X", "ts": 0.0, "dur": dur_us, "args": args}


def ctx(spans, calls=2, waves=4):
    window = {"calls": [(0.0, 1.0)] * calls, "waves": waves}
    return SimpleNamespace(window=window, spans=spans, trace=None,
                           shapes={}, peaks=None)


def test_each_new_metric_is_listed_with_its_cell():
    entries = {m["name"]: m for m in SPEC["per_layer"]}
    for name in NEW:
        m = entries[name]
        cell = "g500-wcc" if m["moves"] == "call_s" else "mol-analytics"
        assert m["workloads"] == [cell]
    assert [m["name"] for m in SPEC["per_layer"]][-len(NEW):] == NEW


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_zero_on_an_empty_instrumented_window(name):
    assert reader(name)(ctx([span("bench.other", 5.0, 1, 0)])) == 0.0


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_none_without_parent_links(name):
    old = [{"name": n, "ph": "X", "ts": 0.0, "dur": 7.0, "args": {}}
           for n in ("cc.frontier", "serve.wave.engine", "cc.call")]
    assert reader(name)(ctx(old)) is None
    assert reader(name)(ctx([], calls=0, waves=0)) is None


SYNTHETIC = [
    span("cc.call", 9000.0, 1, 0, host_sync=12),
    span("cc.dedup", 3000.0, 2, 1),
    span("jax.lower", 10.0, 3, 1),
    span("cc.call", 9000.0, 4, 0, host_sync=12),
    span("cc.dedup", 5000.0, 5, 4),
    span("jax.lower", 10.0, 6, 4),
    span("jax.lower", 10.0, 7, 4),
    span("cc.call", 100.0, 8, 4, host_sync=3),  # nested: not a root
    span("serve.run", 50000.0, 9, 0, host_sync=30),
    span("serve.run", 20000.0, 10, 0, host_sync=10),
    span("trees.forest", 1000.0, 11, 9),
    span("trees.forest", 3000.0, 12, 10),
    span("trees.tour", 400.0, 13, 9),
    span("trees.rank", 200.0, 14, 9),
    span("serve.wave.readback", 800.0, 15, 9),
]


@pytest.mark.parametrize("name,value", [
    ("dedup_ms.call", 4.0), ("syncs_per_call", 12.0),
    ("lowerings_per_call", 1.5), ("forest_ms_per_wave.serve", 1.0),
    ("tour_ms_per_wave.serve", 0.1), ("rank_ms_per_wave.serve", 0.05),
    ("readback_ms_per_wave.serve", 0.2), ("syncs_per_wave.serve", 10.0),
])
def test_reader_value_on_a_synthetic_window(name, value):
    assert reader(name)(ctx(SYNTHETIC)) == pytest.approx(value)


def test_syncs_per_call_matches_the_hand_count():
    """A 1024-node chain through the pre-pass climbs three levels: the
    pre-pass live count, rounds + changed + s on the two levels that do
    not converge and rounds + changed on the last, two live counts
    before the shrinks and the final s: 12."""
    from repro.core import connected_components
    from repro.obs import trace

    src = np.arange(1023, dtype=np.int32)

    def call():
        return connected_components(src, src + 1, 1024, engine="frontier",
                                    min_bucket=16, sample_rounds=2)

    call()
    trace.reset()
    trace.configure(trace="on")
    try:
        for _ in range(2):
            call()
        spans = trace.chrome_trace()["traceEvents"]
    finally:
        trace.configure(trace="off")
        trace.reset()
    window = ctx(spans, calls=2)
    assert reader("syncs_per_call")(window) == 12.0
    assert reader("lowerings_per_call")(window) >= 1.0
    assert reader("dedup_ms.call")(window) > 0.0
