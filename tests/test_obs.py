"""repro.obs: span tracing + metrics registry (docs/observability.md).

Covers the layer's contracts: disabled tracing is the shared no-op
singleton (zero allocation, zero events), spans nest with monotonic
Chrome-trace timestamps and parent links, counters fold into parents,
JAX lowerings become child spans, spans show in a profiler capture,
the engines' host-sync counts match hand counts, the exported JSON
round-trips, the metrics
snapshot of two identical fault-injected serve runs is identical, and
the instrumentation adds NO device->host sync (the RL001 lint pass
over the instrumented tree, plus a traced jitted-CC runtime smoke).
"""
import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from repro.obs import metrics, trace  # noqa: E402
from repro.obs.metrics import Registry, derived_fragment  # noqa: E402
from repro.obs.summarize import format_table, main, summarize  # noqa: E402
from repro.obs.trace import _NULL_SPAN, Tracer  # noqa: E402


# ---------------------------------------------------------------------------
# tracer: disabled path
# ---------------------------------------------------------------------------


def test_disabled_span_is_the_shared_singleton():
    t = Tracer()  # trace="off" default
    s1 = t.span("a", bucket=4)
    s2 = t.span("b")
    assert s1 is _NULL_SPAN and s2 is _NULL_SPAN
    with s1 as sp:
        assert sp.tag(rounds=3) is sp
        assert sp.block_on("value") == "value"
    t.event("instant", uid=1)
    assert t.events == []


def test_disabled_timer_span_still_times_and_blocks():
    t = Tracer()
    x = jnp.arange(8)
    with t.span("step", device=True, timer=True) as sp:
        y = sp.block_on(x * 2)
    assert sp.duration > 0.0
    assert int(y[-1]) == 14
    assert t.events == []  # timed, not recorded


def test_configure_rejects_unknown_modes():
    t = Tracer()
    with pytest.raises(ValueError, match="trace"):
        t.configure(trace="loud")
    with pytest.raises(ValueError, match="trace"):
        Tracer(trace="always")
    t.configure(trace="on")
    assert t.enabled
    t.configure(trace="off")
    assert not t.enabled


def test_disabled_count_records_and_allocates_nothing():
    import tracemalloc

    from repro.obs import trace as trace_mod

    t = Tracer()
    with t.span("a"):
        t.count("host_sync")
    tracemalloc.start()
    try:
        for _ in range(1000):
            t.count("host_sync")
            t.count("host_sync", 4)
        snap = tracemalloc.take_snapshot().filter_traces(
            [tracemalloc.Filter(True, trace_mod.__file__)]
        )
    finally:
        tracemalloc.stop()
    assert sum(st.size for st in snap.statistics("filename")) == 0
    assert t.events == []


# ---------------------------------------------------------------------------
# tracer: enabled path
# ---------------------------------------------------------------------------


def test_nested_spans_monotonic_and_contained():
    t = Tracer(trace="on")
    with t.span("outer", n=2):
        with t.span("inner", i=0):
            pass
        with t.span("inner", i=1):
            pass
    t.event("marker", uid=9)
    # children record before the parent (close order); the event last
    names = [e["name"] for e in t.events]
    assert names == ["inner", "inner", "outer", "marker"]
    inner0, inner1, outer, marker = t.events
    assert all(e["ts"] >= 0 for e in t.events)
    assert inner0["ts"] <= inner1["ts"] <= marker["ts"]
    # containment: both children inside the parent interval
    for child in (inner0, inner1):
        assert outer["ts"] <= child["ts"]
        assert child["ts"] + child["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert outer["args"] == {"n": 2, "span_id": 1, "parent_id": 0}
    assert inner1["args"] == {"i": 1, "span_id": 3, "parent_id": 1}
    assert marker["ph"] == "i"


def test_span_parent_links_and_counts_fold_into_parents():
    t = Tracer(trace="on")
    with t.span("root"):
        t.count("host_sync")
        with t.span("mid"):
            with t.span("leaf"):
                t.count("host_sync", 2)
                t.count("other")
            t.count("host_sync")
        with t.span("quiet"):
            pass
    t.count("host_sync")  # no open span: dropped
    ev = {e["name"]: e["args"] for e in t.events}
    ids = {name: a["span_id"] for name, a in ev.items()}
    assert len(set(ids.values())) == 4
    assert ev["root"]["parent_id"] == 0
    assert ev["mid"]["parent_id"] == ids["root"]
    assert ev["leaf"]["parent_id"] == ids["mid"]
    assert ev["quiet"]["parent_id"] == ids["root"]
    assert ev["leaf"]["counts"] == {"host_sync": 2, "other": 1}
    assert ev["mid"]["counts"] == {"host_sync": 3, "other": 1}
    assert ev["root"]["counts"] == {"host_sync": 4, "other": 1}
    assert "counts" not in ev["quiet"]


def test_span_stacks_are_per_thread():
    import threading

    t = Tracer(trace="on")
    with t.span("main"):
        th = threading.Thread(target=lambda: t.span("worker").__enter__()
                              .__exit__(None, None, None))
        th.start()
        th.join(timeout=30)
    assert not th.is_alive()
    ev = {e["name"]: e["args"] for e in t.events}
    assert ev["worker"]["parent_id"] == 0


def test_fresh_jit_lowering_is_a_child_span():
    import jax

    x = jnp.arange(16)
    t = Tracer(trace="on")
    with t.span("step"):
        jax.jit(lambda v: v * 3 + 1)(x).block_until_ready()
    t.configure(trace="off")
    jax.jit(lambda v: v * 5 + 1)(x).block_until_ready()  # off: unrecorded
    step = next(e for e in t.events if e["name"] == "step")
    lowers = [e for e in t.events if e["name"] == "jax.lower"]
    assert len(lowers) == 1
    assert lowers[0]["args"]["parent_id"] == step["args"]["span_id"]
    assert "<lambda>" in lowers[0]["args"]["fun"]
    compiles = [e for e in t.events if e["name"] == "jax.compile"]
    assert [c["args"]["parent_id"] for c in compiles] == [
        step["args"]["span_id"]]
    for e in lowers + compiles:  # inside the step, on the tracer's clock
        assert step["ts"] <= e["ts"] + 1.0
        assert e["ts"] + e["dur"] <= step["ts"] + step["dur"] + 1.0


def test_spans_show_in_a_profiler_capture(tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData

    t = Tracer(trace="on")
    with jax.profiler.trace(str(tmp_path)):
        with t.span("obs.capture.probe"):
            jnp.arange(8).block_until_ready()
    paths = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    assert paths
    names = {ev.name for plane in ProfileData.from_file(paths[0]).planes
             for line in plane.lines for ev in line.events}
    assert "obs.capture.probe" in names


def test_span_records_exception_tag():
    t = Tracer(trace="on")
    with pytest.raises(RuntimeError):
        with t.span("boom"):
            raise RuntimeError("x")
    assert t.events[0]["args"]["exception"] == "RuntimeError"


def test_chrome_export_round_trips(tmp_path):
    t = Tracer(trace="on")
    with t.span("work", k=1):
        t.event("mid")
    path = tmp_path / "trace.json"
    n = t.export_chrome(str(path))
    assert n == 2
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert {e["ph"] for e in evs} == {"X", "i"}
    x = next(e for e in evs if e["ph"] == "X")
    assert x["name"] == "work" and x["dur"] >= 0
    assert x["args"] == {"k": 1, "span_id": 1, "parent_id": 0}


def test_summarize_table_and_require(tmp_path, capsys):
    t = Tracer(trace="on")
    for _ in range(3):
        with t.span("serve.wave"):
            pass
    path = tmp_path / "t.json"
    t.export_chrome(str(path))
    rows = summarize(t.events)
    assert rows == [("serve.wave", 3, pytest.approx(rows[0][2]),
                     pytest.approx(rows[0][2]), pytest.approx(rows[0][4]),
                     pytest.approx(rows[0][5]), {})]
    assert "serve.wave" in format_table(rows)
    assert main([str(path), "--require", "serve.wave"]) == 0
    capsys.readouterr()
    assert main([str(path), "--require", "serve.bisect"]) == 1
    assert "REQUIRE FAIL" in capsys.readouterr().err


def test_summarize_self_time_and_counts():
    def x(name, dur, sid, parent, **counts):
        args = {"span_id": sid, "parent_id": parent}
        if counts:
            args["counts"] = counts
        return {"name": name, "ph": "X", "ts": 0.0, "dur": dur, "args": args}

    events = [x("leaf", 30.0, 3, 2, host_sync=2), x("lower", 5.0, 4, 2),
              x("mid", 50.0, 2, 1, host_sync=2), x("leaf", 10.0, 5, 1),
              x("root", 100.0, 1, 0, host_sync=3),
              {"name": "mark", "ph": "i", "ts": 1.0, "args": {}}]
    rows = {r[0]: r for r in summarize(events)}
    assert rows["root"][1:4] == (1, 100.0, 40.0)
    assert rows["mid"][1:4] == (1, 50.0, 15.0)
    assert rows["leaf"][1:4] == (2, 40.0, 40.0)
    assert rows["leaf"][6] == {"host_sync": 2}
    assert rows["root"][6] == {"host_sync": 3}
    table = format_table(list(rows.values()))
    assert "self_ms" in table and "host_sync=3" in table


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_registry_snapshot_flat_sorted_and_typed():
    r = Registry()
    r.inc("b.count")
    r.inc("b.count", 2)
    r.gauge("a.frac", 0.25)
    r.observe("c.ms", 3.0)
    r.observe("c.ms", 1.0)
    snap = r.snapshot()
    assert list(snap) == sorted(snap)
    assert snap["b.count"] == 3
    assert snap["a.frac"] == 0.25
    assert snap["c.ms.count"] == 2 and snap["c.ms.sum"] == 4.0
    assert snap["c.ms.min"] == 1.0 and snap["c.ms.max"] == 3.0


def test_registry_rejects_kind_aliasing():
    r = Registry()
    r.inc("x")
    with pytest.raises(ValueError, match="already a counter"):
        r.gauge("x", 1.0)


def test_derived_fragment_formats_ints_and_floats():
    frag = derived_fragment({"a.n": 3, "a.frac": 0.5, "b.n": 2.0}, "a.")
    assert frag == "a.frac=0.500;a.n=3"


def test_publish_stats_field_mapping():
    from dataclasses import dataclass

    @dataclass
    class S:
        hit: bool
        rounds: int
        frac: float
        sizes: np.ndarray
        levels: list
        name: str
        missing: None = None

    r = Registry()
    s = S(True, 4, 0.5, np.array([2, 3]), [1, 2, 3], "skipped")
    from repro.obs.metrics import publish_stats

    publish_stats(s, "t", r)
    publish_stats(s, "t", r)  # accumulates
    snap = r.snapshot()
    assert snap == {
        "t.frac": 0.5,       # gauge: last write wins
        "t.hit": 2,
        "t.levels.count": 6,
        "t.rounds": 8,
        "t.sizes.total": 10.0,
    }


# ---------------------------------------------------------------------------
# engine integration: determinism + no new syncs
# ---------------------------------------------------------------------------


def _chaos_engine():
    from repro.data.graphs import graph_request_stream
    from repro.serve import FaultPlan, GraphRequest, GraphServeEngine

    plan = FaultPlan.random(
        7, range(12), p_poison=0.15, p_transient=0.2, max_transient=1,
    )
    eng = GraphServeEngine(max_requests=4, fault_plan=plan, max_retries=1)
    stream = graph_request_stream(12, kind="cc", family="random", seed=3)
    for i, g in enumerate(stream):
        eng.submit(GraphRequest(uid=i, **g))
    eng.run()
    return eng


def test_engine_metrics_snapshot_deterministic_across_runs():
    """Two identical fault-injected serve runs -> identical unified
    snapshots (what lets benchmarks/run.py --check pin them)."""
    s1 = _chaos_engine().metrics.snapshot()
    s2 = _chaos_engine().metrics.snapshot()
    assert s1 == s2
    assert s1  # nonempty
    assert any(k.startswith("serve.health.") for k in s1)
    assert any(k.startswith("serve.graph.wave.") for k in s1)
    assert s1["serve.health.quarantined"] >= 1  # the plan really fired


def test_traced_chaos_run_produces_containment_spans():
    trace.reset()
    trace.configure(trace="on")
    try:
        _chaos_engine()
        names = {e["name"] for e in trace.chrome_trace()["traceEvents"]}
    finally:
        trace.configure(trace="off")
        trace.reset()
    assert {"serve.run", "serve.wave", "serve.wave.pack",
            "serve.wave.engine", "serve.quarantine"} <= names
    assert "serve.bisect.probe" in names or "serve.retry" in names


def test_traced_jitted_cc_stays_correct_and_synced():
    """Tracing on: the instrumented engines produce the same labels,
    and device spans close on already-synced boundaries (no tracer
    leaks, no exceptions under jit)."""
    from repro.core import frontier_shiloach_vishkin, shiloach_vishkin

    src = jnp.asarray(np.array([0, 1, 2, 4], np.int32))
    dst = jnp.asarray(np.array([1, 2, 3, 5], np.int32))
    base_d, _ = shiloach_vishkin(src, dst, 8)
    base_f, _ = frontier_shiloach_vishkin(src, dst, 8)
    trace.reset()
    trace.configure(trace="on")
    try:
        lab_d, _ = shiloach_vishkin(src, dst, 8)
        lab_f, _ = frontier_shiloach_vishkin(src, dst, 8)
        names = {e["name"] for e in trace.chrome_trace()["traceEvents"]}
    finally:
        trace.configure(trace="off")
        trace.reset()
    np.testing.assert_array_equal(np.asarray(lab_d), np.asarray(base_d))
    np.testing.assert_array_equal(np.asarray(lab_f), np.asarray(base_f))
    assert "cc.dense" in names
    assert "cc.frontier" in names and "cc.frontier.level" in names


def test_instrumented_tree_adds_no_host_syncs():
    """RL001 regression: the obs instrumentation must attach only at
    boundaries that already sync -- zero new host-sync findings across
    the instrumented tree."""
    from tools.lint import load_baseline, run_lint, split_baselined
    from tools.lint.passes import PASS_BY_NAME

    findings = run_lint(
        [os.path.join(_ROOT, "src")],
        root=_ROOT,
        passes=[PASS_BY_NAME["host-sync"]],
    )
    baseline = load_baseline(
        os.path.join(_ROOT, "tools", "lint", "baseline.json")
    )
    new, _old, stale = split_baselined(findings, baseline)
    assert [f.format() for f in new] == []


# ---------------------------------------------------------------------------
# program spans and the host-sync counter
# ---------------------------------------------------------------------------

# A 1024-node chain through the pre-pass and a 16-edge floor climbs this
# ladder: (bucket, rounds) per level, the last one converging.
CHAIN_LADDER = [(1024, 1), (256, 4), (32, 1)]


def _chain_call(**kw):
    from repro.core import connected_components

    src = np.arange(1023, dtype=np.int32)
    return connected_components(src, src + 1, 1024, engine="frontier",
                                min_bucket=16, sample_rounds=2, **kw)


def _traced(fn):
    trace.reset()
    trace.configure(trace="on")
    try:
        out = fn()
        events = trace.chrome_trace()["traceEvents"]
    finally:
        trace.configure(trace="off")
        trace.reset()
    return out, events


def test_frontier_call_syncs_match_the_hand_count():
    """Pre-pass live count (1); rounds + changed + s on each level that
    does not converge (3 + 3) and rounds + changed on the last (2); a
    live count before each of the two shrinks (2); the final s (1)."""
    assert _chain_call(with_stats=True)[2].levels == CHAIN_LADDER
    _, events = _traced(_chain_call)
    by_id = {e["args"]["span_id"]: e for e in events}
    (root,) = [e for e in events if e["name"] == "cc.call"]
    assert root["args"]["parent_id"] == 0
    assert root["args"]["engine"] == "frontier"
    assert root["args"]["counts"]["host_sync"] == 1 + 3 + 3 + 2 + 2 + 1 == 12
    for name, parent in [("cc.dedup", "cc.call"), ("cc.upload", "cc.call"),
                         ("cc.frontier.sample", "cc.call"),
                         ("cc.frontier.sample.permute", "cc.frontier.sample"),
                         ("cc.frontier", "cc.call"),
                         ("cc.frontier.level", "cc.frontier"),
                         ("cc.compress", "cc.frontier")]:
        spans = [e for e in events if e["name"] == name]
        assert spans, name
        assert {by_id[e["args"]["parent_id"]]["name"] for e in spans} == {parent}


def test_dedup_span_tags_its_edge_counts(monkeypatch):
    """Tracing on: one ``cc.dedup`` span per host-input call, tagged with
    the edges it saw and kept. Tracing off: the tags are never built."""
    from repro.core import connected_components, dedup_edges
    from repro.obs.trace import _NullSpan

    src = np.array([0, 1, 1, 2, 3, 3, 3, 5], np.int32)
    dst = np.array([1, 0, 1, 3, 2, 2, 3, 6], np.int32)  # dups + self-loops
    _, events = _traced(lambda: connected_components(src, dst, 8))
    (sp,) = [e for e in events if e["name"] == "cc.dedup"]
    assert sp["args"]["m_in"] == 8
    assert sp["args"]["m_out"] == dedup_edges(src, dst)[0].size == 3

    tagged = []
    monkeypatch.setattr(_NullSpan, "tag",
                        lambda self, **attrs: tagged.append(attrs) or self)
    connected_components(src, dst, 8)
    assert not any("m_in" in attrs or "m_out" in attrs for attrs in tagged)


def test_traced_analytics_waves_time_each_stage():
    from repro.data.graphs import graph_request_stream
    from repro.serve import GraphRequest, GraphServeEngine

    def serve():
        eng = GraphServeEngine(max_requests=3)
        for i, g in enumerate(graph_request_stream(
                7, kind="analytics", family="random", seed=5)):
            eng.submit(GraphRequest(uid=i, **g))
        eng.run()
        return eng

    eng, events = _traced(serve)
    by_id = {e["args"]["span_id"]: e for e in events if e["ph"] == "X"}
    parent = {e["name"]: by_id[e["args"]["parent_id"]]["name"]
              for e in by_id.values() if e["args"]["parent_id"]}
    assert parent["trees.forest"] == "serve.wave.engine"
    assert parent["cc.call"] == "trees.forest"
    for name in ("trees.tour", "trees.rank", "trees.compute",
                 "serve.wave.readback"):
        assert parent[name] == "serve.wave.engine"
    waves = [e for e in events if e["name"] == "serve.wave"]
    assert len(waves) == eng.waves == 3
    # Per wave: the dense CC's convergence flag (1), the forest's hook
    # slots, labels and rounds (4), the five tree arrays (5).
    for w in waves:
        assert w["args"]["counts"] == {"host_sync": 10}
    (run,) = [e for e in events if e["name"] == "serve.run"]
    assert run["args"]["counts"] == {"host_sync": 30}


def test_every_host_sync_pragma_is_counted():
    """Each ``disable=host-sync`` line of the CC and SSSP engines has a
    ``trace.count("host_sync")`` on one of the three lines above it,
    one count per pragma, so a new sync cannot land uncounted."""
    for rel in ("src/repro/core/frontier.py", "src/repro/core/components.py",
                "src/repro/core/sssp.py"):
        with open(os.path.join(_ROOT, rel)) as f:
            lines = f.read().splitlines()
        counts = [i for i, ln in enumerate(lines)
                  if ln.strip() == 'trace.count("host_sync")']
        pragmas = [i for i, ln in enumerate(lines)
                   if "repro-lint: disable=host-sync" in ln]
        assert pragmas, rel
        assert len(counts) == len(pragmas), rel
        for i in pragmas:
            assert any(i - 3 <= c < i for c in counts), f"{rel}:{i + 1}"


def _sssp_call(**kw):
    from repro.core import shortest_paths

    # 2,000 arcs: the widest levels fill the whole list, so some levels
    # relax it uncompacted and the others compact.
    r = np.random.default_rng(7)
    src = r.integers(0, 300, 1000).astype(np.int32)
    dst = r.integers(0, 300, 1000).astype(np.int32)
    w = r.random(1000).astype(np.float32)
    return shortest_paths(src, dst, w, 300, sources=5, min_bucket=64, **kw)


def test_sssp_call_spans_and_counts():
    """One root ``sssp.call`` per call; a live-count read per level and
    the final one that finds the frontier empty; one ``sssp.parents``
    post-pass; the level counters sum to ``SsspStats``, and each level
    says whether its bucket was the whole list."""
    *_, stats = _sssp_call(with_stats=True)
    _, events = _traced(lambda: _sssp_call(with_stats=True))
    by_id = {e["args"]["span_id"]: e for e in events if e["ph"] == "X"}
    (root,) = [e for e in events if e["name"] == "sssp.call"]
    assert root["args"]["parent_id"] == 0
    assert (root["args"]["engine"], root["args"]["n"], root["args"]["m2"],
            root["args"]["sources"]) == ("frontier", 300, 2000, 1)
    counts = root["args"]["counts"]
    assert counts["host_sync"] == len(stats.levels) + 1
    assert counts["sssp.relax_slots"] == stats.relax_visits
    assert counts["sssp.mask_edges"] == stats.mask_visits
    assert counts["sssp.dense_levels"] == stats.dense_levels
    levels = [e for e in events if e["name"] == "sssp.level"]
    assert len(levels) == len(stats.levels) > 1
    dense = [e["args"]["dense"] for e in levels]
    assert dense == [e["args"]["bucket"] == 2000 for e in levels]
    assert 0 < sum(dense) == stats.dense_levels < len(dense)
    for name, parent in [("sssp.prep", "sssp.call"),
                         ("sssp.frontier", "sssp.call"),
                         ("sssp.level", "sssp.frontier"),
                         ("sssp.parents", "sssp.call")]:
        spans = [e for e in events if e["name"] == name]
        assert spans, name
        assert {by_id[e["args"]["parent_id"]]["name"] for e in spans} == {parent}
    assert len([e for e in events if e["name"] == "sssp.parents"]) == 1


def test_sssp_dense_call_counts_its_reads():
    """The dense engine reads its convergence flag, and its round count
    where stats are asked for."""
    from repro.core import shortest_paths

    src = np.arange(9, dtype=np.int32)
    for kw, syncs in [({}, 1), ({"with_stats": True}, 2)]:
        _, events = _traced(lambda: shortest_paths(src, src + 1, None, 10,
                                                   engine="dense", **kw))
        (root,) = [e for e in events if e["name"] == "sssp.call"]
        assert root["args"]["engine"] == "dense"
        assert root["args"]["counts"] == {"host_sync": syncs}


def test_sssp_untraced_call_records_nothing(monkeypatch):
    """Tracing off: no event, and the root span's tags are never built."""
    from repro.obs.trace import _NullSpan

    trace.configure(trace="off")
    trace.reset()
    tagged = []
    monkeypatch.setattr(_NullSpan, "tag",
                        lambda self, **attrs: tagged.append(attrs) or self)
    _sssp_call()
    assert trace.chrome_trace()["traceEvents"] == []
    assert not any({"engine", "m2", "sources"} & set(attrs) for attrs in tagged)
