"""The ``g500-sssp`` cell at scale 8 on the CPU: one weighted graph and
one root order for every seed, the Bellman-Ford reference against
Dijkstra, the control, a run from the cell's own data files, and a
distance altered under the timed path turning ``correct`` false."""
import io
import json
from types import SimpleNamespace

import numpy as np
import pytest

import kronecker_sssp
import run as harness
import sssp
import sssp_bf
from conftest import BENCH

CONFIG = json.loads((BENCH / "configs" / "graph500-s18-sssp.json").read_text())
NEW = {"sssp_syncs_per_call", "parents_ms.call", "relax_slots_per_call",
       "sssp_roofline"}


def graph(seed, scale=8):
    return kronecker_sssp.generate(dict(CONFIG, scale=scale), seed)


@pytest.fixture
def small(tmp_path):
    """A search directory that shadows the cell's configuration with
    scale 8; everything else comes from the cell's own files."""
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / "graph500-s18-sssp.json").write_text(
        json.dumps(dict(CONFIG, scale=8)))
    return tmp_path


def run_cell(root, seed=2**33 + 17, seconds=0.5, trace=0):
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run(
        ["--workload", "g500-sssp", "--seed", str(seed), "--seconds",
         str(seconds), "--trace", str(trace)],
        search=(root, BENCH), require_tpu=False, out=out, err=err)
    return rc, json.loads(out.getvalue().splitlines()[-1]), err.getvalue()


def test_every_seed_times_one_weighted_graph_and_root_order():
    a, b = graph(3), graph(2**40 + 5)
    assert np.array_equal(a["roots"], b["roots"]) and len(a["roots"]) == 64
    assert not np.array_equal(a["src"], b["src"])

    def edges(g):
        return sorted(zip(g["src"].tolist(), g["dst"].tolist(),
                          g["weights"].tolist()))

    assert edges(a) == edges(b)
    again = graph(3)
    assert all(np.array_equal(a[k], again[k]) for k in ("src", "dst", "weights"))


@pytest.mark.parametrize("pick", [0, 1, 7, 63])
def test_bellman_ford_matches_dijkstra(pick):
    g = graph(9)
    r = int(g["roots"][pick])
    ref = sssp_bf.reference(g, [{"sources": r}])
    want_d, want_p = sssp.dijkstra(g["src"], g["dst"], g["weights"],
                                   g["num_nodes"], r)
    d, p = ref[r]
    assert np.array_equal(d.view(np.uint32), want_d.view(np.uint32))
    assert np.array_equal(p, want_p)


def test_checked_roots_are_the_first_distinct_in_call_order():
    calls = [{"sources": r} for r in [5, 5, 9] + list(range(100))]
    roots = sssp_bf.checked_roots(calls)
    assert roots[:3] == [5, 9, 0] and len(roots) == sssp_bf.CHECKED_ROOTS


def test_control_fails(small):
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell = harness.make_cell(spec, "g500-sssp", 2**31 + 9, 1.0, (small, BENCH))
    numbers = cell.load("drivers", cell.workload["driver"]).Driver(cell).control()
    assert numbers["wrong_dist"] > 0, numbers


def test_cell_runs_correct_from_its_data_files(small):
    rc, res, err = run_cell(small)
    assert rc == 0 and res["correct"] and res["failed"] == 0, err
    assert set(res["metrics"]) == {"call_s", "setup_s"}
    assert res["attempted"] > sssp_bf.CHECKED_ROOTS  # repeats compared too
    assert res["checks"] == {"wrong_dist": {"value": 0, "limit": 0},
                             "wrong_parent": {"value": 0, "limit": 0}}


def test_cell_traced_reports_every_metric(small):
    rc, res, err = run_cell(small, trace=1)
    assert rc == 0 and res["correct"], err
    want = {"off_loop_ms.call", "levels_per_call", "idle_pct.call",
            "lowerings_per_call"} | NEW
    # No peak table for the CPU, so no roofline share there.
    assert set(res["metrics"]) == want - {"sssp_roofline"}
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["sssp_syncs_per_call"] == m["levels_per_call"] + 1
    assert m["relax_slots_per_call"] >= 1024 * m["levels_per_call"]
    assert m["parents_ms.call"] > 0
    assert m["off_loop_ms.call"] > 0


def test_roofline_reads_the_shapes_floor():
    ctx = SimpleNamespace(trace={"busy_s": 2.0}, window={"calls": [(0, 1)] * 4},
                          shapes={"num_nodes": 100, "num_edges": 1000},
                          peaks={"hbm_bytes_per_s": 1e6})
    read = harness.metric_reader("sssp_roofline", (BENCH,)).read
    assert read(ctx) == pytest.approx(100.0 * (12 * 1000 + 8 * 100) / 1e6 * 4 / 2.0)
    ctx.peaks = None
    assert read(ctx) is None


@pytest.mark.parametrize("name", sorted(NEW - {"sssp_roofline"}))
def test_span_readers_read_nothing_without_sssp_call(name):
    """A program without the ``sssp.call`` span, as before it existed,
    gives nothing, so the metric is left out of its line."""
    level = {"name": "sssp.level", "ph": "X", "ts": 0.0, "dur": 5.0,
             "args": {"span_id": 1, "parent_id": 0}}
    ctx = SimpleNamespace(window={"calls": [(0, 1)]}, spans=[level])
    assert harness.metric_reader(name, (BENCH,)).read(ctx) is None


@pytest.mark.parametrize("which", ["every", "repeat"])
def test_altered_distance_fails(small, monkeypatch, which):
    """One distance altered where the call returns it: on every call
    (caught against the reference), or only on a root's later calls
    (caught against its first output)."""
    import repro.core as core

    orig, seen = core.shortest_paths, set()

    def broken(*args, sources, **kw):
        dist, parent, rounds = orig(*args, sources=sources, **kw)
        if which == "every" or sources in seen:
            dist = np.array(dist)
            dist[np.flatnonzero(np.isfinite(dist))[-1]] += np.float32(2.0 ** -24)
        seen.add(sources)
        return dist, parent, rounds

    monkeypatch.setattr(core, "shortest_paths", broken)
    rc, res, _ = run_cell(small)
    assert rc == 0 and res["correct"] is False
    assert res["checks"]["wrong_dist"]["value"] == 1
