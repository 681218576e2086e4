"""The harness end to end on the CPU at small sizes: a cell found by name
from data files alone (``g500-sssp``), the refusals without a TPU or
with another chip count, the controls, and the faults each cell can
have, planted under the timed path, each turning ``correct`` false."""
import io
import json
import shutil
import subprocess
import sys

import numpy as np
import pytest

import run as harness
from conftest import BENCH

SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.fixture
def small(tmp_path):
    """A search directory that shadows the cells' sizes with small ones,
    and adds ``g500-sssp`` as data only: a configuration, a workload
    file, and ``BENCHMARK.json`` entries."""
    (tmp_path / "configs").mkdir()
    (tmp_path / "workloads").mkdir()
    g = config("graph500-s18")
    (tmp_path / "configs" / "graph500-s18.json").write_text(json.dumps(dict(g, scale=8)))
    (tmp_path / "configs" / "graph500-s8.json").write_text(
        json.dumps(dict(g, name="graph500-s8", scale=8)))
    mol = json.loads((BENCH / "workloads" / "mol-analytics.json").read_text())
    (tmp_path / "workloads" / "mol-analytics.json").write_text(json.dumps(dict(mol, rate=40)))
    (tmp_path / "workloads" / "g500-sssp.json").write_text(json.dumps({
        "name": "g500-sssp", "config": "graph500-s8", "driver": "library_calls",
        "entry": "repro.core:shortest_paths",
        "args": ["src", "dst", "weights", "num_nodes"],
        "cycle": {"sources": "roots"}, "gen": {"weights": True, "roots": 64},
        "warm_calls": 64, "check": "sssp",
        "limits": {"wrong_dist": 0, "wrong_parent": 0}}))
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append(dict(spec["configs"][0], name="graph500-s8",
                                file="bench/configs/graph500-s8.json"))
    spec["workloads"].append({"name": "g500-sssp", "config": "graph500-s8",
                              "traffic": "library_calls_weighted", "chips": 1,
                              "why": "shortest_paths from 64 roots"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in ("call_s", "levels_per_call", "off_loop_ms.call"):
            m["workloads"].append("g500-sssp")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path


def run_cell(root, name, seed=2**31 + 5, seconds=0.5, trace=0, **kw):
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run(
        ["--workload", name, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)],
        spec_path=kw.pop("spec_path", root / "BENCHMARK.json"),
        search=(root, BENCH), require_tpu=kw.pop("require_tpu", False),
        out=out, err=err)
    lines = out.getvalue().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()


def test_new_cell_from_data_files(small):
    rc, res, err = run_cell(small, "g500-sssp")
    assert rc == 0 and res["correct"], err
    assert set(res["metrics"]) == {"call_s", "setup_s"}
    assert list(res)[-1] == "checks"
    assert res["checks"]["wrong_dist"] == {"value": 0, "limit": 0}
    assert err.strip().splitlines()[-1] == "check wrong_parent 0 limit 0"
    rc, res, _ = run_cell(small, "g500-sssp", trace=1)
    assert rc == 0 and res["correct"]
    assert {"levels_per_call", "off_loop_ms.call"} <= set(res["metrics"])
    assert res["device"]["busy_s"] > 0


@pytest.mark.parametrize("name,metrics", [
    ("g500-wcc", {"call_s", "setup_s"}),
    ("mol-analytics", {"served_rps", "setup_s"}),
])
def test_cells_run_correct(small, name, metrics):
    rc, res, err = run_cell(small, name)
    assert rc == 0 and res["correct"] and res["failed"] == 0, err
    assert set(res["metrics"]) == metrics
    assert res["attempted"] > 0
    assert res["device"]["count"] == 1


@pytest.mark.parametrize("name", ["g500-wcc", "mol-analytics"])
def test_cells_traced(small, name):
    """A traced run reports every per-layer metric its cell lists, a
    device busy time inside the window, and a breakdown."""
    rc, res, err = run_cell(small, name, trace=1)
    assert rc == 0 and res["correct"], err
    spec = json.loads((small / "BENCHMARK.json").read_text())
    want = {m["name"] for m in spec["per_layer"] if name in m["workloads"]}
    # No peak table for the CPU, so no roofline share there.
    assert set(res["metrics"]) == want - {"wcc_roofline"}
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert 0 <= min(m["value"] for m in res["metrics"].values())
    assert res["breakdown"]["device_ops"] and len(res["breakdown"]["idle_gaps"]) <= 10


def test_no_tpu_no_result(small, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(small / "cache"))
    rc, res, err = run_cell(small, "g500-wcc", require_tpu=True)
    assert rc == 2 and res is None and "no TPU" in err


def test_chip_count_no_result(small):
    spec = json.loads((small / "BENCHMARK.json").read_text())
    spec["workloads"][0]["chips"] = 4
    (small / "four.json").write_text(json.dumps(spec))
    rc, res, err = run_cell(small, "g500-wcc", spec_path=small / "four.json")
    assert rc == 2 and res is None and "4 chip" in err


def test_benchmark_alone_fails(tmp_path):
    """Without the program beside it, a run fails and prints no result."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    code = ("import sys; sys.path.insert(0, 'bench'); import run; "
            "sys.exit(run.run(['--workload', 'g500-wcc', '--seed', '1', "
            "'--seconds', '1'], require_tpu=False))")
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300,
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("name", ["g500-wcc", "mol-analytics", "g500-sssp"])
def test_control_fails(small, name):
    spec = json.loads((small / "BENCHMARK.json").read_text())
    cell = harness.make_cell(spec, name, 2**31 + 9, 1.0, (small, BENCH))
    numbers = cell.load("drivers", cell.workload["driver"]).Driver(cell).control()
    limits = cell.workload["limits"]
    assert any(numbers[k] > limits[k] for k in limits), numbers


def _wcc_fault(kind):
    import repro.core as core

    orig = core.connected_components

    def broken(src, dst, n, **kw):
        if kind == "half_batch":  # half the edges left out
            return orig(src[: len(src) // 2], dst[: len(dst) // 2], n, **kw)
        labels, rounds = orig(src, dst, n, **kw)
        labels = np.array(labels)
        if kind == "unchanged":  # the state returned as it started
            labels = np.arange(n, dtype=labels.dtype)
        else:  # one answer altered where it is produced
            big = np.bincount(labels).argmax()
            v = np.flatnonzero(labels == big).max()
            labels[v] = v
        return labels, rounds

    return broken


@pytest.mark.parametrize("kind", ["altered", "unchanged", "half_batch"])
def test_wcc_faults_fail(small, monkeypatch, kind):
    import repro.core as core

    monkeypatch.setattr(core, "connected_components", _wcc_fault(kind))
    rc, res, _ = run_cell(small, "g500-wcc")
    assert rc == 0 and res["correct"] is False
    assert res["checks"]["wrong_labels"]["value"] > 0


@pytest.mark.parametrize("kind", ["altered", "unchanged", "half_batch"])
def test_serve_faults_fail(small, monkeypatch, kind):
    from repro.serve.graph import GraphServeEngine

    unpack, next_wave = GraphServeEngine._unpack, GraphServeEngine._next_wave

    def bad_unpack(self, wave, node_off, labels, *rest):
        if kind == "unchanged":
            labels = np.arange(len(labels), dtype=labels.dtype)
        unpack(self, wave, node_off, labels, *rest)
        if kind == "altered":
            wave[0].result.depth = wave[0].result.depth + 1

    def half_wave(self):
        wave = next_wave(self)
        return wave[: max(1, len(wave) // 2)]

    monkeypatch.setattr(GraphServeEngine, "_unpack", bad_unpack)
    if kind == "half_batch":
        monkeypatch.setattr(GraphServeEngine, "_next_wave", half_wave)
        # a half wave never fills; give it requests to drop
        mol = json.loads((small / "workloads" / "mol-analytics.json").read_text())
        (small / "workloads" / "mol-analytics.json").write_text(
            json.dumps(dict(mol, rate=400)))
    rc, res, _ = run_cell(small, "mol-analytics")
    assert rc == 0 and res["correct"] is False
    checks = res["checks"]
    assert checks["wrong_answers"]["value"] + checks["missing_answers"]["value"] > 0


def test_compile_counter_tells_fetches_from_compiles(tmp_path):
    """A program fetched from the persistent cache is no compile."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    old = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compilation_cache.reset_cache()
    try:
        x = jnp.arange(1000)
        counter = harness.CompileCounter()
        counter.active = True
        for _ in range(2):  # a new function object each time, one program
            jax.jit(lambda v: (v * 3 + 1).sum())(x).block_until_ready()
        counter.active = False
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
        compilation_cache.reset_cache()
    assert (counter.lowered, counter.compiled, counter.fetched) == (2, 1, 1)
