"""``trace_reduce`` on a small trace recorded on the CPU, and its
interval arithmetic on hand-made intervals."""
import time

import jax
import jax.numpy as jnp
import pytest

import trace_reduce


def test_union_and_labels():
    assert trace_reduce._union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [[0, 3], [5, 9]]
    host = [(0, 100, "bench.call"), (10, 20, "PjitFunction(f)"),
            (40, 60, "cc.frontier.level")]
    gaps = [(12, 18), (45, 50), (70, 80), (150, 160)]
    assert trace_reduce.label_gaps(gaps, host) == [
        "PjitFunction(f)", "cc.frontier.level", "bench.call", "untracked"]


def test_ops_named_by_program():
    """A TPU op event carries its whole HLO instruction; the reduction
    keeps the instruction's name and the program around it."""
    modules = [(0, 50, "jit_a(1)"), (60, 90, "jit_b(2)")]
    ops = [(5, 9, "%fusion.3 = s32[8]{0} fusion(s32[8]{0} %p), kind=kLoop"),
           (62, 70, "%while.1 = (s32[]) while(%t)"), (95, 99, "%copy.2")]
    assert [n for _, _, n in trace_reduce.in_modules(ops, modules)] == [
        "jit_a(1):%fusion.3", "jit_b(2):%while.1", "untracked:%copy.2"]


def test_nested_ops_count_once():
    """A loop op holds its body's ops on the same line: each is counted
    by its own time, so the sum is the busy time."""
    ops = [(0, 100, "loop"), (10, 30, "body"), (40, 50, "body"), (20, 25, "inner"),
           (120, 130, "copy")]
    t = trace_reduce.self_times(ops)
    assert t == {"loop": 70, "body": 25, "inner": 5, "copy": 10}
    assert sum(t.values()) == 110


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A trace of three jitted calls inside ``bench.call`` annotations,
    with sleeps between them, and a ``repro.obs`` span anchored on the
    profiler's clock as the harness does it."""
    from jax.profiler import TraceAnnotation
    from repro.obs import trace as spans

    f = jax.jit(lambda x: jnp.sort(x * 3 + 1).sum())
    x = jnp.arange(1 << 16, dtype=jnp.float32)
    f(x).block_until_ready()
    log_dir = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    spans.configure(trace="on")
    spans.reset()
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        with TraceAnnotation("bench.anchor"):
            spans.event("bench.anchor")
        with TraceAnnotation("bench.window"):
            for _ in range(3):
                with TraceAnnotation("bench.call"):
                    f(x).block_until_ready()
                    with spans.span("host.prep"):
                        time.sleep(0.02)
            time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
        spans.configure(trace="off")
    events = spans.chrome_trace()["traceEvents"]
    spans.reset()
    return trace_reduce.find_xplane(str(log_dir)), events


def test_reduce_cpu_trace(recorded):
    path, events = recorded
    r = trace_reduce.reduce(path, "cpu", events)
    assert r["devices"] == 1
    assert 0.07 < r["window_s"] < 5
    assert 0 < r["busy_s"] < r["window_s"]
    names = [n for n, _ in r["device_ops"]]
    assert names and all(n.startswith("jit_") for n in names)
    gaps = dict(r["idle_gaps"])
    # the sleeps inside the calls are the program's span, placed on the
    # profiler's clock through the anchor; the last one is the window's
    assert gaps["host.prep"] >= 0.05
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    assert len(r["device_ops"]) <= trace_reduce.TOP >= len(r["idle_gaps"])


def test_reduce_needs_window(recorded, tmp_path):
    path, _ = recorded
    with pytest.raises(FileNotFoundError):
        trace_reduce.find_xplane(str(tmp_path))
    ops, host, notes = trace_reduce.read_planes(path, "cpu")
    assert any(n == "bench.window" for _, _, n in notes)
    assert any(n.startswith("PjitFunction(") for _, _, n in host)


def test_reduce_refuses_trace_without_device_ops(recorded):
    """A platform whose device lines the trace does not hold reads no
    op: that is an error, never a device idle for the whole window."""
    path, events = recorded
    with pytest.raises(ValueError, match="no device op"):
        trace_reduce.reduce(path, "tpu", events)
