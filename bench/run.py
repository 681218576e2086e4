"""One run of one benchmark cell, on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``
names its configuration (``bench/configs/<config>.json``) and its
workload file (``bench/workloads/<cell>.json``); the workload names its
driver (``bench/drivers/<driver>.py``) and reference
(``bench/refs/<check>.py``), the configuration its generator
(``bench/gen/<generator>.py``); each per-layer metric is read by
``bench/metrics/<metric>.py``. A new cell or metric is new files and
new ``BENCHMARK.json`` entries.

A run: checks that JAX sees a TPU and as many chips as the cell asks
for (else exits 2 and prints no result); makes its inputs from the
seed; warms up every shape the window will use (set-up, ``setup_s``);
measures for ``--seconds``, with JAX's compile events counted; reads
the device's peak memory; copies the outputs to the host and drops the
program's device state; compares the outputs the window produced with
the plain reference. With ``--trace 1`` the window also runs under
``jax.profiler`` (its trace under ``<checkout>/.bench_traces/<cell>``)
and the program's ``repro.obs`` spans, and the per-layer metrics
replace the end-to-end ones.

Standard output ends with one JSON line (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``: each number compared with its limit); the line
before it holds the run's details, compiles inside the window among
them. Standard error ends with one ``check`` line per number compared.
JAX's persistent compilation cache lives in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_traces"
# A lowering ends in a compile or in a fetch from the persistent cache.
# JAX times both under the backend event, and marks a fetch with a
# cache-hit event besides, so compiles proper are the difference.
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/compile_time_saved_sec"


class NoChip(RuntimeError):
    """JAX sees no TPU, or another number of chips than the cell asks for."""


def load_json(kind: str, name: str, search) -> dict:
    for d in search:
        p = Path(d) / kind / f"{name}.json"
        if p.is_file():
            return json.loads(p.read_text())
    raise FileNotFoundError(f"no {kind}/{name}.json under {list(map(str, search))}")


def load_module(kind: str, name: str, search):
    """``<dir>/<kind>/<name>.py`` from the first search directory that
    has it; its directory goes on ``sys.path`` so that modules of one
    kind can import each other."""
    for d in search:
        p = Path(d) / kind / f"{name}.py"
        if p.is_file():
            if str(p.parent) not in sys.path:
                sys.path.insert(0, str(p.parent))
            modname = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
            spec = importlib.util.spec_from_file_location(modname, p)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise FileNotFoundError(f"no {kind}/{name}.py under {list(map(str, search))}")


def metric_reader(name: str, search):
    """``metrics/<name>.py``; a quantity split by the end-to-end metric
    it moves (``idle_pct.call``, ``idle_pct.serve``) may share one
    reader, ``metrics/<quantity>.py``."""
    try:
        return load_module("metrics", name, search)
    except FileNotFoundError:
        return load_module("metrics", name.split(".")[0], search)


def make_cell(spec: dict, name: str, seed: int, seconds: float, search):
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    entry = cells[name]
    config = load_json("configs", entry["config"], search)
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return SimpleNamespace(
        name=name, entry=entry, config=config, seed=seed, seconds=seconds,
        workload=load_json("workloads", name, search), search=search,
        end_to_end=e2e, per_layer=layer,
        load=lambda kind, mod: load_module(kind, mod, search),
    )


def devices(chips: int, require_tpu: bool = True) -> list:
    """The chips of a run, with the program's compile cache set up: JAX
    must see a TPU (where ``require_tpu``) and exactly ``chips`` of them,
    else ``NoChip``. On the chip the program's cache
    (``use_compile_cache``) is given the checkout's ``.jax_cache``, a
    path that does not move, and keeps every program, however fast it
    compiled, so that only a checkout's first run compiles."""
    if require_tpu:
        # JAX reads this when it is imported.
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found platform {devs[0].platform!r} "
                     f"({devs[0].device_kind}); the benchmark never falls "
                     "back to another platform")
    if len(devs) != chips:
        raise NoChip(f"the cell asks for {chips} chip(s); JAX sees {len(devs)}")
    if require_tpu:
        from repro.launch.compile_cache import use_compile_cache

        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return devs


class CompileCounter:
    """Counts JAX's lowerings, compiles and persistent-cache fetches
    while ``active``."""

    def __init__(self):
        import jax

        self.active = False
        self.lowered = self.backend = self.fetched = 0
        self.names: dict[str, int] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    @property
    def compiled(self) -> int:
        return self.backend - self.fetched

    def _on_event(self, event, duration, fun_name="?", **_):
        if not self.active:
            return
        self.backend += event == BACKEND_EVENT
        self.fetched += event == CACHE_HIT_EVENT
        if event == LOWER_EVENT:
            self.lowered += 1
            self.names[fun_name] = self.names.get(fun_name, 0) + 1


def peak_bytes(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def traced_window(cell, driver, platform: str):
    """The window under ``jax.profiler`` and ``repro.obs`` spans; returns
    (window, spans, trace reduction)."""
    import jax
    from jax.profiler import TraceAnnotation
    from repro.obs import trace as spans

    import trace_reduce

    log_dir = TRACE_DIR / cell.name
    shutil.rmtree(log_dir, ignore_errors=True)
    log_dir.mkdir(parents=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    spans.configure(trace="on")
    spans.reset()
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        with TraceAnnotation("bench.anchor"):
            spans.event("bench.anchor")
        with TraceAnnotation("bench.window"):
            window = driver.window(cell.seconds, TraceAnnotation)
    finally:
        jax.profiler.stop_trace()
        spans.configure(trace="off")
    events = spans.chrome_trace()["traceEvents"]
    spans.reset()
    reduced = trace_reduce.reduce(trace_reduce.find_xplane(str(log_dir)),
                                  platform, events)
    return window, events, reduced


def run(argv=None, *, spec_path=ROOT / "BENCHMARK.json", search=(BENCH,),
        require_tpu=True, start=PROCESS_START, out=None, err=None) -> int:
    out, err = out or sys.stdout, err or sys.stderr
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")

    spec = json.loads(Path(spec_path).read_text())
    cell = make_cell(spec, args.workload, args.seed, args.seconds, search)
    try:
        devs = devices(int(cell.entry["chips"]), require_tpu)
    except NoChip as e:
        print(f"bench: {e}", file=err)
        return 2
    counter = CompileCounter()
    driver = cell.load("drivers", cell.workload["driver"]).Driver(cell)
    driver.warm_up()
    setup_s = time.perf_counter() - start

    counter.active = True
    if args.trace:
        window, events, reduced = traced_window(cell, driver, devs[0].platform)
    else:
        window, events, reduced = driver.window(
            cell.seconds, lambda name: contextlib.nullcontext()), [], None
    counter.active = False
    memory = peak_bytes(devs)
    driver.release()
    gc.collect()
    numbers, failed = driver.check()
    limits = cell.workload["limits"]
    correct = all(numbers[k] <= limits[k] for k in limits)

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory}
    metrics = {}
    if args.trace:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        ctx = SimpleNamespace(window=window, spans=events, trace=reduced,
                              shapes=driver.shapes(),
                              peaks=peaks(devs[0].device_kind) if require_tpu else None)
        for m in cell.per_layer:
            value = metric_reader(m["name"], search).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        got = dict(window["metrics"], setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": got[m["name"]], "unit": m["unit"]}

    details = {k: v for k, v in window.items()
               if k in ("attempted", "completed", "waves", "offered_rps",
                        "generator_late_ms", "last_completion_s")}
    if "calls" in window:
        details["call_seconds"] = [end - start for start, end in window["calls"]]
    print(json.dumps({"cell": cell.name, "seed": args.seed, "trace": args.trace,
                      "setup_s": setup_s, "window_compiles": counter.compiled,
                      "window_cache_fetches": counter.fetched,
                      "window_lowerings": counter.lowered,
                      "window_lowered": counter.names,
                      "shapes": driver.shapes(), **details}), file=out)
    result = {"correct": correct, "attempted": window["attempted"],
              "failed": failed, "metrics": metrics, "device": device}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    for k in limits:
        print(f"check {k} {numbers[k]} limit {limits[k]}", file=err)
    print(json.dumps(result), file=out, flush=True)
    return 0


def peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


if __name__ == "__main__":
    sys.exit(run())
