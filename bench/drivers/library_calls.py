"""Back-to-back calls of one library entry point on one generated graph.

The workload file names:

* ``entry`` -- ``"module:function"``, looked up when the run starts;
* ``args`` -- the call's positional arguments, by the names of the
  generator's outputs (``src``, ``dst``, ``num_nodes``, ``weights``);
* ``cycle`` -- keyword arguments that take the next element of a
  generator output on each call (``{"sources": "roots"}``);
* ``check`` -- the reference module under ``refs/``;
* ``gen`` -- traffic parameters passed to the configuration's generator.

Every call gets host NumPy arrays, as callers of ``repro.core`` pass
them, and ends in ``block_until_ready`` on its outputs. Set-up makes
one untimed call per distinct keyword set of the first ``warm_calls``
calls (default 1), on the run's own graph. The window starts calls
while less than ``seconds`` have passed and waits for the last one;
``call_s`` is the time from the window's start to the end of the last
call, over the number of calls.
"""
from __future__ import annotations

import importlib
import time

import jax
import numpy as np


class Driver:
    def __init__(self, cell):
        self.cell = cell
        wl = cell.workload
        params = dict(cell.config, **wl.get("gen", {}))
        self.inputs = cell.load("gen", cell.config["generator"]).generate(
            params, cell.seed)
        mod, fn = wl["entry"].split(":")
        self.entry = getattr(importlib.import_module(mod), fn)
        self.args = [self.inputs[a] for a in wl["args"]]
        self.cycle = dict(wl.get("cycle", {}))
        self.ref = cell.load("refs", wl["check"])
        self.calls: list[dict] = []  # keyword arguments of each timed call
        self.outputs: list = []

    def _kwargs(self, i: int) -> dict:
        return {k: int(self.inputs[src][i % len(self.inputs[src])])
                for k, src in self.cycle.items()}

    def shapes(self) -> dict:
        return {"num_nodes": int(self.inputs["num_nodes"]),
                "num_edges": int(len(self.inputs["src"]))}

    def warm_up(self) -> None:
        seen = []
        for i in range(int(self.cell.workload.get("warm_calls", 1))):
            kw = self._kwargs(i)
            if kw not in seen:
                seen.append(kw)
                jax.block_until_ready(self.entry(*self.args, **kw))

    def window(self, seconds: float, annotate) -> dict:
        times = []
        t0 = time.perf_counter()
        i = 0
        while time.perf_counter() - t0 < seconds:
            kw = self._kwargs(i)
            start = time.perf_counter()
            with annotate("bench.call"):
                out = jax.block_until_ready(self.entry(*self.args, **kw))
            times.append((start - t0, time.perf_counter() - t0))
            self.calls.append(kw)
            self.outputs.append(out)
            i += 1
        return {
            "metrics": {"call_s": times[-1][1] / len(times)},
            "attempted": len(times),
            "calls": times,
        }

    def release(self) -> None:
        """Copy the outputs to the host and drop every device array."""
        self.outputs = [jax.tree.map(np.asarray, o) for o in self.outputs]

    def check(self) -> tuple[dict, int]:
        ref = self.ref.reference(self.inputs, self.calls)
        return self.ref.compare(self.inputs, self.calls, self.outputs, ref)

    def control(self) -> dict:
        """The control's numbers: the reference with one guarantee
        broken, in the program's place, on this run's calls."""
        calls = [self._kwargs(i) for i in range(max(len(self.calls), 1))]
        ref = self.ref.reference(self.inputs, calls)
        outputs = self.ref.control(self.inputs, calls, ref)
        return self.ref.compare(self.inputs, calls, outputs, ref)[0]
