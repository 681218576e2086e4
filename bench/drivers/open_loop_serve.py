"""Open-loop traffic into ``repro.serve.GraphServeEngine``.

The workload file names the request ``kind``, the offered ``rate``
(requests per second, fixed: the benchmark never searches for one) and
the reference module under ``refs/`` (``check``). The configuration
gives the request generator and the engine's knobs (``engine``).

Set-up draws the whole stream from the seed: Poisson due times over the
window and one molecule per request. The engine packs each wave from
consecutive requests of its FIFO queue, so every wave the window can
run is a run of at most ``max_requests`` consecutive requests; set-up
serves one such run in every ``(node_cap, edge_cap)`` bucket that the
stream reaches, and no other.

One thread plays both sides. It submits every request that is due,
calls ``run()`` whenever requests are queued, and sleeps until the next
due time when none are. A request's latency runs from its due time to
the return of the ``run()`` call that delivered it, so a backlog shows
in the tail. Every request due in the window is served, the drain after
its close included (up to ``DRAIN_S``); ``served_rps`` is the requests
completed over the time from the window's start to the last completion.
How late the generator ran (submission time less due time) is reported
beside the metrics.
"""
from __future__ import annotations

import time

import numpy as np

DRAIN_S = 60.0


def next_pow2(x):
    x = np.maximum(np.asarray(x, np.int64), 1)
    return np.left_shift(1, np.ceil(np.log2(x)).astype(np.int64))


class Driver:
    def __init__(self, cell, rate: float | None = None):
        from repro.serve import GraphRequest

        self.cell = cell
        wl, cfg = cell.workload, cell.config
        self.rate = float(rate if rate is not None else wl["rate"])
        self.knobs = dict(cfg["engine"])
        rng = np.random.default_rng(cell.seed)
        gen = cell.load("gen", cfg["generator"])
        self.due = gen.arrivals(self.rate, cell.seconds, rng,
                                int(cfg["assumed"]["structure_seed"]))
        self.mols = gen.generate(cfg, len(self.due), rng)
        m, ptr = self.mols, self.mols["edge_ptr"]
        self.requests = [
            GraphRequest(uid=i, src=m["src"][ptr[i]:ptr[i + 1]],
                         dst=m["dst"][ptr[i]:ptr[i + 1]],
                         num_nodes=int(m["num_nodes"][i]), kind=wl["kind"])
            for i in range(len(self.due))
        ]
        self.ref = cell.load("refs", wl["check"])
        self.results: list = [None] * len(self.requests)

    def shapes(self) -> dict:
        return {"requests": len(self.requests),
                "num_nodes": int(self.mols["num_nodes"].sum()),
                "num_edges": int(len(self.mols["src"]))}

    def buckets(self) -> dict:
        """{(node_cap, edge_cap): (first request, count)} over every run
        of consecutive requests that can form one wave."""
        k = self.knobs
        nn = self.mols["num_nodes"].astype(np.int64)
        ee = np.diff(self.mols["edge_ptr"])
        cn, ce = np.concatenate([[0], np.cumsum(nn)]), np.concatenate([[0], np.cumsum(ee)])
        out = {}
        for size in range(min(k["max_requests"], len(nn)), 0, -1):
            start = np.arange(len(nn) - size + 1)
            sn, se = cn[start + size] - cn[start], ce[start + size] - ce[start]
            fits = (sn <= k["max_nodes"]) & (se <= k["max_edges"])
            if size == 1:
                fits[:] = True  # a lone request always forms a wave
            caps = np.stack([np.maximum(k["min_nodes"], next_pow2(sn)),
                             np.maximum(k["min_edges"], next_pow2(se))], axis=1)[fits]
            for cap, i in zip(*np.unique(caps, axis=0, return_index=True)):
                out.setdefault(tuple(int(c) for c in cap), (int(start[fits][i]), size))
        return out

    def warm_up(self) -> None:
        from repro.serve import GraphRequest, GraphServeEngine

        eng = GraphServeEngine(**self.knobs)
        uid = 0
        for first, size in self.buckets().values():
            for r in self.requests[first:first + size]:
                eng.submit(GraphRequest(uid=uid, src=r.src, dst=r.dst,
                                        num_nodes=r.num_nodes, kind=r.kind))
                uid += 1
            eng.run()

    def window(self, seconds: float, annotate) -> dict:
        from repro.serve import GraphServeEngine

        eng = GraphServeEngine(**self.knobs)
        due, reqs, n = self.due, self.requests, len(self.requests)
        late = np.zeros(n)
        done_at = np.full(n, np.nan)
        i = 0
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            while i < n and due[i] <= now:
                eng.submit(reqs[i])
                late[i] = now - due[i]
                i += 1
            if eng.queue:
                with annotate("bench.run"):
                    served = eng.run()
                t = time.perf_counter() - t0
                for r in served:
                    if r.done and not r.failed:
                        self.results[r.uid] = r.result
                    done_at[r.uid] = t
            elif i < n:
                with annotate("bench.wait"):
                    time.sleep(max(0.0, due[i] - (time.perf_counter() - t0)))
            else:
                break
            if now > seconds + DRAIN_S:
                break
        ok = np.array([r is not None for r in self.results])
        # A request never answered waited at least until the run gave up.
        lat = np.where(ok, done_at - due, seconds + DRAIN_S - due) * 1e3
        last = float(np.nanmax(np.where(ok, done_at, np.nan))) if ok.any() else np.inf
        return {
            "metrics": {
                "p95_ms": float(np.percentile(lat, 95)) if n else 0.0,
                "p50_ms": float(np.percentile(lat, 50)) if n else 0.0,
                "served_rps": float(ok.sum() / last) if ok.any() else 0.0,
            },
            "attempted": n,
            "completed": int(ok.sum()),
            "waves": eng.waves,
            "offered_rps": self.rate,
            "generator_late_ms": {"p50": float(np.median(late) * 1e3) if n else 0.0,
                                  "max": float(late.max() * 1e3) if n else 0.0},
            "latency_ms": lat,
            "due": due,
            "last_completion_s": last,
        }

    def release(self) -> None:
        """Nothing to drop: the window's engine ended with the window,
        and the results are host arrays."""

    def check(self) -> tuple[dict, int]:
        ref = self.ref.reference(self.mols)
        return self.ref.compare(self.mols, self.results, ref)

    def control(self) -> dict:
        ref = self.ref.reference(self.mols)
        return self.ref.compare(self.mols, self.ref.control(self.mols, ref), ref)[0]
