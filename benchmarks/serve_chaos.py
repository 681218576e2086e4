"""Chaos-smoke: graph serving under a deterministic fault plan.

Replays a KISS-deterministic request stream through
``repro.serve.GraphServeEngine`` with a seeded ``FaultPlan`` (poison +
transient + forced-nonconvergence injections, plus a simulated OOM on
the stream's own first-wave bucket) and emits the containment health
counters -- completed/failed/retried/quarantined/degraded/bisections/
wave_runs. Everything in ``derived`` is deterministic: the plan is
seeded, the stream is seeded, and the containment pipeline
(``serve/waves.py``) is sequential -- so ``run.py --check`` guards the
counters against ``BENCH_smoke.json`` in CI exactly like
the packing counters. A drift here means the containment semantics
changed: retry budgets, bisection probe order, or degradation
re-packing.

Wall time per request (faulty vs clean run of the same stream) is
printed as a comment only -- the overhead of containment is bisection
probes and degraded re-packs, which the ``wave_runs`` counter already
pins exactly.
"""
from __future__ import annotations

import time

from benchmarks.common import SCALE, emit
from repro.data.graphs import graph_request_stream
from repro.obs.metrics import derived_fragment
from repro.serve import FaultPlan, GraphRequest, GraphServeEngine


def _requests(stream):
    return [GraphRequest(uid=i, **g) for i, g in enumerate(stream)]


def _serve(stream, plan=None) -> GraphServeEngine:
    eng = GraphServeEngine(max_requests=8, fault_plan=plan, max_retries=2)
    for r in _requests(stream):
        eng.submit(r)
    eng.run()
    return eng


def run(num_requests: int | None = None) -> list[str]:
    R = num_requests or max(16, int(800 * SCALE))
    lines = []
    stream = graph_request_stream(R, kind="cc", family="random", seed=29)

    # clean baseline (no plan): containment machinery at zero overhead
    t0 = time.perf_counter()
    clean = _serve(stream)
    # host-driven wave loop: _run_wave materializes results via
    # np.asarray, so the run is synced when it returns
    t_clean = time.perf_counter() - t0  # repro-lint: disable=block-timer
    h = clean.health_records[-1]
    lines.append(emit(
        f"serve_chaos/clean/req={R}",
        t_clean / R * 1e6,
        f"completed={h.completed};failed={h.failed};"
        f"wave_runs={h.wave_runs};waves={clean.waves}",
    ))

    # seeded chaos: poison + transient + forced-nonconvergence uids,
    # plus an OOM on the first wave's own bucket (degradation path)
    plan = FaultPlan.random(
        31, range(R), p_poison=0.08, p_transient=0.12, max_transient=2,
        p_nonconverge=0.04,
    )
    probe = GraphServeEngine(max_requests=8)
    first_cap, _ = probe._wave_caps(_requests(stream)[:8])
    plan = FaultPlan(
        poison_uids=plan.poison_uids,
        transient_uids=plan.transient_uids,
        nonconverge_uids=plan.nonconverge_uids,
        oom_node_caps=frozenset([first_cap]),
    )
    # the gap since the clean run's read is plan setup, not a timed
    # interval; the chaos interval itself is host-synced (see above)
    t0 = time.perf_counter()  # repro-lint: disable=block-timer
    eng = _serve(stream, plan)
    t_chaos = time.perf_counter() - t0  # repro-lint: disable=block-timer
    h = eng.health_records[-1]
    # legacy health counters first (pinned bit-identical by --check),
    # then the engine's unified metrics.snapshot() (repro.obs.metrics)
    lines.append(emit(
        f"serve_chaos/faulty/req={R}",
        t_chaos / R * 1e6,
        f"completed={h.completed};failed={h.failed};"
        f"retried={h.retried};quarantined={h.quarantined};"
        f"degraded={h.degraded};bisections={h.bisections};"
        f"wave_runs={h.wave_runs};"
        + derived_fragment(eng.metrics.snapshot()),
    ))
    print(
        f"# serve_chaos: {h.failed}/{R} quarantined, "
        f"{h.wave_runs - clean.health_records[-1].wave_runs} extra wave "
        f"runs for containment "
        f"({t_chaos / max(t_clean, 1e-12):.2f}x clean wall)",
        flush=True,
    )

    # kind="sssp" waves through the SAME containment machinery: a
    # weighted multi-source stream, clean then with poison + transient
    # + forced-nonconvergence injections (the relax-bound sentinel).
    R2 = max(8, R // 2)
    sstream = graph_request_stream(
        R2, kind="sssp", family="random", seed=37
    )
    t0 = time.perf_counter()  # repro-lint: disable=block-timer
    sclean = _serve(sstream)
    t_sclean = time.perf_counter() - t0  # repro-lint: disable=block-timer
    h = sclean.health_records[-1]
    lines.append(emit(
        f"serve_chaos/sssp_clean/req={R2}",
        t_sclean / R2 * 1e6,
        f"completed={h.completed};failed={h.failed};"
        f"wave_runs={h.wave_runs};waves={sclean.waves}",
    ))
    # higher rates than the cc stream: R2 is half the size, and the
    # seed must light up all three injection paths even at smoke scale
    splan = FaultPlan.random(
        40, range(R2), p_poison=0.2, p_transient=0.2, max_transient=2,
        p_nonconverge=0.12,
    )
    t0 = time.perf_counter()  # repro-lint: disable=block-timer
    seng = _serve(sstream, splan)
    t_schaos = time.perf_counter() - t0  # repro-lint: disable=block-timer
    h = seng.health_records[-1]
    lines.append(emit(
        f"serve_chaos/sssp_faulty/req={R2}",
        t_schaos / R2 * 1e6,
        f"completed={h.completed};failed={h.failed};"
        f"retried={h.retried};quarantined={h.quarantined};"
        f"degraded={h.degraded};bisections={h.bisections};"
        f"wave_runs={h.wave_runs}",
    ))
    print(
        f"# serve_chaos[sssp]: {h.failed}/{R2} quarantined, "
        f"{h.wave_runs - sclean.health_records[-1].wave_runs} extra "
        f"wave runs for containment",
        flush=True,
    )

    # kind="pagerank" waves: the ADD-monoid family through the same
    # containment machinery. The forced-nonconvergence injection here
    # exercises the dense engine's REAL iteration-budget sentinel
    # (max_rounds=0 + the post-run tolerance probe, core/pagerank.py),
    # not a simulated failure -- so the quarantine counters pin that
    # the sentinel fires and is contained like any other poison.
    R3 = max(8, R // 2)
    pstream = graph_request_stream(
        R3, kind="pagerank", family="random", seed=43
    )
    t0 = time.perf_counter()  # repro-lint: disable=block-timer
    pclean = _serve(pstream)
    t_pclean = time.perf_counter() - t0  # repro-lint: disable=block-timer
    h = pclean.health_records[-1]
    lines.append(emit(
        f"serve_chaos/pagerank_clean/req={R3}",
        t_pclean / R3 * 1e6,
        f"completed={h.completed};failed={h.failed};"
        f"wave_runs={h.wave_runs};waves={pclean.waves}",
    ))
    pplan = FaultPlan.random(
        44, range(R3), p_poison=0.2, p_transient=0.2, max_transient=2,
        p_nonconverge=0.12,
    )
    t0 = time.perf_counter()  # repro-lint: disable=block-timer
    peng = _serve(pstream, pplan)
    t_pchaos = time.perf_counter() - t0  # repro-lint: disable=block-timer
    h = peng.health_records[-1]
    lines.append(emit(
        f"serve_chaos/pagerank_faulty/req={R3}",
        t_pchaos / R3 * 1e6,
        f"completed={h.completed};failed={h.failed};"
        f"retried={h.retried};quarantined={h.quarantined};"
        f"degraded={h.degraded};bisections={h.bisections};"
        f"wave_runs={h.wave_runs}",
    ))
    print(
        f"# serve_chaos[pagerank]: {h.failed}/{R3} quarantined, "
        f"{h.wave_runs - pclean.health_records[-1].wave_runs} extra "
        f"wave runs for containment",
        flush=True,
    )
    return lines


if __name__ == "__main__":
    run()
