"""Observability: unified span tracing + metrics registry.

``repro.obs.trace`` records host-side spans at the boundaries the
level-synchronous engines ALREADY sync on (frontier levels, exchange
rounds, serve waves, train steps) and exports Chrome-trace/Perfetto
JSON; ``repro.obs.metrics`` is the central counter/gauge/histogram
registry all six stats dataclasses publish into through one shared
path. ``python -m repro.obs.summarize trace.json`` prints the
per-phase table. Full model: ``docs/observability.md``.
"""
from repro.obs import metrics, trace
from repro.obs.metrics import Registry, publish_stats
from repro.obs.trace import TRACE_MODES, Tracer

__all__ = [
    "trace",
    "metrics",
    "Tracer",
    "Registry",
    "publish_stats",
    "TRACE_MODES",
]
