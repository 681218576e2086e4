"""Host-side span tracer for the level-synchronous engines.

The repo's engines are host-driven by design: a level loop (frontier
buckets), an exchange round, or a serve wave runs on device, the host
syncs once to read the live count / convergence flag / unpacked
results, and decides the next compiled shape. Those syncs are exactly
the timeline the ROADMAP wants to see (it suspects per-level host
round-trips dominate small-n frontier wall-clock) -- so this tracer
attaches spans ONLY at boundaries that already sync and never adds a
device->host read of its own (RL001 stays clean by construction).

Usage::

    from repro.obs import trace

    trace.configure(trace="on")            # or REPRO_TRACE=1
    with trace.span("cc.frontier.level", bucket=4096) as sp:
        ...                                # host-driven work
        trace.count("host_sync")           # before each device read
        sp.tag(rounds=int(rounds))         # values the host ALREADY read
    trace.event("serve.quarantine", uid=7) # instant marker
    trace.export_chrome("trace.json")      # Chrome/Perfetto timeline

* **Disabled is free.** ``span()`` returns one shared ``_NULL_SPAN``
  singleton and ``count()`` returns at once when tracing is off -- no
  allocation, no clock read, no list append -- so instrumented hot
  loops cost nothing by default.
* **Parents.** The tracer keeps a per-thread stack of open spans. Every
  recorded span carries ``args.span_id`` (unique within the tracer)
  and ``args.parent_id`` (the enclosing span; 0 for a root), so the
  spans of one call or wave share their root and a span's self time is
  its duration less its children's.
* **Counters.** ``count(name, n)`` adds to the innermost open span. A
  span writes its totals as ``args.counts`` when it closes and folds
  them into its parent, so each span's counts cover everything beneath
  it.
* **Profiler clock.** While tracing is on, every span also enters a
  ``jax.profiler.TraceAnnotation`` of its name, so any ``jax.profiler``
  capture shows the program's spans beside the device ops.
* **Compiles.** The first ``configure(trace="on")`` registers one
  ``jax.monitoring`` listener that records JAX's lowerings as
  ``jax.lower`` spans (``fun=`` the function) and its backend compiles
  (cache fetches included) as ``jax.compile`` spans, each a child of
  the span open when it happened.
* **Device spans.** ``span(..., device=True)`` calls
  ``jax.block_until_ready`` at close on the value registered via
  ``sp.block_on(x)`` -- the RL006 block-timer discipline, applied at
  close so the span's duration covers the device work it launched.
  Tracer values pass through ``block_until_ready`` untouched, so
  instrumented functions stay safely traceable under ``jax.jit``.
* **Timer spans.** ``span(..., timer=True)`` returns a real timing
  span even when tracing is disabled (it times and blocks but records
  nothing): callers that need the duration regardless -- the training
  loop's straggler watchdog -- read ``sp.duration`` after the block.

Exported Chrome-trace JSON (``{"traceEvents": [...]}``, complete
events ``ph="X"``, instants ``ph="i"``, microsecond timestamps) loads
directly in ``chrome://tracing`` / Perfetto; ``python -m
repro.obs.summarize trace.json`` prints the per-phase aggregate table.

This module imports nothing from ``repro`` at module level (the
engines it instruments import it), and imports ``jax`` only once
tracing is turned on or a device span has something to block on.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
import weakref

# The RL004 choice set for the tracing knob (docs/engines.md matrix;
# registered in tools/lint/passes/choice_set.py KNOBS).
TRACE_MODES = ("off", "on")

# JAX's monitoring events for a lowering and for a backend compile
# (a fetch from the persistent cache is timed under the latter too).
_JAX_SPANS = {
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
}


class _NullSpan:
    """The shared disabled-path span: every method is a no-op and
    ``span()`` hands out the one module singleton, so a disabled
    tracer allocates nothing per span."""

    __slots__ = ()
    duration = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def tag(self, **attrs):
        return self

    def block_on(self, value):
        return value


_NULL_SPAN = _NullSpan()


def _add(span, name, n) -> None:
    if span.counts is None:
        span.counts = {}
    span.counts[name] = span.counts.get(name, 0) + n


class Span:
    """One live span. Use as a context manager; see module docstring."""

    __slots__ = (
        "_tracer", "name", "attrs", "device", "_blockee", "_ann", "_t0",
        "duration", "span_id", "parent", "counts",
    )

    def __init__(self, tracer, name, attrs, device):
        self._tracer = tracer  # None: timer-only span (tracing disabled)
        self.name = name
        self.attrs = attrs
        self.device = device
        self._blockee = None
        self._ann = None
        self._t0 = 0
        self.duration = 0.0
        self.span_id = 0
        self.parent = None  # the enclosing open Span, if any
        self.counts = None  # {counter: total}, made on the first count

    def tag(self, **attrs) -> "Span":
        """Attach attributes the host has ALREADY read (round counts,
        live sizes, failure classes) -- never pass a device value."""
        self.attrs.update(attrs)
        return self

    def block_on(self, value):
        """Register the device value this span's close blocks on
        (``device=True`` spans only). Returns ``value`` unchanged."""
        self._blockee = value
        return value

    def __enter__(self):
        tracer = self._tracer
        if tracer is not None:
            stack = tracer._stack()
            self.parent = stack[-1] if stack else None
            self.span_id = next(tracer._ids)
            stack.append(self)
            # Entered last, just before the clock read, so the native
            # annotation and the span start on the same instant.
            self._ann = tracer._annotation(self.name)
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self.device and self._blockee is not None:
            import jax

            jax.block_until_ready(self._blockee)
        end = time.perf_counter_ns()
        self.duration = (end - self._t0) * 1e-9
        tracer = self._tracer
        if tracer is not None:
            self._ann.__exit__(exc_type, exc, tb)
            stack = tracer._stack()
            if stack and stack[-1] is self:
                stack.pop()
            attrs, parent = self.attrs, self.parent
            if exc_type is not None:
                attrs.setdefault("exception", exc_type.__name__)
            if self.counts:
                attrs["counts"] = self.counts
                if parent is not None:
                    for name, n in self.counts.items():
                        _add(parent, name, n)
            attrs["span_id"] = self.span_id
            attrs["parent_id"] = parent.span_id if parent is not None else 0
            tracer._record(self.name, self._t0, end, attrs)
        return False


class Tracer:
    """Span/event collector. The module-level functions drive one
    process-global instance; tests may build their own."""

    def __init__(self, *, trace: str = "off"):
        self.events: list[dict] = []
        self._origin = time.perf_counter_ns()
        self._pid = os.getpid()
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._annotation = None  # jax.profiler.TraceAnnotation, once on
        self.enabled = False
        self.configure(trace=trace)

    # -- knobs ---------------------------------------------------------
    def configure(self, *, trace: str | None = None) -> None:
        """Set the ``trace=`` mode (``docs/engines.md`` matrix; unknown
        strings raise like every other dispatch knob)."""
        if trace is None:
            return
        if trace not in TRACE_MODES:
            # check_choice imports lazily, and only to raise: the engines
            # this module instruments import it, so a module-level (or
            # valid-path) import of repro.core here would be a cycle.
            from repro.core.components import check_choice

            check_choice("trace", trace, TRACE_MODES)
        self.trace = trace
        self.enabled = trace == "on"
        if self.enabled and self._annotation is None:
            self._listen_to_jax()

    def _listen_to_jax(self) -> None:
        """Import jax's profiler annotation and register one
        ``jax.monitoring`` listener per tracer, on the first
        ``trace="on"``; the listener holds the tracer weakly (JAX keeps
        its listeners for the life of the process) and does nothing
        while tracing is off."""
        import jax
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation
        ref = weakref.ref(self)

        def on_event(event, duration, fun_name="?", **_):
            tracer = ref()
            if tracer is not None and tracer.enabled and event in _JAX_SPANS:
                tracer._jax_span(_JAX_SPANS[event], duration, fun_name)

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def reset(self) -> None:
        """Drop recorded events (fresh timeline, same knobs)."""
        self.events = []
        self._origin = time.perf_counter_ns()

    # -- recording -----------------------------------------------------
    def _stack(self) -> list:
        """This thread's open spans, innermost last."""
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(
        self,
        name: str,
        *,
        device: bool = False,
        timer: bool = False,
        **attrs,
    ):
        """A context-managed span. Disabled tracing returns the no-op
        singleton unless ``timer=True`` (see module docstring)."""
        if not self.enabled:
            if not timer:
                return _NULL_SPAN
            return Span(None, name, attrs, device)
        return Span(self, name, attrs, device)

    def count(self, name: str, n: int = 1) -> None:
        """Add ``n`` to counter ``name`` of the innermost open span
        (dropped where none is open)."""
        if not self.enabled:
            return
        stack = self._stack()
        if stack:
            _add(stack[-1], name, n)

    def event(self, name: str, **attrs) -> None:
        """An instant marker (Chrome-trace ``ph="i"``)."""
        if not self.enabled:
            return
        now = time.perf_counter_ns()
        self.events.append({
            "name": name, "ph": "i", "s": "t",
            "ts": (now - self._origin) / 1e3,
            "pid": self._pid, "tid": threading.get_ident(),
            "args": attrs,
        })

    def _jax_span(self, name, duration_s, fun_name) -> None:
        """A span JAX timed itself: it ends now and began ``duration_s``
        earlier, under the innermost open span."""
        end = time.perf_counter_ns()
        stack = self._stack()
        self._record(name, end - int(duration_s * 1e9), end, {
            "fun": fun_name, "span_id": next(self._ids),
            "parent_id": stack[-1].span_id if stack else 0,
        })

    def _record(self, name, t0_ns, end_ns, attrs) -> None:
        self.events.append({
            "name": name, "ph": "X",
            "ts": (t0_ns - self._origin) / 1e3,  # Chrome wants microseconds
            "dur": (end_ns - t0_ns) / 1e3,
            "pid": self._pid, "tid": threading.get_ident(),
            "args": attrs,
        })

    # -- export --------------------------------------------------------
    def chrome_trace(self) -> dict:
        """The Chrome-trace/Perfetto JSON object."""
        return {"traceEvents": list(self.events), "displayTimeUnit": "ms"}

    def export_chrome(self, path: str) -> int:
        """Write the timeline as Chrome-trace JSON; returns the number
        of events written (loads in chrome://tracing / Perfetto)."""
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f, indent=1, default=str)
        return len(self.events)


# The process-global tracer the engines record into. REPRO_TRACE=1 (or
# "on") enables tracing from the environment -- the benchmark / CI hook.
_ON = ("1", "on", "true", "yes")
_GLOBAL = Tracer(
    trace="on" if os.environ.get("REPRO_TRACE", "").lower() in _ON else "off",
)


def configure(*, trace: str | None = None):
    _GLOBAL.configure(trace=trace)


def enabled() -> bool:
    return _GLOBAL.enabled


def reset() -> None:
    _GLOBAL.reset()


# Bound-method aliases, not wrapper defs: the disabled path must stay
# near-free in the engines' hot loops, and a wrapper would pay a second
# call frame + kwargs packing per span. _GLOBAL is never reassigned
# (configure mutates it), so the bindings cannot go stale.
span = _GLOBAL.span
count = _GLOBAL.count
event = _GLOBAL.event


def chrome_trace() -> dict:
    return _GLOBAL.chrome_trace()


def export_chrome(path: str) -> int:
    return _GLOBAL.export_chrome(path)
