"""Unified observability: one metrics registry + one span API.

The serving stack's measured quantities (the paper's evaluation currency:
throughput, p50/p99 latency, transferred bytes, server ops) live in
``MetricsRegistry`` instruments instead of scattered ad-hoc counters:
``SchedMetrics`` / ``CacheStats`` / ``PlannerStats`` are thin attribute
views over named instruments (``obs.registry.RegistryView``), and
``registry.snapshot()`` is the plain-dict source of truth ``benchlib``
and the BENCH figures diff (``snap_b - snap_a``) instead of
hand-subtracting before/after field values.

Spans are strictly opt-in.  ``obs.enabled`` is the module-level switch
(default ``False``).  Every layer boundary in ``core/scheduler.py``,
``core/engine.py`` and ``endpoint/service.py`` is one
``with obs.span(name, view, **args):`` — with the switch off that
returns a shared no-op (nothing imported, nothing allocated, nothing
recorded) and never imports ``repro.obs.trace`` (the CI guard pins
this).  With it on, one span feeds two sinks on one clock:

- a ``jax.profiler.TraceAnnotation(name)``, so the span lands in the
  profiler's host plane beside the device ops it explains, under exactly
  ``name`` (visible only while a profiler trace is being taken);
- an exact scalar ``<prefix>.span_s.<name>`` (summed host seconds) in
  the component's own registry (``view.registry``, prefix
  ``view._PREFIX``; the global registry under ``span_s.<name>`` when no
  view is given), which snapshot diffs turn into per-layer times;

and, when a ``SpanTracer`` is attached (``enable(trace=True)``), a
Chrome trace-event in memory as well (``obs.trace``).  Spans never
fence: a span's duration is the host's, and the device's story is the
profiler trace.  Where the host genuinely waits for the device the
program says so with a span of its own (``unit.wait``).

The global ``obs.registry`` holds *observability-only* instruments
(kernel dispatch tallies, engine latency histograms and span seconds)
and is mutated only when ``obs.enabled`` — a dedicated test pins zero
mutations with the switch off.  Functional counters (the
``SchedMetrics`` family) live in per-component registries that count
regardless, exactly as the old dataclass fields did.
"""

from __future__ import annotations

import time
from contextlib import contextmanager

from repro.obs.registry import (  # noqa: F401  (re-exported API)
    MetricsRegistry,
    RegistryView,
    Snapshot,
)

#: Module-level switch for the instrumentation hooks.  Read via attribute
#: access (``obs.enabled``) so flips are visible everywhere; ``False`` is
#: the zero-overhead default the byte-identity suites run under.
enabled: bool = False

#: The active ``SpanTracer`` or ``None``.  ``span`` records into it, and
#: the async ``query`` events and instants are emitted only when it is
#: not ``None`` — the tracer module is imported lazily so a disabled run
#: never touches it.
tracer = None

#: Global registry for observability-only instruments (kernel dispatch
#: tallies, serial-engine latency histograms).  Only mutated when
#: ``enabled`` is True.
registry = MetricsRegistry()


def enable(trace: bool = True):
    """Turn the instrumentation hooks on; returns the active tracer (or
    ``None`` when ``trace=False`` — registry-only mode: spans still reach
    the profiler and the registry, just not an in-memory tracer)."""
    global enabled, tracer
    enabled = True
    if trace and tracer is None:
        from repro.obs.trace import SpanTracer

        tracer = SpanTracer()
    return tracer


def disable() -> None:
    """Back to the zero-overhead default: hooks off, tracer detached
    (already-recorded events stay with the detached tracer object)."""
    global enabled, tracer
    enabled = False
    tracer = None


def snapshot() -> Snapshot:
    """Plain-dict snapshot of the global observability registry."""
    return registry.snapshot()


@contextmanager
def tracing(trace: bool = True):
    """Scoped ``enable()``: yields the tracer, restores the previous
    enabled/tracer state on exit (what tests and the traced bench passes
    use so tracing never leaks across cases)."""
    prev = (enabled, tracer)
    tr = enable(trace)
    try:
        yield tr
    finally:
        globals()["enabled"], globals()["tracer"] = prev


class _NoSpan:
    """The disabled path's span: one shared instance, does nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        return None

    def note(self, **args) -> None:
        return None


_NO_SPAN = _NoSpan()
_annotation = None  # jax.profiler.TraceAnnotation, bound on first span


class _Span:
    """A live span: a profiler annotation, the registry's summed seconds
    and (with a tracer attached) an in-memory event, all from one pair
    of clock reads."""

    __slots__ = ("name", "reg", "key", "args", "_ann", "_t0")

    def __init__(self, name: str, reg: MetricsRegistry, key: str,
                 args: dict):
        self.name = name
        self.reg = reg
        self.key = key
        self.args = args

    def __enter__(self):
        self._ann = _annotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        self.reg.inc(self.key, (t1 - self._t0) * 1e-9)
        tr = tracer
        if tr is not None:
            tr.complete(self.name, self._t0, t1, self.args)

    def note(self, **args) -> None:
        """Add arguments to the in-memory event (known only at the end)."""
        self.args.update(args)


def span(name: str, view=None, **args):
    """One layer boundary, as a context manager.

    Off (the default): the shared no-op.  On: a profiler annotation named
    exactly ``name``, ``name``'s host seconds added to the scalar
    ``<view._PREFIX>.span_s.<name>`` in ``view.registry`` (a
    ``RegistryView``; the global registry's ``span_s.<name>`` when
    ``view`` is ``None``), and a complete event in the attached tracer
    carrying ``args``.  Nothing blocks on the device."""
    if not enabled:
        return _NO_SPAN
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    if view is None:
        return _Span(name, registry, f"span_s.{name}", args)
    return _Span(name, view.registry, f"{view._PREFIX}.span_s.{name}", args)
