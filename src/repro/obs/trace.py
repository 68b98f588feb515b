"""Span tracer: the serving lifecycle as an in-memory Chrome timeline.

The in-memory sink of ``obs.span``: each closed span arrives here as a
complete event with the same ``perf_counter_ns`` readings that its
registry seconds (``<prefix>.span_s.<name>``) were summed from.  The
same spans reach the JAX profiler as ``TraceAnnotation``s, on the
device's clock; this tracer is for tests and for a host-only timeline
without a profiler.  Nothing here blocks on the device: a span's
duration is host time (``unit.step`` is the dispatch, ``unit.wait`` the
host waiting for the step's results), and traced and untraced
executions submit identical device programs (byte-identical results,
the pinned invariant).

Event model (Chrome trace-event format):

- sync spans — ``ph: "X"`` complete events on one track; nesting is
  positional (a span strictly inside another renders as its child), so
  the wave -> lowering -> unit -> kernel/cache hierarchy falls out of
  the call structure.
- async spans — ``ph: "b"``/``"e"`` nestable pairs keyed by ``id``; used
  for per-query lifetimes, which overlap freely across waves.
- instants — ``ph: "i"``; trace-time kernel dispatch notes and other
  point events.

``export_chrome`` writes the ``{"traceEvents": [...]}`` JSON Perfetto
and ``chrome://tracing`` load directly.
"""

from __future__ import annotations

import json
import time


class SpanTracer:
    def __init__(self):
        self.events: list[dict] = []
        self._epoch_ns = time.perf_counter_ns()

    def _ts(self) -> float:
        """Microseconds since tracer start (the Chrome ``ts`` unit)."""
        return (time.perf_counter_ns() - self._epoch_ns) / 1e3

    # -------------------------------------------------------- sync spans
    def complete(self, name: str, t0_ns: int, t1_ns: int, args: dict
                 ) -> None:
        """Record a closed ``obs.span``: ``perf_counter_ns`` readings at
        its open and close."""
        self.events.append({
            "name": name, "ph": "X", "pid": 0, "tid": 0,
            "ts": (t0_ns - self._epoch_ns) / 1e3,
            "dur": (t1_ns - t0_ns) / 1e3,
            "args": args,
        })

    # ------------------------------------------------------ async spans
    def begin_async(self, name: str, aid, **args) -> None:
        self.events.append({
            "name": name, "ph": "b", "cat": name, "id": int(aid),
            "pid": 0, "tid": 0, "ts": self._ts(), "args": args,
        })

    def end_async(self, name: str, aid, **args) -> None:
        self.events.append({
            "name": name, "ph": "e", "cat": name, "id": int(aid),
            "pid": 0, "tid": 0, "ts": self._ts(), "args": args,
        })

    # ---------------------------------------------------------- instants
    def instant(self, name: str, **args) -> None:
        self.events.append({
            "name": name, "ph": "i", "s": "t", "pid": 0, "tid": 0,
            "ts": self._ts(), "args": args,
        })

    # ------------------------------------------------------------- query
    def count(self, name: str, ph: str | None = None) -> int:
        """Events named ``name`` (optionally of one phase) — what the
        metric-invariant tests count."""
        return sum(1 for e in self.events
                   if e["name"] == name and (ph is None or e["ph"] == ph))

    def named(self, name: str) -> list[dict]:
        return [e for e in self.events if e["name"] == name]

    # ------------------------------------------------------------ export
    def to_chrome(self) -> dict:
        return {"traceEvents": list(self.events), "displayTimeUnit": "ms"}

    def export_chrome(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)
