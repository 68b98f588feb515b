"""Compile accounting from ``jax.monitoring``: how long JAX spent compiling,
and how often its persistent cache was hit or missed.

Copied from the bring-up smoke's ``CompileClock`` so that the benchmark
owns it.  ``backend_compiles`` counts the programs JAX compiled or loaded from
the persistent cache (it reports a ``backend_compile_duration`` for
both); ``cache_hits`` and ``cache_misses`` split them where the
persistent cache is on.  ``programs`` names each program in the order
it came, so a run can say which ones compiled where none should.
"""

from __future__ import annotations

import threading


class CompileClock:
    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self.secs = 0.0
        self.backend_compiles = 0
        self.hits = self.misses = 0
        self.programs: list[str] = []

        def on_duration(event, duration, fun_name="?", **_):
            if event == "/jax/core/compile/backend_compile_duration":
                with self._lock:
                    self.secs += duration
                    self.backend_compiles += 1
                    self.programs.append(str(fun_name))

        def on_event(event, **_):
            with self._lock:
                if event == "/jax/compilation_cache/cache_hits":
                    self.hits += 1
                elif event == "/jax/compilation_cache/cache_misses":
                    self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def read(self) -> dict:
        with self._lock:
            return {"compile_s": self.secs,
                    "backend_compiles": self.backend_compiles,
                    "cache_hits": self.hits, "cache_misses": self.misses}
