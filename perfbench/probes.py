"""What a run records besides the client's own observations: the harness's
spans around the calls into each layer, one record per scheduling cycle, and
JAX's compile and cache-load events.

Spans go to two places at once: a list on the host clock (read by the
per-layer metrics in every traced run) and, through
``jax.profiler.TraceAnnotation``, into the profiler's own trace, where the
reduction sets them beside the device's operations to name idle gaps.
"""

from __future__ import annotations

import gc
import threading
import time
from contextlib import contextmanager

import jax
import jax.monitoring

_COMPILE_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


class Recorder:
    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        self.spans: dict = {}       # name -> [(t0, t1, n)]
        self.cycles: list = []      # one dict per finalized solve
        self.compiles: list = []    # (t, kind, fun_name, seconds)
        self.gc_pauses: list = []   # (t0, seconds, generation)
        self._mu = threading.Lock()
        self._listener = None
        self._gc_t0 = None

    @contextmanager
    def span(self, name: str, n: int = 0):
        t0 = self.clock()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                t1 = self.clock()
                with self._mu:
                    self.spans.setdefault(name, []).append((t0, t1, n))

    def wrap(self, obj, attr: str, name: str, count=None):
        """Replace the bound method `obj.attr` by one that runs inside a span.
        Observation only: arguments and result pass through untouched."""
        inner = getattr(obj, attr)

        def wrapped(*a, **kw):
            with self.span(name, count(*a, **kw) if count else 0):
                return inner(*a, **kw)

        setattr(obj, attr, wrapped)
        return inner

    # -- compile events ------------------------------------------------------

    def install_compile_listener(self) -> None:
        def on_duration(event, secs, **kw):
            kind = _COMPILE_EVENTS.get(event)
            if kind is None and event == _TRACE_EVENT:
                kind = "trace"
            if kind is not None:
                with self._mu:
                    self.compiles.append(
                        (self.clock(), kind, str(kw.get("fun_name", "?")), float(secs))
                    )

        self._listener = on_duration
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def install_gc_listener(self) -> None:
        """Times the interpreter's collections (the collector stops every
        thread).  Observation only: no threshold is touched."""
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = self.clock()
        elif self._gc_t0 is not None:
            self.gc_pauses.append((self._gc_t0, self.clock() - self._gc_t0, info["generation"]))
            self._gc_t0 = None

    def uninstall(self) -> None:
        if self._listener is not None:
            jax.monitoring.unregister_event_duration_listener(self._listener)
            self._listener = None
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def last_compile_time(self) -> float:
        """Host time of the newest build or cache load (a re-trace that hits
        the in-memory cache is no compile and is not counted here)."""
        with self._mu:
            ts = [t for t, kind, _, _ in self.compiles if kind != "trace"]
        return max(ts) if ts else float("-inf")

    def snapshot(self) -> dict:
        with self._mu:
            return {
                "spans": {k: list(v) for k, v in self.spans.items()},
                "cycles": list(self.cycles),
                "compiles": list(self.compiles),
                "gc_pauses": list(self.gc_pauses),
            }
