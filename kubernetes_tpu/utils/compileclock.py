"""Did this thread just build or load an executable?

A jitted program's first call in a process blocks its caller while JAX
traces, lowers and compiles it (or loads it from the persistent cache,
utils/compilecache.py): seconds on a first-of-a-bucket batch, nothing
afterwards.  A controller that reads a cycle's wall as LOAD must not
read such a cycle — the scheduler's overload ladder took cold cycles on
the chip (3-11 s against a 0.5 s SLO) for overload and deferred
preemption in an idle cluster.  JAX reports each of the three steps on
the thread that paid for it; this module counts the reports per thread,
so a caller brackets its own work with two reads of :func:`events` and
compares.  Compiles on other threads (the prewarm pool, the parallel
warm-up) never count against the reader.

Each build or cache load also goes to the flight recorder as a span
``sched.compile`` (``a0`` = the seconds JAX reports for the backend
step) under the cycle the paying thread has open, so a slow cycle's
log line names its own compile (docs/scheduler_loop.md, "Reading a
slow cycle").

A count, not seconds to subtract: the durations JAX reports leave out
work it does around the three timed steps, which grows with the
compile.  On a v5e chip 0.3-1.4 s of a compiling cycle stayed
unexplained after subtraction, one to three times the SLO (PERF.md,
Findings PR 21).  A cycle that compiled is no usable reading of steady
cost; one that did not is exact.
"""

from __future__ import annotations

import threading

import jax.monitoring

from . import trace

_EVENTS = frozenset({
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
})

_BACKEND = "/jax/core/compile/backend_compile_duration"

_seen = threading.local()


def _on_duration(event: str, secs: float, **_kw) -> None:
    if event in _EVENTS:
        _seen.n = getattr(_seen, "n", 0) + 1
        if event == _BACKEND:
            t1 = trace.now()
            trace.event("sched.compile", t1 - secs, t1, 1, a0=secs)


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def events() -> int:
    """Trace, lower and compile steps (persistent-cache loads included)
    the calling thread has blocked on since it started.  Monotonic: read
    it twice; a difference means the work in between compiled."""
    return getattr(_seen, "n", 0)
