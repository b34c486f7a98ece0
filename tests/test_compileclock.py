"""utils/compileclock.py: the per-thread count of trace/lower/compile
steps by which the overload ladder tells a cycle that compiled from one
that did not (tests/test_preemption.py drives the ladder end to end)."""

import threading
import time

import jax
import jax.numpy as jnp

from kubernetes_tpu.utils import compileclock


def test_first_call_counts_and_later_calls_do_not():
    @jax.jit
    def fresh(x):
        return jnp.sin(x) * 3 + 1

    x = jnp.arange(7.0)
    n0 = compileclock.events()
    fresh(x).block_until_ready()
    n1 = compileclock.events()
    assert n1 > n0  # trace, lower, compile (or the cache's load)
    fresh(x).block_until_ready()
    assert compileclock.events() == n1


def test_another_threads_compile_does_not_count():
    seen = {}

    def other():
        n0 = compileclock.events()
        jax.jit(lambda x: x * 5 - 2)(jnp.arange(3.0)).block_until_ready()
        seen["delta"] = compileclock.events() - n0

    before = compileclock.events()
    t = threading.Thread(target=other)
    t.start()
    t.join()
    assert seen["delta"] > 0
    assert compileclock.events() == before


def test_other_jax_events_never_count():
    n0 = compileclock.events()
    jax.monitoring.record_event_duration_secs(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.5
    )
    assert compileclock.events() == n0
    jax.monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 0.5
    )
    assert compileclock.events() == n0 + 1


def _compile_rows(t0):
    from kubernetes_tpu.utils import trace

    snap = trace.snapshot(t0)
    return [dict(zip(trace.SPAN_FIELDS, r)) for r in snap["spans"]
            if r[1] == "sched.compile"]


def test_a_compile_inside_a_cycle_is_a_row_under_that_cycle():
    from kubernetes_tpu.utils import trace

    t0 = trace.now() - 1.0      # the row starts where the compile did, before its report
    tr = trace.Trace("schedule_batch", threshold=60.0, span="sched.cycle", pods=1)
    with trace.span("sched.dispatch") as dispatch:
        time.sleep(0.26)        # the compile JAX then reports as 0.25 s
        jax.monitoring.record_event_duration_secs(
            "/jax/core/compile/backend_compile_duration", 0.25
        )
        # tracing and lowering count as events and are no executable
        jax.monitoring.record_event_duration_secs(
            "/jax/core/compile/jaxpr_trace_duration", 0.5
        )
    tr.close()
    (row,) = [r for r in _compile_rows(t0) if r["cycle"] == tr.id]
    # inside the step that paid for it, under the cycle
    assert row["cycle"] == tr.id and row["parent"] == dispatch.id
    assert dispatch.t0 <= row["start"] and row["end"] <= dispatch.t1
    assert row["a0"] == 0.25 and abs((row["end"] - row["start"]) - 0.25) < 1e-6
    assert row["cpu0"] is None and row["cpu1"] is None      # JAX's seconds, no CPU reading
    # the slow-cycle line names it beside the steps
    assert tr._compiles() == "; of which sched.compile x1: 250.0ms"


def test_a_compile_on_a_thread_with_no_cycle_open_is_a_row_of_no_cycle():
    from kubernetes_tpu.utils import trace

    seen = {}

    def other():
        seen["t0"] = trace.now()
        jax.monitoring.record_event_duration_secs(
            "/jax/core/compile/backend_compile_duration", 0.125
        )
        seen["tid"] = threading.get_ident()

    t = threading.Thread(target=other)
    t.start()
    t.join()
    rows = [r for r in _compile_rows(seen["t0"] - 1.0)
            if r["thread"] == seen["tid"] and r["a0"] == 0.125]
    assert [(r["cycle"], r["parent"]) for r in rows] == [(0, 0)]
