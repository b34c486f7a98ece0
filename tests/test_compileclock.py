"""utils/compileclock.py: the per-thread count of trace/lower/compile
steps by which the overload ladder tells a cycle that compiled from one
that did not (tests/test_preemption.py drives the ladder end to end)."""

import threading

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
