"""Host scheduler end-to-end: informer-fed cache/queue, batched cycles,
assume/bind, failure -> unschedulable -> event-driven requeue -> placed.

The integration pattern mirrors the reference's: nodes and pods exist
only as API objects (test/integration/util/util.go:86); the scheduler
watches the store, solves on the (virtual) device, and binds through the
API.
"""

import time

import numpy as np

from kubernetes_tpu.api import store as st
from kubernetes_tpu.api import types as api
from kubernetes_tpu.scheduler import Scheduler, SchedulingQueue
from kubernetes_tpu.testing.wrappers import GI, MI, make_node, make_pod


def _mk_scheduler(store, **kw):
    s = Scheduler(store, **kw)
    s.informers.informer("Node").start()
    s.informers.informer("Pod").start()
    assert s.informers.wait_for_sync(10)
    return s


def _drain(sched, cycles=10, timeout=0.05):
    out = []
    for _ in range(cycles):
        out.append(sched.schedule_batch(timeout=timeout))
    return out


def test_schedules_and_binds_through_api():
    store = st.Store()
    for i in range(4):
        store.create(
            make_node(f"n{i}").capacity(cpu_milli=4000, mem=8 * GI, pods=10).obj()
        )
    for i in range(8):
        store.create(make_pod(f"p{i}").req(cpu_milli=500, mem=512 * MI).obj())
    sched = _mk_scheduler(store)
    try:
        stats = sched.schedule_batch(timeout=2)
        assert stats["scheduled"] == 8, stats
        # the binding stage commits waves asynchronously: drain it before
        # reading the store
        assert sched.flush_binds(timeout=30)
        # bound through the API: store shows nodeName on every pod
        pods, _ = store.list("Pod")
        assert all(p.spec.node_name for p in pods)
        # informer echo confirms the assumed pods (no TTL leak)
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and sched.cache.assumed_count():
            time.sleep(0.02)
        assert sched.cache.assumed_count() == 0
    finally:
        sched.stop()


def test_unschedulable_requeues_on_node_add_then_places():
    store = st.Store()
    store.create(make_node("small").capacity(cpu_milli=500, mem=GI, pods=10).obj())
    store.create(make_pod("big").req(cpu_milli=4000).obj())
    sched = _mk_scheduler(store)
    try:
        stats = sched.schedule_batch(timeout=2)
        assert stats["unschedulable"] == 1
        assert sched.queue.stats()["unschedulable"] == 1
        # a new big-enough node arrives: the event moves the pod out of
        # the unschedulable tier and the next cycles place it
        store.create(
            make_node("big-node").capacity(cpu_milli=8000, mem=8 * GI, pods=10).obj()
        )
        deadline = time.monotonic() + 10
        placed = False
        while time.monotonic() < deadline and not placed:
            sched.schedule_batch(timeout=0.2)
            placed = bool(store.get("Pod", "big").spec.node_name)
        assert placed
        assert store.get("Pod", "big").spec.node_name == "big-node"
    finally:
        sched.stop()


def test_scheduling_gates_hold_until_cleared():
    store = st.Store()
    store.create(make_node("n0").capacity(cpu_milli=4000, mem=8 * GI).obj())
    pod = make_pod("gated").req(cpu_milli=100).obj()
    pod.spec.scheduling_gates = ["wait-for-quota"]
    store.create(pod)
    sched = _mk_scheduler(store)
    try:
        stats = sched.schedule_batch(timeout=0.3)
        assert stats["popped"] == 0
        assert sched.queue.stats()["gated"] == 1
        # clearing the gate releases the pod (PreEnqueue passes)
        cur = store.get("Pod", "gated")
        cur.spec.scheduling_gates = []
        store.update(cur)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            sched.schedule_batch(timeout=0.2)
            if store.get("Pod", "gated").spec.node_name:
                break
        assert store.get("Pod", "gated").spec.node_name == "n0"
    finally:
        sched.stop()


def test_deleted_assigned_pod_frees_resources_for_pending():
    store = st.Store()
    store.create(make_node("n0").capacity(cpu_milli=1000, mem=8 * GI, pods=10).obj())
    store.create(make_pod("first").req(cpu_milli=1000).obj())
    sched = _mk_scheduler(store)
    try:
        assert sched.schedule_batch(timeout=2)["scheduled"] == 1
        assert sched.flush_binds(timeout=30)  # "first" durably bound
        store.create(make_pod("second").req(cpu_milli=1000).obj())
        assert sched.schedule_batch(timeout=2)["unschedulable"] == 1
        store.delete("Pod", "first")
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            sched.schedule_batch(timeout=0.2)
            if store.get("Pod", "second").spec.node_name:
                break
        assert store.get("Pod", "second").spec.node_name == "n0"
    finally:
        sched.stop()


def test_priority_order_in_contended_batch():
    store = st.Store()
    store.create(make_node("n0").capacity(cpu_milli=1000, mem=8 * GI, pods=10).obj())
    store.create(make_pod("low").req(cpu_milli=1000).priority(1).obj())
    store.create(make_pod("high").req(cpu_milli=1000).priority(100).obj())
    sched = _mk_scheduler(store)
    try:
        sched.schedule_batch(timeout=2)
        assert sched.flush_binds(timeout=30)
        assert store.get("Pod", "high").spec.node_name == "n0"
        assert not store.get("Pod", "low").spec.node_name
    finally:
        sched.stop()


def test_queue_backoff_and_flush(monkeypatch):
    now = [0.0]
    clock = lambda: now[0]
    q = SchedulingQueue(backoff_base=1.0, backoff_max=10.0,
                        unschedulable_flush_after=300.0, clock=clock)
    pod = make_pod("x").req(cpu_milli=1).obj()
    q.add(pod)
    (info,) = q.pop_batch(10, timeout=0)
    # transient failure: backoff 1s (attempt 1)
    q.requeue_backoff(info)
    assert q.pop_batch(10, timeout=0) == []
    now[0] = 1.1
    (info,) = q.pop_batch(10, timeout=0)
    # unschedulable parks until flush interval
    q.add_unschedulable(info)
    now[0] = 200.0
    assert q.pop_batch(10, timeout=0) == []
    # flush interval moves it to backoff (attempts=2 -> 2s) ...
    now[0] = 302.0
    assert q.pop_batch(10, timeout=0) == []
    # ... and it pops once that backoff expires
    now[0] = 304.2
    (info,) = q.pop_batch(10, timeout=0)
    assert info.attempts == 3
    q.done(info.pod)    # hand the popped pod back (`make audit`'s ledger)
