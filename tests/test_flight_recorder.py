"""The flight recorder (utils/trace.py): span and pod rows, laps, cost, the
slow-cycle report, and the scheduler's stamps on a pod that is requeued."""

import gc
import importlib
import logging
import threading
import time
import tracemalloc

import numpy as np
import pytest

from kubernetes_tpu.utils import trace

SPAN = trace.SPAN_FIELDS
POD = trace.POD_FIELDS


def spans(snap, name=None):
    rows = [dict(zip(SPAN, r)) for r in snap["spans"]]
    return [r for r in rows if name is None or r["name"] == name]


@pytest.fixture
def fresh():
    trace.reset()
    yield trace
    trace.reset()


def test_nested_spans_record_wall_cpu_thread_cycle_and_parent(fresh):
    t0 = time.perf_counter()
    with trace.span("t.outer", n=3, cycle=77) as outer:
        with trace.span("t.inner") as inner:
            sum(range(20_000))
            inner.a0, inner.a1 = 4.0, 5.0
        outer.n = 9
    t1 = time.perf_counter()
    snap = trace.snapshot(t0, t1)
    (o,), (i,) = spans(snap, "t.outer"), spans(snap, "t.inner")
    assert o["id"] == outer.id and i["id"] == inner.id
    assert i["parent"] == o["id"] and o["parent"] == 0
    assert i["cycle"] == o["cycle"] == 77          # a child takes its parent's cycle
    assert o["start"] <= i["start"] <= i["end"] <= o["end"]
    assert (o["start"], o["end"]) == (outer.t0, outer.t1)   # the handle's reads ARE the row's
    assert 0.0 < i["cpu1"] - i["cpu0"] <= (i["end"] - i["start"]) + 1e-3
    assert o["thread"] == i["thread"] == threading.get_ident()
    assert (o["n"], i["a0"], i["a1"]) == (9, 4.0, 5.0)
    # the thread is left with no span open
    with trace.span("t.after") as after:
        pass
    assert spans(trace.snapshot(after.t0), "t.after")[0]["parent"] == 0


def test_snapshot_takes_the_rows_that_start_inside_the_interval(fresh):
    with trace.span("t.a") as a:
        pass
    with trace.span("t.b") as b:
        pass
    slot = trace.pod_slot("ns/p")
    trace.stamp(slot, trace.ENQUEUED)
    trace.stamp(slot, trace.CYCLE, 5)
    snap = trace.snapshot(b.t0, float("inf"))
    assert [s["name"] for s in spans(snap)] == ["t.b"]
    assert [s["name"] for s in spans(trace.snapshot(a.t0, b.t0))] == ["t.a"]
    (row,) = [dict(zip(POD, r)) for r in snap["pods"]]
    assert row["key"] == "ns/p" and row["cycle"] == 5 and row["popped"] is None
    assert row["route"] == -1 and row["attempts"] == 0
    assert snap["dropped_spans"] == 0 and snap["dropped_pods"] == 0


def test_a_lapped_ring_counts_what_it_dropped_and_readers_get_none(fresh, monkeypatch):
    monkeypatch.setattr(trace, "SPAN_ROWS", 64)
    monkeypatch.setattr(trace, "POD_ROWS", 32)
    trace.reset()
    first = trace.pod_slot("ns/first")
    t_begin = time.perf_counter()
    for i in range(200):
        with trace.span("t.lap"):
            pass
        trace.stamp(trace.pod_slot(f"ns/p{i}"), trace.ENQUEUED)
    with trace.span("t.recent") as recent:
        pass
    assert trace.dropped_spans() >= 200 - 64
    assert trace.dropped_pods() >= 200 - 32
    # an interval the lap reached into: no number from torn rows
    assert trace.snapshot(t_begin, float("inf")) is None
    assert trace.snapshot() is None
    # an interval wholly inside what the ring still holds reads as ever
    snap = trace.snapshot(recent.t0, float("inf"))
    assert [s["name"] for s in spans(snap)] == ["t.recent"] and snap["dropped_spans"] > 0
    # a stamp into a row that now belongs to another pod is left out
    trace.stamp(first, trace.POPPED, 123.0)
    held = np.frombuffer(trace._pmv).reshape(trace.POD_ROWS, -1)
    assert 123.0 not in held[:, trace.POPPED]


def test_stamps_and_spans_allocate_nothing_that_stays(fresh):
    slot = trace.pod_slot("ns/p")

    def warm():                               # names interned, free lists primed
        for _ in range(50):
            with trace.span("t.hot"):
                trace.stamp(slot, trace.POPPED)

    def measure(spans_taken):
        gen0 = gc.get_count()[0]
        before = tracemalloc.get_traced_memory()[0]
        for _ in range(spans_taken):
            with trace.span("t.hot", 1):
                for _ in range(10):
                    trace.stamp(slot, trace.SOLVED)
        return gc.get_count()[0] - gen0, tracemalloc.get_traced_memory()[0] - before

    warm()
    gc.collect()
    gc.disable()
    tracemalloc.start()
    try:
        warm()
        # 2,000 spans and 20,000 stamps leave what taking the two readings
        # alone leaves: nothing new for the collector to walk, net zero
        # bytes, every handle freed.  Both counts are the process's, and a
        # thread an earlier test left behind can add to them: the cleanest
        # of a few pairs of readings.
        pairs = [(measure(0), measure(2_000)) for _ in range(5)]
        assert any(idle == hot for idle, hot in pairs), pairs
    finally:
        tracemalloc.stop()
        gc.enable()


def test_the_module_creates_one_lock_and_a_pods_path_takes_none(monkeypatch):
    made, taken = [], []
    real_lock = threading.Lock

    class Counting:
        def __init__(self):
            self._l = real_lock()
            made.append(self)

        def acquire(self, *a, **kw):
            taken.append(self)
            return self._l.acquire(*a, **kw)

        def release(self):
            self._l.release()

        def __enter__(self):
            self.acquire()
            return self

        def __exit__(self, *exc):
            self.release()

    # a reload starts the module afresh: no CPU ledger may run across it
    owners = set(trace._ledger_owners)
    for o in owners:
        trace.stop_cpu_ledger(o)
    monkeypatch.setattr(threading, "Lock", Counting)
    monkeypatch.setattr(threading, "RLock", Counting)
    try:
        importlib.reload(trace)
        assert len(made) == 1                 # the once-only log guard, and no other
        slot = trace.pod_slot("ns/p")
        with trace.span("t.cycle"):
            for stage in (trace.ENQUEUED, trace.POPPED, trace.SOLVED, trace.COMMIT_BEGIN,
                          trace.COMMITTED, trace.FAILED):
                trace.stamp(slot, stage)
        with trace.Trace("fast", threshold=60.0) as tr:
            tr.step("a")
        trace.snapshot()
        assert len(made) == 1 and taken == []
    finally:
        monkeypatch.undo()
        importlib.reload(trace)
        for o in owners:
            trace.start_cpu_ledger(o)
    assert sum(1 for cb in gc.callbacks
               if getattr(cb, "__module__", "") == trace.__name__) == 1


def test_a_collection_is_a_span_with_its_generation(fresh):
    t0 = time.perf_counter()
    gc.collect(1)
    rows = spans(trace.snapshot(t0), "gc")
    assert rows and rows[-1]["a0"] == 1.0 and rows[-1]["end"] >= rows[-1]["start"]


def test_a_trace_starts_where_its_pop_ended(fresh):
    with trace.span("sched.pop_wait", cycle=0, parent=0) as wait:
        pass
    tr = trace.Trace("schedule_batch", threshold=60.0, span="sched.cycle",
                     start=wait.t1, pods=7)
    with trace.span("sched.encode") as enc:     # inherits the cycle from the thread
        pass
    tr.close(a0=2, a1=1)
    snap = trace.snapshot(wait.t0)
    (root,) = spans(snap, "sched.cycle")
    assert root["id"] == tr.id == root["cycle"] and root["n"] == 7
    assert root["start"] == wait.t1 == tr.start and root["end"] >= enc.t1
    assert (root["a0"], root["a1"]) == (2.0, 1.0)
    (w,) = spans(snap, "sched.pop_wait")
    assert w["cycle"] == 0 and w["parent"] == 0 and w["end"] == root["start"]
    assert spans(snap, "sched.encode")[0]["parent"] == tr.id
    assert tr.steps == [("sched.encode", enc.t1 - enc.t0)]
    # frozen once closed, and counted from the Trace's own start, not the pop's end
    assert root["end"] - enc.t0 <= tr.total == tr.total < root["end"] - wait.t1


def test_short_intervals_are_tallied_into_one_row_a_thread_and_tenth_of_a_second(fresh):
    t0 = time.perf_counter()
    for _ in range(50):
        a = trace.now()
        trace.tally("t.write", a, trace.now())
    seen = []

    def other():
        a = trace.now()
        trace.tally("t.write", a, a + 0.25)
        seen.append(threading.get_ident())

    th = threading.Thread(target=other)
    th.start()
    th.join(5.0)
    time.sleep(trace.TALLY_S + 0.01)
    a = trace.now()
    trace.tally("t.write", a, a + 0.5)           # past the tenth: a row of its own
    rows = spans(trace.snapshot(t0), "t.write")
    mine = [r for r in rows if r["thread"] == threading.get_ident()]
    (theirs,) = [r for r in rows if r["thread"] == seen[0]]
    assert [r["n"] for r in mine] == [50, 1] and theirs["n"] == 1
    first, late = mine
    assert 0.0 < first["a0"] <= first["end"] - first["start"] <= trace.TALLY_S
    assert (late["a0"], theirs["a0"]) == (0.5, 0.25)
    for r in rows:      # wall time only, no cycle, marked as a sum
        assert r["parent"] == trace.TALLIED and r["cycle"] == 0
        assert r["cpu0"] is None and r["cpu1"] is None


def test_the_recorder_and_the_store_load_neither_numpy_nor_jax():
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from kubernetes_tpu.api import store\n"
        "from kubernetes_tpu.utils import trace\n"
        "s = store.Store(); slot = trace.pod_slot('ns/p'); trace.stamp(slot, trace.ENQUEUED)\n"
        "with trace.span('t.a'): pass\n"
        "assert not {'numpy', 'jax'} & set(sys.modules), sorted(sys.modules)\n"
        "assert 't.a' in [r[1] for r in trace.snapshot()['spans']] and 'numpy' in sys.modules\n"
    )
    done = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          timeout=120)
    assert done.returncode == 0, done.stderr


def test_a_cycle_that_fails_in_dispatch_leaves_the_next_cycle_a_trace_of_its_own(fresh):
    from kubernetes_tpu.api import store as st
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.testing.wrappers import GI, MI, make_node, make_pod

    store = st.Store()
    sched = Scheduler(store, batch_size=8)
    try:
        sched.cache.add_node(
            make_node("n0").capacity(cpu_milli=8000, mem=16 * GI, pods=110).obj())
        real = sched._solve_group_async
        calls = []

        def faulty(*a, **kw):
            calls.append(1)
            if len(calls) == 1:
                raise RuntimeError("planted")
            return real(*a, **kw)

        sched._solve_group_async = faulty
        t0 = time.perf_counter()
        for name in ("a", "b"):
            p = make_pod(name).req(cpu_milli=100, mem=64 * MI).obj()
            store.create(p)
            sched.queue.add(p)
            if name == "a":
                with pytest.raises(RuntimeError, match="planted"):
                    sched.schedule_batch(timeout=0.5)
        stats = sched.schedule_batch(timeout=2.0)   # b, and a once its backoff is over
        assert stats["scheduled"] >= 1 and sched.flush_binds(timeout=30.0)
        snap = trace.snapshot(t0)
        dead, live = spans(snap, "sched.cycle")
        assert dead["id"] != live["id"]
        for root in (dead, live):                   # both closed, both forward in time
            assert root["end"] is not None and root["end"] >= root["start"]
            assert root["cpu1"] is not None
        rows = {r[0]: dict(zip(POD, r)) for r in snap["pods"]}
        assert rows["default/a"]["fail_code"] == trace.FAIL_SALVAGED
        assert rows["default/b"]["cycle"] == live["id"]
        waits = {w["end"] for w in spans(snap, "sched.pop_wait")}
        assert {dead["start"], live["start"]} <= waits
        hist = sched.metrics.schedule_batch_duration
        assert hist.n == 1 and hist.total == pytest.approx(live["end"] - live["start"], abs=0.05)
    finally:
        sched.stop()


def test_slow_cycle_prints_once_with_the_other_threads_and_the_collection(fresh, caplog):
    go, done = threading.Event(), threading.Event()

    def other():
        a = trace.now()
        trace.tally("t.write", a, a + 0.004)
        trace.tally("t.write", a, a + 0.004)
        with trace.span("t.elsewhere", 5):
            go.set()
            done.wait(5.0)

    th = threading.Thread(target=other, name="busy-worker")
    with caplog.at_level(logging.WARNING, logger="kubernetes_tpu.trace"):
        tr = trace.Trace("schedule_batch", threshold=0.05, pods=3)
        th.start()
        assert go.wait(5.0)
        with trace.span("sched.encode"):
            gc.collect()                         # a gen-2 collection inside the cycle
            time.sleep(0.06)
        tr.close()
        tr.log_if_long()                         # the other thread's span is still open
        tr.log_if_long()                         # however often it is finalized
        done.set()
        th.join(5.0)
        assert not th.is_alive()
    assert len(caplog.records) == 1
    msg = caplog.records[0].getMessage()
    assert "trace schedule_batch (pods=3) took" in msg and "sched.encode: " in msg
    # a span still open and a tally carry no CPU reading: wall time alone
    assert "meanwhile busy-worker: t.elsewhere x1 wall " in msg
    assert "t.write x2 wall 0.008s" in msg and "t.write x2 wall 0.008s cpu" not in msg
    assert "sched.encode" not in msg.split("meanwhile")[1]      # its own thread's are steps
    assert "gc gen2 x1" in msg
    (entry,) = trace.drain_overruns()
    assert entry["name"] == "schedule_batch" and entry["fields"] == {"pods": 3}
    assert [w for w, _ in entry["steps"]] == ["sched.encode"]
    assert entry["total_s"] >= entry["threshold_s"] == 0.05
    assert trace.drain_overruns() == []


def test_last_timings_are_a_view_of_the_solves_spans(fresh):
    from kubernetes_tpu.models.batch_scheduler import TPUBatchScheduler
    from kubernetes_tpu.testing.wrappers import GI, MI, make_node, make_pod

    tpu = TPUBatchScheduler()
    for i in range(4):
        tpu.add_node(make_node(f"n{i}").capacity(cpu_milli=8000, mem=16 * GI, pods=110).obj())
    pods = [make_pod(f"p{i}").req(cpu_milli=100, mem=64 * MI).obj() for i in range(3)]
    t0 = time.perf_counter()
    names = tpu.schedule_pending(pods, lock=threading.RLock())
    assert all(names)
    snap = trace.snapshot(t0)
    (enc,), (run,), (dec,) = (spans(snap, n) for n in
                              ("sched.encode", "sched.dispatch", "sched.decode_wait"))
    (wait,) = spans(snap, "sched.encode.lock_wait")
    lt = tpu.last_timings
    assert set(lt) == {"encode_s", "compile_s", "solve_s", "decode_wait_s", "decode_overlap_s"}
    assert lt["encode_s"] == enc["end"] - enc["start"]
    assert lt["compile_s"] == run["end"] - run["start"]
    assert lt["decode_wait_s"] == dec["end"] - dec["start"]
    assert lt["decode_overlap_s"] == dec["start"] - run["end"]
    assert lt["solve_s"] == lt["decode_overlap_s"] + lt["decode_wait_s"]
    assert wait["parent"] == enc["id"] and enc["start"] <= wait["start"] <= wait["end"] <= enc["end"]
    assert enc["n"] == 3 and trace.ROUTES[int(enc["a0"])] == "greedy"


def test_a_requeued_pod_keeps_enqueued_counts_attempts_and_says_how_it_failed(fresh):
    from kubernetes_tpu.api import store as st
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.testing.wrappers import GI, MI, make_node, make_pod

    from kubernetes_tpu.scheduler.config import SchedulerConfiguration

    store = st.Store()
    sched = Scheduler(store, batch_size=8, config=SchedulerConfiguration(
        pod_initial_backoff_seconds=0.02, pod_max_backoff_seconds=0.02))
    try:
        sched.cache.add_node(
            make_node("n0").capacity(cpu_milli=1000, mem=1 * GI, pods=110).obj())
        big = make_pod("big").req(cpu_milli=4000, mem=64 * MI).obj()
        small = make_pod("small").req(cpu_milli=100, mem=64 * MI).obj()
        t0 = time.perf_counter()
        for p in (big, small):
            store.create(p)
            sched.queue.add(p)
        sched.queue.add(big)                      # a second add is no second arrival
        assert sched.schedule_batch(timeout=0.5)["unschedulable"] == 1
        assert sched.flush_binds(timeout=30.0)
        rows = {r[0]: dict(zip(POD, r)) for r in trace.snapshot(t0)["pods"]}
        first, ok = rows["default/big"], rows["default/small"]
        assert first["attempts"] == 1 and first["fail_code"] == trace.FAIL_UNSCHEDULABLE
        assert first["enqueued"] <= first["popped"] <= first["solved"] <= first["failed"]
        assert first["commit_begin"] is None and first["committed"] is None
        assert (ok["enqueued"] <= ok["popped"] <= ok["solved"] <= ok["commit_begin"]
                <= ok["committed"]) and ok["failed"] is None
        time.sleep(0.05)                          # past its backoff
        sched.queue.move_all_to_active_or_backoff()
        assert sched.schedule_batch(timeout=2.0)["popped"] == 1
        again = {r[0]: dict(zip(POD, r)) for r in trace.snapshot(t0)["pods"]}["default/big"]
        assert again["id"] == first["id"] and again["enqueued"] == first["enqueued"]
        assert again["attempts"] == 2 and again["popped"] > first["popped"]
        assert again["cycle"] != first["cycle"] and again["failed"] > first["failed"]
        assert ok["route"] == first["route"] == trace.ROUTE_ID["greedy"]
    finally:
        sched.stop()


# -- pods that stay pending: the queue's wake row, its tier depths, the failure branch --


def test_a_wake_is_one_row_with_its_pods_split_by_backoff_and_active(fresh):
    """``move_for_event`` writes one ``sched.queue.wake`` row a call that finds
    pods parked: ``n`` moved, ``a0`` of them into backoff, ``a1`` straight to
    active; an event that finds nothing parked writes nothing."""
    from kubernetes_tpu.ops import assign
    from kubernetes_tpu.scheduler.queue import SchedulingQueue
    from kubernetes_tpu.testing.wrappers import make_pod

    clock = [100.0]
    q = SchedulingQueue(backoff_base=1.0, backoff_max=10.0, clock=lambda: clock[0])
    t0 = time.perf_counter()
    assert q.move_for_event("AssignedPodDelete") == 0      # nothing parked: no row
    for i in range(5):
        q.add(make_pod(f"p{i}").obj())
    infos = q.pop_batch(10, timeout=0.0)
    assert [q.add_unschedulable(i, reason=assign.REASON_RESOURCES) for i in infos[:3]] == [True] * 3
    clock[0] += 5.0                     # the first three are past their 1 s backoff
    assert [q.add_unschedulable(i, reason=assign.REASON_RESOURCES) for i in infos[3:]] == [True] * 2
    assert q.move_for_event("AssignedPodAdd") == 0         # parked, and this event wakes none of them
    assert q.move_for_event("AssignedPodDelete") == 5
    assert q.move_for_event("AssignedPodDelete") == 0      # the set is empty again: no row
    rows = spans(trace.snapshot(t0), "sched.queue.wake")
    assert [(r["n"], r["a0"], r["a1"]) for r in rows] == [(0, 0.0, 0.0), (5, 2.0, 3.0)]
    assert all(r["end"] >= r["start"] and r["cycle"] == 0 for r in rows)
    assert q.stats()["active"] == 3 and q.stats()["backoff"] == 2


def test_a_pod_that_missed_an_event_is_not_parked_and_says_so(fresh):
    from kubernetes_tpu.ops import assign
    from kubernetes_tpu.scheduler.queue import SchedulingQueue
    from kubernetes_tpu.testing.wrappers import make_pod

    q = SchedulingQueue()
    q.add(make_pod("p").obj())
    (info,) = q.pop_batch(10, timeout=0.0)
    q.move_for_event("AssignedPodDelete")       # lands while the pod is in its cycle
    assert q.add_unschedulable(info, reason=assign.REASON_RESOURCES) is False
    assert q.stats()["backoff"] == 1 and q.stats()["unschedulable"] == 0


def test_every_pop_says_how_deep_the_tiers_stand_from_counters_it_keeps(fresh):
    """``sched.queue.depth``: one row of no length a pop that took pods, ``n``
    active, ``a0`` backoff, ``a1`` unschedulable as the pop leaves them, the
    counters ``stats()`` reads too; a walk over the tier map agrees."""
    from kubernetes_tpu.ops import assign
    from kubernetes_tpu.scheduler.queue import SchedulingQueue
    from kubernetes_tpu.testing.wrappers import make_pod

    clock = [100.0]
    q = SchedulingQueue(clock=lambda: clock[0])
    t0 = time.perf_counter()
    pods = [make_pod(f"p{i}").obj() for i in range(12)]
    for p in pods:
        q.add(p)
    assert len(q.pop_batch(10, timeout=0.0, window=0.0)) == 10      # leaves 2 active
    assert q.pop_batch(0, timeout=0.0) == []                        # took nothing: no row
    assert len(q.pop_batch(4, timeout=0.0, window=0.0)) == 2
    depth = lambda: {k: v for k, v in q._tier.depth.items() if v}
    assert depth() == {"inflight": 12}
    inflight = [q._infos[f"default/p{i}"] for i in range(12)]
    for info in inflight[:5]:
        q.add_unschedulable(info, reason=assign.REASON_RESOURCES)
    for info in inflight[5:8]:
        q.requeue_backoff(info)
    for info in inflight[8:]:
        q.done(info.pod)
    q.delete(pods[0])                   # a parked pod deleted: its tier's count goes with it
    assert depth() == {"unsched": 4, "backoff": 3}
    s = q.stats()
    assert (s["active"], s["backoff"], s["unschedulable"], s["inflight"]) == (0, 3, 4, 0)
    import collections

    assert depth() == dict(collections.Counter(q._tier.values()))
    q.add(make_pod("late").obj())
    assert len(q.pop_batch(4, timeout=0.0, window=0.0)) == 1
    rows = spans(trace.snapshot(t0), "sched.queue.depth")
    assert [(r["n"], r["a0"], r["a1"]) for r in rows] == [
        (2, 0.0, 0.0), (0, 0.0, 0.0), (0, 3.0, 4.0)]
    assert all(r["start"] == r["end"] for r in rows)
    clock[0] += 30.0                    # every backoff is over: the next pop takes them
    assert len(q.pop_batch(10, timeout=0.0, window=0.0)) == 3
    assert depth() == {"unsched": 4, "inflight": 4}


def test_a_cycle_with_failing_pods_writes_one_fail_row_and_a_cycle_without_none(fresh):
    """``sched.fail``: ``n`` pods of the cycle that ended without a bind, ``a1``
    of them parked, ``a0`` the seconds their branches took, under the cycle."""
    from kubernetes_tpu.api import store as st
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.testing.wrappers import GI, MI, make_node, make_pod

    store = st.Store()
    sched = Scheduler(store, batch_size=16)
    try:
        sched.cache.add_node(
            make_node("n0").capacity(cpu_milli=4000, mem=32 * GI, pods=110).obj())
        t0 = time.perf_counter()
        for i in range(3):
            p = make_pod(f"big-{i}").req(cpu_milli=9000, mem=500 * MI).obj()
            store.create(p)
            sched.queue.add(p)
        for i in range(4):
            p = make_pod(f"small-{i}").req(cpu_milli=100, mem=500 * MI).obj()
            store.create(p)
            sched.queue.add(p)
        stats = sched.schedule_batch(timeout=0.5)
        assert (stats["scheduled"], stats["unschedulable"]) == (4, 3)
        assert sched.flush_binds(timeout=30.0)
        snap = trace.snapshot(t0)
        (row,) = spans(snap, "sched.fail")
        (cycle,) = spans(snap, "sched.cycle")
        assert (row["n"], row["a1"]) == (3, 3.0) and row["cycle"] == cycle["id"]
        assert 0.0 < row["a0"] <= row["end"] - row["start"] + 1e-9
        assert cycle["start"] <= row["start"] <= row["end"] <= cycle["end"]
        assert sched.queue.stats()["unschedulable"] == 3
        # a cycle in which every pod binds writes none
        for i in range(4, 8):
            p = make_pod(f"small-{i}").req(cpu_milli=100, mem=500 * MI).obj()
            store.create(p)
            sched.queue.add(p)
        assert sched.schedule_batch(timeout=0.5)["scheduled"] == 4
        assert sched.flush_binds(timeout=30.0)
        snap = trace.snapshot(t0)
        assert len(spans(snap, "sched.cycle")) == 2 and len(spans(snap, "sched.fail")) == 1
    finally:
        sched.stop()
