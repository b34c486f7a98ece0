"""The event recorder's TTL (client/events.py): what a sweep deletes and
keeps under an injected clock, and what one write costs however many
events the store holds — no `Store.list` and no copy of a stored Event
on the write path."""

import copy
import random

import pytest

from kubernetes_tpu.api import store as st
from kubernetes_tpu.api import types as api
from kubernetes_tpu.client.events import EventRecorder
from kubernetes_tpu.testing.wrappers import make_pod
from kubernetes_tpu.utils import trace

TTL = 100.0
SWEEP_EVERY = 256


class Clock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


def recorder(store, clock, **kw):
    return EventRecorder(store, ttl=TTL, clock=clock, **kw)


def pod(name, namespace="default"):
    return make_pod(name, namespace=namespace).obj()


def run_to_sweep(rec, filler=None):
    """Repeats of one filler event until the recorder has swept once
    more: a sweep runs in every 256th write, before the write."""
    filler = filler or pod("filler")
    sweeps = rec._writes // SWEEP_EVERY
    while rec._writes // SWEEP_EVERY == sweeps:
        rec.eventf(filler, "Normal", "Pulled", "image present")
        rec.flush()     # an async recorder whose broadcaster has stopped


def names(store):
    return sorted(e.meta.name for e in store.list("Event")[0])


def expire_rows(t0):
    rows = [dict(zip(trace.SPAN_FIELDS, r)) for r in trace.snapshot(t0)["spans"]]
    return [r for r in rows if r["name"] == "events.expire"]


def test_an_event_past_its_ttl_goes_at_the_next_sweep_and_one_inside_it_stays():
    store, clock = st.Store(), Clock()
    rec = recorder(store, clock)
    rec.eventf(pod("old"), "Normal", "Scheduled", "assigned to n0")
    clock.t = 50.0
    rec.eventf(pod("young"), "Normal", "Scheduled", "assigned to n1")
    clock.t = 120.0
    assert names(store) == ["old.scheduled", "young.scheduled"]     # no sweep yet
    t0 = trace.now()
    run_to_sweep(rec)
    assert names(store) == ["filler.pulled", "young.scheduled"]
    (row,) = expire_rows(t0)
    assert (row["n"], row["a0"]) == (1, 1.0)        # examined what expired, no more
    clock.t = 151.0
    run_to_sweep(rec)
    assert names(store) == ["filler.pulled"]


def test_a_repeat_that_bumps_the_count_moves_the_deadline():
    store, clock = st.Store(), Clock()
    rec = recorder(store, clock)
    p = pod("p")
    rec.eventf(p, "Warning", "FailedScheduling", "0/3 nodes available")
    clock.t = 60.0
    rec.eventf(p, "Warning", "FailedScheduling", "0/3 nodes available")
    clock.t = 120.0
    t0 = trace.now()
    run_to_sweep(rec)
    ev = store.get("Event", "p.failedscheduling")
    assert (ev.count, ev.first_timestamp, ev.last_timestamp) == (2, 0.0, 60.0)
    (row,) = expire_rows(t0)
    assert (row["n"], row["a0"]) == (1, 0.0)        # the first write's entry, stale
    clock.t = 161.0
    run_to_sweep(rec)
    assert names(store) == ["filler.pulled"]


def test_an_event_somebody_else_deleted_is_skipped_without_error():
    store, clock = st.Store(), Clock()
    rec = recorder(store, clock)
    rec.eventf(pod("gone"), "Normal", "Scheduled", "assigned to n0")
    rec.eventf(pod("stays"), "Normal", "Scheduled", "assigned to n0")
    store.delete("Event", "gone.scheduled")
    clock.t = 120.0
    t0 = trace.now()
    run_to_sweep(rec)
    assert names(store) == ["filler.pulled"]
    (row,) = expire_rows(t0)
    assert (row["n"], row["a0"]) == (2, 1.0)
    # the write that ran the sweep was made all the same
    assert store.get("Event", "filler.pulled").count == SWEEP_EVERY - 2


def test_events_recovered_from_a_journal_are_expired_by_a_new_recorder(tmp_path):
    path = str(tmp_path / "j.jsonl")
    first, clock = st.Store(journal_path=path, shards=2), Clock()
    rec = recorder(first, clock)
    rec.eventf(pod("a", "ns-0"), "Normal", "Scheduled", "assigned to n0")
    clock.t = 90.0
    rec.eventf(pod("b", "ns-1"), "Normal", "Scheduled", "assigned to n1")
    first.close()
    recovered = st.Store(journal_path=path)
    assert names(recovered) == ["a.scheduled", "b.scheduled"]
    clock.t = 120.0
    rec2 = recorder(recovered, clock)
    run_to_sweep(rec2)
    assert names(recovered) == ["b.scheduled", "filler.pulled"]
    clock.t = 191.0
    run_to_sweep(rec2)
    assert names(recovered) == ["filler.pulled"]
    recovered.close()


def held(store):
    return sorted((e.meta.namespace, e.meta.name, e.count, e.last_timestamp)
                  for e in store.list("Event")[0])


def test_a_recorder_that_takes_over_a_store_expires_what_the_other_wrote():
    """Two recorders on one store, as replicated schedulers have: the
    standby's was built before the leader wrote and writes nothing while
    it stands by.  After the take-over's resync its sweeps leave what a
    sweep that lists the kind leaves."""

    def drive(cls):
        store, clock = st.Store(shards=2), Clock()
        standby = cls(store, ttl=TTL, clock=clock)
        leader = recorder(store, clock)
        for i in range(40):
            clock.t = float(i)
            leader.eventf(pod(f"a{i}", f"ns-{i % 3}"), "Normal", "Scheduled", "assigned to n0")
        clock.t = 60.0
        leader.eventf(pod("a0", "ns-0"), "Normal", "Scheduled", "assigned to n0")   # bumped
        assert not standby._expiry
        standby.resync()        # what Scheduler._reconcile_leadership does on acquisition
        clock.t = 120.5         # a1..a20 are past the TTL; a0 (60.0) and a21.. are not
        run_to_sweep(standby)
        first = held(store)
        clock.t = 170.0
        run_to_sweep(standby)
        return first, held(store)

    first, last = drive(EventRecorder)
    assert sorted(name for _, name, _, _ in first) == sorted(
        ["a0.scheduled", "filler.pulled"] + [f"a{i}.scheduled" for i in range(21, 40)])
    assert [name for _, name, _, _ in last] == ["filler.pulled"]
    assert (first, last) == drive(ListingRecorder)


def test_a_resync_enters_each_stored_event_once_and_keeps_the_entries_it_had():
    store, clock = st.Store(), Clock()
    rec = recorder(store, clock)
    for i in range(5):
        clock.t = float(i)
        rec.eventf(pod(f"p{i}"), "Normal", "Scheduled", "assigned to n0")
    clock.t = 9.0
    rec.eventf(pod("p0"), "Normal", "Scheduled", "assigned to n0")      # p0's first entry is stale
    other = recorder(store, clock)
    other.eventf(pod("q"), "Normal", "Scheduled", "assigned to n0")
    store.delete("Event", "p4.scheduled")
    before = sorted(rec._expiry)
    rec.resync()
    rec.resync()
    heap = rec._expiry
    assert all(heap[(i - 1) // 2] <= heap[i] for i in range(1, len(heap)))
    # what it had (the stale entry and the deleted event's too), and q's, each once
    assert sorted(heap) == sorted(before + [(9.0, "default", "q.scheduled")])


def test_the_scheduler_resyncs_its_recorder_when_it_takes_the_lead():
    """`Scheduler._reconcile_leadership` runs on the first leading pass
    after every acquisition: the Events a former leader wrote since this
    scheduler was built are in its recorder's expiry order after it."""
    from kubernetes_tpu.scheduler import Scheduler

    store = st.Store()
    sched = Scheduler(store)
    try:
        former = recorder(store, Clock(5.0))
        former.eventf(pod("p"), "Normal", "Scheduled", "assigned to n0")
        assert not sched.events._expiry
        sched._reconcile_leadership()
        assert sched.events._expiry == [(5.0, "default", "p.scheduled")]
    finally:
        sched.stop()


def test_a_list_that_fails_raises_out_of_nothing_and_the_next_sweep_resyncs(tmp_path):
    """The constructor's pass goes through `Store.list` and its fault
    point: an error there is logged, the recorder is built with an empty
    order, and the first sweep makes the pass again."""
    from kubernetes_tpu.testing import faults

    store, clock = st.Store(), Clock()
    recorder(store, clock).eventf(pod("old"), "Normal", "Scheduled", "assigned to n0")
    clock.t = 120.0
    with faults.armed(faults.FaultRegistry().fail("store.list", n=2)):
        rec = recorder(store, clock)            # the first failure
        assert rec._expiry == [] and rec._resync_due
        rec.resync()                            # the second: still nothing raised
        assert rec._expiry == [] and rec._resync_due
        run_to_sweep(rec)                       # the sweep's retry finds the list working
    assert not rec._resync_due
    assert names(store) == ["filler.pulled"]


def test_a_list_selector_sees_the_stored_objects_and_what_it_refuses_is_not_copied(monkeypatch):
    """The contract `resync` rests on (`Store.list`'s docstring): the
    selector runs on the stored references before any copy."""
    store = st.Store(shards=4)
    for i in range(50):
        store.create(api.Event(meta=api.ObjectMeta(name=f"e{i}", namespace=f"ns-{i % 3}"),
                               last_timestamp=float(i)))
    stored = {id(o) for shard in store._shards for o in shard._objects.get("Event", {}).values()}
    copies = []
    real = copy.deepcopy
    monkeypatch.setattr(copy, "deepcopy", lambda x, *a, **kw: copies.append(x) or real(x, *a, **kw))
    seen = []
    items, _ = store.list("Event", selector=lambda ev: seen.append(id(ev)) or False)
    assert items == [] and copies == []
    assert len(seen) == 50 and set(seen) == stored
    items, _ = store.list("Event", selector=lambda ev: ev.last_timestamp < 3.0)
    assert len(items) == 3 and [type(c) for c in copies] == [api.Event] * 3
    assert not {id(e) for e in items} & stored


def test_a_normal_and_a_warning_of_one_reason_still_do_not_merge():
    store, clock = st.Store(), Clock()
    rec = recorder(store, clock, async_mode=True, flush_interval=3600.0)
    p = pod("p")
    for event_type in ("Normal", "Normal", "Warning"):
        rec.eventf(p, event_type, "Resized", "volume resized")
        clock.t += 1.0
    rec.stop()
    (ev,) = store.list("Event")[0]
    # the Warning replaced the Normal pair; it did not join their count
    assert (ev.type, ev.count, ev.first_timestamp) == ("Warning", 1, 2.0)
    clock.t = 150.0
    run_to_sweep(rec)       # two entries of one name: the stored event decides, once
    assert names(store) == ["filler.pulled"]


def test_a_flush_is_one_span_with_what_it_wrote_and_what_was_dropped(monkeypatch):
    from kubernetes_tpu.client import events

    monkeypatch.setattr(events, "_QUEUE_CAP", 4)
    store = st.Store()
    rec = EventRecorder(store, async_mode=True, flush_interval=3600.0)
    t0 = trace.now()
    for i in (0, 1, 1, 2, 3, 4):        # the cap holds four; two are dropped
        rec.eventf(pod(f"p{i}"), "Normal", "Scheduled", "assigned to n0")
    rec.stop()
    rows = [dict(zip(trace.SPAN_FIELDS, r)) for r in trace.snapshot(t0)["spans"]]
    (span,) = [r for r in rows if r["name"] == "events.flush"]
    assert (span["n"], span["a0"]) == (3, 2.0)      # p1's repeat coalesced in the queue
    assert span["cpu0"] is not None and span["cpu1"] >= span["cpu0"]
    assert store.get("Event", "p1.scheduled").count == 2
    rec.flush()                          # an empty flush writes no span
    assert len([r for r in trace.snapshot(t0)["spans"] if r[1] == "events.flush"]) == 1


class ListingRecorder(EventRecorder):
    """The sweep as it was: list the kind, delete what is past the TTL.
    The reference the recorder's own expiry order is held to."""

    def _expire(self, now):
        events, _ = self.store.list("Event")
        for ev in events:
            if now - ev.last_timestamp > self.ttl:
                try:
                    self.store.delete("Event", ev.meta.name, ev.meta.namespace)
                except KeyError:
                    pass


@pytest.mark.parametrize("seed", [3, 30, 300])
def test_the_store_holds_what_a_listing_sweep_would_leave(seed):
    """A seeded stream of events, repeats, type flips, foreign deletes
    and clock jumps: after every sweep the two stores hold the same
    events, field for field."""
    pods = [pod(f"p{i}", f"ns-{i % 5}") for i in range(400)]

    def drive(cls):
        rng = random.Random(seed)
        store, clock = st.Store(shards=4), Clock()
        rec = cls(store, ttl=TTL, clock=clock)
        seen = []
        for step in range(6 * SWEEP_EVERY):
            clock.t += rng.choice((0.0, 0.01, 0.5, 3.0))
            p = rng.choice(pods)
            reason = rng.choice(("Scheduled", "FailedScheduling", "Preempted"))
            rec.eventf(p, rng.choice(("Normal", "Normal", "Warning")), reason,
                       rng.choice(("m0", "m0", "m1")))
            if rng.random() < 0.01:
                try:
                    store.delete("Event", f"{p.meta.name}.{reason.lower()}", p.meta.namespace)
                except KeyError:
                    pass
            if rec._writes % SWEEP_EVERY == 0:
                seen.append(sorted(
                    (e.meta.namespace, e.meta.name, e.type, e.reason, e.message, e.count,
                     e.first_timestamp, e.last_timestamp, e.involved_object,
                     e.source_component)
                    for e in store.list("Event")[0]))
        return seen, store.list("Event")[1]

    mine, mine_rv = drive(EventRecorder)
    theirs, their_rv = drive(ListingRecorder)
    assert mine == theirs and mine_rv == their_rv
    # the sweeps had work: 256 writes span over two TTLs of this clock
    assert len(mine) == 6 and all(len(held) < SWEEP_EVERY for held in mine)


@pytest.mark.parametrize("async_mode", [False, True], ids=["sync", "async"])
def test_writes_into_a_full_store_list_nothing_and_copy_no_stored_event(monkeypatch, async_mode):
    """The count that is the change: 2,048 writes into a store that
    already holds 10,000 events (eight sweeps) make no `Store.list`
    call and deep-copy none of the stored events; each write copies
    its own event and nothing else."""
    store = st.Store(shards=8)
    for i in range(10_000):
        store.create(api.Event(
            meta=api.ObjectMeta(name=f"init-{i}.scheduled", namespace=f"ns-{i % 16}"),
            reason="Scheduled", message="assigned", last_timestamp=1.0))
    stored = set()
    store.list("Event", selector=lambda ev: stored.add(id(ev)) or False)
    assert len(stored) == 10_000
    rec = EventRecorder(store, clock=Clock(2.0), async_mode=async_mode, flush_interval=0.01)

    counts = {"list": 0, "stored": 0, "events": 0}
    real_list, real_deepcopy = st.Store.list, copy.deepcopy

    def counting_list(self, *a, **kw):
        counts["list"] += 1
        return real_list(self, *a, **kw)

    def counting_deepcopy(x, *a, **kw):
        if isinstance(x, api.Event):
            counts["events"] += 1
            counts["stored"] += id(x) in stored
        return real_deepcopy(x, *a, **kw)

    monkeypatch.setattr(st.Store, "list", counting_list)
    monkeypatch.setattr(copy, "deepcopy", counting_deepcopy)
    t0 = trace.now()
    for i in range(2048):
        rec.eventf(pod(f"p{i}", f"ns-{i % 16}"), "Normal", "Scheduled", "assigned to n0")
    rec.stop()
    if rec._thread is not None:
        rec._thread.join(60.0)      # stop() waits two seconds, a loaded machine may need more
    monkeypatch.undo()

    assert rec._writes == 2048
    assert counts["list"] == 0 and counts["stored"] == 0
    # a create copies the event in and hands a copy back; the `get` before it found nothing
    assert counts["events"] == 2 * 2048
    sweeps = expire_rows(t0)
    assert len(sweeps) == 2048 // SWEEP_EVERY
    assert all((r["n"], r["a0"]) == (0, 0.0) for r in sweeps)      # nothing is an hour old
    assert len(store.list("Event")[0]) == 10_000 + 2048


def test_threads_that_write_and_sweep_at_once_lose_no_entry():
    """Sync callers share the expiry heap: twelve threads write while the
    clock runs past the TTL under them, so sweeps pop while others push.
    Every event left in the store still has its entry, the heap is a heap,
    and a last sweep leaves nothing that is past the TTL."""
    import sys
    import threading

    store, clock = st.Store(shards=4), Clock()
    rec = recorder(store, clock)
    tick = threading.Lock()
    t0 = trace.now()

    def writer(k):
        for i in range(200):
            with tick:
                clock.t += 0.25         # 256 writes span 64 s of a 100 s TTL
            rec.eventf(pod(f"t{k}-{i % 50}", f"ns-{k % 4}"), "Normal", "Scheduled", "assigned")

    threads = [threading.Thread(target=writer, args=(k,)) for k in range(12)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    heap = rec._expiry
    assert all(heap[(i - 1) // 2] <= heap[i] for i in range(1, len(heap)))
    entries = set(heap)
    held = store.list("Event")[0]
    assert held and all((e.last_timestamp, e.meta.namespace, e.meta.name) in entries
                        for e in held)
    run_to_sweep(rec)
    assert all(clock.t - e.last_timestamp <= TTL for e in store.list("Event")[0])
    assert sum(r["a0"] for r in expire_rows(t0)) > 100  # the sweeps had work meanwhile
