"""Degraded-mode hardening of the solve→assume→bind pipeline.

Fast (tier-1) regression coverage for the fault-injection registry
(testing/faults.py) and the hardening it drives: the device-solve
circuit breaker + host fallback, binder supervision (watchdog restart,
poison-wave splitting), the CRC'd crash-safe journal, duplicate-assume
containment, cycle salvage, and the watch overflow → Expired → relist →
resume contract.  The randomized seeded schedules live in
tests/test_chaos.py (mark: chaos).
"""

import threading
import time

import pytest

from kubernetes_tpu.api import store as st
from kubernetes_tpu.models.batch_scheduler import (
    SolveCircuitBreaker,
    TPUBatchScheduler,
)
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.scheduler.config import SchedulerConfiguration
from kubernetes_tpu.scheduler.queue import QueuedPodInfo, pod_key
from kubernetes_tpu.testing import faults
from kubernetes_tpu.testing.wrappers import GI, make_node, make_pod


def _mk_scheduler(store, **kw):
    s = Scheduler(store, **kw)
    s.informers.informer("Node").start()
    s.informers.informer("Pod").start()
    assert s.informers.wait_for_sync(10)
    return s


@pytest.fixture(autouse=True)
def _disarmed():
    yield
    faults.disarm()


# -- the registry itself ----------------------------------------------------


def test_disarmed_fire_is_noop():
    assert faults.fire("batch.solve") is None  # no registry: no effect


def test_unknown_point_rejected():
    reg = faults.FaultRegistry()
    with pytest.raises(ValueError):
        reg.fail("no.such.point")


def test_fail_n_counts_down_then_stops():
    reg = faults.FaultRegistry()
    reg.fail("batch.solve", n=2)
    with faults.armed(reg):
        for _ in range(2):
            with pytest.raises(faults.FaultInjected):
                faults.fire("batch.solve")
        assert faults.fire("batch.solve") is None  # schedule drained
    assert reg.fired["batch.solve"] == 2
    assert reg.pending()["batch.solve"] == 0


def test_probabilistic_schedule_is_seed_deterministic():
    def run(seed):
        reg = faults.FaultRegistry(seed=seed)
        reg.fail("watch.offer", n=-1, probability=0.5)
        hits = []
        for _ in range(32):
            try:
                reg.fire("watch.offer")
                hits.append(0)
            except faults.FaultInjected:
                hits.append(1)
        return hits

    assert run(7) == run(7)
    assert run(7) != run(8)  # different seed, different plan


def test_armed_context_disarms_on_exit():
    reg = faults.FaultRegistry()
    reg.fail("batch.solve", n=1)
    with faults.armed(reg):
        pass
    assert faults.fire("batch.solve") is None


def test_delay_composes_with_failure():
    reg = faults.FaultRegistry()
    reg.delay("batch.solve", seconds=0.02, n=1)
    reg.fail("batch.solve", n=1)
    t0 = time.monotonic()
    with faults.armed(reg), pytest.raises(faults.FaultInjected):
        faults.fire("batch.solve")
    assert time.monotonic() - t0 >= 0.02


# -- crash-safe journal (CRC path) ------------------------------------------


def test_journal_crc_detects_value_corruption(tmp_path):
    """A corrupted record that still parses as JSON (a flipped value,
    stale CRC) must be caught by the CRC check, skipped, and counted."""
    path = str(tmp_path / "j.jsonl")
    s1 = st.Store(journal_path=path, shards=1)
    s1.create(make_pod("a").req(cpu_milli=100).obj())
    s1.create(make_pod("b").req(cpu_milli=100).obj())
    s1.create(make_pod("c").req(cpu_milli=100).obj())
    lines = open(path, "rb").read().splitlines(keepends=True)
    # flip the payload of the middle record without breaking JSON
    lines[1] = lines[1].replace(b'"name": "b"', b'"name": "x"')
    with open(path, "wb") as f:
        f.writelines(lines)
    s2 = st.Store(journal_path=path, shards=1)
    names = {p.meta.name for p in s2.list("Pod")[0]}
    assert names == {"a", "c"}, "CRC mismatch record was not skipped"
    assert s2.journal_recovered_records == 1
    assert s2.journal_tail_truncations == 0


def test_journal_torn_tail_truncates_and_counts(tmp_path):
    path = str(tmp_path / "j.jsonl")
    s1 = st.Store(journal_path=path, shards=1)
    s1.create(make_pod("a").obj())
    s1.create(make_pod("b").obj())
    with open(path, "a") as f:
        f.write('{"op": "ADDED", "rv": 99, "kind": "Pod", "ke')  # torn
    s2 = st.Store(journal_path=path, shards=1)
    assert {p.meta.name for p in s2.list("Pod")[0]} == {"a", "b"}
    assert s2.journal_recovered_records == 1
    assert s2.journal_tail_truncations == 1


def test_injected_torn_write_is_contained_and_recovered(tmp_path):
    """A torn append (crash mid-write) degrades durability for that
    record only: the store keeps serving, and replay truncates the torn
    tail back to the last good record."""
    path = str(tmp_path / "j.jsonl")
    store = st.Store(journal_path=path, shards=1)
    store.create(make_pod("durable").obj())
    reg = faults.FaultRegistry().torn_write("store.journal.append", n=1)
    with faults.armed(reg):
        store.create(make_pod("torn").obj())  # append tears; API write OK
    assert store.journal_write_errors == 1
    assert store.get("Pod", "torn") is not None  # in-memory commit held
    store.create(make_pod("after").obj())  # appends continue
    s2 = st.Store(journal_path=path, shards=1)
    names = {p.meta.name for p in s2.list("Pod")[0]}
    # the torn record was never durable; records around it replay
    assert "durable" in names
    assert "torn" not in names
    assert s2.journal_recovered_records >= 1


def test_injected_fsync_failure_contained(tmp_path):
    path = str(tmp_path / "j.jsonl")
    store = st.Store(journal_path=path, shards=1)
    reg = faults.FaultRegistry().fail("store.journal.fsync", n=1)
    with faults.armed(reg):
        store.create(make_pod("a").obj())
    assert store.journal_write_errors == 1
    store.create(make_pod("b").obj())
    assert {p.meta.name for p in st.Store(journal_path=path, shards=1).list("Pod")[0]} >= {"b"}


def test_compaction_output_replays_with_crc(tmp_path):
    path = str(tmp_path / "j.jsonl")
    s = st.Store(journal_path=path, shards=1)
    s.create(make_pod("keep").obj())
    for _ in range(1500):  # push past the compaction threshold
        fresh = s.get("Pod", "keep")
        s.update(fresh)
    s2 = st.Store(journal_path=path, shards=1)
    assert s2.get("Pod", "keep") is not None
    assert s2.journal_recovered_records == 0  # compacted file is clean


# -- circuit breaker + host fallback ----------------------------------------


def _cluster(store, nodes=2, cpu=4000):
    for i in range(nodes):
        store.create(
            make_node(f"n{i}").capacity(cpu_milli=cpu, mem=8 * GI, pods=50).obj()
        )


def test_breaker_trips_after_retry_and_falls_back_to_host():
    store = st.Store()
    _cluster(store)
    for i in range(4):
        store.create(make_pod(f"p{i}").req(cpu_milli=100).obj())
    sched = _mk_scheduler(store)
    reg = faults.FaultRegistry().fail("batch.solve", n=-1)  # device dead
    try:
        with faults.armed(reg):
            stats = sched.schedule_batch(timeout=2)
            assert stats["scheduled"] == 4  # host fallback placed them
            assert sched.flush_binds(30)
        assert sched.tpu.breaker.state == SolveCircuitBreaker.OPEN
        assert sched.tpu.breaker.fallbacks >= 1
        assert reg.fired["batch.solve"] == 2  # attempt + ONE retry
        for i in range(4):
            assert store.get("Pod", f"p{i}").spec.node_name
        assert sched.metrics.solve_breaker_state.get() == 2.0
        assert sched.metrics.solve_fallback_total.get() >= 1.0
    finally:
        sched.stop()


def test_failure_log_labels_cannot_raise(monkeypatch):
    """The breaker's handlers format the device and the executable key
    as log arguments: a label that raised there would escape the handler
    and skip the retry and the host fallback it guards."""
    from kubernetes_tpu.models import batch_scheduler as bs

    def no_backend(*a, **kw):
        raise RuntimeError("backend gone")

    monkeypatch.setattr(bs.jax, "devices", no_backend)
    assert bs.device_label() == "unknown device"
    assert bs._executable_key(None, None) == "unknown executable"
    # finalize_pending accepts a HostSolve (no result, no meta)
    assert "host" in bs.HostSolve([None]).executable_key()


def test_tripped_breaker_keeps_scheduling_throughput():
    """With the breaker open, later batches go straight to the host path
    (no device attempt) and still schedule."""
    store = st.Store()
    _cluster(store)
    sched = _mk_scheduler(store)
    sched.tpu.breaker.record_failure()  # force open, long cooldown
    sched.tpu.breaker.cooldown = 3600.0
    try:
        store.create(make_pod("q0").req(cpu_milli=100).obj())
        stats = sched.schedule_batch(timeout=2)
        assert stats["scheduled"] == 1
        assert sched.flush_binds(30)
        assert store.get("Pod", "q0").spec.node_name
    finally:
        sched.stop()


def test_nonfinite_scores_trip_breaker_via_health_check():
    store = st.Store()
    _cluster(store)
    for i in range(2):
        store.create(make_pod(f"p{i}").req(cpu_milli=100).obj())
    sched = _mk_scheduler(store)
    reg = faults.FaultRegistry().corrupt("batch.solve", n=-1)
    try:
        with faults.armed(reg):
            stats = sched.schedule_batch(timeout=2)
            assert stats["scheduled"] == 2
            assert sched.flush_binds(30)
        assert sched.tpu.breaker.state == SolveCircuitBreaker.OPEN
        assert sched.tpu.breaker.fallbacks >= 1
    finally:
        sched.stop()


def test_breaker_half_open_probe_recovers():
    now = [0.0]
    br = SolveCircuitBreaker(cooldown=5.0, clock=lambda: now[0])
    assert br.allow_device()
    br.record_failure()
    assert br.state == br.OPEN
    assert not br.allow_device()  # inside the cooldown
    now[0] = 6.0
    assert br.allow_device()  # the half-open probe
    assert br.state == br.HALF_OPEN
    assert not br.allow_device()  # only ONE probe flows
    br.record_success()
    assert br.state == br.CLOSED
    # failure during the probe re-opens with a fresh cooldown
    br.record_failure()
    now[0] = 12.0
    assert br.allow_device()
    br.record_failure()
    assert br.state == br.OPEN and not br.allow_device()


def test_fallback_parity_with_device_solve():
    """Acceptance: on a healthy snapshot the host fallback must place
    identically to the device solve (the oracle-parity families)."""
    nodes = [
        make_node(f"n{i}")
        .capacity(cpu_milli=4000, mem=8 * GI, pods=20)
        .zone(f"z{i % 2}")
        .label("disk", "ssd" if i % 2 else "hdd")
        .obj()
        for i in range(6)
    ]
    def pods():
        out = []
        for i in range(12):
            p = make_pod(f"p{i}").req(cpu_milli=200 + 50 * (i % 3), mem=GI)
            if i % 4 == 0:
                p = p.label("app", "web").pod_anti_affinity({"app": "web"})
            if i % 3 == 0:
                p = p.node_selector(disk="ssd")
            out.append(p.obj())
        return out

    device = TPUBatchScheduler()
    for n in nodes:
        device.add_node(n)
    want = device.schedule_pending(pods())

    host = TPUBatchScheduler()
    for n in nodes:
        host.add_node(n)
    host.breaker.record_failure()
    host.breaker.cooldown = 3600.0  # pinned open: every batch host-solves
    got = host.schedule_pending(pods())
    assert host.breaker.fallbacks >= 1
    assert got == want, "fallback placements diverge from the device solve"


# -- binder supervision ------------------------------------------------------


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning"
)
def test_binder_watchdog_restarts_crashed_worker_and_recommits():
    store = st.Store()
    _cluster(store)
    for i in range(3):
        store.create(make_pod(f"p{i}").req(cpu_milli=100).obj())
    # whole-wave path pinned: binder supervision (watchdog restart,
    # poison split) belongs to the non-streamed wave worker — the
    # streamed path requeues a failed sub-wave instead by design
    sched = _mk_scheduler(
        store, config=SchedulerConfiguration(stream_subwaves=False)
    )
    reg = faults.FaultRegistry().crash("binder.commit_wave", n=1)
    try:
        with faults.armed(reg):
            stats = sched.schedule_batch(timeout=2)
            assert stats["scheduled"] == 3
            # the worker dies mid-commit; flush_binds' watchdog restarts
            # it and the preserved wave commits
            assert sched.flush_binds(30)
        assert sched.metrics.binder_restarts.total >= 1
        for i in range(3):
            assert store.get("Pod", f"p{i}").spec.node_name
    finally:
        sched.stop()


def test_poison_wave_splits_to_per_pod_commits():
    store = st.Store()
    _cluster(store)
    for i in range(3):
        store.create(make_pod(f"p{i}").req(cpu_milli=100).obj())
    sched = _mk_scheduler(  # whole-wave path: see watchdog test
        store, config=SchedulerConfiguration(stream_subwaves=False)
    )
    # the whole wave fails twice (attempt + retry) -> split; the per-pod
    # commits run with the schedule drained and succeed
    reg = faults.FaultRegistry().fail("binder.commit_wave", n=2)
    try:
        with faults.armed(reg):
            sched.schedule_batch(timeout=2)
            assert sched.flush_binds(30)
        assert sched.metrics.binder_poison_waves.total == 1
        for i in range(3):
            assert store.get("Pod", f"p{i}").spec.node_name
    finally:
        sched.stop()


def test_poison_pod_in_split_requeues_with_backoff():
    store = st.Store()
    _cluster(store)
    for i in range(3):
        store.create(make_pod(f"p{i}").req(cpu_milli=100).obj())
    sched = _mk_scheduler(  # whole-wave path: see watchdog test
        store, config=SchedulerConfiguration(stream_subwaves=False)
    )
    # wave fails twice, then the FIRST per-pod commit fails too: that one
    # pod requeues with backoff instead of riding the assume-TTL
    reg = faults.FaultRegistry().fail("binder.commit_wave", n=3)
    try:
        with faults.armed(reg):
            sched.schedule_batch(timeout=2)
            assert sched.flush_binds(30)
            bound = sum(
                1 for i in range(3)
                if store.get("Pod", f"p{i}").spec.node_name
            )
            assert bound == 2
            assert sched.queue.stats()["backoff"] == 1
            assert sched.cache.assumed_count() <= 2  # failed assume forgotten
            # the requeued pod retries and lands once faults drain
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and bound < 3:
                sched.schedule_batch(timeout=0.3)
                sched.flush_binds(10)
                bound = sum(
                    1 for i in range(3)
                    if store.get("Pod", f"p{i}").spec.node_name
                )
        assert bound == 3
    finally:
        sched.stop()


# -- duplicate-assume containment + cycle salvage ---------------------------


def test_duplicate_pod_in_one_batch_contained_per_pod():
    """The same pod popped twice across the accumulation window (delete +
    recreate racing a requeue) must not kill the cycle: the duplicate
    requeues with backoff, the first instance schedules."""
    store = st.Store()
    _cluster(store)
    store.create(make_pod("dup").req(cpu_milli=100).obj())
    sched = _mk_scheduler(store)
    try:
        pod = store.get("Pod", "dup")
        batch1 = sched.queue.pop_batch(1, timeout=2)
        assert len(batch1) == 1
        sched.queue.delete(pod)
        sched.queue.add(pod)
        batch2 = sched.queue.pop_batch(1, timeout=2)
        assert len(batch2) == 1
        # one batch containing the same pod twice
        cycle = sched._dispatch_batch(batch1 + batch2)
        stats = sched._finish_cycle(cycle)
        assert stats["scheduled"] == 1
        assert sched.metrics.schedule_attempts.get("error") == 1
        assert sched.flush_binds(30)
        assert store.get("Pod", "dup").spec.node_name
    finally:
        sched.stop()


def test_already_assumed_pod_contained_to_requeue():
    """cache.assume raising 'already assumed' must cost that pod one
    backoff, never the cycle (the _stage_group containment)."""
    store = st.Store()
    _cluster(store)
    store.create(make_pod("twice").req(cpu_milli=100).obj())
    store.create(make_pod("ok").req(cpu_milli=100).obj())
    sched = _mk_scheduler(store)
    try:
        sched.cache.assume(store.get("Pod", "twice"), "n0")
        stats = sched.schedule_batch(timeout=2)
        assert stats["popped"] == 2
        assert stats["bind_errors"] == 1  # the duplicate assume
        assert stats["scheduled"] == 1
        assert sched.flush_binds(30)
        assert store.get("Pod", "ok").spec.node_name
    finally:
        sched.stop()


def test_cycle_fault_salvages_popped_pods():
    """A cycle dying mid-stage (a plugin raising) must requeue every
    popped pod and forget stray assumes — no pod strands inflight."""
    store = st.Store()
    _cluster(store)
    for i in range(3):
        store.create(make_pod(f"p{i}").req(cpu_milli=100).obj())
    sched = _mk_scheduler(store)

    def bad_permit(pod, node):
        raise RuntimeError("injected plugin fault")

    sched.profiles.default.run_permit = bad_permit
    try:
        with pytest.raises(RuntimeError):
            sched.schedule_batch(timeout=2)
        s = sched.queue.stats()
        assert s["inflight"] == 0, "pods stranded inflight"
        assert s["backoff"] == 3
        assert sched.cache.assumed_count() == 0, "stray assume leaked"
    finally:
        sched.stop()


# -- watch overflow → Expired → relist → resume -----------------------------


def _wait_for(cond, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.005)
    return False


def test_watch_overflow_expires_instead_of_terminates():
    """Coalescing overflow (more DISTINCT pending objects than the
    capacity) must EXPIRE the watcher — bookmark rv + forced relist —
    never destructively terminate it: iteration raises Expired, and the
    relist + watch(from_rv=rv) resume loses nothing and dupes nothing."""
    store = st.Store(watch_capacity=4)
    w = store.watch("Pod")
    for i in range(8):  # 8 distinct keys against a 4-entry buffer
        store.create(make_pod(f"p{i}").obj())
    assert _wait_for(lambda: w.expired)  # fan-out thread expires it
    assert store.watchers_terminated == 0
    assert store.terminated_by_kind == {}
    assert store.watch_stats()["watch_expired_total"] == 1
    with pytest.raises(st.Expired):
        list(w)  # the 410 signal, never a hang
    # the relist half: list gives a consistent snapshot + resume rv
    items, rv = store.list("Pod")
    assert {p.meta.name for p in items} == {f"p{i}" for i in range(8)}
    w2 = store.watch("Pod", from_rv=rv)
    store.create(make_pod("late").obj())
    ev = w2.get(timeout=2)
    assert ev is not None and ev.obj.meta.name == "late"
    assert w2.get(timeout=0.1) is None  # exactly once: no replayed dupes
    w2.stop()


def test_watch_expired_consistent_after_buffer_eviction():
    store = st.Store(buffer_size=4)
    store.create(make_pod("x").obj())
    old_rv = store.resource_version
    for i in range(16):  # push the buffer past old_rv
        store.create(make_pod(f"y{i}").obj())
    with pytest.raises(st.Expired):
        store.watch("Pod", from_rv=old_rv)
    # relist + resume from the fresh rv works
    _, rv = store.list("Pod")
    w = store.watch("Pod", from_rv=rv)
    store.create(make_pod("z").obj())
    assert w.get(timeout=2).obj.meta.name == "z"
    w.stop()


def test_watch_replay_overflow_raises_expired_not_silent_loss():
    """Chaos-found regression (seed 11): a watch(from_rv=...) whose
    buffered REPLAY overflows (or is fault-dropped) must raise Expired so
    the reflector relists — the old path silently dropped the replayed
    event on a brand-new stream, leaving the consumer stale forever with
    no overflow-kill to expose it."""
    store = st.Store()
    store.create(make_pod("a").obj())
    rv0 = 0  # replay everything
    reg = faults.FaultRegistry().drop("watch.offer", n=1)
    with faults.armed(reg), pytest.raises(st.Expired):
        store.watch("Pod", from_rv=rv0)
    # the refused stream counts as an EXPIRY (observability), never a
    # destructive termination, and a fresh relist + watch works
    assert store.watchers_terminated == 0
    assert store.watch_stats()["watch_expired_total"] == 1
    items, rv = store.list("Pod")
    assert [p.meta.name for p in items] == ["a"]
    w = store.watch("Pod", from_rv=rv)
    store.create(make_pod("b").obj())
    assert w.get(timeout=2).obj.meta.name == "b"
    w.stop()


def test_injected_watch_drop_expires_and_relist_recovers():
    store = st.Store()
    w = store.watch("Pod")
    reg = faults.FaultRegistry().drop("watch.offer", n=1)
    with faults.armed(reg):
        store.create(make_pod("dropped").obj())
        # the drop fires on the fan-out thread: stay armed until it did
        assert _wait_for(lambda: w.expired)
    assert store.watchers_terminated == 0
    assert store.watch_stats()["watch_expired_total"] == 1
    with pytest.raises(st.Expired):
        list(w)  # the 410 signal: relist
    items, rv = store.list("Pod")
    assert [p.meta.name for p in items] == ["dropped"]  # relist sees it
