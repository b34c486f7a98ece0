"""The recorder's CPU ledger (kubernetes_tpu/utils/trace.py): one sampler
thread, run while a Scheduler runs, that writes a ``proc.cpu`` row for every
Python thread whose CPU clock advanced, a ``proc.cpu.process`` row a wake
(its length how late the wake ran past its timer) and a ``proc.stall`` row for
a wake more than a second late, naming the thread that ran through it.  Also
the store's ``store.delete`` tally.  Counts and orderings on the CPU; no
reading here is a time of the chip's."""

import itertools
import logging
import sys
import threading
import time

import pytest

from kubernetes_tpu.utils import trace

SPAN = trace.SPAN_FIELDS
_names = itertools.count()


def rows(name, after=float("-inf")):
    """Closed rows of `name` that end after `after`."""
    snap = trace.snapshot()
    out = [dict(zip(SPAN, r)) for r in snap["spans"] if r[1] == name]
    return [r for r in out if r["end"] is not None and r["end"] > after]


def wait_wakes(k, after):
    """Until `k` wakes of the sampler have ended after `after`."""
    deadline = time.perf_counter() + 20.0
    while len(rows("proc.cpu.process", after)) < k:
        assert time.perf_counter() < deadline, "the sampler does not wake"
        time.sleep(0.02)


def key_of(name):
    (key,) = [k for k, v in trace.thread_names().items() if v == name]
    return key


def spin(seconds):
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def samplers():
    return [t for t in threading.enumerate() if t.name == "trace-cpu" and t.is_alive()]


@pytest.fixture
def alone():
    """No other owner keeps the sampler running (a Scheduler another test
    left started): theirs are stopped for the case and started again after."""
    others = set(trace._ledger_owners)
    for o in others:
        trace.stop_cpu_ledger(o)
    trace.reset()
    try:
        yield
    finally:
        trace.reset()
        for o in others:
            trace.start_cpu_ledger(o)


@pytest.fixture
def ledger(alone):
    owner = object()
    trace.start_cpu_ledger(owner)
    try:
        yield owner
    finally:
        trace.stop_cpu_ledger(owner)


def test_a_spinning_thread_advances_and_an_idle_one_writes_nothing(ledger):
    busy, idle = f"spinner-{next(_names)}", f"idler-{next(_names)}"
    go = threading.Event()
    t_idle = threading.Thread(target=go.wait, args=(10.0,), name=idle)
    t_idle.start()
    t0 = time.perf_counter()
    wait_wakes(2, t0)                   # the idle thread has been read once at least
    mark = time.perf_counter()
    t_busy = threading.Thread(target=spin, args=(1.0,), name=busy)
    t_busy.start()
    t_busy.join()
    wait_wakes(2, time.perf_counter())
    go.set()
    t_idle.join()
    mine = [r for r in rows("proc.cpu", mark) if r["n"] == key_of(busy)]
    assert len(mine) >= 2
    assert all(r["thread"] == t_busy.ident and r["start"] == r["end"] for r in mine)
    assert all(r["cpu1"] > r["cpu0"] for r in mine)
    assert all(a["cpu1"] == b["cpu0"] for a, b in zip(mine, mine[1:]))   # each from the last
    assert sum(r["cpu1"] - r["cpu0"] for r in mine) == pytest.approx(mine[-1]["cpu1"])
    assert mine[-1]["cpu1"] > 0.5
    assert not [r for r in rows("proc.cpu", mark) if r["n"] == key_of(idle)]
    assert trace.thread_names()[key_of(busy)] == busy


def test_a_thread_that_wakes_from_idle_is_read_again_and_loses_no_cpu(ledger):
    name = f"sleeper-{next(_names)}"
    go, done = threading.Event(), threading.Event()

    def sleeper():
        go.wait(10.0)
        spin(0.8)
        done.wait(10.0)

    t = threading.Thread(target=sleeper, name=name)
    t.start()
    wait_wakes(3, time.perf_counter())                        # read idle, writing nothing
    mark = time.perf_counter()
    go.set()
    wait_wakes(2, mark + 0.8)                                 # every thread read since
    total = time.clock_gettime(time.pthread_getcpuclockid(t.ident))
    done.set()
    t.join()
    mine = [r for r in rows("proc.cpu") if r["n"] == key_of(name)]
    assert [r for r in mine if r["start"] > mark]            # read again once it ran
    assert mine[-1]["cpu1"] == total                          # all of it, on its rows
    assert sum(r["cpu1"] - r["cpu0"] for r in mine) == pytest.approx(total)


def test_an_ended_thread_writes_no_more_and_a_thread_on_its_ident_is_another(ledger):
    first = f"first-{next(_names)}"
    a = threading.Thread(target=spin, args=(0.6,), name=first)
    a.start()
    a.join()
    wait_wakes(2, time.perf_counter())
    ended = time.perf_counter()
    # glibc hands a new thread the stack, and so the ident, of one that ended:
    # try a few until the ident repeats
    for _ in range(20):
        name = f"second-{next(_names)}"
        b = threading.Thread(target=spin, args=(0.6,), name=name)
        b.start()
        b.join()
        if b.ident == a.ident:
            break
    wait_wakes(2, time.perf_counter())
    assert key_of(first) != key_of(name)
    assert not [r for r in rows("proc.cpu", ended) if r["n"] == key_of(first)]
    second = [r for r in rows("proc.cpu", ended) if r["n"] == key_of(name)]
    assert second and second[0]["cpu0"] == 0.0      # a new clock, not the first's
    assert trace.thread_names()[key_of(first)] == first     # still named once gone


def test_a_thread_started_and_not_yet_running_is_read_from_a_later_wake(ledger, monkeypatch):
    enumerate_ = threading.enumerate
    limbo = threading.Thread(target=spin, args=(0.0,), name=f"limbo-{next(_names)}")
    # what enumerate() lists between start() and the thread's first bytecode: no ident yet
    monkeypatch.setattr(threading, "enumerate", lambda: enumerate_() + [limbo])
    wait_wakes(3, time.perf_counter())
    assert samplers() and limbo.name not in trace.thread_names().values()


def test_an_ended_threads_name_goes_once_no_row_in_the_ring_names_it(alone, monkeypatch):
    # a long-lived scheduler starts threads without end (one a Permit wait, one
    # an HTTP request): the map keeps only what a row in the ring can name
    monkeypatch.setattr(trace, "SPAN_ROWS", 256)
    monkeypatch.setattr(trace, "CPU_PERIOD_S", 0.01)
    trace.reset()
    owner = object()
    trace.start_cpu_ledger(owner)
    try:
        churned = []
        for _ in range(40):
            batch = [threading.Thread(target=spin, args=(0.03,), name=f"churn-{next(_names)}")
                     for _ in range(8)]
            for th in batch:
                th.start()
            for th in batch:
                th.join()
            churned += batch
            assert len(trace.thread_names()) <= len(threading.enumerate()) + trace.SPAN_ROWS + 1
        names = set(trace.thread_names().values())
        assert {th.name for th in batch} & names              # their rows are in the ring
        assert len(names & {th.name for th in churned}) < len(churned)
        deadline = time.perf_counter() + 20.0
        while names & {th.name for th in churned}:            # until the ring laps them all
            assert time.perf_counter() < deadline, "ended threads stay named"
            time.sleep(0.02)
            names = set(trace.thread_names().values())
        assert trace.thread_names()[trace.NATIVE] == "native"
    finally:
        trace.stop_cpu_ledger(owner)
        monkeypatch.undo()
        trace.reset()


def test_the_process_holds_at_least_every_python_threads_cpu(ledger):
    t = threading.Thread(target=spin, args=(0.8,), name=f"worker-{next(_names)}")
    t.start()
    t.join()
    wait_wakes(3, time.perf_counter())
    spans = [dict(zip(SPAN, r)) for r in trace.snapshot()["spans"]]
    wakes = [r for r in spans if r["name"] == "proc.cpu.process"]
    assert len(wakes) >= 4
    for w in wakes:
        latest = {}
        for r in spans:
            if r["name"] == "proc.cpu" and r["start"] <= w["end"]:
                latest[r["n"]] = r["cpu1"]
        assert sum(latest.values()) <= w["cpu1"] + 1e-9
        assert w["cpu1"] >= w["cpu0"] and w["end"] >= w["start"] and w["n"] >= 2
        assert 0.0 < w["a0"] < trace.CPU_PERIOD_S          # the wake's own cost
    for a, b in zip(wakes, wakes[1:]):
        assert a["cpu1"] == b["cpu0"]                   # each wake from the last
        assert b["start"] >= a["end"]                   # the next timer after this read


def test_a_wake_held_off_past_a_second_names_who_held_the_interpreter(ledger):
    name = f"holder-{next(_names)}"
    release = threading.Event()

    def hold():
        spin(1.5)           # under a 5 s switch interval nobody else runs meanwhile
        release.wait(10.0)  # alive when the sampler reads it

    wait_wakes(1, time.perf_counter())
    before = sys.getswitchinterval()
    t0 = time.perf_counter()
    sys.setswitchinterval(5.0)
    try:
        t = threading.Thread(target=hold, name=name)
        t.start()
        deadline = time.perf_counter() + 20.0
        while not rows("proc.stall", t0) and time.perf_counter() < deadline:
            time.sleep(0.02)
    finally:
        sys.setswitchinterval(before)
        release.set()
    t.join()
    wait_wakes(2, time.perf_counter())
    (stall,) = rows("proc.stall", t0)
    assert stall["end"] - stall["start"] > trace.STALL_S
    assert stall["n"] == key_of(name) and stall["thread"] == t.ident
    assert 0.5 < stall["a0"] <= stall["a1"] + 1e-6       # its CPU, within the process's
    (wake,) = [w for w in rows("proc.cpu.process", t0) if w["end"] == stall["end"]]
    assert wake["start"] == stall["start"]                # the same late wake


def test_start_and_stop_are_idempotent_and_no_sampler_outlives_the_scheduler(alone):
    from kubernetes_tpu.api import store as st
    from kubernetes_tpu.scheduler import Scheduler

    assert not samplers()
    owner = object()
    trace.start_cpu_ledger(owner)
    trace.start_cpu_ledger(owner)
    assert len(samplers()) == 1
    trace.stop_cpu_ledger(owner)
    trace.stop_cpu_ledger(owner)
    assert not samplers()

    store = st.Store()
    sched = Scheduler(store, batch_size=8)
    sched.stop()                                          # never started: nothing to stop
    sched = Scheduler(store, batch_size=8)
    other = object()
    trace.start_cpu_ledger(other)
    try:
        sched.start()
        assert len(samplers()) == 1                       # one for the process
        wait_wakes(2, time.perf_counter())
        names = set(trace.thread_names().values())
        assert {"scheduler", "bind-wave", "informer-Pod"} <= names
        sched.stop()
        assert len(samplers()) == 1                       # another owner still runs
    finally:
        sched.stop()
        trace.stop_cpu_ledger(other)
    assert not samplers()


def test_a_slow_cycle_names_every_threads_cpu_and_the_rest(ledger, caplog):
    name = f"busy-{next(_names)}"
    wait_wakes(1, time.perf_counter())
    with caplog.at_level(logging.WARNING, logger="kubernetes_tpu.trace"):
        tr = trace.Trace("schedule_batch", threshold=0.05, pods=1)
        t = threading.Thread(target=spin, args=(0.7,), name=name)
        t.start()
        t.join()
        wait_wakes(1, time.perf_counter())
        tr.close()
        tr.log_if_long()
    (rec,) = caplog.records
    msg = rec.getMessage()
    cpu = msg.split("; cpu over ")[1]
    assert f"{name} 0." in cpu and "trace-cpu " in cpu
    # the busiest first
    assert cpu.split(": ", 1)[1].startswith(name)
    assert "proc.cpu" not in msg                          # the ledger's rows are no span


def test_the_stores_deletes_are_tallied_like_its_creates(alone):
    from kubernetes_tpu.api import store as st
    from kubernetes_tpu.testing.wrappers import make_pod

    store = st.Store()
    t0 = time.perf_counter()
    for i in range(5):
        store.create(make_pod(f"d{i}").obj())
    for i in range(3):
        store.delete("Pod", f"d{i}")
    with pytest.raises(st.NotFound):
        store.delete("Pod", "d0")                         # a failed call is an interval too
    (row,) = [r for r in rows("store.delete", t0)]
    assert row["parent"] == trace.TALLIED and row["n"] == 4
    assert row["thread"] == threading.get_ident()
    assert 0.0 < row["a0"] <= row["end"] - row["start"] + 1e-9
    assert sum(r["n"] for r in rows("store.create", t0)) == 5
