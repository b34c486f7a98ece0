"""The scheduling loop finishes cycle N in place (docs/scheduler_loop.md,
"Deferred readback"): pop -> dispatch -> finish in ONE pass, and the pop
that follows takes only what the device and the finish left of the batch
window.  Three kinds of case:

  * the loop alone, with scripted halves on a clock the test owns: the
    order of the calls and the exact timeout of every pop;
  * a real Scheduler on a toy cluster whose device answers after a set
    delay: the wave is handed off when the device has it;
  * a real Scheduler read through the flight recorder: every pod's
    ``solved`` stamp lies before the ``sched.pop_wait`` that follows its
    cycle, the next encode sees every assume, the window a pop after a
    dispatch uses stays under 50 ms whatever the adaptive controller
    answers, an empty pop falls back to the idle poll, and a raise in
    either half leaves no pod inflight.
"""

import statistics
import threading
import time
import types

import pytest

from kubernetes_tpu.api import store as st
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.scheduler.config import ProfileConfig, SchedulerConfiguration
from kubernetes_tpu.testing.wrappers import GI, MI, make_node, make_pod
from kubernetes_tpu.utils import trace

W = 0.05          # the loop's cap on a busy lane's window
IDLE = 0.2        # an idle lane's poll
SPAN = trace.SPAN_FIELDS
POD = trace.POD_FIELDS


def spans(snap, name):
    return sorted(
        (r for r in (dict(zip(SPAN, row)) for row in snap["spans"]) if r["name"] == name),
        key=lambda r: r["start"],
    )


def _pod(name, cls=None):
    pod = make_pod(name).req(cpu_milli=50, mem=64 * MI).obj()
    if cls is not None:
        pod.spec.scheduler_name = cls
    return pod


def _nodes(store, n=8):
    for i in range(n):
        store.create(make_node(f"n{i}").capacity(cpu_milli=64000, mem=64 * GI, pods=110).obj())


def _wait_bound(store, n, seconds=90.0):
    deadline = time.monotonic() + seconds
    pods = []
    while time.monotonic() < deadline:
        pods, _ = store.list("Pod")
        if len(pods) >= n and all(p.spec.node_name for p in pods):
            return pods
        time.sleep(0.02)
    unbound = [p.meta.name for p in pods if not p.spec.node_name]
    raise AssertionError(f"{len(unbound)} of {n} pods unbound: {unbound[:5]}")


# -- the loop alone: scripted halves, a clock the test owns -------------------


class _Clock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


class _Elector:
    def __init__(self):
        self.leader = True
        self.on_started_leading = None

    def is_leader(self):
        return self.leader


def _scripted(pops, *, window_seconds=0.05, encode_s=0.003, device_s=0.0,
              raise_in=None, elector=None, between=None):
    """Run ``Scheduler._run`` over a script: ``pops`` holds what each pop
    returns (a batch's size, 0 for empty); the pop after the last entry
    stops the loop.  The dispatch half takes ``encode_s`` on the clock,
    the finish half ``device_s``.  Returns the calls in order, each
    (what, clock reading, timeout or None)."""
    clock = _Clock()
    cfg = SchedulerConfiguration(batch_window_seconds=window_seconds)
    sched = Scheduler(st.Store(), config=cfg, clock=clock, leader_elector=elector)
    calls = []
    script = iter(pops)

    def pop(timeout, profiles=None):
        n = next(script, None)
        calls.append(("pop", clock(), timeout))
        if n is None:
            sched._stop.set()
            return [], clock()
        return [object()] * n, clock()

    def dispatch(batch, t_pop=None):
        clock.t += encode_s
        calls.append(("dispatch", clock(), None))
        if raise_in == "dispatch":
            raise RuntimeError("planted in dispatch")
        return types.SimpleNamespace(batch=batch)

    def finish(cycle):
        clock.t += device_s
        calls.append(("finish", clock(), None))
        if between is not None:
            between()
        if raise_in == "finish":
            raise RuntimeError("planted in finish")

    sched._pop, sched._dispatch_batch, sched._finish_cycle = pop, dispatch, finish
    sched._salvage_cycle = lambda cycle: calls.append(("salvage", clock(), None))
    sched._reconcile_leadership = lambda: None
    try:
        lane = threading.Thread(target=sched._run, daemon=True)
        lane.start()
        lane.join(20.0)
        assert not lane.is_alive()
    finally:
        sched.stop()
    return calls


@pytest.mark.parametrize(
    "device_ms, window_seconds, left",
    [
        (0, 0.05, 0.05),      # the device answers at once: the whole window is left
        (10, 0.05, 0.04),
        (50, 0.05, 0.0),      # ready at the window's very end
        (80, 0.05, 0.0),      # slower than the window: a pop that does not block
        (10, 0.02, 0.01),     # a configured window under the cap is the window
        (30, 0.02, 0.0),
        (10, 0.0, 0.04),      # no configured window: the cap
        (10, 0.5, 0.04),      # a configured window over the cap: the cap
    ],
)
def test_the_pop_after_a_finish_takes_what_is_left_of_the_window(device_ms, window_seconds, left):
    calls = _scripted([3, 3, 0], window_seconds=window_seconds, device_s=device_ms / 1e3)
    assert [c[0] for c in calls] == [
        "pop", "dispatch", "finish", "pop", "dispatch", "finish", "pop", "pop"]
    timeouts = [c[2] for c in calls if c[0] == "pop"]
    # idle, after a dispatch, after a dispatch, idle again after the empty pop
    assert timeouts == pytest.approx([IDLE, left, left, IDLE], abs=1e-9)
    # the window opens where the dispatch returned: device and finish are
    # charged to it, the encode is not
    (_, t_free, _), (_, t_pop, timeout) = calls[1], calls[3]
    window_end = t_free + min(W, window_seconds or W)
    assert t_pop + timeout == pytest.approx(max(window_end, t_pop), abs=1e-9)


@pytest.mark.parametrize("half", ["dispatch", "finish"])
def test_a_raise_in_either_half_salvages_once_and_the_lane_lives(half):
    calls = _scripted([2, 2], raise_in=half, device_s=0.01)
    per_cycle = ["pop", "dispatch"] + (["finish"] if half == "finish" else []) + ["salvage"]
    assert [c[0] for c in calls] == per_cycle * 2 + ["pop"]
    timeouts = [c[2] for c in calls if c[0] == "pop"]
    # a dispatch that raised opened no window; one that returned did
    assert timeouts[1:] == pytest.approx([IDLE if half == "dispatch" else 0.04] * 2, abs=1e-9)


def test_a_lane_that_steps_down_after_a_finish_carries_nothing_back():
    """Leadership lost right after a cycle: nothing is in flight, so there
    is nothing to finish, and the window that cycle opened is not held
    against the first pop after leadership returns."""
    elector = _Elector()
    passes = []

    def step_down():
        elector.leader = False
        # back after the loop has gone round without popping
        threading.Timer(0.12, lambda: setattr(elector, "leader", True)).start()
        passes.append(1)

    calls = _scripted([1], elector=elector, between=step_down)
    assert passes == [1]
    assert [c[0] for c in calls] == ["pop", "dispatch", "finish", "pop"]
    assert calls[-1][2] == IDLE


# -- a device that answers after a set delay ---------------------------------


def _delayed_device(sched, delay_s, log):
    """The default profile's device answers ``delay_s`` after the dispatch
    returned: ``finalize_pending`` sleeps until then, as ``device_get``
    would, and notes the instant it let go."""
    tpu = sched.tpu
    dispatch, finalize = tpu.schedule_pending_async, tpu.finalize_pending

    def schedule_pending_async(pods, **kw):
        ds = dispatch(pods, **kw)
        ds.ready_at = time.monotonic() + delay_s
        return ds

    def finalize_pending(pods, ds, **kw):
        time.sleep(max(0.0, ds.ready_at - time.monotonic()))
        log.append(("ready", time.monotonic(), None))
        return finalize(pods, ds, **kw)

    tpu.schedule_pending_async, tpu.finalize_pending = schedule_pending_async, finalize_pending


@pytest.mark.parametrize("device_ms", [0, 10, 80])
def test_the_wave_leaves_when_the_device_has_it_not_at_the_windows_end(device_ms):
    store = st.Store()
    sched = Scheduler(store, batch_size=8)
    _nodes(store)
    log = []
    _delayed_device(sched, device_ms / 1e3, log)
    handoff, pop_batch = sched._dispatch_subwave_async, sched.queue.pop_batch

    def dispatch_subwave_async(entries, sid):
        log.append(("handoff", time.monotonic(), None))
        return handoff(entries, sid)

    def pop(max_n, timeout=None, **kw):
        log.append(("pop", time.monotonic(), timeout))
        return pop_batch(max_n, timeout=timeout, **kw)

    sched._dispatch_subwave_async, sched.queue.pop_batch = dispatch_subwave_async, pop
    try:
        sched.start()
        store.create(_pod("warm"))          # the compile's cycle, not read
        _wait_bound(store, 1)
        time.sleep(0.3)
        del log[:]
        cycles = 5
        for i in range(cycles):             # one pod a cycle, each on an idle lane
            store.create(_pod(f"p{i}"))
            _wait_bound(store, i + 2)
            time.sleep(0.08)
    finally:
        sched.stop()
    lags, lefts = [], []
    for i, (what, t, _) in enumerate(log):
        if what != "ready":
            continue
        assert [e[0] for e in log[i + 1:i + 3]] == ["handoff", "pop"], log[i:i + 4]
        lags.append(log[i + 1][1] - t)
        lefts.append(log[i + 2][2])
    assert len(lags) == cycles
    # handed off within a few ms of ready in the best cycle at least: a
    # finish left behind the pop cannot beat the rest of the window
    # (50 - d ms) in any
    assert min(lags) < 0.025, lags
    # what is left can only be less than the window less the device's time
    left = max(0.0, W - device_ms / 1e3)
    assert max(lefts) <= left + 1e-9, lefts
    if left:
        assert max(lefts) > left - 0.03, lefts
    else:
        assert lefts == [0.0] * cycles


# -- a real Scheduler, read through the flight recorder ----------------------


@pytest.fixture(scope="module", params=[1, 12, 128])
def served(request):
    """Three full batches of ``size`` pods, created before the loop starts
    so that its cycles follow each other with no idle pass between."""
    size = request.param
    trace.reset()
    store = st.Store()
    sched = Scheduler(store, batch_size=size)
    _nodes(store, 16)
    run = types.SimpleNamespace(size=size, unseen=[], encodes=0)   # what the run left behind
    placed = []
    tpu = sched.tpu
    dispatch, finalize = tpu.schedule_pending_async, tpu.finalize_pending

    def schedule_pending_async(pods, **kw):
        # the encode is about to read the cluster state: every placement
        # an earlier cycle got back must be in it
        run.encodes += 1
        run.unseen += [p.meta.name for p in placed if not sched.cache.state.has_pod(p)]
        return dispatch(pods, **kw)

    def finalize_pending(pods, ds, **kw):
        names = finalize(pods, ds, **kw)
        placed.extend(p for p, n in zip(pods, names) if n is not None)
        return names

    tpu.schedule_pending_async, tpu.finalize_pending = schedule_pending_async, finalize_pending
    for i in range(3 * size):
        store.create(_pod(f"p{i}"))
    t0 = trace.now()
    try:
        sched.start()
        _wait_bound(store, 3 * size)
        time.sleep(0.3)                     # the idle polls after the last cycle
    finally:
        sched.stop()
    run.snap = trace.snapshot(t0)
    run.overlap = sched.metrics.decode_overlap
    run.placed = len(placed)
    trace.reset()
    return run


def _next_pop(pops, t):
    return next(p for p in pops if p["start"] >= t)


def test_every_pod_is_solved_before_the_pop_that_follows_its_cycle(served):
    snap = served.snap
    cycles = {c["id"]: c for c in spans(snap, "sched.cycle")}
    pops = spans(snap, "sched.pop_wait")
    rows = [dict(zip(POD, r)) for r in snap["pods"]]
    assert len(rows) == 3 * served.size and len(cycles) >= 3
    for row in rows:
        cycle = cycles[row["cycle"]]
        following = _next_pop(pops, cycle["start"])
        assert row["popped"] == cycle["start"]
        assert row["popped"] <= row["solved"] <= following["start"], row
        # and the whole cycle, hand-off included, is over by then
        assert cycle["end"] <= following["start"]


def test_the_next_encode_sees_every_assume_of_the_cycle_before(served):
    assert served.encodes >= 3 and served.placed == 3 * served.size
    assert served.unseen == []


def test_the_window_of_a_pop_after_a_dispatch_stays_under_the_cap(served):
    snap = served.snap
    pops = spans(snap, "sched.pop_wait")
    after = [_next_pop(pops, c["end"]) for c in spans(snap, "sched.cycle")]
    assert len(after) >= 3
    for pop in after:
        assert pop["a0"] <= W + 1e-9, pop
        assert pop["end"] - pop["start"] < 0.15, pop   # not the idle poll's 0.2 s


def _dispatch_to_decode_gaps(snap, cycles):
    """Per cycle, from the end of its dispatch to the start of its decode,
    which lies inside the cycle's own span."""
    gaps = []
    for c in cycles:
        (dispatch,) = [s for s in spans(snap, "sched.dispatch") if s["cycle"] == c["id"]]
        (decode,) = [s for s in spans(snap, "sched.decode_wait") if s["cycle"] == c["id"]]
        assert decode["end"] <= c["end"]
        gaps.append(decode["start"] - dispatch["end"])
    return gaps


def test_the_loop_defers_no_decode(served):
    cycles = spans(served.snap, "sched.cycle")
    gaps = _dispatch_to_decode_gaps(served.snap, cycles)
    # scheduler_decode_overlap_seconds holds the same gap: about nothing
    assert statistics.median(gaps) < 0.01, gaps
    assert served.overlap.n == len(cycles)
    assert served.overlap.total == pytest.approx(sum(gaps), abs=1e-6)


@pytest.mark.parametrize("answer", [0.25, 0.1, 0.01])
def test_the_adaptive_window_never_stretches_a_busy_lanes_period(answer):
    """The controller may answer up to 0.25 s; the pop after a dispatch is
    held to what is left of 50 ms, and the pop after an empty one is the
    idle poll, as before."""
    trace.reset()
    store = st.Store()
    sched = Scheduler(store, batch_size=8)
    assert sched.window_ctl is not None     # adaptive_batch_window is the default
    sched.window_ctl.window = lambda: answer
    _nodes(store)
    t0 = trace.now()
    try:
        sched.start()
        for i in range(4):
            store.create(_pod(f"p{i}"))
            _wait_bound(store, i + 1)
            time.sleep(0.35)                # past the empty pop, into the idle poll
    finally:
        sched.stop()
    snap = trace.snapshot(t0)
    trace.reset()
    pops = spans(snap, "sched.pop_wait")
    cycles = spans(snap, "sched.cycle")
    assert len(cycles) == 4
    # no decode waited out a pop: scheduler_decode_overlap_seconds reads
    # about nothing, where it would read the window
    gaps = _dispatch_to_decode_gaps(snap, cycles)
    assert statistics.median(gaps) < 0.01, gaps
    overlap = sched.metrics.decode_overlap
    assert overlap.n == 4 and overlap.total == pytest.approx(sum(gaps), abs=1e-6)
    for c in cycles:
        after = _next_pop(pops, c["end"])
        assert after["n"] == 0              # nothing arrived: an empty pop
        assert after["a0"] <= min(answer, W) + 1e-9, after
        assert after["end"] - after["start"] < 0.15, after
        idle = _next_pop(pops, after["end"])
        # the idle path: the controller's answer under the 0.2 s poll
        assert idle["a0"] == pytest.approx(min(answer, IDLE), abs=1e-9), idle
        if idle["n"] == 0 and idle["end"] is not None:
            assert idle["end"] - idle["start"] >= IDLE - 0.01, idle


@pytest.mark.parametrize("size", [1, 12])
@pytest.mark.parametrize("where", ["dispatch", "decode", "stage", "handoff"])
def test_a_raise_inside_a_cycle_leaves_no_pod_inflight(where, size):
    """The first cycle dies in its dispatch, or in the decode or the staging
    of its in-place finish, or loses a sub-wave at the hand-off: every
    popped pod is requeued and binds on a later cycle."""
    store = st.Store()
    cfg = SchedulerConfiguration(pod_initial_backoff_seconds=0.02, pod_max_backoff_seconds=0.1)
    sched = Scheduler(store, batch_size=size, config=cfg)
    _nodes(store)
    fired = []
    tpu = sched.tpu
    target, attr = {
        "dispatch": (tpu, "schedule_pending_async"),
        "decode": (tpu, "finalize_pending"),
        "stage": (sched, "_stage_group"),
        "handoff": (sched, "_dispatch_subwave_async"),
    }[where]
    real = getattr(target, attr)

    def faulty(*a, **kw):
        if not fired:
            fired.append(where)
            raise RuntimeError(f"planted in {where}")
        return real(*a, **kw)

    setattr(target, attr, faulty)
    for i in range(size):
        store.create(_pod(f"p{i}"))
    try:
        sched.start()
        pods = _wait_bound(store, size)
        assert sched.flush_binds(timeout=30.0)
        assert fired == [where]
        assert len({p.meta.name for p in pods}) == size
        tiers = sched.queue.stats()
        assert tiers["inflight"] == 0 and sched._inflight_get() is None
        # every assume is confirmed (the informer saw the bind) or forgotten
        deadline = time.monotonic() + 10.0
        while sched.cache.assumed_count() and time.monotonic() < deadline:
            time.sleep(0.02)
        assert sched.cache.assumed_count() == 0
    finally:
        sched.stop()


def test_each_profile_lane_finishes_its_own_cycles_in_place():
    """Two lanes, one device: each lane's cycle ends before that lane pops
    again, and the arbiter's slot is back by then."""
    trace.reset()
    store = st.Store()
    cfg = SchedulerConfiguration(
        profiles=[ProfileConfig(), ProfileConfig(scheduler_name="batch-scheduler")])
    sched = Scheduler(store, batch_size=4, config=cfg)
    assert len(sched._lane_profiles) == 2
    _nodes(store)
    for i in range(8):
        store.create(_pod(f"d{i}"))
        store.create(_pod(f"b{i}", cls="batch-scheduler"))
    t0 = trace.now()
    try:
        sched.start()
        _wait_bound(store, 16)
    finally:
        sched.stop()
    snap = trace.snapshot(t0)
    trace.reset()
    cycles = spans(snap, "sched.cycle")
    lanes = {c["thread"] for c in cycles}
    assert len(lanes) == 2
    rows = [dict(zip(POD, r)) for r in snap["pods"]]
    by_cycle = {c["id"]: c for c in cycles}
    for lane in lanes:
        pops = [p for p in spans(snap, "sched.pop_wait") if p["thread"] == lane]
        for c in (c for c in cycles if c["thread"] == lane):
            assert c["end"] <= _next_pop(pops, c["start"])["start"]
    for row in rows:
        assert row["solved"] <= by_cycle[row["cycle"]]["end"]
    assert sched.profiles.arbiter.inflight() == 0
