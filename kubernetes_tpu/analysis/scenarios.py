"""graftsched scenario library — the control plane's real hot windows
driven under the deterministic interleaving explorer.

Each scenario builds REAL components (the sharded store, the scheduler
cache, the binding stage) inside an :class:`~.interleave.Explorer`
window, spawns the racing threads, drives the schedule to quiescence
and then asserts the pipeline's global invariants from a managed oracle
thread:

  * **rv monotonic / gapless** — every publish allocated exactly one
    resourceVersion; the global ring is 1..rv with no holes;
  * **watch replay == final store state** — an informer-style consumer
    (apply events, relist on Expired) converges to exactly the store's
    committed state, coalescing and expiry included;
  * **bound-exactly-once** — no pod ever carries two different nodes
    across any interleaving of commits, retries and fencing;
  * **per-shard sub-wave atomicity** — a fenced or failed sub-wave
    commits nothing; a committed one commits whole;
  * **assume set empty at quiesce** — every assume is confirmed,
    forgotten or expired by the time the pipeline drains;
  * **no lost pods** — every pod handed to the binding stage ends bound
    or back in the queue, across crash-grade binder faults.

Scenario classes keep heavyweight imports (api.store, the scheduler —
JAX) inside methods: this module is imported by the graftlint CLI for
``--interleave`` discovery, and the default import-light ``make lint``
path must never pull JAX.

Use :func:`run_schedule` for one seed and :func:`explore` for a sweep;
``python -m kubernetes_tpu.analysis --interleave`` and the
``interleave``-marked tests (make race) are the standard drivers.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Type

from ..testing import faults
from .interleave import Explorer

# -- oracle helpers ----------------------------------------------------------


def assert_rv_gapless(store, expected: int) -> None:
    """Every commit allocated exactly one rv; the global ring holds
    1..rv in order (monotonic AND gapless)."""
    assert store.resource_version == expected, (
        f"rv {store.resource_version} != {expected} commits"
    )
    rvs = [ev.rv for ev in store._buffer]
    assert rvs == sorted(rvs), f"ring not rv-monotonic: {rvs}"
    assert rvs == list(range(1, expected + 1)), (
        f"rv gap in ring: {rvs}"
    )


def store_pods(store) -> Dict[str, object]:
    items, _ = store.list("Pod")
    return {
        f"{p.meta.namespace}/{p.meta.name}": p for p in items
    }


class InformerConsumer:
    """Minimal informer: watch + apply + relist-on-Expired, the
    reflector contract reduced to its cache.  Runs inside a managed
    thread; `converge` loops until the cache equals `expected` (a
    schedule that loses events without an Expired signal never
    converges and fails the schedule budget — that IS the bug)."""

    def __init__(self, store, kind: str = "Pod"):
        self.store = store
        self.kind = kind
        self.cache: Dict[str, object] = {}
        self.relists = 0
        self._watch = None
        self._relist()

    def _key(self, obj) -> str:
        return f"{obj.meta.namespace}/{obj.meta.name}"

    def _relist(self) -> None:
        from ..api import store as st

        if self._watch is not None:
            self._watch.stop()
        items, rv = self.store.list(self.kind)
        self.cache = {self._key(o): o for o in items}
        self.relists += 1
        while True:
            try:
                self._watch = self.store.watch(self.kind, from_rv=rv)
                return
            except st.Expired:
                items, rv = self.store.list(self.kind)
                self.cache = {self._key(o): o for o in items}
                self.relists += 1

    def pump(self, timeout: float = 0.3) -> bool:
        """Apply one event; False on timeout.  Relists on expiry."""
        from ..api import store as st

        ev = self._watch.get(timeout=timeout)
        if ev is None:
            if self._watch.expired or self._watch.stopped:
                self._relist()
                return True
            return False
        if ev.type == st.DELETED:
            self.cache.pop(self._key(ev.obj), None)
        else:
            self.cache[self._key(ev.obj)] = ev.obj
        return True

    def converged(self, expected: Dict[str, int]) -> bool:
        """cache == expected as {key: resource_version}."""
        got = {
            k: o.meta.resource_version for k, o in self.cache.items()
        }
        return got == expected


# -- scenario protocol -------------------------------------------------------


class Scenario:
    """One reproducible hot window.  Subclasses implement setup()
    (build + spawn inside the explorer window), quiesced() (background
    drain predicate) and check() (invariant oracle, run as a managed
    thread)."""

    name = "scenario"

    @staticmethod
    def preload() -> None:
        """Import everything heavyweight BEFORE the explorer patches
        threading/time — a module import inside the window (lazy
        submodules, first-touch JAX) sees virtual primitives mid-
        initialization and breaks in baffling ways."""
        from ..api import store, types  # noqa: F401

    def fault_plan(self, reg: "faults.FaultRegistry") -> None:
        """Optional seeded fault schedules layered onto the run."""

    def setup(self, ex: Explorer) -> None:
        raise NotImplementedError

    def quiesced(self) -> bool:
        return True

    def check(self) -> None:
        raise NotImplementedError


def _store_quiesced(store) -> bool:
    return all(
        not s._dispatch_backlog and not s._dispatch_inflight
        for s in store._shards
    )


class WritersVsDispatch(Scenario):
    """Concurrent writers vs. the per-shard watch dispatcher vs.
    coalescing expiry: three writers churn two namespaces (different
    shards) on a sharded store while an informer-style consumer follows
    through a DELIBERATELY tiny coalescing buffer, so compaction,
    overflow-expiry and the relist path all run under every
    interleaving.  Oracles: rv monotonic/gapless, consumer cache ==
    final store state, zero destructive watcher terminations."""

    name = "writers_vs_dispatch"
    CAPACITY = 2        # per-watcher coalescing buffer: force expiry
    PODS_PER_NS = 3
    CHURN = True        # update + delete traffic on top of creates

    def setup(self, ex: Explorer) -> None:
        from ..api import store as st
        from ..api import types as api

        self.store = st.Store(shards=2, watch_capacity=self.CAPACITY)
        self.consumer = InformerConsumer(self.store)
        self.expected: Optional[Dict[str, int]] = None
        self.commits = 0
        self.writers_done = 0

        def writer(ns: str) -> None:
            for i in range(self.PODS_PER_NS):
                pod = api.Pod(
                    meta=api.ObjectMeta(name=f"p{i}", namespace=ns)
                )
                created = self.store.create(pod)
                self.commits += 1
                if self.CHURN:
                    created.status.phase = "Pending"
                    self.store.update(created)
                    self.commits += 1
                    if i == 0:
                        # one delete per namespace: annihilation coverage
                        self.store.delete("Pod", f"p{i}", ns)
                        self.commits += 1
            self.writers_done += 1

        def follow() -> None:
            # converge on the writers' final state; a schedule that
            # loses events without an Expired signal never converges
            # and fails the step budget loudly — that IS the bug shape
            while True:
                if self.writers_done == 2:
                    if self.expected is None:
                        self.expected = {
                            k: p.meta.resource_version
                            for k, p in store_pods(self.store).items()
                        }
                    if self.consumer.converged(self.expected):
                        return
                self.consumer.pump()

        ex.spawn(writer, "ns-a", name="writer-a")
        ex.spawn(writer, "ns-b", name="writer-b")
        ex.spawn(follow, name="consumer")

    def quiesced(self) -> bool:
        return _store_quiesced(self.store)

    def check(self) -> None:
        assert_rv_gapless(self.store, self.commits)
        got = {
            k: o.meta.resource_version
            for k, o in self.consumer.cache.items()
        }
        assert got == self.expected, (
            f"consumer diverged after {self.consumer.relists} relists: "
            f"{got} != {self.expected}"
        )
        stats = self.store.watch_stats()
        assert stats["watchers_terminated"] == 0, stats


class WritersVsDispatchFaulted(WritersVsDispatch):
    """writers_vs_dispatch with a fail-grade fault on the offer path:
    the fan-out thread's delivery raises mid-batch.  The watcher must
    EXPIRE (bookmark + relist) — regression pin for the silent
    batch-drop the explorer surfaced in Store._fan_out (a poisoned
    offer starved every remaining watcher of the rest of the batch with
    no 410 signal, so consumer caches went stale forever)."""

    name = "writers_vs_dispatch_faulted"
    # a ROOMY buffer and create-only traffic ON PURPOSE: no capacity
    # expiry forces a relist and no later event for the same object
    # papers over the hole, so the ONLY recovery from the poisoned
    # delivery is the containment path expiring the watcher — pre-fix,
    # the dropped create was simply gone and no seed converged
    CAPACITY = 256
    CHURN = False

    def fault_plan(self, reg: "faults.FaultRegistry") -> None:
        reg.fail("watch.offer", n=1)


class SubwaveVsFencing(Scenario):
    """Concurrent sub-wave commits vs. mid-wave leader fencing: leader
    A commits a fenced bind wave spanning both shards while a rival
    transfers the Lease.  Depending on where the transfer lands, A's
    wave commits whole, commits one shard's sub-wave, or commits
    nothing — but each sub-wave is all-or-nothing, nothing is ever
    bound twice, and a rejected sub-wave is counted in
    fenced_writes_total."""

    name = "subwave_vs_fencing"

    def setup(self, ex: Explorer) -> None:
        from ..api import store as st
        from ..api import types as api

        self.store = st.Store(shards=2)
        # two namespaces living on DIFFERENT shards → two sub-waves
        names = ["ns-a", "ns-b", "ns-c", "ns-d", "ns-e"]
        s0 = self.store.shard_index("Pod", names[0])
        self.ns_a = names[0]
        self.ns_b = next(
            n for n in names if self.store.shard_index("Pod", n) != s0
        )
        self.groups = {
            self.ns_a: [f"a{i}" for i in range(2)],
            self.ns_b: [f"b{i}" for i in range(2)],
        }
        for ns, pods in self.groups.items():
            for name in pods:
                self.store.create(
                    api.Pod(meta=api.ObjectMeta(name=name, namespace=ns))
                )
        lease = api.Lease(
            meta=api.ObjectMeta(name="scheduler", namespace="kube-system"),
            spec=api.LeaseSpec(holder_identity="A", lease_transitions=1),
        )
        self.store.create(lease)
        self.token = st.FenceToken(
            name="scheduler", namespace="kube-system",
            identity="A", generation=1,
        )
        self.fenced = False
        self.applied: List[str] = []

        def leader_commit() -> None:
            def mutate(pod) -> None:
                if pod.spec.node_name and pod.spec.node_name != "n1":
                    raise st.Conflict("double bind")
                pod.spec.node_name = "n1"

            updates = [
                (name, ns, mutate)
                for ns, pods in self.groups.items()
                for name in pods
            ]
            try:
                applied, errors = self.store.update_wave(
                    "Pod", updates, fence=self.token
                )
                self.applied = applied
                assert not errors, errors
            except st.Fenced:
                self.fenced = True

        def depose() -> None:
            cur = self.store.get("Lease", "scheduler", "kube-system")
            cur.spec.holder_identity = "B"
            cur.spec.lease_transitions = 2
            self.store.update(cur)

        ex.spawn(leader_commit, name="leader-A")
        ex.spawn(depose, name="rival-B")

    def quiesced(self) -> bool:
        return _store_quiesced(self.store)

    def check(self) -> None:
        pods = store_pods(self.store)
        by_shard_bound: Dict[str, List[bool]] = {}
        for ns, group in self.groups.items():
            bound = [
                pods[f"{ns}/{n}"].spec.node_name == "n1" for n in group
            ]
            assert pods  # keyed lookups above raise on lost pods
            for n in group:
                node = pods[f"{ns}/{n}"].spec.node_name
                assert node in (None, "", "n1"), (
                    f"bound to an impossible node: {node}"
                )
            # per-shard sub-wave atomicity: all-or-nothing per namespace
            assert all(bound) or not any(bound), (
                f"torn sub-wave in {ns}: {bound}"
            )
            by_shard_bound[ns] = bound
        if self.fenced:
            assert self.store.fenced_writes_total >= 1
            # the wave aborted at some sub-wave boundary: at least one
            # namespace must be wholly unbound
            assert not all(
                all(b) for b in by_shard_bound.values()
            ), "Fenced raised but every sub-wave committed"
        else:
            assert all(all(b) for b in by_shard_bound.values()), (
                f"no fence hit, but wave incomplete: {by_shard_bound}"
            )
        lease = self.store.get("Lease", "scheduler", "kube-system")
        assert lease.spec.holder_identity == "B"


class AssumeBridgeVsCommit(Scenario):
    """Assume-cache bridging vs. wave commit vs. TTL expiry: the
    scheduler cache assumes placements, the binder-side wave commits
    them through the store, the informer-side confirm races both, and a
    near-zero TTL cleanup sweep races everything.  Oracles: the assume
    set is EMPTY at quiesce (every assume confirmed or expired), every
    pod is bound exactly once in the store, and the cache accounts each
    bound pod exactly once (no phantom usage, no double accounting)."""

    name = "assume_bridge_vs_commit"
    PODS = 4

    @staticmethod
    def preload() -> None:
        from ..api import store, types  # noqa: F401
        from ..models.batch_scheduler import TPUBatchScheduler  # noqa: F401
        from ..scheduler.cache import SchedulerCache  # noqa: F401

    def setup(self, ex: Explorer) -> None:
        from ..api import store as st
        from ..api import types as api
        from ..models.batch_scheduler import TPUBatchScheduler
        from ..scheduler.cache import SchedulerCache

        self.store = st.Store(shards=2)
        tpu = TPUBatchScheduler()
        self.cache = SchedulerCache(tpu.state, ttl=0.001, clock=ex.clock)
        self.cache.add_node(
            api.Node(
                meta=api.ObjectMeta(name="n1", namespace=""),
                status=api.NodeStatus(
                    allocatable={"cpu": 64_000, "memory": 1 << 34, "pods": 110}
                ),
            )
        )
        self.pods = []
        for i in range(self.PODS):
            pod = api.Pod(meta=api.ObjectMeta(name=f"p{i}", namespace="d"))
            self.store.create(pod)
            self.pods.append(pod)
        self.requeued: List[object] = []
        self.confirm_done = False

        def assume_and_commit() -> None:
            for pod in self.pods:
                self.cache.assume(pod, "n1")

            def mutate(p) -> None:
                if p.spec.node_name and p.spec.node_name != "n1":
                    raise st.Conflict("double bind")
                p.spec.node_name = "n1"
                p.status.phase = "Running"

            applied, errors = self.store.update_wave(
                "Pod", [(p.meta.name, "d", mutate) for p in self.pods]
            )
            assert not errors, errors
            self.cache.finish_binding_all(self.pods)

        def confirm() -> None:
            # informer-side: follow the store and confirm binds in the
            # cache, exactly what Scheduler._on_pod does for bound pods
            # (from_rv=0: the commit may win the race to the ring, so
            # the bind events must REPLAY to a late registration)
            w = self.store.watch("Pod", from_rv=0)
            confirmed = set()
            while len(confirmed) < self.PODS:
                ev = w.get(timeout=0.3)
                if ev is None:
                    continue
                if ev.obj.spec.node_name:
                    self.cache.add_pod(ev.obj)
                    confirmed.add(ev.obj.meta.name)
            w.stop()
            self.confirm_done = True

        def expire_sweep() -> None:
            # the hot loop's cleanup_expired: TTL is ~0 in virtual time,
            # so any assume whose confirm lost the race gets expired and
            # requeued — the oracle proves the pipeline still converges
            for _ in range(6):
                self.requeued.extend(self.cache.cleanup_expired())

        ex.spawn(assume_and_commit, name="commit")
        ex.spawn(confirm, name="informer")
        ex.spawn(expire_sweep, name="expiry")

    def quiesced(self) -> bool:
        return _store_quiesced(self.store)

    def check(self) -> None:
        # every pod durably bound exactly once
        pods = store_pods(self.store)
        assert len(pods) == self.PODS
        for key, pod in pods.items():
            assert pod.spec.node_name == "n1", f"{key} lost its bind"
        # assume set empty: confirmed (informer) or expired (sweep)
        assert self.cache.assumed_count() == 0, (
            f"assume set not empty at quiesce: {self.cache.assumed_nodes()}"
        )
        # the cache accounts each pod at most once, and every pod it
        # does not account was expired (the requeue path owns it)
        accounted = sum(
            1 for p in self.pods if self.cache.state.has_pod(p)
        )
        expired_keys = {
            f"{p.meta.namespace}/{p.meta.name}" for p in self.requeued
        }
        assert accounted + len(expired_keys) >= self.PODS, (
            f"lost accounting: {accounted} accounted, "
            f"{len(expired_keys)} expired of {self.PODS}"
        )


class BinderCrashVsSalvage(Scenario):
    """Binder crash / restart vs. the salvage path: a staged bind wave
    meets a crash-grade fault inside the commit, the worker dies, the
    watchdog restarts it, and the retried wave must commit every pod
    EXACTLY once — while a concurrent mid-flight cycle dies and
    _salvage_cycle requeues its unhandled pods.  Oracles: no lost pods
    (bound or back in the queue), bound-exactly-once, wave backlog
    drained."""

    name = "binder_crash_vs_salvage"
    PODS = 3

    @staticmethod
    def preload() -> None:
        from ..api import store, types  # noqa: F401
        from ..scheduler import scheduler  # noqa: F401

    def fault_plan(self, reg: "faults.FaultRegistry") -> None:
        reg.crash("binder.commit_wave", n=1)

    def setup(self, ex: Explorer) -> None:
        from ..api import store as st
        from ..api import types as api
        from ..scheduler import scheduler as sched_mod
        from ..scheduler.queue import QueuedPodInfo, pod_key

        # 1-shard store: the commit pool (ThreadPoolExecutor +
        # SimpleQueue) would real-block inside the window
        self.store = st.Store(shards=1)
        self.sched = sched_mod.Scheduler(self.store, clock=ex.clock)
        self.cache = self.sched.cache
        self.cache.add_node(
            api.Node(
                meta=api.ObjectMeta(name="n1", namespace=""),
                status=api.NodeStatus(
                    allocatable={"cpu": 64_000, "memory": 1 << 34, "pods": 110}
                ),
            )
        )
        fwk = self.sched.profiles.default
        for i in range(self.PODS):
            pod = api.Pod(meta=api.ObjectMeta(name=f"p{i}", namespace="d"))
            self.store.create(pod)
            self.sched.queue.add(pod)
        # pop → assume → stage, exactly the _stage_group tail: the
        # queue's own infos ride the wave so failure paths requeue them
        batch = self.sched.queue.pop_batch(self.PODS, timeout=0)
        assert len(batch) == self.PODS
        wave = []
        for info in batch:
            self.cache.assume(info.pod, "n1")
            wave.append((fwk, info, "n1", ex.clock()))
        self.infos: List[QueuedPodInfo] = batch
        self.pod_key = pod_key

        def dispatch_and_flush() -> None:
            self.sched._dispatch_wave_async(wave)
            # flush_binds runs the binder watchdog each lap: the
            # crashed worker is restarted and the requeued remainder
            # commits on the second attempt
            assert self.sched.flush_binds(timeout=30.0)

        def salvage_racer() -> None:
            # a cycle that died mid-flight with nothing staged: its
            # popped pods must come back to the queue, not strand
            pod = api.Pod(meta=api.ObjectMeta(name="stray", namespace="d"))
            self.store.create(pod)
            self.sched.queue.add(pod)
            popped = self.sched.queue.pop_batch(1, timeout=0)
            assert len(popped) == 1
            cycle = sched_mod._Cycle({}, _NullTrace(), [], popped)
            self.sched._salvage_cycle(cycle)

        def stopper() -> None:
            # graceful stop from a MANAGED thread (joins are cooperative)
            self.sched.stop()

        ex.spawn(dispatch_and_flush, name="dispatch")
        ex.spawn(salvage_racer, name="salvage")
        self._stopper = stopper
        self._ex = ex

    def quiesced(self) -> bool:
        with self.sched._wave_cv:
            drained = not self.sched._waves and not self.sched._wave_active
        return drained and _store_quiesced(self.store)

    def check(self) -> None:
        pods = store_pods(self.store)
        for i in range(self.PODS):
            assert pods[f"d/p{i}"].spec.node_name == "n1", (
                f"pod p{i} lost its bind after the binder crash"
            )
        # the salvaged stray is unbound and back in the queue
        assert not pods["d/stray"].spec.node_name
        assert self.sched.queue.contains("d/stray"), (
            "salvage lost the popped pod"
        )
        assert self.sched.metrics.binder_restarts.total >= 1, (
            "binder crash never tripped the watchdog restart"
        )
        # committed pods left the queue; nothing stranded inflight
        stats = self.sched.queue.stats()
        assert stats["inflight"] == 0, stats
        self._stopper()


class _NullTrace:
    total = 0.0

    def step(self, *_a, **_k):
        pass

    def log_if_long(self):
        pass

    def close(self, *_a, **_k):
        pass


class _StubElector:
    """Minimal leader elector for scenarios: always leading, fence
    tokens pinned to one acquisition (identity A, generation 1) — a
    rival transferring the Lease makes every later fenced commit
    reject, without the real elector's renew thread."""

    on_started_leading = None

    def __init__(self, token):
        self._token = token

    def is_leader(self) -> bool:
        return True

    def fence_token(self):
        return self._token


class SpeculativeSolveVsCommit(Scenario):
    """Lane A's SPECULATIVE solve over lane B's assumed placements vs.
    lane B's wave commit vs. assume-TTL expiry vs. a leader fence —
    the PR 12 speculative-overlap window.  Lane B assumes + stages a
    bind wave; lane A records the wave-failure generation, reads the
    snapshot lane B's assumes shaped (the encode analogue), and only
    stages its own wave when the speculation still holds — a commit
    failure or mid-wave fence (the rival's Lease transfer) must
    invalidate lane A's batch and requeue it whole.  Oracles:
    bound-exactly-once, no lost pod (bound or back in the queue),
    assume set empty at quiesce, rv ring gapless, a fenced wave
    commits nothing."""

    name = "speculative_solve_vs_commit"

    @staticmethod
    def preload() -> None:
        from ..api import store, types  # noqa: F401
        from ..scheduler import scheduler  # noqa: F401

    def setup(self, ex: Explorer) -> None:
        from ..api import store as st
        from ..api import types as api
        from ..scheduler import scheduler as sched_mod

        # 1-shard store: the commit pool (ThreadPoolExecutor) would
        # real-block inside the window (same constraint as
        # binder_crash_vs_salvage); streaming is exercised by the chaos
        # seeds with real threads instead
        self.store = st.Store(shards=1)
        lease = api.Lease(
            meta=api.ObjectMeta(name="scheduler", namespace="kube-system"),
            spec=api.LeaseSpec(holder_identity="A", lease_transitions=1),
        )
        self.store.create(lease)
        token = st.FenceToken(
            name="scheduler", namespace="kube-system",
            identity="A", generation=1,
        )
        self.sched = sched_mod.Scheduler(
            self.store, clock=ex.clock, assume_ttl=0.001,
            leader_elector=_StubElector(token),
        )
        self.cache = self.sched.cache
        self.cache.add_node(
            api.Node(
                meta=api.ObjectMeta(name="n1", namespace=""),
                status=api.NodeStatus(
                    allocatable={"cpu": 64_000, "memory": 1 << 34, "pods": 110}
                ),
            )
        )
        fwk = self.sched.profiles.default
        self.pods_b, self.pods_a = [], []
        for i in range(2):
            pod = api.Pod(meta=api.ObjectMeta(name=f"b{i}", namespace="d"))
            pod.spec.priority = 10
            self.store.create(pod)
            self.sched.queue.add(pod)
            self.pods_b.append(pod)
        for i in range(2):
            pod = api.Pod(meta=api.ObjectMeta(name=f"a{i}", namespace="d"))
            pod.spec.scheduler_name = "lane-a"
            self.store.create(pod)
            self.sched.queue.add(pod)
            self.pods_a.append(pod)
        self.invalidated = False
        self.a_observed_b_assumes = 0
        self.lanes_done = 0
        self.requeued: List[object] = []

        def lane_b() -> None:
            batch = self.sched.queue.pop_batch(
                2, timeout=0, profiles={"default-scheduler"}
            )
            assert len(batch) == 2, "lane B lost its pods"
            wave = []
            for info in batch:
                self.cache.assume(info.pod, "n1")
                wave.append((fwk, info, "n1", ex.clock()))
            self.sched._dispatch_wave_async(wave)
            self.lanes_done += 1

        def lane_a() -> None:
            # the speculative dispatch: record the wave-failure
            # generation, then "solve" over whatever lane B assumed
            token = self.sched._spec_token()
            with self.cache.lock:
                self.a_observed_b_assumes = sum(
                    1 for p in self.pods_b
                    if self.cache.state.has_pod(p)
                )
            batch = self.sched.queue.pop_batch(
                2, timeout=0, profiles={"lane-a"}
            )
            assert len(batch) == 2, "lane A lost its pods"
            if self.sched._spec_invalidated(token):
                # mis-speculation: requeue exactly this batch
                self.invalidated = True
                self.sched.metrics.misspeculation_total.inc()
                for info in batch:
                    self.sched.queue.requeue_backoff(info)
                self.lanes_done += 1
                return
            wave = []
            for info in batch:
                self.cache.assume(info.pod, "n1")
                wave.append((fwk, info, "n1", ex.clock()))
            self.sched._dispatch_wave_async(wave)
            self.lanes_done += 1

        def rival() -> None:
            cur = self.store.get("Lease", "scheduler", "kube-system")
            cur.spec.holder_identity = "B"
            cur.spec.lease_transitions = 2
            self.store.update(cur)

        def confirm_and_expire() -> None:
            # informer-style confirm + the assume-TTL sweep: loop until
            # every pod settled (bound-and-confirmed, or unbound and
            # back in the queue) so the assume set provably drains
            w = self.store.watch("Pod", from_rv=0)
            while not self._settled():
                ev = w.get(timeout=0.3)
                if ev is not None and ev.obj.spec.node_name:
                    self.cache.add_pod(ev.obj)
                for pod in self.cache.cleanup_expired():
                    self.requeued.append(pod)
                    self.sched.queue.add(pod)
            w.stop()

        ex.spawn(lane_b, name="lane-b")
        ex.spawn(lane_a, name="lane-a")
        ex.spawn(rival, name="rival")
        ex.spawn(confirm_and_expire, name="confirm")

    def _settled(self) -> bool:
        if self.lanes_done < 2 or self.sched._waves_in_flight():
            return False
        pods = store_pods(self.store)
        for pod in self.pods_b + self.pods_a:
            key = f"{pod.meta.namespace}/{pod.meta.name}"
            cur = pods.get(key)
            if cur is None:
                return False
            if cur.spec.node_name:
                if self.cache.is_assumed(cur):
                    return False  # confirm still pending
            elif not self.sched.queue.contains(key):
                return False  # neither bound nor requeued: in flight
        return True

    def quiesced(self) -> bool:
        with self.sched._wave_cv:
            drained = (
                not self.sched._waves
                and not self.sched._wave_active
                and not self.sched._stream_inflight
            )
        return drained and _store_quiesced(self.store)

    def check(self) -> None:
        pods = store_pods(self.store)
        fenced = self.store.fenced_writes_total
        bound_b = [
            bool(pods[f"d/{p.meta.name}"].spec.node_name)
            for p in self.pods_b
        ]
        for pod in self.pods_b + self.pods_a:
            cur = pods[f"d/{pod.meta.name}"]
            node = cur.spec.node_name
            assert node in (None, "", "n1"), (
                f"{pod.meta.name} bound to an impossible node: {node}"
            )
            if not node:
                # unbound at quiesce: must be back in the queue, never
                # stranded inflight or assumed
                key = f"d/{pod.meta.name}"
                assert self.sched.queue.contains(key), (
                    f"{key} lost: unbound and not requeued"
                )
        # a fenced wave commits nothing: fence hit => at least one
        # whole wave's pods stayed unbound
        if fenced:
            assert not all(bound_b) or not all(
                bool(pods[f"d/{p.meta.name}"].spec.node_name)
                for p in self.pods_a
            ), "Fenced raised but every wave committed"
        # mis-speculation accounting: lane A invalidated => its pods
        # requeued whole (none bound), and the failure generation moved
        if self.invalidated:
            assert self.sched._spec_token() >= 1
            for p in self.pods_a:
                assert not pods[f"d/{p.meta.name}"].spec.node_name, (
                    "invalidated speculative batch still bound a pod"
                )
        # assume set empty at quiesce (confirmed, expired, or released)
        assert self.cache.assumed_count() == 0, (
            f"assume set not empty: {self.cache.assumed_nodes()}"
        )
        # rv ring gapless and monotonic across every commit path
        rvs = [ev.rv for ev in self.store._buffer]
        assert rvs == list(
            range(1, self.store.resource_version + 1)
        ), f"rv ring not gapless: {rvs}"
        self.sched.stop()


SCENARIOS: Dict[str, Type[Scenario]] = {
    cls.name: cls
    for cls in (
        WritersVsDispatch,
        WritersVsDispatchFaulted,
        SubwaveVsFencing,
        AssumeBridgeVsCommit,
        BinderCrashVsSalvage,
        SpeculativeSolveVsCommit,
    )
}


# -- drivers -----------------------------------------------------------------


def run_schedule(
    scenario_cls: Type[Scenario],
    seed: int,
    policy: str = "random",
    max_steps: int = 50_000,
) -> Explorer:
    """One scenario under one schedule; returns the Explorer (trace,
    steps) on success, raises the failing oracle/deadlock otherwise."""
    import gc

    sc = scenario_cls()
    scenario_cls.preload()
    ex = Explorer(seed=seed, policy=policy, max_steps=max_steps)
    reg = faults.FaultRegistry(seed)
    sc.fault_plan(reg)
    with faults.armed(reg):
        with ex.installed():
            sc.setup(ex)
            ex.drive(quiesce=sc.quiesced)
            ex.run_inline(sc.check, name="oracle")
    # drop scenario refs so detached service loops exit via weakrefs
    del sc
    gc.collect()
    return ex


def explore(
    scenario_cls: Type[Scenario],
    seeds=range(100),
    policies=("random", "pct"),
    max_steps: int = 50_000,
    progress: Optional[Callable[[str], None]] = None,
) -> Dict[str, int]:
    """Sweep a scenario across seeds × policies.  Every schedule must
    pass; returns {"schedules": n, "yield_points": n} for reporting."""
    schedules = 0
    points = 0
    for policy in policies:
        for seed in seeds:
            ex = run_schedule(
                scenario_cls, seed, policy=policy, max_steps=max_steps
            )
            schedules += 1
            points += ex.steps
            if progress is not None and schedules % 25 == 0:
                progress(
                    f"{scenario_cls.name}: {schedules} schedules, "
                    f"{points} yield points"
                )
    return {"schedules": schedules, "yield_points": points}
