"""Hollow nodes: control-plane scale simulation without real kubelets.

Reference: pkg/kubemark/hollow_kubelet.go:63-87 — a real kubelet loop
against a no-op runtime, used to exercise 5k-node control planes.  Ours
registers Node objects, heartbeats them through the API (MODIFIED events
— the NodeUpdate churn a real cluster produces), and plays the kubelet
status half: bound pods transition to Running, so Jobs and controllers
see lifecycle progress.

Two layers:

  HollowCluster  the hollow kubelet fleet.  Heartbeats are BATCHED —
                 each tick commits one ``Store.update_wave`` over its
                 node slice (one lock acquisition, one coalesced journal
                 append, one watch fan-out handoff on the Node shard)
                 instead of O(batch) single-object writes, and the tick
                 is jittered so a 100k-node fleet doesn't monopolize the
                 Node shard in phase-locked bursts.
  NodeGroupScaler  the autoscaler-in-the-loop half (chip_smoke.py
                 stage 3): a named node group scaled
                 up/down through the API (or replayed as a frozen
                 trace), with a cluster-autoscaler-shaped reconcile
                 policy — the sustained node add/remove stream the
                 elastic node axis exists to absorb.
  FleetHarness   the first-class fleet driver (tests/test_kubemark.py):
                 registers up to 100k hollow nodes, runs a SUSTAINED
                 pod-lifecycle soak (create → bind via per-shard
                 update_wave sub-waves committed concurrently → hollow
                 kubelets run them → delete) across many namespaces so
                 the waves exercise the sharded store, and reports
                 SLO-style p50/p90/p99 lifecycle latency plus
                 lost/double-bound counts.

This drives the FULL store/watch/journal path at fleet scale — the
thing a solver-only run can't see (ROADMAP's "heavy traffic from
millions of users" axis)."""

from __future__ import annotations

import random
import threading
import time
from collections import defaultdict
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional

from .api import store as st
from .api import types as api
from .testing.wrappers import GI, MI, make_node, make_pod


def percentiles(samples: List[float]) -> Dict[str, float]:
    """SLO-style latency summary: p50/p90/p99 by nearest-rank over the
    sample list (empty list reports zeros)."""
    if not samples:
        return {"p50": 0.0, "p90": 0.0, "p99": 0.0}
    s = sorted(samples)
    n = len(s)

    def rank(q: float) -> float:
        return s[min(n - 1, max(0, int(q * n + 0.5) - 1))]

    return {"p50": rank(0.50), "p90": rank(0.90), "p99": rank(0.99)}


class HollowCluster:
    def __init__(
        self,
        store: st.Store,
        n_nodes: int,
        zones: int = 8,
        cpu_milli: int = 32000,
        mem: int = 64 * GI,
        pods_cap: int = 110,
        heartbeat_interval: float = 10.0,
        run_pods: bool = True,
        # fraction of the tick period each sleep is jittered by (±):
        # de-phases heartbeat waves so the fleet never lands on the Node
        # shard in lockstep with the binder's sub-waves
        heartbeat_jitter: float = 0.2,
    ):
        self.store = store
        self.n_nodes = n_nodes
        self.heartbeat_interval = heartbeat_interval
        self.heartbeat_jitter = heartbeat_jitter
        self.run_pods = run_pods
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self.node_names = [f"hollow-{i}" for i in range(n_nodes)]
        # observability: wave-committed heartbeat batches (tests assert
        # the loop batches instead of issuing per-node writes)
        self.heartbeat_waves = 0
        self.heartbeats = 0
        self._specs = [
            make_node(name)
            .capacity(cpu_milli=cpu_milli, mem=mem, pods=pods_cap)
            .zone(f"zone-{i % zones}")
            .obj()
            for i, name in enumerate(self.node_names)
        ]

    def register(self) -> None:
        """Create every Node through the API (the kubelet registration)."""
        for node in self._specs:
            try:
                self.store.create(node)
            except st.AlreadyExists:
                pass

    def start(self) -> "HollowCluster":
        self.register()
        t = threading.Thread(
            target=self._heartbeat_loop, name="hollow-heartbeat", daemon=True
        )
        t.start()
        self._threads.append(t)
        if self.run_pods:
            t = threading.Thread(
                target=self._pod_runner, name="hollow-pod-runner", daemon=True
            )
            t.start()
            self._threads.append(t)
        return self

    def stop(self) -> None:
        self._stop.set()
        for t in self._threads:
            t.join(timeout=5)

    # -- loops -------------------------------------------------------------

    def _heartbeat_loop(self) -> None:
        """Round-robin status heartbeats (nodeStatusUpdateFrequency),
        BATCHED: each jittered tick commits its node slice through ONE
        ``update_wave`` — one lock acquisition, one coalesced journal
        append and one fan-out handoff on the Node shard, instead of
        O(batch) single-object writes — so the harness itself never
        monopolizes the shard it shares with real Node traffic."""
        i = 0
        per_tick = max(1, self.n_nodes // 10)
        tick = self.heartbeat_interval / 10
        rng = random.Random(0x5EED ^ self.n_nodes)
        j = self.heartbeat_jitter
        while not self._stop.wait(tick * (1.0 + rng.uniform(-j, j))):
            batch = [
                self.node_names[(i + k) % self.n_nodes]
                for k in range(min(per_tick, self.n_nodes))
            ]
            i = (i + per_tick) % self.n_nodes
            now = str(time.time())

            def beat(node) -> None:
                node.meta.annotations["hollow/heartbeat"] = now

            try:
                applied, _ = self.store.update_wave(
                    "Node", [(name, "", beat) for name in batch]
                )
            except Exception:  # noqa: BLE001 — heartbeat best-effort
                continue
            self.heartbeat_waves += 1
            self.heartbeats += len(applied)

    def _pod_runner(self) -> None:
        """The kubelet status half: bound Pending pods become Running
        (status written through the API, like status manager PATCHes).
        A watch the store EXPIRED for falling behind (coalescing
        overflow sets .stopped too) is re-established with a catch-up
        list — the reflector contract; the store never destructively
        terminates a slow watcher."""
        w = self.store.watch("Pod")
        try:
            while not self._stop.is_set():
                if w.stopped:
                    w.stop()
                    pods, rv = self.store.list("Pod")
                    for pod in pods:
                        self._maybe_run(pod)  # catch up on missed binds
                    # resume FROM the list's rv: binds landing between
                    # the snapshot and the new watch must not vanish
                    w = self.store.watch("Pod", from_rv=rv)
                    continue
                ev = w.get(timeout=0.2)
                if ev is None:
                    continue
                pod = ev.obj
                if ev.type in (st.ADDED, st.MODIFIED):
                    self._maybe_run(pod)
        finally:
            w.stop()

    def _maybe_run(self, pod) -> None:
        if (
            pod.spec.node_name
            and pod.spec.node_name.startswith("hollow-")
            and pod.status.phase == "Pending"
        ):
            try:
                fresh = self.store.get(
                    "Pod", pod.meta.name, pod.meta.namespace
                )
                if fresh.status.phase == "Pending" and fresh.spec.node_name:
                    fresh.status.phase = "Running"
                    self.store.update(fresh, force=True)
            except st.NotFound:
                pass


class NodeGroupScaler:
    """Autoscaler-in-the-loop node-group driver — the cluster-autoscaler
    half kubemark didn't model.  Owns a named group of hollow nodes and
    scales it toward a target: `scale_to` creates the missing members
    (highest index first to appear, lowest removed last) and deletes the
    surplus, returning the (added nodes, removed names) so a
    frozen-trace harness can replay the exact churn against a solver
    pair; with a Store attached the membership changes also commit
    through the API (create/delete → informers → scheduler cache), the
    live-loop shape chip_smoke.py stage 3 drives.

    `reconcile` is the bundled scale policy (the CA loop's core):
    scale UP by ceil(pending / pods_per_node) when pods are pending,
    scale DOWN one `step` at a time once idle capacity exceeds a full
    step plus `idle_headroom` nodes — asymmetric on purpose, like the
    reference autoscaler's eager-up / conservative-down posture (the
    ClusterState's bucket-shrink dwell provides the second layer of
    hysteresis underneath)."""

    def __init__(
        self,
        store: Optional[st.Store] = None,
        group: str = "autoscale",
        cpu_milli: int = 32000,
        mem: int = 64 * GI,
        pods_cap: int = 110,
        zones: int = 8,
        max_nodes: int = 1 << 20,
        taints: Optional[List[tuple]] = None,
    ):
        self.store = store
        self.group = group
        self.cpu_milli = cpu_milli
        self.mem = mem
        self.pods_cap = pods_cap
        self.zones = zones
        self.max_nodes = max_nodes
        self.taints = list(taints or [])
        self._size = 0
        self._next_id = 0
        self._members: List[str] = []  # creation order; drain from the tail
        # observability
        self.scale_ups = 0
        self.scale_downs = 0
        self.nodes_added = 0
        self.nodes_removed = 0

    def size(self) -> int:
        return self._size

    def _make_node(self, i: int):
        w = (
            make_node(f"{self.group}-{i}")
            .capacity(
                cpu_milli=self.cpu_milli, mem=self.mem, pods=self.pods_cap
            )
            .zone(f"zone-{i % self.zones}")
        )
        for key, value, effect in self.taints:
            w = w.taint(key, value, effect)
        return w.obj()

    def scale_to(self, target: int):
        """Drive the group to `target` members.  Returns
        (added_node_objects, removed_node_names); store-backed groups
        also commit the changes through the API."""
        target = max(0, min(int(target), self.max_nodes))
        added, removed = [], []
        while self._size < target:
            node = self._make_node(self._next_id)
            self._next_id += 1
            if self.store is not None:
                try:
                    self.store.create(node)
                except st.AlreadyExists:
                    pass
            self._members.append(node.meta.name)
            added.append(node)
            self._size += 1
        while self._size > target:
            name = self._members.pop()  # newest first: oldest nodes pin
            if self.store is not None:
                try:
                    self.store.delete("Node", name)
                except st.NotFound:
                    pass
            removed.append(name)
            self._size -= 1
        if added:
            self.scale_ups += 1
            self.nodes_added += len(added)
        if removed:
            self.scale_downs += 1
            self.nodes_removed += len(removed)
        return added, removed

    def reconcile(
        self,
        pending: int,
        pods_per_node: int,
        idle_nodes: int = 0,
        step: int = 1,
        idle_headroom: int = 0,
        up_step_cap: int = 0,
    ):
        """One autoscaler pass: returns scale_to()'s (added, removed)
        for the policy's chosen target (no-op → ([], [])).
        `up_step_cap` (0 = unbounded) bounds one pass's scale-up so a
        tight reconcile loop ramps instead of bursting — bursts dirty
        more rows than the mirror's delta/grow path can absorb and
        force full re-uploads (the over-fraction safety path)."""
        per = max(1, int(pods_per_node))
        if pending > 0:
            up = (pending + per - 1) // per
            if up_step_cap > 0:
                up = min(up, up_step_cap)
            return self.scale_to(min(self._size + up, self.max_nodes))
        if idle_nodes > max(0, idle_headroom) + max(1, step):
            return self.scale_to(max(0, self._size - max(1, step)))
        return [], []


class _LifecycleAudit:
    """Watches the Pod stream and records, per pod key: the node(s) it
    was ever bound to (double-bind detection) and the instant it was
    first observed Running (lifecycle-latency half).  Poll-style
    consumer: an Expired stream relists and resumes, so audit coverage
    survives overload."""

    def __init__(self, store: st.Store):
        self.store = store
        self.bound_nodes: Dict[str, set] = defaultdict(set)
        self.running_at: Dict[str, float] = {}
        self._mu = threading.Lock()
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="fleet-audit", daemon=True
        )
        self._thread.start()

    def _note(self, pod) -> None:
        key = f"{pod.meta.namespace}/{pod.meta.name}"
        with self._mu:
            if pod.spec.node_name:
                self.bound_nodes[key].add(pod.spec.node_name)
            if pod.status.phase == "Running" and key not in self.running_at:
                self.running_at[key] = time.perf_counter()

    def _run(self) -> None:
        w = self.store.watch("Pod")
        try:
            while not self._stop.is_set():
                if w.stopped:
                    w.stop()
                    pods, rv = self.store.list("Pod")
                    for pod in pods:
                        self._note(pod)
                    w = self.store.watch("Pod", from_rv=rv)
                    continue
                ev = w.get(timeout=0.2)
                if ev is None:
                    continue
                if ev.type in (st.ADDED, st.MODIFIED):
                    self._note(ev.obj)
        finally:
            w.stop()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5)

    def double_bound(self) -> Dict[str, set]:
        with self._mu:
            return {
                k: set(v) for k, v in self.bound_nodes.items() if len(v) > 1
            }

    def first_running(self, key: str) -> Optional[float]:
        with self._mu:
            return self.running_at.get(key)


class FleetHarness:
    """The first-class hollow-node fleet driver: a HollowCluster plus a
    sustained pod-lifecycle soak with SLO-style reporting.

    ``soak`` runs rounds of: create `round_pods` pods spread across
    `namespaces` (so they hash across store shards), bind each
    namespace's slice through its own ``update_wave`` sub-wave — the
    sub-waves commit CONCURRENTLY, the binder-overlap shape the sharded
    store exists for — wait for the hollow kubelets to run every pod
    (recording per-pod create→Running latency), then delete the round.
    The audit watcher independently verifies no pod is ever bound to
    two nodes and no created pod is lost."""

    def __init__(
        self,
        store: st.Store,
        n_nodes: int,
        namespaces: int = 8,
        heartbeat_interval: float = 30.0,
        bind_concurrency: int = 4,
        zones: int = 16,
    ):
        self.store = store
        self.namespaces = [f"fleet-{i}" for i in range(namespaces)]
        self.hollow = HollowCluster(
            store, n_nodes,
            zones=zones,
            heartbeat_interval=heartbeat_interval,
            run_pods=True,
        )
        self._pool = ThreadPoolExecutor(
            max_workers=max(1, bind_concurrency),
            thread_name_prefix="fleet-bind",
        )
        self.audit: Optional[_LifecycleAudit] = None

    def start(self) -> "FleetHarness":
        self.audit = _LifecycleAudit(self.store)
        self.hollow.start()
        return self

    def stop(self) -> None:
        self.hollow.stop()
        if self.audit is not None:
            self.audit.stop()
        self._pool.shutdown(wait=False)

    # -- the sustained lifecycle soak --------------------------------------

    def _bind_round(self, keys: List[tuple]) -> int:
        """Bind one round's pods round-robin onto hollow nodes: one
        update_wave sub-wave per namespace (each a single-shard atomic
        transaction), committed concurrently on the pool."""
        n_nodes = self.hollow.n_nodes
        by_ns: Dict[str, List[tuple]] = defaultdict(list)
        for idx, (name, ns) in enumerate(keys):
            by_ns[ns].append((name, f"hollow-{(idx * 131) % n_nodes}"))

        def bind_ns(ns, entries):
            def mutator(node_name):
                def mutate(pod) -> None:
                    if pod.spec.node_name and pod.spec.node_name != node_name:
                        raise st.Conflict(
                            f"pod already bound to {pod.spec.node_name}"
                        )
                    pod.spec.node_name = node_name
                return mutate

            applied, errors = self.store.update_wave(
                "Pod",
                [(name, ns, mutator(node)) for name, node in entries],
            )
            return len(applied)

        futures = [
            self._pool.submit(bind_ns, ns, entries)
            for ns, entries in by_ns.items()
        ]
        return sum(f.result() for f in futures)

    def soak(
        self,
        total_pods: int,
        round_pods: int = 1024,
        cpu_milli: int = 50,
        round_timeout: float = 60.0,
    ) -> Dict[str, object]:
        """Run the sustained lifecycle soak; returns the SLO report."""
        assert self.audit is not None, "start() the harness first"
        latencies: List[float] = []
        lost: List[str] = []
        created = 0
        rounds = 0
        bind_s = 0.0
        t0 = time.perf_counter()
        while created < total_pods:
            n = min(round_pods, total_pods - created)
            keys = []
            t_create = time.perf_counter()
            for k in range(n):
                i = created + k
                ns = self.namespaces[i % len(self.namespaces)]
                pod = (
                    make_pod(f"soak-{i}")
                    .req(cpu_milli=cpu_milli, mem=8 * MI)
                    .obj()
                )
                pod.meta.namespace = ns
                self.store.create(pod)
                keys.append((f"soak-{i}", ns))
            created += n
            rounds += 1
            t_bind = time.perf_counter()
            self._bind_round(keys)
            bind_s += time.perf_counter() - t_bind
            # wait for the hollow kubelets: every pod of the round must
            # reach Running inside the round budget or count as lost
            deadline = time.monotonic() + round_timeout
            pending = {f"{ns}/{name}" for name, ns in keys}
            while pending and time.monotonic() < deadline:
                done = {
                    k for k in pending
                    if self.audit.first_running(k) is not None
                }
                pending -= done
                if pending:
                    time.sleep(0.01)
            for name, ns in keys:
                key = f"{ns}/{name}"
                at = self.audit.first_running(key)
                if at is None:
                    lost.append(key)
                else:
                    latencies.append(at - t_create)
            # the delete half of the lifecycle: the round leaves the
            # store (sustained churn, not unbounded growth)
            for name, ns in keys:
                try:
                    self.store.delete("Pod", name, ns)
                except st.NotFound:
                    pass
        wall = time.perf_counter() - t0
        pct = percentiles(latencies)
        return {
            "nodes": self.hollow.n_nodes,
            "pods": created,
            "rounds": rounds,
            "soak_wall_s": round(wall, 4),
            "lifecycle_pods_per_s": round(created / wall, 1) if wall else 0.0,
            "lifecycle_p50_ms": round(pct["p50"] * 1000, 2),
            "lifecycle_p90_ms": round(pct["p90"] * 1000, 2),
            "lifecycle_p99_ms": round(pct["p99"] * 1000, 2),
            "lost_pods": len(lost),
            "double_bound_pods": len(self.audit.double_bound()),
            # wall share each round spent inside the concurrent
            # per-shard bind sub-waves (the commit half of the step)
            "bind_s_total": round(bind_s, 4),
            "commit_share_per_step": round(bind_s / wall, 4) if wall else 0.0,
            "heartbeat_waves": self.hollow.heartbeat_waves,
            "heartbeats": self.hollow.heartbeats,
        }
