#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served scheduling path
still starts, compiles and answers correctly on the chip.

One process, no arguments, every input generated from a fixed seed.  It
refuses to run unless ``jax.devices()[0].platform == "tpu"``;
``--rehearse-cpu`` runs the SAME code at toy sizes as a pre-flight in a
CPU-only sandbox and says in its output that it is not a chip result.

  stage 1  the served path at scheduler_perf's 5000Nodes scale: journaled
           8-shard Store, kubemark.HollowCluster, Scheduler(batch_size=
           1024).start(), warmup, then >=5,000 pods through store.create
           in phases that dispatch all three solver routes on the device
           (trickle -> greedy scan; zone-spread and hostname anti-affinity
           bursts from perf/config/ -> wavefront; bulk + gang burst ->
           auction); flush, stop, close, recover a fresh Store from the
           journal.
  stage 2  the paper's size: BASELINE.json config 5 — 50,000 nodes /
           10,000 pods in 100 gangs, solved on the device at the
           65,536 x 16,384 buckets; peak device bytes printed.
  stage 3  kernel census at the 8,192-row node bucket: the batched
           PostFilter dry-run (high-priority pods into a full pool), a
           slice carve-out (a shaped gang on labelled slice nodes) and
           the mirror/partials grow + shrink (a NodeGroupScaler step
           across the bucket boundary and back), each through the live
           Scheduler.
  parity   a seeded one-batch solve per route against testing/oracle.py
           (greedy and wavefront bit-for-bit; auction by validity plus
           gang/feasibility agreement) — where an inexact f32 division
           on the device would show.

Every check on placements is independent of the solver (capacity, skew,
anti-affinity, gangs whole, bound exactly once, journal recovery equal).
Zero tolerance, printed and asserted: breaker trips / host fallbacks,
sharded fallbacks, prewarm compile errors, ERROR-level log records, and
every result array living on the expected platform.  Timings are printed
with the device named and are not claims.

The full summary (stages, facts, compile census, ``"claim": null``) is
printed as the line before last, prefixed ``chip_smoke: summary``.  The
last line of stdout is the verdict alone, one JSON object
``{"ok": ..., "device": {"platform", "kind", "count"}}`` with the device
as JAX reports it; exit code 0 only if every check passed.
"""

from __future__ import annotations

import argparse
import gc
import json
import logging
import os
import shutil
import sys
import tempfile
import threading
import time
from collections import Counter, defaultdict
from types import SimpleNamespace

SEED = 21
STAGES = ("1", "2", "3", "parity")

# the sizes a chip run uses: upstream's supported cluster size for the
# served path, the paper's for the largest bucket
CHIP = SimpleNamespace(
    nodes=5_000, warm_pods=1024, trickle=(3, 8), spread=(3, 400),
    anti=(2, 400), bulk=1_700, gangs=(20, 64), min_pods=5_000,
    s2_nodes=50_000, s2_pods=10_000, s2_gangs=100,
    c_slices=64, c_dims=(4, 4, 4), c_small=3_900, c_preemptors=8,
    c_shape=(2, 2, 2), c_asg=400, c_bucket=8_192,
    p_nodes=5_000, p_pods=512, wait_s=900.0,
)
# --rehearse-cpu: same phases, tens of nodes
TOY = SimpleNamespace(
    nodes=96, warm_pods=16, trickle=(2, 4), spread=(1, 96), anti=(1, 80),
    bulk=96, gangs=(2, 8), min_pods=280,
    s2_nodes=96, s2_pods=64, s2_gangs=4,
    c_slices=2, c_dims=(2, 2, 2), c_small=40, c_preemptors=2,
    c_shape=(2, 2, 1), c_asg=12, c_bucket=64,
    p_nodes=40, p_pods=48, wait_s=300.0,
)


def say(msg: str) -> None:
    print(msg, flush=True)


class Smoke:
    """Shared instruments: the failure list, the per-batch solve log,
    compile accounting and the zero-tolerance sweep."""

    def __init__(self, sizes, device: dict):
        self.z = sizes
        self.platform = device["platform"]
        # a host with several chips runs stage 1 sharded over all of
        # them (SchedulerConfiguration(mesh_devices=N)) and asserts N
        # distinct devices hold shards; one chip is the normal path
        self.mesh_devices = (
            device["count"]
            if device["platform"] == "tpu" and device["count"] > 1 else 0
        )
        self.failures: list = []
        self.facts: dict = {}
        self._phase = "init"
        self._phase_t0 = time.perf_counter()
        self.phase_s: dict = {}     # phase -> wall seconds
        self.solves: list = []      # (phase, route, pods, platforms)
        self._swept = 0             # solves already judged by a sweep
        self.adopted: list = []     # (label, TPUBatchScheduler)
        self.compiles = defaultdict(lambda: [0, 0.0])  # fun -> [n, secs]
        self.cache_events: Counter = Counter()
        self.errors: list = []      # ERROR+ records from kubernetes_tpu
        self._mu = threading.Lock()

    @property
    def phase(self) -> str:
        return self._phase

    @phase.setter
    def phase(self, name: str) -> None:
        now = time.perf_counter()
        self.phase_s[self._phase] = round(
            self.phase_s.get(self._phase, 0.0) + now - self._phase_t0, 2
        )
        self._phase, self._phase_t0 = name, now

    # -- checks -----------------------------------------------------------

    def check(self, ok: bool, what: str) -> bool:
        if not ok:
            self.failures.append(what)
            say(f"FAIL: {what}")
        return ok

    def wait(self, pred, what: str, timeout: float = None) -> None:
        deadline = time.monotonic() + (timeout or self.z.wait_s)
        while not pred():
            if time.monotonic() > deadline:
                raise TimeoutError(f"{self.phase}: timed out waiting for {what}")
            time.sleep(0.02)

    # -- instruments ------------------------------------------------------

    def install(self):
        import jax.monitoring as mon

        smoke = self

        class Capture(logging.Handler):
            def emit(self, record):
                smoke.errors.append(
                    f"{record.name}: {record.getMessage()}"
                )

        self._log_handler = Capture(level=logging.ERROR)
        logging.getLogger("kubernetes_tpu").addHandler(self._log_handler)

        def on_duration(event, secs, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                with smoke._mu:
                    ent = smoke.compiles[kw.get("fun_name", "?")]
                    ent[0] += 1
                    ent[1] += secs

        def on_event(event, **kw):
            if event.startswith("/jax/compilation_cache/"):
                with smoke._mu:
                    smoke.cache_events[event.rsplit("/", 1)[1]] += 1

        self._listeners = (on_duration, on_event)
        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    def uninstall(self):
        import jax.monitoring as mon

        logging.getLogger("kubernetes_tpu").removeHandler(self._log_handler)
        mon.unregister_event_duration_listener(self._listeners[0])
        mon.unregister_event_listener(self._listeners[1])

    def compile_totals(self):
        with self._mu:
            return (
                sum(n for n, _ in self.compiles.values()),
                sum(s for _, s in self.compiles.values()),
            )

    def adopt(self, label: str, tpu) -> None:
        """Log every batch this solver finalizes (route, size, where the
        result arrays live) and register it for the zero-tolerance
        sweep.  Observation only: the wrapped call is the one the
        Scheduler makes."""
        inner = tpu.finalize_pending

        def finalize(pending, ds, **kw):
            names = inner(pending, ds, **kw)
            eff = tpu.last_solve
            if eff is None:
                return names
            if eff.result is None:  # HostSolve
                rec = (self.phase, "host", len(pending), ("host",))
            else:
                rec = (
                    self.phase, eff.meta.route, len(pending),
                    tuple(sorted(
                        {d.platform for d in eff.result.assignment.devices()}
                    )),
                )
            with self._mu:
                self.solves.append(rec)
            return names

        tpu.finalize_pending = finalize
        self.adopted.append((label, tpu))

    def sweep(self, label: str) -> None:
        """Zero tolerance on every adopted solver, then close its prewarm
        pool (a compile thread alive at interpreter teardown aborts the
        process)."""
        for name, tpu in self.adopted:
            b = tpu.breaker
            pool = tpu.prewarm_pool
            facts = {
                "breaker.trips": b.trips,
                "breaker.fallback_count": b.fallback_count(),
                "breaker.state": b.state,
                "sharded_fallbacks": tpu.sharded_fallbacks,
                "prewarm_pool": None if pool is None else {
                    "compiled": pool.compiled, "errors": pool.errors,
                },
            }
            say(f"[{label}] {name}: {json.dumps(facts)}")
            self.check(b.trips == 0, f"{name}: breaker.trips == {b.trips}")
            self.check(
                b.fallback_count() == 0,
                f"{name}: breaker.fallback_count == {b.fallback_count()} "
                "(batches were solved on the host, not the device)",
            )
            self.check(b.state == b.CLOSED, f"{name}: breaker {b.state}")
            self.check(
                tpu.sharded_fallbacks == 0,
                f"{name}: sharded_fallbacks == {tpu.sharded_fallbacks}",
            )
            if pool is not None:
                pool.close(timeout=300.0)
                alive = pool._thread is not None and pool._thread.is_alive()
                self.check(not alive, f"{name}: prewarm pool did not close")
                self.check(
                    pool.errors == 0,
                    f"{name}: prewarm_pool.errors == {pool.errors}",
                )
        self.adopted.clear()
        with self._mu:
            new, self._swept = self.solves[self._swept:], len(self.solves)
        bad = [s for s in new if s[3] != (self.platform,)]
        self.check(
            not bad,
            f"{label}: {len(bad)} batch(es) not solved on "
            f"{self.platform}: {bad[:3]}",
        )
        errors, self.errors[:] = list(self.errors), []
        self.check(
            not errors,
            f"{label}: {len(errors)} ERROR-level log record(s): "
            f"{errors[:3]}",
        )

    def routes(self, phases) -> Counter:
        with self._mu:
            return Counter(r for ph, r, _, _ in self.solves if ph in phases)

    def peak_bytes(self):
        import jax

        stats = jax.devices()[0].memory_stats() or {}
        return stats.get("peak_bytes_in_use")


# -- placement checks, independent of the solver ---------------------------


def check_placement(smoke: Smoke, label: str, nodes, pods) -> None:
    """Hard constraints of a bound set, from the API objects alone: no
    node over allocatable or its pod cap, DoNotSchedule skew within
    maxSkew, no required anti-affinity pair sharing a domain, gangs
    whole."""
    from kubernetes_tpu.api import types as api

    node_by = {n.meta.name: n for n in nodes}
    bound = [p for p in pods if p.spec.node_name]
    stray = [p.meta.name for p in bound if p.spec.node_name not in node_by]
    smoke.check(not stray, f"{label}: pods bound to unknown nodes {stray[:3]}")
    bound = [p for p in bound if p.spec.node_name in node_by]
    used = defaultdict(Counter)
    by_node = defaultdict(list)
    for p in bound:
        used[p.spec.node_name].update(p.resource_requests())
        by_node[p.spec.node_name].append(p)
    over = []
    for name, held in by_node.items():
        alloc = node_by[name].status.allocatable
        if len(held) > min(alloc.get(api.PODS, 110), 110):
            over.append((name, "pods", len(held)))
        over.extend(
            (name, k, v) for k, v in used[name].items()
            if v > alloc.get(k, 0)
        )
    smoke.check(not over, f"{label}: nodes over allocatable {over[:3]}")

    # DoNotSchedule spread: pods of one namespace matching one selector
    # all carry the same constraint here and none is ever deleted, so
    # the final global skew is bounded by maxSkew
    spreads = {}
    for p in bound:
        for c in p.spec.topology_spread_constraints:
            if c.when_unsatisfiable == "DoNotSchedule":
                sel = tuple(sorted(c.label_selector.match_labels.items()))
                spreads[(p.meta.namespace, c.topology_key, sel)] = c
    for (ns, key, _), c in spreads.items():
        counts = Counter({
            n.meta.labels[key]: 0 for n in nodes if key in n.meta.labels
        })
        for q in bound:
            if q.meta.namespace == ns and c.label_selector.matches(
                q.meta.labels
            ):
                counts[node_by[q.spec.node_name].meta.labels[key]] += 1
        skew = max(counts.values()) - min(counts.values())
        smoke.check(
            skew <= c.max_skew,
            f"{label}: spread skew {skew} > maxSkew {c.max_skew} in "
            f"{ns} over {key}: {dict(counts)}",
        )

    # required anti-affinity: no matching pod shares the term's domain
    domains: dict = {}
    clashes = []
    for p in bound:
        aff = p.spec.affinity
        terms = (
            aff.pod_anti_affinity.required
            if aff is not None and aff.pod_anti_affinity is not None else []
        )
        for t in terms:
            idx = domains.get(t.topology_key)
            if idx is None:
                idx = domains[t.topology_key] = defaultdict(list)
                for q in bound:
                    v = node_by[q.spec.node_name].meta.labels.get(
                        t.topology_key
                    )
                    if v is not None:
                        idx[v].append(q)
            here = node_by[p.spec.node_name].meta.labels.get(t.topology_key)
            nss = t.namespaces or [p.meta.namespace]
            clashes.extend(
                (p.meta.name, q.meta.name, here) for q in idx.get(here, ())
                if q is not p and q.meta.namespace in nss
                and t.label_selector.matches(q.meta.labels)
            )
    smoke.check(not clashes, f"{label}: anti-affinity violated {clashes[:3]}")

    gangs = defaultdict(list)
    for p in pods:
        if p.spec.scheduling_group:
            gangs[(p.meta.namespace, p.spec.scheduling_group)].append(
                bool(p.spec.node_name)
            )
    split = [g for g, m in gangs.items() if any(m) and not all(m)]
    smoke.check(not split, f"{label}: gangs partially bound {split[:3]}")


# -- pod and node generators ------------------------------------------------


def _template(name: str) -> dict:
    import yaml

    import kubernetes_tpu.perf as perf

    path = os.path.join(os.path.dirname(perf.__file__), "config", name)
    with open(path) as f:
        return yaml.safe_load(f)


def _from_template(template: dict, name: str, namespace: str):
    from kubernetes_tpu.api import kubeyaml

    meta = dict(template.get("metadata") or {}, name=name, namespace=namespace)
    return kubeyaml.pod_from_dict(dict(template, metadata=meta))


def submit(
    smoke: Smoke, store, sched, created: list, pods, what: str,
    timeout: float = None,
) -> None:
    """Create pods through the API and wait until the scheduler's own
    informer shows every one of them bound."""
    for p in pods:
        store.create(p)
    created.extend(pods)
    want = {(p.meta.namespace, p.meta.name) for p in pods}
    smoke.wait(
        lambda: want <= {
            (q.meta.namespace, q.meta.name)
            for q in sched.informers.informer("Pod").list()
            if q.spec.node_name
        },
        f"{what} to bind", timeout=timeout,
    )


def _team(i: int) -> str:
    # 14 tenant namespaces + the template's sched-0/sched-1 = 16: the
    # stream must spread over the store's shards or every bind wave
    # hashes onto one and the sub-wave commit paths never run
    return f"team-{i % 14}"


# -- stage 1: the served path -------------------------------------------------


def stage1(smoke: Smoke) -> None:
    import jax

    from kubernetes_tpu import kubemark
    from kubernetes_tpu.api import framing
    from kubernetes_tpu.api import store as st
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.scheduler.config import SchedulerConfiguration
    from kubernetes_tpu.testing.wrappers import MI, make_pod

    z = smoke.z
    t0 = time.perf_counter()
    basic_t = _template("pod-default.yaml")
    spread_t = _template("pod-with-topology-spreading.yaml")
    anti_t = _template("pod-with-pod-anti-affinity.yaml")
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    journal = os.path.join(workdir, "cluster.jsonl")
    store = st.Store(journal_path=journal, journal_sync="interval", shards=8)
    audit = kubemark._LifecycleAudit(store)
    hollow = kubemark.HollowCluster(store, z.nodes).start()
    mesh_n = smoke.mesh_devices
    sched = Scheduler(
        store, batch_size=1024,
        config=SchedulerConfiguration(mesh_devices=mesh_n) if mesh_n else None,
    )
    smoke.adopt("stage1 scheduler", sched.tpu)
    created: list = []

    def burst(pods, what: str) -> None:
        submit(smoke, store, sched, created, pods, what)

    try:
        sched.start()
        smoke.wait(
            lambda: len(sched.tpu.state._rows) >= z.nodes,
            "the informer to deliver every node",
        )
        smoke.phase = "warmup"
        warm_s = sched.warmup([
            _from_template(basic_t, f"warm-{i}", _team(i))
            for i in range(z.warm_pods)
        ])
        sched.wait_for_idle(timeout=z.wait_s)
        compiles_warm, secs_warm = smoke.compile_totals()
        say(
            f"[stage1] warmup {warm_s:.1f}s: {compiles_warm} compiles, "
            f"{secs_warm:.1f}s in the backend"
        )

        smoke.phase = "trickle"
        groups, per = z.trickle
        for g in range(groups):
            burst([
                _from_template(basic_t, f"trickle-{g}-{i}", _team(g + i))
                for i in range(per)
            ], f"trickle group {g}")

        smoke.phase = "spread"
        bursts, per = z.spread
        for b in range(bursts):
            burst([
                _from_template(spread_t, f"spread-{b}-{i}", _team(i))
                for i in range(per)
            ], f"spread burst {b}")

        smoke.phase = "anti-affinity"
        bursts, per = z.anti
        for b in range(bursts):
            burst([
                _from_template(anti_t, f"anti-{b}-{i}", f"sched-{i % 2}")
                for i in range(per)
            ], f"anti-affinity burst {b}")

        smoke.phase = "bulk"
        burst([
            _from_template(basic_t, f"bulk-{i}", _team(i))
            for i in range(z.bulk)
        ], "bulk")

        smoke.phase = "gangs"
        n_gangs, members = z.gangs
        burst([
            make_pod(f"gang-{g}-{i}", namespace=_team(g))
            .req(cpu_milli=100, mem=500 * MI)
            .group(f"gang-{g}", size=members)
            .obj()
            for g in range(n_gangs) for i in range(members)
        ], "gang burst")

        smoke.phase = "stage1-teardown"
        smoke.check(sched.flush_binds(timeout=120), "stage1: flush_binds drained")
        mirror = sched.tpu._mirror.stats()
        partials = sched.tpu._partials.stats()
        bucket = sched.tpu.state.node_axis_bucket
        resident = sched.tpu._mirror._dev
        shard_devices = (
            0 if resident is None
            else len(resident.allocatable.sharding.device_set)
        )
    finally:
        sched.stop()
        hollow.stop()
        audit.stop()

    loop_phases = {"trickle", "spread", "anti-affinity", "bulk", "gangs"}
    routes = smoke.routes(loop_phases)
    compiles_all, secs_all = smoke.compile_totals()
    pods, _ = store.list("Pod")
    nodes, _ = store.list("Node")
    store.close()
    smoke.check(
        len(created) >= z.min_pods,
        f"stage1: only {len(created)} pods created (< {z.min_pods})",
    )
    smoke.check(
        len(pods) == len(created),
        f"stage1: {len(pods)} pods in the store, {len(created)} created",
    )
    unbound = [p.meta.name for p in pods if not p.spec.node_name]
    smoke.check(not unbound, f"stage1: unbound pods {unbound[:3]}")
    doubles = audit.double_bound()
    smoke.check(
        not doubles, f"stage1: pods bound twice {list(doubles.items())[:3]}"
    )
    check_placement(smoke, "stage1", nodes, pods)
    for route in ("greedy", "wavefront", "auction"):
        smoke.check(
            routes[route] >= 1,
            f"stage1: route {route} never dispatched by the loop "
            f"(saw {dict(routes)})",
        )
    smoke.check(mirror["delta_syncs"] >= 1, f"stage1: mirror {mirror}")
    smoke.check(
        partials["delta_syncs"] >= 1 and partials["hit_rows_total"] > 0,
        f"stage1: partials warm path never ran {partials}",
    )
    if mesh_n:
        smoke.check(
            shard_devices == mesh_n,
            f"stage1: mirror sharded over {shard_devices} device(s), "
            f"mesh_devices={mesh_n}",
        )

    # acked writes read back: a fresh store recovered from the journal
    # returns the same bindings
    want = {
        (p.meta.namespace, p.meta.name): p.spec.node_name for p in pods
    }
    recovered = st.Store(journal_path=journal)
    got = {
        (p.meta.namespace, p.meta.name): p.spec.node_name
        for p in recovered.list("Pod")[0]
    }
    recovered.close()
    shutil.rmtree(workdir, ignore_errors=True)
    smoke.check(
        got == want,
        f"stage1: recovered store differs on "
        f"{len(set(want.items()) ^ set(got.items()))} binding(s)",
    )
    smoke.facts["stage1"] = {
        "nodes": len(nodes), "node_bucket": bucket,
        "pods_created": len(created), "pods_bound": len(pods) - len(unbound),
        "routes": dict(routes), "recovered_equal": got == want,
        "mirror": mirror, "partials": partials,
        "shard_devices": shard_devices,
        "warmup_s": round(warm_s, 2),
        "compiles_in_warmup": compiles_warm,
        "compiles_after_warmup": compiles_all - compiles_warm,
        "compile_s_after_warmup": round(secs_all - secs_warm, 2),
        "framer_native": framing.native_available(),
        "peak_bytes_in_use": smoke.peak_bytes(),
        "wall_s": round(time.perf_counter() - t0, 2),
        "device": jax.devices()[0].device_kind,
    }
    smoke.sweep("stage1")
    say(f"[stage1] {json.dumps(smoke.facts['stage1'])}")


# -- stage 2: the paper's size ------------------------------------------------


def stage2(smoke: Smoke) -> None:
    import numpy as np

    from kubernetes_tpu.models.batch_scheduler import TPUBatchScheduler
    from kubernetes_tpu.testing.wrappers import GI, MI, make_node, make_pod

    z = smoke.z
    t0 = time.perf_counter()
    smoke.phase = "stage2"
    # BASELINE.json config 5's generator
    nodes = [
        make_node(f"node-{i}")
        .capacity(cpu_milli=32000, mem=64 * GI, pods=110)
        .zone(f"zone-{i % 10}")
        .obj()
        for i in range(z.s2_nodes)
    ]

    def mk(tag):
        rng = np.random.default_rng(5)
        return [
            make_pod(f"c5-{tag}-{i}")
            .req(
                cpu_milli=int(rng.choice([100, 250, 500, 1000, 2000])),
                mem=int(rng.choice([128, 256, 512, 1024, 2048])) * MI,
            )
            .group(f"gang-{i % z.s2_gangs}")
            .obj()
            for i in range(z.s2_pods)
        ]

    sched = TPUBatchScheduler(mode="auto")
    smoke.adopt("stage2 solver", sched)
    for nd in nodes:
        sched.add_node(nd)
    t1 = time.perf_counter()
    sched.schedule_pending(mk("warmup"))  # compiles
    first_s = time.perf_counter() - t1
    pods = mk("run0")
    t1 = time.perf_counter()
    names = sched.schedule_pending(pods)
    step_s = time.perf_counter() - t1
    for p, name in zip(pods, names):
        p.spec.node_name = name or ""
    placed = sum(n is not None for n in names)
    smoke.check(
        placed == len(pods), f"stage2: placed {placed}/{len(pods)}"
    )
    check_placement(smoke, "stage2", nodes, pods)
    routes = smoke.routes({"stage2"})
    smoke.check(
        routes["auction"] >= 2, f"stage2: expected the auction, saw {dict(routes)}"
    )
    shape = sched.last_result.assignment.shape[0]
    smoke.facts["stage2"] = {
        "nodes": len(nodes), "node_bucket": sched.state.node_axis_bucket,
        "pods": len(pods), "pod_bucket": int(shape), "placed": placed,
        "first_step_s": round(first_s, 2), "warm_step_s": round(step_s, 3),
        "peak_bytes_in_use": smoke.peak_bytes(),
        "wall_s": round(time.perf_counter() - t0, 2),
    }
    smoke.sweep("stage2")
    say(f"[stage2] {json.dumps(smoke.facts['stage2'])}")


# -- stage 3: kernel census ---------------------------------------------------


def stage3(smoke: Smoke) -> None:
    from kubernetes_tpu import kubemark
    from kubernetes_tpu.api import store as st
    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.testing.wrappers import GI, MI, make_node, make_pod

    z = smoke.z
    t0 = time.perf_counter()
    smoke.phase = "census-setup"
    store = st.Store(shards=8)
    dx, dy, dz = z.c_dims
    for s in range(z.c_slices):
        for x in range(dx):
            for y in range(dy):
                for k in range(dz):
                    store.create(
                        make_node(f"slice-{s}-{x}{y}{k}")
                        .capacity(cpu_milli=4000, mem=8 * GI, pods=16)
                        .label(api.LABEL_TPU_SLICE, f"slice-{s}")
                        .label(api.LABEL_TPU_TOPOLOGY, f"{dx}x{dy}x{dz}")
                        .label(api.LABEL_TPU_COORDS, f"{x},{y},{k}")
                        .obj()
                    )
    for i in range(z.c_small):
        store.create(
            make_node(f"small-{i}")
            .capacity(cpu_milli=1000, mem=4 * GI, pods=110)
            .zone(f"zone-{i % 8}")
            .label("pool", "small")
            .obj()
        )
    base_nodes = z.c_slices * dx * dy * dz + z.c_small
    sched = Scheduler(store, batch_size=1024)
    smoke.adopt("stage3 scheduler", sched.tpu)
    created: list = []

    def burst(pods, what: str, timeout: float = None) -> None:
        submit(smoke, store, sched, created, pods, what, timeout)

    def small(i: int, tag: str, priority: int):
        return (
            make_pod(f"{tag}-{i}", namespace=_team(i))
            .req(cpu_milli=900, mem=500 * MI)
            .priority(priority)
            .node_selector_kv("pool", "small")
            .obj()
        )

    def nudge(tag: str, n: int = 2) -> None:
        """A small bound wave: moves the cluster generation and runs one
        encode (mirror + partials sync) at the current node bucket."""
        burst([
            make_pod(f"{tag}-{i}", namespace=_team(i))
            .req(cpu_milli=10, mem=MI)
            .obj()
            for i in range(n)
        ], tag)

    try:
        sched.start()
        smoke.wait(
            lambda: len(sched.tpu.state._rows) >= base_nodes,
            "the informer to deliver every node",
        )
        # (a) the batched PostFilter: fill the small pool with
        # low-priority pods, then send high-priority pods into it, with
        # nothing in between.  The template is warmed first, as
        # perf/runner.py does before every measured op.  What still
        # compiles inside the fill's cycles must not read as overload to
        # the scheduler's ladder, and where the burst's real staging
        # time does raise it, the preemptors it sheds must come back by
        # themselves in a cluster gone idle: they get less than the
        # 300 s unschedulable flush to bind
        smoke.phase = "census-warmup"
        sched.warmup([small(i, "warm", 0) for i in range(z.warm_pods)])
        smoke.phase = "census-fill"
        fill = [small(i, "fill", 0) for i in range(z.c_small)]
        burst(fill, "the pool fill")
        smoke.phase = "census-preempt"
        overload0 = sched.overload.level()
        hi = [small(i, "hi", 10) for i in range(z.c_preemptors)]
        t_hi = time.perf_counter()
        burst(
            hi, f"the preemptors (overload level {overload0} after the fill)",
            timeout=min(z.wait_s, 240.0),
        )
        preempt_s = time.perf_counter() - t_hi
        sched.flush_binds(timeout=60)
        m = sched.metrics
        survivors = {
            p.meta.name for p in store.list("Pod")[0]
            if p.meta.name.startswith("fill-")
        }
        evicted = len(fill) - len(survivors)
        smoke.check(
            evicted == len(hi),
            f"stage3: {evicted} victims for {len(hi)} preemptors",
        )
        smoke.check(
            m.preemption_batch_size.n >= 1,
            "stage3: the batched PostFilter dry-run never ran",
        )
        created[:] = [
            p for p in created
            if not p.meta.name.startswith("fill-")
            or p.meta.name in survivors
        ]

        # (b) a shaped gang on the labelled slice nodes
        smoke.phase = "census-carveout"
        sx, sy, sz = z.c_shape
        shaped = []
        for i in range(sx * sy * sz):
            p = (
                make_pod(f"shaped-{i}", namespace="team-0")
                .req(cpu_milli=100, mem=64 * MI)
                .group("shaped", size=sx * sy * sz)
                .obj()
            )
            p.spec.tpu_topology = f"{sx}x{sy}x{sz}"
            shaped.append(p)
        burst(shaped, "the shaped gang")
        cells = []
        for p in store.list("Pod")[0]:
            if p.meta.name.startswith("shaped-"):
                labels = store.get("Node", p.spec.node_name).meta.labels
                cells.append((
                    labels.get(api.LABEL_TPU_SLICE),
                    api.parse_coords(labels.get(api.LABEL_TPU_COORDS, "")),
                ))
        one_slice = len({s for s, _ in cells}) == 1 and cells[0][0]
        extent = sorted(
            max(c[a] for _, c in cells) - min(c[a] for _, c in cells) + 1
            for a in range(3)
        ) if one_slice else None
        smoke.check(
            bool(one_slice) and len(set(cells)) == len(cells)
            and extent == sorted(z.c_shape),
            f"stage3: shaped gang is not one contiguous "
            f"{z.c_shape} box: {cells}",
        )
        smoke.wait(
            lambda: m.gang_contiguous_placements.total >= 1,
            "the carve-out telemetry", timeout=30,
        )

        # (c) mirror/partials grow and shrink across the bucket boundary
        smoke.phase = "census-grow"
        nudge("pre-grow")
        mirror0 = dict(sched.tpu._mirror.stats())
        partials0 = dict(sched.tpu._partials.stats())
        bucket0 = sched.tpu.state.node_axis_bucket
        scaler = kubemark.NodeGroupScaler(
            store, group="asg",
            taints=[("smoke/asg", "true", api.NO_SCHEDULE)],
        )
        scaler.scale_to(z.c_asg)
        smoke.wait(
            lambda: len(sched.tpu.state._rows) >= base_nodes + z.c_asg,
            "the scaled-up nodes",
        )
        nudge("grown")
        bucket1 = sched.tpu.state.node_axis_bucket
        mirror1 = dict(sched.tpu._mirror.stats())
        partials1 = dict(sched.tpu._partials.stats())
        smoke.phase = "census-shrink"
        scaler.scale_to(0)
        smoke.wait(
            lambda: len(sched.tpu.state._rows) <= base_nodes,
            "the scaled-down nodes",
        )
        # the shrink is lazy: the bucket falls only after the dwell
        for k in range(sched.config.bucket_shrink_dwell + 4):
            nudge(f"dwell-{k}")
            if sched.tpu.state.node_axis_bucket == bucket0:
                break
        nudge("shrunk")
        bucket2 = sched.tpu.state.node_axis_bucket
        mirror2 = dict(sched.tpu._mirror.stats())
        smoke.check(
            bucket0 == z.c_bucket and bucket1 == 2 * bucket0
            and bucket2 == bucket0,
            f"stage3: node bucket went {bucket0} -> {bucket1} -> {bucket2}",
        )
        smoke.check(
            mirror1["grow_syncs"] == mirror0["grow_syncs"] + 1
            and mirror2["resync_total"] == mirror0["resync_total"],
            f"stage3: grow/shrink was not in place: {mirror0} -> "
            f"{mirror1} -> {mirror2}",
        )
        smoke.check(
            partials1["grows"] == partials0["grows"] + 1,
            f"stage3: partials did not grow in place: {partials0} -> "
            f"{partials1}",
        )
        sched.flush_binds(timeout=60)
    finally:
        sched.stop()
    smoke.phase = "census-checks"
    pods, _ = store.list("Pod")
    nodes, _ = store.list("Node")
    store.close()
    unbound = [p.meta.name for p in pods if not p.spec.node_name]
    smoke.check(
        not unbound and len(pods) == len(created),
        f"stage3: {len(pods)} pods for {len(created)} live creates, "
        f"unbound {unbound[:3]}",
    )
    check_placement(smoke, "stage3", nodes, pods)
    smoke.facts["stage3"] = {
        "nodes": len(nodes), "buckets": [bucket0, bucket1, bucket2],
        "overload_after_fill": overload0,
        "overload_shed_total": m.overload_shed_total.total,
        "preemptors": len(hi), "victims": evicted,
        "preemptors_bound_s": round(preempt_s, 2),
        "preemption_batches": m.preemption_batch_size.n,
        "carveouts": m.slice_carveouts.total,
        "contiguous_gangs": m.gang_contiguous_placements.total,
        "mirror": mirror2,
        "partials": dict(sched.tpu._partials.stats()),
        "routes": dict(smoke.routes({
            "census-fill", "census-preempt",
            "census-carveout", "census-grow", "census-shrink",
        })),
        "peak_bytes_in_use": smoke.peak_bytes(),
        "wall_s": round(time.perf_counter() - t0, 2),
    }
    smoke.sweep("stage3")
    say(f"[stage3] {json.dumps(smoke.facts['stage3'])}")


# -- parity against the oracle ------------------------------------------------


def parity(smoke: Smoke) -> None:
    import numpy as np

    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.models.batch_scheduler import TPUBatchScheduler
    from kubernetes_tpu.testing.oracle import Oracle
    from kubernetes_tpu.testing.wrappers import GI, MI, make_node, make_pod

    z = smoke.z
    t0 = time.perf_counter()
    rng = np.random.default_rng(SEED)
    zones = [f"z{i}" for i in range(8)]

    def mk_nodes():
        # uneven capacities and a seeded pre-load so the scorers divide
        # by many different allocatable/requested pairs
        nodes = []
        for i in range(z.p_nodes):
            nw = (
                make_node(f"n{i}")
                .capacity(
                    cpu_milli=int(rng.choice([8000, 16000, 32000, 48000])),
                    mem=int(rng.choice([16, 32, 64, 96])) * GI, pods=110,
                )
                .zone(zones[i % 8])
            )
            if i % 7 == 0:
                nw.taint("flaky", "true", api.PREFER_NO_SCHEDULE)
            nodes.append(nw.obj())
        return nodes

    def base_pod(name):
        return make_pod(name).req(
            cpu_milli=int(rng.choice([100, 250, 300, 500, 700, 1000, 1900])),
            mem=int(rng.choice([100, 128, 300, 512, 1000, 1024])) * MI,
        )

    def run(label, mode, pods, nodes, **kw):
        smoke.phase = f"parity-{label}"
        sched = TPUBatchScheduler(mode=mode, **kw)
        smoke.adopt(f"parity {label} solver", sched)
        for nd in nodes:
            sched.add_node(nd)
        return sched.schedule_pending(pods)

    def release_gangs(pods, names):
        groups = defaultdict(list)
        for i, p in enumerate(pods):
            if p.spec.scheduling_group:
                groups[p.spec.scheduling_group].append(i)
        for idx in groups.values():
            if any(names[i] is None for i in idx):
                for i in idx:
                    names[i] = None
        return names

    out = {}
    nodes = mk_nodes()

    # greedy scan: resources, selectors, preferred affinity, taints
    pods = []
    for i in range(z.p_pods):
        pw = base_pod(f"g{i}")
        if i % 3 == 0:
            pw.node_selector_kv(api.LABEL_ZONE, zones[i % 8])
        if i % 5 == 0:
            pw.preferred_affinity(
                10, api.LABEL_ZONE, api.OP_IN, [zones[(i + 1) % 8]]
            )
        pods.append(pw.obj())
    got = run("greedy", "greedy", pods, nodes, use_wavefront=False)
    want = Oracle(nodes).schedule(pods)
    diff = [i for i, (a, b) in enumerate(zip(got, want)) if a != b]
    smoke.check(
        not diff,
        f"parity: greedy differs from the oracle at {len(diff)} pod(s), "
        f"first {[(i, got[i], want[i]) for i in diff[:3]]}",
    )
    out["greedy"] = {
        "pods": len(pods), "placed": sum(n is not None for n in got),
        "equal": not diff,
    }

    # wavefront: zone spread + hostname anti-affinity on top
    pods = []
    for i in range(z.p_pods):
        svc = i % 6
        pw = base_pod(f"w{i}").label("app", f"svc-{svc}")
        if i % 2 == 0:
            pw.spread(2, api.LABEL_ZONE, "DoNotSchedule", {"app": f"svc-{svc}"})
        else:
            pw.pod_anti_affinity({"app": f"svc-{svc}"}, api.LABEL_HOSTNAME)
        pods.append(pw.obj())
    got = run("wavefront", "greedy", pods, nodes)
    want = Oracle(nodes).schedule(pods)
    diff = [i for i, (a, b) in enumerate(zip(got, want)) if a != b]
    smoke.check(
        not diff,
        f"parity: wavefront differs from the oracle at {len(diff)} "
        f"pod(s), first {[(i, got[i], want[i]) for i in diff[:3]]}",
    )
    out["wavefront"] = {
        "pods": len(pods), "placed": sum(n is not None for n in got),
        "equal": not diff,
    }

    # auction: gangs, one of them infeasible — validity + agreement with
    # the oracle on which gangs are placeable at all
    n_gangs = 8
    pods = []
    for i in range(z.p_pods):
        g = i % n_gangs
        pw = base_pod(f"a{i}").group(f"gang-{g}")
        if g == n_gangs - 1 and i < n_gangs:
            pw.req(cpu_milli=100_000)  # fits no node: its gang parks
        pods.append(pw.obj())
    got = run("auction", "auction", pods, nodes)
    want = release_gangs(pods, Oracle(nodes).schedule(pods))
    for p, name in zip(pods, got):
        p.spec.node_name = name or ""
    check_placement(smoke, "parity-auction", nodes, pods)
    placed_got = [n is not None for n in got]
    placed_want = [n is not None for n in want]
    smoke.check(
        placed_got == placed_want,
        f"parity: auction places {sum(placed_got)} pods, the oracle "
        f"{sum(placed_want)}; the placed sets differ",
    )
    out["auction"] = {
        "pods": len(pods), "placed": sum(placed_got),
        "oracle_placed": sum(placed_want),
        "agree": placed_got == placed_want,
    }
    want_routes = {
        "parity-greedy": "greedy", "parity-wavefront": "wavefront",
        "parity-auction": "auction",
    }
    for ph, route in want_routes.items():
        seen = smoke.routes({ph})
        smoke.check(
            set(seen) == {route},
            f"parity: {ph} solved on {dict(seen)}, expected {route}",
        )
    out["wall_s"] = round(time.perf_counter() - t0, 2)
    smoke.facts["parity"] = out
    smoke.sweep("parity")
    say(f"[parity] {json.dumps(out)}")


# -- entry --------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse-cpu", action="store_true",
        help="run the same code at toy sizes on the CPU platform "
        "(a pre-flight, never a chip result)",
    )
    ap.add_argument(
        "--stages", default=",".join(STAGES),
        help="comma-separated subset of 1,2,3,parity (default: all)",
    )
    args = ap.parse_args(argv)
    stages = [s.strip() for s in args.stages.split(",") if s.strip()]
    unknown = [s for s in stages if s not in STAGES]
    if unknown:
        ap.error(f"unknown stage(s) {unknown}; choose from {STAGES}")

    import jax

    devs = jax.devices()
    device = {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
    }
    if device["platform"] != "tpu" and not args.rehearse_cpu:
        print(
            f"chip_smoke: JAX found no TPU (devices: {device}); refusing "
            "to run.  --rehearse-cpu runs a toy-size pre-flight that is "
            "not a chip result.", file=sys.stderr,
        )
        return 2
    if args.rehearse_cpu and device["platform"] != "cpu":
        print(
            "chip_smoke: --rehearse-cpu is for a CPU-only host; run "
            "without it on the chip.", file=sys.stderr,
        )
        return 2
    if args.rehearse_cpu:
        say(
            "chip_smoke: REHEARSAL on the CPU platform at toy sizes — "
            "NOT a chip result; nothing printed below is a device figure"
        )

    import jaxlib

    from kubernetes_tpu.analysis import retrace
    from kubernetes_tpu.api import framing
    from kubernetes_tpu.utils import compilecache

    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__}
    try:
        import libtpu

        versions["libtpu"] = getattr(libtpu, "__version__", "?")
    except ImportError:
        versions["libtpu"] = None
    logging.basicConfig(
        level=logging.INFO, stream=sys.stderr,
        format="%(asctime)s %(levelname)s %(name)s: %(message)s",
    )
    cache_dir = compilecache.enable()  # idempotent: the active directory
    say(f"chip_smoke: device {json.dumps(device)} versions {json.dumps(versions)}")
    say(
        f"chip_smoke: compile cache {cache_dir} "
        f"({len(os.listdir(cache_dir)) if cache_dir and os.path.isdir(cache_dir) else 0} "
        f"entries at start); journal framer native="
        f"{framing.native_available()}"
    )

    smoke = Smoke(TOY if args.rehearse_cpu else CHIP, device)
    smoke.check(bool(cache_dir), "no compile cache directory is active")
    smoke.install()
    t0 = time.perf_counter()
    runners = {"1": stage1, "2": stage2, "3": stage3, "parity": parity}
    try:
        with retrace.tracked() as tracker:
            for s in stages:
                runners[s](smoke)
                gc.collect()  # drop the stage's device residents
    finally:
        # an exception above still closes every compile thread before
        # the interpreter tears down; the traceback is the result
        for _, tpu in smoke.adopted:
            if tpu.prewarm_pool is not None:
                tpu.prewarm_pool.close(timeout=300.0)
        smoke.uninstall()

    smoke.phase = "done"
    census = Counter(label for label, _ in tracker.traces)
    say(f"chip_smoke: executables traced per family {json.dumps(census)}")
    by_fun = sorted(
        smoke.compiles.items(), key=lambda kv: -kv[1][1]
    )
    say(
        "chip_smoke: backend compiles per jitted function (count, seconds; "
        f"device {device['kind']}) "
        + json.dumps({k: [n, round(s, 2)] for k, (n, s) in by_fun[:24]})
    )
    n_comp, s_comp = smoke.compile_totals()
    summary = {
        "ok": not smoke.failures,
        "device": device,
        "mesh_devices": smoke.mesh_devices,
        "versions": versions,
        "stages": stages,
        "rehearsal": bool(args.rehearse_cpu),
        "failures": smoke.failures,
        "compile": {
            "count": n_comp, "seconds": round(s_comp, 2),
            "cache_dir": cache_dir, "cache": dict(smoke.cache_events),
            "traces": dict(census),
        },
        "wall_s": round(time.perf_counter() - t0, 2),
        "phase_s": smoke.phase_s,
        "facts": smoke.facts,
        "claim": None,
    }
    say(f"chip_smoke: summary {json.dumps(summary)}")
    # the verdict, alone on the last line: exactly these keys
    say(json.dumps({"ok": summary["ok"], "device": device}))
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
