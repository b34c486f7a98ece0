"""The executable key is a function of the deployment, not of a batch's
composition: after ``Scheduler.warmup`` with a workload's template pods, no
batch of those pods, whatever its size, its namespaces or what is bound by
then, asks JAX to trace, build or load anything.

Which components make an executable new, and which of them ``warmup``
enumerates, is the table in docs/scheduler_loop.md ("What makes an
executable new").  What varied before the class dims got the constraint
rows' floor (vocab.pad_constraint_dim) and the coupled wave plan one row a
pod (ops.assign.wave_rows): the ``[C, N]`` statics' class dim (2, 4, ...,
32 with the namespaces in the batch and whether it had pad rows), the wave
plan's rows (8 ... P with how the namespaces interleave) and, with them,
the snapshot-unpack layout and the statics gather.
"""

import random

import pytest

from kubernetes_tpu.api import store as st
from kubernetes_tpu.api import types as api
from kubernetes_tpu.ops import assign
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.testing.wrappers import GI, MI, make_node, make_pod
from kubernetes_tpu.utils import compileclock, trace, vocab

NAMESPACES = [f"team-{i}" for i in range(16)]
BATCH = 256
SWEEP = 200


def basic_pod(name, ns):
    return make_pod(name, ns).req(cpu_milli=100, mem=500 * MI).obj()


def spread_pod(name, ns):
    # scheduler_perf's pod-with-topology-spreading.yaml
    return (
        make_pod(name, ns).labels(color="blue").req(cpu_milli=100, mem=500 * MI)
        .spread(5, api.LABEL_ZONE, "DoNotSchedule", {"color": "blue"}).obj()
    )


TEMPLATES = {"basic": basic_pod, "spread": spread_pod}


@pytest.fixture
def cluster():
    store = st.Store()
    for i in range(128):
        store.create(
            make_node(f"node-{i}").capacity(cpu_milli=64000, mem=128 * GI, pods=110)
            .zone(f"zone-{i % 8}").obj()
        )
    sched = Scheduler(store, batch_size=BATCH)
    sched.start()
    assert sched.informers.wait_for_sync()
    try:
        yield sched
    finally:
        sched.stop()


@pytest.mark.parametrize("route", ["greedy", "wavefront"])
@pytest.mark.parametrize("template", ["basic", "spread"])
def test_no_batch_composition_compiles_after_warmup(cluster, template, route):
    sched, mk, rng = cluster, TEMPLATES[template], random.Random(2027)
    tpu = sched.tpu
    tpu.use_wavefront = route == "wavefront"
    sched.warmup([mk(f"warm-{i}", NAMESPACES[i % 16]) for i in range(BATCH)])

    seen = set()
    fresh = []      # (batch, what it was) of every batch that compiled
    assumed = []
    t0 = trace.now()
    for b in range(SWEEP):
        size = rng.randint(1, BATCH)
        nss = rng.sample(NAMESPACES, rng.randint(1, 16))
        pods = [mk(f"p-{b}-{i}", rng.choice(nss)) for i in range(size)]
        mark = compileclock.events()
        names = tpu.schedule_pending(pods, lock=sched.cache.lock)
        meta = tpu.last_solve.meta
        seen.add(meta.route)
        if meta.route == "wavefront":
            # each wave runs at least a step and at most its row's lanes
            waves, steps = tpu.last_solve.wave_count, tpu.last_solve.wave_steps
            assert waves <= steps <= waves * assign.DEFAULT_WAVE_CAP
        else:
            assert tpu.last_solve.wave_steps is None
        if compileclock.events() != mark:
            fresh.append((b, size, len(nss), meta.route, meta.features.bound_spread,
                          None if meta.statics is None else tuple(meta.statics[0].shape)))
        assert all(names), "the cluster has room for every pod"
        # the first quarter solves against a cluster with nothing of the
        # template bound; then placements are assumed, a few or a wave's
        # worth, so counts, dirty rows and the bound_* bits all move
        if b >= SWEEP // 4:
            keep = rng.choice([1, 3, 17, size])
            for pod, node in list(zip(pods, names))[:keep]:
                sched.cache.assume(pod, node)
                assumed.append(pod)
    # nothing binds these: hand the assumes back (`make audit` runs this
    # file with the obligation ledger armed)
    for pod in assumed:
        sched.cache.forget(pod)
    assert fresh == []
    assert seen == ({"greedy", "wavefront"} if route == "wavefront" else {"greedy"})
    # the staging program carried cycles of the sweep (span
    # sched.encode.stage, a1 = 1) and built nothing doing it
    stage = [dict(zip(trace.SPAN_FIELDS, row))
             for row in trace.snapshot(t0, float("inf"))["spans"]]
    assert any(r["a1"] == 1.0 for r in stage if r["name"] == "sched.encode.stage")


def test_one_padding_rule_for_rows_and_classes():
    assert vocab.pad_constraint_dim(0) == 1
    assert [vocab.pad_constraint_dim(n) for n in (1, 2, 16, 17, 32, 33)] == [
        32, 32, 32, 32, 32, 64]
    # the wave plan's rows are the bucket's and the feature set's alone
    assert [assign.wave_rows(p, 32, False) for p in (8, 64, 128, 256, 512)] == [
        8, 8, 8, 8, 16]
    assert [assign.wave_rows(p, 32, True) for p in (8, 64, 128, 256, 512)] == [
        8, 64, 128, 256, 512]
    assert assign.waves_couple(assign.FeatureFlags(spread=True))
    assert not assign.waves_couple(assign.FeatureFlags(images=True))


# -- pods that stay pending: the deployment sched-perf-5000n-unschedulable -----------

PENDING_NODES = 128
PENDING_LIVE = 5 * PENDING_NODES    # bound at once at most: every node keeps 3.5 cpu free


def default_pod(name, ns):
    # scheduler_perf's pod-default.yaml
    return make_pod(name, ns).req(cpu_milli=100, mem=500 * MI).obj()


def large_pod(name, ns):
    # scheduler_perf's pod-large-cpu.yaml as upstream has it: 9 cpu, no priority
    return make_pod(name, ns).req(cpu_milli=9000, mem=500 * MI).obj()


@pytest.fixture
def small_nodes(request):
    """Nodes of node-default.yaml's size (4 cpu), 128 unless the test asks
    for another number: none holds a 9-cpu pod."""
    nodes = [
        make_node(f"node-{i}").capacity(cpu_milli=4000, mem=32 * GI, pods=110)
        .zone(f"zone-{i % 8}").obj()
        for i in range(getattr(request, "param", PENDING_NODES))
    ]
    store = st.Store()
    for node in nodes:
        store.create(node)
    sched = Scheduler(store, batch_size=BATCH)
    sched.start()
    assert sched.informers.wait_for_sync()
    try:
        yield sched, nodes
    finally:
        sched.stop()


def builds_counter():
    """Executables built or loaded (JAX's backend-compile events, which count
    cache loads) on any thread, by the jitted function's name."""
    import collections

    import jax.monitoring

    seen = collections.Counter()

    def on_duration(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen[str(kw.get("fun_name", "?"))] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return seen, lambda: jax.monitoring.unregister_event_duration_listener(on_duration)


def test_two_request_shapes_share_one_key_set_and_land_where_the_oracle_puts_them(small_nodes):
    """``warmup`` with the pods of both templates; then 200 seeded batches,
    pure measured, pure large and mixed in any proportion, 1 … 256 pods, with
    placements assumed and the oldest removed as the sweep goes: none traces,
    builds or loads anything, on any thread; every measured pod lands where
    ``testing/oracle.py`` puts it and no large pod lands.  Before the class
    dim took its floor from ``warmup``'s templates a pure batch had 2 class
    slots and a mixed one 4, and a batch of pods that fit nowhere had a wave
    plan of one row a pod (each alone in its wave): 10 of 120 such batches
    built after a mixed ``warmup`` (CPU count on the parent)."""
    from kubernetes_tpu.testing.oracle import Oracle

    (sched, nodes), rng = small_nodes, random.Random(2035)
    tpu = sched.tpu
    warm = []
    for i in range(BATCH):
        warm += [default_pod(f"warm-{i}", NAMESPACES[i % 16]),
                 large_pod(f"warm-large-{i}", NAMESPACES[i % 16])]
    builds, unregister = builds_counter()
    live = []       # bound and not removed, oldest first
    try:
        sched.warmup(warm)
        assert tpu.builder.spec_class_floor == 3        # two templates and the pad rows' slot
        warmed = sum(builds.values())
        fresh, seen, dims, plans = [], set(), set(), set()
        for b in range(SWEEP):
            kind = ("measured", "large", "mixed")[b % 3]
            size = rng.randint(1, BATCH)
            share = rng.random()
            pods = []
            for i in range(size):
                big = kind == "large" or (kind == "mixed" and rng.random() < share)
                mk = large_pod if big else default_pod
                pods.append(mk(f"{'large' if big else 'p'}-{b}-{i}", rng.choice(NAMESPACES)))
            want = Oracle(nodes, bound_pods=live).schedule(pods)
            mark, built = compileclock.events(), sum(builds.values())
            names = tpu.schedule_pending(pods, lock=sched.cache.lock)
            meta = tpu.last_solve.meta
            if compileclock.events() != mark or sum(builds.values()) != built:
                fresh.append((b, kind, size, meta.route))
            seen.add(meta.route)
            dims.add(meta.statics[0].shape[0])
            if meta.route == "wavefront":
                plans.add((vocab.pad_dim(size, 8), meta.wave_plan.members.shape[0]))
                assert tpu.last_solve.wave_fallbacks == 0
            assert names == want, f"batch {b} ({kind}, {size} pods)"
            for pod, node in zip(pods, names):
                assert (node is None) == pod.meta.name.startswith("large-")
                if node:
                    pod.spec.node_name = node
                    sched.cache.assume(pod, node)
                    live.append(pod)
            while len(live) > PENDING_LIVE:
                k = min(rng.randint(1, 64), len(live))
                for pod in live[:k]:
                    sched.cache.remove_pod(pod)
                del live[:k]
        assert fresh == [] and sum(builds.values()) == warmed
        assert seen == {"greedy", "wavefront"}
        assert dims == {4}              # pure and mixed batches alike: pad_dim(3, 1)
        # one plan a bucket, the uncoupled one, whatever fits nowhere
        assert plans == {(p, assign.wave_rows(p, 32, False)) for p, _ in plans}
    finally:
        unregister()
        for pod in live:
            sched.cache.remove_pod(pod)


@pytest.mark.parametrize("small_nodes", [512], indirect=True)
def test_a_full_batch_of_both_shapes_takes_the_auction_and_builds_nothing(small_nodes):
    """The deployment's 1,024-pod cycles are the only auction cycles of the
    benchmark: with the route's threshold brought down to this test's batch,
    full batches of either shape or both build nothing after ``warmup``, no
    large pod lands and every measured pod does.  On more nodes than a batch
    has pods, so that the auction's ``tie_k`` is not cut to the node axis: it
    followed the batch's largest class (128 pods of 256 in ``warmup``'s mixed
    batch, all 256 of a pure one: another executable, which the chip's first
    run of the cell built inside a window), and is sized by the batch's valid
    pods, on this path as on the gang and the sharded ones."""
    (sched, _), rng = small_nodes, random.Random(2036)
    tpu = sched.tpu
    tpu.AUCTION_MIN_PODS = BATCH
    warm = []
    for i in range(BATCH):
        warm += [default_pod(f"warm-{i}", NAMESPACES[i % 16]),
                 large_pod(f"warm-large-{i}", NAMESPACES[i % 16])]
    builds, unregister = builds_counter()
    try:
        sched.warmup(warm)
        warmed = sum(builds.values())
        for b, share in enumerate([0.0, 1.0, 0.5, 0.95, 0.05, 1.0]):
            pods = []
            for i in range(rng.randint(BATCH // 2 + 1, BATCH)):
                big = rng.random() < share
                mk = large_pod if big else default_pod
                pods.append(mk(f"{'large' if big else 'p'}-{b}-{i}", rng.choice(NAMESPACES)))
            mark = compileclock.events()
            names = tpu.schedule_pending(pods, lock=sched.cache.lock)
            assert tpu.last_solve.meta.route == "auction"
            assert tpu.last_solve.meta.tie_k == BATCH       # the bucket's, whatever fills it
            assert compileclock.events() == mark and sum(builds.values()) == warmed, (b, share)
            for pod, node in zip(pods, names):
                assert (node is None) == pod.meta.name.startswith("large-")
    finally:
        unregister()


# what `warmup` with one template builds or loads on 128 nodes at a batch of
# 256, by function: the count on the parent commit (PR 34), pinned so that a
# deployment of one template keeps the keys, and the set-up, it had.  Since
# the encode's staging program carries the mirror's rows and the partials'
# refresh and gather, each layout's `_stage` replaces its `_unpack`, and the
# standalone gather is built no more: 30 executables where there were 31
ONE_TEMPLATE_WARMUP = {
    "jit(run_warm)": 6, "jit(_stage)": 6, "jit(eval_store)": 1,
    "jit(set_spec_rows)": 1, "jit(insert_slots)": 1, "jit(refresh_rows)": 1, "jit(_set_rows)": 14,
}


ONE_TEMPLATE_SCRIPT = """
import collections, json, sys
import jax.monitoring
from kubernetes_tpu.api import store as st
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.testing.wrappers import GI, MI, make_node, make_pod

builds = collections.Counter()
def on_duration(event, secs, **kw):
    if event == "/jax/core/compile/backend_compile_duration":
        builds[str(kw.get("fun_name", "?"))] += 1
jax.monitoring.register_event_duration_secs_listener(on_duration)
store = st.Store()
for i in range(128):
    store.create(make_node(f"node-{i}").capacity(cpu_milli=4000, mem=32 * GI, pods=110)
                 .zone(f"zone-{i % 8}").obj())
sched = Scheduler(store, batch_size=256)
sched.start()
assert sched.informers.wait_for_sync()
try:
    sched.warmup([make_pod(f"warm-{i}", f"team-{i % 16}").req(cpu_milli=100, mem=500 * MI).obj()
                  for i in range(256)])
    pool = sched.tpu.prewarm_pool
    left = pool is not None and not pool.join(timeout=0.0)
    print(json.dumps({"builds": builds, "floor": sched.tpu.builder.spec_class_floor,
                      "pool_left_a_job": left}))
finally:
    sched.stop()
"""


def test_a_one_template_warmup_builds_exactly_what_it_built_before():
    """In a process of its own, since what an earlier test built this one
    would find built."""
    import json
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=root + os.pathsep + os.environ.get("PYTHONPATH", ""))
    done = subprocess.run([sys.executable, "-c", ONE_TEMPLATE_SCRIPT], env=env, cwd=root,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    out = json.loads(done.stdout.strip().splitlines()[-1])
    assert out["floor"] == 2            # the dim a one-template batch has anyway
    assert out["builds"] == ONE_TEMPLATE_WARMUP
    assert out["pool_left_a_job"] is False


def test_warmup_waits_for_the_prewarm_pools_outstanding_jobs():
    """``SolverPrewarmPool.join`` returns once every job offered so far has
    run, and ``warmup`` calls it before it returns: nothing is built after
    ``warmup`` whatever the timing (before, the pool's last job could end
    after it, PERF.md PR 33)."""
    import threading

    from kubernetes_tpu.models.batch_scheduler import SolverPrewarmPool

    pool = SolverPrewarmPool()
    gate, ran = threading.Event(), []
    try:
        assert pool.join(timeout=0.0)           # nothing offered: nothing to wait for
        assert pool.offer("k1", "slow", lambda: (gate.wait(10), ran.append("k1")))
        assert pool.offer("k2", "boom", lambda: 1 / 0)      # a failed build counts as done
        assert pool.offer("k3", "fast", lambda: ran.append("k3"))
        assert not pool.join(timeout=0.05)      # the first job is still building
        gate.set()
        assert pool.join(timeout=10.0) and ran == ["k1", "k3"]
        assert (pool.compiled, pool.errors) == (2, 1)
    finally:
        gate.set()
        pool.close()


# -- pods that leave: the deployment sched-perf-5000n-antiaffinity -----------------

ANTI_NODES = 256
ANTI_BATCH = 64


@pytest.fixture(scope="module")
def anti_affinity_template():
    # scheduler_perf's pod-with-pod-anti-affinity.yaml, the port's own copy
    import os

    import yaml

    from kubernetes_tpu import perf

    path = os.path.join(os.path.dirname(perf.__file__), "config",
                        "pod-with-pod-anti-affinity.yaml")
    with open(path) as f:
        d = yaml.safe_load(f)
    d["metadata"].pop("generateName")
    return d


def test_no_removal_compiles_after_warmup_and_placements_equal_the_oracles(
        anti_affinity_template):
    """Bound pods leave between batches, 1 to 64 at a time, as the
    informer removes them (``SchedulerCache.remove_pod``): whatever rows
    the binds and the removals since the last encode dirtied, the mirror's
    scatter for them was built in ``warmup`` (before PR 32 only the buckets
    a bind wave leaves were: 64 binds and 64 removals asked for a new one),
    and every batch lands where ``testing/oracle.py`` puts it on the same
    sequence of binds and removals."""
    import copy

    from kubernetes_tpu.api import kubeyaml
    from kubernetes_tpu.testing.oracle import Oracle

    def anti_affinity_pod(name, ns):
        d = copy.deepcopy(anti_affinity_template)
        d["metadata"].update(name=name, namespace=ns)
        return kubeyaml.pod_from_dict(d)

    store = st.Store()
    nodes = [
        make_node(f"node-{i}").capacity(cpu_milli=4000, mem=32 * GI, pods=110)
        .zone(f"zone-{i % 8}").obj()
        for i in range(ANTI_NODES)
    ]
    for node in nodes:
        store.create(node)
    sched = Scheduler(store, batch_size=ANTI_BATCH)
    sched.start()
    assert sched.informers.wait_for_sync()
    rng = random.Random(2032)
    live = []       # bound and not removed, oldest first
    try:
        tpu = sched.tpu
        sched.warmup([anti_affinity_pod(f"warm-{i}", "sched-1") for i in range(ANTI_BATCH)])
        fresh, seen, removed, buckets, full = [], set(), 0, set(), 0
        synced = tpu.state.generation
        for b in range(SWEEP):
            size = rng.randint(1, ANTI_BATCH)
            pods = [
                anti_affinity_pod(f"p-{b}-{i}", rng.choice(["sched-1", "sched-1", "sched-0"]))
                for i in range(size)
            ]
            want = Oracle(nodes, bound_pods=live).schedule(pods)
            dirty = tpu.state.dirty_rows(synced, ANTI_NODES)[1].shape[0]
            buckets.add(vocab.pad_dim(max(dirty, 1), 1))
            mark = compileclock.events()
            names = tpu.schedule_pending(pods, lock=sched.cache.lock)
            synced = tpu.state.generation
            if compileclock.events() != mark:
                fresh.append((b, size, dirty, tpu.last_solve.meta.route))
            seen.add(tpu.last_solve.meta.route)
            assert names == want, f"batch {b}"
            if size == ANTI_BATCH:
                # no pad pod: one wave a pod, each one scan step
                ds = tpu.last_solve
                assert (ds.wave_count, ds.wave_fallbacks, ds.wave_steps) == (
                    size, 0, size)
                full += 1
            for pod, node in zip(pods, names):
                if node:
                    pod.spec.node_name = node
                    sched.cache.assume(pod, node)
                    live.append(pod)
            # the longest-bound leave, as a Job's pods finish; twice where
            # the one-pod-a-node cluster is filling up
            for _ in range(2 if len(live) > ANTI_NODES * 5 // 8 else 1):
                k = min(rng.randint(1, 64), len(live))
                for pod in live[:k]:
                    sched.cache.remove_pod(pod)
                del live[:k]
                removed += k
        assert fresh == []
        assert seen == {"greedy", "wavefront"}
        assert removed > 4000
        assert full >= 1
        # the sweep did ask for scatters past a batch's rows
        assert max(buckets) > ANTI_BATCH
    finally:
        for pod in live:
            sched.cache.remove_pod(pod)
        sched.stop()


def test_wave_steps_reach_the_solve_the_histogram_and_the_recorders_row(
        anti_affinity_template):
    """A served toy cycle of 64 mutually repelling pods: what the device
    counted comes back with the names in the one readback and is written
    three times: ``DeviceSolve.wave_steps``, the histogram
    ``scheduler_solve_wave_steps`` and the ``sched.solve.waves`` row
    (``n`` waves, ``a0`` fallbacks, ``a1`` steps)."""
    import copy
    import time

    from kubernetes_tpu.api import kubeyaml
    from kubernetes_tpu.utils import trace

    store = st.Store()
    for i in range(96):
        store.create(
            make_node(f"node-{i}").capacity(cpu_milli=4000, mem=32 * GI, pods=110).obj()
        )
    for i in range(ANTI_BATCH):     # in the store before the informer lists: one pop
        d = copy.deepcopy(anti_affinity_template)
        d["metadata"].update(name=f"p-{i}", namespace="sched-1")
        store.create(kubeyaml.pod_from_dict(d))
    sched = Scheduler(store, batch_size=ANTI_BATCH)
    sched.informers.informer("Node").start()
    sched.informers.informer("Pod").start()
    try:
        assert sched.informers.wait_for_sync(10)
        deadline = time.monotonic() + 10
        while sched.queue.pending_count() < ANTI_BATCH and time.monotonic() < deadline:
            time.sleep(0.01)
        t0 = time.perf_counter()
        assert sched.schedule_batch(timeout=0.5).get("scheduled", 0) == ANTI_BATCH
        assert sched.flush_binds(timeout=30)
        ds = sched.tpu.last_solve
        assert ds.meta.route == "wavefront"
        assert (ds.wave_count, ds.wave_fallbacks, ds.wave_steps) == (
            ANTI_BATCH, 0, ANTI_BATCH)
        rows = [dict(zip(trace.SPAN_FIELDS, r)) for r in trace.snapshot(t0)["spans"]]
        row, = [r for r in rows if r["name"] == "sched.solve.waves"]
        assert (row["n"], row["a0"], row["a1"]) == (ANTI_BATCH, 0.0, float(ANTI_BATCH))
        hist = sched.metrics.solve_wave_steps
        assert (hist.n, hist.total) == (1, float(ANTI_BATCH))
        pods, _ = store.list("Pod")
        assert len({p.spec.node_name for p in pods}) == ANTI_BATCH  # one a node
    finally:
        sched.stop()
