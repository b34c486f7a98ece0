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
from kubernetes_tpu.utils import compileclock, vocab

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
    for b in range(SWEEP):
        size = rng.randint(1, BATCH)
        nss = rng.sample(NAMESPACES, rng.randint(1, 16))
        pods = [mk(f"p-{b}-{i}", rng.choice(nss)) for i in range(size)]
        mark = compileclock.events()
        names = tpu.schedule_pending(pods, lock=sched.cache.lock)
        meta = tpu.last_solve.meta
        seen.add(meta.route)
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
