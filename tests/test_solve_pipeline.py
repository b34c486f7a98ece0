"""Solve-side pipeline: wavefront routing, the coalesced lazy readback
(`DeviceSolve`), the hot loop end to end, the prewarm pool, and the solve
metrics."""

import time

import numpy as np
import pytest

from kubernetes_tpu.api import store as st
from kubernetes_tpu.models.batch_scheduler import (
    DeviceSolve,
    SolverPrewarmPool,
    TPUBatchScheduler,
)
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.testing.oracle import Oracle
from kubernetes_tpu.testing.wrappers import GI, MI, make_node, make_pod


def mk_nodes(n, cpu=8000):
    return [
        make_node(f"n{i}").capacity(cpu_milli=cpu, mem=16 * GI, pods=110).obj()
        for i in range(n)
    ]


def mk_pods(p, prefix="p"):
    return [
        make_pod(f"{prefix}-{i}").req(cpu_milli=200, mem=128 * MI).obj()
        for i in range(p)
    ]


def test_wavefront_route_matches_oracle():
    """Batches over WAVEFRONT_MIN_PODS route to the wavefront solver and
    still place exactly like the reference-semantics oracle."""
    nodes = mk_nodes(16)
    pods = mk_pods(100)
    s = TPUBatchScheduler()
    for nd in nodes:
        s.add_node(nd)
    names = s.schedule_pending(pods)
    assert names == Oracle(nodes).schedule(pods)
    assert s.last_result.wave_count is not None
    assert int(s.last_result.wave_count) >= 1
    # the wavefront gate off must yield identical placements (scan route)
    s2 = TPUBatchScheduler(use_wavefront=False)
    for nd in nodes:
        s2.add_node(nd)
    assert s2.schedule_pending(pods) == names
    assert s2.last_result.wave_count is None


def test_small_batches_stay_on_scan():
    s = TPUBatchScheduler()
    for nd in mk_nodes(4):
        s.add_node(nd)
    s.schedule_pending(mk_pods(8))
    assert s.last_result.wave_count is None  # scan route, no wave pass


def test_device_solve_defers_and_coalesces_decode():
    s = TPUBatchScheduler()
    for nd in mk_nodes(8):
        s.add_node(nd)
    pods = mk_pods(80)
    ds = s.schedule_pending_async(pods)
    assert ds is not None
    time.sleep(0.02)  # host work the readback would overlap
    names = s.finalize_pending(pods, ds)
    assert sum(n is not None for n in names) == 80
    assert ds.deferred_s >= 0.02  # the decode really was deferred
    # reasons ride the same readback — no second transfer path
    assert ds.reasons() is not None
    assert len(ds.reasons()) == 80
    assert set(s.last_timings) >= {
        "encode_s", "compile_s", "solve_s", "decode_wait_s",
        "decode_overlap_s",
    }


def test_gang_retry_reuses_full_batch_bucket():
    """The gang admission retry's subset solves must encode into the full
    batch's pad bucket (one executable), not per-subset buckets."""
    nodes = [
        make_node("n0").capacity(cpu_milli=4000, mem=8 * GI, pods=110).obj()
    ]
    # three gangs of 3 x 1000m on a 4000m node: no two gangs fit, every
    # full solve releases everything -> the binary search runs
    pods = [
        make_pod(f"g{i}")
        .req(cpu_milli=1000, mem=256 * MI)
        .group(f"gang-{i // 3}")
        .obj()
        for i in range(9)
    ]
    s = TPUBatchScheduler()
    for nd in nodes:
        s.add_node(nd)
    seen_buckets = set()
    orig = s.builder.build_from_state

    def spy(state, pending, num_pods_hint=0, **kw):
        snap, meta = orig(state, pending, num_pods_hint=num_pods_hint, **kw)
        seen_buckets.add(snap.pods.valid.shape[0])
        return snap, meta

    s.builder.build_from_state = spy
    names = s.schedule_pending(pods)
    # one gang admitted whole
    placed = [i for i, n in enumerate(names) if n is not None]
    assert len(placed) == 3
    assert len(seen_buckets) == 1, seen_buckets  # one pad bucket only


def test_hot_loop_pipeline_end_to_end():
    """The hot loop finishes each cycle in place: pods created through
    the store bind correctly, and the overlap metric, one reading a
    solved group, holds about nothing — no decode waits out a pop."""
    store = st.Store()
    sched = Scheduler(store, batch_size=256)
    for nd in mk_nodes(8):
        store.create(nd)
    sched.start()
    try:
        pods = mk_pods(80, prefix="loop")
        for p in pods:
            store.create(p)
        deadline = time.monotonic() + 60
        bound = 0
        while time.monotonic() < deadline:
            bound = sum(
                1
                for p in sched.informers.informer("Pod").list()
                if p.meta.name.startswith("loop-") and p.spec.node_name
            )
            if bound == 80:
                break
            time.sleep(0.05)
        assert bound == 80
        assert sched.flush_binds(timeout=10)
        overlap = sched.metrics.decode_overlap
        assert overlap.n == sched.metrics.batch_solve_duration.n >= 1
        # a decode left behind a pop would read that pop's window (50 ms)
        assert overlap.total / overlap.n < 0.025
        # 80 pods routed wavefront -> wave metrics observed
        assert sched.metrics.solve_wave_count.n >= 1
    finally:
        sched.stop()


def test_prewarm_pool_compiles_neighbors():
    s = TPUBatchScheduler(prewarm=True)
    try:
        for nd in mk_nodes(8):
            s.add_node(nd)
        s.schedule_pending(mk_pods(80))
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and s.prewarm_pool.compiled < 2:
            time.sleep(0.2)
        # the adjacent pod buckets compiled off-thread, no errors
        assert s.prewarm_pool.compiled >= 2
        assert s.prewarm_pool.errors == 0
    finally:
        s.prewarm_pool.close()


def test_prewarm_pool_dedupes_and_drops_when_full():
    pool = SolverPrewarmPool(max_pending=1)
    ran = []
    try:
        assert pool.mark_seen(("k", 1)) is True
        assert pool.mark_seen(("k", 1)) is False  # dispatch-path dedupe
        assert pool.offer(("k", 1), "dup", lambda: ran.append(1)) is False
        assert pool.offer(("k", 2), "a", lambda: ran.append(2)) is True
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and pool.compiled < 1:
            time.sleep(0.05)
        assert pool.compiled == 1 and ran == [2]
    finally:
        pool.close()


def test_packed_device_put_scratch_reuse():
    """Consecutive same-layout encodes reuse the double-buffered staging
    scratch instead of allocating fresh buffers."""
    s = TPUBatchScheduler()
    for nd in mk_nodes(8):
        s.add_node(nd)
    pods = mk_pods(80)
    s.schedule_pending(pods)  # allocates buffer A
    s.schedule_pending(mk_pods(80, prefix="q"))  # allocates buffer B
    cache1 = {
        k: [id(b) for b in v["bufs"]] for k, v in s._unpack_cache.items()
    }
    s.schedule_pending(mk_pods(80, prefix="r"))  # reuses A
    names3 = s.schedule_pending(mk_pods(80, prefix="t"))  # reuses B
    cache2 = {
        k: [id(b) for b in v["bufs"]] for k, v in s._unpack_cache.items()
    }
    assert cache1.keys() == cache2.keys()
    for k in cache1:
        assert cache1[k] == cache2[k]  # same buffers, alternated in place
    # device_put returns before the runtime has read host memory, so each
    # slot remembers an output of the unpack program that last consumed
    # it; the slot's next rewrite waits on that output
    for v in s._unpack_cache.values():
        assert all(c is not None and c.is_ready() for c in v["consumed"])
    # and the placements stay correct across reuse
    assert sum(n is not None for n in names3) == 80
