"""Incremental O(changes) solving: warm-started solves must be
BIT-IDENTICAL to cold solves and to the host oracle on randomized churn
snapshots — the parity gate ISSUE 14's warm start rests on — plus the
cache's resync/rollback/invalidation discipline."""

import numpy as np
import pytest

from kubernetes_tpu.api import types as api
from kubernetes_tpu.models.batch_scheduler import TPUBatchScheduler
from kubernetes_tpu.models.partials import PartialsCache
from kubernetes_tpu.ops import assign as assign_ops
from kubernetes_tpu.testing import faults
from kubernetes_tpu.testing.wrappers import GI, MI, make_node, make_pod


def _mk_sched(use_partials, mesh=None, **kw):
    return TPUBatchScheduler(
        mode="greedy", use_partials=use_partials, mesh=mesh, **kw
    )


def _add_nodes(scheds, n=16, seed=0):
    rng = np.random.default_rng(seed)
    for i in range(n):
        nd = (
            make_node(f"n-{i}")
            .capacity(cpu_milli=8000, mem=16 * GI, pods=110)
            .zone(f"z-{i % 3}")
        )
        if rng.random() < 0.3:
            nd.label("disk", "ssd")
        if rng.random() < 0.2:
            nd.taint("dedicated", "gpu", api.PREFER_NO_SCHEDULE)
        if rng.random() < 0.1:
            nd.taint("maint", "true", api.NO_SCHEDULE)
        node = nd.obj()
        for s in scheds:
            s.add_node(node)


def _mk_pods(step, p, seed):
    """Mixed static specs: selectors, preferred terms, tolerations,
    host ports, NodeName — every input of the partials triple."""
    rng = np.random.default_rng(seed)
    pods = []
    for i in range(p):
        pw = make_pod(f"s{step}-p{i}").req(
            cpu_milli=int(rng.choice([100, 250, 500])), mem=256 * MI
        )
        r = i % 6
        if r == 0:
            pw.required_affinity(
                api.LABEL_ZONE, api.OP_IN, [f"z-{i % 3}"]
            )
        elif r == 1:
            pw.preferred_affinity(10, "disk", api.OP_IN, ["ssd"])
        elif r == 2:
            pw.toleration("dedicated", "gpu", effect=api.PREFER_NO_SCHEDULE)
        elif r == 3:
            pw.toleration("maint", "true", effect=api.NO_SCHEDULE)
        elif r == 4:
            pw.host_port(7000 + (i % 4))
        pods.append(pw.obj())
    return pods


def _churn(scheds, rng, placed):
    """Dirty a handful of rows: assumes, forgets, node updates."""
    for p, nm in placed:
        if rng.random() < 0.6:
            for s in scheds:
                s.assume(p, nm)
    if rng.random() < 0.5:
        node = (
            make_node(f"n-{int(rng.integers(0, 8))}")
            .capacity(cpu_milli=16000, mem=32 * GI, pods=200)
            .zone(f"z-{int(rng.integers(0, 3))}")
            .obj()
        )
        for s in scheds:
            s.update_node(node)


def _solve_both(warm, cold, pods):
    names_w = warm.schedule_pending(pods)
    res_w = warm.last_result
    names_c = cold.schedule_pending(pods)
    res_c = cold.last_result
    assert names_w == names_c
    # bit-identical: the full result surface, not just the names
    if res_w is not None and res_c is not None:
        np.testing.assert_array_equal(
            np.asarray(res_w.assignment), np.asarray(res_c.assignment)
        )
        np.testing.assert_array_equal(
            np.asarray(res_w.scores), np.asarray(res_c.scores)
        )
        np.testing.assert_array_equal(
            np.asarray(res_w.reasons), np.asarray(res_c.reasons)
        )
    return names_w


def test_randomized_churn_parity_and_oracle():
    """Warm == cold == host oracle across randomized churn snapshots,
    with the cache actually serving warm rows (delta syncs > 0)."""
    from kubernetes_tpu.testing.oracle import Oracle

    warm, cold = _mk_sched(True), _mk_sched(False)
    _add_nodes((warm, cold), 16, seed=3)
    rng = np.random.default_rng(7)
    for step in range(6):
        pods = _mk_pods(step, 12, seed=step)
        names = _solve_both(warm, cold, pods)
        # host-oracle parity on the same live state
        state = warm.state
        nodes = [state._node_objs[nm] for nm in state._rows]
        oracle = Oracle(nodes)
        by_name = {s.node.meta.name: s for s in oracle.states}
        for key, bp in state._pods.items():
            ns = by_name.get(state._pod_node.get(key))
            if ns is not None:
                ns.add_pod(bp)
        assert names == oracle.schedule(list(pods))
        _churn((warm, cold), rng, [
            (p, nm) for p, nm in zip(pods, names) if nm is not None
        ])
    stats = warm._partials.stats()
    assert stats["delta_syncs"] >= 3
    assert stats["hit_rows_total"] > 0


def test_statics_match_cold_class_statics():
    """The gathered warm triple equals class_statics on the same
    resident tensors, array-for-array (stronger than placement
    parity)."""
    import jax
    import jax.numpy as jnp

    from kubernetes_tpu.ops.filters import preferred_match, selector_match

    warm = _mk_sched(True)
    _add_nodes((warm,), 12, seed=1)
    for step in range(3):
        pods = _mk_pods(step, 10, seed=step + 20)
        snap, meta = warm.encode_pending(pods)
        assert meta.statics is not None
        # meta.statics leaves were gathered from the resident store;
        # snap.cluster after the packed transfer is the same resident
        # tensors.  Recompute the cold triple from them.
        host_snap, _ = warm.builder.build_from_state(warm.state, pods)
        cluster = snap.cluster
        pods_t = jax.tree.map(jnp.asarray, host_snap.pods)
        sm = selector_match(cluster, jax.tree.map(
            jnp.asarray, host_snap.selectors))
        pm = preferred_match(cluster, jax.tree.map(
            jnp.asarray, host_snap.preferred))
        sfeas, aff, taint = assign_ops.class_statics(
            cluster, pods_t, sm, pm
        )
        np.testing.assert_array_equal(
            np.asarray(meta.statics.sfeas), np.asarray(sfeas)
        )
        np.testing.assert_array_equal(
            np.asarray(meta.statics.aff), np.asarray(aff)
        )
        np.testing.assert_array_equal(
            np.asarray(meta.statics.taint), np.asarray(taint)
        )
        for i, p in enumerate(pods):
            if i % 3 == 0:
                warm.assume(p, f"n-{i % 12}")
        assert warm._partials.verify(cluster, host_snap)


def test_gang_retry_and_ports_parity():
    """Gang batches (all-or-nothing + admission retry) and in-batch
    host-port conflicts ride the warm path unchanged."""
    warm, cold = _mk_sched(True), _mk_sched(False)
    _add_nodes((warm, cold), 8, seed=5)
    for step in range(2):
        pods = []
        for i in range(8):
            pods.append(
                make_pod(f"g{step}-{i}")
                .req(cpu_milli=500, mem=256 * MI)
                .group(f"gang-{i % 2}")
                .obj()
            )
        for i in range(4):
            pods.append(
                make_pod(f"hp{step}-{i}")
                .req(cpu_milli=100, mem=128 * MI)
                .host_port(9000 + (i % 2))
                .obj()
            )
        _solve_both(warm, cold, pods)


def test_vocab_growth_flushes_cache():
    """A selector-relevant vocabulary growing between batches flushes
    the cache whole (stale expansions must never be served warm) — and
    parity holds across the flush."""
    warm, cold = _mk_sched(True), _mk_sched(False)
    _add_nodes((warm, cold), 8, seed=9)
    pods = _mk_pods(0, 8, seed=0)
    _solve_both(warm, cold, pods)
    full0 = warm._partials.full_recomputes
    # a NEW label value: the In-expansion of any selector over that key
    # could now differ from the cached rows' expansion
    node = (
        make_node("n-1").capacity(cpu_milli=8000, mem=16 * GI, pods=110)
        .zone("z-0").label("disk", "nvme").obj()
    )
    for s in (warm, cold):
        s.update_node(node)
    pods2 = [
        make_pod("nv-0").req(cpu_milli=100, mem=128 * MI)
        .required_affinity("disk", api.OP_IN, ["nvme"]).obj()
    ] + _mk_pods(1, 6, seed=1)
    _solve_both(warm, cold, pods2)
    assert warm._partials.full_recomputes > full0


def test_struct_growth_invalidates():
    """A BULK load crossing the padded node bucket forces a full
    recompute through the over-fraction path (most rows dirtied at
    once).  Incremental crossings — few dirty rows — are absorbed in
    place instead (tests/test_elastic_axis.py); the elastic node axis
    reserves the full reseed for genuine struct events and bulk
    loads."""
    warm, cold = _mk_sched(True), _mk_sched(False)
    _add_nodes((warm, cold), 8, seed=11)
    _solve_both(warm, cold, _mk_pods(0, 8, seed=2))
    full0 = warm._partials.full_recomputes
    for i in range(24):  # crosses the growth bucket
        node = (
            make_node(f"grow-{i}")
            .capacity(cpu_milli=8000, mem=16 * GI, pods=110)
            .zone(f"z-{i % 3}").obj()
        )
        for s in (warm, cold):
            s.add_node(node)
    _solve_both(warm, cold, _mk_pods(1, 8, seed=3))
    assert warm._partials.full_recomputes > full0


def test_speculation_rollback_parity():
    """rollback() restores the bookmarked residents; the next sync
    re-refreshes everything dirtied since the bookmark and parity
    holds (the mirror's speculation contract, applied to partials)."""
    warm, cold = _mk_sched(True), _mk_sched(False)
    _add_nodes((warm, cold), 12, seed=13)
    pods0 = _mk_pods(0, 10, seed=4)
    names0 = _solve_both(warm, cold, pods0)
    point = warm._partials.speculation_point()
    mpoint = warm._mirror.speculation_point()
    # speculative progress on the WARM side only: assumes + a batch
    # carrying a first-seen class (allocates a slot the rollback drops)
    for p, nm in zip(pods0, names0):
        if nm is not None:
            warm.assume(p, nm)
    spec = [
        make_pod("spec-0").req(cpu_milli=100, mem=128 * MI)
        .required_affinity(api.LABEL_ZONE, api.OP_NOT_IN, ["z-1"]).obj()
    ]
    warm.schedule_pending(spec)
    # invalidation: drop the speculative deltas whole
    for p, nm in zip(pods0, names0):
        if nm is not None:
            warm.forget(p)
    warm._mirror.rollback(mpoint)
    warm._partials.rollback(point)
    assert warm._partials.rollbacks == 1
    # durable churn applied to BOTH sides, then parity
    rng = np.random.default_rng(17)
    pods1 = _mk_pods(1, 10, seed=5)
    for p in pods0[:3]:
        for s in (warm, cold):
            s.assume(p, "n-2")
    _solve_both(warm, cold, pods1)


def test_corrupt_partials_trips_parity_gate():
    """A CORRUPT solve.partials fault poisons the resident score rows:
    the decode health check must trip, the retry must invalidate +
    fully recompute, and the batch still places correctly."""
    warm, cold = _mk_sched(True), _mk_sched(False)
    _add_nodes((warm, cold), 8, seed=15)
    _solve_both(warm, cold, _mk_pods(0, 8, seed=6))
    full0 = warm._partials.full_recomputes
    reg = faults.FaultRegistry(seed=1)
    reg.corrupt("solve.partials", n=1)
    pods = _mk_pods(1, 8, seed=7)
    with faults.armed(reg):
        names_w = warm.schedule_pending(pods)
    assert reg.fired.get("solve.partials")
    names_c = cold.schedule_pending(pods)
    assert names_w == names_c
    # the gate tripped to a full recompute (or the breaker's host
    # fallback produced the placements)
    assert (
        warm._partials.full_recomputes > full0
        or warm.breaker.fallback_count() > 0
    )
    # and the cache is healthy again afterwards
    _solve_both(warm, cold, _mk_pods(2, 8, seed=8))


def test_fail_grade_fault_degrades_to_cold():
    """A fail-grade solve.partials fault must not kill the encode: the
    batch solves cold and later batches warm again."""
    warm, cold = _mk_sched(True), _mk_sched(False)
    _add_nodes((warm, cold), 8, seed=19)
    reg = faults.FaultRegistry(seed=2)
    reg.fail("solve.partials", n=1)
    with faults.armed(reg):
        _solve_both(warm, cold, _mk_pods(0, 8, seed=9))
    assert reg.fired.get("solve.partials")
    _solve_both(warm, cold, _mk_pods(1, 8, seed=10))
    assert warm._partials.stats()["slots"] > 0


def test_periodic_resync_discipline():
    """Every `resync_interval` delta syncs the cache forces a full
    recompute (the periodic half of the parity discipline)."""
    warm = _mk_sched(True, partials_resync_interval=2)
    cold = _mk_sched(False)
    _add_nodes((warm, cold), 8, seed=21)
    fulls = []
    for step in range(6):
        pods = _mk_pods(step, 8, seed=30 + step)
        _solve_both(warm, cold, pods)
        fulls.append(warm._partials.full_recomputes)
        for i, p in enumerate(pods[:2]):
            for s in (warm, cold):
                s.assume(p, f"n-{(step * 2 + i) % 8}")
    assert fulls[-1] >= 2  # first sync + at least one periodic resync


@pytest.mark.multichip
def test_mesh_warm_parity():
    """Sharded mesh: warm == cold == single-chip on churn snapshots,
    and the resident store carries the node-axis sharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from kubernetes_tpu.parallel.sharded import make_mesh

    mesh = make_mesh(8)
    warm = _mk_sched(True, mesh=mesh)
    cold = _mk_sched(False, mesh=mesh)
    single = _mk_sched(False)
    _add_nodes((warm, cold, single), 16, seed=23)
    rng = np.random.default_rng(29)
    for step in range(4):
        pods = _mk_pods(step, 12, seed=40 + step)
        names = _solve_both(warm, cold, pods)
        assert names == single.schedule_pending(pods)
        _churn((warm, cold, single), rng, [
            (p, nm) for p, nm in zip(pods, names) if nm is not None
        ])
    store = warm._partials._store
    assert store.sfeas.sharding == NamedSharding(mesh, P(None, "nodes"))
    assert warm._partials.stats()["delta_syncs"] >= 1


@pytest.mark.multichip
def test_mesh_small_bucket_replicates():
    """A padded bucket smaller than the mesh keeps the partials
    replicated (these batches solve single-chip) and parity holds."""
    from kubernetes_tpu.ops import schema
    from kubernetes_tpu.parallel.sharded import make_mesh

    mesh = make_mesh(8)
    limits = schema.SnapshotLimits(min_nodes=4)
    warm = TPUBatchScheduler(
        mode="greedy", use_partials=True, mesh=mesh, limits=limits
    )
    cold = TPUBatchScheduler(
        mode="greedy", use_partials=False, mesh=mesh, limits=limits
    )
    for s in (warm, cold):
        for i in range(3):
            s.add_node(
                make_node(f"n-{i}")
                .capacity(cpu_milli=8000, mem=16 * GI, pods=110).obj()
            )
    pods = [
        make_pod(f"p-{i}").req(cpu_milli=100, mem=128 * MI).obj()
        for i in range(4)
    ]
    assert warm.schedule_pending(pods) == cold.schedule_pending(pods)


@pytest.mark.parametrize("dirty", [1, 9, 64])
def test_staged_refresh_is_bit_identical_to_a_cold_solve(dirty):
    """Where only usage rows moved, the encode's staging program runs the
    refresh and the gather (span sched.encode.stage: one transfer, one
    program, a1 = 1): the warm solve stays bit-identical to the cold one
    and the store to a full recompute, whatever the dirty rows' count up
    to the chunk."""
    from kubernetes_tpu.utils import trace

    warm, cold = _mk_sched(True), _mk_sched(False)
    _add_nodes((warm, cold), 160, seed=23)
    _solve_both(warm, cold, _mk_pods(0, 12, seed=40))
    for i in range(dirty):
        p = make_pod(f"fill-{i}").req(cpu_milli=100, mem=128 * MI).obj()
        for s in (warm, cold):
            s.assume(p, f"n-{i}")
    t0 = trace.now()
    _solve_both(warm, cold, _mk_pods(0, 12, seed=40))
    spans = [
        dict(zip(trace.SPAN_FIELDS, row))
        for row in trace.snapshot(t0, float("inf"))["spans"]
    ]
    rows = [
        (int(r["n"]), int(r["a0"]), int(r["a1"]))
        for r in spans if r["name"] == "sched.encode.stage"
    ]
    # the warm side's row is staged; the cold side has no partials and
    # takes the mirror's own scatter and the plain unpack
    assert rows[0] == (1, 1, 1) and rows[1][2] == 0, rows
    assert warm._partials.verify(warm._mirror._dev, None)
    assert warm._partials.stats()["recomputed_rows_total"] > 0
