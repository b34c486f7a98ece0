"""DeviceClusterMirror: delta sync must equal a fresh full upload after
any mutation sequence (the generation-protocol analogue of the
reference's cache_test.go snapshot-consistency cases around
internal/cache/cache.go:185-260)."""

import numpy as np
import jax
import pytest

from kubernetes_tpu.api import types as api
from kubernetes_tpu.models.mirror import DeviceClusterMirror
from kubernetes_tpu.ops import schema
from kubernetes_tpu.testing.wrappers import GI, MI, make_node, make_pod


def _mk_state(n=12):
    state = schema.ClusterState()
    for i in range(n):
        state.add_node(
            make_node(f"n-{i}")
            .capacity(cpu_milli=8000, mem=16 * GI, pods=110)
            .zone(f"z-{i % 3}")
            .obj()
        )
    return state


def _assert_mirror_matches(mirror, state):
    dev = mirror.sync()
    want = state.tensors()
    for name in want._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(dev, name)),
            np.asarray(getattr(want, name)),
            err_msg=f"leaf {name} diverged",
        )


def test_initial_sync_and_noop_resync():
    state = _mk_state()
    mirror = DeviceClusterMirror(state)
    _assert_mirror_matches(mirror, state)
    dev1 = mirror.sync()
    dev2 = mirror.sync()  # no mutations: must return the same arrays
    assert dev1.allocatable is dev2.allocatable


def test_pod_usage_deltas():
    state = _mk_state()
    mirror = DeviceClusterMirror(state)
    mirror.sync()
    pods = [
        make_pod(f"p-{i}").req(cpu_milli=500, mem=256 * MI).obj()
        for i in range(5)
    ]
    for i, p in enumerate(pods):
        state.add_pod(p, f"n-{i % 3}")
    _assert_mirror_matches(mirror, state)
    state.remove_pod(pods[0])
    state.remove_pod(pods[3])
    _assert_mirror_matches(mirror, state)


def test_node_lifecycle_deltas():
    state = _mk_state()
    mirror = DeviceClusterMirror(state)
    mirror.sync()
    state.update_node(
        make_node("n-1").capacity(cpu_milli=32000, mem=64 * GI, pods=200)
        .zone("z-9").label("disk", "ssd").obj()
    )
    _assert_mirror_matches(mirror, state)
    state.remove_node("n-2")
    _assert_mirror_matches(mirror, state)
    state.add_node(
        make_node("n-new").capacity(cpu_milli=1000, mem=GI, pods=10)
        .taint("dedicated", "gpu", "NoSchedule").obj()
    )
    _assert_mirror_matches(mirror, state)


def test_growth_is_not_a_struct_event():
    """Backing-array growth preserves row indices, so it must NOT move
    the struct generation (the elastic-node-axis contract): the mirror
    absorbs bucket crossings with an in-place resident grow — or, for a
    bulk load like this one, the over-fraction full upload — and still
    matches a fresh encode bit-for-bit."""
    state = _mk_state(4)
    mirror = DeviceClusterMirror(state)
    mirror.sync()
    gen0 = state.struct_generation
    for i in range(200):  # cross several growth buckets
        state.add_node(
            make_node(f"g-{i}").capacity(cpu_milli=4000, mem=8 * GI, pods=50)
            .obj()
        )
    assert state.struct_generation == gen0
    _assert_mirror_matches(mirror, state)


def test_resource_widen_forces_struct_resync():
    state = _mk_state()
    mirror = DeviceClusterMirror(state)
    mirror.sync()
    state.add_node(
        make_node("tpu-node")
        .capacity(cpu_milli=8000, mem=16 * GI, pods=110,
                  **{"google.com/tpu": 8})
        .obj()
    )
    _assert_mirror_matches(mirror, state)


def test_compaction_deltas():
    state = _mk_state(40)
    mirror = DeviceClusterMirror(state)
    mirror.sync()
    for i in range(5, 40):
        state.remove_node(f"n-{i}")  # triggers _maybe_compact
    _assert_mirror_matches(mirror, state)


def test_two_mirrors_one_state():
    """Profiles: two consumers sync independently through the shared
    generation counters."""
    state = _mk_state()
    m1 = DeviceClusterMirror(state)
    m2 = DeviceClusterMirror(state)
    m1.sync()
    state.add_pod(make_pod("p").req(cpu_milli=100, mem=MI).obj(), "n-0")
    m2.sync()
    state.add_pod(make_pod("q").req(cpu_milli=100, mem=MI).obj(), "n-1")
    _assert_mirror_matches(m1, state)
    _assert_mirror_matches(m2, state)


def _mesh():
    from kubernetes_tpu.parallel.sharded import make_mesh

    return make_mesh(8)


@pytest.mark.multichip
def test_mesh_mirror_parity_and_sharding():
    """Under a mesh the resident tensors must (a) stay value-identical
    to a full re-encode of the state (the oracle) across every mutation
    family, and (b) carry the node-axis NamedSharding the sharded
    solvers' shard_map specs expect — no per-batch resharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _mesh()
    state = _mk_state(12)
    mirror = DeviceClusterMirror(state, mesh=mesh)
    _assert_mirror_matches(mirror, state)
    dev = mirror.sync()
    assert dev.allocatable.sharding == NamedSharding(mesh, P("nodes"))
    assert dev.taint_bits.sharding == NamedSharding(mesh, P(None, "nodes"))

    # usage deltas scatter into the owning shard
    pods = [
        make_pod(f"p-{i}").req(cpu_milli=500, mem=256 * MI).obj()
        for i in range(5)
    ]
    for i, p in enumerate(pods):
        state.add_pod(p, f"n-{i % 3}")
    _assert_mirror_matches(mirror, state)
    assert mirror.delta_syncs >= 1 and mirror.delta_rows_total >= 3
    # the delta result keeps the sharded layout (a sharding flip would
    # retrace the scatter AND reshard the next solve)
    assert mirror.sync().requested.sharding == NamedSharding(
        mesh, P("nodes")
    )

    # static deltas + node lifecycle
    state.update_node(
        make_node("n-1").capacity(cpu_milli=32000, mem=64 * GI, pods=200)
        .zone("z-9").label("disk", "ssd").obj()
    )
    state.remove_node("n-2")
    _assert_mirror_matches(mirror, state)

    # growth across buckets forces a full RESHARDED re-upload
    resyncs0 = mirror.resync_total
    for i in range(200):
        state.add_node(
            make_node(f"g-{i}").capacity(cpu_milli=4000, mem=8 * GI, pods=50)
            .obj()
        )
    _assert_mirror_matches(mirror, state)
    assert mirror.resync_total > resyncs0
    assert mirror.sync().allocatable.sharding == NamedSharding(
        mesh, P("nodes")
    )


@pytest.mark.multichip
def test_mesh_mirror_small_bucket_replicates():
    """A padded bucket smaller than the mesh cannot shard: the mirror
    replicates it (these batches solve single-chip anyway) and still
    matches the re-encode oracle."""
    state = schema.ClusterState(
        schema.SnapshotBuilder(schema.SnapshotLimits(min_nodes=4))
    )
    for i in range(3):
        state.add_node(
            make_node(f"n-{i}").capacity(cpu_milli=8000, mem=16 * GI, pods=110)
            .obj()
        )
    mirror = DeviceClusterMirror(state, mesh=_mesh())
    _assert_mirror_matches(mirror, state)
    state.add_pod(make_pod("p").req(cpu_milli=100, mem=MI).obj(), "n-0")
    _assert_mirror_matches(mirror, state)


def test_scheduler_steps_use_mirror():
    """End-to-end: repeated schedule_pending steps with assumes between
    them stay correct (the steady-state loop the mirror accelerates)."""
    from kubernetes_tpu.models.batch_scheduler import TPUBatchScheduler

    sched = TPUBatchScheduler()
    for i in range(8):
        sched.add_node(
            make_node(f"n-{i}").capacity(cpu_milli=4000, mem=8 * GI, pods=20)
            .obj()
        )
    placed = {}
    for step in range(4):
        pods = [
            make_pod(f"s{step}-p{i}").req(cpu_milli=1000, mem=GI).obj()
            for i in range(6)
        ]
        names = sched.schedule_pending(pods)
        for p, nm in zip(pods, names):
            assert nm is not None
            sched.assume(p, nm)
            placed[p.meta.name] = nm
    # every node's accumulated usage is visible: a final over-ask fails
    big = [make_pod("big").req(cpu_milli=4000, mem=GI).obj()]
    assert sched.schedule_pending(big) == [None]


# -- the encode's staging program (models/batch_scheduler.TPUBatchScheduler._stage)

def _staged_sched(n_nodes, **kw):
    from kubernetes_tpu.models.batch_scheduler import TPUBatchScheduler

    sched = TPUBatchScheduler(**kw)
    for i in range(n_nodes):
        sched.add_node(
            make_node(f"n-{i}").capacity(cpu_milli=8000, mem=16 * GI, pods=110)
            .zone(f"z-{i % 3}").obj()
        )
    return sched


def _stage_rows(t0):
    """(n, a0, a1) of every sched.encode.stage row since t0."""
    from kubernetes_tpu.utils import trace

    snap = trace.snapshot(t0, float("inf"))
    return [
        (int(r["n"]), int(r["a0"]), int(r["a1"]))
        for r in (dict(zip(trace.SPAN_FIELDS, row)) for row in snap["spans"])
        if r["name"] == "sched.encode.stage"
    ]


def _oracle_names(sched, pods):
    from kubernetes_tpu.testing.oracle import Oracle

    state = sched.state
    oracle = Oracle([state._node_objs[nm] for nm in state._rows])
    by_name = {s.node.meta.name: s for s in oracle.states}
    for key, bp in state._pods.items():
        ns = by_name.get(state._pod_node.get(key))
        if ns is not None:
            ns.add_pod(bp)
    return oracle.schedule(list(pods))


def _cycle(sched, pods, oracle=True):
    """One encode and solve, then the staging path's invariants: the
    resident mirror equals a fresh full upload of the state, the
    partials pass their parity gate, and the placements equal the
    oracle's.  Returns (names, stage rows)."""
    from kubernetes_tpu.utils import trace

    want = _oracle_names(sched, pods) if oracle else None
    t0 = trace.now()
    names = sched.schedule_pending(pods)
    rows = _stage_rows(t0)
    host = sched.state.tensors()
    dev = jax.device_get(sched._mirror._dev)
    for name in host._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(dev, name)), np.asarray(getattr(host, name)),
            err_msg=f"leaf {name} diverged",
        )
    if sched._partials is not None:
        assert sched._partials.verify(sched._mirror._dev, None)
    if oracle:
        assert names == want
    return names, rows


def _pods(tag, k):
    return [
        make_pod(f"{tag}-{i}").req(cpu_milli=100 * (1 + i % 3), mem=256 * MI).obj()
        for i in range(k)
    ]


STAGED_MIX = [
    "binds", "deletions", "rows_over_256", "node_added", "first_seen_class",
    "auction_batch", "fault_mirror_grow", "fault_solve_partials",
]


@pytest.mark.parametrize("event", STAGED_MIX)
def test_staged_cycles_keep_mirror_partials_and_placements_exact(event):
    """Cycles through the staging program, with one event of the mix
    between them: after every cycle the resident mirror equals a full
    upload, the partials equal a full recompute and the placements equal
    the oracle's; the event's own cycle takes the path it must (the
    staging program where only usage rows moved, the old path else)."""
    from kubernetes_tpu.testing import faults

    n_nodes = 320 if event == "rows_over_256" else 12
    sched = _staged_sched(n_nodes)
    names, rows = _cycle(sched, _pods("a", 6))
    assert rows[-1][2] == 0            # the first upload: the old path
    placed = [(p, nm) for p, nm in zip(_pods("a", 6), names)]
    for p, nm in placed:
        sched.assume(p, nm)
    names, rows = _cycle(sched, _pods("b", 6))
    assert rows == [(1, 1, 1)]         # binds: one transfer, one program
    staged = True
    oracle = True
    pods = _pods("c", 6)
    reg = None
    if event == "binds":
        for p, nm in zip(_pods("b", 6), names):
            sched.assume(p, nm)
    elif event == "deletions":
        for p, _ in placed[:4]:
            sched.forget(p)
    elif event == "rows_over_256":
        for i in range(300):
            sched.assume(
                make_pod(f"bulk-{i}").req(cpu_milli=50, mem=MI).obj(), f"n-{i}"
            )
        staged = False
    elif event == "node_added":
        sched.add_node(
            make_node("n-new").capacity(cpu_milli=8000, mem=16 * GI, pods=110)
            .zone("z-0").obj()
        )
        staged = False
    elif event == "first_seen_class":
        pods = pods[:5] + [
            make_pod("c-sel").req(cpu_milli=100, mem=MI).node_selector_kv(
                api.LABEL_ZONE, "z-1").obj()
        ]
        staged = False
    elif event == "auction_batch":
        pods = [
            make_pod(f"g-{i}").req(cpu_milli=100, mem=MI).group("gang", 4).obj()
            for i in range(4)
        ]
        staged = oracle = False
    elif event == "fault_mirror_grow":
        for i in range(n_nodes, 20):   # 12 -> 20 nodes: the 16-row bucket grows
            sched.add_node(
                make_node(f"n-{i}").capacity(cpu_milli=8000, mem=16 * GI, pods=110)
                .zone(f"z-{i % 3}").obj()
            )
        reg = faults.FaultRegistry(seed=3)
        reg.corrupt("mirror.grow", n=1)
        staged = False
    elif event == "fault_solve_partials":
        reg = faults.FaultRegistry(seed=4)
        reg.corrupt("solve.partials", n=1)
        staged = False
    if reg is not None:
        with faults.armed(reg):
            _, rows = _cycle(sched, pods, oracle=oracle)
        assert reg.fired
    else:
        _, rows = _cycle(sched, pods, oracle=oracle)
    assert rows[0][2] == (1 if staged else 0), rows
    if event == "auction_batch":
        assert sched.last_solve.meta.route == "auction"
    # and the next cycle is staged again
    for p in _pods("d", 2):
        sched.assume(p, "n-3")
    _, rows = _cycle(sched, _pods("e", 6))
    assert rows == [(1, 1, 1)]


def test_rollback_restores_the_buffers_bookmarked_before_a_staged_cycle():
    """A speculation bookmark taken before a staged cycle still holds the
    residents the staging program replaced (nothing is donated), and a
    rollback to it restores them; the next cycle re-stages every row
    dirtied since and stays exact."""
    sched = _staged_sched(12)
    names, _ = _cycle(sched, _pods("a", 6))
    mpoint = sched._mirror.speculation_point()
    ppoint = sched._partials.speculation_point()
    before = jax.device_get(sched._mirror._dev.requested)
    old_dev, old_store = sched._mirror._dev, sched._partials._store
    for p, nm in zip(_pods("a", 6), names):
        sched.assume(p, nm)
    _, rows = _cycle(sched, _pods("b", 6))
    assert rows == [(1, 1, 1)]
    assert sched._mirror._dev.requested is not old_dev.requested
    # the bookmarked buffers are intact, not donated to the staging program
    np.testing.assert_array_equal(jax.device_get(old_dev.requested), before)
    sched._mirror.rollback(mpoint)
    sched._partials.rollback(ppoint)
    assert sched._mirror._dev is old_dev
    assert sched._partials._store is old_store
    _, rows = _cycle(sched, _pods("c", 6))
    assert rows == [(1, 1, 1)]


def test_staged_cycles_count_rows_as_the_delta_path_does():
    """mirror.stats() means the same on either path: a scheduler whose
    cycles the staging program carries and one whose cycles the mirror's
    own scatter serves (no partials: the plain unpack) count the same
    delta rows and delta syncs over the same binds and deletions."""
    staged = _staged_sched(12)
    plain = _staged_sched(12, use_partials=False)
    paths = {True: set(), False: set()}
    for step in range(4):
        pods = _pods(f"s{step}", 5)
        for sched, key in ((staged, True), (plain, False)):
            names, rows = _cycle(sched, pods)
            paths[key].update(r[2] for r in rows)
            for p, nm in zip(pods, names):
                sched.assume(p, nm)
            if step == 2:
                sched.forget(pods[0])
    assert paths == {True: {0, 1}, False: {0}}
    assert staged._mirror.stats() == plain._mirror.stats()
    assert staged._mirror.stats()["delta_syncs"] == 3
