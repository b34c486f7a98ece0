"""Pod-axis-sharded kernels must place/score identically to their
single-shard twins.

The node-axis mesh (test_sharded.py) scales N; these twins scale the
OTHER long axis — wave members in the wavefront, preemptors in the
PostFilter batch kernels — with node tensors replicated.  Every parity
assertion here is exact: bit-identical assignments, reasons, counters,
and dry-run tensors.  Runs on the 8-virtual-device CPU mesh from
conftest.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import wave_cases

from kubernetes_tpu.api import types as api
from kubernetes_tpu.ops import assign, preemption, schema
from kubernetes_tpu.parallel import sharded
from kubernetes_tpu.testing.wrappers import GI, MI, make_node, make_pod

pytestmark = pytest.mark.multichip


def _workload(seed, n_nodes=24, n_pods=72):
    """Wavefront-shaped batch with every dynamic-coupling family active
    (ports, spread, anti-affinity) so the wave partition, the mini-scan
    corrections, and the serialized fallback all exercise under the pod
    shard too."""
    rng = np.random.default_rng(seed)
    zones = ["z1", "z2", "z3"]
    nodes = [
        make_node(f"n{i}")
        .capacity(
            cpu_milli=int(rng.choice([4000, 8000, 16000])),
            mem=int(rng.choice([8, 16, 32])) * GI,
            pods=110,
        )
        .zone(str(rng.choice(zones)))
        .obj()
        for i in range(n_nodes)
    ]
    pods = []
    for i in range(n_pods):
        pw = make_pod(f"p{i}").req(
            cpu_milli=int(rng.choice([100, 500, 1000])),
            mem=int(rng.choice([128, 512])) * MI,
        ).labels(app=f"a{i % 3}")
        if i % 4 == 0:
            pw.spread(1, api.LABEL_ZONE, "DoNotSchedule", {"app": f"a{i % 3}"})
        elif i % 4 == 1:
            pw.pod_anti_affinity({"app": f"a{i % 3}"}, api.LABEL_HOSTNAME)
        elif i % 4 == 2:
            pw.host_port(8000 + (i % 5))
        pods.append(pw.obj())
    return nodes, pods


def _assert_solve_equal(single, multi):
    np.testing.assert_array_equal(
        np.asarray(single.assignment), np.asarray(multi.assignment)
    )
    np.testing.assert_array_equal(
        np.asarray(single.reasons), np.asarray(multi.reasons)
    )
    np.testing.assert_array_equal(
        np.asarray(single.feasible_counts),
        np.asarray(multi.feasible_counts),
    )
    np.testing.assert_array_equal(
        np.asarray(single.cluster.requested),
        np.asarray(multi.cluster.requested),
    )
    assert int(single.wave_count) == int(multi.wave_count)
    assert int(single.wave_fallbacks) == int(multi.wave_fallbacks)


@pytest.mark.parametrize("seed", range(3))
def test_podsharded_wavefront_matches_scan_and_single_chip(seed):
    """The pod-sharded wavefront must equal BOTH the single-chip
    wavefront (bit-identical, including the fallback counters) and the
    classic scan — the same chain the node-sharded wavefront satisfies,
    on the orthogonal axis."""
    assert len(jax.devices()) == 8, "conftest must provide 8 virtual devices"
    nodes, pods = _workload(seed)
    snap, _ = schema.SnapshotBuilder().build(nodes, pods)
    plan = assign.plan_waves(snap)
    scan = assign.greedy_assign(snap)
    single = assign.wavefront_assign(snap, plan.members)
    multi = sharded.podsharded_wavefront_assign(
        snap, plan.members, sharded.make_pod_mesh(8)
    )
    np.testing.assert_array_equal(
        np.asarray(scan.assignment), np.asarray(single.assignment)
    )
    _assert_solve_equal(single, multi)


def test_podsharded_wavefront_pads_indivisible_waves():
    """A hand-built wave width NOT divisible by the mesh size: the
    wrapper pads the member axis with inert -1 columns and placements
    stay identical to the unpadded single-chip plan — the padding is
    exercised, not just the error path."""
    nodes, pods = _workload(5)
    snap, _ = schema.SnapshotBuilder().build(nodes, pods)
    p = np.asarray(snap.pods.req).shape[0]
    order = np.argsort(
        -np.asarray(snap.pods.priority), kind="stable"
    ).astype(np.int32)
    width = 20  # not a multiple of 8 -> padded to 24
    n_waves = (p + width - 1) // width
    members = np.full((max(8, n_waves), width), -1, np.int32)
    for w in range(n_waves):
        chunk = order[w * width:(w + 1) * width]
        members[w, : len(chunk)] = chunk
    mesh = sharded.make_pod_mesh(8)
    padded = sharded.pad_wave_columns(members, mesh)
    assert padded.shape[1] == 24 and (padded[:, width:] == -1).all()
    single = assign.wavefront_assign(snap, members)
    multi = sharded.podsharded_wavefront_assign(snap, members, mesh)
    np.testing.assert_array_equal(
        np.asarray(single.assignment), np.asarray(multi.assignment)
    )
    np.testing.assert_array_equal(
        np.asarray(single.reasons), np.asarray(multi.reasons)
    )


def test_podsharded_wavefront_serialized_waves_parity():
    """A coupled contiguous partition forces the serialized-wave
    fallback; the pod shard must fall back identically (the serial path
    runs replicated on every device)."""
    nodes, pods = _workload(7)
    snap, _ = schema.SnapshotBuilder().build(nodes, pods)
    p = np.asarray(snap.pods.req).shape[0]
    order = np.argsort(
        -np.asarray(snap.pods.priority), kind="stable"
    ).astype(np.int32)
    n_waves = (p + 31) // 32
    members = np.full((max(8, n_waves), 32), -1, np.int32)
    for w in range(n_waves):
        chunk = order[w * 32:(w + 1) * 32]
        members[w, : len(chunk)] = chunk
    single = assign.wavefront_assign(snap, members)
    assert int(single.wave_fallbacks) > 0  # coupling actually fired
    multi = sharded.podsharded_wavefront_assign(
        snap, members, sharded.make_pod_mesh(8)
    )
    _assert_solve_equal(single, multi)


# -- a wave costs what its members cost, under the pod shard ----------
#
# The loops' trip count and the one-member branch are functions of the
# replicated plan, so every shard takes the same branch and runs the same
# trips; results and all three counters must equal the single chip's.


@pytest.mark.parametrize(
    "name", ["repel-64", "repel-64-owners", "widths", "holes"]
)
def test_podsharded_wave_steps_follow_the_members(name):
    snap, members, want = wave_cases.step_case(name)
    scan = assign.greedy_assign(snap)
    single = assign.wavefront_assign(snap, members)
    multi = sharded.podsharded_wavefront_assign(snap, members, sharded.make_pod_mesh(8))
    for res in (single, multi):
        for field in ("assignment", "scores", "feasible_counts", "reasons"):
            np.testing.assert_array_equal(
                np.asarray(getattr(scan, field)),
                np.asarray(getattr(res, field)), err_msg=field,
            )
        np.testing.assert_array_equal(
            np.asarray(scan.cluster.requested),
            np.asarray(res.cluster.requested),
        )
        assert wave_cases.counters(res) == want


def test_podsharded_wavefront_mesh_sizes():
    nodes, pods = _workload(9, n_nodes=16, n_pods=40)
    snap, _ = schema.SnapshotBuilder().build(nodes, pods)
    plan = assign.plan_waves(snap)
    want = np.asarray(assign.wavefront_assign(snap, plan.members).assignment)
    for n_dev in (2, 4):
        got = sharded.podsharded_wavefront_assign(
            snap, plan.members, sharded.make_pod_mesh(n_dev)
        )
        np.testing.assert_array_equal(want, np.asarray(got.assignment))


def test_podsharded_wavefront_gang_release_parity():
    """Gang all-or-nothing releases identically under the pod shard:
    the post-pass runs replicated on the gathered assignment."""
    nodes = [
        make_node(f"n{i}").capacity(cpu_milli=2000, mem=4 * GI, pods=4).obj()
        for i in range(8)
    ]
    pods = [
        make_pod(f"g{i}").req(cpu_milli=1500, mem=GI).group("g", size=70).obj()
        for i in range(70)
    ] + [
        make_pod(f"s{i}").req(cpu_milli=100, mem=MI).obj() for i in range(10)
    ]
    snap, _ = schema.SnapshotBuilder().build(nodes, pods)
    ng = schema.num_groups(snap)
    plan = assign.plan_waves(snap)
    single = assign.wavefront_assign(snap, plan.members, n_groups=ng)
    assert (np.asarray(single.assignment)[:70] == -1).all()  # gang released
    multi = sharded.podsharded_wavefront_assign(
        snap, plan.members, sharded.make_pod_mesh(8), n_groups=ng
    )
    _assert_solve_equal(single, multi)


def test_podsharded_wavefront_jit_dispatch():
    """The jitted wrapper plans, pads, and dispatches like the eager
    wrapper."""
    nodes, pods = _workload(3, n_nodes=16, n_pods=32)
    snap, _ = schema.SnapshotBuilder().build(nodes, pods)
    mesh = sharded.make_pod_mesh(8)
    call = sharded.podsharded_wavefront_jit(mesh)
    got = call(snap)
    want = assign.wavefront_assign(snap, assign.plan_waves(snap).members)
    np.testing.assert_array_equal(
        np.asarray(want.assignment), np.asarray(got.assignment)
    )


# -- preemption twins --------------------------------------------------------


def _random_preemption_batch(rng, n=16, k=8, l=3, p=16, r=4):
    """Synthetic but well-formed PostFilter batch: per-(level, node) a
    true eviction-order permutation, eligible prefix lengths within K,
    non-negative victim usage, mixed-sign free rows (overcommitted nodes
    included)."""
    perm = np.empty((l, n, k), np.int32)
    for li in range(l):
        for ni in range(n):
            perm[li, ni] = rng.permutation(k)
    return preemption.PreemptionBatch(
        free=jnp.asarray(
            rng.uniform(-2.0, 4.0, size=(n, r)).astype(np.float32)
        ),
        victim_req=jnp.asarray(
            rng.uniform(0.0, 2.0, size=(n, k, r)).astype(np.float32)
        ),
        perm=jnp.asarray(perm),
        elig_len=jnp.asarray(
            rng.integers(0, k + 1, size=(l, n)).astype(np.int32)
        ),
        viol=jnp.asarray(rng.random((l, n, k)) < 0.3),
        pods_req=jnp.asarray(
            rng.uniform(0.0, 3.0, size=(p, r)).astype(np.float32)
        ),
        pod_level=jnp.asarray(
            rng.integers(0, l, size=(p,)).astype(np.int32)
        ),
    )


@pytest.mark.parametrize("seed", range(3))
def test_sharded_batched_dry_run_parity(seed):
    rng = np.random.default_rng(seed)
    batch = _random_preemption_batch(rng)
    single = preemption.batched_dry_run(batch)
    multi = sharded.sharded_batched_dry_run(
        batch, sharded.make_pod_mesh(8)
    )
    np.testing.assert_array_equal(
        np.asarray(single.feasible), np.asarray(multi.feasible)
    )
    np.testing.assert_array_equal(
        np.asarray(single.min_k), np.asarray(multi.min_k)
    )
    np.testing.assert_array_equal(
        np.asarray(single.viol_k), np.asarray(multi.viol_k)
    )


def test_sharded_batched_dry_run_rejects_indivisible():
    rng = np.random.default_rng(0)
    batch = _random_preemption_batch(rng, p=12)
    with pytest.raises(ValueError, match="not divisible"):
        sharded.sharded_batched_dry_run(batch, sharded.make_pod_mesh(8))


@pytest.mark.parametrize("seed", range(2))
def test_sharded_static_feasible_parity(seed):
    """The static-Filter sweep sharded on the preemptor axis: identical
    bool[P, N] rows, including named-node, taint, and affinity pods."""
    nodes, pods = _workload(seed, n_nodes=16, n_pods=40)
    pods[0] = make_pod("named").req(cpu_milli=100).node_name("n3").obj()
    snap, _ = schema.SnapshotBuilder().build(nodes, pods)
    single = preemption.run_static_feasible_batch(
        snap.cluster, snap.pods, snap.selectors
    )
    multi = sharded.sharded_static_feasible_batch(
        snap.cluster, snap.pods, snap.selectors, sharded.make_pod_mesh(8)
    )
    np.testing.assert_array_equal(np.asarray(single), np.asarray(multi))
