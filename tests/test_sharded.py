"""Sharded solve must place pods identically to the single-chip solve.

Runs on the 8-virtual-device CPU mesh from conftest.py.
"""

import jax
import numpy as np
import pytest
import wave_cases

from kubernetes_tpu.api import types as api
from kubernetes_tpu.ops import assign, schema
from kubernetes_tpu.parallel import sharded
from kubernetes_tpu.testing.wrappers import GI, MI, make_node, make_pod

pytestmark = pytest.mark.multichip


def _workload(seed, n_nodes=32, n_pods=40):
    rng = np.random.default_rng(seed)
    zones = ["z1", "z2", "z3"]
    nodes = []
    for i in range(n_nodes):
        nw = (
            make_node(f"n{i}")
            .capacity(
                cpu_milli=int(rng.choice([4000, 8000, 16000])),
                mem=int(rng.choice([8, 16, 32])) * GI,
                pods=110,
            )
            .zone(str(rng.choice(zones)))
        )
        if rng.random() < 0.2:
            nw.taint("dedicated", "batch", api.NO_SCHEDULE)
        if rng.random() < 0.2:
            nw.taint("flaky", "true", api.PREFER_NO_SCHEDULE)
        nodes.append(nw.obj())
    pods = []
    for i in range(n_pods):
        pw = make_pod(f"p{i}").req(
            cpu_milli=int(rng.choice([100, 500, 1000, 2000])),
            mem=int(rng.choice([128, 512, 1024])) * MI,
        )
        if rng.random() < 0.3:
            pw.node_selector_kv(api.LABEL_ZONE, str(rng.choice(zones)))
        if rng.random() < 0.2:
            pw.toleration("dedicated", api.OP_EQUAL, "batch", api.NO_SCHEDULE)
        if rng.random() < 0.25:
            pw.preferred_affinity(
                int(rng.integers(1, 50)), api.LABEL_ZONE, api.OP_IN, [str(rng.choice(zones))]
            )
        pods.append(pw.obj())
    return nodes, pods


@pytest.mark.parametrize("seed", range(3))
def test_sharded_matches_single_chip(seed):
    assert len(jax.devices()) == 8, "conftest must provide 8 virtual devices"
    nodes, pods = _workload(seed)
    snap, meta = schema.SnapshotBuilder().build(nodes, pods)

    single = assign.greedy_assign(snap)
    mesh = sharded.make_mesh(8)
    multi = sharded.sharded_greedy_assign(snap, mesh)

    np.testing.assert_array_equal(
        np.asarray(single.assignment), np.asarray(multi.assignment)
    )
    np.testing.assert_array_equal(
        np.asarray(single.feasible_counts), np.asarray(multi.feasible_counts)
    )
    # post-solve cluster state matches too (gather the sharded one)
    np.testing.assert_allclose(
        np.asarray(single.cluster.requested),
        np.asarray(multi.cluster.requested),
        rtol=0,
        atol=0,
    )


def test_mesh_sizes():
    nodes, pods = _workload(7, n_nodes=16, n_pods=12)
    snap, _ = schema.SnapshotBuilder().build(nodes, pods)
    want = np.asarray(assign.greedy_assign(snap).assignment)
    for n_dev in (2, 4):
        mesh = sharded.make_mesh(n_dev)
        got = np.asarray(sharded.sharded_greedy_assign(snap, mesh).assignment)
        np.testing.assert_array_equal(want, got)


def test_sharded_with_spread_and_interpod():
    """Constraint count-state must stay consistent across shards (the
    psum-broadcast of the winning node's topology values)."""
    from kubernetes_tpu.testing.oracle import Oracle

    nodes = [
        make_node(f"n{i}").capacity(cpu_milli=8000, mem=16 * GI, pods=20)
        .zone(f"z{i % 3}").obj()
        for i in range(16)
    ]
    pods = []
    for i in range(24):
        pw = make_pod(f"p{i}").labels(app=f"a{i % 2}").req(cpu_milli=500)
        if i % 3 == 0:
            pw.spread(max_skew=1, topology_key=api.LABEL_ZONE,
                      selector={"app": f"a{i % 2}"})
        elif i % 3 == 1:
            pw.pod_anti_affinity({"app": f"a{i % 2}"}, topology_key=api.LABEL_HOSTNAME)
        else:
            pw.pod_affinity({"app": f"a{i % 2}"}, topology_key=api.LABEL_ZONE)
        pods.append(pw.obj())

    snap, meta = schema.SnapshotBuilder().build(nodes, pods)
    single = assign.greedy_assign(snap, topo_z=meta.topo_z)
    mesh = sharded.make_mesh(8)
    multi = sharded.sharded_greedy_assign(snap, mesh, topo_z=meta.topo_z)
    np.testing.assert_array_equal(
        np.asarray(single.assignment), np.asarray(multi.assignment)
    )
    # and both match the oracle
    got = [meta.node_name(int(i)) for i in np.asarray(single.assignment)[:24]]
    want = Oracle(nodes).schedule(pods)
    assert got == want


def test_sharded_greedy_scores_prefpod_and_images():
    """Round-4: the extra-score families (preferred inter-pod affinity,
    ImageLocality) are now psum-hoisted — the sharded greedy must match
    the single-chip solve instead of raising."""
    nodes = []
    for i in range(16):
        nw = (
            make_node(f"n{i}").capacity(cpu_milli=8000, mem=16 * GI, pods=20)
            .zone(f"z{i % 3}")
        )
        if i % 2 == 0:
            nw.image(f"img-{i % 4}", 500 * MI)
        nodes.append(nw.obj())
    def _pref(pw, selector):
        aff = pw.pod.spec.affinity or api.Affinity()
        pw.pod.spec.affinity = aff
        if aff.pod_affinity is None:
            aff.pod_affinity = api.PodAffinity()
        aff.pod_affinity.preferred.append(
            api.WeightedPodAffinityTerm(
                weight=40,
                term=api.PodAffinityTerm(
                    label_selector=api.LabelSelector(match_labels=selector),
                    topology_key=api.LABEL_ZONE,
                ),
            )
        )

    pods = []
    for i in range(20):
        pw = make_pod(f"p{i}").labels(app=f"a{i % 2}").req(cpu_milli=400)
        if i % 2 == 0:
            _pref(pw, {"app": f"a{i % 2}"})
        if i % 3 == 0:
            pw.image(f"img-{i % 4}")
        pods.append(pw.obj())
    snap, meta = schema.SnapshotBuilder().build(nodes, pods)
    feats = assign.features_of(snap)
    assert feats.interpod_pref or feats.images
    single = assign.greedy_assign(snap, topo_z=meta.topo_z)
    mesh = sharded.make_mesh(8)
    multi = sharded.sharded_greedy_assign(snap, mesh, topo_z=meta.topo_z)
    np.testing.assert_array_equal(
        np.asarray(single.assignment), np.asarray(multi.assignment)
    )


def _wavefront_workload(seed, n_nodes=32, n_pods=80):
    """Wavefront-shaped batch: every dynamic-coupling family active
    (ports, spread, anti-affinity) so the wave partition, the mini-scan
    corrections, and the serialized fallback all exercise."""
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


@pytest.mark.parametrize("seed", range(3))
def test_sharded_wavefront_matches_scan_and_single_chip(seed):
    """The sharded wavefront must equal BOTH the single-chip wavefront
    (bit-identical, including the fallback counters) and the classic
    scan (the wavefront's own parity contract) — the full chain the
    mesh hot path rests on."""
    nodes, pods = _wavefront_workload(seed)
    snap, meta = schema.SnapshotBuilder().build(nodes, pods)
    plan = assign.plan_waves(snap)
    scan = assign.greedy_assign(snap)
    single = assign.wavefront_assign(snap, plan.members)
    mesh = sharded.make_mesh(8)
    multi = sharded.sharded_wavefront_assign(snap, plan.members, mesh)
    np.testing.assert_array_equal(
        np.asarray(scan.assignment), np.asarray(single.assignment)
    )
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
    np.testing.assert_allclose(
        np.asarray(single.cluster.requested),
        np.asarray(multi.cluster.requested),
        rtol=0, atol=0,
    )
    assert int(single.wave_count) == int(multi.wave_count)
    assert int(single.wave_fallbacks) == int(multi.wave_fallbacks)


def test_sharded_wavefront_serialized_waves_parity():
    """A hand-built COUPLED partition (naive contiguous 32-chunks of the
    solve order) forces the device-side safety check to serialize waves:
    any contiguous partition is scan-identical, on both layouts."""
    nodes, pods = _wavefront_workload(5)
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
    scan = assign.greedy_assign(snap)
    single = assign.wavefront_assign(snap, members)
    multi = sharded.sharded_wavefront_assign(
        snap, members, sharded.make_mesh(8)
    )
    assert int(single.wave_fallbacks) > 0  # coupling actually fired
    np.testing.assert_array_equal(
        np.asarray(scan.assignment), np.asarray(single.assignment)
    )
    np.testing.assert_array_equal(
        np.asarray(single.assignment), np.asarray(multi.assignment)
    )
    assert int(single.wave_fallbacks) == int(multi.wave_fallbacks)


# -- a wave costs what its members cost, under the node shard ---------
#
# The loops' trip count and the one-member branch are functions of the
# replicated plan, so every shard takes the same branch and runs the same
# trips; results and all three counters must equal the single chip's.


@pytest.mark.parametrize(
    "name", ["repel-64", "repel-64-owners", "widths", "holes"]
)
def test_sharded_wave_steps_follow_the_members(name):
    snap, members, want = wave_cases.step_case(name)
    scan = assign.greedy_assign(snap)
    single = assign.wavefront_assign(snap, members)
    multi = sharded.sharded_wavefront_assign(snap, members, sharded.make_mesh(8))
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


def test_sharded_wavefront_and_greedy_gang_release_parity():
    """Gang all-or-nothing releases identically across shards: the
    shared post-pass subtracts only owned rows per shard."""
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
    mesh = sharded.make_mesh(8)
    scan = assign.greedy_assign(snap, n_groups=ng)
    wf_multi = sharded.sharded_wavefront_assign(
        snap, plan.members, mesh, n_groups=ng
    )
    gr_multi = sharded.sharded_greedy_assign(snap, mesh, n_groups=ng)
    assert (np.asarray(scan.assignment)[:70] == -1).all()  # gang released
    for got in (wf_multi, gr_multi):
        np.testing.assert_array_equal(
            np.asarray(scan.assignment), np.asarray(got.assignment)
        )
        np.testing.assert_array_equal(
            np.asarray(scan.reasons), np.asarray(got.reasons)
        )
        np.testing.assert_allclose(
            np.asarray(scan.cluster.requested),
            np.asarray(got.cluster.requested),
            rtol=0, atol=0,
        )


def _auction_parity(nodes, pods, tie_k=64, n_dev=8):
    from kubernetes_tpu.ops import auction as auc

    snap, meta = schema.SnapshotBuilder().build(nodes, pods)
    feats = assign.features_of(snap)
    tsplit = assign.required_topo_z_split(snap)
    ng = schema.num_groups(snap)
    single = auc.auction_assign(
        snap, n_groups=ng, features=feats, topo_z=tsplit, tie_k=tie_k
    )
    mesh = sharded.make_mesh(n_dev)
    multi = sharded.sharded_auction_assign(
        snap, mesh, n_groups=ng, features=feats, topo_z=tsplit, tie_k=tie_k
    )
    np.testing.assert_array_equal(
        np.asarray(single.assignment), np.asarray(multi.assignment)
    )
    np.testing.assert_array_equal(
        np.asarray(single.reasons), np.asarray(multi.reasons)
    )
    np.testing.assert_allclose(
        np.asarray(single.cluster.requested),
        np.asarray(multi.cluster.requested),
        rtol=0, atol=0,
    )
    return single, multi, meta


def test_sharded_auction_basic_parity():
    """Sharded auction == single-chip auction, resources-only + gangs."""
    rng = np.random.default_rng(11)
    nodes = [
        make_node(f"n{i}")
        .capacity(cpu_milli=int(rng.choice([8000, 16000])), mem=32 * GI, pods=64)
        .zone(f"z{i % 3}").obj()
        for i in range(32)
    ]
    pods = [
        make_pod(f"p{i}")
        .req(cpu_milli=int(rng.choice([500, 1000])), mem=512 * MI)
        .group(f"g{i % 4}", size=8)
        .obj()
        for i in range(32)
    ]
    single, multi, _ = _auction_parity(nodes, pods)
    assert (np.asarray(single.assignment) >= 0).sum() == 32


def test_sharded_auction_spread_interpod_parity():
    """Sharded auction must repair spread + anti-affinity identically."""
    nodes = [
        make_node(f"n{i}").capacity(cpu_milli=8000, mem=16 * GI, pods=20)
        .zone(f"z{i % 4}").obj()
        for i in range(32)
    ]
    pods = []
    for i in range(40):
        pw = make_pod(f"p{i}").labels(app=f"s{i % 5}").req(cpu_milli=300)
        if i % 2 == 0:
            pw.spread(1, api.LABEL_ZONE, "DoNotSchedule", {"app": f"s{i % 5}"})
        else:
            pw.pod_anti_affinity({"app": f"s{i % 5}"}, api.LABEL_HOSTNAME)
        pods.append(pw.obj())
    single, multi, meta = _auction_parity(nodes, pods)
    placed = (np.asarray(single.assignment)[:40] >= 0).sum()
    assert placed > 0


def test_sharded_auction_gang_release_parity():
    """An unplaceable gang releases identically on both layouts."""
    nodes = [
        make_node(f"n{i}").capacity(cpu_milli=2000, mem=4 * GI, pods=4).obj()
        for i in range(8)
    ]
    # gang of 12 pods each needing 1500m: at most 8 can place -> released
    pods = [
        make_pod(f"g{i}").req(cpu_milli=1500, mem=GI).group("g", size=12).obj()
        for i in range(12)
    ]
    single, multi, _ = _auction_parity(nodes, pods, n_dev=4)
    assert (np.asarray(single.assignment)[:12] == -1).all()
    assert np.asarray(single.gang_dropped).any()
    np.testing.assert_array_equal(
        np.asarray(single.gang_dropped), np.asarray(multi.gang_dropped)
    )
