"""Wavefront/scan parity: wavefront_assign must place *identically* to
greedy_assign — same assignments, same failure reasons, same feasible
counts, same winning scores — across every constraint family, including
its forced-serialization and per-pod re-evaluation fallbacks.

The wavefront contract is stronger than "the planner produces good
waves": ANY contiguous partition of the solve order must solve exactly
(the device re-verifies coupling and serializes unsafe waves), so these
tests also drive hostile hand-built partitions.
"""

import numpy as np
import pytest
import wave_cases

from kubernetes_tpu.api import types as api
from kubernetes_tpu.ops import assign, schema
from kubernetes_tpu.testing.wrappers import GI, MI, make_node, make_pod


def run_both(nodes, pods, bound=(), wave_cap=8, members=None):
    snap, meta = schema.SnapshotBuilder().build(nodes, pods, bound_pods=bound)
    scan = assign.greedy_assign_jit()(snap)
    wave = assign.wavefront_assign_jit()(
        snap, wave_members=members, wave_cap=wave_cap
    )
    return snap, meta, scan, wave


def assert_parity(scan, wave, n_pods):
    assert (
        np.asarray(scan.assignment)[:n_pods]
        == np.asarray(wave.assignment)[:n_pods]
    ).all(), "placements diverge"
    assert (
        np.asarray(scan.reasons)[:n_pods]
        == np.asarray(wave.reasons)[:n_pods]
    ).all(), "failure reasons diverge"
    assert (
        np.asarray(scan.feasible_counts)[:n_pods]
        == np.asarray(wave.feasible_counts)[:n_pods]
    ).all(), "feasible counts diverge"
    s1 = np.asarray(scan.scores)[:n_pods]
    s2 = np.asarray(wave.scores)[:n_pods]
    placed = np.asarray(scan.assignment)[:n_pods] >= 0
    assert np.allclose(s1[placed], s2[placed]), "winning scores diverge"
    # the post-solve cluster usage must agree too (it seeds later batches)
    np.testing.assert_allclose(
        np.asarray(scan.cluster.requested),
        np.asarray(wave.cluster.requested),
    )


def one_wave_members(snap):
    """A hostile plan: the whole batch in a single wave."""
    prio = np.asarray(snap.pods.priority)
    p = prio.shape[0]
    order = np.argsort(-prio, kind="stable").astype(np.int32)
    k = max(8, 1 << (p - 1).bit_length())
    members = np.full((8, k), -1, dtype=np.int32)
    members[0, :p] = order
    return members


def test_resources_only_identical_pods():
    """Identical pods all argmax to the same node — the mini-scan must
    reproduce the scan's node-by-node stacking exactly."""
    nodes = [
        make_node(f"n{i}").capacity(cpu_milli=4000, mem=8 * GI, pods=110).obj()
        for i in range(6)
    ]
    pods = [
        make_pod(f"p{i}").req(cpu_milli=900, mem=1 * GI).obj()
        for i in range(20)
    ]
    _, _, scan, wave = run_both(nodes, pods)
    assert_parity(scan, wave, len(pods))
    assert int(wave.wave_count) >= 1


def test_fit_flip_forces_full_reeval():
    """Nearly-full nodes: placements inside one wave flip later members'
    resource fit — the per-pod exact fallback must fire and match."""
    nodes = [
        make_node("n0").capacity(cpu_milli=1000, mem=2 * GI, pods=110).obj(),
        make_node("n1").capacity(cpu_milli=700, mem=2 * GI, pods=110).obj(),
    ]
    pods = [
        make_pod(f"p{i}").req(cpu_milli=600, mem=256 * MI).obj()
        for i in range(4)
    ]
    snap, _, scan, _ = run_both(nodes, pods)
    wave = assign.wavefront_assign_jit()(
        snap, wave_members=one_wave_members(snap)
    )
    assert_parity(scan, wave, len(pods))
    assert int(wave.wave_fallbacks) > 0  # the flips were detected


def test_ports_conflict_parity():
    nodes = [
        make_node(f"n{i}").capacity(cpu_milli=8000, mem=16 * GI, pods=110).obj()
        for i in range(3)
    ]
    pods = [
        make_pod(f"w{i}").req(cpu_milli=500, mem=256 * MI).host_port(80).obj()
        for i in range(5)
    ]
    _, _, scan, wave = run_both(nodes, pods)
    assert_parity(scan, wave, len(pods))


def test_spread_coupling_serializes_wave():
    """Same-service spread pods crammed into one wave couple through the
    count rows — the device must detect it and serialize that wave."""
    nodes = [
        make_node(f"n{i}")
        .capacity(cpu_milli=32000, mem=64 * GI, pods=110)
        .zone(f"z{i % 3}")
        .obj()
        for i in range(9)
    ]
    pods = [
        make_pod(f"s{i}")
        .req(cpu_milli=500, mem=256 * MI)
        .label("app", "svc")
        .spread(1, api.LABEL_ZONE, "DoNotSchedule", {"app": "svc"})
        .obj()
        for i in range(9)
    ]
    snap, _, scan, _ = run_both(nodes, pods)
    wave = assign.wavefront_assign_jit()(
        snap, wave_members=one_wave_members(snap)
    )
    assert_parity(scan, wave, len(pods))
    assert int(wave.wave_fallbacks) > 0  # wave went serial
    # and the planner keeps them apart, so the planned path stays fast
    planned = assign.wavefront_assign_jit()(snap, wave_cap=8)
    assert_parity(scan, planned, len(pods))
    assert int(planned.wave_fallbacks) == 0


def test_soft_spread_score_parity():
    nodes = [
        make_node(f"n{i}")
        .capacity(cpu_milli=32000, mem=64 * GI, pods=110)
        .zone(f"z{i % 4}")
        .obj()
        for i in range(8)
    ]
    pods = [
        make_pod(f"s{i}")
        .req(cpu_milli=500, mem=256 * MI)
        .label("app", f"svc{i % 3}")
        .spread(2, api.LABEL_ZONE, "ScheduleAnyway", {"app": f"svc{i % 3}"})
        .obj()
        for i in range(12)
    ]
    _, _, scan, wave = run_both(nodes, pods)
    assert_parity(scan, wave, len(pods))


def test_interpod_anti_affinity_parity():
    nodes = [
        make_node(f"n{i}").capacity(cpu_milli=32000, mem=64 * GI, pods=110).obj()
        for i in range(10)
    ]
    pods = []
    for i in range(20):
        svc = i % 4
        pods.append(
            make_pod(f"a{i}")
            .req(cpu_milli=500, mem=256 * MI)
            .label("app", f"s{svc}")
            .pod_anti_affinity({"app": f"s{svc}"}, api.LABEL_HOSTNAME)
            .obj()
        )
    snap, _, scan, wave = run_both(nodes, pods)
    assert_parity(scan, wave, len(pods))
    # hostile single-wave partition: coupling detected, wave serialized
    forced = assign.wavefront_assign_jit()(
        snap, wave_members=one_wave_members(snap)
    )
    assert_parity(scan, forced, len(pods))


def test_interpod_affinity_first_pod_escape():
    """Required affinity with the first-pod-of-group escape: later pods
    must see the first placement's presence bits at wave boundaries."""
    nodes = [
        make_node(f"n{i}")
        .capacity(cpu_milli=32000, mem=64 * GI, pods=110)
        .zone(f"z{i % 2}")
        .obj()
        for i in range(6)
    ]
    pods = [
        make_pod(f"co{i}")
        .req(cpu_milli=500, mem=256 * MI)
        .label("app", "web")
        .pod_affinity({"app": "web"}, api.LABEL_ZONE)
        .obj()
        for i in range(6)
    ]
    snap, _, scan, wave = run_both(nodes, pods, wave_cap=4)
    assert_parity(scan, wave, len(pods))


def test_gang_release_parity():
    nodes = [
        make_node(f"n{i}").capacity(cpu_milli=2000, mem=4 * GI, pods=110).obj()
        for i in range(4)
    ]
    pods = [
        make_pod(f"g{i}")
        .req(cpu_milli=900, mem=512 * MI)
        .group(f"gang-{i // 3}")
        .obj()
        for i in range(9)
    ]
    _, _, scan, wave = run_both(nodes, pods, wave_cap=4)
    assert_parity(scan, wave, len(pods))
    got = np.asarray(wave.reasons)[:9]
    assert (got == np.asarray(scan.reasons)[:9]).all()


@pytest.mark.parametrize("seed", range(6))
def test_randomized_mixed_constraints(seed):
    """Randomized mixes of every family + mixed priorities, solved with
    a random wave cap — the strongest drift detector."""
    rng = np.random.default_rng(seed)
    zones = ["z1", "z2", "z3"]
    nodes = []
    for i in range(16):
        nw = (
            make_node(f"n{i}")
            .capacity(
                cpu_milli=int(rng.choice([2000, 4000, 8000])),
                mem=int(rng.choice([4, 8, 16])) * GI,
                pods=int(rng.choice([5, 110])),
            )
            .zone(str(rng.choice(zones)))
        )
        if rng.random() < 0.2:
            nw.taint("dedicated", "batch", api.NO_SCHEDULE)
        nodes.append(nw.obj())

    pods = []
    for i in range(40):
        pw = make_pod(f"p{i}").req(
            cpu_milli=int(rng.choice([100, 500, 1000, 2000])),
            mem=int(rng.choice([128, 512, 1024])) * MI,
        )
        pw.priority(int(rng.integers(-2, 3)))
        r = rng.random()
        if r < 0.2:
            pw.label("app", f"svc{i % 4}").spread(
                2, api.LABEL_ZONE, "DoNotSchedule", {"app": f"svc{i % 4}"}
            )
        elif r < 0.4:
            pw.label("app", f"svc{i % 4}").pod_anti_affinity(
                {"app": f"svc{i % 4}"}, api.LABEL_HOSTNAME
            )
        elif r < 0.5:
            pw.host_port(int(rng.choice([80, 443])))
        elif r < 0.6:
            pw.node_selector_kv(api.LABEL_ZONE, str(rng.choice(zones)))
        if rng.random() < 0.15:
            pw.group(f"gang-{i % 3}")
        pods.append(pw.obj())

    cap = int(rng.choice([4, 8, 16]))
    _, _, scan, wave = run_both(nodes, pods, wave_cap=cap)
    assert_parity(scan, wave, len(pods))


@pytest.mark.parametrize("seed", range(3))
def test_random_partitions_are_exact(seed):
    """Device-side safety: an arbitrary (not planner-produced) contiguous
    partition of the solve order must still match the scan."""
    rng = np.random.default_rng(100 + seed)
    nodes = [
        make_node(f"n{i}")
        .capacity(cpu_milli=4000, mem=8 * GI, pods=110)
        .zone(f"z{i % 2}")
        .obj()
        for i in range(6)
    ]
    pods = []
    for i in range(18):
        pw = make_pod(f"p{i}").req(
            cpu_milli=int(rng.choice([500, 1000])), mem=512 * MI
        )
        if i % 3 == 0:
            pw.label("app", "x").spread(
                1, api.LABEL_ZONE, "DoNotSchedule", {"app": "x"}
            )
        pods.append(pw.obj())
    snap, _ = schema.SnapshotBuilder().build(nodes, pods)
    scan = assign.greedy_assign_jit()(snap)

    prio = np.asarray(snap.pods.priority)
    p = prio.shape[0]
    order = np.argsort(-prio, kind="stable").astype(np.int32)
    # random contiguous split into waves of random widths, K=8
    k = 8
    cuts = sorted(rng.choice(np.arange(1, p), size=4, replace=False).tolist())
    chunks, start = [], 0
    for c in cuts + [p]:
        while c - start > k:
            chunks.append(order[start : start + k])
            start += k
        chunks.append(order[start:c])
        start = c
    chunks = [c for c in chunks if len(c)]
    w_pad = max(8, 1 << (len(chunks) - 1).bit_length())
    members = np.full((w_pad, k), -1, dtype=np.int32)
    for wi, ch in enumerate(chunks):
        members[wi, : len(ch)] = ch
    wave = assign.wavefront_assign_jit()(snap, wave_members=members)
    assert_parity(scan, wave, len(pods))


# -- a wave costs what its members cost (wave_steps) -------------------------
#
# The in-wave loops run to the wave's last valid lane and a one-member
# wave is the scan's own step, so `wave_steps` (the in-wave sequential
# steps the device ran) is the members' sum — and every result below
# must stay the scan's, bit for bit.


@pytest.mark.parametrize(
    "name",
    ["repel-64", "repel-64-owners", "repel-128", "repel-128-owners",
     "widths", "holes"],
)
def test_wave_steps_follow_the_members(name):
    snap, members, want = wave_cases.step_case(name)
    scan = assign.greedy_assign_jit()(snap)
    wave = assign.wavefront_assign_jit()(snap, wave_members=members)
    wave_cases.assert_bit_parity(scan, wave)
    assert wave_cases.counters(wave) == want
    if name.startswith("repel"):
        # one pod a node, and none beside a bound owner of the term
        placed = np.asarray(wave.assignment).tolist()
        assert min(placed) >= 0 and len(set(placed)) == len(placed)
        if name.endswith("owners"):
            owned = {3 * i for i in range(wave_cases.REPEL_OWNERS)}
            assert not owned & set(placed)


def test_coupled_wave_of_three_is_three_serial_steps():
    """Three mutually repelling pods forced into one wave: the safety
    check serializes it, 3 steps and 3 fallbacks; the lone waves beside
    it are a step each and no fallback."""
    pods = [wave_cases.repelling(f"p{i}").obj() for i in range(8)]
    snap, _ = schema.SnapshotBuilder().build(wave_cases.big_nodes(8), pods)
    o = wave_cases.solve_order(snap)
    members = wave_cases.plan_of([o[0:3]] + [[i] for i in o[3:]])
    scan = assign.greedy_assign_jit()(snap)
    wave = assign.wavefront_assign_jit()(snap, wave_members=members)
    wave_cases.assert_bit_parity(scan, wave)
    assert wave_cases.counters(wave) == (6, 3, 3 + 5)


def test_fit_flip_inside_a_five_member_wave_still_reevaluates():
    """test_fit_flip_forces_full_reeval's nodes under a 5-member wave:
    the flips are still found inside the shortened loop and counted as
    they were (4 on the parent's 32-step loop: pinned)."""
    nodes = [
        make_node("n0").capacity(cpu_milli=1000, mem=2 * GI, pods=110).obj(),
        make_node("n1").capacity(cpu_milli=700, mem=2 * GI, pods=110).obj(),
    ]
    pods = [
        make_pod(f"p{i}").req(cpu_milli=600, mem=256 * MI).obj()
        for i in range(8)
    ]
    snap, _ = schema.SnapshotBuilder().build(nodes, pods)
    o = wave_cases.solve_order(snap)
    scan = assign.greedy_assign_jit()(snap)
    wave = assign.wavefront_assign_jit()(
        snap, wave_members=wave_cases.plan_of([o[:5], o[5:]])
    )
    wave_cases.assert_bit_parity(scan, wave)
    assert wave_cases.counters(wave) == (2, 4, 5 + 3)
