"""PodTopologySpread + InterPodAffinity kernel tests (parity vs oracle and
pinned semantic cases)."""

import numpy as np
import pytest

from kubernetes_tpu.api import types as api
from kubernetes_tpu.ops import assign, schema
from kubernetes_tpu.testing.oracle import Oracle
from kubernetes_tpu.testing.wrappers import GI, MI, make_node, make_pod


def run_both(nodes, pods, bound=()):
    snap, meta = schema.SnapshotBuilder().build(nodes, pods, bound_pods=bound)
    result = assign.greedy_assign(snap, topo_z=meta.topo_z)
    got = [meta.node_name(int(i)) for i in np.asarray(result.assignment)[: len(pods)]]
    want = Oracle(nodes, bound_pods=bound).schedule(pods)
    return got, want


def _zoned_nodes(n, zones=3):
    return [
        make_node(f"n{i}").capacity(cpu_milli=16000, mem=32 * GI, pods=110)
        .zone(f"z{i % zones}").obj()
        for i in range(n)
    ]


# ---------------------------------------------------------------------------
# PodTopologySpread
# ---------------------------------------------------------------------------


def test_hard_spread_by_zone():
    nodes = _zoned_nodes(6)
    pods = [
        make_pod(f"p{i}").labels(app="web").req(cpu_milli=100)
        .spread(max_skew=1, topology_key=api.LABEL_ZONE, selector={"app": "web"})
        .obj()
        for i in range(9)
    ]
    got, want = run_both(nodes, pods)
    assert got == want
    # 9 pods over 3 zones with maxSkew 1 -> exactly 3 per zone
    zones = [int(g[1]) % 3 for g in got]
    assert sorted(np.bincount(zones, minlength=3).tolist()) == [3, 3, 3]


def test_hard_spread_blocks_when_skew_exceeded():
    nodes = [
        make_node("a").capacity(cpu_milli=16000, mem=32 * GI, pods=110).zone("z0").obj(),
        make_node("b").capacity(cpu_milli=50, mem=32 * GI, pods=110).zone("z1").obj(),
    ]
    # z1 can hold exactly one tiny pod.  p0->a, p1->b, p2->a (skew 1); p3
    # would need z0=3 vs min(z1)=1 -> skew 2 > maxSkew 1, and z1 is out of
    # cpu -> unschedulable from then on.
    pods = [
        make_pod(f"p{i}").labels(app="x").req(cpu_milli=50)
        .spread(max_skew=1, topology_key=api.LABEL_ZONE, selector={"app": "x"})
        .obj()
        for i in range(5)
    ]
    got, want = run_both(nodes, pods)
    assert got == want
    assert got[3] is None and got[4] is None


def test_spread_requires_topology_key():
    nodes = [
        make_node("zoned").zone("z1").obj(),
        make_node("bare").obj(),  # no zone label
    ]
    pods = [
        make_pod("p").labels(app="x")
        .spread(max_skew=1, topology_key=api.LABEL_ZONE, selector={"app": "x"})
        .obj()
    ]
    got, want = run_both(nodes, pods)
    assert got == want == ["zoned"]


def test_soft_spread_prefers_low_count_zone():
    nodes = _zoned_nodes(4, zones=2)
    bound = [
        make_pod(f"b{i}").labels(app="w").node_name("n0").obj() for i in range(3)
    ]
    pods = [
        make_pod("p").labels(app="w").req(cpu_milli=100)
        .spread(
            max_skew=1,
            topology_key=api.LABEL_ZONE,
            when_unsatisfiable="ScheduleAnyway",
            selector={"app": "w"},
        )
        .obj()
    ]
    got, want = run_both(nodes, pods, bound=bound)
    assert got == want
    # z0 already has 3 matching pods -> z1 preferred
    assert int(got[0][1]) % 2 == 1


# ---------------------------------------------------------------------------
# InterPodAffinity
# ---------------------------------------------------------------------------


def test_required_anti_affinity_by_hostname():
    nodes = _zoned_nodes(3)
    pods = [
        make_pod(f"p{i}").labels(app="db").req(cpu_milli=100)
        .pod_anti_affinity({"app": "db"}, topology_key=api.LABEL_HOSTNAME)
        .obj()
        for i in range(4)
    ]
    got, want = run_both(nodes, pods)
    assert got == want
    assert sorted(g for g in got[:3]) == ["n0", "n1", "n2"]
    assert got[3] is None  # no fourth distinct node


def test_required_affinity_colocates():
    nodes = _zoned_nodes(6)
    first = make_pod("lead").labels(app="grp").req(cpu_milli=100).obj()
    followers = [
        make_pod(f"f{i}").labels(app="grp").req(cpu_milli=100)
        .pod_affinity({"app": "grp"}, topology_key=api.LABEL_ZONE)
        .obj()
        for i in range(3)
    ]
    got, want = run_both(nodes, [first] + followers)
    assert got == want
    lead_zone = int(got[0][1]) % 3
    assert all(int(g[1]) % 3 == lead_zone for g in got[1:])


def test_first_pod_self_match_escape():
    """A pod whose affinity matches itself may schedule when nothing in the
    cluster matches yet (filtering.go:352-360)."""
    nodes = _zoned_nodes(3)
    pods = [
        make_pod("solo").labels(app="self").req(cpu_milli=100)
        .pod_affinity({"app": "self"}, topology_key=api.LABEL_ZONE)
        .obj()
    ]
    got, want = run_both(nodes, pods)
    assert got == want
    assert got[0] is not None


def test_first_pod_no_self_match_stays_pending():
    nodes = _zoned_nodes(3)
    pods = [
        make_pod("orphan").labels(app="other").req(cpu_milli=100)
        .pod_affinity({"app": "missing"}, topology_key=api.LABEL_ZONE)
        .obj()
    ]
    got, want = run_both(nodes, pods)
    assert got == want == [None]


def test_existing_pods_anti_affinity_blocks_incoming():
    nodes = _zoned_nodes(2, zones=2)
    bound = [
        make_pod("guard").labels(app="guard")
        .pod_anti_affinity({"app": "noisy"}, topology_key=api.LABEL_ZONE)
        .node_name("n0")
        .obj()
    ]
    pods = [make_pod("noisy-1").labels(app="noisy").req(cpu_milli=100).obj()]
    got, want = run_both(nodes, pods, bound=bound)
    assert got == want
    # n0 is in z0 where the guard's anti-affinity applies -> must land z1
    assert got[0] == "n1"


def test_batch_pod_anti_affinity_carries_forward():
    """Anti-affinity of a pod placed earlier in the batch must constrain
    later pods in the same solve (the in-scan counts_owner update)."""
    nodes = _zoned_nodes(2, zones=2)
    pods = [
        make_pod("guard").labels(app="guard").req(cpu_milli=100)
        .pod_anti_affinity({"app": "noisy"}, topology_key=api.LABEL_ZONE)
        .obj(),
        make_pod("noisy-1").labels(app="noisy").req(cpu_milli=100).obj(),
    ]
    got, want = run_both(nodes, pods)
    assert got == want
    assert got[0] is not None and got[1] is not None
    assert int(got[0][1]) % 2 != int(got[1][1]) % 2  # different zones


# ---------------------------------------------------------------------------
# Randomized parity with everything on
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(3))
def test_randomized_parity_with_constraints(seed):
    rng = np.random.default_rng(seed + 100)
    nodes = []
    for i in range(10):
        nw = make_node(f"n{i}").capacity(
            cpu_milli=int(rng.choice([4000, 8000])), mem=16 * GI, pods=20
        ).zone(f"z{i % 3}")
        nodes.append(nw.obj())
    apps = ["a", "b", "c"]
    pods = []
    for i in range(30):
        app = str(rng.choice(apps))
        pw = make_pod(f"p{i}").labels(app=app).req(
            cpu_milli=int(rng.choice([100, 500, 1000]))
        )
        r = rng.random()
        if r < 0.25:
            pw.spread(
                max_skew=int(rng.choice([1, 2])),
                topology_key=api.LABEL_ZONE,
                when_unsatisfiable=str(
                    rng.choice(["DoNotSchedule", "ScheduleAnyway"])
                ),
                selector={"app": app},
            )
        elif r < 0.45:
            pw.pod_anti_affinity({"app": app}, topology_key=str(
                rng.choice([api.LABEL_HOSTNAME, api.LABEL_ZONE])
            ))
        elif r < 0.6:
            pw.pod_affinity({"app": app}, topology_key=api.LABEL_ZONE)
        pods.append(pw.obj())
    got, want = run_both(nodes, pods)
    assert got == want


# ---------------------------------------------------------------------------
# Class dims padded by the constraint rows' rule (vocab.pad_constraint_dim)
# ---------------------------------------------------------------------------


def _tight_classes(snap):
    """The same batch with every class dim cut back to pad_dim(n, 1), the
    shape a split batch had before its class dims got the floor of 32."""
    from kubernetes_tpu.utils import vocab as vb

    pods = snap.pods

    def cut(reps, *per_class):
        n = int((reps >= 0).sum())
        dim = vb.pad_dim(max(n, 1), 1)
        return [a[:dim] for a in (reps, *per_class)]

    class_rep, joint_spec, joint_cons = cut(
        pods.class_rep, pods.joint_spec, pods.joint_cons
    )
    (spec_rep,), (cons_rep,) = cut(pods.spec_rep), cut(pods.cons_rep)
    return snap._replace(pods=pods._replace(
        class_rep=class_rep, joint_spec=joint_spec, joint_cons=joint_cons,
        spec_rep=spec_rep, cons_rep=cons_rep,
    ))


@pytest.mark.parametrize("route", ["greedy", "wavefront"])
@pytest.mark.parametrize("seed", range(4))
def test_padded_class_dims_place_as_unpadded_and_as_the_oracle(seed, route):
    """scheduler_perf's TopologySpreading pods over a seeded subset of 16
    namespaces, against a cluster that already holds some of them: the
    padded class rows are masked out, so placements equal the oracle's
    whatever the class dims."""
    rng = np.random.default_rng(700 + seed)
    nodes = [
        make_node(f"n{i}").capacity(cpu_milli=4000, mem=32 * GI, pods=110)
        .zone(f"zone-{i % 8}").obj()
        for i in range(24)
    ]
    nss = [f"team-{i}" for i in rng.choice(16, size=int(rng.integers(1, 17)), replace=False)]

    def blue(name):
        return (
            make_pod(name, str(rng.choice(nss))).labels(color="blue")
            .req(cpu_milli=100, mem=500 * MI)
            .spread(5, api.LABEL_ZONE, "DoNotSchedule", {"color": "blue"}).obj()
        )

    bound = []
    for i in range(int(rng.integers(0, 40))):
        p = blue(f"b{i}")
        p.spec.node_name = nodes[int(rng.integers(0, 6))].meta.name   # skewed on purpose
        bound.append(p)
    pods = [blue(f"p{i}") for i in range(int(rng.integers(9, 70)))]

    snap, meta = schema.SnapshotBuilder().build(nodes, pods, bound_pods=bound)
    tight = _tight_classes(snap)
    assert snap.pods.class_rep.shape[0] == 32 >= tight.pods.class_rep.shape[0]
    solve = (
        assign.greedy_assign_jit() if route == "greedy" else assign.wavefront_assign_jit()
    )
    got = [np.asarray(solve(s).assignment)[: len(pods)] for s in (snap, tight)]
    assert (got[0] == got[1]).all()
    want = Oracle(nodes, bound_pods=bound).schedule(pods)
    assert [meta.node_name(int(i)) for i in got[0]] == want
