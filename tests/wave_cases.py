"""Wave plans whose in-wave step count is known, shared by the single-chip
parity suite (test_wavefront_parity.py) and the node- and pod-sharded twins
(test_sharded.py, test_pod_sharded.py): a wave costs what its members cost,
so ``wave_steps`` is the members' sum (to each row's last valid lane)."""

import numpy as np

from kubernetes_tpu.api import types as api
from kubernetes_tpu.ops import assign, schema
from kubernetes_tpu.testing.wrappers import GI, MI, make_node, make_pod


def big_nodes(n):
    return [
        make_node(f"n{i}").capacity(cpu_milli=32000, mem=64 * GI, pods=110).obj()
        for i in range(n)
    ]


def repelling(name):
    """scheduler_perf's pod-with-pod-anti-affinity: repels its own label
    over the hostname, so every pod of a batch couples with every other."""
    return (
        make_pod(name)
        .req(cpu_milli=100, mem=500 * MI)
        .label("color", "green")
        .pod_anti_affinity({"color": "green"}, api.LABEL_HOSTNAME)
    )


def plain_pods(n):
    return [
        make_pod(f"p{i}").req(cpu_milli=100 * (1 + i % 3), mem=256 * MI).obj()
        for i in range(n)
    ]


def plan_of(rows, k=8):
    """i32[W, k] wave plan of the given rows (-1 pads, at least 8 rows)."""
    members = np.full((max(8, len(rows)), k), -1, dtype=np.int32)
    for wi, row in enumerate(rows):
        members[wi, : len(row)] = row
    return members


def solve_order(snap):
    return np.asarray(assign.solve_order(snap.pods)).tolist()


def counters(res):
    return int(res.wave_count), int(res.wave_fallbacks), int(res.wave_steps)


REPEL_OWNERS = 12   # bound pods that own the same term, on nodes 0, 3, 6, ...


def step_case(name):
    """(snapshot, wave plan, (waves, fallbacks, steps)) of one shape:

    repel-<P>[-owners]  P pods that all repel each other (P fills its
                        bucket: no pad pod), with or without bound owners
                        of the term: the planner's one wave a pod, a scan
                        step each and no fallback
    widths              safe waves of 1, 2, 5, 32 and 24 members in one plan
    holes               holes inside a row, a lone member in a late lane
    """
    if name.startswith("repel"):
        n_pods = int(name.split("-")[1])
        pods = [repelling(f"p{i}").obj() for i in range(n_pods)]
        bound = [
            repelling(f"b{i}").node_name(f"n{3 * i}").obj()
            for i in range(REPEL_OWNERS if name.endswith("owners") else 0)
        ]
        snap, _ = schema.SnapshotBuilder().build(
            big_nodes(n_pods + 32), pods, bound_pods=bound
        )
        assert np.asarray(snap.pods.req).shape[0] == n_pods
        return snap, assign.plan_waves(snap).members, (n_pods, 0, n_pods)
    if name == "widths":
        snap, _ = schema.SnapshotBuilder().build(big_nodes(16), plain_pods(64))
        o = solve_order(snap)
        cuts = [0, 1, 3, 8, 40, 64]
        rows = [o[a:b] for a, b in zip(cuts, cuts[1:])]
        return snap, plan_of(rows, k=32), (5, 0, 64)
    assert name == "holes", name
    snap, _ = schema.SnapshotBuilder().build(big_nodes(8), plain_pods(8))
    o = solve_order(snap)
    rows = [
        [o[0], -1, o[1], -1, o[2], -1, -1, -1],    # 3 members, last lane 4
        [-1, -1, -1, -1, -1, -1, o[3], -1],        # alone, in lane 6
        [-1, o[4], o[5], o[6], o[7], -1, -1, -1],  # a hole first
    ]
    return snap, plan_of(rows), (3, 0, 5 + 1 + 5)


def assert_bit_parity(scan, wave):
    """Every output array equal, pads and scores included, and the
    post-solve usage both solvers hand on."""
    for name in ("assignment", "scores", "feasible_counts", "reasons"):
        np.testing.assert_array_equal(
            np.asarray(getattr(scan, name)), np.asarray(getattr(wave, name)),
            err_msg=name,
        )
    for name in ("requested", "nonzero_requested", "port_bits"):
        np.testing.assert_array_equal(
            np.asarray(getattr(scan.cluster, name)),
            np.asarray(getattr(wave.cluster, name)),
            err_msg=name,
        )


# -- self-affine batches: a saturated affinity term is no write ----------------

AFFINE_ZONES = 8


def zoned_nodes(n):
    """n nodes of 4 cpu (40 template pods each) over AFFINE_ZONES zones,
    node i in zone-(i mod 8): the port's node-default.yaml at toy size."""
    return [
        make_node(f"n{i}").capacity(cpu_milli=4000, mem=32 * GI, pods=110)
        .zone(f"zone-{i % AFFINE_ZONES}").obj()
        for i in range(n)
    ]


def affine(name, color="blue", key=api.LABEL_ZONE):
    """scheduler_perf's pod-with-pod-affinity: requires a pod of its own
    label in its zone, so every pod of a batch reads the term every other
    one matches."""
    return (
        make_pod(name).req(cpu_milli=100, mem=500 * MI).label("color", color)
        .pod_affinity({"color": color}, key)
    )


def writer(name, color="blue"):
    """Matches the affine pods' term and carries none: its match of the
    term is a write whatever the term's state."""
    return make_pod(name).req(cpu_milli=100, mem=500 * MI).label("color", color)


def affine_case(name):
    """(nodes, pods, bound pods) of one self-affine shape:

    saturated    64 affine pods, 4 bound matches in zone-0: every pod's
                 match of its own term is saturated
    escape       the same pods and no bound match: the first pod takes
                 the escape, and every pod couples with the one before
    writer       8 affine pods, a writer-only pod, 8 affine pods, with the
                 bound matches: the writer still couples with what follows
    """
    nodes = zoned_nodes(64)
    bound = [affine(f"b{i}").node_name(f"n{8 * i}").obj() for i in range(4)]
    if name == "saturated":
        return nodes, [affine(f"p{i}").obj() for i in range(64)], bound
    if name == "escape":
        return nodes, [affine(f"p{i}").obj() for i in range(64)], []
    assert name == "writer", name
    pods = [affine(f"a{i}").obj() for i in range(8)] + [writer("w").obj()]
    pods += [affine(f"c{i}").obj() for i in range(8)]
    return nodes, pods, bound


def mixed_affine_case(seed):
    """A seeded batch mixing zone affinity, hostname affinity, hostname
    anti-affinity and writer-only pods over two colors, on 32 zoned
    nodes with a few bound pods of each color (none for some seeds)."""
    rng = np.random.default_rng(seed)
    nodes = zoned_nodes(32)
    bound = []
    for i in range(int(rng.integers(0, 4))):
        color = str(rng.choice(["blue", "red"]))
        bound.append(writer(f"b{i}", color).node_name(f"n{int(rng.integers(32))}").obj())
    pods = []
    for i in range(48):
        color = str(rng.choice(["blue", "red"]))
        r = rng.random()
        if r < 0.45:
            pw = affine(f"p{i}", color)
        elif r < 0.6:
            pw = affine(f"p{i}", color, api.LABEL_HOSTNAME)
        elif r < 0.75:
            pw = writer(f"p{i}", color).pod_anti_affinity(
                {"color": "red" if color == "blue" else "blue"}, api.LABEL_HOSTNAME
            )
        else:
            pw = writer(f"p{i}", color)
        pods.append(pw.obj())
    return nodes, pods, bound
