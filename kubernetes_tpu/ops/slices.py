"""TPU slice topology — torus-aware gang carve-outs as tensor ops.

A TPU slice is a torus of devices; a training gang wants a *contiguous
axis-aligned sub-cuboid* of one slice (the ICI-connected block), not G
scattered hosts.  The cluster tensors carry each node's slice id, torus
coordinates and the owning slice's extent (ops/schema.py, from the
api.LABEL_TPU_* node labels); this module turns them into the three
batched ops the solver scan consumes:

  contiguity   corner_mask: is node n the min-corner of a fully-free
               a x b x c sub-cuboid of its slice?  Free occupancy is
               scattered into a value-space grid ``[S, D, D, D]`` (the
               prep_spread idiom — node space in, value space for the
               window math, node space out), a 3-D integral image makes
               every window sum O(1), and the per-node gather answers
               all N corners in one shot.
  adjacency    carveout_eval: the carve-out score family.  Anchors
               (first member of a gang, or a solo shaped pod) prefer
               corners by best-fit leftover (minimize the fragment the
               carve-out leaves behind) then by coordinate-sum packing;
               anchored members prefer in-cuboid nodes by torus hop
               distance to the carved corner.  Bonuses are large exact
               integers, so contiguous placements score strictly above
               fragmenting ones and the host oracle reproduces the
               totals bit-for-bit (testing/oracle.py).
  fragmentation  cluster-wide packing health: per-slice largest
               placeable free cube (edge k, the same integral-image
               window check swept over k) and the free-device share
               those cubes cover — ``score = 1 - placeable/free``,
               0 = every free device sits in a maximal cube.

Everything is jit/shard_map-friendly: under ``axis_name`` the grid
scatters psum across node shards (a slice spanning shards is counted
whole) and the per-node gathers stay local — the ops.assign "one
implementation, two layouts" idiom.

Semantics contract (shared verbatim by the device kernels, the host
oracle, and CoschedulingPermit's release check):

  * a node is FREE iff it carries no (bound or in-scan assumed) pods —
    ``requested[:, RESOURCE_PODS] == 0`` — and belongs to a slice;
  * a carve-out is a non-wrapping axis-aligned box ``[lo, lo+shape)``
    inside one slice's declared extent;
  * the gang's FIRST placed member anchors the carve-out at its own
    coordinates (the anchor filter/score steers it onto a free-box
    min-corner); every later member of the gang targets the anchored
    box.  ``require`` policy turns both preferences into filters, so a
    gang that cannot fit contiguously parks whole (all-or-nothing
    releases the anchor too); ``prefer`` falls back to scattered
    placement and counts a carve-out fallback.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..analysis.markers import hot_path
from .schema import RESOURCE_PODS, ClusterTensors

# Carve-out score-family weights.  Exact small integers inside f32's
# exact envelope (2^24): the base score families sum to <= ~700, so the
# ordering is strict — in-carve-out/corner >> same-slice >> any base
# score difference — and the host oracle's float math lands on the same
# totals.  testing/oracle.py imports these; change them only together.
BONUS_CARVE = 1_000_000.0   # in-carve-out member / free-box corner anchor
BONUS_SLICE = 10_000.0      # anchored gang's slice (prefer-mode fallback)
W_LEFTOVER = 100.0          # anchor best-fit: slice free count minus volume
W_HOP = 10.0                # member compactness: torus hops to the corner
W_CORNER = 10.0             # anchor packing: corner coordinate sum


class SliceStats(NamedTuple):
    """fragmentation() report (device scalars/vectors)."""

    score: jnp.ndarray         # f32[]  1 - largest-placeable-cube share of free
    largest_cube: jnp.ndarray  # i32[S] per-slice largest free cube edge
    free_count: jnp.ndarray    # f32[S] free devices per slice (the histogram)


def free_devices(cluster: ClusterTensors) -> jnp.ndarray:
    """bool[N]: slice-member nodes hosting no pods (training devices are
    whole-node; RESOURCE_PODS counts bound AND in-scan assumed pods, so
    the mask tightens as the solve places gangs)."""
    return (
        cluster.node_valid
        & (cluster.slice_id >= 0)
        & (cluster.requested[:, RESOURCE_PODS] <= 0)
    )


# coherence: rebuilt-per-solve -- the occupancy grid tightens as the solve
# places gangs; a copy cached across solves would double-place
def _cell_grid(
    cluster: ClusterTensors,
    free: jnp.ndarray,
    slice_z: int,
    dmax: int,
    axis_name: Optional[str] = None,
) -> jnp.ndarray:
    """bool[S, D, D, D]: coordinate (s, x, y, z) is present AND free.
    A coordinate shared by several nodes (core index) is free only when
    every node on it is free.  Under shard_map the presence/occupancy
    scatters psum across shards before combining."""
    xyz = cluster.torus_coords[:, :3]
    has = (cluster.slice_id >= 0) & (xyz >= 0).all(axis=-1)
    sc = jnp.clip(cluster.slice_id, 0, slice_z - 1)
    cc = jnp.clip(xyz, 0, dmax - 1)
    idx = (sc, cc[:, 0], cc[:, 1], cc[:, 2])
    shape = (slice_z, dmax, dmax, dmax)
    pres = jnp.zeros(shape, jnp.int32).at[idx].max(has.astype(jnp.int32))
    occ = jnp.zeros(shape, jnp.int32).at[idx].max(
        (has & ~free).astype(jnp.int32)
    )
    if axis_name is not None:
        pres = jax.lax.psum(pres, axis_name)
        occ = jax.lax.psum(occ, axis_name)
    return (pres > 0) & (occ == 0)


def _integral(cell: jnp.ndarray) -> jnp.ndarray:
    """Zero-padded 3-D integral image: I[s, i, j, k] = free cells with
    x < i, y < j, z < k — every box sum becomes 8 gathers."""
    g = jnp.pad(cell.astype(jnp.float32), ((0, 0), (1, 0), (1, 0), (1, 0)))
    return g.cumsum(axis=1).cumsum(axis=2).cumsum(axis=3)


def _box_sum(integral, s, lo, hi):
    """Free-cell count in [lo, hi) of slice s (vectorized gathers; lo/hi
    i32[..., 3] already within [0, D])."""
    def at(a, b, c):
        return integral[s, a, b, c]

    l0, l1, l2 = lo[..., 0], lo[..., 1], lo[..., 2]
    h0, h1, h2 = hi[..., 0], hi[..., 1], hi[..., 2]
    return (
        at(h0, h1, h2)
        - at(l0, h1, h2) - at(h0, l1, h2) - at(h0, h1, l2)
        + at(l0, l1, h2) + at(l0, h1, l2) + at(h0, l1, l2)
        - at(l0, l1, l2)
    )


def corner_mask(
    cluster: ClusterTensors,
    free: jnp.ndarray,
    shape: jnp.ndarray,
    slice_z: int,
    dmax: int,
    axis_name: Optional[str] = None,
    integral: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """bool[N]: node n is the min-corner of a fully-free ``shape`` box
    inside its slice's declared extent.  ``shape`` is a traced i32[3]
    (per-pod), so one executable serves every gang shape."""
    if integral is None:
        integral = _integral(
            _cell_grid(cluster, free, slice_z, dmax, axis_name=axis_name)
        )
    xyz = cluster.torus_coords[:, :3]
    has = (cluster.slice_id >= 0) & (xyz >= 0).all(axis=-1)
    fits = has & ((xyz + shape[None, :]) <= cluster.slice_dims).all(axis=-1)
    s = jnp.clip(cluster.slice_id, 0, slice_z - 1)
    lo = jnp.clip(xyz, 0, dmax)
    hi = jnp.clip(xyz + shape[None, :], 0, dmax)
    vol = shape.prod().astype(jnp.float32)
    full = _box_sum(integral, s, lo, hi) >= vol
    return fits & full & free


def slice_free_counts(
    cluster: ClusterTensors,
    free: jnp.ndarray,
    slice_z: int,
    axis_name: Optional[str] = None,
) -> jnp.ndarray:
    """f32[S]: free COORDINATES per slice (core-collapsed, matching the
    cell grid's granularity would cost another scatter — node counts
    are the best-fit signal and stay exact integers)."""
    sc = jnp.clip(cluster.slice_id, 0, slice_z - 1)
    counts = jnp.zeros(slice_z, jnp.float32).at[sc].add(
        jnp.where(free, 1.0, 0.0)
    )
    if axis_name is not None:
        counts = jax.lax.psum(counts, axis_name)
    return counts


def carveout_eval(
    cluster: ClusterTensors,
    pods,
    i,
    gang_sl: Optional[jnp.ndarray],
    gang_lo: Optional[jnp.ndarray],
    features,
    axis_name: Optional[str] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The carve-out Filter+Score slice for pod ``i`` against the carry
    state: ``(bonus f32[N], ok bool[N])``.  ``ok`` is the require-mode
    filter (anchors: free-box corners; members: the anchored cuboid);
    ``bonus`` is the adjacency-aware score family added on top of the
    normalized base scores (module constants).  Unshaped pods return
    (0, True) everywhere — the family is free for them."""
    shape = pods.pod_shape[i]                       # i32[3]
    shaped = shape.prod() > 0
    g = pods.group_id[i]
    n = cluster.slice_id.shape[0]
    sid = cluster.slice_id
    xyz = cluster.torus_coords[:, :3]

    free = free_devices(cluster)
    corner = corner_mask(
        cluster, free, shape, features.slice_z, features.slice_dim,
        axis_name=axis_name,
    )
    fc = slice_free_counts(cluster, free, features.slice_z, axis_name=axis_name)
    leftover = jnp.maximum(
        fc[jnp.clip(sid, 0, features.slice_z - 1)]
        - shape.prod().astype(jnp.float32),
        0.0,
    )
    coordsum = jnp.where(
        (xyz >= 0).all(axis=-1), xyz.sum(axis=-1), 0
    ).astype(jnp.float32)
    anchor_bonus = jnp.where(
        corner,
        BONUS_CARVE - W_LEFTOVER * leftover - W_CORNER * coordsum,
        0.0,
    )

    if gang_sl is not None:
        gc = jnp.clip(g, 0, gang_sl.shape[0] - 1)
        asl, alo = gang_sl[gc], gang_lo[gc]
        anchored = shaped & (g >= 0) & (asl >= 0)
    else:
        asl = jnp.int32(-1)
        alo = jnp.full(3, -1, jnp.int32)
        anchored = jnp.bool_(False)
    # one member per DEVICE: a member targets free in-cuboid nodes only
    # (the anchor occupied its corner; each later member takes the next
    # free device, nearest-to-corner first)
    same = (sid == asl) & (sid >= 0) & free
    in_cub = (
        same
        & (xyz >= alo[None, :]).all(axis=-1)
        & (xyz < alo[None, :] + shape[None, :]).all(axis=-1)
    )
    hop = jnp.abs(xyz - alo[None, :]).sum(axis=-1).astype(jnp.float32)
    member_bonus = jnp.where(
        in_cub,
        BONUS_CARVE + BONUS_SLICE - W_HOP * hop,
        jnp.where(same, BONUS_SLICE - W_HOP * hop, 0.0),
    )

    bonus = jnp.where(
        shaped, jnp.where(anchored, member_bonus, anchor_bonus), 0.0
    )
    ok = jnp.where(
        shaped,
        jnp.where(anchored, in_cub, corner),
        jnp.ones(n, dtype=bool),
    )
    return bonus, ok


@hot_path
def fragmentation(
    cluster: ClusterTensors,
    slice_z: int,
    dmax: int,
    axis_name: Optional[str] = None,
) -> SliceStats:
    """Cluster-wide packing health from the current free mask: per-slice
    largest placeable free cube (the same integral-image window check,
    swept over the static edge ladder k = 1..D) and the share of free
    devices those cubes cover.  ``score`` is 0 when every free device
    sits inside a maximal cube (freshly drained slices), approaching 1
    as free devices shatter into unplaceable fragments."""
    free = free_devices(cluster)
    cell = _cell_grid(cluster, free, slice_z, dmax, axis_name=axis_name)
    integral = _integral(cell)
    # per-slice declared extent, in value space (psum-combined so a
    # shard that owns no node of a slice still sees its dims)
    sc = jnp.clip(cluster.slice_id, 0, slice_z - 1)
    sdims = jnp.zeros((slice_z, 3), jnp.int32).at[sc].max(
        jnp.where((cluster.slice_id >= 0)[:, None], cluster.slice_dims, 0)
    )
    if axis_name is not None:
        sdims = jax.lax.pmax(sdims, axis_name)
    largest = jnp.zeros(slice_z, jnp.int32)
    coords = jnp.arange(dmax)
    for k in range(1, dmax + 1):
        lo = jnp.stack(
            jnp.meshgrid(coords, coords, coords, indexing="ij"), axis=-1
        )                                              # [D, D, D, 3]
        hi = jnp.clip(lo + k, 0, dmax)
        s_idx = jnp.arange(slice_z)[:, None, None, None]
        cnt = _box_sum(
            integral,
            jnp.broadcast_to(s_idx, (slice_z, dmax, dmax, dmax)),
            jnp.broadcast_to(lo[None], (slice_z, dmax, dmax, dmax, 3)),
            jnp.broadcast_to(hi[None], (slice_z, dmax, dmax, dmax, 3)),
        )
        in_bounds = (
            (lo[None] + k) <= sdims[:, None, None, None, :]
        ).all(axis=-1)
        exists = (in_bounds & (cnt >= float(k ** 3))).any(axis=(1, 2, 3))
        largest = jnp.where(exists, k, largest)
    free_count = slice_free_counts(cluster, free, slice_z, axis_name=axis_name)
    placeable = (largest.astype(jnp.float32) ** 3).sum()
    total_free = free_count.sum()
    score = 1.0 - placeable / jnp.maximum(total_free, 1.0)
    return SliceStats(
        score=jnp.maximum(score, 0.0),
        largest_cube=largest,
        free_count=free_count,
    )


def fragmentation_report(cluster: ClusterTensors) -> dict:
    """Host convenience: derive the static capacities from the (host or
    device) cluster tensors and return plain numbers — what tests
    read."""
    import numpy as np

    from ..utils.vocab import pad_dim

    sids = np.asarray(cluster.slice_id)
    if not (sids >= 0).any():
        return {"score": 0.0, "largest_cube": [], "free_count": []}
    slice_z = pad_dim(int(sids.max()) + 1, 1)
    dmax = max(int(np.asarray(cluster.slice_dims).max()), 1)
    stats = fragmentation(
        jax.tree.map(jnp.asarray, cluster), slice_z, dmax
    )
    n_real = int(sids.max()) + 1
    return {
        "score": float(stats.score),
        "largest_cube": np.asarray(stats.largest_cube)[:n_real].tolist(),
        "free_count": np.asarray(stats.free_count)[:n_real].tolist(),
    }
