"""Batched assignment solves.

The reference schedules one pod at a time: pop, filter, score, pick, then
`assume` the pod into the cache so the next pod sees its resources
(schedule_one.go:66-133, :940-957).  `greedy_assign` reproduces exactly
those semantics inside a single compiled program: a lax.scan over the pod
axis whose carry *is* the assume bookkeeping (requested / ports updated
tensor-side between picks), so a 10k-pod batch needs one device dispatch
instead of 10k scheduling cycles.

Pods are solved in priority-then-batch-index order (the reference's
queuesort/priority_sort.go:52 pop order); results are scattered back to
input positions.

The scan step is kept minimal: everything placement-independent — the
NodeName/TaintToleration/NodeAffinity filter slice and the raw
affinity/taint score rows — is hoisted out per *pod class*
(schema.PodBatch.class_id groups pods with byte-identical static state),
so a step only re-evaluates resource fit, the carried constraint state,
and the closed-form allocation scores.

Host round-trips per batch: one.

Tie-breaking: first-max-index (deterministic).  The reference picks
uniformly at random among max-score nodes via reservoir sampling
(schedule_one.go:867-905); pass `tie_seed` to sample the same distribution
with a counter-based PRNG instead.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis import retrace
from ..analysis.markers import hot_path
from ..utils import trace
from .filters import (
    fits_resources,
    pod_view,
    preferred_match,
    selector_match,
    static_feasible_for_pod,
)
from .interpod import interpod_filter, interpod_update, prep_terms
from .schema import ClusterTensors, PodBatch, Snapshot, num_groups
from .scores import (
    DEFAULT_SCORE_CONFIG,
    ScoreConfig,
    node_affinity_raw,
    score_from_raw,
    taint_toleration_raw,
)
from .topology import prep_spread, spread_filter, spread_score, spread_update

NEG_INF = jnp.float32(-jnp.inf)


class FeatureFlags(NamedTuple):
    """Static gates: a workload only pays scan-step cost for the constraint
    families it actually uses (the analogue of the reference's PreFilter
    returning Skip to elide a plugin for a pod — framework.go:687)."""

    spread: bool = False       # any topology-spread constraints
    soft_spread: bool = False  # any ScheduleAnyway constraints (scoring)
    interpod: bool = False     # any inter-pod (anti-)affinity terms
    term_slots: Tuple[int, ...] = ()  # topology-key slots those terms use
    ports: bool = False        # any pending pod claims host ports (the
                               # dynamic port-conflict carry; the static
                               # check against bound pods is always on)
    interpod_aff: bool = False  # any AFFINITY-direction terms (the
                               # co-location + first-pod-escape family;
                               # the joint auction covers anti-affinity
                               # only, so this gates its routing)
    spread_slots: Tuple[int, ...] = ()  # topology-key slots spread rows use
    interpod_pref: bool = False  # any preferred (scoring) interpod terms
    images: bool = False         # any pending pod names a known image
    # Whether any BOUND pod contributes to each family's count tables.
    # Static so the preps' value-space scatter+gather folds away at
    # trace time when the tables are zero — they arrive as runtime
    # device arrays, so XLA cannot discover zero-ness on its own, and
    # the folded-out gathers are ~0.3 s/solve at 32k nodes.
    bound_spread: bool = False
    bound_terms: bool = False
    bound_pref: bool = False
    # TPU slice-topology carve-outs (ops/slices.py): active when shaped
    # pods meet a slice-labelled cluster.  slice_z/slice_dim size the
    # value-space grid [S, D, D, D] (static, like topo_z they are part
    # of the executable key); slice_require flips the carve-out
    # preference into a filter (the prefer-vs-require config knob).
    slices: bool = False
    slice_require: bool = False
    slice_z: int = 1
    slice_dim: int = 1


def required_topo_z(snapshot: Snapshot) -> int:  # graftlint: disable=purity -- host-side prep on the pre-transfer snapshot
    """Smallest valid topo-value capacity for this snapshot.  Using a
    smaller z would alias topology values together in the prep-time count
    scatter and silently corrupt spread/inter-pod state."""
    from ..utils.vocab import pad_dim

    return pad_dim(int(np.asarray(snapshot.cluster.topo_ids).max()) + 1, 1)


def required_topo_z_split(snapshot: Snapshot) -> Tuple[int, int]:  # graftlint: disable=purity -- host-side prep on the pre-transfer snapshot
    """(z_spread, z_terms): value capacities sized to the topology slots
    each family actually uses.  Hostname ids scale with the cluster (50k
    nodes → 50k values) while zone/region stay tiny; sizing each family's
    value-space buffers to ITS slots keeps a zone-spread batch's scatters
    at z≈64 instead of z≈cluster-size."""
    from ..utils.vocab import pad_dim

    topo = np.asarray(snapshot.cluster.topo_ids)

    def z_for(slots) -> int:
        if len(slots) == 0:
            return 1
        return pad_dim(int(topo[:, sorted(slots)].max()) + 1, 1)

    spread_valid = np.asarray(snapshot.spread.valid)
    spread_slots = set(np.asarray(snapshot.spread.slot)[spread_valid].tolist())
    term_valid = np.asarray(snapshot.terms.valid)
    term_slots = set(np.asarray(snapshot.terms.slot)[term_valid].tolist())
    pref_valid = np.asarray(snapshot.prefpod.valid)
    term_slots |= set(np.asarray(snapshot.prefpod.slot)[pref_valid].tolist())
    return z_for(spread_slots), z_for(term_slots)


def needs_topo(features: FeatureFlags) -> bool:
    """True when the solve carries any topology-value state — spread,
    required inter-pod terms, or PREFERRED inter-pod terms (forgetting
    the last aliased every domain to value 0 and silently zeroed the
    preferred-affinity scores on the dispatch path)."""
    return features.spread or features.interpod or features.interpod_pref


def features_of(  # graftlint: disable=purity -- host-side prep: cheap numpy reductions on the pre-transfer snapshot
    snapshot: Snapshot, no_bound_pods: bool = False,
    slice_policy: str = "prefer",
) -> FeatureFlags:
    """Derive the static gates host-side (cheap numpy reductions).

    no_bound_pods: the caller knows the cluster holds zero bound pods
    (ClusterState._pods empty), so the bound-count tables are zeros by
    construction — skips full scans of the largest snapshot arrays
    (tens of MB each at 20k+ nodes) on the per-batch encode path.

    slice_policy: the carve-out knob ("prefer" | "require" | "off",
    SchedulerConfiguration.slice_carveout_policy) — the slice family
    arms only when shaped pods meet a slice-labelled cluster AND the
    policy isn't off."""
    from ..utils.vocab import pad_dim

    spread_valid = np.asarray(snapshot.spread.valid)
    hard = np.asarray(snapshot.spread.hard)
    term_valid = np.asarray(snapshot.terms.valid)
    slots = np.asarray(snapshot.terms.slot)
    if no_bound_pods:
        bound_spread = bound_terms = bound_pref = False
    else:
        bound_spread = bool(np.asarray(snapshot.spread.node_matches).any())
        bound_terms = bool(
            np.asarray(snapshot.terms.node_matches).any()
            or np.asarray(snapshot.terms.node_owners).any()
        )
        bound_pref = bool(
            np.asarray(snapshot.prefpod.node_counts).any()
            or np.asarray(snapshot.prefpod.owner_weight).any()
        )
    shapes = np.asarray(snapshot.pods.pod_shape)
    sids = np.asarray(snapshot.cluster.slice_id)
    slices_on = (
        slice_policy != "off"
        and bool((shapes.prod(axis=1) > 0).any())
        and bool((sids >= 0).any())
    )
    if slices_on:
        slice_z = pad_dim(int(sids.max()) + 1, 1)
        slice_dim = pad_dim(
            max(int(np.asarray(snapshot.cluster.slice_dims).max()), 1), 1
        )
    else:
        slice_z = slice_dim = 1
    return FeatureFlags(
        spread=bool(spread_valid.any()),
        soft_spread=bool((spread_valid & ~hard).any()),
        interpod=bool(term_valid.any()),
        term_slots=tuple(sorted(set(slots[term_valid].tolist()))),
        ports=bool(np.asarray(snapshot.pods.port_bits).any()),
        interpod_aff=bool((np.asarray(snapshot.terms.aff_idx) >= 0).any()),
        spread_slots=tuple(
            sorted(set(np.asarray(snapshot.spread.slot)[spread_valid].tolist()))
        ),
        interpod_pref=bool(np.asarray(snapshot.prefpod.valid).any()),
        images=bool(
            (np.asarray(snapshot.images.pod_ids) >= 0).any()
            and np.asarray(snapshot.cluster.image_bits).any()
        ),
        bound_spread=bound_spread,
        bound_terms=bound_terms,
        bound_pref=bound_pref,
        slices=slices_on,
        slice_require=slices_on and slice_policy == "require",
        slice_z=slice_z,
        slice_dim=slice_dim,
    )


# Failure-reason codes: the FIRST filter stage that emptied the pod's
# candidate set.  The queue's event-scoped requeue (QueueingHints-lite)
# keys off these — e.g. an AssignedPodDelete frees resources but cannot
# fix a node-affinity mismatch, so REASON_STATIC pods stay parked
# (internal/queue/events.go's event→plugin map, reduced to stages).
REASON_NONE = -1      # placed
REASON_STATIC = 0     # NodeName/affinity/taints/validity (+ bound ports)
REASON_RESOURCES = 1  # NodeResourcesFit
REASON_PORTS = 2      # in-batch host-port conflicts
REASON_SPREAD = 3     # PodTopologySpread (hard)
REASON_INTERPOD = 4   # InterPodAffinity (required)
REASON_GANG = 5       # placed individually but released with its gang
REASON_UNENCODABLE = 6  # spec exceeds encoder caps / unsupported field —
                        # only a pod UPDATE can help; no event wakes it
REASON_SLICE = 7      # slice carve-out (require mode): no free contiguous
                      # sub-cuboid / anchored cuboid exhausted


def _axis_any(x: jnp.ndarray, axis_name: Optional[str]) -> jnp.ndarray:
    """Global `.any()` over the node axis: local under a single chip, an
    OR across shards (pmax of the local any) under shard_map."""
    if axis_name is None:
        return x.any()
    return jax.lax.pmax(x.any().astype(jnp.int32), axis_name) > 0


def _shard_layout(axis_name: Optional[str], n: int):
    """Node-axis layout helpers shared by the greedy/wavefront solvers —
    identity under a single chip, ownership-masked collectives under
    shard_map (the ops.auction idiom: one implementation, two layouts).

    Returns ``(offset, n_total, node_rows, node_col)``: `offset` is the
    shard's first global row, `n_total` the GLOBAL node count (psum of a
    constant folds to the static axis size, so it stays a Python int),
    ``node_rows(mat, idx)`` gathers rows of a node-major tensor at
    GLOBAL node ids (the owning shard contributes, psum replicates), and
    ``node_col(mat, idx)`` broadcasts the column of a [R, N] tensor at
    one GLOBAL id."""
    if axis_name is None:
        return 0, n, (lambda mat, idx: mat[idx]), (lambda mat, idx: mat[:, idx])
    offset = jax.lax.axis_index(axis_name) * n
    n_total = n * jax.lax.psum(1, axis_name)

    def node_rows(mat, idx):
        own = (idx >= offset) & (idx < offset + n)
        loc = jnp.clip(idx - offset, 0, n - 1)
        vals = mat[loc]
        mask = own.reshape(own.shape + (1,) * (vals.ndim - own.ndim))
        if vals.dtype == jnp.bool_:
            return jax.lax.psum(
                jnp.where(mask, vals, False).astype(jnp.int32), axis_name
            ) > 0
        return jax.lax.psum(
            jnp.where(mask, vals, jnp.zeros_like(vals)), axis_name
        )

    def node_col(mat, idx):
        own = (idx >= offset) & (idx < offset + n)
        loc = jnp.clip(idx - offset, 0, n - 1)
        col = mat[:, loc]
        if col.dtype == jnp.bool_:
            return jax.lax.psum(
                jnp.where(own, col, False).astype(jnp.int32), axis_name
            ) > 0
        return jax.lax.psum(
            jnp.where(own, col, jnp.zeros_like(col)), axis_name
        )

    return offset, n_total, node_rows, node_col


def _elect(masked: jnp.ndarray, offset, axis_name: str):
    """Global argmax election under shard_map: local champion, then a
    pmax/pmin pair picks (best score, lowest global index) — the
    first-max-index tie-break of the single-chip argmax, exactly.
    Returns (global index i32, best value)."""
    li = jnp.argmax(masked)
    lv = masked[li]
    best = jax.lax.pmax(lv, axis_name)
    cand = jnp.where(
        lv == best, (offset + li).astype(jnp.int32), jnp.int32(2 ** 31 - 1)
    )
    return jax.lax.pmin(cand, axis_name), best


class SolveResult(NamedTuple):
    assignment: jnp.ndarray   # i32[P]: node index, or -1 unschedulable
    scores: jnp.ndarray       # f32[P]: winning node's score (-inf if none)
    feasible_counts: jnp.ndarray  # i32[P]: feasible nodes seen by each pod
    cluster: ClusterTensors   # post-solve cluster (assumed placements applied)
    reasons: jnp.ndarray = None   # i32[P]: REASON_* for unplaced pods
    # wavefront-path telemetry (None on the classic scan): executed wave
    # count, fallback count (pods of serialized waves + per-pod full
    # re-evals) and the in-wave sequential steps the device ran
    wave_count: jnp.ndarray = None      # i32[]
    wave_fallbacks: jnp.ndarray = None  # i32[]
    wave_steps: jnp.ndarray = None      # i32[]
    # slice carve-out telemetry (None unless features.slices): post-solve
    # cluster fragmentation and per-gang carve-out outcomes
    frag_score: jnp.ndarray = None          # f32[]
    carveouts: jnp.ndarray = None           # i32[]
    contiguous_gangs: jnp.ndarray = None    # i32[]
    carveout_fallbacks: jnp.ndarray = None  # i32[]


def class_statics(
    cluster: ClusterTensors,
    pods: PodBatch,
    sel_mask: jnp.ndarray,
    pref_mask: jnp.ndarray,
    reps: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Per-class hoisted tables: (static_feas[C, N], aff_raw[C, N],
    taint_raw[C, N]).  One row per static-equivalence class, computed from
    its representative pod; the scan gathers rows by class_id.  The static
    feasibility folds in the port check against *initial* (bound-pod)
    port claims; in-batch port conflicts ride the dynamic carry.

    reps: representative-pod indices to evaluate (defaults to the joint
    class_rep).  The auction passes pods.spec_rep — static state depends
    only on the spec factor, so the heavy label/taint row kernels run
    once per spec class (see PodBatch's factorization note)."""
    p = pods.req.shape[0]
    if reps is None:
        reps = jnp.clip(pods.class_rep, 0, p - 1)

    def one(rep):
        pod = pod_view(pods, rep)
        sfeas = static_feasible_for_pod(cluster, pod, sel_mask) & ~(
            (cluster.port_bits & pod.port_bits[None, :]).any(axis=-1)
        )
        return (
            sfeas,
            node_affinity_raw(pod, pref_mask),
            taint_toleration_raw(cluster, pod),
        )

    return jax.vmap(one)(reps)


def solve_order(pods: PodBatch) -> jnp.ndarray:
    """Priority-then-batch-index pop order (queuesort/priority_sort.go:52:
    higher priority first, earlier arrival breaking ties).  Stable argsort
    on negated priority ≡ lexicographic (-priority, index)."""
    return jnp.argsort(-pods.priority, stable=True).astype(jnp.int32)


def _pick(
    masked_scores: jnp.ndarray,
    feasible: jnp.ndarray,
    key: Optional[jax.Array],
) -> jnp.ndarray:
    """argmax with first-index ties, or uniform-among-ties when keyed
    (the reference's selectHost reservoir sampling)."""
    if key is None:
        return jnp.argmax(masked_scores)
    best = jnp.max(masked_scores)
    tie = feasible & (masked_scores == best)
    # Gumbel-max over the tie set = uniform choice among ties.
    g = jax.random.gumbel(key, masked_scores.shape)
    return jnp.argmax(jnp.where(tie, g, NEG_INF))


def _eval_pod(
    cl: ClusterTensors,
    pods: PodBatch,
    i: jnp.ndarray,
    cls: jnp.ndarray,
    sfeas_c: jnp.ndarray,
    aff_c: jnp.ndarray,
    taint_c: jnp.ndarray,
    extra_c: Optional[jnp.ndarray],
    new_ports,
    sp,
    tm,
    spread,
    terms,
    features: FeatureFlags,
    cfg: ScoreConfig,
    axis_name: Optional[str] = None,
    gang_sl: Optional[jnp.ndarray] = None,
    gang_lo: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The Filter+Score half of one scheduling step for pod i against the
    given carry state: (feas[N], masked_scores[N], found, reason,
    feasible_count).  Shared verbatim by the classic scan step, the
    wavefront pre-evaluation, and the wavefront's exact re-evaluation
    fallback, so the three paths cannot drift apart.

    gang_sl/gang_lo: the slice carve-out carry ([G] anchored slice id,
    [G, 3] carved corner) when features.slices and gangs are present —
    the carve-out family (ops/slices.py) filters (require mode) and
    score-biases (both modes) shaped pods toward contiguous sub-cuboids.

    Under shard_map (axis_name set) the node tensors hold one shard:
    feas/masked stay local while the per-stage anys, the feasible count,
    and the score normalization maxima span shards — found/reason/count
    come back replicated."""
    pod = pod_view(pods, i)
    s_static = sfeas_c[cls]
    s_any = _axis_any(s_static, axis_name)
    feas = s_static & fits_resources(cl, pod)
    a_res = _axis_any(feas, axis_name)
    if features.ports:
        feas = feas & ~((new_ports & pod.port_bits[None, :]).any(axis=-1))
    a_ports = _axis_any(feas, axis_name)
    if features.spread:
        feas = feas & spread_filter(sp, spread, i, axis_name=axis_name)
    a_spread = _axis_any(feas, axis_name)
    if features.interpod:
        feas = feas & interpod_filter(tm, terms, i)
    s_bonus = None
    if features.slices:
        from .slices import carveout_eval

        s_bonus, s_ok = carveout_eval(
            cl, pods, i, gang_sl, gang_lo, features, axis_name=axis_name
        )
        if features.slice_require:
            a_interpod = _axis_any(feas, axis_name)
            feas = feas & s_ok
    found = _axis_any(feas, axis_name)
    # first stage whose filter emptied the candidate set
    last = (
        jnp.where(~a_interpod, REASON_INTERPOD, REASON_SLICE)
        if features.slices and features.slice_require
        else REASON_INTERPOD
    )
    reason = jnp.where(
        found, REASON_NONE,
        jnp.where(
            ~s_any, REASON_STATIC,
            jnp.where(
                ~a_res, REASON_RESOURCES,
                jnp.where(
                    ~a_ports, REASON_PORTS,
                    jnp.where(~a_spread, REASON_SPREAD, last),
                ),
            ),
        ),
    ).astype(jnp.int32)
    sp_score = (
        spread_score(sp, spread, i, feas, axis_name=axis_name)
        if features.soft_spread
        else None
    )
    scores = score_from_raw(
        cl, pod, feas, aff_c[cls], taint_c[cls], cfg, axis_name=axis_name,
        spread_score=sp_score,
        extra=extra_c[cls] if extra_c is not None else None,
    )
    if s_bonus is not None:
        # the carve-out family rides OUTSIDE the normalized base sum:
        # exact-integer bonuses large enough that contiguous placements
        # rank strictly above fragmenting ones (ops/slices.py weights)
        scores = scores + s_bonus
    masked = jnp.where(feas, scores, NEG_INF)
    cnt = feas.sum().astype(jnp.int32)
    if axis_name is not None:
        cnt = jax.lax.psum(cnt, axis_name)
    return feas, masked, found, reason, cnt


def _solver_prep(
    snapshot: Snapshot, cfg: ScoreConfig, topo_z: int, features: FeatureFlags,
    axis_name: Optional[str] = None, statics=None,
):
    """Per-batch device prep shared by the scan and wavefront solvers:
    materialized tensors, class-hoisted static tables, and the spread /
    inter-pod prep states (the PreFilter/PreScore analogue).  Under
    shard_map the hoisted tables cover the local node shard; the
    value-space count preps and normalizers span shards via psum/pmax
    inside prep_spread/prep_terms/static_extra.

    statics: a precomputed (sfeas, aff, taint) triple
    (ops.partials.ClassStatics) warm-started from the device-resident
    PartialsCache — bit-identical to what class_statics would compute
    here (the cache's parity gate pins it), so the whole [C, N]
    selector/taint/affinity re-evaluation is skipped.  The selector
    mask is still computed when the spread family needs it
    (prep_spread's owner-eligibility input)."""
    (cluster, pods, sel, pref, spread, terms, prefpod, images) = jax.tree.map(
        jnp.asarray, tuple(snapshot)
    )
    n = cluster.allocatable.shape[0]
    p = pods.req.shape[0]

    if statics is None:
        sel_mask = selector_match(cluster, sel)
        pref_mask = preferred_match(cluster, pref)
        sfeas_c, aff_c, taint_c = class_statics(
            cluster, pods, sel_mask, pref_mask
        )
    else:
        sfeas_c = jnp.asarray(statics.sfeas)
        aff_c = jnp.asarray(statics.aff)
        taint_c = jnp.asarray(statics.taint)
        sel_mask = (
            selector_match(cluster, sel) if features.spread else None
        )
    c_dim = sfeas_c.shape[0]
    extra_c = None
    if features.interpod_pref or features.images:
        # Hoisted per-class static score extras: preferred inter-pod
        # affinity (counts from BOUND pods at prep — scoring.go PreScore
        # over the cycle snapshot; in-batch placements don't attract
        # later batchmates within this solve, documented divergence, and
        # the normalization set is the class's static-feasible nodes) and
        # ImageLocality (image presence never changes mid-solve).
        from .interpod import prep_pref_pod
        from .scores import static_extra

        pp = (
            prep_pref_pod(
                cluster, prefpod, topo_z, axis_name=axis_name,
                has_bound=features.bound_pref,
            )
            if features.interpod_pref
            else None
        )
        reps_e = jnp.clip(pods.class_rep, 0, p - 1)
        extra_c = jax.vmap(
            lambda c, rep: static_extra(
                cluster, prefpod, images, features, cfg, rep, sfeas_c[c], pp,
                axis_name=axis_name,
            )
        )(jnp.arange(c_dim, dtype=jnp.int32), reps_e)
    sp0 = (
        prep_spread(
            cluster, sel_mask, spread, topo_z, axis_name=axis_name,
            has_bound=features.bound_spread,
        )
        if features.spread
        else None
    )
    tm0 = (
        prep_terms(
            cluster, terms, topo_z, axis_name=axis_name,
            slots=features.term_slots, has_bound=features.bound_terms,
        )
        if features.interpod
        else None
    )
    return (cluster, pods, spread, terms, sfeas_c, aff_c, taint_c, extra_c,
            sp0, tm0, c_dim, n, p)


def _gang_release(
    assignment, win_scores, reasons, requested, nonzero, pods, n_groups, n,
    offset=0,
):
    """All-or-nothing gang post-pass shared by the scan and wavefront
    solvers: release every placement of a group with an unplaced member.
    Only requested/nonzero need subtracting: ports and spread/interpod
    counts are rebuilt from *actually bound* pods at the next batch's
    prep, and the host never assumes released members.

    `n` is the LOCAL node count and `offset` the shard's first global
    row under shard_map (0 single-chip): each shard subtracts only the
    released rows it owns — out-of-window scatter targets drop."""
    g = pods.group_id
    gc = jnp.clip(g, 0, n_groups - 1)
    incomplete = jnp.zeros(n_groups, bool).at[gc].max(
        (assignment < 0) & pods.valid & (g >= 0)
    )
    dropped = (g >= 0) & incomplete[gc] & (assignment >= 0)
    tgt = jnp.where(
        dropped & (assignment >= offset) & (assignment < offset + n),
        assignment - offset, n,
    )
    w = dropped[:, None].astype(jnp.float32)
    requested = requested.at[tgt].add(-pods.req * w)
    nonzero = nonzero.at[tgt].add(-pods.nonzero_req * w)
    assignment = jnp.where(dropped, -1, assignment)
    win_scores = jnp.where(dropped, NEG_INF, win_scores)
    reasons = jnp.where(dropped, REASON_GANG, reasons)
    return assignment, win_scores, reasons, requested, nonzero


@hot_path
def greedy_assign(
    snapshot: Snapshot,
    cfg: ScoreConfig = DEFAULT_SCORE_CONFIG,
    tie_seed: Optional[int] = None,
    topo_z: Optional[int] = None,
    features: Optional[FeatureFlags] = None,
    n_groups: int = 0,
    axis_name: Optional[str] = None,
    statics=None,
) -> SolveResult:
    """Sequential-greedy solve of the whole pending batch on device.

    Semantically equivalent to running the reference's scheduling cycle
    once per pod in priority order with cache assume between cycles — the
    scan carry holds everything a placement changes: resource usage,
    in-batch port claims, topology-spread counts, and inter-pod affinity
    term state.

    topo_z: padded topology-value vocab size (SnapshotMeta.topo_z or
    required_topo_z); auto-derived when None.  Both topo_z and features
    can only be auto-derived outside jit — jitted callers must pass them
    (greedy_assign_jit's wrapper does).

    n_groups (static): gang-group count.  When > 0, groups with any
    unplaced member release every placement after the scan (all-or-nothing,
    the coscheduling-PodGroup contract) — this is what lets gangs carrying
    spread/interpod/port constraints keep gang semantics instead of
    routing-away to a solver that drops them.  Later in-scan pods saw the
    released placements' resource/count impact (conservative: they may
    park and retry next batch); the released members return as
    unschedulable (-1).

    axis_name: mesh axis when called under shard_map with the NODE axis
    sharded (parallel.sharded.sharded_greedy_assign) — one
    implementation, two layouts, like ops.auction: pod-space state is
    replicated, node-space state sharded, the per-step election is a
    pmax/pmin pair, and constraint updates broadcast the winning node's
    column from its owning shard.  Placements are bit-identical to the
    single-chip scan (first-max-index resolves to the lowest global node
    index in both layouts).  Keyed (tie_seed) solves are single-chip
    only: reservoir sampling needs the full gumbel tie set per step."""
    if features is None:
        features = features_of(snapshot)
    if topo_z is None:
        topo_z = required_topo_z(snapshot)
    if axis_name is not None and tie_seed is not None:
        raise ValueError("keyed (tie_seed) solves are single-chip only")
    (cluster, pods, spread, terms, sfeas_c, aff_c, taint_c, extra_c,
     sp0, tm0, c_dim, n, p) = _solver_prep(
        snapshot, cfg, topo_z, features, axis_name=axis_name,
        statics=statics,
    )
    offset, n_total, node_rows, node_col = _shard_layout(axis_name, n)
    order = solve_order(pods)
    keys = (
        jax.random.split(jax.random.PRNGKey(tie_seed), p)
        if tie_seed is not None
        else None
    )
    # slice carve-out carry: per-gang anchored slice + carved corner
    # (written by the gang's first placed member, read by the rest)
    use_gang_carve = features.slices and n_groups > 0

    def step(carry, k):
        (requested, nonzero, new_ports, sp_counts, tm_present, tm_blocked,
         tm_global, gang_sl, gang_lo, gang_corner) = carry
        i = order[k]
        cl = cluster._replace(requested=requested, nonzero_requested=nonzero)
        pod = pod_view(pods, i)
        cls = jnp.clip(pods.class_id[i], 0, c_dim - 1)
        sp = tm = None
        if features.spread:
            sp = sp0._replace(counts_node=sp_counts)
        if features.interpod:
            tm = tm0._replace(
                present_bits=tm_present, blocked_bits=tm_blocked, global_any=tm_global
            )
        feas, masked, found, reason, feas_cnt = _eval_pod(
            cl, pods, i, cls, sfeas_c, aff_c, taint_c, extra_c,
            new_ports, sp, tm, spread, terms, features, cfg,
            axis_name=axis_name,
            gang_sl=gang_sl if use_gang_carve else None,
            gang_lo=gang_lo if use_gang_carve else None,
        )
        if axis_name is None:
            choice = _pick(masked, feas, keys[k] if keys is not None else None)
            win_val = masked[choice]
        else:
            choice, win_val = _elect(masked, offset, axis_name)
        idx = jnp.where(found, choice, -1).astype(jnp.int32)

        onehot = ((jnp.arange(n) + offset) == choice) & found
        requested = requested + onehot[:, None] * pod.req[None, :]
        nonzero = nonzero + onehot[:, None] * pod.nonzero_req[None, :]
        if features.ports:
            new_ports = jnp.where(
                onehot[:, None], new_ports | pod.port_bits[None, :], new_ports
            )
        if features.spread:
            sp = spread_update(
                sp, spread, i, node_col(sp.v, choice),
                node_col(sp.eligible, choice), found,
            )
            sp_counts = sp.counts_node
        if features.interpod:
            tm = interpod_update(
                tm, terms, i, node_rows(cluster.topo_ids, choice), found,
                slots=features.term_slots,
            )
            tm_present, tm_blocked, tm_global = (
                tm.present_bits, tm.blocked_bits, tm.global_any
            )
        if use_gang_carve:
            from .slices import corner_mask as _corner_mask
            from .slices import free_devices as _free_devices

            g = pods.group_id[i]
            gc = jnp.clip(g, 0, n_groups - 1)
            shaped = pods.pod_shape[i].prod() > 0
            ch_sid = node_rows(cluster.slice_id, choice)
            ch_xyz = node_rows(cluster.torus_coords, choice)[:3]
            # was the anchor a genuine free-box corner (pre-placement
            # carry state)?  Drives the contiguous-vs-fallback counters:
            # a prefer-mode anchor dropped on a non-corner can still
            # cluster its members, but the REQUESTED carve-out was not
            # realized
            corner_n = _corner_mask(
                cl, _free_devices(cl), pods.pod_shape[i],
                features.slice_z, features.slice_dim, axis_name=axis_name,
            )
            ch_corner = node_rows(corner_n, choice)
            new_anchor = found & (g >= 0) & shaped & (gang_sl[gc] < 0)
            gang_sl = gang_sl.at[gc].set(
                jnp.where(new_anchor, ch_sid, gang_sl[gc])
            )
            gang_lo = gang_lo.at[gc].set(
                jnp.where(new_anchor, ch_xyz, gang_lo[gc])
            )
            gang_corner = gang_corner.at[gc].set(
                jnp.where(new_anchor, ch_corner, gang_corner[gc])
            )
        out = (i, idx, jnp.where(found, win_val, NEG_INF),
               feas_cnt, reason)
        carry = (requested, nonzero, new_ports, sp_counts, tm_present,
                 tm_blocked, tm_global, gang_sl, gang_lo, gang_corner)
        return carry, out

    zero = jnp.zeros(())
    init = (
        cluster.requested,
        cluster.nonzero_requested,
        jnp.zeros_like(cluster.port_bits) if features.ports else zero,
        sp0.counts_node if features.spread else zero,
        tm0.present_bits if features.interpod else zero,
        tm0.blocked_bits if features.interpod else zero,
        tm0.global_any if features.interpod else zero,
        jnp.full(n_groups, -1, jnp.int32) if use_gang_carve else zero,
        jnp.full((n_groups, 3), -1, jnp.int32) if use_gang_carve else zero,
        jnp.zeros(n_groups, bool) if use_gang_carve else zero,
    )
    (
        (requested, nonzero, new_ports, _sp_c, _tm_p, _tm_b, _tm_g,
         gang_sl_f, gang_lo_f, gang_corner_f),
        (pod_is, assign_o, win_o, feas_o, reason_o),
    ) = jax.lax.scan(step, init, jnp.arange(p))
    # Scatter scan outputs (priority order) back to batch positions.
    assignment = jnp.full(p, -1, jnp.int32).at[pod_is].set(assign_o)
    win_scores = jnp.full(p, NEG_INF).at[pod_is].set(win_o)
    feas_counts = jnp.zeros(p, jnp.int32).at[pod_is].set(feas_o)
    reasons = jnp.full(p, REASON_NONE, jnp.int32).at[pod_is].set(reason_o)

    # Gang post-pass: all-or-nothing release, mirroring ops.auction's
    # post-pass (shared with the wavefront solver via _gang_release).
    if n_groups > 0:
        assignment, win_scores, reasons, requested, nonzero = _gang_release(
            assignment, win_scores, reasons, requested, nonzero,
            pods, n_groups, n, offset=offset,
        )

    final = cluster._replace(
        requested=requested,
        nonzero_requested=nonzero,
        port_bits=(cluster.port_bits | new_ports) if features.ports
        else cluster.port_bits,
    )
    frag = carveouts = contiguous = fallbacks = None
    if features.slices:
        from .slices import fragmentation

        frag = fragmentation(
            final, features.slice_z, features.slice_dim,
            axis_name=axis_name,
        ).score
        carveouts = jnp.int32(0)
        contiguous = jnp.int32(0)
        fallbacks = jnp.int32(0)
        if use_gang_carve:
            # carve-out telemetry over the POST-RELEASE assignment:
            # anchored = the gang carved a box; complete = every shaped
            # member placed; contiguous = complete with every member
            # inside its box (require mode makes complete ⇒ contiguous)
            g = pods.group_id
            gc = jnp.clip(g, 0, n_groups - 1)
            member = pods.valid & (g >= 0) & (pods.pod_shape.prod(-1) > 0)
            any_member = jnp.zeros(n_groups, bool).at[gc].max(member)
            unplaced = jnp.zeros(n_groups, bool).at[gc].max(
                member & (assignment < 0)
            )
            complete = any_member & ~unplaced
            a = jnp.clip(assignment, 0, n_total - 1)
            a_sid = node_rows(cluster.slice_id, a)           # i32[P]
            a_xyz = node_rows(cluster.torus_coords, a)[:, :3]
            lo = gang_lo_f[gc]
            in_cub = (
                (a_sid == gang_sl_f[gc])
                & (a_xyz >= lo).all(-1)
                & (a_xyz < lo + pods.pod_shape).all(-1)
            )
            out_of_cub = jnp.zeros(n_groups, bool).at[gc].max(
                member & (assignment >= 0) & ~in_cub
            )
            anchored = (gang_sl_f >= 0) & any_member
            carveouts = anchored.sum().astype(jnp.int32)
            contiguous = (
                (complete & anchored & gang_corner_f & ~out_of_cub)
                .sum().astype(jnp.int32)
            )
            fallbacks = complete.sum().astype(jnp.int32) - contiguous
    return SolveResult(
        assignment, win_scores, feas_counts, final, reasons,
        frag_score=frag, carveouts=carveouts,
        contiguous_gangs=contiguous, carveout_fallbacks=fallbacks,
    )


def greedy_assign_jit(cfg: ScoreConfig = DEFAULT_SCORE_CONFIG):
    """A jitted closure over the (static, hashable) score config.
    topo_z and the feature gates are static: one executable per
    (shape-bucket, topo_z, features).  Features are auto-detected
    host-side when not supplied.

    `statics` (ops.partials.ClassStatics) selects the WARM twin: a
    distinct executable (three extra [C, N] operands, no in-program
    selector/taint/affinity re-evaluation) warm-started from the
    device-resident PartialsCache — the incremental O(changes) solve."""

    @partial(jax.jit, static_argnums=(1, 2, 3))
    def run(
        snapshot: Snapshot, topo_z: int, features: FeatureFlags, n_groups: int
    ) -> SolveResult:
        return greedy_assign(
            snapshot, cfg, topo_z=topo_z, features=features, n_groups=n_groups
        )

    @partial(jax.jit, static_argnums=(2, 3, 4))
    def run_warm(
        snapshot: Snapshot, statics, topo_z: int, features: FeatureFlags,
        n_groups: int,
    ) -> SolveResult:
        return greedy_assign(
            snapshot, cfg, topo_z=topo_z, features=features,
            n_groups=n_groups, statics=statics,
        )

    def call(
        snapshot: Snapshot,
        topo_z: Optional[int] = None,
        features: Optional[FeatureFlags] = None,
        n_groups: Optional[int] = None,
        statics=None,
    ) -> SolveResult:
        if features is None:
            features = features_of(snapshot)
        if topo_z is None:
            # topo_z only shapes spread/inter-pod prep state; pinning it
            # to 1 when no family is active keeps the jit cache key
            # stable as topology vocabularies grow.
            topo_z = required_topo_z(snapshot) if needs_topo(features) else 1
        if n_groups is None:
            n_groups = num_groups(snapshot)
        if n_groups > 0:
            # Bucket to a power of two: n_groups is a static jit arg, and
            # the post-pass clips, so padding costs nothing but stabilizes
            # the executable cache as gang counts vary batch to batch.
            from ..utils.vocab import pad_dim

            n_groups = pad_dim(n_groups, 1)
        if statics is not None:
            out = run_warm(snapshot, statics, topo_z, features, n_groups)
            retrace.note(
                "greedy-warm", run_warm,
                lambda: retrace.signature(
                    (snapshot, statics), (topo_z, features, n_groups)
                ),
            )
            return out
        out = run(snapshot, topo_z, features, n_groups)
        retrace.note(
            "greedy", run,
            lambda: retrace.signature(snapshot, (topo_z, features, n_groups)),
        )
        return out

    call.jitted = run  # raw jit, for AOT prewarm (lower().compile())
    call.jitted_warm = run_warm
    return call


# -- wavefront greedy -------------------------------------------------------
#
# The scan above pays one sequential device step per pod.  The wavefront
# solver partitions the priority-ordered batch into WAVES and pays one
# heavy step per wave: the [K, N] Filter+Score evaluation of all wave
# members runs batched against the wave-start carry, and the sequential
# decisions inside the wave run in an O(K) mini-scan that only *corrects*
# the precomputed scores at nodes picked earlier in the wave (the
# allocation scores are the only usage-dependent score family, and they
# are per-node closed forms).  Exact one-pod-at-a-time semantics are
# preserved:
#
#   * Wave membership guarantees no dynamic coupling: pairwise-disjoint
#     host-port bits and no spread/inter-pod row written by an earlier
#     member that a later member reads.  The device re-verifies this
#     (ports/spread/term pairwise masks) and serializes the whole wave
#     through the original step body when the partitioner got it wrong —
#     ANY contiguous partition of the solve order is therefore correct.
#   * A saturated affinity term is no write.  A pod that requires term t
#     by affinity, once t has a matching pod somewhere (global_any), can
#     only land where t is already present: the escape needs every term
#     of the pod unmatched everywhere.  Presence is domain-wide and only
#     goes 0 -> 1, so its own match of t flips no bit any member reads.
#     Both the plan (against the batch-start table) and the device
#     (against the wave-start global_any) drop that write; anti-affinity
#     writes, matches of terms the pod does not require, and the escape
#     (global_any[t] == 0) couple as ever.
#   * Within a safe wave, a member's sequential score vector differs from
#     its wave-start vector only at nodes picked earlier in the wave, so
#     the mini-scan compares the corrected picked-node scores against the
#     best unpicked candidate from a precomputed top-(K+1) list —
#     first-max-index tie-breaks included (lax.top_k is index-stable).
#   * Resource tightening that FLIPS a member's fit at a picked node
#     would change its feasible set (and the score normalization over
#     it), so that member falls back to an exact full re-evaluation
#     against the live carry inside its mini-step (lax.cond — the rare
#     branch costs nothing when untaken).
#
# A wave costs what its members cost.  The plan's rows are K lanes wide
# whatever they hold (a coupled batch gets one row a pod: one member and
# K-1 pads), so the device reads the row and takes one of three shapes:
#
#   * ONE MEMBER: the wave is that member's scan step — one _eval_pod
#     against the wave-start carry, one argmax, the usage/ports/spread/
#     term updates.  No [K, N] evaluation, no top-k, no safety check.
#     Not a fallback: there was nothing to batch.
#   * SAFE (two members or more, no in-wave coupling): the [K, N]
#     evaluation and the top-(K+1), then the mini-scan — which runs to
#     the wave's LAST VALID LANE, not to K (a hole before it is skipped
#     by its valid bit; members need not be packed at the front).
#   * COUPLED: the original step body over the members, to the last
#     valid lane; it pays for no [K, N] evaluation either.
#
# The trip counts and the branch are functions of the plan alone, which
# is replicated under both sharded layouts, so every shard runs the same
# trips and the collectives inside a step stay aligned.
#
# Telemetry (SolveResult): wave_count — rows with a member; wave_fallbacks
# — members of coupled waves plus per-member fit-flip re-evaluations;
# wave_steps — in-wave sequential steps the device ran (1 a one-member
# wave, the last valid lane's index + 1 otherwise).  Steps over pods is
# 1.0 where the waves hold nothing but members; K x waves / pods is what
# loops to the cap would have run.
#
# Gang all-or-nothing rides the same shared post-pass.  Keyed (tie_seed)
# solves stay on the classic scan — reservoir sampling needs the full
# gumbel tie set per step.

DEFAULT_WAVE_CAP = 32


class WavePlan(NamedTuple):
    """Host-side wave partition of one batch (plan_waves)."""

    members: np.ndarray  # i32[W_pad, K] pod indices in solve order, -1 pad
    n_waves: int         # real (non-empty) wave count


def waves_couple(features: FeatureFlags) -> bool:
    """True when members of one wave can conflict (host ports, spread
    rows, inter-pod terms): how many waves a batch then splits into is
    its composition's, anything from P/K to one wave a pod.  (A batch
    whose only term rows are saturated affinity terms plans P/K waves,
    but its shape stays the coupled one: whether a term is saturated is
    the cluster's state, not the feature set.)"""
    return bool(
        features.ports or features.spread or features.soft_spread
        or features.interpod
    )


def wave_rows(p: int, wave_cap: int, coupled: bool) -> int:
    """Rows W of the i32[W, K] wave plan of a p-pod bucket — a shape of
    the wavefront executable, so a function of the bucket and the
    feature set, never of the batch's composition.  Uncoupled batches
    fill every wave to the cap: ceil(p/K) rows, floored at 8.  Coupled
    ones get one row a pod, the most any partition needs: rows past the
    real waves are all -1 and the scan skips them (a cond on
    ``mvalid.any()``), so the width costs a predicate a row.  An
    uncoupled batch that headroom splits past its rows takes the
    coupled shape (plan_waves)."""
    from ..utils.vocab import pad_dim

    if coupled:
        return pad_dim(p, 8)
    return pad_dim(max(-(-p // wave_cap), 1), 8)


def _pack_idx_rows(idx: np.ndarray, dim: int) -> np.ndarray:
    """i32[P, M] index lists (-1 pad) -> packed u32[P, words] membership."""
    p = idx.shape[0]
    words = max(1, (dim + 31) // 32)
    out = np.zeros((p, words), dtype=np.uint32)
    rows, vals = np.nonzero(idx >= 0)
    ids = idx[rows, vals]
    # the shift count must be u32: `np.uint32(1) << (i32 & 31)` promotes
    # the whole expression to i64 under NumPy 2 (a tensor-contract
    # bitset-widening true positive)
    np.bitwise_or.at(
        out, (rows, ids >> 5), np.uint32(1) << (ids & 31).astype(np.uint32)
    )
    return out


def plan_waves(  # graftlint: disable=purity -- host-side prep: the wave partition walks host numpy (module docstring)
    snapshot: Snapshot,
    features: Optional[FeatureFlags] = None,
    wave_cap: int = DEFAULT_WAVE_CAP,
    headroom_frac: float = 1.0,
) -> WavePlan:
    """Partition the solve order into conflict-free waves (host numpy).

    A pod joins the open wave unless one of these would break:
      * size: the wave already holds `wave_cap` members;
      * ports: its host-port bits intersect a member's (the in-wave port
        carry must stay untouched for wave members);
      * spread/terms: a wave member WRITES a constraint row this pod
        READS (spread: pod_matches vs pod_idx; terms: matches_incoming ∪
        anti vs matches_incoming ∪ anti ∪ aff) — count/bit drift inside
        the wave would break the wave-start evaluation.  A pod's match
        of a term it requires by affinity is no write where the term has
        a matching pod somewhere at the batch's start (saturated: the pod
        can only land where the term is present already, see the
        wavefront section comment);
      * headroom: aggregate wave demand would exceed `headroom_frac` of
        the emptiest node's free capacity (elementwise) — a heuristic
        that keeps per-member fit-flip fallbacks rare, not a correctness
        condition (the device detects flips exactly).

    The partition is a pure performance hint: wavefront_assign re-checks
    coupling on device and serializes unsafe waves, so any output of this
    function yields placements identical to the scan.

    Row ``sched.plan.terms`` (one a plan whose batch has term rows): n =
    pods that carry or match a term, a0 = those whose match of a term
    they require was dropped as saturated, a1 = affinity terms with no
    matching pod at the batch's start (the escape is open)."""
    t_plan = trace.now()
    if features is None:
        features = features_of(snapshot)
    pods = snapshot.pods
    priority = np.asarray(pods.priority)
    p = priority.shape[0]
    order = np.argsort(-priority, kind="stable").astype(np.int32)

    use_ports = bool(features.ports)
    use_spread = bool(features.spread or features.soft_spread)
    use_terms = bool(features.interpod)
    port_bits = np.asarray(pods.port_bits) if use_ports else None
    if use_spread:
        sp_idx = np.asarray(snapshot.spread.pod_idx)
        reads_sp = _pack_idx_rows(sp_idx, np.asarray(snapshot.spread.valid).shape[0])
        pm = np.asarray(snapshot.spread.pod_matches)
        writes_sp = np.packbits(
            pm, axis=1, bitorder="little"
        )
        # pad packbits' u8 words up to the u32 row width of reads_sp
        w32 = reads_sp.shape[1] * 4
        if writes_sp.shape[1] < w32:
            writes_sp = np.pad(writes_sp, ((0, 0), (0, w32 - writes_sp.shape[1])))
        writes_sp = writes_sp[:, :w32].copy().view(np.uint32)
    if use_terms:
        t_dim = np.asarray(snapshot.terms.valid).shape[0]
        mi = np.asarray(snapshot.terms.matches_incoming)
        aff_idx = np.asarray(snapshot.terms.aff_idx)
        anti = _pack_idx_rows(np.asarray(snapshot.terms.anti_idx), t_dim)
        aff = _pack_idx_rows(aff_idx, t_dim)
        w = min(mi.shape[1], anti.shape[1])
        present = _terms_present(snapshot, aff_idx)
        sat = aff[:, :w] & _pack_idx_rows(
            np.flatnonzero(present).astype(np.int32)[None, :], t_dim
        )[:, :w]
        writes_tm = (mi[:, :w] & ~sat) | anti[:, :w]
        reads_tm = mi[:, :w] | anti[:, :w] | aff[:, :w]

    req = np.asarray(pods.req)
    alloc = np.asarray(snapshot.cluster.allocatable)
    used = np.asarray(snapshot.cluster.requested)
    valid = np.asarray(snapshot.cluster.node_valid)
    free = np.where(valid[:, None], alloc - used, 0.0)
    slack = free.max(axis=0) * float(headroom_frac)

    waves: List[List[int]] = []
    cur: List[int] = []
    port_acc = None if not use_ports else np.zeros_like(port_bits[0])
    sp_acc = None if not use_spread else np.zeros_like(writes_sp[0])
    tm_acc = None if not use_terms else np.zeros_like(writes_tm[0])
    # f32, matching the schema's request dtype: an f64 accumulator here
    # promoted every downstream `demand + req[i]` comparison to f64 (a
    # tensor-contract finding), and request quantities stay inside f32's
    # exact-integer envelope by construction (schema.F32_EXACT_LIMIT)
    demand = np.zeros(req.shape[1], dtype=np.float32)

    def close():
        nonlocal cur, port_acc, sp_acc, tm_acc, demand
        if cur:
            waves.append(cur)
        cur = []
        if use_ports:
            port_acc = np.zeros_like(port_bits[0])
        if use_spread:
            sp_acc = np.zeros_like(writes_sp[0])
        if use_terms:
            tm_acc = np.zeros_like(writes_tm[0])
        demand = np.zeros(req.shape[1], dtype=np.float32)

    # a pod that asks for more than the emptiest node has free fits
    # nowhere whatever its wave places: it writes no usage row, so it
    # rides in the open wave and counts for nothing in its demand (alone
    # in a wave each, a batch of such pods took a plan of one row a pod,
    # another executable than its bucket's)
    unplaceable = (req > slack).any(axis=1)

    for i in order.tolist():
        rides = bool(unplaceable[i])
        conflict = len(cur) >= wave_cap
        if not conflict and cur:
            if use_ports and (port_acc & port_bits[i]).any():
                conflict = True
            elif use_spread and (sp_acc & reads_sp[i]).any():
                conflict = True
            elif use_terms and (tm_acc & reads_tm[i]).any():
                conflict = True
            elif not rides and ((demand + req[i]) > slack).any():
                conflict = True
        if conflict:
            close()
        cur.append(i)
        if use_ports:
            port_acc |= port_bits[i]
        if use_spread:
            sp_acc |= writes_sp[i]
        if use_terms:
            tm_acc |= writes_tm[i]
        if not rides:
            demand += req[i]
    close()

    n_waves = len(waves)
    w_pad = wave_rows(p, wave_cap, waves_couple(features))
    if n_waves > w_pad:
        # headroom splits of an uncoupled batch (a cluster near full)
        # passed the rows its bucket always gets: the coupled shape
        # then, so a bucket still has two plans at most
        w_pad = wave_rows(p, wave_cap, True)
    members = np.full((w_pad, wave_cap), -1, dtype=np.int32)
    for wi, wv in enumerate(waves):
        members[wi, : len(wv)] = wv
    if use_terms:
        real = np.asarray(pods.valid)
        required = np.unique(aff_idx[real][aff_idx[real] >= 0])
        trace.event(
            "sched.plan.terms", t_plan, trace.now(),
            n=int((reads_tm[real] != 0).any(axis=1).sum()),
            a0=int(((mi[:, :w] & sat)[real] != 0).any(axis=1).sum()),
            a1=int((~present[required]).sum()),
        )
    return WavePlan(members=members, n_waves=n_waves)


def _terms_present(snapshot: Snapshot, aff_idx: np.ndarray) -> np.ndarray:  # graftlint: disable=purity -- host-side prep on the pre-transfer snapshot
    """bool[T]: the terms some pod requires by affinity that have a
    matching bound pod on a valid node carrying the term's key — the
    host-side twin of interpod.prep_terms' global_any, row by row."""
    terms = snapshot.terms
    valid = np.asarray(terms.valid)
    out = np.zeros(valid.shape[0], dtype=bool)
    rows = np.unique(aff_idx[aff_idx >= 0])
    if rows.size == 0:
        return out
    cluster = snapshot.cluster
    keyed = np.asarray(cluster.topo_ids)[:, np.asarray(terms.slot)[rows]].T >= 0
    ok = keyed & np.asarray(cluster.node_valid)[None, :] & valid[rows, None]
    out[rows] = ((np.asarray(terms.node_matches)[rows] > 0) & ok).any(axis=1)
    return out


def _rows_cluster(cap, requested, nonzero):
    """A K-row stand-in ClusterTensors for the per-node allocation score
    recomputes (resource_score_parts only touches these three fields)."""
    return ClusterTensors(
        allocatable=cap, requested=requested, nonzero_requested=nonzero,
        node_valid=None, name_id=None, label_bits=None, taint_bits=None,
        port_bits=None, topo_ids=None, image_bits=None, slice_id=None,
        torus_coords=None, slice_dims=None, slice_pos=None,
    )


@hot_path
def wavefront_assign(
    snapshot: Snapshot,
    wave_members: jnp.ndarray,
    cfg: ScoreConfig = DEFAULT_SCORE_CONFIG,
    topo_z: Optional[int] = None,
    features: Optional[FeatureFlags] = None,
    n_groups: int = 0,
    axis_name: Optional[str] = None,
    statics=None,
    pod_axis_name: Optional[str] = None,
) -> SolveResult:
    """Wave-parallel greedy solve with exact scan parity (see module
    section comment).  wave_members: i32[W, K] pod indices covering every
    batch position in solve order (-1 pads), from plan_waves.

    pod_axis_name: mesh axis when called under shard_map with the POD
    axis sharded (parallel.sharded.podsharded_wavefront_assign) — the
    twin of the node-axis layout for wide-wave batches: node tables stay
    replicated, wave_members arrives K-sharded, and each device runs the
    heavy batched [K, N] evaluation only for its K/D member slice; one
    all_gather per wave rebuilds the full [K, N] score block, after
    which the top-(K+1), wave-safety, and O(K) mini-scan math runs
    replicated-identically on every device (node offset 0, no
    elections).  Placements are bit-identical to the single-shard
    wavefront.  Mutually exclusive with axis_name.

    axis_name: mesh axis when called under shard_map with the NODE axis
    sharded (parallel.sharded.sharded_wavefront_assign).  The batched
    [K, N] evaluation and the O(K) mini-scan both keep the node tensors
    sharded: each shard pre-evaluates its node shard and takes a local
    top-(K+1), an all_gather merges the per-shard candidate lists into
    the global top-(K+1) (equal scores resolve to the lowest global
    index in both layouts, so the merge is tie-stable), the mini-scan's
    picked-node score corrections run on ownership-masked psum-gathered
    rows (replicated, so every shard reaches the same choice with no
    further election), and only the rare fit-flip / serialized-wave
    fallbacks pay a per-pod pmax/pmin election.  Placements are
    bit-identical to the single-chip scan."""
    from .scores import resource_score_parts

    if features is None:
        features = features_of(snapshot)
    if features.slices:
        # every shaped pod writes the free mask that every other shaped
        # pod's corner evaluation reads — wave-start evaluation cannot
        # hold; TPUBatchScheduler._route keeps these on the classic scan
        raise ValueError(
            "slice carve-out batches (features.slices) route to the "
            "classic greedy scan, not the wavefront solver"
        )
    if topo_z is None:
        topo_z = required_topo_z(snapshot)
    (cluster, pods, spread, terms, sfeas_c, aff_c, taint_c, extra_c,
     sp0, tm0, c_dim, n, p) = _solver_prep(
        snapshot, cfg, topo_z, features, axis_name=axis_name,
        statics=statics,
    )
    offset, n_total, node_rows, node_col = _shard_layout(axis_name, n)
    wave_members = jnp.asarray(wave_members, jnp.int32)
    if pod_axis_name is not None:
        if axis_name is not None:
            raise ValueError(
                "axis_name (node shard) and pod_axis_name (pod shard) "
                "are mutually exclusive in one wavefront call"
            )
        # wave_members arrives K-sharded: rebuild the full [W, K] plan
        # once up front (shard-major reshape matches shard_map's
        # contiguous blocks; psum of a constant folds to the static
        # axis size, so k_dim stays a Python int)
        d_pods = jax.lax.psum(1, pod_axis_name)
        k_local = wave_members.shape[1]
        wave_members = jnp.moveaxis(
            jax.lax.all_gather(wave_members, pod_axis_name), 0, 1
        ).reshape(wave_members.shape[0], k_local * d_pods)
    k_dim = wave_members.shape[1]
    # local and GLOBAL top-(K+1) widths: each shard's list must be wide
    # enough that the merged global list still holds the best unpicked
    # candidate after up to K in-wave picks
    kk = min(k_dim + 1, n)
    kk_g = min(k_dim + 1, n_total)
    arange_k = jnp.arange(k_dim, dtype=jnp.int32)

    # per-pod coupling rows for the device-side wave-safety check
    if features.interpod:
        t_dim = terms.valid.shape[0]
        from .interpod import _idx_to_bits, _pack_bits_t

        anti_w = _pack_bits_t(_idx_to_bits(terms.anti_idx, t_dim))
        aff_w = _pack_bits_t(_idx_to_bits(terms.aff_idx, t_dim))
        tw = min(terms.matches_incoming.shape[1], anti_w.shape[1])
        tm_mi = terms.matches_incoming[:, :tw]
        tm_anti = anti_w[:, :tw]
        tm_aff = aff_w[:, :tw]
        tm_reads = tm_mi | tm_anti | tm_aff
    if features.spread or features.soft_spread:
        c_rows = spread.valid.shape[0]
        sp_reads_all = (
            jnp.arange(c_rows)[None, None, :] == spread.pod_idx[:, :, None]
        ).any(axis=1)  # bool[P, C]

    def wave_safe(mk, mvalid, tm_global):
        """True when no member writes dynamic state an in-wave successor
        reads — the conflict-detection pass.  mk: clipped member ids;
        tm_global: the wave-start global_any (a match of a term the
        member requires by affinity is no write once that term is set
        there: the member lands where it is present already)."""
        tri = (arange_k[:, None] < arange_k[None, :]) & (
            mvalid[:, None] & mvalid[None, :]
        )
        ok = jnp.bool_(True)
        if features.ports:
            pb = pods.port_bits[mk]  # [K, PW]
            hit = (pb[:, None, :] & pb[None, :, :]).any(-1)
            ok = ok & ~(tri & hit).any()
        if features.spread or features.soft_spread:
            wr = spread.pod_matches[mk]  # [K, C]
            rd = sp_reads_all[mk]
            hit = (wr[:, None, :] & rd[None, :, :]).any(-1)
            ok = ok & ~(tri & hit).any()
        if features.interpod:
            sat = tm_aff[mk] & tm_global[None, :tw]
            wr = (tm_mi[mk] & ~sat) | tm_anti[mk]
            rd = tm_reads[mk]
            hit = (wr[:, None, :] & rd[None, :, :]).any(-1)
            ok = ok & ~(tri & hit).any()
        return ok

    def wave_step(carry, members):
        (requested, nonzero, new_ports, sp_counts,
         tm_present, tm_blocked, tm_global, n_fb, n_waves, n_steps) = carry
        mvalid = members >= 0
        mk = jnp.clip(members, 0, p - 1)
        # the in-wave loops stop after the last valid lane (holes before
        # it are skipped by valid_j, as ever).  A function of the
        # replicated plan: every shard runs the same trips.
        m_last = jnp.max(jnp.where(mvalid, arange_k + 1, 0))
        n_members = mvalid.sum().astype(jnp.int32)
        req0, nz0 = requested, nonzero
        cl0 = cluster._replace(requested=requested, nonzero_requested=nonzero)
        sp = tm = None
        if features.spread:
            sp = sp0._replace(counts_node=sp_counts)
        if features.interpod:
            tm = tm0._replace(
                present_bits=tm_present, blocked_bits=tm_blocked,
                global_any=tm_global,
            )

        def lane_rows():
            """The four [K] output rows as a lane nobody ran leaves them:
            unplaced (-1, so the deferred updates below stay no-ops), and
            the rest what skip_wave yields (dropped with the -1 member)."""
            return (
                jnp.full(k_dim, -1, jnp.int32),
                jnp.full(k_dim, NEG_INF),
                jnp.zeros(k_dim, jnp.int32),
                jnp.full(k_dim, REASON_NONE, jnp.int32),
            )

        def fast(_):
            # heavy half, batched: every member evaluated from the
            # wave-start carry in one vectorized pass
            def eval_one(i):
                cls = jnp.clip(pods.class_id[i], 0, c_dim - 1)
                _, masked, found, reason, cnt = _eval_pod(
                    cl0, pods, i, cls, sfeas_c, aff_c, taint_c, extra_c,
                    new_ports, sp, tm, spread, terms, features, cfg,
                    axis_name=axis_name,
                )
                return masked, found, reason, cnt

            if pod_axis_name is None:
                masked_k, found_k, reason_k, cnt_k = jax.vmap(eval_one)(mk)
            else:
                # pod-axis twin: each device evaluates only its K/D
                # member slice against the replicated node tables; one
                # all_gather rebuilds the full [K, N] block, and every
                # shard runs the identical downstream math
                k_loc = k_dim // d_pods
                mk_l = jax.lax.dynamic_slice_in_dim(
                    mk, jax.lax.axis_index(pod_axis_name) * k_loc, k_loc
                )
                m_l, f_l, r_l, c_l = jax.vmap(eval_one)(mk_l)
                masked_k = jax.lax.all_gather(
                    m_l, pod_axis_name
                ).reshape(k_dim, -1)
                found_k = jax.lax.all_gather(
                    f_l, pod_axis_name
                ).reshape(k_dim)
                reason_k = jax.lax.all_gather(
                    r_l, pod_axis_name
                ).reshape(k_dim)
                cnt_k = jax.lax.all_gather(
                    c_l, pod_axis_name
                ).reshape(k_dim)
            topv, topi = jax.lax.top_k(masked_k, kk)
            if axis_name is not None:
                # merge the per-shard top-(K+1) lists into the global
                # one: all_gather stacks shard-major, so the flattened
                # candidate order is (shard, local rank) — equal values
                # resolve to the lowest global node index, exactly the
                # single-chip top_k tie order
                vg = jax.lax.all_gather(topv, axis_name)           # [D, K, kk]
                ig = jax.lax.all_gather(topi + offset, axis_name)  # [D, K, kk]
                vg = jnp.moveaxis(vg, 0, 1).reshape(k_dim, -1)
                ig = jnp.moveaxis(ig, 0, 1).reshape(k_dim, -1)
                topv, pos = jax.lax.top_k(vg, kk_g)
                topi = jnp.take_along_axis(ig, pos, axis=1)

            def mini(j, mc):
                req_c, nz_c, picked, fb, w_k, c_k, r_k = mc
                i = mk[j]
                valid_j = mvalid[j]
                pod = pod_view(pods, i)
                cls = jnp.clip(pods.class_id[i], 0, c_dim - 1)
                prev = (arange_k < j) & (picked >= 0)
                # picked holds GLOBAL node ids; sharded, the row
                # gathers below replicate the K picked rows to every
                # shard so the correction math (and the choice) is
                # identical everywhere — no per-pod election needed
                pxc = jnp.clip(picked, 0, n_total - 1)
                cap_rows = node_rows(cluster.allocatable, pxc)
                req0_rows = node_rows(req0, pxc)
                reqc_rows = node_rows(req_c, pxc)
                skip = (pod.req[None, :] <= 0)
                fits0 = (
                    skip | (req0_rows + pod.req[None, :] <= cap_rows)
                ).all(-1)
                fitsc = (
                    skip | (reqc_rows + pod.req[None, :] <= cap_rows)
                ).all(-1)
                flip = (
                    prev & node_rows(sfeas_c[cls], pxc)
                    & (fits0 != fitsc)
                ).any() & valid_j

                def full(_):
                    # exact re-evaluation against the live carry:
                    # ports/spread/terms are wave-start but untouched
                    # within a safe wave, so this IS the sequential
                    # state
                    clj = cluster._replace(
                        requested=req_c, nonzero_requested=nz_c
                    )
                    _, masked, found, reason, cnt = _eval_pod(
                        clj, pods, i, cls, sfeas_c, aff_c, taint_c,
                        extra_c, new_ports, sp, tm, spread, terms,
                        features, cfg, axis_name=axis_name,
                    )
                    found = found & valid_j
                    if axis_name is None:
                        choice = jnp.argmax(masked).astype(jnp.int32)
                        win = jnp.where(found, masked[choice], NEG_INF)
                    else:
                        choice, best = _elect(masked, offset, axis_name)
                        win = jnp.where(found, best, NEG_INF)
                    return (choice, win, cnt, reason, found,
                            jnp.int32(1))

                def cheap(_):
                    # sequential scores differ from the wave-start
                    # vector only at picked nodes, and only in the
                    # (un-normalized) allocation parts — correct
                    # those entries in closed form
                    fit0, bal0 = resource_score_parts(
                        _rows_cluster(cap_rows, req0_rows,
                                      node_rows(nz0, pxc)),
                        pod, cfg,
                    )
                    fitc, balc = resource_score_parts(
                        _rows_cluster(cap_rows, reqc_rows,
                                      node_rows(nz_c, pxc)),
                        pod, cfg,
                    )
                    d_alloc = (
                        cfg.fit_weight * (fitc - fit0)
                        + cfg.balanced_weight * (balc - bal0)
                    )
                    base = node_rows(masked_k[j], pxc)
                    cand_ok = prev & (base > NEG_INF)
                    cand_val = base + d_alloc
                    tv, ti = topv[j], topi[j]
                    ispicked = (
                        (ti[:, None] == pxc[None, :]) & prev[None, :]
                    ).any(-1)
                    un_ok = ~ispicked & (tv > NEG_INF)
                    first = jnp.argmax(un_ok)
                    has_un = un_ok.any()
                    bu_val = jnp.where(has_un, tv[first], NEG_INF)
                    bu_idx = jnp.where(has_un, ti[first], n_total).astype(
                        jnp.int32
                    )
                    vals = jnp.concatenate(
                        [jnp.where(cand_ok, cand_val, NEG_INF),
                         bu_val[None]]
                    )
                    idxs = jnp.concatenate([pxc, bu_idx[None]])
                    best = jnp.max(vals)
                    found = found_k[j] & valid_j & (best > NEG_INF)
                    # first-max-index over the candidate union ==
                    # first-max-index over the corrected [N] vector
                    choice = jnp.min(
                        jnp.where((vals >= best) & (vals > NEG_INF),
                                  idxs, n_total)
                    ).astype(jnp.int32)
                    return (
                        choice, jnp.where(found, best, NEG_INF),
                        cnt_k[j], reason_k[j], found, jnp.int32(0),
                    )

                choice, win, cnt, reason, found, used_full = (
                    jax.lax.cond(flip, full, cheap, None)
                )
                cc = jnp.clip(choice, 0, n_total - 1)
                if axis_name is None:
                    tgt = cc
                else:
                    # the owning shard's local row; everyone else
                    # scatters out of bounds (dropped)
                    in_sh = (cc >= offset) & (cc < offset + n)
                    tgt = jnp.where(in_sh, cc - offset, n)
                wgt = found.astype(req_c.dtype)
                req_c = req_c.at[tgt].add(pod.req * wgt)
                nz_c = nz_c.at[tgt].add(pod.nonzero_req * wgt)
                picked = picked.at[j].set(jnp.where(found, cc, -1))
                return (req_c, nz_c, picked, fb + used_full,
                        w_k.at[j].set(win), c_k.at[j].set(cnt),
                        r_k.at[j].set(reason))

            a0_k, w0_k, c0_k, r0_k = lane_rows()
            req2, nz2, picked, fb, w_k, c_k, r_k = jax.lax.fori_loop(
                0, m_last, mini,
                (requested, nonzero, a0_k, jnp.int32(0), w0_k, c0_k, r0_k),
            )
            a_k = picked  # a lane's placement IS its picked node (-1: none)
            # deferred dynamic-state updates: no member read these, so
            # they commit batched at wave end (adds/ORs commute)
            ports2 = new_ports
            if features.ports:
                okp = picked >= 0
                if axis_name is None:
                    tgt = jnp.where(okp, picked, n)  # OOB rows drop
                else:
                    own = okp & (picked >= offset) & (
                        picked < offset + n
                    )
                    tgt = jnp.where(own, picked - offset, n)
                bits = pods.port_bits[mk] * okp[:, None].astype(
                    jnp.uint32
                )
                ports2 = new_ports.at[tgt].add(bits)
            spc2 = sp_counts
            if features.spread:
                # unrolled so XLA fuses the K count-updates into one
                # pass over [C, N] instead of K carried array writes
                st = sp0._replace(counts_node=sp_counts)
                for j in range(k_dim):
                    ch = jnp.clip(a_k[j], 0, n_total - 1)
                    st = spread_update(
                        st, spread, mk[j], node_col(st.v, ch),
                        node_col(st.eligible, ch), a_k[j] >= 0,
                    )
                spc2 = st.counts_node
            pr2, bl2, ga2 = tm_present, tm_blocked, tm_global
            if features.interpod:
                st = tm0._replace(
                    present_bits=tm_present, blocked_bits=tm_blocked,
                    global_any=tm_global,
                )
                for j in range(k_dim):
                    ch = jnp.clip(a_k[j], 0, n_total - 1)
                    st = interpod_update(
                        st, terms, mk[j], node_rows(cluster.topo_ids, ch),
                        a_k[j] >= 0, slots=features.term_slots,
                    )
                pr2, bl2, ga2 = (
                    st.present_bits, st.blocked_bits, st.global_any
                )
            return ((req2, nz2, ports2, spc2, pr2, bl2, ga2, fb, m_last),
                    (a_k, w_k, c_k, r_k))

        def sstep(c, j):
            """The original scan step for lane j against carry c — the
            body of a serialized wave and, alone, of a one-member wave."""
            (req_c, nz_c, ports_c, spc, pr, bl, ga) = c
            i = mk[j]
            valid_j = mvalid[j]
            clj = cluster._replace(
                requested=req_c, nonzero_requested=nz_c
            )
            spj = tmj = None
            if features.spread:
                spj = sp0._replace(counts_node=spc)
            if features.interpod:
                tmj = tm0._replace(
                    present_bits=pr, blocked_bits=bl, global_any=ga
                )
            cls = jnp.clip(pods.class_id[i], 0, c_dim - 1)
            pod = pod_view(pods, i)
            _, masked, found, reason, cnt = _eval_pod(
                clj, pods, i, cls, sfeas_c, aff_c, taint_c,
                extra_c, ports_c, spj, tmj, spread, terms,
                features, cfg, axis_name=axis_name,
            )
            found = found & valid_j
            if axis_name is None:
                choice = jnp.argmax(masked).astype(jnp.int32)
                win = jnp.where(found, masked[choice], NEG_INF)
            else:
                choice, best = _elect(masked, offset, axis_name)
                win = jnp.where(found, best, NEG_INF)
            cc = jnp.clip(choice, 0, n_total - 1)
            onehot = ((jnp.arange(n) + offset) == cc) & found
            wgt = found.astype(req_c.dtype)
            req_c = req_c + onehot[:, None] * pod.req[None, :] * wgt
            nz_c = (
                nz_c + onehot[:, None] * pod.nonzero_req[None, :] * wgt
            )
            if features.ports:
                ports_c = jnp.where(
                    onehot[:, None], ports_c | pod.port_bits[None, :],
                    ports_c,
                )
            if features.spread:
                spj = spread_update(
                    spj, spread, i, node_col(spj.v, cc),
                    node_col(spj.eligible, cc), found,
                )
                spc = spj.counts_node
            if features.interpod:
                tmj = interpod_update(
                    tmj, terms, i, node_rows(cluster.topo_ids, cc),
                    found, slots=features.term_slots,
                )
                pr, bl, ga = (
                    tmj.present_bits, tmj.blocked_bits,
                    tmj.global_any,
                )
            out = (jnp.where(found, cc, -1).astype(jnp.int32),
                   win, cnt, reason)
            return (req_c, nz_c, ports_c, spc, pr, bl, ga), out

        state0 = (requested, nonzero, new_ports, sp_counts,
                  tm_present, tm_blocked, tm_global)

        def step_into(j, c_rows):
            c, rows = c_rows
            c, out = sstep(c, j)
            return c, tuple(r.at[j].set(o) for r, o in zip(rows, out))

        def serial(_):
            # unsafe wave (in-wave coupling): run the original scan
            # step over the members — exact by construction
            c, outs = jax.lax.fori_loop(
                0, m_last, step_into, (state0, lane_rows())
            )
            return c + (n_members, m_last), outs

        def single(_):
            # one member: nothing in the wave to batch or to correct, so
            # the wave is that member's scan step — no [K, N] evaluation,
            # no top-k, no safety check; not a fallback
            c, outs = step_into(
                jnp.argmax(mvalid).astype(jnp.int32), (state0, lane_rows())
            )
            return c + (jnp.int32(0), jnp.int32(1)), outs

        def many(_):
            return jax.lax.cond(
                wave_safe(mk, mvalid, tm_global), fast, serial, None
            )

        def skip_wave(_):
            return state0 + (jnp.int32(0), jnp.int32(0)), lane_rows()

        (*state, fb, steps), outs = jax.lax.switch(
            jnp.minimum(n_members, 2), (skip_wave, single, many), None
        )
        new_carry = (*state, n_fb + fb, n_waves + jnp.minimum(n_members, 1),
                     n_steps + steps)
        return new_carry, outs

    zero = jnp.zeros(())
    init = (
        cluster.requested,
        cluster.nonzero_requested,
        jnp.zeros_like(cluster.port_bits) if features.ports else zero,
        sp0.counts_node if features.spread else zero,
        tm0.present_bits if features.interpod else zero,
        tm0.blocked_bits if features.interpod else zero,
        tm0.global_any if features.interpod else zero,
        jnp.int32(0),
        jnp.int32(0),
        jnp.int32(0),
    )
    (requested, nonzero, new_ports, *_rest, n_fb, n_waves, n_steps), (
        assign_w, win_w, cnt_w, reason_w
    ) = jax.lax.scan(wave_step, init, wave_members)

    flat_members = wave_members.reshape(-1)
    pod_is = jnp.where(flat_members >= 0, flat_members, p)  # OOB drop
    assignment = jnp.full(p, -1, jnp.int32).at[pod_is].set(
        assign_w.reshape(-1)
    )
    win_scores = jnp.full(p, NEG_INF).at[pod_is].set(win_w.reshape(-1))
    feas_counts = jnp.zeros(p, jnp.int32).at[pod_is].set(cnt_w.reshape(-1))
    reasons = jnp.full(p, REASON_NONE, jnp.int32).at[pod_is].set(
        reason_w.reshape(-1)
    )

    if n_groups > 0:
        assignment, win_scores, reasons, requested, nonzero = _gang_release(
            assignment, win_scores, reasons, requested, nonzero,
            pods, n_groups, n, offset=offset,
        )

    final = cluster._replace(
        requested=requested,
        nonzero_requested=nonzero,
        port_bits=(cluster.port_bits | new_ports) if features.ports
        else cluster.port_bits,
    )
    return SolveResult(
        assignment, win_scores, feas_counts, final, reasons,
        wave_count=n_waves, wave_fallbacks=n_fb, wave_steps=n_steps,
    )


def wavefront_assign_jit(cfg: ScoreConfig = DEFAULT_SCORE_CONFIG):
    """Jitted wavefront solver: one executable per (shape-bucket, topo_z,
    features, n_groups, wave shape).  The wave plan is a device argument
    (i32[W, K]) so repartitions of the same shapes reuse the executable."""

    @partial(jax.jit, static_argnums=(2, 3, 4))
    def run(
        snapshot: Snapshot, wave_members, topo_z: int,
        features: FeatureFlags, n_groups: int,
    ) -> SolveResult:
        return wavefront_assign(
            snapshot, wave_members, cfg, topo_z=topo_z, features=features,
            n_groups=n_groups,
        )

    @partial(jax.jit, static_argnums=(3, 4, 5))
    def run_warm(
        snapshot: Snapshot, wave_members, statics, topo_z: int,
        features: FeatureFlags, n_groups: int,
    ) -> SolveResult:
        return wavefront_assign(
            snapshot, wave_members, cfg, topo_z=topo_z, features=features,
            n_groups=n_groups, statics=statics,
        )

    def call(
        snapshot: Snapshot,
        wave_members=None,
        topo_z: Optional[int] = None,
        features: Optional[FeatureFlags] = None,
        n_groups: Optional[int] = None,
        wave_cap: int = DEFAULT_WAVE_CAP,
        statics=None,
    ) -> SolveResult:
        if features is None:
            features = features_of(snapshot)
        if topo_z is None:
            topo_z = required_topo_z(snapshot) if needs_topo(features) else 1
        if n_groups is None:
            n_groups = num_groups(snapshot)
        if n_groups > 0:
            from ..utils.vocab import pad_dim

            n_groups = pad_dim(n_groups, 1)
        if wave_members is None:
            wave_members = plan_waves(
                snapshot, features=features, wave_cap=wave_cap
            ).members
        members = jnp.asarray(wave_members, jnp.int32)
        if statics is not None:
            out = run_warm(snapshot, members, statics, topo_z, features,
                           n_groups)
            retrace.note(
                "wavefront-warm", run_warm,
                lambda: retrace.signature(
                    (snapshot, members, statics),
                    (topo_z, features, n_groups),
                ),
            )
            return out
        out = run(snapshot, members, topo_z, features, n_groups)
        retrace.note(
            "wavefront", run,
            lambda: retrace.signature(
                (snapshot, members), (topo_z, features, n_groups)
            ),
        )
        return out

    call.jitted = run  # raw jit, for AOT prewarm (lower().compile())
    call.jitted_warm = run_warm
    return call


@hot_path
def evaluate_single(
    snapshot: Snapshot,
    cfg: ScoreConfig = DEFAULT_SCORE_CONFIG,
    topo_z: Optional[int] = None,
    features: Optional[FeatureFlags] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(feasible[N], scores[N]) for pod 0 of the snapshot — the full
    Filter + Score chain with no placement (what an extender's
    filter/prioritize verbs need: the node SET, not one pick).

    Same kernels the solvers use: static filters + resources + spread +
    inter-pod affinity; scores are the weighted normalized sum
    (runtime/framework.go RunScorePlugins semantics)."""
    if features is None:
        features = features_of(snapshot)
    if topo_z is None:
        topo_z = required_topo_z(snapshot) if needs_topo(features) else 1
    (cluster, pods, sel, pref, spread, terms, prefpod, images) = jax.tree.map(
        jnp.asarray, tuple(snapshot)
    )
    from .interpod import interpod_filter, pref_pod_raw, prep_pref_pod, prep_terms
    from .topology import prep_spread, spread_filter, spread_score

    sel_mask = selector_match(cluster, sel)
    pref_mask = preferred_match(cluster, pref)
    pod = pod_view(pods, 0)
    feas = static_feasible_for_pod(cluster, pod, sel_mask) & ~(
        (cluster.port_bits & pod.port_bits[None, :]).any(axis=-1)
    )
    feas = feas & fits_resources(cluster, pod)
    sp_score = None
    if features.spread:
        sp = prep_spread(
            cluster, sel_mask, spread, topo_z,
            has_bound=features.bound_spread,
        )
        feas = feas & spread_filter(sp, spread, 0)
        if features.soft_spread:
            sp_score = spread_score(sp, spread, 0, feas)
    if features.interpod:
        tm = prep_terms(
            cluster, terms, topo_z, slots=features.term_slots,
            has_bound=features.bound_terms,
        )
        feas = feas & interpod_filter(tm, terms, 0)
    s_bonus = None
    if features.slices:
        # single-pod view: anchor semantics only (no gang carry)
        from .slices import carveout_eval

        s_bonus, s_ok = carveout_eval(
            cluster, pods, 0, None, None, features
        )
        if features.slice_require:
            feas = feas & s_ok
    extra = None
    if features.interpod_pref or features.images:
        from .scores import static_extra

        pp = (
            prep_pref_pod(
                cluster, prefpod, topo_z, has_bound=features.bound_pref
            )
            if features.interpod_pref
            else None
        )
        extra = static_extra(
            cluster, prefpod, images, features, cfg, 0, feas, pp
        )
    scores = score_from_raw(
        cluster, pod, feas,
        node_affinity_raw(pod, pref_mask),
        taint_toleration_raw(cluster, pod),
        cfg, spread_score=sp_score, extra=extra,
    )
    if s_bonus is not None:
        scores = scores + s_bonus
    return feas, jnp.where(feas, scores, NEG_INF)
