"""Node-axis-sharded solves: the multi-chip scheduling step.

The reference scales its hot loop with 16 goroutines and adaptive node
sampling (parallelize/parallelism.go, schedule_one.go:662); the TPU-native
scale-out shards the *node axis* of every cluster tensor across a device
mesh with shard_map.  Each chip filters and scores its node shard, reduces
its local champion, and a pmax/pmin pair elects the global winner — the
ring-reduction analogue sketched in SURVEY.md section 5.7.  The winning
shard applies the assume-update locally; per-pod state (requested, ports)
never leaves its shard, so per-step communication is O(1) scalars on ICI
(plus the wavefront's O(K) merged candidate list per wave), independent
of cluster size.

All three solver families follow the ops.auction pattern — ONE
implementation, two layouts: ops.assign.greedy_assign /
wavefront_assign and ops.auction.auction_assign take an ``axis_name``
and internally switch their node-axis boundary crossings to
ownership-masked psums, pmax/pmin elections, and all_gather merges.
The wrappers here only set up the shard_map specs, so the sharded
solvers cannot drift from the single-chip ones.

Tie-break parity with the single-chip path: lowest node index among
max-score nodes (argmax-first-index locally, pmin on the winner index
globally).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..analysis import retrace
from ..ops.assign import (
    DEFAULT_WAVE_CAP,
    FeatureFlags,
    SolveResult,
    features_of,
    greedy_assign,
    needs_topo,
    plan_waves,
    required_topo_z,
    required_topo_z_split,
    wavefront_assign,
)
from ..ops.auction import (
    AuctionResult,
    auction_assign,
    auction_features_ok,
    default_tie_k,
)
from ..ops.partials import ClassStatics
from ..ops.schema import (
    ClusterTensors,
    PrefPodTable,
    Snapshot,
    SpreadTable,
    TermTable,
    num_groups,
)
from ..ops.preemption import (
    BatchDryRunResult,
    PreemptionBatch,
    batched_dry_run,
)
from ..ops.scores import DEFAULT_SCORE_CONFIG, ScoreConfig

AXIS = "nodes"

# PartitionSpec for each ClusterTensors field: node axis sharded, the rest
# replicated.  taint_bits is effect-major so its node axis is dim 1.
CLUSTER_SPECS = ClusterTensors(
    allocatable=P(AXIS, None),
    requested=P(AXIS, None),
    nonzero_requested=P(AXIS, None),
    node_valid=P(AXIS),
    name_id=P(AXIS),
    label_bits=P(AXIS, None),
    taint_bits=P(None, AXIS, None),
    port_bits=P(AXIS, None),
    topo_ids=P(AXIS, None),
    image_bits=P(AXIS, None),
    slice_id=P(AXIS),
    torus_coords=P(AXIS, None),
    slice_dims=P(AXIS, None),
    slice_pos=P(AXIS),
)


# Warm-start statics ([C, N] per-class triples gathered from the
# device-resident PartialsCache): node axis sharded like every other
# [·, N] table — the resident store carries exactly this layout, so a
# warm mesh solve consumes it without resharding.
STATICS_SPECS = ClassStatics(
    sfeas=P(None, AXIS), aff=P(None, AXIS), taint=P(None, AXIS)
)


def make_mesh(n_devices: Optional[int] = None, devices=None) -> Mesh:
    if devices is None:
        devices = jax.devices()[: n_devices or len(jax.devices())]
    return Mesh(devices, (AXIS,))


def mesh_signature(mesh: Mesh) -> tuple:
    """Hashable mesh-shape component of a sharded executable key (the
    retrace tracker's and the prewarm pool's mesh discriminator)."""
    return ("mesh",) + tuple(int(d) for d in mesh.devices.shape)


def _spread_specs(rep):
    return SpreadTable(
        valid=rep, slot=rep, max_skew=rep, min_domains=rep, hard=rep,
        owner_sel_idx=rep, owner_keys=rep, node_matches=P(None, AXIS),
        pod_matches=rep, pod_idx=rep,
    )


def _term_specs(rep):
    return TermTable(
        valid=rep, slot=rep, node_matches=P(None, AXIS),
        node_owners=P(None, AXIS), matches_incoming=rep, aff_idx=rep,
        anti_idx=rep, self_match_all=rep,
    )


def _prefpod_specs(rep):
    return PrefPodTable(
        valid=rep, slot=rep, node_counts=P(None, AXIS),
        owner_weight=P(None, AXIS), matches_incoming=rep, pod_idx=rep,
        pod_weight=rep,
    )


def _snapshot_in_specs(parts):
    """shard_map in_specs for the 8 Snapshot components: cluster tensors
    node-sharded, pod/constraint tables replicated except their [·, N]
    per-node count matrices."""
    rep = P()
    (cluster, pods, sel, pref, spread, terms, prefpod, images) = parts
    return (
        CLUSTER_SPECS,
        jax.tree.map(lambda _: rep, pods),
        jax.tree.map(lambda _: rep, sel),
        jax.tree.map(lambda _: rep, pref),
        _spread_specs(rep),
        _term_specs(rep),
        _prefpod_specs(rep),
        jax.tree.map(lambda _: rep, images),
    )


def _check_divisible(n: int, mesh: Mesh) -> None:
    n_dev = mesh.devices.size
    if n % n_dev:
        raise ValueError(
            f"padded node count {n} not divisible by mesh size {n_dev}"
        )


def sharded_greedy_assign(
    snapshot: Snapshot,
    mesh: Mesh,
    cfg: ScoreConfig = DEFAULT_SCORE_CONFIG,
    topo_z: Optional[int] = None,
    features: Optional[FeatureFlags] = None,
    n_groups: int = 0,
    statics: Optional[ClassStatics] = None,
) -> SolveResult:
    """greedy_assign with the node axis sharded over `mesh`.

    Placement semantics are identical to ops.assign.greedy_assign; only
    the data layout differs — this wrapper sets up shard_map specs and
    calls greedy_assign(axis_name=...), which handles the elections and
    constraint-state broadcasts internally.  Requires the padded node
    count to be divisible by the mesh size (SnapshotBuilder pads to
    powers of two, mesh sizes are powers of two, so this holds whenever
    the cluster bucket is at least one row per chip;
    TPUBatchScheduler._dispatch falls back to the single chip — counted
    in `sharded_solve_fallbacks` — otherwise).

    Constraint count state ([C/T, Z]) is small and kept replicated: each
    shard scatter-builds counts from its node shard, a psum replicates
    them, and per-placement updates are broadcast from the winning
    shard.  Gang all-or-nothing (n_groups) runs the shared post-pass
    with per-shard ownership masking."""
    if features is None:
        features = features_of(snapshot)
    if topo_z is None:
        topo_z = required_topo_z(snapshot)
    parts = jax.tree.map(jnp.asarray, tuple(snapshot))
    _check_divisible(parts[0].allocatable.shape[0], mesh)

    rep = P()
    slice_specs = (
        {
            "frag_score": rep, "carveouts": rep,
            "contiguous_gangs": rep, "carveout_fallbacks": rep,
        }
        if features.slices
        else {}
    )
    out_specs = SolveResult(
        assignment=rep, scores=rep, feasible_counts=rep,
        cluster=CLUSTER_SPECS, reasons=rep, **slice_specs,
    )

    if statics is None:

        @partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=_snapshot_in_specs(parts),
            out_specs=out_specs,
            check_vma=False,
        )
        def run(cl, pods, sel, pref, spread, terms, prefpod, images):
            local = Snapshot(
                cl, pods, sel, pref, spread, terms, prefpod, images
            )
            return greedy_assign(
                local, cfg, topo_z=topo_z, features=features,
                n_groups=n_groups, axis_name=AXIS,
            )

        return run(*parts)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=_snapshot_in_specs(parts) + (STATICS_SPECS,),
        out_specs=out_specs,
        check_vma=False,
    )
    def run_warm(cl, pods, sel, pref, spread, terms, prefpod, images, st):
        local = Snapshot(cl, pods, sel, pref, spread, terms, prefpod, images)
        return greedy_assign(
            local, cfg, topo_z=topo_z, features=features,
            n_groups=n_groups, axis_name=AXIS, statics=st,
        )

    return run_warm(*parts, jax.tree.map(jnp.asarray, statics))


def sharded_wavefront_assign(
    snapshot: Snapshot,
    wave_members,
    mesh: Mesh,
    cfg: ScoreConfig = DEFAULT_SCORE_CONFIG,
    topo_z: Optional[int] = None,
    features: Optional[FeatureFlags] = None,
    n_groups: int = 0,
    statics: Optional[ClassStatics] = None,
) -> SolveResult:
    """wavefront_assign with the node axis sharded over `mesh` — the
    production mesh route for large greedy batches: one [K, N]
    evaluation a wave, on all chips in parallel, and a light step a
    member (a one-member wave is the scan's own step).

    The wave plan stays a replicated host-side device argument
    (plan_waves — pod-space only), the batched [K, N] evaluation runs
    per shard, the top-(K+1) candidate lists merge through one
    all_gather per wave, and the O(K) mini-scan corrections are computed
    on psum-replicated picked rows so every shard reaches the same
    choice without per-pod elections (see wavefront_assign's axis_name
    docstring).  Placements — and the serialized-wave / fit-flip
    fallback counters — are bit-identical to the single-chip wavefront,
    which is itself scan-identical."""
    if features is None:
        features = features_of(snapshot)
    if topo_z is None:
        topo_z = required_topo_z(snapshot)
    parts = jax.tree.map(jnp.asarray, tuple(snapshot))
    _check_divisible(parts[0].allocatable.shape[0], mesh)
    members = jnp.asarray(wave_members, jnp.int32)

    rep = P()
    out_specs = SolveResult(
        assignment=rep, scores=rep, feasible_counts=rep,
        cluster=CLUSTER_SPECS, reasons=rep, wave_count=rep,
        wave_fallbacks=rep, wave_steps=rep,
    )

    if statics is None:

        @partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=_snapshot_in_specs(parts) + (rep,),
            out_specs=out_specs,
            check_vma=False,
        )
        def run(cl, pods, sel, pref, spread, terms, prefpod, images, mem):
            local = Snapshot(
                cl, pods, sel, pref, spread, terms, prefpod, images
            )
            return wavefront_assign(
                local, mem, cfg, topo_z=topo_z, features=features,
                n_groups=n_groups, axis_name=AXIS,
            )

        return run(*parts, members)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=_snapshot_in_specs(parts) + (rep, STATICS_SPECS),
        out_specs=out_specs,
        check_vma=False,
    )
    def run_warm(cl, pods, sel, pref, spread, terms, prefpod, images, mem, st):
        local = Snapshot(cl, pods, sel, pref, spread, terms, prefpod, images)
        return wavefront_assign(
            local, mem, cfg, topo_z=topo_z, features=features,
            n_groups=n_groups, axis_name=AXIS, statics=st,
        )

    return run_warm(*parts, members, jax.tree.map(jnp.asarray, statics))


def sharded_auction_assign(
    snapshot: Snapshot,
    mesh: Mesh,
    cfg: ScoreConfig = DEFAULT_SCORE_CONFIG,
    n_groups: int = 0,
    tie_seed: int = 0,
    max_rounds: int = 64,
    features: Optional[FeatureFlags] = None,
    topo_z=None,
    tie_k: Optional[int] = None,
) -> AuctionResult:
    """auction_assign with the node axis sharded over `mesh` — the
    multi-chip joint solve (the north-star gang-burst config at scales
    one chip's HBM can't hold).

    One implementation, two layouts: this wrapper only sets up
    shard_map specs and calls ops.auction.auction_assign(axis_name=...)
    — pod-space state is replicated, node-space state sharded, and the
    boundary crossings are ownership-masked psums, a pmax/pmin election,
    and an all_gather tie-set merge (see auction_assign's docstring).
    Placements are bit-identical to the single-chip auction.
    """
    if features is None:
        features = features_of(snapshot)
    if not auction_features_ok(features):
        raise ValueError(
            "auction does not cover in-batch host ports or "
            "affinity-direction inter-pod terms; route through "
            "sharded_greedy_assign"
        )
    if topo_z is None:
        topo_z = required_topo_z_split(snapshot)
    if tie_k is None:
        tie_k = default_tie_k(snapshot)
    parts = jax.tree.map(jnp.asarray, tuple(snapshot))
    n = parts[0].allocatable.shape[0]
    _check_divisible(n, mesh)
    # tie_k bounds the GLOBAL tie list; each shard's local top_k clamps
    # to its shard size inside auction_assign and the all_gather merge
    # restores the global length
    tie_k = min(tie_k, n)

    rep = P()
    out_specs = AuctionResult(
        assignment=rep, scores=rep, rounds=rep, gang_dropped=rep,
        cluster=CLUSTER_SPECS, reasons=rep,
        debug_sp_counts=P(None, AXIS) if features.spread else None,
    )

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=_snapshot_in_specs(parts),
        out_specs=out_specs,
        check_vma=False,
    )
    def run(cl, pods, sel, pref, spread, terms, prefpod, images):
        local = Snapshot(cl, pods, sel, pref, spread, terms, prefpod, images)
        return auction_assign(
            local, cfg, n_groups=n_groups, tie_seed=tie_seed,
            max_rounds=max_rounds, features=features, topo_z=topo_z,
            tie_k=tie_k, axis_name=AXIS,
        )

    return run(*parts)


# -- jitted wrappers ---------------------------------------------------------
#
# Mirrors of ops.assign's *_jit closures for the mesh layout: one
# executable per (shape bucket, statics, MESH SHAPE).  Every dispatch
# reports to the recompile-discipline tracker (analysis/retrace.py) with
# the mesh shape folded into the signature — a mesh-mode batch must
# never silently compile a fresh executable in steady state.  `.jitted`
# exposes the raw jit for the prewarm pool's AOT lower().compile().


def sharded_greedy_jit(mesh: Mesh, cfg: ScoreConfig = DEFAULT_SCORE_CONFIG):
    mesh_sig = mesh_signature(mesh)

    @partial(jax.jit, static_argnums=(1, 2, 3))
    def run(
        snapshot: Snapshot, topo_z: int, features: FeatureFlags,
        n_groups: int,
    ) -> SolveResult:
        return sharded_greedy_assign(
            snapshot, mesh, cfg, topo_z=topo_z, features=features,
            n_groups=n_groups,
        )

    @partial(jax.jit, static_argnums=(2, 3, 4))
    def run_warm(
        snapshot: Snapshot, statics, topo_z: int, features: FeatureFlags,
        n_groups: int,
    ) -> SolveResult:
        return sharded_greedy_assign(
            snapshot, mesh, cfg, topo_z=topo_z, features=features,
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
            topo_z = (
                required_topo_z(snapshot) if needs_topo(features) else 1
            )
        if n_groups is None:
            n_groups = num_groups(snapshot)
        if n_groups > 0:
            from ..utils.vocab import pad_dim

            n_groups = pad_dim(n_groups, 1)
        if statics is not None:
            out = run_warm(snapshot, statics, topo_z, features, n_groups)
            retrace.note(
                "greedy-sharded-warm", run_warm,
                lambda: retrace.signature(
                    (snapshot, statics),
                    (topo_z, features, n_groups, mesh_sig),
                ),
            )
            return out
        out = run(snapshot, topo_z, features, n_groups)
        retrace.note(
            "greedy-sharded", run,
            lambda: retrace.signature(
                snapshot, (topo_z, features, n_groups, mesh_sig)
            ),
        )
        return out

    call.jitted = run  # raw jit, for AOT prewarm (lower().compile())
    call.jitted_warm = run_warm
    return call


def sharded_wavefront_jit(mesh: Mesh, cfg: ScoreConfig = DEFAULT_SCORE_CONFIG):
    """Jitted sharded wavefront: one executable per (shape bucket,
    topo_z, features, n_groups, wave shape, mesh shape).  The wave plan
    stays a device argument so repartitions reuse the executable."""
    mesh_sig = mesh_signature(mesh)

    @partial(jax.jit, static_argnums=(2, 3, 4))
    def run(
        snapshot: Snapshot, wave_members, topo_z: int,
        features: FeatureFlags, n_groups: int,
    ) -> SolveResult:
        return sharded_wavefront_assign(
            snapshot, wave_members, mesh, cfg, topo_z=topo_z,
            features=features, n_groups=n_groups,
        )

    @partial(jax.jit, static_argnums=(3, 4, 5))
    def run_warm(
        snapshot: Snapshot, wave_members, statics, topo_z: int,
        features: FeatureFlags, n_groups: int,
    ) -> SolveResult:
        return sharded_wavefront_assign(
            snapshot, wave_members, mesh, cfg, topo_z=topo_z,
            features=features, n_groups=n_groups, statics=statics,
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
            topo_z = (
                required_topo_z(snapshot) if needs_topo(features) else 1
            )
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
                "wavefront-sharded-warm", run_warm,
                lambda: retrace.signature(
                    (snapshot, members, statics),
                    (topo_z, features, n_groups, mesh_sig),
                ),
            )
            return out
        out = run(snapshot, members, topo_z, features, n_groups)
        retrace.note(
            "wavefront-sharded", run,
            lambda: retrace.signature(
                (snapshot, members), (topo_z, features, n_groups, mesh_sig)
            ),
        )
        return out

    call.jitted = run  # raw jit, for AOT prewarm (lower().compile())
    call.jitted_warm = run_warm
    return call


def sharded_auction_jit(mesh: Mesh, cfg: ScoreConfig = DEFAULT_SCORE_CONFIG):
    mesh_sig = mesh_signature(mesh)

    @partial(jax.jit, static_argnums=(1, 2, 3, 4))
    def run(snapshot, n_groups, features, topo_z, tie_k):
        return sharded_auction_assign(
            snapshot, mesh, cfg, n_groups=n_groups, features=features,
            topo_z=topo_z, tie_k=tie_k,
        )

    def call(
        snapshot: Snapshot,
        n_groups: Optional[int] = None,
        features: Optional[FeatureFlags] = None,
        topo_z=None,
        tie_k: Optional[int] = None,
    ) -> AuctionResult:
        if features is None:
            features = features_of(snapshot)
        if n_groups is None:
            n_groups = num_groups(snapshot)
        if topo_z is None:
            topo_z = required_topo_z_split(snapshot)
        if tie_k is None:
            tie_k = default_tie_k(snapshot)
        out = run(snapshot, n_groups, features, topo_z, tie_k)
        retrace.note(
            "auction-sharded", run,
            lambda: retrace.signature(
                snapshot, (n_groups, features, topo_z, tie_k, mesh_sig)
            ),
        )
        return out

    call.jitted = run  # raw jit, for AOT prewarm (lower().compile())
    return call


# -- pod-axis sharding -------------------------------------------------------
#
# The node axis has been elastic since the mesh wrappers above; the POD
# axis is the other long dimension of a 12k+ pods/s burst, and three
# kernels are wide on it: the wavefront's per-wave [K, N] evaluation
# (K members per wave), and the PostFilter pass's [P, N] batched
# dry-run / static-feasibility sweeps.  These twins shard THAT axis:
# node tensors stay replicated (they fit — the node mesh exists for the
# opposite regime), each device evaluates its contiguous pod/member
# block, and the only boundary crossing is one all_gather of the
# per-pod result rows.  Placements are bit-identical to the
# single-shard kernels: the wavefront runs its top-k/mini-scan math
# replicated after the gather (see wavefront_assign's pod_axis_name
# docstring), and the preemption kernels are pod-row independent, so a
# row block computed locally IS the global row slice.

POD_AXIS = "pods"


def make_pod_mesh(n_devices: Optional[int] = None, devices=None) -> Mesh:
    if devices is None:
        devices = jax.devices()[: n_devices or len(jax.devices())]
    return Mesh(devices, (POD_AXIS,))


def _check_divisible_pods(p: int, mesh: Mesh, what: str) -> None:
    n_dev = mesh.devices.size
    if p % n_dev:
        raise ValueError(
            f"{what} {p} not divisible by pod-mesh size {n_dev}"
        )


def pad_wave_columns(wave_members, mesh: Mesh) -> np.ndarray:
    """Pad the wave plan's member axis with -1 columns to a multiple of
    the pod-mesh size.  -1 members are the same inert pads plan_waves
    already emits for ragged waves — masked out of every eval, dropped
    by the out-of-bounds final scatter — so padded plans place
    identically to the originals."""
    members = np.asarray(wave_members, np.int32)
    d = mesh.devices.size
    pad = (-members.shape[1]) % d
    if pad:
        members = np.concatenate(
            [members, np.full((members.shape[0], pad), -1, np.int32)],
            axis=1,
        )
    return members


def podsharded_wavefront_assign(
    snapshot: Snapshot,
    wave_members,
    mesh: Mesh,
    cfg: ScoreConfig = DEFAULT_SCORE_CONFIG,
    topo_z: Optional[int] = None,
    features: Optional[FeatureFlags] = None,
    n_groups: int = 0,
    statics: Optional[ClassStatics] = None,
) -> SolveResult:
    """wavefront_assign with the WAVE-MEMBER axis sharded over `mesh` —
    the twin of sharded_wavefront_assign for the wide-batch/modest-node
    regime, where waves are K-wide but every chip can hold the full
    cluster: each device evaluates K/D members per wave against the
    replicated node tables, one all_gather per wave rebuilds the [K, N]
    score block, and the candidate merge / wave-safety / mini-scan math
    runs replicated-identically everywhere (no elections, node offset
    0).  Pads the member axis with inert -1 columns when K is not
    divisible by the mesh size.  Placements are bit-identical to the
    single-chip wavefront."""
    if features is None:
        features = features_of(snapshot)
    if topo_z is None:
        topo_z = required_topo_z(snapshot)
    parts = jax.tree.map(jnp.asarray, tuple(snapshot))
    # pad with jnp so the wrapper also traces under the jitted dispatch
    # (the K axis is static, so the pad width is a Python int either way)
    members = jnp.asarray(wave_members, jnp.int32)
    pad = (-members.shape[1]) % mesh.devices.size
    if pad:
        members = jnp.concatenate(
            [
                members,
                jnp.full((members.shape[0], pad), -1, jnp.int32),
            ],
            axis=1,
        )

    rep = P()
    rep_parts = tuple(jax.tree.map(lambda _: rep, part) for part in parts)
    rep_cluster = ClusterTensors(*([rep] * len(CLUSTER_SPECS)))
    out_specs = SolveResult(
        assignment=rep, scores=rep, feasible_counts=rep,
        cluster=rep_cluster, reasons=rep, wave_count=rep,
        wave_fallbacks=rep, wave_steps=rep,
    )

    if statics is None:

        @partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=rep_parts + (P(None, POD_AXIS),),
            out_specs=out_specs,
            check_vma=False,
        )
        def run(cl, pods, sel, pref, spread, terms, prefpod, images, mem):
            local = Snapshot(
                cl, pods, sel, pref, spread, terms, prefpod, images
            )
            return wavefront_assign(
                local, mem, cfg, topo_z=topo_z, features=features,
                n_groups=n_groups, pod_axis_name=POD_AXIS,
            )

        return run(*parts, members)

    statics_rep = ClassStatics(sfeas=rep, aff=rep, taint=rep)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=rep_parts + (P(None, POD_AXIS), statics_rep),
        out_specs=out_specs,
        check_vma=False,
    )
    def run_warm(cl, pods, sel, pref, spread, terms, prefpod, images, mem, st):
        local = Snapshot(cl, pods, sel, pref, spread, terms, prefpod, images)
        return wavefront_assign(
            local, mem, cfg, topo_z=topo_z, features=features,
            n_groups=n_groups, pod_axis_name=POD_AXIS, statics=st,
        )

    return run_warm(*parts, members, jax.tree.map(jnp.asarray, statics))


def podsharded_wavefront_jit(
    mesh: Mesh, cfg: ScoreConfig = DEFAULT_SCORE_CONFIG
):
    """Jitted pod-sharded wavefront: one executable per (shape bucket,
    topo_z, features, n_groups, wave shape, mesh shape), same discipline
    as sharded_wavefront_jit."""
    mesh_sig = mesh_signature(mesh)

    @partial(jax.jit, static_argnums=(2, 3, 4))
    def run(
        snapshot: Snapshot, wave_members, topo_z: int,
        features: FeatureFlags, n_groups: int,
    ) -> SolveResult:
        return podsharded_wavefront_assign(
            snapshot, wave_members, mesh, cfg, topo_z=topo_z,
            features=features, n_groups=n_groups,
        )

    @partial(jax.jit, static_argnums=(3, 4, 5))
    def run_warm(
        snapshot: Snapshot, wave_members, statics, topo_z: int,
        features: FeatureFlags, n_groups: int,
    ) -> SolveResult:
        return podsharded_wavefront_assign(
            snapshot, wave_members, mesh, cfg, topo_z=topo_z,
            features=features, n_groups=n_groups, statics=statics,
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
            topo_z = (
                required_topo_z(snapshot) if needs_topo(features) else 1
            )
        if n_groups is None:
            n_groups = num_groups(snapshot)
        if n_groups > 0:
            from ..utils.vocab import pad_dim

            n_groups = pad_dim(n_groups, 1)
        if wave_members is None:
            wave_members = plan_waves(
                snapshot, features=features, wave_cap=wave_cap
            ).members
        members = jnp.asarray(pad_wave_columns(wave_members, mesh))
        if statics is not None:
            out = run_warm(snapshot, members, statics, topo_z, features,
                           n_groups)
            retrace.note(
                "wavefront-podsharded-warm", run_warm,
                lambda: retrace.signature(
                    (snapshot, members, statics),
                    (topo_z, features, n_groups, mesh_sig),
                ),
            )
            return out
        out = run(snapshot, members, topo_z, features, n_groups)
        retrace.note(
            "wavefront-podsharded", run,
            lambda: retrace.signature(
                (snapshot, members), (topo_z, features, n_groups, mesh_sig)
            ),
        )
        return out

    call.jitted = run  # raw jit, for AOT prewarm (lower().compile())
    call.jitted_warm = run_warm
    return call


def sharded_batched_dry_run(
    batch: PreemptionBatch, mesh: Mesh
) -> BatchDryRunResult:
    """batched_dry_run with the PREEMPTOR axis sharded over `mesh`: the
    per-node victim tensors (free/victim_req/perm/elig_len/viol) stay
    replicated — each shard redundantly recomputes the per-LEVEL
    cumulative eviction tensors, which are shared across pods anyway —
    and the [P, N, K+1] broadcast fit test, the dominant term, runs on
    P/D pod rows per device.  Every row is computed exactly as in the
    single-shard kernel (pure per-pod gathers), so the stitched [P, N]
    result is bit-identical."""
    parts = jax.tree.map(jnp.asarray, batch)
    _check_divisible_pods(
        int(parts.pods_req.shape[0]), mesh, "preemptor count"
    )

    rep = P()
    in_specs = PreemptionBatch(
        free=rep, victim_req=rep, perm=rep, elig_len=rep, viol=rep,
        pods_req=P(POD_AXIS, None), pod_level=P(POD_AXIS),
    )
    out_specs = BatchDryRunResult(
        feasible=P(POD_AXIS, None), min_k=P(POD_AXIS, None),
        viol_k=P(POD_AXIS, None),
    )

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(in_specs,),
        out_specs=out_specs,
        check_vma=False,
    )
    def run(b):
        return batched_dry_run(b)

    return run(parts)


def sharded_static_feasible_batch(
    cluster, pods, selectors, mesh: Mesh
) -> jnp.ndarray:
    """static_feasible_batch with the preemptor axis sharded: the
    PodBatch stays replicated (pod views gather class/spec rows from
    shared tables, so slicing the structure itself would tear them) and
    each device evaluates its contiguous index block, axis_index-offset
    into the global pod range.  Output rows are bit-identical to the
    single-shard sweep."""
    from ..ops.filters import (
        pod_view,
        selector_match,
        static_feasible_for_pod,
    )

    p = int(pods.req.shape[0])
    _check_divisible_pods(p, mesh, "preemptor count")
    p_local = p // mesh.devices.size

    rep = P()
    in_specs = tuple(
        jax.tree.map(lambda _: rep, part)
        for part in (cluster, pods, selectors)
    )

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=P(POD_AXIS, None),
        check_vma=False,
    )
    def run(cl, pd, sel):
        sel_mask = selector_match(cl, sel)
        i0 = jax.lax.axis_index(POD_AXIS) * p_local

        def one(i):
            return static_feasible_for_pod(cl, pod_view(pd, i), sel_mask)

        return jax.vmap(one)(i0 + jnp.arange(p_local, dtype=jnp.int32))

    return run(
        jax.tree.map(jnp.asarray, cluster),
        jax.tree.map(jnp.asarray, pods),
        jax.tree.map(jnp.asarray, selectors),
    )
