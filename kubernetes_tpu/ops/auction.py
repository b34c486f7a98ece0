"""Joint batched assignment — the auction-style parallel solve.

The greedy scan (ops.assign) preserves the reference's one-pod-at-a-time
semantics (schedule_one.go:66-133) but is inherently sequential: P scan
steps.  For large pending bursts — the gang/coscheduling config in
BASELINE — this module solves the batch *jointly* in rounds:

  1. filtering + scoring runs once per pod *class* (pods with
     byte-identical specs — schema.PodBatch.class_id — see identical
     masks and score rows, so the pass is [C, N] with C typically tens,
     not [P, N]); each class's max-score tie nodes are enumerated by
     cumsum-rank with a per-round hashed rotation (the joint analogue of
     the reference's uniform selectHost sampling, schedule_one.go:
     867-905) and the class's j-th pod bids the j-th tie node — distinct
     bids while ties last, so uniform clusters commit in bulk;
  2. each node accepts its bidders in solve order (priority, then batch
     index — queuesort/priority_sort.go:52) while they fit its remaining
     capacity, computed with one sort + segmented cumulative sum — no
     host round-trips;
  3. accepted pods commit (their resources leave the pool); rejected
     pods re-bid against the updated pool next round.

Every round in which an unplaced pod still has a feasible node commits at
least one pod (the first bidder in solve order on each node always fits),
so the loop terminates; contention bursts converge in a handful of
rounds because acceptance is per-node-parallel.

Gang semantics (all-or-nothing groups, api.PodSpec.scheduling_group):
after the rounds converge, groups with any unplaced member release all
their placements in one masked subtract — the coscheduling-PodGroup
pattern (no in-tree reference counterpart; the out-of-tree coscheduling
plugin's Permit phase is the analogue).

Constraint coverage: the static families + resources (NodeResourcesFit,
NodeName, NodeUnschedulable, TaintToleration, NodeAffinity, NodePorts
against bound pods), PLUS the two coupled families the round structure
can repair:

  * PodTopologySpread (hard + soft): filtering/scoring reads the round's
    counts; after acceptance a per-(constraint, topology value) prefix
    cap releases over-admitted pods (rank r kept iff
    count + r + 1 - globalMin <= maxSkew, the filtering.go:336 criterion
    applied cumulatively), then counts commit from net accepts.
  * InterPodAntiAffinity (required, both directions incl. existing-pods
    anti-affinity): the filter handles bound state; within-round
    conflicts (a carrier and a matcher of one term accepted into one
    topology domain) release everything after the first accepted pod of
    that (term, value) group.

Affinity-direction terms (co-location + the first-pod escape) and
in-batch host-port claims still route to the greedy scan
(`auction_features_ok`): concurrent co-location bids can deadlock-split
groups, which is exactly what the reference serializes for.

Placements released by repair re-bid next round against updated counts;
pods still unplaced at max_rounds return -1 and the host scheduler parks
and retries them — system-level behaviour is unchanged, only the batch
boundary moves.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis import retrace
from ..analysis.markers import hot_path
from .assign import (
    NEG_INF,
    REASON_GANG,
    REASON_INTERPOD,
    REASON_NONE,
    REASON_PORTS,
    REASON_RESOURCES,
    REASON_SPREAD,
    REASON_STATIC,
    FeatureFlags,
    class_statics,
    features_of,
    required_topo_z_split,
    solve_order,
)
from .filters import fits_resources, pod_view, preferred_match, selector_match
from .interpod import (
    _idx_to_bits,
    _pack_bits_t,
    _unpack_bits_t,
    interpod_filter,
    prep_terms,
)
from .schema import ClusterTensors, Snapshot, num_groups
from .scores import (
    DEFAULT_SCORE_CONFIG,
    ScoreConfig,
    combine_scores,
    resource_score_parts,
)
from .topology import prep_spread, spread_filter, spread_score

_BIG_I = jnp.int32(2**30)


class AuctionResult(NamedTuple):
    assignment: jnp.ndarray   # i32[P]: node index, -1 unschedulable/dropped
    scores: jnp.ndarray       # f32[P]: accepted bid's score (-inf if none)
    rounds: jnp.ndarray       # i32[]: bidding rounds executed
    gang_dropped: jnp.ndarray  # bool[P]: placed but released with its gang
    cluster: ClusterTensors   # post-solve cluster
    reasons: jnp.ndarray = None  # i32[P]: assign.REASON_* for unplaced pods
    debug_sp_counts: jnp.ndarray = None  # f32[C, N] final spread counts (debug)


def auction_features_ok(features: FeatureFlags) -> bool:
    """True when the joint solve covers this batch's constraint families.
    Slice carve-outs (features.slices) are sequential-by-construction —
    the anchor member's placement defines every later member's cuboid —
    so shaped batches stay on the greedy scan."""
    return not (features.ports or features.interpod_aff or features.slices)


def default_tie_k(snapshot: Snapshot) -> int:  # graftlint: disable=purity -- host-side prep on the pre-transfer snapshot
    """Tie nodes enumerated per class per round: enough for a class that
    holds every valid pod of the batch to bid distinct nodes (a burst of
    identical pods would otherwise cram onto tie_k nodes instead of
    spreading over the tie set), power-of-two bucketed, bounded by the
    node axis.  tie_k is static, so part of the executable's key: sized
    by the batch's pods, not by its largest class, it follows the pod
    bucket whatever request shapes fill it.  A pod reads only the first
    `its class's size` slots of the list and top_k's prefix does not
    move with k, so the larger size changes no bid."""
    from ..utils.vocab import pad_dim

    pods = int(np.count_nonzero(np.asarray(snapshot.pods.valid)))
    return min(pad_dim(max(pods, 64), 1), snapshot.cluster.allocatable.shape[0])


@hot_path
def auction_assign(
    snapshot: Snapshot,
    cfg: ScoreConfig = DEFAULT_SCORE_CONFIG,
    n_groups: int = 0,
    tie_seed: int = 0,
    max_rounds: int = 64,
    features: Optional[FeatureFlags] = None,
    topo_z: Optional[Tuple[int, int]] = None,
    tie_k: int = 128,
    axis_name: Optional[str] = None,
) -> AuctionResult:
    """Jointly assign the pending batch: rounds of (parallel bid →
    per-node prefix acceptance → constraint repair).  n_groups:
    gang-group count (static; 0 disables the gang post-pass).  topo_z:
    (z_spread, z_terms) per-family padded value capacities (static;
    auto-derived outside jit — required_topo_z_split).  tie_k (static):
    tie nodes enumerated per class per round; classes with more active
    pods than surviving tie nodes wrap and resolve through repair.

    Relative to greedy, concurrent bids don't see each other's score
    impact within a round — acceptance order still respects priority,
    capacity is never oversubscribed, and the spread / anti-affinity
    repairs keep every committed placement constraint-valid.  Where no
    two pods contend, round-1 bids equal the greedy picks (same
    filter/score kernels).

    axis_name: mesh axis when called under shard_map with the NODE axis
    sharded (parallel.sharded.sharded_auction_assign).  One
    implementation serves both layouts: pod-space state (bids,
    acceptance, repair ranks, gang bookkeeping) is replicated; node-space
    state (capacity, spread counts, interpod bits) stays sharded, with
    ownership-masked psum gathers at the pod<->node boundary, pmax/pmin
    for score normalization and election, and an all_gather merge of the
    per-shard tie sets.  Placements are bit-identical to the single-chip
    solve (top_k ties resolve to the lowest global node index in both
    layouts).
    """
    if features is None:
        features = features_of(snapshot)
    if not auction_features_ok(features):
        raise ValueError(
            "auction_assign does not cover in-batch host ports or "
            f"affinity-direction inter-pod terms; route batches with "
            f"{features} through greedy_assign"
        )
    if topo_z is None:
        topo_z = required_topo_z_split(snapshot)
    z_spread, z_terms = topo_z
    if axis_name is None:
        tie_k = min(tie_k, snapshot.cluster.allocatable.shape[0])
    # sharded: the wrapper guarantees tie_k <= GLOBAL node count; the
    # local shape here is one shard, so clamping against it would
    # silently shrink the tie set (each shard's top_k clamps to its
    # local size below; the merge restores the global tie_k)
    (cluster, pods, sel, pref, spread, terms, prefpod, images) = jax.tree.map(
        jnp.asarray, tuple(snapshot)
    )
    n = cluster.allocatable.shape[0]      # LOCAL node count under shard_map
    p = pods.req.shape[0]

    # -- shard-layout helpers (identity when axis_name is None) -----------
    if axis_name is not None:
        n_shards = jax.lax.psum(1, axis_name)
        offset = jax.lax.axis_index(axis_name) * n
        n_total = n * n_shards
    else:
        offset = 0
        n_total = n

    def _pmax(x):
        return x if axis_name is None else jax.lax.pmax(x, axis_name)

    def _pmin(x):
        return x if axis_name is None else jax.lax.pmin(x, axis_name)

    def _psum(x):
        return x if axis_name is None else jax.lax.psum(x, axis_name)

    def _any(x):
        if axis_name is None:
            return x.any()
        return jax.lax.pmax(x.any().astype(jnp.int32), axis_name) > 0

    def node_rows(mat, idx):
        """Gather rows of a node-axis tensor at GLOBAL node ids [P].
        Sharded: the owning shard contributes, psum replicates."""
        if axis_name is None:
            return mat[idx]
        own = (idx >= offset) & (idx < offset + n)
        loc = jnp.clip(idx - offset, 0, n - 1)
        vals = mat[loc]
        mask = own.reshape(own.shape + (1,) * (vals.ndim - own.ndim))
        if vals.dtype == jnp.bool_:
            out = jax.lax.psum(
                jnp.where(mask, vals, False).astype(jnp.int32), axis_name
            )
            return out > 0
        return jax.lax.psum(
            jnp.where(mask, vals, jnp.zeros_like(vals)), axis_name
        )

    def node_cell_gather(mat, rows, idx):
        """mat[rows[p], idx[p]] where mat is [R, N]-sharded on axis 1 and
        idx holds GLOBAL node ids."""
        if axis_name is None:
            return mat[rows, idx]
        own = (idx >= offset) & (idx < offset + n)
        loc = jnp.clip(idx - offset, 0, n - 1)
        return jax.lax.psum(
            jnp.where(own, mat[rows, loc], jnp.zeros((), mat.dtype)),
            axis_name,
        )

    def scatter_add_rows(dst, idx, vals, mask):
        """dst.at[idx].add(vals * mask) with idx GLOBAL; sharded, only
        the owning shard writes its local rows."""
        if axis_name is None:
            return dst.at[idx].add(vals * mask[:, None])
        own = mask & (idx >= offset) & (idx < offset + n)
        loc = jnp.clip(idx - offset, 0, n - 1)
        return dst.at[loc].add(vals * own[:, None].astype(vals.dtype))
    sel_mask = selector_match(cluster, sel)
    pref_mask = preferred_match(cluster, pref)
    # Factorized class axes (PodBatch docstring): heavy per-row kernels
    # run on the small spec / constraint factors; the joint axis only
    # gathers + combines.  sfeas/aff/taint rows are identical across
    # joint classes sharing a spec class, so computing them on the spec
    # axis is exact, not an approximation.
    s_reps = jnp.clip(pods.spec_rep, 0, p - 1)      # [Cs]
    k_reps = jnp.clip(pods.cons_rep, 0, p - 1)      # [Cc]
    c_dim = pods.class_rep.shape[0]
    cs_dim = pods.spec_rep.shape[0]
    cc_dim = pods.cons_rep.shape[0]
    jspec = jnp.clip(pods.joint_spec, 0, cs_dim - 1)  # [C]
    jcons = jnp.clip(pods.joint_cons, 0, cc_dim - 1)  # [C]
    sfeas_s, aff_s, taint_s = class_statics(
        cluster, pods, sel_mask, pref_mask, reps=s_reps
    )
    reps = jnp.clip(pods.class_rep, 0, p - 1)
    pref_raw_k = img_k = None
    if features.interpod_pref:
        # raw preferred-interpod rows per CONSTRAINT class; the joint
        # combine normalizes each against its spec class's static
        # feasibility (static_extra's contract — the normalization set
        # is placement-independent)
        from .interpod import prep_pref_pod, pref_pod_raw

        pp = prep_pref_pod(
            cluster, prefpod, z_terms, axis_name=axis_name,
            has_bound=features.bound_pref,
        )
        pref_raw_k = jax.vmap(lambda rep: pref_pod_raw(pp, prefpod, rep))(
            k_reps
        )
    if features.images:
        from .scores import image_locality_score

        img_k = jax.vmap(
            lambda rep: image_locality_score(
                cluster, images, rep, axis_name=axis_name
            )
        )(k_reps)

    def joint_extra(s, k):
        """Already-weighted extra score row for joint class (s, k), or
        None when neither family is active (matches static_extra)."""
        if pref_raw_k is None and img_k is None:
            return None
        from .scores import normalize_minmax

        total = jnp.zeros(n, jnp.float32)
        if pref_raw_k is not None:
            total = total + cfg.interpod_weight * normalize_minmax(
                pref_raw_k[k], sfeas_s[s], axis_name=axis_name
            )
        if img_k is not None:
            total = total + cfg.image_weight * img_k[k]
        return total

    order = solve_order(pods)
    # solve_pos[i] = pod i's rank in solve order (repair keeps prefixes
    # in this order, matching acceptance's priority discipline)
    solve_pos = jnp.zeros(p, jnp.int32).at[order].set(
        jnp.arange(p, dtype=jnp.int32)
    )

    sp0 = (
        prep_spread(
            cluster, sel_mask, spread, z_spread, axis_name=axis_name,
            has_bound=features.bound_spread,
        )
        if features.spread
        else None
    )
    tm0 = (
        prep_terms(
            cluster, terms, z_terms, axis_name=axis_name,
            slots=features.term_slots, has_bound=features.bound_terms,
        )
        if features.interpod
        else None
    )
    if features.interpod:
        t_dim = terms.valid.shape[0]
        # dense [P, T] involvement tables for the within-round repair
        mi_dense = (
            _unpack_bits_t(terms.matches_incoming, t_dim)
            & terms.valid[None, :]
        )
        anti_dense = _idx_to_bits(terms.anti_idx, t_dim) & terms.valid[None, :]
        slot_of_t = terms.slot                                    # [T]

    seed_c = jnp.uint32(tie_seed * 2 + 1)
    arange_p = jnp.arange(p, dtype=jnp.int32)

    def bids(requested, nonzero, assigned, rnd, sp_counts, tm_bits):
        # Pods of one class (byte-identical spec incl. requests) see
        # identical filter masks and score rows against the current pool,
        # so filtering + scoring runs once per *class* — and the class
        # axis itself factorizes: resource fit + fit/balanced score rows
        # per SPEC class ([Cs, N], a handful of rows), spread/inter-pod
        # filter rows per CONSTRAINT class ([Cc, N], one per service
        # shape), with the joint [C, N] pass reduced to gathers, the
        # normalize-and-weight combine, and top_k.  Within a round the
        # class's max-score tie set is fixed, so bidding needs no per-pod
        # (P x N) pass either: rank the tie nodes once per class in
        # counter-hash order (uniform, like the reference's selectHost
        # sampling schedule_one.go:867) and hand the class's j-th active
        # pod the j-th tie node.  Pods of a class thus bid *distinct*
        # nodes while ties last — fewer conflicts than independent
        # sampling — and the whole per-pod step is O(P) gathers.
        cl = cluster._replace(requested=requested, nonzero_requested=nonzero)
        sp = sp0._replace(counts_node=sp_counts) if features.spread else None
        tm = (
            tm0._replace(
                present_bits=tm_bits[0], blocked_bits=tm_bits[1],
                global_any=tm_bits[2],
            )
            if features.interpod
            else None
        )

        def per_spec(rep):
            pod = pod_view(pods, rep)
            fit, bal = resource_score_parts(cl, pod, cfg)
            return fits_resources(cl, pod), fit, bal

        fits_s, fit_s, bal_s = jax.vmap(per_spec)(s_reps)   # [Cs, N]
        spf_k = (
            jax.vmap(
                lambda rep: spread_filter(
                    sp, spread, rep, axis_name=axis_name
                )
            )(k_reps)
            if features.spread
            else None
        )
        ipf_k = (
            jax.vmap(lambda rep: interpod_filter(tm, terms, rep))(k_reps)
            if features.interpod
            else None
        )

        def per_class(c, rep):
            s, k = jspec[c], jcons[c]
            feas = sfeas_s[s] & fits_s[s]
            if features.spread:
                feas = feas & spf_k[k]
            if features.interpod:
                feas = feas & ipf_k[k]
            sp_score = (
                spread_score(sp, spread, rep, feas, axis_name=axis_name)
                if features.soft_spread
                else None
            )
            scores = combine_scores(
                fit_s[s], bal_s[s], aff_s[s], taint_s[s], feas, cfg,
                axis_name=axis_name, spread_score=sp_score,
                extra=joint_extra(s, k),
            )
            masked = jnp.where(feas, scores, NEG_INF)
            best = _pmax(jnp.max(masked))
            tie = jnp.asarray(feas & (masked == best))
            # Tie nodes enumerated by top_k over a per-(class, round)
            # hashed node ordering: one fused top_k per class instead of
            # the earlier full-[N] inverse scatter (TPU scatters
            # serialize; at hundreds of classes the scatter dominated the
            # round).  The hash randomizes which tie nodes surface and
            # rotates every round, so re-bidding classes diversify.  The
            # hash input is the GLOBAL node id, so the tie ORDER is
            # layout-independent; sharded, each shard takes its local
            # top-k and an all_gather + re-top_k merges them (equal keys
            # resolve to the lowest global id in both layouts).
            rot = (
                (c.astype(jnp.uint32) * jnp.uint32(0x9E3779B9))
                ^ (rnd.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B))
                ^ seed_c
            ) * jnp.uint32(0x27D4EB2F)
            gids = jnp.arange(n, dtype=jnp.uint32) + jnp.uint32(1)
            if axis_name is not None:
                gids = gids + jnp.uint32(offset)
            hkey = (gids * jnp.uint32(0x9E3779B9)) ^ rot
            key = jnp.where(tie, (hkey >> 2).astype(jnp.int32), -1)
            local_k = min(tie_k, n)  # a shard holds at most n tie nodes
            _vals, topk_idx = jax.lax.top_k(key, local_k)  # i32[K_local]
            if axis_name is not None:
                topk_idx = topk_idx + offset
                vals_g = jax.lax.all_gather(_vals, axis_name)    # [D, Kl]
                idx_g = jax.lax.all_gather(topk_idx, axis_name)  # [D, Kl]
                m_vals, m_pos = jax.lax.top_k(vals_g.reshape(-1), tie_k)
                topk_idx = idx_g.reshape(-1)[m_pos]
            cnt = jnp.minimum(
                _psum(tie.sum()), tie_k
            ).astype(jnp.int32)
            return topk_idx, cnt, best

        inv_c, cnt_c, best_c = jax.vmap(per_class)(
            jnp.arange(c_dim, dtype=jnp.int32), reps
        )  # i32[C, K], i32[C], f32[C]

        # Within-class position j of each active pod, in solve order (so
        # higher-priority pods take earlier tie slots).
        cls = jnp.clip(pods.class_id, 0, c_dim - 1)
        active = (assigned < 0) & pods.valid
        actkey = jnp.where(active, cls, c_dim)
        sperm = order[jnp.argsort(actkey[order], stable=True)]
        skey = actkey[sperm]
        firstpos = jnp.searchsorted(skey, skey, side="left")
        j = jnp.zeros(p, jnp.int32).at[sperm].set(
            arange_p - firstpos.astype(jnp.int32)
        )
        cnt = cnt_c[cls]
        has = active & (best_c[cls] > NEG_INF) & (cnt > 0)
        # the per-round rotation lives in the tie hash; j indexes the
        # class's hash-ordered tie list directly
        slot = j % jnp.maximum(cnt, 1)
        bid = jnp.where(has, inv_c[cls, slot], n_total).astype(jnp.int32)
        val = jnp.where(has, best_c[cls], NEG_INF)
        return bid, val

    _BIGF = jnp.float32(1e9)

    # how many admit passes one round's spread repair runs: each pass
    # commits what fits under the current global minimum, then the next
    # pass re-evaluates the remainder against the RAISED minimum — the
    # sequential scan's continuously-rising min, approximated in k steps
    SPREAD_REPAIR_ITERS = 3

    if features.spread:
        # [N, C] row-gather layouts: axis-1 (per-column) gathers and
        # scatters of [C, P] tables serialize on TPU (~0.08 s each at
        # 16k pods); row gathers of the transposed layout are contiguous
        v_nc = sp0.v.T
        elig_nc = sp0.eligible.T
        cmax_sp = sp0.counts_node.shape[0]
        # per-slot value one-hots [Z, N]: value-space -> node-space maps
        # become small matmuls on the MXU instead of [C, N] gathers from
        # [C, Z] tables (gathers serialize: ~0.08 s per call at 16k
        # nodes; the matmul is [C, Z] @ [Z, N] with Z tiny)
        spread_onehot = {}
        for s in features.spread_slots:
            v_n = cluster.topo_ids[:, s]
            spread_onehot[s] = (
                (v_n[None, :] == jnp.arange(z_spread)[:, None])
                & (v_n >= 0)[None, :]
            ).astype(jnp.float32)                                # [Z, N]

    def _slot_sorts(topo_pt):
        """Per-slot (perm, inv, firstv) of the round's bid values —
        depends only on the bids, so it hoists out of the repair's
        admit iterations.  topo_pt: [P, TK] bid nodes' topo values
        (gathered once per round; replicated under shard_map)."""
        out = {}
        for s in features.spread_slots:
            v_p = topo_pt[:, s]
            key = jnp.where(v_p >= 0, v_p, _BIG_I)
            perm = order[jnp.argsort(key[order], stable=True)]
            skey = key[perm]
            firstv = jnp.searchsorted(skey, skey, side="left")   # [P]
            inv = jnp.zeros(p, jnp.int32).at[perm].set(arange_p)
            out[s] = (perm, inv, firstv)
        return out

    def _spread_ranks(cand, v_pc, slot_sorts):
        """rank[P, C]: among `cand` pods matching row c, this pod's
        0-based position (solve order) within its (row, value) group.
        One value-sort per spread SLOT (hoisted) + a segmented [P, C]
        cumsum with row gathers (per-row sorts serialize on TPU)."""
        act_pc = cand[:, None] & spread.pod_matches & (v_pc >= 0)  # [P, C]
        rank_pc = jnp.zeros((p, cmax_sp), jnp.int32)
        for s in features.spread_slots:
            perm, inv, firstv = slot_sorts[s]
            rows_s = spread.slot == s                            # [C]
            act_s = act_pc & rows_s[None, :]
            srt = act_s[perm].astype(jnp.int32)                  # [P, C]
            exc = jnp.cumsum(srt, axis=0) - srt                  # exclusive
            seg = exc - exc[firstv]                              # segmented
            back = seg[inv]                                      # unsort
            rank_pc = jnp.where(rows_s[None, :], back, rank_pc)
        return rank_pc

    def spread_repair(accept, nodes, sp_counts, topo_pt):
        """Keep the subset of capacity-accepted pods whose placements
        satisfy every hard constraint (rank r in its (row, value) group
        kept iff count + r + 1 - min <= maxSkew — the filtering.go:336
        criterion applied to the round's concurrent admits).  Runs
        SPREAD_REPAIR_ITERS admit passes, committing each pass's admits
        into a working copy of the counts so the global minimum rises
        WITHIN the round — without this, a round can only advance each
        constraint by maxSkew per topology value."""
        md = spread.min_domains
        kept = jnp.zeros(p, bool)
        counts_it = sp_counts
        v_pc = node_rows(v_nc, nodes)                            # [P, C]
        slot_sorts = _slot_sorts(topo_pt)
        for _ in range(SPREAD_REPAIR_ITERS):
            cand = accept & ~kept
            min_c = _pmin(jnp.min(
                jnp.where(sp0.eligible, counts_it, _BIGF), axis=-1
            ))
            min_c = jnp.where(min_c >= _BIGF, 0.0, min_c)
            min_c = jnp.where((md > 0) & (sp0.sizes < md), 0.0, min_c)
            rank_pc = _spread_ranks(cand, v_pc, slot_sorts)
            admit = cand
            for j in range(spread.pod_idx.shape[1]):
                cidx = spread.pod_idx[:, j]
                c = jnp.clip(cidx, 0, cmax_sp - 1)
                vj = v_pc[arange_p, c]
                own = cand & (cidx >= 0) & spread.hard[c] & (vj >= 0)
                cnt = node_cell_gather(counts_it, c, nodes)
                # sequential criterion: count + rank + selfMatch - min <=
                # maxSkew.  A carrier whose own labels don't match its
                # constraint's selector (selfMatch=0, legal in k8s) gets
                # one extra admit slot — releasing it at the boundary
                # would park a pod the filter just passed, forever.
                self_m = spread.pod_matches[arange_p, c].astype(jnp.float32)
                allowed = (
                    spread.max_skew[c] + min_c[c] - cnt + (1.0 - self_m)
                )
                rank = rank_pc[arange_p, c].astype(jnp.float32)
                admit = admit & ~(own & (rank >= allowed))
            kept = kept | admit
            counts_it = commit_spread(
                admit, nodes, counts_it, topo_pt, v_pc
            )
        return kept

    def interpod_repair(accept, topo_pt):
        """Release within-round anti-affinity conflicts: in each (term,
        topology value) group containing an accepted CARRIER of the term,
        only the first accepted involved pod (solve order) survives."""
        release = jnp.zeros(p, bool)
        slots_used = features.term_slots or tuple(
            range(cluster.topo_ids.shape[1])
        )
        for s in slots_used:
            v_p = topo_pt[:, s]                                  # [P]
            rel_t = slot_of_t == s                               # [T]
            inv = (mi_dense | anti_dense) & rel_t[None, :]       # [P, T]
            involved = inv & accept[:, None] & (v_p >= 0)[:, None]
            flat = (
                jnp.clip(v_p, 0, z_terms - 1)[:, None] * t_dim
                + jnp.arange(t_dim)[None, :]
            )                                                    # [P, T]
            pos = jnp.where(involved, solve_pos[:, None], _BIG_I)
            minpos = jnp.full(z_terms * t_dim, _BIG_I, jnp.int32).at[
                flat.reshape(-1)
            ].min(pos.reshape(-1))
            carrier = involved & anti_dense
            c_any = jnp.zeros(z_terms * t_dim, bool).at[
                flat.reshape(-1)
            ].max(carrier.reshape(-1))
            viol = involved & c_any[flat] & (solve_pos[:, None] > minpos[flat])
            release = release | viol.any(axis=1)
        return accept & ~release

    def commit_spread(accept, nodes, sp_counts, topo_pt, v_pc=None):
        """Fold net accepts into the node-space counts (the batched
        spread_update): every row a placed pod matches gains one on every
        node sharing the placement's topology value."""
        if v_pc is None:
            v_pc = node_rows(v_nc, nodes)                        # [P, C]
        elig_pc = node_rows(elig_nc, nodes)
        act = (
            accept[:, None] & spread.pod_matches & elig_pc & (v_pc >= 0)
        ).astype(jnp.float32)
        # Both directions ride the MXU: pod-space -> value-space counts
        # as act^T @ onehot(pod value), then value-space -> node-space
        # as adds @ onehot(node value).  The equivalent scatter-add +
        # take_along_axis each serialized at ~0.08 s per repair pass.
        # Precision.HIGHEST: spread counts are exact integers feeding the
        # exact admit criterion (count + rank + selfMatch - min <=
        # maxSkew).  Default TPU matmul precision casts to bf16, which
        # rounds counts past 256 and flips admit/release decisions.
        hi = jax.lax.Precision.HIGHEST
        adds = jnp.zeros((cmax_sp, z_spread), jnp.float32)
        zr = jnp.arange(z_spread)
        for s in features.spread_slots:
            v_p = topo_pt[:, s]                                  # [P]
            oh_pz = (
                (v_p[:, None] == zr[None, :]) & (v_p >= 0)[:, None]
            ).astype(jnp.float32)                                # [P, Z]
            rows_s = spread.slot == s                            # [C]
            act_s = act * rows_s[None, :]
            adds = adds + jnp.einsum(
                "pc,pz->cz", act_s, oh_pz, precision=hi
            )
        delta = jnp.zeros_like(sp_counts)
        for s in features.spread_slots:
            rows_s = spread.slot == s                            # [C]
            d = jnp.matmul(adds, spread_onehot[s], precision=hi)  # [C, N]
            delta = jnp.where(rows_s[:, None], d, delta)
        return sp_counts + jnp.where(sp0.v >= 0, delta, 0.0)

    def commit_terms(accept, nodes, topo_pt, present, blocked, global_any):
        """Batched interpod_update: matched terms turn present (and
        global) in each placement's topology; carried anti terms turn
        blocked there.  Scatter in value space as bools (replicated —
        built from pod-space data), then map back to LOCAL nodes and
        pack."""
        slots_used = features.term_slots or tuple(
            range(cluster.topo_ids.shape[1])
        )
        for s in slots_used:
            v_p = topo_pt[:, s]                                  # [P]
            rel_t = slot_of_t == s
            ok_p = accept & (v_p >= 0)
            vcp = jnp.clip(v_p, 0, z_terms - 1)
            mi_s = mi_dense & rel_t[None, :] & ok_p[:, None]     # [P, T]
            an_s = anti_dense & rel_t[None, :] & ok_p[:, None]
            z_mi = jnp.zeros((z_terms, t_dim), bool).at[vcp].max(mi_s)
            z_an = jnp.zeros((z_terms, t_dim), bool).at[vcp].max(an_s)
            v_n = cluster.topo_ids[:, s]                         # [N]
            vn = jnp.clip(v_n, 0, z_terms - 1)
            has = (v_n >= 0)[:, None]
            present = present | _pack_bits_t(z_mi[vn] & has)
            blocked = blocked | _pack_bits_t(z_an[vn] & has)
            global_any = global_any | _pack_bits_t(z_mi.any(axis=0))
        return present, blocked, global_any

    def body(state):
        (assigned, bid_scores, requested, nonzero, rnd, _progress,
         sp_counts, tm_present, tm_blocked, tm_global) = state
        bid, val = bids(
            requested, nonzero, assigned, rnd, sp_counts,
            (tm_present, tm_blocked, tm_global),
        )

        # Per-node prefix acceptance in solve order: pre-permute pods into
        # solve order, then a *stable* sort by bid keeps that order within
        # each node group (no composite integer key to overflow).  Bids
        # are GLOBAL node ids; pod-space state is replicated, so this
        # whole block is layout-independent except the remaining-capacity
        # gather and the requested scatter.
        perm = order[jnp.argsort(bid[order], stable=True)]
        sbid = bid[perm]
        sreq = pods.req[perm]                                   # [P, R]
        prefix = jnp.cumsum(sreq, axis=0)
        first = jnp.searchsorted(sbid, sbid, side="left")       # [P]
        within = prefix - prefix[first] + sreq[first]
        remaining = node_rows(
            cluster.allocatable - requested, jnp.clip(sbid, 0, n_total - 1)
        )
        ok = ((sreq <= 0) | (within <= remaining)).all(axis=-1) & (
            sbid < n_total
        )
        accept = jnp.zeros(p, bool).at[perm].set(ok)
        nodes = jnp.clip(bid, 0, n_total - 1)
        topo_pt = (
            node_rows(cluster.topo_ids, nodes)
            if (features.spread or features.interpod)
            else None
        )

        # constraint repair: releases only shrink the accept set, so
        # capacity stays safe; released pods re-bid next round
        pre_repair = accept
        if features.spread:
            accept = spread_repair(accept, nodes, sp_counts, topo_pt)
        if features.interpod:
            accept = interpod_repair(accept, topo_pt)
        # a round that only RELEASES still progresses: the released pods
        # re-bid under the next round's rotation and updated counts (the
        # filter now excludes the domains that capped them); max_rounds
        # bounds the loop regardless
        progress = accept.any() | (pre_repair & ~accept).any()

        requested = scatter_add_rows(requested, nodes, pods.req, accept)
        nonzero = scatter_add_rows(
            nonzero, nodes, pods.nonzero_req, accept
        )
        if features.spread:
            sp_counts = commit_spread(accept, nodes, sp_counts, topo_pt)
        if features.interpod:
            tm_present, tm_blocked, tm_global = commit_terms(
                accept, nodes, topo_pt, tm_present, tm_blocked, tm_global
            )
        assigned = jnp.where(accept, bid, assigned)
        bid_scores = jnp.where(accept, val, bid_scores)
        return (assigned, bid_scores, requested, nonzero, rnd + 1,
                progress, sp_counts, tm_present, tm_blocked, tm_global)

    def cond(state):
        assigned, _s, _r, _n, rnd, progress = state[:6]
        unplaced = ((assigned < 0) & pods.valid).any()
        return (rnd < max_rounds) & progress & unplaced

    zero = jnp.zeros(())
    init = (
        jnp.full(p, -1, jnp.int32),
        jnp.full(p, NEG_INF),
        cluster.requested,
        cluster.nonzero_requested,
        jnp.int32(0),
        jnp.bool_(True),
        sp0.counts_node if features.spread else zero,
        tm0.present_bits if features.interpod else zero,
        tm0.blocked_bits if features.interpod else zero,
        tm0.global_any if features.interpod else zero,
    )
    (assigned, bid_scores, requested, nonzero, rounds, _,
     sp_counts_f, tm_present_f, tm_blocked_f, tm_global_f) = (
        jax.lax.while_loop(cond, body, init)
    )

    # Failure reasons for unplaced pods (QueueingHints-lite): one staged
    # [C, N] filter pass against the FINAL state per class — the first
    # stage that empties the candidate set; a pod with survivors at every
    # stage parked on capacity contention/max_rounds, which requeues like
    # a resource failure.
    cl_f = cluster._replace(requested=requested, nonzero_requested=nonzero)
    sp_f = sp0._replace(counts_node=sp_counts_f) if features.spread else None
    tm_f = (
        tm0._replace(
            present_bits=tm_present_f, blocked_bits=tm_blocked_f,
            global_any=tm_global_f,
        )
        if features.interpod
        else None
    )

    fits_f_s = jax.vmap(
        lambda rep: fits_resources(cl_f, pod_view(pods, rep))
    )(s_reps)
    spf_f_k = (
        jax.vmap(
            lambda rep: spread_filter(sp_f, spread, rep, axis_name=axis_name)
        )(k_reps)
        if features.spread
        else None
    )
    ipf_f_k = (
        jax.vmap(lambda rep: interpod_filter(tm_f, terms, rep))(k_reps)
        if features.interpod
        else None
    )

    def class_reason(c, rep):
        s, k = jspec[c], jcons[c]
        s_static = sfeas_s[s]
        f = s_static & fits_f_s[s]
        a_res = _any(f)
        if features.spread:
            f = f & spf_f_k[k]
        a_spread = _any(f)
        if features.interpod:
            f = f & ipf_f_k[k]
        a_inter = _any(f)
        return jnp.where(
            a_inter, REASON_RESOURCES,  # feasible yet unplaced: contention
            jnp.where(
                ~_any(s_static), REASON_STATIC,
                jnp.where(
                    ~a_res, REASON_RESOURCES,
                    jnp.where(~a_spread, REASON_SPREAD, REASON_INTERPOD),
                ),
            ),
        ).astype(jnp.int32)

    reason_c = jax.vmap(class_reason)(
        jnp.arange(c_dim, dtype=jnp.int32), reps
    )
    cls_all = jnp.clip(pods.class_id, 0, c_dim - 1)
    reasons = jnp.where(assigned >= 0, REASON_NONE, reason_c[cls_all])

    # Gang post-pass: all-or-nothing groups.
    gang_dropped = jnp.zeros(p, bool)
    if n_groups > 0:
        g = pods.group_id
        gc = jnp.clip(g, 0, n_groups - 1)
        incomplete = jnp.zeros(n_groups, bool).at[gc].max(
            (assigned < 0) & pods.valid & (g >= 0)
        )
        gang_dropped = (g >= 0) & incomplete[gc] & (assigned >= 0)
        nodes = jnp.clip(assigned, 0, n_total - 1)
        requested = scatter_add_rows(
            requested, nodes, -pods.req, gang_dropped
        )
        nonzero = scatter_add_rows(
            nonzero, nodes, -pods.nonzero_req, gang_dropped
        )
        assigned = jnp.where(gang_dropped, -1, assigned)
        bid_scores = jnp.where(gang_dropped, NEG_INF, bid_scores)
        reasons = jnp.where(gang_dropped, REASON_GANG, reasons)

    final = cluster._replace(requested=requested, nonzero_requested=nonzero)
    return AuctionResult(
        assigned, bid_scores, rounds, gang_dropped, final, reasons,
        sp_counts_f if features.spread else None,
    )


_ = num_groups  # canonical definition lives in ops.schema (re-exported here)


def auction_assign_jit(
    cfg: ScoreConfig = DEFAULT_SCORE_CONFIG,
    tie_seed: int = 0,
    max_rounds: int = 64,
):
    """Jitted closure; n_groups/features/topo_z static per executable."""

    @partial(jax.jit, static_argnums=(1, 2, 3, 4))
    def run(
        snapshot: Snapshot,
        n_groups: int,
        features: FeatureFlags,
        topo_z: Tuple[int, int],
        tie_k: int,
    ):
        return auction_assign(
            snapshot, cfg, n_groups=n_groups, tie_seed=tie_seed,
            max_rounds=max_rounds, features=features, topo_z=topo_z,
            tie_k=tie_k,
        )

    def call(
        snapshot: Snapshot,
        n_groups: Optional[int] = None,
        features: Optional[FeatureFlags] = None,
        topo_z: Optional[Tuple[int, int]] = None,
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
            "auction", run,
            lambda: retrace.signature(
                snapshot, (n_groups, features, topo_z, tie_k)
            ),
        )
        return out

    return call
