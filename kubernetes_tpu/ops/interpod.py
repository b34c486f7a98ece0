"""InterPodAffinity as per-node bitsets.

The reference's PreFilter builds topology-pair count maps and Filter does
three boolean checks per node (interpodaffinity/filtering.go:306-366):

  1. no existing pod's required anti-affinity term matches the incoming
     pod in the node's topology
  2. none of the incoming pod's anti-affinity terms match an existing pod
     in the node's topology
  3. every affinity term has a matching existing pod in the node's
     topology — with the first-pod-of-a-group escape: all terms globally
     unmatched + the pod matches its own terms + node has the keys.

Every check consumes only count *presence* (> 0), and presence is
monotone during a batch solve (placements never remove pods), so the
state is three bitsets over the term axis instead of [T, Z] count tensors:

  present_bits[N, W] : term t has a matching pod in node n's topology
  blocked_bits[N, W] : a pod carrying anti-term t sits in n's topology
  global_any[W]      : term t has a matching pod anywhere

and the per-step work is O(N * W) word ops — no gathers or scatters in
the scan.  Updates exploit that terms share at most TK topology keys:
one node-mask per key, OR-ed with per-(slot, pod) precomputed bit rows.

All selector/namespace string matching was precomputed host-side into
schema.TermTable matrices — the O(pods x nodes) pairwise term the north
star turns into bit algebra.

The preferred (scoring) terms are modelled too, apart from the filter:
prep_pref_pod domain-sums their bound-pod match counts and owner weights
once a solve (assign._solver_prep, auction.py), and pref_pod_raw scores
both directions for one pod class (scores.py, auction.py).  Not yet
modelled: namespaceSelector on terms and matchLabelKeys.

Presence is also why a pod that requires term t by affinity couples with
nothing through its own match of t once global_any[t] is set: it can land
only where t is present already (the escape needs every term of the pod
unmatched everywhere), so the write flips no bit.  The wavefront planner
and its device check use this (assign.plan_waves, wave_safe).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .schema import ClusterTensors, TermTable


class TermState(NamedTuple):
    present_bits: jnp.ndarray  # u32[N, W]
    blocked_bits: jnp.ndarray  # u32[N, W]
    global_any: jnp.ndarray    # u32[W]
    # static within a solve:
    key_bits: jnp.ndarray      # u32[N, W] node has term t's topology key
    slot_v: jnp.ndarray        # i32[TK, N] node topo values by slot
    mi_slot_bits: jnp.ndarray  # u32[TK, P, W] matches_incoming split by term slot
    anti_slot_bits: jnp.ndarray  # u32[TK, P, W] own anti terms split by slot
    aff_bits: jnp.ndarray      # u32[P, W] own required affinity terms
    anti_bits: jnp.ndarray     # u32[P, W] own required anti-affinity terms


def _pack_bits_t(mat: jnp.ndarray) -> jnp.ndarray:
    """Pack bool[..., T] -> u32[..., ceil(T/32)] (little-endian bits)."""
    t = mat.shape[-1]
    w = (t + 31) // 32
    pad = w * 32 - t
    if pad:
        mat = jnp.concatenate(
            [mat, jnp.zeros(mat.shape[:-1] + (pad,), dtype=bool)], axis=-1
        )
    grouped = mat.reshape(mat.shape[:-1] + (w, 32)).astype(jnp.uint32)
    weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    return (grouped * weights).sum(axis=-1, dtype=jnp.uint32)


def _idx_to_bits(idx: jnp.ndarray, t_dim: int) -> jnp.ndarray:
    """int32[P, MA] term indices (-1 pad) -> bool[P, T] membership."""
    return (jnp.arange(t_dim)[None, None, :] == idx[:, :, None]).any(axis=1)


def _unpack_bits_t(bits: jnp.ndarray, t_dim: int) -> jnp.ndarray:
    """u32[..., W] packed (little-endian per word) -> bool[..., T]."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    expanded = (bits[..., :, None] >> shifts) & jnp.uint32(1)
    flat = expanded.reshape(*bits.shape[:-1], bits.shape[-1] * 32)
    return flat[..., :t_dim].astype(bool)


# coherence: rebuilt-per-solve -- affinity term grids derive from THIS
# snapshot's cluster tensors; a cached copy would score a stale generation
def prep_terms(
    cluster: ClusterTensors,
    terms: TermTable,
    z: int,
    axis_name: str | None = None,
    slots: tuple = (),
    has_bound: bool = True,
) -> TermState:
    """One-time assembly (the PreFilter analogue).  z is the topo-value
    vocab bound, used only for the prep-time count scatter.  Under
    shard_map pass axis_name: global_any must OR across node shards
    (pre-pack — psum on packed bitsets would carry between bits), and
    counts must be psum-reduced so a topology domain spanning shards is
    seen whole.  has_bound=False (FeatureFlags.bound_terms) statically
    elides the count scatter + [T, N] value-space gathers — the tables
    are runtime arrays, so XLA cannot fold them even when zero, and the
    gathers cost ~0.2 s at 32k nodes x 256 terms."""
    t_dim = terms.valid.shape[0]
    v = jnp.take_along_axis(cluster.topo_ids, terms.slot[None, :], axis=1).T  # [T, N]
    vc = jnp.clip(v, 0, z - 1)
    ok = (v >= 0) & cluster.node_valid[None, :] & terms.valid[:, None]

    if has_bound:
        def per_t(vc_row, ok_row, m_row, o_row):
            cm = jnp.zeros(z, jnp.float32).at[vc_row].add(m_row * ok_row)
            co = jnp.zeros(z, jnp.float32).at[vc_row].add(o_row * ok_row)
            return cm, co

        cm, co = jax.vmap(per_t)(vc, ok, terms.node_matches, terms.node_owners)
        if axis_name is not None:
            cm = jax.lax.psum(cm, axis_name)
            co = jax.lax.psum(co, axis_name)
        present = ok & (jnp.take_along_axis(cm, vc, axis=-1) > 0)   # [T, N]
        blocked = ok & (jnp.take_along_axis(co, vc, axis=-1) > 0)   # [T, N]
        global_any = _pack_bits_t((cm.sum(axis=-1) > 0) & terms.valid)
    else:
        shape = (t_dim, cluster.node_valid.shape[0])
        present = jnp.zeros(shape, bool)
        blocked = jnp.zeros(shape, bool)
        global_any = _pack_bits_t(jnp.zeros(t_dim, bool))

    # matches_incoming arrives PACKED (u32 words, schema.TermTable) —
    # slot splitting happens directly in word space.
    valid_words = _pack_bits_t(terms.valid)                      # [W]
    mi_bits = terms.matches_incoming & valid_words[None, :]      # [P, W]
    # Only the topology-key slots some term actually uses get a row in the
    # per-slot bit tables (static from FeatureFlags.term_slots) — real
    # workloads use one or two keys, so the per-step slot loop shrinks
    # from TK to that count.
    used = jnp.asarray(slots or tuple(range(cluster.topo_ids.shape[1])), dtype=jnp.int32)
    slot_onehot = terms.slot[None, :] == used[:, None]           # [U, T]
    slot_words = _pack_bits_t(slot_onehot)                       # [U, W]
    anti_membership = _idx_to_bits(terms.anti_idx, t_dim) & terms.valid[None, :]
    aff_membership = _idx_to_bits(terms.aff_idx, t_dim) & terms.valid[None, :]

    return TermState(
        present_bits=_pack_bits_t(present.T),
        blocked_bits=_pack_bits_t(blocked.T),
        global_any=global_any,
        key_bits=_pack_bits_t(ok.T),
        slot_v=cluster.topo_ids.T[used],
        mi_slot_bits=mi_bits[None, :, :] & slot_words[:, None, :],
        anti_slot_bits=_pack_bits_t(
            anti_membership[None, :, :] & slot_onehot[:, None, :]
        ),
        aff_bits=_pack_bits_t(aff_membership),
        anti_bits=_pack_bits_t(anti_membership),
    )


def interpod_filter(
    state: TermState, terms: TermTable, p: jnp.ndarray
) -> jnp.ndarray:
    """The three checks for pod p over all nodes: bool[N], as bit algebra."""
    mi_all = jnp.zeros_like(state.global_any)
    for s in range(state.mi_slot_bits.shape[0]):
        mi_all = mi_all | state.mi_slot_bits[s, p]

    # 1. existing pods' anti-affinity against the incoming pod
    viol_existing = (state.blocked_bits & mi_all[None, :]).any(axis=-1)

    # 2. incoming pod's anti-affinity against existing pods
    viol_own = (state.present_bits & state.anti_bits[p][None, :]).any(axis=-1)

    # 3. incoming pod's affinity (with the first-pod escape)
    aff = state.aff_bits[p]                                       # [W]
    any_active = (aff != 0).any()
    all_here = ((aff[None, :] & ~state.present_bits) == 0).all(axis=-1)
    keys_ok = ((aff[None, :] & ~state.key_bits) == 0).all(axis=-1)
    none_anywhere = ((aff & state.global_any) == 0).all()
    fallback = none_anywhere & terms.self_match_all[p] & keys_ok
    aff_ok = ~any_active | (all_here & keys_ok) | fallback

    return aff_ok & ~viol_existing & ~viol_own


def interpod_update(
    state: TermState,
    terms: TermTable,
    p: jnp.ndarray,
    topo_at: jnp.ndarray,
    found: jnp.ndarray,
    slots: tuple = (),
) -> TermState:
    """Account a placement: terms the placed pod matches turn present (and
    globally-any) in the placement's topology; its own anti-affinity terms
    turn blocked there.  topo_at = the chosen node's topo_ids row ([TK]);
    the sharded solve psum-broadcasts it from the owning shard.  slots
    must match the tuple prep_terms was built with
    (FeatureFlags.term_slots)."""
    idxs = slots or tuple(range(state.slot_v.shape[0]))
    present = state.present_bits
    blocked = state.blocked_bits
    global_any = state.global_any
    for j, s in enumerate(idxs):
        ta = topo_at[s]
        node_mask = (state.slot_v[j] == ta) & (ta >= 0) & found
        mi_bits = state.mi_slot_bits[j, p]
        anti_bits = state.anti_slot_bits[j, p]
        present = present | jnp.where(node_mask[:, None], mi_bits[None, :], 0)
        blocked = blocked | jnp.where(node_mask[:, None], anti_bits[None, :], 0)
        global_any = global_any | jnp.where((ta >= 0) & found, mi_bits, 0)
    return state._replace(
        present_bits=present, blocked_bits=blocked, global_any=global_any
    )


class PrefPodState(NamedTuple):
    """Domain-summed preferred-term match data (prep_pref_pod)."""

    counts_dom: jnp.ndarray   # f32[U, N] matching bound pods in n's topology
    ownerw_dom: jnp.ndarray   # f32[U, N] Σ signed owner weights in n's topology


def prep_pref_pod(
    cluster: ClusterTensors,
    table,
    z: int,
    axis_name: str | None = None,
    has_bound: bool = True,
) -> PrefPodState:
    """Domain-sum the per-node match counts / owner weights over each
    row's topology value (interpodaffinity/scoring.go PreScore builds the
    same topology-pair score map).  Under shard_map, value-space sums
    psum across node shards.  has_bound=False
    (FeatureFlags.bound_pref) statically folds the zero tables away."""
    if not has_bound:
        shape = (table.valid.shape[0], cluster.node_valid.shape[0])
        return PrefPodState(
            jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)
        )
    v = jnp.take_along_axis(cluster.topo_ids, table.slot[None, :], axis=1).T
    vc = jnp.clip(v, 0, z - 1)
    ok = (v >= 0) & cluster.node_valid[None, :] & table.valid[:, None]

    def per_u(vc_row, ok_row, c_row, w_row):
        cz = jnp.zeros(z, jnp.float32).at[vc_row].add(c_row * ok_row)
        wz = jnp.zeros(z, jnp.float32).at[vc_row].add(w_row * ok_row)
        return cz, wz

    cz, wz = jax.vmap(per_u)(vc, ok, table.node_counts, table.owner_weight)
    if axis_name is not None:
        cz = jax.lax.psum(cz, axis_name)
        wz = jax.lax.psum(wz, axis_name)
    counts_dom = jnp.where(ok, jnp.take_along_axis(cz, vc, axis=-1), 0.0)
    ownerw_dom = jnp.where(ok, jnp.take_along_axis(wz, vc, axis=-1), 0.0)
    return PrefPodState(counts_dom, ownerw_dom)


def pref_pod_raw(state: PrefPodState, table, p: jnp.ndarray) -> jnp.ndarray:
    """Raw preferred-interpod score of pod p over all nodes: f32[N].

    Both directions of scoring.go processExistingPod:
      Σ_j weight(p, j) * |matching existing pods in n's topology|   (own terms)
      Σ_u [p matches u] * Σ owner weights of u in n's topology      (their terms)
    """
    u_dim = state.counts_dom.shape[0]
    idx = jnp.clip(table.pod_idx[p], 0, u_dim - 1)          # [MA]
    w = jnp.where(table.pod_idx[p] >= 0, table.pod_weight[p], 0.0)
    own = (w[:, None] * state.counts_dom[idx]).sum(axis=0)   # [N]
    mi = table.matches_incoming[p].astype(jnp.float32)       # [U]
    theirs = (mi[:, None] * state.ownerw_dom).sum(axis=0)    # [N]
    return own + theirs
