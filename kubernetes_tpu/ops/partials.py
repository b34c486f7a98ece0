"""Device-resident Filter/Score partials — the O(changes) warm-start
kernels.

Every solve hoists a per-pod-class triple out of its scan
(ops.assign.class_statics): static feasibility (NodeName + taints +
NodeAffinity + bound-port conflicts), the raw preferred-node-affinity
score row, and the raw PreferNoSchedule taint count — three [C, N]
tables recomputed from scratch per batch even though (a) churn batches
re-present the same pod classes over and over (a Deployment's replicas
are one class) and (b) under sustained churn <1% of node rows change
between solves.  At 50k nodes with selector-bearing classes that
re-evaluation IS the dominant per-batch cost: selector matching alone is
S x T x E x K x N element ops.

These kernels keep the triple RESIDENT on device next to the
DeviceClusterMirror (models/mirror.py) and warm-start each solve from
it:

  ClassSpecs      per-slot static pod spec (the placement-independent
                  inputs the triple derives from), resident so dirty
                  ROWS can be re-evaluated for every cached class
                  without the batch's tables;
  PartialsStore   the resident [G, N] triple, one row per cached class
                  signature;
  eval_store      full recompute (first sync / resync discipline);
  refresh_rows    scatter-recompute ONLY the node columns dirtied since
                  the last sync (ClusterState.dirty_rows — includes the
                  rows the previous wave's picks touched);
  insert_slots    full rows for classes first seen this batch;
  gather_statics  the batch-ordered [C, N] view the solver consumes
                  (ops.assign greedy/wavefront `statics=` operand).

Bit-parity with the cold path is BY CONSTRUCTION: `_eval_slot` calls
the very kernels class_statics calls (match_terms,
static_feasible_for_pod, node_affinity_raw, taint_toleration_raw) on
the slot's stored spec, and every function is elementwise over the node
axis, so a column subset evaluated on gathered rows equals the same
columns of a full evaluation.  models/partials.py owns the host-side
cache protocol (signature keying, generation watermarks, the resync /
rollback discipline) and the parity gate.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .filters import PodView, match_terms, static_feasible_for_pod
from .schema import ClusterTensors
from .scores import node_affinity_raw, taint_toleration_raw


class ClassStatics(NamedTuple):
    """The per-class hoisted Filter/Score triple in BATCH class order —
    exactly what ops.assign.class_statics produces, gathered from the
    resident store instead of recomputed (C = padded joint-class dim)."""

    sfeas: jnp.ndarray  # bool[C, N]
    aff: jnp.ndarray    # f32[C, N]
    taint: jnp.ndarray  # f32[C, N]


class ClassSpecs(NamedTuple):
    """Resident per-slot static pod spec: everything the partials triple
    depends on besides the cluster tensors.  G = slot capacity; T/E/K,
    MT, TW, PW follow SnapshotLimits exactly like the batch tables —
    slot rows are byte-copies of the encoder's rows, so re-evaluating a
    slot is re-evaluating its representative pod."""

    valid: jnp.ndarray        # bool[G]
    name_id: jnp.ndarray      # i32[G]
    has_sel: jnp.ndarray      # bool[G]
    sel_ids: jnp.ndarray      # i32[G, T, E, K]
    sel_op: jnp.ndarray       # i32[G, T, E]
    sel_slot: jnp.ndarray     # i32[G, T, E]
    sel_tv: jnp.ndarray       # bool[G, T]
    tol_bits: jnp.ndarray     # u32[3, G, TW]
    tol_all: jnp.ndarray      # bool[3, G]
    port_bits: jnp.ndarray    # u32[G, PW]
    pref_ids: jnp.ndarray     # i32[G, MT, E, K]
    pref_op: jnp.ndarray      # i32[G, MT, E]
    pref_slot: jnp.ndarray    # i32[G, MT, E]
    pref_valid: jnp.ndarray   # bool[G, MT]
    pref_weight: jnp.ndarray  # f32[G, MT]


class PartialsStore(NamedTuple):
    """The resident partials triple, one row per cached class slot."""

    sfeas: jnp.ndarray  # bool[G, N]
    aff: jnp.ndarray    # f32[G, N]
    taint: jnp.ndarray  # f32[G, N]


def _eval_slot(cluster: ClusterTensors, specs: ClassSpecs, g):
    """One slot's partials row over the given cluster rows — the same
    kernel chain class_statics runs per class representative, fed from
    the stored spec instead of the batch tables (the parity claim)."""
    term_ok = match_terms(
        cluster, specs.sel_ids[g], specs.sel_op[g], specs.sel_slot[g]
    )  # bool[T, N]
    sel_mask = (term_ok & specs.sel_tv[g][:, None]).any(axis=0)[None, :]
    mt = specs.pref_valid.shape[1]
    pv = PodView(
        valid=specs.valid[g],
        req=jnp.zeros((1,), jnp.float32),          # unused by static kernels
        nonzero_req=jnp.zeros((1,), jnp.float32),  # unused by static kernels
        name_id=specs.name_id[g],
        sel_idx=jnp.where(specs.has_sel[g], 0, -1).astype(jnp.int32),
        tol_bits=specs.tol_bits[:, g, :],
        tol_all=specs.tol_all[:, g],
        port_bits=specs.port_bits[g],
        pref_idx=jnp.where(
            specs.pref_valid[g], jnp.arange(mt, dtype=jnp.int32), -1
        ),
        pref_weight=specs.pref_weight[g],
    )
    pref_mask = (
        match_terms(
            cluster, specs.pref_ids[g], specs.pref_op[g], specs.pref_slot[g]
        )
        & specs.pref_valid[g][:, None]
    )  # bool[MT, N]
    sfeas = static_feasible_for_pod(cluster, pv, sel_mask) & ~(
        (cluster.port_bits & pv.port_bits[None, :]).any(axis=-1)
    )
    return (
        sfeas,
        node_affinity_raw(pv, pref_mask),
        taint_toleration_raw(cluster, pv),
    )


def take_rows(cluster: ClusterTensors, idx) -> ClusterTensors:
    """The node-axis rows of every cluster leaf at `idx` (taint_bits is
    effect-major: its node axis is dim 1) — the sub-cluster the dirty
    refresh evaluates against."""
    return ClusterTensors(
        allocatable=cluster.allocatable[idx],
        requested=cluster.requested[idx],
        nonzero_requested=cluster.nonzero_requested[idx],
        node_valid=cluster.node_valid[idx],
        name_id=cluster.name_id[idx],
        label_bits=cluster.label_bits[idx],
        taint_bits=cluster.taint_bits[:, idx, :],
        port_bits=cluster.port_bits[idx],
        topo_ids=cluster.topo_ids[idx],
        image_bits=cluster.image_bits[idx],
        slice_id=cluster.slice_id[idx],
        torus_coords=cluster.torus_coords[idx],
        slice_dims=cluster.slice_dims[idx],
        slice_pos=cluster.slice_pos[idx],
    )


def take_specs(specs: ClassSpecs, idx) -> ClassSpecs:
    """Slot rows of the spec store at `idx` (tol axes are effect-major:
    slot axis is dim 1)."""
    return ClassSpecs(
        valid=specs.valid[idx],
        name_id=specs.name_id[idx],
        has_sel=specs.has_sel[idx],
        sel_ids=specs.sel_ids[idx],
        sel_op=specs.sel_op[idx],
        sel_slot=specs.sel_slot[idx],
        sel_tv=specs.sel_tv[idx],
        tol_bits=specs.tol_bits[:, idx, :],
        tol_all=specs.tol_all[:, idx],
        port_bits=specs.port_bits[idx],
        pref_ids=specs.pref_ids[idx],
        pref_op=specs.pref_op[idx],
        pref_slot=specs.pref_slot[idx],
        pref_valid=specs.pref_valid[idx],
        pref_weight=specs.pref_weight[idx],
    )


def set_spec_rows(specs: ClassSpecs, rows: ClassSpecs, idx) -> ClassSpecs:
    """Scatter freshly-encoded spec rows into the resident store at
    slot indices `idx` (duplicate indices carry identical rows — the
    bucket-padding convention, see models.mirror._pad_idx)."""
    return ClassSpecs(
        valid=specs.valid.at[idx].set(rows.valid),
        name_id=specs.name_id.at[idx].set(rows.name_id),
        has_sel=specs.has_sel.at[idx].set(rows.has_sel),
        sel_ids=specs.sel_ids.at[idx].set(rows.sel_ids),
        sel_op=specs.sel_op.at[idx].set(rows.sel_op),
        sel_slot=specs.sel_slot.at[idx].set(rows.sel_slot),
        sel_tv=specs.sel_tv.at[idx].set(rows.sel_tv),
        tol_bits=specs.tol_bits.at[:, idx].set(rows.tol_bits),
        tol_all=specs.tol_all.at[:, idx].set(rows.tol_all),
        port_bits=specs.port_bits.at[idx].set(rows.port_bits),
        pref_ids=specs.pref_ids.at[idx].set(rows.pref_ids),
        pref_op=specs.pref_op.at[idx].set(rows.pref_op),
        pref_slot=specs.pref_slot.at[idx].set(rows.pref_slot),
        pref_valid=specs.pref_valid.at[idx].set(rows.pref_valid),
        pref_weight=specs.pref_weight.at[idx].set(rows.pref_weight),
    )


def eval_store(cluster: ClusterTensors, specs: ClassSpecs) -> PartialsStore:
    """Full recompute: every slot's partials row over every node — the
    first-sync upload and the periodic-resync discipline's dispatch."""
    g_dim = specs.valid.shape[0]
    sfeas, aff, taint = jax.vmap(
        lambda g: _eval_slot(cluster, specs, g)
    )(jnp.arange(g_dim, dtype=jnp.int32))
    return PartialsStore(sfeas=sfeas, aff=aff, taint=taint)


def refresh_rows(
    store: PartialsStore,
    specs: ClassSpecs,
    cluster: ClusterTensors,
    idx,
) -> PartialsStore:
    """Re-evaluate ONLY the node columns at `idx` (the rows dirtied
    since the last sync, bucket-padded by repeating the first index) for
    every cached slot, and scatter them into the store — the
    O(changed-rows) half of the warm start.  An index past the last
    column is dropped (the staging program pads with one): its gathered
    row is clamped and evaluated, and its write goes nowhere."""
    sub = take_rows(cluster, idx)
    cols = eval_store(sub, specs)  # [G, D]
    return PartialsStore(
        sfeas=store.sfeas.at[:, idx].set(cols.sfeas, mode="drop"),
        aff=store.aff.at[:, idx].set(cols.aff, mode="drop"),
        taint=store.taint.at[:, idx].set(cols.taint, mode="drop"),
    )


def insert_slots(
    store: PartialsStore,
    specs: ClassSpecs,
    cluster: ClusterTensors,
    idx,
) -> PartialsStore:
    """Full [N] rows for the slots at `idx` (classes first seen this
    batch, bucket-padded by repeating the first index), scattered into
    the store."""
    rows = eval_store(cluster, take_specs(specs, idx))  # [M, N]
    return PartialsStore(
        sfeas=store.sfeas.at[idx].set(rows.sfeas),
        aff=store.aff.at[idx].set(rows.aff),
        taint=store.taint.at[idx].set(rows.taint),
    )


def grow_store_cols(store: PartialsStore, dn: int) -> PartialsStore:
    """Pad `dn` zero node-columns onto every resident row — the elastic
    node axis's in-place partials grow (one on-device concat per leaf,
    zero host transfer).  The caller immediately refresh_rows()-es the
    new column range against the grown cluster, so the pad value never
    reaches a solve: every class row stays warm across the bucket
    crossing."""
    import jax.numpy as jnp

    def pad(arr):
        return jnp.concatenate(
            [arr, jnp.zeros(arr.shape[:1] + (dn,), arr.dtype)], axis=1
        )

    return PartialsStore(
        sfeas=pad(store.sfeas), aff=pad(store.aff), taint=pad(store.taint)
    )


def shrink_store_cols(store: PartialsStore, n: int) -> PartialsStore:
    """Slice the resident rows to the first `n` node columns — the
    post-dwell bucket shrink (every live row index is < n by the
    watermark invariant)."""
    return PartialsStore(
        sfeas=store.sfeas[:, :n],
        aff=store.aff[:, :n],
        taint=store.taint[:, :n],
    )


def gather_statics(store: PartialsStore, slots) -> ClassStatics:
    """The batch-ordered [C, N] statics view: store rows at `slots`
    (one slot id per joint class; padded classes alias class 0's slot,
    matching class_statics' clipped-representative convention)."""
    return ClassStatics(
        sfeas=store.sfeas[slots],
        aff=store.aff[slots],
        taint=store.taint[slots],
    )


# Shared single-chip executables: every PartialsCache on the default
# device set dispatches through these, so N caches (one per scheduler
# profile / test instance) share one compile cache per shape bucket
# instead of paying one XLA compile each.  Mesh-mode caches build their
# own out_shardings-pinned twins (models/partials.py).
eval_store_jit = jax.jit(eval_store)
refresh_rows_jit = jax.jit(refresh_rows)
insert_slots_jit = jax.jit(insert_slots)
gather_statics_jit = jax.jit(gather_statics)
set_spec_rows_jit = jax.jit(set_spec_rows)
grow_store_cols_jit = jax.jit(grow_store_cols, static_argnums=(1,))
shrink_store_cols_jit = jax.jit(shrink_store_cols, static_argnums=(1,))
