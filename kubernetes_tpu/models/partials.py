"""PartialsCache — device-resident Filter/Score partials warm-started
from the mirror (the incremental O(changes) solve).

The sibling of DeviceClusterMirror: where the mirror makes host→device
TRANSFER O(changed rows), this cache makes the per-batch Filter/Score
RE-EVALUATION O(changes).  It keeps the per-class static triple
(ops/partials.py PartialsStore: static feasibility + raw
affinity/taint score rows) resident on device, keyed by CONTENT
signatures of the encoder's pod classes (schema._pod_classes, with the
batch-local selector/preferred table indices replaced by the builder's
persistent signature registry ids, so the key survives across batches).

Per sync (called under the cache lock from encode_pending, right after
mirror.sync()):

  1. classes already cached re-evaluate ONLY the node rows dirtied
     since the cache's last sync (ClusterState.dirty_rows — this
     includes every row the previous wave's picks touched, since
     assumes bump the usage generation);
  2. classes first seen this batch evaluate their full [N] row once
     and stay resident;
  3. the solver consumes a batch-ordered gather — the `statics=`
     operand of the warm greedy/wavefront executables.

Resync discipline (the mirror's, applied whole):

  * full recompute when the struct generation moved, the padded node
    bucket changed, or the delta would touch more than half the rows;
  * full FLUSH (keys dropped) when an expansion-relevant vocabulary
    grew — selector/preferred rows are expanded against the vocab at
    encode time, so a grown vocab silently changes what a cached row
    SHOULD contain (the key can't see it; the watermark can);
  * a PERIODIC full recompute every `resync_interval` delta syncs —
    the standing parity discipline — plus verify(), the oracle-parity
    gate the test suite and chaos seeds drive;
  * speculation_point()/rollback() double-buffer the resident arrays
    exactly like the mirror's speculation bookmark (immutable device
    arrays make holding the reference a true double buffer), and
    invalidate() serves leadership reconcile / RESHARDED.

The `solve.partials` fault point fires here: CORRUPT poisons the
resident score rows with NaN so the decode-side health check
(SolveUnhealthy) trips and the breaker/retry path falls back to a full
recompute — the parity gate's runtime wire.

All state is mutated under the scheduler-cache lock (sync() shares
encode_pending's locked section), like the mirror's counters.
"""

from __future__ import annotations

import logging
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from ..analysis import epochs, retrace
from ..ops import partials as pops
from ..ops import schema
from ..testing import faults
from ..utils import vocab as vb

_DOMAIN_LABELS = schema.DOMAIN_LABELS


def _pad_idx(idx: np.ndarray, bucket: int) -> np.ndarray:
    out = np.full(bucket, idx[0], dtype=np.int32)
    out[: idx.shape[0]] = idx
    return out


@jax.jit
def _poison_aff(store: pops.PartialsStore) -> pops.PartialsStore:
    """CORRUPT-grade fault: poison the resident raw-affinity rows with
    +inf.  The per-pod normalization divides by the feasible-set max —
    floor(100 * inf / inf) is NaN — so every feasible node's score goes
    NaN and the decode health check trips
    (models.batch_scheduler.SolveUnhealthy).  A direct NaN poison would
    be SQUASHED: normalize's `where(m > 0, ...)` reads a NaN max as
    False and silently zeroes the row — wrong scores with no trip."""
    import jax.numpy as jnp

    return store._replace(aff=jnp.full_like(store.aff, jnp.inf))


class PartialsCache:
    """One consumer's resident Filter/Score partials for a ClusterState
    (each TPUBatchScheduler owns one, next to its DeviceClusterMirror)."""

    # deltas touching more rows than this fraction fall back to a full
    # recompute (the mirror's threshold, same rationale)
    FULL_SYNC_FRACTION = 0.5
    # forced full recompute every this many delta syncs — the periodic
    # half of the resync/parity discipline
    DEFAULT_RESYNC_INTERVAL = 1024
    MIN_SLOTS = 32
    MAX_SLOTS = 1024
    # FIXED dispatch buckets: dirty rows refresh in ROW_CHUNK-sized
    # chunks and misses insert in MISS_CHUNK-sized chunks (padded by
    # repeating the first index), so each cache compiles exactly ONE
    # refresh and ONE insert executable per (cap, n, r) instead of
    # walking a delta-size bucket ladder with a ~1s XLA compile on the
    # hot path at every first-seen bucket.  A 3-row delta evaluating
    # 256 padded rows costs ~cap*256 elementwise ops — noise next to
    # one solve.
    ROW_CHUNK = 256
    MISS_CHUNK = 8

    def __init__(
        self,
        state: schema.ClusterState,
        mesh=None,
        resync_interval: int = DEFAULT_RESYNC_INTERVAL,
    ):
        self.state = state
        self.mesh = mesh
        self.resync_interval = max(int(resync_interval), 1)
        # graftcoh-registered device-resident buffer (docs/static_analysis.md)
        self._store: Optional[pops.PartialsStore] = None  # resident: fault=solve.partials chaos=PARTIALS_SEEDS
        self._specs: Optional[pops.ClassSpecs] = None
        self._slots: Dict[tuple, int] = {}
        self._cap = 0
        self._n = 0
        self._synced_gen = 0
        self._struct_gen = 0
        self._vocab_key: Optional[tuple] = None
        self._since_full = 0
        # epoch stamp + invalidation fence (analysis/epochs.py;
        # models/mirror.py carries the same pair and documents the
        # rollback-resurrection hazard the fence closes)
        self._epoch: Optional[epochs.EpochStamp] = None
        self._inval_gen = 0
        # counters (read by the scheduler_partials_* gauges through
        # stats()); mutated under the cache
        # lock — sync() runs inside encode_pending's locked section
        self.hit_rows_total = 0         # [class, row] entries served warm
        self.recomputed_rows_total = 0  # node rows re-evaluated
        self.full_recomputes = 0        # full store recomputes (any cause)
        self.rollbacks = 0              # speculation rollbacks
        self.delta_syncs = 0
        self.grows = 0                  # in-place node-axis grows/shrinks
        # safety valve (the mirror's, same contract): False restores the
        # pre-elastic behavior — any node-axis change reseeds the whole
        # store, dropping every warm class row
        self.incremental_grow = True
        if mesh is None:
            self._put = jax.device_put
            self._eval = pops.eval_store_jit
            self._refresh = pops.refresh_rows_jit
            self._insert = pops.insert_slots_jit
            self._gather = pops.gather_statics_jit
            self._set_specs = pops.set_spec_rows_jit
            self._grow_cols = pops.grow_store_cols_jit
            self._shrink_cols = pops.shrink_store_cols_jit
        else:
            from jax.sharding import NamedSharding, PartitionSpec as P

            axis = mesh.axis_names[0]
            row_sh = NamedSharding(mesh, P(None, axis))
            rep_sh = NamedSharding(mesh, P())
            store_sh = pops.PartialsStore(
                sfeas=row_sh, aff=row_sh, taint=row_sh
            )
            statics_sh = pops.ClassStatics(
                sfeas=row_sh, aff=row_sh, taint=row_sh
            )
            # small uploads (spec rows, index buckets) replicate so every
            # jit operand shares the mesh's device set; store outputs pin
            # to the resident layout so executable keys never drift
            # (models/mirror.py, same discipline).  Replicated-resident
            # buckets (smaller than the mesh) use the plain twins below.
            self._put = lambda x: jax.device_put(x, rep_sh)
            self._eval = jax.jit(pops.eval_store, out_shardings=store_sh)
            self._refresh = jax.jit(
                pops.refresh_rows, out_shardings=store_sh
            )
            self._insert = jax.jit(pops.insert_slots, out_shardings=store_sh)
            self._gather = jax.jit(
                pops.gather_statics, out_shardings=statics_sh
            )
            self._set_specs = pops.set_spec_rows_jit
            self._grow_cols = jax.jit(
                pops.grow_store_cols, static_argnums=(1,),
                out_shardings=store_sh,
            )
            self._shrink_cols = jax.jit(
                pops.shrink_store_cols, static_argnums=(1,),
                out_shardings=store_sh,
            )
            self._eval_rep = pops.eval_store_jit
            self._refresh_rep = pops.refresh_rows_jit
            self._insert_rep = pops.insert_slots_jit
            self._gather_rep = pops.gather_statics_jit
            self._grow_cols_rep = pops.grow_store_cols_jit
            self._shrink_cols_rep = pops.shrink_store_cols_jit
        self._resident_sharded = False

    # -- bookkeeping -------------------------------------------------------

    def stats(self) -> dict:
        return {
            "hit_rows_total": self.hit_rows_total,
            "recomputed_rows_total": self.recomputed_rows_total,
            "full_recomputes": self.full_recomputes,
            "rollbacks": self.rollbacks,
            "delta_syncs": self.delta_syncs,
            "slots": len(self._slots),
            "grows": self.grows,
        }

    def epoch(self) -> Optional[epochs.EpochStamp]:
        """The resident store's epoch stamp (None when invalidated,
        declined, or never synced) — read by the GRAFTLINT_COHERENCE
        auditor."""
        return self._epoch

    def speculation_point(self) -> tuple:
        """Bookmark the resident buffers for a speculative encode —
        device arrays are immutable, so holding the references IS the
        double buffer (models.mirror.DeviceClusterMirror
        .speculation_point, same contract: caller holds the cache
        lock)."""
        return (
            self._store, self._specs, dict(self._slots), self._cap,
            self._n, self._synced_gen, self._struct_gen, self._vocab_key,
            self._since_full, self._resident_sharded, self._epoch,
            self._inval_gen,
        )

    def rollback(self, point: tuple) -> None:
        """Restore a speculation_point() bookmark: the speculative batch
        was invalidated, so the rows refreshed/inserted for it are
        dropped whole; the next sync re-evaluates every row dirtied
        since the bookmarked generation.  Counted into
        scheduler_partials_rollbacks_total.  Refused (stays
        invalidated) when an invalidate() landed after the bookmark —
        the fence contract documented on DeviceClusterMirror.rollback."""
        (
            store, specs, slots, cap, n, synced_gen, struct_gen,
            vocab_key, since_full, resident_sharded, epoch_stamp,
            inval_gen,
        ) = point
        if inval_gen != self._inval_gen:
            epochs.note_rollback_blocked("partials")
            return
        self._store = store
        self._specs = specs
        self._slots = dict(slots)
        self._cap = cap
        self._n = n
        self._synced_gen = synced_gen
        self._struct_gen = struct_gen
        self._vocab_key = vocab_key
        self._since_full = since_full
        self._resident_sharded = resident_sharded
        self._epoch = epoch_stamp
        self.rollbacks += 1

    def invalidate(self) -> None:
        """Drop the resident buffers AND the signature map: the next
        sync performs a full recompute from the current batch.
        Leadership reconcile calls this alongside mirror.invalidate()
        (a reconciled cache's generation history no longer matches the
        resident rows), and the device-solve retry path calls it before
        re-encoding (resident state is a fault suspect)."""
        self._store = None
        self._specs = None
        self._slots = {}
        self._cap = 0
        self._n = 0
        self._synced_gen = 0
        self._struct_gen = 0
        self._vocab_key = None
        self._since_full = 0
        self._epoch = None
        self._inval_gen += 1

    def _vocab_watermark(self) -> tuple:
        """Selector/preferred rows expand Exists/NotIn/Gt/Lt against the
        CURRENT vocabularies at encode time (schema._expand_requirement)
        — a grown vocab changes what a cached row should contain without
        changing its signature, so growth flushes the cache whole.  The
        watermark is PER REFERENCED KEY (builder.expansion_watermark):
        only keys some encoded requirement actually expanded against
        count, so the label pairs every autoscaled node interns (its
        hostname, fresh zone values under unreferenced keys) do NOT
        flush warm rows — sustained node churn keeps the cache hot (the
        elastic-node-axis contract; tests/test_elastic_axis.py).  Toleration
        re-expansions are self-keying (the expanded bitset bytes are
        part of the class key), so the taint vocab is not watermarked."""
        return self.state.builder.expansion_watermark()

    # -- signature keying --------------------------------------------------

    @staticmethod
    def class_key(
        pods: schema.PodBatch, rep: int, meta: schema.SnapshotMeta
    ) -> tuple:
        """Content signature of one class representative's STATIC spec —
        exactly the inputs of the partials triple (name/selector/
        tolerations/ports/preferred), with the batch-local table indices
        replaced by the builder's persistent signature-registry ids
        (SnapshotMeta.sel_stable / pref_stable) so the key is stable
        across batches.  Requests are deliberately excluded: classes
        differing only in resources share one partials row."""
        si = int(pods.sel_idx[rep])
        mt = pods.pref_idx.shape[1]
        prefs = tuple(
            (
                meta.pref_stable[int(pods.pref_idx[rep, j])]
                if int(pods.pref_idx[rep, j]) >= 0
                else -1,
                float(pods.pref_weight[rep, j]),
            )
            for j in range(mt)
        )
        return (
            bool(pods.valid[rep]),
            int(pods.name_id[rep]),
            meta.sel_stable[si] if si >= 0 else -1,
            np.ascontiguousarray(pods.tol_bits[:, rep, :]).tobytes(),
            np.ascontiguousarray(pods.tol_all[:, rep]).tobytes(),
            np.ascontiguousarray(pods.port_bits[rep]).tobytes(),
            prefs,
        )

    def _spec_row(self, snap: schema.Snapshot, rep: int) -> tuple:
        """One ClassSpecs row (host numpy leaves) for a representative
        pod, byte-copied from the batch tables."""
        pods, sel, pref = snap.pods, snap.selectors, snap.preferred
        lim = self.state.builder.limits
        t_cap, e_cap, k_cap, mt = (
            lim.max_terms, lim.max_exprs, lim.max_ids_per_expr,
            lim.max_preferred,
        )
        si = int(pods.sel_idx[rep])
        if si >= 0:
            sel_ids = np.array(sel.expr_ids[si])
            sel_op = np.array(sel.expr_op[si])
            sel_slot = np.array(sel.expr_slot[si])
            sel_tv = np.array(sel.term_valid[si])
        else:
            sel_ids = np.full((t_cap, e_cap, k_cap), -1, dtype=np.int32)
            sel_op = np.zeros((t_cap, e_cap), dtype=np.int32)
            sel_slot = np.full((t_cap, e_cap), _DOMAIN_LABELS, dtype=np.int32)
            sel_tv = np.zeros(t_cap, dtype=bool)
        pref_ids = np.full((mt, e_cap, k_cap), -1, dtype=np.int32)
        pref_op = np.zeros((mt, e_cap), dtype=np.int32)
        pref_slot = np.full((mt, e_cap), _DOMAIN_LABELS, dtype=np.int32)
        pref_valid = np.zeros(mt, dtype=bool)
        pref_weight = np.zeros(mt, dtype=np.float32)
        for j in range(mt):
            pi = int(pods.pref_idx[rep, j])
            if pi < 0:
                continue
            pref_ids[j] = pref.expr_ids[pi]
            pref_op[j] = pref.expr_op[pi]
            pref_slot[j] = pref.expr_slot[pi]
            pref_valid[j] = True
            pref_weight[j] = pods.pref_weight[rep, j]
        return (
            bool(pods.valid[rep]), int(pods.name_id[rep]), si >= 0,
            sel_ids, sel_op, sel_slot, sel_tv,
            np.array(pods.tol_bits[:, rep, :]),
            np.array(pods.tol_all[:, rep]),
            np.array(pods.port_bits[rep]),
            pref_ids, pref_op, pref_slot, pref_valid, pref_weight,
        )

    def _stack_spec_rows(self, rows: List[tuple], bucket: int) -> pops.ClassSpecs:
        """Stack host spec rows into an [Mpad]-bucketed ClassSpecs
        (padding repeats the first row — duplicate scatter of identical
        values is a no-op)."""
        pad = [rows[0]] * (bucket - len(rows))
        rows = rows + pad
        cols = list(zip(*rows))
        return pops.ClassSpecs(
            valid=np.array(cols[0], dtype=bool),
            name_id=np.array(cols[1], dtype=np.int32),
            has_sel=np.array(cols[2], dtype=bool),
            sel_ids=np.stack(cols[3]),
            sel_op=np.stack(cols[4]),
            sel_slot=np.stack(cols[5]),
            sel_tv=np.stack(cols[6]),
            tol_bits=np.stack(cols[7], axis=1),
            tol_all=np.stack(cols[8], axis=1),
            port_bits=np.stack(cols[9]),
            pref_ids=np.stack(cols[10]),
            pref_op=np.stack(cols[11]),
            pref_slot=np.stack(cols[12]),
            pref_valid=np.stack(cols[13]),
            pref_weight=np.stack(cols[14]),
        )

    def _empty_specs(self, cap: int) -> pops.ClassSpecs:
        lim = self.state.builder.limits
        t_cap, e_cap, k_cap, mt = (
            lim.max_terms, lim.max_exprs, lim.max_ids_per_expr,
            lim.max_preferred,
        )
        return pops.ClassSpecs(
            valid=np.zeros(cap, dtype=bool),
            name_id=np.full(cap, -1, dtype=np.int32),
            has_sel=np.zeros(cap, dtype=bool),
            sel_ids=np.full((cap, t_cap, e_cap, k_cap), -1, dtype=np.int32),
            sel_op=np.zeros((cap, t_cap, e_cap), dtype=np.int32),
            sel_slot=np.full(
                (cap, t_cap, e_cap), _DOMAIN_LABELS, dtype=np.int32
            ),
            sel_tv=np.zeros((cap, t_cap), dtype=bool),
            tol_bits=np.zeros(
                (3, cap, lim.taint_words), dtype=np.uint32
            ),
            tol_all=np.zeros((3, cap), dtype=bool),
            port_bits=np.zeros((cap, lim.port_words), dtype=np.uint32),
            pref_ids=np.full((cap, mt, e_cap, k_cap), -1, dtype=np.int32),
            pref_op=np.zeros((cap, mt, e_cap), dtype=np.int32),
            pref_slot=np.full(
                (cap, mt, e_cap), _DOMAIN_LABELS, dtype=np.int32
            ),
            pref_valid=np.zeros((cap, mt), dtype=bool),
            pref_weight=np.zeros((cap, mt), dtype=np.float32),
        )

    # -- the sync protocol -------------------------------------------------

    def _kernels(self):
        """(eval, refresh, insert, gather): the pinned-sharding twins
        when the resident layout is node-axis sharded, the plain ones
        otherwise (single chip, or replicated small-bucket residents —
        the same batches the solver runs single-chip)."""
        if self.mesh is not None and not self._resident_sharded:
            return (
                self._eval_rep, self._refresh_rep, self._insert_rep,
                self._gather_rep,
            )
        return self._eval, self._refresh, self._insert, self._gather

    def sync(
        self,
        cluster,
        snap: schema.Snapshot,
        meta: schema.SnapshotMeta,
        cluster_epoch: Optional[epochs.EpochStamp] = None,
    ) -> Optional[pops.ClassStatics]:
        """Warm statics for this batch, or None when the cache declines
        (capacity overflow past MAX_SLOTS with more classes than fit).
        `cluster` is the mirror's device-resident ClusterTensors for the
        state's CURRENT generation — the exact tensors the solve
        consumes, so warm rows are evaluated against what the cold path
        would see.  Caller holds the cache lock (mirror.sync contract);
        `snap` is still host-resident (pre-transfer).  `cluster_epoch`
        is the mirror's epoch stamp for `cluster` — the resident store's
        stamp inherits its buffer lineage so the GRAFTLINT_COHERENCE
        auditor can tie the rows to the exact mirror buffer they were
        evaluated against."""
        state = self.state
        class_rep = np.asarray(snap.pods.class_rep)
        c_dim = class_rep.shape[0]
        n_real = int((class_rep >= 0).sum())
        act = faults.fire("solve.partials", classes=n_real)
        keys = [
            self.class_key(snap.pods, int(class_rep[c]), meta)
            for c in range(n_real)
        ]
        n = int(cluster.allocatable.shape[0])
        vkey = self._vocab_watermark()
        if self.mesh is not None:
            sharded = n % int(self.mesh.devices.size) == 0
        else:
            sharded = False

        stale = (
            self._store is None
            or self._struct_gen < state.struct_generation
            or self._vocab_key != vkey
            or self._resident_sharded != sharded
            # the incremental_grow valve off: any node-axis change
            # reseeds the store (the pre-elastic behavior, kept as the
            # oracle/safety path)
            or (self._n != n and not self.incremental_grow)
        )
        # distinct first-seen keys (two classes differing only in
        # requests share one slot — requests are not in the key)
        misses = list(
            dict.fromkeys(k for k in keys if k not in self._slots)
        )
        needed = len(self._slots) + len(misses)
        if needed > self._cap:
            if needed > self.MAX_SLOTS:
                return None  # more live classes than the cache may hold
            stale = True  # reallocation: reseed from this batch
        if not stale and self._since_full >= self.resync_interval:
            stale = True  # periodic full recompute (parity discipline)

        self._resident_sharded = sharded
        ev, rf, ins, ga = self._kernels()
        if stale:
            self._full_reset(cluster, snap, keys, n, vkey, ev)
        else:
            static_idx, usage_idx = state.dirty_rows(self._synced_gen, n)
            dirty = np.union1d(static_idx, usage_idx).astype(np.int32)
            if dirty.shape[0] > self.FULL_SYNC_FRACTION * n:
                self._full_reset(cluster, snap, keys, n, vkey, ev)
            else:
                if self._n != n:
                    # elastic node axis: the padded bucket moved while
                    # struct/vocab identity held — resize the resident
                    # [G, N] columns in place, keeping every cached
                    # class row warm across the crossing
                    self._resize_store(cluster, n, rf)
                miss_set = set(misses)
                hits = sum(1 for k in keys if k not in miss_set)
                if misses:
                    reps_by_key = {}
                    for c in range(n_real):
                        reps_by_key.setdefault(keys[c], int(class_rep[c]))
                    miss_rows, miss_idx = [], []
                    for k in misses:
                        slot = len(self._slots)
                        self._slots[k] = slot
                        miss_rows.append(self._spec_row(snap, reps_by_key[k]))
                        miss_idx.append(slot)
                    r = int(cluster.allocatable.shape[1])
                    chunk = self.MISS_CHUNK
                    for off in range(0, len(miss_idx), chunk):
                        seg_rows = miss_rows[off:off + chunk]
                        seg_idx = np.asarray(
                            miss_idx[off:off + chunk], np.int32
                        )
                        idx = self._put(_pad_idx(seg_idx, chunk))
                        rows = jax.tree.map(
                            self._put,
                            self._stack_spec_rows(seg_rows, chunk),
                        )
                        self._specs = self._set_specs(self._specs, rows, idx)
                        self._store = ins(
                            self._store, self._specs, cluster, idx
                        )
                    retrace.note(
                        "partials-insert", ins,
                        lambda: ("partials-insert", self._cap, n, r, chunk,
                                 self._resident_sharded),
                    )
                    self.recomputed_rows_total += len(miss_idx) * n
                if dirty.shape[0]:
                    r = int(cluster.allocatable.shape[1])
                    chunk = min(self.ROW_CHUNK, n)
                    for off in range(0, dirty.shape[0], chunk):
                        idx = self._put(
                            _pad_idx(dirty[off:off + chunk], chunk)
                        )
                        self._store = rf(
                            self._store, self._specs, cluster, idx
                        )
                    retrace.note(
                        "partials-refresh", rf,
                        lambda: ("partials-refresh", self._cap, n, r, chunk,
                                 self._resident_sharded),
                    )
                    self.recomputed_rows_total += int(dirty.shape[0])
                self.hit_rows_total += max(hits, 0) * (n - int(dirty.shape[0]))
                self.delta_syncs += 1
                self._since_full += 1
                self._synced_gen = state.generation
        # stamp AFTER both paths: the store now matches the cache's
        # current generations, and its lineage follows the mirror buffer
        # the rows were evaluated against (a CORRUPT fault below poisons
        # CONTENT, not epochs — the parity gate / heal wire owns that)
        self._epoch = epochs.EpochStamp(
            "partials", self._struct_gen, self._vocab_key,
            self._synced_gen,
            cluster_epoch.buffer_id if cluster_epoch is not None else 0,
        )

        if act == faults.CORRUPT:
            # poison the RESIDENT partials: the warm solve's scores go
            # NaN, the decode health check trips, and the retry path
            # invalidates this cache → full recompute (or the breaker's
            # host fallback) — chaos seeds 700-704 assert the healing
            self._store = _poison_aff(self._store)

        # batch-ordered slot gather ([C] — padded classes alias class
        # 0's slot, the clipped-representative convention)
        slot_arr = np.empty(c_dim, dtype=np.int32)
        for c in range(c_dim):
            slot_arr[c] = self._slots[keys[c if c < n_real else 0]]
        statics = ga(self._store, self._put(slot_arr))
        retrace.note(
            "partials-gather", ga,
            lambda: ("partials-gather", self._cap, n, c_dim,
                     self._resident_sharded),
        )
        return statics

    def _grow_kernels(self):
        """(grow_cols, shrink_cols): the pinned-sharding twins when the
        resident layout is node-axis sharded, the plain ones otherwise
        (the _kernels() convention)."""
        if self.mesh is not None and not self._resident_sharded:
            return self._grow_cols_rep, self._shrink_cols_rep
        return self._grow_cols, self._shrink_cols

    def _resize_store(self, cluster, n: int, rf) -> None:
        """In-place node-axis resize of the resident store (the elastic
        node axis): grow pads zero columns on device and immediately
        re-evaluates the new column range against the grown cluster —
        every cached class row stays warm across the pad-bucket
        crossing, at O(new columns) device work and O(new rows) index
        transfer; shrink slices (live rows are always below the new
        bucket by the watermark invariant)."""
        grow_c, shrink_c = self._grow_kernels()
        old_n = self._n
        if n > old_n:
            self._store = grow_c(self._store, n - old_n)
            gidx = np.arange(old_n, n, dtype=np.int32)
            chunk = vb.pad_dim(int(gidx.shape[0]), 1)
            idx = self._put(_pad_idx(gidx, chunk))
            self._store = rf(self._store, self._specs, cluster, idx)
            self.recomputed_rows_total += int(gidx.shape[0])
            retrace.note(
                "partials-grow", grow_c,
                lambda: ("partials-grow", self._cap, old_n, n,
                         self._resident_sharded),
            )
        else:
            self._store = shrink_c(self._store, n)
            retrace.note(
                "partials-shrink", shrink_c,
                lambda: ("partials-shrink", self._cap, old_n, n,
                         self._resident_sharded),
            )
        self.grows += 1
        self._n = n

    def _full_reset(self, cluster, snap, keys, n, vkey, ev) -> None:
        """Reseed the cache from this batch's classes and recompute the
        whole store in one dispatch (first sync, struct/shape/vocab
        invalidation, over-fraction delta, periodic resync, growth)."""
        state = self.state
        class_rep = np.asarray(snap.pods.class_rep)
        self._slots = {}
        rows: List[tuple] = []
        for c, k in enumerate(keys):
            if k in self._slots:
                continue
            self._slots[k] = len(rows)
            rows.append(self._spec_row(snap, int(class_rep[c])))
        cap = min(
            max(vb.pad_dim(max(len(rows), 1), self.MIN_SLOTS), self._cap),
            self.MAX_SLOTS,
        )
        specs = self._empty_specs(cap)
        if rows:
            stacked = self._stack_spec_rows(rows, len(rows))
            specs = pops.ClassSpecs(
                valid=_scatter0(specs.valid, stacked.valid),
                name_id=_scatter0(specs.name_id, stacked.name_id),
                has_sel=_scatter0(specs.has_sel, stacked.has_sel),
                sel_ids=_scatter0(specs.sel_ids, stacked.sel_ids),
                sel_op=_scatter0(specs.sel_op, stacked.sel_op),
                sel_slot=_scatter0(specs.sel_slot, stacked.sel_slot),
                sel_tv=_scatter0(specs.sel_tv, stacked.sel_tv),
                tol_bits=_scatter1(specs.tol_bits, stacked.tol_bits),
                tol_all=_scatter1(specs.tol_all, stacked.tol_all),
                port_bits=_scatter0(specs.port_bits, stacked.port_bits),
                pref_ids=_scatter0(specs.pref_ids, stacked.pref_ids),
                pref_op=_scatter0(specs.pref_op, stacked.pref_op),
                pref_slot=_scatter0(specs.pref_slot, stacked.pref_slot),
                pref_valid=_scatter0(specs.pref_valid, stacked.pref_valid),
                pref_weight=_scatter0(
                    specs.pref_weight, stacked.pref_weight
                ),
            )
        self._specs = jax.tree.map(self._put, specs)
        self._store = ev(cluster, self._specs)
        r = int(cluster.allocatable.shape[1])
        retrace.note(
            "partials-eval", ev,
            lambda: ("partials-eval", cap, n, r, self._resident_sharded),
        )
        self._cap = cap
        self._n = n
        self._synced_gen = state.generation
        self._struct_gen = state.struct_generation
        self._vocab_key = vkey
        self._since_full = 0
        self.full_recomputes += 1
        self.recomputed_rows_total += len(rows) * n

    # -- the oracle-parity gate --------------------------------------------

    def verify(self, cluster, snap: schema.Snapshot) -> bool:
        """Recompute every cached slot's row from scratch and compare to
        the resident store — the parity gate the test suite and chaos
        triage drive (not on the hot path).  A mismatch invalidates the
        cache (next sync performs a full recompute) and returns False."""
        if self._store is None or self._specs is None:
            return True
        ev = self._kernels()[0]
        want = jax.device_get(ev(cluster, self._specs))
        got = jax.device_get(self._store)
        for f in pops.PartialsStore._fields:
            w, g = getattr(want, f), getattr(got, f)
            ok = (
                np.array_equal(w, g)
                if f == "sfeas"
                else np.array_equal(w, g, equal_nan=True) and not np.isnan(
                    np.asarray(g)
                ).any()
            )
            if not ok:
                logging.getLogger(__name__).warning(
                    "partials parity gate tripped on %s: forcing full "
                    "recompute", f,
                )
                self.invalidate()
                return False
        return True


def _scatter0(base: np.ndarray, rows: np.ndarray) -> np.ndarray:
    out = np.array(base)
    out[: rows.shape[0]] = rows
    return out


def _scatter1(base: np.ndarray, rows: np.ndarray) -> np.ndarray:
    out = np.array(base)
    out[:, : rows.shape[1]] = rows
    return out
