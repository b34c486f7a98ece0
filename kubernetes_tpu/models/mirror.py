"""Device-resident cluster mirror — delta uploads instead of full
snapshots.

The cluster half of a Snapshot (allocatable/requested/label-bits/... —
~98% of the bytes at 50k nodes) changes by a handful of rows per
scheduling step: assumes touch `requested` on the placed nodes, node
add/update/remove touches one row.  Shipping the whole thing to the
device every encode makes per-step host→device traffic O(cluster)
where the change is O(rows touched), and it dominated end-to-end step
latency at 64k padded nodes (the round-3 north-star regression).

This mirror keeps the last-uploaded cluster tensors resident on device
and applies ClusterState's generation-tracked row deltas with jitted
scatter-sets — the device-side completion of the reference's
incremental UpdateSnapshot design (internal/cache/cache.go:185-260:
walk nodes by generation, stop at the first unchanged one).  The node
axis is ELASTIC: a pad-bucket crossing (autoscaler growth or a
post-dwell shrink) resizes the resident arrays IN PLACE — a device-side
pad/concat (or slice) carries every old row over and the new rows'
content rides the ordinary delta scatter, so a bucket crossing costs
O(new rows) host→device, not a full re-upload.  Full re-upload happens
only for genuine identity changes (resource-axis widening —
ClusterState.struct_generation — or invalidate()), for over-fraction
deltas, and as the safety path whenever the incremental resize
declines (sharded↔replicated layout flips, the incremental_grow valve,
injected mirror.grow faults).

Under a device mesh (mesh not None) the resident tensors carry a
NamedSharding over the node axis — the same layout the sharded solvers'
shard_map specs expect (parallel.sharded.CLUSTER_SPECS), so a mesh-mode
solve consumes the mirror without any per-batch resharding.  Row deltas
scatter into the owning shard: the bucketed index/value uploads are
replicated (tiny) and the jitted scatter — pinned to the resident
sharding via out_shardings so the executable key never drifts — lets
GSPMD route each row to its shard.  Struct-generation changes trigger a
full RESHARDED re-upload, exactly like the single-device case.

Row updates are bucketed to powers of two and padded by repeating the
first dirty row (duplicate scatter-set of identical values is a
no-op), so the jit cache stays small and stable.

A sync that is a usage scatter of at most a few hundred rows and
nothing else can skip sync() altogether: plan_rows() says so,
stage_rows() lays the rows out as segments of the encode's one staging
buffer (models/batch_scheduler._packed_device_put), and adopt_rows()
takes the staging program's scattered usage leaves as the resident ones
— one transfer and one program for the mirror, the partials and the pod
tables together, where the scatter here costs four transfers and three
programs (docs/scheduler_loop.md, "The encode's staging").

`resync_total` / `delta_rows_total` / `delta_syncs` count full uploads
and real (unbucketed) scattered rows, on either path —
`scheduler_mirror_resync_total` / `scheduler_mirror_delta_rows` read
them through `stats()`; tests/test_mirror.py holds steady-state
transfer to O(changed rows).  `transfers` / `programs` count the
host→device transfers and device programs the mirror issued (span
`sched.encode.stage` reads their change over one encode).
"""

from __future__ import annotations

import logging
from functools import partial
from typing import Optional, Tuple

import jax
import numpy as np

from ..analysis import epochs, retrace
from ..ops import schema
from ..testing import faults
from ..utils import vocab as vb

# Leaves of ClusterTensors grouped by which mutation family dirties
# them (ClusterState._static_gen / _usage_gen).  taint_bits is handled
# separately: its node axis is axis 1.
_STATIC_LEAVES = (
    "allocatable", "node_valid", "name_id", "label_bits", "topo_ids",
    "image_bits", "slice_id", "torus_coords", "slice_dims", "slice_pos",
)
_USAGE_LEAVES = ("requested", "nonzero_requested", "port_bits")

# Pad-row fill per leaf for the incremental resident grow: MUST match
# ClusterState._alloc's defaults — rows beyond the watermark the host
# never wrote read these values, and the grow carries them on device
# without any host transfer (leaves absent here fill with 0).
_GROW_FILLS = {
    "name_id": -1, "topo_ids": -1, "slice_id": -1, "torus_coords": -1,
    "slice_pos": -1,
}


@jax.jit
def _set_rows(arr, idx, vals):
    return arr.at[idx].set(vals)


@jax.jit
def _set_rows_ax1(arr, idx, vals):
    return arr.at[:, idx].set(vals)


# Elastic node-axis kernels: grow pads default-valued rows onto the
# resident arrays ON DEVICE (one concat per leaf, zero host transfer —
# the O(new rows) content follows through the ordinary delta scatter),
# shrink slices them.  dn / n / fill are static: one executable per
# (leaf shape, transition), reused across repeat crossings.
@partial(jax.jit, static_argnums=(1, 2))
def _grow_rows(arr, dn, fill):
    import jax.numpy as jnp

    pad = jnp.full((dn,) + arr.shape[1:], fill, arr.dtype)
    return jnp.concatenate([arr, pad], axis=0)


@partial(jax.jit, static_argnums=(1, 2))
def _grow_rows_ax1(arr, dn, fill):
    import jax.numpy as jnp

    pad = jnp.full(arr.shape[:1] + (dn,) + arr.shape[2:], fill, arr.dtype)
    return jnp.concatenate([arr, pad], axis=1)


@partial(jax.jit, static_argnums=(1,))
def _shrink_rows(arr, n):
    return arr[:n]


@partial(jax.jit, static_argnums=(1,))
def _shrink_rows_ax1(arr, n):
    return arr[:, :n]


def _pad_idx(idx: np.ndarray, bucket: int) -> np.ndarray:
    out = np.full(bucket, idx[0], dtype=np.int32)
    out[: idx.shape[0]] = idx
    return out


def scatter_usage(cluster: schema.ClusterTensors, idx, vals) -> dict:
    """{usage leaf: `cluster`'s leaf with its row of `vals` (in
    _USAGE_LEAVES order) written at rows `idx`}; an index past the last
    row is dropped.  Traced inside the encode's staging program
    (DeviceClusterMirror.stage_rows lays out `idx` and `vals`,
    adopt_rows takes the result)."""
    return {
        leaf: getattr(cluster, leaf).at[idx].set(v, mode="drop")
        for leaf, v in zip(_USAGE_LEAVES, vals)
    }


def pad_rows(idx: np.ndarray, n: int, rows: int) -> np.ndarray:
    """`idx` padded to `rows` with n, one past the last row of an n-row
    bucket: the staging program's scatters drop those writes."""
    out = np.full(rows, n, dtype=np.int32)
    out[: idx.shape[0]] = idx
    return out


class DeviceClusterMirror:
    """One consumer's device copy of a ClusterState's cluster tensors.

    Each TPUBatchScheduler owns its own mirror; several schedulers
    (profiles) sharing one ClusterState sync independently through the
    state's generation counters — the same protocol the reference uses
    for its per-snapshot generation watermark."""

    # Deltas touching more rows than this fraction of the cluster fall
    # back to a full upload: the scatter machinery stops paying for
    # itself once most rows move (e.g. right after a bulk node load).
    FULL_SYNC_FRACTION = 0.5

    def __init__(self, state: schema.ClusterState, mesh=None):
        self.state = state
        self.mesh = mesh
        # graftcoh-registered device-resident buffer (docs/static_analysis.md)
        self._dev: Optional[schema.ClusterTensors] = None  # resident: fault=mirror.grow chaos=NODE_CHURN_SEEDS oracle=full-resync
        self._synced_gen = 0
        self._struct_gen = 0
        self._shape: Optional[Tuple] = None
        # epoch stamp of the resident buffer (analysis/epochs.py): the
        # GRAFTLINT_COHERENCE auditor compares it against the state's
        # CURRENT generations at consume time.  buffer id is the
        # lineage token: minted per full upload, carried by delta
        # scatters and in-place grows, restored by rollback.
        self._epoch: Optional[epochs.EpochStamp] = None
        self._buffer_id = 0
        # invalidation fence: a rollback() whose bookmark predates a
        # later invalidate() must NOT resurrect the dropped buffer
        # (leadership reconcile / the finalize_pending heal wire
        # invalidate deliberately; a mis-speculation rollback racing
        # them would restore exactly the state they dropped — a
        # graftcoh true positive, regression-pinned in
        # tests/test_coherence.py)
        self._inval_gen = 0
        # transfer accounting (read by the scheduler's gauges through
        # stats()); mutated under the cache lock
        # — sync() is called inside encode_pending's locked section
        self.resync_total = 0      # full uploads (first sync included)
        self.delta_rows_total = 0  # real dirty rows scattered
        self.delta_syncs = 0       # syncs served by the delta path
        # elastic node axis (docs/scheduler_loop.md): pad-bucket
        # crossings absorbed IN PLACE — a device-side pad/concat (grow)
        # or slice (shrink) carries the old resident rows over, and the
        # new rows' content rides the ordinary delta scatter.  Mirrored
        # into scheduler_mirror_grow_total / scheduler_mirror_grow_rows.
        self.grow_syncs = 0        # in-place resident grows/shrinks
        self.grow_rows_total = 0   # axis rows added without a re-upload
        # host→device transfers and device programs issued, whatever the
        # path (span sched.encode.stage: their change over one encode)
        self.transfers = 0
        self.programs = 0
        # safety valve: False restores the pre-elastic behavior — every
        # shape change performs the full (RESHARDED under a mesh)
        # re-upload; the parity oracle tests drive it
        self.incremental_grow = True
        # whether the resident copy is node-axis sharded (False when no
        # mesh, or when the padded bucket doesn't split across it — the
        # same batches TPUBatchScheduler solves single-chip)
        self._resident_sharded = False
        if mesh is None:
            self._shardings = None
            self._set = _set_rows
            self._set_ax1 = _set_rows_ax1
            self._grow = _grow_rows
            self._grow_ax1 = _grow_rows_ax1
            self._shrink = _shrink_rows
            self._shrink_ax1 = _shrink_rows_ax1
            self._put_small = jax.device_put
        else:
            from jax.sharding import NamedSharding, PartitionSpec as P

            axis = mesh.axis_names[0]
            row_sh = NamedSharding(mesh, P(axis))          # node axis = dim 0
            ax1_sh = NamedSharding(mesh, P(None, axis))    # taint_bits
            rep_sh = NamedSharding(mesh, P())
            self._shardings = schema.ClusterTensors(
                **{
                    f: (ax1_sh if f == "taint_bits" else row_sh)
                    for f in schema.ClusterTensors._fields
                }
            )
            # replicated layout for buckets the mesh can't split (the
            # single-chip fallback batches): still mesh-committed so
            # every consumer sees one device set
            self._rep_shardings = schema.ClusterTensors(
                **{f: rep_sh for f in schema.ClusterTensors._fields}
            )
            # out_shardings pin the scatter results to the resident
            # layout: without them GSPMD may pick a different output
            # sharding, and a sharding flip is a fresh executable key on
            # the NEXT delta — a steady-state recompile
            self._set = jax.jit(
                lambda a, i, v: a.at[i].set(v), out_shardings=row_sh
            )
            self._set_ax1 = jax.jit(
                lambda a, i, v: a.at[:, i].set(v), out_shardings=ax1_sh
            )
            # sharded twins of the elastic-axis kernels: the grown /
            # shrunk resident keeps the NamedSharding node-axis layout
            # (out_shardings pin it — GSPMD re-pads each shard in place,
            # no host round-trip, and the executable key never drifts)
            import jax.numpy as jnp

            self._grow = jax.jit(
                lambda a, dn, fill: jnp.concatenate(
                    [a, jnp.full((dn,) + a.shape[1:], fill, a.dtype)], axis=0
                ),
                static_argnums=(1, 2), out_shardings=row_sh,
            )
            self._grow_ax1 = jax.jit(
                lambda a, dn, fill: jnp.concatenate(
                    [a, jnp.full(a.shape[:1] + (dn,) + a.shape[2:], fill,
                                 a.dtype)],
                    axis=1,
                ),
                static_argnums=(1, 2), out_shardings=ax1_sh,
            )
            self._shrink = jax.jit(
                lambda a, n: a[:n], static_argnums=(1,), out_shardings=row_sh
            )
            self._shrink_ax1 = jax.jit(
                lambda a, n: a[:, :n], static_argnums=(1,),
                out_shardings=ax1_sh,
            )
            # index/value uploads replicate over the mesh: they are a
            # few KB, and replication keeps every jit operand on the
            # same device set (mixing single-device-committed arrays
            # with mesh-committed ones is a placement error)
            self._put_small = lambda x: jax.device_put(x, rep_sh)

    def sync(self) -> schema.ClusterTensors:
        """Return device-resident cluster tensors matching the state's
        current contents.  Caller must hold the cache lock (the host
        arrays are read here)."""
        state = self.state
        host = state.tensors()
        shape = tuple(np.shape(leaf) for leaf in host)
        n = host.allocatable.shape[0]
        stale_struct = (
            self._dev is None
            or self._struct_gen < state.struct_generation
        )
        shape_moved = not stale_struct and self._shape != shape
        if (
            not stale_struct
            and not shape_moved
            and self._synced_gen == state.generation
        ):
            return self._dev
        if stale_struct:
            dev = self._full_upload(host)
        else:
            static_idx, usage_idx = state.dirty_rows(self._synced_gen, n)
            if (
                static_idx.shape[0] + usage_idx.shape[0]
                > self.FULL_SYNC_FRACTION * n
            ):
                dev = self._full_upload(host)
            elif shape_moved:
                # elastic node axis: the padded bucket moved while row
                # identity held (growth is no longer a struct event) —
                # resize the resident arrays in place and let the delta
                # scatter carry the changed rows' content: O(new rows)
                # host→device, not a full re-upload
                resized = self._resize_resident(shape)
                if resized is None:
                    dev = self._full_upload(host)  # the safety path
                else:
                    self._dev = resized
                    dev = self._apply_deltas(host, static_idx, usage_idx)
            else:
                dev = self._apply_deltas(host, static_idx, usage_idx)
        self._dev = dev
        self._synced_gen = state.generation
        self._struct_gen = state.struct_generation
        self._shape = shape
        self._epoch = epochs.EpochStamp(
            "mirror", self._struct_gen, None, self._synced_gen,
            self._buffer_id,
        )
        return dev

    def plan_rows(
        self, rows: int
    ) -> Optional[Tuple[schema.ClusterTensors, np.ndarray]]:
        """(host tensors, dirty usage rows) when sync() would scatter at
        most `rows` usage rows and do nothing else, so that the encode's
        staging program can carry them instead; None when sync() has more
        to do (a first or full upload, an elastic resize, dirty static
        rows, more rows) or the resident copy is sharded over a mesh.
        No device work, no fault point, no state change.  Caller holds
        the cache lock (sync() contract)."""
        state = self.state
        if (
            self.mesh is not None
            or self._dev is None
            or self._struct_gen < state.struct_generation
        ):
            return None
        host = state.tensors()
        if tuple(np.shape(leaf) for leaf in host) != self._shape:
            return None
        if self._synced_gen == state.generation:
            return host, np.zeros(0, dtype=np.int32)
        n = host.allocatable.shape[0]
        static_idx, usage_idx = state.dirty_rows(self._synced_gen, n)
        if static_idx.shape[0] or usage_idx.shape[0] > min(
            rows, self.FULL_SYNC_FRACTION * n
        ):
            return None
        return host, usage_idx

    def resident(self) -> Optional[schema.ClusterTensors]:
        """The resident tensors as they stand, unsynced: what the staging
        program scatters plan_rows()' rows into."""
        return self._dev

    @staticmethod
    def stage_rows(
        host: schema.ClusterTensors, usage_idx: np.ndarray, rows: int
    ) -> tuple:
        """The staging buffer's mirror segments: the dirty rows' indices
        padded to `rows` with n (one past the last row: the staging
        program's scatter drops them), then each usage leaf's host rows
        at those indices, zero-padded."""
        k = usage_idx.shape[0]
        segs = [pad_rows(usage_idx, host.allocatable.shape[0], rows)]
        for leaf in _USAGE_LEAVES:
            src = np.asarray(getattr(host, leaf))
            vals = np.zeros((rows,) + src.shape[1:], dtype=src.dtype)
            vals[:k] = src[usage_idx]
            segs.append(vals)
        return tuple(segs)

    def adopt_rows(self, usage: dict, rows: int) -> None:
        """Take the staging program's usage leaves (scatter_usage) as the
        resident ones: what sync() leaves after a delta of `rows` usage
        rows, counters included.  The old leaves are not donated: a
        speculation_point() bookmark may hold them."""
        state = self.state
        if self._synced_gen == state.generation:
            return  # sync() would have returned at once
        if rows:
            self._dev = self._dev._replace(**usage)
        self.delta_syncs += 1
        self.delta_rows_total += int(rows)
        self._synced_gen = state.generation
        self._epoch = epochs.EpochStamp(
            "mirror", self._struct_gen, None, self._synced_gen,
            self._buffer_id,
        )

    def _resize_resident(self, shape) -> Optional[schema.ClusterTensors]:
        """Grow (device-side pad) or shrink (device-side slice) the
        resident tensors to the new padded bucket, preserving every
        carried row — one on-device copy per leaf, zero host transfer.
        Returns None to decline (layout flip under a mesh, a non-node
        axis moved, the safety valve, or an injected mirror.grow
        fault), in which case the caller takes the full (RESHARDED)
        re-upload safety path."""
        old_n = self._shape[0][0]
        new_n = shape[0][0]
        if not self.incremental_grow or new_n == old_n:
            return None
        # only the node axis may differ: every other dim change is an
        # identity change the struct generation should have declared
        for f, old_s, new_s in zip(
            schema.ClusterTensors._fields, self._shape, shape
        ):
            ax = 1 if f == "taint_bits" else 0
            if (
                old_s[:ax] + old_s[ax + 1:] != new_s[:ax] + new_s[ax + 1:]
                or old_s[ax] != old_n or new_s[ax] != new_n
            ):
                return None
        if self._shardings is not None:
            sharded = new_n % self.mesh.devices.size == 0
            if sharded != self._resident_sharded:
                return None  # layout flip: full RESHARDED re-upload
        try:
            act = faults.fire("mirror.grow", old_n=old_n, new_n=new_n)
        except Exception:  # noqa: BLE001 — injected grow fault: contained
            logging.getLogger(__name__).warning(
                "mirror.grow fault injected; falling back to full resync"
            )
            return None
        grow, grow1, shrink, shrink1 = (
            self._grow, self._grow_ax1, self._shrink, self._shrink_ax1,
        )
        if self._shardings is not None and not self._resident_sharded:
            # replicated small-bucket resident: the pinned-sharding
            # kernels don't apply (models/mirror._apply_deltas, same)
            grow, grow1 = _grow_rows, _grow_rows_ax1
            shrink, shrink1 = _shrink_rows, _shrink_rows_ax1
        updates = {}
        dn = new_n - old_n
        for f in schema.ClusterTensors._fields:
            leaf = getattr(self._dev, f)
            if f == "taint_bits":
                updates[f] = (
                    grow1(leaf, dn, _GROW_FILLS.get(f, 0))
                    if dn > 0 else shrink1(leaf, new_n)
                )
            else:
                updates[f] = (
                    grow(leaf, dn, _GROW_FILLS.get(f, 0))
                    if dn > 0 else shrink(leaf, new_n)
                )
        self.grow_syncs += 1
        self.programs += len(updates)
        if dn > 0:
            self.grow_rows_total += dn
        kernel = grow if dn > 0 else shrink
        retrace.note(
            "mirror-grow", kernel,
            lambda: ("mirror-grow", old_n, new_n, self._resident_sharded),
        )
        dev = schema.ClusterTensors(**updates)
        if act == faults.CORRUPT:
            # poison the carried rows so the solve's fit scores go
            # (inf - req) / inf = NaN: the decode health check trips and
            # the retry's mirror invalidation heals via full resync —
            # the elastic axis's parity-gate wire (chaos seeds 800-804)
            import jax.numpy as jnp

            dev = dev._replace(
                allocatable=jnp.full_like(dev.allocatable, jnp.inf)
            )
            self.programs += 1
        return dev

    def stats(self) -> dict:
        return {
            "resync_total": self.resync_total,
            "delta_rows_total": self.delta_rows_total,
            "delta_syncs": self.delta_syncs,
            "grow_syncs": self.grow_syncs,
            "grow_rows_total": self.grow_rows_total,
        }

    def epoch(self) -> Optional[epochs.EpochStamp]:
        """The resident buffer's epoch stamp (None when invalidated or
        never synced) — read by the GRAFTLINT_COHERENCE auditor and by
        the PartialsCache's lineage stamping."""
        return self._epoch

    def speculation_point(self) -> tuple:
        """Bookmark the resident buffer for a SPECULATIVE encode: the
        current device tensors + generations.  Device arrays are
        immutable, so holding the reference IS the double buffer — a
        later sync() scatters into fresh arrays while any in-flight
        solve keeps reading the bookmarked ones.  Caller holds the
        cache lock (same contract as sync())."""
        return (
            self._dev, self._synced_gen, self._struct_gen, self._shape,
            self._resident_sharded, self._epoch, self._buffer_id,
            self._inval_gen,
        )

    def rollback(self, point: tuple) -> None:
        """Restore the resident buffer to a speculation_point() bookmark
        — the speculative batch was invalidated (the wave it solved over
        failed or was fenced), so the deltas synced for it are dropped
        whole instead of layering the forget-restore scatters on top.
        Always safe: ClusterState.dirty_rows(synced_gen) covers EVERY
        row dirtied since the bookmarked generation, so the next sync()
        re-scatters anything the dropped buffer carried (or performs a
        full upload when the struct generation moved past the
        bookmark).  Caller holds the cache lock.

        EXCEPT after an intervening invalidate(): a bookmark taken
        before a leadership reconcile or the finalize_pending heal wire
        dropped the resident must not resurrect the dropped buffer —
        the invalidation fence keeps the mirror invalidated and the
        next sync() performs the full re-upload instead."""
        (
            dev, synced_gen, struct_gen, shape, resident_sharded,
            epoch_stamp, buffer_id, inval_gen,
        ) = point
        if inval_gen != self._inval_gen:
            epochs.note_rollback_blocked("mirror")
            return
        self._dev = dev
        self._synced_gen = synced_gen
        self._struct_gen = struct_gen
        self._shape = shape
        self._resident_sharded = resident_sharded
        self._epoch = epoch_stamp
        self._buffer_id = buffer_id

    def invalidate(self) -> None:
        """Drop the resident copy so the next sync() performs a full
        (RESHARDED, under a mesh) re-upload.  Leadership reconciliation
        calls this on takeover/restart: the delta protocol assumes the
        resident tensors match some past generation of THIS state's
        history, which a rebuilt or reconciled cache no longer
        guarantees.  Caller holds the cache lock (same contract as
        sync())."""
        self._dev = None
        self._synced_gen = 0
        self._struct_gen = 0
        self._shape = None
        self._epoch = None
        self._buffer_id = 0
        self._inval_gen += 1

    def _full_upload(self, host: schema.ClusterTensors) -> schema.ClusterTensors:
        # host-copy before device_put: on the CPU backend device_put can
        # zero-copy a numpy view, which would alias live cache state
        # (see TPUBatchScheduler.encode_pending's aliasing note)
        self.resync_total += 1
        self.transfers += len(host)
        self._buffer_id = epochs.fresh_buffer_id()
        copied = jax.tree.map(np.array, host)
        if self._shardings is None:
            return jax.device_put(copied)
        # mesh: the upload lands already sharded over the node axis;
        # buckets smaller than the mesh replicate instead (they solve
        # single-chip anyway — TPUBatchScheduler._sharded_ok)
        self._resident_sharded = (
            copied.allocatable.shape[0] % self.mesh.devices.size == 0
        )
        return jax.device_put(
            copied,
            self._shardings if self._resident_sharded
            else self._rep_shardings,
        )

    def _setters(self):
        """(row scatter, axis-1 scatter) for the resident layout."""
        if self._shardings is not None and not self._resident_sharded:
            # replicated resident copy (bucket smaller than the mesh):
            # the pinned-sharding scatters don't apply — use the plain
            # ones; operands are all mesh-replicated so placement agrees
            return _set_rows, _set_rows_ax1
        return self._set, self._set_ax1

    def warm_usage_buckets(self) -> None:
        """Build or load the usage-leaf scatter of every dirty-row bucket
        the delta path serves (1, 2, 4, ... rows, up to the share of the
        cluster past which sync() re-uploads whole), against the
        resident tensors: Scheduler.warmup's share of the mirror, so
        that the first delta of each size does not pay for an executable
        inside a cycle.  Not only up to a batch: a bind wave dirties at
        most a batch of rows, but pods that LEAVE between two encodes
        (completions, evictions; ClusterState.remove_pod) dirty a row
        each however many they are, and their scatter is this one.  The
        results are dropped: nothing resident changes.  Caller holds the
        cache lock and has synced (a mirror without residents has
        nothing to warm)."""
        dev = self._dev
        if dev is None:
            return
        host = self.state.tensors()
        set_rows, _ = self._setters()
        top = int(self.FULL_SYNC_FRACTION * host.allocatable.shape[0])
        bucket = 1
        while bucket <= top:
            self._scatter_usage(
                dev, host, np.zeros(bucket, dtype=np.int32), set_rows
            )
            bucket *= 2

    def _scatter_usage(self, base, host, pidx: np.ndarray, set_rows) -> dict:
        """{leaf: resident leaf with the host's rows `pidx` written in}
        for the usage family."""
        self.transfers += 1 + len(_USAGE_LEAVES)
        self.programs += len(_USAGE_LEAVES)
        idx_dev = self._put_small(pidx)
        return {
            leaf: set_rows(
                getattr(base, leaf), idx_dev,
                self._put_small(np.asarray(getattr(host, leaf))[pidx]),
            )
            for leaf in _USAGE_LEAVES
        }

    def _apply_deltas(
        self,
        host: schema.ClusterTensors,
        static_idx: np.ndarray,
        usage_idx: np.ndarray,
    ) -> schema.ClusterTensors:
        dev = self._dev
        self.delta_syncs += 1
        self.delta_rows_total += int(static_idx.shape[0] + usage_idx.shape[0])
        set_rows, set_ax1 = self._setters()
        updates = {}
        if static_idx.shape[0]:
            bucket = vb.pad_dim(static_idx.shape[0], 1)
            pidx = _pad_idx(static_idx, bucket)
            self.transfers += 2 + len(_STATIC_LEAVES)
            self.programs += 1 + len(_STATIC_LEAVES)
            idx_dev = self._put_small(pidx)
            for leaf in _STATIC_LEAVES:
                vals = self._put_small(np.asarray(getattr(host, leaf))[pidx])
                updates[leaf] = set_rows(getattr(dev, leaf), idx_dev, vals)
            tvals = self._put_small(np.asarray(host.taint_bits)[:, pidx])
            updates["taint_bits"] = set_ax1(
                dev.taint_bits, idx_dev, tvals
            )
        if usage_idx.shape[0]:
            bucket = vb.pad_dim(usage_idx.shape[0], 1)
            base = dev._replace(**updates) if updates else dev
            updates.update(self._scatter_usage(
                base, host, _pad_idx(usage_idx, bucket), set_rows
            ))
        return dev._replace(**updates) if updates else dev
