"""PersistentVolume controller: the binding/reclaim reconciler.

Reference: pkg/controller/volume/persistentvolume/pv_controller.go —
syncClaim (bind pending Immediate-mode claims to matching Available
volumes) and syncVolume (repair half-bound pairs; apply the reclaim
policy when a bound claim disappears).  The SCHEDULER owns
WaitForFirstConsumer binding (scheduler/volumebinding.py — topology
decides there); this controller owns everything that must work without
a pod: Immediate-mode claims bind as soon as a volume matches, crashed
half-bindings heal, and released volumes are retained or deleted per
their reclaim policy.
"""

from __future__ import annotations

from typing import List, Optional

from ..api import store as st
from ..api import types as api
from .base import Controller, split_key


class PersistentVolumeController(Controller):
    KIND = "PersistentVolume"
    NAME = "PersistentVolumeBinder"

    def register(self) -> None:
        self.informers.informer("PersistentVolume").add_handler(self._on_pv)
        self.informers.informer("PersistentVolumeClaim").add_handler(
            self._on_pvc
        )

    def _on_pv(self, typ: str, pv, old) -> None:
        if typ == st.DELETED:
            return
        self.queue.add(f"pv||{pv.meta.name}")
        if not pv.spec.claim_ref and pv.status.phase == api.PV_AVAILABLE:
            # a volume a waiting claim may have been created before:
            # nothing else would sync that claim again
            for pvc in self.informers.informer(
                "PersistentVolumeClaim"
            ).list():
                if not pvc.spec.volume_name:
                    self.queue.add(
                        f"pvc|{pvc.meta.namespace}|{pvc.meta.name}"
                    )

    def _on_pvc(self, typ: str, pvc, old) -> None:
        if typ == st.DELETED:
            if pvc.spec.volume_name:
                # the bound volume must react (reclaim)
                self.queue.add(f"pv||{pvc.spec.volume_name}")
            else:
                # half-bound death: a PV may hold a dangling claim_ref
                # to this claim with the PVC side never written — scan
                # for it or a Delete-policy volume leaks forever
                self.queue.add(
                    f"scan|{pvc.meta.namespace}|{pvc.meta.name}"
                )
            return
        self.queue.add(f"pvc|{pvc.meta.namespace}|{pvc.meta.name}")

    def sync(self, key: str) -> None:
        what, namespace, name = key.split("|", 2)
        if what == "pvc":
            self._sync_claim(namespace, name)
        elif what == "scan":
            claim_key = f"{namespace}/{name}"
            for pv in self.informers.informer("PersistentVolume").list():
                if pv.spec.claim_ref == claim_key:
                    self.queue.add(f"pv||{pv.meta.name}")
        else:
            self._sync_volume(name)

    # -- syncClaim ----------------------------------------------------------

    def _binding_mode(self, pvc) -> str:
        sc = next(
            (
                c
                for c in self.informers.informer("StorageClass").list()
                if c.meta.name == pvc.spec.storage_class_name
            ),
            None,
        )
        return sc.volume_binding_mode if sc else api.VOLUME_BINDING_IMMEDIATE

    def _sync_claim(self, namespace: str, name: str) -> None:
        try:
            pvc = self.store.get("PersistentVolumeClaim", name, namespace)
        except st.NotFound:
            return
        if pvc.spec.volume_name:
            if pvc.status.phase != api.PVC_BOUND:
                pvc.status.phase = api.PVC_BOUND
                self.store.update(pvc, force=True)
            return
        if self._binding_mode(pvc) == api.VOLUME_BINDING_WAIT:
            return  # the scheduler binds at pod placement time
        key = f"{namespace}/{name}"
        pv = self._match(pvc, key)
        if pv is None:
            return
        # bind PV side first, then PVC (the same order prebind uses; a
        # crash in between heals via _sync_volume's repair half)
        fresh_pv = self.store.get("PersistentVolume", pv.meta.name)
        if fresh_pv.spec.claim_ref and fresh_pv.spec.claim_ref != key:
            return  # raced with another binder; resync will re-match
        fresh_pv.spec.claim_ref = key
        fresh_pv.spec.claim_uid = pvc.meta.uid
        fresh_pv.status.phase = api.PV_BOUND
        self.store.update(fresh_pv)
        pvc.spec.volume_name = pv.meta.name
        pvc.status.phase = api.PVC_BOUND
        self.store.update(pvc, force=True)

    def _match(self, pvc, claim_key: str) -> Optional[api.PersistentVolume]:
        """findMatchingVolume: smallest Available PV satisfying class,
        modes, and size (or one already claimRef'd to this PVC — the
        half-bound repair).  Read from the STORE, not the volume
        informer: the claim's event can overtake the events of volumes
        created before it, and a match over the volumes the informer
        has seen so far binds a larger volume than the smallest, for
        good."""
        want_modes = set(pvc.spec.access_modes)
        best = None
        for pv in self.store.list("PersistentVolume")[0]:
            if pv.spec.claim_ref == claim_key:
                return pv  # finish the half-bound pair
            if pv.spec.claim_ref or pv.status.phase != api.PV_AVAILABLE:
                continue
            if pv.spec.storage_class_name != pvc.spec.storage_class_name:
                continue
            if not want_modes.issubset(set(pv.spec.access_modes)):
                continue
            if pv.storage() < pvc.requested_storage():
                continue
            if best is None or pv.storage() < best.storage():
                best = pv
        return best

    # -- syncVolume ---------------------------------------------------------

    def _sync_volume(self, name: str) -> None:
        try:
            pv = self.store.get("PersistentVolume", name)
        except st.NotFound:
            return
        ref = pv.spec.claim_ref
        if not ref:
            return
        ns, _, claim_name = ref.partition("/")
        pvc = None
        try:
            pvc = self.store.get("PersistentVolumeClaim", claim_name, ns)
        except st.NotFound:
            pass
        if pvc is not None and pv.spec.claim_uid and (
            pvc.meta.uid != pv.spec.claim_uid
        ):
            # same NAME, different claim: the bound claim was deleted and
            # recreated — the new claim must not inherit the volume
            pvc = None
        if pvc is None:
            # claim gone: apply the reclaim policy
            if pv.spec.reclaim_policy == "Delete":
                try:
                    self.store.delete("PersistentVolume", name)
                except st.NotFound:
                    pass
            elif pv.status.phase != api.PV_RELEASED:
                pv.status.phase = api.PV_RELEASED
                self.store.update(pv, force=True)
            return
        if not pvc.spec.volume_name:
            # half-bound (crash between the two binding writes): finish
            # the PVC side (syncVolume's repair)
            pvc.spec.volume_name = name
            pvc.status.phase = api.PVC_BOUND
            self.store.update(pvc, force=True)
        if pv.status.phase != api.PV_BOUND:
            pv.status.phase = api.PV_BOUND
            self.store.update(pv, force=True)
