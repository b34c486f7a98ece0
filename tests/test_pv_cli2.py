"""PV controller (bind/reclaim/repair) + CLI apply/edit/logs.

Reference: pkg/controller/volume/persistentvolume/pv_controller.go
(syncClaim/syncVolume), kubectl apply/edit/logs verb family.
"""

import io
import json
import sys
import time

import pytest

from kubernetes_tpu.api import store as st
from kubernetes_tpu.api import types as api
from kubernetes_tpu.api.server import APIServer
from kubernetes_tpu.cli import main as cli_main
from kubernetes_tpu.controllers import ControllerManager
from kubernetes_tpu.controllers.pvcontroller import PersistentVolumeController
from kubernetes_tpu.testing.wrappers import GI, make_pod


def _wait(cond, timeout=10.0, every=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(every)
    return False


def _pv(name, size_gi=10, sc="standard", reclaim="Retain"):
    return api.PersistentVolume(
        meta=api.ObjectMeta(name=name),
        spec=api.PersistentVolumeSpec(
            capacity={api.STORAGE: size_gi * GI},
            access_modes=["ReadWriteOnce"],
            storage_class_name=sc,
            reclaim_policy=reclaim,
        ),
    )


def _pvc(name, size_gi=5, sc="standard"):
    return api.PersistentVolumeClaim(
        meta=api.ObjectMeta(name=name),
        spec=api.PersistentVolumeClaimSpec(
            access_modes=["ReadWriteOnce"],
            storage_class_name=sc,
            resources={api.STORAGE: size_gi * GI},
        ),
    )


def test_immediate_claim_binds_smallest_fit_and_reclaims():
    store = st.Store()
    mgr = ControllerManager(
        store, controllers=[PersistentVolumeController]
    ).start()
    try:
        store.create(_pv("big", size_gi=100))
        store.create(_pv("small", size_gi=10))
        store.create(_pv("tiny", size_gi=1))
        store.create(_pvc("data", size_gi=5))
        # binds the SMALLEST satisfying volume
        assert _wait(
            lambda: store.get("PersistentVolumeClaim", "data").spec.volume_name
            == "small"
        )
        pv = store.get("PersistentVolume", "small")
        assert pv.spec.claim_ref == "default/data"
        assert pv.status.phase == api.PV_BOUND

        # claim deleted -> Retain policy: volume goes Released, not away
        store.delete("PersistentVolumeClaim", "data")
        assert _wait(
            lambda: store.get("PersistentVolume", "small").status.phase
            == api.PV_RELEASED
        )

        # Delete policy volume disappears with its claim
        store.create(_pv("ephemeral", size_gi=5, reclaim="Delete"))
        store.create(_pvc("scratch", size_gi=2))
        assert _wait(
            lambda: store.get(
                "PersistentVolumeClaim", "scratch"
            ).spec.volume_name == "ephemeral"
        )
        store.delete("PersistentVolumeClaim", "scratch")

        def gone():
            try:
                store.get("PersistentVolume", "ephemeral")
                return False
            except KeyError:
                return True
        assert _wait(gone)
    finally:
        mgr.stop()


def test_claim_created_before_its_volume_binds_when_the_volume_arrives():
    store = st.Store()
    mgr = ControllerManager(
        store, controllers=[PersistentVolumeController]
    ).start()
    try:
        store.create(_pvc("early", size_gi=5))
        store.create(_pv("tiny", size_gi=1))    # too small: still waiting
        assert not _wait(
            lambda: store.get("PersistentVolumeClaim", "early").spec.volume_name,
            timeout=0.3,
        )
        store.create(_pv("late", size_gi=10))
        assert _wait(
            lambda: store.get("PersistentVolumeClaim", "early").spec.volume_name
            == "late"
        )
        assert store.get("PersistentVolume", "late").spec.claim_ref == "default/early"
    finally:
        mgr.stop()


def test_half_bound_repair_and_wfc_left_alone():
    store = st.Store()
    # crash artifact: PV claims the PVC, PVC side never written
    pv = _pv("pv0", size_gi=10)
    pv.spec.claim_ref = "default/data"
    pv.status.phase = api.PV_BOUND
    store.create(pv)
    store.create(_pvc("data", size_gi=5))
    # a WaitForFirstConsumer claim must NOT be touched
    store.create(api.StorageClass(
        meta=api.ObjectMeta(name="wfc", namespace=""),
        provisioner="x", volume_binding_mode=api.VOLUME_BINDING_WAIT,
    ))
    store.create(_pvc("later", size_gi=1, sc="wfc"))
    mgr = ControllerManager(
        store, controllers=[PersistentVolumeController]
    ).start()
    try:
        assert _wait(
            lambda: store.get("PersistentVolumeClaim", "data").spec.volume_name
            == "pv0"
        )
        time.sleep(0.3)
        assert not store.get("PersistentVolumeClaim", "later").spec.volume_name
    finally:
        mgr.stop()


# -- CLI ----------------------------------------------------------------------


def _run_cli(argv):
    out = io.StringIO()
    old = sys.stdout
    sys.stdout = out
    try:
        cli_main(argv)
    finally:
        sys.stdout = old
    return out.getvalue()


@pytest.fixture
def server():
    store = st.Store()
    srv = APIServer(store).start()
    yield store, srv
    srv.stop()


def test_cli_apply_create_then_configure(server, tmp_path):
    store, srv = server
    base = ["--server", srv.url]
    f = tmp_path / "pod.yaml"
    f.write_text(
        "kind: Pod\nmetadata: {name: web, labels: {v: '1'}}\n"
        "spec:\n  containers:\n  - resources: {requests: {cpu: 500m}}\n"
    )
    out = _run_cli(base + ["apply", "-f", str(f)])
    assert "pod/web created" in out
    # second apply with a changed label patches in place
    f.write_text(
        "kind: Pod\nmetadata: {name: web, labels: {v: '2'}}\n"
        "spec:\n  containers:\n  - resources: {requests: {cpu: 500m}}\n"
    )
    out = _run_cli(base + ["apply", "-f", str(f)])
    assert "pod/web configured" in out
    assert store.get("Pod", "web").meta.labels["v"] == "2"


def test_cli_edit_applies_buffer(server, tmp_path, monkeypatch):
    store, srv = server
    store.create(make_pod("web").req(cpu_milli=100).obj())
    # "editor": a script that sets a label in the JSON buffer
    editor = tmp_path / "ed.py"
    editor.write_text(
        "import json, sys\n"
        "p = sys.argv[1]\n"
        "d = json.load(open(p))\n"
        "d['meta']['labels']['edited'] = 'yes'\n"
        "json.dump(d, open(p, 'w'))\n"
    )
    monkeypatch.setenv("EDITOR", f"{sys.executable} {editor}")
    # EDITOR with args: subprocess.run([editor, path]) needs a single
    # executable — wrap via env shim
    import os
    wrapper = tmp_path / "ed.sh"
    wrapper.write_text(f"#!/bin/sh\nexec {sys.executable} {editor} \"$1\"\n")
    os.chmod(wrapper, 0o755)
    monkeypatch.setenv("EDITOR", str(wrapper))
    out = _run_cli(["--server", srv.url, "edit", "pod", "web"])
    assert "edited" in out
    assert store.get("Pod", "web").meta.labels.get("edited") == "yes"


def test_cli_logs_lifecycle(server):
    store, srv = server
    p = make_pod("web").req(cpu_milli=100).obj()
    p.spec.node_name = "n0"
    p.status.phase = "Running"
    p.status.pod_ip = "10.88.0.1"
    p.status.restart_counts = {"c": 2}
    store.create(p)
    store.create(api.Event(
        meta=api.ObjectMeta(name="web.scheduled"),
        involved_object=api.ObjectReference(kind="Pod", name="web"),
        reason="Scheduled", message="assigned default/web to n0",
        type="Normal", last_timestamp=time.time(),
    ))
    out = _run_cli(["--server", srv.url, "logs", "web"])
    assert "Scheduled" in out
    assert "restarts: {'c': 2}" in out
    assert "phase: Running on n0 ip 10.88.0.1" in out


def test_recreated_claim_does_not_inherit_volume():
    """pv_controller.go's claimRef.UID check: a deleted-then-recreated
    same-name PVC must trigger reclaim, not silently inherit the data."""
    store = st.Store()
    mgr = ControllerManager(
        store, controllers=[PersistentVolumeController]
    ).start()
    try:
        store.create(_pv("pv1", size_gi=10, reclaim="Delete"))
        store.create(_pvc("data", size_gi=5))
        assert _wait(
            lambda: store.get("PersistentVolumeClaim", "data").spec.volume_name
            == "pv1"
        )
        # delete + immediately recreate under the same name
        store.delete("PersistentVolumeClaim", "data")
        store.create(_pvc("data", size_gi=5))

        # the Delete-policy volume goes away (new claim has a new uid)
        def pv_gone():
            try:
                store.get("PersistentVolume", "pv1")
                return False
            except KeyError:
                return True
        assert _wait(pv_gone)
        assert store.get(
            "PersistentVolumeClaim", "data"
        ).spec.volume_name != "pv1"
    finally:
        mgr.stop()
