"""Fleet-scale serving plane regressions (fast tier-1 surface).

Covers the serving-path hardening contracts: the per-watcher HTTP
write deadline (a stalled TCP client trips Expired and frees the
handler thread — it never pins it), read-replica API servers over one
shared store (kill/restart leaves no watcher wedged), the multiplexed
watch client's failover, and the serving-plane gauge mirror the
scheduler reads each cycle.  The randomized chaos-grade versions live
in tests/test_chaos.py (SERVING_SEEDS, `make chaos-serving`).
"""

import socket
import time

import pytest

from kubernetes_tpu.api import store as st
from kubernetes_tpu.api.server import APIServer, APIServerReplicaSet
from kubernetes_tpu.client.rest import RestClient
from kubernetes_tpu.client.watchmux import HttpWatchMux
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.testing import faults
from kubernetes_tpu.testing.wrappers import GI, MI, make_node, make_pod


@pytest.fixture(autouse=True)
def _disarmed():
    yield
    faults.disarm()


def _wait_for(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return False


# -- per-watcher write deadline ----------------------------------------------


def test_watch_write_deadline_expires_stalled_client():
    """A watch client that stops READING (socket deliberately unread,
    tiny buffers) must not pin the handler thread: the per-watcher
    write deadline trips, the stall is counted, the watch expires
    (watch_expired_total) and the handler thread is freed."""
    store = st.Store()
    srv = APIServer(
        store, watch_write_deadline=1.0, watch_sndbuf=4096
    ).start()
    try:
        expired0 = store.watch_stats()["watch_expired_total"]
        host, port = srv.httpd.server_address[:2]
        sock = socket.create_connection((host, port), timeout=5)
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
        sock.sendall(
            b"GET /api/v1/watch/Pod HTTP/1.1\r\n"
            b"Host: x\r\nAccept: application/json\r\n\r\n"
        )
        # the stream is live: the handler thread is inside _watch now
        assert _wait_for(lambda: srv.httpd.active_handlers() >= 1)
        # flood events the client never reads — kernel buffers fill,
        # the next frame write blocks, and the 1s deadline trips
        for i in range(400):
            store.create(make_pod(f"flood-{i}").req(
                cpu_milli=100, mem=8 * MI
            ).obj())
        assert _wait_for(
            lambda: srv.httpd.watch_write_stalls_total >= 1, timeout=30
        ), "write deadline never tripped"
        assert srv.watch_write_stalls_total >= 1
        # the watch expired (the consumer would relist on reconnect)
        assert _wait_for(
            lambda: store.watch_stats()["watch_expired_total"] > expired0
        )
        # and the handler thread is FREED, not pinned by the dead client
        assert _wait_for(
            lambda: srv.httpd.active_handlers() == 0, timeout=10
        ), "handler thread still pinned by the stalled client"
        sock.close()
        # the store-side registration is gone too
        assert _wait_for(
            lambda: sum(len(v) for v in store._watchers.values()) == 0
        )
    finally:
        srv.stop()


def test_watch_survives_without_deadline_pressure():
    """Control case: a NORMALLY consuming client under the same tiny
    deadline never trips it — the deadline only fires on stalls."""
    store = st.Store()
    srv = APIServer(store, watch_write_deadline=1.0).start()
    try:
        client = RestClient(srv.url)
        store.create(make_pod("p0").obj())
        gen = client.watch("Pod", from_rv=0)  # ring replay delivers p0
        typ, obj, rv = next(gen)
        assert (typ, obj.meta.name) == ("ADDED", "p0")
        time.sleep(1.5)  # a few bookmark intervals pass
        store.create(make_pod("p1").obj())
        typ, obj, rv = next(gen)
        assert obj.meta.name == "p1"
        gen.close()
        assert srv.watch_write_stalls_total == 0
    finally:
        srv.stop()


# -- read-replica API servers ------------------------------------------------


def test_replica_set_shares_store_and_gate():
    store = st.Store()
    plane = APIServerReplicaSet(store, replicas=3)
    try:
        urls = plane.urls()
        assert len(urls) == 3 and len(set(urls)) == 3
        # one shared store: a write through any replica is read from all
        RestClient(urls[0]).create(make_pod("p").obj())
        for u in urls:
            assert RestClient(u).get("Pod", "p").meta.name == "p"
        # one shared APF gate across replicas
        handlers = {s.httpd.RequestHandlerClass.apf for s in plane.servers()}
        assert len(handlers) == 1
        # the store back-reference the scheduler mirror derefs
        assert store.serving_plane() is plane
    finally:
        plane.stop()


def test_replica_kill_restart_leaves_no_watcher_wedged():
    """kill() severs a replica's live connections like a process death:
    a blocking watch client on the dead replica unblocks promptly
    (Expired/connection error — not a hang), no handler thread stays
    pinned, and a restarted instance serves fresh watches."""
    import threading

    store = st.Store()
    plane = APIServerReplicaSet(store, replicas=2)
    try:
        dead_url = plane.urls()[0]
        outcome = []

        def consume():
            client = RestClient(dead_url, timeout=5)
            try:
                for _ in client.watch("Pod"):
                    pass
                outcome.append("ended")
            except Exception as e:  # Expired or a connection error
                outcome.append(type(e).__name__)

        t = threading.Thread(target=consume, daemon=True)
        t.start()
        assert _wait_for(lambda: plane.active_handlers() >= 1)
        plane.kill(0)
        t.join(timeout=10)
        assert not t.is_alive(), "watch client wedged after replica kill"
        assert outcome, "consumer never returned"
        assert _wait_for(lambda: plane.active_handlers() == 0)
        assert plane.serving_stats()["replica_failovers_total"] == 1
        # the fresh instance serves the same shared store
        srv = plane.restart(0)
        store.create(make_pod("after").obj())
        assert RestClient(srv.url).get("Pod", "after").meta.name == "after"
        gen = RestClient(srv.url).watch("Pod", from_rv=0)
        typ, obj, rv = next(gen)
        gen.close()
        assert typ == "ADDED"
    finally:
        plane.stop()


def test_mux_informers_failover_across_replica_kill():
    """The multiplexed watch client: informers spread over the replica
    set fail over on a kill, keep delivering (rv-monotonic per shard
    segment), and none ends up wedged."""
    store = st.Store()
    plane = APIServerReplicaSet(store, replicas=2)
    mux = HttpWatchMux(plane.urls(), threads=2)
    try:
        infs = [mux.add_informer("Pod") for _ in range(8)]
        mux.start()
        assert _wait_for(lambda: all(i.synced for i in infs))
        cli = RestClient(plane.urls()[0])
        for i in range(10):
            cli.create(make_pod(f"a-{i}").obj())
        assert _wait_for(
            lambda: all(len(i.cache) == 10 for i in infs), timeout=15
        )
        plane.kill(0)
        cli = RestClient(plane.urls()[0])  # the survivor
        for i in range(10, 20):
            cli.create(make_pod(f"a-{i}").obj())
        assert _wait_for(
            lambda: all(len(i.cache) == 20 for i in infs), timeout=20
        ), "informer wedged after replica kill"
        assert sum(i.failovers for i in infs) >= 1
        assert mux.violations() == []
    finally:
        mux.stop()
        plane.stop()


def test_mux_informer_that_listed_an_empty_store_watches_from_rv_zero():
    """A list of an empty store reads rv 0; what is written between that
    list and the server's taking the watch must still arrive (the watch
    asked "from now" for a falsy rv and lost it: the wedge behind the
    fail-over test's rare failures under load)."""
    store = st.Store()
    plane = APIServerReplicaSet(store, replicas=1)
    mux = HttpWatchMux(plane.urls(), threads=1)
    try:
        inf = mux.add_informer("Pod")
        stream = mux._streams[0]
        mux._relist(stream)            # the list, by hand: the loop is not running yet
        assert inf.synced and inf.last_rv == 0 and inf.cache == {}
        for i in range(5):             # written before any watch exists
            store.create(make_pod(f"early-{i}").obj())
        mux.start()                    # now the loop connects the watch
        assert _wait_for(lambda: len(inf.cache) == 5), sorted(inf.cache)
        assert mux.violations() == []
    finally:
        mux.stop()
        plane.stop()


# -- the scheduler's serving-plane mirror ------------------------------------


def test_note_scheduler_drives_adaptive_gate():
    store = st.Store()
    plane = APIServerReplicaSet(store, replicas=1, recover_after=2)
    try:
        full = plane.apf.seats_current()
        assert plane.note_scheduler(2) == 2
        stats = plane.serving_stats()
        assert stats["apf_seats_current"] < full
        # hysteresis: two calm cycles per step down
        assert plane.note_scheduler(0) == 2
        assert plane.note_scheduler(0) == 1
        assert plane.note_scheduler(0) == 1
        assert plane.note_scheduler(0) == 0
        assert plane.serving_stats()["apf_seats_current"] == full
    finally:
        plane.stop()


def test_scheduler_cycle_mirrors_serving_gauges():
    """A real scheduler cycle dereferences store.serving_plane, feeds
    the adaptive controller, and mirrors the four serving gauges into
    its Registry."""
    store = st.Store()
    plane = APIServerReplicaSet(store, replicas=2)
    sched = None
    try:
        store.create(
            make_node("n0").capacity(
                cpu_milli=4000, mem=8 * GI, pods=10
            ).obj()
        )
        store.create(make_pod("p0").req(cpu_milli=100, mem=8 * MI).obj())
        sched = Scheduler(store)
        sched.informers.informer("Node").start()
        sched.informers.informer("Pod").start()
        assert sched.informers.wait_for_sync(10)
        plane.kill(1)  # give replica_failovers_total something to show
        sched.schedule_batch(timeout=2)
        assert sched.metrics.apf_seats_current.get() == float(
            plane.apf.seats_current()
        )
        assert sched.metrics.replica_failovers_total.get() == 1.0
        assert sched.metrics.server_watch_write_stalls_total.get() == 0.0
    finally:
        if sched is not None:
            sched.stop()
        plane.stop()
