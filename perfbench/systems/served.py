"""The system under test: a journaled, sharded ``Store`` served by a running
``Scheduler`` in this process (the chip belongs to one process).

The harness talks to it as a client does: ``create`` a pod, read events from
its own Pod watch, relist when that watch expires.  Everything else here is
observation: spans around the calls into each layer and one record per solve,
taken by wrapping bound methods of the live objects; arguments and results
pass through untouched.  Nothing under ``kubernetes_tpu`` is changed.
"""

from __future__ import annotations

import os
import time


class Watch:
    """The client's Pod watch as plain tuples
    ``(type, namespace, name, node, rv)``."""

    def __init__(self, store):
        self._store = store
        self._w = store.watch("Pod")

    def get(self, timeout: float):
        ev = self._w.get(timeout=timeout)
        if ev is None:
            return None
        o = ev.obj
        return (ev.type, o.meta.namespace, o.meta.name, o.spec.node_name or "", ev.rv)

    @property
    def expired(self) -> bool:
        return self._w.expired

    def relist(self):
        """What a client does on 410: list, then watch from the list's rv.
        Returns {(namespace, name): node} of the pods bound at the cut, and
        the cut's resourceVersion."""
        from kubernetes_tpu.api import store as st

        while True:
            pods, rv = self._store.list("Pod")
            try:
                self._w = self._store.watch("Pod", from_rv=rv)
            except st.Expired:
                continue
            return {
                (p.meta.namespace, p.meta.name): p.spec.node_name
                for p in pods if p.spec.node_name
            }, rv

    def stop(self) -> None:
        self._w.stop()


class System:
    def __init__(self, deployment, workdir: str, recorder):
        self.dep = deployment
        self.rec = recorder
        self.journal = os.path.join(workdir, "cluster.jsonl")
        self.store = None
        self.sched = None

    # -- life cycle ----------------------------------------------------------

    def start(self) -> None:
        from kubernetes_tpu.api import kubeyaml
        from kubernetes_tpu.api import store as st
        from kubernetes_tpu.scheduler import Scheduler

        self._kubeyaml = kubeyaml
        self.store = st.Store(journal_path=self.journal, **self.dep.store_args)
        for d in self.dep.nodes():
            self.store.create(kubeyaml.node_from_dict(d))
        self.sched = Scheduler(self.store, **self.dep.scheduler_args)
        self._instrument()
        self.sched.start()
        deadline = time.monotonic() + 120.0
        while len(self.sched.tpu.state._rows) < self.dep.n_nodes:
            if time.monotonic() > deadline:
                raise TimeoutError("the scheduler's informer never saw every node")
            time.sleep(0.01)

    def warmup(self, pods: list) -> None:
        self.sched.warmup([self._kubeyaml.pod_from_dict(d) for d in pods])
        self.sched.wait_for_idle(timeout=120.0)

    def create(self, pod: dict, role: str) -> None:
        self.store.create(self._kubeyaml.pod_from_dict(pod))

    def delete(self, namespace: str, name: str) -> None:
        self.store.delete("Pod", name, namespace)

    def watch(self) -> Watch:
        return Watch(self.store)

    def stop(self) -> None:
        """Flush the bind stage, stop the scheduler, close the journal and
        drop every reference to device state."""
        sched, self.sched = self.sched, None
        if sched is not None:
            sched.flush_binds(timeout=60.0)
            sched.stop()
            pool = getattr(sched.tpu, "prewarm_pool", None)
            if pool is not None:
                pool.close(timeout=120.0)
        if self.store is not None:
            self.store.close()
        self.store = None

    def recover(self) -> dict:
        """The bindings a fresh Store reads back from the journal."""
        from kubernetes_tpu.api import store as st

        fresh = st.Store(journal_path=self.journal)
        try:
            pods, _ = fresh.list("Pod")
        finally:
            fresh.close()
        return {(p.meta.namespace, p.meta.name): p.spec.node_name or "" for p in pods}

    # -- counters ------------------------------------------------------------

    def counters(self) -> dict:
        tpu = self.sched.tpu
        out = {"overload_shed": float(self.sched.metrics.overload_shed_total.total)}
        out.update({"watch_" + k: v for k, v in self.store.watch_stats().items()})
        if getattr(tpu, "_mirror", None) is not None:
            out.update({"mirror_" + k: v for k, v in tpu._mirror.stats().items()})
        if getattr(tpu, "_partials", None) is not None:
            out.update({"partials_" + k: v for k, v in tpu._partials.stats().items()})
        b = tpu.breaker
        out["breaker_trips"] = b.trips
        out["host_fallbacks"] = b.fallback_count()
        out["journal_frame_bytes"] = self.store.journal_frame_bytes
        out["checkpoints"] = self.store.checkpoints_total
        out.update({"queue_" + k: v for k, v in self.sched.queue.stats().items()
                    if isinstance(v, (int, float))})
        return out

    # -- observation ---------------------------------------------------------

    def _instrument(self) -> None:
        rec, tpu, sched = self.rec, self.sched.tpu, self.sched
        shapes = {}

        inner_solve = tpu.solve_encoded_async

        def solve_encoded_async(snap, meta):
            shapes["P"], shapes["R"] = (int(x) for x in snap.pods.req.shape[:2])
            shapes["N"] = int(snap.cluster.allocatable.shape[0])
            return inner_solve(snap, meta)

        tpu.solve_encoded_async = solve_encoded_async

        inner_dispatch = tpu.schedule_pending_async

        def schedule_pending_async(pending, *a, **kw):
            t0 = rec.clock()
            with rec.span("encode_dispatch", len(pending)):
                ds = inner_dispatch(pending, *a, **kw)
            if ds is not None:
                ds._perfbench = dict(shapes, t_dispatch0=t0, t_dispatch1=rec.clock())
            return ds

        tpu.schedule_pending_async = schedule_pending_async

        inner_final = tpu.finalize_pending

        def finalize_pending(pending, ds, *a, **kw):
            t0 = rec.clock()
            with rec.span("decode", len(pending)):
                names = inner_final(pending, ds, *a, **kw)
            eff = tpu.last_solve
            info = dict(getattr(ds, "_perfbench", None) or {})
            lt = dict(tpu.last_timings or {})
            meta = getattr(eff, "meta", None)
            info.update(
                pods=len(pending), t_decode0=t0, t_decode1=rec.clock(),
                keys=[(p.meta.namespace, p.meta.name) for p in pending],
                route=getattr(meta, "route", "host"),
                placed=sum(1 for n in names if n),
                encode_s=float(lt.get("encode_s", 0.0)),
                compile_s=float(lt.get("compile_s", 0.0)),
                decode_wait_s=float(lt.get("decode_wait_s", 0.0)),
                decode_overlap_s=float(lt.get("decode_overlap_s", 0.0)),
            )
            rec.cycles.append(info)
            return names

        tpu.finalize_pending = finalize_pending

        rec.wrap(sched, "_commit_wave", "commit", count=lambda wave: len(wave))
        rec.wrap(
            self.store, "update_wave", "store_update_wave",
            count=lambda kind, updates, **kw: len(updates),
        )
