"""One run of one cell: set-up, warm replay, the measured window, the drain,
and the record that the metric readers and the comparison work from.

The window is a slice of a continuous run.  After the nodes, ``warmup`` and the
init pods, the cell's own traffic runs from a stream of the seed the window
does not use (the warm replay) until no executable has been built or loaded
for a quiet interval, or a cap; only then does the window open, with the load
still on.  All of that is set-up and is in ``setup_s``.
"""

from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time

from . import compare as compare_mod
from . import reference, rules, tracered, waves
from .client import WatchClient
from .deployment import Deployment
from .manifest import ManifestError
from .probes import Recorder

QUIET_CYCLES = 3    # whole solves since the last build before the replay counts as quiet

SPAN_NAMES = (
    "perfbench_window", "store_create", "encode_dispatch", "decode",
    "commit", "store_update_wave",
)


def log(msg: str) -> None:
    print(f"perfbench: {msg}", file=sys.stderr, flush=True)


def make_system(name: str, deployment, workdir, recorder, control=None):
    if name == "reference":
        from . import reference

        return reference.System(deployment, workdir, recorder, broken=control)
    from .systems import served

    return served.System(deployment, workdir, recorder)


def traffic_params(traffic: dict, toy: bool, overrides=None) -> dict:
    p = {k: v for k, v in traffic.items() if k != "toy"}
    if toy:
        p.update(traffic.get("toy") or {})
    p.update(overrides or {})
    return p


def wait_for(pred, timeout: float, poll: float = 0.02) -> bool:
    deadline = time.monotonic() + timeout
    while not pred():
        if time.monotonic() > deadline:
            return False
        time.sleep(poll)
    return True


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def peak_device_bytes():
    import jax

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()
    ]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class Setup:
    """Everything up to the end of the init pods; shared by a cell's run and
    by the rate sweep."""

    def __init__(self, manifest, cell: dict, toy: bool, system_name: str, control,
                 overrides=None):
        self.manifest = manifest
        self.cell = cell
        self.toy = toy
        self.config = manifest.config(cell["config"])
        self.dep = Deployment(self.config, toy=toy)
        rules.require_claimed(self.dep.templates)   # before any load: no rule unchecked
        # a role that no node of the empty cluster admits, by the rules alone,
        # is not meant to bind: its pods are created, acknowledged and left
        self.unplaceable = reference.unplaceable_roles(self.dep)
        for role in sorted(self.unplaceable):
            if not self.dep.skip_wait[role]:
                raise ManifestError(
                    f"no node of the empty cluster admits a {role} pod of {self.config['name']}, "
                    "and the createPods op that creates them does not say "
                    "skipWaitToCompletion: true; a run would wait for binds that cannot come, "
                    "as upstream's would, so the deployment does not run"
                )
        self.params = traffic_params(manifest.traffic(cell["traffic"]), toy, overrides)
        self.rec = Recorder()
        self.rec.install_compile_listener()
        self.rec.install_gc_listener()
        self.workdir = tempfile.mkdtemp(prefix="perfbench_")
        self.system = make_system(system_name, self.dep, self.workdir, self.rec, control)
        self.client = None
        self.init_created: list = []
        self.left_pending: set = set()      # the keys of set-up's pods that are not meant to bind
        self.phases: dict = {}

    def phase(self, name: str, t0: float) -> None:
        self.phases[name] = self.rec.clock() - t0
        log(f"set-up: {name} {self.phases[name]:.2f} s")

    def n_awaited(self, created) -> int:
        """How many of `created` are pods a run waits to see bound."""
        return sum(1 for c in created if c[2] not in self.unplaceable)

    def n_bound(self) -> int:
        """Pods the run waits for that the client has seen bound."""
        bound = self.client.bound
        return len(bound) - sum(1 for key in self.left_pending if key in bound)

    def warmup_pods(self, seed: int, n_warm: int) -> list:
        """Pods of every template that can be pending in the window: the
        measured one, and each role's that is left pending; `n_warm` of each,
        turn by turn, so that every bucket `warmup` solves holds them all."""
        walk = self.dep.namespace_walk(seed, 0)
        pods = [self.dep.pod("measure", f"warmup-{i}", next(walk)) for i in range(n_warm)]
        others = sorted(self.unplaceable - {"measure"})
        if not others:
            return pods
        walks = {role: self.dep.namespace_walk(seed, 0, role) for role in others}
        mixed = []
        for i, d in enumerate(pods):
            mixed.append(d)
            mixed += [self.dep.pod(r, f"warmup-{r}-{i}", next(walks[r])) for r in others]
        return mixed

    def bring_up(self, seed: int) -> None:
        clock = self.rec.clock
        t = clock()
        self.system.start()
        self.client = WatchClient(self.system).start()
        self.phase("nodes_and_scheduler", t)

        t = clock()
        n_warm = int(self.params.get("warmup_pods", 0))
        if n_warm:
            self.system.warmup(self.warmup_pods(seed, n_warm))
        self.phase("warmup", t)
        self.t_warmup_end = clock()

        t = clock()
        walk = self.dep.namespace_walk(seed, 0)
        # one walk for init and measured pods alike, unless the roles have
        # namespaces of their own
        init_walk = self.dep.namespace_walk(seed, 0, "init") if self.dep.role_namespaces else walk
        for i in range(self.dep.n_init_pods):
            d = self.dep.pod("init", f"init-{i}", next(init_walk))
            self.system.create(d, "init")
            self.init_created.append((d["metadata"]["namespace"], d["metadata"]["name"], "init"))
        self.left_pending = {c[:2] for c in self.init_created if c[2] in self.unplaceable}
        # upstream's skipWaitToCompletion: created, acknowledged, and not waited
        # for here (a role that can bind is still awaited by every later wait)
        awaited = self.n_awaited(self.init_created)
        if not self.dep.skip_wait["init"] and not wait_for(
                lambda: self.n_bound() >= awaited, 600.0):
            raise TimeoutError("the init pods were not all bound within 600 s")
        self.phase("init_pods", t)

        # the bucket walk: bursts of the cell's own pods, one size after
        # another, each followed to its binds, so that every pod bucket and
        # dirty-row bucket a starved or draining cycle can meet is built or
        # loaded before the window, through the live path
        t = clock()
        for n in self.bucket_walk():
            base = len(self.init_created)
            for i in range(int(n)):
                d = self.dep.pod("measure", f"walk-{base + i}", next(walk))
                self.system.create(d, "measure")
                self.init_created.append(
                    (d["metadata"]["namespace"], d["metadata"]["name"], "measure")
                )
            awaited = self.n_awaited(self.init_created)
            if not wait_for(lambda: self.n_bound() >= awaited, 600.0):
                raise TimeoutError(f"the walk's burst of {n} pods was not bound within 600 s")
        self.phase("bucket_walk", t)

    def bucket_walk(self) -> list:
        """Burst sizes of the walk: every power of two from the scheduler's
        batch down to one pod (a toy mix names a shorter walk)."""
        walk = self.params.get("replay_walk")
        if walk is None:
            batch = int(self.dep.scheduler_args["batch_size"])
            walk = [1 << i for i in range(batch.bit_length() - 1, -1, -1)]
        return [int(n) for n in walk]

    def tear_down(self) -> None:
        self.rec.uninstall()
        shutil.rmtree(self.workdir, ignore_errors=True)


def warm_replay(setup: Setup, gen, seconds: float) -> dict:
    """Run the cell's traffic until compilation has gone quiet (or the cap),
    keeping the cluster under its fill limit whatever the rate."""
    p, rec, clock = setup.params, setup.rec, setup.rec.clock
    quiet_s, cap_s = float(p["replay_quiet_s"]), float(p["replay_cap_s"])
    # a fixed amount of work before the window: throughput falls as the
    # cluster fills, so every run opens its window at about the same fill
    min_pods = int(p.get("replay_pods", 0))
    room = setup.dep.max_fill_share * setup.dep.capacity_pods
    backlog = float(p.get("backlog_pods", 0))
    live_pods = p.get("live_pods")
    if live_pods is not None:
        # where pods complete, the population is the mix's own number and not
        # a function of the run's length: a wave may land whole before its
        # completions do, so it peaks a chunk and a backlog over live_pods (or
        # at what set-up left), with another backlog pending
        peak = max(float(live_pods) + float(p.get("topup_chunk", 64)) + backlog,
                   float(setup.client.n_live())) + backlog
        if peak > room:
            raise ManifestError(
                f"the mix holds up to {peak:.0f} pods at once, the deployment has room for "
                f"{room:.0f} (max_fill_share x capacity_pods)"
            )
    t0 = clock()
    n0 = setup.client.n_bound()
    gen.start()
    why = "cap"
    while True:
        time.sleep(0.05)
        now = clock()
        ran = now - t0
        if ran >= cap_s:
            break
        last = max(rec.last_compile_time(), t0)
        # a build in progress reports only when it ends, so quiet also asks
        # for whole cycles since the last one
        settled = sum(1 for c in rec.cycles if c.get("t_dispatch0", 0.0) > last)
        if ran >= quiet_s and now - last >= quiet_s and gen.primed and (
            settled >= QUIET_CYCLES or not rec.cycles
        ) and setup.client.n_bound() - n0 >= min_pods:
            why = "quiet"
            break
        bound = setup.client.n_bound()
        rate = (bound - n0) / ran if ran > 1.0 else 0.0
        # what the window, its closing wave and the drain will still add
        if live_pods is None and bound + backlog + rate * (seconds + 5.0) > room:
            why = "fill"
            break
    ran = clock() - t0
    log(f"warm replay: {ran:.2f} s, ended by {why}, {setup.client.n_bound() - n0} pods bound")
    return {"seconds": ran, "ended_by": why, "t0": t0, "t1": clock()}


def run_cell(manifest, cell: dict, seed: int, seconds: float, trace: bool, toy: bool,
             system_name: str = "served", control=None, t_start=None, plant=None,
             overrides=None) -> dict:
    """`t_start`: the process's first instant on ``time.perf_counter``.
    `plant`: a function of the set-up system, called before any load (the
    tests plant a fault in the timed path with it)."""
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    setup = Setup(manifest, cell, toy, system_name, control, overrides)
    rec, clock, p = setup.rec, setup.rec.clock, setup.params
    trace_dir = None
    try:
        setup.bring_up(seed)
        if plant is not None:
            plant(setup.system)
        client, system = setup.client, setup.system
        gen = manifest.generator(p["kind"])(p, setup.dep, system, client, rec, seed)
        replay = warm_replay(setup, gen, seconds)

        # -- the window ------------------------------------------------------
        trace_s = min(float(p.get("trace_seconds", seconds)), seconds) if trace else 0.0
        counters_open = system.counters()
        t_open = gen.open_window(seconds)
        setup_s = t_open - t_start
        t_close = t_open + seconds
        t_trace0 = t_trace1 = None
        if trace:
            time.sleep(max(0.0, t_close - trace_s - clock()))
            trace_dir = tempfile.mkdtemp(prefix="perfbench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            t_trace0 = clock()
            with rec.span("perfbench_window"):
                time.sleep(max(0.0, t_close - clock()))
            t_trace1 = clock()
        else:
            time.sleep(max(0.0, t_close - clock()))
        counters_close = system.counters()
        i_close = max(0, len(client.bind_log) - 1)
        gen.close_window(t_close)
        gap = float(p.get("wave_gap_s", 0.0))
        if p["kind"] == "backlog":
            # the load stays on until the wave that ends the interval begins
            seen = wait_for(
                lambda: waves.closing_wave_seen(
                    [b[0] for b in client.bind_log_since(i_close)], gap, t_close
                ),
                float(p["drain_s"]),
            )
            if not seen:
                log("no wave began after the window closed within drain_s")
        # the load comes off before the trace is written out: the profiler
        # takes a minute and more over a busy 10 s slice, and a generator left
        # running through it sends three windows' worth of pods nobody measures
        gen.stop()
        t_off = clock()
        if trace:
            jax.profiler.stop_trace()

        # -- the drain: every pod is followed to its bind or the deadline, ----
        # drain_s after the load came off (counted from the window's close, a
        # slow stop_trace used the whole of it up and the run's last pods read
        # as unbound)
        created = setup.init_created + [c[:3] for c in gen.created]
        awaited = setup.n_awaited(created)
        drained = wait_for(
            lambda: setup.n_bound() >= awaited,
            max(0.0, t_off + float(p["drain_s"]) - clock()),
        )
        # where pods complete, every acknowledged deletion is followed to the
        # client's watch as well, within the same deadline
        deleted = None
        if p.get("live_pods") is not None:
            deleted = [d[:2] for d in gen.deleted]
            wait_for(
                lambda: len(client.gone) >= len(deleted),
                max(0.0, t_off + float(p["drain_s"]) - clock()),
            )
        t_drained = clock()
        peak = peak_device_bytes()
        system.stop()
        time.sleep(0.2)     # the fan-out's last events reach the watch
        client.stop()
        gc.collect()

        # -- the comparison, once the window has closed and state is freed ---
        t_cmp = clock()
        recovered = system.recover()
        solves = [c["keys"] for c in sorted(rec.cycles, key=lambda c: c.get("t_decode1", 0.0))
                  if "keys" in c]
        verdict = compare_mod.compare(setup.dep, created, client, recovered, solves, deleted)
        # the pods left pending, as they should be: never seen bound, read back unbound
        pending_at_end = sum(1 for key in setup.left_pending
                             if key not in client.bound and recovered.get(key) == "")
        compare_s = clock() - t_cmp

        tr = None
        if trace_dir is not None:
            t_red = clock()
            tr = tracered.reduce(tracered.load_xplane(trace_dir), SPAN_NAMES)
            log(f"trace reduced in {clock() - t_red:.2f} s")
        snap = rec.snapshot()
        record = {
            "cell": cell["name"], "kind": p["kind"], "params": p, "seed": seed,
            "seconds": seconds, "device": device_info(),
            "t_start": t_start, "t_open": t_open, "t_close": t_close,
            "t_off": t_off, "t_drained": t_drained, "drained": drained, "setup_s": setup_s,
            "setup_phases": dict(setup.phases, warm_replay=replay["seconds"]),
            "replay": replay, "t_warmup_end": setup.t_warmup_end,
            "bind_log": list(client.bind_log), "bound": dict(client.bound),
            "created": list(gen.created), "depth": list(gen.depth),
            "deleted": list(getattr(gen, "deleted", ())), "gone": dict(client.gone),
            "live": list(getattr(gen, "live", ())),
            "due": gen.due_in_window(t_open, t_close),
            "client": {
                "events": client.events, "expired": client.expired,
                "relists": list(client.relists),
            },
            "spans": snap["spans"], "cycles": snap["cycles"], "compiles": snap["compiles"],
            "gc_pauses": snap["gc_pauses"],
            "counters_open": counters_open, "counters_close": counters_close,
            "trace": tr, "trace_window": (t_trace0, t_trace1),
            "peak_device_bytes": peak, "compare_s": compare_s,
            "verdict": verdict, "pending_at_end": pending_at_end,
        }
        return record
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
        try:
            if setup.system is not None and getattr(setup.system, "sched", None) is not None:
                setup.system.stop()
        finally:
            setup.tear_down()
