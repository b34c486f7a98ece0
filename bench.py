"""Headline benchmark: the five BASELINE.json solver configs (c1-c5) plus
the loop, sharded, preemption, slice, incremental, autoscale and
serving cells added since (16 cells in all).

Prints ONE JSON line.  Headline metric = config 5, the north star: a
50k-node / 10k-pod gang burst jointly solved on device, reported as
end-to-end warm-step latency (pod-batch encode + device solve + result
readback) against a warm cluster state — the steady-state step a running
scheduler executes per batch, matching the reference scheduler's warm
informer-fed cache.  `extra` carries every cell:

  c1   500 nodes /  500 pods  NodeResourcesFit, oracle-parity checked
  c2    5k nodes /   5k pods  Fit + BalancedAllocation
  c3   10k nodes /  10k pods  PodTopologySpread (hard) + preferred NodeAffinity
  c3s   5k nodes / 1024 pods  spread, pinned greedy/wavefront (strict budget)
  c4   20k nodes /  10k pods  InterPodAffinity/AntiAffinity (required)
  c4s   5k nodes / 1024 pods  anti-affinity, pinned greedy/wavefront (strict budget)
  c5   50k nodes /  10k pods  gang/coscheduling burst, joint auction solve
  c6    5k nodes /   2k pods  kubemark churn through the full loop
  c6s  50k nodes /   4k pods  SUSTAINED constant-rate arrival stream
       (strict budget: >= 1050 pods/s, watchers_terminated == 0), run
       journaled + ends with a crash-restart recovery gate (snapshot +
       journal-suffix recovery under STRICT_RECOVERY_BUDGET_MS, zero
       lost pods)
  c7  100k nodes /   2k pods  SHARDED solve on a forced 8-device host
       mesh — a snapshot one chip cannot hold; gates: mesh/single-chip
       assignment parity, steady_recompiles == 0, and steady host→device
       transfer O(changed rows) via the mirror delta counters
  c8  100k hollow nodes       the kubemark FLEET harness on the 8-shard
       store: batched wave-committed heartbeats + a sustained
       pod-lifecycle soak across namespaces (concurrent per-shard bind
       sub-waves), p50/p90/p99 lifecycle latency, zero lost/double-bound
       pods, watchers_terminated == 0, and per-shard snapshot+suffix
       recovery under STRICT_RECOVERY_BUDGET_MS
  c10   4k nodes / 64 slices of 4x4x4  SLICE PACKING: mixed gang shapes
       arriving/leaving through the carve-out scorer (prefer policy);
       gates placement QUALITY — BENCH_STRICT floors on the
       contiguous-placement rate and the end-state fragmentation score
       — alongside throughput and steady_recompiles == 0
  c9   20k nodes / 128 preemptors  mixed-priority preemption churn with
       PDBs through the BATCHED PostFilter (one [P, N, K] dry-run per
       pass); gates: oracle + batched-vs-sequential plan parity,
       bound-exactly-once for preemptors and evicted victims, guarded
       victims survive, a sustained preemption-throughput floor, zero
       steady recompiles in the planning phase, and a ≥5x exposed
       PostFilter planning speedup vs the per-pod walk on the same trace
  c11  50k nodes / 64 pod classes  INCREMENTAL churn: <=1% of node rows
       dirtied per cycle under a recurring service-shaped stream; the
       warm-started solve (device-resident Filter/Score partials,
       ISSUE 14) runs the same frozen trace as a cold scheduler — gates:
       bit-identical placements, a ≥3x warm-vs-cold planning speedup,
       zero steady recompiles, and the <=1% dirtied-rows contract;
       reports the partials hit rate and rows re-evaluated (c6/c6s
       report the same accounting for their live loops)
  c12  50k nodes  AUTOSCALE churn: a kubemark NodeGroupScaler drives
       ±1% node add/remove per cycle plus deliberate oscillation around
       the 65536 pad-bucket boundary against the ELASTIC node axis
       (ISSUE 15) — gates: placements bit-identical to the
       full-RESHARDED-rebuild oracle, zero resyncs/recompiles under
       within-bucket churn AND under boundary oscillation (the shrink
       dwell), crossings absorbed by in-place resident grows with exact
       pad-row accounting, ≥90% of partials class rows warm across the
       grow, and the post-dwell drain shrink served; plus a LIVE phase
       (HPA + CA-shaped scaler reconcile over a hollow fleet) gating
       zero unbound pods at peak, ≥1 live in-place grow, and
       watchers_terminated == 0

Every scenario reports step-latency p50/p90/p99 (the windowed sampler:
attempt-duration percentiles for the loop configs, timed-sample
percentiles for the solver configs) plus its commit share per step.

vs_baseline compares c5 against the upstream-folklore scheduler SLO of
~100 pods/s at 5k nodes (the reference publishes no in-tree absolute
numbers; see BASELINE.md): value = (10_000 / latency) / 100.
"""

import json
import os
import time

# c7 needs a multi-device host-platform mesh; the flag must land before
# the first JAX backend init (tests/conftest.py forces the same 8).  It
# shapes the CPU platform ONLY: on a one-chip machine jax.devices() is
# still one TPU and c7 builds a mesh of one (ROADMAP S0)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np

BASELINE_PODS_PER_SEC = 100.0


def _mk_nodes(n, zones=10):
    from kubernetes_tpu.testing.wrappers import GI, make_node

    return [
        make_node(f"node-{i}")
        .capacity(cpu_milli=32000, mem=64 * GI, pods=110)
        .zone(f"zone-{i % zones}")
        .obj()
        for i in range(n)
    ]


def _mk_basic_pods(p, seed=0, prefix="pod"):
    from kubernetes_tpu.testing.wrappers import MI, make_pod

    rng = np.random.default_rng(seed)
    return [
        make_pod(f"{prefix}-{i}")
        .req(
            cpu_milli=int(rng.choice([100, 250, 500, 1000, 2000])),
            mem=int(rng.choice([128, 256, 512, 1024, 2048])) * MI,
        )
        .obj()
        for i in range(p)
    ]


class _Runner:
    """Warm-state end-to-end step timer: state prebuilt with nodes (the
    warm scheduler cache), timed step = encode pending batch + solve +
    readback.  First call compiles; second identical-shape call is the
    measurement.  The first-shape compile wall and the steady-state
    encode/compile/solve split are reported separately so CI can gate on
    solve-half regressions without compile churn polluting the number."""

    def __init__(self, nodes, mode, mesh=None):
        from kubernetes_tpu.models.batch_scheduler import TPUBatchScheduler

        self.sched = TPUBatchScheduler(mode=mode, mesh=mesh)
        for nd in nodes:
            self.sched.add_node(nd)

    def step(self, pods):
        t0 = time.perf_counter()
        names = self.sched.schedule_pending(pods)
        dt = time.perf_counter() - t0
        return names, dt, dict(self.sched.last_timings)

    SAMPLES = 3

    def run(self, mk_pods):
        from kubernetes_tpu.analysis import retrace

        # compile; identical shapes.  Its wall clock IS the first-shape
        # cost (XLA compile dominates) — recorded, not mixed into steady.
        retrace.clear_steady()
        _, first_s, _ = self.step(mk_pods("warmup"))
        # warmup traced every executable this scenario needs; any trace
        # during the timed steps below is a steady-state recompile — a
        # kernel argument escaped the pad-bucket lattice (the
        # recompile-discipline invariant, analysis/retrace.py)
        retrace.mark_steady()
        steady0 = retrace.steady_total()
        # min-of-3 timed runs; the full sample list makes the recorded
        # JSON self-diagnosing (ROADMAP S0 replaces this with medians
        # and quartiles over many readings)
        names, dt, samples, best_t = None, None, [], {}
        for k in range(self.SAMPLES):
            nms, d, lt = self.step(mk_pods(f"run{k}"))
            samples.append(round(d, 4))
            if dt is None or d < dt:
                names, dt, best_t = nms, d, lt
        steady_recompiles = retrace.steady_total() - steady0
        retrace.clear_steady()
        placed = sum(n is not None for n in names)
        return _Run(
            names, placed, dt, samples, first_s, best_t, steady_recompiles
        )


class _Run:
    def __init__(self, names, placed, dt, samples, first_s, timings,
                 steady_recompiles=0):
        self.names = names
        self.placed = placed
        self.dt = dt
        self.samples = samples
        self.first_s = first_s
        self.timings = timings
        self.steady_recompiles = steady_recompiles

    def report(self, nodes, pods, **extra):
        from kubernetes_tpu.kubemark import percentiles

        t = self.timings
        pct = percentiles(list(self.samples))
        out = {
            "nodes": nodes, "pods": pods, "placed": self.placed,
            "latency_s": round(self.dt, 4),
            "pods_per_s": round(pods / self.dt, 1),
            "samples_s": self.samples,
            # windowed-sampler surface (every scenario): step-latency
            # percentiles over the timed samples; solver-only configs
            # have no store in the loop, so their commit share is 0 by
            # construction (the loop configs report the real split)
            "latency_p50_s": round(pct["p50"], 4),
            "latency_p90_s": round(pct["p90"], 4),
            "latency_p99_s": round(pct["p99"], 4),
            "commit_share_per_step": 0.0,
            # first-of-shape step (compile included) vs the steady split
            "first_step_s": round(self.first_s, 4),
            "steady_encode_s": round(t.get("encode_s", 0.0), 4),
            "steady_compile_s": round(t.get("compile_s", 0.0), 4),
            "steady_solve_s": round(t.get("solve_s", 0.0), 4),
            "solve_share": round(
                (t.get("compile_s", 0.0) + t.get("solve_s", 0.0))
                / self.dt, 4,
            ) if self.dt else 0.0,
            # XLA traces during the TIMED steps (warmup excluded): must
            # be zero — a steady-state retrace eats a full compile on
            # the hot path (BENCH_STRICT gates on this)
            "steady_recompiles": self.steady_recompiles,
        }
        out.update(extra)
        return out


def config1():
    """500/500 Fit; placement parity vs the reference-semantics oracle."""
    from kubernetes_tpu.testing.oracle import Oracle

    nodes = _mk_nodes(500)
    runner = _Runner(nodes, mode="auto")
    pods_fn = lambda tag: _mk_basic_pods(500, seed=1, prefix=f"c1-{tag}")
    run = runner.run(pods_fn)
    want = Oracle(nodes).schedule(pods_fn("run0"))
    return run.report(500, 500, oracle_parity=run.names == want)


def config2():
    nodes = _mk_nodes(5_000)
    runner = _Runner(nodes, mode="auto")
    run = runner.run(
        lambda tag: _mk_basic_pods(5_000, seed=2, prefix=f"c2-{tag}")
    )
    return run.report(5_000, 5_000)


def config3():
    """10k/10k: hard zone-spread + preferred node affinity."""
    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.testing.wrappers import MI, make_pod

    nodes = _mk_nodes(10_000, zones=32)

    def mk(tag):
        rng = np.random.default_rng(3)
        pods = []
        for i in range(10_000):
            svc = i % 50
            pw = (
                make_pod(f"c3-{tag}-{i}")
                .req(cpu_milli=int(rng.choice([100, 250, 500])), mem=256 * MI)
                .label("app", f"svc-{svc}")
                .spread(2, api.LABEL_ZONE, "DoNotSchedule", {"app": f"svc-{svc}"})
            )
            if i % 4 == 0:
                pw.preferred_affinity(
                    10, api.LABEL_ZONE, api.OP_IN, [f"zone-{svc % 32}"]
                )
            pods.append(pw.obj())
        return pods

    runner = _Runner(nodes, mode="auto")
    run = runner.run(mk)
    return run.report(10_000, 10_000, **_wave_stats(runner))


def config4():
    """20k/10k: required inter-pod anti-affinity (self-spread per service
    over hostnames) — the O(N^2) pairwise family."""
    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.testing.wrappers import MI, make_pod

    nodes = _mk_nodes(20_000)

    def mk(tag):
        rng = np.random.default_rng(4)
        pods = []
        for i in range(10_000):
            svc = i % 200
            pods.append(
                make_pod(f"c4-{tag}-{i}")
                .req(cpu_milli=int(rng.choice([100, 250, 500])), mem=256 * MI)
                .label("app", f"svc-{svc}")
                .pod_anti_affinity({"app": f"svc-{svc}"}, api.LABEL_HOSTNAME)
                .obj()
            )
        return pods

    runner = _Runner(nodes, mode="auto")
    run = runner.run(mk)
    return run.report(20_000, 10_000, **_wave_stats(runner))


def config5():
    """50k/10k gang burst: joint auction solve, target < 1 s end-to-end."""
    from kubernetes_tpu.testing.wrappers import MI, make_pod

    nodes = _mk_nodes(50_000)

    def mk(tag):
        rng = np.random.default_rng(5)
        return [
            make_pod(f"c5-{tag}-{i}")
            .req(
                cpu_milli=int(rng.choice([100, 250, 500, 1000, 2000])),
                mem=int(rng.choice([128, 256, 512, 1024, 2048])) * MI,
            )
            .group(f"gang-{i % 100}")
            .obj()
            for i in range(10_000)
        ]

    runner = _Runner(nodes, mode="auto")
    run = runner.run(mk)
    return run.report(50_000, 10_000, gangs=100)


def _wave_stats(runner):
    """Wavefront telemetry of the runner's most recent solve."""
    res = runner.sched.last_result
    wc = getattr(res, "wave_count", None)
    if wc is None:
        return {}
    return {
        "solve_waves": int(wc),
        "solve_wave_fallbacks": int(res.wave_fallbacks or 0),
    }


# Steady-state budgets for the 1k-pod greedy-routed shapes, enforced
# under BENCH_STRICT=1.  BENCH_r05 measured these batches at 582.8 ms
# (spread) and 1195.7 ms (inter-pod) per schedule_pending step; the
# wavefront solve must hold ≥2x better.
STRICT_SOLVE_BUDGETS_S = {
    "c3s_spread_1k": 0.291,
    "c4s_interpod_1k": 0.598,
}


def config3s():
    """1024-pod spread batch on 5k nodes pinned to the greedy route (the
    auto-router would hand exactly-1024 to the auction) — the shape whose
    BENCH_r05 solve half ran 582.8 ms.  Wavefront target: < 291 ms."""
    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.testing.wrappers import MI, make_pod

    nodes = _mk_nodes(5_000, zones=32)

    def mk(tag):
        rng = np.random.default_rng(31)
        pods = []
        for i in range(1024):
            svc = i % 50
            pods.append(
                make_pod(f"c3s-{tag}-{i}")
                .req(cpu_milli=int(rng.choice([100, 250, 500])), mem=256 * MI)
                .label("app", f"svc-{svc}")
                .spread(2, api.LABEL_ZONE, "DoNotSchedule", {"app": f"svc-{svc}"})
                .obj()
            )
        return pods

    runner = _Runner(nodes, mode="greedy")
    run = runner.run(mk)
    return run.report(5_000, 1024, **_wave_stats(runner))


def config4s():
    """1024-pod required-anti-affinity batch on 5k nodes — the shape
    whose BENCH_r05 solve half ran 1195.7 ms.  Target: < 598 ms."""
    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.testing.wrappers import MI, make_pod

    nodes = _mk_nodes(5_000)

    def mk(tag):
        rng = np.random.default_rng(41)
        return [
            make_pod(f"c4s-{tag}-{i}")
            .req(cpu_milli=int(rng.choice([100, 250, 500])), mem=256 * MI)
            .label("app", f"svc-{i % 200}")
            .pod_anti_affinity({"app": f"svc-{i % 200}"}, api.LABEL_HOSTNAME)
            .obj()
            for i in range(1024)
        ]

    runner = _Runner(nodes, mode="greedy")
    run = runner.run(mk)
    return run.report(5_000, 1024, **_wave_stats(runner))


def config6():
    """5k-node kubemark churn: the store/informer WRITE path under
    concurrent load (VERDICT r4 #10) — hollow-node heartbeats + pod
    churn + GC/namespace sweeps running while 2,000 measured pods
    schedule through the full informer/cache/queue/solve/bind loop.
    Reports wall throughput, window-scoped attempt p99, and asserts no
    watcher was terminated for falling behind (cacher data-loss
    signal).  Reference shape: performance-config.yaml MixedChurn,
    pkg/kubemark/hollow_kubelet.go:87."""
    import threading

    from kubernetes_tpu import kubemark
    from kubernetes_tpu.api import store as st
    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.controllers import ControllerManager
    from kubernetes_tpu.controllers.garbagecollector import GarbageCollector
    from kubernetes_tpu.controllers.namespace import NamespaceController
    from kubernetes_tpu.perf.collectors import histogram_baseline
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.testing.wrappers import MI, make_pod

    n_nodes, n_measured, n_churn = 5_000, 2_000, 600
    store = st.Store(shards=8)
    hollow = kubemark.HollowCluster(
        store, n_nodes, heartbeat_interval=5.0
    ).start()
    mgr = ControllerManager(
        store, controllers=[GarbageCollector, NamespaceController]
    ).start()
    sched = Scheduler(store, batch_size=1024)
    sched.start()

    def mk(i, prefix):
        return (
            make_pod(f"{prefix}-{i}")
            .req(cpu_milli=100 + (i % 5) * 100, mem=256 * MI)
            .obj()
        )

    # warm the solver's shape buckets outside the measured window
    sched.warmup([mk(i, "warm") for i in range(1024)])
    sched.wait_for_idle(timeout=120)

    stop = threading.Event()

    def churn():
        i = 0
        while not stop.is_set():
            p = mk(i, "churn")
            try:
                store.create(p)
                store.delete("Pod", p.meta.name, p.meta.namespace)
            except st.NotFound:
                pass
            i += 1
            if i >= n_churn:
                i = 0
            stop.wait(0.002)

    churner = threading.Thread(target=churn, daemon=True)
    baseline = histogram_baseline(sched.metrics)
    terminated0 = store.watchers_terminated
    churner.start()
    t0 = time.perf_counter()
    for i in range(n_measured):
        store.create(mk(i, "c6"))
    deadline = time.monotonic() + 300
    while time.monotonic() < deadline:
        bound = sum(
            1
            for p in sched.informers.informer("Pod").list()
            if p.meta.name.startswith("c6-") and p.spec.node_name
        )
        if bound >= n_measured:
            break
        time.sleep(0.05)
    dt = time.perf_counter() - t0
    stop.set()
    churner.join(timeout=2)
    sched.stop()  # quiesce BEFORE reading histograms (locked reads,
    mgr.stop()    # but a consistent window beats a racing one)
    hollow.stop()
    from kubernetes_tpu.perf.collectors import MetricsCollector

    collector = MetricsCollector(sched.metrics, baseline=baseline)
    win = collector._windowed(
        "scheduler_scheduling_attempt_duration_seconds",
        sched.metrics.scheduling_attempt_duration,
    )
    # pipeline accounting: how much wall clock the binding stage spent
    # committing, how much of that ran under a device solve (overlap),
    # and the commit share of the solve-stage step (commits are
    # off-thread, so a healthy pipeline keeps the non-overlapped share
    # well under the old in-line ~50%)
    m = sched.metrics
    ws = store.watch_stats()
    step_s = m.schedule_batch_duration.total
    commit_s = m.commit_wave_duration.total
    overlap_s = m.pipeline_overlap.total
    exposed = max(commit_s - overlap_s, 0.0)  # commit time NOT hidden
    return {
        "nodes": n_nodes, "pods": n_measured, "placed": bound,
        "latency_s": round(dt, 4),
        "pods_per_s": round(bound / dt, 1) if dt else 0.0,
        "attempt_p50_ms": round(win.percentile(0.50) * 1000, 2),
        "attempt_p90_ms": round(win.percentile(0.90) * 1000, 2),
        "attempt_p99_ms": round(win.percentile(0.99) * 1000, 2),
        "store_shard_count": store.shard_count,
        "commit_subwaves": m.commit_subwave_duration.n,
        "commit_subwave_s_total": round(m.commit_subwave_duration.total, 4),
        "commit_subwave_overlap_s": round(
            m.commit_subwave_overlap.total, 4
        ),
        "watchers_terminated": store.watchers_terminated - terminated0,
        # overload-protection surface: events compacted by per-watcher
        # coalescing, watchers expired to relist, and the adaptive
        # window the loop settled on
        "watch_coalesced_total": ws["watch_coalesced_total"],
        "watch_expired_total": ws["watch_expired_total"],
        "batch_window_ms": round(m.batch_window_ms.total, 2),
        "overload_level": m.overload_level.total,
        "step_s_total": round(step_s, 4),
        # batch_solve now observes the EXPOSED solve cost (encode +
        # compile + the decode wait the host blocked on); readback hidden
        # behind the pop window lands in decode_overlap_s
        "solve_s_total": round(m.batch_solve_duration.total, 4),
        "solve_compile_s": round(m.solve_compile_duration.total, 4),
        "decode_overlap_s": round(m.decode_overlap.total, 4),
        "wave_solves": m.solve_wave_count.n,
        "wave_fallbacks_total": round(m.solve_wave_fallbacks.total, 1),
        # total solver XLA traces this config's full loop performed
        # (retrace tracker mirror; churn legitimately walks buckets, so
        # this is reported, not gated)
        "solve_retrace_total": round(m.solve_retrace_total.total, 1),
        # incremental-solve accounting (ISSUE 14): partials rows served
        # warm vs re-evaluated across the run, and the resulting hit rate
        "partials_hit_rows": int(m.partials_hit_rows.total),
        "partials_recomputed_rows": int(m.partials_recomputed_rows.total),
        "partials_hit_rate": round(
            m.partials_hit_rows.total
            / max(
                m.partials_hit_rows.total + m.partials_recomputed_rows.total,
                1.0,
            ),
            4,
        ),
        "commit_s_total": round(commit_s, 4),
        "commit_overlap_s": round(overlap_s, 4),
        "commit_waves": m.commit_wave_size.n,
        "commit_share_of_step": round(
            exposed / (step_s + exposed), 4
        ) if step_s + exposed > 0 else 0.0,
    }


# Sustained-churn budget, enforced under BENCH_STRICT=1: the control
# plane must hold a CONSTANT arrival stream with zero destructively-
# terminated watchers.  History: 1050 (pre-sharding) -> 1300 (the
# (kind, namespace)-sharded store) -> 4000 with the pipelined
# multi-lane cycle (ISSUE 12): speculative solve overlap keeps the
# device busy through every commit seam and streamed sub-wave commits
# start each shard's store write the moment its slice of the wave
# stages -> 12000 with the columnar host plane (ISSUE 16): vectorized
# snapshot encode, framed group-commit journal writes, and chunked
# watch fan-out take the host encode/commit path off the critical
# rate.  The generator must outrun the floor (measured pods/s can
# never beat the arrival stream), so the stream default rises with it
# — and BENCH_C6S_RAMP=1 measures the true capacity knee instead of
# self-capping at the configured pace.
STRICT_SUSTAINED_MIN_PODS_PER_S = 12_000.0
# Crash-restart budget (ISSUE 8): after the sustained run the store is
# restarted from its journal+snapshot and must recover the full 50k-node
# / 4k-pod state — snapshot load + journal-suffix replay — inside this
# wall-clock budget with ZERO pods lost or unbound in the recovered
# state.  The bound is intentionally loose against today's measured
# recovery (the gate catches unbounded-replay regressions, not noise).
STRICT_RECOVERY_BUDGET_MS = 30_000.0


def config6_sustained():
    """50k-node sustained churn: a CONSTANT pod arrival stream (not a
    burst) against hollow-node heartbeats — the millions-of-users shape.
    The backpressured watch fan-out + adaptive batch window must hold a
    minimum sustained pods/s with `watchers_terminated == 0`; coalescing
    and Expired-relist absorb any consumer that falls behind.

    The run is JOURNALED (interval group-commit — the write-heavy
    deployment shape) and ends with a crash-restart phase: graceful
    close (drains the final dirty batch), then a fresh Store recovers
    from checkpoint snapshot + journal suffix.  BENCH_STRICT gates the
    recovery wall time and zero lost pods."""
    import tempfile
    import threading

    from kubernetes_tpu import kubemark
    from kubernetes_tpu.api import store as st
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.testing.wrappers import MI, make_pod

    from kubernetes_tpu.perf.collectors import histogram_baseline

    # arrival pacing bounds measurable sustained throughput from above
    # (bound/dt can never beat the stream rate): the 12k STRICT floor
    # needs a stream faster than the floor.  Both knobs are
    # environment-configurable so a capacity hunt does not mean
    # editing the bench:
    #   BENCH_C6S_ARRIVAL=<pods/s>  constant-stream rate
    #       (default 16k — comfortably above the STRICT floor so the
    #       gate measures the control plane, not the generator)
    #   BENCH_C6S_RAMP=1  ramp mode: step the rate up each segment
    #       until the backlog diverges and report the capacity knee
    n_nodes, n_measured = 50_000, 12_000
    arrival_rate = float(os.environ.get("BENCH_C6S_ARRIVAL", "16000"))
    ramp = os.environ.get("BENCH_C6S_RAMP", "") == "1"
    journal_dir = tempfile.mkdtemp(prefix="bench_c6s_")
    journal = os.path.join(journal_dir, "journal.jsonl")
    store = st.Store(
        journal_path=journal, journal_sync="interval", shards=8
    )
    hollow = kubemark.HollowCluster(
        store, n_nodes, heartbeat_interval=10.0
    ).start()
    sched = Scheduler(store, batch_size=1024)
    sched.start()

    def mk(i, prefix):
        # spread the stream across namespaces (the fleet shape): a
        # single-namespace stream hashes every bind wave onto ONE store
        # shard, which silently disables both the concurrent sub-wave
        # commits (PR 9) and the streamed per-shard hand-off (ISSUE 12)
        return (
            make_pod(f"{prefix}-{i}", namespace=f"team-{i % 16}")
            .req(cpu_milli=100 + (i % 5) * 100, mem=256 * MI)
            .obj()
        )

    sched.warmup([mk(i, "warm") for i in range(1024)])
    sched.wait_for_idle(timeout=240)
    # checkpoint the warm 50k-node baseline so the recovery phase below
    # measures snapshot + MEASURED-WINDOW suffix, not setup history
    store.checkpoint()

    terminated0 = store.watchers_terminated
    baseline = histogram_baseline(sched.metrics)

    def _pace(start, count, rate):
        """Create pods [start, start+count) paced at `rate` pods/s —
        the constant-stream primitive both modes share."""
        period = 1.0 / rate
        next_t = time.perf_counter()
        for i in range(start, start + count):
            store.create(mk(i, "c6s"))
            next_t += period
            lag = next_t - time.perf_counter()
            if lag > 0:
                time.sleep(lag)

    def _bound_now():
        return sum(
            1
            for p in sched.informers.informer("Pod").list()
            if p.meta.name.startswith("c6s-") and p.spec.node_name
        )

    knee_rate = 0.0
    t0 = time.perf_counter()
    if ramp:
        # ramp mode: a constant stream can only ever report
        # min(capacity, configured rate) — a self-cap whenever the
        # knob lags the control plane.  Step the rate up per segment;
        # a segment whose backlog drains within the settle budget
        # advances the knee, one whose backlog diverges ends the hunt.
        rate = max(arrival_rate / 4.0, 2_000.0)
        injected = 0
        while injected < n_measured:
            seg = min(max(int(rate * 0.4), 512), n_measured - injected)
            _pace(injected, seg, rate)
            injected += seg
            settle = time.monotonic() + 1.0
            backlog = injected - _bound_now()
            while backlog > 0 and time.monotonic() < settle:
                time.sleep(0.02)
                backlog = injected - _bound_now()
            # a residue under 5% of one second's arrivals is pipeline
            # fill, not divergence
            if backlog <= max(int(rate * 0.05), 64):
                knee_rate = rate
                rate *= 1.5
            else:
                break
        arrival_rate = rate  # the rate the stream ended on
        n_measured = injected
    else:
        # the constant arrival stream: pace creates at arrival_rate
        # instead of dumping a burst — the batch window must adapt to
        # the stream
        _pace(0, n_measured, arrival_rate)
    deadline = time.monotonic() + 600
    while time.monotonic() < deadline:
        bound = sum(
            1
            for p in sched.informers.informer("Pod").list()
            if p.meta.name.startswith("c6s-") and p.spec.node_name
        )
        if bound >= n_measured:
            break
        time.sleep(0.05)
    dt = time.perf_counter() - t0
    sched.stop()
    hollow.stop()
    m = sched.metrics
    if knee_rate:
        m.c6s_arrival_knee.set(knee_rate)
    ws = store.watch_stats()
    from kubernetes_tpu.perf.collectors import MetricsCollector

    win = MetricsCollector(m, baseline=baseline)._windowed(
        "scheduler_scheduling_attempt_duration_seconds",
        m.scheduling_attempt_duration,
    )
    commit_s = m.commit_wave_duration.total
    overlap_s = m.pipeline_overlap.total
    exposed = max(commit_s - overlap_s, 0.0)
    step_s = m.schedule_batch_duration.total
    # crash-restart phase: graceful close (interval-sync's final dirty
    # batch flushes), then recover a fresh store from the same files —
    # the BENCH_STRICT recovery gate
    store.close()
    t_rec = time.perf_counter()
    recovered = st.Store(journal_path=journal)
    recovery_wall_ms = (time.perf_counter() - t_rec) * 1000.0
    rec_bound = sum(
        1
        for p in recovered.list("Pod")[0]
        if p.meta.name.startswith("c6s-") and p.spec.node_name
    )
    return {
        "nodes": n_nodes, "pods": n_measured, "placed": bound,
        "arrival_rate_pods_per_s": arrival_rate,
        # the ramp hunt's capacity knee (0.0 in constant-stream mode):
        # the highest arrival rate whose backlog stayed bounded
        "arrival_knee_pods_per_s": knee_rate,
        "recovery_ms": round(recovery_wall_ms, 1),
        "recovery_snapshot_records": recovered.snapshot_records,
        "recovery_suffix_records": recovered.journal_suffix_records,
        "recovery_lost_pods": bound - rec_bound,
        "latency_s": round(dt, 4),
        "pods_per_s": round(bound / dt, 1) if dt else 0.0,
        "attempt_p50_ms": round(win.percentile(0.50) * 1000, 2),
        "attempt_p90_ms": round(win.percentile(0.90) * 1000, 2),
        "attempt_p99_ms": round(win.percentile(0.99) * 1000, 2),
        "watchers_terminated": store.watchers_terminated - terminated0,
        "watch_coalesced_total": ws["watch_coalesced_total"],
        "watch_expired_total": ws["watch_expired_total"],
        "watch_queue_depth": ws["watch_queue_depth"],
        "batch_window_ms": round(m.batch_window_ms.total, 2),
        "overload_level": m.overload_level.total,
        "overload_shed_total": m.overload_shed_total.total,
        "commit_waves": m.commit_wave_size.n,
        "commit_s_total": round(commit_s, 4),
        "commit_overlap_s": round(overlap_s, 4),
        "commit_share_per_step": round(
            exposed / (step_s + exposed), 4
        ) if step_s + exposed > 0 else 0.0,
        "store_shard_count": store.shard_count,
        "commit_subwaves": m.commit_subwave_duration.n,
        "commit_subwave_s_total": round(m.commit_subwave_duration.total, 4),
        "commit_subwave_overlap_s": round(
            m.commit_subwave_overlap.total, 4
        ),
        "solve_s_total": round(m.batch_solve_duration.total, 4),
        # incremental-solve accounting (ISSUE 14): warm-row hit rate and
        # rows re-evaluated across the sustained stream
        "partials_hit_rows": int(m.partials_hit_rows.total),
        "partials_recomputed_rows": int(m.partials_recomputed_rows.total),
        "partials_hit_rate": round(
            m.partials_hit_rows.total
            / max(
                m.partials_hit_rows.total + m.partials_recomputed_rows.total,
                1.0,
            ),
            4,
        ),
        # pipelined multi-lane cycle (ISSUE 12): lanes in force,
        # per-lane share of the sustained rate, the speculation hit
        # rate (1 - invalidated/dispatched) and the commit lead
        # streaming bought each sub-wave
        "lanes": int(m.lane_count.total) or 1,
        "pods_per_s_per_lane": round(
            (bound / dt) / max(int(m.lane_count.total) or 1, 1), 1
        ) if dt else 0.0,
        "speculative_solves": int(m.speculative_solves_total.total),
        "misspeculations": int(m.misspeculation_total.total),
        "speculation_hit_rate": round(
            1.0
            - m.misspeculation_total.total
            / max(m.speculative_solves_total.total, 1.0),
            4,
        ),
        "subwave_stream_handoffs": m.subwave_stream_lead_ms.n,
        "subwave_stream_lead_ms_p50": round(
            m.subwave_stream_lead_ms.percentile(0.50), 2
        ),
        "subwave_stream_lead_ms_p99": round(
            m.subwave_stream_lead_ms.percentile(0.99), 2
        ),
    }


def config7():
    """c7: 100k hollow nodes / 2048-pod batches solved SHARDED on a
    forced 8-device host-platform mesh — the ≥100k-node scale the
    single chip cannot hold (ROADMAP's structural unlock past 50k).

    Measures the steady mesh-mode schedule_pending step (sharded
    wavefront + NamedSharding-resident mirror), dirtying a bounded set
    of rows between steps so the report can assert that steady-state
    host→device transfer is O(changed rows), not O(N), via the mirror
    delta/resync counters.  A small parity workload per solver family
    (fit/greedy, spread/wavefront, gang/auction) checks mesh vs
    single-chip assignment identity — BENCH_STRICT fails on any
    divergence, on a steady recompile, or on unbounded mirror traffic."""
    import jax

    from kubernetes_tpu.analysis import retrace
    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.parallel.sharded import make_mesh
    from kubernetes_tpu.testing.wrappers import MI, make_pod

    n_devices = len(jax.devices())
    mesh = make_mesh(min(8, n_devices))
    n_nodes, n_pods, dirty_rows = 100_000, 2_048, 16

    # -- mesh vs single-chip parity, one workload per solver family ----
    small_nodes = _mk_nodes(512, zones=8)

    def fit_pods():
        return _mk_basic_pods(256, seed=71, prefix="c7p-fit")

    def spread_pods():
        rng = np.random.default_rng(72)
        return [
            make_pod(f"c7p-sp-{i}")
            .req(cpu_milli=int(rng.choice([100, 250, 500])), mem=256 * MI)
            .label("app", f"svc-{i % 20}")
            .spread(2, api.LABEL_ZONE, "DoNotSchedule", {"app": f"svc-{i % 20}"})
            .obj()
            for i in range(128)
        ]

    def gang_pods():
        rng = np.random.default_rng(73)
        return [
            make_pod(f"c7p-g-{i}")
            .req(cpu_milli=int(rng.choice([250, 500])), mem=256 * MI)
            .group(f"gang-{i % 4}")
            .obj()
            for i in range(256)
        ]

    mesh_parity = {}
    for label, mk_parity in (
        ("fit", fit_pods), ("spread", spread_pods), ("gang", gang_pods),
    ):
        pods = mk_parity()
        single = _Runner(small_nodes, mode="auto")
        multi = _Runner(small_nodes, mode="auto", mesh=mesh)
        mesh_parity[label] = (
            single.sched.schedule_pending(pods)
            == multi.sched.schedule_pending(pods)
        )

    # -- the 100k-node sharded steady step -----------------------------
    nodes = _mk_nodes(n_nodes, zones=64)
    runner = _Runner(nodes, mode="greedy", mesh=mesh)  # pinned: sharded wavefront
    mirror = runner.sched._mirror

    step = [0]

    def mk(tag):
        # dirty a bounded row set between steps: the steady-state mirror
        # sync must move exactly these rows, not the 100k-node snapshot
        base = step[0] * dirty_rows
        for j in range(dirty_rows):
            p = make_pod(f"c7-bind-{tag}-{j}").req(cpu_milli=10, mem=MI).obj()
            runner.sched.assume(p, f"node-{(base + j * 97) % n_nodes}")
        step[0] += 1
        return [
            make_pod(f"c7-{tag}-{i}")
            .req(cpu_milli=100 + (i % 5) * 100, mem=256 * MI)
            .obj()
            for i in range(n_pods)
        ]

    retrace.clear_steady()
    _, first_s, _ = runner.step(mk("warmup"))
    retrace.mark_steady()
    steady0 = retrace.steady_total()
    resync0, delta0 = mirror.resync_total, mirror.delta_rows_total
    names, dt, samples, best_t = None, None, [], {}
    for k in range(_Runner.SAMPLES):
        nms, d, lt = runner.step(mk(f"run{k}"))
        samples.append(round(d, 4))
        if dt is None or d < dt:
            names, dt, best_t = nms, d, lt
    steady_recompiles = retrace.steady_total() - steady0
    retrace.clear_steady()
    delta_rows = mirror.delta_rows_total - delta0
    resyncs = mirror.resync_total - resync0
    dirtied = _Runner.SAMPLES * dirty_rows
    run = _Run(
        names, sum(n is not None for n in names), dt, samples, first_s,
        best_t, steady_recompiles,
    )
    return run.report(
        n_nodes, n_pods,
        solve_shard_count=int(mesh.devices.size),
        mesh_parity=mesh_parity,
        watchers_terminated=0,  # raw-solver config: no store in the loop
        # steady host→device traffic: the delta path must have carried
        # exactly the dirtied rows with zero full resyncs — O(changed
        # rows), not O(N) (BENCH_STRICT gates on the bounded flag)
        mirror_delta_rows=delta_rows,
        mirror_resync_total=resyncs,
        dirtied_rows=dirtied,
        mirror_delta_bounded=bool(resyncs == 0 and delta_rows <= dirtied),
        sharded_solve_fallbacks=runner.sched.sharded_fallbacks,
        **_wave_stats(runner),
    )


# c9 preemption gates (BENCH_STRICT=1): the mixed-priority churn's
# batched PostFilter must hold a minimum sustained preemption rate,
# plan identically to the sequential per-pod loop AND the pure-Python
# oracle, never double-bind a preemptor or evicted victim, keep
# PDB-guarded victims alive while unguarded alternatives exist, and the
# batched planning phase must beat the sequential walk by at least
# STRICT_PREEMPT_SPEEDUP_MIN on the same frozen trace.
STRICT_PREEMPT_MIN_PER_S = 0.5  # measured 1.43/s on a 1-CPU host
STRICT_PREEMPT_SPEEDUP_MIN = 5.0  # measured 9.0x on the frozen trace


def config9():
    """c9: mixed-priority preemption churn at 20k nodes with PDBs — the
    batched PostFilter (one [P, N, K] dry-run per pass,
    scheduler/preemption.py shared_pass) as a first-class workload.

    Phase A (live): every node is filled by a low-priority victim
    (every 4th node's victim guarded by a zero-budget PDB), then a
    mixed-priority preemptor stream (50/100/200) arrives — each
    preemptor needs one eviction, so sustained PostFilter work is the
    only way the stream binds.  An event audit asserts bound-exactly-
    once for preemptors AND evicted victims.

    Phase B (frozen trace): the SAME failed-pod set is planned twice on
    an identical 20k-node state — once through the shared batched pass,
    once through the sequential per-pod walk — proving plan parity and
    measuring the exposed PostFilter planning speedup; a 256-node
    randomized sub-state checks oracle parity (the documented
    reprieve-policy divergence stays pinned).  The planning phase runs
    under the retrace tracker with a steady window: zero recompiles."""
    import threading
    from collections import defaultdict

    from kubernetes_tpu.analysis import retrace
    from kubernetes_tpu.api import store as st
    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.models.batch_scheduler import TPUBatchScheduler
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.scheduler.cache import SchedulerCache
    from kubernetes_tpu.scheduler.config import SchedulerConfiguration
    from kubernetes_tpu.scheduler.metrics import Registry
    from kubernetes_tpu.scheduler.preemption import PreemptionEvaluator
    from kubernetes_tpu.testing.oracle import Oracle
    from kubernetes_tpu.testing.wrappers import GI, make_node, make_pod

    n_nodes, n_preempt = 20_000, 128

    def mk_nodes():
        return [
            make_node(f"node-{i}")
            .capacity(cpu_milli=2000, mem=8 * GI, pods=16)
            .zone(f"zone-{i % 16}")
            .obj()
            for i in range(n_nodes)
        ]

    def mk_victim(i):
        pw = (
            make_pod(f"victim-{i}")
            .req(cpu_milli=1600, mem=GI // 2)
            .priority(i % 5)
            .node_name(f"node-{i}")
        )
        if i % 4 == 0:
            pw = pw.labels(app="guarded")
        p = pw.obj()
        p.status.phase = "Running"
        return p

    def mk_preemptor(i, prefix="hi"):
        return (
            make_pod(f"{prefix}-{i}")
            .req(cpu_milli=1800, mem=GI // 2)
            .priority([50, 100, 200][i % 3])
            .obj()
        )

    # -- phase A: live mixed-priority churn ----------------------------
    store = st.Store(shards=8)
    nodes = mk_nodes()
    for nd in nodes:
        store.create(nd)
    for i in range(n_nodes):
        store.create(mk_victim(i))
    pdb = api.PodDisruptionBudget(
        meta=api.ObjectMeta(name="guard", namespace="default"),
        spec=api.PodDisruptionBudgetSpec(
            selector=api.LabelSelector(match_labels={"app": "guarded"})
        ),
    )
    pdb.status.disruptions_allowed = 0
    store.create(pdb)

    # bound-exactly-once audit over every committed event (preemptors
    # AND victims: an evicted victim must never re-bind)
    bound_nodes = defaultdict(set)
    audit_lock = threading.Lock()
    orig_dispatch = store._dispatch
    orig_wave = store._dispatch_wave

    def check(ev):
        if ev.kind == "Pod" and ev.obj.spec.node_name:
            with audit_lock:
                key = f"{ev.obj.meta.namespace}/{ev.obj.meta.name}"
                bound_nodes[key].add(ev.obj.spec.node_name)

    def dispatch(ev):
        check(ev)
        orig_dispatch(ev)

    def dispatch_wave(kind, events):
        for ev in events:
            check(ev)
        orig_wave(kind, events)

    store._dispatch = dispatch
    store._dispatch_wave = dispatch_wave

    # the latency SLO scales with the scenario: a 20k-node cycle on one
    # host runs seconds of decode, and the DEFAULT 0.5s SLO would pin
    # the overload ladder at level 2 (preemption deferred) on platform
    # slowness alone; the short unschedulable flush is the liveness
    # safety net for parked preemptors between eviction wake-ups
    sched = Scheduler(
        store, batch_size=256,
        config=SchedulerConfiguration(
            batch_latency_slo_seconds=10.0,
            unschedulable_flush_seconds=2.0,
        ),
    )
    sched.start()
    sched.warmup([mk_preemptor(i, "warm") for i in range(64)])
    terminated0 = store.watchers_terminated
    m = sched.metrics
    t0 = time.perf_counter()
    for i in range(n_preempt):
        store.create(mk_preemptor(i))
    deadline = time.monotonic() + 600
    bound = 0
    while time.monotonic() < deadline:
        bound = sum(
            1
            for p in sched.informers.informer("Pod").list()
            if p.meta.name.startswith("hi-") and p.spec.node_name
        )
        if bound >= n_preempt:
            break
        time.sleep(0.1)
    dt = time.perf_counter() - t0
    nominated = m.preemption_attempts.get("nominated")
    sched.stop()
    survivors = {p.meta.name for p in store.list("Pod")[0]}
    guarded_total = sum(1 for i in range(0, n_nodes, 4))
    guarded_alive = sum(
        1 for i in range(0, n_nodes, 4) if f"victim-{i}" in survivors
    )
    evicted = sum(
        1 for i in range(n_nodes) if f"victim-{i}" not in survivors
    )
    double_bound = sum(1 for v in bound_nodes.values() if len(v) > 1)

    # -- phase B: frozen-trace planning parity + speedup ----------------
    tpu = TPUBatchScheduler()
    for nd in nodes:
        tpu.add_node(nd)
    for i in range(n_nodes):
        v = mk_victim(i)
        tpu.assume(v, v.spec.node_name)
    ev = PreemptionEvaluator(
        tpu, SchedulerCache(tpu.state), st.Store(), Registry()
    )
    failed = [mk_preemptor(i, "plan") for i in range(16)]

    def plan_key(got):
        if got is None:
            return None
        cands, ranked, min_k = got
        row, name, victims, _ = cands[ranked[0]]
        return (name, [v.meta.name for v in victims[: int(min_k[ranked[0]])]])

    retrace.clear_steady()
    with ev.shared_pass(failed):
        warm_batched = [plan_key(ev._candidates(p)) for p in failed]
    warm_classic = plan_key(ev._candidates_classic(failed[0]))
    retrace.mark_steady()
    steady0 = retrace.steady_total()
    t_b = time.perf_counter()
    with ev.shared_pass(failed):
        batched_plans = [plan_key(ev._candidates(p)) for p in failed]
    t_batched = time.perf_counter() - t_b
    t_s = time.perf_counter()
    seq_plans = [plan_key(ev._candidates_classic(p)) for p in failed]
    t_sequential = time.perf_counter() - t_s
    steady_recompiles = retrace.steady_total() - steady0
    retrace.clear_steady()
    plan_parity = batched_plans == seq_plans
    del warm_batched, warm_classic

    # oracle parity on a randomized 256-node sub-state (no PDBs — the
    # oracle mirrors the minimal-prefix policy, not budgets)
    rng = np.random.default_rng(91)
    small_nodes = [
        make_node(f"o{i}").capacity(cpu_milli=4000, mem=8 * GI, pods=20).obj()
        for i in range(256)
    ]
    small_bound = []
    for i in range(512):
        p = (
            make_pod(f"ov{i}")
            .req(cpu_milli=int(rng.choice([500, 1000, 1500])), mem=GI)
            .priority(int(rng.integers(0, 5)))
            .node_name(f"o{i % 256}")
            .obj()
        )
        small_bound.append(p)
    tpu2 = TPUBatchScheduler()
    for nd in small_nodes:
        tpu2.add_node(nd)
    for p in small_bound:
        tpu2.assume(p, p.spec.node_name)
    ev2 = PreemptionEvaluator(
        tpu2, SchedulerCache(tpu2.state), st.Store(), Registry()
    )
    oracle_parity = True
    for j in range(6):
        preemptor = (
            make_pod(f"op{j}").req(cpu_milli=3500, mem=GI).priority(100).obj()
        )
        with ev2.shared_pass([preemptor]):
            got = ev2._candidates(preemptor)
        want = Oracle(small_nodes, bound_pods=small_bound).preempt(preemptor)
        have = plan_key(got)
        if want is None:
            oracle_parity &= have is None
        else:
            oracle_parity &= have is not None and have[0] == want[0] and (
                sorted(have[1]) == sorted(v.meta.name for v in want[1])
            )

    return {
        "nodes": n_nodes, "preemptors": n_preempt, "placed": bound,
        "latency_s": round(dt, 4),
        "preempted": nominated,
        "preemptions_per_s": round(nominated / dt, 2) if dt else 0.0,
        "victims_evicted": evicted,
        "guarded_total": guarded_total,
        "guarded_alive": guarded_alive,
        "guarded_survived": bool(guarded_alive == guarded_total),
        "double_bound": double_bound,
        "watchers_terminated": store.watchers_terminated - terminated0,
        "preempt_batch_passes": m.preemption_batch_size.n,
        "preempt_batch_size_avg": round(m.preemption_batch_size.average, 2),
        "preempt_solve_s_total": round(
            m.preemption_solve_duration.total, 4
        ),
        "conflict_serializations": (
            m.preemption_conflict_serializations.total
        ),
        "pdb_blocked_total": m.preemption_pdb_blocked_total.total,
        "preemption_victims": m.preemption_victims.n,
        # phase B: the exposed PostFilter planning cost on one frozen
        # 16-pod trace — batched (one encode + one [P, N, K] dispatch)
        # vs the sequential per-pod walk the batch replaced
        "postfilter_batched_s": round(t_batched, 4),
        "postfilter_sequential_s": round(t_sequential, 4),
        "postfilter_speedup": round(t_sequential / t_batched, 2)
        if t_batched else 0.0,
        "plan_parity": plan_parity,
        "oracle_parity": oracle_parity,
        "steady_recompiles": steady_recompiles,
    }


# c8 fleet gates (BENCH_STRICT=1): the 100k-node hollow fleet's
# sustained lifecycle soak must lose no pod, double-bind no pod,
# terminate no watcher, and the post-soak kill-free recovery (per-shard
# snapshot + journal suffix) must land inside the shared budget.
STRICT_FLEET_NODES = 100_000
STRICT_FLEET_SOAK_PODS = 12_288


def config8():
    """c8: the kubemark fleet harness as a first-class store benchmark —
    100k hollow nodes on an 8-shard JOURNALED store (interval group
    commit), batched wave-committed heartbeats, and a sustained
    pod-lifecycle soak (create → concurrent per-shard bind sub-waves →
    hollow kubelets run → delete) across 8 namespaces so every round
    spreads over the shards.  Reports SLO-style p50/p90/p99 lifecycle
    latency and ends with the crash-restart phase: graceful close, then
    a fresh store recovers all 8 shards (snapshot + suffix) under the
    STRICT_RECOVERY_BUDGET_MS gate.  No solver in the loop: this is the
    control-plane ceiling the solve bench can't see."""
    import tempfile

    from kubernetes_tpu import kubemark
    from kubernetes_tpu.api import store as st

    n_nodes, soak_pods = STRICT_FLEET_NODES, STRICT_FLEET_SOAK_PODS
    journal_dir = tempfile.mkdtemp(prefix="bench_c8_")
    journal = os.path.join(journal_dir, "journal.jsonl")
    store = st.Store(
        journal_path=journal, journal_sync="interval", shards=8
    )
    fleet = kubemark.FleetHarness(
        store, n_nodes, namespaces=8, heartbeat_interval=60.0,
        bind_concurrency=4,
    )
    t_reg = time.perf_counter()
    fleet.start()
    register_s = time.perf_counter() - t_reg
    # checkpoint the registered fleet so the recovery phase measures
    # per-shard snapshot + SOAK-WINDOW suffix, not registration history
    store.checkpoint()
    terminated0 = store.watchers_terminated
    report = fleet.soak(total_pods=soak_pods, round_pods=2_048)
    fleet.stop()
    ws = store.watch_stats()
    nodes_before = len(store.list("Node")[0])
    store.close()
    t_rec = time.perf_counter()
    recovered = st.Store(journal_path=journal)
    recovery_wall_ms = (time.perf_counter() - t_rec) * 1000.0
    report.update({
        "register_s": round(register_s, 2),
        "store_shard_count": store.shard_count,
        "watchers_terminated": store.watchers_terminated - terminated0,
        "watch_coalesced_total": ws["watch_coalesced_total"],
        "watch_expired_total": ws["watch_expired_total"],
        "recovery_ms": round(recovery_wall_ms, 1),
        "recovery_shards": recovered.shard_count,
        "recovery_snapshot_records": recovered.snapshot_records,
        "recovery_suffix_records": recovered.journal_suffix_records,
        "recovery_lost_nodes": nodes_before - len(
            recovered.list("Node")[0]
        ),
    })
    return report


# c10 slice-packing gates (BENCH_STRICT=1): the carve-out scorer must
# realize contiguous placements for nearly every gang of the churn mix
# (prefer policy — quality is the scorer's job, not a filter's) and the
# end-state fragmentation must stay bounded after arrivals/departures.
STRICT_SLICE_CONTIG_MIN = 0.9   # contiguous gangs / completed gangs
STRICT_SLICE_FRAG_MAX = 0.5     # final cluster fragmentation score


def config10():
    """c10: slice packing — 4096 nodes as 64 slices of 4x4x4, mixed gang
    shapes arriving and leaving through the carve-out scorer (prefer
    policy).  Gates placement QUALITY, not just throughput: the
    fragmentation score of the end state and the contiguous-placement
    rate across the churn, plus steady_recompiles == 0 (every round
    reuses one executable — fixed gang mix, one pad bucket)."""
    from kubernetes_tpu.analysis import retrace
    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.models.batch_scheduler import TPUBatchScheduler
    from kubernetes_tpu.ops import slices as slices_ops
    from kubernetes_tpu.testing.wrappers import GI, make_node, make_pod

    rng = np.random.default_rng(10)
    dims, n_slices = (4, 4, 4), 64
    nodes = [
        make_node(f"s{s:02d}-{x}{y}{z}")
        .capacity(cpu_milli=16000, mem=32 * GI, pods=110)
        .label(api.LABEL_TPU_SLICE, f"slice-{s:02d}")
        .label(api.LABEL_TPU_TOPOLOGY, "4x4x4")
        .label(api.LABEL_TPU_COORDS, f"{x},{y},{z}")
        .obj()
        for s in range(n_slices)
        for z in range(dims[2])
        for y in range(dims[1])
        for x in range(dims[0])
    ]
    sched = TPUBatchScheduler(carveout_policy="prefer")
    for nd in nodes:
        sched.add_node(nd)

    # fixed per-round gang mix (same pod count + gang count each round,
    # so every round hits one executable): 26 gangs / 208 pods per round
    mix = (("2x2x1", 4, 12), ("2x2x2", 8, 8), ("4x2x2", 16, 4),
           ("4x4x1", 16, 2))

    def make_round(r):
        pods, gid = [], 0
        for shape, size, count in mix:
            for _k in range(count):
                for i in range(size):
                    p = (
                        make_pod(f"c10-r{r}-g{gid}-{i}")
                        .req(cpu_milli=100)
                        .group(f"c10-r{r}-g{gid}")
                        .obj()
                    )
                    p.spec.tpu_topology = shape
                    pods.append(p)
                gid += 1
        return pods

    live = []  # (pod, node) per placed member, grouped per gang
    stats = {"completed": 0, "contiguous": 0, "fallbacks": 0,
             "carveouts": 0, "placed": 0, "arrived": 0}

    def run_round(r, timed):
        pods = make_round(r)
        t0 = time.perf_counter()
        names = sched.schedule_pending(pods)
        dt = time.perf_counter() - t0
        ds = sched.last_solve
        stats["arrived"] += len(pods)
        stats["placed"] += sum(n is not None for n in names)
        stats["carveouts"] += ds.carveouts or 0
        stats["contiguous"] += ds.contiguous_gangs or 0
        stats["fallbacks"] += ds.carveout_fallbacks or 0
        stats["completed"] += (ds.contiguous_gangs or 0) + (
            ds.carveout_fallbacks or 0
        )
        by_gang = {}
        for p, n in zip(pods, names):
            if n is not None:
                sched.assume(p, n)
                by_gang.setdefault(p.spec.scheduling_group, []).append((p, n))
        live.extend(by_gang.values())
        return dt, float(ds.frag_score or 0.0)

    rounds = 6
    retrace.clear_steady()
    warm_dt, _ = run_round(0, timed=False)  # compiles the executable
    retrace.mark_steady()
    steady0 = retrace.steady_total()
    walls, frags = [], []
    for r in range(1, rounds):
        # departures: half the live gangs leave (seeded), freeing boxes
        rng.shuffle(live)
        for members in live[: len(live) // 2]:
            for p, n in members:
                sched.forget(p)
        del live[: len(live) // 2]
        dt, frag = run_round(r, timed=True)
        walls.append(dt)
        frags.append(frag)
    steady_recompiles = retrace.steady_total() - steady0
    retrace.clear_steady()
    final_frag = slices_ops.fragmentation_report(sched.state.tensors())
    contig_rate = stats["contiguous"] / max(stats["completed"], 1)
    pods_per_round = stats["arrived"] // rounds
    from kubernetes_tpu.kubemark import percentiles

    pct = percentiles(list(walls))
    return {
        "nodes": len(nodes), "pods": stats["arrived"],
        "placed": stats["placed"],
        "slices": n_slices, "slice_dims": "4x4x4",
        "rounds": rounds, "pods_per_round": pods_per_round,
        "latency_s": round(min(walls), 4),
        "pods_per_s": round(pods_per_round / min(walls), 1),
        "latency_p50_s": round(pct["p50"], 4),
        "latency_p90_s": round(pct["p90"], 4),
        "latency_p99_s": round(pct["p99"], 4),
        "commit_share_per_step": 0.0,
        "first_step_s": round(warm_dt, 4),
        "steady_recompiles": steady_recompiles,
        # the quality gates
        "carveouts": stats["carveouts"],
        "contiguous_gangs": stats["contiguous"],
        "carveout_fallbacks": stats["fallbacks"],
        "contiguous_rate": round(contig_rate, 4),
        "frag_score_per_round": [round(f, 4) for f in frags],
        "frag_score_final": round(final_frag["score"], 4),
    }


# c11 incremental-churn gates (BENCH_STRICT=1): with <=1% of node rows
# dirtied per cycle, the warm-started solve (device-resident partials,
# ISSUE 14) must beat the cold solve by at least this factor on the
# same frozen trace with bit-identical placements and zero steady
# recompiles.  Measured 4-7x per steady cycle on a CPU host.
STRICT_PARTIALS_SPEEDUP_MIN = 3.0
STRICT_PARTIALS_DIRTY_FRAC_MAX = 0.01


def config11():
    """c11: incremental churn at 50k nodes — the warm-started solve as
    a first-class workload.  A sustained service-shaped arrival stream
    (64 distinct selector/preferred pod classes recurring every cycle)
    against bounded churn: <=1% of node rows dirtied per cycle via
    assumes walking the cluster.

    Frozen-trace phase: the SAME (churn, batch) trace runs through a
    warm scheduler (PartialsCache on) and a cold one (off, the
    pre-ISSUE-14 path) sharing identical state mutations; every cycle's
    placements must be bit-identical and the cold/warm wall ratio is
    the gated speedup.  The warm side must also hold
    steady_recompiles == 0 — the partials refresh/gather kernels stay
    on their pad buckets."""
    from kubernetes_tpu.analysis import epochs, retrace
    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.models.batch_scheduler import TPUBatchScheduler
    from kubernetes_tpu.testing.wrappers import MI, make_pod

    n_nodes, n_pods, n_svc, dirty_rows = 50_000, 128, 64, 256
    cycles = 4  # timed cycles after the warmup cycle
    nodes = _mk_nodes(n_nodes, zones=64)
    warm = TPUBatchScheduler(mode="greedy", use_partials=True)
    cold = TPUBatchScheduler(mode="greedy", use_partials=False)
    for nd in nodes:
        warm.add_node(nd)
        cold.add_node(nd)

    def mk(r):
        # the recurring service shapes: selector + preferred affinity
        # per svc — the [S, T, E, K, N] matching the warm start hoists
        pods = []
        for i in range(n_pods):
            svc = i % n_svc
            pods.append(
                make_pod(f"c11-r{r}-{i}")
                .req(cpu_milli=100 + (svc % 5) * 100, mem=256 * MI)
                .required_affinity(
                    api.LABEL_ZONE, api.OP_IN,
                    [f"zone-{svc % 64}", f"zone-{(svc + 1) % 64}",
                     f"zone-{(svc + 32) % 64}"],
                )
                .preferred_affinity(
                    10, api.LABEL_ZONE, api.OP_IN, [f"zone-{svc % 64}"]
                )
                .obj()
            )
        return pods

    def churn(r):
        # <=1% of rows dirtied: small binds walking the cluster (the
        # usage-generation rows the partials refresh re-evaluates)
        base = r * dirty_rows
        for j in range(dirty_rows):
            p = make_pod(f"c11-bind-r{r}-{j}").req(cpu_milli=10, mem=MI).obj()
            nm = f"node-{(base + j * 97) % n_nodes}"
            warm.assume(p, nm)
            cold.assume(p, nm)

    retrace.clear_steady()
    # warmup WITH churn: compiles the warm/cold solver executables AND
    # the partials kernels at their steady buckets.  Two warm solves on
    # purpose: the first sync is a FULL reset (eval kernel), only the
    # second hits the dirty-row refresh kernel the steady cycles use.
    churn(0)
    t0 = time.perf_counter()
    warm.schedule_pending(mk(0))
    warm_first = time.perf_counter() - t0
    cold.schedule_pending(mk(0))
    churn(100)
    warm.schedule_pending(mk(100))
    retrace.mark_steady()
    steady0 = retrace.steady_total()
    stats0 = dict(warm._partials.stats())
    audits0, violations0 = epochs.audits_total(), epochs.violations_total()
    warm_walls, cold_walls, parity = [], [], True
    for r in range(1, cycles + 1):
        churn(r)
        t0 = time.perf_counter()
        names_w = warm.schedule_pending(mk(r))
        warm_walls.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        names_c = cold.schedule_pending(mk(r))
        cold_walls.append(time.perf_counter() - t0)
        parity = parity and names_w == names_c
    steady_recompiles = retrace.steady_total() - steady0
    retrace.clear_steady()
    stats = warm._partials.stats()
    hit = stats["hit_rows_total"] - stats0["hit_rows_total"]
    recomputed = (
        stats["recomputed_rows_total"] - stats0["recomputed_rows_total"]
    )
    from kubernetes_tpu.kubemark import percentiles

    pct = percentiles(list(warm_walls))
    return {
        "nodes": n_nodes, "pods": n_pods * cycles,
        "pod_classes": n_svc, "cycles": cycles,
        "dirtied_rows_per_cycle": dirty_rows,
        "dirty_fraction": round(dirty_rows / n_nodes, 5),
        "latency_s": round(min(warm_walls), 4),
        "pods_per_s": round(n_pods / min(warm_walls), 1),
        "latency_p50_s": round(pct["p50"], 4),
        "latency_p90_s": round(pct["p90"], 4),
        "latency_p99_s": round(pct["p99"], 4),
        "commit_share_per_step": 0.0,
        "first_step_s": round(warm_first, 4),
        "steady_recompiles": steady_recompiles,
        # the frozen-trace gates
        "warm_walls_s": [round(w, 4) for w in warm_walls],
        "cold_walls_s": [round(w, 4) for w in cold_walls],
        "warm_parity": parity,
        "warm_speedup": round(sum(cold_walls) / sum(warm_walls), 2),
        # partials accounting over the timed window: rows served warm
        # vs re-evaluated (the O(changes) claim in numbers)
        "partials_hit_rows": hit,
        "partials_recomputed_rows": recomputed,
        "partials_hit_rate": round(hit / max(hit + recomputed, 1), 4),
        "partials_full_recomputes": stats["full_recomputes"],
        # graftcoh epoch audits over the timed window (main() arms the
        # auditor; 0/0 when run standalone-disarmed)
        "coherence_audits": epochs.audits_total() - audits0,
        "coherence_violations": epochs.violations_total() - violations0,
    }


# c12 autoscale-churn gates (BENCH_STRICT=1): under steady WITHIN-bucket
# node churn (±1% nodes/cycle at 50k nodes) the elastic node axis must
# hold zero full mirror re-uploads and zero steady recompiles;
# bucket-boundary oscillation under the shrink dwell must add zero
# resyncs AND zero recompiles (the hysteresis claim); the crossing
# itself must be absorbed by in-place resident grows whose device-side
# pad rows account exactly for the bucket deltas (mirror_grow_rows —
# host→device stays O(changed rows) throughout, gated like c7), at
# least STRICT_AUTOSCALE_WARM_SLOTS_MIN of the partials class rows must
# stay warm across the grow, and every cycle's placements must be
# bit-identical to the full-RESHARDED-rebuild oracle (incremental_grow
# valves off — every transition re-uploads and reseeds from scratch).
STRICT_AUTOSCALE_WARM_SLOTS_MIN = 0.9


def config12():
    """c12: autoscaler churn at 50k nodes — the elastic node axis as a
    first-class workload.

    Frozen-trace phase: a kubemark NodeGroupScaler generates the node
    add/remove stream (scale-ups, drains, deliberate oscillation around
    the 65536 pad-bucket boundary) and the SAME stream drives an
    elastic scheduler (in-place mirror/partials grows) and the
    full-RESHARDED-rebuild oracle (incremental_grow valves off); every
    cycle solves a recurring service-shaped batch and placements must
    match bit-for-bit.  Measured: steady within-bucket churn (zero
    resyncs, zero recompiles), the boundary crossing (grow events, not
    re-uploads; partials class rows stay warm), oscillation under the
    shrink dwell (bucket pinned — zero shape flips), and the post-dwell
    drain shrink.

    Live phase: the existing HPA scales a Deployment against synthetic
    PodMetrics while the NodeGroupScaler (store-backed, CA-shaped
    reconcile policy) adds nodes under pending-pod pressure and drains
    them when idle — sustained node add/remove against the live
    scheduler loop, crossing pad buckets in both directions."""
    from kubernetes_tpu.analysis import retrace
    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.kubemark import NodeGroupScaler
    from kubernetes_tpu.models.batch_scheduler import TPUBatchScheduler
    from kubernetes_tpu.testing.wrappers import MI, make_pod

    base, n_pods, n_svc = 50_000, 128, 32
    churn_half = base // 200  # 250 removed + 250 added = ±1% rows/cycle
    steady_cycles, osc_cycles = 4, 6
    boundary = 65_536  # pad_dim(50_000) — the oscillation axis
    over, under = boundary + 1_024, boundary - 1_024

    elastic = TPUBatchScheduler(mode="greedy", use_partials=True)
    oracle = TPUBatchScheduler(mode="greedy", use_partials=True)
    # the oracle: every node-axis transition takes the full (RESHARDED)
    # re-upload / full-reseed safety path — the parity reference
    oracle._mirror.incremental_grow = False
    oracle._partials.incremental_grow = False
    pair = (elastic, oracle)

    trace_scaler = NodeGroupScaler(group="node", zones=64)

    def apply(added, removed):
        for nd in added:
            for s in pair:
                s.add_node(nd)
        for name in removed:
            for s in pair:
                s.remove_node(name)

    apply(*trace_scaler.scale_to(base))

    def mk(r):
        pods = []
        for i in range(n_pods):
            svc = i % n_svc
            pods.append(
                make_pod(f"c12-r{r}-{i}")
                .req(cpu_milli=100 + (svc % 5) * 100, mem=256 * MI)
                .required_affinity(
                    api.LABEL_ZONE, api.OP_IN,
                    [f"zone-{svc % 64}", f"zone-{(svc + 1) % 64}",
                     f"zone-{(svc + 32) % 64}"],
                )
                .preferred_affinity(
                    10, api.LABEL_ZONE, api.OP_IN, [f"zone-{svc % 64}"]
                )
                .obj()
            )
        return pods

    parity = True

    def cycle(r):
        nonlocal parity
        names_e = elastic.schedule_pending(mk(r))
        names_o = oracle.schedule_pending(mk(r))
        parity = parity and names_e == names_o
        # assume the placements (the next cycle's usage churn — the
        # previous wave's picks are dirty rows, ISSUE 14's contract)
        for p, nm in zip(mk(r), names_e):
            if nm is not None and nm in elastic.state._rows:
                elastic.assume(p, nm)
                oracle.assume(p, nm)
        return names_e

    def churn(r):
        # ±1% membership churn: drain churn_half newest, add churn_half
        # fresh (scale down then up through the scaler so the node-name
        # stream is reproducible)
        apply(*trace_scaler.scale_to(trace_scaler.size() - churn_half))
        apply(*trace_scaler.scale_to(trace_scaler.size() + churn_half))

    retrace.clear_steady()
    # warmup: compile the 65536-bucket executables + the partials
    # eval/refresh kernels (two cycles — the refresh kernel only runs
    # once the store exists, the c11 discipline)
    churn(0)
    t0 = time.perf_counter()
    cycle(0)
    first_step_s = time.perf_counter() - t0
    churn(1)
    cycle(1)

    # -- phase S: steady WITHIN-bucket churn --------------------------------
    e0 = dict(elastic._mirror.stats())
    retrace.mark_steady()
    steady0 = retrace.steady_total()
    walls = []
    for r in range(2, 2 + steady_cycles):
        churn(r)
        t0 = time.perf_counter()
        cycle(r)
        walls.append(time.perf_counter() - t0)
    steady_recompiles = retrace.steady_total() - steady0
    retrace.clear_steady()
    eS = dict(elastic._mirror.stats())
    steady_resyncs = eS["resync_total"] - e0["resync_total"]
    steady_delta_rows = eS["delta_rows_total"] - e0["delta_rows_total"]
    # dirtied per steady cycle: removals + adds (static+usage gens each)
    # + the assumed placements of the previous cycle
    steady_dirtied = steady_cycles * (2 * 2 * churn_half + n_pods)

    # -- phase X: cross the boundary, then oscillate under the dwell --------
    slots_before = set(elastic._partials._slots.keys())
    full0 = elastic._partials.stats()["full_recomputes"]
    apply(*trace_scaler.scale_to(over))  # the crossing (one sync)
    cycle(100)
    grow_after_cross = dict(elastic._mirror.stats())
    for k in range(osc_cycles):
        apply(*trace_scaler.scale_to(under if k % 2 == 0 else over))
        cycle(101 + k)
    eX = dict(elastic._mirror.stats())
    osc_resyncs = eX["resync_total"] - grow_after_cross["resync_total"]
    # the dwell must pin the bucket across the oscillation: the crossing
    # is the ONLY shape change (grow_syncs moves once, then holds)
    osc_grows = eX["grow_syncs"] - grow_after_cross["grow_syncs"]
    slots_after = set(elastic._partials._slots.keys())
    warm_slots_frac = (
        len(slots_before & slots_after) / max(len(slots_before), 1)
    )
    partials_reseeds_x = (
        elastic._partials.stats()["full_recomputes"] - full0
    )

    # -- phase D: drain home; the shrink fires only after the dwell ---------
    apply(*trace_scaler.scale_to(base))
    pre_shrink_bucket = elastic.state.node_axis_bucket
    for k in range(elastic.state.bucket_shrink_dwell + 1):
        churn(200 + k)
        cycle(200 + k)
    post_shrink_bucket = elastic.state.node_axis_bucket
    eD = dict(elastic._mirror.stats())
    pD = dict(elastic._partials.stats())

    from kubernetes_tpu.kubemark import percentiles

    pct = percentiles(list(walls))
    live = _c12_live_phase()
    return {
        "nodes": base, "pods": n_pods, "pod_classes": n_svc,
        "churn_frac_per_cycle": round(2 * churn_half / base, 4),
        "latency_s": round(min(walls), 4),
        "pods_per_s": round(n_pods / min(walls), 1),
        "latency_p50_s": round(pct["p50"], 4),
        "latency_p90_s": round(pct["p90"], 4),
        "latency_p99_s": round(pct["p99"], 4),
        "commit_share_per_step": 0.0,
        "first_step_s": round(first_step_s, 4),
        "steady_recompiles": steady_recompiles,
        # the elastic-axis gates
        "oracle_parity": parity,
        "steady_resyncs": steady_resyncs,
        "steady_delta_rows": steady_delta_rows,
        "steady_dirtied_rows": steady_dirtied,
        "steady_delta_bounded": steady_delta_rows <= steady_dirtied,
        "grow_syncs": eD["grow_syncs"],
        "mirror_grow_rows": eD["grow_rows_total"],
        # every grow's device-side pad rows must account exactly for the
        # bucket deltas (one 65536->131072 crossing; the drain shrink
        # adds no rows) — anything more means a hidden re-upload
        "grow_rows_expected": 131_072 - 65_536,
        "grow_bounded": eD["grow_rows_total"] == 131_072 - 65_536,
        "osc_resyncs": osc_resyncs,
        "osc_grows": osc_grows,
        "warm_slots_frac": round(warm_slots_frac, 4),
        "partials_reseeds_in_osc": partials_reseeds_x,
        "partials_grows": pD["grows"],
        "pre_shrink_bucket": pre_shrink_bucket,
        "post_shrink_bucket": post_shrink_bucket,
        "shrink_served": post_shrink_bucket == boundary,
        "mirror_resync_total": eD["resync_total"],
        "compactions_total": elastic.state.compactions_total,
        "compaction_moved_rows": elastic.state.compaction_moved_rows_total,
        "scaler_nodes_added": trace_scaler.nodes_added,
        "scaler_nodes_removed": trace_scaler.nodes_removed,
        # top-level so the generic terminated gate sees the live phase
        "watchers_terminated": live["watchers_terminated"],
        **{f"live_{k}": v for k, v in live.items()},
    }


def _c12_live_phase():
    """The autoscaler-in-the-loop half of c12: a live Scheduler over a
    journal-less store while the existing HorizontalPodAutoscaler
    scales a Deployment (synthetic PodMetrics drive utilization) and a
    store-backed NodeGroupScaler reacts to pending-pod pressure / idle
    capacity — sustained node add/remove, pad buckets crossed in both
    directions, zero destructive watcher terminations."""
    import threading

    from kubernetes_tpu import kubemark
    from kubernetes_tpu.api import store as st
    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.controllers import ControllerManager
    from kubernetes_tpu.controllers.deployment import DeploymentController
    from kubernetes_tpu.controllers.podautoscaler import (
        HorizontalPodAutoscalerController,
    )
    from kubernetes_tpu.controllers.replicaset import ReplicaSetController
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.testing.wrappers import MI

    store = st.Store()
    # the permanent fleet sits just UNDER the 512 pad bucket, so the
    # autoscaler's scale-up crosses a large boundary where the dirty
    # fraction is small enough for the in-place grow path (tiny fleets
    # cross small buckets in over-fraction bulk, which correctly takes
    # the full-upload safety path instead).  Hollow kubelets run the
    # status half (-> Running) for every hollow-* node; the scaler's
    # group shares the prefix so scaled-up nodes' pods run too.
    # base nodes are deliberately too small for the web pods (100m vs
    # 2000m requests): every replica PENDS until the scaler provisions
    # group capacity — the pressure signal the CA policy keys on
    hollow = kubemark.HollowCluster(
        store, 504, cpu_milli=100, heartbeat_interval=10.0
    ).start()
    scaler = kubemark.NodeGroupScaler(
        store, group="hollow-asg", cpu_milli=32000, mem=64 * kubemark.GI,
        max_nodes=64,
    )
    pods_per_node = 16  # 32000m / 2000m requests

    def hpa_factory(*args, **kw):
        return HorizontalPodAutoscalerController(
            *args, downscale_stabilization_s=0.2, **kw
        )

    hpa_factory.KIND = "HorizontalPodAutoscaler"
    mgr = ControllerManager(
        store,
        controllers=[DeploymentController, ReplicaSetController, hpa_factory],
    ).start()
    sched = Scheduler(store, batch_size=256)
    sched.start()
    stop = threading.Event()

    def autoscale_loop():
        # the CA-shaped reconcile: pending pods scale the group up,
        # idle group capacity drains it one step at a time
        while not stop.wait(0.05):
            pods, _ = store.list("Pod")
            pending = sum(1 for p in pods if not p.spec.node_name)
            used = {p.spec.node_name for p in pods if p.spec.node_name}
            idle = sum(
                1 for i in range(scaler.size())
                if f"{scaler.group}-{i}" not in used
            )
            try:
                scaler.reconcile(
                    pending, pods_per_node, idle_nodes=idle,
                    step=2, idle_headroom=1, up_step_cap=4,
                )
            except Exception:  # noqa: BLE001 — reconcile is best-effort
                pass

    ca = threading.Thread(target=autoscale_loop, daemon=True)
    ca.start()

    labels = {"app": "web"}
    deployment = api.Deployment(
        meta=api.ObjectMeta(name="web"),
        spec=api.DeploymentSpec(
            replicas=8,
            selector=api.LabelSelector(match_labels=labels),
            template=api.PodTemplateSpec(
                meta=api.ObjectMeta(labels=labels),
                spec=api.PodSpec(
                    containers=[
                        api.Container(
                            requests={api.CPU: 2000, api.MEMORY: 64 * MI}
                        )
                    ]
                ),
            ),
        ),
    )
    peak_target, idle_target = 192, 8
    unbound_at_peak = 0
    grow_syncs = 0
    replicas = 0
    peak_nodes = 0
    try:
        store.create(deployment)
        store.create(
            api.HorizontalPodAutoscaler(
                meta=api.ObjectMeta(name="web-hpa"),
                spec=api.HorizontalPodAutoscalerSpec(
                    scale_target_ref=api.ScaleTargetRef("Deployment", "web"),
                    min_replicas=idle_target,
                    max_replicas=peak_target,
                    target_cpu_utilization_percentage=50,
                ),
            )
        )

        def feed_metrics(cpu):
            for p in store.list("Pod")[0]:
                m = api.PodMetrics(
                    meta=api.ObjectMeta(
                        name=p.meta.name, namespace=p.meta.namespace
                    ),
                    usage={api.CPU: cpu},
                    timestamp=time.time(),
                )
                try:
                    store.create(m)
                except st.AlreadyExists:
                    cur = store.get("PodMetrics", p.meta.name, p.meta.namespace)
                    cur.usage = {api.CPU: cpu}
                    store.update(cur, force=True)

        # scale-up half: hot metrics drive the HPA toward max_replicas,
        # pending pods drive the scaler up with it
        deadline = time.monotonic() + 120
        replicas = 8
        while time.monotonic() < deadline:
            feed_metrics(2000)  # 100% utilization vs the 50% target
            pods, _ = store.list("Pod")
            replicas = sum(1 for p in pods if p.meta.name.startswith("web-"))
            bound = sum(
                1
                for p in pods
                if p.meta.name.startswith("web-") and p.spec.node_name
            )
            if replicas >= peak_target and bound >= replicas:
                break
            time.sleep(0.1)
        pods, _ = store.list("Pod")
        unbound_at_peak = sum(
            1
            for p in pods
            if p.meta.name.startswith("web-") and not p.spec.node_name
        )
        peak_nodes = scaler.size()
        # scale-down half: idle metrics shrink the deployment, the
        # ReplicaSet deletes pods, idle capacity drains the node group
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            feed_metrics(100)  # 5% utilization
            pods, _ = store.list("Pod")
            n_web = sum(1 for p in pods if p.meta.name.startswith("web-"))
            if n_web <= idle_target * 2 and scaler.size() < peak_nodes:
                break
            time.sleep(0.1)
        grow_syncs = sched.tpu._mirror.grow_syncs
    finally:
        stop.set()
        ca.join(timeout=5)
        sched.stop()
        mgr.stop()
        hollow.stop()
    return {
        "replicas_peak": replicas,
        "unbound_at_peak": unbound_at_peak,
        "nodes_peak": peak_nodes,
        "nodes_final": scaler.size(),
        "scaler_nodes_added": scaler.nodes_added,
        "scaler_nodes_removed": scaler.nodes_removed,
        "mirror_grow_syncs": grow_syncs,
        "mirror_resync_total": sched.tpu._mirror.resync_total,
        "node_axis_bucket": sched.tpu.state.node_axis_bucket,
        "watchers_terminated": store.watchers_terminated,
    }


# c13 serving-fleet gates (BENCH_STRICT=1): the serving plane must hold
# ≥1000 concurrent multiplexed HTTP informers over ≥2 read replicas, a
# mid-soak replica kill must recover (every stream failed over and
# caught up on a post-kill marker) inside the shared restart budget
# with NO wedged watcher, delivery must stay rv-monotonic per shard
# segment with zero lost pods and zero double binds, and p99
# watch-delivery latency is always reported.
STRICT_SERVING_INFORMERS = 1_000
STRICT_SERVING_REPLICAS = 2
STRICT_SERVING_SOAK_PODS = 4_096


def config13():
    """c13: the fleet-scale serving plane — an APIServerReplicaSet over
    the sharded store, a thousand informers multiplexed over HTTP
    (client/watchmux.py, a few selector loops instead of a thousand
    threads), pods created THROUGH the HTTP path and bound via the
    store's wave path while hollow kubelets run them, and a mid-soak
    replica kill + restart.  Measures p99 watch-delivery latency
    (create-call → event delivery), failover/recovery health, and the
    adaptive-APF serving gauges the scheduler mirrors.

    Env knobs (smoke-scale a laptop run):
      BENCH_C13_INFORMERS=<n>  informer count   (default 1000)
      BENCH_C13_REPLICAS=<n>   replica count    (default 2)
      BENCH_C13_PODS=<n>       soak pods        (default 4096)
    """
    from kubernetes_tpu import kubemark
    from kubernetes_tpu.api import store as st

    informers = int(
        os.environ.get("BENCH_C13_INFORMERS", STRICT_SERVING_INFORMERS)
    )
    replicas = int(
        os.environ.get("BENCH_C13_REPLICAS", STRICT_SERVING_REPLICAS)
    )
    soak_pods = int(
        os.environ.get("BENCH_C13_PODS", STRICT_SERVING_SOAK_PODS)
    )
    store = st.Store(shards=8)
    fleet = kubemark.FleetHarness(
        store, n_nodes=256, namespaces=8, heartbeat_interval=60.0,
        bind_concurrency=4,
    )
    fleet.start()
    terminated0 = store.watchers_terminated
    try:
        report = fleet.serve(
            replicas=replicas,
            informers=informers,
            soak_pods=soak_pods,
            round_pods=min(1_024, soak_pods),
            recovery_budget_s=STRICT_RECOVERY_BUDGET_MS / 1000.0,
        )
    finally:
        fleet.stop()
    report["watchers_terminated"] = (
        store.watchers_terminated - terminated0
    )
    return report


def main() -> None:
    import sys

    from kubernetes_tpu.analysis import epochs, ledger, retrace
    from kubernetes_tpu.utils import trace as tracemod

    tracemod.drain_overruns()  # measure only this run's traces
    # arm the recompile-discipline runtime tracker for the whole run:
    # each _Runner marks its steady window after warmup, and the churn
    # config's scheduler mirrors the trace total into
    # scheduler_solve_retrace_total (perf/collectors SCALAR_METRICS).
    # c6 deliberately has no steady window — churn walks the pod-bucket
    # ladder by design, so its first-seen buckets are not steady-state
    # retraces.  The graftcoh epoch auditor is armed alongside it: every
    # resident buffer a solve consumes is audited against the scheduler
    # cache's current generations, and BENCH_STRICT fails on any
    # violation (docs/static_analysis.md coherence section).  The
    # graftobl exactly-once ledger rides along: every popped pod, cache
    # assume, APF seat, arbiter slot and inflight counter must discharge
    # exactly once, and BENCH_STRICT fails on any leak or
    # double-discharge (docs/static_analysis.md obligations section).
    with retrace.tracked(), epochs.tracked() as coh, \
            ledger.tracked() as led:
        extra = {
            "c1_fit_500": config1(),
            "c2_balanced_5k": config2(),
            "c3_spread_10k": config3(),
            "c3s_spread_1k": config3s(),
            "c4_interpod_20k": config4(),
            "c4s_interpod_1k": config4s(),
            "c5_gang_50k": config5(),
            "c6_churn_5k": config6(),
            "c6s_sustained_50k": config6_sustained(),
            "c7_sharded_100k": config7(),
            "c8_store_100k": config8(),
            "c9_preempt_churn": config9(),
            "c10_slice_pack": config10(),
            "c11_incremental_churn": config11(),
            "c12_autoscale_churn": config12(),
            "c13_serving_fleet": config13(),
        }
    # every over-threshold schedule_batch cycle, with its per-step share
    # (commit- and solve-share per step are readable straight off the
    # steps list); BENCH_STRICT=1 turns any such trace into a non-zero
    # exit so CI fails on slow cycles instead of shipping them as log
    # warnings
    overruns = tracemod.drain_overruns()

    def _share(o, prefixes):
        if not o["total_s"]:
            return 0.0
        return round(
            sum(dt for w, dt in o["steps"] if w.startswith(prefixes))
            / o["total_s"], 4,
        )

    extra["trace_overruns"] = [
        {
            "name": o["name"],
            "total_s": o["total_s"],
            "steps": o["steps"],
            # the steps are the cycle's spans on the scheduling lane
            # (utils/trace.py): staging + hand-off is its commit half,
            # encode + dispatch + the exposed decode wait its solve half
            "commit_share": _share(
                o, ("sched.stage", "sched.wave_handoff")
            ),
            "solve_share": _share(
                o, ("sched.encode", "sched.dispatch", "sched.decode_wait")
            ),
            **o["fields"],
        }
        for o in overruns
    ]
    # steady-state solve-half regression gate: the 1k-pod greedy shapes
    # must hold their budget (2x better than the BENCH_r05 traces)
    solve_regressions = [
        {
            "config": name,
            "latency_s": extra[name]["latency_s"],
            "budget_s": budget,
        }
        for name, budget in STRICT_SOLVE_BUDGETS_S.items()
        if extra[name]["latency_s"] > budget
    ]
    extra["solve_regressions"] = solve_regressions
    # recompile-discipline gate: zero steady-state retraces on every
    # fixed-shape scenario (c6 reports through solve_retrace_total
    # instead — see the tracked() comment above)
    steady_retraces = {
        name: cfg["steady_recompiles"]
        for name, cfg in extra.items()
        if isinstance(cfg, dict) and cfg.get("steady_recompiles")
    }
    extra["steady_retraces"] = steady_retraces
    # graftcoh epoch-auditor totals for the whole run (the warm-path
    # configs — c11/c12 — drive the audited consume sites)
    extra["coherence"] = {
        "audits_total": coh.audits_total,
        "violations_total": coh.violations_total,
        "rollbacks_blocked": coh.rollbacks_blocked,
        "violations": coh.violations[:5],
    }
    # graftobl ledger totals for the whole run (leaks are computed at
    # this point — after every runner quiesced, so anything still held
    # really is leaked, not merely in flight)
    extra["obligations"] = {
        "tracked_total": led.tracked_total,
        "leaks_total": led.leaks_total,
        "double_discharge_total": led.double_discharge_total,
        "leaks": led.outstanding()[:5],
        "double_discharges": led.double[:5],
    }
    c5 = extra["c5_gang_50k"]
    pods_per_s = 10_000 / c5["latency_s"]
    print(
        json.dumps(
            {
                "metric": "gang_burst_latency_50k_nodes_10k_pods",
                "value": c5["latency_s"],
                "unit": "s",
                "vs_baseline": round(pods_per_s / BASELINE_PODS_PER_SEC, 2),
                "extra": extra,
            }
        )
    )
    if os.environ.get("BENCH_STRICT") == "1":
        failures = []
        n_slow = sum(o["name"] == "schedule_batch" for o in overruns)
        if n_slow:
            failures.append(
                f"{n_slow} over-threshold schedule_batch trace(s)"
            )
        if solve_regressions:
            failures.append(
                "steady-state solve-half over budget: "
                + ", ".join(
                    f"{r['config']}={r['latency_s']}s (budget {r['budget_s']}s)"
                    for r in solve_regressions
                )
            )
        if steady_retraces:
            failures.append(
                "steady-state XLA retraces (pad-bucket escape): "
                + ", ".join(
                    f"{name}={n}" for name, n in sorted(steady_retraces.items())
                )
            )
        # graftcoh gate: the armed auditor must have observed the warm
        # path (audits > 0) and found every consumed resident epoch
        # consistent (violations == 0)
        if coh.violations_total:
            failures.append(
                f"{coh.violations_total} resident-epoch coherence "
                "violation(s): " + "; ".join(coh.violations[:3])
            )
        if not coh.audits_total:
            failures.append(
                "coherence auditor armed but performed 0 audits (warm "
                "path never reached an audited consume site)"
            )
        # graftobl gates: the armed ledger must have tracked real
        # acquisitions and seen every one discharged exactly once
        obl = extra["obligations"]
        if obl["leaks_total"]:
            failures.append(
                f"{obl['leaks_total']} leaked obligation(s): "
                + "; ".join(obl["leaks"][:3])
            )
        if obl["double_discharge_total"]:
            failures.append(
                f"{obl['double_discharge_total']} obligation "
                "double-discharge(s): "
                + "; ".join(obl["double_discharges"][:3])
            )
        if not obl["tracked_total"]:
            failures.append(
                "obligation ledger armed but tracked 0 acquisitions "
                "(hooks never reached)"
            )
        # overload-protection gates: NO scenario may destructively
        # terminate a watcher (backpressure must absorb the load), and
        # the sustained-churn stream must hold its throughput floor
        terminated = {
            name: cfg["watchers_terminated"]
            for name, cfg in extra.items()
            if isinstance(cfg, dict) and cfg.get("watchers_terminated")
        }
        if terminated:
            failures.append(
                "watchers terminated (backpressure must hold): "
                + ", ".join(f"{k}={v}" for k, v in sorted(terminated.items()))
            )
        c6s = extra["c6s_sustained_50k"]
        # in ramp mode the whole-run average includes the deliberately
        # slow early segments; the knee is the sustained figure there
        sustained = max(
            c6s["pods_per_s"], c6s.get("arrival_knee_pods_per_s", 0.0)
        )
        if sustained < STRICT_SUSTAINED_MIN_PODS_PER_S:
            failures.append(
                f"sustained churn below budget: {sustained} < "
                f"{STRICT_SUSTAINED_MIN_PODS_PER_S} pods/s"
            )
        # crash-restart recovery gates: snapshot+suffix recovery of the
        # post-run store must finish inside the fixed budget and lose
        # NOTHING (close() flushed the final interval-sync batch)
        if c6s["recovery_ms"] > STRICT_RECOVERY_BUDGET_MS:
            failures.append(
                f"c6s recovery over budget: {c6s['recovery_ms']}ms > "
                f"{STRICT_RECOVERY_BUDGET_MS}ms"
            )
        if c6s["recovery_lost_pods"]:
            failures.append(
                f"c6s recovery lost {c6s['recovery_lost_pods']} bound "
                "pod(s)"
            )
        # sharded-solve gates: mesh placements must be assignment-
        # identical to single-chip, and steady mesh-mode host→device
        # transfer must be O(changed rows) (zero resyncs, delta rows
        # bounded by the dirtied set)
        c7 = extra["c7_sharded_100k"]
        bad_parity = sorted(
            k for k, ok in c7["mesh_parity"].items() if not ok
        )
        if bad_parity:
            failures.append(
                "sharded solve diverged from single-chip on: "
                + ", ".join(bad_parity)
            )
        if not c7["mirror_delta_bounded"]:
            failures.append(
                "c7 steady host→device transfer not O(changed rows): "
                f"{c7['mirror_delta_rows']} delta rows / "
                f"{c7['mirror_resync_total']} resyncs for "
                f"{c7['dirtied_rows']} dirtied rows"
            )
        # fleet-harness gates: the 100k-node soak must be lossless
        # (every created pod ran exactly once on exactly one node) and
        # the 8-shard recovery must fit the shared restart budget
        c8 = extra["c8_store_100k"]
        if c8["lost_pods"]:
            failures.append(f"c8 fleet lost {c8['lost_pods']} pod(s)")
        if c8["double_bound_pods"]:
            failures.append(
                f"c8 fleet double-bound {c8['double_bound_pods']} pod(s)"
            )
        if c8["recovery_lost_nodes"]:
            failures.append(
                f"c8 recovery lost {c8['recovery_lost_nodes']} node(s)"
            )
        if c8["recovery_ms"] > STRICT_RECOVERY_BUDGET_MS:
            failures.append(
                f"c8 per-shard recovery over budget: {c8['recovery_ms']}ms"
                f" > {STRICT_RECOVERY_BUDGET_MS}ms"
            )
        # batched-preemption gates: oracle + batched-vs-sequential plan
        # parity, bound-exactly-once across preemptors AND evicted
        # victims, PDB-guarded victims alive, the sustained preemption
        # floor, and the ≥5x exposed-PostFilter planning speedup on the
        # same frozen trace (steady_recompiles rides the generic gate)
        c9 = extra["c9_preempt_churn"]
        if not c9["oracle_parity"]:
            failures.append("c9 batched preemption diverged from the oracle")
        if not c9["plan_parity"]:
            failures.append(
                "c9 batched plans diverged from the sequential walk"
            )
        if c9["double_bound"] or c9["placed"] < c9["preemptors"]:
            failures.append(
                f"c9 bound-exactly-once violated: {c9['double_bound']} "
                f"double binds, {c9['placed']}/{c9['preemptors']} "
                "preemptors placed"
            )
        if not c9["guarded_survived"]:
            failures.append(
                f"c9 evicted PDB-guarded victims: {c9['guarded_alive']}/"
                f"{c9['guarded_total']} survived"
            )
        if c9["preemptions_per_s"] < STRICT_PREEMPT_MIN_PER_S:
            failures.append(
                f"c9 preemption throughput below floor: "
                f"{c9['preemptions_per_s']} < {STRICT_PREEMPT_MIN_PER_S}/s"
            )
        if c9["postfilter_speedup"] < STRICT_PREEMPT_SPEEDUP_MIN:
            failures.append(
                f"c9 batched PostFilter speedup below floor: "
                f"{c9['postfilter_speedup']}x < "
                f"{STRICT_PREEMPT_SPEEDUP_MIN}x"
            )
        # slice-packing quality gates: the carve-out scorer must keep
        # placing gangs contiguously through churn and the end state
        # must not shatter (steady_recompiles rides the generic gate)
        c10 = extra["c10_slice_pack"]
        if c10["contiguous_rate"] < STRICT_SLICE_CONTIG_MIN:
            failures.append(
                f"c10 contiguous-placement rate below floor: "
                f"{c10['contiguous_rate']} < {STRICT_SLICE_CONTIG_MIN}"
            )
        c11 = extra["c11_incremental_churn"]
        if not c11["warm_parity"]:
            failures.append(
                "c11 warm-started placements diverged from cold solves "
                "(the partials parity gate)"
            )
        if c11["warm_speedup"] < STRICT_PARTIALS_SPEEDUP_MIN:
            failures.append(
                f"c11 warm-solve speedup {c11['warm_speedup']}x < "
                f"{STRICT_PARTIALS_SPEEDUP_MIN}x on the frozen churn trace"
            )
        if c11["dirty_fraction"] > STRICT_PARTIALS_DIRTY_FRAC_MAX:
            failures.append(
                f"c11 dirtied {c11['dirty_fraction']} of rows per cycle > "
                f"{STRICT_PARTIALS_DIRTY_FRAC_MAX} (the <=1% churn contract)"
            )
        if c10["frag_score_final"] > STRICT_SLICE_FRAG_MAX:
            failures.append(
                f"c10 fragmentation above ceiling: "
                f"{c10['frag_score_final']} > {STRICT_SLICE_FRAG_MAX}"
            )
        # elastic-node-axis gates: within-bucket autoscaler churn must
        # never force a full mirror re-upload, boundary oscillation
        # under the shrink dwell must not flip shapes, bucket crossings
        # must be absorbed by in-place grows with exact pad-row
        # accounting (transfer stays O(changed rows)), the partials
        # class rows must stay warm across the grow, and the elastic
        # placements must match the full-RESHARDED-rebuild oracle
        # bit-for-bit (steady_recompiles rides the generic gate)
        c12 = extra["c12_autoscale_churn"]
        if not c12["oracle_parity"]:
            failures.append(
                "c12 elastic placements diverged from the full-rebuild "
                "oracle"
            )
        if c12["steady_resyncs"]:
            failures.append(
                f"c12 within-bucket churn forced {c12['steady_resyncs']} "
                "full mirror re-upload(s)"
            )
        if not c12["steady_delta_bounded"]:
            failures.append(
                "c12 steady host→device transfer not O(changed rows): "
                f"{c12['steady_delta_rows']} delta rows for "
                f"{c12['steady_dirtied_rows']} dirtied"
            )
        if c12["osc_resyncs"] or c12["osc_grows"]:
            failures.append(
                "c12 bucket-boundary oscillation escaped the shrink "
                f"dwell: {c12['osc_resyncs']} resyncs / "
                f"{c12['osc_grows']} shape changes during oscillation"
            )
        if not c12["grow_bounded"]:
            failures.append(
                "c12 bucket crossing not absorbed in place: "
                f"{c12['mirror_grow_rows']} grow rows != "
                f"{c12['grow_rows_expected']} expected "
                f"({c12['mirror_resync_total']} resyncs total)"
            )
        if c12["warm_slots_frac"] < STRICT_AUTOSCALE_WARM_SLOTS_MIN:
            failures.append(
                f"c12 partials class rows went cold across the grow: "
                f"{c12['warm_slots_frac']} warm < "
                f"{STRICT_AUTOSCALE_WARM_SLOTS_MIN}"
            )
        if c12["partials_reseeds_in_osc"] or not c12["partials_grows"]:
            failures.append(
                "c12 partials did not stay warm through the crossing: "
                f"{c12['partials_reseeds_in_osc']} reseed(s) during "
                f"oscillation, {c12['partials_grows']} in-place grow(s) "
                "— node churn must not flush the cache (the per-key "
                "expansion watermark)"
            )
        if not c12["shrink_served"]:
            failures.append(
                "c12 post-dwell drain never shrank the bucket "
                f"(still {c12['post_shrink_bucket']})"
            )
        if c12["live_unbound_at_peak"]:
            failures.append(
                f"c12 live autoscale left {c12['live_unbound_at_peak']} "
                "pod(s) unbound at peak"
            )
        if not c12["live_mirror_grow_syncs"]:
            failures.append(
                "c12 live autoscale crossing never took the in-place "
                "grow path (0 grow syncs)"
            )
        # serving-plane gates: the replica-set soak must run at fleet
        # scale (>=1000 informers over >=2 replicas), the mid-soak
        # replica kill must recover inside the shared restart budget
        # with no wedged watcher, delivery must stay rv-monotonic with
        # zero lost pods / double binds, and p99 delivery latency must
        # be reported (NaN-free) for the SLO trendline
        c13 = extra["c13_serving_fleet"]
        if (
            c13["informers"] < STRICT_SERVING_INFORMERS
            or c13["replicas"] < STRICT_SERVING_REPLICAS
        ):
            failures.append(
                f"c13 ran under scale: {c13['informers']} informers / "
                f"{c13['replicas']} replicas < "
                f"{STRICT_SERVING_INFORMERS}/{STRICT_SERVING_REPLICAS}"
            )
        if c13["recovery_ms"] is None:
            failures.append("c13 never exercised the mid-soak replica kill")
        elif c13["recovery_ms"] > STRICT_RECOVERY_BUDGET_MS:
            failures.append(
                f"c13 replica-kill recovery over budget: "
                f"{c13['recovery_ms']}ms > {STRICT_RECOVERY_BUDGET_MS}ms"
            )
        if c13["wedged_watchers"]:
            failures.append(
                f"c13 left {c13['wedged_watchers']} watcher(s) wedged "
                "after the replica kill"
            )
        if c13["rv_violations"]:
            failures.append(
                f"c13 rv-monotonic delivery violated {c13['rv_violations']}"
                " time(s)"
            )
        if c13["lost_watch_pods"] or c13["double_bound_pods"]:
            failures.append(
                f"c13 lost {c13['lost_watch_pods']} pod(s) / "
                f"double-bound {c13['double_bound_pods']} through the "
                "serving path"
            )
        if not (c13["watch_p99_ms"] == c13["watch_p99_ms"]):
            failures.append("c13 p99 watch-delivery latency not measured")
        if failures:
            print("BENCH_STRICT: " + "; ".join(failures), file=sys.stderr)
            sys.exit(1)


if __name__ == "__main__":
    main()
