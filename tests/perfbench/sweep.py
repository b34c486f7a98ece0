"""The rate sweep that finds the knee of an open loop, once, on the chip: one
process, one set-up, rates stepped up, each held long enough to see whether
(created - bound) grows.  Its table goes into PERF.md and four fifths of the
knee into the traffic file; no run of a cell searches for a rate."""

from __future__ import annotations

import time

from perfbench import harness, reduce


class Tagged:
    """A deployment whose pods carry a step's tag in their names, so that the
    steps of one process create no name twice."""

    def __init__(self, deployment, tag: str):
        self._dep, self._tag = deployment, tag

    def __getattr__(self, name):
        return getattr(self._dep, name)

    def pod(self, role: str, name: str, namespace: str) -> dict:
        return self._dep.pod(role, self._tag + name, namespace)


def run(manifest, cell: dict, seed: int, rates: list, hold_s: float, toy: bool) -> list:
    setup = harness.Setup(manifest, cell, toy, "served", None)
    table = []
    try:
        setup.bring_up(seed)
        rec, client, clock = setup.rec, setup.client, setup.rec.clock
        gen_cls = manifest.generator("open_loop")
        created_total = len(setup.init_created)
        for step, rate in enumerate(rates):
            params = dict(setup.params, rate_pods_per_s=rate)
            gen = gen_cls(params, Tagged(setup.dep, f"s{step}-"), setup.system, client, rec,
                          seed + step)
            t0 = clock()
            gen.start()
            samples = []
            while clock() - t0 < hold_s:
                time.sleep(0.25)
                samples.append((clock() - t0, created_total + len(gen.created) - client.n_bound()))
            gen.stop()
            t1 = clock()
            created_total += len(gen.created)
            half = [d for t, d in samples if t >= hold_s / 2]
            lat = [
                client.bound[(ns, name)][0] - due
                for ns, name, _, _, due in gen.created
                if (ns, name) in client.bound and due - t0 >= hold_s / 2
            ]
            cyc = [c for c in rec.cycles if t0 + hold_s / 2 <= c.get("t_dispatch0", -1) < t1]
            row = {
                "rate": rate, "created": len(gen.created), "held_s": t1 - t0,
                "depth_mid": half[0] if half else None, "depth_end": half[-1] if half else None,
                "depth_max": max(d for _, d in samples),
                "bind_p50_s": reduce.percentile(lat, 50), "bind_p95_s": reduce.percentile(lat, 95),
                "late_p99_s": reduce.percentile(
                    [issued - due for _, _, _, issued, due in gen.created], 99),
                "cycle_pods_p50": reduce.percentile([c["pods"] for c in cyc], 50),
                "compiles": len(reduce.compiles_between({"compiles": rec.compiles}, t0, t1)),
            }
            harness.log(f"sweep {row}")
            table.append(row)
            # let the backlog of a step above the knee drain before the next
            harness.wait_for(lambda: client.n_bound() >= created_total, 60.0)
        setup.system.stop()
        client.stop()
    finally:
        setup.tear_down()
    return table
