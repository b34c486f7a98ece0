"""The comparison that decides `correct`.

What the timed path produced, at the timed size: every bind the client saw
(set-up, warm replay, window and drain alike) is replayed against the plain
reference's ledger, and every bind is read back from a store recovered from
the journal once the system is stopped.  Each number has a limit of its own,
and every one is an exact comparison (limit 0) but the zone skew, whose limit
the configuration states (maxSkew): it is counted namespace by namespace, as a
topologySpreadConstraint's selector does, and read after every whole solve.
"""

from __future__ import annotations

from . import reference


def compare(deployment, created, client, recovered, solves) -> dict:
    """`created`: (namespace, name, role) of every pod the harness created
    and was acknowledged.  `client`: the WatchClient after the drain.
    `recovered`: {(namespace, name): node} read back from the journal.
    `solves`: the pods of each solve, in order (the spans' record): the spread
    rule is held after every whole solve, because the binds of one solve
    reach the client shard by shard and in no order.
    Returns {"checks": {name: [value, limit]}, "correct": bool}."""
    ledger = reference.Ledger(deployment.nodes(), deployment.templates)
    role_of = {(ns, name): role for ns, name, role in created}
    seen = {key: node for key, (_, node, _) in client.bound.items()}

    unbound = sum(1 for key in role_of if key not in seen)
    stray = sum(1 for key in seen if key not in role_of)   # binds of pods nobody created
    done = set()
    for keys in solves:
        for key in keys:
            if key in role_of and key in seen and key not in done:
                done.add(key)
                ledger.bind(role_of[key], seen[key], key[0])
        ledger.mark_wave_end()
    for key, node in seen.items():          # binds no solve's record covers
        if key in role_of and key not in done:
            ledger.bind(role_of[key], node, key[0])
    ledger.mark_wave_end()

    journal_diff = sum(1 for key, node in seen.items() if recovered.get(key) != node)
    journal_diff += sum(1 for key, node in recovered.items() if node and key not in seen)

    checks = {
        "unbound": [unbound, 0],
        "bound_twice": [len(client.rebound), 0],
        "stray_binds": [stray + ledger.unknown_node, 0],
        "overcommitted_nodes": [len(ledger.overcommitted()), 0],
        "journal_diff": [journal_diff, 0],
        "rv_regressions": [client.rv_regressions, 0],
    }
    if ledger.rule is not None:
        checks["max_zone_skew"] = [ledger.max_skew_seen, ledger.rule[1]]
    return {
        "checks": checks,
        "correct": all(v <= lim for v, lim in checks.values()),
    }
