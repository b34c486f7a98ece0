"""The comparison that decides `correct`.

What the timed path produced, at the timed size: every bind and every deletion
the client saw (set-up, warm replay, window and drain alike) is replayed
against the plain reference's ledger, the deployment's rules, and every pod
whose deletion was not acknowledged is read back from a store recovered from
the journal once the system is stopped.  Each number has a limit of its own,
and every one is an exact comparison (limit 0) but a rule's whose limit the
configuration states (the zone skew's maxSkew).

Two replays, because the rules are read at two kinds of instant.  A rule held
at every bind (allocatable, anti-affinity) is replayed in the store's own
order, the resourceVersions of binds and deletions alike: a deletion frees its
room and leaves its rule's counts for every bind with a greater rv and for
none before, so a slot reused before its delete is a breach, whatever the
order of the program's record.  A rule read after whole solves (the zone
skew) is replayed solve by solve, as the program's record orders them, because
the binds of one solve reach the client shard by shard and in no order; there
a deletion is taken in before the first bind with a greater rv, and a pod
whose whole life fell between two readings is in neither.  Where nothing was
deleted both replays give what one gave before.

The deletions the harness asked for and had acknowledged are its own truth
and not the program's: one of them that the client never saw, and a deletion
the client saw that nobody asked for, are counted each under its own name
where the mix deletes at all.

A role that no node of the empty cluster admits, by the rules alone, is not
meant to bind: its pods are left out of `unbound`, one of them seen bound at
any time counts under `bound_unplaceable`, and each has to read back, unbound,
from the recovered store.  Which roles those are is asked of the fresh ledger
before the replay, never of what the program did.
"""

from __future__ import annotations

import collections

from . import reference


def compare(deployment, created, client, recovered, solves, deleted=None) -> dict:
    """`created`: (namespace, name, role) of every pod the harness created
    and was acknowledged.  `client`: the WatchClient after the drain.
    `recovered`: {(namespace, name): node} read back from the journal.
    `solves`: the pods of each solve, in order (the spans' record).
    `deleted`: (namespace, name) of every deletion the harness asked for and
    was acknowledged; None where the mix deletes nothing.
    Returns {"checks": {name: [value, limit]}, "correct": bool}."""
    ledger = reference.Ledger(deployment.nodes(), deployment.templates)
    unplaceable = reference.unplaceable_roles(deployment, ledger)
    role_of = {(ns, name): role for ns, name, role in created}
    seen = {key: node for key, (_, node, _) in client.bound.items()}
    bind_rv = {key: rv for key, (_, _, rv) in client.bound.items()}
    asked = set(deleted or ())

    unbound = sum(1 for key, role in role_of.items()
                  if key not in seen and role not in unplaceable)
    # pods of a role no node can hold: those left pending, as they should be
    pending = [key for key, role in role_of.items()
               if key not in seen and role in unplaceable]
    stray = sum(1 for key in seen if key not in role_of)   # binds of pods nobody created
    mine = [key for key in seen if key in role_of]
    stray += sum(1 for key in mine if seen[key] not in ledger.nodes)   # binds to no node

    # a deletion the client saw, at its rv and never before the pod's own bind
    gone_rv = {key: max(rv, bind_rv[key]) for key, (_, rv) in client.gone.items()
               if key in role_of and key in seen}

    # -- held at every bind: the store's order, a bind before a deletion of one rv
    events = [(bind_rv[key], 0, key) for key in mine]
    events += [(rv, 1, key) for key, rv in gone_rv.items()]
    for _, is_deletion, key in sorted(events):
        (ledger.unbind if is_deletion else ledger.bind)(
            role_of[key], seen[key], key[0], held="every_bind")

    # -- read after whole solves: the program's order of solves
    deletions = collections.deque(sorted((rv, key) for key, rv in gone_rv.items()))
    done, live, early = set(), set(), set()

    def bind(key) -> None:
        while deletions and deletions[0][0] < bind_rv[key]:
            _, dead = deletions.popleft()
            if dead in live:
                live.discard(dead)
                ledger.unbind(role_of[dead], seen[dead], dead[0], held="whole_solves")
            else:
                early.add(dead)     # gone before the replay came to its bind
        done.add(key)
        if key not in early:
            live.add(key)
            ledger.bind(role_of[key], seen[key], key[0], held="whole_solves")

    for keys in solves:
        batch = [key for key in keys if key in role_of and key in seen and key not in done]
        for key in sorted(batch, key=bind_rv.get):
            if key not in done:
                bind(key)
        ledger.mark_wave_end()
    for key in mine:                        # binds no solve's record covers
        if key not in done:
            bind(key)
    ledger.mark_wave_end()

    # the recovered store holds exactly the pods whose deletion was not
    # acknowledged, each where the client saw it
    journal_diff = sum(
        1 for key, node in seen.items() if key not in asked and recovered.get(key) != node
    )
    journal_diff += sum(
        1 for key, node in recovered.items() if key in asked or (node and key not in seen)
    )
    # and every pod left pending and never asked away, unbound (one that reads
    # back with a node is in the sum above)
    journal_diff += sum(1 for key in pending if key not in asked and key not in recovered)

    rule_checks = ledger.checks()
    checks = {
        "unbound": [unbound, 0],
        "bound_twice": [len(client.rebound), 0],
        "stray_binds": [stray, 0],
        "overcommitted_nodes": rule_checks.pop("overcommitted_nodes"),
        "journal_diff": [journal_diff, 0],
        "rv_regressions": [client.rv_regressions, 0],
        **rule_checks,
    }
    if unplaceable:
        checks["bound_unplaceable"] = [
            sum(1 for key in seen if role_of.get(key) in unplaceable), 0]
    if deleted is not None:
        checks["deletions_lost"] = [sum(1 for key in asked if key not in client.gone), 0]
        checks["deletions_unasked"] = [
            sum(1 for key in client.gone if key not in asked)
            + sum(1 for key in pending if key in client.gone_pending and key not in asked), 0]
    return {
        "checks": checks,
        "correct": all(v <= lim for v, lim in checks.values()),
    }
