"""graftcoh runtime half — the resident-epoch auditor (analysis/epochs.py).

Proves the auditor observes the real warm path (audits > 0, zero
violations on steady churn), detects an injected stale epoch with the
divergent (resident, field, epoch) triple, and pins the two true
positives the coherence work surfaced:

  * the dispatch-retry failure path invalidated the resident partials
    but NOT the resident mirror (batch_scheduler.schedule_pending_async
    — asymmetric against finalize_pending's heal wire, which names both
    residents as fault suspects);
  * rollback() unconditionally restored a bookmarked buffer even when
    an invalidate() (heal wire, leadership reconcile) landed after the
    bookmark — resurrecting the deliberately-dropped resident so later
    delta syncs layered onto stale state.  The invalidation fence keeps
    the resident invalidated instead.

The smoke subset rides tier-1 ('coherence and not slow'); chaos runs
arm the auditor session-wide via GRAFTLINT_COHERENCE=1 (conftest).
"""

import contextlib

import numpy as np
import pytest

from kubernetes_tpu.analysis import epochs
from kubernetes_tpu.api import types as api
from kubernetes_tpu.models.batch_scheduler import TPUBatchScheduler
from kubernetes_tpu.testing.wrappers import GI, MI, make_node, make_pod

pytestmark = pytest.mark.coherence


@contextlib.contextmanager
def _isolated():
    """A private armed auditor, even when the GRAFTLINT_COHERENCE=1
    session auditor is active — the stale-injection tests must not
    poison the session-teardown assert_clean()."""
    prev = epochs._active
    epochs._active = None
    try:
        with epochs.tracked() as auditor:
            yield auditor
    finally:
        epochs._active = prev


def _mk_sched(**kw):
    return TPUBatchScheduler(mode="greedy", use_partials=True, **kw)


def _add_nodes(sched, n=12, seed=0):
    rng = np.random.default_rng(seed)
    for i in range(n):
        nd = (
            make_node(f"n-{i}")
            .capacity(cpu_milli=8000, mem=16 * GI, pods=110)
            .zone(f"z-{i % 3}")
        )
        if rng.random() < 0.3:
            nd.label("disk", "ssd")
        sched.add_node(nd.obj())


def _mk_pods(step, p, seed):
    rng = np.random.default_rng(seed)
    pods = []
    for i in range(p):
        pw = make_pod(f"s{step}-p{i}").req(
            cpu_milli=int(rng.choice([100, 250, 500])), mem=256 * MI
        )
        if i % 3 == 0:
            pw.required_affinity(api.LABEL_ZONE, api.OP_IN, [f"z-{i % 3}"])
        elif i % 3 == 1:
            pw.preferred_affinity(10, "disk", api.OP_IN, ["ssd"])
        pods.append(pw.obj())
    return pods


def _churn(sched, step):
    p = make_pod(f"churn-{step}").req(cpu_milli=50, mem=64 * MI).obj()
    sched.assume(p, f"n-{step % 8}")


# -- clean steady state ------------------------------------------------------

def test_clean_steady_state_audits():
    """Warm solves over bounded churn: the armed auditor observes every
    consume site and records zero violations."""
    sched = _mk_sched()
    _add_nodes(sched)
    with _isolated() as auditor:
        for step in range(4):
            _churn(sched, step)
            sched.schedule_pending(_mk_pods(step, 8, seed=step))
        assert auditor.audits_total > 0
        assert auditor.violations_total == 0
        auditor.assert_clean()


def test_staged_cycles_audit_clean():
    """Cycles the encode's staging program carries (the mirror's rows,
    the partials' refresh and gather in the snapshot's one transfer)
    stamp both residents as their own sync paths do: every consume and
    dispatch audit passes, bookmarks taken between them included."""
    from kubernetes_tpu.utils import trace

    sched = _mk_sched()
    _add_nodes(sched)
    sched.schedule_pending(_mk_pods(0, 8, seed=5))
    with _isolated() as auditor:
        t0 = trace.now()
        for step in range(1, 5):
            _churn(sched, step)
            sched._mirror.speculation_point()
            sched._partials.speculation_point()
            sched.schedule_pending(_mk_pods(0, 8, seed=5))
        spans = [
            dict(zip(trace.SPAN_FIELDS, row))
            for row in trace.snapshot(t0, float("inf"))["spans"]
        ]
        staged = [r["a1"] for r in spans if r["name"] == "sched.encode.stage"]
        assert staged == [1.0] * 4
        assert auditor.audits_total > 0
        auditor.assert_clean()


def test_disarmed_hooks_are_noops():
    prev = epochs._active
    epochs._active = None
    try:
        assert epochs.audits_total() == 0
        assert epochs.violations_total() == 0
        sched = _mk_sched()
        _add_nodes(sched, n=8, seed=1)
        sched.schedule_pending(_mk_pods(0, 6, seed=1))
        assert epochs.audits_total() == 0
    finally:
        epochs._active = prev


# -- injected staleness ------------------------------------------------------

def test_injected_stale_epoch_detected():
    """A resident reporting a stale epoch at consume time fails loudly
    with the (resident, field, epoch) triple."""
    sched = _mk_sched()
    _add_nodes(sched)
    sched.schedule_pending(_mk_pods(0, 8, seed=2))
    stale = epochs.EpochStamp("mirror", 0, None, -1, 1)
    sched._mirror.epoch = lambda: stale
    with _isolated() as auditor:
        sched.schedule_pending(_mk_pods(1, 8, seed=3))
        assert auditor.violations_total > 0
        blob = "\n".join(auditor.violations)
        assert "(mirror, synced_gen" in blob
        with pytest.raises(epochs.CoherenceViolation):
            auditor.assert_clean()


def test_missing_stamp_is_a_violation():
    with _isolated() as auditor:
        auditor.audit_consume(None, "mirror", 1, 1)
        assert auditor.violations_total == 1
        assert "(mirror, stamp, None)" in auditor.violations[0]


def test_cross_resident_pair_divergence_detected():
    """Dispatch-time audit: partials evaluated against a different
    epoch than the mirror the solve consumes."""
    m = epochs.EpochStamp("mirror", 2, None, 7, 3)
    p = epochs.EpochStamp("partials", 2, (), 5, 3)  # dirty mark behind
    with _isolated() as auditor:
        auditor.audit_pair(m, p)
        assert auditor.violations_total == 1
        assert "(partials, synced_gen" in auditor.violations[0]


# -- epoch transitions -------------------------------------------------------

def test_rollback_restores_bookmarked_epoch():
    sched = _mk_sched()
    _add_nodes(sched)
    sched.schedule_pending(_mk_pods(0, 8, seed=4))
    m_stamp = sched._mirror.epoch()
    p_stamp = sched._partials.epoch()
    assert m_stamp is not None and p_stamp is not None
    m_point = sched._mirror.speculation_point()
    p_point = sched._partials.speculation_point()
    # speculative progress moves the epochs forward
    _churn(sched, 0)
    sched.schedule_pending(_mk_pods(1, 8, seed=5))
    assert sched._mirror.epoch() != m_stamp
    sched._mirror.rollback(m_point)
    sched._partials.rollback(p_point)
    assert sched._mirror.epoch() == m_stamp
    assert sched._partials.epoch() == p_stamp
    # and the next warm solve re-syncs cleanly under the auditor
    with _isolated() as auditor:
        sched.schedule_pending(_mk_pods(2, 8, seed=6))
        assert auditor.audits_total > 0
        auditor.assert_clean()


def test_invalidate_clears_epoch_and_forces_full_upload():
    sched = _mk_sched()
    _add_nodes(sched)
    sched.schedule_pending(_mk_pods(0, 8, seed=7))
    resyncs = sched._mirror.resync_total
    sched._mirror.invalidate()
    sched._partials.invalidate()
    assert sched._mirror.epoch() is None
    assert sched._partials.epoch() is None
    with _isolated() as auditor:
        sched.schedule_pending(_mk_pods(1, 8, seed=8))
        auditor.assert_clean()
    assert sched._mirror.resync_total > resyncs
    assert sched._mirror.epoch() is not None


def test_invalidate_then_rollback_does_not_resurrect():
    """Regression pin: a bookmark taken BEFORE an invalidate() must not
    roll the resident back to life — the heal wire / leader reconcile
    dropped that buffer on purpose, and resurrecting it would base later
    delta syncs on stale state.  The invalidation fence refuses the
    rollback (counted, not a violation) and the next sync performs the
    full re-upload."""
    sched = _mk_sched()
    _add_nodes(sched)
    sched.schedule_pending(_mk_pods(0, 8, seed=9))
    m_point = sched._mirror.speculation_point()
    p_point = sched._partials.speculation_point()
    # the heal wire fires between bookmark and rollback
    sched._mirror.invalidate()
    sched._partials.invalidate()
    with _isolated() as auditor:
        sched._mirror.rollback(m_point)
        sched._partials.rollback(p_point)
        assert auditor.rollbacks_blocked == 2
    # stayed invalidated: no resurrected buffer, no stamp
    assert sched._mirror._dev is None
    assert sched._mirror.epoch() is None
    assert sched._partials._store is None
    assert sched._partials.epoch() is None
    resyncs = sched._mirror.resync_total
    with _isolated() as auditor:
        sched.schedule_pending(_mk_pods(1, 8, seed=10))
        auditor.assert_clean()
    assert sched._mirror.resync_total > resyncs


def test_fresh_buffer_lineage_only_on_full_upload():
    sched = _mk_sched()
    _add_nodes(sched)
    sched.schedule_pending(_mk_pods(0, 8, seed=11))
    buf0 = sched._mirror.epoch().buffer_id
    assert buf0 > 0
    # bounded churn: delta syncs keep the buffer lineage
    _churn(sched, 1)
    sched.schedule_pending(_mk_pods(1, 8, seed=12))
    assert sched._mirror.epoch().buffer_id == buf0
    # invalidate: the next sync is a full upload with a NEW lineage
    sched._mirror.invalidate()
    sched.schedule_pending(_mk_pods(2, 8, seed=13))
    assert sched._mirror.epoch().buffer_id > buf0


# -- the dispatch-retry heal wire (true positive #1) -------------------------

def test_dispatch_retry_invalidates_both_residents():
    """Regression pin: the schedule_pending_async double-dispatch
    failure must invalidate the mirror alongside the partials (it used
    to drop only the partials — asymmetric against finalize_pending's
    heal wire, which names BOTH residents as fault suspects)."""
    sched = _mk_sched()
    _add_nodes(sched)
    names0 = sched.schedule_pending(_mk_pods(0, 8, seed=14))
    assert sched._mirror._dev is not None
    assert sched._partials._store is not None

    def boom(snap, meta):
        raise RuntimeError("injected dispatch fault")

    sched.solve_encoded_async = boom
    pods = _mk_pods(1, 8, seed=15)
    ds = sched.schedule_pending_async(pods)
    assert ds is not None  # host fallback still places the batch
    assert len(ds.names()) == len(pods)
    assert sched._mirror._dev is None
    assert sched._mirror.epoch() is None
    assert sched._partials._store is None
    assert sched._partials.epoch() is None
    # the residents heal on the next device solve
    del sched.solve_encoded_async
    sched.breaker.reset()
    with _isolated() as auditor:
        sched.schedule_pending(_mk_pods(2, 8, seed=16))
        assert auditor.audits_total > 0
        auditor.assert_clean()
    assert names0 is not None


# -- sharded mesh ------------------------------------------------------------

@pytest.mark.multichip
def test_mesh_sharded_epochs_stay_clean():
    """Mesh-sharded residents carry epochs exactly like single-chip,
    including across a sharded→replicated bucket transition."""
    from kubernetes_tpu.parallel.sharded import make_mesh

    mesh = make_mesh(8)
    sched = _mk_sched(mesh=mesh)
    _add_nodes(sched, n=16, seed=21)
    with _isolated() as auditor:
        for step in range(3):
            _churn(sched, step)
            sched.schedule_pending(_mk_pods(step, 8, seed=20 + step))
        assert auditor.audits_total > 0
        auditor.assert_clean()
    assert sched._mirror.epoch() is not None
