"""chip_smoke.py's contract, rehearsed on the CPU platform.

The smoke is the repository's proof that the served path starts on the
chip, so what is pinned here is that it cannot pass by accident: it
refuses a host without a TPU, a toy-size rehearsal can pass, and a solve
that falls back to the host — which still binds every pod, as the
product promises — fails the smoke by name while every solver-independent
check of stage 1 holds.  Run in-process (main() is import-safe) to spare
three JAX start-ups.

Tier-1 is cut off by the clock, so the passing rehearsal here is stage 2
(one auction compile, ~2 s); the passing stage 1 traces all three routes
and their warm twins (measured 15-17 s on this CPU platform) and rides
the `slow` full rehearsal; `make test` runs the same four stages first
(`make rehearse`), so they have passed here before any chip call.
"""

import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


SUMMARY = "chip_smoke: summary "


def _run(chip_smoke, capsys, argv):
    """(exit code, stdout, the full summary).  The last line is the
    verdict alone, with exactly the keys the chip check reads; the
    summary is the line before it."""
    rc = chip_smoke.main(argv)
    out = capsys.readouterr().out
    lines = out.strip().splitlines()
    if not lines:
        return rc, out, None
    assert lines[-2].startswith(SUMMARY)
    summary = json.loads(lines[-2][len(SUMMARY):])
    verdict = json.loads(lines[-1])
    assert list(verdict) == ["ok", "device"]
    assert list(verdict["device"]) == ["platform", "kind", "count"]
    assert verdict["ok"] is summary["ok"] is (rc == 0)
    assert verdict["device"] == summary["device"]
    assert isinstance(verdict["device"]["count"], int)
    return rc, out, summary


def test_refuses_to_run_without_a_tpu(chip_smoke, capsys):
    rc, out, summary = _run(chip_smoke, capsys, [])
    assert rc != 0
    assert summary is None and out == ""  # no work done, no result printed


def test_rehearsal_passes_and_says_it_is_not_a_chip_result(
    chip_smoke, capsys
):
    rc, out, summary = _run(
        chip_smoke, capsys, ["--rehearse-cpu", "--stages", "2"]
    )
    assert "NOT a chip result" in out
    assert rc == 0, summary["failures"]
    assert summary["ok"] is True and summary["rehearsal"] is True
    assert summary["device"]["platform"] == "cpu"
    facts = summary["facts"]["stage2"]
    assert facts["placed"] == facts["pods"]
    assert summary["compile"]["cache_dir"]
    assert list(summary)[-1] == "claim" and summary["claim"] is None


def test_host_fallback_fails_the_smoke_by_name(
    chip_smoke, capsys, monkeypatch
):
    from kubernetes_tpu.models.batch_scheduler import TPUBatchScheduler

    def refused(self, snap, meta=None):
        raise RuntimeError("injected: the compiler refused this kernel")

    monkeypatch.setattr(TPUBatchScheduler, "_dispatch", refused)
    rc, _, summary = _run(
        chip_smoke, capsys, ["--rehearse-cpu", "--stages", "1"]
    )
    assert rc != 0 and summary["ok"] is False
    failures = summary["failures"]
    assert any("breaker.fallback_count" in f for f in failures)
    assert any("breaker.trips" in f for f in failures)
    # the product behaviour stands: the fallback still bound every pod,
    # validly and durably — it is the smoke, not the scheduler, that
    # fails, and only on what the missing device solves explain
    facts = summary["facts"]["stage1"]
    assert facts["pods_bound"] == facts["pods_created"] >= 280
    assert facts["recovered_equal"] is True
    assert facts["routes"] == {"host": facts["routes"]["host"]}
    explained = (
        "breaker", "never dispatched", "partials warm path", "mirror",
        "not solved on cpu", "ERROR-level log record",
    )
    assert [f for f in failures if not any(e in f for e in explained)] == []


@pytest.mark.slow
def test_full_rehearsal_passes(chip_smoke, capsys):
    rc, _, summary = _run(chip_smoke, capsys, ["--rehearse-cpu"])
    assert rc == 0, summary["failures"]
    assert summary["stages"] == ["1", "2", "3", "parity"]
    # stage 3 sends its preemptors straight behind the fill
    stage3 = summary["facts"]["stage3"]
    assert stage3["victims"] == stage3["preemptors"] > 0
    stage1 = summary["facts"]["stage1"]
    assert stage1["pods_bound"] == stage1["pods_created"] >= 280
    assert {"greedy", "wavefront", "auction"} <= set(stage1["routes"])
    assert stage1["recovered_equal"] is True
    assert summary["facts"]["parity"]["greedy"]["equal"] is True
    assert summary["facts"]["parity"]["wavefront"]["equal"] is True
    assert summary["facts"]["parity"]["auction"]["agree"] is True
