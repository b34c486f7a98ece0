"""Test configuration.

Forces JAX onto the host CPU platform with 8 virtual devices so
sharding/collective tests exercise a multi-chip mesh without TPU hardware
(the reference's analogue: integration tests create Nodes as API objects
only — test/integration/util/util.go:86).

The device count is an XLA flag and must be in the environment before the
first backend initializes.  The platform is pinned through jax.config as
well as JAX_PLATFORMS: a plugin or an earlier import may already have
imported jax, which reads the variable once at import, while the config
update holds until the first backend is created.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


if os.environ.get("GRAFTLINT_LOCK_ORDER") == "1":
    # opt-in runtime lock-order tracking (docs/static_analysis.md): every
    # threading.Lock/RLock created during the session is wrapped and the
    # session fails if any pair of locks was acquired in both orders.
    @pytest.fixture(autouse=True, scope="session")
    def _graftlint_lock_order():
        from kubernetes_tpu.analysis import runtime as lockorder

        with lockorder.tracked() as tracker:
            yield tracker
        tracker.assert_no_inversions()


if os.environ.get("GRAFTLINT_OBLIGATIONS") == "1":
    # opt-in runtime exactly-once obligation tracking
    # (docs/static_analysis.md obligations section): every popped pod /
    # cache assume / APF seat / arbiter slot / inflight counter / armed
    # fault registry acquisition is recorded with its call chain; a
    # double-discharge raises at the offending call and the session
    # fails on any obligation still held at teardown.
    @pytest.fixture(autouse=True, scope="session")
    def _graftlint_obligations():
        from kubernetes_tpu.analysis import ledger

        with ledger.tracked() as led:
            yield led
        led.assert_clean()

    @pytest.fixture(autouse=True)
    def _graftlint_obligations_boundary(_graftlint_obligations):
        # pod keys recur across tests: reset the double-discharge
        # lookback window at each boundary so one test's retired
        # 'default/p3' never taints the next test's own 'default/p3'
        # (held obligations and recorded violations survive the reset)
        _graftlint_obligations.reset_cycles()
        yield


if os.environ.get("GRAFTLINT_COHERENCE") == "1":
    # opt-in runtime resident-epoch auditing (docs/static_analysis.md
    # coherence section): every resident buffer a solve consumes is
    # checked against the scheduler cache's current generations at
    # consume time, and the session fails on any divergent
    # (resident, field, epoch) triple.
    @pytest.fixture(autouse=True, scope="session")
    def _graftlint_coherence():
        from kubernetes_tpu.analysis import epochs

        with epochs.tracked() as auditor:
            yield auditor
        auditor.assert_clean()


if os.environ.get("GRAFTLINT_SHAPES") == "1":
    # opt-in runtime recompile-discipline tracking (docs/
    # static_analysis.md): every solver jit dispatch reports to the
    # retrace tracker, and the session fails if any executable key was
    # traced twice — the compile cache must hold every key for a whole
    # test session (steady-state windows are a bench concept; tests
    # legitimately visit new buckets all the time).
    @pytest.fixture(autouse=True, scope="session")
    def _graftlint_shapes():
        from kubernetes_tpu.analysis import retrace

        with retrace.tracked() as tracker:
            yield tracker
        tracker.assert_no_duplicate_traces()
