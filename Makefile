# Developer entry points.  Tier-1 is the gate CI runs on every PR; the
# chaos suite (randomized seeded fault injection, tests/test_chaos.py)
# is opt-in because each of its 20 fixed seeds drives a full cluster
# run.

PY ?= python

.PHONY: test chaos chaos-restart chaos-serving audit lint lint-shapes \
	lint-coherence lint-obligations multichip race native-ext test-journal \
	proto rehearse

# graftlint: the project-native static analysis suite (guarded-by,
# hot-path purity, registry drift, lock-order, tensor-contract,
# atomicity, coherence, obligations — docs/static_analysis.md).  Exits
# non-zero on any finding outside kubernetes_tpu/analysis/baseline.json
# and on stale baseline entries.  Import-light: no JAX init.
lint:
	$(PY) -m kubernetes_tpu.analysis

# recompile-discipline: eval_shape over the pad-bucket lattice + real
# encoder shape validation (analysis/shapes.py).  Imports JAX, hence a
# separate mode — `make lint` must stay import-light.
lint-shapes:
	JAX_PLATFORMS=cpu $(PY) -m kubernetes_tpu.analysis --shapes

# graftcoh focused mode: the resident-cache discipline matrix alone
# (analysis/coherence.py; it also rides `make lint`).  The runtime half
# is the GRAFTLINT_COHERENCE=1 epoch auditor (analysis/epochs.py).
lint-coherence:
	$(PY) -m kubernetes_tpu.analysis --coherence

# graftobl focused mode: the linear-obligation engine alone
# (analysis/obligations.py; it also rides `make lint`).  The runtime
# half is the GRAFTLINT_OBLIGATIONS=1 exactly-once ledger
# (analysis/ledger.py).
lint-obligations:
	$(PY) -m kubernetes_tpu.analysis --obligations

test: rehearse
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow and not chaos' \
		--continue-on-collection-errors -p no:cacheprovider

# chip_smoke.py's four stages at toy sizes on the CPU platform (~40 s):
# the pre-flight before any chip call.  Tier-1 is cut off by the clock
# and carries only stage 2 and the refusal/fallback cases
# (tests/test_chip_smoke.py), so the passing stage 1 runs here.
rehearse:
	JAX_PLATFORMS=cpu $(PY) chip_smoke.py --rehearse-cpu

# graftsched: the concurrency gate (docs/static_analysis.md).  Arms the
# runtime lock-order tracker for the whole session and runs the
# deterministic interleaving suite — the DEEP sweeps (200+ seeded
# schedules per scenario, every invariant oracle green, seed-replay
# determinism) plus the atomicity-sensitive test files.  Tier-1 carries
# only the fast interleave smoke subset ('interleave and not slow').
race:
	GRAFTLINT_LOCK_ORDER=1 JAX_PLATFORMS=cpu $(PY) -m pytest \
		tests/test_interleave.py tests/test_static_analysis.py \
		tests/test_concurrency_stress.py tests/test_watch_backpressure.py \
		-q -m 'not chaos' -p no:cacheprovider

# the fixed seed matrices live in tests/test_chaos.py: SEEDS = range(20)
# for the full-pipeline plans plus the overload-protection scenarios
# (SLOW_CONSUMER_SEEDS, RELIST_STORM_SEEDS — backpressured fan-out,
# coalescing, relist-storm containment), the mixed-priority preemption
# churn (PREEMPT_SEEDS — batched-dry-run faults, PDB-guarded victims),
# the gang carve-outs (CARVEOUT_SEEDS) and the incremental-solve
# partials poison (PARTIALS_SEEDS = 700-704 — resident-store CORRUPT
# must trip the parity gate, never be absorbed); every seed replays
# byte-identically via FaultRegistry(seed)
chaos:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_chaos.py -m chaos -q \
		-p no:cacheprovider

# the kill-restart subset only (RESTART_SEEDS = range(300, 310)): tear a
# component down at a registered crash point (store mid-fsync, binder
# mid-wave, leader mid-pop-window), restart it, and prove no pod lost,
# no double bind, rv monotonic across the restart, and snapshot+suffix
# recovery bit-identical to a full-journal-replay oracle
chaos-restart:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_chaos.py -m restart -q \
		-p no:cacheprovider

# the serving-plane subset (SERVING_SEEDS = range(900, 910) plus the
# journal-frame native/fallback parity seed and the pod-axis breaker
# fallback): pods created THROUGH the read-replica HTTP plane under
# injected request failures, torn watch frames and admission stalls,
# with a replica killed and restarted mid-run — no watcher terminated,
# no pinned handler thread, informer caches converge on the store's
# bindings exactly once
chaos-serving:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_chaos.py -m serving -q \
		-p no:cacheprovider

# the sharded multichip suite on a FORCED 8-device host-platform mesh:
# sharded-vs-single-chip parity (greedy/wavefront/auction + gang retry),
# the mesh-sharded mirror, and mesh-mode pipeline/fallback behavior.
# conftest.py forces the same device count for every pytest run; the
# explicit flag keeps this target correct in any environment.
multichip:
	XLA_FLAGS="--xla_force_host_platform_device_count=8" JAX_PLATFORMS=cpu \
		$(PY) -m pytest tests/ -q -m multichip -p no:cacheprovider

# the audited count run: the served-path tests (a Scheduler over a Store)
# with the obligation ledger, the epoch auditor and the retrace tracker
# armed for the session.  tests/conftest.py arms them and fails the
# session on a leak, a double discharge, a coherence violation or an
# executable traced twice; tests/test_executable_keys.py holds "nothing
# built after warm-up".  Counts only: valid from any platform.
audit:
	GRAFTLINT_OBLIGATIONS=1 GRAFTLINT_COHERENCE=1 GRAFTLINT_SHAPES=1 \
		JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_scheduler_loop.py \
		tests/test_finish_at_ready.py tests/test_executable_keys.py \
		tests/test_metrics_sources.py -q -m 'not slow and not chaos' \
		-p no:cacheprovider

# regenerate the committed protobuf gencode after editing the contract
# (nothing regenerates it at import time)
proto:
	cd kubernetes_tpu/proto && protoc --python_out=. snapshot.proto

# optional _hostplane C extension (native/hostplane.c): journal frame
# trailer splice + CRC and proto wire framing.  Pure accelerator —
# api/framing.py is the contract and the fallback, so this target is
# best-effort: no compiler, no extension, everything still runs.
native-ext:
	@cc=$$($(PY) -c "import sysconfig; print(sysconfig.get_config_var('CC') or 'cc')" | cut -d' ' -f1); \
	if command -v $$cc >/dev/null 2>&1; then \
		inc=$$($(PY) -c "import sysconfig; print(sysconfig.get_paths()['include'])"); \
		ext=$$($(PY) -c "import sysconfig; print(sysconfig.get_config_var('EXT_SUFFIX'))"); \
		$$cc -O2 -Wall -shared -fPIC -I$$inc native/hostplane.c \
			-o _hostplane$$ext && echo "built _hostplane$$ext"; \
	else \
		echo "no C compiler; skipping _hostplane (pure-Python fallback)"; \
	fi

# journal/framing tests in BOTH modes: with the native extension if it
# builds, and with the pure-Python fallback forced — the fallback must
# stay green on machines with no compiler at all.
test-journal: native-ext
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_journal_framing.py \
		tests/test_restart_recovery.py tests/test_durability_leaderelection.py \
		-q -p no:cacheprovider
	HOSTPLANE_DISABLE=1 JAX_PLATFORMS=cpu $(PY) -m pytest \
		tests/test_journal_framing.py -q -p no:cacheprovider
