# Convenience targets mirroring the CI tiers (.github/workflows/).
# CPU-only: everything runs on the virtual 8-device CPU mesh.

PYTEST := env JAX_PLATFORMS=cpu python -m pytest
# Three fixed seeds for the deterministic fault-injection suite; each
# run must inject the same faults at the same points (the suite itself
# asserts cross-run determinism per seed).
CHAOS_SEED_SETS := 7,21,1337 11,23,4242 1,2,3
# Recovery seed set: the mid-stream-failover (resumable streams) suite
# sweeps crash-at-token faults under these seeds pre-merge.
RECOVERY_SEED_SETS := 7,21,1337 5,8,13
# Overload seed sets: seeded overload_burst scenarios (mixed-priority
# bursts against a tiny KV pool) driving edge shedding + KV-pressure
# preemption in tests/test_overload.py.
OVERLOAD_SEED_SETS := 7,21,1337 3,9,27
# Simulation seed sets: the discrete-event cluster simulator's
# regression runs (determinism, calibration vs the live overload
# harness, reactive-vs-SLO planner comparison) in tests/test_sim.py.
SIM_SEED_SETS := 7,21,1337 3,9,27
# Speculative-decoding seed set: re-run the resumable (mid-stream
# failover) and overload (preempt→resume) identity suites with
# speculation force-enabled via the DYN_SPEC env toggle — every stream
# must stay token-identical with spec on (docs/speculative.md).
SPEC_SEED_SETS := 7,21,1337
# Predictive KV tiering seed sets: the 8x-pool aggregate-context
# identity sweep (proactive offload + prefetch under pressure,
# conservation-audited) in tests/test_kv_tiering.py.
TIERING_SEED_SETS := 7,21,1337 3,9,27
# Spot-reclamation seed sets: deadline-bounded live migration +
# journal failover (tests/test_reclaim.py) — migrated streams must be
# token-identical to uninterrupted oracles, and a too-short grace must
# degrade to journal failover with zero lost/duplicated tokens.
RECLAIM_SEED_SETS := 7,21,1337 5,8,13
# Durable-KV storage-fault seed sets: the seeded storage chaos family
# (bit-flip, torn tail, ENOSPC, injected fetch latency, store-dir
# missing) against the G3 persistent tier (tests/test_kv_persist.py)
# — corrupt pages must quarantine with token-identical journal
# re-prefill, and a failing store must degrade to G2-only, never hang.
STORE_SEED_SETS := 7,21,1337 3,9,27

.PHONY: test pre-merge nightly chaos sim sim-scale flight profile-smoke lint prewarm-smoke anatomy-smoke tune-smoke

test:
	$(PYTEST) tests/ -q -m "not tpu and not weekly"

pre-merge:
	$(PYTEST) tests/ -q -m pre_merge

nightly:
	$(PYTEST) tests/ -q -m "not tpu and not weekly"

# Fault-injection suite under three fixed seed sets (satellite of the
# fault-tolerance PR; see docs/fault_tolerance.md), plus the resumable
# streams / mid-stream failover suite under its recovery seed sets —
# both run pre-merge.
chaos:
	@set -e; for seeds in $(CHAOS_SEED_SETS); do \
		echo "=== chaos suite, CHAOS_SEEDS=$$seeds ==="; \
		env CHAOS_SEEDS=$$seeds $(PYTEST) tests/test_fault_tolerance.py -q -m chaos; \
	done; \
	for seeds in $(RECOVERY_SEED_SETS); do \
		echo "=== recovery suite, CHAOS_SEEDS=$$seeds ==="; \
		env CHAOS_SEEDS=$$seeds $(PYTEST) tests/test_resumable.py -q -m chaos; \
	done; \
	for seeds in $(OVERLOAD_SEED_SETS); do \
		echo "=== overload suite, CHAOS_SEEDS=$$seeds ==="; \
		env CHAOS_SEEDS=$$seeds $(PYTEST) tests/test_overload.py -q -m chaos; \
	done; \
	for seeds in $(SPEC_SEED_SETS); do \
		echo "=== spec-on identity suites (DYN_SPEC=ngram), CHAOS_SEEDS=$$seeds ==="; \
		env DYN_SPEC=ngram CHAOS_SEEDS=$$seeds $(PYTEST) tests/test_resumable.py tests/test_overload.py -q -m "not slow"; \
	done; \
	for seeds in $(CHAOS_SEED_SETS); do \
		echo "=== KV conservation ledger suite, CHAOS_SEEDS=$$seeds ==="; \
		env CHAOS_SEEDS=$$seeds $(PYTEST) tests/test_kv_ledger.py -q -m chaos; \
	done; \
	for seeds in $(TIERING_SEED_SETS); do \
		echo "=== predictive KV tiering sweep, CHAOS_SEEDS=$$seeds ==="; \
		env CHAOS_SEEDS=$$seeds $(PYTEST) tests/test_kv_tiering.py -q -m chaos; \
	done; \
	for seeds in $(RECLAIM_SEED_SETS); do \
		echo "=== spot-reclamation suite, CHAOS_SEEDS=$$seeds ==="; \
		env CHAOS_SEEDS=$$seeds $(PYTEST) tests/test_reclaim.py -q -m chaos; \
	done; \
	for seeds in $(SPEC_SEED_SETS); do \
		echo "=== spec-on reclaim identity (DYN_SPEC=ngram), CHAOS_SEEDS=$$seeds ==="; \
		env DYN_SPEC=ngram CHAOS_SEEDS=$$seeds $(PYTEST) tests/test_reclaim.py -q -m chaos; \
	done; \
	for seeds in $(STORE_SEED_SETS); do \
		echo "=== durable-KV storage-fault suite, CHAOS_SEEDS=$$seeds ==="; \
		env CHAOS_SEEDS=$$seeds $(PYTEST) tests/test_kv_persist.py -q -m chaos; \
	done

# Seeded simulator regression sets (mirrors `make chaos`): every seed
# set re-runs the sim suite — determinism and calibration must hold for
# each (docs/simulation.md). The marked-slow fleet-scale runs are
# excluded here; `make sim-scale` runs them.
sim:
	@set -e; for seeds in $(SIM_SEED_SETS); do \
		echo "=== sim suite, SIM_SEEDS=$$seeds ==="; \
		env SIM_SEEDS=$$seeds $(PYTEST) tests/test_sim.py -q -m "sim and not slow"; \
	done

sim-scale:
	$(PYTEST) tests/test_sim.py -q -m "sim and slow"

# Flight-recorder demo (docs/observability.md): tiny engine, SIGUSR1,
# render the dump with `llmctl flight`.
flight:
	env JAX_PLATFORMS=cpu python examples/flight_demo.py

# Profiler-overhead smoke: the instrumented decode path must perform
# ZERO additional host syncs per window (sync-spy shim, not wall clock
# — CPU timing is load-sensitive).
profile-smoke:
	$(PYTEST) tests/test_dispatch_profile.py -q -k overhead

# AOT warm-boot smoke (docs/aot.md): boot an engine twice against a
# tmp persistent compile-cache dir; the second boot must compile
# NOTHING — zero ragged compile misses, zero variant growth under
# traffic, zero new cache entries. Runs pre-merge (pre-merge.yml).
prewarm-smoke:
	env JAX_PLATFORMS=cpu python -m dynamo_exp_tpu.llmctl aot smoke \
		--preset tiny --max-decode-slots 2 --page-size 8 \
		--max-model-len 128 --prefill-chunk 16 --decode-window 4 \
		--kv-dtype float32

# Style lint (ruff) + dynlint, the AST invariant checkers
# (docs/static_analysis.md): host-sync / determinism / thread-ownership
# / recompile-hazard over the package tree, zero unwaived findings.
# Runs in the pre-merge lane next to `make chaos`; the same gate is a
# tier-1 test (tests/test_analysis.py).
lint:
	ruff check dynamo_exp_tpu/ tests/ __graft_entry__.py
	python -m dynamo_exp_tpu.llmctl lint --json

# Request-anatomy + workload-fingerprint smoke (docs/observability.md
# "Request anatomy" / "Workload fingerprint"): decompose every trace in
# the checked-in fixture (`--why` waterfalls must render, components
# summing to the edge latency), list the worst-N, and fingerprint the
# fixture — the digest is deterministic, so it is pinned here and in
# tests/test_anatomy.py; a bucketing or hashing change must touch both.
# Runs pre-merge (pre-merge.yml).
anatomy-smoke:
	env JAX_PLATFORMS=cpu python -m dynamo_exp_tpu.llmctl trace aaaa1111 \
		--trace-file tests/fixtures/anatomy_trace.jsonl --why
	env JAX_PLATFORMS=cpu python -m dynamo_exp_tpu.llmctl trace bbbb2222 \
		--trace-file tests/fixtures/anatomy_trace.jsonl --why
	env JAX_PLATFORMS=cpu python -m dynamo_exp_tpu.llmctl slow \
		--trace-file tests/fixtures/anatomy_trace.jsonl -n 5
	env JAX_PLATFORMS=cpu python -m dynamo_exp_tpu.llmctl fingerprint \
		tests/fixtures/anatomy_trace.jsonl

# Autotuner smoke (docs/tuning.md): `llmctl tune` against the
# checked-in workload-fingerprint fixture — seeded search over the
# knob registry must beat the registry defaults in-sim (--check exits
# nonzero otherwise), and the journal/space digest must stay
# deterministic for the fixed seed. Runs pre-merge (pre-merge.yml).
tune-smoke:
	env JAX_PLATFORMS=cpu python -m dynamo_exp_tpu.llmctl tune \
		--fingerprint tests/fixtures/tune_fingerprint.json \
		--budget 96 --seed 0 --check --json
