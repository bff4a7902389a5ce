"""Declared dynlint zones and manifests (docs/static_analysis.md).

This file is the one place the lint suite learns *where* each contract
applies. The ragged-dispatch rewrite (docs/engine_perf.md "One ragged
dispatch") moved the engine's dispatch sites onto ``_ragged_fn``, which
the builders (``_build_windowed`` / ``_build_mixed`` and the others)
reach through the one ``_launch`` — when files split or move again,
update the declarations here (and the doc) and the checkers follow.
"""

from __future__ import annotations

from .core import Zone
from .ownership import LockManifest, ThreadManifest
from .recompile import VariantSiteManifest

# --------------------------------------------------------- host-sync zones
# The engine hot path: code that runs on (or hands work to) the engine
# loop thread, where one accidental blocking transfer serializes the
# dispatch pipeline (docs/engine_perf.md "Dispatch/host overlap").
# ``generate``/``prefill_extract`` are excluded: they run on asyncio
# threads at submission time and never touch device values.
HOT_PATH_ZONES: tuple[Zone, ...] = (
    Zone(
        "dynamo_exp_tpu/engine/engine.py",
        exclude=("TPUEngine.generate", "TPUEngine.prefill_extract"),
    ),
    # The block-diffusion half of the engine loop (a mixin of TPUEngine:
    # its builders, chained launch and consume run where the engine's do).
    Zone("dynamo_exp_tpu/engine/block.py"),
    Zone("dynamo_exp_tpu/engine/scheduler.py"),
    Zone("dynamo_exp_tpu/engine/offload.py"),
    Zone("dynamo_exp_tpu/engine/kv_manager.py"),
    # The prefix-sharing radix index runs inside allocate_sequence /
    # register_full_page on the loop thread — pure host bookkeeping,
    # and it must stay that way.
    Zone("dynamo_exp_tpu/kv/prefix.py"),
    # The G3 persistent store's match/fetch run inside admission and
    # the prefetch planner on the loop thread (file I/O is host work;
    # no device value may reach it, and no blocking transfer may hide
    # in it).
    Zone("dynamo_exp_tpu/kv/persistent.py"),
    # Predictive KV tiering (docs/engine_perf.md "Predictive KV
    # tiering"): footprint forecasts, packing selection, and swap
    # planning all run inside the admission/pressure paths on the loop
    # thread — pure host bookkeeping, no device value may reach them.
    Zone("dynamo_exp_tpu/engine/tiering.py"),
    # The profiler's whole contract is "zero added host syncs"
    # (docs/observability.md); the checker turns that claim into a
    # standing property instead of one driven smoke test.
    Zone("dynamo_exp_tpu/telemetry/dispatch.py"),
    # The fleet plane's transfer ledger is recorded from the KV
    # transfer paths and the conservation auditor runs inside the
    # engine loop (docs/observability.md "Fleet plane"): both must stay
    # pure host bookkeeping — no device value may ever reach them.
    Zone("dynamo_exp_tpu/telemetry/fleet.py"),
)

# ------------------------------------------------------ determinism zones
# Seed-deterministic code: same seed must mean bit-identical outputs
# across runs and hosts (docs/simulation.md "Determinism rules", the
# flight-recorder bit-identity test). The FlightRecorder class is in
# zone because its ring payloads are compared across runs; the Watchdog
# in the same file is wall-clock-driven by design and stays out.
DETERMINISM_ZONES: tuple[Zone, ...] = (
    Zone("dynamo_exp_tpu/sim/"),
    Zone("dynamo_exp_tpu/spec/"),
    Zone("dynamo_exp_tpu/runtime/transports/chaos.py"),
    # The G3 persistent store (docs/fault_tolerance.md "Durable KV"):
    # eviction order, boot-scan adoption order, and chaos fault
    # injection must all be seed/insertion-deterministic — the
    # restart-identity and corruption-containment suites compare token
    # streams bit-for-bit across runs. The only sanctioned sleep is the
    # injected-latency fault (time.sleep is not a clock read).
    Zone("dynamo_exp_tpu/kv/persistent.py"),
    Zone("dynamo_exp_tpu/telemetry/flight.py", include=("FlightRecorder",)),
    # The AOT compile lattice (docs/aot.md): the manifest hash IS the
    # cache-invalidation key, so enumeration and hashing must be free
    # of id()/wall-clock/uuid — byte-identical across processes and
    # hosts. The prewarm/compile timing metrics are the only sanctioned
    # wall-clock reads (inline-waived: "prewarm wall-clock metric").
    Zone("dynamo_exp_tpu/aot/"),
    # The request-anatomy plane (docs/observability.md "Request
    # anatomy"): decompositions are assembled from recorded spans /
    # flight events / accumulated timings — pure arithmetic, so the
    # same trace must always yield the same waterfall. The workload
    # fingerprint digest doubles as a comparison key across runs and
    # hosts, so bucketing and hashing must be free of wall-clock /
    # id() / dict-order effects.
    Zone("dynamo_exp_tpu/telemetry/anatomy.py"),
    Zone("dynamo_exp_tpu/telemetry/fingerprint.py"),
    # The spot-reclamation triage planner (docs/fault_tolerance.md
    # "Spot reclamation & live migration") is shared verbatim between
    # the live ReclaimController and the simulator's reclaim event —
    # same snapshot + survivors + grace must always produce the same
    # plan, so the pure planning functions sit in zone. The controller
    # itself is wall-clock-driven by design (it races a SIGKILL
    # deadline) and stays out.
    Zone(
        "dynamo_exp_tpu/runtime/reclaim.py",
        include=(
            "plan_triage",
            "nearest_survivor",
            "migration_lease_ttl_s",
            "survivors_from_instances",
        ),
    ),
    # The autotuner (docs/tuning.md): the trial journal is the resume
    # contract — same seed + same target must rewrite it byte-identical
    # — and the knob-space digest is a cache key, so search, space, and
    # artifact assembly must be free of wall clocks and unseeded draws.
    # The live-validation stage necessarily times a real engine; its
    # reads are inline-waived ("live validation wall-clock
    # measurement").
    Zone("dynamo_exp_tpu/tune/"),
)

# ------------------------------------------------- thread-ownership model
# Engine-loop-owned state vs cross-thread handoff surfaces. The PR 5
# gotcha this encodes: scheduler/page state may only be mutated on the
# loop thread, with no decode window in flight over the pages involved;
# other threads talk to the loop through the queues and events below.
OWNERSHIP_MANIFESTS: tuple[ThreadManifest, ...] = (
    ThreadManifest(
        path="dynamo_exp_tpu/engine/engine.py",
        cls="TPUEngine",
        loop_entries=("_loop",),
        external_entries=(
            "generate",  # asyncio ingress
            "prefill_extract",  # asyncio ingress (disagg prefill)
            "confirm_kv_lease",  # prefill worker's delivery ack thread
            "pin_prefix",  # disagg router's suffix-transfer pin (asyncio)
            "_on_prefetched",  # CopyStream fetch completion (copy thread)
            "start",
            "stop",
            "metrics",  # /metrics scrapes from serving threads
            "_flight_snapshot",  # watchdog thread
            "_dump_flight",  # watchdog / SIGUSR1 / crash paths
            # Spot-reclamation plane (docs/fault_tolerance.md): asyncio
            # ingress for the triage snapshot / page extraction /
            # survivor-side prefix seeding — all serviced on the loop
            # through _reclaim_q.
            "reclaim_inflight",
            "reclaim_extract",
            "seed_prefix",
            "_reclaim_call",
        ),
        loop_owned=frozenset(
            {
                "sched",
                "kv",
                "k_cache",
                "v_cache",
                "params",
                "_counts",
                "_inflight",
                "_pending_offloads",
                "_ragged_fns",
                "_spec",
                "steps",
                "wasted_steps",
                "kv_page_moves",
                "kv_move_dispatches",
                "preempted",
                "spec_dispatches",
                "spec_row_dispatches",
                "spec_draft_tokens",
                "spec_accepted_tokens",
                "spec_emitted_tokens",
                "_progress_mark",
                "_last_move_t",
                "_last_gauge_pub",
                "_last_reap",
                "_pub_prefix_hits",  # gauge-publish counter snapshots
                "_pub_store_checksum_failures",  # G3 counter snapshots
                "_pub_store_quarantined",
                # KV conservation auditor (docs/observability.md "KV
                # conservation auditor"): the in-loop check's episode
                # state and violation counter, plus the open lease-span
                # map (grant, confirm, and reap all run on the loop).
                "kv_ledger_violations",
                "_ledger_last",
                "_ledger_dumped",
                "_lease_traces",
                # Predictive KV tiering (docs/engine_perf.md): prefetch
                # planning state + counters and the proactive-offload
                # (swap) counters — all mutated on the loop only; the
                # copy thread answers through _prefetch_done_q.
                "_prefetch_inflight",
                "_prefetch_served",
                "_last_prefetch_scan",
                "prefetch_pages",
                "prefetch_hits",
                "prefetch_late",
                "proactive_offloads",
                "swap_ins",
                # Request anatomy plane (docs/observability.md "Request
                # anatomy"): component totals and the finished-request
                # count are accumulated in the scheduler's finish
                # callback on the loop; metrics() reads them cross-
                # thread as monotonic GIL-atomic snapshots, same
                # contract as `steps`/`preempted` above.
                "anatomy_totals",
                "anatomy_requests",
            }
        ),
        handoff=frozenset(
            {
                # Queues/events other threads feed the loop through.
                "_submit_q",
                "_lease_confirm_q",
                "_pin_q",
                "_reclaim_q",  # reclaim plane ingress -> loop
                "_prefetch_done_q",  # copy thread -> loop (fetch results)
                "_wake",
                # Lifecycle flags/threads, written only before the loop
                # starts or after it is joined.
                "_running",
                "_thread",
                "_watchdog",
                "_flight_handle",
                "copy_stream",
                # Internally synchronized (lock / GIL-relying, see the
                # lock manifests and DispatchProfiler docstring).
                "host_pool",
                "g3_store",  # PersistentKvStore, internally locked
                "flight",
                "profiler",
                "anatomy_ring",  # worst-N exemplars, internally locked
                "fingerprint",  # workload digest builder, internally locked
                "drift_watch",  # reads fingerprint snapshots only
                "cfg",
                "mesh",
                "_seed_rng",  # submission-side only (asyncio threads)
                "_build_info",  # written once in __init__, read-only after
                "_gather_pages",
                "_inject_pages",
                "_cow_pages",
                "_init_row",
                "_attn_impl",
                "_attn_interpret",
            }
        ),
    ),
)

# Lock-guarded shared state: every read or write of a guarded attribute
# inside its class must sit under ``with self.<lock>:``.
LOCK_MANIFESTS: tuple[LockManifest, ...] = (
    LockManifest(
        path="dynamo_exp_tpu/engine/offload.py",
        cls="HostKvPool",
        lock="_lock",
        guarded=frozenset(
            {"_k", "_v", "_free", "_by_hash", "stores", "hits", "evictions"}
        ),
    ),
    LockManifest(
        # The G3 persistent store's index + ledger counters: written by
        # the copy thread (demotions) and the engine loop (admission
        # promotes, stop drain), read by both. File I/O deliberately
        # runs OUTSIDE the lock (content addressing makes same-hash
        # racers benign); only the index/counter state is guarded.
        path="dynamo_exp_tpu/kv/persistent.py",
        cls="PersistentKvStore",
        lock="_lock",
        guarded=frozenset({"_by_hash", "_quarantined"}),
    ),
    LockManifest(
        path="dynamo_exp_tpu/telemetry/flight.py",
        cls="FlightRecorder",
        lock="_lock",
        guarded=frozenset({"_ring", "_head", "seq"}),
    ),
    LockManifest(
        # The fleet transfer ledger: recorded from asyncio transfer
        # paths, snapshotted from serving/scraper threads — every
        # ``_links`` access sits under the lock.
        path="dynamo_exp_tpu/telemetry/fleet.py",
        cls="TransferLedger",
        lock="_lock",
        guarded=frozenset({"_links"}),
    ),
    LockManifest(
        path="dynamo_exp_tpu/telemetry/slo.py",
        cls="SloAttribution",
        lock="_lock",
        guarded=frozenset(
            {
                "_win_ttft",
                "_win_itl",
                "completed",
                "violations",
                "goodput_by_priority",
                "_burn",  # multi-window burn-rate deques
            }
        ),
    ),
    LockManifest(
        # Worst-N anatomy exemplars: offered from the engine loop's
        # finish callback, snapshotted by /metrics scrapes and
        # `llmctl slow` — every ring access sits under the lock.
        path="dynamo_exp_tpu/telemetry/anatomy.py",
        cls="AnatomyRing",
        lock="_lock",
        guarded=frozenset({"_worst"}),
    ),
    LockManifest(
        # Online workload fingerprint: admissions observed on the
        # engine loop, snapshots taken from /metrics scrapes and the
        # drift watch — all histogram state sits under the lock.
        path="dynamo_exp_tpu/telemetry/fingerprint.py",
        cls="FingerprintBuilder",
        lock="_lock",
        guarded=frozenset(
            {
                "_n",
                "_isl",
                "_osl",
                "_prio",
                "_prompt_tokens",
                "_cached_tokens",
                "_spec_sum",
                "_spec_n",
                "_first_t",
                "_last_t",
                "_ia_n",
                "_ia_mean",
                "_ia_m2",
            }
        ),
    ),
)

# ------------------------------------------------- recompile-hazard sites
# Callables whose listed argument positions become compiled-variant
# cache keys (static shapes): those arguments must trace to a
# ``*_bucket_for`` helper, a constant, or static config — never a raw
# dynamic int (docs/engine_perf.md "Decode batch compaction").
VARIANT_SITE_MANIFESTS: tuple[VariantSiteManifest, ...] = (
    VariantSiteManifest(
        path="dynamo_exp_tpu/engine/engine.py",
        sites={
            # (total padded query tokens, page bound) — the two
            # shape-carrying axes of the collapsed ragged lattice; the
            # trailing windowed/sampler/lp key components are bools.
            "_ragged_fn": (0, 1),
            # A builder's token bucket, which ``_launch`` keys the
            # program by (the page bound is bucketed inside it); its
            # other keywords are no key.
            "_launch": (0, "nb"),
            "_gather_pages": (2,),
            "_inject_pages": (2,),
        },
    ),
    VariantSiteManifest(
        path="dynamo_exp_tpu/engine/block.py",
        sites={"_launch": (0, "nb")},
    ),
)
