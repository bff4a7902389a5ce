"""Pytest root conftest: force JAX onto an 8-device virtual CPU mesh.

The suite is the CPU check: it must give the same answer on a machine
with a chip as on one without, so it never runs on one. Multi-chip
sharding is validated on virtual CPU devices; the chip itself is
``chip_smoke.py``'s job. Must run before jax initializes its backends,
hence env vars here (child processes inherit them).
"""

import asyncio
import contextlib
import faulthandler
import inspect
import os
import signal
import sys
import threading

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "asyncio: run the coroutine test on a fresh event loop"
    )


# ---------------------------------------------------------------- test tiers
# Reference discipline: marker tiers (pre_merge/nightly/weekly) selected by
# CI (.github/workflows/); what the driver holds a PR to is tier-1, every
# test not marked ``slow`` (docs/testing.md "Tier-1": its command, budgets
# and conventions). Tiering is centralized here instead of per-file
# pytestmark lines so the split is auditable in one place: a test is
# pre_merge unless its file (or name) is listed below.
#
# Nightly = the wall-clock-dominant suites: HF-parity across all model
# families, multi-process supervisors (SDK serve, CLI, multihost), and
# the interpret-mode Pallas kernel oracle checks.
_NIGHTLY_FILES = {
    "test_model_families.py",  # 11-family HF logits parity, ~2.5 min
    "test_llm_graphs.py",  # SDK graph supervisors over HTTP
    "test_run_cli.py",  # multi-process discovery serve
    "test_sdk.py",  # SDK supervisor lifecycle
    "test_multihost.py",  # jax.distributed bring-up subprocesses
    "test_ragged_attention.py",  # interpret-mode engines, the recompile guard
    "test_ragged_kernels.py",  # ragged kernel interpret-mode vs ref oracle
    "test_ragged_expanded.py",  # the expanded call, interpret-mode vs ref oracle
    "test_ragged_tile_rows.py",  # tiles walked as rows, interpret-mode vs the tile kernel
    "test_logprobs.py",  # engine logprob oracle runs
    "test_disagg.py",  # two-engine disagg e2e
    "test_decode_compaction.py",  # occupancy-proportional decode proofs
    "test_ring_attention.py",  # ring vs dense oracles on the 8-dev mesh
    "test_kv_offload.py",  # host-offload round trips
    "test_model.py",  # full-model forward oracles
    "test_hub_gguf.py",  # GGUF write/load round trips
    "test_planner.py",  # supervisor scale up/down under load
}
# Individually slow tests inside otherwise pre_merge files.
_NIGHTLY_TESTS = {
    "test_concurrent_requests_batch",  # 110s: full batching soak
    # Real-TPUEngine resumable-generation proofs (compile-heavy; the
    # request-plane resumable tests in the same file stay pre_merge).
    "test_engine_greedy_continuation_token_identical",
    "test_engine_seeded_sampling_continuation_identical",
    "test_engine_penalized_continuation_restores_counts",
    "test_engine_lease_reaper_reclaims_orphaned_extract",
    "test_engine_lease_confirm_releases_without_reclaim",
    "test_prefill_worker_leaves_lease_to_reaper_on_delivery_failure",
    "test_sse_stream_gapless_and_duplicate_free_across_failover",
    # Real-TPUEngine overload/preemption proofs (compile-heavy; the
    # admission/scheduler/routing units in the same file stay pre_merge).
    "test_waiting_queue_reaps_cancelled_anywhere",
    "test_preempt_resume_greedy_token_identity",
    "test_preempt_resume_seeded_token_identity",
    "test_preempt_resume_penalized_restores_counts",
    "test_engine_drops_expired_at_admission",
    "test_capacity_exceeding_requests_finish_instead_of_hanging",
    "test_preemption_disabled_by_negative_grace",
    "test_overload_burst_no_hangs_sheds_tagged_streams_identical",
    # AOT warm-boot proofs (compile-heavy: two full-lattice prewarns /
    # a subprocess jax import; the lattice/fit/sim units in the same
    # file stay pre_merge, and `make prewarm-smoke` gates pre-merge).
    "test_warm_boot_compiles_nothing",
    "test_identity_prewarmed_vs_cold_all_sampler_modes",
    "test_manifest_hash_identical_across_processes",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if any(m in ("nightly", "weekly", "tpu", "pre_merge") for m in item.keywords):
            continue  # explicitly marked — leave as-is
        name = item.function.__name__ if hasattr(item, "function") else item.name
        if item.fspath.basename in _NIGHTLY_FILES or name in _NIGHTLY_TESTS:
            item.add_marker(pytest.mark.nightly)
        else:
            item.add_marker(pytest.mark.pre_merge)


# A test's own clock (docs/testing.md "Budgets"): a coroutine test is
# cancelled by its event loop, a synchronous one by SIGALRM, which raises in
# the test and leaves the worker alive, so a hang costs one failure and the
# stacks of all threads on stderr, never the run's whole time limit. 300 s:
# the slowest test fits in it twice over. Constants, not options.
_ASYNC_TEST_LIMIT_S = 120.0
_SYNC_TEST_LIMIT_S = 300.0


@contextlib.contextmanager
def a_clock_of_its_own(nodeid: str, limit_s: float):
    """Raise ``TimeoutError`` in the calling (main) thread once ``limit_s``
    have passed inside the block; off the main thread a signal cannot be
    taken, so there the block runs unlimited."""
    if threading.current_thread() is not threading.main_thread():
        yield
        return

    def expired(signum, frame):
        raise TimeoutError(f"{nodeid} ran past its {limit_s:.0f} s")

    before = signal.signal(signal.SIGALRM, expired)
    # A hang inside a call that takes no signal still leaves its traceback.
    faulthandler.dump_traceback_later(limit_s, exit=False, file=sys.__stderr__)
    signal.setitimer(signal.ITIMER_REAL, limit_s)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()
        signal.signal(signal.SIGALRM, before)


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    """Minimal asyncio support (pytest-asyncio is not in the image) and the
    per-test limit (neither is pytest-timeout)."""
    fn = pyfuncitem.obj
    kwargs = {
        name: pyfuncitem.funcargs[name]
        for name in pyfuncitem._fixtureinfo.argnames
    }
    if inspect.iscoroutinefunction(fn):
        asyncio.run(asyncio.wait_for(fn(**kwargs), timeout=_ASYNC_TEST_LIMIT_S))
    else:
        with a_clock_of_its_own(pyfuncitem.nodeid, _SYNC_TEST_LIMIT_S):
            fn(**kwargs)
    return True


_CACHE_OPTIONS = (
    "jax_compilation_cache_dir",
    "jax_persistent_cache_min_compile_time_secs",
    "jax_persistent_cache_min_entry_size_bytes",
)


@pytest.fixture(autouse=True)
def _compile_cache_stays_in_its_test():
    """``aot.enable_persistent_cache`` sets process-wide JAX options. A
    test that points them at its ``tmp_path`` (tests/test_aot.py,
    tests/test_tune.py) used to leave every later test of its xdist
    worker compiling through that directory: executables of earlier
    engines came back by ``deserialize_executable``, which is where a
    worker died inside ``test_recompile_guard_steady_state`` (PR 48's
    run). What a test changed is put back when it ends."""
    jax = sys.modules.get("jax")
    before = (
        {name: getattr(jax.config, name) for name in _CACHE_OPTIONS}
        if jax is not None else None
    )
    yield
    jax = sys.modules.get("jax")
    if jax is None:
        return
    if before is None:
        # jax came in with this test: what it found is JAX's default
        # (the environment's directory, the stock gates).
        before = {
            "jax_compilation_cache_dir":
                os.environ.get("JAX_COMPILATION_CACHE_DIR") or None,
            "jax_persistent_cache_min_compile_time_secs": 1.0,
            "jax_persistent_cache_min_entry_size_bytes": 0,
        }
    changed = [n for n in _CACHE_OPTIONS if getattr(jax.config, n) != before[n]]
    if changed:
        from jax._src.compilation_cache import reset_cache

        for name in changed:
            jax.config.update(name, before[name])
        reset_cache()
