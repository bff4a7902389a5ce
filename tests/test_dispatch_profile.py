"""Per-dispatch device profiling (docs/observability.md).

Covers the profiler acceptance path: every one of the three dispatch
kinds — ragged compute batches (prefill chunks, decode windows, and
spec verify spans all ride ``kind="ragged"``), KV gather/scatter, and
eviction offload batches — gets host-gap/in-flight/compile attribution
during one mixed run and surfaces on ``/metrics``; the decode span
carries dispatch attrs ``sim/fit.py`` can fit from; and the overhead
guarantee holds: profiling adds ZERO host syncs to the decode path
(sync-spy shim counting jax→numpy materializations, not wall clock —
CPU timing is load-sensitive).
"""

import asyncio

import numpy as np
import pytest

from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
from dynamo_exp_tpu.models import TINY
from dynamo_exp_tpu.parallel import single_device_mesh
from dynamo_exp_tpu.protocols.common import BackendInput
from dynamo_exp_tpu.telemetry import get_telemetry
from dynamo_exp_tpu.telemetry.dispatch import (
    DISPATCH_KINDS,
    SUMMARY_FIELDS,
    DispatchProfiler,
)

PS = 8


def _cfg(**over) -> EngineConfig:
    base = dict(
        model=TINY,
        max_decode_slots=2,
        page_size=PS,
        num_pages=64,
        max_model_len=128,
        eos_token_ids=[],
        kv_dtype="float32",
        decode_window=4,
    )
    return EngineConfig(**(base | over))


async def _generate(engine, prompt, max_tokens=8):
    b = BackendInput(token_ids=list(prompt))
    b.stop_conditions.max_tokens = max_tokens
    b.stop_conditions.ignore_eos = True
    stream = await engine.generate(b.to_dict())
    n = 0
    async for item in stream:
        n += len(item.get("token_ids", []))
    return n


# ------------------------------------------------------------------- units
def test_profiler_summary_shape_is_stable():
    prof = DispatchProfiler()
    s = prof.summary()
    assert set(s) == set(DISPATCH_KINDS)
    for stats in s.values():
        assert set(stats) == set(SUMMARY_FIELDS)
        assert stats["count"] == 0 and stats["in_flight_p50_s"] is None


def test_profiler_gap_in_flight_and_compile_accounting():
    prof = DispatchProfiler()
    t0 = prof.begin("ragged")
    t_disp = prof.end("ragged", t0, fresh=True)
    prof.consume("ragged", t_disp)
    # Second dispatch: the gap since the consume is now measurable.
    t1 = prof.begin("ragged")
    t_disp = prof.end("ragged", t1, fresh=False)
    prof.consume("ragged", t_disp)
    s = prof.summary()["ragged"]
    assert s["count"] == 2
    assert s["compile_misses"] == 1 and s["compile_total_s"] >= 0.0
    assert s["in_flight_p50_s"] is not None
    assert s["host_gap_p50_s"] is not None


def test_profiler_idle_drops_gap_reference():
    prof = DispatchProfiler()
    t0 = prof.begin("ragged")
    prof.consume("ragged", prof.end("ragged", t0))
    prof.mark_idle()
    prof.begin("ragged")  # would be a huge gap if the mark survived idle
    assert prof.summary()["ragged"]["host_gap_p50_s"] is None


def test_first_variant_is_once_per_key():
    prof = DispatchProfiler()
    assert prof.first_variant("gather", 8)
    assert not prof.first_variant("gather", 8)
    assert prof.first_variant("gather", 16)
    assert prof.first_variant("scatter", 8)


# --------------------------------------------- all five kinds, one engine
@pytest.mark.nightly
async def test_all_dispatch_kinds_profiled_in_mixed_run():
    """Acceptance: a mixed prefill+decode+spec run (plus the disagg
    extract and an eviction burst the same engine serves) populates
    dispatch/host-gap timing for ALL three kinds, and the per-kind
    series surface on the telemetry registry ``/metrics`` renders."""
    cfg = _cfg(
        num_pages=8,  # tight pool: the second prompt evicts the first's
        host_cache_pages=16,  # parked pages -> offload batch
        spec_mode="ngram",
        spec_draft_len=4,
        spec_adaptive=False,
    )
    engine = TPUEngine(cfg, mesh=single_device_mesh(), seed=0)
    engine.start()
    try:
        # Prefill + decode, pages registered then parked at finish.
        await _generate(engine, range(20, 36), max_tokens=6)
        # Repetitive prompt: the n-gram drafter proposes (spec spans
        # in the mixed ragged dispatch),
        # and its 6-page allocation evicts parked pages (offload).
        block = [50, 51, 52, 53, 54, 55, 56, 57]
        await _generate(engine, block * 6, max_tokens=8)
        # Disagg prefill-extract: batched gather + existing sync
        # (kv_move), pages pinned under a lease we confirm.
        _tok, pages, lease = await engine.prefill_extract(
            BackendInput(token_ids=list(range(100, 116))).to_dict()
        )
        assert pages and lease
        engine.confirm_kv_lease(lease)
        if engine.copy_stream is not None:
            engine.copy_stream.drain()

        disp = engine.metrics()["dispatch"]
        for kind in DISPATCH_KINDS:
            assert disp[kind]["count"] > 0, f"{kind} never dispatched"
        # Synced kinds carry in-flight samples (scatter-only moves
        # would not, but extract's gather is synced).
        for kind in ("ragged", "kv_move", "offload"):
            assert disp[kind]["in_flight_p50_s"] is not None, kind
        # Compile attribution: the ragged variant cache missed at least
        # once this run. The page-move gather shapes are ONE jit shared
        # by kv_move and offload, so the miss lands on whichever kind
        # dispatched the bucket first — assert across the pair, not per
        # kind.
        assert disp["ragged"]["compile_misses"] >= 1
        assert (
            disp["kv_move"]["compile_misses"]
            + disp["offload"]["compile_misses"]
        ) >= 1

        rendered = get_telemetry().render().decode()
        # A ragged dispatch is labelled by its compiled program (a
        # decode window or a mixed batch); the page-move kinds carry
        # none. Window + mixed is what ``{kind="ragged"}`` sums to:
        # tests/test_engine_phases.py.
        for kind, program in (
            ("ragged", "window"), ("ragged", "mixed"),
            ("kv_move", "-"), ("offload", "-"),
        ):
            assert (
                f'dynamo_dispatch_seconds_count{{kind="{kind}",'
                f'program="{program}"}}' in rendered
            )
            assert f'kind="{kind}"' in rendered
        assert "dynamo_host_gap_seconds_bucket" in rendered
        assert "dynamo_compile_seconds_bucket" in rendered
        assert "dynamo_compile_cache_misses_total" in rendered
    finally:
        engine.stop()


# ------------------------------------------------------- span integration
async def test_decode_span_carries_dispatch_attrs_and_fit_reads_them(tmp_path):
    from dynamo_exp_tpu.telemetry import span

    tel = get_telemetry()
    trace_file = str(tmp_path / "trace.jsonl")
    tel.configure(trace_file)
    engine = TPUEngine(_cfg(), mesh=single_device_mesh(), seed=0)
    engine.start()
    try:
        # The engine stamps spans onto the trace captured at
        # submission — open one like the HTTP root span would.
        with span("test_root"):
            b = BackendInput(token_ids=list(range(30, 46)))
            b.stop_conditions.max_tokens = 8
            b.stop_conditions.ignore_eos = True
            stream = await engine.generate(b.to_dict())
        async for _ in stream:
            pass
    finally:
        engine.stop()
        tel.configure(None)
    from dynamo_exp_tpu.telemetry import load_spans

    decode = [s for s in load_spans([trace_file]) if s.stage == "decode"]
    assert decode, "no decode span recorded"
    attrs = decode[-1].attrs
    assert attrs["dispatch_p50_s"] > 0
    assert attrs["decode_window"] == 4
    assert "host_gap_p50_s" in attrs

    from dynamo_exp_tpu.sim.fit import ServiceTimeModel

    model = ServiceTimeModel.from_spans([trace_file])
    assert model.itl_s.median_s > 0


def test_bench_dispatch_stats_fit_without_throughput_metric(tmp_path):
    """A bench line with no concurrency-tagged metric still fits ITL
    from its per-kind dispatch percentiles + decode_window — from the
    ragged engine's ``kind="ragged"`` lines AND (back-compat) from
    pre-ragged ``BENCH_r*.json`` lines that carry the old ``decode``
    kind."""
    import json

    from dynamo_exp_tpu.sim.fit import ServiceTimeModel

    def line(kind, flight):
        return {
            "metric": "custom_point",
            "value": 1.0,
            "decode_window": 8,
            "dispatch": {
                kind: {
                    "count": 10,
                    "in_flight_p50_s": flight,
                    "host_gap_p50_s": 0.008,
                }
            },
        }

    old = tmp_path / "bench_old.json"
    old.write_text(json.dumps(line("decode", 0.08)) + "\n")
    model = ServiceTimeModel.from_bench_json([old])
    assert model.itl_s.median_s == pytest.approx((0.08 + 0.008) / 8)

    new = tmp_path / "bench_ragged.json"
    new.write_text(json.dumps(line("ragged", 0.16)) + "\n")
    model = ServiceTimeModel.from_bench_json([new])
    assert model.itl_s.median_s == pytest.approx((0.16 + 0.008) / 8)


# ------------------------------------------------------- overhead (sync spy)
@pytest.mark.nightly
def test_profiler_adds_zero_host_syncs_per_window(monkeypatch):
    """Overhead smoke (`make profile-smoke`): the instrumented decode
    path performs ZERO additional host syncs — the same workload runs
    with profiling on and off under a sync-spy shim counting
    jax-Array→numpy materializations, and the counts must be equal
    (wall clock is deliberately not compared; CPU timing is
    load-sensitive)."""
    import jax

    def run_counted(profile: bool) -> tuple[int, int]:
        engine = TPUEngine(
            _cfg(profile_dispatches=profile),
            mesh=single_device_mesh(),
            seed=0,
        )
        engine.start()
        counter = {"n": 0}
        orig = np.asarray

        def spy(a, *args, **kw):
            if isinstance(a, jax.Array):
                counter["n"] += 1
            return orig(a, *args, **kw)

        monkeypatch.setattr(np, "asarray", spy)
        try:
            asyncio.run(_generate(engine, range(40, 56), max_tokens=12))
        finally:
            monkeypatch.setattr(np, "asarray", orig)
            engine.stop()
        return counter["n"], engine.steps

    syncs_on, steps_on = run_counted(True)
    syncs_off, steps_off = run_counted(False)
    assert steps_on == steps_off  # identical window schedule
    assert syncs_on == syncs_off, (
        f"profiling changed host-sync count: {syncs_on} vs {syncs_off}"
    )
    assert syncs_on > 0  # the spy actually saw the consume syncs


# ---------------------------------------------------------- compile guard
async def test_compile_misses_stop_in_steady_state():
    engine = TPUEngine(_cfg(), mesh=single_device_mesh(), seed=0)
    engine.start()
    try:
        await _generate(engine, range(20, 36), max_tokens=8)
        first = {
            k: v["compile_misses"]
            for k, v in engine.metrics()["dispatch"].items()
        }
        assert first["ragged"] >= 2  # prefill-shaped + windowed variants
        # Same shapes again: every variant is cached, misses must not move.
        await _generate(engine, range(60, 76), max_tokens=8)
        second = {
            k: v["compile_misses"]
            for k, v in engine.metrics()["dispatch"].items()
        }
        assert second == first
    finally:
        engine.stop()
