"""Benchmark: engine decode throughput on the real TPU chip.

Default mode prints ONE JSON line: {"metric", "value", "unit",
"vs_baseline"} for the driver.

``--sweep`` runs the reference harness shape scaled to one chip —
ISL 3000 / OSL 150 fixed lengths, ignore_eos, concurrency sweep
(``/root/reference/examples/llm/benchmarks/perf.sh:22-44`` uses 1→256
on 8×H100; one v5e chip sweeps 1→32) — and prints one JSON line per
concurrency point.

``vs_baseline`` is measured tok/s divided by the single-chip HBM
roofline for this model (weights are re-read every decode step, so
steps/s <= HBM_BW / weight_bytes; tokens/s <= steps/s * batch). This is
an honest hardware-efficiency fraction rather than a cross-hardware
comparison the reference never published absolute numbers for
(SURVEY.md §6). The peak comes from ``HBM_PEAK_GBPS`` keyed by the
``device_kind`` JAX reports; a device that is not in the table gets no
``vs_baseline`` at all.

One process owns the chip: the device is read in-process, and a run
that finds no TPU exits non-zero. ``JAX_PLATFORMS=cpu`` asks for a CPU
run by name (trimmed shapes, ``tiny`` preset): its lines carry
``platform: "cpu"`` and no device metric.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import time

import numpy as np

MODEL = "llama-1b"
ISL = 128
OSL = 64
CONCURRENCY = 32
# A requested CPU run's default point: a 1B model at the TPU shape is
# hours on a CI box's cores, which reads as a dead bench run — and even
# the trimmed shape is minutes of f32 weight init + compile there, so
# a CPU run also drops to the ``tiny`` preset. The metric name carries
# the model and shape and every line carries the platform, so the
# trajectory stays unambiguous. Explicit --model/--isl/--osl/
# --concurrency always win.
CPU_MODEL = "tiny"
CPU_ISL = 64
CPU_OSL = 32
CPU_CONCURRENCY = 4
# Peak HBM bandwidth in GB/s by ``jax.devices()[0].device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (819 GB/s per chip).
HBM_PEAK_GBPS = {"TPU v5 lite": 819.0}
# The device this process measures on, as JAX reports it; set once by
# main() (platform, device_kind, device_count) and tagged on every line.
DEVICE: dict = {}

SWEEP_ISL = 3000
SWEEP_OSL = 150
SWEEP_CONCURRENCY = (1, 4, 16, 32)
# CPU-run sweep shapes: the reference sweep point (ISL 3000 at
# concurrency 32) is the "hours on a CI box" case above even with the
# tiny preset, so every sweep mode trims the same way the default
# point does — the emitted shape labels + platform tag keep CPU
# lines distinguishable from chip lines.
CPU_SWEEP_ISL = 256
CPU_SWEEP_OSL = 32
CPU_SWEEP_CONCURRENCY = (1, 2, 4)
CPU_SWEEP_KW = dict(slots=4, isl=128, osl=32)  # occupancy/overload sweeps
# Offload-pressure axis CPU trim (occupancy sweep only — the shared
# CPU_SWEEP_KW also feeds run_overload_sweep, which has no such axis).
CPU_PRESSURE_MULTIPLES = (1, 2, 4)
CPU_OVERLOAD_BURSTS = (4, 8, 16)
CPU_PREFIX_KW = dict(isl=256, osl=8, concurrency=4)
# Prefix-sharing sweep CPU run: same trim treatment — tiny shapes,
# two ratio points, enough to exercise shared-vs-private both arms.
CPU_PREFIX_SWEEP_KW = dict(
    isl=128, osl=8, concurrency=4, ratios=(0.0, 0.75)
)
# Spec-sweep CPU run: same trimming policy as every other sweep —
# tiny shapes, one draft length besides the off baseline.
CPU_SPEC_KW = dict(slots=2, isl=96, osl=32, draft_lens=(0, 4))

# Coldstart sweep CPU run: small shapes, the same trim policy.
CPU_COLDSTART_KW = dict(isl=64, osl=16, concurrency=2)

# Reclaim sweep CPU trim: the sweep is sim-driven (no chip, no
# compile), so the trim only shortens the simulated window and drops a
# rate point to keep the CI lane seconds-scale.
CPU_RECLAIM_KW = dict(duration_s=120.0, reclaim_rates=(0.0, 2.0, 6.0))

# Restart sweep CPU run: same trim policy as coldstart — tiny
# shapes, both arms still exercised end to end.
CPU_RESTART_KW = dict(isl=64, osl=16, concurrency=2)

# Burst policy: warmup rounds (compile + program load) and timed rounds
# (best-of). A CPU run trims both to 1 — XLA:CPU timings are
# low-variance and a 1B-model burst is minutes, not seconds, there.
WARMUP_BURSTS = 2
TIMED_BURSTS = 3
# Set on a CPU run: the model runs in float32 there (XLA:CPU
# software-emulates bfloat16 matmuls — order-of-magnitude slower than
# native f32 on the same cores).
CPU_RUN = False
# AOT warm boot (docs/aot.md): --prewarm prewarns every bench engine
# before measurement. LINE_TAGS rides on every JSON line so sim/fit.py
# can tell warm samples from cold (the manifest hash pins which compile
# lattice produced the numbers).
PREWARM = False
LINE_TAGS = {
    "prewarmed": False,
    "manifest_hash": None,
    # Resolved tunable-knob dict + stable hash (tune/space.py), filled
    # in by _build_engine; None until an engine exists.
    "knobs": None,
    "config_hash": None,
}


def _preset(name: str):
    from dataclasses import replace

    from dynamo_exp_tpu.models import PRESETS

    mcfg = PRESETS[name]
    return replace(mcfg, dtype="float32") if CPU_RUN else mcfg


def _kv_dtype() -> str:
    return "float32" if CPU_RUN else "bfloat16"


def _vs_roofline(tok_s: float, params, batch: int) -> float | None:
    """``tok_s`` as a share of the weights-only HBM bound on this
    device; None (and so no ``vs_baseline``) for a device whose peak
    is not in ``HBM_PEAK_GBPS``."""
    import jax

    peak = HBM_PEAK_GBPS.get(DEVICE.get("device_kind"))
    if peak is None:
        return None
    weight_bytes = sum(
        int(np.prod(x.shape)) * x.dtype.itemsize
        for x in jax.tree.leaves(params)
    )
    return round(tok_s / (peak * 1e9 / weight_bytes * batch), 4)


def _dispatch_stats(engine) -> dict:
    """Per-kind dispatch-timing percentiles (p50/p99 host-gap and
    in-flight) from the engine's dispatch profiler, attached to every
    bench JSON line — so ``sim/fit.py --fit-bench`` can fit service
    times without a span file (it reads ``dispatch.ragged`` — or the
    retired ``dispatch.decode`` of pre-ragged bench files — together
    with the line's ``decode_window``). Kinds that never dispatched in
    the run keep count 0 / null percentiles."""
    disp = engine.metrics().get("dispatch") or {}
    keep = (
        "count",
        "host_gap_p50_s",
        "host_gap_p99_s",
        "in_flight_p50_s",
        "in_flight_p99_s",
    )
    return {
        kind: {f: stats.get(f) for f in keep}
        for kind, stats in disp.items()
    }


def _anatomy_stats(engine) -> dict:
    """Mean per-request latency anatomy (telemetry/anatomy.py component
    seconds over finished requests) attached to every bench JSON line,
    so ``llmctl bench compare`` can attribute a throughput regression
    to the component that moved (queue vs prefill vs decode vs swap
    stall) instead of just flagging the headline number. Zero-valued
    components are dropped to keep bench lines compact."""
    m = engine.metrics()
    n = m.get("anatomy_requests") or 0
    totals = m.get("anatomy_totals") or {}
    if not n:
        return {}
    return {comp: round(sec / n, 6) for comp, sec in totals.items() if sec}


def _enable_compile_cache() -> None:
    """Persistent XLA compilation cache (docs/aot.md): repeat bench
    runs skip the per-variant compiles, so the measured TTFT reflects
    serving, not compilation. The directory is the one every entry
    point uses (``aot.resolve_cache_dir``)."""
    from dynamo_exp_tpu.aot import enable_persistent_cache

    enable_persistent_cache()


def _build_engine(cfg, params=None, seed: int = 0):
    """Every bench engine goes through here: tags each subsequent JSON
    line with the engine's compile-manifest hash, whether it was
    warm-booted (``--prewarm``), and the resolved tunable-knob dict
    plus its stable ``config_hash`` (tune/space.py) — so
    ``llmctl bench compare`` pairs lines knobbed identically instead of
    silently comparing differently-tuned runs, and ``sim/fit.py`` can
    split warm from cold samples (docs/aot.md, docs/tuning.md)."""
    from dynamo_exp_tpu.aot import manifest_for_engine
    from dynamo_exp_tpu.engine import TPUEngine
    from dynamo_exp_tpu.tune import space as tune_space

    engine = TPUEngine(cfg, params=params, seed=seed)
    manifest = manifest_for_engine(engine)
    if PREWARM:
        engine.prewarm(manifest)
    knobs = tune_space.resolved_engine_knobs(cfg)
    LINE_TAGS.update(
        prewarmed=bool(PREWARM),
        manifest_hash=manifest.hash(),
        knobs=knobs,
        config_hash=tune_space.config_hash(knobs),
    )
    engine.start()
    return engine


def run_point(isl: int, osl: int, concurrency: int) -> dict:
    """One measured point: build an engine, double-warm, time a burst."""
    from dynamo_exp_tpu.engine import EngineConfig
    from dynamo_exp_tpu.protocols.common import BackendInput

    _enable_compile_cache()

    mcfg = _preset(MODEL)
    cfg = EngineConfig(
        model=mcfg,
        max_decode_slots=concurrency,
        page_size=16,
        num_pages=concurrency * ((isl + osl) // 16 + 2) + 64,
        max_model_len=max(512, ((isl + osl) // 256 + 2) * 256),
        eos_token_ids=[],
        kv_dtype=_kv_dtype(),
        # One host sync per 32 decode steps.
        decode_window=32,
    )
    engine = _build_engine(cfg)

    rs = np.random.RandomState(0)

    # Fresh tokens for every burst: identical shapes hit the same
    # compiled variants, distinct tokens keep the prefix cache honest
    # (re-serving a previous burst's prompts would measure warm-cache
    # prefill instead of steady-state decode).
    def fresh_prompts() -> list[list[int]]:
        return [
            rs.randint(10, mcfg.vocab_size - 10, size=isl).tolist()
            for _ in range(concurrency)
        ]

    warmups = fresh_prompts()

    async def run_one(prompt):
        b = BackendInput(token_ids=prompt)
        b.stop_conditions.max_tokens = osl
        b.stop_conditions.ignore_eos = True
        stream = await engine.generate(b.to_dict())
        n = 0
        ttft = None
        t0 = time.perf_counter()
        async for item in stream:
            if item.get("token_ids") and ttft is None:
                ttft = time.perf_counter() - t0
            n += len(item.get("token_ids", []))
        return n, ttft

    async def burst():
        # Warmup: full concurrent bursts. The first compiles every
        # variant (token buckets, decode window). Steady-state
        # throughput, not compile/load time, is the metric.
        for _ in range(WARMUP_BURSTS):
            await asyncio.gather(*[run_one(p) for p in warmups])
        # Best of the timed bursts.
        best = None
        for burst_prompts in (fresh_prompts() for _ in range(TIMED_BURSTS)):
            t0 = time.perf_counter()
            results = await asyncio.gather(*[run_one(p) for p in burst_prompts])
            dt = time.perf_counter() - t0
            total = sum(n for n, _ in results)
            ttfts = sorted(t for _, t in results if t is not None)
            point = (total / dt, ttfts[len(ttfts) // 2])
            if best is None or point[0] > best[0]:
                best = point
        return best

    tok_s, p50_ttft = asyncio.run(burst())
    vs_roofline = _vs_roofline(tok_s, engine.params, concurrency)
    dispatch = _dispatch_stats(engine)
    anatomy = _anatomy_stats(engine)
    engine.stop()
    return {
        "metric": f"decode_throughput_{MODEL}_isl{isl}_osl{osl}_c{concurrency}",
        "value": round(tok_s, 1),
        "unit": "tok/s",
        "vs_baseline": vs_roofline,
        "p50_ttft_s": round(p50_ttft, 3),
        "decode_window": engine.cfg.decode_window,
        "dispatch": dispatch,
        "anatomy": anatomy,
    }


def run_occupancy_sweep(
    slots: int = 8,
    isl: int = 512,
    osl: int = 128,
    pressure_multiples: tuple = (1, 2, 4, 8),
) -> list[dict]:
    """Decode throughput vs *occupancy* on a fixed-slot engine.

    The compiled decode window is row-compacted (docs/engine_perf.md):
    at 1 active sequence of ``slots`` slots the engine should pick the
    rows=1 variant and pay ~1/slots of the full-batch FLOPs/HBM — this
    sweep captures that curve plus the compiled-variant counts and
    wasted-step counters, so BENCH_r* records regressions where decode
    cost snaps back to the worst case."""
    import asyncio

    from dynamo_exp_tpu.engine import EngineConfig
    from dynamo_exp_tpu.protocols.common import BackendInput

    _enable_compile_cache()
    mcfg = _preset(MODEL)
    cfg = EngineConfig(
        model=mcfg,
        max_decode_slots=slots,
        page_size=16,
        num_pages=slots * ((isl + osl) // 16 + 2) + 64,
        max_model_len=max(512, ((isl + osl) // 256 + 2) * 256),
        eos_token_ids=[],
        kv_dtype=_kv_dtype(),
        decode_window=32,
    )
    engine = _build_engine(cfg)
    rs = np.random.RandomState(0)

    async def run_one(prompt):
        b = BackendInput(token_ids=prompt)
        b.stop_conditions.max_tokens = osl
        b.stop_conditions.ignore_eos = True
        stream = await engine.generate(b.to_dict())
        n = 0
        async for item in stream:
            n += len(item.get("token_ids", []))
        return n

    def prompts(n):
        return [
            rs.randint(10, mcfg.vocab_size - 10, size=isl).tolist()
            for _ in range(n)
        ]

    async def point(active: int) -> float:
        # Double warmup per occupancy (compile + program load), then
        # best-of-three timed bursts (same policy as run_point).
        for _ in range(WARMUP_BURSTS):
            await asyncio.gather(*[run_one(p) for p in prompts(active)])
        best = 0.0
        for _ in range(TIMED_BURSTS):
            batch = prompts(active)
            t0 = time.perf_counter()
            results = await asyncio.gather(*[run_one(p) for p in batch])
            dt = time.perf_counter() - t0
            best = max(best, sum(results) / dt)
        return best

    out = []
    occupancies = sorted({1, 2, 4, slots})
    for active in occupancies:
        wasted0 = engine.wasted_steps
        moves0 = engine.kv_page_moves
        tok_s = asyncio.run(point(active))
        m = engine.metrics()
        out.append(
            {
                "metric": f"decode_occupancy_{MODEL}_isl{isl}_osl{osl}"
                f"_a{active}of{slots}",
                "value": round(tok_s, 1),
                "unit": "tok/s",
                "vs_baseline": _vs_roofline(tok_s, engine.params, active),
                "active": active,
                "slots": slots,
                "compiled_ragged_variants": m["compiled_ragged_variants"],
                "wasted_steps": engine.wasted_steps - wasted0,
                "kv_page_moves": engine.kv_page_moves - moves0,
                "decode_window": engine.cfg.decode_window,
                "dispatch": _dispatch_stats(engine),
                "anatomy": _anatomy_stats(engine),
            }
        )

    # ---------------- mixed prefill+decode axis (ragged late-join) ----------
    # Late prompts injected MID-decode: `active` established decoders
    # chain windows; once they have visibly stepped, `n_late` short
    # prompts arrive. On the ragged engine a latecomer's chunk rides
    # the next compute dispatch together with the decode rows (one
    # mixed program), so its TTFT should sit under one decode-window
    # duration — `late_join_ttft_p50_s` vs the window's in-flight p50
    # is the acceptance comparison, and the variant count shows the
    # mixed shapes landing in the SAME compiled cache.
    isl_late = max(isl // 4, 16)
    n_late = max(slots - max(slots // 2, 1), 1)
    # Established rows must still be decoding when the lates land: give
    # them several windows of runway past the injection point.
    long_osl = max(osl, 6 * cfg.decode_window)

    async def long_one(prompt):
        b = BackendInput(token_ids=prompt)
        b.stop_conditions.max_tokens = long_osl
        b.stop_conditions.ignore_eos = True
        stream = await engine.generate(b.to_dict())
        n = 0
        async for item in stream:
            n += len(item.get("token_ids", []))
        return n

    async def late_one(prompt):
        b = BackendInput(token_ids=prompt)
        b.stop_conditions.max_tokens = 8
        b.stop_conditions.ignore_eos = True
        t0 = time.perf_counter()
        stream = await engine.generate(b.to_dict())
        ttft = None
        n = 0
        async for item in stream:
            if item.get("token_ids") and ttft is None:
                ttft = time.perf_counter() - t0
            n += len(item.get("token_ids", []))
        return n, ttft

    async def mixed_point(active: int) -> tuple[float, list, float]:
        def late_prompts():
            return [
                rs.randint(10, mcfg.vocab_size - 10, size=isl_late).tolist()
                for _ in range(n_late)
            ]

        # Warmup: compile the mixed (prefill+decode in one dispatch)
        # variants this axis exercises, then time one injection burst.
        for _ in range(WARMUP_BURSTS):
            jobs = [
                asyncio.ensure_future(long_one(p)) for p in prompts(active)
            ]
            await asyncio.sleep(0)
            lates = [asyncio.ensure_future(late_one(p)) for p in late_prompts()]
            await asyncio.gather(*jobs, *lates)
        jobs = [asyncio.ensure_future(long_one(p)) for p in prompts(active)]
        # Wait until the established rows have demonstrably stepped
        # (at least one full decode window) before injecting.
        steps0 = engine.steps
        t0 = time.perf_counter()
        while (
            engine.steps < steps0 + engine.cfg.decode_window
            and time.perf_counter() - t0 < 60.0
        ):
            await asyncio.sleep(0.005)
        lates = [asyncio.ensure_future(late_one(p)) for p in late_prompts()]
        results = await asyncio.gather(*jobs, *lates)
        dt = time.perf_counter() - t0
        total = sum(r[0] if isinstance(r, tuple) else r for r in results)
        ttfts = sorted(t for _, t in results[active:] if t is not None)
        return total / dt, ttfts, dt

    for active in sorted({1, max(slots // 2, 1)}):
        tok_s, ttfts, _dt = asyncio.run(mixed_point(active))
        m = engine.metrics()
        disp = _dispatch_stats(engine)
        # Windows are the slowest ragged dispatches in this phase, so
        # the kind's in-flight p99 approximates one full decode-window
        # duration — the bound the late-join TTFT is judged against
        # (mixed single-step batches drag the p50 far below it).
        window_s = (disp.get("ragged") or {}).get("in_flight_p99_s")
        p50_ttft = ttfts[len(ttfts) // 2] if ttfts else None
        out.append(
            {
                "metric": f"decode_mixed_{MODEL}_isl{isl}_osl{osl}"
                f"_a{active}of{slots}_late{n_late}",
                "value": round(tok_s, 1),
                "unit": "tok/s",
                "vs_baseline": _vs_roofline(
                    tok_s, engine.params, active + n_late
                ),
                "active": active,
                "slots": slots,
                "late": n_late,
                "late_isl": isl_late,
                "late_join_ttft_p50_s": round(p50_ttft, 4)
                if p50_ttft is not None
                else None,
                "window_in_flight_p99_s": window_s,
                "compiled_ragged_variants": m["compiled_ragged_variants"],
                "decode_window": engine.cfg.decode_window,
                "dispatch": disp,
                "anatomy": _anatomy_stats(engine),
            }
        )
    engine.stop()

    # -------- offload-pressure axis (predictive KV tiering) --------
    # The ROADMAP's named proof surface: hold the pool fixed and scale
    # the AGGREGATE context to multiples of it. One line per multiple,
    # tagged with the tiering counters (prefetch hit rate, proactive
    # offloads, swap-ins) plus the preemptions and p99 ITL the policy
    # is supposed to bound — at 8x pool a healthy line shows proactive
    # offloads absorbing the pressure with preemptions near zero.
    per_seq_pages = (isl + osl) // 16 + 2
    pool = max(2 * per_seq_pages, (slots * per_seq_pages) // 2)
    for mult in pressure_multiples:
        n_req = max(-(-mult * pool * 16 // (isl + osl)), 1)
        pcfg = EngineConfig(
            model=mcfg,
            max_decode_slots=slots,
            page_size=16,
            num_pages=pool,
            max_model_len=max(512, ((isl + osl) // 256 + 2) * 256),
            eos_token_ids=[],
            kv_dtype=_kv_dtype(),
            decode_window=32,
            host_cache_pages=pool * 8,
            preempt_stall_grace_s=0.5,
        )
        peng = _build_engine(pcfg)

        async def pressure_one(prompt, eng=peng):
            b = BackendInput(token_ids=prompt)
            b.stop_conditions.max_tokens = osl
            b.stop_conditions.ignore_eos = True
            stream = await eng.generate(b.to_dict())
            n = 0
            gaps: list[float] = []
            last = None
            async for item in stream:
                got = len(item.get("token_ids", []))
                if got:
                    now_t = time.perf_counter()
                    if last is not None:
                        gaps.append((now_t - last) / got)
                    last = now_t
                    n += got
            return n, gaps

        async def pressure_point(n=n_req):
            batch = [
                rs.randint(10, mcfg.vocab_size - 10, size=isl).tolist()
                for _ in range(n)
            ]
            t0 = time.perf_counter()
            results = await asyncio.gather(*[pressure_one(p) for p in batch])
            dt = time.perf_counter() - t0
            total = sum(n for n, _ in results)
            itls = sorted(g for _, gaps in results for g in gaps)
            p99 = itls[min(int(len(itls) * 0.99), len(itls) - 1)] if itls else None
            return total / dt, p99

        tok_s, p99_itl = asyncio.run(pressure_point())
        m = peng.metrics()
        restored = m["kv_prefetch_pages"]
        hit_rate = (
            round(m["kv_prefetch_hits"] / restored, 4) if restored else None
        )
        out.append(
            {
                "metric": f"kv_tiering_{MODEL}_isl{isl}_osl{osl}_x{mult}",
                "value": round(tok_s, 1),
                "unit": "tok/s",
                "aggregate_x_pool": mult,
                "requests": n_req,
                "pool_pages": pool,
                "host_pages": pool * 8,
                "prefetch_restored_pages": restored,
                "prefetch_hit_rate": hit_rate,
                "proactive_offloads": m["kv_proactive_offloads"],
                "swap_ins": m["kv_swap_ins"],
                "preemptions": m["preemptions"],
                "p99_itl_s": round(p99_itl, 4) if p99_itl is not None else None,
                "decode_window": peng.cfg.decode_window,
                "dispatch": _dispatch_stats(peng),
                "anatomy": _anatomy_stats(peng),
            }
        )
        peng.stop()
    return out


def run_overload_sweep(
    slots: int = 8,
    isl: int = 512,
    osl: int = 128,
    burst_levels: tuple[int, ...] = (8, 16, 32, 64),
) -> list[dict]:
    """Graceful degradation under bursts: goodput, shed rate, p99 TTFT,
    and KV-pressure preemption count per burst level.

    The engine gets a pool sized to roughly *half* its slots' worst-case
    KV need, behind an AdmissionController capped at 2x slots — so
    rising burst levels walk the whole overload ladder: full batches,
    engine-side queuing, KV-pressure preemption, priority shedding
    (429), hard-cap refusals (503). The JSON lines record the curve the
    overload-protection layer is supposed to flatten: goodput should
    plateau near capacity instead of collapsing, and shed rate should
    absorb the excess."""
    import asyncio

    from dynamo_exp_tpu.engine import EngineConfig
    from dynamo_exp_tpu.http.admission import (
        AdmissionController,
        RequestShedError,
        parse_priority,
    )
    from dynamo_exp_tpu.protocols.common import BackendInput

    _enable_compile_cache()
    mcfg = _preset(MODEL)
    pages_per_seq = (isl + osl) // 16 + 2
    cfg = EngineConfig(
        model=mcfg,
        max_decode_slots=slots,
        page_size=16,
        num_pages=(slots * pages_per_seq) // 2 + 16,  # deliberate pressure
        max_model_len=max(512, ((isl + osl) // 256 + 2) * 256),
        eos_token_ids=[],
        kv_dtype=_kv_dtype(),
        decode_window=32,
        preempt_stall_grace_s=0.2,
    )
    engine = _build_engine(cfg)
    rs = np.random.RandomState(0)
    priorities = ("low", "normal", "high")

    async def run_one(prompt, priority, admission):
        try:
            admission.acquire(parse_priority(priority))
        except RequestShedError as e:
            return {"shed": e.status}
        try:
            b = BackendInput(
                token_ids=prompt, priority=parse_priority(priority)
            )
            b.stop_conditions.max_tokens = osl
            b.stop_conditions.ignore_eos = True
            stream = await engine.generate(b.to_dict())
            n = 0
            ttft = None
            t0 = time.perf_counter()
            async for item in stream:
                if item.get("token_ids") and ttft is None:
                    ttft = time.perf_counter() - t0
                n += len(item.get("token_ids", []))
            return {"tokens": n, "ttft": ttft}
        finally:
            admission.release()

    async def burst(n: int) -> dict:
        admission = AdmissionController(
            max_inflight=slots * 2, shed_watermark=(slots * 3) // 2
        )
        prompts = [
            rs.randint(10, mcfg.vocab_size - 10, size=isl).tolist()
            for _ in range(n)
        ]
        preempted0 = engine.preempted
        t0 = time.perf_counter()
        results = await asyncio.gather(
            *[
                run_one(p, priorities[i % len(priorities)], admission)
                for i, p in enumerate(prompts)
            ]
        )
        dt = time.perf_counter() - t0
        done = [r for r in results if "tokens" in r]
        shed = [r for r in results if "shed" in r]
        ttfts = sorted(r["ttft"] for r in done if r["ttft"] is not None)
        return {
            "metric": f"overload_burst_{MODEL}_isl{isl}_osl{osl}_b{n}",
            "value": round(sum(r["tokens"] for r in done) / dt, 1),
            "unit": "goodput tok/s",
            "vs_baseline": _vs_roofline(
                sum(r["tokens"] for r in done) / dt, engine.params, slots
            ),
            "burst": n,
            "admitted": len(done),
            "shed": len(shed),
            "shed_rate": round(len(shed) / n, 3),
            "shed_429": sum(1 for r in shed if r["shed"] == 429),
            "shed_503": sum(1 for r in shed if r["shed"] == 503),
            "p99_ttft_s": round(ttfts[int(0.99 * (len(ttfts) - 1))], 3)
            if ttfts
            else None,
            "preemptions": engine.preempted - preempted0,
            "decode_window": engine.cfg.decode_window,
            "dispatch": _dispatch_stats(engine),
            "anatomy": _anatomy_stats(engine),
        }

    out = []
    # Warmup at the smallest level: compile prefill/decode variants so
    # the measured TTFTs reflect serving, not compilation.
    asyncio.run(burst(min(burst_levels)))
    for n in burst_levels:
        out.append(asyncio.run(burst(n)))
    engine.stop()
    return out


def run_spec_sweep(
    slots: int = 4,
    isl: int = 512,
    osl: int = 128,
    draft_lens: tuple[int, ...] = (0, 2, 4, 8),
) -> list[dict]:
    """Speculative decoding: tok/s + acceptance across draft lengths
    and workload repetitiveness (docs/speculative.md).

    Two workloads bound the drafter's operating range: ``repeat``
    prompts tile one random block (prefix-repetitive — the prompt-
    lookup n-gram match should hit, acceptance and tokens-per-dispatch
    should rise above 1), ``random`` prompts have no repeated structure
    (lookup mostly misses and the adaptive controller's miss backoff
    should keep the overhead near zero). ``draft_lens`` sweeps the
    pinned per-row draft length; 0 is the speculation-off baseline.
    Every JSON line carries the draft config and the measured
    acceptance, so the sim's service-time fit can learn
    tokens-per-dispatch from these lines."""
    import asyncio

    from dynamo_exp_tpu.engine import EngineConfig
    from dynamo_exp_tpu.protocols.common import BackendInput

    _enable_compile_cache()
    mcfg = _preset(MODEL)
    rs = np.random.RandomState(0)

    def engine_cfg(n_slots: int, spec_mode: str, draft: int) -> "EngineConfig":
        return EngineConfig(
            model=mcfg,
            max_decode_slots=n_slots,
            page_size=16,
            num_pages=n_slots * ((isl + osl) // 16 + 2) + 64,
            max_model_len=max(512, ((isl + osl) // 256 + 2) * 256),
            eos_token_ids=[],
            kv_dtype=_kv_dtype(),
            decode_window=8,
            spec_mode=spec_mode,
            spec_draft_len=max(draft, 1),
            spec_max_draft=max(draft, 1),
            # Pin the draft length: this sweep measures the length axis
            # itself, not the controller's trajectory.
            spec_adaptive=False,
        )

    def probe_block(n: int) -> list[int]:
        """The model's own greedy tail over a random prompt: a genuinely
        prefix-repetitive workload must repeat content the model
        actually continues (an arbitrary random block tiled into a
        prompt is repetitive to the *drafter* but not to the target's
        greedy trajectory, so acceptance would measure luck)."""
        eng = _build_engine(engine_cfg(1, "off", 0))

        async def gen(prompt):
            b = BackendInput(token_ids=prompt)
            b.stop_conditions.max_tokens = osl
            b.stop_conditions.ignore_eos = True
            stream = await eng.generate(b.to_dict())
            toks = []
            async for item in stream:
                toks.extend(item.get("token_ids", []))
            return toks

        tail = asyncio.run(
            gen(rs.randint(10, mcfg.vocab_size - 10, size=isl).tolist())
        )[-n:]
        eng.stop()
        return [int(t) for t in tail]

    def build_prompts(workload: str) -> list[list[int]]:
        if workload == "repeat":
            block = probe_block(16)
            return [block * (isl // 16) for _ in range(slots)]
        return [
            rs.randint(10, mcfg.vocab_size - 10, size=isl).tolist()
            for _ in range(slots)
        ]

    out = []
    for workload in ("repeat", "random"):
        # One fixed prompt set per workload: the sweep's axis is the
        # draft length, so every draft point (incl. the d0 baseline)
        # must serve the SAME prompts or the deltas mix in prompt
        # variation.
        workload_prompts = build_prompts(workload)
        for draft in draft_lens:
            cfg = engine_cfg(slots, "off" if draft == 0 else "ngram", draft)
            engine = _build_engine(cfg)

            async def run_one(prompt):
                b = BackendInput(token_ids=prompt)
                b.stop_conditions.max_tokens = osl
                b.stop_conditions.ignore_eos = True
                stream = await engine.generate(b.to_dict())
                n = 0
                async for item in stream:
                    n += len(item.get("token_ids", []))
                return n

            async def burst(batch):
                for _ in range(WARMUP_BURSTS):
                    await asyncio.gather(*[run_one(p) for p in batch])
                best = 0.0
                for _ in range(TIMED_BURSTS):
                    t0 = time.perf_counter()
                    results = await asyncio.gather(
                        *[run_one(p) for p in batch]
                    )
                    best = max(
                        best, sum(results) / (time.perf_counter() - t0)
                    )
                return best

            tok_s = asyncio.run(burst(workload_prompts))
            m = engine.metrics()
            drafted = m["spec_draft_tokens"]
            # Per-ROW basis: a batched verify dispatch over N rows is N
            # row participations; emitted / device-dispatches would
            # conflate batch occupancy with speculation speedup (the
            # sim fit divides per-row ITL by this number).
            dispatches = m["spec_row_dispatches"]
            out.append(
                {
                    "metric": f"spec_decode_{MODEL}_isl{isl}_osl{osl}"
                    f"_{workload}_d{draft}",
                    "value": round(tok_s, 1),
                    "unit": "tok/s",
                    "vs_baseline": _vs_roofline(
                        tok_s, engine.params, slots
                    ),
                    "workload": workload,
                    "spec": {
                        "mode": cfg.spec_mode,
                        "draft_len": draft,
                        "ngram": cfg.spec_ngram,
                    },
                    "draft_tokens": drafted,
                    "accepted_tokens": m["spec_accepted_tokens"],
                    "acceptance_rate": round(
                        m["spec_accepted_tokens"] / drafted, 4
                    )
                    if drafted
                    else None,
                    "tokens_per_dispatch": round(
                        m["spec_emitted_tokens"] / dispatches, 4
                    )
                    if dispatches
                    else None,
                    "decode_window": engine.cfg.decode_window,
                    "dispatch": _dispatch_stats(engine),
                    "anatomy": _anatomy_stats(engine),
                }
            )
            engine.stop()
    return out


def run_prefix_reuse(isl: int = 1024, osl: int = 16, concurrency: int = 8) -> dict:
    """TTFT with a warm shared prefix vs cold prompts.

    The reference's headline KV-reuse claims (BASELINE.md: 3x TTFT from
    KV-aware routing over cached prefixes, 40% from offload) rest on
    exactly this effect: a request whose prefix blocks are already in
    the pool skips their prefill. Here every request shares the first
    ~87% of the prompt; warm TTFT should approach the cost of
    prefilling only the distinct tail.
    """
    import asyncio

    from dynamo_exp_tpu.engine import EngineConfig
    from dynamo_exp_tpu.protocols.common import BackendInput

    _enable_compile_cache()
    mcfg = _preset(MODEL)
    cfg = EngineConfig(
        model=mcfg,
        max_decode_slots=concurrency,
        page_size=16,
        num_pages=concurrency * ((isl + osl) // 16 + 2) + 256,
        max_model_len=max(512, ((isl + osl) // 256 + 2) * 256),
        eos_token_ids=[],
        kv_dtype=_kv_dtype(),
        decode_window=8,
    )
    engine = _build_engine(cfg)
    rs = np.random.RandomState(0)
    shared = rs.randint(10, mcfg.vocab_size - 10, size=(isl * 7) // 8).tolist()
    tail = isl - len(shared)

    async def one(prompt):
        b = BackendInput(token_ids=prompt)
        b.stop_conditions.max_tokens = osl
        b.stop_conditions.ignore_eos = True
        t0 = time.perf_counter()
        stream = await engine.generate(b.to_dict())
        async for item in stream:
            if item.get("token_ids"):
                return time.perf_counter() - t0
        return None

    async def measure():
        # Cold: all-distinct prompts (after compile warmup on other shapes).
        warm_prompt = rs.randint(10, mcfg.vocab_size - 10, size=isl).tolist()
        await one(warm_prompt)  # compile
        cold = [
            await one(rs.randint(10, mcfg.vocab_size - 10, size=isl).tolist())
            for _ in range(concurrency)
        ]
        # Warm: seed the shared prefix once, then same-prefix requests.
        await one(shared + rs.randint(10, mcfg.vocab_size - 10, size=tail).tolist())
        warm = [
            await one(
                shared + rs.randint(10, mcfg.vocab_size - 10, size=tail).tolist()
            )
            for _ in range(concurrency)
        ]
        # Stop inside the loop: engine callbacks scheduled during the
        # last responses must land on a live loop, not a closed one.
        engine.stop()
        return cold, warm

    cold, warm = asyncio.run(measure())
    p50 = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
    return {
        "metric": f"prefix_reuse_ttft_{MODEL}_isl{isl}",
        "value": round(p50(cold) / p50(warm), 2),
        "unit": "x speedup",
        "vs_baseline": round((p50(cold) / p50(warm)) / 3.0, 4),  # ref: 3x
        "p50_ttft_cold_s": round(p50(cold), 3),
        "p50_ttft_warm_s": round(p50(warm), 3),
        "decode_window": engine.cfg.decode_window,
        "dispatch": _dispatch_stats(engine),
        "anatomy": _anatomy_stats(engine),
    }


def run_prefix_sweep(
    isl: int = 1024,
    osl: int = 32,
    concurrency: int = 8,
    ratios: tuple = (0.0, 0.5, 0.875),
) -> list:
    """Fleet-wide prefix sharing vs the private-copy baseline
    (docs/prefix_sharing.md) across a shared-prefix ratio axis.

    Each point fires one *concurrent* burst of ``concurrency`` requests
    whose prompts share the first ``ratio * isl`` tokens — the
    many-users-one-system-prompt shape — against a sharing engine and a
    ``prefix_sharing=False`` baseline, and reports HBM pages per request
    (resident-page high-water mark / requests), p50 TTFT, and the
    page-granular prefix-hit breakdown. Concurrent admission is the
    point: sharing must collapse pages even when every request arrives
    before the first one has prefilled (pending-fill attach).
    """
    import asyncio

    from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
    from dynamo_exp_tpu.protocols.common import BackendInput

    _enable_compile_cache()
    mcfg = _preset(MODEL)
    ps = 16

    def build_engine(sharing: bool) -> TPUEngine:
        cfg = EngineConfig(
            model=mcfg,
            max_decode_slots=concurrency,
            page_size=ps,
            # Sized for the PRIVATE worst case so the baseline arm
            # measures pages, not preemption thrash.
            num_pages=concurrency * ((isl + osl) // ps + 2) + 64,
            max_model_len=max(512, ((isl + osl) // 256 + 2) * 256),
            eos_token_ids=[],
            kv_dtype=_kv_dtype(),
            decode_window=8,
            prefix_sharing=sharing,
        )
        return _build_engine(cfg)

    async def run_one(engine, prompt):
        b = BackendInput(token_ids=prompt)
        b.stop_conditions.max_tokens = osl
        b.stop_conditions.ignore_eos = True
        t0 = time.perf_counter()
        stream = await engine.generate(b.to_dict())
        ttft = None
        async for item in stream:
            if item.get("token_ids") and ttft is None:
                ttft = time.perf_counter() - t0
        return ttft

    async def burst(engine, prompts):
        return await asyncio.gather(*[run_one(engine, p) for p in prompts])

    def arm(engine, warm_prompts, prompts) -> dict:
        # Per-ratio warm burst: a shared-prefix burst exercises suffix-
        # length prefill buckets the full-prompt warmup never compiled —
        # TTFT must measure steady state, not variant compiles. The warm
        # burst uses its own prefix, so the measured burst's hit counts
        # stay cold/honest.
        asyncio.run(burst(engine, warm_prompts))
        # High-water marks measured per burst: rebase to the quiesced
        # pool (previous bursts' pages are parked, not active/shared).
        engine.kv.peak_active_pages = engine.kv.active_pages
        engine.kv.peak_shared_pages = engine.kv.live_shared
        hits0 = dict(engine.kv.prefix_hits)
        cow0 = engine.kv.cow_copies
        ttfts = sorted(
            t for t in asyncio.run(burst(engine, prompts)) if t is not None
        )
        m = engine.metrics()
        return {
            "pages_per_request": round(
                engine.kv.peak_active_pages / max(len(prompts), 1), 2
            ),
            "p50_ttft_s": round(ttfts[len(ttfts) // 2], 3),
            "prefix_hits": {
                k: m[f"kv_prefix_hits_{k}"] - hits0[k]
                for k in ("shared", "restore", "miss")
            },
            "cow_copies": m["kv_cow_copies"] - cow0,
            "shared_pages_peak": engine.kv.peak_shared_pages,
        }

    rs = np.random.RandomState(0)
    shared_eng = build_engine(True)
    private_eng = build_engine(False)
    out = []
    # Compile warmup on both arms (distinct prompts: no sharing yet).
    warm = [
        rs.randint(10, mcfg.vocab_size - 10, size=isl).tolist()
        for _ in range(concurrency)
    ]
    for eng in (shared_eng, private_eng):
        for _ in range(WARMUP_BURSTS):
            asyncio.run(burst(eng, warm))
    for ratio in ratios:
        prefix_len = int(isl * ratio) // ps * ps

        def ratio_prompts() -> list:
            prefix = rs.randint(
                10, mcfg.vocab_size - 10, size=prefix_len
            ).tolist()
            return [
                prefix
                + rs.randint(
                    10, mcfg.vocab_size - 10, size=isl - prefix_len
                ).tolist()
                for _ in range(concurrency)
            ]

        warm_prompts, prompts = ratio_prompts(), ratio_prompts()
        shared = arm(shared_eng, warm_prompts, prompts)
        private = arm(private_eng, warm_prompts, prompts)
        out.append(
            {
                "metric": (
                    f"prefix_sweep_{MODEL}_isl{isl}_c{concurrency}"
                    f"_r{ratio}"
                ),
                "value": shared["pages_per_request"],
                "unit": "pages/request",
                "shared_prefix_ratio": ratio,
                "vs_baseline": round(
                    shared["pages_per_request"]
                    / max(private["pages_per_request"], 1e-9),
                    4,
                ),
                "shared": shared,
                "private": private,
                "decode_window": shared_eng.cfg.decode_window,
                "dispatch": _dispatch_stats(shared_eng),
                "anatomy": _anatomy_stats(shared_eng),
            }
        )
    shared_eng.stop()
    private_eng.stop()
    return out


def run_coldstart_sweep(
    isl: int = 512, osl: int = 32, concurrency: int = 4
) -> list[dict]:
    """Cold vs warm boot: what an autoscaled instance pays between
    "worker add" and serving (docs/aot.md "Coldstart study").

    Three phases against one persistent compilation cache directory:

    1. **cold** — a fresh engine with an *empty* cache serves the probe
       burst; every variant compiles inline on the serving path, so its
       first-token and first-burst TTFTs carry the compile stalls (this
       also populates the cache, like the first instance of a fleet).
    2. **populate** — ``aot_compile`` fills the remainder of the
       lattice offline (the ``llmctl aot compile`` deployment step;
       untimed).
    3. **warm** — a fresh engine prewarns from the populated cache
       before accepting traffic, then serves the identical burst with
       zero compile misses.

    Each arm's line reports the components separately — ``boot_s``
    (engine build; weights are shared across arms, checkpoint load is
    arm-invariant), ``prewarm_s``, ``first_token_s`` (serving start →
    first emitted token), ``first_burst_ttft_p50_s``, and
    ``steady_ttft_p50_s`` — plus ``provision_s`` (= boot + prewarm +
    first token), which is what ``sim/fit.py`` feeds
    ``planner_hints()`` → ``SloTargets.provision_s``. The headline
    ``value`` is ``provision_s``; the summary line carries the
    cold/warm ratios. On XLA:CPU compiles are cheap and the ratios are
    modest; on the chip the ratio is not measured yet (the ``platform``
    tag keeps the two regimes apart)."""
    import asyncio

    import jax

    from dynamo_exp_tpu.aot import (
        aot_compile,
        fresh_cache_dir,
        manifest_for_engine,
    )
    from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
    from dynamo_exp_tpu.models.llama import init_params
    from dynamo_exp_tpu.protocols.common import BackendInput, SamplingOptions

    cache_dir = fresh_cache_dir("coldstart")
    mcfg = _preset(MODEL)

    def cfg() -> EngineConfig:
        return EngineConfig(
            model=mcfg,
            max_decode_slots=concurrency,
            page_size=16,
            num_pages=concurrency * ((isl + osl) // 16 + 2) + 64,
            max_model_len=max(512, ((isl + osl) // 256 + 2) * 256),
            eos_token_ids=[],
            kv_dtype=_kv_dtype(),
            decode_window=8,
        )

    # Checkpoint load is arm-invariant (and not what AOT optimizes):
    # share one weight init so the arms differ only in compile work.
    params = init_params(jax.random.PRNGKey(0), mcfg)
    jax.block_until_ready(params)
    rs = np.random.RandomState(0)

    def prompts() -> list[list[int]]:
        return [
            rs.randint(10, mcfg.vocab_size - 10, size=isl).tolist()
            for _ in range(concurrency)
        ]

    async def burst(engine, batch) -> tuple[float | None, list[float]]:
        """Serve one mixed burst (alternating greedy / seeded rows);
        returns (wall time of the burst's FIRST emitted token, all
        per-request TTFTs)."""
        first: list[float] = []
        ttfts: list[float] = []

        async def one(i: int, prompt):
            b = BackendInput(token_ids=prompt)
            b.stop_conditions.max_tokens = osl
            b.stop_conditions.ignore_eos = True
            if i % 2:
                b.sampling_options = SamplingOptions(
                    seed=i, temperature=0.8
                )
            t0 = time.perf_counter()
            stream = await engine.generate(b.to_dict())
            async for item in stream:
                if item.get("token_ids"):
                    now = time.perf_counter()
                    first.append(now)
                    ttfts.append(now - t0)
                    break
            async for _ in stream:
                pass

        await asyncio.gather(*[one(i, p) for i, p in enumerate(batch)])
        return (min(first) if first else None), sorted(ttfts)

    def arm(prewarmed: bool) -> dict:
        t0 = time.perf_counter()
        engine = TPUEngine(cfg(), params=params, seed=0)
        manifest = manifest_for_engine(engine)
        boot_s = time.perf_counter() - t0
        prewarm_s = 0.0
        if prewarmed:
            report = engine.prewarm(manifest)
            prewarm_s = report.seconds
        engine.start()
        serving_at = time.perf_counter()
        first_at, ttfts = asyncio.run(burst(engine, prompts()))
        first_token_s = (
            first_at - serving_at if first_at is not None else None
        )
        _, steady = asyncio.run(burst(engine, prompts()))
        m = engine.metrics()
        disp = m["dispatch"]["ragged"]
        provision_s = boot_s + prewarm_s + (first_token_s or 0.0)
        point = {
            "metric": (
                f"coldstart_{MODEL}_isl{isl}_osl{osl}_c{concurrency}_"
                f"{'warm' if prewarmed else 'cold'}"
            ),
            # Headline: provisioned -> first token (the serving-path
            # stall AOT removes). The full worker-add -> first-token
            # delay (boot + prewarm + first token) rides as
            # ``provision_s`` — the sample sim/fit.py feeds the
            # planner, warm and cold distinguished by ``prewarmed``.
            "value": round(first_token_s, 3)
            if first_token_s is not None
            else None,
            "unit": "s provisioned-to-first-token",
            "provision_s": round(provision_s, 3),
            "boot_s": round(boot_s, 3),
            "prewarm_s": round(prewarm_s, 3),
            "first_token_s": round(first_token_s, 3)
            if first_token_s is not None
            else None,
            "first_burst_ttft_p50_s": round(
                ttfts[len(ttfts) // 2], 3
            )
            if ttfts
            else None,
            "steady_ttft_p50_s": round(steady[len(steady) // 2], 3)
            if steady
            else None,
            "prewarmed": prewarmed,
            "manifest_hash": manifest.hash(),
            "prewarmed_variants": m["prewarmed_variants"],
            "compiled_ragged_variants": m["compiled_ragged_variants"],
            "ragged_compile_misses": disp["compile_misses"],
            "ragged_compile_total_s": disp["compile_total_s"],
            "decode_window": engine.cfg.decode_window,
            "dispatch": _dispatch_stats(engine),
            "anatomy": _anatomy_stats(engine),
        }
        engine.stop()
        return point

    cold = arm(False)
    # Deployment's offline populate step (llmctl aot compile): fill the
    # lattice entries cold traffic never walked. Untimed; the engine
    # (and its full KV pool) is dropped before the warm arm boots so
    # the warm measurement doesn't run under doubled HBM residency.
    populate = TPUEngine(cfg(), params=params, seed=0)
    aot_compile(populate, cache_dir=cache_dir)
    del populate
    warm = arm(True)

    def ratio(a, b):
        return round(a / b, 2) if a and b else None

    summary = {
        "metric": f"coldstart_{MODEL}_isl{isl}_osl{osl}_c{concurrency}"
        "_speedup",
        "value": ratio(cold["first_token_s"], warm["first_token_s"]),
        "unit": "x provisioned-to-first-token",
        "first_burst_ttft_speedup": ratio(
            cold["first_burst_ttft_p50_s"], warm["first_burst_ttft_p50_s"]
        ),
        "full_provision_speedup": ratio(
            cold["provision_s"], warm["provision_s"]
        ),
        "cold_provision_s": cold["provision_s"],
        "warm_provision_s": warm["provision_s"],
        "prewarmed": True,
        "manifest_hash": warm["manifest_hash"],
        "compile_cache_dir": cache_dir,
    }
    return [cold, warm, summary]


def run_restart_sweep(
    isl: int = 512, osl: int = 32, concurrency: int = 4
) -> list[dict]:
    """Cold-boot vs warm-cache restart TTFT (docs/fault_tolerance.md
    "Durable KV & corruption containment").

    Three phases against one durable G3 store directory:

    1. **seed** — an engine with the store serves a shared-prefix
       burst, then a churn burst large enough to evict the parked
       prefix blocks into the host tier; ``stop()`` drains the host
       tier through the G3 writer (the crash-consistent demotion
       path), leaving the prefix on disk.
    2. **cold** — a fresh engine over an *empty* store serves the
       identical shared-prefix probe: nothing to adopt, the full
       prefix re-prefills (the restart-without-durability baseline).
    3. **warm** — a fresh engine over the seeded store ``boot_scan``s,
       re-adopts the surviving pages, and serves the same probe: the
       shared prefix re-attaches from G3 (checksum-verified) and only
       the per-request suffix prefills.

    Both arms run the same compile warmup first, so the TTFT delta is
    the shared-prefix prefill cost the durable tier removes — the
    restart-recovery headline. Lines carry ``prewarmed`` (store, not
    compile, prewarming here) and the per-arm G3 counters
    (``kv_prefix_hits_persist``, ``kv_store_adopted``) as proof the
    warm arm actually restored rather than re-prefilled."""
    import asyncio
    import shutil
    import tempfile

    import jax

    from dynamo_exp_tpu.aot import manifest_for_engine
    from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
    from dynamo_exp_tpu.models.llama import init_params
    from dynamo_exp_tpu.protocols.common import BackendInput

    _enable_compile_cache()
    mcfg = _preset(MODEL)
    ps = 16
    prefix_len = (isl // 2) // ps * ps
    num_pages = concurrency * ((isl + osl) // ps + 2) + 8

    def cfg(store_dir: str) -> EngineConfig:
        return EngineConfig(
            model=mcfg,
            max_decode_slots=concurrency,
            page_size=ps,
            # Tight pool: the seed arm's churn burst must evict the
            # parked prefix into the host tier for stop() to drain.
            num_pages=num_pages,
            max_model_len=max(512, ((isl + osl) // 256 + 2) * 256),
            eos_token_ids=[],
            kv_dtype=_kv_dtype(),
            decode_window=8,
            prefix_sharing=True,
            host_cache_pages=num_pages * 4,
            kv_store_dir=store_dir,
        )

    params = init_params(jax.random.PRNGKey(0), mcfg)
    jax.block_until_ready(params)
    rs = np.random.RandomState(0)

    def distinct(n: int) -> list[list[int]]:
        return [
            rs.randint(10, mcfg.vocab_size - 10, size=isl).tolist()
            for _ in range(n)
        ]

    # One fixed prompt set across all three phases: identical tokens
    # mean identical chained block hashes, so the warm arm's G3 match
    # is exactly the seed arm's demoted prefix.
    warm_prompts = distinct(concurrency)

    def shared_burst() -> list[list[int]]:
        p = rs.randint(10, mcfg.vocab_size - 10, size=prefix_len).tolist()
        return [
            p
            + rs.randint(
                10, mcfg.vocab_size - 10, size=isl - prefix_len
            ).tolist()
            for _ in range(concurrency)
        ]

    # A second, never-stored shared prefix for warmup: the probe's
    # suffix-length prefill bucket must compile during warmup in BOTH
    # arms, or the warm arm's G3-shortened prefill pays a variant
    # compile the cold arm's full-prompt path never hits.
    probe_prompts, suffix_warm_prompts = shared_burst(), shared_burst()
    churn_prompts = distinct(2 * concurrency)

    async def run_one(engine, prompt):
        b = BackendInput(token_ids=prompt)
        b.stop_conditions.max_tokens = osl
        b.stop_conditions.ignore_eos = True
        t0 = time.perf_counter()
        stream = await engine.generate(b.to_dict())
        ttft = None
        async for item in stream:
            if item.get("token_ids") and ttft is None:
                ttft = time.perf_counter() - t0
        return ttft

    async def burst(engine, prompts):
        return await asyncio.gather(*[run_one(engine, p) for p in prompts])

    seeded_store = tempfile.mkdtemp(prefix="dynamo_restart_g3_")
    empty_store = tempfile.mkdtemp(prefix="dynamo_restart_empty_")

    # Phase 1: seed the store, then churn the prefix off-device and
    # drain it to disk through the stop() path.
    engine = TPUEngine(cfg(seeded_store), params=params, seed=0)
    manifest = manifest_for_engine(engine)
    engine.start()
    asyncio.run(burst(engine, warm_prompts))
    asyncio.run(burst(engine, probe_prompts))
    for i in range(0, len(churn_prompts), concurrency):
        asyncio.run(burst(engine, churn_prompts[i : i + concurrency]))
    engine.stop()
    seeded_pages = engine.g3_store.resident if engine.g3_store else 0

    def arm(store_dir: str, prewarmed: bool) -> dict:
        engine = TPUEngine(cfg(store_dir), params=params, seed=0)
        adopted = engine.g3_store.adopted if engine.g3_store else 0
        engine.start()
        asyncio.run(burst(engine, warm_prompts))  # full-prompt warmup
        asyncio.run(burst(engine, suffix_warm_prompts))  # suffix bucket
        ttfts = sorted(
            t
            for t in asyncio.run(burst(engine, probe_prompts))
            if t is not None
        )
        m = engine.metrics()
        point = {
            "metric": (
                f"restart_{MODEL}_isl{isl}_osl{osl}_c{concurrency}_"
                f"{'warm' if prewarmed else 'cold'}"
            ),
            "value": round(ttfts[len(ttfts) // 2], 3) if ttfts else None,
            "unit": "s probe-burst ttft p50",
            "ttft_p50_s": round(ttfts[len(ttfts) // 2], 3)
            if ttfts
            else None,
            "ttft_max_s": round(ttfts[-1], 3) if ttfts else None,
            "prefix_tokens": prefix_len,
            "prewarmed": prewarmed,
            "manifest_hash": manifest.hash(),
            "kv_store_adopted": adopted,
            "kv_prefix_hits_persist": m.get("kv_prefix_hits_persist", 0),
            "kv_store_checksum_failures": m.get(
                "kv_store_checksum_failures", 0
            ),
            "dispatch": _dispatch_stats(engine),
            "anatomy": _anatomy_stats(engine),
        }
        engine.stop()
        return point

    cold = arm(empty_store, False)
    warm = arm(seeded_store, True)

    def ratio(a, b):
        return round(a / b, 2) if a and b else None

    summary = {
        "metric": f"restart_{MODEL}_isl{isl}_osl{osl}_c{concurrency}"
        "_speedup",
        "value": ratio(cold["ttft_p50_s"], warm["ttft_p50_s"]),
        "unit": "x cold/warm probe ttft p50",
        "seeded_store_pages": seeded_pages,
        "warm_adopted_pages": warm["kv_store_adopted"],
        "warm_persist_hits": warm["kv_prefix_hits_persist"],
        "cold_ttft_p50_s": cold["ttft_p50_s"],
        "warm_ttft_p50_s": warm["ttft_p50_s"],
        "prewarmed": True,
        "manifest_hash": manifest.hash(),
    }
    shutil.rmtree(empty_store, ignore_errors=True)
    shutil.rmtree(seeded_store, ignore_errors=True)
    return [cold, warm, summary]


def run_reclaim_sweep(
    seed: int = 11,
    spot_fraction: float = 0.5,
    grace_s: float = 4.0,
    duration_s: float = 240.0,
    instances: int = 4,
    reclaim_rates: tuple[float, ...] = (0.0, 2.0, 6.0, 12.0),
) -> list[dict]:
    """Spot-reclamation economics: goodput, migrated-vs-failover split,
    p99 TTFT, and billed chip-seconds per reclaim rate
    (docs/fault_tolerance.md "Spot reclamation & live migration").

    Sim-driven (no chip): a fixed fleet with ``spot_fraction`` of its
    instances on spot capacity serves one deterministic ramp while
    reclaim notices arrive at each swept rate, each with ``grace_s`` of
    warning. Every notice runs the REAL ``runtime.reclaim.plan_triage``
    deadline planner, so the migrated fraction per line is the live
    triage policy's hit rate at that grace window, not a modeling knob.

    The first line is the all-on-demand control (``spot_fraction=0``,
    no reclaims, full price); spot lines report ``vs_baseline`` as
    goodput relative to it. The headline is the pair (``vs_baseline``,
    ``goodput_per_billed_chip_s``): a healthy triage plane holds
    goodput near the control while billed chip-seconds shrink by the
    spot discount — and rising ``reclaim_failovers`` with falling
    ``migrated_fraction`` at high rates shows exactly where the grace
    deadline stops covering the transfer bill."""
    from dynamo_exp_tpu.sim.cluster import ClusterSim, SimConfig
    from dynamo_exp_tpu.sim.workload import ramp_workload

    def one(rate: float, spot: float, label: str) -> dict:
        cfg = SimConfig(
            seed=seed,
            slots_per_instance=8,
            pages_per_instance=144,
            page_size=16,
            max_inflight=16,
            shed_watermark=12,
            admission_per_instance=True,
            initial_instances=instances,
            provision_s=5.0,
            spot_fraction=spot,
            reclaim_rate_per_min=rate,
            reclaim_grace_s=grace_s,
            record_events=False,
        )
        wl = ramp_workload(
            seed,
            duration_s=duration_s,
            rps_start=2.0,
            rps_end=8.0,
            prompt_len=(64, 256),
            max_tokens=(16, 64),
        )
        rep = ClusterSim(cfg, wl).run()
        moved = rep.reclaim_migrated + rep.reclaim_failovers
        return {
            "metric": f"reclaim_sweep_spot{int(spot * 100)}"
            f"_g{grace_s:g}_{label}",
            "value": rep.goodput_tok_s,
            "unit": "goodput tok/s",
            "reclaim_rate_per_min": rate,
            "spot_fraction": spot,
            "grace_s": grace_s,
            "reclaims": rep.reclaims,
            "reclaim_migrated": rep.reclaim_migrated,
            "reclaim_failovers": rep.reclaim_failovers,
            "reclaim_migrated_pages": rep.reclaim_migrated_pages,
            "migrated_fraction": round(rep.reclaim_migrated / moved, 4)
            if moved
            else None,
            "ttft_p99_s": rep.ttft_p99_s,
            "submitted": rep.submitted,
            "completed": rep.completed,
            "preemptions": rep.preemptions,
            "chip_seconds": rep.chip_seconds,
            "billed_chip_seconds": rep.billed_chip_seconds,
            "goodput_per_billed_chip_s": round(
                rep.completed_tokens / rep.billed_chip_seconds, 2
            )
            if rep.billed_chip_seconds
            else None,
        }

    base = one(0.0, 0.0, "ondemand")
    base["vs_baseline"] = 1.0
    out = [base]
    for rate in reclaim_rates:
        point = one(rate, spot_fraction, f"r{rate:g}")
        point["vs_baseline"] = (
            round(point["value"] / base["value"], 4)
            if base["value"]
            else None
        )
        out.append(point)
    return out


def _device() -> dict:
    """The device this process measures on, as JAX reports it. One
    process owns the chip, so it is read here, in-process. No TPU is an
    error — a measurement never falls back — unless the CPU was asked
    for by name (``JAX_PLATFORMS=cpu``)."""
    import os

    import jax

    dev = jax.devices()[0]
    cpu_requested = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if dev.platform != "tpu" and not cpu_requested:
        raise SystemExit(
            f"bench: no TPU (jax reports platform={dev.platform!r}). Set "
            "JAX_PLATFORMS=cpu to ask for a CPU run; it prints no device "
            "metric."
        )
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
    }


def main() -> None:
    global MODEL
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--sweep",
        action="store_true",
        help="reference-shape sweep (ISL 3000 / OSL 150, concurrency 1..32)",
    )
    ap.add_argument(
        "--prefix-reuse",
        action="store_true",
        help="warm-prefix vs cold TTFT (the KV-reuse headline claim)",
    )
    ap.add_argument(
        "--occupancy-sweep",
        action="store_true",
        help="tok/s at 1/2/4/8 active sequences of 8 slots (compacted "
        "decode proportionality curve) plus a mixed prefill+decode "
        "axis: late prompts injected mid-decode, reporting late-join "
        "TTFT and compiled-ragged-variant counts per line",
    )
    ap.add_argument(
        "--overload-sweep",
        action="store_true",
        help="goodput / shed rate / p99 TTFT / preemption count per "
        "burst level against a pressure-sized pool (graceful "
        "degradation curve)",
    )
    ap.add_argument(
        "--spec-sweep",
        action="store_true",
        help="speculative decoding tok/s + acceptance across draft "
        "lengths {0,2,4,8} on prefix-repetitive vs random workloads",
    )
    ap.add_argument(
        "--prefix-sweep",
        action="store_true",
        help="HBM pages/request, TTFT, and prefix-hit breakdown across "
        "a shared-prefix ratio axis, sharing vs private-copy baseline",
    )
    ap.add_argument(
        "--coldstart-sweep",
        action="store_true",
        help="cold vs AOT-warm boot: provision-to-first-token, "
        "first-burst TTFT and compile-stall attribution per arm "
        "against one persistent compile cache (docs/aot.md)",
    )
    ap.add_argument(
        "--restart-sweep",
        action="store_true",
        help="cold-boot vs durable-G3 warm-cache restart: shared-prefix "
        "probe TTFT per arm against one seeded store directory, with "
        "adopted-page / persist-hit proof (docs/fault_tolerance.md)",
    )
    ap.add_argument(
        "--reclaim-sweep",
        action="store_true",
        help="spot-reclamation economics (sim-driven): goodput, "
        "migrated-vs-failover split, p99 TTFT, and billed "
        "chip-seconds per reclaim rate vs an all-on-demand control",
    )
    ap.add_argument(
        "--prewarm",
        action="store_true",
        help="prewarm every bench engine from the compile lattice "
        "before measuring (lines are tagged prewarmed=true)",
    )
    ap.add_argument(
        "--model",
        default=None,
        help=f"preset name (default {MODEL}; {CPU_MODEL} on a CPU run)",
    )
    # Default-point shape overrides (smoke tests run a tiny point; the
    # metric name carries the shape, so overridden runs stay labeled).
    # None = not given: the default resolves per platform, but an
    # explicit flag always wins, even on a CPU run.
    ap.add_argument("--isl", type=int, default=None)
    ap.add_argument("--osl", type=int, default=None)
    ap.add_argument("--concurrency", type=int, default=None)
    args = ap.parse_args()
    DEVICE.update(_device())
    platform = DEVICE["platform"]
    if platform == "cpu":
        global CPU_RUN, WARMUP_BURSTS, TIMED_BURSTS
        CPU_RUN = True
        WARMUP_BURSTS = TIMED_BURSTS = 1
    MODEL = args.model or (CPU_MODEL if platform == "cpu" else MODEL)
    if args.isl is None:
        args.isl = CPU_ISL if platform == "cpu" else ISL
    if args.osl is None:
        args.osl = CPU_OSL if platform == "cpu" else OSL
    if args.concurrency is None:
        args.concurrency = CPU_CONCURRENCY if platform == "cpu" else CONCURRENCY

    if args.prewarm:
        global PREWARM
        PREWARM = True

    def emit(point: dict) -> None:
        # Every line carries the warm/cold tag + manifest hash (set by
        # _build_engine; coldstart lines carry their own per-arm
        # values, which win) so sim/fit.py can split provision samples,
        # and names the device. ``vs_baseline`` is a statement about
        # the chip: a device that is not in the peak table gets none.
        if DEVICE["device_kind"] not in HBM_PEAK_GBPS:
            point = {k: v for k, v in point.items() if k != "vs_baseline"}
        print(json.dumps(dict(LINE_TAGS) | point | DEVICE), flush=True)

    cpu = platform == "cpu"
    if args.reclaim_sweep:
        # Sim-driven: numbers are host-independent, so lines carry
        # platform="sim" — chip and CPU captures of this
        # sweep stay comparable in `llmctl bench compare` instead of
        # being skipped as a platform mismatch.
        for point in run_reclaim_sweep(**(CPU_RECLAIM_KW if cpu else {})):
            print(
                json.dumps(dict(LINE_TAGS) | point | {"platform": "sim"}),
                flush=True,
            )
        return
    if args.coldstart_sweep:
        for point in run_coldstart_sweep(**(CPU_COLDSTART_KW if cpu else {})):
            emit(point)
        return
    if args.restart_sweep:
        for point in run_restart_sweep(**(CPU_RESTART_KW if cpu else {})):
            emit(point)
        return
    if args.sweep:
        s_isl = CPU_SWEEP_ISL if cpu else SWEEP_ISL
        s_osl = CPU_SWEEP_OSL if cpu else SWEEP_OSL
        for c in CPU_SWEEP_CONCURRENCY if cpu else SWEEP_CONCURRENCY:
            emit(run_point(s_isl, s_osl, c))
    elif args.occupancy_sweep:
        kw = (
            dict(CPU_SWEEP_KW, pressure_multiples=CPU_PRESSURE_MULTIPLES)
            if cpu
            else {}
        )
        for point in run_occupancy_sweep(**kw):
            emit(point)
    elif args.overload_sweep:
        kw = (
            dict(CPU_SWEEP_KW, burst_levels=CPU_OVERLOAD_BURSTS) if cpu else {}
        )
        for point in run_overload_sweep(**kw):
            emit(point)
    elif args.spec_sweep:
        for point in run_spec_sweep(**(CPU_SPEC_KW if cpu else {})):
            emit(point)
    elif args.prefix_sweep:
        for point in run_prefix_sweep(**(CPU_PREFIX_SWEEP_KW if cpu else {})):
            emit(point)
    elif args.prefix_reuse:
        emit(run_prefix_reuse(**(CPU_PREFIX_KW if cpu else {})))
    else:
        emit(run_point(args.isl, args.osl, args.concurrency))


if __name__ == "__main__":
    main()
