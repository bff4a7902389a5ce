"""Sim-vs-live validation of tuned candidates (docs/tuning.md).

Before a recommendation ships, the top-K candidates re-run against the
live tiny harness — the same PR 6 calibration bridge the sim suite
uses (a TINY-model engine whose shape mirrors the SimConfig under
test, fronted by the real AdmissionController) — and the live ranking
must agree with the sim ranking (Kendall tau + top-1). A candidate
that only wins in the model is a modeling artifact, not a tuning.

This module necessarily reads the wall clock (it measures a real engine):
``_wall_clock``, the one read, inline-waived for the determinism zone the
rest of ``tune/`` lives in.
"""

from __future__ import annotations

import time

from .search import SearchSettings, TuneTarget, evaluate
from . import space


def kendall_tau(a: list[float], b: list[float]) -> float:
    """Rank agreement between two score lists over the same candidates
    (b[i] scores the same candidate as a[i]); 1.0 = identical order,
    -1.0 = reversed. Ties count as discordant half-weight-free (they
    simply don't contribute)."""
    n = len(a)
    if n < 2:
        return 1.0
    conc = disc = 0
    for i in range(n):
        for j in range(i + 1, n):
            da = (a[i] - a[j])
            db = (b[i] - b[j])
            prod = da * db
            if prod > 0:
                conc += 1
            elif prod < 0:
                disc += 1
    total = n * (n - 1) / 2
    return round((conc - disc) / total, 4)


def _wall_clock() -> float:
    return time.monotonic()  # dynlint: determinism(live validation wall-clock measurement)


def harness_workload(
    target: TuneTarget,
    seed: int,
    n: int = 10,
    rate_rps: float = 8.0,
    max_prompt: int = 96,
    max_new: int = 24,
):
    """The shared validation workload: the target replayed at tiny-
    harness scale (prompt/output lengths clamped to the harness model
    length) — BOTH sides consume exactly this list, so ranking
    differences come from the configs, never the workload."""
    from dataclasses import replace

    reqs = TuneTarget(
        kind=target.kind,
        fingerprint=target.fingerprint,
        name=target.name or "burst",
        requests=n,
        rate_rps=rate_rps,
        duration_s=max(n / rate_rps, 1.0),
    ).workload(seed)
    return [
        replace(
            r,
            prompt_len=min(r.prompt_len, max_prompt),
            max_tokens=min(max(r.max_tokens, 2), max_new),
            prefix_group=-1,
            prefix_len=0,
        )
        for r in reqs
    ]


async def measure_live(
    overrides: dict,
    workload,
    harness: dict,
    slo_ttft_s: float = 30.0,
    slo_itl_s: float = 2.0,
    clock=None,
) -> dict:
    """Run one candidate's live-applicable engine knobs on a tiny real
    engine against the shared workload; score with the same composite
    shape the sim objective uses (1-instance chip-seconds = duration).

    The ``max_inflight`` knob (the edge admission bound) applies here
    too — it is a live deployment surface, just not an ``EngineConfig``
    field: it sizes the AdmissionController fronting the engine, so a
    candidate that sheds in the sim sheds on the harness for the same
    reason. The SLO gates default to harness scale (not the production
    2s/0.2s constants): the tiny engine runs on whatever host CI
    provides, and production-scale gates would make the compliance
    fractions encode host speed rather than config quality — the
    ranking, not the absolute score, is the validated signal.
    ``clock``: what the durations are read from, the wall clock unless
    a test passes its own."""
    import asyncio

    from ..engine import EngineConfig, TPUEngine
    from ..http.admission import AdmissionController, RequestShedError
    from ..models import TINY
    from ..parallel import single_device_mesh
    from ..protocols.common import BackendInput, SamplingOptions

    clock = clock or _wall_clock
    kwargs = dict(harness)
    kwargs.update(space.engine_kwargs_from_overrides(overrides))
    cfg = EngineConfig(
        model=TINY, eos_token_ids=[], kv_dtype="float32", **kwargs
    )
    adm = AdmissionController(
        max_inflight=int(
            overrides.get("max_inflight") or max(len(workload), 4)
        )
    )
    engine = TPUEngine(cfg, mesh=single_device_mesh(), seed=0)
    engine.start()
    try:
        results: list[dict] = []

        async def one(req, admit: bool = True, record: bool = True) -> None:
            if admit:
                await asyncio.sleep(req.arrival_s)
                try:
                    adm.acquire(req.priority)
                except RequestShedError:
                    results.append({"shed": True})
                    return
            try:
                bi = BackendInput(
                    token_ids=[
                        (17 * req.index + k) % 211 + 3
                        for k in range(req.prompt_len)
                    ],
                    priority=req.priority,
                )
                bi.stop_conditions.max_tokens = req.max_tokens
                bi.stop_conditions.ignore_eos = True
                bi.sampling_options = SamplingOptions(
                    temperature=0.9, seed=1000 + req.index
                )
                start = clock()
                first = last = None
                tokens = 0
                stream = await engine.generate(bi.to_dict())
                async for item in stream:
                    now = clock()
                    if item.get("token_ids"):
                        tokens += len(item["token_ids"])
                        if first is None:
                            first = now
                        last = now
                itl = (
                    (last - first) / (tokens - 1)
                    if first is not None and last is not None and tokens > 1
                    else 0.0
                )
                if record:
                    results.append({
                        "shed": False,
                        "tokens": tokens,
                        "ttft_s": (first - start) if first is not None else 0.0,
                        "itl_s": itl,
                    })
            finally:
                if admit:
                    adm.release()

        # Warm pass: the whole workload shape-for-shape, no admission,
        # no arrival spread, nothing recorded. Every prefill bucket and
        # the decode graph compile HERE, identically for every
        # candidate — otherwise whichever candidate the host still owes
        # a compile donates that stall to its measured duration and the
        # ranking encodes cache state, not config quality.
        await asyncio.gather(
            *[one(r, admit=False, record=False) for r in workload]
        )

        t0 = clock()
        await asyncio.gather(*[one(r) for r in workload])
        duration = max(clock() - t0, 1e-6)
    finally:
        engine.stop()

    done = [r for r in results if not r["shed"]]
    completed = max(len(done), 1)
    ttft_ok = sum(1 for r in done if r["ttft_s"] <= slo_ttft_s) / completed
    itl_ok = sum(1 for r in done if r["itl_s"] <= slo_itl_s) / completed
    tokens = sum(r["tokens"] for r in done)
    goodput_per_chip_s = tokens / duration
    return {
        "score": round(goodput_per_chip_s * ttft_ok * itl_ok, 6),
        "goodput_per_chip_s": round(goodput_per_chip_s, 4),
        "ttft_compliance": round(ttft_ok, 4),
        "itl_compliance": round(itl_ok, 4),
        "completed": len(done),
        "shed": sum(1 for r in results if r["shed"]),
        "chip_seconds": round(duration, 3),
    }


async def validate_candidates(
    candidates: list[dict],
    target: TuneTarget,
    seed: int,
    harness: dict | None = None,
    n: int = 10,
    slo_ttft_s: float = 30.0,
    slo_itl_s: float = 2.0,
    clock=None,
) -> dict:
    """Rank the candidates in the sim AND on the live tiny harness over
    one shared clamped workload; report both rankings plus Kendall tau
    and top-1 agreement. ``harness`` is the engine-shape envelope
    (defaults mirror the PR 6 pressure harness, roomier pool).
    ``slo_ttft_s``/``slo_itl_s`` gate the live composite (harness-scale
    defaults; pass large values to rank on goodput alone — a cold-start
    compile stall on a slow host can blow a single inter-token gap past
    any fixed gate and flip a ranking the throughput still decides).
    ``clock`` is ``measure_live``'s."""
    harness = harness or {
        "max_decode_slots": 4,
        "page_size": 8,
        "num_pages": 64,
        "max_model_len": 128,
        "preempt_stall_grace_s": 0.2,
    }
    workload = harness_workload(target, seed, n=n)
    sim_base = {
        "slots_per_instance": harness["max_decode_slots"],
        "pages_per_instance": harness["num_pages"],
        "page_size": harness["page_size"],
        "preempt_stall_grace_s": harness["preempt_stall_grace_s"],
        "max_inflight": max(len(workload), 4),
        "initial_instances": 1,
    }

    sim_settings = SearchSettings(
        seed=seed, base_sim=sim_base, eval_seeds=1
    )
    fixed = TuneTarget(
        kind="synthetic", name="burst", requests=len(workload)
    )

    sim_scores: list[float] = []
    live_scores: list[float] = []
    rows: list[dict] = []
    for i, overrides in enumerate(candidates):
        sim_comp = evaluate(
            overrides, fixed, sim_settings, seed, workload=list(workload)
        )
        live_comp = await measure_live(
            overrides,
            workload,
            harness,
            slo_ttft_s=slo_ttft_s,
            slo_itl_s=slo_itl_s,
            clock=clock,
        )
        sim_scores.append(sim_comp["score"])
        live_scores.append(live_comp["score"])
        rows.append({
            "candidate": i,
            "overrides": {k: overrides[k] for k in sorted(overrides)},
            "sim": sim_comp,
            "live": live_comp,
        })

    tau = kendall_tau(sim_scores, live_scores)
    top1 = (
        sim_scores.index(max(sim_scores))
        == live_scores.index(max(live_scores))
        if sim_scores
        else True
    )
    return {
        "candidates": rows,
        "sim_scores": sim_scores,
        "live_scores": live_scores,
        "kendall_tau": tau,
        "top1_agreement": top1,
        "agreed": top1 and tau >= 0.0,
    }
