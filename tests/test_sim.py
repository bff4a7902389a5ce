"""Cluster-simulator suite (docs/simulation.md).

Four pillars, mirroring the subsystem's claims:

- **Determinism**: the same (seed, workload, config) triple produces a
  bit-identical event log and report — the property every other sim
  assertion stands on.
- **Calibration**: a seeded ``overload_burst`` replayed through the sim
  matches the *live* overload harness (real TPUEngine + real
  AdmissionController, the exact scenario of
  ``tests/test_overload.py``) on shed/completion counts exactly and on
  preemption counts within the documented tolerance.
- **Planner comparison**: at equal chip budget, the SLO-driven
  predictive planner achieves at least the reactive threshold
  planner's goodput on a ramp that forces both to scale.
- **Scale**: a million-synthetic-user run completes in bounded
  wall-clock (marked ``slow``; ``make sim-scale``).

Plus the pure planner-policy units the ``plan_step`` extraction makes
possible.

Seeded like the chaos suites: ``SIM_SEEDS`` (comma-separated) selects
the regression seed set; ``make sim`` sweeps several.
"""

import json
import os
import time

import pytest

from dynamo_exp_tpu.planner import (
    PlannerConfig,
    PlannerObservation,
    PlannerState,
    SloTargets,
    plan_step,
    plan_step_slo,
)
from dynamo_exp_tpu.sim import (
    ClusterSim,
    LatencyDist,
    ServiceTimeModel,
    SimConfig,
    SimRequest,
    burst_workload,
    load_trace,
    ramp_workload,
    save_trace,
    synthetic_users,
)

pytestmark = pytest.mark.sim

SEEDS = tuple(
    int(s) for s in os.environ.get("SIM_SEEDS", "7,21,1337").split(",")
)

# The live pressure harness's shape (tests/test_overload.py): a TINY
# engine with 4 slots over an 8-page/8-token-page pool, fronted by a
# 6-in-flight admission controller with the shed watermark at 3.
PRESSURE_CFG = dict(
    slots_per_instance=4,
    pages_per_instance=8,
    page_size=8,
    preempt_stall_grace_s=0.05,
    max_inflight=6,
    shed_watermark=3,
    initial_instances=1,
)


def _pressure_sim(seed: int, **over) -> ClusterSim:
    cfg = SimConfig(seed=seed, **(PRESSURE_CFG | over))
    return ClusterSim(cfg, burst_workload(seed, n=8, osl_range=(6, 12)))


def _report_key(report) -> dict:
    """to_dict() drops the host-dependent wall clock by default, so the
    whole serialized report is the comparison key."""
    d = report.to_dict()
    assert "wall_clock_s" not in d  # regression: default must stay clean
    return d


# ------------------------------------------------------------- determinism
@pytest.mark.parametrize("seed", SEEDS)
def test_event_log_bit_identical_across_runs(seed):
    s1, s2 = _pressure_sim(seed), _pressure_sim(seed)
    r1, r2 = s1.run(), s2.run()
    assert s1.event_log == s2.event_log
    assert _report_key(r1) == _report_key(r2)
    assert r1.submitted == 8
    assert r1.completed + r1.shed + r1.errors == r1.submitted  # no req lost


def test_stale_grace_timer_noops_after_resume_and_restall():
    """A stall -> resume -> re-stall cycle within one epoch must give
    the second stall a FULL grace window (the engine re-sets
    stalled_since): the first stall's still-pending timer no-ops
    instead of preempting early."""
    from dynamo_exp_tpu.sim.cluster import SeqState, _SimSeq

    cfg = SimConfig(seed=0, **PRESSURE_CFG)
    sim = ClusterSim(cfg, [])
    inst = sim.instances[0]
    seq = _SimSeq(SimRequest(0, 0.0, prompt_len=8, max_tokens=4), 0.0)
    seq.instance = inst
    seq.state = SeqState.ACTIVE
    inst.bound.append(seq)
    sim._hard_stall(seq)
    first = seq.stall_epoch
    # Pages freed elsewhere: the row resumes...
    seq.stalled = False
    inst.stall_queue.remove(seq)
    # ...then exhausts them and re-stalls, same epoch.
    sim._hard_stall(seq)
    assert seq.stall_epoch == first + 1
    # The first stall's timer fires mid-way through the new grace
    # window: stale, must not preempt.
    sim._on_grace(seq, seq.epoch, first)
    assert sim.report.preemptions == 0
    # The re-stall's own timer preempts as usual.
    sim._on_grace(seq, seq.epoch, seq.stall_epoch)
    assert sim.report.preemptions == 1


def test_distinct_seeds_diverge():
    s1, s2 = _pressure_sim(7), _pressure_sim(8)
    s1.run(), s2.run()
    assert s1.event_log != s2.event_log


def test_sim_requires_nondecreasing_arrivals():
    cfg = SimConfig(seed=0)
    bad = [
        SimRequest(index=0, arrival_s=1.0, prompt_len=4, max_tokens=2),
        SimRequest(index=1, arrival_s=0.5, prompt_len=4, max_tokens=2),
    ]
    with pytest.raises(ValueError, match="non-decreasing"):
        ClusterSim(cfg, bad).run()


# --------------------------------------------------------------- workloads
def test_synthetic_users_monotone_capped_and_seeded():
    a = list(synthetic_users(11, users=500, duration_s=60.0))
    b = list(synthetic_users(11, users=500, duration_s=60.0))
    assert a == b
    assert len(a) <= 500
    assert all(x.arrival_s <= y.arrival_s for x, y in zip(a, a[1:]))
    assert all(0.0 <= r.arrival_s < 60.0 for r in a)
    assert {r.priority for r in a} <= {0, 1, 2}


def test_burst_workload_mirrors_chaos_generator():
    from dynamo_exp_tpu.protocols.common import parse_priority
    from dynamo_exp_tpu.runtime.transports.chaos import overload_burst

    reqs = burst_workload(7, n=8, osl_range=(6, 12))
    burst = overload_burst(7, n=8, osl_range=(6, 12))
    assert [(r.prompt_len, r.max_tokens, r.priority) for r in reqs] == [
        (len(b.prompt), b.max_tokens, parse_priority(b.priority))
        for b in burst
    ]


def test_trace_roundtrip(tmp_path):
    path = tmp_path / "trace.jsonl"
    reqs = ramp_workload(5, duration_s=20.0, rps_start=1.0, rps_end=4.0)
    n = save_trace(path, reqs)
    assert n == len(reqs)
    assert load_trace(path) == reqs


# ----------------------------------------------------------------- fitting
def test_fit_from_span_jsonl(tmp_path):
    path = tmp_path / "spans.jsonl"
    events = [
        {
            "type": "span", "stage": "prefill", "start": 0.0, "end": 0.1,
            "attrs": {"prompt_tokens": 100, "cached_tokens": 0},
        },
        {
            "type": "span", "stage": "decode", "start": 0.0, "end": 0.8,
            "attrs": {"generated_tokens": 41},
        },
        {"type": "log", "stage": "decode"},  # non-span lines are skipped
    ]
    path.write_text("\n".join(json.dumps(e) for e in events) + "\n")
    model = ServiceTimeModel.from_spans([path])
    assert model.prefill_token_s.median_s == pytest.approx(0.001)
    # The decode span (first token -> finish) holds generated-1
    # inter-token intervals: 0.8s over 40 intervals.
    assert model.itl_s.median_s == pytest.approx(0.02)


def test_fit_from_bench_wrapper_json(tmp_path):
    path = tmp_path / "BENCH_r01.json"
    path.write_text(
        json.dumps(
            {
                "rc": 0,
                "parsed": {
                    "metric": "decode_throughput_llama-1b_isl128_osl64_c32",
                    "value": 64.0,
                    "p50_ttft_s": 1.28,
                },
            }
        )
    )
    model = ServiceTimeModel.from_bench_json([path])
    assert model.itl_s.median_s == pytest.approx(32 / 64.0)  # rows/tok_s
    assert model.prefill_token_s.median_s == pytest.approx(1.28 / 128)


def test_fit_learns_spec_tokens_per_dispatch(tmp_path):
    """Spec-tagged telemetry scales the modeled decode ITL: spec-tagged
    BENCH JSON lines carry `tokens_per_dispatch`, decode spans carry
    `spec_tokens_per_dispatch`, and the fitted factor divides every
    per-token interval (docs/speculative.md)."""
    import random

    bench = tmp_path / "bench.jsonl"
    bench.write_text(
        "\n".join(
            json.dumps(d)
            for d in [
                {
                    "metric": "spec_decode_tiny_isl96_osl32_repeat_d4",
                    "value": 100.0,
                    "tokens_per_dispatch": 2.5,
                },
                {  # speculation-off baseline line: no sample
                    "metric": "spec_decode_tiny_isl96_osl32_repeat_d0",
                    "value": 80.0,
                    "tokens_per_dispatch": None,
                },
            ]
        )
    )
    model = ServiceTimeModel.from_bench_json([bench])
    assert model.spec_tokens_per_dispatch == pytest.approx(2.5)
    base = ServiceTimeModel()
    rng1, rng2 = random.Random(0), random.Random(0)
    assert model.decode_itl(1, 8, rng1) == pytest.approx(
        base.decode_itl(1, 8, rng2) / 2.5
    )
    # planner hints see the effective (speculation-scaled) decode rate.
    assert model.planner_hints()["decode_tokens_per_s"] == pytest.approx(
        2.5 * base.planner_hints()["decode_tokens_per_s"]
    )

    spans = tmp_path / "spans.jsonl"
    spans.write_text(
        json.dumps(
            {
                "type": "span", "stage": "decode", "start": 0.0, "end": 0.8,
                "attrs": {"generated_tokens": 41,
                          "spec_tokens_per_dispatch": 3.0},
            }
        )
        + "\n"
    )
    # Spans win over bench where both speak (per-request measurements).
    both = ServiceTimeModel.from_telemetry(
        span_paths=[spans], bench_paths=[bench]
    )
    assert both.spec_tokens_per_dispatch == pytest.approx(3.0)
    # No double-counting: a spec-on span's per-token ITL already embeds
    # the speedup, so the fitter normalizes it to the per-dispatch
    # interval (x3.0) and decode_itl's /3.0 lands back on the measured
    # per-token interval — NOT measured/3.
    assert both.itl_s.median_s == pytest.approx(0.8 / 40 * 3.0)
    assert both.decode_itl(1, 1, random.Random(0)) == pytest.approx(0.8 / 40)


def test_report_accepted_per_dispatch_and_host_time_opt_in():
    """SimReport serialization: the fitted speculation factor is
    reported, and host wall clock stays out unless asked for."""
    from dynamo_exp_tpu.sim.report import SimReport

    r = SimReport(wall_clock_s=1.23, accepted_per_dispatch=2.0)
    d = r.to_dict()
    assert "wall_clock_s" not in d
    assert d["accepted_per_dispatch"] == 2.0
    assert r.to_dict(include_host_time=True)["wall_clock_s"] == 1.23
    assert '"wall_clock_s"' not in r.to_json()


def test_latency_dist_deterministic_and_lognormal():
    import random

    d = LatencyDist.fit([0.01, 0.02, 0.04])
    assert d.median_s == pytest.approx(0.02)
    assert d.sigma > 0
    assert LatencyDist(0.5).sample(random.Random(0)) == 0.5  # sigma=0
    r1 = d.sample(random.Random(3))
    assert r1 == d.sample(random.Random(3)) and r1 > 0


# ---------------------------------------------------- calibration (live)
@pytest.fixture(scope="module")
def pressure_engine():
    from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
    from dynamo_exp_tpu.models import TINY
    from dynamo_exp_tpu.parallel import single_device_mesh

    cfg = EngineConfig(
        model=TINY,
        max_decode_slots=PRESSURE_CFG["slots_per_instance"],
        page_size=PRESSURE_CFG["page_size"],
        num_pages=PRESSURE_CFG["pages_per_instance"],
        max_model_len=128,
        eos_token_ids=[],
        kv_dtype="float32",
        preempt_stall_grace_s=PRESSURE_CFG["preempt_stall_grace_s"],
    )
    eng = TPUEngine(cfg, mesh=single_device_mesh(), seed=0)
    eng.start()
    yield eng
    eng.stop()


@pytest.mark.nightly
@pytest.mark.parametrize("seed", SEEDS)
async def test_sim_matches_live_overload_harness(pressure_engine, seed):
    """Calibration acceptance: the same ``overload_burst`` seed through
    the live stack (real engine, real admission) and through the sim
    produces the same shed/completion counts exactly — admission order
    is deterministic in both — and preemption counts within the
    documented tolerance (±2: live preemption timing depends on real
    decode-window pacing; docs/simulation.md)."""
    import asyncio

    from dynamo_exp_tpu.http.admission import (
        AdmissionController,
        RequestShedError,
    )
    from dynamo_exp_tpu.protocols.common import (
        BackendInput,
        SamplingOptions,
        parse_priority,
    )
    from dynamo_exp_tpu.runtime.transports.chaos import overload_burst

    burst = overload_burst(seed, n=8, osl_range=(6, 12))
    adm = AdmissionController(
        max_inflight=PRESSURE_CFG["max_inflight"],
        shed_watermark=PRESSURE_CFG["shed_watermark"],
    )

    async def submit(b):
        try:
            adm.acquire(parse_priority(b.priority))
        except RequestShedError as e:
            return ("shed", e.status)
        try:
            bi = BackendInput(
                token_ids=list(b.prompt), priority=parse_priority(b.priority)
            )
            bi.stop_conditions.max_tokens = b.max_tokens
            bi.stop_conditions.ignore_eos = True
            bi.sampling_options = SamplingOptions(temperature=0.9, seed=b.seed)
            stream = await pressure_engine.generate(bi.to_dict())
            final = None
            async for item in stream:
                if item.get("finish_reason"):
                    final = item["finish_reason"]
            return ("done", final)
        finally:
            adm.release()

    preempted_before = pressure_engine.preempted
    results = await asyncio.wait_for(
        asyncio.gather(*[submit(b) for b in burst]), timeout=90
    )
    live_preemptions = pressure_engine.preempted - preempted_before
    live_done = sum(1 for r in results if r == ("done", "length"))
    live_shed = sum(1 for r in results if r[0] == "shed")
    assert live_done + live_shed == len(burst)

    rep = _pressure_sim(seed).run()
    assert rep.completed == live_done
    assert rep.shed == live_shed
    assert rep.errors == 0
    assert abs(rep.preemptions - live_preemptions) <= 2


@pytest.mark.nightly
@pytest.mark.parametrize("seed", SEEDS[:1])
async def test_slo_attribution_live_and_sim_share_code_path(
    pressure_engine, seed
):
    """Calibration (docs/observability.md "SLO attribution & goodput"):
    the live edge and the simulator count goodput/violations through
    the SAME telemetry.SloAttribution code path on the same seeded
    overload burst. With unreachable targets every completed request is
    goodput on both sides — and the counts match EXACTLY (completion
    counts already calibrate exactly); with impossible targets every
    completed request is a TTFT violation on both sides."""
    import asyncio

    from dynamo_exp_tpu.http.admission import (
        AdmissionController,
        RequestShedError,
    )
    from dynamo_exp_tpu.protocols.common import (
        BackendInput,
        SamplingOptions,
        parse_priority,
    )
    from dynamo_exp_tpu.runtime.transports.chaos import overload_burst
    from dynamo_exp_tpu.telemetry import SloAttribution, SloConfig

    burst = overload_burst(seed, n=8, osl_range=(6, 12))
    adm = AdmissionController(
        max_inflight=PRESSURE_CFG["max_inflight"],
        shed_watermark=PRESSURE_CFG["shed_watermark"],
    )
    # Two live attributions fed by the same measured latencies — the
    # edge's record() call, made here with the timings the HTTP layer
    # would have measured.
    live_lax = SloAttribution(SloConfig(ttft_s=1e9, itl_s=1e9))
    live_strict = SloAttribution(SloConfig(ttft_s=1e-12, itl_s=None))

    async def submit(b):
        try:
            adm.acquire(parse_priority(b.priority))
        except RequestShedError as e:
            return ("shed", e.status)
        try:
            bi = BackendInput(
                token_ids=list(b.prompt), priority=parse_priority(b.priority)
            )
            bi.stop_conditions.max_tokens = b.max_tokens
            bi.stop_conditions.ignore_eos = True
            bi.sampling_options = SamplingOptions(temperature=0.9, seed=b.seed)
            t0 = time.monotonic()
            ttft = None
            t_first = t_last = 0.0
            tokens = 0
            stream = await pressure_engine.generate(bi.to_dict())
            final = None
            async for item in stream:
                got = item.get("token_ids") or []
                if got:
                    now = time.monotonic()
                    if ttft is None:
                        ttft = now - t0
                        t_first = now
                    t_last = now
                    tokens += len(got)
                if item.get("finish_reason"):
                    final = item["finish_reason"]
            if final == "length":
                itl = (
                    (t_last - t_first) / (tokens - 1) if tokens > 1 else None
                )
                for attr in (live_lax, live_strict):
                    attr.record(b.priority, ttft_s=ttft, itl_s=itl)
            return ("done", final)
        finally:
            adm.release()

    results = await asyncio.wait_for(
        asyncio.gather(*[submit(b) for b in burst]), timeout=90
    )
    live_done = sum(1 for r in results if r == ("done", "length"))
    assert live_done > 0

    # Unreachable targets: completed == goodput, zero violations — and
    # the sim agrees exactly (its completion count calibrates exactly).
    sim_lax = _pressure_sim(
        seed, slo=SloTargets(ttft_p99_slo_s=1e9, itl_p99_slo_s=1e9)
    )
    rep_lax = sim_lax.run()
    assert live_lax.completed == live_lax.goodput_total == live_done
    assert rep_lax.goodput_requests == rep_lax.completed == live_done
    assert rep_lax.slo_violations_ttft == live_lax.violations["ttft"] == 0

    # Impossible TTFT target: every completed request violates, on the
    # live side and in the sim, through the same count() path.
    sim_strict = _pressure_sim(
        seed, slo=SloTargets(ttft_p99_slo_s=1e-12, itl_p99_slo_s=0.0)
    )
    rep_strict = sim_strict.run()
    assert live_strict.violations["ttft"] == live_done
    assert live_strict.goodput_total == 0
    assert rep_strict.slo_violations_ttft == rep_strict.completed == live_done
    assert rep_strict.goodput_requests == 0
    # Same class, same instance types — the shared-path guarantee.
    assert type(sim_lax.slo_attr) is type(live_lax)


# ------------------------------------------------------------- admission
def test_admission_resize_moves_bounds():
    from dynamo_exp_tpu.http.admission import AdmissionController

    adm = AdmissionController(max_inflight=4, shed_watermark=2)
    adm.resize(8)
    assert adm.max_inflight == 8 and adm.shed_watermark == 6
    adm.resize(8, 20)  # watermark clamps to the cap
    assert adm.shed_watermark == 8
    with pytest.raises(ValueError):
        adm.resize(0)


# ------------------------------------------------------------------- CLI
def test_llmctl_sim_burst_prints_report(capsys):
    from dynamo_exp_tpu.llmctl import main

    rc = main(
        [
            "sim", "burst", "--seed", "7", "--requests", "8",
            "--slots", "4", "--pages", "8", "--page-size", "8",
            "--max-inflight", "6", "--shed-watermark", "3",
        ]
    )
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert report["submitted"] == 8
    assert report["completed"] + report["shed"] + report["errors"] == 8


def test_llmctl_sim_trace_roundtrip(tmp_path, capsys):
    from dynamo_exp_tpu.llmctl import main

    trace = tmp_path / "wl.jsonl"
    assert (
        main(
            [
                "sim", "ramp", "--seed", "5", "--duration-s", "20",
                "--rps-start", "1", "--rps-end", "4",
                "--trace-out", str(trace),
            ]
        )
        == 0
    )
    first = json.loads(capsys.readouterr().out)
    assert (
        main(["sim", "ramp", "--trace-in", str(trace), "--seed", "5"]) == 0
    )
    replay = json.loads(capsys.readouterr().out)
    assert replay["submitted"] == first["submitted"]
    assert replay["completed"] == first["completed"]


# -------------------------------------------------------- planner policies
def _obs(**kw) -> PlannerObservation:
    base = dict(num_prefill=0, num_decode=2)
    return PlannerObservation(**(base | kw))


def _pcfg(**kw) -> PlannerConfig:
    return PlannerConfig(**(dict(max_tpu_budget=8, min_endpoint=1) | kw))


def test_plan_step_no_samples_is_no_signal():
    decision, state = plan_step(_obs(), PlannerState(), _pcfg())
    assert decision.actions == ()  # scrape outage must not scale down
    assert state == PlannerState()


def test_plan_step_scales_up_and_proposes_grace():
    from dynamo_exp_tpu.planner import arm_decode_grace

    decision, state = plan_step(
        _obs(kv_load=(0.95, 0.97)), PlannerState(), _pcfg()
    )
    assert [a.op for a in decision.actions] == ["add"]
    # Grace is a fold the caller applies only when the add LANDS — a
    # failed connector add must not protect a worker that never
    # spawned from scale-down (state untouched here).
    assert decision.arm_decode_grace
    assert state.decode_grace_remaining == 0
    armed = arm_decode_grace(state)
    assert armed.decode_grace_remaining == 2  # 3 minus the arming round


def test_plan_step_grace_blocks_scale_down_then_expires():
    cfg = _pcfg()
    state = PlannerState(decode_grace_remaining=1)
    decision, state = plan_step(_obs(kv_load=(0.1,)), state, cfg)
    assert decision.actions == () and "grace" in decision.notes[0]
    decision, state = plan_step(_obs(kv_load=(0.1,)), state, cfg)
    assert [a.op for a in decision.actions] == ["remove"]
    assert state.decode_grace_remaining == 0


def test_plan_step_respects_chip_budget_and_min_endpoint():
    decision, _ = plan_step(
        _obs(num_decode=8, kv_load=(0.95,)), PlannerState(), _pcfg()
    )
    assert decision.actions == ()  # 8 decode TPUs = the whole budget
    decision, _ = plan_step(
        _obs(num_decode=1, kv_load=(0.01,)), PlannerState(), _pcfg()
    )
    assert decision.actions == ()  # already at min_endpoint


def test_plan_step_prefill_trend_gate():
    cfg = _pcfg()
    rising = _obs(num_prefill=1, prefill_queue=(4.0, 8.0))
    decision, _ = plan_step(rising, PlannerState(), cfg)
    assert [a.component for a in decision.actions] == [cfg.prefill_component]
    draining = _obs(num_prefill=1, prefill_queue=(9.0, 2.1))
    decision, _ = plan_step(draining, PlannerState(), cfg)
    assert decision.actions == () and "drain" in decision.notes[0]


def test_plan_step_slo_scales_ahead_of_breach():
    """KV at 0.55 rising 0.1/window: the reactive planner sees nothing
    (threshold 0.9) while the SLO planner's 2-window forecast already
    exceeds its 0.75 target and adds capacity."""
    obs = _obs(kv_load=(0.45, 0.55))
    reactive, _ = plan_step(obs, PlannerState(), _pcfg())
    assert reactive.actions == ()
    slo, _ = plan_step_slo(obs, PlannerState(), _pcfg(), SloTargets())
    assert [a.op for a in slo.actions] == ["add"]


def test_plan_step_slo_provision_hint_extends_forecast():
    """A fitted provision_s (ServiceTimeModel.planner_hints) looks
    further along the trend: an add decided now only lands provision_s
    later, so a ramp whose default 2-window forecast stays under target
    (0.55 + 2*0.05 = 0.65 < 0.75) crosses it once the landing delay
    (+30s / 10s interval = +3 windows -> 0.80) is folded in."""
    obs = _obs(kv_load=(0.50, 0.55))
    base, _ = plan_step_slo(obs, PlannerState(), _pcfg(), SloTargets())
    assert base.actions == ()
    hinted, _ = plan_step_slo(
        obs, PlannerState(), _pcfg(), SloTargets(provision_s=30.0)
    )
    assert [a.op for a in hinted.actions] == ["add"]


def test_plan_step_slo_scales_on_breached_latency_even_with_cool_kv():
    slo, _ = plan_step_slo(
        _obs(kv_load=(0.2, 0.2), ttft_p99_s=9.0),
        PlannerState(),
        _pcfg(),
        SloTargets(ttft_p99_slo_s=2.0),
    )
    assert all(a.op == "add" for a in slo.actions) and slo.actions


def test_plan_step_slo_bounded_by_step_and_budget():
    targets = SloTargets(max_scale_step=2)
    slo, _ = plan_step_slo(
        _obs(kv_load=(3.0, 3.0)), PlannerState(), _pcfg(), targets
    )
    assert len(slo.actions) == 2  # pressure wants 4+, step caps at 2
    slo, _ = plan_step_slo(
        _obs(num_decode=7, kv_load=(3.0, 3.0)), PlannerState(), _pcfg(), targets
    )
    assert len(slo.actions) == 1  # budget 8 affords one more


def test_plan_step_slo_scale_down_needs_headroom_and_no_grace():
    targets = SloTargets()
    cool = _obs(kv_load=(0.1, 0.1))
    slo, _ = plan_step_slo(cool, PlannerState(1), _pcfg(), targets)
    assert slo.actions == ()  # grace holds the fleet
    slo, _ = plan_step_slo(cool, PlannerState(), _pcfg(), targets)
    assert [a.op for a in slo.actions] == ["remove"]


# ------------------------------------------------- reactive vs SLO planner
def _planner_run(mode: str, seed: int):
    pcfg = PlannerConfig(
        max_tpu_budget=8,
        min_endpoint=1,
        metric_pulling_interval=1.0,
        adjustment_interval=10.0,
    )
    cfg = SimConfig(
        seed=seed,
        slots_per_instance=8,
        pages_per_instance=144,
        page_size=16,
        max_inflight=16,
        shed_watermark=12,
        admission_per_instance=True,
        initial_instances=1,
        planner=mode,
        planner_cfg=pcfg,
        slo=SloTargets(ttft_p99_slo_s=2.0, itl_p99_slo_s=0.08),
        provision_s=5.0,
        record_events=False,
    )
    wl = ramp_workload(
        seed,
        duration_s=300.0,
        rps_start=1.0,
        rps_end=12.0,
        prompt_len=(64, 256),
        max_tokens=(32, 96),
    )
    return ClusterSim(cfg, wl).run()


@pytest.mark.parametrize("seed", SEEDS)
def test_slo_planner_goodput_at_least_reactive_at_equal_budget(seed):
    """Tentpole acceptance: on a load ramp that forces the reactive
    planner to scale, the SLO-driven predictive planner — same chip
    budget, same workload — achieves at least the reactive goodput
    (it provisions ahead of the breach instead of after it)."""
    reactive = _planner_run("reactive", seed)
    slo = _planner_run("slo", seed)
    assert reactive.planner_actions, "scenario must engage the reactive planner"
    assert slo.goodput_tok_s >= reactive.goodput_tok_s
    assert slo.max_instances * 1 <= 8  # never exceeds the chip budget
    assert reactive.max_instances <= 8


# ------------------------------------------------------------ fleet scale
@pytest.mark.slow
@pytest.mark.weekly
def test_million_user_run_bounded_wall_clock():
    """Scale acceptance: one million synthetic users replayed through
    the real policy code completes in bounded wall-clock on one box."""
    pcfg = PlannerConfig(
        max_tpu_budget=64,
        min_endpoint=2,
        metric_pulling_interval=2.0,
        adjustment_interval=10.0,
    )
    cfg = SimConfig(
        seed=11,
        slots_per_instance=16,
        pages_per_instance=1024,
        page_size=16,
        max_inflight=64,
        shed_watermark=48,
        admission_per_instance=True,
        initial_instances=2,
        planner="slo",
        planner_cfg=pcfg,
        slo=SloTargets(ttft_p99_slo_s=2.0, itl_p99_slo_s=0.08),
        provision_s=5.0,
        record_events=False,
        max_events=50_000_000,
    )
    wl = synthetic_users(
        11,
        users=1_000_000,
        duration_s=3600.0,
        prompt_len=(32, 128),
        max_tokens=(8, 32),
    )
    t0 = time.perf_counter()
    rep = ClusterSim(cfg, wl).run()
    wall = time.perf_counter() - t0
    assert wall < 900.0  # bounded: minutes, not hours, for 1M users
    # The open-loop stream is duration-bounded: ~1M exponential arrivals
    # land in the hour, the exact count is seed-determined.
    assert rep.submitted > 950_000
    assert rep.completed + rep.shed + rep.errors == rep.submitted
    assert rep.completed > 0 and rep.goodput_tok_s > 0
    assert rep.max_instances <= 64
