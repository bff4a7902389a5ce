"""Request anatomy + workload fingerprint plane (docs/observability.md
"Request anatomy" / "Workload fingerprint").

Covers the PR 16 acceptance surface: span-sweep decomposition
determinism and the component-sum == edge-latency invariant (synthetic
trees, the checked-in fixture, and a live tiny-engine run), flight-dump
reconstruction, fingerprint digest bit-identity across feed orders,
the fingerprint→sim replay round-trip, multi-window SLO burn rates,
the drift watch + fleet rollup, and the new llmctl surfaces.
"""

import asyncio
import contextlib
import io
import json
import os
import random

import pytest

from dynamo_exp_tpu import llmctl
from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
from dynamo_exp_tpu.models import TINY
from dynamo_exp_tpu.parallel import single_device_mesh
from dynamo_exp_tpu.protocols.common import BackendInput
from dynamo_exp_tpu.telemetry import (
    COMPONENTS,
    AnatomyRing,
    FingerprintBuilder,
    RequestAnatomy,
    Span,
    WorkloadDriftWatch,
    anatomy_from_flight,
    anatomy_from_spans,
    anatomy_from_timing,
    drift_score,
    fingerprint_from_spans,
    load_spans,
    render_anatomy,
    render_slow,
    replay_workload,
)

PS = 8
FIXTURE = os.path.join(
    os.path.dirname(__file__), "fixtures", "anatomy_trace.jsonl"
)
# The checked-in fixture's fingerprint digest, pinned: bucketing or
# hashing changes must land with a deliberate update here AND in the
# anatomy-smoke CI job's expectations (docs/observability.md).
FIXTURE_DIGEST = "cc4c9acebff3d398e80362e750157f64"


def _span(stage, trace, sid, start, end, parent="", **attrs):
    return Span(
        stage=stage, trace_id=trace, span_id=sid,
        parent_span_id=parent, start=start, end=end, attrs=attrs,
    )


def _synthetic_trace():
    """One request: queue 0.2s, prefill 1.0s (0.3s compile), transfer
    0.1s inside prefill, decode 1.5s (0.2s swap stall), 0.3s edge
    overhead -> 3.0s total."""
    t = "t" * 32
    return [
        _span("http_request", t, "r", 0.0, 3.0,
              request_id="req-x", ttft_s=1.3, latency_s=3.0, priority=1),
        _span("queue_wait", t, "q", 0.1, 0.3, parent="r"),
        _span("prefill", t, "f", 0.3, 1.3, parent="r",
              prompt_tokens=256, cached_tokens=0, compile_s=0.3),
        _span("kv_transfer_send", t, "s", 1.2, 1.3, parent="f"),
        _span("decode", t, "d", 1.3, 2.8, parent="r",
              generated_tokens=32, priority=1, pages=6, swap_stall_s=0.2),
    ]


# ------------------------------------------------------------ span sweep
def test_sweep_decomposition_sums_to_edge_exactly():
    a = anatomy_from_spans(_synthetic_trace())
    assert a is not None
    assert set(a.components) == set(COMPONENTS)
    assert a.total_s == pytest.approx(a.edge_latency_s, abs=1e-6)
    assert a.edge_latency_s == pytest.approx(3.0)
    # The transfer span's claim wins its instants away from prefill.
    assert a.components["kv_transfer"] == pytest.approx(0.1, abs=1e-6)
    # Carve-outs move time within the component, preserving the total.
    assert a.components["compile_stall"] == pytest.approx(0.3, abs=1e-6)
    assert a.components["swap_stall"] == pytest.approx(0.2, abs=1e-6)
    assert a.components["prefill_compute"] == pytest.approx(0.6, abs=1e-6)
    assert a.components["decode_compute"] == pytest.approx(1.3, abs=1e-6)
    assert a.components["queue_wait"] == pytest.approx(0.2, abs=1e-6)
    # Unclaimed edge overhead books as `other`, never disappears.
    assert a.components["other"] == pytest.approx(0.3, abs=1e-6)
    assert a.dominant == "decode_compute"
    assert a.prompt_tokens == 256 and a.generated_tokens == 32
    # chip-seconds = compute components; page-seconds = pages * compute.
    compute = sum(
        a.components[c]
        for c in ("prefill_compute", "compile_stall", "decode_compute",
                  "host_gap")
    )
    assert a.chip_seconds == pytest.approx(compute, abs=1e-6)
    assert a.kv_page_seconds == pytest.approx(6 * compute, abs=1e-5)


def test_decomposition_deterministic_across_span_order():
    spans = _synthetic_trace()
    base = anatomy_from_spans(spans).to_dict()
    for seed in (1, 2, 3):
        shuffled = list(spans)
        random.Random(seed).shuffle(shuffled)
        assert anatomy_from_spans(shuffled).to_dict() == base


def test_preemption_claims_instants_from_decode():
    t = "p" * 32
    spans = [
        _span("http_request", t, "r", 0.0, 4.0, request_id="req-p"),
        _span("decode", t, "d", 0.5, 4.0, parent="r", generated_tokens=8),
        _span("preemption", t, "e", 1.0, 2.5, parent="r"),
    ]
    a = anatomy_from_spans(spans)
    assert a.components["preemption"] == pytest.approx(1.5, abs=1e-6)
    assert a.components["decode_compute"] == pytest.approx(2.0, abs=1e-6)
    assert a.total_s == pytest.approx(a.edge_latency_s, abs=1e-6)


def test_anatomy_from_timing_invariant_and_clamps():
    a = anatomy_from_timing(
        "req-t", queue_s=0.2, prefill_s=0.5, decode_s=1.0,
        compile_s=0.7, swap_s=0.4, preempt_s=0.3, gap_frac=0.1,
        edge_latency_s=2.5, prompt_tokens=64, generated_tokens=16,
        priority=2, page_seconds=8.0,
    )
    # compile clamps into prefill, swap into decode, gap out of decode.
    assert a.components["compile_stall"] == pytest.approx(0.5)
    assert a.components["prefill_compute"] == pytest.approx(0.0)
    assert a.components["swap_stall"] == pytest.approx(0.4)
    assert a.components["host_gap"] == pytest.approx(0.06)
    assert a.components["decode_compute"] == pytest.approx(0.54)
    assert a.total_s == pytest.approx(2.5, abs=1e-6)
    assert a.components["other"] == pytest.approx(0.5, abs=1e-6)
    # Round-trip through the mirror dict (`llmctl slow` live path).
    back = RequestAnatomy.from_dict(a.to_dict())
    assert back.components == a.to_dict()["components"]
    assert back.dominant == a.dominant


def test_anatomy_from_flight_state_machine():
    block = {
        "events": [
            {"seq": 0, "t": 10.0, "kind": "admit", "req": "r1", "slot": 0,
             "prompt": 32, "cached": 0, "priority": 1},
            {"seq": 1, "t": 10.5, "kind": "first_token", "req": "r1"},
            {"seq": 2, "t": 11.0, "kind": "preempt", "req": "r1"},
            {"seq": 3, "t": 12.0, "kind": "admit", "req": "r1", "slot": 1},
            {"seq": 4, "t": 12.2, "kind": "first_token", "req": "r1"},
            {"seq": 5, "t": 12.4, "kind": "stall_start", "req": "r1"},
            {"seq": 6, "t": 12.6, "kind": "stall_end", "req": "r1"},
            {"seq": 7, "t": 13.0, "kind": "finish", "req": "r1",
             "generated": 12, "pages": 3, "priority": 1},
            # A request whose admit fell off the ring: skipped, not
            # invented.
            {"seq": 8, "t": 13.5, "kind": "finish", "req": "r2"},
        ]
    }
    out = anatomy_from_flight(block)
    assert len(out) == 1
    a = out[0]
    assert a.request_id == "r1"
    assert a.components["prefill_compute"] == pytest.approx(0.7, abs=1e-6)
    assert a.components["preemption"] == pytest.approx(1.0, abs=1e-6)
    assert a.components["swap_stall"] == pytest.approx(0.2, abs=1e-6)
    assert a.components["decode_compute"] == pytest.approx(1.1, abs=1e-6)
    assert a.total_s == pytest.approx(a.edge_latency_s, abs=1e-6)
    assert anatomy_from_flight(block, "r2") == []


def test_anatomy_ring_bounded_worst_first():
    ring = AnatomyRing(capacity=3)
    for i in range(8):
        ring.offer(
            anatomy_from_timing(
                f"req-{i}", queue_s=0.0, prefill_s=0.1, decode_s=float(i),
                compile_s=0.0, swap_s=0.0, preempt_s=0.0, gap_frac=0.0,
                edge_latency_s=0.1 + i,
            )
        )
    snap = ring.snapshot()
    assert [d["request_id"] for d in snap] == ["req-7", "req-6", "req-5"]
    assert all(set(d["components"]) == set(COMPONENTS) for d in snap)


# --------------------------------------------------------------- fixture
def test_fixture_traces_decompose_and_render():
    spans = load_spans([FIXTURE])
    by_trace = {}
    for s in spans:
        by_trace.setdefault(s.trace_id, []).append(s)
    assert len(by_trace) == 3
    for group in by_trace.values():
        a = anatomy_from_spans(group)
        assert a.total_s == pytest.approx(a.edge_latency_s, abs=1e-5)
        rendered = render_anatomy(a)
        assert "dominant" in rendered and "chip-seconds" in rendered
    listing = render_slow(
        [anatomy_from_spans(g) for g in by_trace.values()], n=3
    )
    assert "req-fixture-2" in listing  # worst edge latency first
    assert listing.index("req-fixture-2") < listing.index("req-fixture-1")


def test_fixture_fingerprint_digest_pinned():
    fp = fingerprint_from_spans(load_spans([FIXTURE]))
    assert fp.n == 3
    assert fp.digest()[:32] == FIXTURE_DIGEST
    assert fp.priority_mix == (
        pytest.approx(1 / 3, abs=1e-3),
    ) * 3


# ----------------------------------------------------------- fingerprint
def test_fingerprint_digest_stable_across_feed_orders():
    def build(order, arrival_scale):
        b = FingerprintBuilder()
        for i in order:
            b.observe_admit(
                prompt_tokens=32 * (i + 1), cached_tokens=8 * i,
                priority=i % 3, arrival_t=1000.0 + i * arrival_scale,
            )
        for i in order:
            b.observe_finish(generated_tokens=16 * (i + 1))
        return b.snapshot()

    base = build(list(range(6)), 1.0)
    reordered = build([3, 0, 5, 2, 4, 1], 1.0)
    assert reordered.digest() == base.digest()
    # Wall-clock-derived fields ride alongside but never enter the
    # digest: stretching arrivals 50x changes the rate, not the hash.
    stretched = build(list(range(6)), 50.0)
    assert stretched.digest() == base.digest()
    assert stretched.arrival_rate_rps != base.arrival_rate_rps
    # Round-trip through the saved-reference format.
    from dynamo_exp_tpu.telemetry import WorkloadFingerprint

    back = WorkloadFingerprint.from_dict(base.to_dict())
    assert back.digest() == base.digest()


def test_fingerprint_replay_roundtrip():
    """fingerprint -> replay_workload -> re-fingerprint: the replayed
    population drifts < 0.2 from the source (PR-6-style calibration
    tolerance; the shape axes must essentially match) and is
    deterministic in the seed."""
    b = FingerprintBuilder()
    rng = random.Random(11)
    for i in range(300):
        isl = rng.choice((64, 128, 512, 900))
        b.observe_admit(isl, cached_tokens=isl // 4 if i % 2 else 0,
                        priority=rng.choice((1, 1, 1, 2, 0)),
                        arrival_t=500.0 + i * 0.25)
        b.observe_finish(rng.choice((16, 32, 128)))
    fp = b.snapshot()

    reqs = replay_workload(fp, seed=3, n=400)
    assert len(reqs) == 400
    assert reqs == replay_workload(fp, seed=3, n=400)  # seed-determinism
    assert reqs != replay_workload(fp, seed=4, n=400)

    rb = FingerprintBuilder()
    for r in reqs:
        rb.observe_admit(r.prompt_len, r.prefix_len if r.prefix_group >= 0
                         else 0, r.priority, r.arrival_s or 1e-9)
        rb.observe_finish(r.max_tokens)
    replayed = rb.snapshot()
    assert drift_score(replayed, fp) < 0.2
    # And identical populations score (near) zero drift.
    assert drift_score(fp, fp) == 0.0


def test_replay_drives_cluster_sim_with_anatomy():
    """The fingerprint→sim seam end to end: a replayed workload runs
    through ClusterSim, the report carries the anatomy rollup, and the
    whole thing is bit-deterministic per seed."""
    from dynamo_exp_tpu.sim import ClusterSim, SimConfig

    b = FingerprintBuilder()
    for i in range(24):
        b.observe_admit(24 + 8 * (i % 3), priority=1,
                        arrival_t=100.0 + i * 0.05)
        b.observe_finish(6 + (i % 4))
    reqs = replay_workload(b.snapshot(), seed=5, n=12, rate_rps=50.0)

    def run():
        cfg = SimConfig(seed=0, slots_per_instance=4, pages_per_instance=64,
                        page_size=8, initial_instances=1)
        return ClusterSim(cfg, reqs).run()

    r1, r2 = run(), run()
    assert r1.completed > 0
    assert set(r1.anatomy) == {
        "queue_wait", "prefill_compute", "decode_compute", "preemption"
    }
    assert r1.anatomy["prefill_compute"] > 0
    assert r1.anatomy["decode_compute"] > 0
    assert r1.to_dict() == r2.to_dict()
    assert "anatomy" in r1.to_dict()


# ------------------------------------------------------- burn rate, drift
def test_multi_window_burn_rates():
    from dynamo_exp_tpu.telemetry.slo import SloAttribution, SloConfig

    slo = SloAttribution(SloConfig(ttft_s=0.5, itl_s=0.05))
    for _ in range(8):
        slo.count(1, ttft_s=0.1, itl_s=0.01)  # all met
    rates = slo.burn_rates()
    assert rates["ttft/fast"] == 0.0 and rates["itl/slow"] == 0.0
    for _ in range(8):
        slo.count(1, ttft_s=2.0, itl_s=0.01)  # ttft breached
    rates = slo.burn_rates()
    assert rates["ttft/fast"] == pytest.approx(0.5)
    assert rates["ttft/slow"] == pytest.approx(0.5)
    assert rates["itl/fast"] == 0.0
    # An unmeasurable axis (1-token response) never dilutes the window.
    slo.count(1, ttft_s=2.0, itl_s=None)
    assert sum(len(w) for (s, _), w in slo._burn.items() if s == "itl") == 32
    # The fast window forgets; the slow window remembers (fast = 64
    # requests, so 64 clean ones wash the breaches out of fast only).
    for _ in range(64):
        slo.count(1, ttft_s=0.1, itl_s=0.01)
    rates = slo.burn_rates()
    assert rates["ttft/fast"] == 0.0
    assert rates["ttft/slow"] > 0.0


def test_burn_rate_gauge_exported():
    from prometheus_client import CollectorRegistry

    from dynamo_exp_tpu.telemetry.slo import SloAttribution, SloConfig
    from dynamo_exp_tpu.telemetry.spans import Telemetry

    hub = Telemetry(CollectorRegistry())
    slo = SloAttribution(SloConfig(ttft_s=0.5), telemetry=hub)
    slo.count(1, ttft_s=2.0)
    assert hub.slo_burn_rate.labels("ttft", "fast")._value.get() == 1.0


def test_drift_watch_min_n_and_scoring():
    ref_b = FingerprintBuilder()
    for i in range(32):
        ref_b.observe_admit(128, priority=1, arrival_t=10.0 + i)
        ref_b.observe_finish(32)
    ref = ref_b.snapshot()

    live = FingerprintBuilder()
    watch = WorkloadDriftWatch(live, ref, min_n=8)
    assert watch.score() == 0.0  # too few samples to accuse anyone
    for i in range(8):
        live.observe_admit(4096, priority=0, arrival_t=20.0 + i)
        live.observe_finish(512)
    s = watch.score()
    assert s > 0.3  # a genuinely different workload
    assert WorkloadDriftWatch(live, None).score() == 0.0


def test_fleet_rollup_and_top_carry_drift():
    from dynamo_exp_tpu.telemetry.fleet import FleetView, render_top

    view = FleetView.from_snapshots({
        "a": {"num_requests_running": 1, "workload_drift_score": 0.41},
        "b": {"num_requests_running": 0, "workload_drift_score": 0.05},
    })
    roll = view.rollup()
    assert roll["workload_drift"] == pytest.approx(0.41)  # max, not mean
    body = render_top(view)
    assert "DRIFT:0.41" in body
    assert "DRIFT:0.05" not in body  # below the flag threshold


# -------------------------------------------------------------- live engine
def make_engine(**env) -> TPUEngine:
    cfg = EngineConfig(
        model=TINY,
        max_decode_slots=2,
        page_size=PS,
        num_pages=64,
        max_model_len=128,
        eos_token_ids=[],
        kv_dtype="float32",
    )
    return TPUEngine(cfg, mesh=single_device_mesh(), seed=0)


async def _drive(engine, n_requests=3, max_tokens=4, prompt_len=12):
    async def one(i):
        b = BackendInput(token_ids=list(range(3, 3 + prompt_len)))
        b.stop_conditions.max_tokens = max_tokens
        b.stop_conditions.ignore_eos = True
        stream = await engine.generate(b.to_dict())
        tokens = []
        async for item in stream:
            tokens.extend(item.get("token_ids", []))
        return tokens

    return await asyncio.gather(*[one(i) for i in range(n_requests)])


async def test_engine_anatomy_and_fingerprint_mirrors():
    """Live acceptance: finished requests land in the anatomy mirrors,
    every exemplar's component sum explains its edge latency exactly
    (the within-5% acceptance bound, met by construction engine-side),
    and the fingerprint digest is identical across two same-shape
    runs."""
    e1 = make_engine()
    try:
        outs = await _drive(e1, n_requests=3)
        assert all(len(t) == 4 for t in outs)
        m = e1.metrics()
        assert m["anatomy_requests"] == 3
        assert set(m["anatomy_totals"]) == set(COMPONENTS)
        slow = m["anatomy_slow"]
        assert len(slow) == 3
        for d in slow:
            total = sum(d["components"].values())
            assert d["edge_latency_s"] > 0
            # Acceptance: components explain the edge latency within 5%.
            assert total == pytest.approx(d["edge_latency_s"], rel=0.05)
            assert d["prompt_tokens"] == 12 and d["generated_tokens"] == 4
        # Totals are the sum over requests, and the prometheus family
        # mirrors them.
        from prometheus_client import REGISTRY as _  # noqa: F401
        from dynamo_exp_tpu.telemetry import get_telemetry

        fam = {
            tuple(s.labels.values()): s.value
            for metric in get_telemetry().registry.collect()
            if metric.name == "dynamo_request_seconds"
            for s in metric.samples
            if s.name.endswith("_total")
        }
        for comp, v in m["anatomy_totals"].items():
            if v > 0:
                assert fam.get((comp,), 0.0) >= v * 0.99
        assert m["workload_requests"] == 3
        digest1 = m["workload_fingerprint"]
        assert m["workload_drift_score"] == 0.0  # no reference pinned
    finally:
        e1.stop()

    e2 = make_engine()
    try:
        await _drive(e2, n_requests=3)
        assert e2.metrics()["workload_fingerprint"] == digest1
    finally:
        e2.stop()


async def test_engine_drift_watch_reads_reference(tmp_path, monkeypatch):
    """DYN_WORKLOAD_REF pins a reference at boot; a live mix far from
    it drives the drift mirror (and gauge) above zero."""
    ref_b = FingerprintBuilder()
    for i in range(16):
        ref_b.observe_admit(4096, priority=2, arrival_t=5.0 + i)
        ref_b.observe_finish(1024)
    ref_path = tmp_path / "ref.json"
    ref_path.write_text(json.dumps(ref_b.snapshot().to_dict()))
    monkeypatch.setenv("DYN_WORKLOAD_REF", str(ref_path))
    monkeypatch.setenv("DYN_ANATOMY_RING", "2")

    engine = make_engine()
    try:
        assert engine.drift_watch.reference is not None
        assert engine.drift_watch.min_n <= 8
        await _drive(engine, n_requests=8, max_tokens=2)
        m = engine.metrics()
        assert m["workload_drift_score"] > 0.3
        assert len(m["anatomy_slow"]) == 2  # DYN_ANATOMY_RING honored
    finally:
        engine.stop()


# ------------------------------------------------------------- llmctl CLI
def _run_cli(argv):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = asyncio.run(llmctl.run(llmctl.build_parser().parse_args(argv)))
    return rc, out.getvalue()


def test_llmctl_trace_why_over_fixture():
    rc, out = _run_cli(
        ["trace", "aaaa1111", "--trace-file", FIXTURE, "--why"]
    )
    assert rc == 0
    assert "dominant: decode_compute" in out
    assert "compile_stall" in out and "kv_transfer" in out
    assert "chip-seconds" in out


def test_llmctl_slow_offline_over_fixture():
    rc, out = _run_cli(["slow", "--trace-file", FIXTURE, "-n", "2"])
    assert rc == 0
    assert "req-fixture-2" in out and "req-fixture-3" not in out
    rc, out = _run_cli(
        ["slow", "--trace-file", FIXTURE, "--by", "ttft", "--why"]
    )
    assert rc == 0
    assert "by ttft" in out and "dominant:" in out


def test_llmctl_fingerprint_json_ref_and_replay(tmp_path):
    ref = str(tmp_path / "ref.json")
    rc, out = _run_cli(["fingerprint", FIXTURE, "--json", "--out", ref])
    assert rc == 0
    doc = json.loads(out[out.index("{"):])
    assert doc["digest"][:32] == FIXTURE_DIGEST
    assert os.path.exists(ref)

    rc, out = _run_cli(["fingerprint", FIXTURE, "--ref", ref])
    assert rc == 0
    assert "drift" in out and "0.0000" in out  # self-drift is zero

    replay = str(tmp_path / "replay.jsonl")
    rc, _ = _run_cli(
        ["fingerprint", ref, "--replay-out", replay, "--requests", "50",
         "--seed", "3"]
    )
    assert rc == 0
    from dynamo_exp_tpu.sim import load_trace

    assert len(load_trace(replay)) == 50


def test_llmctl_flight_why(tmp_path):
    dump = tmp_path / "flight.jsonl"
    lines = [
        {"type": "flight_header", "reason": "test", "capacity": 16,
         "dumped_at": 0.0},
        {"type": "flight_event", "seq": 0, "t": 1.0, "kind": "admit",
         "req": "rq", "slot": 0, "prompt": 16, "cached": 0, "priority": 1},
        {"type": "flight_event", "seq": 1, "t": 1.4, "kind": "first_token",
         "req": "rq"},
        {"type": "flight_event", "seq": 2, "t": 2.0, "kind": "finish",
         "req": "rq", "generated": 6, "pages": 2, "priority": 1},
    ]
    dump.write_text("\n".join(json.dumps(x) for x in lines) + "\n")
    rc, out = _run_cli(["flight", str(dump), "--why"])
    assert rc == 0
    assert "request rq" in out and "dominant: decode_compute" in out
    rc, out = _run_cli(["flight", str(dump), "--why", "--req", "nope"])
    assert rc != 0 or "no request anatomy" in out


def test_llmctl_top_json_over_fake_runtime(capsys):
    class _Addr:
        component = "TpuWorker"

    class _Info:
        def __init__(self, iid):
            self.address = _Addr()
            self.instance_id = iid
            self.metadata = {}

    class _Discovery:
        async def list_instances(self, _prefix):
            return [_Info(1)]

    class _Plane:
        async def scrape_stats(self, info):
            return {
                "num_requests_running": 2,
                "workload_drift_score": 0.31,
            }

    class _Drt:
        discovery = _Discovery()
        request_plane = _Plane()

    class _Args:
        once = False
        interval = 2.0
        json = True

    rc = asyncio.run(llmctl.run_top(_Drt(), _Args()))
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["rollup"]["workload_drift"] == pytest.approx(0.31)
    assert doc["instances"]["TpuWorker/1"]["workload_drift"] == (
        pytest.approx(0.31)
    )
    assert doc["missing"] == {}
