"""Fleet observability plane (docs/observability.md "Fleet plane"):
FleetAggregator rollups, scrape fault tolerance, the per-link
TransferLedger, `llmctl top`, the multi-instance trace
timeline, and the live↔sim fleet-rollup mirror."""

from __future__ import annotations

import asyncio
import json
import time

import pytest

from dynamo_exp_tpu import llmctl
from dynamo_exp_tpu.telemetry import Span
from dynamo_exp_tpu.telemetry.fleet import (
    FleetAggregator,
    FleetView,
    InstanceView,
    TransferLedger,
    parse_prometheus_text,
    render_top,
)
from dynamo_exp_tpu.telemetry.timeline import render_timeline, transfer_hops

pytestmark = pytest.mark.pre_merge


def _metrics(name="w", running=2, waiting=1, occ=0.5, **extra) -> dict:
    return {
        "num_requests_running": running,
        "num_requests_waiting": waiting,
        "gpu_cache_usage_perc": occ,
        "request_active_slots": running,
        "request_total_slots": 8,
        "preemptions": extra.pop("preemptions", 0),
        "kv_ledger_violations": extra.pop("violations", 0),
        "build_info": extra.pop(
            "build_info",
            {"manifest_hash": "abc", "jax_version": "0.4",
             "prefix_sharing": True, "spec": "off"},
        ),
        **extra,
    }


# ------------------------------------------------------------ transfer ledger
def test_ledger_records_links_and_estimates_bandwidth():
    led = TransferLedger()
    # 1 MB in 0.1 s = 10 MB/s on a->b; 2 MB in 0.1 s = 20 MB/s on a->c.
    led.record("a", "b", 1 << 20, 0.1)
    led.record("a", "c", 2 << 20, 0.1)
    bw_ab = led.bandwidth_bps("a", "b")
    assert bw_ab == pytest.approx((1 << 20) / 0.1)
    assert led.estimate_transfer_s("a", "c", 2 << 20) == pytest.approx(0.1)
    assert led.bandwidth_bps("a", "zz") is None
    # Never-observed links price at the cold-start prior (reclaim triage
    # must cost transfers on a fresh fleet); only a disabled prior
    # leaves them unpriceable — tests/test_reclaim.py covers the knob.
    assert led.estimate_transfer_s("a", "zz", 100) == pytest.approx(
        100 / led.default_bandwidth_bps
    )
    assert TransferLedger(default_bandwidth_bps=0).estimate_transfer_s(
        "a", "zz", 100
    ) is None
    # EWMA: a second, slower observation moves the estimate toward it
    # without erasing the history.
    led.record("a", "b", 1 << 20, 0.2)
    bw2 = led.bandwidth_bps("a", "b")
    assert (1 << 20) / 0.2 < bw2 < bw_ab
    snap = led.snapshot()
    assert [(s["src"], s["dst"]) for s in snap] == [("a", "b"), ("a", "c")]
    assert snap[0]["transfers"] == 2
    # Degenerate observations count the transfer, not the bandwidth.
    led.record("a", "b", 0, 0.0)
    assert led.bandwidth_bps("a", "b") == pytest.approx(bw2)


def test_ledger_mirrors_prometheus_link_series():
    from dynamo_exp_tpu.telemetry import get_telemetry, get_transfer_ledger

    led = get_transfer_ledger()
    led.record("src-x", "dst-y", 4096, 0.01)
    text = get_telemetry().render().decode()
    assert 'dynamo_kv_link_bytes_total{dst="dst-y",src="src-x"}' in text
    assert "dynamo_kv_link_bandwidth_bytes_per_s" in text


# ------------------------------------------------------------- fleet view
def test_fleet_view_rollup_and_skew():
    view = FleetView.from_snapshots(
        {
            "w0": _metrics(running=2, waiting=1, occ=0.5),
            "w1": _metrics(running=3, waiting=0, occ=0.7),
            "w2": _metrics(
                running=1, waiting=4, occ=0.1,
                build_info={"manifest_hash": "OTHER", "jax_version": "0.4",
                            "prefix_sharing": True, "spec": "off"},
            ),
        }
    )
    roll = view.rollup()
    assert roll["instances"] == 3
    assert roll["running"] == 6 and roll["waiting"] == 5
    assert roll["occupancy_mean"] == round((0.5 + 0.7 + 0.1) / 3, 4)
    # The odd-one-out fingerprint is flagged, not the majority.
    assert roll["config_skew"] == ["w2"]
    assert "SKEW" in render_top(view)


def test_fleet_scrape_fault_tolerance():
    """Satellite acceptance: an instance dying or returning garbage
    mid-scrape yields a fleet view tagged with the missing member —
    never an exception, never a poisoned rollup."""
    healthy = _metrics(running=2, waiting=1, occ=0.5)

    async def dead():
        raise ConnectionError("instance died mid-scrape")

    def garbage():
        return "}{ not metrics"

    async def nan_fields():
        # Numeric garbage inside an otherwise-dict snapshot: fields
        # degrade to defaults, the member stays healthy.
        return {"num_requests_running": "NaN-ish", "gpu_cache_usage_perc": None}

    agg = FleetAggregator(
        {
            "good": lambda: dict(healthy),
            "dead": dead,
            "garbage": garbage,
            "weird": nan_fields,
        }
    )
    view = asyncio.run(agg.scrape())
    assert set(view.members) == {"good", "weird"}
    assert set(view.missing) == {"dead", "garbage"}
    assert "died mid-scrape" in view.missing["dead"]
    roll = view.rollup()
    assert roll["running"] == 2  # garbage contributed nothing
    assert roll["missing"] == ["dead", "garbage"]
    body = render_top(view)
    assert "MISSING" in body and "dead" in body


def test_fleet_view_from_prometheus_text():
    text = """
# HELP dynamo_engine_num_requests_running Sequences actively decoding
dynamo_engine_num_requests_running 3.0
dynamo_engine_num_requests_waiting 2.0
dynamo_engine_hbm_page_occupancy 0.25
dynamo_requests_shed_total{priority="low",code="429"} 4.0
dynamo_requests_shed_total{priority="high",code="503"} 1.0
dynamo_kv_ledger_violations_total 0.0
dynamo_build_info{manifest_hash="mh1",jax_version="0.4",prefix_sharing="true",spec="off"} 1.0
garbage line that parses to nothing
{"not": "prometheus"}
"""
    parsed = parse_prometheus_text(text)
    view = InstanceView.from_metrics("edge", parsed)
    assert view.running == 3 and view.waiting == 2
    assert view.occupancy == pytest.approx(0.25)
    assert view.shed == 5  # summed across label sets
    assert view.ledger_violations == 0
    # build_info's fingerprint lives in its LABELS — the parser must
    # surface them so text-scraped members join skew detection.
    assert view.build_info["manifest_hash"] == "mh1"


def test_parse_prometheus_text_handles_exposition_timestamps():
    """The optional trailing timestamp (federation/pushgateway output)
    must never be mistaken for the value or drop the sample."""
    text = (
        'dynamo_preemptions_total 3 1722700000000\n'
        'dynamo_kv_link_bytes_total{src="a",dst="b"} 123 1722700000000\n'
    )
    parsed = parse_prometheus_text(text)
    assert parsed["dynamo_preemptions_total"] == 3.0
    assert parsed["dynamo_kv_link_bytes_total"] == 123.0


def test_parse_prometheus_text_brace_in_label_value_and_fallback():
    """A '}' inside a quoted label value must not break the sample, and
    a payload the strict parser rejects falls back to lenient per-line
    parsing instead of discarding the healthy lines."""
    text = (
        'dynamo_build_info{manifest_hash="m}1",jax_version="0.4",'
        'prefix_sharing="true",spec="off"} 1.0\n'
        "dynamo_engine_num_requests_running 2\n"
        "!!! this line is garbage !!!\n"
    )
    parsed = parse_prometheus_text(text)
    assert parsed["dynamo_engine_num_requests_running"] == 2.0
    assert parsed["build_info"]["manifest_hash"] == "m}1"


def test_config_skew_ignores_members_without_build_info():
    """A member whose scrape surface carries no build_info at all is
    *unknown*, not skewed — a mixed stats-plane/text fleet must not
    light up red."""
    view = FleetView.from_snapshots(
        {
            "w0": _metrics(),
            "w1": _metrics(),
            "edge": {"num_requests_running": 1, "build_info": {}},
        }
    )
    assert view.config_skew() == []


def test_merged_links_rollup_is_duration_weighted():
    view = FleetView.from_snapshots(
        {
            "w0": _metrics(kv_links=[
                {"src": "a", "dst": "b", "transfers": 1, "bytes": 1000,
                 "duration_s": 1.0, "bandwidth_bps": 1000.0},
            ]),
            "w1": _metrics(kv_links=[
                {"src": "a", "dst": "b", "transfers": 3, "bytes": 3000,
                 "duration_s": 1.0, "bandwidth_bps": 3000.0},
            ]),
        }
    )
    (link,) = view.rollup()["links"]
    assert link["transfers"] == 4 and link["bytes"] == 4000
    assert link["bandwidth_bps"] == pytest.approx(2000.0)
    assert "a -> b" in render_top(view)


def test_llmctl_top_once_over_fake_runtime(capsys):
    """`llmctl top --once` walks discovery, scrapes each instance's
    stats plane, tags the dead one, and prints a single dashboard."""

    class _Addr:
        component = "TpuWorker"

    class _Info:
        def __init__(self, iid, draining=False):
            self.address = _Addr()
            self.instance_id = iid
            self.metadata = {"draining": True} if draining else {}

    class _Discovery:
        async def list_instances(self, _prefix):
            return [_Info(1), _Info(2, draining=True), _Info(3)]

    class _Plane:
        async def scrape_stats(self, info):
            if info.instance_id == 3:
                raise ConnectionError("gone")
            return _metrics(running=info.instance_id)

    class _Drt:
        discovery = _Discovery()
        request_plane = _Plane()

    class _Args:
        once = True
        interval = 2.0

    rc = asyncio.run(llmctl.run_top(_Drt(), _Args()))
    out = capsys.readouterr().out
    assert rc == 0
    assert "TpuWorker/1" in out and "TpuWorker/3" in out
    assert "MISSING" in out and "draining" in out


# --------------------------------------------------- multi-instance timeline
def _span(stage, trace, start, end, parent="", **attrs):
    return Span(
        stage=stage, trace_id=trace, span_id=f"{stage}-{start}",
        parent_span_id=parent, start=start, end=end, attrs=attrs,
    )


def test_render_timeline_multi_instance_with_transfer_hops():
    t = 1000.0
    spans = [
        _span("http_request", "T", t, t + 1.0, instance="decode-0",
              request_id="r1"),
        _span("remote_prefill", "T", t + 0.1, t + 0.6,
              parent="http_request-1000.0", instance="decode-0"),
        _span("prefill", "T", t + 0.15, t + 0.4, instance="prefill-0"),
        _span("kv_transfer_send", "T", t + 0.4, t + 0.5,
              instance="prefill-0", src="prefill-0", dst="decode-0",
              bytes=2 << 20),
        _span("kv_transfer_recv", "T", t + 0.41, t + 0.5,
              instance="decode-0", src="prefill-0", dst="decode-0",
              bytes=2 << 20),
        _span("kv_lease", "T", t + 0.35, t + 0.52, instance="prefill-0",
              outcome="confirmed"),
    ]
    out = render_timeline(spans)
    assert "across 2 instances" in out
    assert "[prefill-0" in out and "[decode-0" in out
    assert "transfer hops:" in out
    assert "prefill-0 -> decode-0" in out
    assert "MB/s" in out
    hops = transfer_hops(spans)
    assert len(hops) == 2
    assert hops[0]["stage"] == "kv_transfer_send"
    assert hops[0]["duration_s"] == pytest.approx(0.1)
    # Single-instance traces keep the compact label format.
    solo = [_span("decode", "S", t, t + 1, instance="only")]
    assert "[only]" not in render_timeline(solo)


# --------------------------------------------------------------- sim mirror
@pytest.mark.sim
def test_sim_report_fleet_rollup_mirrors_live_shape():
    """`SimReport.fleet` is built through the SAME FleetView.rollup()
    path the live aggregator uses — identical keys, deterministic
    across same-seed runs."""
    from dynamo_exp_tpu.sim import ClusterSim, SimConfig, burst_workload

    def run():
        cfg = SimConfig(seed=7, initial_instances=2, record_events=False)
        return ClusterSim(cfg, burst_workload(7, n=6)).run()

    r1, r2 = run(), run()
    assert r1.fleet == r2.fleet  # deterministic
    live_keys = set(
        FleetView.from_snapshots({"w": _metrics()}).rollup().keys()
    )
    assert set(r1.fleet.keys()) == live_keys
    assert r1.fleet["instances"] == 2
    assert r1.fleet["missing"] == [] and r1.fleet["config_skew"] == []
    # to_dict round-trips with the fleet block included.
    assert json.loads(r1.to_json())["fleet"] == r1.fleet


def test_instance_view_handles_draining_and_violations():
    view = FleetView.from_snapshots(
        {"w0": _metrics(draining=True, violations=2)}
    )
    m = view.members["w0"]
    assert m.draining and m.ledger_violations == 2
    body = render_top(view)
    assert "draining" in body and "LEDGER!2" in body
    assert view.rollup()["ledger_violations"] == 2


def test_fleet_view_scrape_timestamp_never_enters_rollup():
    """The rollup must stay wall-clock-free (the sim mirrors it into
    seeded regression diffs)."""
    v1 = FleetView.from_snapshots({"w": _metrics()})
    time.sleep(0.01)
    v2 = FleetView.from_snapshots({"w": _metrics()})
    assert v1.scraped_at != v2.scraped_at
    assert v1.rollup() == v2.rollup()


def test_render_top_empty_fleet():
    view = FleetView.from_snapshots({})
    body = render_top(view)
    assert "0 instance(s)" in body
