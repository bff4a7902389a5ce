"""End-to-end request tracing + per-stage telemetry.

Covers the observability acceptance path: one request through an
in-proc disagg graph (HTTP ingress → preprocess → disagg decode →
prefill worker → KV transfer → decode) yields a single connected trace
with ≥5 stage spans, the stage histograms surface on ``/metrics``,
JSONL log lines carry the trace_id, and ``llmctl trace`` reconstructs
the timeline from the recorder JSONL.
"""

import asyncio
import io
import json
import logging

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from dynamo_exp_tpu import llmctl
from dynamo_exp_tpu.disagg import (
    DisaggConfig,
    DisaggConfigWatcher,
    DisaggDecodeEngine,
    KvPageReceiver,
    PrefillWorker,
)
from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
from dynamo_exp_tpu.models import TINY
from dynamo_exp_tpu.parallel import single_device_mesh
from dynamo_exp_tpu.runtime.logging import JsonlFormatter
from dynamo_exp_tpu.runtime.runtime import CancellationToken
from dynamo_exp_tpu.runtime.transports.inproc import (
    InProcDiscovery,
    InProcWorkQueue,
)
from dynamo_exp_tpu.telemetry import (
    Span,
    current_trace,
    find_trace,
    get_telemetry,
    load_spans,
    new_trace,
    render_timeline,
    span,
)

PS = 8


def make_engine() -> TPUEngine:
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


# ----------------------------------------------------------------- unit level
def test_span_nesting_and_contextvar():
    assert current_trace() is None
    with span("outer") as outer:
        assert current_trace() is outer.context
        with span("inner") as inner:
            assert inner.context.trace_id == outer.context.trace_id
            assert inner._parent_id == outer.context.span_id
    assert current_trace() is None


def test_span_records_to_recorder(tmp_path):
    tel = get_telemetry()
    path = str(tmp_path / "trace.jsonl")
    tel.configure(path)
    try:
        with span("solo", foo=1):
            pass
        spans = load_spans([path])
        assert [s.stage for s in spans] == ["solo"]
        # Every span carries the process's instance identity (the fleet
        # plane's multi-instance stitching, docs/observability.md).
        assert spans[0].attrs == {"foo": 1, "instance": tel.instance}
        assert spans[0].duration_s >= 0
    finally:
        tel.configure(None)


def test_emit_stage_without_trace_is_dropped(tmp_path):
    tel = get_telemetry()
    path = str(tmp_path / "trace.jsonl")
    tel.configure(path)
    try:
        tel.emit_stage("ghost", 0.0, 1.0, None)
        assert load_spans([path]) == []
        tc = new_trace()
        tel.emit_stage("real", 0.0, 1.0, tc, n=3)
        (s,) = load_spans([path])
        assert s.trace_id == tc.trace_id and s.parent_span_id == tc.span_id
    finally:
        tel.configure(None)


def test_configure_from_env_records_per_process(tmp_path, monkeypatch):
    """DYN_TRACE_FILE is shared by a whole graph's processes; each one
    must record to its own <path>.<pid> (single-writer rotation), and
    load_spans must find the siblings through the base path."""
    import os

    base = str(tmp_path / "t.jsonl")
    monkeypatch.setenv("DYN_TRACE_FILE", base)
    tel = get_telemetry()
    tel.configure_from_env()
    try:
        assert tel.trace_file == f"{base}.pid{os.getpid()}"
        with span("from-env"):
            pass
    finally:
        tel.configure(None)
    assert [s.stage for s in load_spans([base])] == ["from-env"]


def test_recorder_rotation_env_and_atexit_flush(tmp_path, monkeypatch):
    """Satellite: the span recorder is bounded — DYN_TRACE_ROTATE_MB /
    DYN_TRACE_KEEP size the rotation, and the atexit flush hook closes
    the live file so a dying worker doesn't lose its tail."""
    monkeypatch.setenv("DYN_TRACE_ROTATE_MB", "0.001")  # ~1 KiB
    monkeypatch.setenv("DYN_TRACE_KEEP", "2")
    tel = get_telemetry()
    path = str(tmp_path / "t.jsonl")
    tel.configure(path)
    try:
        assert tel._recorder.max_bytes == int(0.001 * (1 << 20))
        assert tel._recorder.max_files == 2
        for i in range(40):  # ~150 bytes/span: forces rotation
            with span("rot", i=i):
                pass
        import os

        assert os.path.exists(path + ".1"), "no rotation happened"
        assert not os.path.exists(path + ".3")  # keep-N bound
        # Bounded retention: the newest spans survive across the kept
        # generations; older generations were deleted (the point of the
        # bound), never grown forever.
        spans = load_spans([path])
        assert 0 < len(spans) < 40
        assert max(s.attrs["i"] for s in spans) == 39  # newest kept
        # Crash-flush path: the atexit hook closes the live recorder
        # (idempotent; a normal configure(None) later is a no-op).
        assert tel._atexit_registered
        tel._flush_at_exit()
        assert tel._recorder is None
    finally:
        tel.configure(None)


def test_invalid_rotation_env_falls_back(tmp_path, monkeypatch):
    monkeypatch.setenv("DYN_TRACE_ROTATE_MB", "not-a-number")
    tel = get_telemetry()
    tel.configure(str(tmp_path / "t.jsonl"))
    try:
        assert tel._recorder.max_bytes == 64 << 20  # default
    finally:
        tel.configure(None)


def test_load_spans_follows_rotations_and_skips_non_numeric(tmp_path):
    tel = get_telemetry()
    path = str(tmp_path / "t.jsonl")
    tel.configure(path)
    try:
        with span("newer"):
            pass
    finally:
        tel.configure(None)
    # A rotated generation plus glob-matching junk siblings.
    import shutil

    with open(path + ".1", "w") as f:
        older = Span("older", "tid", "sid", "", 1.0, 2.0)
        f.write(json.dumps({"ts": 2.0, "event": older.to_event()}) + "\n")
    shutil.copy(path + ".1", path + ".1.bak")  # must not crash load_spans
    stages = [s.stage for s in load_spans([path])]
    assert stages == ["older", "newer"]  # rotation read first (oldest)


def test_timeline_find_by_request_id_and_render():
    tc = new_trace()
    spans = [
        Span("http_request", tc.trace_id, "a", "", 0.0, 1.0,
             {"request_id": "req-9"}),
        Span("preprocess", tc.trace_id, "b", "a", 0.1, 0.2),
        Span("decode", tc.trace_id, "c", "a", 0.3, 0.9, {"generated_tokens": 4}),
    ]
    got = find_trace(spans, "req-9")
    assert len(got) == 3
    out = render_timeline(got)
    assert "http_request" in out and "preprocess" in out
    assert "req-9" in out
    # children indented under the root
    assert "\n  preprocess" in out


# --------------------------------------------------------- e2e disagg trace
async def test_disagg_request_produces_connected_trace(tmp_path, tiny_model_dir):
    """Acceptance: one HTTP request through the in-proc disagg graph →
    one trace, ≥5 stage spans sharing a trace_id, stage histograms on
    /metrics, trace_id in JSONL log lines emitted during handling."""
    from dynamo_exp_tpu.http import HttpService, build_pipeline_engine
    from dynamo_exp_tpu.model_card import ModelDeploymentCard

    from dynamo_exp_tpu.telemetry import get_transfer_ledger

    tel = get_telemetry()
    trace_file = str(tmp_path / "trace.jsonl")
    tel.configure(trace_file)
    get_transfer_ledger().reset()

    prefill_eng, decode_eng = make_engine(), make_engine()
    queue = InProcWorkQueue()
    recv = KvPageReceiver()
    await recv.start()
    cancel = CancellationToken()
    worker = PrefillWorker(prefill_eng, queue, cancel)
    worker_task = asyncio.ensure_future(worker.run())
    watcher = DisaggConfigWatcher(
        InProcDiscovery(), "tiny",
        default=DisaggConfig(max_local_prefill_length=0),  # force remote
    )
    disagg = DisaggDecodeEngine(decode_eng, queue, recv, watcher)

    mdc = ModelDeploymentCard.from_local_path(tiny_model_dir, "tiny")
    mdc.kv_cache_block_size = PS
    svc = HttpService()
    svc.manager.add_chat_model("tiny", build_pipeline_engine(mdc, disagg))

    # Capture JSONL log lines emitted while the request is handled.
    buf = io.StringIO()
    handler = logging.StreamHandler(buf)
    handler.setFormatter(JsonlFormatter())
    root_logger = logging.getLogger()
    root_logger.addHandler(handler)
    old_level = root_logger.level
    root_logger.setLevel(logging.INFO)

    client = TestClient(TestServer(svc.app))
    await client.start_server()
    try:
        body = {
            "model": "tiny",
            "messages": [{"role": "user", "content": "hello " * 30}],
            "max_tokens": 5,
            "stream": False,
        }
        r = await client.post("/v1/chat/completions", json=body)
        assert r.status == 200, await r.text()
        assert disagg.remote_prefills == 1

        m = await client.get("/metrics")
        metrics_text = await m.text()

        # Idle decay: the engine loop publishes gauges on its idle path
        # too, so "requests running" clears after the last request
        # instead of freezing on the final busy-loop snapshot.
        await asyncio.sleep(0.8)
        assert (
            get_telemetry().engine_gauges["num_requests_running"]._value.get()
            == 0
        )
    finally:
        root_logger.removeHandler(handler)
        root_logger.setLevel(old_level)
        await client.close()
        cancel.cancel()
        await asyncio.wait_for(worker_task, 5)
        await recv.close()
        for e in (prefill_eng, decode_eng):
            e.stop()
        tel.configure(None)

    spans = load_spans([trace_file])
    assert spans, "no spans recorded"
    trace_ids = {s.trace_id for s in spans}
    assert len(trace_ids) == 1, f"trace fragmented: {trace_ids}"
    stages = {s.stage for s in spans}
    # HTTP ingress → preprocess → remote prefill hand-off → prefill
    # worker compute → KV transfer both directions → decode.
    expected = {
        "http_request", "preprocess", "remote_prefill", "queue_wait",
        "prefill", "kv_transfer_send", "kv_transfer_recv", "decode",
        # The handoff lease's grant -> confirm hop (fleet plane,
        # docs/observability.md "Fleet plane").
        "kv_lease",
    }
    assert expected <= stages
    assert len(spans) >= 5

    # Fleet-plane acceptance: the trace's transfer hops carry the link
    # endpoints, and the TransferLedger's per-link bandwidth estimate is
    # consistent with the traced extract->ack durations.
    from dynamo_exp_tpu.telemetry import transfer_hops

    hops = transfer_hops(spans)
    assert hops, "no transfer hops in the stitched trace"
    for hop in hops:
        assert hop["src"] == tel.instance  # in-proc graph: one identity
        assert hop["bytes"] > 0 and hop["duration_s"] > 0
    lease_spans = [s for s in spans if s.stage == "kv_lease"]
    assert lease_spans and lease_spans[0].attrs["outcome"] == "confirmed"
    led = get_transfer_ledger()
    rates = [h["bytes"] / h["duration_s"] for h in hops]
    for hop in hops:
        bw = led.bandwidth_bps(hop["src"], hop["dst"])
        assert bw is not None
        # EWMA over the traced observations stays inside their range.
        assert min(rates) * 0.5 <= bw <= max(rates) * 2.0

    # Every non-root span parents into the tree (single connected trace).
    ids = {s.span_id for s in spans}
    roots = [s for s in spans if not s.parent_span_id]
    assert len(roots) == 1 and roots[0].stage == "http_request"
    for s in spans:
        if s.parent_span_id:
            assert s.parent_span_id in ids

    # Stage histograms + engine gauges surface on /metrics.
    for name in (
        "dynamo_stage_duration_seconds",
        "dynamo_engine_queue_wait_seconds",
        "dynamo_engine_prefill_seconds",
        "dynamo_engine_time_between_tokens_seconds",
        "dynamo_kv_transfer_bytes",
        "dynamo_engine_hbm_page_occupancy",
    ):
        assert name in metrics_text, name
    assert 'stage="prefill"' in metrics_text

    # Log correlation: JSONL lines during handling carry the trace_id.
    trace_id = next(iter(trace_ids))
    logged = [
        json.loads(line)
        for line in buf.getvalue().splitlines()
        if line.startswith("{")
    ]
    assert any(e.get("trace_id") == trace_id for e in logged)

    # llmctl trace reconstructs the timeline from the recorder output.
    import contextlib as _ctx

    out = io.StringIO()
    with _ctx.redirect_stdout(out):
        rc = await llmctl.run(
            llmctl.build_parser().parse_args(
                ["trace", trace_id[:8], "--trace-file", trace_file]
            )
        )
    assert rc == 0
    rendered = out.getvalue()
    assert "http_request" in rendered
    assert "kv_transfer_send" in rendered
    assert f"{len(spans)} spans" in rendered

    # ...and lists traces when called without an id.
    out = io.StringIO()
    with _ctx.redirect_stdout(out):
        rc = await llmctl.run(
            llmctl.build_parser().parse_args(
                ["trace", "--trace-file", trace_file]
            )
        )
    assert rc == 0
    assert trace_id in out.getvalue()


async def test_trace_rides_tcp_request_plane():
    """The request plane carries the caller's trace context: spans
    emitted inside the remote handler join the caller's trace."""
    from dynamo_exp_tpu.runtime.transports.base import (
        EndpointAddress,
        InstanceInfo,
    )
    from dynamo_exp_tpu.runtime.transports.tcp import TcpRequestPlane

    plane = TcpRequestPlane()
    seen: list = []

    async def handler(request, context):
        seen.append(current_trace())
        yield {"ok": True}

    info = InstanceInfo(
        address=EndpointAddress("ns", "comp", "ep"), instance_id=7
    )
    served = await plane.serve(info, handler)
    try:
        from dynamo_exp_tpu.runtime.engine import AsyncEngineContext

        with span("caller") as sp:
            stream = await plane.request_stream(
                info, {"x": 1}, AsyncEngineContext()
            )
            frames = [f async for f in stream]
        assert frames == [{"ok": True}]
        assert seen[0] is not None
        assert seen[0].trace_id == sp.context.trace_id
        assert seen[0].span_id == sp.context.span_id  # parents onto caller
    finally:
        await served.close()
        await plane.close()


# ------------------------------------------------------------- satellite fixes
async def test_coordinator_call_cancel_does_not_leak_pending():
    """A caller cancelled while awaiting the reply must not leave its
    entry in CoordinatorClient._pending forever."""
    from dynamo_exp_tpu.runtime.transports.coordinator import (
        CoordinatorClient,
        CoordinatorServer,
    )

    server = CoordinatorServer()
    await server.start()
    client = CoordinatorClient(server.address)
    await client.connect()
    try:
        # queue_pull with nothing queued blocks server-side: cancel the
        # caller mid-await.
        task = asyncio.ensure_future(
            client.call("queue_pull", {"queue": "q", "timeout_s": 30})
        )
        await asyncio.sleep(0.1)
        assert len(client._pending) == 1
        task.cancel()
        with pytest.raises(asyncio.CancelledError):
            await task
        assert len(client._pending) == 0
        # The connection is still usable for the next caller.
        h, _ = await client.call("queue_size", {"queue": "q"})
        assert h["size"] == 0
    finally:
        await client.close()
        await server.close()


async def test_card_sweep_rechecks_expiry_before_delete(monkeypatch):
    """A heartbeat landing mid-sweep must not lose its fresh card."""
    from dynamo_exp_tpu.http.discovery import ModelWatcher
    from dynamo_exp_tpu.local_model import MDC_BUCKET
    from dynamo_exp_tpu.model_card import ModelDeploymentCard
    from dynamo_exp_tpu.runtime.transports.inproc import InProcObjectStore

    store = InProcObjectStore()
    card = ModelDeploymentCard(display_name="m", model_path="/m")
    card.last_published = 0.0  # long expired
    await store.put(MDC_BUCKET, "m", card.to_json().encode())

    class _Drt:
        object_store = store

    fresh = ModelDeploymentCard(display_name="m", model_path="/m")
    fresh.stamp()  # heartbeat: freshly published

    orig_get = store.get
    calls = {"n": 0}

    async def racy_get(bucket, key):
        calls["n"] += 1
        if calls["n"] == 2:
            # Heartbeat wins the race between first read and delete.
            await store.put(MDC_BUCKET, "m", fresh.to_json().encode())
        return await orig_get(bucket, key)

    monkeypatch.setattr(store, "get", racy_get)
    watcher = ModelWatcher.__new__(ModelWatcher)
    watcher.drt = _Drt()

    async def run_once():
        task = asyncio.ensure_future(
            watcher._sweep_expired_cards(period_s=0.01)
        )
        await asyncio.sleep(0.2)
        task.cancel()
        with pytest.raises(asyncio.CancelledError):
            await task

    await run_once()
    raw = await orig_get(MDC_BUCKET, "m")
    assert raw is not None, "sweep deleted a freshly heartbeated card"
    assert not ModelDeploymentCard.from_json(raw.decode()).is_expired()


async def test_card_sweep_still_removes_stale_cards():
    from dynamo_exp_tpu.http.discovery import ModelWatcher
    from dynamo_exp_tpu.local_model import MDC_BUCKET
    from dynamo_exp_tpu.model_card import ModelDeploymentCard
    from dynamo_exp_tpu.runtime.transports.inproc import InProcObjectStore

    store = InProcObjectStore()
    card = ModelDeploymentCard(display_name="m", model_path="/m")
    card.last_published = 0.0
    await store.put(MDC_BUCKET, "stale", card.to_json().encode())

    class _Drt:
        object_store = store

    watcher = ModelWatcher.__new__(ModelWatcher)
    watcher.drt = _Drt()
    task = asyncio.ensure_future(watcher._sweep_expired_cards(period_s=0.01))
    for _ in range(100):
        if await store.get(MDC_BUCKET, "stale") is None:
            break
        await asyncio.sleep(0.01)
    task.cancel()
    with pytest.raises(asyncio.CancelledError):
        await task
    assert await store.get(MDC_BUCKET, "stale") is None


# ---------------------------------------------------- metric doc-sync guard
def _observability_doc() -> str:
    import os

    doc_path = os.path.join(
        os.path.dirname(__file__), "..", "docs", "observability.md"
    )
    with open(doc_path) as f:
        return f.read()


def test_every_registered_metric_name_is_documented():
    """Doc-sync guard: every ``dynamo_*`` metric registered by the
    telemetry hub — counters, gauges, AND histograms — must appear in
    docs/observability.md; new series land with their documentation or
    not at all (this is exactly the drift a PR adding counters would
    otherwise start)."""
    from prometheus_client import CollectorRegistry

    from dynamo_exp_tpu.telemetry.spans import Telemetry

    doc = _observability_doc()
    hub = Telemetry(CollectorRegistry())
    missing = []
    seen_types = set()
    for family in hub.registry.collect():
        seen_types.add(family.type)
        # The client lib reports counters by base name; the exposition
        # (and the docs) use the _total suffix.
        name = family.name + ("_total" if family.type == "counter" else "")
        if name.startswith("dynamo_") and name not in doc:
            missing.append(name)
    # The walk really does cover all three instrument kinds (a refactor
    # that silently dropped one family type would hollow the guard out).
    assert {"counter", "gauge", "histogram"} <= seen_types
    assert not missing, (
        f"metrics registered in telemetry/ but undocumented in "
        f"docs/observability.md: {sorted(missing)}"
    )


def test_every_engine_metrics_mirror_key_is_documented():
    """Doc-sync guard (PR 9 extension): every ``engine.metrics()``
    mirror key — including the host-tier keys and the per-kind
    dispatch-profiler stat fields — must appear in
    docs/observability.md, so the stats-plane surface the tests and the
    sim fit consume can't drift undocumented."""
    from dynamo_exp_tpu.telemetry.dispatch import SUMMARY_FIELDS

    doc = _observability_doc()
    engine = make_engine()
    try:
        # Host tier on a throwaway copy of the config surface: the
        # host_cache_* keys only exist when the tier is enabled.
        m = dict(engine.metrics())
        m.update(
            {"host_cache_resident": 0, "host_cache_hits": 0,
             "host_cache_stores": 0}
        )
        missing = [k for k in m if f"`{k}`" not in doc]
        assert not missing, (
            f"engine.metrics() keys undocumented in "
            f"docs/observability.md: {sorted(missing)}"
        )
        # The dispatch mirror's per-kind stat fields are part of the
        # contract too (bench lines carry them verbatim).
        undocumented_fields = [
            f for f in SUMMARY_FIELDS if f"`{f}`" not in doc
        ]
        assert not undocumented_fields, undocumented_fields
        # ...and so is every build_info label: it is what a fleet
        # scrape compares and what a chip run reads to learn where the
        # engine really ran.
        assert {"platform", "device_kind", "device_count", "attn_impl"} <= set(
            m["build_info"]
        )
        undocumented_labels = [
            k for k in m["build_info"] if f"`{k}`" not in doc
        ]
        assert not undocumented_labels, undocumented_labels
    finally:
        engine.stop()


def test_fleet_plane_surface_is_documented():
    """Doc-sync guard (fleet-plane extension): the fleet-level rollup
    keys, the per-link ledger fields, and the new operator commands
    (`llmctl top` / `llmctl audit`) must land
    in docs/observability.md's "Fleet plane" section, with matching
    suite rows in docs/testing.md and the README pointer — the same
    discipline as the metric-name guard above."""
    from dynamo_exp_tpu.telemetry.fleet import FleetView, LinkStats

    doc = _observability_doc()
    assert "## Fleet plane" in doc
    assert "## KV conservation auditor" in doc
    for cmd in ("llmctl top", "llmctl audit"):
        assert cmd in doc, f"{cmd!r} undocumented in docs/observability.md"
    # Every fleet rollup key and ledger link field is contract surface
    # (llmctl top, SimReport.fleet, and the planner consume them).
    rollup = FleetView.from_snapshots({}).rollup()
    missing = [k for k in rollup if f"`{k}`" not in doc and k not in doc]
    assert not missing, (
        f"fleet rollup keys undocumented in docs/observability.md: {missing}"
    )
    link = LinkStats("a", "b").to_dict()
    missing_link = [k for k in link if f"`{k}`" not in doc and k not in doc]
    assert not missing_link, missing_link

    import os

    here = os.path.dirname(__file__)
    with open(os.path.join(here, "..", "docs", "testing.md")) as f:
        testing = f.read()
    for row in ("test_fleet.py", "test_kv_ledger.py", "llmctl audit"):
        assert row in testing, f"{row!r} missing from docs/testing.md"
    with open(os.path.join(here, "..", "README.md")) as f:
        readme = f.read()
    assert "Fleet plane" in readme


def test_anatomy_plane_surface_is_documented():
    """Doc-sync guard (anatomy-plane extension): the request-anatomy
    component vocabulary, the fingerprint/drift surface, and the new
    operator commands must land in docs/observability.md, with the
    suite row in docs/testing.md and the README pointer — same
    discipline as the fleet-plane guard above."""
    import os

    from dynamo_exp_tpu.telemetry.anatomy import COMPONENTS

    doc = _observability_doc()
    assert "## Request anatomy" in doc
    assert "## Workload fingerprint" in doc
    # Every anatomy component name is contract surface: prometheus
    # label, metrics() mirror key, bench-line field, --why waterfall.
    missing = [c for c in COMPONENTS if c not in doc]
    assert not missing, (
        f"anatomy components undocumented in docs/observability.md: "
        f"{missing}"
    )
    for cmd in (
        "llmctl slow",
        "llmctl fingerprint",
        "llmctl trace 4f1f2a --trace-file /tmp/trace.jsonl --why",
        "DYN_WORKLOAD_REF",
        "dynamo_slo_burn_rate",
    ):
        assert cmd in doc, f"{cmd!r} undocumented in docs/observability.md"

    here = os.path.dirname(__file__)
    with open(os.path.join(here, "..", "docs", "testing.md")) as f:
        testing = f.read()
    assert "test_anatomy.py" in testing
    with open(os.path.join(here, "..", "README.md")) as f:
        readme = f.read()
    assert "Request anatomy" in readme
    assert "llmctl fingerprint" in readme
