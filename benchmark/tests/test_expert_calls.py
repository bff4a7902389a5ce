"""The readers PR 27 adds for a routed-expert cell, on a synthetic trace
written with the schema ``lib/xplane_meta.py`` reads: the ``ragged-dot``
calls' own shapes, their least time on the chip, and the copies of one
layer's expert matrix, known by the calls' three-dimensional operand."""

import importlib

import pytest

from lib import expert_calls, xplane_meta

DOT_UP = (
    "%ragged-dot-none.1 = bf16[128,3584]{1,0:T(8,128)(2,1)} custom-call("
    "bf16[128,4096]{1,0:T(8,128)(2,1)} %fusion.3, bf16[8,4096,3584]{2,1,0:T(8,128)(2,1)} "
    "%dynamic-slice_bitcast_fusion.6, s32[8]{0:T(128)} %bincount), "
    'custom_call_target="ragged_dot", metadata={op_name="jit(ragged_window)/moe/ragged_dot"}'
)
DOT_DOWN = (
    "%ragged-dot-none.2 = bf16[128,4096]{1,0:T(8,128)(2,1)} custom-call("
    "bf16[128,3584]{1,0:T(8,128)(2,1)} %fusion.9, bf16[8,3584,4096]{2,1,0:T(8,128)(2,1)} "
    '%dynamic-slice_bitcast_fusion.8, s32[8]{0:T(128)} %bincount), custom_call_target="ragged_dot"'
)
COPY = (
    "%dynamic-slice_bitcast_fusion.6 = bf16[8,4096,3584]{2,1,0:T(8,128)(2,1)} fusion("
    "bf16[16,8,4096,3584]{3,2,1,0} %p, s32[] %l), kind=kLoop"
)
VIEW = "%get-tuple-element.4 = bf16[8,4096,3584]{2,1,0} get-tuple-element((s32[], bf16[8,4096,3584]) %t), index=1"
OTHER = "%fusion.3 = bf16[128,4096]{1,0:T(8,128)(2,1)} fusion(bf16[128,4096]{1,0} %x), kind=kLoop"
PEAK = {"hbm_gbps": 819.0, "bf16_tflops": 197.0}


def write_trace(path, events):
    """One chip; ``events``: (whole HLO line, start ns, duration ns)."""
    space = xplane_meta._schema()()
    plane = space.planes.add()
    plane.name = "/device:TPU:0"
    line = plane.lines.add()
    line.name = "XLA Ops"
    ids = {}
    for name, start, dur in events:
        if name not in ids:
            ids[name] = len(ids) + 1
            entry = plane.event_metadata.add()
            entry.key = ids[name]
            entry.value.id = ids[name]
            entry.value.name = name
        ev = line.events.add()
        ev.metadata_id = ids[name]
        ev.offset_ps = start * 1000
        ev.duration_ps = dur * 1000
    with open(path, "wb") as f:
        f.write(space.SerializeToString())


def test_a_calls_operands_and_its_least_time_on_the_chip():
    assert expert_calls.operand_arrays(DOT_UP) == [
        ("bf16", (128, 4096)), ("bf16", (8, 4096, 3584)), ("s32", (8,)),
    ]
    call = {"lhs": ("bf16", (128, 4096)), "rhs": ("bf16", (8, 4096, 3584)),
            "result": ("bf16", (128, 3584))}
    assert expert_calls.call_operations(call) == 2 * 128 * 4096 * 3584
    weights = 8 * 4096 * 3584 * 2
    assert expert_calls.call_bytes(call) == weights + 128 * 4096 * 2 + 128 * 3584 * 2
    seconds, bound = expert_calls.least_seconds(call, PEAK)
    assert bound == "hbm" and seconds == pytest.approx(expert_calls.call_bytes(call) / 819e9)
    # Fewer rows than experts: no more matrices than rows are read.
    two = {**call, "lhs": ("bf16", (2, 4096)), "result": ("bf16", (2, 3584))}
    assert expert_calls.call_bytes(two) == weights // 4 + 2 * 4096 * 2 + 2 * 3584 * 2
    # Rows enough to reuse each matrix ~1000 times: the matrix unit bounds it.
    wide = {**call, "lhs": ("bf16", (16384, 4096)), "result": ("bf16", (16384, 3584))}
    assert expert_calls.least_seconds(wide, PEAK)[1] == "mxu"


def test_the_readers_on_a_synthetic_trace(tmp_path, monkeypatch):
    path = str(tmp_path / "t.xplane.pb")
    least = expert_calls.least_seconds(
        {"lhs": ("bf16", (128, 4096)), "rhs": ("bf16", (8, 4096, 3584)),
         "result": ("bf16", (128, 3584))}, PEAK)[0]
    dot_ns = int(2 * least * 1e9)  # each call at half its roofline
    events, at = [], 0
    for _ in range(3):
        for name, dur in ((COPY, 400_000), (VIEW, 0), (OTHER, 100_000), (DOT_UP, dot_ns), (DOT_DOWN, dot_ns)):
            events.append((name, at, dur))
            at += dur + 10
    write_trace(path, events)
    calls = expert_calls.ragged_dot_calls(path)
    assert sorted(c["count"] for c in calls) == [3, 3]
    assert {c["rhs"][1] for c in calls} == {(8, 4096, 3584), (8, 3584, 4096)}
    assert sum(c["ns"] for c in calls) == 6 * dot_ns

    monkeypatch.setattr(xplane_meta, "find_trace", lambda: path)
    xplane_meta._CACHE.clear()
    ctx = {"trace": {"ops_s": {}}}
    copy = importlib.import_module("readers.trace_expert_weight_copy_share").read({}, ctx)
    busy = 3 * (400_000 + 100_000 + 2 * dot_ns)
    assert copy == pytest.approx(3 * 400_000 / busy * 100.0)

    import jax

    kind = jax.devices()[0].device_kind
    real_load = __import__("json").load
    monkeypatch.setattr(
        "json.load", lambda f: {"devices": {kind: PEAK}} if f.name.endswith("peaks.json") else real_load(f)
    )
    share = importlib.import_module("readers.trace_expert_dot_roofline").read({}, ctx)
    assert share == pytest.approx(50.0, rel=1e-3)
    xplane_meta._CACHE.clear()


def test_the_readers_say_nothing_without_a_trace_or_without_such_calls(tmp_path, monkeypatch):
    for kind in ("trace_expert_weight_copy_share", "trace_expert_dot_roofline"):
        assert importlib.import_module(f"readers.{kind}").read({}, {"trace": None}) is None
    path = str(tmp_path / "dense.xplane.pb")
    write_trace(path, [(OTHER, 0, 1000)])
    monkeypatch.setattr(xplane_meta, "find_trace", lambda: path)
    xplane_meta._CACHE.clear()
    for kind in ("trace_expert_weight_copy_share", "trace_expert_dot_roofline"):
        assert importlib.import_module(f"readers.{kind}").read({}, {"trace": {"ops_s": {}}}) is None
    xplane_meta._CACHE.clear()
    # The counter reader on a program that lacks the counter (the parent).
    ratio = importlib.import_module("readers.prom_ratio")
    spec = {"metric": "dynamo_moe_rows_total", "numerator": [{"kind": "pad"}], "denominator": [{}], "scale": 100}
    assert ratio.read(spec, {"prom_before": {}, "prom_after": {}}) is None
