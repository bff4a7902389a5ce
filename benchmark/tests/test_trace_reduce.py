"""The reduction from a device trace to numbers: on hand-made intervals,
and on the small trace recorded on a TPU v5e beside this file."""

import os

import pytest

from lib import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_merges_overlaps_and_nesting():
    ev = [(0, 10, "a"), (5, 10, "b"), (20, 5, "c"), (21, 2, "d")]
    assert tr.union_ns(ev) == 15 + 5
    assert tr.union_ns([]) == 0


def test_gaps_are_what_no_event_covers():
    ev = [(10, 10, "a"), (15, 10, "b"), (40, 5, "c")]
    assert tr.gaps_ns(ev, 0, 50) == [(0, 10), (25, 15), (45, 5)]
    assert tr.gaps_ns([], 0, 7) == [(0, 7)]


def test_self_time_takes_children_out_of_their_parent():
    # while [0,100) holds fusion [10,40) and copy [50,70); copy holds x [55,60).
    ev = [(0, 100, "while"), (10, 30, "fusion"), (50, 20, "copy"), (55, 5, "x"), (200, 7, "fusion")]
    assert tr.self_time_ns(ev) == {"while": 50, "fusion": 37, "copy": 15, "x": 5}


def test_short_name_keeps_the_op_and_a_custom_calls_target():
    assert tr.short_name("%fusion.55 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop") == "fusion.55"
    assert tr.short_name(
        '%custom-call.6 = bf16[4]{0} custom-call(), custom_call_target="tpu_custom_call"'
    ) == "custom-call.6[tpu_custom_call]"
    assert tr.short_name("jit_step(123)") == "jit_step(123)"


def test_reduce_reports_the_worst_chip_and_attributes_gaps():
    trace = {
        "devices": {
            0: [(0, 80, "fusion"), (90, 10, "copy")],
            1: [(0, 40, "fusion"), (90, 10, "copy")],
        },
        "host": [(35, 60, "main: schedule"), (0, 5, "main: tiny")],
    }
    r = tr.reduce(trace)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["chips"][0]["idle_share"] == pytest.approx(0.1)
    assert r["chips"][1]["idle_share"] == pytest.approx(0.5)
    assert r["worst_chip"] == 1
    assert r["busy_s"] == pytest.approx((90 + 50) / 2 * 1e-9)
    assert r["device_ops"][0] == ["fusion", pytest.approx(120e-9)]
    assert r["idle_gaps"][0] == ["main: schedule", pytest.approx(50e-9)]


def test_reduce_of_no_device_events_has_nothing_to_read():
    assert tr.reduce({"devices": {}, "host": []})["chips"] == {}


def test_recorded_v5e_trace():
    """Three runs of a jitted step (a fori_loop of four matmul+tanh) on one
    TPU v5e chip, recorded by rehearse/record_trace.py (PR 23)."""
    trace = tr.load(os.path.join(DATA, "small_trace.xplane.pb"))
    assert sorted(trace["devices"]) == [0] and len(trace["devices"][0]) == 42
    assert any("PjitFunction(step)" in name for _, _, name in trace["host"])
    r = tr.reduce(trace)
    # 3 runs x ~5.4 us of device time in a ~15 ms window.
    assert r["busy_s"] == pytest.approx(16.116e-6, rel=1e-3)
    assert 0.01 < r["window_s"] < 0.02
    assert r["chips"][0]["idle_share"] > 0.99
    ops = dict(r["device_ops"])
    assert ops["convolution_tanh_fusion.2"] == pytest.approx(9.607e-6, rel=1e-3)  # 12 matmuls
    # The while's own time is what its body does not account for.
    assert ops["while"] < 1e-6
    assert sum(r["ops_s"].values()) == pytest.approx(r["busy_s"], rel=0.02)
    assert len(r["idle_gaps"]) == 5 and r["idle_gaps"][0][1] > 1e-3
