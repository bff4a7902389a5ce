"""The readers PR 31 adds for ``lfm2-8b-a1b.chat-saturate``: the keyed
forms of the expert kernel's roofline and of the slab copy (the experts
counted under the key the metric's file names), on a synthetic trace with
hand-computed shares; the scope reader on the recorded engine trace,
against the class reader where the class table has the same scope; each
says nothing without a trace and 0.0 where there is nothing to find."""

import importlib
import json
import os

import pytest

from lib import xplane_meta
from test_expert_calls import OTHER, PEAK, write_trace
from test_expert_gmm_readers import META

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
GMM_UP = (
    "%expert_gmm.3 = bf16[256,1792]{1,0:T(8,128)(2,1)} custom-call(" + META + ", "
    "bf16[256,2048]{1,0:T(8,128)(2,1)} %fusion.5, bf16[14,32,2048,1792]{3,2,1,0:T(8,128)(2,1)} "
    '%get-tuple-element.51), custom_call_target="tpu_custom_call"'
)
LAYER_COPY = "%copy.5 = bf16[32,2048,1792]{2,1,0} copy(bf16[32,2048,1792]{2,1,0} %dynamic-slice.3)"
STACK_COPY = "%copy.9 = bf16[14,32,1792,2048]{3,2,1,0} copy(bf16[14,32,1792,2048]{2,3,1,0} %p.3)"
STACK_VIEW = "%bitcast.7 = bf16[14,32,2048,1792]{3,2,1,0} bitcast(bf16[448,2048,1792]{2,1,0} %p.1)"
MIXTRAL_SLAB = "%copy.2 = bf16[8,4096,3584]{2,1,0} copy(bf16[8,4096,3584]{2,1,0} %x)"


def spec_of(metric: str) -> dict:
    with open(os.path.join(xplane_meta.HERE, "layer_metrics", f"{metric}.json")) as f:
        return json.load(f)["reader"]


def reader(spec: dict):
    return importlib.import_module(f"readers.{spec['kind']}")


@pytest.fixture()
def on(monkeypatch):
    import jax

    kind = jax.devices()[0].device_kind
    real_load = json.load
    monkeypatch.setattr(
        "json.load",
        lambda f: {"devices": {kind: PEAK}} if f.name.endswith("peaks.json") else real_load(f),
    )

    def point(path):
        monkeypatch.setattr(xplane_meta, "find_trace", lambda: path)
        xplane_meta._CACHE.clear()

    yield point
    xplane_meta._CACHE.clear()


def test_the_keyed_roofline_counts_the_configurations_experts(tmp_path, on):
    path = str(tmp_path / "t.xplane.pb")
    # By hand: 32 experts' matrices (num_experts of the file, not the stack's
    # 14 x 32), lhs and result once; HBM-bound at 256 rows.
    least = (32 * 2048 * 1792 * 2 + 256 * 2048 * 2 + 256 * 1792 * 2) / 819e9
    assert least > 2 * 256 * 2048 * 1792 / 197e12
    dur = int(least / 0.5 * 1e9)  # each call at half its roofline
    write_trace(path, [(OTHER, 0, 50_000), (GMM_UP, 60_000, dur), (GMM_UP, 70_000 + dur, dur)])
    on(path)
    spec = spec_of("lfm2_expert_gmm_roofline_share")
    assert spec["experts_key"] == "num_experts"
    assert reader(spec).read(spec, {"trace": {"ops_s": {}}}) == pytest.approx(50.0, rel=1e-3)
    write_trace(path, [(OTHER, 0, 50_000)])
    on(path)
    assert reader(spec).read(spec, {"trace": {"ops_s": {}}}) is None
    assert reader(spec).read(spec, {"trace": None}) is None


def test_the_keyed_slab_reader_finds_this_models_copies(tmp_path, on):
    path = str(tmp_path / "t.xplane.pb")
    events, at = [], 0
    for name, ns in (
        (LAYER_COPY, 700_000), (STACK_VIEW, 0), (OTHER, 100_000), (GMM_UP, 300_000),
        (STACK_COPY, 500_000), (MIXTRAL_SLAB, 400_000),
    ):
        events.append((name, at, ns))
        at += ns + 10
    write_trace(path, events)
    on(path)
    spec = spec_of("lfm2_expert_slab_copy_time_share")
    ctx = {"trace": {"ops_s": {}}}
    # The layer's copy and the stack's relayout; not the kernel, the view, or
    # another model's slab.
    assert reader(spec).read(spec, ctx) == pytest.approx(1_200_000 / 2_000_000 * 100.0)
    with open(os.path.join(xplane_meta.HERE, "configs", f"{spec['config']}.json")) as f:
        shapes = reader(spec).slab_shapes(json.load(f), spec)
    assert shapes == {
        (32, 2048, 1792), (32, 1792, 2048), (14, 32, 2048, 1792), (14, 32, 1792, 2048),
    }
    write_trace(path, [(GMM_UP, 0, 300_000), (OTHER, 300_010, 100_000)])
    on(path)
    assert reader(spec).read(spec, ctx) == 0.0  # none left: 0.0, not None
    assert reader(spec).read(spec, {"trace": None}) is None


@pytest.mark.parametrize("scope", ["qkv", "ffn", "short_conv"])
def test_the_scope_reader_on_the_recorded_engine_trace(scope, monkeypatch):
    """``tiny-qwen3``'s trace has ``qkv`` and ``ffn`` and no
    ``short_conv``: the first two read what the class reader reads for the
    class the table gives that scope, the third reads 0.0."""
    monkeypatch.setattr(
        xplane_meta, "find_trace", lambda: os.path.join(DATA, "engine_trace.xplane.pb")
    )
    xplane_meta._CACHE.clear()
    ctx = {"trace": {"ops_s": {}}}
    scoped = importlib.import_module("readers.trace_scope_share")
    got = scoped.read({"scope": f"(^|/){scope}(/|$)"}, ctx)
    if scope == "short_conv":
        spec = spec_of("short_conv_time_share")
        assert scoped.read(spec, ctx) == 0.0 == got
    else:
        assert 0.0 < got < 100.0
    assert scoped.read({"scope": scope}, {"trace": None}) is None
    xplane_meta._CACHE.clear()


def test_the_restore_guard_reads_the_counters_delta():
    spec = spec_of("seq_state_restores_in_window")
    key = lambda cause: ("dynamo_seq_state_restores_total", (("cause", cause),))
    before = {key("prefix"): 2.0}
    after = {key("prefix"): 5.0, key("resume"): 1.0}
    assert reader(spec).read(spec, {"prom_before": before, "prom_after": after}) == 4.0
    assert reader(spec).read(spec, {"prom_before": {}, "prom_after": {}}) == 0.0
