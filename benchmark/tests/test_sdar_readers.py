"""The seven per-layer metrics ``sdar-30b-a3b.fixedlen-saturate`` adds,
each a data file over a reader that was there: the counters' ratios on
hand-made scrapes; the keyed roofline and slab-copy readers on a synthetic
trace at this model's shapes (128 experts of [2048, 768], 2048 sorted rows)
with hand-computed shares; the program and scope readers on a recorded
trace of the block program (``rehearse/record_block_trace.py``, a tiny
``sdar_moe`` on the chip). Each says nothing without a trace."""

import importlib
import json
import os

import pytest

from lib import xplane_meta
from test_expert_calls import OTHER, PEAK, write_trace
from test_expert_gmm_readers import META

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "sdar-30b-a3b.fixedlen-saturate"
NEW = (
    "denoise_passes_per_block", "block_write_pass_share", "block_dispatch_mean_ms",
    "block_program_time_share", "denoise_select_time_share",
    "sdar_expert_gmm_roofline_share", "sdar_expert_slab_copy_time_share",
)
GMM_UP = (
    "%expert_gmm.3 = bf16[2048,768]{1,0:T(8,128)(2,1)} custom-call(" + META + ", "
    "bf16[2048,2048]{1,0:T(8,128)(2,1)} %fusion.5, bf16[6,128,2048,768]{3,2,1,0:T(8,128)(2,1)} "
    '%get-tuple-element.51), custom_call_target="tpu_custom_call"'
)
LAYER_COPY = "%copy.5 = bf16[128,2048,768]{2,1,0} copy(bf16[128,2048,768]{2,1,0} %dynamic-slice.3)"
DOWN_COPY = "%copy.6 = bf16[128,768,2048]{2,1,0} copy(bf16[128,768,2048]{2,1,0} %dynamic-slice.4)"
STACK_VIEW = "%bitcast.7 = bf16[6,128,2048,768]{3,2,1,0} bitcast(bf16[768,2048,768]{2,1,0} %p.1)"
LFM2_SLAB = "%copy.2 = bf16[32,2048,1792]{2,1,0} copy(bf16[32,2048,1792]{2,1,0} %x)"


def spec_of(metric: str) -> dict:
    with open(os.path.join(xplane_meta.HERE, "layer_metrics", f"{metric}.json")) as f:
        return json.load(f)["reader"]


def reader(spec: dict):
    return importlib.import_module(f"readers.{spec['kind']}")


def test_the_cell_lists_its_metrics_and_every_reader_was_there():
    with open(os.path.join(os.path.dirname(xplane_meta.HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL], name
        assert os.path.exists(
            os.path.join(xplane_meta.HERE, "readers", f"{spec_of(name)['kind']}.py"))
    for name in ("moe_time_share", "moe_pad_row_share", "expert_inplace_dispatch_share"):
        assert listed[name]["workloads"][-1] == CELL
    # A block model never runs a decode window: the three metrics that read
    # program="window" list the cells that do.
    for name in ("window_dispatch_mean_ms", "decode_window_rows_mean",
                 "window_kernel_dispatch_share"):
        assert CELL not in listed[name]["workloads"] and len(listed[name]["workloads"]) == 5


def passes(denoise: float, write: float) -> dict:
    key = lambda kind: ("dynamo_diffusion_passes_total", (("kind", kind),))  # noqa: E731
    return {key("denoise"): denoise, key("write"): write}


def test_the_counters_ratios():
    ctx = {"prom_before": passes(40.0, 10.0), "prom_after": passes(840.0, 210.0)}
    spec = spec_of("denoise_passes_per_block")
    assert reader(spec).read(spec, ctx) == pytest.approx(4.0)
    spec = spec_of("block_write_pass_share")
    assert reader(spec).read(spec, ctx) == pytest.approx(20.0)
    # A trained checkpoint: two passes a block, the write a third of all.
    ctx = {"prom_before": passes(0.0, 0.0), "prom_after": passes(200.0, 100.0)}
    assert reader(spec).read(spec, ctx) == pytest.approx(100 / 3)
    # A program without the counters (the parent): nothing, and no error.
    empty = {"prom_before": {}, "prom_after": {}}
    for name in ("denoise_passes_per_block", "block_write_pass_share", "block_dispatch_mean_ms"):
        spec = spec_of(name)
        assert reader(spec).read(spec, empty) is None


def test_the_block_dispatch_histogram():
    def scrape(n, total, other):
        labels = lambda p: (("kind", "ragged"), ("program", p))  # noqa: E731
        return {
            ("dynamo_dispatch_seconds_count", labels("block")): n,
            ("dynamo_dispatch_seconds_sum", labels("block")): total,
            ("dynamo_dispatch_seconds_count", labels("mixed")): other,
            ("dynamo_dispatch_seconds_sum", labels("mixed")): other * 0.5,
        }

    spec = spec_of("block_dispatch_mean_ms")
    ctx = {"prom_before": scrape(10, 0.6, 3), "prom_after": scrape(110, 6.6, 9)}
    assert reader(spec).read(spec, ctx) == pytest.approx(60.0)


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


def test_the_keyed_roofline_at_2048_rows_over_128_experts(tmp_path, on):
    path = str(tmp_path / "t.xplane.pb")
    # By hand: 128 experts' matrices (num_experts of the file, not the
    # stack's 6 x 128), lhs and result once; HBM-bound at 2048 rows.
    least = (128 * 2048 * 768 * 2 + 2048 * 2048 * 2 + 2048 * 768 * 2) / 819e9
    assert least > 2 * 2048 * 2048 * 768 / 197e12
    dur = int(least / 0.8 * 1e9)  # each call at four fifths of its roofline
    write_trace(path, [(OTHER, 0, 50_000), (GMM_UP, 60_000, dur), (GMM_UP, 70_000 + dur, dur)])
    on(path)
    spec = spec_of("sdar_expert_gmm_roofline_share")
    assert reader(spec).read(spec, {"trace": {"ops_s": {}}}) == pytest.approx(80.0, rel=1e-3)
    write_trace(path, [(OTHER, 0, 50_000)])
    on(path)
    assert reader(spec).read(spec, {"trace": {"ops_s": {}}}) is None
    assert reader(spec).read(spec, {"trace": None}) is None


def test_the_keyed_slab_reader_finds_this_models_copies(tmp_path, on):
    path = str(tmp_path / "t.xplane.pb")
    events, at = [], 0
    for name, ns in (
        (LAYER_COPY, 500_000), (STACK_VIEW, 0), (OTHER, 100_000), (GMM_UP, 600_000),
        (DOWN_COPY, 500_000), (LFM2_SLAB, 300_000),
    ):
        events.append((name, at, ns))
        at += ns + 10
    write_trace(path, events)
    on(path)
    spec = spec_of("sdar_expert_slab_copy_time_share")
    ctx = {"trace": {"ops_s": {}}}
    assert reader(spec).read(spec, ctx) == pytest.approx(1_000_000 / 2_000_000 * 100.0)
    with open(os.path.join(xplane_meta.HERE, "configs", f"{spec['config']}.json")) as f:
        shapes = reader(spec).slab_shapes(json.load(f), spec)
    assert shapes == {
        (128, 2048, 768), (128, 768, 2048), (6, 128, 2048, 768), (6, 128, 768, 2048),
    }
    write_trace(path, [(GMM_UP, 0, 300_000), (OTHER, 300_010, 100_000)])
    on(path)
    assert reader(spec).read(spec, ctx) == 0.0  # none left: 0.0, not None
    assert reader(spec).read(spec, {"trace": None}) is None


@pytest.fixture()
def block_trace(monkeypatch):
    monkeypatch.setattr(
        xplane_meta, "find_trace", lambda: os.path.join(DATA, "block_trace.xplane.pb"))
    xplane_meta._CACHE.clear()
    yield {"trace": {"ops_s": {}}}
    xplane_meta._CACHE.clear()


def test_the_program_and_scope_readers_on_the_recorded_block_trace(block_trace):
    """The recording holds mixed dispatches and chained block dispatches:
    most of the busy time is the block program's; the confidence rule is
    there and is not all of it; the two programs' shares do not overlap."""
    spec = spec_of("block_program_time_share")
    block = reader(spec).read(spec, block_trace)
    mixed_spec = spec_of("mixed_program_time_share")
    mixed = reader(mixed_spec).read(mixed_spec, block_trace)
    assert 50.0 < block < 100.0 and 0.0 < mixed < 50.0 and block + mixed <= 100.0 + 1e-6
    spec = spec_of("denoise_select_time_share")
    select = reader(spec).read(spec, block_trace)
    assert 0.0 < select < block
    # The routed experts' half is there under its scope, as in every routed model.
    moe = spec_of("moe_time_share")
    assert reader(moe).read(moe, block_trace) > 0.0
    for name in ("block_program_time_share", "denoise_select_time_share"):
        spec = spec_of(name)
        assert reader(spec).read(spec, {"trace": None}) is None


def test_a_trace_without_the_block_program_reads_zero(monkeypatch):
    """``tiny-qwen3``'s recorded trace: no block program, no such scope.
    The parent of PR 37 reads these two so, and nothing raises."""
    monkeypatch.setattr(
        xplane_meta, "find_trace", lambda: os.path.join(DATA, "engine_trace.xplane.pb"))
    xplane_meta._CACHE.clear()
    ctx = {"trace": {"ops_s": {}}}
    for name in ("block_program_time_share", "denoise_select_time_share"):
        spec = spec_of(name)
        assert reader(spec).read(spec, ctx) == 0.0
    xplane_meta._CACHE.clear()
