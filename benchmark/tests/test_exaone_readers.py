"""What ``k-exaone-236b.mixedlen-saturate`` adds beside its comparison: the
configuration's file against the catalog's row, the cell's lists in
``BENCHMARK.json``, the new readers ``trace_attn_roofline_by_form`` (with
``lib/attn_calls.py`` at this model's shapes and hand-computed shares) and
``prom_gauge_ratio_mean``, the accepted readers the other new metrics are
data for, the scope metric's expression, and the traffic through
``closed_loop``. Each reader says nothing without its trace or its series
(the parent)."""

import importlib
import json
import os
import re

import pytest

from lib import attn_calls, xplane_meta
from test_expert_calls import PEAK
from test_pangu_readers import counters
from test_state_space import on  # noqa: F401  (the fixture: peaks and the trace's path)

BENCH = xplane_meta.HERE
CELL = "k-exaone-236b.mixedlen-saturate"
CONFIG = "k-exaone-236b-l8e16"
NEW = (
    "swa_attn_time_share", "swa_attn_roofline_share", "full_attn_roofline_share",
    "kv_sliding_pool_occupancy_mean", "kv_resident_bytes_per_token",
    "exaone_expert_gmm_roofline_share",
)
APPENDED = (
    "moe_time_share", "moe_pad_row_share", "expert_inplace_dispatch_share",
    "moe_held_pick_share", "window_dispatch_mean_ms", "decode_window_rows_mean",
    "window_kernel_dispatch_share",
)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def spec_of(metric: str) -> dict:
    return load("layer_metrics", f"{metric}.json")["reader"]


def reader(spec: dict):
    return importlib.import_module(f"readers.{spec['kind']}")


# ------------------------------------------------------ the configuration
def test_the_file_is_the_catalogs_row_but_for_its_share():
    config = load("configs", f"{CONFIG}.json")
    assert config["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types", "sliding_windows",
        "num_experts", "vocab_size", "num_nextn_predict_layers",
    ]
    assert config["num_hidden_layers"] == 8 and config["first_k_dense_replace"] == 1
    assert config["layer_types"] == ["sliding_attention"] * 3 + ["full_attention"] + [
        "sliding_attention"] * 3 + ["full_attention"]
    assert config["sliding_windows"] == [128, 128, 128, 0, 128, 128, 128, 0]
    assert (config["num_experts"], config["num_experts_global"]) == (8, 128)
    assert config["vocab_size"] == 153600 // 8 and config["num_nextn_predict_layers"] == 0
    assert (config["comparison"], config["reference"]) == ("sliding_attention", "exaone_moe")
    assert config["published"]["num_experts"] == 128
    for key in ("norm_placement", "rotary", "router", "multi_token_prediction", "weights",
                "tokenizer", "chat_template", "torch_dtype", "num_experts_global", "tensor_names"):
        assert key in config["assumed"], key
    for phrase in ("stages of 16 chips", "3,865,420,672", "one sixteenth"):
        assert phrase in config["deployment"], phrase
    for phrase in ("8,192 B", "sliding"):
        assert phrase in config["num_pages_arithmetic"], phrase
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "K-EXAONE-236B-A23B")
    assert config["source"] == row["source_url"]
    cut = {"layer_types", "mlp_layer_types", "sliding_windows"}
    for key, value in row["config"].items():
        if key in cut:  # the first eight layers' entries of the row's list
            assert config[key] == value[:8], key
        elif key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key


def test_the_cell_and_its_metrics_are_appended_entries():
    bench = load("..", "BENCHMARK.json")
    assert bench["configs"][-1]["name"] == CONFIG
    assert bench["workloads"][-1] == {
        **bench["workloads"][-1], "name": CELL, "config": CONFIG,
        "traffic": "mixedlen-saturate", "chips": 1,
    }
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-len(NEW):]] == list(NEW)
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL], name
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", f"{name}.json"))
    for name in APPENDED:
        assert by_name[name]["workloads"][-1] == CELL, name
    assert by_name["exaone_expert_gmm_roofline_share"]["moves"] == "tpot_p95_ms"
    cell = load("workloads", f"{CELL}.json")
    assert (cell["clients"], cell["drain_s"]) == (32, 120)


# ------------------------------------------------------------ the readers
def test_the_work_is_the_models_at_its_widths():
    hf = load("configs", f"{CONFIG}.json")
    assert attn_calls.operations(1, hf) == 4 * 128 * 64
    # a context token: K and V of 8 heads of 128; a query: its row in and out
    assert attn_calls.bytes_moved(1, 0, hf) == 4096 and attn_calls.bytes_moved(0, 1, hf) == 32768
    # a decode step of 32 rows at 8 k context, the 2 full layers: HBM-bound
    least, bound = attn_calls.least_seconds(2 * 32 * 8192, 2 * 32 * 8192, 2 * 32, hf, PEAK)
    assert bound == "hbm" and least == pytest.approx(
        (2 * 32 * 8192 * 4096 + 64 * 32768) / (PEAK["hbm_gbps"] * 1e9)
    )
    # a chunk of 2,048 queries of one row at 8 k: compute-bound
    assert attn_calls.least_seconds(2048 * 7000, 8192, 2048, hf, PEAK)[1] == "bf16"


def _ctx(kernel_scope: str, share: float, keys: float):
    before = counters(dynamo_engine_phase_seconds_total__phase_sync=10.0)
    after = counters(
        dynamo_attn_key_positions_total__kind_sliding=keys,
        dynamo_attn_context_tokens_total__kind_sliding=keys / 100,
        dynamo_attn_query_tokens_total__kind_sliding=keys / 120,
        dynamo_attn_key_positions_total__kind_full=10 * keys,
        dynamo_attn_context_tokens_total__kind_full=keys / 10,
        dynamo_attn_query_tokens_total__kind_full=keys / 360,
        dynamo_engine_phase_seconds_total__phase_sync=40.0,
        dynamo_engine_phase_seconds_total__phase_launch=20.0,
    )
    trace = {"busy_s": 3.2, "window_s": 4.0, "ops_s": {"x": 1.0}}
    return {"trace": trace, "prom_before": before, "prom_after": after}


@pytest.mark.parametrize("form", ["sliding", "full"])
def test_the_roofline_reader_tells_the_forms_apart_by_the_scope(on, monkeypatch, form):  # noqa: F811
    spec = spec_of(f"{'swa' if form == 'sliding' else 'full'}_attn_roofline_share")
    assert (spec["kind"], spec["config"], spec["form"]) == (
        "trace_attn_roofline_by_form", CONFIG, form)
    read = reader(spec).read
    Op = xplane_meta.Op
    inside = "jit(ragged_mixed)/while/body/attend/swa_attn/pallas_call"
    outside = "jit(ragged_mixed)/while/body/attend/pallas_call"
    ops = [
        Op(0, 100, "ragged_paged_attention.3", "custom-call", "", inside, "jit_ragged_mixed"),
        Op(100, 300, "ragged_paged_attention.4", "custom-call", "", outside, "jit_ragged_mixed"),
        Op(400, 600, "fusion.9", "fusion", "", "jit(ragged_mixed)/moe", "jit_ragged_mixed"),
    ]
    monkeypatch.setattr(xplane_meta, "current", lambda: {"ops": ops})
    monkeypatch.setattr(
        xplane_meta, "time_by",
        lambda meta, key: {k: sum(o.dur for o in meta["ops"] if key(o) == k)
                           for k in {key(o) for o in meta["ops"]}},
    )
    hf = load("configs", f"{CONFIG}.json")
    keys = 3.0e9
    ctx = _ctx("", 0.0, keys)
    share = {"sliding": 0.1, "full": 0.3}[form]
    took = share * 0.8 * 50.0
    args = {"sliding": (keys, keys / 100, keys / 120), "full": (10 * keys, keys / 10, keys / 360)}
    least = attn_calls.least_seconds(*args[form], hf, PEAK)[0]
    got = read(spec, ctx)
    assert got == pytest.approx(least / took * 100.0) and 0 < got < 100
    assert read(spec, {"trace": None}) is None
    parent = dict(ctx)
    parent["prom_after"] = {k: v for k, v in ctx["prom_after"].items() if "attn" not in k[0]}
    assert read(spec, parent) is None  # the parent: no such counter
    monkeypatch.setattr(xplane_meta, "current", lambda: {"ops": ops[2:]})
    assert read(spec, ctx) is None  # no such call


def test_resident_bytes_a_token_is_a_mean_of_ratios():
    spec = spec_of("kv_resident_bytes_per_token")
    read = reader(spec).read

    def scrape(full, sliding, tokens):
        return counters(
            dynamo_kv_pool_held_bytes__pool_full=full,
            dynamo_kv_pool_held_bytes__pool_sliding=sliding,
            dynamo_kv_context_tokens=tokens,
        )

    ctx = {"gauge_scrapes": [scrape(8e8, 1e8, 1e5), scrape(0.0, 0.0, 0.0), scrape(4e8, 1e8, 5e4)]}
    assert read(spec, ctx) == pytest.approx((9000 + 10000) / 2)
    assert read(spec, {"gauge_scrapes": [counters(dynamo_kv_pool_info__layers_36=1.0)]}) is None
    occupancy = spec_of("kv_sliding_pool_occupancy_mean")
    got = reader(occupancy).read(occupancy, {"gauge_scrapes": [
        counters(dynamo_kv_pool_usage__pool_sliding=0.25, dynamo_kv_pool_usage__pool_full=0.9),
        counters(dynamo_kv_pool_usage__pool_sliding=0.75, dynamo_kv_pool_usage__pool_full=0.9),
    ]})
    assert got == pytest.approx(50.0)


def test_the_held_experts_reader_is_glm5s_on_this_models_widths():
    spec = spec_of("exaone_expert_gmm_roofline_share")
    assert spec == {**spec_of("glm5_expert_gmm_roofline_share"), "config": CONFIG}
    config = load("configs", f"{CONFIG}.json")
    assert (config["hidden_size"], config[spec["width_key"]]) == (6144, 2048)


@pytest.mark.parametrize("scope,hit", [
    ("jit(ragged_mixed)/while/body/attend/swa_attn/pallas_call", True),
    ("jit(ragged_window)/while/body/while/body/attend/swa_attn/ragged_paged_attention", True),
    ("jit(ragged_mixed)/while/body/attend/pallas_call", False),
    ("jit(ragged_mixed)/while/body/kv_write/scatter", False),
])
def test_the_scope_metric_finds_the_sliding_calls(scope, hit):
    spec = spec_of("swa_attn_time_share")
    assert spec["kind"] == "trace_scope_share"
    assert bool(re.search(spec["scope"], scope)) is hit


def test_the_program_names_the_scope_kernel_and_series_the_metrics_read():
    import inspect

    from dynamo_exp_tpu.models import llama
    from dynamo_exp_tpu.ops import ragged_attention
    from dynamo_exp_tpu.telemetry import spans

    assert 'jax.named_scope("swa_attn")' in inspect.getsource(llama)
    assert re.search(spec_of("swa_attn_roofline_share")["pattern"], "ragged_paged_attention.12")
    assert "def ragged_paged_attention(" in inspect.getsource(ragged_attention)
    text = inspect.getsource(spans)
    for series in ("dynamo_attn_key_positions_total", "dynamo_attn_context_tokens_total",
                   "dynamo_attn_query_tokens_total", "dynamo_kv_pool_held_bytes",
                   "dynamo_kv_context_tokens", "dynamo_kv_pool_usage",
                   "dynamo_kv_sliding_pool_info", "dynamo_kv_sliding_pages_released_total"):
        assert f'"{series}"' in text, series


# ------------------------------------------------------------- the traffic
def test_the_traffic_is_the_issues_letter_for_letter():
    from test_traffic import load as load_traffic
    from test_traffic import plan

    traffic = load("traffic", "mixedlen-saturate.json")
    assert traffic["generator"] == "closed_loop" and traffic["lead_in_s"] == 15
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 2048, "sigma": 1.3, "min": 64, "max": 30720,
    }
    assert traffic["output_tokens"] == {
        "dist": "lognormal", "median": 256, "sigma": 0.7, "min": 32, "max": 1024,
    }
    assert traffic["schedule_seed"] == 23
    mine = load_traffic("workloads", CELL)
    a = plan("closed_loop", "mixedlen-saturate", mine, 1, seconds=51.0)
    assert len(a["chains"]) == 32 and all(len(c["requests"]) >= 16 for c in a["chains"])
    # every run is offered the same lengths, whatever its seed
    b = plan("closed_loop", "mixedlen-saturate", mine, 2**31 + 5, seconds=51.0)
    assert [[r["max_tokens"] for r in c["requests"]] for c in a["chains"]] == [
        [r["max_tokens"] for r in c["requests"]] for c in b["chains"]
    ]
