"""What ``mimo-v2-flash.agent-longout-saturate`` adds beside its comparison:
the configuration's file against the catalog's row, the cell's MEMBERSHIP in
``BENCHMARK.json``'s lists (not its place in them: a later PR appends behind
it), the new reader ``trace_attn_roofline_by_kind`` (with
``lib/attn_calls_by_kind.py`` at this model's shapes by form and
hand-computed shares), the accepted readers the other new metrics are data
for, and the traffic through ``closed_loop``. Each reader says nothing
without its trace or its series (the parent)."""

import importlib
import json
import os
import re

import pytest

from lib import attn_calls_by_kind, xplane_meta
from test_expert_calls import PEAK
from test_pangu_readers import counters
from test_state_space import on  # noqa: F401  (the fixture: peaks and the trace's path)

BENCH = xplane_meta.HERE
CELL = "mimo-v2-flash.agent-longout-saturate"
CONFIG = "mimo-v2-flash-l11e32"
NEW = (
    "mimo_swa_attn_roofline_share", "mimo_full_attn_roofline_share",
    "mimo_expert_gmm_roofline_share", "full_pool_token_bytes",
)
APPENDED = (
    "moe_time_share", "moe_pad_row_share", "window_dispatch_mean_ms",
    "window_kernel_dispatch_share", "decode_window_rows_mean",
    "expert_inplace_dispatch_share", "moe_held_pick_share", "swa_attn_time_share",
    "kv_sliding_pool_occupancy_mean", "kv_resident_bytes_per_token", "attn_row_behind_share",
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
        "num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq", "n_routed_experts",
        "vocab_size",
    ]
    assert config["num_hidden_layers"] == 11
    assert config["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 0, 1, 1, 1, 1, 1]
    assert config["moe_layer_freq"] == [0] + [1] * 10
    assert (config["n_routed_experts"], config["n_routed_experts_global"]) == (8, 256)
    assert config["vocab_size"] == 152576 // 8 == 19072
    assert (config["comparison"], config["reference"]) == ("attention_by_kind", "mimo_v2_flash")
    assert config["published"]["n_routed_experts"] == 256
    for key in ("rotary", "attention_value_scale", "sliding_window", "sink", "qk_norm",
                "norm_placement", "router", "multi_token_prediction", "weights", "tokenizer",
                "chat_template", "torch_dtype", "n_routed_experts_global", "tensor_names"):
        assert key in config["assumed"], key
    for phrase in ("stages of 32 chips", "3,409,017,920", "1/32", "layers 0-10"):
        assert phrase in config["deployment"], phrase
    for phrase in ("5,120 B", "sliding", "192"):
        assert phrase in config["num_pages_arithmetic"], phrase
    assert config["serve_args"] == [
        "--max-decode-slots", "64", "--page-size", "16", "--max-model-len", "20480",
        "--tp", "1", "--num-pages", "32768",
    ]
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "MiMo-V2-Flash")
    assert config["source"] == row["source_url"]
    cut = {"hybrid_layer_pattern", "moe_layer_freq"}
    for key, value in row["config"].items():
        if key in cut:  # the first eleven layers' entries of the row's list
            assert config[key] == value[:11], key
        elif key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key


def test_the_cell_and_its_metrics_are_members_of_the_lists():
    bench = load("..", "BENCHMARK.json")
    configs = {c["name"]: c for c in bench["configs"]}
    assert configs[CONFIG]["file"] == f"benchmark/configs/{CONFIG}.json"
    assert configs[CONFIG]["reduced"] == load("configs", f"{CONFIG}.json")["reduced"]
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL] == {
        **cells[CELL], "config": CONFIG, "traffic": "agent-longout-saturate", "chips": 1,
    }
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "output_tok_s", name
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", f"{name}.json"))
    for name in APPENDED:
        assert CELL in by_name[name]["workloads"], name
    # readers that name another configuration are not this cell's
    for name in ("swa_attn_roofline_share", "full_attn_roofline_share",
                 "exaone_expert_gmm_roofline_share"):
        assert CELL not in by_name[name]["workloads"], name
    for w in (cells[CELL], configs[CONFIG]):
        assert len(w["why"]) <= 200
    cell = load("workloads", f"{CELL}.json")
    assert (cell["clients"], cell["drain_s"]) == (64, 120)


# ------------------------------------------------------------ the readers
def test_the_work_is_the_models_at_its_widths_by_form():
    hf = load("configs", f"{CONFIG}.json")
    calls = attn_calls_by_kind
    assert calls.shape(hf, "full") == (64, 4, 192, 128)
    assert calls.shape(hf, "sliding") == (64, 8, 192, 128)
    # a visible pair and query head: the score over 192 and the value sum over 128
    assert calls.operations(1, hf, "full") == calls.operations(1, hf, "sliding") == 2 * 320 * 64
    # a context token of one layer: 4 x 320 values full, 8 x 320 sliding; a
    # query: its row in at 64 x 192 and its result out at 64 x 128
    assert calls.bytes_moved(1, 0, hf, "full") == 2 * 4 * 320 == 2560
    assert calls.bytes_moved(1, 0, hf, "sliding") == 5120
    assert calls.bytes_moved(0, 1, hf, "full") == 2 * 64 * 320
    # a decode step of 64 rows at 5 k of context, the 2 full layers: HBM-bound
    least, bound = calls.least_seconds(2 * 64 * 5000, 2 * 64 * 5000, 2 * 64, hf, PEAK, "full")
    assert bound == "hbm" and least == pytest.approx(
        (2 * 64 * 5000 * 2560 + 128 * 40960) / (PEAK["hbm_gbps"] * 1e9)
    )
    # a chunk of 2,048 queries of one row at 8 k: compute-bound
    assert calls.least_seconds(2048 * 7000, 8192, 2048, hf, PEAK, "full")[1] == "bf16"
    # a configuration without the sliding key or the value width reads the plain ones
    plain = {"head_dim": 128, "num_key_value_heads": 8, "num_attention_heads": 64}
    assert calls.shape(plain, "sliding") == (64, 8, 128, 128)


def _ctx(keys: float):
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
    spec = spec_of(f"mimo_{'swa' if form == 'sliding' else 'full'}_attn_roofline_share")
    assert (spec["kind"], spec["config"], spec["form"]) == (
        "trace_attn_roofline_by_kind", CONFIG, form)
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
    ctx = _ctx(keys)
    share = {"sliding": 0.1, "full": 0.3}[form]
    took = share * 0.8 * 50.0
    args = {"sliding": (keys, keys / 100, keys / 120), "full": (10 * keys, keys / 10, keys / 360)}
    least = attn_calls_by_kind.least_seconds(*args[form], hf, PEAK, form)[0]
    got = read(spec, ctx)
    assert got == pytest.approx(least / took * 100.0) and 0 < got < 100
    assert read(spec, {"trace": None}) is None
    parent = dict(ctx)
    parent["prom_after"] = {k: v for k, v in ctx["prom_after"].items() if "attn" not in k[0]}
    assert read(spec, parent) is None  # the parent: no such counter
    monkeypatch.setattr(xplane_meta, "current", lambda: {"ops": ops[2:]})
    assert read(spec, ctx) is None  # no such call


def test_the_accepted_readers_the_other_metrics_are_data_for():
    spec = spec_of("mimo_expert_gmm_roofline_share")
    assert spec == {**spec_of("exaone_expert_gmm_roofline_share"), "config": CONFIG}
    config = load("configs", f"{CONFIG}.json")
    assert (config["hidden_size"], config[spec["width_key"]]) == (4096, 2048)
    spec = spec_of("full_pool_token_bytes")
    assert spec == spec_of("latent_cache_bytes_per_token")
    after = counters(**{"dynamo_kv_pool_info__token_bytes_5120": 1.0})
    assert reader(spec).read(spec, {"prom_after": after}) == 5120.0
    assert reader(spec).read(spec, {"prom_after": {}}) is None


def test_the_program_names_the_scope_kernel_and_series_the_metrics_read():
    import inspect

    from dynamo_exp_tpu.models import llama
    from dynamo_exp_tpu.telemetry import spans

    assert 'jax.named_scope("swa_attn")' in inspect.getsource(llama)
    assert re.search(spec_of("mimo_swa_attn_roofline_share")["pattern"], "ragged_paged_attention.12")
    text = inspect.getsource(spans)
    for series in ("dynamo_attn_key_positions_total", "dynamo_attn_context_tokens_total",
                   "dynamo_attn_query_tokens_total", "dynamo_kv_pool_info",
                   "dynamo_kv_sliding_pool_info", "dynamo_attn_form_info"):
        assert f'"{series}"' in text, series


# ------------------------------------------------------------- the traffic
def test_the_traffic_is_the_issues_letter_for_letter():
    from test_traffic import load as load_traffic
    from test_traffic import plan

    traffic = load("traffic", "agent-longout-saturate.json")
    assert traffic["generator"] == "closed_loop" and traffic["lead_in_s"] == 15
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 3072, "sigma": 1.0, "min": 256, "max": 16384,
    }
    assert traffic["output_tokens"] == {
        "dist": "lognormal", "median": 768, "sigma": 0.6, "min": 128, "max": 1536,
    }
    assert traffic["schedule_seed"] == 23 and traffic["requests_per_client_s"] == 0.25
    mine = load_traffic("workloads", CELL)
    a = plan("closed_loop", "agent-longout-saturate", mine, 1, seconds=51.0)
    assert len(a["chains"]) == 64 and all(len(c["requests"]) >= 16 for c in a["chains"])
    lengths = [r["max_tokens"] for c in a["chains"] for r in c["requests"]]
    assert min(lengths) >= 128 and max(lengths) <= 1536 and 700 < sum(lengths) / len(lengths) < 1000
    # every run is offered the same lengths, whatever its seed
    b = plan("closed_loop", "agent-longout-saturate", mine, 2**31 + 5, seconds=51.0)
    assert [[r["max_tokens"] for r in c["requests"]] for c in a["chains"]] == [
        [r["max_tokens"] for r in c["requests"]] for c in b["chains"]
    ]
