"""What ``glm-5.longctx-saturate`` adds beside its comparison: the
configuration's file against the catalog's row, the parameter count and the
two pools, the cell's lists (wherever they stand in ``BENCHMARK.json``),
the new reader ``trace_dsa_index_roofline`` with ``lib/dsa_index_calls.py``
at this model's shapes with hand-computed shares, the accepted readers the
other new metrics are data for, the scope metrics' expressions, the
counters' ratios, and the traffic through ``closed_loop``. Each reader says
nothing without its trace or its counters (the parent)."""

import importlib
import json
import os
import re

import pytest

from lib import dsa_index_calls, mla_attn_calls, xplane_meta
from test_expert_calls import PEAK
from test_pangu_readers import counters
from test_state_space import on  # noqa: F401  (the fixture: peaks and the trace's path)

BENCH = xplane_meta.HERE
CELL = "glm-5.longctx-saturate"
CONFIG = "glm-5-l5e16"
NEW = (
    "dsa_index_time_share", "dsa_select_time_share", "dsa_selecting_token_share",
    "dsa_kept_pair_share", "dsa_index_roofline_share", "dsa_attn_roofline_share",
    "glm5_expert_gmm_roofline_share",
)
APPENDED = (
    "moe_time_share", "moe_pad_row_share", "expert_inplace_dispatch_share",
    "window_dispatch_mean_ms", "decode_window_rows_mean", "window_kernel_dispatch_share",
    "mla_block_time_share", "mla_absorb_time_share", "mla_expand_time_share",
    "mla_expanded_token_share", "moe_held_pick_share", "latent_cache_bytes_per_token",
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
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers",
    ]
    assert (config["num_hidden_layers"], config["first_k_dense_replace"]) == (5, 1)
    assert (config["n_routed_experts"], config["n_routed_experts_global"]) == (16, 256)
    assert config["vocab_size"] == 154880 // 8 and config["num_nextn_predict_layers"] == 0
    assert (config["comparison"], config["reference"]) == ("sparse_attention", "glm_moe_dsa")
    assert config["published"]["n_routed_experts"] == 256
    for key in ("indexer", "hadamard_and_fp8", "rotary", "latent_attention", "router",
                "multi_token_prediction", "weights", "tokenizer", "chat_template",
                "torch_dtype", "n_routed_experts_global", "tensor_names"):
        assert key in config["assumed"], key
    for phrase in ("stages of 16 chips", "3,909,632,768", "one sixteenth"):
        assert phrase in config["deployment"], phrase
    assert "7,680 B" in config["num_pages_arithmetic"]
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key


def test_the_parameter_count_and_the_two_pools_are_eval_shapes():
    import jax
    import numpy as np

    import run_cell
    from dynamo_exp_tpu.models import ModelConfig
    from dynamo_exp_tpu.models.llama import init_kv_cache, init_params

    config = load("configs", f"{CONFIG}.json")
    mcfg = ModelConfig.from_hf_config(run_cell.hf_config(config))
    assert mcfg.rope_theta == 1e6 and mcfg.index_topk == 2048
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), mcfg))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert count == 3_909_632_768 and f"{count:,}" in config["deployment"]
    pool, keys = jax.eval_shape(lambda: init_kv_cache(mcfg, 8, 16))
    assert pool.shape == (5, 8, 16, 640) and keys.shape == (5, 8, 16, 128)
    assert (pool.size + keys.size) * 2 // (8 * 16) == 7680


def test_the_cell_lists_its_metrics_wherever_it_stands():
    bench = load(os.pardir, "BENCHMARK.json")
    (config,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "longctx-saturate", 1)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL], name
        assert os.path.exists(os.path.join(BENCH, "readers", f"{spec_of(name)['kind']}.py"))
    for name in APPENDED:
        assert CELL in listed[name]["workloads"], name
    # No recurrence, no block pass, nobody else's experts, and no roofline keyed
    # to another configuration's widths.
    for name, m in listed.items():
        if name.startswith(("ssm_", "mamba_", "gdn_", "seq_state", "block_", "denoise_",
                            "lfm2_", "sdar_", "pangu_", "expert_gmm", "expert_dot",
                            "expert_slab", "expert_weight", "ttft_", "queue_wait",
                            "mla_attn_roofline")):
            assert CELL not in m.get("workloads", [CELL]), name
    file = load("workloads", f"{CELL}.json")
    assert (file["config"], file["traffic"], file["clients"], file["drain_s"]) == (
        CONFIG, "longctx-saturate", 32, 120,
    )
    for phrase in ("one sixteenth", "16 x its share", "32 k-128 k"):
        assert phrase in file["why"]
    args = load("configs", f"{CONFIG}.json")["serve_args"]
    assert args[args.index("--max-decode-slots") + 1] == "32"  # a slot a client


# ----------------------------------------------------- the indexer's work
def test_the_indexers_operations_and_bytes():
    hf = load("configs", f"{CONFIG}.json")
    assert dsa_index_calls.operations(1, hf) == 2 * 32 * 128 * 5
    assert dsa_index_calls.bytes_moved(0, 1, 0, hf, 256) == 5 * 256
    assert dsa_index_calls.bytes_moved(0, 0, 1, hf, 256) == 5 * (32 * 128 * 2 + 32 * 4)
    assert dsa_index_calls.bytes_moved(1, 0, 0, hf, 256) == 5 * 4
    # a chunk of 2048 queries at a mean context of 5000: the matrix unit's
    pairs = 2048 * 5000
    seconds, bound = dsa_index_calls.least_seconds(pairs, 6000, 2048, hf, 256, PEAK)
    assert bound == "bf16" and seconds == pytest.approx(pairs * 8192 * 5 / 197e12)
    # 32 decode rows over 9000 index keys each: HBM's
    seconds, bound = dsa_index_calls.least_seconds(32 * 9000, 32 * 9000, 32, hf, 256, PEAK)
    assert bound == "hbm"


def _ctx(kernel: str, share: float, pairs: float, kept: float):
    before = counters(dynamo_engine_phase_seconds_total__phase_sync=10.0)
    after = counters(
        dynamo_dsa_index_pairs_total__program_mixed=pairs,
        dynamo_dsa_selected_pairs_total__program_mixed=kept,
        dynamo_mla_attended_pairs_total__program_mixed=kept,
        dynamo_mla_context_tokens_total__program_mixed=pairs / 500,
        dynamo_mla_query_tokens_total__program_mixed__form_absorbed=pairs / 5000,
        dynamo_dsa_query_tokens_total__program_mixed__selecting_selecting=770.0,
        dynamo_dsa_query_tokens_total__program_mixed__selecting_all=200.0,
        dynamo_dsa_query_tokens_total__program_window__selecting_selecting=30.0,
        dynamo_engine_phase_seconds_total__phase_sync=40.0,
        dynamo_engine_phase_seconds_total__phase_launch=20.0,
        dynamo_kv_pool_info__layers_5__token_bytes_7680__index_width_128=1.0,
    )
    trace = {"busy_s": 3.2, "window_s": 4.0, "ops_s": {kernel: share, "fusion.1": 1.0 - share}}
    return {"trace": trace, "prom_before": before, "prom_after": after}


@pytest.mark.parametrize("kernel", ["dsa_index_scores.3", "index_scores"])
def test_the_index_roofline_reader_takes_both_sides_from_the_window(on, kernel):  # noqa: F811
    spec = spec_of("dsa_index_roofline_share")
    assert spec["config"] == CONFIG
    read = reader(spec).read
    hf = load("configs", f"{CONFIG}.json")
    pairs = 4.0e9
    least = dsa_index_calls.least_seconds(pairs, pairs / 500, pairs / 5000, hf, 256, PEAK)[0]
    took = 0.25 * 0.8 * 50.0  # a quarter of busy time, 80 % busy, 50 s of loop
    got = read(spec, _ctx(kernel, 0.25, pairs, pairs * 0.35))
    assert got == pytest.approx(least / took * 100.0) and 0 < got < 100
    assert read(spec, {"trace": None}) is None
    parent = _ctx(kernel, 0.25, pairs, pairs * 0.35)
    parent["prom_after"] = {k: v for k, v in parent["prom_after"].items() if "dsa" not in k[0]}
    assert read(spec, parent) is None  # the parent: no such counter
    assert read(spec, _ctx("ragged_paged_attention.2", 0.25, pairs, pairs)) is None  # no such call


def test_the_attention_roofline_counts_the_selected_pairs_in_the_published_form(on):  # noqa: F811
    spec = spec_of("dsa_attn_roofline_share")
    assert (spec["kind"], spec["config"]) == ("trace_mla_attn_roofline", CONFIG)
    hf = load("configs", f"{CONFIG}.json")
    assert mla_attn_calls.operations(1, hf) == 2 * (192 + 64 + 256) * 64 * 5
    assert mla_attn_calls.lanes(hf) == (640, 512)
    kept = 1.4e9
    got = reader(spec).read(spec, _ctx("ragged_paged_attention.7", 0.5, 4.0e9, kept))
    least = mla_attn_calls.least_seconds(kept, 4.0e9 / 500, 4.0e9 / 5000, hf, 1536, PEAK)[0]
    assert got == pytest.approx(least / (0.5 * 0.8 * 50.0) * 100.0) and 0 < got < 100
    # a masked dense absorbed call at the bf16 peak would read the published
    # form's operations over the selected pairs against the absorbed form's
    # over all of them: 35 % x 1024 / 2304
    assert 0.35 * 2 * (192 + 64 + 256) / (2 * sum(mla_attn_calls.lanes(hf))) == pytest.approx(
        0.1556, abs=1e-3
    )


def test_the_held_experts_reader_is_pangus_on_this_models_widths():
    spec = spec_of("glm5_expert_gmm_roofline_share")
    assert spec == {**spec_of("pangu_expert_gmm_roofline_share"), "config": CONFIG}
    config = load("configs", f"{CONFIG}.json")
    assert (config["hidden_size"], config[spec["width_key"]]) == (6144, 2048)


# --------------------------------------------------- counters and the label
def test_ratios_and_the_pools_label():
    ctx = _ctx("x", 0.5, 1000.0, 350.0)
    selecting = spec_of("dsa_selecting_token_share")
    assert reader(selecting).read(selecting, ctx) == pytest.approx(80.0)
    kept = spec_of("dsa_kept_pair_share")
    assert reader(kept).read(kept, ctx) == pytest.approx(35.0)
    label = spec_of("latent_cache_bytes_per_token")
    assert reader(label).read(label, ctx) == 7680.0
    parent = {"prom_before": {}, "prom_after": counters(dynamo_kv_pool_info__layers_36=1.0)}
    for spec in (selecting, kept):
        assert reader(spec).read(spec, parent) is None


@pytest.mark.parametrize("metric,scope,not_scope", [
    ("dsa_index_time_share", "jit(ragged_mixed)/while/body/closed_call/mla/dsa_index/dot_general",
     "jit(ragged_mixed)/while/body/closed_call/mla/mla_q/dot_general"),
    ("dsa_index_time_share",
     "jit(ragged_mixed)/while/body/mla/attend/cond/branch_1_fun/dsa_index/pallas_call",
     "jit(ragged_mixed)/while/body/mla/attend/cond/branch_1_fun/dsa_select/while/body/reduce_sum"),
    ("dsa_select_time_share",
     "jit(ragged_window)/while/body/mla/cond/branch_1_fun/dsa_select/while/body/reduce_sum",
     "jit(ragged_window)/while/body/mla/dsa_index/kv_write/scatter"),
    ("mla_block_time_share", "jit(ragged_mixed)/while/body/mla/cond/branch_1_fun/dsa_select/ge",
     "jit(ragged_mixed)/while/body/moe/dsa_selected/dot_general"),
])
def test_the_scope_metrics_find_their_scopes(metric, scope, not_scope):
    spec = spec_of(metric)
    assert spec["kind"] == "trace_scope_share"
    assert re.search(spec["scope"], scope) and not re.search(spec["scope"], not_scope)


def test_the_program_names_the_scopes_kernel_and_counters_the_metrics_read():
    import inspect

    from dynamo_exp_tpu.models import llama
    from dynamo_exp_tpu.ops import sparse_index
    from dynamo_exp_tpu.telemetry import spans

    text = inspect.getsource(llama)
    for scope in ("mla", "dsa_index", "dsa_select", "attend", "kv_write"):
        assert f'jax.named_scope("{scope}")' in text, scope
    assert 'name="dsa_index_scores"' in inspect.getsource(sparse_index)
    assert re.search(spec_of("dsa_index_roofline_share")["pattern"], "dsa_index_scores.12")
    text = inspect.getsource(spans)
    for series in ("dynamo_dsa_index_pairs_total", "dynamo_dsa_selected_pairs_total",
                   "dynamo_dsa_query_tokens_total", "dynamo_mla_attended_pairs_total"):
        assert f'"{series}"' in text, series
    assert '"index_width"' in text


# ------------------------------------------------------------- the traffic
def test_the_traffic_is_the_issues_letter_for_letter():
    from test_traffic import load as load_traffic
    from test_traffic import plan

    traffic = load("traffic", "longctx-saturate.json")
    assert traffic["generator"] == "closed_loop" and traffic["lead_in_s"] == 15
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 8192, "sigma": 0.5, "min": 2048, "max": 15360,
    }
    assert traffic["output_tokens"] == {
        "dist": "lognormal", "median": 160, "sigma": 0.5, "min": 64, "max": 512,
    }
    assert (traffic["schedule_seed"], traffic["requests_per_client_s"]) == (23, 0.1)
    mine = load_traffic("workloads", CELL)
    a = plan("closed_loop", "longctx-saturate", mine, 1, seconds=51.0)
    assert len(a["chains"]) == 32 and all(len(c["requests"]) >= 6 for c in a["chains"])
    # every run is offered the same lengths, whatever its seed
    b = plan("closed_loop", "longctx-saturate", mine, 2**31 + 5, seconds=51.0)
    assert [[r["max_tokens"] for r in c["requests"]] for c in a["chains"]] == [
        [r["max_tokens"] for r in c["requests"]] for c in b["chains"]
    ]
