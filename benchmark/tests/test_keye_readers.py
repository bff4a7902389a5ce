"""What ``keye-vl-2.0-30b-a3b.longctx-saturate`` adds beside its comparison:
the configuration's file against the catalog's row, the parameter count and
the row of three things, the cell's lists (wherever they stand in
``BENCHMARK.json``), the new readers ``trace_keye_index_roofline``,
``trace_keye_attn_roofline`` and ``pool_index_key_bytes`` with
``lib/keye_calls.py`` at this model's shapes with hand-computed shares, the
accepted held reader the expert metric is data for, and the traffic through
``closed_loop``. Each reader says nothing without its trace or its counters
(the parent)."""

import importlib
import json
import os
import re

import pytest

from lib import keye_calls, xplane_meta
from test_expert_calls import PEAK
from test_pangu_readers import counters
from test_state_space import on  # noqa: F401  (the fixture: peaks and the trace's path)

BENCH = xplane_meta.HERE
CELL = "keye-vl-2.0-30b-a3b.longctx-saturate"
CONFIG = "keye-vl-2.0-30b-a3b-l5"
NEW = (
    "keye_index_roofline_share", "keye_attn_roofline_share", "keye_expert_gmm_roofline_share",
    "index_key_bytes_per_token",
)
APPENDED = (
    "window_dispatch_mean_ms", "decode_window_rows_mean", "window_kernel_dispatch_share",
    "attn_row_behind_share", "attn_heads_together_share", "moe_time_share",
    "moe_pad_row_share", "expert_inplace_dispatch_share", "dsa_index_time_share",
    "dsa_select_time_share", "dsa_selecting_token_share", "dsa_kept_pair_share",
    "dsa_index_block_slot_share", "kv_resident_bytes_per_token",
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
def test_the_file_is_the_catalogs_row_but_for_its_depth():
    config = load("configs", f"{CONFIG}.json")
    assert config["reduced"] == ["num_hidden_layers"] and config["num_hidden_layers"] == 5
    assert config["published"]["num_hidden_layers"] == 48
    assert (config["comparison"], config["reference"]) == ("sparse_attention_gqa", "keye_vl2")
    for key in ("a_index_queries", "b_index_rotary", "c_chunk_sizes", "d_indexer", "e_qk_norms",
                "f_tensor_names", "mrope", "vision_tower", "weights", "tokenizer",
                "chat_template", "torch_dtype"):
        assert key in config["assumed"], key
    for phrase in ("pipeline stages of one chip each", "3,749,240,704", "N x 8 / 128"):
        assert phrase in config["deployment"], phrase
    assert "11,520 B" in config["num_pages_arithmetic"]
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Keye-VL-2.0-30B-A3B")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key


def test_the_parameter_count_and_the_row_of_three_things_are_eval_shapes():
    import jax
    import numpy as np

    import run_cell
    from dynamo_exp_tpu.models import ModelConfig
    from dynamo_exp_tpu.models.llama import init_kv_cache, init_params

    config = load("configs", f"{CONFIG}.json")
    mcfg = ModelConfig.from_hf_config(run_cell.hf_config(config))
    assert mcfg.rope_theta == 1e7 and mcfg.index_topk == 2048
    assert mcfg.mrope_section == (16, 24, 24) and mcfg.num_experts == 128
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), mcfg))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert count == 3_749_240_704 and f"{count:,}" in config["deployment"]
    a_layer = sum(int(np.prod(x.shape[1:])) for x in shapes["layers"].values())
    assert a_layer == 625_381_760
    k, v = jax.eval_shape(lambda: init_kv_cache(mcfg, 8, 16))
    assert k.shape == (5, 8, 16, 640) and v.shape == (5, 8, 16, 512)
    assert (k.size + v.size) * 2 // (8 * 16) == 11520


def test_the_cell_lists_its_metrics_wherever_it_stands():
    bench = load(os.pardir, "BENCHMARK.json")
    (config,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    assert config["reduced"] == ["num_hidden_layers"]
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "longctx-saturate", 1)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert CELL in listed[name]["workloads"] and listed[name]["moves"] == "output_tok_s", name
        assert os.path.exists(os.path.join(BENCH, "readers", f"{spec_of(name)['kind']}.py"))
    for name in APPENDED:
        assert CELL in listed[name]["workloads"], name
    # No latent pool, no share of experts held, no recurrence, no block pass,
    # and no roofline keyed to another configuration's widths.
    for name, m in listed.items():
        if name.startswith(("mla_", "moe_held", "expert_dot", "expert_weight", "ssm_", "gdn_",
                            "block_", "denoise_", "sdar_", "pangu_", "glm5_", "dsa_attn_roof",
                            "dsa_index_roof", "latent_cache")):
            assert CELL not in m.get("workloads", [CELL]), name
    file = load("workloads", f"{CELL}.json")
    assert (file["config"], file["traffic"], file["clients"], file["drain_s"]) == (
        CONFIG, "longctx-saturate", 32, 120,
    )
    args = load("configs", f"{CONFIG}.json")["serve_args"]
    assert args[args.index("--max-decode-slots") + 1] == "32"  # a slot a client
    assert args[args.index("--max-model-len") + 1] == "16384"


# ------------------------------------------------------ the counted work
def test_the_indexers_and_attentions_operations_and_bytes():
    hf = load("configs", f"{CONFIG}.json")
    assert keye_calls.index_operations(1, hf) == 2 * 16 * 64 * 5
    assert keye_calls.index_bytes(0, 5, 0, hf, 256) == 5 * 256  # a token over 5 layers
    assert keye_calls.index_bytes(0, 0, 5, hf, 256) == 5 * (16 * 64 * 2 + 16 * 4)
    assert keye_calls.index_bytes(1, 0, 0, hf, 256) == 5 * 4
    assert keye_calls.attn_operations(1, hf) == 4 * 128 * 32 * 5
    assert keye_calls.attn_bytes(5, 0, hf) == 5 * 2048  # K and V of 4 x 128 bf16
    assert keye_calls.attn_bytes(0, 1, hf) == 2 * 2 * 32 * 128
    # a chunk of 2048 queries at a mean context of 5000: the matrix unit's
    pairs = 2048 * 5000
    seconds, bound = keye_calls.least_seconds(
        keye_calls.index_operations(pairs, hf),
        keye_calls.index_bytes(pairs, 5 * 6000, 5 * 2048, hf, 256), PEAK,
    )
    assert bound == "bf16" and seconds == pytest.approx(pairs * 2048 * 5 / 197e12)
    # 32 decode rows over 9000 index keys each: HBM's (the keys read once a row)
    seconds, bound = keye_calls.least_seconds(
        keye_calls.index_operations(32 * 9000, hf),
        keye_calls.index_bytes(32 * 9000, 5 * 32 * 9000, 5 * 32, hf, 256), PEAK,
    )
    assert bound == "hbm"
    seconds, bound = keye_calls.least_seconds(
        keye_calls.attn_operations(pairs * 0.35, hf), keye_calls.attn_bytes(5 * 6000, 5 * 2048, hf),
        PEAK,
    )
    assert bound == "bf16" and seconds == pytest.approx(pairs * 0.35 * 16384 * 5 / 197e12)


def _ctx(kernel: str, share: float, pairs: float, kept: float):
    before = counters(dynamo_engine_phase_seconds_total__phase_sync=10.0)
    after = counters(
        dynamo_dsa_index_pairs_total__program_mixed=pairs,
        dynamo_dsa_selected_pairs_total__program_mixed=kept,
        dynamo_attn_context_tokens_total__kind_full=5 * pairs / 500,
        dynamo_attn_query_tokens_total__kind_full=5 * pairs / 5000,
        dynamo_attn_key_positions_total__kind_full=5 * pairs,
        dynamo_engine_phase_seconds_total__phase_sync=40.0,
        dynamo_engine_phase_seconds_total__phase_launch=20.0,
        dynamo_kv_pool_info__layers_5__token_bytes_11520__index_width_128__dtype_bfloat16=1.0,
    )
    trace = {"busy_s": 3.2, "window_s": 4.0, "ops_s": {kernel: share, "fusion.1": 1.0 - share}}
    return {"trace": trace, "prom_before": before, "prom_after": after}


@pytest.mark.parametrize("kernel", ["dsa_index_scores.3", "index_scores"])
def test_the_index_roofline_reader_takes_both_sides_from_the_window(on, kernel):  # noqa: F811
    spec = spec_of("keye_index_roofline_share")
    assert spec["config"] == CONFIG
    read = reader(spec).read
    hf = load("configs", f"{CONFIG}.json")
    pairs = 4.0e9
    least = keye_calls.least_seconds(
        keye_calls.index_operations(pairs, hf),
        keye_calls.index_bytes(pairs, 5 * pairs / 500, 5 * pairs / 5000, hf, 256), PEAK,
    )[0]
    took = 0.25 * 0.8 * 50.0  # a quarter of busy time, 80 % busy, 50 s of loop
    got = read(spec, _ctx(kernel, 0.25, pairs, pairs * 0.35))
    assert got == pytest.approx(least / took * 100.0) and 0 < got < 100
    assert read(spec, {"trace": None}) is None
    parent = _ctx(kernel, 0.25, pairs, pairs * 0.35)
    parent["prom_after"] = {k: v for k, v in parent["prom_after"].items() if "dsa" not in k[0]}
    assert read(spec, parent) is None  # the parent: no such counter
    assert read(spec, _ctx("ragged_paged_attention.2", 0.25, pairs, pairs)) is None  # no such call


def test_the_attention_roofline_counts_the_selected_pairs(on):  # noqa: F811
    spec = spec_of("keye_attn_roofline_share")
    assert (spec["kind"], spec["config"]) == ("trace_keye_attn_roofline", CONFIG)
    hf = load("configs", f"{CONFIG}.json")
    pairs, kept = 4.0e9, 1.4e9
    got = reader(spec).read(spec, _ctx("ragged_paged_attention.7", 0.5, pairs, kept))
    least = keye_calls.least_seconds(
        keye_calls.attn_operations(kept, hf),
        keye_calls.attn_bytes(5 * pairs / 500, 5 * pairs / 5000, hf), PEAK,
    )[0]
    assert got == pytest.approx(least / (0.5 * 0.8 * 50.0) * 100.0) and 0 < got < 100
    # a masked dense call at the bf16 peak would read the kept share of the pairs
    assert read_none(spec, "dsa_index_scores.1")


def read_none(spec, kernel):
    return reader(spec).read(spec, _ctx(kernel, 0.5, 4.0e9, 1.4e9)) is None


def test_the_expert_reader_counts_the_experts_the_picks_reached(on):  # noqa: F811
    """The accepted held reader's work over this configuration (its data
    file but for the kind; the time base is the next test's): the work from
    what the programs count on the device. A decode window's calls as my
    chip run of PR 61 traced them (256 rows, 0.200 ms a call): counted by the
    shape (128 matrices) 246 %, the fault; 48 experts reached read 93 % (that
    time allows 51 at the HBM peak)."""
    from lib import expert_calls, held_expert_calls

    spec = spec_of("keye_expert_gmm_roofline_share")
    assert spec == {**spec_of("glm5_expert_gmm_roofline_share"), "config": CONFIG,
                    "kind": "trace_expert_gmm_roofline_held_steady"}
    config = load("configs", f"{CONFIG}.json")
    D, I = config["hidden_size"], config[spec["width_key"]]
    assert (D, I, config["num_experts"]) == (2048, 768, 128)
    call = {"lhs": ("bf16", (256, D)), "rhs": ("bf16", (128, D, I)), "result": ("bf16", (256, I))}
    assert expert_calls.least_seconds(call, PEAK)[0] / 0.200e-3 == pytest.approx(2.46, abs=0.02)
    least, bound = held_expert_calls.least_seconds(256, 48, D, I, PEAK)
    assert bound == "hbm" and least == pytest.approx(3 * (48 * D * I + 256 * (D + I)) * 2 / 819e9)
    assert least / (3 * 0.200e-3) == pytest.approx(0.93, abs=0.01)
    # both sides over the whole window: 1e6 picks that reached 2e5 experts,
    # the kernel a fifth of a busy time that is 80 % of 50 s of loop
    read = reader(spec).read
    before = counters(dynamo_engine_phase_seconds_total__phase_sync=10.0)
    after = counters(
        dynamo_moe_picks_total__where_held=1.0e6,
        dynamo_moe_held_expert_reads_total__program_mixed=0.5e5,
        dynamo_moe_held_expert_reads_total__program_window=1.5e5,
        dynamo_engine_phase_seconds_total__phase_sync=60.0,
    )
    trace = {"busy_s": 3.2, "window_s": 4.0,
             "ops_s": {"expert_gmm.18[tpu_custom_call]": 0.2, "ragged_paged_attention.19": 0.8}}
    ctx = {"trace": trace, "prom_before": before, "prom_after": after}
    want = held_expert_calls.least_seconds(1.0e6, 2.0e5, D, I, PEAK)[0] / (0.2 * 0.8 * 50.0) * 100.0
    assert read(spec, ctx) == pytest.approx(want) and 0 < want < 100
    assert read(spec, {"trace": None}) is None
    # a program that counts no picks (a parent of the count): nothing, no raise
    silent = {**ctx, "prom_after": {k: v for k, v in after.items() if "moe" not in k[0]}}
    assert read(spec, silent) is None


@pytest.mark.parametrize("metric,kernel", [
    ("keye_expert_gmm_roofline_share", "expert_gmm.18[tpu_custom_call]"),
    ("keye_index_roofline_share", "dsa_index_scores.2[tpu_custom_call]"),
    ("keye_attn_roofline_share", "ragged_paged_attention.19[tpu_custom_call]"),
])
def test_a_stall_in_the_slice_does_not_lift_a_share(on, metric, kernel):  # noqa: F811
    """My chip run of PR 61, seed 3300000011: a slice of 5.506 s behind ONE gap
    of 4.200 s under the runtime's ``ReadSyncFlag`` thread (busy 1.134 s) read
    the expert kernel at 228 % on ``lib/window_time.py``. The same slice
    without the gap reads what the stalled one reads here; a gap under a
    second stays in."""
    from lib import steady_window_time, window_time

    spec = spec_of(metric)
    ctx = _ctx(kernel, 0.2, 4.0e9, 1.4e9)
    ctx["prom_after"].update(counters(
        dynamo_moe_picks_total__where_held=3.0e7,
        dynamo_moe_held_expert_reads_total__program_mixed=1.0e5,
        dynamo_moe_held_expert_reads_total__program_window=0.3e5,
    ))
    ctx["trace"].update(busy_s=1.134, window_s=1.306, idle_gaps=[["python3: dyn/sync", 0.102]])
    plain = reader(spec).read(spec, ctx)
    assert plain is not None and 0 < plain < 100
    took = steady_window_time.kernel_seconds(kernel[:8], ctx)
    assert took == pytest.approx(window_time.kernel_seconds(kernel[:8], ctx))  # no stall: the accepted time
    stalled = {**ctx, "trace": {**ctx["trace"], "window_s": 5.506, "idle_gaps": [
        ["futex-default-SDomainT/437: ReadSyncFlag", 4.200], ["python3: dyn/sync", 0.102]]}}
    assert reader(spec).read(spec, stalled) == pytest.approx(plain)
    assert steady_window_time.kernel_seconds(kernel[:8], stalled) == pytest.approx(took)
    assert window_time.kernel_seconds(kernel[:8], stalled) == pytest.approx(took * 1.306 / 5.506)
    nothing_left = {**ctx, "trace": {**ctx["trace"], "idle_gaps": [["x", 1.4]]}}
    assert reader(spec).read(spec, nothing_left) is None


def test_the_index_keys_bytes_come_from_the_pool_info():
    spec = spec_of("index_key_bytes_per_token")
    read = reader(spec).read
    assert read(spec, _ctx("x", 0.5, 1000.0, 350.0)) == 1280.0
    wide = {"prom_after": counters(
        dynamo_kv_pool_info__layers_5__index_width_640__dtype_bfloat16=1.0)}
    assert read(spec, wide) == 6400.0  # a key cached at the K row's width would show
    for labels in ("layers_36__dtype_bfloat16", "layers_5__index_width_0__dtype_bfloat16"):
        parent = {"prom_after": counters(**{f"dynamo_kv_pool_info__{labels}": 1.0})}
        assert read(spec, parent) is None  # no index key cached
    assert read(spec, {}) is None
    resident = spec_of("kv_resident_bytes_per_token")
    assert resident["kind"] == "prom_gauge_ratio_mean"


def test_ratios_read_this_models_counters():
    ctx = _ctx("x", 0.5, 1000.0, 350.0)
    kept = spec_of("dsa_kept_pair_share")
    assert reader(kept).read(kept, ctx) == pytest.approx(35.0)


@pytest.mark.parametrize("metric,scope,not_scope", [
    ("dsa_index_time_share", "jit(ragged_mixed)/while/body/closed_call/dsa_index/dot_general",
     "jit(ragged_mixed)/while/body/closed_call/qkv/dot_general"),
    ("dsa_index_time_share",
     "jit(ragged_mixed)/while/body/attend/cond/branch_1_fun/dsa_index/pallas_call",
     "jit(ragged_mixed)/while/body/attend/cond/branch_1_fun/dsa_select/while/body/reduce_sum"),
    ("dsa_select_time_share",
     "jit(ragged_window)/while/body/attend/cond/branch_1_fun/dsa_select/while/body/reduce_sum",
     "jit(ragged_window)/while/body/dsa_index/dot_general"),
])
def test_the_scope_metrics_find_this_models_scopes(metric, scope, not_scope):
    spec = spec_of(metric)
    assert spec["kind"] == "trace_scope_share"
    assert re.search(spec["scope"], scope) and not re.search(spec["scope"], not_scope)


def test_the_program_names_the_scopes_kernel_and_counters_the_metrics_read():
    import inspect

    from dynamo_exp_tpu.engine import engine
    from dynamo_exp_tpu.models import llama
    from dynamo_exp_tpu.ops import sparse_index

    text = inspect.getsource(llama._attn_half)
    assert 'jax.named_scope("dsa_index")' in text
    assert 'name="dsa_index_scores"' in inspect.getsource(sparse_index)
    for pattern, name in (("keye_index_roofline_share", "dsa_index_scores.12"),
                          ("keye_attn_roofline_share", "ragged_paged_attention.3")):
        assert re.search(spec_of(pattern)["pattern"], name)
    text = inspect.getsource(engine.TPUEngine._count_latent_reads)
    assert "has_indexer" in text and "dsa_selected_pairs" in text


# ------------------------------------------------------------- the traffic
def test_the_traffic_is_glm5s_file_unchanged():
    from test_traffic import load as load_traffic
    from test_traffic import plan

    mine = load_traffic("workloads", CELL)
    assert mine["traffic"] == load_traffic("workloads", "glm-5.longctx-saturate")["traffic"]
    a = plan("closed_loop", "longctx-saturate", mine, 1, seconds=51.0)
    assert len(a["chains"]) == 32 and all(len(c["requests"]) >= 6 for c in a["chains"])
    b = plan("closed_loop", "longctx-saturate", mine, 2**31 + 5, seconds=51.0)
    assert [[r["max_tokens"] for r in c["requests"]] for c in a["chains"]] == [
        [r["max_tokens"] for r in c["requests"]] for c in b["chains"]
    ]
