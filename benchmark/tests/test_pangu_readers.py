"""What ``openpangu-ultra-moe-718b.longdoc-saturate`` adds beside its
comparison: the configuration's file against the catalog's row, the
parameter count, the cell's lists, the three new readers
(``trace_mla_attn_roofline``, ``trace_expert_gmm_roofline_held``,
``prom_info_label``) with ``lib/mla_attn_calls.py`` and
``lib/held_expert_calls.py`` at this model's shapes with hand-computed
shares, the scope metrics' expressions, the counters' ratios, and the
traffic through ``closed_loop``. Each reader says nothing without its trace
or its counters (the parent)."""

import importlib
import json
import os

import pytest

from lib import held_expert_calls, mla_attn_calls, xplane_meta
from test_expert_calls import OTHER, PEAK, write_trace
from test_state_space import on  # noqa: F401  (the fixture: peaks and the trace's path)

BENCH = xplane_meta.HERE
CELL = "openpangu-ultra-moe-718b.longdoc-saturate"
CONFIG = "openpangu-ultra-moe-718b-l5e16"
NEW = (
    "mla_block_time_share", "mla_absorb_time_share", "mla_expanded_token_share",
    "moe_held_pick_share", "latent_cache_bytes_per_token", "mla_attn_roofline_share",
    "pangu_expert_gmm_roofline_share", "pangu_expert_slab_copy_time_share",
)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
D, I, E = 7680, 2048, 16


def gmm(rows: int, k: int = D, n: int = I) -> str:
    return (
        f"%expert_gmm.3 = bf16[{rows},{n}]{{1,0:T(8,128)(2,1)}} custom-call(s32[1]{{0}} %l, "
        f"s32[31]{{0}} %g, s32[31]{{0}} %b, s32[1]{{0}} %t, s32[17]{{0}} %o, "
        f"bf16[{rows},{k}]{{1,0}} %lhs, bf16[4,{E},{k},{n}]{{3,2,1,0}} %w), "
        'custom_call_target="tpu_custom_call"'
    )


SLAB_COPY = f"%copy.4 = bf16[{E},{D},{I}]{{2,1,0}} copy(bf16[{E},{D},{I}]{{2,1,0}} %w)"


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def spec_of(metric: str) -> dict:
    return load("layer_metrics", f"{metric}.json")["reader"]


def reader(spec: dict):
    return importlib.import_module(f"readers.{spec['kind']}")


def counters(**series) -> dict:
    """{(name, sorted labels): value} as ``parse_prometheus`` gives it, from
    ``name__label_value=number`` keywords."""
    out = {}
    for key, value in series.items():
        name, *labels = key.split("__")
        out[(name, tuple(sorted(tuple(l.rsplit("_", 1)) for l in labels)))] = value
    return out


# ------------------------------------------------------ the configuration
def test_the_file_is_the_catalogs_row_but_for_its_share():
    config = load("configs", f"{CONFIG}.json")
    assert config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers",
    ]
    assert (config["num_hidden_layers"], config["first_k_dense_replace"]) == (5, 1)
    assert (config["n_routed_experts"], config["n_routed_experts_global"]) == (16, 256)
    assert config["vocab_size"] == 153600 // 8 and config["num_nextn_predict_layers"] == 0
    assert (config["comparison"], config["reference"]) == ("latent_attention", "openpangu_moe")
    assert config["published"]["n_routed_experts"] == 256
    for key in ("router", "rotary", "latent_attention", "sandwich_norm", "weights", "tokenizer",
                "chat_template", "torch_dtype", "n_routed_experts_global"):
        assert key in config["assumed"], key
    for phrase in ("16 pipeline stages of 16 chips", "4,919,139,840", "one sixteenth"):
        assert phrase in config["deployment"], phrase
    assert "6,400 B a token" in config["num_pages_arithmetic"]
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "openPangu-Ultra-MoE-718B")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key


def test_the_parameter_count_is_eval_shapes():
    import jax
    import numpy as np

    import run_cell
    from dynamo_exp_tpu.models import ModelConfig
    from dynamo_exp_tpu.models.llama import init_kv_cache, init_params

    config = load("configs", f"{CONFIG}.json")
    mcfg = ModelConfig.from_hf_config(run_cell.hf_config(config))
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), mcfg))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert count == 4_919_139_840 and f"{count:,}" in config["deployment"]
    # One pool of 640 lanes a token and layer, and no value pool.
    pool, values = jax.eval_shape(lambda: init_kv_cache(mcfg, 8, 16))
    assert pool.shape == (5, 8, 16, 640) and values.shape == (5, 8, 16, 0)
    assert (mcfg.latent_width, mcfg.latent_lanes) == (576, 640)
    assert pool.size * 2 // (8 * 16) == 6400


def test_the_cell_lists_its_metrics():
    bench = load(os.pardir, "BENCHMARK.json")
    assert bench["configs"][-1]["name"] == CONFIG
    assert bench["workloads"][-1] == {
        "name": CELL, "config": CONFIG, "traffic": "longdoc-saturate", "chips": 1,
        "why": bench["workloads"][-1]["why"],
    }
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL], name
        assert os.path.exists(os.path.join(BENCH, "readers", f"{spec_of(name)['kind']}.py"))
    for name in ("window_dispatch_mean_ms", "decode_window_rows_mean",
                 "window_kernel_dispatch_share", "moe_time_share", "moe_pad_row_share",
                 "expert_inplace_dispatch_share"):
        assert listed[name]["workloads"][-1] == CELL
    # No recurrence, no block pass and nobody else's experts run here.
    for name, m in listed.items():
        if name.startswith(("ssm_", "mamba_", "gdn_", "seq_state", "block_", "denoise_",
                            "lfm2_", "sdar_", "expert_gmm", "expert_dot", "expert_slab",
                            "expert_weight", "ttft_", "queue_wait")):
            assert CELL not in m.get("workloads", [CELL]), name
    cell = load("workloads", f"{CELL}.json")
    assert (cell["config"], cell["traffic"], cell["clients"], cell["drain_s"]) == (
        CONFIG, "longdoc-saturate", 32, 90,
    )
    for phrase in ("one sixteenth", "16 x its share"):
        assert phrase in cell["why"]
    args = load("configs", f"{CONFIG}.json")["serve_args"]
    assert args[args.index("--max-decode-slots") + 1] == "32"  # a slot a client


# ------------------------------------------------- latent attention's work
def test_the_published_forms_operations_and_the_pools_bytes():
    hf = load("configs", f"{CONFIG}.json")
    assert mla_attn_calls.lanes(hf) == (640, 512)
    # A pair and head: the score over 192 and the value sum over 128.
    assert mla_attn_calls.operations(1, hf) == 2 * (192 + 128) * 128 * 5
    # A context token at the pool's real row, a query's 128 heads in and out.
    assert mla_attn_calls.bytes_moved(1, 0, hf, 1280) == 5 * 1280
    assert mla_attn_calls.bytes_moved(0, 1, hf, 1280) == 5 * 128 * (640 + 512) * 2
    # A prompt chunk of 4096 queries at a mean context of 3000: the matrix unit's.
    pairs = 4096 * 3000
    seconds, bound = mla_attn_calls.least_seconds(pairs, 5000, 4096, hf, 1280, PEAK)
    assert bound == "bf16" and seconds == pytest.approx(pairs * 81920 * 5 / 197e12)
    # 32 decode rows over 5000 tokens each: a row's latents once, HBM's.
    seconds, bound = mla_attn_calls.least_seconds(32 * 5000, 32 * 5000, 32, hf, 1280, PEAK)
    assert bound == "hbm"
    assert seconds == pytest.approx(5 * (32 * 5000 * 1280 + 32 * 128 * 1152 * 2) / 819e9)


def _mla_ctx(kernel_share: float, pairs: float):
    """A traced run whose slice was 80 % busy, ``kernel_share`` of that in
    the kernel, over a window the loop's phases sum to 50 s."""
    before = counters(
        dynamo_mla_attended_pairs_total__program_mixed=1e6,
        dynamo_engine_phase_seconds_total__phase_sync=10.0,
    )
    after = counters(
        dynamo_mla_attended_pairs_total__program_mixed=1e6 + pairs,
        dynamo_mla_context_tokens_total__program_mixed=pairs / 500,
        dynamo_mla_query_tokens_total__program_mixed__form_absorbed=pairs / 3000,
        dynamo_engine_phase_seconds_total__phase_sync=40.0,
        dynamo_engine_phase_seconds_total__phase_launch=20.0,
        dynamo_kv_pool_info__layers_5__token_bytes_6400=1.0,
    )
    trace = {
        "busy_s": 3.2, "window_s": 4.0,
        "ops_s": {"ragged_paged_attention.7": kernel_share, "fusion.1": 1.0 - kernel_share},
    }
    return {"trace": trace, "prom_before": before, "prom_after": after}


def test_the_attention_roofline_reader_takes_both_sides_from_the_window(on):  # noqa: F811
    spec = spec_of("mla_attn_roofline_share")
    assert spec["config"] == CONFIG
    read = reader(spec).read
    hf = load("configs", f"{CONFIG}.json")
    pairs = 2.0e9
    least = mla_attn_calls.least_seconds(pairs, pairs / 500, pairs / 3000, hf, 1280, PEAK)[0]
    took = 0.5 * 0.8 * 50.0  # half of busy time, 80 % busy, 50 s of loop
    assert read(spec, _mla_ctx(0.5, pairs)) == pytest.approx(least / took * 100.0)
    assert 0 < read(spec, _mla_ctx(0.5, pairs)) < 100
    assert read(spec, {"trace": None}) is None
    parent = _mla_ctx(0.5, pairs)
    parent["prom_after"] = {k: v for k, v in parent["prom_after"].items() if "mla" not in k[0]}
    parent["prom_before"] = {k: v for k, v in parent["prom_before"].items() if "mla" not in k[0]}
    assert read(spec, parent) is None  # the parent: no such counter
    no_kernel = _mla_ctx(0.0, pairs)
    assert read(spec, no_kernel) is None


def test_the_absorbed_form_caps_the_share():
    """A kernel at the bf16 peak executing the absorbed form's 2 x (640 +
    512) a pair and head reads 27.8 %: the published form's count over
    what is executed."""
    hf = load("configs", f"{CONFIG}.json")
    executed = 2 * sum(mla_attn_calls.lanes(hf))
    assert 2 * (192 + 128) / executed == pytest.approx(0.2778, abs=1e-4)


# ------------------------------------------------------- the held experts
def test_the_held_picks_work_and_its_least_time():
    matrix = D * I * 2
    # 2048 picks that reach all 16 held experts of a layer: the matrices' bytes bound it.
    assert held_expert_calls.operations(2048, D, I) == 3 * 2 * 2048 * D * I
    assert held_expert_calls.bytes_moved(2048, 16, D, I) == 3 * 16 * matrix + 3 * 2048 * (D + I) * 2
    seconds, bound = held_expert_calls.least_seconds(2048, 16, D, I, PEAK)
    assert bound == "hbm" and seconds == pytest.approx((3 * 16 * matrix + 3 * 2048 * (D + I) * 2) / 819e9)
    # A decode step whose 13 picks crowd onto 3 experts: 3 x 3 matrices, not 3 x 16.
    by_shape = 3 * 16 * matrix
    assert held_expert_calls.bytes_moved(13, 3, D, I) < 0.2 * by_shape
    # Rows enough to reuse each matrix: the matrix unit's.
    assert held_expert_calls.least_seconds(16 * 4096, 16, D, I, PEAK)[1] == "mxu"


def _held_ctx(kernel_share: float, picks: float, reads: float):
    before = counters(dynamo_engine_phase_seconds_total__phase_sync=10.0)
    after = counters(
        dynamo_moe_picks_total__where_held=picks,
        dynamo_moe_picks_total__where_elsewhere=15 * picks,
        dynamo_moe_held_expert_reads_total__program_mixed=reads * 0.25,
        dynamo_moe_held_expert_reads_total__program_window=reads * 0.75,
        dynamo_engine_phase_seconds_total__phase_sync=40.0,
        dynamo_engine_phase_seconds_total__phase_launch=20.0,
    )
    trace = {
        "busy_s": 3.2, "window_s": 4.0,
        "ops_s": {"expert_gmm.15[tpu_custom_call]": kernel_share, "fusion.1": 1.0 - kernel_share},
    }
    return {"trace": trace, "prom_before": before, "prom_after": after}


def test_the_held_roofline_reader_takes_both_sides_from_the_window(on):  # noqa: F811
    spec = spec_of("pangu_expert_gmm_roofline_share")
    assert (spec["config"], spec["width_key"]) == (CONFIG, "moe_intermediate_size")
    read = reader(spec).read
    picks, reads = 4.0e5, 2.0e4
    least = held_expert_calls.least_seconds(picks, reads, D, I, PEAK)[0]
    took = 0.1 * 0.8 * 50.0  # a tenth of busy time, 80 % busy, 50 s of loop
    assert read(spec, _held_ctx(0.1, picks, reads)) == pytest.approx(least / took * 100.0)
    assert 0 < read(spec, _held_ctx(0.1, picks, reads)) < 100
    assert read(spec, {"trace": None}) is None
    parent = _held_ctx(0.1, picks, reads)
    parent["prom_after"] = {k: v for k, v in parent["prom_after"].items() if "moe" not in k[0]}
    assert read(spec, parent) is None  # the parent: holds all it routes over
    assert read(spec, _held_ctx(0.0, picks, reads)) is None  # no such call


def test_the_slab_copy_guard_knows_the_held_matrices(tmp_path, on):  # noqa: F811
    path = str(tmp_path / "t.xplane.pb")
    write_trace(path, [(gmm(4096), 0, 1000), (SLAB_COPY, 1000, 500), (OTHER, 1500, 500)])
    on(path)
    spec = spec_of("pangu_expert_slab_copy_time_share")
    mod = reader(spec)
    shapes = mod.slab_shapes(load("configs", f"{CONFIG}.json"), spec)
    assert {(E, D, I), (E, I, D), (4, E, D, I)} <= shapes
    assert mod.read(spec, {"trace": {"ops_s": {}}}) == pytest.approx(25.0)
    write_trace(path, [(gmm(4096), 0, 1000), (OTHER, 1500, 500)])
    on(path)
    assert mod.read(spec, {"trace": {"ops_s": {}}}) == 0.0


# --------------------------------------------------- counters and the label
def test_ratios_and_the_pools_label():
    after = counters(
        dynamo_moe_picks_total__where_held=625.0, dynamo_moe_picks_total__where_elsewhere=9375.0,
        dynamo_mla_query_tokens_total__program_mixed__form_absorbed=900.0,
        dynamo_mla_query_tokens_total__program_window__form_absorbed=100.0,
        dynamo_kv_pool_info__layers_5__pools_1__token_bytes_6400=1.0,
    )
    ctx = {"prom_before": {}, "prom_after": after}
    share = spec_of("moe_held_pick_share")
    assert reader(share).read(share, ctx) == pytest.approx(6.25)
    expanded = spec_of("mla_expanded_token_share")
    assert reader(expanded).read(expanded, ctx) == 0.0  # every query absorbed
    label = spec_of("latent_cache_bytes_per_token")
    assert reader(label).read(label, ctx) == 6400.0
    parent = {"prom_before": {}, "prom_after": counters(dynamo_kv_pool_info__layers_36=1.0)}
    for spec in (share, expanded, label):
        assert reader(spec).read(spec, parent) is None


@pytest.mark.parametrize("metric,scope,not_scope", [
    ("mla_block_time_share", "jit(ragged_mixed)/while/body/closed_call/mla/mla_q/dot_general",
     "jit(ragged_mixed)/while/body/closed_call/moe/shared_expert/dot_general"),
    ("mla_block_time_share", "jit(ragged_mixed)/while/body/closed_call/mla/attend/pallas_call",
     "jit(ragged_mixed)/while/body/closed_call/mla_q/dot_general"),
    ("mla_absorb_time_share", "jit(ragged_window)/while/body/mla/attend/mla_absorb/dot_general",
     "jit(ragged_window)/while/body/mla/mla_kv/kv_write/scatter"),
])
def test_the_scope_metrics_find_their_scopes(metric, scope, not_scope):
    import re

    spec = spec_of(metric)
    assert spec["kind"] == "trace_scope_share"
    assert re.search(spec["scope"], scope) and not re.search(spec["scope"], not_scope)


def test_the_program_names_the_scopes_and_counters_the_metrics_read():
    import inspect

    from dynamo_exp_tpu.models import llama
    from dynamo_exp_tpu.telemetry import spans

    text = inspect.getsource(llama)
    for scope in ("mla", "mla_q", "mla_kv", "mla_absorb", "shared_expert", "attend", "kv_write"):
        assert f'jax.named_scope("{scope}")' in text, scope
    text = inspect.getsource(spans)
    for series in ("dynamo_moe_picks_total", "dynamo_moe_held_expert_reads_total",
                   "dynamo_mla_query_tokens_total",
                   "dynamo_mla_attended_pairs_total", "dynamo_mla_context_tokens_total"):
        assert f'"{series}"' in text, series
    assert '"token_bytes"' in text


# ------------------------------------------------------------- the traffic
def test_the_schedule_is_jambas_and_olmos_letter_for_letter():
    from test_traffic import load as load_traffic
    from test_traffic import plan

    mine = load_traffic("workloads", CELL)
    a = plan("closed_loop", "longdoc-saturate", mine, 1, seconds=51.0)
    assert len(a["chains"]) == 32
    for other in ("jamba2-3b.longdoc-saturate", "olmo-hybrid-7b.longdoc-saturate"):
        theirs = load_traffic("workloads", other)
        assert (mine["traffic"], mine["clients"]) == (theirs["traffic"], theirs["clients"])
        assert a == plan("closed_loop", "longdoc-saturate", theirs, 2**31 + 5, seconds=51.0)
