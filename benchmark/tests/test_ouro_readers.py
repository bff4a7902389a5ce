"""What ``ouro-2.6b.reason-short-saturate`` adds: the configuration's file
against the catalog's row (whole: nothing reduced), the cell's MEMBERSHIP in
``BENCHMARK.json``'s lists (not its place in them: a later PR appends behind
it), the three new metrics as data for accepted readers, each reading a
recorded sample and saying nothing without its trace or its series (the
parent), and the traffic through ``closed_loop``."""

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
CELL = "ouro-2.6b.reason-short-saturate"
CONFIG = "ouro-2.6b"
NEW = {
    "layer_passes_per_token": "output_tok_s", "loop_norm_time_share": "tpot_p95_ms",
    "ouro_attn_roofline_share": "output_tok_s",
}
APPENDED = (
    "full_pool_token_bytes", "kv_resident_bytes_per_token", "window_dispatch_mean_ms",
    "decode_window_rows_mean", "window_kernel_dispatch_share", "attn_row_behind_share",
    "attn_heads_together_share",
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
def test_the_file_is_the_catalogs_row_whole():
    config = load("configs", f"{CONFIG}.json")
    assert config["reduced"] == [] and config["reference"] == "ouro"
    assert "comparison" not in config  # the default: logprob_walk
    assert (config["num_hidden_layers"], config["total_ut_steps"]) == (48, 4)
    assert config["early_exit_threshold"] == 1 and len(config["layer_types"]) == 48
    for key in ("norm_placement", "norm_weights", "cache", "positions", "exit_gate",
                "unread_keys", "weights", "tokenizer", "chat_template", "torch_dtype",
                "tensor_names"):
        assert key in config["assumed"], key
    for phrase in ("WHOLE", "2,667,974,657", "1.5 MiB", "103 GB"):
        assert phrase in config["deployment"], phrase
    for phrase in ("1,572,864", "25,165,824", "compile-only"):
        assert phrase in config["num_pages_arithmetic"], phrase
    assert config["serve_args"][:8] == [
        "--max-decode-slots", "16", "--page-size", "16", "--max-model-len", "1024", "--tp", "1",
    ]
    pages = int(config["serve_args"][config["serve_args"].index("--num-pages") + 1])
    assert pages % 16 == 0 and 288 <= pages <= 352
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert config[key] == value, key


def test_the_cell_and_its_metrics_are_members_of_the_lists():
    bench = load("..", "BENCHMARK.json")
    configs = {c["name"]: c for c in bench["configs"]}
    assert configs[CONFIG]["file"] == f"benchmark/configs/{CONFIG}.json"
    assert configs[CONFIG]["reduced"] == []
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL] == {
        **cells[CELL], "config": CONFIG, "traffic": "reason-short-saturate", "chips": 1,
    }
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, moves in NEW.items():
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == moves, name
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", f"{name}.json"))
    for name in APPENDED:
        assert CELL in by_name[name]["workloads"], name
    # readers that name another configuration are not this cell's
    for name in ("full_attn_roofline_share", "mimo_full_attn_roofline_share"):
        assert CELL not in by_name[name]["workloads"], name
    for w in (cells[CELL], configs[CONFIG]):
        assert len(w["why"]) <= 200
    cell = load("workloads", f"{CELL}.json")
    assert cell["clients"] in (8, 10, 12) and cell["drain_s"] == 60
    assert "clients_arithmetic" in cell


# ------------------------------------------------------------ the readers
def test_layer_passes_per_token_reads_the_two_counters():
    spec = spec_of("layer_passes_per_token")
    assert spec["kind"] == "prom_counter_ratio"
    before = counters(
        dynamo_layer_passes_total=192.0 * 1000,
        dynamo_ragged_slots_total__program_window__kind_live=600.0,
        dynamo_ragged_slots_total__program_mixed__kind_live=400.0,
        dynamo_ragged_slots_total__program_mixed__kind_pad=77.0,
    )
    after = counters(
        dynamo_layer_passes_total=192.0 * 6000,
        dynamo_ragged_slots_total__program_window__kind_live=4600.0,
        dynamo_ragged_slots_total__program_mixed__kind_live=1400.0,
        dynamo_ragged_slots_total__program_mixed__kind_pad=900.0,
    )
    read = reader(spec).read
    assert read(spec, {"prom_before": before, "prom_after": after}) == 192.0
    parent = {k: v for k, v in after.items() if k[0] != "dynamo_layer_passes_total"}
    assert read(spec, {"prom_before": before, "prom_after": parent}) is None  # no such counter
    assert read(spec, {"prom_before": after, "prom_after": after}) is None  # nothing fed


def _ops():
    Op = xplane_meta.Op
    loop = "jit(ragged_window)/while/body/while/body/"
    return [
        Op(0, 700, "ragged_paged_attention.3", "custom-call", "",
           loop + "loop_pass/while/body/attend/pallas_call", "jit_ragged_window"),
        Op(700, 20, "fusion.7", "fusion", "", loop + "loop_norm/cond/branch_1_fun/mul", "jit_ragged_window"),
        Op(720, 5, "conditional.2", "conditional", "", loop + "loop_norm/cond", "jit_ragged_window"),
        Op(725, 1275, "fusion.9", "fusion", "", loop + "loop_pass/while/body/ffn/dot_general", "jit_ragged_window"),
    ]


def _time_by(meta, key):
    kinds = {key(o) for o in meta["ops"]}
    return {k: sum(o.dur for o in meta["ops"] if key(o) == k) for k in kinds}


def test_loop_norm_time_share_reads_the_scope(monkeypatch):
    spec = spec_of("loop_norm_time_share")
    assert spec["kind"] == "trace_scope_share"
    read = reader(spec).read
    monkeypatch.setattr(xplane_meta, "current", lambda: {"ops": _ops()})
    monkeypatch.setattr(xplane_meta, "time_by", _time_by)
    # the norm's fusion, not the conditional that holds it (control flow)
    assert read(spec, {"trace": {"busy_s": 1.0}}) == pytest.approx(20 / 2000 * 100)
    assert read(spec, {"trace": None}) is None
    monkeypatch.setattr(xplane_meta, "current", lambda: {"ops": _ops()[:1]})
    assert read(spec, {"trace": {"busy_s": 1.0}}) == 0.0  # the parent: no such scope


def test_the_roofline_reader_counts_every_layer_pass_as_a_full_layer(on, monkeypatch):  # noqa: F811
    spec = spec_of("ouro_attn_roofline_share")
    assert spec == {**spec_of("full_attn_roofline_share"), "config": CONFIG}
    hf = load("configs", f"{CONFIG}.json")
    # a context token of one layer-pass: K and V at 16 heads x 128; a pair and
    # head: the score and the value sum over 128
    assert attn_calls.bytes_moved(1, 0, hf) == 2 * 2 * 16 * 128 == 8192
    assert attn_calls.operations(1, hf) == 4 * 128 * 16
    # a decode step of 10 rows at 320 tokens over the 192 layer-passes: HBM-bound
    keys = 192 * 10 * 320.0
    least, bound = attn_calls.least_seconds(keys, keys, 192 * 10, hf, PEAK)
    assert bound == "hbm" and least == pytest.approx(
        (keys * 8192 + 1920 * 8192) / (PEAK["hbm_gbps"] * 1e9)
    )
    before = counters(dynamo_engine_phase_seconds_total__phase_sync=10.0)
    after = counters(
        dynamo_attn_key_positions_total__kind_full=1000 * keys,
        dynamo_attn_context_tokens_total__kind_full=1000 * keys,
        dynamo_attn_query_tokens_total__kind_full=1000 * 1920.0,
        dynamo_engine_phase_seconds_total__phase_sync=40.0,
        dynamo_engine_phase_seconds_total__phase_launch=20.0,
    )
    ctx = {"trace": {"busy_s": 3.2, "window_s": 4.0}, "prom_before": before, "prom_after": after}
    monkeypatch.setattr(xplane_meta, "current", lambda: {"ops": _ops()})
    monkeypatch.setattr(xplane_meta, "time_by", _time_by)
    read = reader(spec).read
    took = 700 / 2000 * 0.8 * 50.0
    got = read(spec, ctx)
    assert got == pytest.approx(1000 * least / took * 100.0) and 0 < got < 100
    assert read(spec, {"trace": None}) is None
    parent = {**ctx, "prom_after": {k: v for k, v in after.items() if "attn" not in k[0]}}
    assert read(spec, parent) is None  # the parent: no such counter


def test_the_accepted_readers_the_appended_metrics_are_data_for():
    spec = spec_of("full_pool_token_bytes")
    after = counters(**{"dynamo_kv_pool_info__token_bytes_1572864": 1.0})
    assert reader(spec).read(spec, {"prom_after": after}) == 1572864.0
    spec = spec_of("kv_resident_bytes_per_token")
    scrapes = [counters(
        dynamo_kv_pool_held_bytes__pool_full=200 * 25165824.0, dynamo_kv_context_tokens=3000.0,
    )]
    # 200 pages of 16 tokens held for 3,000 tokens of context: page rounding over 1,572,864
    assert reader(spec).read(spec, {"gauge_scrapes": scrapes}) == pytest.approx(1677721.6)


def test_the_program_names_the_scopes_kernel_and_series_the_metrics_read():
    import inspect

    from dynamo_exp_tpu.engine import engine
    from dynamo_exp_tpu.models import llama
    from dynamo_exp_tpu.telemetry import spans

    source = inspect.getsource(llama._loop_passes)
    assert 'jax.named_scope("loop_pass")' in source and 'jax.named_scope("loop_norm")' in source
    assert re.search(spec_of("loop_norm_time_share")["scope"], "jit(x)/while/body/loop_norm/cond")
    assert not re.search(spec_of("loop_norm_time_share")["scope"], "jit(x)/loop_norm_2/mul")
    assert re.search(spec_of("ouro_attn_roofline_share")["pattern"], "ragged_paged_attention.12")
    text = inspect.getsource(spans)
    for series in ("dynamo_layer_passes_total", "dynamo_model_loop_info",
                   "dynamo_ragged_slots_total", "dynamo_attn_key_positions_total",
                   "dynamo_kv_pool_held_bytes", "dynamo_kv_context_tokens"):
        assert f'"{series}"' in text, series
    assert "tel.layer_passes.inc(" in inspect.getsource(engine.TPUEngine._count_slots)


# ------------------------------------------------------------- the traffic
def test_the_traffic_is_the_issues_letter_for_letter():
    from test_traffic import load as load_traffic
    from test_traffic import plan

    traffic = load("traffic", "reason-short-saturate.json")
    assert traffic["generator"] == "closed_loop" and traffic["lead_in_s"] == 15
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 128, "sigma": 0.6, "min": 32, "max": 512,
    }
    assert traffic["output_tokens"] == {
        "dist": "lognormal", "median": 192, "sigma": 0.5, "min": 64, "max": 448,
    }
    assert traffic["schedule_seed"] == 23
    mine = load_traffic("workloads", CELL)
    a = plan("closed_loop", "reason-short-saturate", mine, 1, seconds=51.0)
    assert len(a["chains"]) == mine["clients"]
    assert all(len(c["requests"]) >= 30 for c in a["chains"])
    lengths = [r["max_tokens"] for c in a["chains"] for r in c["requests"]]
    assert min(lengths) >= 64 and max(lengths) <= 448 and 190 < sum(lengths) / len(lengths) < 240
    # every run is offered the same lengths, whatever its seed
    b = plan("closed_loop", "reason-short-saturate", mine, 2**31 + 5, seconds=51.0)
    assert [[r["max_tokens"] for r in c["requests"]] for c in a["chains"]] == [
        [r["max_tokens"] for r in c["requests"]] for c in b["chains"]
    ]
