"""What ``nemotron-3-nano-30b-a3b.longdoc-fast-saturate`` adds beside its
comparison: the configuration's file against the catalog's row, the cell's
lists (wherever they stand in ``BENCHMARK.json``), the two roofline counts
(``lib/ssd_scan_calls.py``, ``lib/two_matrix_expert_calls.py``) against hand
arithmetic with a case in which three products a pick would read over 100 %,
the new readers on recorded data, and the traffic through ``closed_loop``.
Each reader says nothing without its trace or its counters (the parent)."""

import importlib
import json
import os
import re

import pytest

from lib import ssd_scan_calls, two_matrix_expert_calls, xplane_meta
from test_expert_calls import OTHER, PEAK, write_trace
from test_pangu_readers import counters
from test_state_space import on  # noqa: F401  (the fixture: peaks and the trace's path)

BENCH = xplane_meta.HERE
CELL = "nemotron-3-nano-30b-a3b.longdoc-fast-saturate"
CONFIG = "nemotron-3-nano-30b-a3b-l13e64"
NEW = (
    "mamba2_mixer_time_share", "ssd_scan_time_share", "ssd_prompt_token_share",
    "ssd_step_time_share", "ssd_scan_roofline_share", "nemotron_expert_gmm_roofline_share",
    "nemotron_state_pool_copy_time_share",
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
def test_the_file_is_the_catalogs_row_but_for_its_cut():
    config = load("configs", f"{CONFIG}.json")
    assert config["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts", "vocab_size",
    ]
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"]
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert row["config"]["hybrid_override_pattern"].startswith(config["hybrid_override_pattern"])
    assert len(config["hybrid_override_pattern"]) == config["num_hidden_layers"] == 13
    assert (config["n_routed_experts"], config["n_routed_experts_global"]) == (64, 128)
    for key in ("positions", "tensor_names", "weights", "tokenizer", "chat_template"):
        assert key in config["assumed"], key
    assert "four pipeline stages" in config["deployment"]


def test_the_cell_lists_its_metrics_wherever_it_stands():
    bench = load(os.pardir, "BENCHMARK.json")
    (config,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "longdoc-fast-saturate", 1)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL], name
        assert os.path.exists(os.path.join(BENCH, "readers", f"{spec_of(name)['kind']}.py"))
    # A gate matrix that does not exist, a Mamba-1 scan's counter, another pool.
    for name, m in listed.items():
        if name.startswith(("mla_", "expert_dot", "expert_weight", "ssm_", "gdn_", "block_",
                            "denoise_", "sdar_", "pangu_", "glm5_", "keye_", "dsa_",
                            "prompt_token_share", "seq_state_pool_copy")):
            assert CELL not in m.get("workloads", [CELL]), name
    file = load("workloads", f"{CELL}.json")
    assert (file["config"], file["traffic"], file["clients"], file["drain_s"]) == (
        CONFIG, "longdoc-fast-saturate", 32, 90,
    )
    args = load("configs", f"{CONFIG}.json")["serve_args"]
    assert args[args.index("--max-decode-slots") + 1] == "32"  # a slot a client


def test_the_traffic_is_longdoc_saturates_but_for_its_rounds():
    from generators import closed_loop

    fast, slow = load("traffic", "longdoc-fast-saturate.json"), load("traffic", "longdoc-saturate.json")
    assert {k: v for k, v in fast.items() if k not in ("about", "requests_per_client_s")} == {
        k: v for k, v in slow.items() if k not in ("about", "requests_per_client_s")
    }
    assert (fast["requests_per_client_s"], slow["requests_per_client_s"]) == (0.3, 0.15)

    class Text:
        def user_message(self, n):
            return f"<{n}>"

    plans = [
        closed_loop.generate(t, {"clients": 32}, 7, 51.0, Text()) for t in (fast, slow)
    ]
    rounds = [len(p["chains"][0]["requests"]) for p in plans]
    assert rounds == [19, 9]
    # the generator draws round by round: the rounds both files write are the same requests
    for a, b in zip(plans[0]["chains"], plans[1]["chains"]):
        assert a["start_s"] == b["start_s"] and a["requests"][:9] == b["requests"]


# ------------------------------------------------------ the counted work
def test_the_recurrences_operations_and_bytes():
    config = load("configs", f"{CONFIG}.json")
    assert ssd_scan_calls.widths(config) == (64, 64, 8, 128)
    assert ssd_scan_calls.mamba2_layers(config) == 6
    assert ssd_scan_calls.token_operations(64, 64, 128) == 5 * 128 * 4096 == 2_621_440
    assert ssd_scan_calls.token_bytes(64, 64, 8, 128) == 4 * (8192 + 2048 + 64) == 41_216
    least, bound = ssd_scan_calls.least_seconds(6 * 1.0e6, config, PEAK)
    assert bound == "hbm" and least == pytest.approx(6e6 * 41_216 / 819e9)
    # the state a row carries is what the count leaves out: 2 MiB a layer
    assert 4 * 128 * 4096 == 2 * 2**20


def test_two_products_a_pick_where_three_would_read_over_100():
    """A decode window's call of 192 held picks that reach 40 experts, taking
    1.1 ms for its two products: two matrices an expert read 89 % of the HBM
    bound; counted with a gate matrix that does not exist (the accepted held
    count) the same call would read 134 %."""
    from lib import held_expert_calls

    D, I = 2688, 1856
    least, bound = two_matrix_expert_calls.least_seconds(192, 40, D, I, PEAK)
    assert bound == "hbm"
    assert least == pytest.approx(2 * (2 * 40 * D * I + 2 * 192 * (D + I)) / 819e9)
    assert two_matrix_expert_calls.operations(192, D, I) == 2 * 2 * 192 * D * I
    took = 1.1e-3
    assert least / took == pytest.approx(0.89, abs=0.01)
    three = held_expert_calls.least_seconds(192, 40, D, I, PEAK)[0]
    assert three / least == pytest.approx(1.5) and three / took > 1.0
    # rows enough to reuse each matrix: the matrix unit bounds it
    assert two_matrix_expert_calls.least_seconds(24576, 64, D, I, PEAK)[1] == "mxu"


def _window(ops_s, busy=3.2, window=4.0, gaps=(), **after):
    before = counters(dynamo_engine_phase_seconds_total__phase_sync=10.0)
    after = counters(dynamo_engine_phase_seconds_total__phase_sync=60.0, **after)
    trace = {"busy_s": busy, "window_s": window, "ops_s": ops_s, "idle_gaps": list(gaps)}
    return {"trace": trace, "prom_before": before, "prom_after": after}


def test_the_expert_reader_counts_two_products(on):  # noqa: F811
    spec = spec_of("nemotron_expert_gmm_roofline_share")
    assert spec == {
        "kind": "trace_expert_gmm_roofline_two_matrix", "config": CONFIG,
        "width_key": "moe_intermediate_size", "pattern": "^expert_gmm",
    }
    read = reader(spec).read
    ctx = _window(
        {"expert_gmm.18[tpu_custom_call]": 0.2, "ragged_paged_attention.19": 0.8},
        dynamo_moe_picks_total__where_held=3.0e7,
        dynamo_moe_held_expert_reads_total__program_mixed=0.5e5,
        dynamo_moe_held_expert_reads_total__program_window=1.5e5,
    )
    want = two_matrix_expert_calls.least_seconds(3.0e7, 2.0e5, 2688, 1856, PEAK)[0]
    # the kernel a fifth of a busy time that is 80 % of 50 s of loop
    assert read(spec, ctx) == pytest.approx(want / (0.2 * 0.8 * 50.0) * 100.0)
    assert 0 < read(spec, ctx) < 100
    assert read(spec, {"trace": None}) is None
    silent = {**ctx, "prom_after": {k: v for k, v in ctx["prom_after"].items() if "moe" not in k[0]}}
    assert read(spec, silent) is None  # a program that counts no picks: nothing, no raise
    stalled = _window(ctx["trace"]["ops_s"], window=8.2, gaps=[["x: ReadSyncFlag", 4.2]])
    stalled["prom_after"] = ctx["prom_after"]
    assert read(spec, stalled) == pytest.approx(read(spec, ctx))  # a stall lifts no share


def _meta(kinds):
    """A parsed trace as ``xplane_meta.time_by`` reads it: {(name, opcode,
    result, scope, program): self ns}."""
    return {"_self_by_kind": dict(kinds)}


def test_the_recurrences_roofline_reader_on_recorded_scopes(on, monkeypatch):  # noqa: F811
    base = "jit(ragged_mixed)/while/body/closed_call/mamba2"
    meta = _meta({
        ("fusion.1", "fusion", "f32[97,64,128,128]", f"{base}/ssd_scan/exp", "jit_ragged_mixed"): 150_000_000,
        ("ssd_step.3", "custom-call", "(f32[32,1,4096], f32[6,160,128,4096])",
         "jit(ragged_window)/while/body/closed_call/mamba2/ssd_scan/pallas_call", "jit_ragged_window"): 50_000_000,
        ("while.2", "while", "()", f"{base}/ssd_scan/while", "jit_ragged_mixed"): 0,
        ("fusion.2", "fusion", "bf16[8192,10240]", f"{base}/dot_general", "jit_ragged_mixed"): 300_000_000,
        ("fusion.9", "fusion", "bf16[8192,2688]", "jit(ragged_mixed)/while/body/moe/x", "jit_ragged_mixed"): 500_000_000,
    })
    monkeypatch.setattr(xplane_meta, "current", lambda: meta)
    spec = spec_of("ssd_scan_roofline_share")
    read = reader(spec).read
    ctx = _window({}, busy=1.0, window=1.25, dynamo_mamba2_tokens_total__program_mixed=3.0e6,
                  dynamo_mamba2_tokens_total__program_window=1.0e5)
    config = load("configs", f"{CONFIG}.json")
    least = ssd_scan_calls.least_seconds(6 * 3.1e6, config, PEAK)[0]
    # the scope's 0.2 of 1.0 busy seconds (the while holds its body's time already),
    # a busy share of 0.8, 50 s of loop
    assert ssd_scan_calls.scope_seconds(ctx) == pytest.approx(0.2 * 0.8 * 50.0)
    assert read(spec, ctx) == pytest.approx(least / 8.0 * 100.0) and 0 < read(spec, ctx) < 100
    assert read(spec, {"trace": None}) is None
    parent = {**ctx, "prom_after": counters(dynamo_engine_phase_seconds_total__phase_sync=60.0)}
    assert read(spec, parent) is None  # no such counter: nothing, no raise
    for metric, share in (("ssd_scan_time_share", 20.0), ("mamba2_mixer_time_share", 50.0)):
        s = spec_of(metric)
        assert reader(s).read(s, ctx) == pytest.approx(share), metric
    monkeypatch.setattr(xplane_meta, "current", lambda: _meta({
        ("fusion.9", "fusion", "bf16[8192,2688]", "jit(ragged_mixed)/moe/x", "jit_ragged_mixed"): 5,
    }))
    assert read(spec, ctx) is None  # a program without the scope


STEP = (
    "%ssd_step.1 = (f32[32,1,4096]{2,1,0:T(8,128)}, f32[6,160,128,4096]{3,2,1,0:T(8,128)}) "
    "custom-call(s32[1]{0} %l, s32[32]{0} %a, s32[32]{0} %b, s32[32]{0} %c, "
    'f32[6,160,128,4096]{3,2,1,0} %pool), custom_call_target="tpu_custom_call"'
)
POOL_COPY = "%copy.7 = f32[6,160,128,4096]{3,2,1,0} copy(f32[6,160,128,4096]{3,2,1,0} %p)"
CONV_SLICE = "%fusion.8 = bf16[160,3,6144]{2,1,0} fusion(bf16[6,160,3,6144]{3,2,1,0} %p), kind=kLoop"
LANE_CUT = "%fusion.9 = f32[6,160,128,1408]{3,2,1,0} fusion(f32[6,160,128,4096]{3,2,1,0} %p), kind=kLoop"
POOL_VIEW = "%bitcast.2 = f32[6,160,128,4096]{3,2,1,0} bitcast(f32[960,128,4096]{2,1,0} %p)"


def test_the_pool_copy_reader_on_a_synthetic_trace(tmp_path, on):  # noqa: F811
    path = str(tmp_path / "t.xplane.pb")
    write_trace(path, [
        (STEP, 0, 600), (STEP.replace("ssd_step", "ssd_chunk"), 600, 400), (POOL_COPY, 1000, 300),
        (CONV_SLICE, 1300, 100),
        (POOL_VIEW, 1400, 50), (LANE_CUT, 1450, 50), (OTHER, 1500, 550),
    ])
    on(path)
    spec = spec_of("nemotron_state_pool_copy_time_share")
    mod = reader(spec)
    shapes = mod.pool_shapes(load("configs", f"{CONFIG}.json"))
    assert {(6, 160, 128, 4096), (6, 160, 3, 6144), (160, 128, 4096), (1, 160, 3, 6144)} <= shapes
    # The kernels (whose results hold the pool) and the view are left out.
    assert mod.read(spec, {"trace": {"ops_s": {}}}) == pytest.approx(450 / 2050 * 100.0)
    assert mod.read(spec, {"trace": None}) is None
    write_trace(path, [(OTHER, 0, 500)])
    on(path)
    assert mod.read(spec, {"trace": {"ops_s": {}}}) == 0.0
    step = spec_of("ssd_step_time_share")
    assert reader(step).read(step, {"trace": {"ops_s": {"ssd_step.3": 1.0, "fusion.2": 3.0}}}) == 25.0
    assert reader(step).read(step, {"trace": {"ops_s": {"fusion.2": 3.0}}}) == 0.0


def test_the_token_share_reads_the_new_counter():
    spec = spec_of("ssd_prompt_token_share")
    ctx = {"prom_before": {}, "prom_after": counters(
        dynamo_mamba2_tokens_total__program_mixed=900.0,
        dynamo_mamba2_tokens_total__program_window=100.0,
    )}
    assert reader(spec).read(spec, ctx) == pytest.approx(90.0)
    assert reader(spec).read(spec, {"prom_before": {}, "prom_after": counters(
        dynamo_ssm_scan_tokens_total__program_mixed=9.0)}) is None


def test_the_program_names_the_scopes_kernel_and_counter_the_metrics_read():
    import inspect

    from dynamo_exp_tpu.engine import engine
    from dynamo_exp_tpu.models import llama
    from dynamo_exp_tpu.ops import ssd

    assert 'jax.named_scope("mamba2")' in inspect.getsource(llama._mamba2_half)
    text = inspect.getsource(llama.mamba2_mixer)
    assert 'jax.named_scope("mamba2_conv")' in text and 'jax.named_scope("ssd_scan")' in text
    assert 'name="ssd_step"' in inspect.getsource(ssd)
    assert re.search(spec_of("ssd_step_time_share")["pattern"], "ssd_step.12")
    assert ssd_scan_calls.KERNELS.match("%ssd_step.1 = (") and ssd_scan_calls.KERNELS.match("%ssd_chunk.2 = (")
    assert 'name="ssd_chunk"' in inspect.getsource(ssd)
    assert "mamba2_tokens" in inspect.getsource(engine.TPUEngine._count_slots)
    for metric, scope, not_scope in (
        ("mamba2_mixer_time_share", "jit(ragged_mixed)/while/body/closed_call/mamba2/dot_general",
         "jit(ragged_mixed)/while/body/closed_call/mamba/dot_general"),
        ("ssd_scan_time_share", "jit(ragged_mixed)/while/body/closed_call/mamba2/ssd_scan/exp",
         "jit(ragged_mixed)/while/body/closed_call/mamba2/mamba2_conv/mul"),
    ):
        spec = spec_of(metric)
        assert re.search(spec["scope"], scope) and not re.search(spec["scope"], not_scope)
