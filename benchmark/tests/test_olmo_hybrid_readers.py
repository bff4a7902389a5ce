"""What ``olmo-hybrid-7b.longdoc-saturate`` adds beside its comparison:
the configuration's file against the catalog's row, the cell's lists, the
two new readers (``trace_gdn_scan_roofline``, ``trace_gdn_state_pool_copy_share``)
and ``lib/gdn_scan_calls.py`` on a synthetic trace at this model's shapes
with hand-computed shares, the scope metrics' expressions, the counter's
ratio, and the traffic through ``closed_loop``. Each reader says nothing
without a trace."""

import importlib
import json
import os

import pytest

from lib import gdn_scan_calls, xplane_meta
from test_expert_calls import OTHER, PEAK, write_trace
from test_state_space import on  # noqa: F401  (the fixture: peaks and the trace's path)

BENCH = xplane_meta.HERE
CELL = "olmo-hybrid-7b.longdoc-saturate"
CONFIG = "olmo-hybrid-7b-l8"
NEW = (
    "gdn_mixer_time_share", "gdn_scan_time_share", "gdn_scan_roofline_share",
    "gdn_state_pool_copy_time_share", "gdn_prompt_token_share",
)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
N, H, DK, DV, ENTRIES = 4096, 30, 96, 192, 160
CHUNK = (
    f"%gated_delta_chunk.1 = (f32[{N},{H * DV}]{{1,0:T(8,128)}}, "
    f"f32[6,{ENTRIES},{DK},{H * DV}]{{3,2,1,0:T(8,128)}}) custom-call(s32[1]{{0}} %l, "
    f"s32[97]{{0}} %base, f32[{H},{N},128]{{2,1,0}} %q, f32[{H},{N},128]{{2,1,0}} %k, "
    f"f32[{N},{H * DV}]{{1,0}} %v, f32[{N},128]{{1,0}} %g, f32[{N},128]{{1,0}} %b, "
    f'f32[6,{ENTRIES},{DK},{H * DV}]{{3,2,1,0}} %pool), custom_call_target="tpu_custom_call"'
)
POOL_COPY = (
    f"%copy.7 = f32[6,{ENTRIES},{DK},{H * DV}]{{3,2,1,0}} "
    f"copy(f32[6,{ENTRIES},{DK},{H * DV}]{{3,2,1,0}} %p)"
)
CONV_SLICE = (
    f"%fusion.8 = bf16[{ENTRIES},3,11520]{{2,1,0}} "
    f"fusion(bf16[6,{ENTRIES},3,11520]{{3,2,1,0}} %p), kind=kLoop"
)
POOL_VIEW = (
    f"%bitcast.2 = f32[6,{ENTRIES},{DK},{H * DV}]{{3,2,1,0}} "
    f"bitcast(f32[{6 * ENTRIES},{DK},{H * DV}]{{2,1,0}} %p)"
)
LANE_CUT = (
    f"%mini-gather-slice.32 = f32[6,{ENTRIES},{DK},1920]{{3,2,1,0}} "
    f"slice(f32[6,{ENTRIES},{DK},{H * DV}]{{3,2,1,0}} %p), slice={{[0:6], [0:160], [0:96], [0:1920]}}"
)
JAMBA_POOL = "%copy.9 = f32[26,320,16,5120]{3,2,1,0} copy(f32[26,320,16,5120]{3,2,1,0} %p)"


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def spec_of(metric: str) -> dict:
    return load("layer_metrics", f"{metric}.json")["reader"]


def reader(spec: dict):
    return importlib.import_module(f"readers.{spec['kind']}")


def slots(live: float, pad: float) -> dict:
    key = lambda kind: (  # noqa: E731
        "dynamo_ragged_slots_total", (("kind", kind), ("program", "mixed")),
    )
    return {key("live"): live, key("pad"): pad}


# ------------------------------------------------------ the configuration
def test_the_file_is_the_catalogs_row_but_for_its_depth():
    config = load("configs", f"{CONFIG}.json")
    assert config["reduced"] == ["num_hidden_layers", "layer_types"]
    assert config["num_hidden_layers"] == 8 and config["chips"] == 1
    assert config["layer_types"] == (["linear_attention"] * 3 + ["full_attention"]) * 2
    assert (config["comparison"], config["reference"]) == ("linear_attention", "olmo_hybrid")
    for key in ("norm_placement", "qk_norm", "positions", "linear_mixer", "weights",
                "tokenizer", "chat_template", "tensor_names"):
        assert key in config["assumed"], key
    assert "pipeline stages" in config["deployment"] and "2,435,748,072" in config["deployment"]
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Olmo-Hybrid-7B")
    assert config["source"] == row["source_url"]
    published = row["config"]
    assert published["num_hidden_layers"] == 32
    assert config["layer_types"] == published["layer_types"][:8]
    for key, value in published.items():
        if key not in config["reduced"]:
            assert config[key] == value, key


def test_the_parameter_count_is_eval_shapes():
    import jax
    import numpy as np

    import run_cell
    from dynamo_exp_tpu.models import ModelConfig
    from dynamo_exp_tpu.models.llama import init_params, init_seq_state

    config = load("configs", f"{CONFIG}.json")
    mcfg = ModelConfig.from_hf_config(run_cell.hf_config(config))
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), mcfg))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert f"{count:,}" in config["deployment"]
    assert abs(count / 2435.7e6 - 1) < 1e-3
    # What a sequence carries: 13,685,760 B an entry, in two parts.
    state = jax.eval_shape(lambda: init_seq_state(mcfg, 1))
    assert state.state.shape == (6, 1, DK, H * DV) and str(state.state.dtype) == "float32"
    assert state.conv.shape == (6, 1, 3, 11520)
    assert sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state)) == 13_685_760


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
                 "window_kernel_dispatch_share", "seq_state_restores_in_window"):
        assert listed[name]["workloads"][-1] == CELL
    # No selective scan, no routed expert, no block pass runs here.
    for name, m in listed.items():
        if name.startswith(("ssm_", "mamba_", "moe_", "expert_", "block_", "denoise_")):
            assert CELL not in m.get("workloads", [CELL]), name
    cell = load("workloads", f"{CELL}.json")
    assert (cell["config"], cell["traffic"], cell["clients"], cell["drain_s"]) == (
        CONFIG, "longdoc-saturate", 32, 90,
    )
    args = load("configs", f"{CONFIG}.json")["serve_args"]
    assert args[args.index("--max-decode-slots") + 1] == "32"  # a slot a client


# ------------------------------------------------------------ the readers
def test_a_chunk_calls_sizes_and_its_least_time():
    call = gdn_scan_calls.call_of(CHUNK)
    assert (call["slots"], call["values"], call["dk"]) == (N, H * DV, DK)
    assert gdn_scan_calls.call_operations(call) == 6 * DK * DV * H * N
    assert gdn_scan_calls.call_bytes(call, H) == 4 * N * (2 * H * DK + 2 * H * DV + 2 * H)
    seconds, bound = gdn_scan_calls.least_seconds(call, H, PEAK)
    # 3.3 MFLOP against 69 KB a token: 16.8 us against 84 us a thousand tokens. HBM's.
    assert bound == "hbm"
    assert seconds == pytest.approx(gdn_scan_calls.call_bytes(call, H) / 819e9)
    assert gdn_scan_calls.least_seconds(call, H, PEAK, live=0.5)[0] == pytest.approx(seconds / 2)
    assert gdn_scan_calls.call_of(OTHER) is None


def test_the_roofline_reader_on_a_synthetic_trace(tmp_path, on):  # noqa: F811
    path = str(tmp_path / "t.xplane.pb")
    least = gdn_scan_calls.least_seconds(gdn_scan_calls.call_of(CHUNK), H, PEAK)[0]
    took_ns = 3_000_000
    write_trace(path, [(CHUNK, 0, took_ns), (OTHER, took_ns, 500), (CHUNK, 4_000_000, took_ns)])
    on(path)
    spec = spec_of("gdn_scan_roofline_share")
    assert spec["config"] == CONFIG
    read = reader(spec).read
    whole = read(spec, {"trace": {"ops_s": {}}})  # a program without the slots' counter
    assert whole == pytest.approx(least / (took_ns / 1e9) * 100.0) and 0 < whole < 100
    ctx = {"trace": {"ops_s": {}}, "prom_before": slots(100.0, 100.0),
           "prom_after": slots(3100.0, 1100.0)}
    assert read(spec, ctx) == pytest.approx(whole * 0.75)  # three slots in four held a token
    assert read(spec, {"trace": None}) is None
    write_trace(path, [(OTHER, 0, 500)])  # a program without the kernel: the parent
    on(path)
    assert read(spec, {"trace": {"ops_s": {}}}) is None


def test_a_share_of_the_roofline_cannot_pass_100(tmp_path, on):  # noqa: F811
    """A kernel as fast as the chip allows reads 100; what is counted is
    less than any implementation moves, so no real call reads more."""
    path = str(tmp_path / "t.xplane.pb")
    call = gdn_scan_calls.call_of(CHUNK)
    least_ns = int(gdn_scan_calls.least_seconds(call, H, PEAK)[0] * 1e9)
    write_trace(path, [(CHUNK, 0, least_ns)])
    on(path)
    spec = spec_of("gdn_scan_roofline_share")
    assert reader(spec).read(spec, {"trace": {"ops_s": {}}}) == pytest.approx(100.0, rel=1e-3)
    # What the kernel itself moves at the least: q and k padded to 128 lanes a head.
    kernel_bytes = 4 * N * (2 * H * 128 + 2 * H * DV + 2 * 128)
    assert gdn_scan_calls.call_bytes(call, H) < kernel_bytes


def test_the_pool_copy_reader_on_a_synthetic_trace(tmp_path, on):  # noqa: F811
    path = str(tmp_path / "t.xplane.pb")
    write_trace(path, [
        (CHUNK, 0, 1000), (POOL_COPY, 1000, 300), (CONV_SLICE, 1300, 100),
        (POOL_VIEW, 1400, 50), (JAMBA_POOL, 1450, 200), (OTHER, 1650, 300),
        (LANE_CUT, 1950, 100),
    ])
    on(path)
    spec = spec_of("gdn_state_pool_copy_time_share")
    mod = reader(spec)
    shapes = mod.pool_shapes(load("configs", f"{CONFIG}.json"))
    assert {(6, ENTRIES, DK, H * DV), (6, ENTRIES, 3, 11520), (ENTRIES, DK, H * DV),
            (1, ENTRIES, 3, 11520)} <= shapes
    # The kernel (whose result holds the pool), the view and another model's pool are left
    # out; a third of the whole pool's lanes, as XLA cut it for a gather, is counted.
    assert mod.read(spec, {"trace": {"ops_s": {}}}) == pytest.approx(500 / 2050 * 100.0)
    assert mod.read(spec, {"trace": None}) is None
    write_trace(path, [(OTHER, 0, 500)])
    on(path)
    assert mod.read(spec, {"trace": {"ops_s": {}}}) == 0.0


@pytest.mark.parametrize("metric,scope", [
    ("gdn_scan_time_share", "jit(ragged_mixed)/while/body/closed_call/gated_delta/gdn_scan/mul"),
    ("gdn_mixer_time_share", "jit(ragged_mixed)/while/body/closed_call/gated_delta/dot_general"),
])
def test_the_scope_metrics_find_their_scopes(metric, scope):
    import re

    spec = spec_of(metric)
    assert spec["kind"] == "trace_scope_share"
    assert re.search(spec["scope"], scope)
    assert not re.search(spec["scope"], "jit(ragged_mixed)/while/body/closed_call/ffn/dot_general")
    # gdn_conv and gdn_scan lie under gated_delta, not beside it
    assert re.search(spec_of("gdn_mixer_time_share")["scope"], scope)
    assert not re.search(spec_of("gdn_scan_time_share")["scope"], "a/gated_delta/gdn_conv/add")


def test_the_program_names_the_scopes_the_metrics_read():
    import inspect

    from dynamo_exp_tpu.models import llama

    text = inspect.getsource(llama)
    for scope in ("gated_delta", "gdn_conv", "gdn_scan"):
        assert f'jax.named_scope("{scope}")' in text
    from dynamo_exp_tpu.ops import gated_delta

    assert 'name="gated_delta_chunk"' in inspect.getsource(gated_delta)


def test_prompt_token_share_reads_the_counters_delta():
    spec = spec_of("gdn_prompt_token_share")
    key = lambda program: ("dynamo_linear_attn_tokens_total", (("program", program),))  # noqa: E731
    before = {key("mixed"): 1000.0, key("window"): 100.0}
    after = {key("mixed"): 4000.0, key("window"): 1100.0}
    got = reader(spec).read(spec, {"prom_before": before, "prom_after": after})
    assert got == pytest.approx(75.0)
    assert reader(spec).read(spec, {"prom_before": {}, "prom_after": {}}) is None  # the parent


# ------------------------------------------------------------- the traffic
def test_the_schedule_is_jambas_letter_for_letter():
    from test_traffic import load as load_traffic
    from test_traffic import plan

    mine = load_traffic("workloads", CELL)
    jambas = load_traffic("workloads", "jamba2-3b.longdoc-saturate")
    assert mine["traffic"] == jambas["traffic"] and mine["clients"] == jambas["clients"]
    a = plan("closed_loop", "longdoc-saturate", mine, 1, seconds=51.0)
    b = plan("closed_loop", "longdoc-saturate", jambas, 2**31 + 5, seconds=51.0)
    assert a == b and len(a["chains"]) == 32
