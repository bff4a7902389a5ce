"""``comparisons/state_space.py`` has teeth: on the CPU, an 8-layer jamba
at hidden 256 (period 4: six Mamba layers, two attention layers of 1 KV
head), ``models/llama.py::forward`` in the server's place (one device,
XLA attention, the scan's oracle), with the comparison's sizes cut to
128 + 8 tokens in chunks of 32; the program sound, and with deliberate
faults, each on the engine's side: ``references/jamba.py`` stays plain.

Also here: the new readers on a synthetic trace, and the traffic file
through ``closed_loop``."""

import dataclasses
import importlib
import json
import os
import types

import numpy as np
import pytest

from comparisons import state_space  # noqa: E402  (conftest.py put benchmark/ on the path)
from lib import modeldir, ssm_scan_calls, xplane_meta  # noqa: E402
from references import jamba  # noqa: E402
from test_expert_calls import OTHER, PEAK, write_trace  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HF = {
    "architectures": ["JambaForCausalLM"], "model_type": "jamba", "attn_layer_period": 4,
    "attn_layer_offset": 2, "expert_layer_period": 2, "expert_layer_offset": 1,
    "hidden_act": "silu", "hidden_size": 256, "intermediate_size": 512,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 16,
    "mamba_expand": 2, "mamba_proj_bias": False, "max_position_embeddings": 512,
    "num_attention_heads": 4, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 8, "num_key_value_heads": 1, "rms_norm_eps": 1e-6,
    "sliding_window": None, "tie_word_embeddings": True, "torch_dtype": "bfloat16",
    "vocab_size": 2048,
}
SEED = 2**31 + 7
FAULTS = ("zero_state", "reversed_taps", "no_d", "rope")
# Made on the reference's side by rehearse/check_seeds_jamba.py, as on the chip.
# ``bf16_state`` is left to the chip: a rounding of 2**-9 a token gathers over the
# scan's memory (hundreds of tokens at the slowest states), which 128 tokens are not.
CONTROLS = ("zero_state", "unsigned_a", "no_softplus", "no_inner_norms", "fp8_mamba", "rope")


@pytest.fixture(scope="module", autouse=True)
def small(request):
    """The comparison's sizes cut to what the CPU runs in seconds."""
    saved = {k: getattr(state_space, k) for k in ("PREFILL", "CHUNK", "DECODE")}
    state_space.PREFILL, state_space.CHUNK, state_space.DECODE = 128, 32, 8
    yield
    for k, v in saved.items():
        setattr(state_space, k, v)


def build_model(model_dir: str):
    import jax
    import jax.numpy as jnp
    from tokenizers import Tokenizer

    from dynamo_exp_tpu.models import llama
    from dynamo_exp_tpu.models.config import ModelConfig

    model_dir = modeldir.prepare(model_dir, HF)
    tokenizer_file = os.path.join(model_dir, "tokenizer.json")
    tok = Tokenizer.from_file(tokenizer_file)
    mcfg = ModelConfig.from_hf_config(HF)
    sound = llama.init_params(jax.random.PRNGKey(SEED % 2**31), mcfg)
    page, width = 16, 64

    def program(fault):
        cfg, layers = mcfg, dict(sound["layers"])
        if fault == "no_d":
            layers["mamba_D"] = jnp.zeros_like(layers["mamba_D"])
        elif fault == "reversed_taps":
            layers["mamba_conv_w"] = layers["mamba_conv_w"][:, ::-1]
        elif fault == "rope":  # rotary positions where the model has none
            cfg = dataclasses.replace(mcfg, use_rope=True)
        params = {**sound, "layers": layers}

        @jax.jit
        def top5(ids, n):
            k, v = llama.init_kv_cache(cfg, width // page, page)
            pos = jnp.where(jnp.arange(width) < n, jnp.arange(width), -1)
            logits, *_ = llama.forward(
                params, cfg, ids[None], pos[None].astype(jnp.int32),
                jnp.arange(width // page, dtype=jnp.int32)[None], k, v,
                last_positions=(n - 1)[None],
                seq_state=llama.init_seq_state(cfg, 1), state_slots=jnp.zeros((1,), jnp.int32),
            )
            return jax.lax.top_k(jax.nn.log_softmax(logits[0, 0]), 5)

        def ask(content, n_tokens):
            ids = tok.encode(f"<|user|>{content}</s><|assistant|>", add_special_tokens=False).ids
            n_prompt, entries = len(ids), []
            for _ in range(n_tokens):
                padded = jnp.asarray(ids + [0] * (width - len(ids)), jnp.int32)
                lp, idx = top5(padded, jnp.asarray(len(ids), jnp.int32))
                lp, idx = np.asarray(lp), np.asarray(idx)
                entries.append({
                    "token": tok.decode([int(idx[0])], skip_special_tokens=True),
                    "logprob": float(lp[0]),
                    "top_logprobs": [{"logprob": float(x)} for x in lp],
                })
                ids.append(int(idx[0]))
            return n_prompt, entries

        engine = types.SimpleNamespace(
            params=params, mesh=None, cfg=types.SimpleNamespace(model=cfg)
        )
        return engine, ask

    return sound, tokenizer_file, program


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    return build_model(str(tmp_path_factory.mktemp("model")))


@pytest.mark.parametrize("fault", (None,) + FAULTS)
def test_state_space_passes_the_sound_program_and_fails_each_fault(model, fault):
    import jax.numpy as jnp

    sound, tokenizer_file, program = model
    engine, ask = program(fault)
    ctx = {
        "engine": engine, "ask": ask, "params": sound, "hf": HF, "seed": SEED,
        "tokenizer_file": tokenizer_file, "reference": jamba,
    }
    if fault == "zero_state":  # the scan's state dropped after the second chunk
        ctx["state_fault"] = lambda st: st._replace(ssm=jnp.zeros_like(st.ssm))
    out = state_space.compare(ctx)
    print(fault, {k: v for k, v in out.items() if k != "step_diffs"})
    by_op = {
        op: max((c for c in out["op_half"] if c["op"] == op), key=lambda c: c["max_rel_err"])
        for op in ("mamba", "attn")
    }
    if fault is None:
        assert out["ok"] and out["steps"] == 32, out["why"]
        assert [(c["layer"], c["op"]) for c in out["op_half"]] == [
            (0, "mamba"), (2, "attn"), (4, "mamba"), (6, "attn"), (7, "mamba"),
        ]
        assert by_op["mamba"]["max_rel_err"] < state_space.MAMBA_TOL
        assert by_op["mamba"]["max_rel_err_decoded"] <= by_op["mamba"]["max_rel_err"]
        assert 0 < out["state_max_rel_err"] < state_space.STATE_TOL
        assert by_op["attn"]["max_rel_err"] < state_space.ATTN_TOL
        return
    assert not out["ok"], out
    if fault == "rope":
        assert by_op["attn"]["max_rel_err"] > state_space.ATTN_TOL
        assert by_op["mamba"]["max_rel_err"] < state_space.MAMBA_TOL
    else:
        assert by_op["mamba"]["max_rel_err"] > state_space.MAMBA_TOL
        assert by_op["attn"]["max_rel_err"] < state_space.ATTN_TOL


@pytest.mark.parametrize("name", CONTROLS)
def test_the_chips_controls_fail_the_mamba_part(model, name):
    """``rehearse/check_seeds_jamba.py``'s controls, the reference
    computing with the fault against the sound program, as the chip runs
    them: each fails the Mamba part (``rope`` the attention part), and leaves attention sound unless the
    fault is gross enough to blow up the hidden states attention is given."""
    import sys

    sys.path.insert(0, os.path.join(BENCH, "rehearse"))
    import check_seeds_jamba

    sound, tokenizer_file, program = model
    engine, ask = program(None)
    ctx = {
        "engine": engine, "ask": ask, "params": sound, "hf": HF, "seed": SEED,
        "tokenizer_file": tokenizer_file, "reference": jamba,
    }
    with check_seeds_jamba.control(name, ctx) as faulty:
        out = state_space.compare(faulty)
    print(name, {k: v for k, v in out.items() if k not in ("step_diffs", "op_half")})
    assert not out["ok"]
    if name == "rope":
        assert out["attn_max_rel_err"] > state_space.ATTN_TOL
    else:
        assert not out["mamba_max_rel_err"] <= state_space.MAMBA_TOL
    if name not in ("unsigned_a", "no_softplus", "rope"):
        assert out["attn_max_rel_err"] < state_space.ATTN_TOL
    # The reference is plain again afterwards.
    assert state_space.compare(ctx)["ok"]


def test_head_rows_stand_round_every_dispatch_boundary():
    rows = state_space.head_rows(2048, 512, 32)
    for edge in (512, 1024, 1536):
        assert {edge - 1, edge, edge + 3} <= set(rows)
    assert rows[-32:] == list(range(2048, 2080)) and rows == sorted(set(rows))
    per_chunk = [sum(1 for r in rows if a <= r < a + 512) for a in range(0, 2048, 512)]
    assert len(set(per_chunk)) == 1  # one compiled prefill program


def test_the_comparison_ends_a_tree_that_lacks_the_operator():
    """What makes the parent of PR 33 fail at once in the new cell: the
    module takes ``mamba_mixer`` from the program when it is imported,
    which is before the engine is built (``comparisons.resolve``)."""
    import inspect

    head = inspect.getsource(state_space).split("from lib import correct")[0]
    assert "from dynamo_exp_tpu.models.llama import MAMBA_PARAMS, mamba_mixer" in head


# ------------------------------------------------------------- the readers
SCAN = (
    "%ssm_scan.1 = (f32[4096,5120]{1,0:T(8,128)}, f32[26,320,16,5120]{3,2,1,0:T(8,128)}) "
    "custom-call(s32[1]{0} %l, s32[512]{0} %a, s32[512]{0} %b, s32[512]{0} %c, "
    "f32[4096,5120]{1,0} %x, f32[4096,5120]{1,0} %dt, f32[4096,16,128]{2,1,0} %bb, "
    "f32[4096,16,128]{2,1,0} %cc, f32[16,5120]{1,0} %aa, f32[1,5120]{1,0} %d, "
    'f32[26,320,16,5120]{3,2,1,0} %pool), custom_call_target="tpu_custom_call"'
)
POOL_COPY = "%copy.7 = f32[26,320,16,5120]{3,2,1,0} copy(f32[26,320,16,5120]{3,2,1,0} %p)"
CONV_SLICE = "%fusion.8 = bf16[320,3,5120]{2,1,0} fusion(bf16[26,320,3,5120]{3,2,1,0} %p), kind=kLoop"
POOL_VIEW = "%bitcast.2 = f32[26,320,16,5120]{3,2,1,0} bitcast(f32[8320,16,5120]{2,1,0} %p)"


def spec_of(metric: str) -> dict:
    with open(os.path.join(BENCH, "layer_metrics", f"{metric}.json")) as f:
        return json.load(f)["reader"]


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


def test_a_scan_calls_sizes_and_its_least_time():
    call = ssm_scan_calls.call_of(SCAN)
    assert (call["slots"], call["channels"], call["states"]) == (4096, 5120, 16)
    n, e, s = 4096, 5120, 16
    assert ssm_scan_calls.call_bytes(call) == 4 * (3 * n * e + 2 * n * s + s * e + e)
    assert ssm_scan_calls.call_operations(call) == 7 * n * e * s
    seconds, bound = ssm_scan_calls.least_seconds(call, PEAK)
    assert bound == "hbm" and seconds == pytest.approx(ssm_scan_calls.call_bytes(call) / 819e9)
    assert ssm_scan_calls.call_of(OTHER) is None


def test_the_roofline_reader_on_a_synthetic_trace(tmp_path, on):
    path = str(tmp_path / "t.xplane.pb")
    least = ssm_scan_calls.least_seconds(ssm_scan_calls.call_of(SCAN), PEAK)[0]
    took_ns = 2_000_000
    write_trace(path, [(SCAN, 0, took_ns), (OTHER, took_ns, 500), (SCAN, 3_000_000, took_ns)])
    on(path)
    spec = spec_of("ssm_scan_roofline_share")
    read = importlib.import_module(f"readers.{spec['kind']}").read
    got = read(spec, {"trace": {"ops_s": {}}})
    assert got == pytest.approx(least / (took_ns / 1e9) * 100.0) and 0 < got < 100
    assert read(spec, {"trace": None}) is None
    write_trace(path, [(OTHER, 0, 500)])  # a program without the kernel: the parent
    on(path)
    assert read(spec, {"trace": {"ops_s": {}}}) is None


def test_the_pool_copy_reader_on_a_synthetic_trace(tmp_path, on):
    path = str(tmp_path / "t.xplane.pb")
    write_trace(path, [
        (SCAN, 0, 1000), (POOL_COPY, 1000, 300), (CONV_SLICE, 1300, 100),
        (POOL_VIEW, 1400, 50), (OTHER, 1450, 600),
    ])
    on(path)
    spec = spec_of("seq_state_pool_copy_time_share")
    mod = importlib.import_module(f"readers.{spec['kind']}")
    with open(os.path.join(BENCH, "configs", "jamba2-3b.json")) as f:
        shapes = mod.pool_shapes(json.load(f))
    assert {(26, 320, 16, 5120), (26, 320, 3, 5120), (320, 16, 5120), (1, 320, 3, 5120)} <= shapes
    # The kernel (whose result holds the pool) and the view are left out.
    assert mod.read(spec, {"trace": {"ops_s": {}}}) == pytest.approx(400 / 2050 * 100.0)
    assert mod.read(spec, {"trace": None}) is None
    write_trace(path, [(OTHER, 0, 500)])
    on(path)
    assert mod.read(spec, {"trace": {"ops_s": {}}}) == 0.0


@pytest.mark.parametrize("metric,scope", [
    ("ssm_scan_time_share", "jit(ragged_mixed)/while/body/closed_call/mamba/ssm_scan/mul"),
    ("mamba_mixer_time_share", "jit(ragged_mixed)/while/body/closed_call/mamba/dot_general"),
])
def test_the_scope_metrics_find_their_scopes(metric, scope):
    import re

    spec = spec_of(metric)
    assert spec["kind"] == "trace_scope_share"
    assert re.search(spec["scope"], scope)
    assert not re.search(spec["scope"], "jit(ragged_mixed)/while/body/closed_call/ffn/dot_general")
    # mamba_conv and ssm_scan lie under mamba, not beside it
    assert re.search(spec_of("mamba_mixer_time_share")["scope"], scope)
    assert not re.search(spec_of("ssm_scan_time_share")["scope"], "a/mamba/mamba_conv/add")


def test_prompt_token_share_reads_the_counters_delta():
    spec = spec_of("prompt_token_share")
    read = importlib.import_module(f"readers.{spec['kind']}").read
    key = lambda program: ("dynamo_ssm_scan_tokens_total", (("program", program),))  # noqa: E731
    before = {key("mixed"): 1000.0, key("window"): 100.0}
    after = {key("mixed"): 4000.0, key("window"): 1100.0}
    got = read(spec, {"prom_before": before, "prom_after": after})
    assert got == pytest.approx(75.0)
    assert read(spec, {"prom_before": {}, "prom_after": {}}) is None  # the parent: no counter


# ------------------------------------------------------------- the traffic
def test_longdoc_saturate_through_closed_loop():
    from test_traffic import load, plan

    traffic, cell = load("traffic", "longdoc-saturate"), load("workloads", "jamba2-3b.longdoc-saturate")
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 4096, "sigma": 0.6, "min": 1024, "max": 16384,
    }
    assert traffic["output_tokens"] == {
        "dist": "lognormal", "median": 160, "sigma": 0.5, "min": 64, "max": 512,
    }
    assert (cell["clients"], cell["drain_s"], traffic["lead_in_s"], traffic["schedule_seed"]) == (
        32, 90, 15, 23,
    )
    a = plan("closed_loop", "longdoc-saturate", cell, 1, seconds=51.0)
    b = plan("closed_loop", "longdoc-saturate", cell, 2, seconds=51.0)
    assert a == b  # FakeText: the seed makes the text alone
    assert len(a["chains"]) == 32 and {len(c["requests"]) for c in a["chains"]} == {9}
    prompts = [int(r["messages"][0]["content"][1:-1]) for c in a["chains"] for r in c["requests"]]
    outputs = [r["max_tokens"] for c in a["chains"] for r in c["requests"]]
    assert min(prompts) >= 1024 and max(outputs) <= 512 and min(outputs) >= 64
    # No request outgrows --max-model-len 16384 with its reply.
    assert max(p + o for p, o in zip(prompts, outputs)) + 64 < 16384
    assert 3800 < sorted(prompts)[len(prompts) // 2] < 4400
    assert 25 < sum(prompts) / sum(outputs) < 35  # prompt tokens outnumber output ~30 : 1
