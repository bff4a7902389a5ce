"""``comparisons/linear_attention.py`` has teeth: on the CPU, an 8-layer
olmo_hybrid at hidden 256 (two periods of linear, linear, linear, full:
six delta-rule layers of 4 heads of 16 by 32, two attention layers of as
many KV heads as heads), ``models/llama.py::forward`` in the server's
place (one device, XLA attention, the recurrence's oracle), with the
comparison's sizes cut to 128 + 8 tokens in chunks of 32; the program
sound, and with deliberate faults, each on the engine's side:
``references/olmo_hybrid.py`` stays plain."""

import dataclasses
import itertools
import json
import os
import sys
import types

import numpy as np
import pytest

from comparisons import linear_attention, state_space  # noqa: E402  (conftest.py: benchmark/ on the path)
from lib import modeldir  # noqa: E402
from references import olmo_hybrid  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KINDS = ["linear_attention"] * 3 + ["full_attention"]
HF = {
    "architectures": ["OlmoHybridForCausalLM"], "model_type": "olmo_hybrid",
    "hidden_act": "silu", "hidden_size": 256, "intermediate_size": 512,
    "attention_bias": False, "max_position_embeddings": 512, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_hidden_layers": 8, "layer_types": KINDS * 2,
    "linear_num_key_heads": 4, "linear_num_value_heads": 4, "linear_key_head_dim": 16,
    "linear_value_head_dim": 32, "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16", "vocab_size": 2048,
}
LONG_PROBE = 160  # the long probe's prompt: past PREFILL, where ``zero_state_late`` strikes
SEED = 2**31 + 5  # at this width the walk reads 0.16-0.34 by seed (PERF.md section 7, PR 39 (7))
FAULTS = ("zero_state", "reversed_taps", "beta_no_2", "no_decay", "rope")
# Made on the reference's side by rehearse/check_seeds_olmo_hybrid.py, as on the chip.
# ``bf16_state``'s reading after decode is left to the chip: a rounding of 2**-9 a step
# gathers over the heads' memory (thousands of tokens at the slowest decays), which 8
# decoded tokens are not. After prefill it shows here too (2 kernel chunks of 64).
CONTROLS = (
    "zero_state", "beta_no_2", "no_decay", "no_qk_norm", "reversed_taps", "no_gate",
    "fp8_mixer", "rope", "bf16_state_prefill", "zero_state_late",
)


@pytest.fixture(scope="module", autouse=True)
def small():
    """The comparison's sizes cut to what the CPU runs in seconds (they
    are ``state_space``'s, read by both modules when called)."""
    names = ("PREFILL", "CHUNK", "DECODE")
    saved = {(m, k): getattr(m, k) for m in (state_space, linear_attention) for k in names}
    for k in ("LONG_PROBE_TOKENS", "_ASKINGS"):
        saved[linear_attention, k] = getattr(linear_attention, k)
    for m in (state_space, linear_attention):
        m.PREFILL, m.CHUNK, m.DECODE = 128, 32, 8
    linear_attention.LONG_PROBE_TOKENS = LONG_PROBE
    # one long probe for every asking: nothing is cached here, and at this width its
    # reading swings by its words as the short walk's does (SEED's note)
    linear_attention._ASKINGS = itertools.repeat(0)
    yield
    for (m, k), v in saved.items():
        setattr(m, k, v)


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
    page, width = 16, 192  # the long probe and its 8 tokens

    def program(fault):
        cfg, layers = mcfg, dict(sound["layers"])
        if fault == "reversed_taps":
            layers["gdn_conv_w"] = layers["gdn_conv_w"][:, ::-1]
        elif fault == "no_decay":
            layers["gdn_A_log"] = jnp.full_like(layers["gdn_A_log"], -jnp.inf)
        elif fault == "beta_no_2":
            cfg = dataclasses.replace(mcfg, linear_allow_neg_eigval=False)
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


def ctx_of(model, fault=None):
    sound, tokenizer_file, program = model
    engine, ask = program(fault)
    return {
        "engine": engine, "ask": ask, "params": sound, "hf": HF, "seed": SEED,
        "tokenizer_file": tokenizer_file, "reference": olmo_hybrid,
    }


def worst(out, op):
    return max((c for c in out["op_half"] if c["op"] == op), key=lambda c: c["max_rel_err"])


def state_fails(out) -> bool:
    return (
        not out["state_prefill_max_rel_err"] <= linear_attention.STATE_PREFILL_TOL
        or not out["state_decode_max_rel_err"] <= linear_attention.STATE_DECODE_TOL
    )


@pytest.mark.parametrize("fault", (None,) + FAULTS)
def test_linear_attention_passes_the_sound_program_and_fails_each_fault(model, fault):
    import jax.numpy as jnp

    ctx = ctx_of(model, fault)
    if fault == "zero_state":  # the matrix state dropped after the second chunk
        ctx["state_fault"] = lambda st, c: (
            st._replace(state=jnp.zeros_like(st.state)) if c == 1 else st
        )
    out = linear_attention.compare(ctx)
    print(fault, {k: v for k, v in out.items() if k != "step_diffs"})
    if fault is None:
        assert out["ok"] and out["steps"] == 32, out["why"]
        assert LONG_PROBE - 2 <= out["long_prompt_tokens"] <= LONG_PROBE
        assert len(out["long_step_diffs"]) == 8
        assert 0 < out["long_max_abs_logprob_diff"] < linear_attention.LONG_WALK_TOL
        assert [(c["layer"], c["op"]) for c in out["op_half"]] == [
            (0, "linear"), (3, "attn"), (4, "linear"), (6, "linear"), (7, "attn"),
        ]
        assert worst(out, "linear")["max_rel_err"] < linear_attention.MIXER_TOL
        assert worst(out, "linear")["max_rel_err_decoded"] <= worst(out, "linear")["max_rel_err"]
        assert 0 < out["state_prefill_max_rel_err"] < linear_attention.STATE_PREFILL_TOL
        # the one-step form reads its convolution's history in bfloat16: ten times the kernel's
        assert out["state_prefill_max_rel_err"] < out["state_decode_max_rel_err"]
        assert out["state_decode_max_rel_err"] < linear_attention.STATE_DECODE_TOL
        assert worst(out, "attn")["max_rel_err"] < linear_attention.ATTN_TOL
        return
    assert not out["ok"], out
    if fault == "rope":
        assert worst(out, "attn")["max_rel_err"] > linear_attention.ATTN_TOL
        assert worst(out, "linear")["max_rel_err"] < linear_attention.MIXER_TOL
        return
    assert worst(out, "attn")["max_rel_err"] < linear_attention.ATTN_TOL
    assert worst(out, "linear")["max_rel_err"] > linear_attention.MIXER_TOL or state_fails(out)
    if fault in ("zero_state", "beta_no_2", "no_decay"):  # the state's own limits hold them
        assert state_fails(out)


@pytest.mark.parametrize("name", CONTROLS)
def test_the_chips_controls_fail_their_part(model, name):
    """``rehearse/check_seeds_olmo_hybrid.py``'s controls, the reference
    computing with the fault against the sound program, as the chip runs
    them: each fails the linear part (its log-probabilities or the state),
    ``rope`` the attention part, and the reference is plain again after."""
    sys.path.insert(0, os.path.join(BENCH, "rehearse"))
    import check_seeds_olmo_hybrid

    ctx = ctx_of(model)
    with check_seeds_olmo_hybrid.control(name, ctx) as faulty:
        out = linear_attention.compare(faulty)
    print(name, {k: v for k, v in out.items() if k not in ("step_diffs", "op_half")})
    assert not out["ok"]
    if name == "rope":
        assert out["attn_max_rel_err"] > linear_attention.ATTN_TOL
    elif name == "bf16_state_prefill":  # the chunked kernel's limit holds it, and no other
        assert out["state_prefill_max_rel_err"] > linear_attention.STATE_PREFILL_TOL
        assert out["why"].count(";") == 0 and "after prefill" in out["why"]
    elif name == "zero_state_late":  # a state lost before the long probe's last tokens
        assert out["long_max_abs_logprob_diff"] > linear_attention.LONG_WALK_TOL
        assert out["max_abs_logprob_diff"] < linear_attention.WALK_TOL
    else:
        assert not out["mixer_max_rel_err"] <= linear_attention.MIXER_TOL or state_fails(out)
    assert linear_attention.compare(ctx)["ok"]


def test_the_one_step_forms_reading_is_the_convolutions_history_in_bfloat16(model):
    """``bf16_conv_history`` is a diagnosis and no fault: the reference
    reading a decoded token's three earlier inputs in bfloat16, as the
    pool's entry holds them, passes, and the state after decode then
    agrees as closely as the state after prefill."""
    sys.path.insert(0, os.path.join(BENCH, "rehearse"))
    import check_seeds_olmo_hybrid

    ctx = ctx_of(model)
    plain = linear_attention.compare(ctx)
    with check_seeds_olmo_hybrid.control("bf16_conv_history", ctx) as held:
        out = linear_attention.compare(held)
    assert out["ok"], out["why"]
    assert out["state_prefill_max_rel_err"] == plain["state_prefill_max_rel_err"]
    assert out["state_decode_max_rel_err"] < linear_attention.STATE_PREFILL_TOL
    assert out["state_decode_max_rel_err"] < plain["state_decode_max_rel_err"] / 3


def test_the_state_is_compared_a_head_at_a_time():
    want = np.ones((3, 4, 5), np.float32)
    want[1] *= 100.0  # a head that remembers
    got = want.copy()
    got[0] *= 1.5  # a small head half wrong
    pool = got.transpose(1, 0, 2).reshape(4, 15)
    by_head, together = linear_attention.state_errors(pool, want)
    assert by_head == pytest.approx(0.5) and together < 0.01


def test_the_layers_read_at_the_cut():
    with open(os.path.join(BENCH, "configs", "olmo-hybrid-7b-l8.json")) as f:
        assert linear_attention.layers_read(json.load(f)) == ([0, 4, 6], [3, 7])


def test_the_comparison_ends_a_tree_that_lacks_the_operator():
    """What makes the parent of PR 39 fail at once in the new cell: the
    module takes ``gated_delta_mixer`` from the program when it is
    imported, which is before the engine is built (``comparisons.resolve``)."""
    import inspect

    head = inspect.getsource(linear_attention).split("from lib import correct")[0]
    assert "from dynamo_exp_tpu.models.llama import LINEAR_PARAMS, gated_delta_mixer" in head
