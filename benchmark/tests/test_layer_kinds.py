"""``comparisons/layer_kinds.py`` has teeth: on the CPU, an 8-layer
lfm2_moe at hidden 256 (both operator kinds, both feed-forward kinds),
``models/llama.py::forward`` in the server's place (one device, XLA
attention), the program sound and with deliberate faults, each on the
engine's side: ``references/lfm2_moe.py`` stays plain."""

import dataclasses
import os
import types

import numpy as np
import pytest

from comparisons import layer_kinds  # noqa: E402  (conftest.py put benchmark/ on the path)
from lib import modeldir  # noqa: E402
from references import lfm2_moe  # noqa: E402

HF = {
    "architectures": ["Lfm2MoeForCausalLM"], "model_type": "lfm2_moe", "hidden_size": 256,
    "intermediate_size": 512, "moe_intermediate_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 8, "num_dense_layers": 2,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv"],
    "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True, "use_expert_bias": True,
    "routed_scaling_factor": 1, "norm_eps": 1e-5, "rope_theta": 1000000, "conv_L_cache": 3,
    "conv_bias": False, "max_position_embeddings": 512, "torch_dtype": "bfloat16",
    "vocab_size": 2048,
}
SEED = 2**31 + 7
FAULTS = (
    "select_by_score", "no_renorm", "fp8_experts", "fp8_conv", "zero_state", "reversed_taps",
)


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

    def fp8(w):
        return w.astype(jnp.float8_e4m3fn).astype(w.dtype)

    def program(fault):
        cfg, layers = mcfg, dict(sound["layers"])
        if fault == "select_by_score":  # the bias ignored
            layers["router_bias"] = jnp.zeros_like(layers["router_bias"])
        elif fault == "no_renorm":
            cfg = dataclasses.replace(mcfg, norm_topk_prob=False)
        elif fault == "fp8_experts":
            layers.update({k: fp8(layers[k]) for k in ("w_gate", "w_up", "w_down")})
        elif fault == "fp8_conv":
            layers.update({k: fp8(layers[k]) for k in ("conv_in", "conv_out")})
        elif fault == "reversed_taps":
            layers["conv_w"] = layers["conv_w"][:, ::-1]
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
def test_layer_kinds_passes_the_sound_program_and_fails_each_fault(model, fault):
    import jax.numpy as jnp

    sound, tokenizer_file, program = model
    engine, ask = program(fault)
    ctx = {
        "engine": engine, "ask": ask, "params": sound, "hf": HF, "seed": SEED,
        "tokenizer_file": tokenizer_file, "reference": lfm2_moe,
    }
    if fault == "zero_state":  # the decode steps start from a zero state
        ctx["state_fault"] = jnp.zeros_like
    out = layer_kinds.compare(ctx)
    print(fault, {k: v for k, v in out.items() if k not in ("block", "step_diffs")})
    block = max(out["block"], key=lambda c: c["max_rel_err"])
    op_half = max(out["op_half"], key=lambda c: c["max_rel_err"])
    if fault is None:
        assert out["ok"] and out["steps"] == 32, out["why"]
        assert [c["layer"] for c in out["block"]][::2] == [2, 5, 7]
        assert [(c["layer"], c["op"]) for c in out["op_half"]] == [
            (0, "conv"), (2, "attn"), (6, "attn"), (7, "conv"),
        ]
        assert block["max_rel_err"] < layer_kinds.BLOCK_TOL / 1.5
        assert op_half["max_rel_err"] < layer_kinds.OP_HALF_TOL  # hidden 256: 0.010; the chip at 2048: 0.007
        return
    assert not out["ok"], out
    if fault in ("select_by_score", "no_renorm", "fp8_experts"):
        assert block["max_rel_err"] > layer_kinds.BLOCK_TOL
    else:
        assert op_half["op"] == "conv" and op_half["max_rel_err"] > layer_kinds.OP_HALF_TOL
    if fault == "zero_state":  # the prefilled rows are sound; the decoded ones are not
        assert op_half["max_rel_err_decoded"] == op_half["max_rel_err"]


def test_the_comparison_ends_a_tree_that_lacks_the_operator():
    """What makes the parent of PR 31 fail at once in the new cell: the
    module takes ``short_conv`` from the program when it is imported,
    which is before the engine is built (``comparisons.resolve``)."""
    import inspect

    head = inspect.getsource(layer_kinds).split("from lib import correct")[0]
    assert "from dynamo_exp_tpu.models.llama import init_seq_state, short_conv" in head
