"""``comparisons/latent_attention.py`` has teeth: on the CPU, a 4-layer
pangu_ultra_moe at hidden 256 (latent attention whose ranks are no
multiples of 128, one dense layer and three routed ones, 8 of 32 experts
held beside a shared one), ``models/llama.py::forward`` in the server's
place (one device, XLA attention), the program sound and with each of the
faults ``rehearse/check_seeds_pangu.py`` makes on the chip, every one on the
ENGINE's side: ``references/openpangu_moe.py`` stays plain. The served walk
asks the sound program (as on the chip, where the server compiled its
programs before a fault was made), so a fault has to show in the parts that
call ``models/llama.py`` anew."""

import os
import sys
import types

import numpy as np
import pytest

from comparisons import latent_attention  # noqa: E402  (conftest.py put benchmark/ on the path)
from lib import modeldir  # noqa: E402
from references import openpangu_moe  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "rehearse"))
import check_seeds_pangu  # noqa: E402

HF = {
    "architectures": ["PanguUltraMoEForCausalLM"], "model_type": "pangu_ultra_moe",
    "hidden_size": 256, "intermediate_size": 512, "moe_intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 4, "num_hidden_layers": 4,
    "first_k_dense_replace": 1, "q_lora_rank": 96, "kv_lora_rank": 72, "qk_nope_head_dim": 32,
    "qk_rope_head_dim": 32, "v_head_dim": 32, "n_routed_experts": 8,
    "n_routed_experts_global": 32, "first_routed_expert": 16, "n_shared_experts": 1,
    "num_experts_per_tok": 4, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "sandwich_norm": True, "attention_bias": False, "hidden_act": "silu",
    "num_nextn_predict_layers": 0, "rms_norm_eps": 1e-5, "rope_theta": 25600000,
    "max_position_embeddings": 512, "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "vocab_size": 2048,
}
SEED = 2**31 + 7
# (the control, the part of the comparison that has to refuse it)
FAULTS = (
    ("score_scale", "long"), ("latent_before_norm", "latent_rows"),
    ("key_before_rope", "latent_rows"), ("held_renorm", "block"), ("no_shared", "block"),
    ("latent_fp8", "latent_rows"), ("ukv_fp8", "long"), ("fp8_experts", "block"),
    ("top1", "block"),
)


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    import jax
    import jax.numpy as jnp
    from tokenizers import Tokenizer

    from dynamo_exp_tpu.models import llama
    from dynamo_exp_tpu.models.config import ModelConfig

    model_dir = modeldir.prepare(str(tmp_path_factory.mktemp("model")), HF)
    tokenizer_file = os.path.join(model_dir, "tokenizer.json")
    tok = Tokenizer.from_file(tokenizer_file)
    mcfg = ModelConfig.from_hf_config(HF)
    params = llama.init_params(jax.random.PRNGKey(SEED % 2**31), mcfg)
    page, width = 16, 64

    @jax.jit
    def top5(ids, n):
        k, v = llama.init_kv_cache(mcfg, width // page, page)
        pos = jnp.where(jnp.arange(width) < n, jnp.arange(width), -1)
        logits, *_ = llama.forward(
            params, mcfg, ids[None], pos[None].astype(jnp.int32),
            jnp.arange(width // page, dtype=jnp.int32)[None], k, v, last_positions=(n - 1)[None],
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

    engine = types.SimpleNamespace(params=params, mesh=None, cfg=types.SimpleNamespace(model=mcfg))
    return {
        "engine": engine, "ask": ask, "params": params, "hf": HF, "seed": SEED,
        "tokenizer_file": tokenizer_file, "reference": openpangu_moe,
        # three chunks and a ragged last one for the longer row, two for the shorter
        "long_tokens": (200, 72), "long_chunk": 64,
    }


def brief(out: dict) -> dict:
    return {k: v for k, v in out.items() if k not in ("block", "step_diffs", "latent_rows", "long")}


def test_the_sound_program_passes_every_part(model):
    out = latent_attention.compare(model)
    print(brief(out))
    assert out["ok"], out["why"]
    assert out["steps"] == 32 and out["latent_padding_max_abs"] == 0.0
    assert [(c["layer"], c["rows"]) for c in out["block"]] == [
        (1, 64), (1, 1024), (2, 64), (2, 1024), (3, 64), (3, 1024),
    ]
    assert [c["layer"] for c in out["latent_rows"]] == [0, 2, 3]
    assert [(c["layer"], c["tokens"]) for c in out["long"]] == [
        (0, 200), (0, 72), (2, 200), (2, 72), (3, 200), (3, 72),
    ]
    # rows of every chunk and every decoded token were compared
    assert out["long"][0]["rows"] == len(latent_attention.head_rows(200, 64, 1)) + latent_attention.DECODE
    assert 0.15 < out["held_pick_share"] < 0.35  # 8 of 32
    assert 0 < out["block_max_rel_err"] < latent_attention.BLOCK_TOL
    assert 0 < out["latent_rows_max_rel_err"] < latent_attention.LATENT_TOL
    assert 0 < out["long_max_rel_err"] < latent_attention.ATTN_TOL


@pytest.mark.parametrize("fault,part", FAULTS)
def test_each_fault_on_the_engines_side_is_refused(model, fault, part):
    with check_seeds_pangu.control(fault, model) as faulty:
        out = latent_attention.compare(faulty)
    print(fault, brief(out))
    assert not out["ok"] and part in out["why"], out["why"]


def test_head_rows_cover_every_chunk():
    rows = latent_attention.head_rows(16000, 2048)
    assert rows[0] == 7 and rows[-1] == 15999 and len(rows) == 8 * latent_attention.ROWS_A_CHUNK
    for a in range(0, 16000, 2048):
        assert a + 7 in rows and min(a + 2047, 15999) in rows


def test_layers_read():
    assert latent_attention.layers_read({"num_hidden_layers": 5, "first_k_dense_replace": 1}) == (
        [0, 2, 4], [1, 3, 4],
    )
