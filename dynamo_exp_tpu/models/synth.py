"""Write a self-contained HF-style model directory from a seed.

A machine with no network and no checkout history still has to serve a
model through the normal path: ``--model-path <dir> --random-weights``
needs a ``config.json`` and a tokenizer. This module generates both —
never weights — at any :class:`ModelConfig`'s widths:

- ``config.json`` in HF spelling, checked to parse back to the same
  ``ModelConfig`` (so a family this writer does not cover fails here
  instead of serving a different model under its name);
- a byte-level BPE ``tokenizer.json`` whose vocabulary has exactly
  ``vocab_size`` entries. Random weights sample any id below
  ``vocab_size``, so every id must decode or the detokenizer path is
  not being exercised;
- ``tokenizer_config.json`` with the chat template the tests use.

    python -m dynamo_exp_tpu.models.synth --preset llama-1b --out DIR
"""

from __future__ import annotations

import argparse
import json
import os
import random

from .config import PRESETS, ModelConfig

CHAT_TEMPLATE = (
    "{% for message in messages %}"
    "<|{{ message.role }}|>{{ message.content }}</s>"
    "{% endfor %}"
    "{% if add_generation_prompt %}<|assistant|>{% endif %}"
)
SPECIAL_TOKENS = ["<s>", "</s>", "<|user|>", "<|assistant|>", "<|system|>"]
BOS_ID, EOS_ID = 0, 1  # positions in SPECIAL_TOKENS
# A merged token never grows past this many byte symbols, so decoded
# text stays short however deep the merge chain.
_MAX_TOKEN_SYMBOLS = 12


def write_config(path: str, cfg: ModelConfig) -> None:
    """``config.json`` in HF spelling for a llama-family ``cfg``."""
    hf = {
        "architectures": ["LlamaForCausalLM"],
        "model_type": cfg.model_type,
        "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "intermediate_size": cfg.intermediate_size,
        "max_position_embeddings": cfg.max_position_embeddings,
        "rms_norm_eps": cfg.rms_norm_eps,
        "rope_theta": cfg.rope_theta,
        "bos_token_id": BOS_ID,
        "eos_token_id": EOS_ID,
        "tie_word_embeddings": cfg.tie_word_embeddings,
        "torch_dtype": cfg.dtype,
        # Read only by MoE families, but its parsed default differs
        # from the dataclass default: spell it so the round trip holds.
        "norm_topk_prob": cfg.norm_topk_prob,
    }
    if cfg.model_type == "lfm2_moe":  # the family's own keys
        del hf["rms_norm_eps"]
        hf.update(
            architectures=["Lfm2MoeForCausalLM"], norm_eps=cfg.rms_norm_eps,
            layer_types=list(cfg.layer_types), num_dense_layers=cfg.num_dense_layers,
            num_experts=cfg.num_experts, num_experts_per_tok=cfg.num_experts_per_tok,
            moe_intermediate_size=cfg.moe_intermediate_size,
            conv_L_cache=cfg.conv_L_cache, conv_bias=False,
            use_expert_bias=cfg.use_expert_bias,
            routed_scaling_factor=cfg.routed_scaling_factor,
        )
    if cfg.model_type == "jamba":  # the family's own keys
        attn = [i for i, k in enumerate(cfg.op_kinds) if k == "attn"]
        hf.update(
            architectures=["JambaForCausalLM"],
            attn_layer_offset=attn[0],
            attn_layer_period=attn[1] - attn[0] if len(attn) > 1 else cfg.num_layers,
            num_experts=1, num_experts_per_tok=1, hidden_act="silu",
            mamba_d_state=cfg.mamba_d_state, mamba_d_conv=cfg.mamba_d_conv,
            mamba_expand=cfg.mamba_expand, mamba_dt_rank=cfg.mamba_dt_rank,
            mamba_conv_bias=True, mamba_proj_bias=False, sliding_window=None,
        )
    if cfg.model_type == "olmo_hybrid":  # the family's own keys
        del hf["rope_theta"]
        hf.update(
            architectures=["OlmoHybridForCausalLM"], hidden_act="silu",
            attention_bias=False, layer_types=list(cfg.layer_types),
            linear_num_key_heads=cfg.linear_num_heads,
            linear_num_value_heads=cfg.linear_num_heads,
            linear_key_head_dim=cfg.linear_key_head_dim,
            linear_value_head_dim=cfg.linear_value_head_dim,
            linear_conv_kernel_dim=cfg.linear_conv_kernel_dim,
            linear_allow_neg_eigval=cfg.linear_allow_neg_eigval,
            rope_parameters={"rope_theta": None},
        )
    if cfg.model_type == "pangu_ultra_moe":  # the family's own keys
        del hf["num_key_value_heads"]
        hf.update(
            architectures=["PanguUltraMoEForCausalLM"], hidden_act="silu",
            attention_bias=False, sandwich_norm=True,
            q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
            qk_nope_head_dim=cfg.qk_nope_head_dim,
            qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
            first_k_dense_replace=cfg.num_dense_layers,
            n_routed_experts=cfg.experts_held,
            n_routed_experts_global=cfg.num_experts,
            first_routed_expert=cfg.first_expert_held,
            n_shared_experts=cfg.num_shared_experts,
            num_experts_per_tok=cfg.num_experts_per_tok,
            moe_intermediate_size=cfg.moe_intermediate_size,
            routed_scaling_factor=cfg.routed_scaling_factor,
            num_nextn_predict_layers=0,
        )
    elif cfg.model_type == "glm_moe_dsa":  # the family's own keys
        del hf["num_key_value_heads"]
        hf.update(
            architectures=["GlmMoeDsaForCausalLM"], hidden_act="silu",
            attention_bias=False,
            q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
            qk_nope_head_dim=cfg.qk_nope_head_dim,
            qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
            index_n_heads=cfg.index_n_heads, index_head_dim=cfg.index_head_dim,
            index_topk=cfg.index_topk,
            first_k_dense_replace=cfg.num_dense_layers,
            n_routed_experts=cfg.experts_held,
            n_routed_experts_global=cfg.num_experts,
            first_routed_expert=cfg.first_expert_held,
            n_shared_experts=cfg.num_shared_experts,
            num_experts_per_tok=cfg.num_experts_per_tok,
            moe_intermediate_size=cfg.moe_intermediate_size,
            routed_scaling_factor=cfg.routed_scaling_factor,
            scoring_func="sigmoid", topk_method="noaux_tc", n_group=1,
            topk_group=1, num_nextn_predict_layers=0,
        )
    elif cfg.model_type == "exaone_moe":  # the family's own keys
        del hf["rope_theta"]
        sliding = [t == "sliding_attention" for t in cfg.layer_types]
        hf.update(
            architectures=["ExaoneMoEForCausalLM"], hidden_act="silu",
            head_dim=cfg.head_dim_, layer_types=list(cfg.layer_types),
            sliding_window=cfg.sliding_window,
            sliding_windows=[cfg.sliding_window if s else 0 for s in sliding],
            rope_parameters={"rope_theta": cfg.rope_theta, "rope_type": "default"},
            first_k_dense_replace=cfg.num_dense_layers,
            mlp_layer_types=[
                "dense" if i < cfg.num_dense_layers else "sparse"
                for i in range(cfg.num_layers)
            ],
            num_experts=cfg.experts_held,
            num_experts_global=cfg.num_experts,
            first_routed_expert=cfg.first_expert_held,
            num_shared_experts=cfg.num_shared_experts,
            num_experts_per_tok=cfg.num_experts_per_tok,
            moe_intermediate_size=cfg.moe_intermediate_size,
            routed_scaling_factor=cfg.routed_scaling_factor,
            scoring_func="sigmoid", n_group=1, topk_group=1,
            num_nextn_predict_layers=0,
        )
    elif cfg.model_type == "mimo_v2_flash":  # the family's own keys
        del hf["rms_norm_eps"]
        sliding = [int(form == "sliding") for form in cfg.attn_forms]
        hd, dv = cfg.head_dim_, cfg.value_head_dim_
        hf.update(
            architectures=["MiMoV2FlashForCausalLM"], hidden_act="silu",
            layernorm_epsilon=cfg.rms_norm_eps, attention_bias=False,
            head_dim=hd, v_head_dim=dv, swa_head_dim=hd, swa_v_head_dim=dv,
            swa_num_attention_heads=cfg.num_heads,
            swa_num_key_value_heads=cfg.kv_heads_of("sliding"),
            # the smallest factor that int() takes back to the same count
            partial_rotary_factor=(cfg.rope_dim + 0.5) / hd if cfg.rotary_dim else 1.0,
            attention_value_scale=cfg.value_scale,
            swa_rope_theta=cfg.rope_local_base_freq or cfg.rope_theta,
            hybrid_layer_pattern=sliding, sliding_window=cfg.sliding_window,
            sliding_window_size=cfg.sliding_window,
            add_swa_attention_sink_bias="sliding" in cfg.sink_forms,
            add_full_attention_sink_bias="full" in cfg.sink_forms,
            moe_layer_freq=[int(i >= cfg.num_dense_layers) for i in range(cfg.num_layers)],
            n_routed_experts=cfg.experts_held,
            n_routed_experts_global=cfg.num_experts,
            first_routed_expert=cfg.first_expert_held,
            n_shared_experts=cfg.num_shared_experts or None,
            num_experts_per_tok=cfg.num_experts_per_tok,
            moe_intermediate_size=cfg.moe_intermediate_size,
            routed_scaling_factor=cfg.routed_scaling_factor,
            scoring_func="sigmoid", topk_method="noaux_tc", n_group=1, topk_group=1,
        )
    elif cfg.model_type == "ouro":  # the family's own keys
        hf.update(
            architectures=["OuroForCausalLM"], hidden_act="silu",
            head_dim=cfg.head_dim_, total_ut_steps=cfg.loop_passes,
            early_exit_threshold=1.0, rope_scaling=None,
            layer_types=["full_attention"] * cfg.num_layers,
            sliding_window=None, use_sliding_window=False,
            max_window_layers=cfg.num_layers,
        )
    elif cfg.model_type == "KeyeVL2":  # the family's own keys
        hf.update(
            architectures=["KeyeVL2ForConditionalGeneration"], hidden_act="silu",
            attention_bias=False, head_dim=cfg.head_dim_,
            num_experts=cfg.num_experts, num_local_experts=cfg.num_experts,
            num_experts_per_tok=cfg.num_experts_per_tok,
            moe_intermediate_size=cfg.moe_intermediate_size,
            decoder_sparse_step=1, mlp_only_layers=[],
            rope_scaling={
                "mrope_section": list(cfg.mrope_section),
                "rope_type": "default", "type": "default",
            },
            sa_config={
                "indexer_head_dim": cfg.index_head_dim,
                "indexer_num_heads": cfg.index_n_heads,
                "indexer_num_kv_heads": 1, "topk": cfg.index_topk,
                "q_chunk_size": 512, "kv_chunk_size": 512,
            },
            sliding_window=None, use_sliding_window=False,
            max_window_layers=cfg.num_layers,
        )
    elif cfg.model_type == "nemotron_h":  # the family's own keys
        del hf["rms_norm_eps"], hf["rope_theta"]
        pattern = "".join(
            ("M" if op == "mamba2" else "*") + ("E" if ff == "routed" else "")
            for op, ff in zip(cfg.op_kinds, cfg.ff_kinds)
        )
        hf.update(
            architectures=["NemotronHForCausalLM"], num_hidden_layers=len(pattern),
            hybrid_override_pattern=pattern, layer_norm_epsilon=cfg.rms_norm_eps,
            head_dim=cfg.head_dim_, attention_bias=False, mlp_bias=False,
            use_bias=False, mamba_proj_bias=False, use_conv_bias=True,
            mlp_hidden_act="relu2", mamba_hidden_act="silu",
            mamba_num_heads=cfg.mamba2_heads, mamba_head_dim=cfg.mamba2_head_dim,
            n_groups=cfg.mamba2_groups, ssm_state_size=cfg.mamba_d_state,
            conv_kernel=cfg.mamba_d_conv, chunk_size=128, expand=2,
            n_routed_experts=cfg.experts_held,
            n_routed_experts_global=cfg.num_experts,
            first_routed_expert=cfg.first_expert_held,
            num_experts_per_tok=cfg.num_experts_per_tok,
            moe_intermediate_size=cfg.moe_intermediate_size,
            moe_shared_expert_intermediate_size=cfg.shared_expert_width,
            n_shared_experts=1, n_group=1, topk_group=1,
            routed_scaling_factor=cfg.routed_scaling_factor,
            sliding_window=None,
        )
    elif cfg.head_dim is not None:
        hf["head_dim"] = cfg.head_dim
    if cfg.rope_scaling is not None:
        hf["rope_scaling"] = cfg.rope_scaling
    if ModelConfig.from_hf_config(hf) != cfg:
        raise ValueError(
            f"config.json written for model_type={cfg.model_type!r} does "
            "not parse back to the same ModelConfig; this writer covers "
            "the llama family, lfm2_moe, jamba, olmo_hybrid, pangu_ultra_moe, "
            "glm_moe_dsa, exaone_moe, mimo_v2_flash, ouro, KeyeVL2 and "
            "nemotron_h only"
        )
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(hf, f)


def write_tokenizer_config(path: str) -> None:
    with open(os.path.join(path, "tokenizer_config.json"), "w") as f:
        json.dump(
            {
                "bos_token": SPECIAL_TOKENS[BOS_ID],
                "eos_token": SPECIAL_TOKENS[EOS_ID],
                "chat_template": CHAT_TEMPLATE,
            },
            f,
        )


def write_full_vocab_tokenizer(path: str, vocab_size: int, seed: int) -> None:
    """Byte-level BPE with exactly ``vocab_size`` ids, from ``seed``:
    the special tokens, the 256 byte symbols, then synthetic merges —
    each new token is an existing token plus one byte symbol, which is
    all BPE needs for the merge table to be consistent."""
    from tokenizers import (
        AddedToken,
        Tokenizer,
        decoders,
        models,
        pre_tokenizers,
    )

    alphabet = sorted(pre_tokenizers.ByteLevel.alphabet())
    n_merges = vocab_size - len(SPECIAL_TOKENS) - len(alphabet)
    if n_merges < 0:
        raise ValueError(
            f"vocab_size {vocab_size} is smaller than the special tokens "
            f"plus the byte alphabet ({len(SPECIAL_TOKENS) + len(alphabet)})"
        )
    rng = random.Random(seed)
    vocab = {tok: i for i, tok in enumerate(SPECIAL_TOKENS)}
    for sym in alphabet:
        vocab[sym] = len(vocab)
    growable = list(alphabet)  # tokens still short enough to extend
    merges: list[tuple[str, str]] = []
    while len(merges) < n_merges:
        left, right = rng.choice(growable), rng.choice(alphabet)
        token = left + right
        if token in vocab:
            continue
        vocab[token] = len(vocab)
        merges.append((left, right))
        if len(token) < _MAX_TOKEN_SYMBOLS:
            growable.append(token)
    tok = Tokenizer(models.BPE(vocab=vocab, merges=merges, unk_token=None))
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tok.decoder = decoders.ByteLevel()
    tok.add_special_tokens(
        [AddedToken(t, special=True) for t in SPECIAL_TOKENS]
    )
    if tok.get_vocab_size() != vocab_size:
        raise RuntimeError(
            f"tokenizer has {tok.get_vocab_size()} ids, wanted {vocab_size}"
        )
    tok.save(os.path.join(path, "tokenizer.json"))


def write_model_dir(path: str, cfg: ModelConfig, seed: int = 0) -> str:
    """Config + full-vocabulary tokenizer + chat template; no weights."""
    os.makedirs(path, exist_ok=True)
    write_full_vocab_tokenizer(path, cfg.vocab_size, seed)
    write_config(path, cfg)
    write_tokenizer_config(path)
    return path


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--preset", required=True, choices=sorted(PRESETS))
    ap.add_argument("--out", required=True, help="directory to write")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    write_model_dir(args.out, PRESETS[args.preset], args.seed)
    print(args.out)


if __name__ == "__main__":
    main()
