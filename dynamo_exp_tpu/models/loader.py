"""Load Llama-family HF checkpoints (safetensors) into stacked JAX params.

The reference leaves weight loading to the wrapped engines (and its own
GGUF loader, ``/root/reference/lib/llm/src/gguf.rs``). Here checkpoints
are read tensor-by-tensor from safetensors, transposed to the matmul
layout ``x @ W`` used by ``models/llama.py``, and stacked along a leading
layer axis for the scan.
"""

from __future__ import annotations

import functools

import json
import os

import numpy as np

from .config import ModelConfig
from .llama import Params, _dtype


def _open_safetensors(path: str):
    from safetensors import safe_open  # lazy: only needed for real ckpts

    files = sorted(
        os.path.join(path, f)
        for f in os.listdir(path)
        if f.endswith(".safetensors")
    )
    if not files:
        raise FileNotFoundError(f"no .safetensors under {path}")
    handles = [safe_open(f, framework="numpy") for f in files]
    index: dict[str, int] = {}
    for i, h in enumerate(handles):
        for name in h.keys():
            index[name] = i
    return handles, index


# Parameters that stay float32 whatever the model's dtype.
_FLOAT32 = (
    "router_bias", "mamba_A_log", "mamba_D", "mamba_dt_b", "gdn_A_log", "gdn_dt_b",
    "sink", "m2_A_log", "m2_D", "m2_dt_b",
)


def _host_params(params: dict, dt) -> dict:
    """Host arrays of the model's dtype (``_FLOAT32`` stay float32)."""
    def host(path, x):
        return np.asarray(x).astype(np.float32 if path in _FLOAT32 else dt)

    out = {k: host(k, v) for k, v in params.items() if k != "layers"}
    out["layers"] = {k: host(k, v) for k, v in params["layers"].items()}
    return out


def _lfm2_moe_params(cfg: ModelConfig, get, linear) -> dict:
    """LiquidAI's ``lfm2_moe`` tensor names into the stacks by kind of
    ``models/llama.py::init_params``: ``operator_norm`` / ``ffn_norm``
    over every layer; ``self_attn.{q,k,v,out}_proj`` and
    ``{q,k}_layernorm`` over the attention layers; ``conv.in_proj``
    ([3D, D]), ``conv.conv`` ([D, 1, K] depthwise) and ``conv.out_proj``
    over the convolution layers; ``feed_forward.{w1,w3,w2}`` (gate, up,
    down) over the dense layers; ``feed_forward.gate``, ``expert_bias``
    and ``experts.N.{w1,w3,w2}`` over the routed ones. The head is tied
    to ``embed_tokens``; the final norm is ``embedding_norm``."""
    names = (
        "attn_norm", "mlp_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
        "conv_in", "conv_w", "conv_out", "dense_gate", "dense_up", "dense_down",
        "router", "router_bias", "w_gate", "w_up", "w_down",
    )
    layers: dict[str, list] = {k: [] for k in names}
    for i, (op, ff) in enumerate(zip(cfg.op_kinds, cfg.ff_kinds)):
        p = f"model.layers.{i}."
        layers["attn_norm"].append(get(p + "operator_norm.weight"))
        layers["mlp_norm"].append(get(p + "ffn_norm.weight"))
        if op == "conv":
            layers["conv_in"].append(linear(p + "conv.in_proj.weight"))
            layers["conv_w"].append(get(p + "conv.conv.weight")[:, 0, :].T)  # [K, D]
            layers["conv_out"].append(linear(p + "conv.out_proj.weight"))
        else:
            a = p + "self_attn."
            for key, name in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"), ("wo", "out_proj")):
                layers[key].append(linear(f"{a}{name}.weight"))
            layers["q_norm"].append(get(a + "q_layernorm.weight"))
            layers["k_norm"].append(get(a + "k_layernorm.weight"))
        f = p + "feed_forward."
        if ff == "dense":
            for key, name in (("dense_gate", "w1"), ("dense_up", "w3"), ("dense_down", "w2")):
                layers[key].append(linear(f"{f}{name}.weight"))
        else:
            layers["router"].append(linear(f + "gate.weight"))
            if cfg.use_expert_bias:
                layers["router_bias"].append(get(f + "expert_bias"))
            for key, name in (("w_gate", "w1"), ("w_up", "w3"), ("w_down", "w2")):
                layers[key].append(np.stack([
                    linear(f"{f}experts.{e}.{name}.weight") for e in range(cfg.num_experts)
                ]))
    return {
        "embed": get("model.embed_tokens.weight"),
        "layers": {k: np.stack(v) for k, v in layers.items() if v},
        "final_norm": get("model.embedding_norm.weight"),
    }


def _jamba_params(cfg: ModelConfig, get, linear) -> dict:
    """AI21's ``jamba`` tensor names into the stacks by kind of
    ``models/llama.py::init_params``: ``input_layernorm`` /
    ``pre_ff_layernorm`` and ``feed_forward.{gate,up,down}_proj`` over
    every layer; ``self_attn.{q,k,v,o}_proj`` over the attention layers;
    over the Mamba layers ``mamba.in_proj`` ([2E, D]), ``mamba.conv1d``
    ([E, 1, K] depthwise, with its bias), ``mamba.x_proj``,
    ``mamba.dt_proj`` (with its bias), ``mamba.A_log`` ([E, S], held
    transposed), ``mamba.D``, ``mamba.{dt,b,c}_layernorm`` and
    ``mamba.out_proj``. The head is tied to ``embed_tokens``; the final
    norm is ``final_layernorm``."""
    names = (
        "attn_norm", "mlp_norm", "w_gate", "w_up", "w_down", "wq", "wk", "wv",
        "wo", "mamba_in", "mamba_conv_w", "mamba_conv_b", "mamba_x",
        "mamba_dt_w", "mamba_dt_b", "mamba_A_log", "mamba_D", "mamba_dt_norm",
        "mamba_b_norm", "mamba_c_norm", "mamba_out",
    )
    layers: dict[str, list] = {k: [] for k in names}
    for i, op in enumerate(cfg.op_kinds):
        p = f"model.layers.{i}."
        layers["attn_norm"].append(get(p + "input_layernorm.weight"))
        layers["mlp_norm"].append(get(p + "pre_ff_layernorm.weight"))
        for key, name in (("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj")):
            layers[key].append(linear(f"{p}feed_forward.{name}.weight"))
        if op == "attn":
            for key, name in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"), ("wo", "o_proj")):
                layers[key].append(linear(f"{p}self_attn.{name}.weight"))
            continue
        m = p + "mamba."
        layers["mamba_in"].append(linear(m + "in_proj.weight"))
        layers["mamba_conv_w"].append(get(m + "conv1d.weight")[:, 0, :].T)  # [K, E]
        layers["mamba_conv_b"].append(get(m + "conv1d.bias"))
        layers["mamba_x"].append(linear(m + "x_proj.weight"))
        layers["mamba_dt_w"].append(linear(m + "dt_proj.weight"))
        layers["mamba_dt_b"].append(get(m + "dt_proj.bias"))
        layers["mamba_A_log"].append(get(m + "A_log").T)  # [S, E]
        layers["mamba_D"].append(get(m + "D"))
        for key, name in (("mamba_dt_norm", "dt"), ("mamba_b_norm", "b"), ("mamba_c_norm", "c")):
            layers[key].append(get(f"{m}{name}_layernorm.weight"))
        layers["mamba_out"].append(linear(m + "out_proj.weight"))
    params = {
        "embed": get("model.embed_tokens.weight"),
        "layers": {k: np.stack(v) for k, v in layers.items() if v},
        "final_norm": get("model.final_layernorm.weight"),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = linear("lm_head.weight")
    return params


def _olmo_hybrid_params(cfg: ModelConfig, get, linear) -> dict:
    """Ai2's ``olmo_hybrid`` tensor names into the stacks by kind of
    ``models/llama.py::init_params``. ASSUMED (no checkpoint is read in
    this repository's tests or benchmark): Olmo 2's names for what every
    layer has (``post_attention_layernorm`` / ``post_feedforward_layernorm``
    on the halves' outputs, ``mlp.{gate,up,down}_proj``) and for the
    attention layers (``self_attn.{q,k,v,o}_proj``, ``self_attn.{q,k}_norm``
    over the whole projection); flash-linear-attention's GatedDeltaNet
    names under ``linear_attn.`` for the linear layers: ``{q,k,v}_proj``
    and their depthwise ``{q,k,v}_conv1d`` ([C, 1, K]), held side by side
    as ``gdn_qkv`` / ``gdn_conv_w``; ``a_proj`` and ``b_proj`` as
    ``gdn_ab``; ``A_log``, ``dt_bias``; ``g_proj``, ``o_norm``, ``o_proj``.
    The head is ``lm_head``; the final norm ``model.norm``."""
    names = (
        "post_attn_norm", "post_ffn_norm", "w_gate", "w_up", "w_down", "wq",
        "wk", "wv", "wo", "q_norm", "k_norm", "gdn_qkv", "gdn_conv_w", "gdn_ab",
        "gdn_A_log", "gdn_dt_b", "gdn_gate", "gdn_norm", "gdn_out",
    )
    layers: dict[str, list] = {k: [] for k in names}
    for i, op in enumerate(cfg.op_kinds):
        p = f"model.layers.{i}."
        layers["post_attn_norm"].append(get(p + "post_attention_layernorm.weight"))
        layers["post_ffn_norm"].append(get(p + "post_feedforward_layernorm.weight"))
        for key, name in (("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj")):
            layers[key].append(linear(f"{p}mlp.{name}.weight"))
        if op == "attn":
            a = p + "self_attn."
            for key, name in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"), ("wo", "o_proj")):
                layers[key].append(linear(f"{a}{name}.weight"))
            layers["q_norm"].append(get(a + "q_norm.weight"))
            layers["k_norm"].append(get(a + "k_norm.weight"))
            continue
        m = p + "linear_attn."
        qkv = ("q", "k", "v")
        layers["gdn_qkv"].append(
            np.concatenate([linear(f"{m}{x}_proj.weight") for x in qkv], axis=1)
        )
        layers["gdn_conv_w"].append(  # [K, C]
            np.concatenate([get(f"{m}{x}_conv1d.weight")[:, 0, :].T for x in qkv], axis=1)
        )
        layers["gdn_ab"].append(
            np.concatenate([linear(m + "a_proj.weight"), linear(m + "b_proj.weight")], axis=1)
        )
        layers["gdn_A_log"].append(get(m + "A_log"))
        layers["gdn_dt_b"].append(get(m + "dt_bias"))
        layers["gdn_gate"].append(linear(m + "g_proj.weight"))
        layers["gdn_norm"].append(get(m + "o_norm.weight"))
        layers["gdn_out"].append(linear(m + "o_proj.weight"))
    params = {
        "embed": get("model.embed_tokens.weight"),
        "layers": {k: np.stack(v) for k, v in layers.items() if v},
        "final_norm": get("model.norm.weight"),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = linear("lm_head.weight")
    return params


SUPPORTED_MODEL_TYPES = (
    "llama", "mistral", "qwen2", "qwen3", "gemma", "gemma2",
    "gemma3_text", "phi3",
    "mixtral", "qwen2_moe", "qwen3_moe", "sdar_moe", "lfm2_moe", "jamba",
    "olmo_hybrid", "pangu_ultra_moe", "glm_moe_dsa", "exaone_moe",
    "mimo_v2_flash", "ouro", "KeyeVL2", "nemotron_h",
)


def _pangu_ultra_moe_params(cfg: ModelConfig, get, linear) -> dict:
    """``pangu_ultra_moe`` tensor names (DeepSeek-V3's, with the four norms
    of ``sandwich_norm``) into the stacks by kind of
    ``models/llama.py::init_params``: ``input_layernorm`` /
    ``post_attention_layernorm`` / ``pre_mlp_layernorm`` /
    ``post_mlp_layernorm`` over every layer; latent attention's
    ``self_attn.{q_a_proj, q_a_layernorm, q_b_proj, kv_a_proj_with_mqa,
    kv_a_layernorm, kv_b_proj, o_proj}`` over every layer;
    ``mlp.{gate,up,down}_proj`` over the dense layers; ``mlp.gate.weight``
    (the router, every expert it scores), ``mlp.shared_experts.*`` and
    ``mlp.experts.N.*`` over the routed ones, ``N`` running over the
    experts this device holds (``first_expert_held`` onward) and no
    others: an expert-parallel rank reads its share of the checkpoint."""
    names = (
        "attn_norm", "post_attn_norm", "mlp_norm", "post_ffn_norm", "w_dq",
        "q_a_norm", "w_uq", "w_dkv", "kv_a_norm", "w_ukv", "wo", "dense_gate",
        "dense_up", "dense_down", "router", "shared_gate", "shared_up",
        "shared_down", "w_gate", "w_up", "w_down",
    )
    layers: dict[str, list] = {k: [] for k in names}
    proj = (("gate", "gate_proj"), ("up", "up_proj"), ("down", "down_proj"))
    held = range(cfg.first_expert_held, cfg.first_expert_held + cfg.experts_held)
    for i, ff in enumerate(cfg.ff_kinds):
        p = f"model.layers.{i}."
        for key, name in (
            ("attn_norm", "input_layernorm"), ("post_attn_norm", "post_attention_layernorm"),
            ("mlp_norm", "pre_mlp_layernorm"), ("post_ffn_norm", "post_mlp_layernorm"),
        ):
            layers[key].append(get(f"{p}{name}.weight"))
        a = p + "self_attn."
        for key, name in (
            ("w_dq", "q_a_proj"), ("w_uq", "q_b_proj"), ("w_dkv", "kv_a_proj_with_mqa"),
            ("w_ukv", "kv_b_proj"), ("wo", "o_proj"),
        ):
            layers[key].append(linear(f"{a}{name}.weight"))
        layers["q_a_norm"].append(get(a + "q_a_layernorm.weight"))
        layers["kv_a_norm"].append(get(a + "kv_a_layernorm.weight"))
        m = p + "mlp."
        if ff == "dense":
            for key, name in proj:
                layers[f"dense_{key}"].append(linear(f"{m}{name}.weight"))
            continue
        layers["router"].append(linear(m + "gate.weight"))
        for key, name in proj:
            if cfg.shared_width:
                layers[f"shared_{key}"].append(linear(f"{m}shared_experts.{name}.weight"))
            layers[f"w_{key}"].append(np.stack([
                linear(f"{m}experts.{e}.{name}.weight") for e in held
            ]))
    return {
        "embed": get("model.embed_tokens.weight"),
        "layers": {k: np.stack(v) for k, v in layers.items() if v},
        "final_norm": get("model.norm.weight"),
        "lm_head": linear("lm_head.weight"),
    }


def rotary_columns(cfg: ModelConfig) -> dict[str, list[int]]:
    """Where the rotary blocks (``qk_rope_head_dim`` columns each) of a
    ``glm_moe_dsa`` layer's tensors start on the last axis, by parameter:
    the rope part of every head's query, the latent's ``k_r``, and the first
    lanes of every index head's query, of the index key and of its
    LayerNorm's scale and bias."""
    dn, dr, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    return {
        "w_uq": [h * (dn + dr) + dn for h in range(cfg.num_heads)],
        "w_dkv": [r],
        "idx_wq": [j * cfg.index_head_dim for j in range(cfg.index_n_heads)],
        "idx_wk": [0], "idx_k_norm": [0], "idx_k_bias": [0],
    }


def rotary_layout(arr: np.ndarray, starts: list[int], width: int, to: str) -> np.ndarray:
    """``arr`` with each rotary block of ``width`` columns (last axis)
    re-ordered: ``to="halves"`` takes the published interleaved pairs
    ``(2i, 2i + 1)`` to this repo's ``apply_rope`` pairs ``(i, i + width /
    2)``; ``to="pairs"`` takes them back. A permutation of columns on both
    sides of every rotary dot product: exact."""
    half = np.arange(width // 2)
    order = np.concatenate([2 * half, 2 * half + 1])  # halves <- pairs
    if to == "pairs":
        order = np.argsort(order)
    elif to != "halves":
        raise ValueError(to)
    out = np.array(arr)
    for a in starts:
        out[..., a:a + width] = arr[..., a:a + width][..., order]
    return out


def _glm_moe_dsa_params(cfg: ModelConfig, get, linear) -> dict:
    """``glm_moe_dsa`` tensor names (DeepSeek-V3.2's) into the stacks by
    kind of ``models/llama.py::init_params``: ``input_layernorm`` /
    ``post_attention_layernorm`` over every layer; latent attention's
    ``self_attn.{q_a_proj, q_a_layernorm, q_b_proj, kv_a_proj_with_mqa,
    kv_a_layernorm, kv_b_proj, o_proj}`` and the indexer's
    ``self_attn.indexer.{wq_b, wk, k_norm (weight, bias), weights_proj}``
    over every layer; ``mlp.{gate,up,down}_proj`` over the dense layers;
    ``mlp.gate.weight`` (the router), ``mlp.gate.e_score_correction_bias``
    (the selection bias), ``mlp.shared_experts.*`` and ``mlp.experts.N.*``
    over the routed ones, ``N`` over the experts this device holds. The
    family rotates interleaved pairs and ``apply_rope`` halves: every
    rotary block's columns are re-ordered here (``rotary_layout``)."""
    names = (
        "attn_norm", "mlp_norm", "w_dq", "q_a_norm", "w_uq", "w_dkv",
        "kv_a_norm", "w_ukv", "wo", "idx_wq", "idx_wk", "idx_k_norm",
        "idx_k_bias", "idx_wp", "dense_gate", "dense_up", "dense_down",
        "router", "router_bias", "shared_gate", "shared_up", "shared_down",
        "w_gate", "w_up", "w_down",
    )
    layers: dict[str, list] = {k: [] for k in names}
    proj = (("gate", "gate_proj"), ("up", "up_proj"), ("down", "down_proj"))
    held = range(cfg.first_expert_held, cfg.first_expert_held + cfg.experts_held)
    for i, ff in enumerate(cfg.ff_kinds):
        p = f"model.layers.{i}."
        layers["attn_norm"].append(get(p + "input_layernorm.weight"))
        layers["mlp_norm"].append(get(p + "post_attention_layernorm.weight"))
        a = p + "self_attn."
        for key, name in (
            ("w_dq", "q_a_proj"), ("w_uq", "q_b_proj"), ("w_dkv", "kv_a_proj_with_mqa"),
            ("w_ukv", "kv_b_proj"), ("wo", "o_proj"), ("idx_wq", "indexer.wq_b"),
            ("idx_wk", "indexer.wk"), ("idx_wp", "indexer.weights_proj"),
        ):
            layers[key].append(linear(f"{a}{name}.weight"))
        layers["q_a_norm"].append(get(a + "q_a_layernorm.weight"))
        layers["kv_a_norm"].append(get(a + "kv_a_layernorm.weight"))
        layers["idx_k_norm"].append(get(a + "indexer.k_norm.weight"))
        layers["idx_k_bias"].append(get(a + "indexer.k_norm.bias"))
        m = p + "mlp."
        if ff == "dense":
            for key, name in proj:
                layers[f"dense_{key}"].append(linear(f"{m}{name}.weight"))
            continue
        layers["router"].append(linear(m + "gate.weight"))
        layers["router_bias"].append(get(m + "gate.e_score_correction_bias"))
        for key, name in proj:
            if cfg.shared_width:
                layers[f"shared_{key}"].append(linear(f"{m}shared_experts.{name}.weight"))
            layers[f"w_{key}"].append(np.stack([
                linear(f"{m}experts.{e}.{name}.weight") for e in held
            ]))
    stacked = {k: np.stack(v) for k, v in layers.items() if v}
    for key, starts in rotary_columns(cfg).items():
        stacked[key] = rotary_layout(stacked[key], starts, cfg.qk_rope_head_dim, "halves")
    return {
        "embed": get("model.embed_tokens.weight"),
        "layers": stacked,
        "final_norm": get("model.norm.weight"),
        "lm_head": linear("lm_head.weight"),
    }


def _exaone_moe_params(cfg: ModelConfig, get, linear, has=None) -> dict:
    """``exaone_moe`` tensor names (the DeepSeek-V3 / Qwen2-MoE decoder
    layer's) into the stacks by kind of ``models/llama.py::init_params``:
    ``input_layernorm`` / ``post_attention_layernorm`` over every layer,
    BEFORE each half; ``self_attn.{q,k,v,o}_proj`` and the per-head
    ``self_attn.{q_norm, k_norm}`` over every layer (rotary as HF's
    ``rotate_half``: this repo's ``apply_rope`` pairs, no re-ordering);
    ``mlp.{gate,up,down}_proj`` over the dense layers; ``mlp.gate.weight``
    (the router), ``mlp.gate.e_score_correction_bias`` (the selection
    bias), ``mlp.shared_experts.*`` and ``mlp.experts.N.*`` over the routed
    ones, ``N`` over the experts this device holds. A checkpoint that
    carries ``post_feedforward_layernorm`` is EXAONE 4.0's block (each
    half's OUTPUT normed) and is refused rather than guessed at."""
    if has is not None and has("model.layers.0.post_feedforward_layernorm.weight"):
        raise ValueError(
            "exaone_moe checkpoint carries post_feedforward_layernorm: that "
            "is the exaone4 block (norms on each half's output), not the "
            "pre-norm block this reader builds"
        )
    names = (
        "attn_norm", "mlp_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
        "dense_gate", "dense_up", "dense_down", "router", "router_bias",
        "shared_gate", "shared_up", "shared_down", "w_gate", "w_up", "w_down",
    )
    layers: dict[str, list] = {k: [] for k in names}
    proj = (("gate", "gate_proj"), ("up", "up_proj"), ("down", "down_proj"))
    held = range(cfg.first_expert_held, cfg.first_expert_held + cfg.experts_held)
    for i, ff in enumerate(cfg.ff_kinds):
        p = f"model.layers.{i}."
        layers["attn_norm"].append(get(p + "input_layernorm.weight"))
        layers["mlp_norm"].append(get(p + "post_attention_layernorm.weight"))
        a = p + "self_attn."
        for key, name in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"), ("wo", "o_proj")):
            layers[key].append(linear(f"{a}{name}.weight"))
        layers["q_norm"].append(get(a + "q_norm.weight"))
        layers["k_norm"].append(get(a + "k_norm.weight"))
        m = p + "mlp."
        if ff == "dense":
            for key, name in proj:
                layers[f"dense_{key}"].append(linear(f"{m}{name}.weight"))
            continue
        layers["router"].append(linear(m + "gate.weight"))
        layers["router_bias"].append(get(m + "gate.e_score_correction_bias"))
        for key, name in proj:
            if cfg.shared_width:
                layers[f"shared_{key}"].append(linear(f"{m}shared_experts.{name}.weight"))
            layers[f"w_{key}"].append(np.stack([
                linear(f"{m}experts.{e}.{name}.weight") for e in held
            ]))
    return {
        "embed": get("model.embed_tokens.weight"),
        "layers": {k: np.stack(v) for k, v in layers.items() if v},
        "final_norm": get("model.norm.weight"),
        "lm_head": linear("lm_head.weight"),
    }


def _mimo_v2_flash_params(cfg: ModelConfig, get, linear, has=None) -> dict:
    """``mimo_v2_flash`` tensor names (the DeepSeek-V3 / Qwen2-MoE decoder
    layer's; ``benchmark/configs/mimo-v2-flash-*.json`` lists them as
    assumed) into the stacks of ``models/llama.py::init_params``:
    ``input_layernorm`` / ``post_attention_layernorm`` over every layer,
    BEFORE each half; ``self_attn.{q,o}_proj`` over every layer and
    ``self_attn.{k,v}_proj`` over the layers of each form (the forms' KV
    heads differ: ``wk`` / ``wv`` the full layers', ``swa_wk`` / ``swa_wv``
    the sliding ones'); ``self_attn.attention_sink_bias`` ([H], float32) over
    the layers whose form has a sink; ``mlp.{gate,up,down}_proj`` over the
    dense layers; ``mlp.gate.weight`` (the router),
    ``mlp.gate.e_score_correction_bias`` (the selection bias) and
    ``mlp.experts.N.*`` over the routed ones, ``N`` over the experts this
    device holds. A checkpoint that carries a multi-token-prediction module
    (``model.mtp_layers.*``) is refused rather than served without it."""
    if has is not None and has("model.mtp_layers.0.input_layernorm.weight"):
        raise ValueError(
            "mimo_v2_flash checkpoint carries model.mtp_layers: the "
            "multi-token-prediction module is not served"
        )
    names = (
        "attn_norm", "mlp_norm", "wq", "wk", "wv", "swa_wk", "swa_wv", "wo", "sink",
        "dense_gate", "dense_up", "dense_down", "router", "router_bias",
        "shared_gate", "shared_up", "shared_down", "w_gate", "w_up", "w_down",
    )
    layers: dict[str, list] = {k: [] for k in names}
    proj = (("gate", "gate_proj"), ("up", "up_proj"), ("down", "down_proj"))
    held = range(cfg.first_expert_held, cfg.first_expert_held + cfg.experts_held)
    for i, (ff, form) in enumerate(zip(cfg.ff_kinds, cfg.attn_forms)):
        p = f"model.layers.{i}."
        layers["attn_norm"].append(get(p + "input_layernorm.weight"))
        layers["mlp_norm"].append(get(p + "post_attention_layernorm.weight"))
        a = p + "self_attn."
        stack = "swa_" if cfg.kv_heads_by_form and form == "sliding" else ""
        for key, name in (
            ("wq", "q_proj"), (stack + "wk", "k_proj"), (stack + "wv", "v_proj"),
            ("wo", "o_proj"),
        ):
            layers[key].append(linear(f"{a}{name}.weight"))
        if form in cfg.sink_forms:
            layers["sink"].append(get(a + "attention_sink_bias"))
        m = p + "mlp."
        if ff == "dense":
            for key, name in proj:
                layers[f"dense_{key}"].append(linear(f"{m}{name}.weight"))
            continue
        layers["router"].append(linear(m + "gate.weight"))
        layers["router_bias"].append(get(m + "gate.e_score_correction_bias"))
        for key, name in proj:
            if cfg.shared_width:
                layers[f"shared_{key}"].append(linear(f"{m}shared_experts.{name}.weight"))
            layers[f"w_{key}"].append(np.stack([
                linear(f"{m}experts.{e}.{name}.weight") for e in held
            ]))
    return {
        "embed": get("model.embed_tokens.weight"),
        "layers": {k: np.stack(v) for k, v in layers.items() if v},
        "final_norm": get("model.norm.weight"),
        "lm_head": linear("lm_head.weight"),
    }


def _ouro_params(cfg: ModelConfig, get, linear) -> dict:
    """``ouro`` tensor names (a looped language model; the family's
    modeling file's, ``benchmark/configs/ouro-2.6b.json`` lists them as
    assumed) into ``models/llama.py::init_params``' stacks: the four norms
    of the sandwich placement ``input_layernorm`` (before attention),
    ``input_layernorm_2`` (on attention's output),
    ``post_attention_layernorm`` (before the feed-forward) and
    ``post_attention_layernorm_2`` (on its output) over every layer;
    ``self_attn.{q,k,v,o}_proj`` and ``mlp.{gate,up,down}_proj``, no bias;
    ``model.norm`` (the norm that closes every pass) and
    ``model.early_exit_gate`` (``[1, D]`` and its bias ``[1]``: held, not
    evaluated). ONE set of layers whatever ``total_ut_steps``."""
    norms = (
        ("attn_norm", "input_layernorm"), ("post_attn_norm", "input_layernorm_2"),
        ("mlp_norm", "post_attention_layernorm"),
        ("post_ffn_norm", "post_attention_layernorm_2"),
    )
    mats = (
        ("wq", "self_attn.q_proj"), ("wk", "self_attn.k_proj"),
        ("wv", "self_attn.v_proj"), ("wo", "self_attn.o_proj"),
        ("w_gate", "mlp.gate_proj"), ("w_up", "mlp.up_proj"), ("w_down", "mlp.down_proj"),
    )
    layers: dict[str, list] = {k: [] for k, _ in (*norms, *mats)}
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        for key, name in norms:
            layers[key].append(get(f"{p}{name}.weight"))
        for key, name in mats:
            layers[key].append(linear(f"{p}{name}.weight"))
    params = {
        "embed": get("model.embed_tokens.weight"),
        "layers": {k: np.stack(v) for k, v in layers.items()},
        "final_norm": get("model.norm.weight"),
        "exit_gate_w": get("model.early_exit_gate.weight").reshape(-1),
        "exit_gate_b": get("model.early_exit_gate.bias").reshape(1),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = linear("lm_head.weight")
    return params


def _keye_vl2_params(cfg: ModelConfig, get, linear) -> dict:
    """``KeyeVL2``'s language model (``benchmark/configs/
    keye-vl-2.0-30b-a3b-l5.json`` lists the names as assumed) into
    ``models/llama.py::init_params``' stacks: Qwen3-MoE's names
    (``input_layernorm``, ``self_attn.{q,k,v,o}_proj``, ``self_attn.{q,k}_norm``,
    ``post_attention_layernorm``, ``mlp.gate`` and
    ``mlp.experts.N.{gate,up,down}_proj``, no bias) and the indexer's,
    DeepSeek-V3.2's: ``self_attn.indexer.{wq, wk, k_norm (weight, bias),
    weights_proj}``, ``wq`` from the hidden size. The rotary pairs are the
    engine's own (half-split), so no column is re-ordered. ``visual.*`` (the
    vision tower, not served) is never asked for."""
    mats = (
        ("wq", "self_attn.q_proj"), ("wk", "self_attn.k_proj"),
        ("wv", "self_attn.v_proj"), ("wo", "self_attn.o_proj"),
        ("idx_wq", "self_attn.indexer.wq"), ("idx_wk", "self_attn.indexer.wk"),
        ("idx_wp", "self_attn.indexer.weights_proj"), ("router", "mlp.gate"),
    )
    vecs = (
        ("attn_norm", "input_layernorm.weight"),
        ("mlp_norm", "post_attention_layernorm.weight"),
        ("q_norm", "self_attn.q_norm.weight"), ("k_norm", "self_attn.k_norm.weight"),
        ("idx_k_norm", "self_attn.indexer.k_norm.weight"),
        ("idx_k_bias", "self_attn.indexer.k_norm.bias"),
    )
    experts = (("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj"))
    layers: dict[str, list] = {k: [] for k, _ in (*mats, *vecs, *experts)}
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        for key, name in mats:
            layers[key].append(linear(f"{p}{name}.weight"))
        for key, name in vecs:
            layers[key].append(get(p + name))
        for key, name in experts:
            layers[key].append(np.stack([
                linear(f"{p}mlp.experts.{e}.{name}.weight")
                for e in range(cfg.num_experts)
            ]))
    params = {
        "embed": get("model.embed_tokens.weight"),
        "layers": {k: np.stack(v) for k, v in layers.items()},
        "final_norm": get("model.norm.weight"),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = linear("lm_head.weight")
    return params


def _nemotron_h_params(cfg: ModelConfig, get, linear) -> dict:
    """NVIDIA's ``nemotron_h`` tensor names into the stacks by kind of
    ``models/llama.py::init_params``. The published layers are one half
    each under ``backbone.layers.N`` with ``norm`` and ``mixer``; the stack
    here is (mixer, experts) pairs, so ``norm`` of an ``M`` or ``*`` layer
    goes to ``attn_norm`` and an ``E`` layer's to ``mlp_norm``. Over the
    Mamba-2 layers ``mixer.in_proj`` ([E + C + H, D]: its rows ``z | xBC``
    into ``m2_in``, the last ``H`` into ``m2_dt_w``), ``mixer.conv1d`` ([C,
    1, K] depthwise, with its bias), ``mixer.A_log``, ``mixer.D``,
    ``mixer.dt_bias``, ``mixer.norm`` and ``mixer.out_proj``; over the
    attention layers ``mixer.{q,k,v,o}_proj``; over the routed layers
    ``mixer.gate.weight``, ``mixer.gate.e_score_correction_bias``,
    ``mixer.experts.N.{up,down}_proj`` (a held share's are experts
    ``first_expert_held`` on) and ``mixer.shared_experts.{up,down}_proj``.
    ``backbone.embeddings``, ``backbone.norm_f``, an untied ``lm_head``."""
    names = (
        "attn_norm", "mlp_norm", "wq", "wk", "wv", "wo", "m2_in", "m2_dt_w",
        "m2_conv_w", "m2_conv_b", "m2_dt_b", "m2_A_log", "m2_D", "m2_norm", "m2_out",
        "router", "router_bias", "shared_up", "shared_down", "w_up", "w_down",
    )
    layers: dict[str, list] = {k: [] for k in names}
    split = cfg.mamba2_inner + cfg.mamba2_conv_width  # z | xBC, then dt
    i = 0
    for op, ff in zip(cfg.op_kinds, cfg.ff_kinds):
        p = f"backbone.layers.{i}."
        m = p + "mixer."
        layers["attn_norm"].append(get(p + "norm.weight"))
        if op == "mamba2":
            w_in = linear(m + "in_proj.weight")  # [D, E + C + H]
            layers["m2_in"].append(w_in[:, :split])
            layers["m2_dt_w"].append(w_in[:, split:])
            layers["m2_conv_w"].append(get(m + "conv1d.weight")[:, 0, :].T)  # [K, C]
            layers["m2_conv_b"].append(get(m + "conv1d.bias"))
            layers["m2_dt_b"].append(get(m + "dt_bias"))
            layers["m2_A_log"].append(get(m + "A_log"))
            layers["m2_D"].append(get(m + "D"))
            layers["m2_norm"].append(get(m + "norm.weight"))
            layers["m2_out"].append(linear(m + "out_proj.weight"))
        else:
            for key, name in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"), ("wo", "o_proj")):
                layers[key].append(linear(f"{m}{name}.weight"))
        i += 1
        if ff == "none":
            continue
        p = f"backbone.layers.{i}."
        m = p + "mixer."
        layers["mlp_norm"].append(get(p + "norm.weight"))
        layers["router"].append(linear(m + "gate.weight"))
        layers["router_bias"].append(get(m + "gate.e_score_correction_bias"))
        layers["shared_up"].append(linear(m + "shared_experts.up_proj.weight"))
        layers["shared_down"].append(linear(m + "shared_experts.down_proj.weight"))
        held = range(cfg.first_expert_held, cfg.first_expert_held + cfg.experts_held)
        pad = cfg.expert_stack_width - cfg.expert_intermediate_size  # zeros: whole lane groups
        for key, name, axes in (
            ("w_up", "up_proj", ((0, 0), (0, 0), (0, pad))),
            ("w_down", "down_proj", ((0, 0), (0, pad), (0, 0))),
        ):
            layers[key].append(np.pad(np.stack([
                linear(f"{m}experts.{e}.{name}.weight") for e in held
            ]), axes))
        i += 1
    return {
        "embed": get("backbone.embeddings.weight"),
        "layers": {k: np.stack(v) for k, v in layers.items()},
        "final_norm": get("backbone.norm_f.weight"),
        "lm_head": linear("lm_head.weight"),
    }


def load_params(path: str, cfg: ModelConfig | None = None) -> tuple[Params, ModelConfig]:
    """Load a HF checkpoint directory (llama/mistral/qwen2/mixtral
    families) into the stacked param pytree."""
    if cfg is None:
        cfg = ModelConfig.from_pretrained(path)
    if cfg.model_type not in SUPPORTED_MODEL_TYPES:
        # Fail loudly: e.g. qwen2_moe parses to an MoE config but uses
        # different tensor names (mlp.experts.N.gate_proj + shared
        # expert) — loading it with mixtral names would KeyError deep in
        # the loop with no hint the arch is unsupported.
        raise ValueError(
            f"unsupported model_type {cfg.model_type!r}; "
            f"supported: {SUPPORTED_MODEL_TYPES}"
        )
    handles, index = _open_safetensors(path)
    dt = _dtype(cfg)

    def get(name: str) -> np.ndarray:
        arr = handles[index[name]].get_tensor(name)
        if arr.dtype == np.dtype("V2"):  # raw bf16 comes out as void16
            arr = arr.view(np.uint16)
            arr = (arr.astype(np.uint32) << 16).view(np.float32)
        return arr

    def linear(name: str) -> np.ndarray:
        # HF stores [out, in]; we use x @ W so transpose to [in, out].
        return get(name).T

    pre = "model."
    by_kind = {
        "lfm2_moe": _lfm2_moe_params, "jamba": _jamba_params,
        "olmo_hybrid": _olmo_hybrid_params,
        "pangu_ultra_moe": _pangu_ultra_moe_params,
        "glm_moe_dsa": _glm_moe_dsa_params,
        "exaone_moe": functools.partial(_exaone_moe_params, has=index.__contains__),
        "mimo_v2_flash": functools.partial(_mimo_v2_flash_params, has=index.__contains__),
        "ouro": _ouro_params,
        "KeyeVL2": _keye_vl2_params,
        "nemotron_h": _nemotron_h_params,
    }
    if cfg.model_type in by_kind:
        params = by_kind[cfg.model_type](cfg, get, linear)
        handles.clear()
        return _host_params(params, dt), cfg
    L = cfg.num_layers
    keys = ["attn_norm", "wq", "wk", "wv", "wo", "mlp_norm",
            "w_gate", "w_up", "w_down"]
    if cfg.attention_bias:
        keys += ["bq", "bk", "bv"]
    if cfg.qk_norm:
        keys += ["q_norm", "k_norm"]
    if cfg.post_norms:
        keys += ["post_attn_norm", "post_ffn_norm"]
    if cfg.is_moe:
        keys.append("router")
        if cfg.shared_expert_intermediate_size:
            keys += ["shared_gate", "shared_up", "shared_down", "shared_router"]
    layers: dict[str, list] = {k: [] for k in keys}
    for i in range(L):
        p = f"{pre}layers.{i}."
        layers["attn_norm"].append(get(p + "input_layernorm.weight"))
        if cfg.model_type == "phi3":
            # phi3 packs q/k/v into one tensor [(H + 2*Hkv)*hd, D].
            qkv = get(p + "self_attn.qkv_proj.weight")
            hd = cfg.head_dim_
            nq = cfg.num_heads * hd
            nk = cfg.num_kv_heads * hd
            layers["wq"].append(qkv[:nq].T)
            layers["wk"].append(qkv[nq : nq + nk].T)
            layers["wv"].append(qkv[nq + nk :].T)
        else:
            layers["wq"].append(linear(p + "self_attn.q_proj.weight"))
            layers["wk"].append(linear(p + "self_attn.k_proj.weight"))
            layers["wv"].append(linear(p + "self_attn.v_proj.weight"))
        layers["wo"].append(linear(p + "self_attn.o_proj.weight"))
        if cfg.post_norms:
            # gemma2 layer norms: post_attention_layernorm norms the
            # attn OUTPUT; pre_feedforward_layernorm is the pre-FFN
            # norm (the role post_attention_layernorm plays elsewhere).
            layers["post_attn_norm"].append(
                get(p + "post_attention_layernorm.weight")
            )
            layers["mlp_norm"].append(
                get(p + "pre_feedforward_layernorm.weight")
            )
            layers["post_ffn_norm"].append(
                get(p + "post_feedforward_layernorm.weight")
            )
        else:
            layers["mlp_norm"].append(
                get(p + "post_attention_layernorm.weight")
            )
        if cfg.attention_bias:  # qwen2: bias on q/k/v only
            layers["bq"].append(get(p + "self_attn.q_proj.bias"))
            layers["bk"].append(get(p + "self_attn.k_proj.bias"))
            layers["bv"].append(get(p + "self_attn.v_proj.bias"))
        if cfg.qk_norm:  # qwen3: [head_dim] norms applied per head
            layers["q_norm"].append(get(p + "self_attn.q_norm.weight"))
            layers["k_norm"].append(get(p + "self_attn.k_norm.weight"))
        if cfg.is_moe:
            # Stack per-expert weights to [E, D, I] / [E, I, D] for the
            # grouped ragged_dot matmuls. Mixtral names them
            # block_sparse_moe.experts.N.{w1=gate, w3=up, w2=down};
            # qwen3_moe (and sdar_moe, the same stack) uses
            # mlp.experts.N.{gate,up,down}_proj.
            if cfg.model_type in ("qwen2_moe", "qwen3_moe", "sdar_moe"):
                m = p + "mlp."
                names = ("gate_proj.weight", "up_proj.weight", "down_proj.weight")
            else:
                m = p + "block_sparse_moe."
                names = ("w1.weight", "w3.weight", "w2.weight")
            layers["router"].append(linear(m + "gate.weight"))
            for key, tname in zip(("w_gate", "w_up", "w_down"), names):
                layers[key].append(np.stack([
                    linear(f"{m}experts.{e}.{tname}")
                    for e in range(cfg.num_experts)
                ]))
            if cfg.shared_expert_intermediate_size:  # qwen2_moe
                s = p + "mlp.shared_expert."
                layers["shared_gate"].append(linear(s + "gate_proj.weight"))
                layers["shared_up"].append(linear(s + "up_proj.weight"))
                layers["shared_down"].append(linear(s + "down_proj.weight"))
                # shared_expert_gate is Linear(D, 1): [1, D] -> [D]
                layers["shared_router"].append(
                    get(p + "mlp.shared_expert_gate.weight")[0]
                )
        elif cfg.model_type == "phi3":
            # phi3 packs gate and up into one tensor [2I, D].
            gu = get(p + "mlp.gate_up_proj.weight")
            layers["w_gate"].append(gu[: cfg.intermediate_size].T)
            layers["w_up"].append(gu[cfg.intermediate_size :].T)
            layers["w_down"].append(linear(p + "mlp.down_proj.weight"))
        else:
            layers["w_gate"].append(linear(p + "mlp.gate_proj.weight"))
            layers["w_up"].append(linear(p + "mlp.up_proj.weight"))
            layers["w_down"].append(linear(p + "mlp.down_proj.weight"))

    def host(x) -> np.ndarray:
        # Host arrays, not device arrays: the engine device_puts each
        # tensor straight to its NamedSharding, so a checkpoint larger
        # than one chip never passes through one chip.
        return np.asarray(x).astype(dt)

    params: Params = {
        "embed": host(get(pre + "embed_tokens.weight")),
        "layers": {k: host(np.stack(v)) for k, v in layers.items()},
        "final_norm": host(get(pre + "norm.weight")),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = host(linear("lm_head.weight"))
    handles.clear()  # drop mmap handles now rather than at caller GC
    return params, cfg
