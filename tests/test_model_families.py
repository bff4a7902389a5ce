"""Model-family coverage: qwen2 (QKV bias), mistral (sliding window),
mixtral (sparse MoE) — each checked against an independent oracle
(dense-dispatch MoE reference, masked dense attention, and HF
transformers forward for tiny random checkpoints)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_exp_tpu.models import (
    TINY_MOE,
    TINY_QWEN2,
    ModelConfig,
    forward,
    init_kv_cache,
    init_params,
    param_shardings,
)

PS = 8

# Jitted once for the module: an eager ``forward`` compiles its layer
# scan anew on every call.
fwd = jax.jit(forward, static_argnums=(1,), static_argnames=("mesh",))


def _full_logits(params, cfg, token_list):
    T = len(token_list)
    pmax = (T + PS - 1) // PS
    k, v = init_kv_cache(cfg, num_pages=pmax + 1, page_size=PS, dtype=jnp.float32)
    tokens = jnp.array([token_list], dtype=jnp.int32)
    positions = jnp.arange(T, dtype=jnp.int32)[None, :]
    table = jnp.arange(pmax, dtype=jnp.int32)[None, :] + 1
    logits, _, _ = fwd(params, cfg, tokens, positions, table, k, v)
    return np.asarray(logits[0])


def _f32_params(cfg, seed):
    return jax.tree.map(
        lambda x: x.astype(jnp.float32), init_params(jax.random.PRNGKey(seed), cfg)
    )


def test_moe_ffn_matches_dense_reference():
    from dynamo_exp_tpu.ops.moe import moe_ffn, moe_ffn_reference

    key = jax.random.PRNGKey(0)
    N, D, I, E, K = 17, 32, 48, 4, 2
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (N, D), jnp.float32)
    router = jax.random.normal(ks[1], (D, E), jnp.float32)
    wg = jax.random.normal(ks[2], (E, D, I), jnp.float32) * D**-0.5
    wu = jax.random.normal(ks[3], (E, D, I), jnp.float32) * D**-0.5
    wd = jax.random.normal(ks[4], (E, I, D), jnp.float32) * I**-0.5

    got = moe_ffn(x, router, wg, wu, wd, K)
    want = moe_ffn_reference(x, router, wg, wu, wd, K)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)

    # Unnormalised top-k weights (norm_topk_prob=False) must also agree.
    got = moe_ffn(x, router, wg, wu, wd, K, norm_topk_prob=False)
    want = moe_ffn_reference(x, router, wg, wu, wd, K, norm_topk_prob=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_sliding_window_matches_masked_dense():
    from dynamo_exp_tpu.ops import paged_attention, write_kv_pages

    key = jax.random.PRNGKey(1)
    B, T, H, Hkv, D, W = 2, 16, 4, 2, 8, 5
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), jnp.float32)

    # Dense oracle with an explicit sliding-window mask.
    qg = q.reshape(B, T, Hkv, H // Hkv, D)
    scores = jnp.einsum("bthqd,bshd->bhqts", qg, k) * D**-0.5
    i = jnp.arange(T)[:, None]
    j = jnp.arange(T)[None, :]
    mask = (j <= i) & (j > i - W)
    probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
    want = jnp.einsum("bhqts,bshd->bthqd", probs, v).reshape(B, T, H, D)

    pmax = T // PS
    kc = jnp.zeros((B * pmax + 1, PS, Hkv * D))
    vc = jnp.zeros_like(kc)
    table = (jnp.arange(B * pmax, dtype=jnp.int32).reshape(B, pmax)) + 1
    pos = jnp.tile(jnp.arange(T, dtype=jnp.int32)[None], (B, 1))
    flat = pos.reshape(-1)
    bidx = jnp.repeat(jnp.arange(B, dtype=jnp.int32), T)
    kc, vc = write_kv_pages(
        kc, vc, k.reshape(B * T, -1), v.reshape(B * T, -1),
        table[bidx, flat // PS], flat % PS, jnp.ones(B * T, bool),
    )
    got = paged_attention(q, kc, vc, table, pos, window=W)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_moe_incremental_decode_matches_full_prefill():
    cfg = TINY_MOE
    params = _f32_params(cfg, 7)
    toks = list(np.random.RandomState(2).randint(1, cfg.vocab_size, size=13))
    want = _full_logits(params, cfg, toks)

    pmax = 2
    k, v = init_kv_cache(cfg, num_pages=pmax + 1, page_size=PS, dtype=jnp.float32)
    table = jnp.arange(pmax, dtype=jnp.int32)[None, :] + 1
    split = 9
    logits, k, v = fwd(
        params, cfg,
        jnp.array([toks[:split]], jnp.int32),
        jnp.arange(split, dtype=jnp.int32)[None, :], table, k, v,
    )
    np.testing.assert_allclose(np.asarray(logits[0]), want[:split], rtol=1e-4, atol=1e-4)
    for i in range(split, len(toks)):
        logits, k, v = fwd(
            params, cfg,
            jnp.array([[toks[i]]], jnp.int32),
            jnp.array([[i]], jnp.int32), table, k, v,
        )
        np.testing.assert_allclose(np.asarray(logits[0, 0]), want[i], rtol=1e-4, atol=1e-4)


def test_qwen2_bias_changes_logits_and_tp_matches():
    """Bias params must actually affect the forward (guard against the
    config knob parsing but the model ignoring it), and the tp-sharded
    qwen2 forward must agree with single-device."""
    from dynamo_exp_tpu.parallel import build_mesh, shard_pytree

    cfg = TINY_QWEN2
    params = _f32_params(cfg, 11)
    toks = list(np.random.RandomState(3).randint(1, cfg.vocab_size, size=9))
    want = _full_logits(params, cfg, toks)

    zeroed = jax.tree.map(lambda x: x, params)
    zeroed["layers"] = dict(zeroed["layers"])
    zeroed["layers"]["bq"] = jnp.zeros_like(params["layers"]["bq"])
    assert np.abs(_full_logits(zeroed, cfg, toks) - want).max() > 1e-6

    mesh = build_mesh(tp=2)
    sp, _ = shard_pytree(mesh, params, param_shardings(cfg))
    T = len(toks)
    pmax = (T + PS - 1) // PS
    k, v = init_kv_cache(cfg, num_pages=pmax + 1, page_size=PS, dtype=jnp.float32)
    table = jnp.arange(pmax, dtype=jnp.int32)[None, :] + 1
    logits, _, _ = fwd(
        sp, cfg,
        jnp.array([toks], jnp.int32),
        jnp.arange(T, dtype=jnp.int32)[None, :], table, k, v,
    )
    np.testing.assert_allclose(np.asarray(logits[0]), want, rtol=1e-3, atol=1e-3)


def test_moe_tp_sharded_matches_single_device():
    from dynamo_exp_tpu.parallel import build_mesh, shard_pytree

    cfg = TINY_MOE
    params = _f32_params(cfg, 13)
    toks = list(np.random.RandomState(5).randint(1, cfg.vocab_size, size=11))
    want = _full_logits(params, cfg, toks)

    mesh = build_mesh(tp=2)
    sp, _ = shard_pytree(mesh, params, param_shardings(cfg))
    T = len(toks)
    pmax = (T + PS - 1) // PS
    k, v = init_kv_cache(cfg, num_pages=pmax + 1, page_size=PS, dtype=jnp.float32)
    table = jnp.arange(pmax, dtype=jnp.int32)[None, :] + 1
    logits, _, _ = fwd(
        sp, cfg,
        jnp.array([toks], jnp.int32),
        jnp.arange(T, dtype=jnp.int32)[None, :], table, k, v,
    )
    np.testing.assert_allclose(np.asarray(logits[0]), want, rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# HF transformers parity: tiny random checkpoints saved to disk, loaded by
# our loader, logits compared to the HF torch forward.
# ---------------------------------------------------------------------------

def _save_hf_model(tmp_path, hf_model, config):
    hf_model.save_pretrained(tmp_path, safe_serialization=True)
    with open(os.path.join(tmp_path, "config.json"), "w") as f:
        json.dump(config.to_dict(), f)


def _parity_check(tmp_path, hf_model, hf_config, n_tokens=12, atol=2e-3):
    import torch

    from dynamo_exp_tpu.models.loader import load_params

    hf_model = hf_model.eval()
    _save_hf_model(str(tmp_path), hf_model, hf_config)
    params, cfg = load_params(str(tmp_path))
    assert cfg.model_type == hf_config.model_type

    toks = list(np.random.RandomState(9).randint(1, cfg.vocab_size, size=n_tokens))
    with torch.no_grad():
        want = hf_model(torch.tensor([toks])).logits[0].float().numpy()
    got = _full_logits(params, cfg, toks)
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=atol)


@pytest.fixture
def _hf_env(monkeypatch):
    """Requested only by the HF parity tests — NOT autouse, so the
    pure-JAX tests above keep running on hosts without torch."""
    monkeypatch.setenv("TRANSFORMERS_VERBOSITY", "error")
    monkeypatch.setenv("HF_HUB_OFFLINE", "1")
    torch = pytest.importorskip("torch")
    torch.manual_seed(0)  # deterministic random init → stable tolerances


@pytest.mark.slow  # full-logit torch parity: the longest single model
# proof; the per-family engine serve tests keep covering qwen2 in tier-1.
def test_hf_parity_qwen2(tmp_path, _hf_env):
    transformers = pytest.importorskip("transformers")
    c = transformers.Qwen2Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, tie_word_embeddings=False,
        torch_dtype="float32",
    )
    _parity_check(tmp_path, transformers.Qwen2ForCausalLM(c), c)


def test_hf_parity_mistral_sliding_window(tmp_path, _hf_env):
    transformers = pytest.importorskip("transformers")
    c = transformers.MistralConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, sliding_window=6,
        torch_dtype="float32",
    )
    # attn_implementation="eager" honours sliding_window in small models.
    model = transformers.MistralForCausalLM._from_config(
        c, attn_implementation="eager"
    )
    _parity_check(tmp_path, model, c, n_tokens=16, atol=5e-3)


def test_hf_parity_mixtral(tmp_path, _hf_env):
    transformers = pytest.importorskip("transformers")
    c = transformers.MixtralConfig(
        vocab_size=128, hidden_size=32, intermediate_size=48,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, num_local_experts=4,
        num_experts_per_tok=2, sliding_window=None, torch_dtype="float32",
    )
    # Slightly looser: expert-sum accumulation order differs between
    # ragged_dot grouping and HF's per-expert index_add.
    _parity_check(tmp_path, transformers.MixtralForCausalLM(c), c, atol=5e-3)


def test_moe_expert_parallel_matches_single_device():
    """Experts sharded over the mesh's ep axis (moe_ffn_ep shard_map
    path) must match the single-device forward — alone and composed
    with tp (SURVEY §2.10 'mesh expert axis')."""
    from dynamo_exp_tpu.parallel import build_mesh, shard_pytree

    cfg = TINY_MOE
    params = _f32_params(cfg, 17)
    toks = list(np.random.RandomState(8).randint(1, cfg.vocab_size, size=10))
    want = _full_logits(params, cfg, toks)

    for ep, tp in ((2, 1), (2, 2)):
        mesh = build_mesh(tp=tp, ep=ep)
        sp, _ = shard_pytree(
            mesh, params, param_shardings(cfg, ep_axis="ep")
        )
        T = len(toks)
        pmax = (T + PS - 1) // PS
        k, v = init_kv_cache(cfg, num_pages=pmax + 1, page_size=PS, dtype=jnp.float32)
        table = jnp.arange(pmax, dtype=jnp.int32)[None, :] + 1
        logits, _, _ = fwd(
            sp, cfg,
            jnp.array([toks], jnp.int32),
            jnp.arange(T, dtype=jnp.int32)[None, :], table, k, v,
            mesh=mesh,
        )
        np.testing.assert_allclose(
            np.asarray(logits[0]), want, rtol=1e-3, atol=1e-3,
            err_msg=f"ep={ep} tp={tp}",
        )


def test_hf_parity_qwen3(tmp_path, _hf_env):
    transformers = pytest.importorskip("transformers")
    c = transformers.Qwen3Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, max_position_embeddings=128, tie_word_embeddings=False,
        torch_dtype="float32",
    )
    _parity_check(tmp_path, transformers.Qwen3ForCausalLM(c), c, atol=5e-3)


def test_hf_parity_qwen3_moe(tmp_path, _hf_env):
    transformers = pytest.importorskip("transformers")
    c = transformers.Qwen3MoeConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=48, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        num_experts=4, num_experts_per_tok=2, norm_topk_prob=True,
        max_position_embeddings=128, tie_word_embeddings=False,
        torch_dtype="float32",
    )
    _parity_check(
        tmp_path, transformers.Qwen3MoeForCausalLM(c), c, atol=5e-3
    )


def test_hf_parity_gemma(tmp_path, _hf_env):
    transformers = pytest.importorskip("transformers")
    c = transformers.GemmaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, max_position_embeddings=128, torch_dtype="float32",
    )
    # Gemma always ties embeddings; eager attention for exactness.
    model = transformers.GemmaForCausalLM._from_config(
        c, attn_implementation="eager"
    )
    _parity_check(tmp_path, model, c, atol=5e-3)


@pytest.mark.parametrize(
    "preset",
    ["tiny", "tiny-qwen2", "tiny-qwen3", "tiny-moe", "tiny-shared-moe",
     "tiny-gemma", "tiny-gemma2"]
)
async def test_engine_serves_every_family(preset):
    """Engine e2e per family: greedy decode through the full continuous-
    batching stack must equal the bare-forward oracle — catches family
    plumbing breaks (penalty counts, prefix cache, decode windows) that
    forward-level parity tests can't."""
    import dataclasses

    from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
    from dynamo_exp_tpu.models import PRESETS
    from dynamo_exp_tpu.parallel import single_device_mesh
    from dynamo_exp_tpu.protocols.common import BackendInput

    if preset == "tiny-gemma2":  # softcaps + alternating sliding window
        mcfg = dataclasses.replace(
            PRESETS["tiny"], hidden_act="gelu_tanh", rms_norm_offset=True,
            scale_embeddings=True, post_norms=True, attn_logit_softcap=50.0,
            final_logit_softcap=30.0, query_pre_attn_scalar=16.0,
            sliding_window=6, alt_sliding_window=True, model_type="gemma2",
        )
    elif preset == "tiny-shared-moe":  # qwen2_moe: shared expert + gate
        mcfg = dataclasses.replace(
            PRESETS["tiny-moe"], shared_expert_intermediate_size=80,
            norm_topk_prob=False, model_type="qwen2_moe",
        )
    elif preset == "tiny-gemma":
        mcfg = dataclasses.replace(
            PRESETS["tiny"], hidden_act="gelu_tanh", rms_norm_offset=True,
            scale_embeddings=True, model_type="gemma",
        )
    else:
        mcfg = PRESETS[preset]
    cfg = EngineConfig(
        model=mcfg, max_decode_slots=2, page_size=PS, num_pages=32,
        max_model_len=128, eos_token_ids=[],
    )
    engine = TPUEngine(cfg, mesh=single_device_mesh(), seed=0)
    engine.start()
    try:
        prompt = [5, 9, 17, 3, 11, 2]
        params = engine.params
        pmax = 8
        k, v = init_kv_cache(mcfg, num_pages=pmax + 1, page_size=PS)
        table = jnp.arange(pmax, dtype=jnp.int32)[None, :] + 1
        logits, k, v = forward(
            params, mcfg,
            jnp.array([prompt], jnp.int32),
            jnp.arange(len(prompt), dtype=jnp.int32)[None, :], table, k, v,
        )
        want = [int(np.asarray(logits)[0, -1].argmax())]
        for _ in range(4):
            pos = len(prompt) + len(want) - 1
            logits, k, v = forward(
                params, mcfg,
                jnp.array([[want[-1]]], jnp.int32),
                jnp.array([[pos]], jnp.int32), table, k, v,
            )
            want.append(int(np.asarray(logits)[0, 0].argmax()))

        b = BackendInput(token_ids=prompt)
        b.stop_conditions.max_tokens = 5
        b.stop_conditions.ignore_eos = True
        stream = await engine.generate(b.to_dict())
        got = []
        async for item in stream:
            got.extend(item.get("token_ids", []))
        assert got == want, f"family {preset} engine/oracle mismatch"
    finally:
        engine.stop()


def test_hf_parity_qwen2_moe(tmp_path, _hf_env):
    transformers = pytest.importorskip("transformers")
    c = transformers.Qwen2MoeConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=48, shared_expert_intermediate_size=56,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_experts=4, num_experts_per_tok=2, norm_topk_prob=False,
        max_position_embeddings=128, tie_word_embeddings=False,
        torch_dtype="float32",
    )
    _parity_check(
        tmp_path, transformers.Qwen2MoeForCausalLM(c), c, atol=5e-3
    )


def test_hf_parity_gemma2(tmp_path, _hf_env):
    """gemma2: 4 norms/layer, attn+final softcaps, query scale, and
    sliding window on alternating layers (T > window exercises the
    alternation)."""
    transformers = pytest.importorskip("transformers")
    c = transformers.Gemma2Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, max_position_embeddings=128, sliding_window=6,
        query_pre_attn_scalar=8, attn_logit_softcapping=50.0,
        final_logit_softcapping=30.0, torch_dtype="float32",
    )
    model = transformers.Gemma2ForCausalLM._from_config(
        c, attn_implementation="eager"
    )
    _parity_check(tmp_path, model, c, n_tokens=16, atol=5e-3)


def test_hf_parity_phi3(tmp_path, _hf_env):
    """phi3: llama-shaped with packed qkv_proj / gate_up_proj tensors
    (the loader splits them)."""
    transformers = pytest.importorskip("transformers")
    c = transformers.Phi3Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, pad_token_id=0, torch_dtype="float32",
    )
    model = transformers.Phi3ForCausalLM._from_config(
        c, attn_implementation="eager"
    )
    _parity_check(tmp_path, model, c, atol=5e-3)


def test_hf_parity_gemma3(tmp_path, _hf_env):
    """gemma3 text: explicit layer_types (sliding/full), dual rope base
    (local 10k on sliding layers, rope_theta on full), q/k norm, 4-norm
    layers, no softcaps."""
    transformers = pytest.importorskip("transformers")
    c = transformers.Gemma3TextConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, max_position_embeddings=128, sliding_window=6,
        sliding_window_pattern=2, rope_theta=1000000.0,
        rope_local_base_freq=10000.0, query_pre_attn_scalar=8,
        torch_dtype="float32",
    )
    model = transformers.Gemma3ForCausalLM._from_config(
        c, attn_implementation="eager"
    )
    # Slightly looser: four offset-norms per layer in float32 accumulate
    # more ordering noise than the other families.
    _parity_check(tmp_path, model, c, n_tokens=16, atol=8e-3)


def test_gemma3_layer_types_from_pattern():
    """Older gemma3 configs with only sliding_window_pattern derive the
    explicit layer kinds (every Nth layer full attention)."""
    cfg = ModelConfig.from_hf_config({
        "model_type": "gemma3_text", "num_hidden_layers": 6,
        "sliding_window": 512, "sliding_window_pattern": 3,
        "rope_local_base_freq": 10000.0,
    })
    assert cfg.layer_types == (
        "sliding_attention", "sliding_attention", "full_attention",
        "sliding_attention", "sliding_attention", "full_attention",
    )
