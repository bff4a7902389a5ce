"""``mimo_v2_flash`` (MiMo-V2-Flash): sliding and full attention layers whose
KV-head counts differ, keys wider than values, a learned sink logit in the
sliding layers' softmax, rotary on a third of a head at a base by layer
kind, values scaled ahead of the cache, a held share of bias-selected
sigmoid experts with no shared expert. Tiny sizes, seeded weights, float32,
against the plain reference (``benchmark/references/mimo_v2_flash.py``: the
mask written as a mask, the sink as a column, no cache)."""

import asyncio
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
from dynamo_exp_tpu.models import ModelConfig, llama
from dynamo_exp_tpu.models.config import PRESETS, TINY_EXAONE_MOE, TINY_MIMO
from dynamo_exp_tpu.ops.ragged_attention import (
    ragged_paged_attention, ragged_paged_attention_ref,
)
from dynamo_exp_tpu.protocols.common import BackendInput

# Jitted: a shape and set of options compiles once a file (tests/programs.py).
from .programs import forward, forward_ragged, init_params

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmark"))
from references import mimo_v2_flash as reference  # noqa: E402

CFG = dataclasses.replace(TINY_MIMO, dtype="float32")
W = CFG.sliding_window  # 8
HF = {
    "model_type": "mimo_v2_flash", "vocab_size": CFG.vocab_size,
    "hidden_size": CFG.hidden_size, "intermediate_size": CFG.intermediate_size,
    "num_hidden_layers": CFG.num_layers, "num_attention_heads": CFG.num_heads,
    "num_key_value_heads": 1, "head_dim": 24, "v_head_dim": 16,
    "swa_num_attention_heads": CFG.num_heads, "swa_num_key_value_heads": 2,
    "swa_head_dim": 24, "swa_v_head_dim": 16,
    "max_position_embeddings": 512, "layernorm_epsilon": CFG.rms_norm_eps,
    "rope_theta": 5000000, "swa_rope_theta": 10000, "partial_rotary_factor": 0.334,
    "attention_value_scale": 0.707, "attention_bias": False,
    "tie_word_embeddings": False, "torch_dtype": "float32", "hidden_act": "silu",
    "hybrid_layer_pattern": [0, 1, 1, 0, 1, 1], "sliding_window": W,
    "sliding_window_size": W, "attention_chunk_size": W,
    "add_swa_attention_sink_bias": True, "add_full_attention_sink_bias": False,
    "moe_layer_freq": [0, 1, 1, 1, 1, 1], "moe_intermediate_size": CFG.moe_intermediate_size,
    "n_routed_experts": CFG.num_experts_held, "n_routed_experts_global": CFG.num_experts,
    "first_routed_expert": CFG.first_expert_held, "n_shared_experts": None,
    "num_experts_per_tok": CFG.num_experts_per_tok, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc",
    "routed_scaling_factor": None,
}
PS = 4  # the width is two pages
LOGP_TOL = 1e-3
SEED = 5
PROMPT = [int(t) for t in np.random.default_rng(0).integers(2, 256, 96)]


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(SEED), CFG)


def _logp(logits):
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


def _impl(impl):
    return {"attn_impl": "pallas", "interpret": True} if impl == "pallas" else {}


# ------------------------------------------------------------------ reader
def test_config_reads_the_published_keys():
    cfg = ModelConfig.from_hf_config(HF)
    assert cfg == CFG
    assert cfg.attn_forms == ("full", "sliding", "sliding", "full", "sliding", "sliding")
    assert cfg.ff_kinds == ("dense",) + ("routed",) * 5
    assert cfg.kv_by_kind and cfg.attn_kernel_form and cfg.kv_heads_by_form
    assert cfg.layer_runs == (
        ("attn", "dense", 0, 1, 0, 0), ("attn", "routed", 1, 2, 1, 0),
        ("attn", "routed", 3, 1, 3, 2), ("attn", "routed", 4, 2, 4, 3),
    )
    assert (cfg.kv_heads_of("full"), cfg.kv_heads_of("sliding")) == (1, 2)
    assert cfg.rotary_dim == 8 == cfg.rope_dim and cfg.value_head_dim_ == 16
    assert cfg.sink_forms == ("sliding",) and cfg.value_scale == 0.707
    assert cfg.rope_theta == 5e6 and cfg.rope_local_base_freq == 1e4
    assert cfg.num_experts == 16 and cfg.experts_held == 8 and cfg.num_shared_experts == 0
    assert cfg.routed_scaling_factor == 1.0 and cfg.use_expert_bias
    # a file without the sliding keys, or with the scale and a shared expert set
    plain = {k: v for k, v in HF.items() if not k.startswith("swa_num_key")}
    assert not ModelConfig.from_hf_config(plain).kv_heads_by_form
    set_ = ModelConfig.from_hf_config({**HF, "routed_scaling_factor": 2.5, "n_shared_experts": 1})
    assert set_.routed_scaling_factor == 2.5 and set_.num_shared_experts == 1


def test_the_published_widths_give_the_cut_of_the_issue():
    path = os.path.join(
        os.path.dirname(__file__), "..", "benchmark", "configs", "mimo-v2-flash-l11e32.json"
    )
    with open(path) as f:
        cfg = ModelConfig.from_hf_config(json.load(f))
    # F-dense / S S S S / F / S S S S S
    assert [r[3] for r in cfg.layer_runs] == [1, 4, 1, 5]
    assert cfg.kv_pool_widths_of("full") == (4 * 192, 4 * 128)
    assert cfg.kv_pool_widths_of("sliding") == (8 * 192, 8 * 128)
    assert cfg.rotary_dim == 64 and cfg.num_sliding_layers == 9
    shapes = jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert n == 3_409_017_920  # 6.82 GB bfloat16 (the bias and the sinks, float32, counted)
    k, v = jax.eval_shape(lambda: llama.init_kv_cache(cfg, 64, 16, sliding_pages=8))
    assert [a.shape for a in k] == [(2, 64, 16, 768), (9, 8, 16, 1536)]
    assert [a.shape for a in v] == [(2, 64, 16, 512), (9, 8, 16, 1024)]
    # a context token of the full pool: 2 layers x 4 heads x (192 + 128) x 2 B
    assert (k[0].size + v[0].size) * 2 // (64 * 16) == 5120


@pytest.mark.parametrize("key,value,why", [
    ("swa_num_attention_heads", 8, "swa_num_attention_heads"),
    ("swa_head_dim", 32, "swa_head_dim"),
    ("swa_v_head_dim", 24, "swa_v_head_dim"),
    ("attention_bias", True, "attention_bias"),
    ("n_group", 2, "group-limited"),
    ("topk_group", 2, "group-limited"),
    ("scoring_func", "softmax", "scoring_func"),
    ("topk_method", "greedy", "topk_method"),
    ("hidden_act", "gelu", "hidden_act"),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}, "rope_type"),
    ("num_nextn_predict_layers", 3, "multi-token-prediction"),
    ("moe_layer_freq", [0, 1, 0, 1, 1, 1], "moe_layer_freq"),
    ("hybrid_layer_pattern", [0, 1, 1, 0], "hybrid_layer_pattern"),
    ("sliding_window", None, "sliding_window"),
    ("partial_rotary_factor", 0.3, "pairs"),
    ("first_routed_expert", 12, "outside the router"),
])
def test_reader_refuses_what_it_does_not_compute(key, value, why):
    with pytest.raises(ValueError, match=why):
        ModelConfig.from_hf_config({**HF, key: value})


def test_parameters_and_pools_by_kind(params):
    layers = params["layers"]
    assert layers["wq"].shape == (6, 64, 96) and layers["wo"].shape == (6, 64, 64)
    assert layers["wk"].shape == (2, 64, 24) and layers["wv"].shape == (2, 64, 16)
    assert layers["swa_wk"].shape == (4, 64, 48) and layers["swa_wv"].shape == (4, 64, 32)
    assert layers["sink"].shape == (4, 4) and layers["sink"].dtype == jnp.float32
    assert layers["router"].shape == (5, 64, 16) and layers["w_gate"].shape == (5, 8, 64, 32)
    assert "shared_gate" not in layers and "q_norm" not in layers
    k, v = llama.init_kv_cache(CFG, 32, PS, sliding_pages=12)
    assert [a.shape for a in k] == [(2, 32, PS, 24), (4, 12, PS, 48)]
    assert [a.shape for a in v] == [(2, 32, PS, 16), (4, 12, PS, 32)]
    # one KV-head count, one head size: the stacks and pools every other model has
    other = init_params(jax.random.PRNGKey(0), TINY_EXAONE_MOE)["layers"]
    assert "swa_wk" not in other and "sink" not in other
    assert not TINY_EXAONE_MOE.kv_heads_by_form
    assert TINY_EXAONE_MOE.kv_pool_widths_of("sliding") == TINY_EXAONE_MOE.kv_pool_widths


def test_each_form_has_its_own_query_block_and_manifest_names_the_form():
    """``q_per_kv`` 16 (full) and 8 (sliding) in one model, one process: a
    block of 8 and of 16 slots; the one-query call's rows a cell follow from
    the query heads and the two head widths, which the forms share."""
    from dynamo_exp_tpu.aot.lattice import _attention_form

    published = dataclasses.replace(
        CFG, num_heads=64, num_kv_heads=4, sliding_num_kv_heads=8, head_dim=192,
        v_head_dim=128, rotary_dim=64,
    )
    blocks = {
        form: llama.ragged_query_block(published, 8, published.kv_heads_of(form))
        for form in ("full", "sliding")
    }
    assert blocks == {"full": 8, "sliding": 16}
    assert llama.ragged_query_block(published, 8) == 8  # the model's own: the full layers'
    assert llama.one_query_rows(published, 64) == 32  # 64 heads of 192 + 128 lanes
    ecfg = EngineConfig(model=CFG, page_size=PS, num_pages=16, max_decode_slots=2)
    assert _attention_form(ecfg, "pallas", True) == (
        "heads-sliding-8-by-kind-full:1x24/16+sliding:2x24/16-sink"
    )
    plain = EngineConfig(model=TINY_EXAONE_MOE, page_size=PS, num_pages=16, max_decode_slots=2)
    assert _attention_form(plain, "pallas", True) == "heads-sliding-8-by-kind"


def test_the_kernel_takes_a_sink_at_sixteen_queries_a_kv_head():
    """A full layer's shape (``q_per_kv`` 16) with a sink and values narrower
    than keys, which ``add_full_attention_sink_bias`` would ask for: the tile
    kernel and the one-query call against the reference."""
    H, Hkv, D, Dv, ps = 16, 1, 24, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    k = jax.random.normal(ks[0], (16, ps, Hkv * D), jnp.float32)
    v = jax.random.normal(ks[1], (16, ps, Hkv * Dv), jnp.float32)
    sink = 2.0 * jax.random.normal(ks[2], (H,), jnp.float32)
    table = jnp.asarray(np.random.default_rng(1).permutation(16).reshape(2, 8), jnp.int32)
    row_of = jnp.asarray([0] * 8 + [1] * 8, jnp.int32)
    positions = jnp.asarray([*range(30, 38), *range(3), *[-1] * 5], jnp.int32)
    q = jax.random.normal(ks[3], (16, H, D), jnp.float32)
    want = ragged_paged_attention_ref(q, k, v, table, row_of, positions, num_kv_heads=Hkv, sink=sink)
    live = np.asarray(positions) >= 0
    for tile in (8, 1):
        got = ragged_paged_attention(
            q, k, v, table, row_of, positions, num_kv_heads=Hkv, q_tile=tile,
            interpret=True, sink=sink,
        )
        assert got.shape == (16, H, Dv)
        np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live], atol=2e-5)
        assert (np.asarray(got)[~live] == 0.0).all()


# ------------------------------------------- both pools against the reference
def _tables(rows, full_pages, sliding_pages):
    """Disjoint page ids a row in each pool, scrambled."""
    rng = np.random.default_rng(3)
    full = rng.permutation(rows * full_pages).reshape(rows, full_pages)
    sliding = rng.permutation(rows * sliding_pages).reshape(rows, sliding_pages)
    return jnp.asarray(full, jnp.int32), jnp.asarray(sliding, jnp.int32)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_chunked_prefill_then_decode_through_both_pools(params, impl):
    """Two rows of unequal length (45 and 23 tokens) prefilled in chunks of
    16 in one stream, then 4 tokens decoded a step at a time: every row's
    logits equal the reference's full forward. Contexts cross the width of
    8 and several pages of 4; the sliding pool is a RING of 8 pages a row,
    so every page behind the width has been overwritten by the time a later
    chunk reads. The two pools' rows are 24 + 16 and 48 + 32 lanes wide."""
    lengths, steps, chunk, ring = (45, 23), 4, 16, 8
    seqs = [PROMPT[:n + steps] for n in lengths]
    want = [reference.log_probs(params, HF, s, range(len(s))) for s in seqs]
    full, base = _tables(2, 16, ring)
    sliding = jnp.concatenate([base] * 2, axis=1)  # index j -> page j % ring
    k, v = llama.init_kv_cache(CFG, 32, PS, sliding_pages=2 * ring)
    prefill = jax.jit(lambda k, v, toks, pos, row: llama.forward_ragged(
        params, CFG, toks, pos, row, (full, sliding), k, v,
        jnp.arange(2 * chunk, dtype=jnp.int32), q_tile=8, **_impl(impl),
    ))
    step = jax.jit(lambda k, v, toks, pos: llama.forward(
        params, CFG, toks, pos, (full, sliding), k, v, **_impl(impl),
    ))
    row = np.repeat(np.arange(2, dtype=np.int32), chunk)
    for a in range(0, max(lengths), chunk):
        toks, pos = np.zeros(2 * chunk, np.int32), np.full(2 * chunk, -1, np.int32)
        for r, n in enumerate(lengths):
            b = min(a + chunk, n)
            if b > a:
                toks[r * chunk : r * chunk + b - a] = seqs[r][a:b]
                pos[r * chunk : r * chunk + b - a] = np.arange(a, b)
        logits, k, v = prefill(k, v, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(row))
        got = _logp(logits)
        for at in np.flatnonzero(pos >= 0):
            assert np.abs(got[at] - want[row[at]][pos[at]]).max() < LOGP_TOL, (a, at)
    for t in range(steps):
        logits, k, v = step(
            k, v, jnp.asarray([[s[n + t]] for s, n in zip(seqs, lengths)], jnp.int32),
            jnp.asarray([[n + t] for n in lengths], jnp.int32),
        )
        got = _logp(logits[:, 0])
        for r, n in enumerate(lengths):
            assert np.abs(got[r] - want[r][n + t]).max() < LOGP_TOL, (t, r)


def test_the_pools_hold_what_the_text_says(params):
    """K with its first 8 of 24 values turned at the base of the layer's form
    and V times 0.707, at the form's KV heads; whatever no token wrote stays
    zero."""
    n = 23
    full, sliding = _tables(1, 8, 8)
    k, v = llama.init_kv_cache(CFG, 8, PS, sliding_pages=8)
    _, k, v = forward(
        params, CFG, jnp.asarray([PROMPT[:n]], jnp.int32), jnp.arange(n)[None],
        (full, sliding), k, v,
    )
    entering, _ = reference.hidden_states(params, HF, PROMPT[:n], range(6))
    nth = {"sliding": 0, "full": 0}
    for layer, form in enumerate(CFG.attn_forms):
        kind = int(form == "sliding")
        table = np.asarray((full, sliding)[kind][0])
        pool_k = np.asarray(k[kind][nth[form]])[table].reshape(8 * PS, -1)
        pool_v = np.asarray(v[kind][nth[form]])[table].reshape(8 * PS, -1)
        want_k, want_v = reference.kv_rows(params, HF, layer, entering[layer])
        assert want_k.shape == (n, 24 * (1 + kind)) and want_v.shape == (n, 16 * (1 + kind))
        assert np.abs(pool_k[:n] - want_k).max() < 1e-4, layer
        assert np.abs(pool_v[:n] - want_v).max() < 1e-4, layer
        assert (pool_k[n:] == 0).all() and (pool_v[n:] == 0).all()
        nth[form] += 1


_FAULTS = {
    "no_sink": {"sink_forms": ()},
    "rotary_10": {"rotary_dim": 10},
    "rotary_all": {"rotary_dim": 0},
    "sliding_base_on_full": {"rope_theta": 10000.0},
    "no_value_scale": {"value_scale": 1.0},
    "width_7": {"sliding_window": 7},
}


@pytest.mark.parametrize("fault", [*_FAULTS, "sink_on_full", "other_kinds_heads"])
def test_a_fault_shows(params, fault):
    """The controls the chip's comparison has, at the tiny size: each moves
    the last token's log-probabilities by more than ten times the limit the
    sound program keeps."""
    n = 40
    want = reference.log_probs(params, HF, PROMPT[:n], range(n))
    cfg, weights = dataclasses.replace(CFG, **_FAULTS.get(fault, {})), params
    layers = dict(params["layers"])
    if fault == "sink_on_full":  # the full layers take sinks too: layer order F S S F S S
        cfg = dataclasses.replace(CFG, sink_forms=("full", "sliding"))
        sink = layers["sink"]
        layers["sink"] = jnp.stack([sink[0], sink[0], sink[1], sink[1], sink[2], sink[3]])
    if fault == "other_kinds_heads":  # a full layer's table read as a sliding one's
        cfg = dataclasses.replace(CFG, num_kv_heads=2, sliding_num_kv_heads=0)
        layers["wk"] = jnp.stack([
            layers["swa_wk"][0], layers["swa_wk"][0], layers["swa_wk"][1],
            layers["swa_wk"][1], layers["swa_wk"][2], layers["swa_wk"][3],
        ])
        layers["wv"] = jnp.stack([
            layers["swa_wv"][0], layers["swa_wv"][0], layers["swa_wv"][1],
            layers["swa_wv"][1], layers["swa_wv"][2], layers["swa_wv"][3],
        ])
    weights = {**params, "layers": layers}
    full, sliding = _tables(1, 10, 10)
    k, v = llama.init_kv_cache(cfg, 10, PS, sliding_pages=10)
    logits, *_ = forward(
        weights, cfg, jnp.asarray([PROMPT[:n]], jnp.int32), jnp.arange(n)[None],
        (full, sliding), k, v,
    )
    assert np.abs(_logp(logits[0, n - 1]) - want[n - 1]).max() > 10 * LOGP_TOL


def test_a_sink_on_the_full_layers_too_is_served(params):
    """``add_full_attention_sink_bias`` true: the reference and the served
    path agree with sinks in every layer (both paths of attention)."""
    hf = {**HF, "add_full_attention_sink_bias": True}
    cfg = ModelConfig.from_hf_config(hf)
    assert cfg.sink_forms == ("full", "sliding")
    weights = init_params(jax.random.PRNGKey(SEED), cfg)
    assert weights["layers"]["sink"].shape == (6, 4)
    n = 30
    want = reference.log_probs(weights, hf, PROMPT[:n], range(n))
    for impl in ("xla", "pallas"):
        full, sliding = _tables(1, 8, 8)
        k, v = llama.init_kv_cache(cfg, 8, PS, sliding_pages=8)
        logits, *_ = forward_ragged(
            weights, cfg, jnp.asarray(PROMPT[:n] + [0, 0], jnp.int32),
            jnp.asarray([*range(n), -1, -1], jnp.int32), jnp.zeros((32,), jnp.int32),
            (full, sliding), k, v, jnp.arange(n, dtype=jnp.int32), q_tile=8, **_impl(impl),
        )
        assert np.abs(_logp(logits) - want).max() < LOGP_TOL, impl


# ------------------------------------------------------------------ the share
def test_the_references_share_is_the_engines(params):
    """The reference given the same share: the second half of the first
    routed layer equals ``_ffn_block``'s, and the two ranks' shares add up to
    the reference's uncut layer (no shared expert to count once)."""
    from tests.test_exaone_moe import _whole

    x = jnp.asarray(np.random.default_rng(7).standard_normal((96, 64)), jnp.float32)
    lw = params["layers"]
    lp = {"mlp_norm": lw["mlp_norm"][1]}
    lp.update((k, lw[k][0]) for k in ("router", "router_bias", *llama.EXPERT_STACKS))
    got = llama._ffn_block(x[None], lp, CFG, CFG.rms_norm_eps, lambda y: y, None)[0]
    want, margin, here = reference.ffn_half(params, HF, 1, x)
    assert np.abs(np.asarray(got) - want).max() < 1e-4 and 0.2 < here < 0.8
    assert margin.shape == (96,) and (margin >= 0).all()
    whole = {**params, "layers": _whole(CFG, params)}
    uncut, _, all_held = reference.ffn_half(
        whole, {**HF, "n_routed_experts": 16, "first_routed_expert": 0}, 1, x, normed=False
    )
    assert all_held == 1.0
    total = 0.0
    for lo in (0, 8):
        part = {k: whole["layers"][k][:, lo:lo + 8] for k in llama.EXPERT_STACKS}
        y, _, _ = reference.ffn_half(
            {**whole, "layers": {**whole["layers"], **part}},
            {**HF, "n_routed_experts": 8, "first_routed_expert": lo}, 1, x, normed=False,
        )
        total = total + y
    assert np.abs(total - uncut).max() < 1e-4 and np.abs(uncut).max() > 0.1


# ------------------------------------------------------------------ engine
def _engine(model=CFG, **kw) -> TPUEngine:
    from dynamo_exp_tpu.parallel import single_device_mesh

    cfg = EngineConfig(
        model=model, max_decode_slots=kw.pop("max_decode_slots", 4), page_size=PS,
        num_pages=kw.pop("num_pages", 96), max_model_len=kw.pop("max_model_len", 256),
        prefill_chunk=16, decode_window=4, kv_dtype="float32",
        eos_token_ids=[], preempt_stall_grace_s=0.05, **kw,
    )
    return TPUEngine(cfg, mesh=single_device_mesh(), seed=SEED)


@pytest.fixture(scope="module")
def engine():
    eng = _engine()
    eng.start()
    yield eng
    eng.stop()


async def _run(eng, prompt, n):
    b = BackendInput(token_ids=list(prompt))
    b.stop_conditions.max_tokens = n
    b.stop_conditions.ignore_eos = True
    stream = await eng.generate(b.to_dict())
    tokens = []
    async for item in stream:
        tokens.extend(item.get("token_ids", []))
    return tokens


def _greedy_reference(params, prompt, n, hf=HF):
    ids = list(prompt)
    for _ in range(n):
        ids.append(int(reference.log_probs(params, hf, ids, [len(ids) - 1])[0].argmax()))
    return ids[len(prompt):]


def _samples(*names):
    from dynamo_exp_tpu.telemetry import get_telemetry

    return [
        s for m in get_telemetry().registry.collect() for s in m.samples if s.name in names
    ]


async def test_engine_serves_rows_of_unequal_length_through_both_pools(engine):
    """Through the scheduler: prompts of 61, 5 and 37 tokens in chunks of 16
    (mixed program), then windows of 4 steps, each equal to the reference's
    greedy continuation; sliding pages went back while the rows lived."""
    assert [a.shape for a in engine.k_cache] == [
        (2, 96, PS, 24), (4, engine.cfg.sliding_pool_pages, PS, 48)
    ]
    assert [a.shape[-1] for a in engine.v_cache] == [16, 32]
    prompts = [PROMPT[:61], PROMPT[61:66], PROMPT[30:67]]
    got = await asyncio.gather(*(_run(engine, p, 12) for p in prompts))
    for p, g in zip(prompts, got):
        assert g == _greedy_reference(engine.params, p, 12)
    assert engine.kv.sliding.released_total >= 61 // PS - 3
    assert engine.kv.sliding.held == 0 and not engine.kv.ledger_check()
    assert engine.kv_audit()["ok"]


def test_pool_and_form_info_say_what_each_pool_holds(engine):
    info = {s.name: s.labels for s in _samples("dynamo_kv_pool_info", "dynamo_kv_sliding_pool_info")}
    full, sliding = info["dynamo_kv_pool_info"], info["dynamo_kv_sliding_pool_info"]
    # a token of the full pools: 2 layers x 1 head x (24 + 16) x 4 B
    assert full["layers"] == "2" and full["width"] == "24" and full["token_bytes"] == str(2 * 40 * 4)
    # a page of the sliding pools: 4 layers x 2 heads x (24 + 16) x 4 tokens x 4 B
    assert sliding["layers"] == "4" and sliding["width"] == "48"
    assert sliding["page_bytes"] == str(4 * 2 * 40 * PS * 4)
    forms = {s.labels["form"]: s.labels for s in _samples("dynamo_attn_form_info")}
    assert forms["full"] == {
        "form": "full", "layers": "2", "kv_heads": "1", "key_width": "24",
        "value_width": "16", "sink": "0",
    }
    assert forms["sliding"]["kv_heads"] == "2" and forms["sliding"]["sink"] == "1"
    assert forms["sliding"]["layers"] == "4"


def test_engine_through_the_kernel():
    """The same path with the kernel (interpreted) in both programs: tiles of
    8 merged into each form's own block, and the one-query form, with the
    sink and the narrow values. A stack of two layers (a full one with the
    dense feed-forward, a sliding one with the routed) on two slots."""
    hf = {
        **HF, "num_hidden_layers": 2, "hybrid_layer_pattern": [0, 1], "moe_layer_freq": [0, 1],
    }
    eng = _engine(
        ModelConfig.from_hf_config(hf), attention_impl="pallas", pallas_interpret=True,
        max_decode_slots=2, max_model_len=32,  # 8 pages a row: 8 page copies a chunk
    )
    eng.start()
    try:
        assert eng._attn_impl == "pallas" and len(eng.cfg.model.layer_runs) == 2
        prompt = PROMPT[3:24]  # a chunk of 16 and one of 5, then two windows
        assert asyncio.run(_run(eng, prompt, 5)) == _greedy_reference(eng.params, prompt, 5, hf)
        assert eng.kv.sliding.released_total > 0 and not eng.kv.ledger_check()
    finally:
        eng.stop()


def test_tiers_refuse_and_the_kernel_resolves():
    from dynamo_exp_tpu.engine.engine import resolve_attn_impl
    from dynamo_exp_tpu.parallel import single_device_mesh

    with pytest.raises(ValueError, match="4 sliding-attention layers .width 8. in a page pool of their own"):
        TPUEngine(EngineConfig(model=CFG, max_decode_slots=2, page_size=PS, num_pages=16, tp=2))
    cfg = EngineConfig(model=CFG, attention_impl="pallas", pallas_interpret=True)
    assert resolve_attn_impl(cfg, single_device_mesh())[0] == "pallas"
    assert PRESETS["tiny-mimo"] == TINY_MIMO


def test_checkpoint_names_round_trip(tmp_path, params):
    """A checkpoint under the family's tensor names with all 16 experts in it
    loads into the stacks by kind and by form, the held share alone; one that
    carries a multi-token-prediction module is refused."""
    from safetensors.numpy import save_file
    from tests.test_exaone_moe import _whole

    from dynamo_exp_tpu.models.loader import load_params
    from dynamo_exp_tpu.models.synth import write_config

    whole = _whole(CFG, params)
    own = {k: np.asarray(v) for k, v in params["layers"].items()}
    out = {
        "model.embed_tokens.weight": np.asarray(params["embed"]),
        "model.norm.weight": np.asarray(params["final_norm"]),
        "lm_head.weight": np.asarray(params["lm_head"]).T,
    }
    proj = (("gate", "gate_proj"), ("up", "up_proj"), ("down", "down_proj"))
    nth = {"full": 0, "sliding": 0}
    for i, (ff, form) in enumerate(zip(CFG.ff_kinds, CFG.attn_forms)):
        p = f"model.layers.{i}."
        out[p + "input_layernorm.weight"] = own["attn_norm"][i]
        out[p + "post_attention_layernorm.weight"] = own["mlp_norm"][i]
        stack = "swa_" if form == "sliding" else ""
        out[p + "self_attn.q_proj.weight"] = own["wq"][i].T
        out[p + "self_attn.o_proj.weight"] = own["wo"][i].T
        out[p + "self_attn.k_proj.weight"] = own[stack + "wk"][nth[form]].T
        out[p + "self_attn.v_proj.weight"] = own[stack + "wv"][nth[form]].T
        if form == "sliding":
            out[p + "self_attn.attention_sink_bias"] = own["sink"][nth[form]]
        nth[form] += 1
        if ff == "dense":
            for key, name in proj:
                out[f"{p}mlp.{name}.weight"] = own[f"dense_{key}"][i].T
            continue
        f = i - CFG.num_dense_layers
        out[p + "mlp.gate.weight"] = own["router"][f].T
        out[p + "mlp.gate.e_score_correction_bias"] = own["router_bias"][f]
        for key, name in proj:
            for e in range(16):
                out[f"{p}mlp.experts.{e}.{name}.weight"] = np.asarray(whole[f"w_{key}"][f, e]).T
    path = tmp_path / "ok"
    path.mkdir()
    save_file({k: np.ascontiguousarray(v) for k, v in out.items()}, str(path / "model.safetensors"))
    write_config(str(path), CFG)
    loaded, cfg = load_params(str(path))
    assert cfg == CFG and set(loaded["layers"]) == set(params["layers"])
    for k, v in params["layers"].items():
        np.testing.assert_array_equal(loaded["layers"][k], np.asarray(v), err_msg=k)
    bad = tmp_path / "mtp"
    bad.mkdir()
    out["model.mtp_layers.0.input_layernorm.weight"] = own["mlp_norm"][0]
    save_file({k: np.ascontiguousarray(v) for k, v in out.items()}, str(bad / "model.safetensors"))
    write_config(str(bad), CFG)
    with pytest.raises(ValueError, match="mtp_layers"):
        load_params(str(bad))
