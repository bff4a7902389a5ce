"""pangu_ultra_moe (openPangu-Ultra-MoE-718B) on the normal path: multi-head
latent attention over one latent page pool (no value pool), a share of the
experts held beside a shared expert, four norms a layer. Tiny sizes, seeded
weights, float32, so the engine and the plain reference
(``benchmark/references/openpangu_moe.py``, the EXPANDED form) differ by
float32 rounding alone, whichever form the engine computes: the ABSORBED
one everywhere off the kernel and for a row of few queries, the expanded
one for a row of at least 8 queries (tiny-pangu's widths and a tile of 8:
``latent_form_queries``) in a kernel call's tiled stream.

TOLERANCES. Both sides compute in float32 (the reference at ``highest``
matmul precision, the engine at the CPU's default, also float32 products):
log-probabilities agree to ~1e-5, so ``LOGP_TOL`` 1e-3 stands a hundred
times above the rounding and far below any fault (a score scale of
1/sqrt(nope), a latent cached before its norm, a rotary key cached before
RoPE, a renormalisation over the held picks alone: each test that makes one
says how far it moves them). Token streams are compared exactly.
"""

import asyncio
import dataclasses
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
from dynamo_exp_tpu.engine.config import refuse_latent_tiers
from dynamo_exp_tpu.models import ModelConfig, llama
from dynamo_exp_tpu.models.config import TINY_PANGU
from dynamo_exp_tpu.ops import moe
from dynamo_exp_tpu.protocols.common import BackendInput

# Jitted: a shape and set of options compiles once a file (tests/programs.py).
from .programs import forward, forward_ragged, init_params

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmark"))
from references import openpangu_moe as reference  # noqa: E402

CFG = dataclasses.replace(TINY_PANGU, dtype="float32")
HF = {
    "model_type": "pangu_ultra_moe", "vocab_size": CFG.vocab_size,
    "hidden_size": CFG.hidden_size, "intermediate_size": CFG.intermediate_size,
    "num_hidden_layers": CFG.num_layers, "num_attention_heads": CFG.num_heads,
    "num_key_value_heads": CFG.num_heads, "max_position_embeddings": 512,
    "rms_norm_eps": CFG.rms_norm_eps, "rope_theta": CFG.rope_theta,
    "tie_word_embeddings": False, "torch_dtype": "float32", "hidden_act": "silu",
    "attention_bias": False, "sandwich_norm": True, "norm_topk_prob": True,
    "q_lora_rank": CFG.q_lora_rank, "kv_lora_rank": CFG.kv_lora_rank,
    "qk_nope_head_dim": CFG.qk_nope_head_dim, "qk_rope_head_dim": CFG.qk_rope_head_dim,
    "v_head_dim": CFG.v_head_dim, "first_k_dense_replace": CFG.num_dense_layers,
    "n_routed_experts": CFG.num_experts_held, "n_routed_experts_global": CFG.num_experts,
    "first_routed_expert": CFG.first_expert_held, "n_shared_experts": 1,
    "num_experts_per_tok": CFG.num_experts_per_tok,
    "moe_intermediate_size": CFG.moe_intermediate_size,
    "routed_scaling_factor": CFG.routed_scaling_factor, "num_nextn_predict_layers": 0,
}
PS = 8
LOGP_TOL = 1e-3
SEED = 3
PROMPT = [int(t) for t in np.random.default_rng(0).integers(2, 256, 48)]
# The forwards called outright read pools of LARGE pages on narrow tables (two
# pages of 32 a row: every context here is under 64 tokens): a chunk of the
# interpreted kernel is then 2 page copies to lower, not 16
# (tests/ragged_cases.py "SHAPES"). The engines keep pages of ``PS``.
FPS = 32
TABLE = jnp.arange(2, dtype=jnp.int32)[None] + 1


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(SEED), CFG)


def _pools():
    return llama.init_kv_cache(CFG, 9, FPS, dtype=jnp.float32)


def _logp(logits):
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


# ------------------------------------------------------------------ reader
def test_config_reads_the_published_keys():
    assert ModelConfig.from_hf_config(HF) == CFG
    assert CFG.op_kinds == ("attn",) * 3 and CFG.ff_kinds == ("dense", "routed", "routed")
    assert CFG.layer_runs == (("attn", "dense", 0, 1, 0, 0), ("attn", "routed", 1, 2, 1, 0))
    assert CFG.is_latent and not CFG.has_seq_state
    # 40 + 16 values in one group of 128 lanes; the values' lanes a group too
    assert (CFG.latent_width, CFG.latent_lanes, CFG.kv_pool_widths) == (56, 128, (128, 0))
    assert (CFG.experts_held, CFG.first_expert_held, CFG.num_experts) == (8, 8, 32)


def test_every_expert_held_is_no_share():
    cfg = ModelConfig.from_hf_config({**HF, "n_routed_experts": 32, "first_routed_expert": 0})
    assert cfg.num_experts_held == 0 and cfg.experts_held == 32


@pytest.mark.parametrize("key,value,why", [
    ("num_nextn_predict_layers", 1, "multi-token-prediction"),
    ("sandwich_norm", False, "sandwich_norm"),
    ("rope_scaling", {"type": "yarn", "factor": 4}, "rope_scaling"),
    ("n_group", 8, "group-limited"),
    ("attention_bias", True, "attention_bias"),
    ("hidden_act", "gelu", "hidden_act"),
    ("kv_lora_rank", 0, "kv_lora_rank"),
    ("qk_rope_head_dim", 15, "even"),
    ("first_routed_expert", 30, "outside the router's range"),
    ("num_experts_per_tok", 64, "exceeds"),
])
def test_reader_refuses_what_it_does_not_compute(key, value, why):
    with pytest.raises(ValueError, match=why):
        ModelConfig.from_hf_config({**HF, key: value})


def test_parameters_by_kind(params):
    layers = params["layers"]
    assert not {"wq", "wk", "wv", "shared_router", "router_bias"} & set(layers)
    assert layers["router"].shape == (2, 64, 32)  # the router scores all 32
    assert layers["w_gate"].shape == (2, 8, 64, 32)  # 8 of them are held
    assert layers["shared_gate"].shape == (2, 64, 32) and layers["dense_gate"].shape == (1, 64, 128)
    assert layers["w_dkv"].shape == (3, 64, 56) and layers["w_ukv"].shape == (3, 40, 4 * 32)
    for norm in ("attn_norm", "post_attn_norm", "mlp_norm", "post_ffn_norm"):
        assert layers[norm].shape == (3, 64)
    specs = llama.param_shardings(CFG)
    assert jax.tree.structure(specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)) \
        == jax.tree.structure(params)


def test_one_pool_and_no_value_pool():
    pool, values = _pools()
    assert pool.shape == (3, 9, FPS, 128) and values.shape == (3, 9, FPS, 0)
    assert values.nbytes == 0


# ------------------------------------------- absorbed against the expanded
@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_forward_matches_reference(params, phase):
    """The engine's absorbed form against the reference's expanded one: the
    prompt in one piece, then six steps through the latent cache."""
    k, v = _pools()
    T = 21
    logits, k, v = forward(
        params, CFG, jnp.asarray(PROMPT[:T])[None], jnp.arange(T, dtype=jnp.int32)[None],
        TABLE, k, v,
    )
    if phase == "prefill":
        want = reference.log_probs(params, HF, PROMPT[:T], range(T))
        assert np.abs(_logp(logits[0]) - want).max() < LOGP_TOL
        return
    for t in range(T, T + 6):
        logits, k, v = forward(
            params, CFG, jnp.asarray(PROMPT[t:t + 1])[None], jnp.asarray([[t]], jnp.int32),
            TABLE, k, v,
        )
        want = reference.log_probs(params, HF, PROMPT[:t + 1], [t])
        assert np.abs(_logp(logits[0]) - want).max() < LOGP_TOL


def _ragged(params, impl, chunks, pools=None, cfg=CFG):
    """``PROMPT`` through ``forward_ragged`` in ``chunks`` ((a, b) spans), a
    row's span padded to whole tiles for the kernel, and every chunk to the
    longest one's slots (the dispatches are then one compiled program).
    Log-probabilities of every real token, and the pools."""
    k, v = pools or _pools()
    got = []
    n = max(-(-(b - a) // 8) * 8 for a, b in chunks)
    for a, b in chunks:
        tokens = jnp.asarray(PROMPT[a:b] + [0] * (n - (b - a)), jnp.int32)
        pos = jnp.asarray(list(range(a, b)) + [-1] * (n - (b - a)), jnp.int32)
        logits, k, v = forward_ragged(
            params, cfg, tokens, pos, jnp.zeros(n, jnp.int32), TABLE, k, v,
            jnp.arange(n), attn_impl=impl, q_tile=8, interpret=impl == "pallas",
        )
        got.append(_logp(logits)[: b - a])
    return np.concatenate(got), (k, v)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_chunked_prefill_through_the_latent_pages(params, impl):
    """Three chunks (the second ends inside a page and a tile), then
    decode steps through the kernel's one-query form: the uncut forward's
    log-probabilities."""
    T = 37
    want = reference.log_probs(params, HF, PROMPT[:T + 3], range(T + 3))
    got, (k, v) = _ragged(params, impl, ((0, 16), (16, 29), (29, T)))
    assert np.abs(got - want[:T]).max() < LOGP_TOL
    for t in range(T, T + 3):
        logits, k, v = forward(
            params, CFG, jnp.asarray(PROMPT[t:t + 1])[None], jnp.asarray([[t]], jnp.int32),
            TABLE, k, v, attn_impl=impl, interpret=impl == "pallas",
        )
        assert np.abs(_logp(logits[0]) - want[t]).max() < LOGP_TOL


def test_the_pool_holds_the_normed_latent_and_the_rotated_key(params):
    """The pool's own rows after a prefill: ``c_kv`` after its norm and
    ``k_r`` after RoPE, zeros in the padding lanes, layer by layer."""
    T = 24
    _, (pool, _) = _ragged(params, "xla", ((0, T),))
    x = np.asarray(params["embed"])[PROMPT[:T]]
    rows = np.asarray(pool[0, 1]).reshape(FPS, -1)[:T]  # page 1 of layer 0
    want = reference.latent_rows(params, HF, 0, x)
    assert np.abs(rows[:, :CFG.latent_width] - want).max() < 1e-4
    assert np.abs(rows[:, CFG.latent_width:]).max() == 0.0


def test_two_rows_of_one_dispatch_take_the_two_forms(params):
    """A stream whose tiled part holds a chunk of 19 queries on a context
    another dispatch wrote (expanded) beside another sequence's chunk of 5
    (absorbed: under the rule's 8), a decode row of a third in the packed
    tail and padding tiles: each row's logits are its own uncut forward's,
    and they are what the absorbed form alone gives (the reference path)."""
    k, v = _pools()
    a, b, c = PROMPT[:30], PROMPT[30:35], PROMPT[35:45]
    table = jnp.stack([jnp.arange(1, 3), jnp.arange(3, 5), jnp.arange(5, 7)]).astype(jnp.int32)
    table = jnp.concatenate([table, jnp.zeros((1, 2), jnp.int32)])
    for row, ids in ((0, a[:11]), (2, c[:9])):  # what earlier dispatches wrote
        _, k, v = forward(
            params, CFG, jnp.asarray(ids)[None], jnp.arange(len(ids), dtype=jnp.int32)[None],
            table[row:row + 1], k, v,
        )
    tokens = a[11:30] + [0] * 5 + b + [0] * 3 + [0] * 8 + [c[9]] + [0] * 7
    positions = list(range(11, 30)) + [-1] * 5 + list(range(5)) + [-1] * 3 + [-1] * 8 + [9] + [-1] * 7
    row_of = [0] * 24 + [1] * 8 + [3] * 8 + [2] + [3] * 7
    args = (
        jnp.asarray(tokens, jnp.int32), jnp.asarray(positions, jnp.int32),
        jnp.asarray(row_of, jnp.int32), table,
    )
    out_idx = jnp.asarray([18, 28, 40])
    logits, *_ = forward_ragged(
        params, CFG, *args, k, v, out_idx, attn_impl="pallas", q_tile=8, packed_from=40,
        interpret=True,
    )
    absorbed, *_ = forward_ragged(params, CFG, *args, k, v, out_idx, attn_impl="xla")
    got = _logp(logits)
    for i, (ids, at) in enumerate(((a, 29), (b, 4), (c, 9))):
        assert np.abs(got[i] - reference.log_probs(params, HF, ids, [at])[0]).max() < LOGP_TOL
    assert np.abs(got - _logp(absorbed)).max() < LOGP_TOL
    assert llama.latent_form_queries(CFG, 8, FPS, jnp.float32, interpret=True) == 8


def test_the_rule_is_the_models_widths_and_none_off_the_kernels_tiling():
    """160 queries at the published widths on a chip; tiny-pangu's ranks
    tile in interpret mode alone; no other kind of model has a rule."""
    from dynamo_exp_tpu.models import TINY

    published = dataclasses.replace(
        TINY_PANGU, num_heads=128, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128,
    )
    assert llama.latent_form_queries(published, 8, 16, jnp.bfloat16) == 160
    assert llama.latent_form_queries(TINY_PANGU, 8, 16, jnp.bfloat16) is None
    assert llama.latent_form_queries(TINY_PANGU, 8, 16, jnp.bfloat16, interpret=True) == 8
    assert llama.latent_form_queries(TINY, 8, 16, jnp.bfloat16, interpret=True) is None


def test_mixed_stream_with_padding(params):
    """One stream of a prefill chunk padded to its tile, a decode row of
    another sequence in the packed tail and padding slots, through the
    kernel in both forms: each row's logits are its own uncut forward's."""
    k, v = _pools()
    other = PROMPT[20:30]
    table = jnp.stack([jnp.arange(1, 3), jnp.arange(3, 5)]).astype(jnp.int32)
    _, k, v = forward(
        params, CFG, jnp.asarray(other[:9])[None], jnp.arange(9, dtype=jnp.int32)[None],
        table[1:], k, v,
    )
    tokens = jnp.asarray(PROMPT[:5] + [0] * 3 + [other[9]] + [0] * 7, jnp.int32)
    positions = jnp.asarray([0, 1, 2, 3, 4, -1, -1, -1, 9] + [-1] * 7, jnp.int32)
    row_of = jnp.asarray([0] * 8 + [1] + [2] * 7, jnp.int32)
    full_table = jnp.concatenate([table, jnp.zeros((1, 2), jnp.int32)])
    logits, k, v, held = forward_ragged(
        params, CFG, tokens, positions, row_of, full_table, k, v, jnp.asarray([4, 8]),
        attn_impl="pallas", q_tile=8, packed_from=8, interpret=True, count_picks=True,
    )
    got = _logp(logits)
    assert np.abs(got[0] - reference.log_probs(params, HF, PROMPT[:5], [4])[0]).max() < LOGP_TOL
    assert np.abs(got[1] - reference.log_probs(params, HF, other, [9])[0]).max() < LOGP_TOL
    # 6 live tokens x 4 picks x 2 routed layers, of which the held share; and
    # the held experts (8 a layer) those picks reached
    picks, reached = (int(n) for n in held)
    assert 0 <= picks <= 48 and 0 <= reached <= min(picks, 16)


# ----------------------------------------------------- a share of the experts
def _ffn_inputs(params, n=96):
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((n, CFG.hidden_size)), jnp.float32)
    return x, llama.rms_norm(x, params["layers"]["mlp_norm"][1], CFG.rms_norm_eps)


def _whole(params):
    """The uncut layer's parameters: 32 experts, of which ``params`` holds
    ids 8..15; the others seeded here."""
    cfg = dataclasses.replace(CFG, num_experts_held=0, first_expert_held=0)
    full = init_params(jax.random.PRNGKey(11), cfg)
    layers = dict(full["layers"])
    for name in ("router", "shared_gate", "shared_up", "shared_down", "mlp_norm", "post_ffn_norm"):
        layers[name] = params["layers"][name]
    for name in llama.EXPERT_STACKS:
        layers[name] = layers[name].at[:, 8:16].set(params["layers"][name])
    return {**full, "layers": layers}


def test_the_shares_add_up_to_the_whole_layer(params):
    """32 experts in 4 shares of 8: the four shares' routed parts, each
    computed by ``moe_ffn`` told its share, plus the shared expert ONCE,
    equal the uncut reference's feed-forward output before
    ``post_mlp_layernorm``; and so do the reference's own four shares."""
    whole = _whole(params)
    x, h = _ffn_inputs(params)
    uncut = {**HF, "n_routed_experts": 32, "first_routed_expert": 0}
    want, _, here = reference.ffn_half(whole, uncut, 1, x, normed=False)
    assert here == 1.0
    lw = whole["layers"]
    shared = reference._swiglu(h, lw["shared_gate"][0], lw["shared_up"][0], lw["shared_down"][0])
    routed, ref_routed, shares = 0.0, 0.0, []
    for lo in range(0, 32, 8):
        part = {**whole, "layers": {
            **lw, **{k: lw[k][:, lo:lo + 8] for k in llama.EXPERT_STACKS},
        }}
        routed = routed + moe.moe_ffn(
            h, lw["router"][0], *(part["layers"][k][0] for k in llama.EXPERT_STACKS),
            CFG.num_experts_per_tok, True, score="sigmoid", scale=2.5, norm_eps=1e-20,
            held_from=lo,
        )
        got, _, here = reference.ffn_half(
            part, {**HF, "n_routed_experts": 8, "first_routed_expert": lo}, 1, x, normed=False,
        )
        ref_routed = ref_routed + got - np.asarray(shared)
        shares.append(here)
    assert np.abs(np.asarray(routed + shared) - want).max() < 1e-4
    assert np.abs(ref_routed + np.asarray(shared) - want).max() < 1e-4
    assert abs(sum(shares) - 1.0) < 1e-6 and min(shares) > 0.1


def test_a_share_renormalised_over_its_own_picks_shows(params):
    """The fault the share invites: a held pick weighed by the held picks'
    sum and not by all the chosen ones'."""
    x, h = _ffn_inputs(params)
    lp = {k: params["layers"][k][0] for k in ("router", *llama.EXPERT_STACKS)}
    right = moe.moe_ffn(
        h, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"], 4, True,
        score="sigmoid", scale=2.5, norm_eps=1e-20, held_from=8,
    )
    w, ids = moe.moe_router(h, lp["router"], 4, False, score="sigmoid")
    here = (ids >= 8) & (ids < 16)
    w = jnp.where(here, w, 0.0)
    w = 2.5 * w / (w.sum(-1, keepdims=True) + 1e-20)
    dense = jnp.einsum(
        "nei,eid->ned",
        jax.nn.silu(jnp.einsum("nd,edi->nei", h, lp["w_gate"]))
        * jnp.einsum("nd,edi->nei", h, lp["w_up"]), lp["w_down"],
    )
    onehot = jax.nn.one_hot(jnp.where(here, ids - 8, 8), 9)[..., :8] * w[..., None]
    wrong = jnp.einsum("nke,ned->nd", onehot, dense)
    some = np.asarray(here.any(-1))
    rel = np.linalg.norm(np.asarray(wrong - right)[some], axis=1) / np.linalg.norm(
        np.asarray(right)[some], axis=1)
    assert np.median(rel) > 0.5


@pytest.mark.parametrize("block", [16, 48, None])
def test_held_rows_walked_in_blocks(params, block):
    """The held rows in blocks of 16 (several blocks, groups straddling
    them), of 48 and in one pass: the same sum, padding rows left out."""
    x, h = _ffn_inputs(params)
    live = jnp.asarray(np.random.default_rng(3).random(h.shape[0]) > 0.2)
    lp = {k: params["layers"][k][0] for k in ("router", *llama.EXPERT_STACKS)}
    args = (h, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"], 4, True, live)
    kw = dict(score="sigmoid", scale=2.5, norm_eps=1e-20, held_from=8)
    tally = []
    one = moe.moe_ffn(*args, **kw, held_row_block=10**6, tally=tally)
    got = moe.moe_ffn(*args, **kw, held_row_block=block)
    assert np.abs(np.asarray(got - one)).max() < 1e-5
    assert float(jnp.abs(one[~live]).max()) == 0.0
    _, ids = moe.moe_router(h, lp["router"], 4, True, score="sigmoid")
    here = ((ids >= 8) & (ids < 16)) & live[:, None]
    assert [int(n) for n in tally[0]] == [
        int(here.sum()), len(set(np.asarray(ids)[np.asarray(here)].tolist())),
    ]


def test_router_denominator():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32) * 1e-3 - 30.0  # scores ~ e^-30
    w = jnp.eye(8, dtype=jnp.float32)
    tiny, _ = moe.moe_router(x, w, 2, True, score="sigmoid", norm_eps=1e-20)
    lfm2, _ = moe.moe_router(x, w, 2, True, score="sigmoid")
    assert np.allclose(np.asarray(tiny).sum(-1), 1.0, atol=1e-3)
    assert np.asarray(lfm2).sum(-1).max() < 1e-3  # 1e-6 swamps scores this small


# ------------------------------------------------------------------ engine
def _engine(**kw) -> TPUEngine:
    from dynamo_exp_tpu.parallel import single_device_mesh

    cfg = EngineConfig(
        model=CFG, max_decode_slots=4, page_size=kw.pop("page_size", PS),
        num_pages=kw.pop("num_pages", 64), max_model_len=kw.pop("max_model_len", 256),
        prefill_chunk=16, decode_window=8, kv_dtype="float32",
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


def _greedy_reference(params, prompt, n):
    ids = list(prompt)
    for _ in range(n):
        ids.append(int(reference.log_probs(params, HF, ids, [len(ids) - 1])[0].argmax()))
    return ids[len(prompt):]


def _counter(name, **labels):
    from dynamo_exp_tpu.telemetry import get_telemetry

    total = 0.0
    for metric in get_telemetry().registry.collect():
        for s in metric.samples:
            if s.name == name and all(s.labels.get(k) == v for k, v in labels.items()):
                total += s.value
    return total


async def test_engine_serves_windows_and_chunks(engine):
    """Through the scheduler: a 40-token prompt in chunks of 16 (mixed
    program), then 20 tokens through 8-step windows, equal to the
    reference's greedy continuation; the pool is one array and the counters
    say what was attended and where the picks fell."""
    assert engine.k_cache.shape == (3, 64, PS, 128) and engine.v_cache.nbytes == 0
    assert engine.seq_state is None
    before = {
        k: _counter(k) for k in (
            "dynamo_mla_query_tokens_total", "dynamo_mla_attended_pairs_total",
            "dynamo_mla_context_tokens_total", "dynamo_moe_picks_total",
        )
    }
    prompt = PROMPT[:40]
    got = await _run(engine, prompt, 20)
    assert got == _greedy_reference(engine.params, prompt, 20)
    fed = 40 + 19  # every token but the last sampled one is fed
    assert _counter("dynamo_mla_query_tokens_total") - before["dynamo_mla_query_tokens_total"] >= fed
    assert _counter("dynamo_mla_query_tokens_total", form="expanded") == 0
    pairs = _counter("dynamo_mla_attended_pairs_total") - before["dynamo_mla_attended_pairs_total"]
    assert pairs >= fed * (fed + 1) // 2
    ctx = _counter("dynamo_mla_context_tokens_total") - before["dynamo_mla_context_tokens_total"]
    assert 0 < ctx <= pairs
    picks = _counter("dynamo_moe_picks_total") - before["dynamo_moe_picks_total"]
    assert picks >= fed * 4 * 2  # top-4, two routed layers
    held = _counter("dynamo_moe_picks_total", where="held")
    assert 0 < held < _counter("dynamo_moe_picks_total", where="elsewhere")
    # the held experts those picks reached: at most 8 a layer, step and dispatch
    reads = _counter("dynamo_moe_held_expert_reads_total")
    assert 0 < reads <= held
    assert _counter("dynamo_moe_held_expert_reads_total", program="window") > 0


def test_pool_info_says_what_a_token_holds(engine):
    from dynamo_exp_tpu.telemetry import get_telemetry

    (labels,) = [
        s.labels for m in get_telemetry().registry.collect() for s in m.samples
        if s.name == "dynamo_kv_pool_info"
    ]
    assert labels["pools"] == "1" and labels["width"] == "128"
    assert labels["pad_lanes"] == str(128 - 56)
    assert labels["token_bytes"] == str(3 * 128 * 4)  # layers x lanes x float32


@pytest.mark.parametrize("asking", ["again", "longer"])
async def test_second_asking_from_cached_latent_pages(engine, asking):
    """A prompt whose head is in cached pages is served from them: latent
    rows another request wrote, read by the absorbed form."""
    base = PROMPT[5:38]  # 33 tokens: four whole pages
    await _run(engine, base, 4)
    before = engine.kv.prefix_hits["shared"]
    prompt = base if asking == "again" else base + PROMPT[:7]
    got = await _run(engine, prompt, 12)
    assert got == _greedy_reference(engine.params, prompt, 12)
    assert engine.kv.prefix_hits["shared"] - before == 4


async def test_engine_through_the_kernel():
    """The same path with the attention kernel (interpreted) in both
    programs and the experts' kernel in place."""
    # pages of 32 on a table of 4: four page copies a chunk of the interpreted
    # kernel to lower, not sixteen (tests/ragged_cases.py "SHAPES")
    eng = _engine(
        attention_impl="pallas", pallas_interpret=True, page_size=32, max_model_len=128,
        num_pages=16,
    )
    eng.start()
    name = "dynamo_mla_query_tokens_total"
    before = {form: _counter(name, form=form) for form in ("expanded", "absorbed")}
    try:
        prompt = PROMPT[3:30]
        got = await _run(eng, prompt, 10)
        assert got == _greedy_reference(eng.params, prompt, 10)
        assert eng._attn_impl == "pallas"
    finally:
        eng.stop()
    # 27 prompt tokens in chunks of 16 and 11, each in a bucket of 16 slots
    # whose tiled part is 8 (``ragged_packed_slots``: the span runs on into
    # the packed tail): 8 queries of each chunk meet the rule's 8 in the tiled
    # part and took the expanded form; the 8 + 3 in the tail and the 9 fed
    # decode tokens (windows) the absorbed one
    assert _counter(name, form="expanded") - before["expanded"] == 16
    assert _counter(name, form="absorbed") - before["absorbed"] == 11 + 9


def test_the_counter_asks_the_programs_rule(engine):
    """``_count_latent_reads`` and the program split one stream alike: the
    labels ``expanded`` and ``absorbed`` add up to the dispatch's query
    tokens, and ``expanded`` counts the live queries of the slots the
    device's plan marks (``expanded_plan``), for a stream with a row at the
    rule's threshold, one a query under it, a long one and a decode row in
    the packed tail. An engine off the kernel counts every query absorbed."""
    from dynamo_exp_tpu.ops.ragged_attention import expanded_plan

    eng = types.SimpleNamespace(
        cfg=dataclasses.replace(engine.cfg, attention_impl="pallas"),
        _attn_impl="pallas", _attn_interpret=True,
    )
    for method in ("_count_latent_reads", "_latent_form_queries"):
        setattr(eng, method, types.MethodType(getattr(TPUEngine, method), eng))
    least = eng._latent_form_queries()
    assert least == llama.latent_form_queries(CFG, 8, PS, jnp.float32, interpret=True) == 8
    spans = [(8, 20), (7, 0), (21, 3)]
    row_of, positions = [], []
    for r, (n, first) in enumerate(spans):
        pad = (-n) % 8
        row_of += [r] * (n + pad)
        positions += list(range(first, first + n)) + [-1] * pad
    tiled = len(row_of) + 8
    row_of += [4] * 8 + [3] + [4] * 7
    positions += [-1] * 8 + [30] + [-1] * 7
    row_of, positions = np.asarray(row_of, np.int32), np.asarray(positions, np.int32)
    name = "dynamo_mla_query_tokens_total"
    before = {form: _counter(name, program="mixed", form=form) for form in ("expanded", "absorbed")}
    eng._count_latent_reads("mixed", positions, row_of, tiled=tiled)
    took = {
        form: _counter(name, program="mixed", form=form) - before[form]
        for form in ("expanded", "absorbed")
    }
    takes, _ = expanded_plan(
        jnp.asarray(row_of[:tiled]), jnp.asarray(positions[:tiled]), 5, 8, least
    )
    assert took["expanded"] == int((np.asarray(takes) & (positions[:tiled] >= 0)).sum()) == 29
    assert took["expanded"] + took["absorbed"] == int((positions >= 0).sum()) == 37
    before = _counter(name, form="expanded")
    engine._count_latent_reads("mixed", positions, row_of, tiled=tiled)
    assert _counter(name, form="expanded") == before and engine._latent_form_queries() is None


async def test_preempt_and_resume():
    """Two requests on a pool that holds one and a half: one is preempted,
    re-admitted onto its own cached latent pages, and both streams equal
    the reference's."""
    eng = _engine(num_pages=12)
    eng.start()
    try:
        p1, p2, n = PROMPT[:9], PROMPT[10:19], 44
        r1, r2 = await asyncio.gather(_run(eng, p1, n), _run(eng, p2, n))
        assert eng.preempted > 0
        assert r1 == _greedy_reference(eng.params, p1, n)
        assert r2 == _greedy_reference(eng.params, p2, n)
        assert not eng.kv.ledger_check()
    finally:
        eng.stop()


# ---------------------------------------------------------------- refusals
@pytest.mark.parametrize("tier", [
    "host_cache_pages", "kv_store_dir", "spec_mode", "sp", "tp",
    "disaggregated transfer", "migration",
])
def test_tiers_that_carry_two_pools_or_split_heads_refuse(tier, tmp_path):
    kw = {
        "host_cache_pages": {"host_cache_pages": 8}, "spec_mode": {"spec_mode": "ngram"},
        "kv_store_dir": {"host_cache_pages": 8, "kv_store_dir": str(tmp_path)},
        "sp": {"sp": 2}, "tp": {"tp": 2},
    }.get(tier, {})
    cfg = EngineConfig(model=CFG, max_decode_slots=2, page_size=PS, num_pages=16, **kw)
    with pytest.raises(ValueError, match="one latent row"):
        if kw:
            TPUEngine(cfg)
        else:
            refuse_latent_tiers(cfg, tier)
    from dynamo_exp_tpu.models import TINY

    refuse_latent_tiers(dataclasses.replace(cfg, model=TINY), None if kw else tier)


@pytest.mark.parametrize("component", ["MigrationSink", "ReclaimController", "PrefillWorker"])
def test_components_round_the_engine_refuse(component):
    from dynamo_exp_tpu.disagg.prefill_worker import PrefillWorker
    from dynamo_exp_tpu.runtime import reclaim

    stub = types.SimpleNamespace(
        cfg=EngineConfig(model=CFG, max_decode_slots=2, page_size=PS, num_pages=16)
    )
    build = {
        "MigrationSink": lambda: reclaim.MigrationSink(stub, types.SimpleNamespace()),
        "ReclaimController": lambda: reclaim.ReclaimController(stub),
        "PrefillWorker": lambda: PrefillWorker(stub, queue=None),
    }[component]
    with pytest.raises(ValueError, match="one latent row"):
        build()


def test_ring_prefill_refuses(params):
    with pytest.raises(ValueError, match="one latent a token"):
        llama.forward_ring_prefill(
            params, CFG, jnp.zeros((1, 8), jnp.int32), jnp.arange(8)[None],
            types.SimpleNamespace(shape={"sp": 2, "tp": 1}),
        )


@pytest.mark.parametrize("impl,page_size,ok", [
    ("pallas", 16, True), ("auto", 16, True), ("xla", 16, False), ("pallas", 8, False),
])
def test_a_latent_pool_never_takes_the_gather_on_a_chip(impl, page_size, ok):
    """On a TPU the latent pool is the kernel's or nobody's: an explicit
    ``xla`` and a page the kernel cannot tile are refused, not sent to the
    gather. Off the TPU ``auto`` is the reference."""
    from dynamo_exp_tpu.engine.engine import resolve_attn_impl
    from dynamo_exp_tpu.parallel import single_device_mesh

    chip = types.SimpleNamespace(
        devices=types.SimpleNamespace(flat=[types.SimpleNamespace(platform="tpu")]),
        shape={"tp": 1},
    )
    cfg = EngineConfig(
        model=TINY_PANGU, max_decode_slots=2, page_size=page_size, num_pages=16,
        attention_impl=impl,
    )
    if ok:
        assert resolve_attn_impl(cfg, chip) == ("pallas", False)
    else:
        with pytest.raises(ValueError, match="attention kernel alone"):
            resolve_attn_impl(cfg, chip)
    off_chip = dataclasses.replace(cfg, attention_impl="auto")
    assert resolve_attn_impl(off_chip, single_device_mesh())[0] == "xla"


def test_manifest_names_the_attention_form_and_the_pools_widths():
    from dynamo_exp_tpu.aot.lattice import build_manifest
    from dynamo_exp_tpu.models import TINY

    def manifest(model, impl="pallas", interpret=False):
        cfg = EngineConfig(model=model, max_decode_slots=4, page_size=16, num_pages=64)
        return build_manifest(cfg, impl, {"tp": 1}, "x", interpret=interpret)

    def fingerprint(model, **kw):
        return manifest(model, **kw).engine

    assert fingerprint(TINY_PANGU)["attention_form"] == "latent-absorbed"
    assert fingerprint(TINY_PANGU)["kv_pool_widths"] == [128, 0]
    assert fingerprint(TINY)["attention_form"] == "heads"
    # The form is in the key: programs that hold the expanded call beside the
    # absorbed one (the published widths on a chip, from 160 queries a row)
    # are not the absorbed-only ones a warm boot might find.
    published = dataclasses.replace(
        TINY_PANGU, num_heads=128, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128,
    )
    both = fingerprint(published)["attention_form"]
    assert both == "latent-absorbed+expanded-from-160-queries"
    assert fingerprint(published, impl="xla")["attention_form"] == "latent-absorbed"
    assert fingerprint(TINY_PANGU, interpret=True)["attention_form"] == (
        "latent-absorbed+expanded-from-8-queries"
    )
    hashes = {
        manifest(TINY_PANGU).hash(), manifest(TINY_PANGU, interpret=True).hash(),
    }
    assert len(hashes) == 2


def test_the_expanded_forms_scope_is_what_its_metric_reads():
    """``mla_expand_time_share`` (the benchmark's, data alone) reads the scope
    the program gives the expanded form's work outside its kernel, and
    neither the kernel's own call nor the absorbed form's products; the call
    keeps the name the attention metrics find it by."""
    import inspect
    import json
    import re

    from dynamo_exp_tpu.ops import ragged_attention

    root = os.path.join(os.path.dirname(__file__), "..")
    with open(os.path.join(root, "benchmark", "layer_metrics", "mla_expand_time_share.json")) as f:
        reader = json.load(f)["reader"]
    assert reader["kind"] == "trace_scope_share"
    scope = re.compile(reader["scope"])
    at = "jit(ragged_mixed)/while/body/closed_call/mla/attend/"
    assert scope.search(at + "mla_expand/pad") and scope.search(at + "mla_expand/scatter-max")
    assert not scope.search(at + "jit(ragged_paged_attention_expanded)/pallas_call")
    assert not scope.search(at + "mla_absorb/dot_general")
    for module in (llama, ragged_attention):
        assert 'jax.named_scope("mla_expand")' in inspect.getsource(module)
    assert 'name="ragged_paged_attention_expanded"' in inspect.getsource(ragged_attention)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"] if m["name"] == "mla_expand_time_share"]
    assert "openpangu-ultra-moe-718b.longdoc-saturate" in entry["workloads"]
    assert entry["moves"] == "output_tok_s" and entry["layer"] == "Kernels"


# ------------------------------------------------------------------ loader
def test_checkpoint_names_round_trip(tmp_path, params):
    """A checkpoint under the family's tensor names, with all 32 experts in
    it, loads into the stacks by kind, the held share alone."""
    from safetensors.numpy import save_file

    from dynamo_exp_tpu.models.loader import load_params
    from dynamo_exp_tpu.models.synth import write_config

    whole = _whole(params)
    layers = {k: np.asarray(v) for k, v in whole["layers"].items()}
    out = {
        "model.embed_tokens.weight": np.asarray(params["embed"]),
        "model.norm.weight": np.asarray(params["final_norm"]),
        "lm_head.weight": np.asarray(params["lm_head"]).T,
    }
    proj = (("gate", "gate_proj"), ("up", "up_proj"), ("down", "down_proj"))
    for i, ff in enumerate(CFG.ff_kinds):
        p = f"model.layers.{i}."
        own = {k: np.asarray(v) for k, v in params["layers"].items()}
        for key, name in (
            ("attn_norm", "input_layernorm"), ("post_attn_norm", "post_attention_layernorm"),
            ("mlp_norm", "pre_mlp_layernorm"), ("post_ffn_norm", "post_mlp_layernorm"),
        ):
            out[f"{p}{name}.weight"] = own[key][i]
        for key, name in (
            ("w_dq", "q_a_proj"), ("w_uq", "q_b_proj"), ("w_dkv", "kv_a_proj_with_mqa"),
            ("w_ukv", "kv_b_proj"), ("wo", "o_proj"),
        ):
            out[f"{p}self_attn.{name}.weight"] = own[key][i].T
        out[p + "self_attn.q_a_layernorm.weight"] = own["q_a_norm"][i]
        out[p + "self_attn.kv_a_layernorm.weight"] = own["kv_a_norm"][i]
        if ff == "dense":
            for key, name in proj:
                out[f"{p}mlp.{name}.weight"] = own[f"dense_{key}"][i].T
            continue
        f = i - CFG.num_dense_layers
        out[p + "mlp.gate.weight"] = own["router"][f].T
        for key, name in proj:
            out[f"{p}mlp.shared_experts.{name}.weight"] = own[f"shared_{key}"][f].T
            for e in range(32):
                out[f"{p}mlp.experts.{e}.{name}.weight"] = layers[f"w_{key}"][f, e].T
    save_file({k: np.ascontiguousarray(v) for k, v in out.items()}, str(tmp_path / "model.safetensors"))
    write_config(str(tmp_path), CFG)
    loaded, cfg = load_params(str(tmp_path))
    assert cfg == CFG
    assert set(loaded["layers"]) == set(params["layers"])
    for k, v in params["layers"].items():
        np.testing.assert_array_equal(loaded["layers"][k], np.asarray(v), err_msg=k)
    np.testing.assert_array_equal(loaded["lm_head"], np.asarray(params["lm_head"]))


# ------------------------------------------------- what the tolerance is far below
# Last in the file: each case clears JAX's caches (the engine's forward must be
# traced anew with its fault), and with them the compiled programs the tests
# above share.
def _faulty(monkeypatch, fault):
    """The engine with one of the faults the comparison's controls make."""
    if fault == "scale_without_rope":
        monkeypatch.setattr(
            llama, "_latent_read_args",
            lambda cfg: {"sm_scale": cfg.qk_nope_head_dim ** -0.5,
                         "value_dim": llama.latent_value_lanes(cfg)},
        )
    elif fault in ("latent_before_norm", "key_before_rope"):
        norm, rope = llama.rms_norm, llama.apply_rope

        def rms_norm(x, w, eps, offset=False):  # kv_a_norm alone has this width
            return x if x.shape[-1] == CFG.kv_lora_rank else norm(x, w, eps, offset)

        def apply_rope(x, pos, inv_freq):  # the shared key alone has one head
            return x if x.shape[-2] == 1 else rope(x, pos, inv_freq)

        if fault == "latent_before_norm":
            monkeypatch.setattr(llama, "rms_norm", rms_norm)
        else:
            monkeypatch.setattr(llama, "apply_rope", apply_rope)
    elif fault in ("uk_and_uv_swapped", "rotary_key_a_head_apart"):
        # faults of the expanded form alone: what its call is handed
        from dynamo_exp_tpu.ops import ragged_attention

        call = ragged_attention.ragged_paged_attention_expanded
        r, dn = CFG.kv_lora_rank, CFG.qk_nope_head_dim

        def faulty(q, pool, w_ukv, *args, **kw):
            if fault == "uk_and_uv_swapped":
                w = w_ukv.reshape(r, CFG.num_heads, -1)
                w_ukv = jnp.concatenate([w[..., dn:], w[..., :dn]], axis=-1).reshape(r, -1)
            else:  # the shared key taken as if each head had a slice of it
                k_r = jnp.roll(pool[..., r:CFG.latent_width], 1, axis=-1)
                pool = pool.at[..., r:CFG.latent_width].set(k_r)
            return call(q, pool, w_ukv, *args, **kw)

        monkeypatch.setattr(ragged_attention, "ragged_paged_attention_expanded", faulty)


@pytest.mark.parametrize("impl,fault", [
    ("xla", "scale_without_rope"), ("xla", "latent_before_norm"), ("xla", "key_before_rope"),
    # the expanded form (rows of 16 queries through the kernel): its scale,
    # its one rotated key, and which half of W_ukv makes keys
    ("pallas", "scale_without_rope"), ("pallas", "key_before_rope"),
    ("pallas", "rotary_key_a_head_apart"), ("pallas", "uk_and_uv_swapped"),
])
def test_a_fault_in_the_latent_half_shows(params, monkeypatch, impl, fault):
    """What the tolerance is far below: scores scaled by 1/sqrt(nope), the
    latent cached before its norm, the rotary key cached before RoPE, in
    either form; in the expanded one also ``W_uk`` and ``W_uv`` exchanged
    and a rotary key that is not the one all heads share."""
    T = 32
    want = reference.log_probs(params, HF, PROMPT[:T], range(T))
    _faulty(monkeypatch, fault)
    jax.clear_caches()  # a jitted call of the sound form must not answer
    got, _ = _ragged(params, impl, ((0, 16), (16, T)))
    jax.clear_caches()
    assert np.abs(got - want).max() > 30 * LOGP_TOL
