"""KeyeVL2 (Keye-VL-2.0-30B-A3B's language model) on the normal path:
grouped-query attention read through a learned selection (an indexer a layer
whose key rides the K pool's row behind the heads' keys, the ``index_topk``
context tokens of largest index score a query and layer), rotary by sections
over three position components, 8 routed experts top 2. Tiny sizes
(``index_topk`` 16: every prompt here is longer), seeded weights, float32, so
the engine and the plain reference (``benchmark/references/keye_vl2.py``:
``lax.top_k``, no cache) differ by float32 rounding alone, whichever path
computes the scores and the mask: the gather or the two kernels, interpreted.

TOLERANCES. Both sides compute in float32: log-probabilities agree to ~1e-5
where the two selections agree, so ``LOGP_TOL`` 1e-3 stands a hundred times
above the rounding; a selection that differs in ONE position of one row moves
them by more (``test_a_fault_shows``).
"""

import asyncio
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
from dynamo_exp_tpu.engine.config import refuse_latent_tiers
from dynamo_exp_tpu.models import ModelConfig, llama
from dynamo_exp_tpu.models.config import PRESETS, TINY_KEYE, refuse_unserved_tower
from dynamo_exp_tpu.ops import sparse_index
from dynamo_exp_tpu.ops.attention import paged_attention
from dynamo_exp_tpu.ops.ragged_attention import ragged_paged_attention
from dynamo_exp_tpu.ops.rope import apply_mrope, apply_rope, rope_frequencies
from dynamo_exp_tpu.protocols.common import BackendInput

from .programs import forward, forward_ragged, init_params

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmark"))
from references import keye_vl2 as reference  # noqa: E402

CFG = dataclasses.replace(TINY_KEYE, dtype="float32")
HF = {
    "model_type": "KeyeVL2", "vocab_size": CFG.vocab_size, "hidden_size": CFG.hidden_size,
    "intermediate_size": CFG.intermediate_size, "num_hidden_layers": CFG.num_layers,
    "num_attention_heads": CFG.num_heads, "num_key_value_heads": CFG.num_kv_heads,
    "head_dim": CFG.head_dim, "max_position_embeddings": 512,
    "rms_norm_eps": CFG.rms_norm_eps, "rope_theta": CFG.rope_theta,
    "tie_word_embeddings": False, "torch_dtype": "float32", "hidden_act": "silu",
    "attention_bias": False, "norm_topk_prob": True, "num_experts": CFG.num_experts,
    "num_local_experts": CFG.num_experts, "num_experts_per_tok": CFG.num_experts_per_tok,
    "moe_intermediate_size": CFG.moe_intermediate_size, "decoder_sparse_step": 1,
    "mlp_only_layers": [], "sliding_window": None, "use_sliding_window": False,
    "max_window_layers": 3,
    "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default", "type": "default"},
    "sa_config": {
        "indexer_head_dim": 16, "indexer_num_heads": 4, "indexer_num_kv_heads": 1,
        "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 16,
    },
}
PS = 8
LOGP_TOL = 1e-3
SEED = 5
PROMPT = [int(t) for t in np.random.default_rng(0).integers(2, 256, 64)]
# The forwards called outright read pools of LARGE pages on narrow tables
# (tests/ragged_cases.py "SHAPES"); the engines keep pages of ``PS``.
FPS = 32
TABLE = jnp.arange(2, dtype=jnp.int32)[None] + 1
TOPK = CFG.index_topk
KL = CFG.num_kv_heads * CFG.head_dim  # 32 lanes of keys; the index key behind them


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(SEED), CFG)


def _pools():
    return llama.init_kv_cache(CFG, 9, FPS, dtype=jnp.float32)


def _logp(logits):
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


def _impl(impl):
    return {"attn_impl": "pallas", "interpret": True} if impl == "pallas" else {}


# ------------------------------------------------------------------ reader
def test_config_reads_the_published_keys():
    assert ModelConfig.from_hf_config(HF) == CFG
    assert PRESETS["tiny-keye"] is TINY_KEYE
    assert CFG.op_kinds == ("attn",) * 3 and CFG.ff_kinds == ("routed",) * 3
    assert CFG.has_indexer and not CFG.is_latent and not CFG.has_seq_state and CFG.qk_norm
    assert CFG.kv_row_parts() == ((("k", 32), ("index", 128)), (("v", 32),))
    assert CFG.kv_pool_widths == (160, 32) and CFG.index_lane0 == 32
    assert CFG.index_key_in_k_row and not PRESETS["tiny-glm-dsa"].index_key_in_k_row
    assert (CFG.index_rope_dim, CFG.mrope_section) == (16, (2, 3, 3))


def test_the_published_widths_give_the_row_of_the_issue():
    """4 x 128 of K and 128 lanes of index key, 4 x 128 of V: 2,304 B a token
    and layer in bfloat16, 11,520 B over five layers."""
    published = ModelConfig.from_hf_config({
        **HF, "hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 4,
        "head_dim": 128, "num_hidden_layers": 5, "num_experts": 128, "num_local_experts": 128,
        "num_experts_per_tok": 8, "moe_intermediate_size": 768, "torch_dtype": "bfloat16",
        "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default"},
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "topk": 2048},
    })
    assert published.kv_pool_widths == (640, 512) and published.index_lane0 == 512
    assert 2 * sum(published.kv_pool_widths) * published.kv_layer_slots == 11520
    k, v = jax.eval_shape(lambda: llama.init_kv_cache(published, 4, 16))
    assert k.shape == (5, 4, 16, 640) and v.shape == (5, 4, 16, 512)


@pytest.mark.parametrize("key,value,why", [
    ("use_sliding_window", True, "use_sliding_window"),
    ("rope_scaling", {"mrope_section": [2, 3, 3], "rope_type": "yarn"}, "rope_type"),
    ("rope_scaling", {"mrope_section": [2, 3, 4], "rope_type": "default"}, "sum to half"),
    ("decoder_sparse_step", 2, "mixed dense"),
    ("attention_bias", True, "attention_bias"),
    ("hidden_act", "gelu", "hidden_act"),
    ("sa_config", {"indexer_head_dim": 16, "indexer_num_heads": 4, "topk": 0}, "topk"),
    ("sa_config", {"indexer_head_dim": 16, "indexer_num_heads": 4, "topk": 16,
                   "indexer_num_kv_heads": 2}, "one index key head"),
])
def test_reader_refuses_what_it_does_not_compute(key, value, why):
    with pytest.raises(ValueError, match=why):
        ModelConfig.from_hf_config({**HF, key: value})


def test_parameters_by_kind(params):
    layers = params["layers"]
    assert layers["idx_wq"].shape == (3, 64, 4 * 16)  # from the normed stream, no bottleneck
    assert layers["idx_wk"].shape == (3, 64, 16) and layers["idx_wp"].shape == (3, 64, 4)
    assert layers["idx_k_norm"].shape == layers["idx_k_bias"].shape == (3, 16)
    assert layers["q_norm"].shape == layers["k_norm"].shape == (3, 16)
    assert layers["w_gate"].shape == (3, 8, 64, 32) and "shared_gate" not in layers
    assert set(llama.param_shardings(CFG)["layers"]) == set(layers)


# ------------------------------------------------------------------ rotary
def test_sectioned_rotary():
    """(iv) Three UNEQUAL components equal the reference's; equal ones equal
    ``apply_rope`` bit for bit."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, 7, 4, 16)), jnp.float32)
    inv = rope_frequencies(16, CFG.rope_theta)
    pos = jnp.asarray(rng.integers(0, 400, (2, 7)), jnp.int32)
    same = apply_mrope(x, jnp.broadcast_to(pos, (3, 2, 7)), inv, (2, 3, 3))
    np.testing.assert_array_equal(np.asarray(same), np.asarray(apply_rope(x, pos, inv)))
    pos3 = jnp.asarray(rng.integers(0, 400, (3, 2, 7)), jnp.int32)
    got = apply_mrope(x, pos3, inv, (2, 3, 3))
    for b in range(2):
        want = reference._mrope(x[b], pos3[:, b], CFG.rope_theta, (2, 3, 3))
        assert np.abs(np.asarray(got[b]) - np.asarray(want)).max() < 1e-5
    assert np.abs(np.asarray(got) - np.asarray(same)).max() > 0.1
    with pytest.raises(ValueError, match="do not cover"):
        apply_mrope(x, pos3, inv, (2, 3, 4))


def test_forward_with_three_unequal_position_components(params):
    """The seam a tower's soft tokens would come through: ``rope_positions``
    [3, B, T] turn q and k while the cache and the mask go by ``positions``."""
    T = 24
    pos3 = np.stack([np.arange(T), np.arange(T) // 3, np.arange(T) % 5]).astype(np.int32)
    k, v = _pools()
    logits, *_ = llama.forward(
        params, CFG, jnp.asarray(PROMPT[:T])[None], jnp.arange(T, dtype=jnp.int32)[None],
        TABLE, k, v, rope_positions=jnp.asarray(pos3)[:, None],
    )
    want = reference.log_probs(params, HF, PROMPT[:T], range(T), positions=pos3)
    assert np.abs(_logp(logits[0]) - want).max() < LOGP_TOL
    plain = reference.log_probs(params, HF, PROMPT[:T], range(T))
    assert np.abs(plain - want).max() > 10 * LOGP_TOL
    with pytest.raises(ValueError, match="no rotary by sections"):
        llama._rope_positions(PRESETS["tiny"], jnp.arange(4), jnp.zeros((3, 4), jnp.int32))


# ------------------------------------------------------------------ kernels
@pytest.mark.parametrize("q_tile", [8, 1])
def test_scores_kernel_reads_the_key_behind_k(q_tile):
    """(vi) ``index_scores`` with the index key at a lane offset in a wider
    row equals ``index_scores_ref``, and the kernel on a pool of index keys
    alone (the latent model's call)."""
    rng = np.random.default_rng(7)
    n, hi, di, ps, pmax = 16, 16, 64, 16, 4
    rows = jnp.asarray(rng.standard_normal((9, ps, 128 + 128)), jnp.float32)
    rows = rows.at[..., 128 + di:].set(0.0)  # the key's padding lanes
    q = jnp.asarray(rng.standard_normal((n, hi, di)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((n, hi)), jnp.float32)
    table = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    row_of = jnp.repeat(jnp.arange(2, dtype=jnp.int32), 8)
    bounds = jnp.asarray(list(range(40, 48)) + [5, 6, 7, 8, -1, -1, -1, -1], jnp.int32)
    got = sparse_index.by_query(sparse_index.index_scores(
        q, w, rows, table, row_of, bounds, q_tile=q_tile, interpret=True, lane0=128,
    ))
    alone = sparse_index.by_query(sparse_index.index_scores(
        q, w, rows[..., 128:], table, row_of, bounds, q_tile=q_tile, interpret=True,
    ))
    want = sparse_index.index_scores_ref(
        q[:, None], w[:, None], rows, table[row_of], bounds[:, None], lane0=128,
    )[:, 0]
    live = np.asarray(bounds) >= 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(alone))


@pytest.mark.parametrize("q_tile", [8, 1])
def test_keep_on_grouped_query_pools(q_tile):
    """(v) The attention kernel under ``keep`` on grouped-query pools whose K
    row holds more than the keys (tiled and one-query forms, 8 query heads a
    KV head) equals ``paged_attention``'s gather under the same mask."""
    rng = np.random.default_rng(11)
    n, H, Hkv, D, ps, pmax, topk = 16, 16, 2, 32, 16, 4, 12
    k_rows = jnp.asarray(rng.standard_normal((9, ps, Hkv * D + 128)), jnp.float32)
    v_rows = jnp.asarray(rng.standard_normal((9, ps, Hkv * D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((n, H, D)), jnp.float32)
    q_i = jnp.asarray(rng.standard_normal((n, 4, 16)), jnp.float32)
    w_i = jnp.asarray(rng.standard_normal((n, 4)), jnp.float32)
    table = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    row_of = jnp.repeat(jnp.arange(2, dtype=jnp.int32), 8)
    bounds = jnp.asarray(list(range(40, 48)) + [5, 6, 7, 8, -1, -1, -1, -1], jnp.int32)
    scores = sparse_index.index_scores(
        q_i, w_i, k_rows, table, row_of, bounds, q_tile=q_tile, interpret=True, lane0=Hkv * D,
    )
    tau, allow = sparse_index.select_tiled(
        scores, bounds.reshape(-1, q_tile), topk, interpret=True
    )
    got = ragged_paged_attention(
        q, k_rows, v_rows, table, row_of, bounds, num_kv_heads=Hkv, q_tile=q_tile,
        interpret=True, keep=(scores, tau.reshape(n), allow.reshape(n)),
    )
    flat = sparse_index.by_query(scores)
    mask = sparse_index.kept(flat, tau.reshape(n), allow.reshape(n))
    want = paged_attention(
        q[:, None], k_rows, v_rows, table[row_of], bounds[:, None], keep=mask[:, None],
        kv_heads=Hkv,
    )[:, 0]
    assert int(mask[0].sum()) == topk and bool(mask[8].all())  # a row under topk keeps all
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    # unselected, the wider K row reads as a plain one does
    plain = ragged_paged_attention(
        q, k_rows, v_rows, table, row_of, bounds, num_kv_heads=Hkv, q_tile=q_tile,
        interpret=True, q_block=q_tile if q_tile > 1 else None,
    )
    narrow = ragged_paged_attention(
        q, k_rows[..., : Hkv * D], v_rows, table, row_of, bounds, num_kv_heads=Hkv,
        q_tile=q_tile, interpret=True, q_block=q_tile if q_tile > 1 else None,
    )
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(narrow))


# ------------------------------------------------------------------ forwards
@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_forward_matches_reference(params, phase):
    """(i) ``forward`` (the gather): the prompt in one piece, longer than
    ``index_topk``, then six steps through the cache."""
    k, v = _pools()
    T = 37
    logits, k, v = forward(
        params, CFG, jnp.asarray(PROMPT[:T])[None], jnp.arange(T, dtype=jnp.int32)[None],
        TABLE, k, v,
    )
    if phase == "prefill":
        want = reference.log_probs(params, HF, PROMPT[:T], range(T))
        assert np.abs(_logp(logits[0]) - want).max() < LOGP_TOL
        return
    ids = PROMPT[:T]
    for t in range(T, T + 6):
        ids.append(PROMPT[t])
        logits, k, v = forward(
            params, CFG, jnp.asarray([[ids[-1]]]), jnp.asarray([[t]], jnp.int32), TABLE, k, v,
        )
        want = reference.log_probs(params, HF, ids, [t])
        assert np.abs(_logp(logits[0]) - want).max() < LOGP_TOL


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_chunked_prefill_then_decode(params, impl):
    """(ii) 40 tokens in chunks of 16, 16 and 8 through ``forward_ragged``,
    then five decode steps through ``forward`` (the kernels' one-query form
    with ``pallas``): logits against the reference at every position; the
    chunks' queries select among index keys earlier dispatches wrote."""
    k, v = _pools()
    got = []
    for a, b in ((0, 16), (16, 32), (32, 40)):
        n, pad = b - a, 16 - (b - a)
        logits, k, v = forward_ragged(
            params, CFG, jnp.asarray(PROMPT[a:b] + [0] * pad),
            jnp.asarray(list(range(a, b)) + [-1] * pad, jnp.int32),
            jnp.zeros((16,), jnp.int32), TABLE, k, v, jnp.arange(16), **_impl(impl),
        )
        got.append(_logp(logits)[:n])
    want = reference.log_probs(params, HF, PROMPT[:40], range(40))
    assert np.abs(np.concatenate(got) - want).max() < LOGP_TOL
    ids = PROMPT[:40]
    for t in range(40, 45):
        ids.append(PROMPT[t])
        logits, k, v = forward(
            params, CFG, jnp.asarray([[ids[-1]]]), jnp.asarray([[t]], jnp.int32), TABLE, k, v,
            **_impl(impl),
        )
        want = reference.log_probs(params, HF, ids, [t])
        assert np.abs(_logp(logits[0]) - want).max() < LOGP_TOL


def _mixed_stream():
    """Row 0: a chunk of 24 queries at positions 16..39 over a context an
    earlier dispatch wrote; row 1: a whole prompt of 5 (a tile, padded);
    then a packed tail of one decode row (row 2 at position 20)."""
    tokens = PROMPT[16:40] + PROMPT[40:45] + [0] * 3 + [PROMPT[20]] + [0] * 7
    positions = list(range(16, 40)) + list(range(5)) + [-1] * 3 + [20] + [-1] * 7
    row_of = [0] * 24 + [1] * 8 + [2] * 8
    table = jnp.asarray([[1, 2], [3, 4], [5, 6]], jnp.int32)
    return (jnp.asarray(tokens, jnp.int32), jnp.asarray(positions, jnp.int32),
            jnp.asarray(row_of, jnp.int32), table)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_mixed_dispatch_gives_one_prefills_sets_and_logits(params, impl):
    """(ii) A mixed dispatch (a later chunk of one row, a whole short prompt,
    a decode row in the packed tail) gives the logits the reference's full
    forward gives and hands out the sets it kept, which are the reference's
    ``lax.top_k``: ``min(t + 1, 16)`` positions a row."""
    k, v = _pools()
    first = jnp.asarray(PROMPT[:16] + PROMPT[:20] + [0] * 4, jnp.int32)
    pos = jnp.asarray(list(range(16)) + list(range(20)) + [-1] * 4, jnp.int32)
    rows = jnp.asarray([0] * 16 + [2] * 24, jnp.int32)
    table = _mixed_stream()[3]
    _, k, v = forward_ragged(
        params, CFG, first, pos, rows, table, k, v, jnp.arange(1), **_impl(impl),
    )
    tokens, positions, row_of, table = _mixed_stream()
    at = jnp.asarray(list(range(24)) + list(range(24, 29)) + [32], jnp.int32)
    logits, k, v, kept = forward_ragged(
        params, CFG, tokens, positions, row_of, table, k, v, at, kept_of=at,
        q_tile=8, packed_from=32 if impl == "pallas" else None, **_impl(impl),
    )
    want = np.concatenate([
        reference.log_probs(params, HF, PROMPT[:40], range(16, 40)),
        reference.log_probs(params, HF, PROMPT[40:45], range(5)),
        reference.log_probs(params, HF, PROMPT[:20] + [PROMPT[20]], [20]),
    ])
    assert np.abs(_logp(logits) - want).max() < LOGP_TOL
    kept = np.asarray(kept)
    assert kept.shape[:2] == (3, 30)
    seen = np.asarray(positions)[np.asarray(at)] + 1
    causal = np.arange(kept.shape[-1])[None] < seen[:, None]
    np.testing.assert_array_equal((kept & causal).sum(-1), np.minimum(seen, TOPK)[None].repeat(3, 0))
    # layer 0's sets against the reference's own (its hidden state is the embedding)
    x = np.asarray(params["embed"])[np.asarray(PROMPT[:40])]
    own = reference.attn_rows_and_head(params, HF, 0, x, range(16, 40))
    np.testing.assert_array_equal((kept[0, :24] & causal[:24])[:, :40], own["keep"])


def test_the_cache_holds_what_the_text_says(params):
    """(iii) After a prefill the K pool's rows are ``[K after norm and rotary
    | the index key after LayerNorm and rotary | zeros]`` and the V pool's
    the values, at every layer the reference's."""
    k, v = _pools()
    T = 29
    _, k, v = forward_ragged(
        params, CFG, jnp.asarray(PROMPT[:T]), jnp.arange(T, dtype=jnp.int32),
        jnp.zeros((T,), jnp.int32), TABLE, k, v, jnp.arange(T),
    )
    entering, _ = reference.hidden_states(params, HF, PROMPT[:T], [0, 1, 2])
    for layer in range(3):
        keys, values, index = reference.pool_rows(params, HF, layer, entering[layer])
        rows = np.asarray(k[layer, 1:]).reshape(-1, 160)
        assert np.abs(rows[:T, :KL] - keys).max() < 1e-4
        assert np.abs(rows[:T, KL : KL + 16] - index).max() < 1e-4
        assert np.abs(rows[:T, KL + 16 :]).max() == 0.0  # the key's padding lanes
        assert np.abs(rows[T:]).max() == 0.0  # nothing past the prompt
        assert np.abs(np.asarray(v[layer, 1:]).reshape(-1, 32)[:T] - values).max() < 1e-4


def test_the_programs_count_the_experts_reached(params):
    """A model that holds every expert and asks (``count_expert_reads``):
    the mixed program returns, last, the live rows' picks and the experts
    they reached, summed over the layers: the reference's router's, the
    stream's padding rows counted nowhere."""
    assert CFG.counts_picks and not CFG.num_experts_held
    assert not PRESETS["tiny-moe"].counts_picks  # the older models' programs stay
    k, v = _pools()
    T, pad = 29, 3
    *_, tally = forward_ragged(
        params, CFG, jnp.asarray(PROMPT[: T + pad]),
        jnp.where(jnp.arange(T + pad) < T, jnp.arange(T + pad), -1).astype(jnp.int32),
        jnp.zeros((T + pad,), jnp.int32), TABLE, k, v, jnp.arange(T), count_picks=True,
    )
    _, before_ffn = reference.hidden_states(params, HF, PROMPT[:T], [0, 1, 2])
    reached = 0
    for layer in range(3):
        h = reference._rms_norm(jnp.asarray(before_ffn[layer]), params["layers"]["mlp_norm"][layer], 1e-6)
        weight, _ = reference._route(h @ params["layers"]["router"][layer], top_k=2, norm=True)
        reached += int((np.asarray(weight) > 0).any(axis=0).sum())
    assert [int(n) for n in tally] == [T * 2 * 3, reached]
    assert 3 * 2 <= reached <= 3 * CFG.num_experts


@pytest.mark.parametrize("preset", ["tiny", "tiny-moe", "tiny-qwen3"])
def test_other_models_programs_hold_no_selector(preset):
    cfg = PRESETS[preset]
    p = jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    k, v = jax.eval_shape(lambda: llama.init_kv_cache(cfg, 9, 8, dtype=jnp.float32))
    n = 16
    args = (
        jax.ShapeDtypeStruct((n,), jnp.int32), jax.ShapeDtypeStruct((n,), jnp.int32),
        jax.ShapeDtypeStruct((n,), jnp.int32), jax.ShapeDtypeStruct((2, 4), jnp.int32),
    )
    text = jax.jit(
        lambda p, t, pos, row, tab, k, v: llama.forward_ragged(
            p, cfg, t, pos, row, tab, k, v, jnp.arange(2), attn_impl="pallas", interpret=True)
    ).lower(p, *args, k, v).as_text(debug_info=True)
    assert "dsa_" not in text and "idx_" not in text
    assert k.shape[-1] == cfg.num_kv_heads * cfg.head_dim_


def test_the_scopes_are_in_the_program(params):
    k, v = _pools()
    n = 16
    text = jax.jit(
        lambda t, pos, row: llama.forward_ragged(
            params, CFG, t, pos, row, TABLE, k, v, jnp.arange(2), attn_impl="pallas",
            interpret=True)
    ).lower(*(jax.ShapeDtypeStruct((n,), jnp.int32),) * 3).as_text(debug_info=True)
    for scope in ("dsa_index", "dsa_select", "attend", "dsa_index_scores", "kv_write"):
        assert scope in text, scope


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
        ids.append(int(reference.log_probs(
            params, HF, ids, [len(ids) - 1], pad_to=128)[0].argmax()))
    return ids[len(prompt):]


def _counter(name, **labels):
    from dynamo_exp_tpu.telemetry import get_telemetry

    total = 0.0
    for metric in get_telemetry().registry.collect():
        for s in metric.samples:
            if s.name == name and all(s.labels.get(k) == v for k, v in labels.items()):
                total += s.value
    return total


_COUNTERS = (
    "dynamo_dsa_index_pairs_total", "dynamo_dsa_selected_pairs_total",
    "dynamo_dsa_query_tokens_total", "dynamo_mla_query_tokens_total",
    "dynamo_moe_picks_total", "dynamo_moe_held_expert_reads_total",
)


async def test_engine_serves_windows_and_chunks(engine):
    """Through the scheduler: a 40-token prompt in chunks of 16 (mixed
    program), then 20 tokens through 8-step windows, equal to the
    reference's greedy continuation; the counters say what was scored, what
    was selected and what a query MAY see; no latent series moves."""
    assert engine.k_cache.shape == (3, 64, PS, 160) and engine.v_cache.shape == (3, 64, PS, 32)
    before = {k: _counter(k) for k in _COUNTERS}
    attn = {
        k: _counter(k, kind="full") for k in
        ("dynamo_attn_query_tokens_total", "dynamo_attn_key_positions_total")
    }
    prompt = PROMPT[:40]
    got = await _run(engine, prompt, 20)
    assert got == _greedy_reference(engine.params, prompt, 20)
    took = {k: _counter(k) - before[k] for k in _COUNTERS}
    fed = 40 + 19
    assert took["dynamo_dsa_query_tokens_total"] >= fed
    assert took["dynamo_dsa_index_pairs_total"] >= fed * (fed + 1) // 2
    assert took["dynamo_dsa_selected_pairs_total"] >= TOPK * (TOPK + 1) // 2 + (fed - TOPK) * TOPK
    assert took["dynamo_dsa_selected_pairs_total"] < took["dynamo_dsa_index_pairs_total"]
    assert took["dynamo_mla_query_tokens_total"] == 0
    # the key positions a query MAY see stay t + 1, over the three layers
    queries = _counter("dynamo_attn_query_tokens_total", kind="full") - attn[
        "dynamo_attn_query_tokens_total"]
    keys = _counter("dynamo_attn_key_positions_total", kind="full") - attn[
        "dynamo_attn_key_positions_total"]
    assert queries == 3 * took["dynamo_dsa_query_tokens_total"]
    assert keys == 3 * took["dynamo_dsa_index_pairs_total"]
    # every expert is here and the programs count all the same (counts_picks):
    # each fed token's top 2 in three layers, none elsewhere, and the experts
    # those picks reached, at most 8 a layer and dispatch (a window: a step)
    assert took["dynamo_moe_picks_total"] == fed * 2 * 3
    assert _counter("dynamo_moe_picks_total", where="elsewhere") == 0
    assert fed * 2 * 3 // 8 <= took["dynamo_moe_held_expert_reads_total"] <= fed * 2 * 3
    assert _counter("dynamo_moe_held_expert_reads_total", program="window") > 0


def test_pool_info_says_what_a_token_holds(engine):
    from dynamo_exp_tpu.telemetry import get_telemetry

    (labels,) = [
        s.labels for m in get_telemetry().registry.collect() for s in m.samples
        if s.name == "dynamo_kv_pool_info"
    ]
    assert labels["pools"] == "2" and labels["width"] == "160" and labels["index_width"] == "128"
    assert labels["token_bytes"] == str(3 * (160 + 32) * 4)  # layers x both rows x float32


@pytest.mark.parametrize("asking", ["again", "longer"])
async def test_a_prefix_hit_carries_index_keys(engine, asking):
    """(vii) A prompt whose head is in cached pages is served from them: K, V
    AND index keys another request wrote (one row of the K pool), selected
    among by the new request's queries."""
    base = PROMPT[5:38]  # 33 tokens: four whole pages
    await _run(engine, base, 4)
    before = engine.kv.prefix_hits["shared"]
    prompt = base if asking == "again" else base + PROMPT[:7]
    got = await _run(engine, prompt, 12)
    assert got == _greedy_reference(engine.params, prompt, 12)
    assert engine.kv.prefix_hits["shared"] - before == 4


async def test_engine_through_the_kernels():
    """The same path with both kernels (interpreted) in both programs."""
    eng = _engine(
        attention_impl="pallas", pallas_interpret=True, page_size=32, max_model_len=128,
        num_pages=16,
    )
    eng.start()
    merged = _counter("dynamo_attn_query_slots_total", **{"pass": "merged"})
    try:
        prompt = PROMPT[3:40]
        got = await _run(eng, prompt, 10)
        assert got == _greedy_reference(eng.params, prompt, 10)
        assert eng._attn_impl == "pallas"
        assert _counter("dynamo_dsa_index_slots_total") > 0
        # a long prompt's chunks lie in blocks of one row: the selected call
        # reads them a block a pass (``selected_query_block``), as the
        # scores kernel does, and the engine's counter asks the same rule
        assert _counter("dynamo_attn_query_slots_total", **{"pass": "merged"}) > merged
    finally:
        eng.stop()


def test_the_attention_counter_asks_the_selected_calls_rule(engine):
    """``_count_attn_passes`` and the program ask ``ragged_query_block``: a
    grouped-query model under a selection counts its tiled slots at the
    selected call's block (64 at these widths too), a span's middle merged
    and a block of two rows by tile."""
    import dataclasses
    import types

    from dynamo_exp_tpu.models.llama import ragged_query_block
    from dynamo_exp_tpu.ops.ragged_attention import merged_slot_counts

    eng = types.SimpleNamespace(
        cfg=dataclasses.replace(engine.cfg, attention_impl="pallas"), _attn_impl="pallas",
        _count_one_query_rows=lambda positions: None,
    )
    count = types.MethodType(TPUEngine._count_attn_passes, eng)
    tile = eng.cfg.ragged_q_tile
    block = ragged_query_block(CFG, tile, CFG.num_kv_heads)
    assert block == 64 and tile == 8
    nb = 512
    tiled = nb - eng.cfg.ragged_packed_slots(nb)
    # row 0 fills four blocks and the head of a fifth, which row 1 shares
    row_of = np.where(np.arange(nb) < 300, 0, 1).astype(np.int32)
    positions = np.where(np.arange(nb) < tiled, np.arange(nb) % 300 + 7, -1).astype(np.int32)
    name = "dynamo_attn_query_slots_total"
    before = {k: _counter(name, **{"pass": k}) for k in ("merged", "tile")}
    count(nb, row_of, positions)
    took = {k: _counter(name, **{"pass": k}) - before[k] for k in before}
    assert (took["merged"], took["tile"]) == merged_slot_counts(
        row_of[:tiled], positions[:tiled], tile, block
    )
    assert took["merged"] == 256 + (tiled - 320) and took["tile"] == 64


async def test_preempt_and_resume():
    eng = _engine(num_pages=14)
    eng.start()
    try:
        p1, p2, n = PROMPT[:19], PROMPT[20:39], 44
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
def test_tiers_refuse_a_row_of_three_things(tier, tmp_path):
    """(viii)"""
    kw = {
        "host_cache_pages": {"host_cache_pages": 8}, "spec_mode": {"spec_mode": "ngram"},
        "kv_store_dir": {"host_cache_pages": 8, "kv_store_dir": str(tmp_path)},
        "sp": {"sp": 2}, "tp": {"tp": 2},
    }.get(tier, {})
    cfg = EngineConfig(model=CFG, max_decode_slots=2, page_size=PS, num_pages=16, **kw)
    with pytest.raises(ValueError, match="three things a token.*16 of index key in 128 lanes"):
        if kw:
            TPUEngine(cfg)
        else:
            refuse_latent_tiers(cfg, tier)


def test_an_image_part_is_refused_with_a_message():
    text = [{"role": "user", "content": [{"type": "text", "text": "hello"}]}]
    refuse_unserved_tower("KeyeVL2", text)
    image = [{"role": "user", "content": [
        {"type": "text", "text": "what is this"},
        {"type": "image_url", "image_url": {"url": "data:image/png;base64,AAAA"}},
    ]}]
    refuse_unserved_tower("llama", image)
    with pytest.raises(ValueError, match="language model alone.*image_url.*SigLIP"):
        refuse_unserved_tower("KeyeVL2", image)


def test_the_preprocessor_refuses_an_image_request(tmp_path):
    from dynamo_exp_tpu.model_card import ModelDeploymentCard
    from dynamo_exp_tpu.models.synth import write_model_dir
    from dynamo_exp_tpu.preprocessor.preprocessor import InvalidRequestError, OpenAIPreprocessor
    from dynamo_exp_tpu.protocols.openai import ChatCompletionRequest

    path = write_model_dir(str(tmp_path), dataclasses.replace(CFG, vocab_size=512))
    pre = OpenAIPreprocessor(ModelDeploymentCard.from_local_path(path))
    ok = ChatCompletionRequest.model_validate({
        "model": "m", "messages": [{"role": "user", "content": "hello"}],
    })
    assert pre.preprocess_chat(ok).token_ids
    bad = ChatCompletionRequest.model_validate({"model": "m", "messages": [{
        "role": "user", "content": [{"type": "image_url", "image_url": {"url": "x"}}],
    }]})
    with pytest.raises(InvalidRequestError, match="language model alone"):
        pre.preprocess_chat(bad)


def test_manifest_names_the_selector_and_the_row():
    from dynamo_exp_tpu.aot.lattice import build_manifest

    def manifest(model):
        cfg = EngineConfig(model=model, max_decode_slots=4, page_size=16, num_pages=64)
        return build_manifest(cfg, "pallas", {"tp": 1}, "x")

    keye = manifest(TINY_KEYE)
    assert keye.engine["attention_form"] == "heads-index-key-at-32-selected-top-16"
    assert keye.engine["kv_pool_widths"] == [160, 32]
    assert keye.model["mrope_section"] == [2, 3, 3]
    assert manifest(dataclasses.replace(TINY_KEYE, index_topk=32)).hash() != keye.hash()
    assert keye.model["count_expert_reads"] is True
    # a model that sets neither keeps the manifest it had before the fields were
    assert not {"mrope_section", "count_expert_reads"} & set(manifest(PRESETS["tiny-moe"]).model)
    assert manifest(PRESETS["tiny-moe"]).engine["attention_form"] == "heads"


# ------------------------------------------------------------------ loader
def test_checkpoint_names_round_trip(tmp_path, params):
    """(ix) A checkpoint under the family's tensor names (Qwen3-MoE's plus
    the indexer's, and a ``visual.*`` tensor that is skipped) loads into the
    stacks: exactly the arrays it was written from."""
    from safetensors.numpy import save_file

    from dynamo_exp_tpu.models.loader import load_params
    from dynamo_exp_tpu.models.synth import write_config

    own = {k: np.asarray(v) for k, v in params["layers"].items()}
    out = {
        "model.embed_tokens.weight": np.asarray(params["embed"]),
        "model.norm.weight": np.asarray(params["final_norm"]),
        "lm_head.weight": np.asarray(params["lm_head"]).T,
        "visual.patch_embed.proj.weight": np.zeros((4, 4), np.float32),
    }
    for i in range(CFG.num_layers):
        p = f"model.layers.{i}."
        out[p + "input_layernorm.weight"] = own["attn_norm"][i]
        out[p + "post_attention_layernorm.weight"] = own["mlp_norm"][i]
        for key, name in (
            ("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"), ("wo", "o_proj"),
            ("idx_wq", "indexer.wq"), ("idx_wk", "indexer.wk"),
            ("idx_wp", "indexer.weights_proj"),
        ):
            out[f"{p}self_attn.{name}.weight"] = own[key][i].T
        out[p + "self_attn.q_norm.weight"] = own["q_norm"][i]
        out[p + "self_attn.k_norm.weight"] = own["k_norm"][i]
        out[p + "self_attn.indexer.k_norm.weight"] = own["idx_k_norm"][i]
        out[p + "self_attn.indexer.k_norm.bias"] = own["idx_k_bias"][i]
        out[p + "mlp.gate.weight"] = own["router"][i].T
        for key, name in (("gate", "gate_proj"), ("up", "up_proj"), ("down", "down_proj")):
            for e in range(CFG.num_experts):
                out[f"{p}mlp.experts.{e}.{name}.weight"] = own[f"w_{key}"][i, e].T
    save_file({k: np.ascontiguousarray(v) for k, v in out.items()}, str(tmp_path / "model.safetensors"))
    write_config(str(tmp_path), CFG)
    loaded, cfg = load_params(str(tmp_path))
    assert cfg == CFG
    assert set(loaded["layers"]) == set(params["layers"])
    for k, v in params["layers"].items():
        np.testing.assert_array_equal(loaded["layers"][k], np.asarray(v), err_msg=k)
    np.testing.assert_array_equal(loaded["lm_head"], np.asarray(params["lm_head"]))


# ------------------------------------------------------------------ faults
FAULT_T = 40


@pytest.fixture(scope="module")
def sound_logp(params):
    k, v = _pools()
    logits, *_ = forward_ragged(
        params, CFG, jnp.asarray(PROMPT[:FAULT_T]), jnp.arange(FAULT_T, dtype=jnp.int32),
        jnp.zeros((FAULT_T,), jnp.int32), TABLE, k, v, jnp.arange(FAULT_T),
    )
    return _logp(logits)


def _faulty_constants(monkeypatch, **fault):
    plain = reference.block_constants
    monkeypatch.setattr(reference, "block_constants", lambda hf: {**plain(hf), **fault})


@pytest.mark.parametrize("fault", [
    "dense", "top-15", "no relu", "sections permuted", "index keys before rope",
    "no layernorm", "no q/k norms",
])
def test_a_fault_shows(params, sound_logp, monkeypatch, fault):
    """Each thing the text says and a tolerance could hide: the reference
    computing WITHOUT it differs from the engine by far more than
    ``LOGP_TOL`` (and the sound reference does not)."""
    T, got = FAULT_T, sound_logp
    sound = reference.log_probs(params, HF, PROMPT[:T], range(T))
    assert np.abs(got - sound).max() < LOGP_TOL
    try:
        jax.clear_caches()
        if fault == "dense":
            _faulty_constants(monkeypatch, topk=10**6)
        elif fault == "top-15":
            _faulty_constants(monkeypatch, topk=TOPK - 1)
        elif fault == "no relu":
            _faulty_constants(monkeypatch, relu=False)
        elif fault == "sections permuted":
            _faulty_constants(monkeypatch, sections=(3, 3, 2))
        elif fault == "index keys before rope":
            _faulty_constants(monkeypatch, index_rope=False)
        elif fault == "no layernorm":
            monkeypatch.setattr(reference, "_layer_norm", lambda x, w, b, eps=0: x)
        elif fault == "no q/k norms":
            plain = reference._rms_norm
            monkeypatch.setattr(
                reference, "_rms_norm",
                lambda x, w, eps: x if x.ndim == 3 else plain(x, w, eps),
            )
        positions = None
        if fault == "sections permuted":
            # permuted sections show only where the components differ
            positions = np.stack([np.arange(T), np.arange(T) // 2, np.arange(T) % 7])
            k, v = _pools()
            logits, *_ = llama.forward(
                params, CFG, jnp.asarray(PROMPT[:T])[None], jnp.arange(T, dtype=jnp.int32)[None],
                TABLE, k, v, rope_positions=jnp.asarray(positions, jnp.int32)[:, None],
            )
            got = _logp(logits[0])
        wrong = reference.log_probs(params, HF, PROMPT[:T], range(T), positions=positions)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert np.abs(got - wrong).max() > 20 * LOGP_TOL, fault


async def test_held_bytes_over_context_tokens_is_a_tokens_row(engine):
    """``dynamo_kv_pool_held_bytes{pool="full"}`` over
    ``dynamo_kv_context_tokens`` (what ``kv_resident_bytes_per_token`` reads)
    is published for this model: a token's K row with its index key and its V
    row over the layers, with a page's rounding."""
    task = asyncio.ensure_future(_run(engine, PROMPT[:33], 40))
    seen = []
    for _ in range(200):
        await asyncio.sleep(0.02)
        held, tokens = _counter("dynamo_kv_pool_held_bytes", pool="full"), _counter(
            "dynamo_kv_context_tokens")
        if held and tokens:
            seen.append(held / tokens)
        if task.done():
            break
    await task
    a_token = 3 * (160 + 32) * 4
    # A token's row, rounded up by pages of 8 tokens and a window's pages
    # provisioned ahead. The median: the two gauges are read one after the
    # other while the loop publishes them, so a lone sample may pair an old
    # numerator with a new denominator.
    middle = sorted(seen)[len(seen) // 2] if seen else 0
    assert a_token <= middle <= a_token * 1.5, sorted(seen)[:5]
