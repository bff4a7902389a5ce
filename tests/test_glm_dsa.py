"""glm_moe_dsa (GLM-5) on the normal path: multi-head latent attention read
through a learned selection (an indexer a layer whose keys lie in the second
page pool, the ``index_topk`` context tokens of largest index score a query
and layer), a share of the experts held beside a shared expert, chosen
through a selection bias, two norms a layer. Tiny sizes (``index_topk`` 16:
every prompt here is longer), seeded weights, float32, so the engine and the
plain reference (``benchmark/references/glm_moe_dsa.py``: interleaved rotary
pairs, ``lax.top_k``, the expanded form over the selected positions) differ
by float32 rounding alone, whichever path computes the scores and the mask:
the gather (``index_scores_ref``) or the two kernels, interpreted.

TOLERANCES. Both sides compute in float32: log-probabilities agree to ~1e-5
where the two selections agree, so ``LOGP_TOL`` 1e-3 stands a hundred times
above the rounding; a selection that differs in ONE position of one row
moves them by more (the faults below say how far each moves them).
"""

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
from dynamo_exp_tpu.models.config import PRESETS, TINY_GLM_DSA, TINY_PANGU
from dynamo_exp_tpu.ops import moe, sparse_index

# Jitted: a shape and set of options compiles once a file (tests/programs.py).
try:
    from .programs import forward, forward_ragged, init_params
except ImportError:  # as a top-level module: benchmark/tests/test_glm5_long_part.py
    from programs import forward, forward_ragged, init_params

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmark"))
from references import glm_moe_dsa as reference  # noqa: E402

CFG = dataclasses.replace(TINY_GLM_DSA, dtype="float32")
HF = {
    "model_type": "glm_moe_dsa", "vocab_size": CFG.vocab_size,
    "hidden_size": CFG.hidden_size, "intermediate_size": CFG.intermediate_size,
    "num_hidden_layers": CFG.num_layers, "num_attention_heads": CFG.num_heads,
    "num_key_value_heads": CFG.num_heads, "max_position_embeddings": 512,
    "rms_norm_eps": CFG.rms_norm_eps, "rope_theta": CFG.rope_theta,
    "tie_word_embeddings": False, "torch_dtype": "float32", "hidden_act": "silu",
    "attention_bias": False, "norm_topk_prob": True,
    "q_lora_rank": CFG.q_lora_rank, "kv_lora_rank": CFG.kv_lora_rank,
    "qk_nope_head_dim": CFG.qk_nope_head_dim, "qk_rope_head_dim": CFG.qk_rope_head_dim,
    "v_head_dim": CFG.v_head_dim, "index_n_heads": CFG.index_n_heads,
    "index_head_dim": CFG.index_head_dim, "index_topk": CFG.index_topk,
    "first_k_dense_replace": CFG.num_dense_layers,
    "n_routed_experts": CFG.num_experts_held, "n_routed_experts_global": CFG.num_experts,
    "first_routed_expert": CFG.first_expert_held, "n_shared_experts": 1,
    "num_experts_per_tok": CFG.num_experts_per_tok,
    "moe_intermediate_size": CFG.moe_intermediate_size,
    "routed_scaling_factor": CFG.routed_scaling_factor, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "num_nextn_predict_layers": 0,
}
PS = 8
LOGP_TOL = 1e-3
SEED = 5
PROMPT = [int(t) for t in np.random.default_rng(0).integers(2, 256, 64)]
# The forwards called outright read pools of LARGE pages on narrow tables (two
# pages of 32 a row: every context here is under 64 tokens): a chunk of the
# interpreted kernels is then 2 page copies to lower, not 16
# (tests/ragged_cases.py "SHAPES"). The engines keep pages of ``PS``.
FPS = 32
TABLE = jnp.arange(2, dtype=jnp.int32)[None] + 1
TOPK = CFG.index_topk


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
    assert PRESETS["tiny-glm-dsa"] is TINY_GLM_DSA
    assert CFG.op_kinds == ("attn",) * 3 and CFG.ff_kinds == ("dense", "routed", "routed")
    assert CFG.is_latent and CFG.has_indexer and not CFG.has_seq_state
    assert not CFG.post_norms and CFG.use_expert_bias
    # 40 + 16 values in 128 lanes; the index key's 32 in 128 of the second pool
    assert (CFG.latent_width, CFG.latent_lanes, CFG.index_lanes) == (56, 128, 128)
    assert CFG.kv_pool_widths == (128, 128)
    assert (CFG.experts_held, CFG.first_expert_held, CFG.num_experts) == (4, 4, 8)
    assert not TINY_PANGU.has_indexer and TINY_PANGU.kv_pool_widths == (128, 0)


def test_the_published_widths_give_the_two_pools_of_the_issue():
    published = dataclasses.replace(
        CFG, kv_lora_rank=512, qk_rope_head_dim=64, index_head_dim=128, index_topk=2048,
    )
    assert published.kv_pool_widths == (640, 128)


@pytest.mark.parametrize("key,value,why", [
    ("num_nextn_predict_layers", 1, "multi-token-prediction"),
    ("rope_scaling", {"type": "yarn", "factor": 4}, "rope_scaling"),
    ("n_group", 8, "group-limited"),
    ("topk_group", 4, "group-limited"),
    ("attention_bias", True, "attention_bias"),
    ("hidden_act", "gelu", "hidden_act"),
    ("scoring_func", "softmax", "scoring_func"),
    ("kv_lora_rank", 0, "kv_lora_rank"),
    ("index_topk", 0, "index_topk"),
    ("index_n_heads", 0, "index_n_heads"),
    ("index_head_dim", 8, "index_head_dim >= qk_rope_head_dim"),
    ("qk_rope_head_dim", 15, "even"),
    ("first_routed_expert", 6, "outside the router's range"),
    ("num_experts_per_tok", 64, "exceeds"),
])
def test_reader_refuses_what_it_does_not_compute(key, value, why):
    with pytest.raises(ValueError, match=why):
        ModelConfig.from_hf_config({**HF, key: value})


def test_parameters_by_kind(params):
    layers = params["layers"]
    assert not {"wq", "wk", "wv", "post_attn_norm", "post_ffn_norm"} & set(layers)
    assert layers["router"].shape == (2, 64, 8) and layers["router_bias"].shape == (2, 8)
    assert layers["w_gate"].shape == (2, 4, 64, 32)  # 4 of the 8 are held
    assert layers["idx_wq"].shape == (3, 24, 4 * 32) and layers["idx_wk"].shape == (3, 64, 32)
    assert layers["idx_k_norm"].shape == layers["idx_k_bias"].shape == (3, 32)
    assert layers["idx_wp"].shape == (3, 64, 4)
    assert layers["w_ukv"].shape == (3, 40, 4 * (16 + 24)) and layers["wo"].shape == (3, 4 * 24, 64)
    specs = llama.param_shardings(CFG)
    assert jax.tree.structure(specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)) \
        == jax.tree.structure(params)


def test_two_pools_of_two_meanings():
    pool, keys = _pools()
    assert pool.shape == (3, 9, FPS, 128) and keys.shape == (3, 9, FPS, 128)


# ------------------------------------------------------- the selection's ops
def _scores_case(seed=0, n_rows=3, hi=2, di=32, lanes=128, pages=3, ps=16):
    rng = np.random.default_rng(seed)
    pool = np.zeros((n_rows * pages + 1, ps, lanes), np.float32)
    pool[..., :di] = rng.standard_normal((*pool.shape[:2], di))
    table = (np.arange(n_rows * pages).reshape(n_rows, pages) + 1).astype(np.int32)
    spans = [(16, 20), (8, 0), (3, 41)]  # (queries, first position) a row
    row_of, bounds = [], []
    for r, (n, first) in enumerate(spans):
        pad = (-n) % 8
        row_of += [r] * (n + pad)
        bounds += list(range(first, first + n)) + [-1] * pad
    n = len(row_of)
    q = rng.standard_normal((n, hi, di)).astype(np.float32)
    w = rng.standard_normal((n, hi)).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (q, w, pool, table, np.asarray(row_of, np.int32),
                                           np.asarray(bounds, np.int32)))


@pytest.mark.parametrize("q_tile", [8, 1])
def test_scores_kernel_equals_the_gather(q_tile):
    q, w, pool, table, row_of, bounds = _scores_case()
    want = sparse_index.index_scores_ref(
        q[:, None], w[:, None], pool, table[row_of], bounds[:, None]
    )[:, 0]
    got = sparse_index.by_query(sparse_index.index_scores(
        q, w, pool, table, row_of, bounds, q_tile=q_tile, interpret=True
    ))
    seen = np.asarray(want) > -np.inf
    assert (np.asarray(got)[:, : want.shape[1]] > -np.inf).tolist() == seen.tolist()
    assert np.abs(np.where(seen, np.asarray(got)[:, : want.shape[1]] - np.asarray(want), 0)).max() < 1e-4
    # by the definition, a plain loop over one query
    i = 5
    ctx = np.asarray(pool)[np.asarray(table)[0]].reshape(-1, pool.shape[-1])[: int(bounds[i]) + 1, :32]
    plain = (np.maximum(np.asarray(q)[i] @ ctx.T, 0) * np.asarray(w)[i][:, None]).sum(0)
    assert np.abs(np.asarray(want)[i, : len(plain)] - plain).max() < 1e-4


@pytest.mark.parametrize("k", [1, 7, 64, 200])
def test_kth_largest_is_exact(k):
    rng = np.random.default_rng(k)
    x = rng.standard_normal((9, 200)).astype(np.float32)
    x[0, :50] = -np.inf
    x[1] = np.round(x[1])  # many ties
    x[2, ::2] = 0.0
    x[2, 1::4] = -0.0
    got = np.asarray(sparse_index.kth_largest(jnp.asarray(x), k))
    want = np.sort(x, axis=1)[:, ::-1][:, k - 1]
    assert np.array_equal(got, want + 0.0)
    # the kernel, over tiles of 1 and of 3 queries whose live chunks differ:
    # a query that sees at least k positions reads the same value and count
    above = (x > want[:, None]).sum(axis=1)
    for qt in (1, 3):
        tiled = np.full((9 // qt, 5, qt, 50), -np.inf, np.float32)
        tiled[:, :4] = x.reshape(9 // qt, qt, 4, 50).swapaxes(1, 2)
        bounds = np.full((9 // qt, qt), 199, np.int32)
        got, n = sparse_index.kth_largest_tiled(
            jnp.asarray(tiled), jnp.asarray(bounds), k, interpret=True
        )
        assert np.array_equal(np.asarray(got).reshape(9), want + 0.0)
        assert np.asarray(n).reshape(9).tolist() == above.tolist()


def test_select_keeps_everything_up_to_k_positions_and_breaks_ties_by_position():
    scores = np.random.default_rng(1).standard_normal((4, 40)).astype(np.float32)
    scores[3] = np.round(scores[3]) + 0.0  # a handful of values: ties at the threshold
    bounds = jnp.asarray([15, 16, 39, 39], jnp.int32)
    seen = jnp.arange(40)[None] <= bounds[:, None]
    scores = jnp.where(seen, jnp.asarray(scores), -jnp.inf)
    tau, allow = sparse_index.select(scores, bounds, 16)
    assert float(tau[0]) == -np.inf and float(allow[0]) == sparse_index.NO_LIMIT  # 16 seen: all
    kept = np.asarray(sparse_index.kept(scores, tau, allow)) & np.asarray(seen)
    assert kept.sum(axis=1).tolist() == [16, 16, 16, 16]  # 17 seen: one dropped
    _, at = jax.lax.top_k(scores[3], 16)  # ties by the lower position
    assert sorted(np.flatnonzero(kept[3]).tolist()) == sorted(np.asarray(at).tolist())
    assert (np.asarray(scores)[3] == float(tau[3])).sum() > float(allow[3])  # a tie was cut


# ------------------------------------------- the model against the reference
def _two_row_stream():
    """A 40-token chunk (five tiles: the expanded form at the tiny preset's
    rule of 8 queries) beside a row of 5 tokens (under it: absorbed), each
    on its own two pages: (tokens, positions, row_of, table)."""
    tokens = PROMPT[:40] + PROMPT[40:45] + [0] * 3
    positions = list(range(40)) + list(range(5)) + [-1] * 3
    row_of = [0] * 40 + [1] * 8
    table = jnp.arange(4, dtype=jnp.int32).reshape(2, 2) + 1
    return jnp.asarray(tokens), jnp.asarray(positions, jnp.int32), jnp.asarray(row_of, jnp.int32), table


@pytest.mark.parametrize("phase", ["prefill", "decode", "chunk_in_the_expanded_form"])
def test_forward_matches_reference(params, phase):
    """``forward`` (the gather): the prompt in one piece, longer than
    ``index_topk``, then six steps through both caches; and
    ``forward_ragged`` through the kernels, where a chunk of 40 queries
    takes the expanded form under its selection beside an absorbed row."""
    k, v = _pools()
    if phase == "chunk_in_the_expanded_form":
        assert llama.latent_form_queries(CFG, 8, FPS, jnp.float32, interpret=True) == 8
        tokens, positions, row_of, table = _two_row_stream()
        logits, *_ = forward_ragged(
            params, CFG, tokens, positions, row_of, table, k, v, jnp.arange(45),
            q_tile=8, **_impl("pallas"),
        )
        want = np.concatenate([
            reference.log_probs(params, HF, PROMPT[:40], range(40)),
            reference.log_probs(params, HF, PROMPT[40:45], range(5)),
        ])
        assert np.abs(_logp(logits) - want).max() < LOGP_TOL
        return
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
def test_chunked_prefill_then_decode_through_both_pools(params, impl):
    """40 tokens in chunks of 16, 16 and 8 through ``forward_ragged``, then
    five decode steps through ``forward`` (the kernel's one-query form with
    ``pallas``): logits against the reference at every position; the chunks'
    queries select among index keys earlier dispatches wrote."""
    k, v = _pools()
    got = []
    for a, b in ((0, 16), (16, 32), (32, 40)):
        # every chunk in 16 slots (the last: a tile of padding behind it), so
        # that the three dispatches are one compiled program
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


def _layer0(params, tokens):
    """Layer 0's inputs as the engine computes them: the embedding rows,
    the normed stream, the queries' bottleneck."""
    lp = {k: v[0] for k, v in params["layers"].items() if v.shape[0] == 3}
    x = params["embed"][jnp.asarray(tokens)]
    h = llama.rms_norm(x, lp["attn_norm"], CFG.rms_norm_eps)
    c_q = llama.rms_norm(h @ lp["w_dq"], lp["q_a_norm"], CFG.rms_norm_eps)
    return lp, x, h, c_q


def test_the_pools_hold_what_the_text_says(params):
    """After a prefill the first pool's rows are ``[c_kv normed | k_r
    rotated | zeros]`` and the second's ``[LayerNorm(u W_Ik), first lanes
    rotated | zeros]``, at every layer the reference's (given the
    reference's hidden states)."""
    k, v = _pools()
    T = 29
    _, k, v = forward_ragged(
        params, CFG, jnp.asarray(PROMPT[:T]), jnp.arange(T, dtype=jnp.int32),
        jnp.zeros((T,), jnp.int32), TABLE, k, v, jnp.arange(T),
    )
    entering, _ = reference.hidden_states(params, HF, PROMPT[:T], [0, 1, 2])
    for layer in range(3):
        latent, key = reference.pool_rows(params, HF, layer, entering[layer])
        rows = np.asarray(k[layer, 1:]).reshape(-1, 128)[:T]
        keys = np.asarray(v[layer, 1:]).reshape(-1, 128)[:T]
        assert np.abs(rows[:, :56] - latent).max() < 1e-4
        assert np.abs(keys[:, :32] - key).max() < 1e-4
        assert np.abs(rows[:, 56:]).max() == 0.0 and np.abs(keys[:, 32:]).max() == 0.0
    assert np.abs(np.asarray(v[0, 1:]).reshape(-1, 128)[T:]).max() == 0.0  # nothing past the prompt


@pytest.mark.parametrize("impl", ["xla", "pallas", "expanded_row_of_the_program"])
def test_selection_equals_the_references_on_every_row(params, impl):
    """Layer 0's selection from the engine's own pieces (its projections,
    its pool of index keys, the scores by the gather or by the kernel, the
    bisection) against the reference's ``lax.top_k``: the same set on every
    row of a 48-token sequence, ``min(16, t + 1)`` positions each; the row
    at context 17 drops exactly one. Third case: what ``forward_ragged``
    hands out (``kept_of``) for every query of that sequence as ONE chunk,
    which takes the expanded form (48 queries against the rule's 8)."""
    T = 48
    lp, x, h, c_q = _layer0(params, PROMPT[:T])
    if impl == "expanded_row_of_the_program":
        k, v = _pools()
        *_, kept = forward_ragged(
            params, CFG, jnp.asarray(PROMPT[:T]), jnp.arange(T, dtype=jnp.int32),
            jnp.zeros((T,), jnp.int32), TABLE, k, v, jnp.arange(1),
            kept_of=jnp.arange(T), q_tile=8, **_impl("pallas"),
        )
        got = np.asarray(kept)[0, :, :T] & (np.arange(T)[None] <= np.arange(T)[:, None])
        ref = reference.attn_rows_and_head(params, HF, 0, np.asarray(x), range(T))
        assert np.array_equal(got, ref["keep"])
        assert got.sum(axis=1).tolist() == [min(TOPK, t + 1) for t in range(T)]
        return
    inv_freq = llama.rope_frequencies(CFG.qk_rope_head_dim, CFG.rope_theta, None)
    pos = jnp.arange(T, dtype=jnp.int32)
    q_i, k_i, w_i = llama.index_projections(h[None], c_q[None], lp, CFG, inv_freq, pos[None])
    pool = k_i[0].reshape(T // 16, 16, -1)  # the index keys as three pages of 16
    table = jnp.arange(T // 16, dtype=jnp.int32)[None]
    if impl == "pallas":
        scores = sparse_index.by_query(sparse_index.index_scores(
            q_i[0], w_i[0], pool, table, jnp.zeros((T,), jnp.int32), pos, interpret=True,
        ))
    else:
        scores = sparse_index.index_scores_ref(q_i, w_i, pool, table, pos[None])[0]
    tau, allow = sparse_index.select(scores, pos, TOPK)
    got = np.asarray(sparse_index.kept(scores, tau, allow))[:, :T]
    got = got & (np.arange(T)[None] <= np.arange(T)[:, None])
    ref = reference.attn_rows_and_head(params, HF, 0, np.asarray(x), range(T))
    assert np.array_equal(got, ref["keep"])
    assert got.sum(axis=1).tolist() == [min(TOPK, t + 1) for t in range(T)]
    assert got[TOPK].sum() == TOPK and (~got[TOPK][: TOPK + 1]).sum() == 1
    assert np.abs(np.asarray(scores)[:, :T][got] - ref["scores"][got]).max() < 1e-4


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_forward_hands_out_what_it_kept(params, impl):
    """``kept_of``: the positions each asked query's selection kept in each
    layer, an output of the dispatch beside its logits (a prompt in tiles
    with a decode row in the packed tail, then one step of ``forward``):
    the reference's set on its own hidden states entering each of the three
    layers (two runs of the scan), and the logits are those of the call
    that does not ask."""
    T, rows = 41, [0, 7, TOPK - 1, TOPK, TOPK + 1, 31, 40]
    # through the kernels the prompt's 40 queries take the expanded form
    # (the rule: 8), the decode row the absorbed one: both hand out theirs
    assert llama.latent_form_queries(CFG, 8, FPS, jnp.float32, interpret=True) == 8
    k, v = _pools()
    tokens = PROMPT[:40] + [PROMPT[40]] + [0] * 7
    positions = jnp.asarray(list(range(40)) + [40] + [-1] * 7, jnp.int32)
    kw = dict(q_tile=8, packed_from=40, **_impl(impl)) if impl == "pallas" else {}
    args = (params, CFG, jnp.asarray(tokens), positions, jnp.zeros((48,), jnp.int32), TABLE)
    plain, *_ = forward_ragged(*args, k, v, jnp.asarray(rows), **kw)
    logits, k, v, kept = forward_ragged(
        *args, k, v, jnp.asarray(rows), kept_of=jnp.asarray(rows), **kw
    )
    assert np.array_equal(np.asarray(plain), np.asarray(logits))
    step = forward(
        params, CFG, jnp.asarray([[PROMPT[T]]]), jnp.asarray([[T]], jnp.int32), TABLE, k, v,
        kept_of=jnp.zeros((1,), jnp.int32), **_impl(impl),
    )
    assert kept.shape[:2] == (3, len(rows)) and step[3].shape[:2] == (3, 1)
    entering, _ = reference.hidden_states(params, HF, PROMPT[: T + 1], range(3))
    rows = rows + [T]
    got = np.concatenate([np.asarray(kept)[:, :, : T + 1], np.asarray(step[3])[:, :, : T + 1]], axis=1)
    got = got & (np.arange(T + 1)[None] <= np.asarray(rows)[:, None])
    for layer in range(3):
        ref = reference.attn_rows_and_head(params, HF, layer, entering[layer], rows)
        assert np.array_equal(got[layer], ref["keep"]), layer
    assert got.sum(axis=2).tolist() == [[min(TOPK, t + 1) for t in rows]] * 3


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_two_rows_of_one_dispatch_one_under_and_one_over_topk(params, impl):
    """A row whose context stays under ``index_topk`` (everything kept, its
    index keys written all the same) beside one over it, and a decode row in
    the packed tail, in one stream with padding."""
    k, v = _pools()
    table = jnp.stack([jnp.arange(1, 3), jnp.arange(3, 5), jnp.arange(5, 7)]).astype(jnp.int32)
    other = PROMPT[20:54]
    # the third row's first 30 tokens, written by an earlier dispatch (through
    # the gather in both cases: one program; what is under test reads them)
    third = PROMPT[7:38]
    _, k, v = forward_ragged(
        params, CFG, jnp.asarray(third[:30] + [0, 0]),
        jnp.asarray(list(range(30)) + [-1, -1], jnp.int32),
        jnp.full((32,), 2, jnp.int32), table, k, v, jnp.arange(30),
    )
    tokens = PROMPT[:12] + [0] * 4 + other + [0] * 6 + [0] * 8 + [third[30]] + [0] * 7
    positions = list(range(12)) + [-1] * 4 + list(range(34)) + [-1] * 6 + [-1] * 8 + [30] + [-1] * 7
    row_of = [0] * 16 + [1] * 40 + [2] * 16
    kw = dict(q_tile=8, packed_from=64, **_impl(impl)) if impl == "pallas" else {}
    logits, k, v = forward_ragged(
        params, CFG, jnp.asarray(tokens), jnp.asarray(positions, jnp.int32),
        jnp.asarray(row_of, jnp.int32), table, k, v, jnp.asarray([11, 16 + 33, 64]), **kw,
    )
    got = _logp(logits)
    assert np.abs(got[0] - reference.log_probs(params, HF, PROMPT[:12], [11])[0]).max() < LOGP_TOL
    assert np.abs(got[1] - reference.log_probs(params, HF, other, [33])[0]).max() < LOGP_TOL
    assert np.abs(got[2] - reference.log_probs(params, HF, third, [30])[0]).max() < LOGP_TOL
    # the short row wrote its index keys though it selected nothing
    assert float(jnp.abs(v[0, 1, :12, :32]).min()) > 0.0


def test_a_part_that_selects_nothing_computes_no_scores(params):
    """``_selection_tiled``: no query past ``index_topk`` gives thresholds of
    ``-inf`` and zeros for scores, the kernel then keeps all it sees."""
    q, w, pool, table, row_of, bounds = _scores_case()
    short = jnp.minimum(bounds, TOPK - 1)
    scores, tau, allow = llama._selection_tiled(CFG, (q, w, pool), table, row_of, short, 8, True)
    assert float(jnp.abs(scores).max()) == 0.0 and bool(jnp.all(tau == -jnp.inf))
    assert bool(jnp.all(allow == sparse_index.NO_LIMIT))
    scores, tau, allow = llama._selection_tiled(CFG, (q, w, pool), table, row_of, bounds, 8, True)
    assert bool(jnp.all((tau > -jnp.inf) == (bounds >= TOPK)))
    assert bool(jnp.all((allow >= 1) & ((allow <= TOPK) | (bounds < TOPK))))


# ----------------------------------------------------- a share of the experts
def _ffn_inputs(params, n=96):
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((n, CFG.hidden_size)), jnp.float32)
    return x, llama.rms_norm(x, params["layers"]["mlp_norm"][1], CFG.rms_norm_eps)


def _whole(params):
    """The uncut layer's parameters: 8 experts, of which ``params`` holds
    ids 4..7; the others seeded here."""
    cfg = dataclasses.replace(CFG, num_experts_held=0, first_expert_held=0)
    full = init_params(jax.random.PRNGKey(11), cfg)
    layers = dict(full["layers"])
    for name in ("router", "router_bias", "shared_gate", "shared_up", "shared_down", "mlp_norm"):
        layers[name] = params["layers"][name]
    for name in llama.EXPERT_STACKS:
        layers[name] = layers[name].at[:, 4:8].set(params["layers"][name])
    return {**full, "layers": layers}


def test_the_shares_add_up_to_the_whole_layer(params):
    """8 experts in 2 shares of 4, chosen through the selection bias: the
    shares' routed parts, each by ``moe_ffn`` told its share, plus the
    shared expert ONCE, equal the uncut reference's feed-forward; so do the
    reference's own two shares; and the bias changes the chosen set."""
    whole = _whole(params)
    x, h = _ffn_inputs(params)
    uncut = {**HF, "n_routed_experts": 8, "first_routed_expert": 0}
    want, _, here = reference.ffn_half(whole, uncut, 1, x, normed=False)
    assert here == 1.0
    lw = whole["layers"]
    shared = reference._swiglu(h, lw["shared_gate"][0], lw["shared_up"][0], lw["shared_down"][0])
    routed, ref_routed, shares = 0.0, 0.0, []
    for lo in (0, 4):
        stacks = {k: lw[k][:, lo:lo + 4] for k in llama.EXPERT_STACKS}
        routed = routed + moe.moe_ffn(
            h, lw["router"][0], *(stacks[k][0] for k in llama.EXPERT_STACKS),
            CFG.num_experts_per_tok, True, score="sigmoid", scale=2.5, norm_eps=1e-20,
            held_from=lo, select_bias=lw["router_bias"][0],
        )
        got, _, part = reference.ffn_half(
            {**whole, "layers": {**lw, **stacks}},
            {**HF, "n_routed_experts": 4, "first_routed_expert": lo}, 1, x, normed=False,
        )
        ref_routed = ref_routed + got - np.asarray(shared)
        shares.append(part)
    assert np.abs(np.asarray(routed + shared) - want).max() < 1e-4
    assert np.abs(ref_routed + np.asarray(shared) - want).max() < 1e-4
    assert abs(sum(shares) - 1.0) < 1e-6 and min(shares) > 0.1
    _, biased = moe.moe_router(h, lw["router"][0], 2, True, score="sigmoid",
                               select_bias=lw["router_bias"][0])
    _, plain = moe.moe_router(h, lw["router"][0], 2, True, score="sigmoid")
    assert (np.sort(np.asarray(biased)) != np.sort(np.asarray(plain))).any(axis=1).mean() > 0.1


# ------------------------------------------------------------------ engine
# (its tests: tests/test_glm_dsa_engine.py; benchmark/tests/test_glm5_long_part.py
# builds this engine too)
def _engine(**kw) -> TPUEngine:
    from dynamo_exp_tpu.parallel import single_device_mesh

    cfg = EngineConfig(
        model=CFG, max_decode_slots=4, page_size=kw.pop("page_size", PS),
        num_pages=kw.pop("num_pages", 64), max_model_len=kw.pop("max_model_len", 256),
        prefill_chunk=16, decode_window=8, kv_dtype="float32",
        eos_token_ids=[], preempt_stall_grace_s=0.05, **kw,
    )
    return TPUEngine(cfg, mesh=single_device_mesh(), seed=SEED)


# ---------------------------------------------------------------- refusals
@pytest.mark.parametrize("tier", [
    "host_cache_pages", "kv_store_dir", "spec_mode", "sp", "tp",
    "disaggregated transfer", "migration",
])
def test_tiers_refuse_a_latent_model_of_two_pools(tier, tmp_path):
    kw = {
        "host_cache_pages": {"host_cache_pages": 8}, "spec_mode": {"spec_mode": "ngram"},
        "kv_store_dir": {"host_cache_pages": 8, "kv_store_dir": str(tmp_path)},
        "sp": {"sp": 2}, "tp": {"tp": 2},
    }.get(tier, {})
    cfg = EngineConfig(model=CFG, max_decode_slots=2, page_size=PS, num_pages=16, **kw)
    with pytest.raises(ValueError, match="one latent row.*32 of index key in a second"):
        if kw:
            TPUEngine(cfg)
        else:
            refuse_latent_tiers(cfg, tier)


def test_ring_prefill_refuses(params):
    with pytest.raises(ValueError, match="one latent a token"):
        llama.forward_ring_prefill(
            params, CFG, jnp.zeros((1, 8), jnp.int32), jnp.arange(8)[None],
            types.SimpleNamespace(shape={"sp": 2, "tp": 1}),
        )


def test_manifest_names_the_selector_and_both_widths():
    from dynamo_exp_tpu.aot.lattice import build_manifest

    def manifest(model, impl="pallas", interpret=False):
        cfg = EngineConfig(model=model, max_decode_slots=4, page_size=16, num_pages=64)
        return build_manifest(cfg, impl, {"tp": 1}, "x", interpret=interpret)

    glm = manifest(TINY_GLM_DSA)
    # the tiny widths do not tile on a chip (4 heads): absorbed alone there
    assert glm.engine["attention_form"] == "latent-absorbed-selected-top-16"
    assert glm.engine["kv_pool_widths"] == [128, 128]
    assert glm.model["index_topk"] == 16
    assert manifest(TINY_GLM_DSA, interpret=True).engine["attention_form"] == (
        "latent-absorbed+expanded-from-8-queries-selected-top-16"
    )
    assert manifest(TINY_GLM_DSA, impl="xla").engine["attention_form"] == (
        "latent-absorbed-selected-top-16"
    )
    other = manifest(dataclasses.replace(TINY_GLM_DSA, index_topk=32))
    assert other.hash() != glm.hash()
    assert manifest(TINY_PANGU).engine["attention_form"] == "latent-absorbed"
    # GLM-5's published widths (a head of 192 + 64, values of 256): the
    # kernel takes them, and the form names the selector AND the threshold
    published = dataclasses.replace(
        TINY_GLM_DSA, num_heads=64, num_kv_heads=64, kv_lora_rank=512,
        qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256, index_topk=2048,
    )
    assert manifest(published).engine["attention_form"] == (
        "latent-absorbed+expanded-from-360-queries-selected-top-2048"
    )
    # the rule both the program and the counter ask, indexer or not
    assert llama.latent_form_queries(TINY_GLM_DSA, 8, 16, jnp.bfloat16, interpret=True) == 8
    assert llama.latent_form_queries(TINY_GLM_DSA, 8, 16, jnp.bfloat16) is None
    assert llama.latent_form_queries(published, 8, 16, jnp.bfloat16) == 360
    assert llama.latent_form_queries(TINY_PANGU, 8, 16, jnp.bfloat16, interpret=True) == 8


@pytest.mark.parametrize("preset", ["tiny-pangu", "tiny", "tiny-moe"])
def test_other_models_programs_hold_no_selector(preset):
    """A model without an indexer lowers to a program with no scope, kernel
    or operand of the selection: the second pool of a latent model without
    one still has no lanes."""
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
    assert not any(k.startswith("idx_") for k in p["layers"])


def test_the_scopes_are_in_the_program(params):
    k, v = _pools()
    n = 16
    text = jax.jit(
        lambda t, pos, row: llama.forward_ragged(
            params, CFG, t, pos, row, TABLE, k, v, jnp.arange(2), attn_impl="pallas",
            interpret=True)
    ).lower(*(jax.ShapeDtypeStruct((n,), jnp.int32),) * 3).as_text(debug_info=True)
    for scope in ("mla/dsa_index", "dsa_select", "mla/attend", "dsa_index_scores"):
        assert scope in text, scope


# ------------------------------------------------------------------ loader
def test_checkpoint_names_round_trip(tmp_path, params):
    """A checkpoint under the family's tensor names (DeepSeek-V3.2's for
    the indexer), with all 8 experts in it and every rotary block in the
    PUBLISHED order (interleaved pairs), loads into the stacks by kind, the
    held share alone, the rotary blocks in the engine's order: exactly the
    arrays it was written from."""
    from safetensors.numpy import save_file

    from dynamo_exp_tpu.models.loader import load_params, rotary_columns, rotary_layout
    from dynamo_exp_tpu.models.synth import write_config

    whole = _whole(params)
    layers = {k: np.asarray(v) for k, v in whole["layers"].items()}
    own = {k: np.asarray(v) for k, v in params["layers"].items()}
    moved = 0
    for key, starts in rotary_columns(CFG).items():
        published = rotary_layout(own[key], starts, CFG.qk_rope_head_dim, "pairs")
        moved += int((published != own[key]).any())  # the norm's scale is ones
        back = rotary_layout(published, starts, CFG.qk_rope_head_dim, "halves")
        np.testing.assert_array_equal(back, own[key])
        own[key] = published
    assert moved == 5
    out = {
        "model.embed_tokens.weight": np.asarray(params["embed"]),
        "model.norm.weight": np.asarray(params["final_norm"]),
        "lm_head.weight": np.asarray(params["lm_head"]).T,
    }
    proj = (("gate", "gate_proj"), ("up", "up_proj"), ("down", "down_proj"))
    for i, ff in enumerate(CFG.ff_kinds):
        p = f"model.layers.{i}."
        out[p + "input_layernorm.weight"] = own["attn_norm"][i]
        out[p + "post_attention_layernorm.weight"] = own["mlp_norm"][i]
        for key, name in (
            ("w_dq", "q_a_proj"), ("w_uq", "q_b_proj"), ("w_dkv", "kv_a_proj_with_mqa"),
            ("w_ukv", "kv_b_proj"), ("wo", "o_proj"), ("idx_wq", "indexer.wq_b"),
            ("idx_wk", "indexer.wk"), ("idx_wp", "indexer.weights_proj"),
        ):
            out[f"{p}self_attn.{name}.weight"] = own[key][i].T
        out[p + "self_attn.q_a_layernorm.weight"] = own["q_a_norm"][i]
        out[p + "self_attn.kv_a_layernorm.weight"] = own["kv_a_norm"][i]
        out[p + "self_attn.indexer.k_norm.weight"] = own["idx_k_norm"][i]
        out[p + "self_attn.indexer.k_norm.bias"] = own["idx_k_bias"][i]
        if ff == "dense":
            for key, name in proj:
                out[f"{p}mlp.{name}.weight"] = own[f"dense_{key}"][i].T
            continue
        f = i - CFG.num_dense_layers
        out[p + "mlp.gate.weight"] = own["router"][f].T
        out[p + "mlp.gate.e_score_correction_bias"] = own["router_bias"][f]
        for key, name in proj:
            out[f"{p}mlp.shared_experts.{name}.weight"] = own[f"shared_{key}"][f].T
            for e in range(8):
                out[f"{p}mlp.experts.{e}.{name}.weight"] = layers[f"w_{key}"][f, e].T
    save_file({k: np.ascontiguousarray(v) for k, v in out.items()}, str(tmp_path / "model.safetensors"))
    write_config(str(tmp_path), CFG)
    loaded, cfg = load_params(str(tmp_path))
    assert cfg == CFG
    assert set(loaded["layers"]) == set(params["layers"])
    for k, v in params["layers"].items():
        np.testing.assert_array_equal(loaded["layers"][k], np.asarray(v), err_msg=k)
    np.testing.assert_array_equal(loaded["lm_head"], np.asarray(params["lm_head"]))


def test_the_reference_rotates_the_published_pairs(params):
    """The reference multiplies by the published order and rotates pairs
    ``(2i, 2i + 1)``: a rotated vector in the engine's order, taken back to
    the published one, is the reference's rotation of the published
    vector."""
    rng = np.random.default_rng(2)
    x = rng.standard_normal((5, 16)).astype(np.float32)  # engine order
    pos = jnp.arange(5)
    inv_freq = llama.rope_frequencies(16, CFG.rope_theta, None)
    engine = np.asarray(llama.apply_rope(jnp.asarray(x)[None, :, None], pos[None], inv_freq))[0, :, 0]
    pairs = reference._pairs_from_halves(16)
    published = np.asarray(reference._rope(jnp.asarray(x[:, pairs]), pos, CFG.rope_theta))
    assert np.abs(reference.engine_order(published, 0, 16) - engine).max() < 1e-5


# ------------------------------------------------- what the tolerance is far below
# Last in the file: each case clears JAX's caches (the reference is jitted and
# must be traced anew with its fault), and with them the compiled programs the
# tests above share.
FAULT_T = 44


@pytest.fixture(scope="module")
def sound_logp(params):
    """The engine's own log-probabilities of the faults' prompt: the same for
    every fault (the fault is made in the reference), so computed once."""
    k, v = _pools()
    logits, *_ = forward_ragged(
        params, CFG, jnp.asarray(PROMPT[:FAULT_T]), jnp.arange(FAULT_T, dtype=jnp.int32),
        jnp.zeros((FAULT_T,), jnp.int32), TABLE, k, v, jnp.arange(FAULT_T),
    )
    return _logp(logits)


def _patched(monkeypatch, fault):
    """The reference computing with ``fault``."""
    jax.clear_caches()
    if fault == "dense":
        monkeypatch.setattr(reference, "_select", lambda s, p, k: (
            p[:, None] >= jnp.arange(s.shape[1])[None], jnp.full(p.shape, jnp.inf)))
    elif fault == "top-half":
        plain = reference._select
        monkeypatch.setattr(reference, "_select", lambda s, p, k: plain(s, p, k // 2))
    elif fault == "no relu":
        monkeypatch.setattr(reference.jax.nn, "relu", lambda a: a)
    elif fault == "no weights":
        plain = reference._index_queries
        monkeypatch.setattr(reference, "_index_queries", lambda *a: (
            plain(*a)[0], jnp.ones_like(plain(*a)[1])))
    elif fault == "no layernorm":
        monkeypatch.setattr(reference, "_layer_norm", lambda x, w, b, eps=0: x)
    elif fault == "keys before rope":
        def keys(u, lp, c, positions):
            return reference._layer_norm(
                u @ reference._f32(lp["idx_wk"]), lp["idx_k_norm"], lp["idx_k_bias"])
        monkeypatch.setattr(reference, "_index_keys", keys)
    elif fault == "rope on the last lanes":
        def keys(u, lp, c, positions):
            k = reference._layer_norm(
                u @ reference._f32(lp["idx_wk"]), lp["idx_k_norm"], lp["idx_k_bias"])
            return jnp.concatenate([
                k[:, : -c["rope"]], reference._rope(k[:, -c["rope"]:], positions, c["theta"]),
            ], axis=-1)
        monkeypatch.setattr(reference, "_index_keys", keys)
    elif fault == "selection without the bias":
        plain = reference._route
        monkeypatch.setattr(reference, "_route", lambda s, b, **kw: plain(s, 0 * b, **kw))
    elif fault == "half-split rotary pairs":
        def rope(x, positions, theta):
            d = x.shape[-1]
            inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
            ang = positions.astype(jnp.float32)[:, None] * inv[None]
            ang = ang.reshape(ang.shape[0], *(1,) * (x.ndim - 2), d // 2)
            a, b = x[..., : d // 2], x[..., d // 2:]
            return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                                    b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)
        monkeypatch.setattr(reference, "_rope", rope)
    else:
        raise ValueError(fault)


@pytest.mark.parametrize("fault", [
    "dense", "top-half", "no relu", "no weights", "no layernorm", "keys before rope",
    "rope on the last lanes", "selection without the bias", "half-split rotary pairs",
])
def test_a_fault_shows(params, sound_logp, monkeypatch, fault):
    """Each thing the text says and a tolerance could hide: the reference
    computing WITHOUT it differs from the engine by far more than
    ``LOGP_TOL`` (and the sound reference does not)."""
    T, got = FAULT_T, sound_logp
    try:
        _patched(monkeypatch, fault)
        wrong = reference.log_probs(params, HF, PROMPT[:T], range(T))
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert np.abs(got - wrong).max() > 20 * LOGP_TOL
    if fault in ("dense", "top-half"):  # rows that see <= topk / 2 positions are untouched
        assert np.abs(got - wrong)[: TOPK // 2].max() < LOGP_TOL
