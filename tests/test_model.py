"""Model-layer correctness: paged incremental forward == dense oracle,
TP-sharded forward == single-device forward."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_exp_tpu.models import (
    TINY,
    forward,
    init_kv_cache,
    init_params,
    kv_cache_shardings,
    param_shardings,
)
from dynamo_exp_tpu.ops import dense_causal_attention, paged_attention, write_kv_pages
from dynamo_exp_tpu.parallel import build_mesh, shard_pytree


PS = 8  # page size

# Jitted once for the module: an eager ``forward`` compiles its layer
# scan anew on every call.
fwd = jax.jit(
    forward,
    static_argnums=(1,),
    static_argnames=("attn_impl", "interpret", "mesh"),
)


def _full_forward_logits(params, cfg, token_list):
    """Oracle: run the whole sequence in one prefill pass, fresh cache."""
    T = len(token_list)
    pmax = (T + PS - 1) // PS
    k, v = init_kv_cache(cfg, num_pages=pmax + 1, page_size=PS, dtype=jnp.float32)
    tokens = jnp.array([token_list], dtype=jnp.int32)
    positions = jnp.arange(T, dtype=jnp.int32)[None, :]
    table = jnp.arange(pmax, dtype=jnp.int32)[None, :] + 1  # pages 1..pmax
    logits, _, _ = fwd(params, cfg, tokens, positions, table, k, v)
    return np.asarray(logits[0])


def test_paged_attention_matches_dense():
    key = jax.random.PRNGKey(0)
    B, T, H, Hkv, D = 2, 16, 4, 2, 8
    q = jax.random.normal(key, (B, T, H, D), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, T, Hkv, D), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, T, Hkv, D), jnp.float32)

    want = dense_causal_attention(q, k, v)

    # Put k/v into pages: each batch row owns its own pages. Pools hold
    # (Hkv, D) collapsed into the lane dim.
    pmax = T // PS
    kc = jnp.zeros((B * pmax + 1, PS, Hkv * D))
    vc = jnp.zeros_like(kc)
    table = (jnp.arange(B * pmax, dtype=jnp.int32).reshape(B, pmax)) + 1
    pos = jnp.tile(jnp.arange(T, dtype=jnp.int32)[None], (B, 1))
    flat_pos = pos.reshape(-1)
    bidx = jnp.repeat(jnp.arange(B, dtype=jnp.int32), T)
    page_ids = table[bidx, flat_pos // PS]
    kc, vc = write_kv_pages(
        kc, vc,
        k.reshape(B * T, Hkv * D), v.reshape(B * T, Hkv * D),
        page_ids, flat_pos % PS, jnp.ones(B * T, bool),
    )
    got = paged_attention(q, kc, vc, table, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_incremental_decode_matches_full_prefill():
    cfg = TINY
    params = jax.tree.map(
        lambda x: x.astype(jnp.float32), init_params(jax.random.PRNGKey(7), cfg)
    )
    toks = list(np.random.RandomState(0).randint(1, cfg.vocab_size, size=21))

    want = _full_forward_logits(params, cfg, toks)

    # Incremental: prefill first 13 tokens, then decode one at a time.
    pmax = 4
    k, v = init_kv_cache(cfg, num_pages=pmax + 1, page_size=PS, dtype=jnp.float32)
    table = jnp.arange(pmax, dtype=jnp.int32)[None, :] + 1
    split = 13
    tokens = jnp.array([toks[:split]], dtype=jnp.int32)
    positions = jnp.arange(split, dtype=jnp.int32)[None, :]
    logits, k, v = fwd(params, cfg, tokens, positions, table, k, v)
    np.testing.assert_allclose(
        np.asarray(logits[0]), want[:split], rtol=1e-4, atol=1e-4
    )
    for i in range(split, len(toks)):
        tok = jnp.array([[toks[i]]], dtype=jnp.int32)
        pos = jnp.array([[i]], dtype=jnp.int32)
        logits, k, v = fwd(params, cfg, tok, pos, table, k, v)
        np.testing.assert_allclose(
            np.asarray(logits[0, 0]), want[i], rtol=1e-4, atol=1e-4
        )


def test_padding_rows_do_not_corrupt_cache():
    """Inactive decode slots (position == -1) must not write KV anywhere."""
    cfg = TINY
    params = init_params(jax.random.PRNGKey(3), cfg)
    k, v = init_kv_cache(cfg, num_pages=8, page_size=PS)
    table = jnp.array([[1, 2], [3, 4]], dtype=jnp.int32)
    tokens = jnp.array([[5], [0]], dtype=jnp.int32)
    positions = jnp.array([[0], [-1]], dtype=jnp.int32)  # slot 1 inactive
    _, k2, v2 = fwd(params, cfg, tokens, positions, table, k, v)
    # Slot 1's pages (3, 4) must be untouched.
    np.testing.assert_array_equal(np.asarray(k2[:, 3:5]), np.asarray(k[:, 3:5]))
    # Slot 0 wrote page 1 offset 0.
    assert np.abs(np.asarray(k2[:, 1, 0])).sum() > 0


def test_tp_sharded_forward_matches_single_device():
    cfg = TINY  # 2 kv heads -> tp=2
    params = init_params(jax.random.PRNGKey(11), cfg)
    toks = list(np.random.RandomState(1).randint(1, cfg.vocab_size, size=9))
    want = _full_forward_logits(
        jax.tree.map(lambda x: x.astype(jnp.float32), params), cfg, toks
    )

    mesh = build_mesh(tp=2)
    sp, _ = shard_pytree(mesh, params, param_shardings(cfg))
    T = len(toks)
    pmax = (T + PS - 1) // PS
    kspec, vspec = kv_cache_shardings()
    k, v = init_kv_cache(cfg, num_pages=pmax + 1, page_size=PS)
    from jax.sharding import NamedSharding

    k = jax.device_put(k, NamedSharding(mesh, kspec))
    v = jax.device_put(v, NamedSharding(mesh, vspec))
    tokens = jnp.array([toks], dtype=jnp.int32)
    positions = jnp.arange(T, dtype=jnp.int32)[None, :]
    table = jnp.arange(pmax, dtype=jnp.int32)[None, :] + 1
    logits, _, _ = fwd(sp, cfg, tokens, positions, table, k, v)
    # bf16 params => loose tolerance; checking agreement not exactness.
    np.testing.assert_allclose(
        np.asarray(logits[0]), want, rtol=0.1, atol=0.15
    )


def test_sampling_greedy_and_topk():
    from dynamo_exp_tpu.ops import sample_tokens

    logits = jnp.array([[0.0, 5.0, 1.0, 2.0], [3.0, 0.0, 0.0, 0.0]], jnp.float32)
    out = sample_tokens(
        logits,
        jax.random.PRNGKey(0),
        temperature=jnp.zeros(2),
        top_k=jnp.zeros(2, jnp.int32),
        top_p=jnp.ones(2),
    )
    np.testing.assert_array_equal(np.asarray(out), [1, 0])
    # top_k=1 at any temperature is greedy.
    out = sample_tokens(
        logits,
        jax.random.PRNGKey(1),
        temperature=jnp.full(2, 0.9),
        top_k=jnp.ones(2, jnp.int32),
        top_p=jnp.ones(2),
    )
    np.testing.assert_array_equal(np.asarray(out), [1, 0])


def test_model_config_hashable_with_rope_scaling():
    from dynamo_exp_tpu.models import ModelConfig

    cfg = ModelConfig.from_hf_config(
        {"rope_scaling": {"rope_type": "llama3", "factor": 8.0}}
    )
    hash(cfg)  # must be usable as a jit static argument

    from dynamo_exp_tpu.ops import rope_frequencies

    base = rope_frequencies(8, 10000.0)
    lin = rope_frequencies(8, 10000.0, {"type": "linear", "factor": 4.0})
    np.testing.assert_allclose(np.asarray(lin), np.asarray(base) / 4.0)
    with pytest.raises(ValueError):
        rope_frequencies(8, 10000.0, {"type": "yarn", "factor": 2.0})


def test_top_p_zero_degrades_to_greedy():
    from dynamo_exp_tpu.ops import sample_tokens

    logits = jnp.array([[0.0, 5.0, 1.0, 2.0]], jnp.float32)
    out = sample_tokens(
        logits,
        jax.random.PRNGKey(0),
        temperature=jnp.full(1, 1.0),
        top_k=jnp.zeros(1, jnp.int32),
        top_p=jnp.zeros(1),
    )
    np.testing.assert_array_equal(np.asarray(out), [1])


def test_position_beyond_page_table_is_dropped_not_clamped():
    cfg = TINY
    params = init_params(jax.random.PRNGKey(5), cfg)
    k, v = init_kv_cache(cfg, num_pages=4, page_size=PS)
    table = jnp.array([[1, 2]], dtype=jnp.int32)  # capacity = 2 pages
    tokens = jnp.array([[7]], dtype=jnp.int32)
    positions = jnp.array([[2 * PS]], dtype=jnp.int32)  # one past capacity
    _, k2, _ = fwd(params, cfg, tokens, positions, table, k, v)
    np.testing.assert_array_equal(np.asarray(k2), np.asarray(k))  # no write


# ------------------------------------------- pools carried through the layers
@functools.partial(jax.jit, static_argnums=(1,))
def _layer_loop_reference(params, cfg, tokens, positions, row_of, table, k, v):
    """Plain reference for both paged forwards: a Python loop over the
    layers, each with its own ``[P, ps, W]`` pool, every flat token a
    T=1 row of ``paged_attention``. Returns (logits [N, V], k, v)."""
    from dynamo_exp_tpu.models import llama
    from dynamo_exp_tpu.ops import rope_frequencies

    ps = k.shape[2]
    N = tokens.shape[0]
    valid = (positions >= 0) & (positions // ps < table.shape[1])
    safe = jnp.maximum(positions, 0)
    page_ids = table[row_of, jnp.minimum(safe // ps, table.shape[1] - 1)]
    inv_freq = rope_frequencies(cfg.head_dim_, cfg.rope_theta, cfg.rope_scaling)
    x = llama._maybe_scale_embeds(cfg, params["embed"][tokens][None])
    new_k, new_v = [], []
    for l in range(cfg.num_layers):
        lp = jax.tree.map(lambda a: a[l], params["layers"])
        window = None
        if cfg.sliding_window is not None:  # gemma2: even layers slide
            slides = not cfg.alt_sliding_window or l % 2 == 0
            window = cfg.sliding_window if slides else 1 << 30

        def attend(q, kn, vn):
            kp, vp = write_kv_pages(
                k[l], v[l], kn.reshape(N, -1), vn.reshape(N, -1),
                page_ids, safe % ps, valid,
            )
            attn = paged_attention(
                q[0][:, None], kp, vp, table[row_of], positions[:, None],
                sm_scale=(
                    cfg.query_pre_attn_scalar ** -0.5
                    if cfg.query_pre_attn_scalar else None
                ),
                window=window, softcap=cfg.attn_logit_softcap,
            )
            return attn[:, 0][None], (kp, vp)

        x, (kp, vp) = llama._attn_mlp_layer(
            x, lp, cfg, inv_freq, safe[None], cfg.rms_norm_eps, attend
        )
        new_k.append(kp)
        new_v.append(vp)
    logits = llama._final_logits(params, cfg, x[0], cfg.rms_norm_eps)
    return logits, jnp.stack(new_k), jnp.stack(new_v)


def _carry_case(name):
    import dataclasses

    cfg = dataclasses.replace(TINY, num_layers=3)
    if name == "gemma2-like":  # per-layer windows ride the scan beside l
        cfg = dataclasses.replace(
            cfg, hidden_act="gelu_tanh", rms_norm_offset=True,
            scale_embeddings=True, post_norms=True, attn_logit_softcap=50.0,
            final_logit_softcap=30.0, query_pre_attn_scalar=16.0,
            sliding_window=6, alt_sliding_window=True, model_type="gemma2",
        )
    params = jax.tree.map(
        lambda x: x.astype(jnp.float32), init_params(jax.random.PRNGKey(9), cfg)
    )
    # Pools start full of (recorded) noise, so an untouched slot is told
    # from a written one and a stray write from none.
    shape = (cfg.num_layers, 6, PS, cfg.num_kv_heads * cfg.head_dim_)
    k = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), shape, jnp.float32)
    return cfg, params, k, v


def _assert_only_named_slots_written(before, after, want, named):
    """``after`` differs from ``before`` exactly at ``named`` (a set of
    (page, offset), the same in every layer), where it holds ``want``."""
    before, after = np.asarray(before), np.asarray(after)
    untouched = np.ones(before.shape[:3], bool)
    for page, off in named:
        untouched[:, page, off] = False
        assert (after[:, page, off] != before[:, page, off]).all()
    np.testing.assert_array_equal(after[untouched], before[untouched])
    np.testing.assert_allclose(after, np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize(
    "case,attn_impl",
    [("tiny", "xla"), ("tiny", "pallas"), ("gemma2-like", "xla")],
)
def test_forwards_write_only_named_slots_and_match_layer_loop(case, attn_impl):
    """Both paged forwards carry the whole ``[L, P, ps, W]`` pools through
    the layer loop and write them at ``[l, page, offset]``. With rows at
    position -1 and rows past the page table's capacity in the batch,
    every slot of every layer that no valid (row, position) names stays
    bit-identical (a drop sentinel that is in range of a flattened pool
    lands in the next layer's page 0), and logits and pools agree with
    the plain per-layer loop. Reads: the XLA gather and the interpreted
    kernel, both through the flat view with the page table moved to
    layer ``l``'s range."""
    from dynamo_exp_tpu.models.llama import forward_ragged

    cfg, params, k, v = _carry_case(case)
    kw = dict(attn_impl=attn_impl, interpret=True)
    table = jnp.array([[1, 2], [3, 4], [5, 1]], jnp.int32)

    # forward, decode shape: row 0 at position 10 (page 2, offset 2),
    # row 1 inactive, row 2 one past its table's capacity.
    tokens = jnp.array([[7], [0], [9]], jnp.int32)
    positions = jnp.array([[10], [-1], [2 * PS]], jnp.int32)
    logits, k1, v1 = fwd(params, cfg, tokens, positions, table, k, v, **kw)
    want, want_k, want_v = _layer_loop_reference(
        params, cfg, tokens[:, 0], positions[:, 0], jnp.arange(3), table, k, v
    )
    _assert_only_named_slots_written(k, k1, want_k, {(2, 2)})
    _assert_only_named_slots_written(v, v1, want_v, {(2, 2)})
    np.testing.assert_allclose(
        np.asarray(logits[0, 0]), np.asarray(want[0]), rtol=1e-4, atol=1e-4
    )

    # forward_ragged, tile-aligned flat stream: row 0 prefills positions
    # 3..6 (page 1), row 1 decodes at 9 (page 4, offset 1) beside three
    # pads, row 2's token is past capacity.
    pad = [-1, -1, -1]
    tokens = jnp.array([5, 6, 7, 8, 11, 0, 0, 0, 12, 0, 0, 0], jnp.int32)
    positions = jnp.array([3, 4, 5, 6, 9, *pad, 2 * PS, *pad], jnp.int32)
    row_of = jnp.repeat(jnp.arange(3, dtype=jnp.int32), 4)
    out_idx = jnp.array([3, 4], jnp.int32)
    logits, k1, v1 = jax.jit(forward_ragged, static_argnums=(1,), static_argnames=(
        "attn_impl", "interpret", "q_tile"))(
        params, cfg, tokens, positions, row_of, table, k, v, out_idx,
        q_tile=4, **kw,
    )
    want, want_k, want_v = _layer_loop_reference(
        params, cfg, tokens, positions, row_of, table, k, v
    )
    named = {(1, 3), (1, 4), (1, 5), (1, 6), (4, 1)}
    _assert_only_named_slots_written(k, k1, want_k, named)
    _assert_only_named_slots_written(v, v1, want_v, named)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(want[out_idx]), rtol=1e-4, atol=1e-4
    )
