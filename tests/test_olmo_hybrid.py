"""olmo_hybrid (Olmo-Hybrid-7B) on the normal path: a linear-attention
layer kind (the gated delta rule) with its chunked matrix-form kernel, a
float32 matrix state beside the paged KV, multi-head attention without
positions, Olmo 2's block. Tiny sizes, seeded weights, float32, so the
engine and the plain reference (``benchmark/references/olmo_hybrid.py``)
differ by float32 rounding alone.

TOLERANCES. Both sides compute in float32 with the reference at
``highest`` matmul precision and the engine at the CPU's default (also
float32 products): log-probabilities agree to ~1e-5, so ``LOGP_TOL`` 1e-3
stands a hundred times above the rounding and far below any fault (a
state left at zero moves them by 1e-2 and more: the test that zeroes it
says so). Token streams are compared exactly: float32 logits of random
weights tie nowhere. The kernel against its oracle:
``tests/test_gated_delta.py``.
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
from dynamo_exp_tpu.engine.config import refuse_seq_state_tiers
from dynamo_exp_tpu.models import PRESETS, ModelConfig, llama
from dynamo_exp_tpu.models.config import TINY_JAMBA, TINY_LFM2, TINY_OLMO_HYBRID
from dynamo_exp_tpu.protocols.common import BackendInput

# Jitted: a shape and set of options compiles once a file (tests/programs.py).
from .programs import forward, forward_ragged, init_params

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmark"))
from references import olmo_hybrid as reference  # noqa: E402

CFG = dataclasses.replace(TINY_OLMO_HYBRID, dtype="float32")
LINEAR, FULL = "linear_attention", "full_attention"
HF = {
    "model_type": "olmo_hybrid", "vocab_size": CFG.vocab_size,
    "hidden_size": CFG.hidden_size, "intermediate_size": CFG.intermediate_size,
    "num_hidden_layers": CFG.num_layers, "num_attention_heads": CFG.num_heads,
    "num_key_value_heads": CFG.num_kv_heads, "hidden_act": "silu",
    "max_position_embeddings": 512, "attention_bias": False, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "layer_types": [LINEAR, LINEAR, LINEAR, FULL, LINEAR],
    "linear_num_key_heads": 2, "linear_num_value_heads": 2, "linear_key_head_dim": 8,
    "linear_value_head_dim": 64, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None},
    "torch_dtype": "float32",
}
# The catalog's row of Olmo-Hybrid-7B (model-configs guide), every key.
CATALOG = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32, "num_attention_heads": 30,
    "num_key_value_heads": 30, "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "layer_types": [LINEAR, LINEAR, LINEAR, FULL] * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30, "linear_key_head_dim": 96,
    "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None},
}
# Pages of 16 on tables of 4 (64 tokens a row: every sequence of this file is
# shorter): a chunk of an interpreted kernel is four page copies to lower, a
# quarter of what pages of 8 on a table of 16 cost (tests/ragged_cases.py
# "SHAPES"). No rule under test here reads the page size but the prefix hit's
# count of pages, which is asked as tokens over PS.
PS = 16
LOGP_TOL = 1e-3
SEED = 3
PROMPT = [int(t) for t in np.random.default_rng(0).integers(2, 256, 80)]
TABLE = jnp.arange(4, dtype=jnp.int32)[None] + 1


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(SEED), CFG)


def _pools(entries=4):
    k, v = llama.init_kv_cache(CFG, 17, PS, dtype=jnp.float32)
    return k, v, llama.init_seq_state(CFG, entries)


def _logp(logits):
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


def _count(shapes) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))


# ------------------------------------------------------------------ config
def test_config_reads_the_published_keys():
    assert ModelConfig.from_hf_config(HF) == CFG
    assert CFG.op_kinds == ("linear", "linear", "linear", "attn", "linear")
    assert CFG.layer_runs == (
        ("linear", "dense", 0, 3, 0, 0), ("attn", "dense", 3, 1, 0, 3),
        ("linear", "dense", 4, 1, 3, 4),
    )
    assert CFG.has_seq_state and not CFG.use_rope and not CFG.pre_norms
    assert CFG.post_norms and CFG.qk_norm_whole and CFG.linear_conv_width == 160


def test_catalog_row_builds_the_whole_model():
    """``from_hf_config`` builds Olmo-Hybrid-7B from the catalog's keys
    alone: 24 linear and 8 full layers in 16 runs, the published widths,
    and the parameter counts the issue reckons, whole and at the cut."""
    cfg = ModelConfig.from_hf_config(CATALOG)
    assert cfg.op_kinds.count("linear") == 24 and cfg.op_kinds.count("attn") == 8
    assert [(r[0], r[3]) for r in cfg.layer_runs] == [("linear", 3), ("attn", 1)] * 8
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_, cfg.q_per_kv) == (30, 30, 128, 1)
    assert (cfg.linear_num_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim) == (30, 96, 192)
    assert cfg.linear_conv_width == 11520 and cfg.linear_allow_neg_eigval
    assert not cfg.tie_word_embeddings and not cfg.is_moe and cfg.max_position_embeddings == 65536
    layer = {
        "linear": 3840 * (11520 + 5760 + 60) + 5760 * 3840 + 4 * 11520 + 60 + 192,
        "attn": 4 * 3840 * 3840 + 2 * 3840,
    }
    every = 3 * 3840 * 11008 + 2 * 3840  # the feed-forward, the two output norms
    assert layer["linear"] + 3 * 3840 * 11008 == 215_562_492  # the issue's linear layer
    ends = 2 * 100352 * 3840 + 3840
    shapes = jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    assert _count(shapes) == 24 * layer["linear"] + 8 * layer["attn"] + 32 * every + ends
    cut = ModelConfig.from_hf_config(
        {**CATALOG, "num_hidden_layers": 8, "layer_types": CATALOG["layer_types"][:8]}
    )
    assert [(r[0], r[3]) for r in cut.layer_runs] == [("linear", 3), ("attn", 1)] * 2
    shapes = jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0), cut))
    assert _count(shapes) == 2_435_748_072
    assert abs(_count(shapes) / 2_435.7e6 - 1) < 1e-3
    state = jax.eval_shape(lambda: llama.init_seq_state(cut, 1))
    assert sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state)) == 13_685_760
    assert state.state.shape == (6, 1, 96, 5760) and state.state.dtype == jnp.float32
    assert state.conv.shape == (6, 1, 3, 11520) and state.conv.dtype == jnp.bfloat16
    kv = jax.eval_shape(lambda: llama.init_kv_cache(cut, 1, 1))
    assert sum(x.size * x.dtype.itemsize for x in kv) == 30_720  # a token


@pytest.mark.parametrize("key,value", [
    ("rope_theta", 500000.0), ("rope_parameters", {"rope_theta": 10000.0}),
    ("hidden_act", "gelu"), ("attention_bias", True), ("linear_num_key_heads", 1),
    ("linear_conv_kernel_dim", 1),
])
def test_unsupported_olmo_hybrid_keys_raise(key, value):
    with pytest.raises(ValueError, match="not supported|needs"):
        ModelConfig.from_hf_config({**HF, key: value})


def test_layer_types_must_name_every_layer():
    with pytest.raises(ValueError, match="layer_types must name 5 layers"):
        ModelConfig.from_hf_config({**HF, "layer_types": [LINEAR, "conv", FULL, FULL, FULL]})


def test_random_linear_parameters_are_the_familys(params):
    """``A_log = log a`` with ``a`` in (0, 16) a head, the step's bias the
    inverse softplus of a step in ``MAMBA_DT_RANGE``; both float32. No
    norm on a half's input."""
    layers = params["layers"]
    a = np.asarray(jnp.exp(layers["gdn_A_log"]))
    assert a.shape == (4, 2) and 0 < a.min() and a.max() <= 16
    step = np.asarray(jax.nn.softplus(layers["gdn_dt_b"]))
    lo, hi = llama.MAMBA_DT_RANGE
    assert step.min() >= lo * 0.999 and step.max() <= hi * 1.001
    assert "attn_norm" not in layers and "mlp_norm" not in layers
    assert layers["q_norm"].shape == (1, 64) and layers["post_attn_norm"].shape == (5, 64)
    bf16 = init_params(jax.random.PRNGKey(SEED), TINY_OLMO_HYBRID)["layers"]
    for name in ("gdn_A_log", "gdn_dt_b"):
        assert bf16[name].dtype == jnp.float32
    assert bf16["gdn_qkv"].dtype == jnp.bfloat16
    assert set(llama.param_shardings(CFG)["layers"]) == set(layers)


# ----------------------------------------------------------------- forward
@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_forward_matches_reference(params, phase):
    """The whole prompt in one piece, then six steps through the cache and
    both parts of the state."""
    k, v, st = _pools()
    T = 21
    slot = jnp.asarray([2])
    logits, k, v, st = forward(
        params, CFG, jnp.asarray(PROMPT[:T])[None], jnp.arange(T, dtype=jnp.int32)[None],
        TABLE, k, v, seq_state=st, state_slots=slot,
    )
    if phase == "prefill":
        want = reference.log_probs(params, HF, PROMPT[:T], range(T))
        assert np.abs(_logp(logits[0]) - want).max() < LOGP_TOL
        return
    for t in range(T, T + 6):
        logits, k, v, st = forward(
            params, CFG, jnp.asarray(PROMPT[t:t + 1])[None], jnp.asarray([[t]], jnp.int32),
            TABLE, k, v, seq_state=st, state_slots=slot,
        )
        want = reference.log_probs(params, HF, PROMPT[:t + 1], [t])
        assert np.abs(_logp(logits[0]) - want).max() < LOGP_TOL


def test_the_pools_state_is_the_references(params):
    """The float32 state the forward leaves in the pool, a head at a time,
    against the reference's after the same tokens (layer 0, whose input is
    the embedding)."""
    k, v, st = _pools()
    T = 21
    *_, st = forward(
        params, CFG, jnp.asarray(PROMPT[:T])[None], jnp.arange(T, dtype=jnp.int32)[None],
        TABLE, k, v, seq_state=st, state_slots=jnp.asarray([2]),
    )
    want = reference.linear_state(params, HF, 0, params["embed"][jnp.asarray(PROMPT[:T])])
    got = np.asarray(st.state[0, 2]).reshape(8, 2, 64).transpose(1, 0, 2)
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()


def _stream(spans, tail=()):
    """The flat stream (tokens, positions, rows) of ``spans`` = (tokens,
    first position, row), each padded to the tile of 8, then ``tail`` =
    (token, position, row) slots one a row."""
    flat_t, flat_p, flat_r = [], [], []
    for t, p0, r in spans:
        pad = -len(t) % 8
        flat_t += list(t) + [0] * pad
        flat_p += list(range(p0, p0 + len(t))) + [-1] * pad
        flat_r += [r] * (len(t) + pad)
    for t, p, r in tail:
        flat_t, flat_p, flat_r = flat_t + [t], flat_p + [p], flat_r + [r]
    return tuple(jnp.asarray(a, jnp.int32) for a in (flat_t, flat_p, flat_r))


def _ragged(params, impl, pools, stream, table, state_rows, out_idx, **kw):
    """One ``forward_ragged`` over ``_stream``'s arrays, the kernels in
    interpret mode."""
    k, v, st = pools
    return forward_ragged(
        params, CFG, *stream, table, k, v, jnp.asarray(out_idx, jnp.int32),
        attn_impl=impl, q_tile=8, interpret=True, seq_state=st,
        state_rows=tuple(jnp.asarray(a, jnp.int32) for a in state_rows), **kw,
    )


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_chunks_of_unequal_size_then_decode(params, impl):
    """A prompt in chunks of 16, 24, 5 and 8 tokens across four
    ``forward_ragged`` dispatches (the state through the pool each time;
    with ``pallas`` the delta-rule kernel in interpret mode), then decode
    steps through ``forward``: the uncut forward's logits throughout."""
    pools = _pools()
    table = jnp.concatenate([TABLE, TABLE * 0])
    got, at = [], 0
    for n in (16, 24, 5, 8):
        # every chunk in the longest one's 24 slots (padding tiles of the
        # empty row behind it), so that the four dispatches are one program
        pad = [(0, -1, 1)] * (24 - -(-n // 8) * 8)
        logits, *pools = _ragged(
            params, impl, pools, _stream([(PROMPT[at:at + n], at, 0)], tail=pad), table,
            ([1, 4], [n - 1, 0], [1, 4]), range(24),
        )
        got.append(_logp(logits)[:n])
        at += n
    want = reference.log_probs(params, HF, PROMPT[:at], range(at))
    assert np.abs(np.concatenate(got) - want).max() < LOGP_TOL
    k, v, st = pools
    for t in range(at, at + 4):
        logits, k, v, st = forward(
            params, CFG, jnp.asarray(PROMPT[t:t + 1])[None], jnp.asarray([[t]], jnp.int32),
            TABLE, k, v, seq_state=st, state_slots=jnp.asarray([1]),
            attn_impl=impl, interpret=True,
        )
        want = reference.log_probs(params, HF, PROMPT[:t + 1], [t])
        assert np.abs(_logp(logits[0]) - want).max() < LOGP_TOL


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_mixed_dispatch_of_spans_and_packed_decode_rows(params, impl):
    """One stream holding two prefill spans of different sequences (one
    from position 0, one resuming from the pool), a snapshot at a block
    boundary inside the first, then in the packed tail two decode rows of
    two more sequences and padding: each row's logits are its own uncut
    forward's, the snapshot continues, an entry no row owns is untouched."""
    seqs = [PROMPT[:13], PROMPT[20:48], PROMPT[50:60], PROMPT[60:67]]
    tables = jnp.stack([jnp.arange(1 + 2 * i, 3 + 2 * i) for i in range(5)]).astype(jnp.int32)
    k, v, st = _pools(entries=8)
    # Rows 1, 2, 3 have 16, 9 and 6 tokens behind them, states in entries 1, 2, 3.
    for r, n in ((1, 16), (2, 9), (3, 6)):  # each in 16 slots: one compiled program
        _, k, v, st = forward(
            params, CFG, jnp.asarray(seqs[r][:n] + [0] * (16 - n))[None],
            jnp.asarray(list(range(n)) + [-1] * (16 - n), jnp.int32)[None],
            tables[r:r + 1], k, v, seq_state=st, state_slots=jnp.asarray([r]),
        )
    st = jax.tree.map(lambda a: a.at[:, 7].set(7.0), st)
    # Tiles: row 0's 13 tokens (16 slots), row 1's next 12 (16 slots); tail
    # from slot 32: rows 2 and 3 one token each, then padding.
    stream = _stream(
        [(seqs[0], 0, 0), (seqs[1][16:28], 16, 1)],
        tail=[(seqs[2][9], 9, 2), (seqs[3][6], 6, 3)] + [(0, -1, 4)] * 6,
    )
    rows = (
        [0, 1, 2, 3, 8],  # row 4 is padding: past the pool
        [12, 16 + 11, 32, 33, 0, 7, 0, 0, 0, 0],
        [0, 1, 2, 3, 8, 5, 8, 8, 8, 8],  # + a snapshot of row 0 after its 8th token
    )
    logits, k2, v2, st2 = _ragged(
        params, impl, (k, v, st), stream, tables, rows, [12, 27, 32, 33], packed_from=32,
    )
    got = _logp(logits)
    for i, (seq, last) in enumerate(((seqs[0], 12), (seqs[1], 27), (seqs[2], 9), (seqs[3], 6))):
        want = reference.log_probs(params, HF, seq[:last + 1], [last])[0]
        assert np.abs(got[i] - want).max() < LOGP_TOL, i
    for part in jax.tree.leaves(st2):
        assert float(jnp.abs(part[:, 7] - 7.0).max()) == 0.0
    # The snapshot (entry 5) is row 0's state after 8 tokens: continue from it.
    logits, *_ = forward(
        params, CFG, jnp.asarray(seqs[0][8:13])[None], jnp.arange(8, 13, dtype=jnp.int32)[None],
        tables[:1], k2, v2, seq_state=st2, state_slots=jnp.asarray([5]),
    )
    want = reference.log_probs(params, HF, seqs[0], range(8, 13))
    assert np.abs(_logp(logits[0]) - want).max() < LOGP_TOL
    # A decode row goes on from the state the mixed dispatch left.
    logits, *_ = forward(
        params, CFG, jnp.asarray([[PROMPT[0]]]), jnp.asarray([[10]], jnp.int32),
        tables[2:3], k2, v2, seq_state=st2, state_slots=jnp.asarray([2]),
    )
    want = reference.log_probs(params, HF, seqs[2] + [PROMPT[0]], [10])
    assert np.abs(_logp(logits[0]) - want).max() < LOGP_TOL


def test_a_matrix_state_left_at_zero_shows(params):
    """What the tolerance is far below: the second chunk computed from a
    zero matrix state (the convolutions' inputs kept)."""
    T, cut = 24, 8
    want = reference.log_probs(params, HF, PROMPT[:T], range(cut, T))
    k, v, st = _pools()
    _, k, v, st = forward(
        params, CFG, jnp.asarray(PROMPT[:cut])[None], jnp.arange(cut, dtype=jnp.int32)[None],
        TABLE, k, v, seq_state=st, state_slots=jnp.asarray([1]),
    )
    logits, *_ = forward(
        params, CFG, jnp.asarray(PROMPT[cut:T])[None], jnp.arange(cut, T, dtype=jnp.int32)[None],
        TABLE, k, v, seq_state=st._replace(state=jnp.zeros_like(st.state)),
        state_slots=jnp.asarray([1]),
    )
    assert np.abs(_logp(logits[0]) - want).max() > 10 * LOGP_TOL


def test_attention_kernel_at_one_query_head_a_kv_head(params):
    """The ragged attention kernel (interpret) at as many KV heads as
    heads, through the full layer of the stack: the reference's numbers."""
    pools = _pools()
    n = 40
    logits, *_ = _ragged(
        params, "pallas", pools, _stream([(PROMPT[:n], 0, 0)]),
        jnp.concatenate([TABLE, TABLE * 0]), ([1, 4], [n - 1, 0], [1, 4]), range(n),
    )
    want = reference.log_probs(params, HF, PROMPT[:n], range(n))
    assert CFG.q_per_kv == 1
    assert np.abs(_logp(logits) - want).max() < LOGP_TOL


# ------------------------------------------------------------------ engine
def _engine(**kw) -> TPUEngine:
    from dynamo_exp_tpu.parallel import single_device_mesh

    cfg = EngineConfig(
        model=CFG, max_decode_slots=4, page_size=PS, num_pages=kw.pop("num_pages", 64),
        max_model_len=kw.pop("max_model_len", 256), prefill_chunk=16, decode_window=8,
        kv_dtype="float32",
        eos_token_ids=[], preempt_stall_grace_s=0.05, **kw,
    )
    return TPUEngine(cfg, mesh=single_device_mesh(), seed=SEED)


@pytest.fixture(scope="module", params=["xla", "pallas"])
def engine(request):
    # a table of 4 pages: no sequence of these tests passes 60 tokens
    eng = _engine(max_model_len=64, pallas_interpret=request.param == "pallas")
    assert eng._attn_impl == request.param
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


async def test_engine_serves_windows_and_chunks(engine):
    """Through the scheduler: a 40-token prompt in chunks of 16 (mixed
    program, the state crossing two dispatch boundaries), then 20 tokens
    through 8-step windows, equal to the reference's greedy continuation."""
    assert engine.seq_state.state.shape == (4, 20, 8, 128)
    assert engine.seq_state.conv.shape == (4, 20, 3, 160)
    assert engine.seq_state.state.dtype == jnp.float32
    assert engine.k_cache.shape[0] == 1  # pages for the attention layer alone
    got = await _run(engine, PROMPT[:40], 20)
    assert got == _greedy_reference(engine.params, PROMPT[:40], 20)


async def test_engine_logits_are_the_references(engine):
    """Logits, not tokens: the log-probabilities the engine serves for a
    prompt prefilled in chunks and then decoded, against the reference's
    full forward over the same tokens."""
    b = BackendInput(token_ids=list(PROMPT[3:40]))
    b.stop_conditions.max_tokens = 10
    b.stop_conditions.ignore_eos = True
    b.sampling_options.logprobs = 1
    stream = await engine.generate(b.to_dict())
    tokens, logps = [], []
    async for item in stream:
        tokens.extend(item.get("token_ids", []))
        logps.extend(item.get("logprobs") or [])
    assert len(tokens) == 10 and len(logps) == 10
    ids = list(PROMPT[3:40]) + tokens
    want = reference.log_probs(engine.params, HF, ids, range(36, 46))
    for i, (tok, lp) in enumerate(zip(tokens, logps)):
        assert abs(float(lp) - float(want[i, tok])) < LOGP_TOL, i


async def test_concurrent_requests_share_mixed_dispatches(engine):
    """Three requests at once: prompt chunks of some ride with decode rows
    of others in one stream (the packed tail on the kernel engine)."""
    prompts = [PROMPT[:9], PROMPT[10:29], PROMPT[30:52]]
    got = await asyncio.gather(*(_run(engine, p, 30) for p in prompts))
    for p, g in zip(prompts, got):
        assert g == _greedy_reference(engine.params, p, 30)


async def test_a_decode_row_beside_a_prompt_chunk_takes_the_one_token_step(engine):
    """A request admitted while another decodes: its chunks ride with that
    one's decode row, which on the kernel engine lies in the packed tail,
    takes the delta rule's one-token step and is counted; the reference's
    stream is the oracle's, walked whole. Both streams stay the reference's."""
    from dynamo_exp_tpu.telemetry import get_telemetry

    tail_steps = get_telemetry().linear_attn_step_rows.labels("mixed")._value
    before = tail_steps.get()
    first, second = PROMPT[:9], PROMPT[10:60]
    b = BackendInput(token_ids=list(first))
    b.stop_conditions.max_tokens = 40
    b.stop_conditions.ignore_eos = True
    stream = await engine.generate(b.to_dict())
    got, other = [], None
    async for item in stream:
        got.extend(item.get("token_ids", []))
        if other is None:
            other = asyncio.ensure_future(_run(engine, second, 4))
    assert got == _greedy_reference(engine.params, first, 40)
    assert await other == _greedy_reference(engine.params, second, 4)
    assert (tail_steps.get() > before) == (engine._attn_impl == "pallas")


@pytest.mark.parametrize("asking", ["again", "longer"])
async def test_second_asking_restores_both_parts(engine, asking):
    """A prompt whose head is in cached pages is served from them with
    both parts of the state that belongs to the hit's last token."""
    base = PROMPT[5:38]  # 33 tokens: boundaries 16 (a chunk's end) and 32
    await _run(engine, base, 4)
    before = dict(engine.kv.state_hits), engine.kv.prefix_hits["shared"]
    prompt = base if asking == "again" else base + PROMPT[:7]
    got = await _run(engine, prompt, 12)
    assert got == _greedy_reference(engine.params, prompt, 12)
    assert engine.kv.state_hits["restored"] == before[0]["restored"] + 1
    assert engine.kv.prefix_hits["shared"] - before[1] == 32 // PS  # 32 of its tokens


@pytest.mark.parametrize("impl", ["xla", "pallas"])
async def test_preempt_and_resume(impl):
    """Two requests on a pool that holds one and a half: one is preempted
    and re-admitted with its state restored or recomputed; both streams
    equal the reference's and every entry is handed back."""
    # 96 tokens of pool; a table of 4 pages (no sequence here passes 53 tokens)
    eng = _engine(num_pages=96 // PS, max_model_len=64, pallas_interpret=impl == "pallas")
    eng.start()
    try:
        p1, p2, n = PROMPT[:9], PROMPT[10:19], 44
        r1, r2 = await asyncio.gather(_run(eng, p1, n), _run(eng, p2, n))
        assert eng.preempted > 0
        assert r1 == _greedy_reference(eng.params, p1, n)
        assert r2 == _greedy_reference(eng.params, p2, n)
        assert eng.kv._held_state == 0 and not eng.kv.ledger_check()
    finally:
        eng.stop()


async def test_linear_tokens_are_counted_and_the_pool_is_published(engine):
    from dynamo_exp_tpu.telemetry import get_telemetry

    tel = get_telemetry()
    nbytes = sum(a.nbytes for a in jax.tree.leaves(engine.seq_state))
    assert nbytes == 4 * 20 * (8 * 128 * 4 + 3 * 160 * 4)
    assert tel.seq_state_pool_bytes._value.get() == nbytes
    before = tel.linear_attn_tokens.labels("mixed")._value.get()
    scans = tel.ssm_scan_tokens.labels("mixed")._value.get()
    await _run(engine, PROMPT[40:60], 2)
    assert tel.linear_attn_tokens.labels("mixed")._value.get() >= before + 20
    assert tel.ssm_scan_tokens.labels("mixed")._value.get() == scans
    assert b"dynamo_linear_attn_tokens_total" in tel.render()
    # every live slot of a window takes the one-token step
    assert tel.linear_attn_step_rows.labels("window")._value.get() == (
        tel.linear_attn_tokens.labels("window")._value.get()
    )
    assert b"dynamo_linear_attn_step_rows_total" in tel.render()


# ----------------------------------------------------------------- refusals
@pytest.mark.parametrize("tier", [
    "host_cache_pages", "spec_mode", "tp", "disaggregated transfer", "migration",
])
def test_what_cannot_carry_the_state_refuses(tier):
    kw = {
        "host_cache_pages": {"host_cache_pages": 8}, "spec_mode": {"spec_mode": "ngram"},
        "tp": {"tp": 2},
    }.get(tier, {})
    cfg = EngineConfig(model=CFG, max_decode_slots=2, page_size=PS, num_pages=16, **kw)
    with pytest.raises(ValueError, match="per-sequence state.*linear-attention"):
        refuse_seq_state_tiers(cfg, None if kw else tier)
    if tier == "tp":
        with pytest.raises(ValueError, match=r"tp > 1\) of linear attention"):
            refuse_seq_state_tiers(cfg)


def test_ring_prefill_refuses_a_model_with_state(params):
    with pytest.raises(ValueError, match="ring prefill walks attention layers alone"):
        llama.forward_ring_prefill(
            params, CFG, jnp.zeros((1, 8), jnp.int32), jnp.arange(8)[None],
            jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("sp", "tp")),
        )


def test_a_pool_holds_one_kinds_parts():
    both = dataclasses.replace(CFG, layer_types=(LINEAR, "mamba", LINEAR, FULL, LINEAR))
    with pytest.raises(ValueError, match="mamba and linear layers has no state pool"):
        llama.init_seq_state(both, 2)


# -------------------------------------------------- what stayed what it was
def _bench_config(name: str) -> ModelConfig:
    import json

    path = os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs", f"{name}.json")
    with open(path) as f:
        hf = json.load(f)
    return ModelConfig.from_hf_config(hf)


@pytest.mark.parametrize("name,runs,state", [
    ("qwen3-4b", [("attn", "dense", 0, 36, 0, 0)], None),
    ("jamba2-3b", [
        ("mamba", "dense", 0, 7, 0, 0), ("attn", "dense", 7, 1, 0, 7),
        ("mamba", "dense", 8, 13, 7, 8), ("attn", "dense", 21, 1, 1, 21),
        ("mamba", "dense", 22, 6, 20, 22),
    ], [((26, 3, 3, 5120), "bfloat16"), ((26, 3, 16, 5120), "float32")]),
    ("lfm2-8b-a1b-l16", None, [((12, 3, 2, 2048), "bfloat16")]),
])
def test_other_families_resolve_to_what_they_were(name, runs, state):
    """The fourth operator kind changed no other family's resolved
    programs: their runs of layers, their state pool's shapes and their
    parameter stacks are what they were before it."""
    cfg = _bench_config(name)
    assert "linear" not in cfg.op_kinds and cfg.num_linear_layers == 0
    assert cfg.pre_norms and not cfg.qk_norm_whole
    if runs is not None:
        assert list(cfg.layer_runs) == runs
    else:  # lfm2's cut: conv and attention runs, dense then routed
        assert {r[0] for r in cfg.layer_runs} == {"conv", "attn"}
        assert sum(r[3] for r in cfg.layer_runs if r[0] == "conv") == 12
    pool = jax.eval_shape(lambda: llama.init_seq_state(cfg, 3))
    got = None if pool is None else [(x.shape, str(x.dtype)) for x in jax.tree.leaves(pool)]
    assert got == state
    shapes = jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    assert not any(k.startswith("gdn_") for k in shapes["layers"])
    assert "attn_norm" in shapes["layers"] and "mlp_norm" in shapes["layers"]


def test_the_lattice_has_no_new_axis():
    """tiny-olmo-hybrid has the variant keys of a model of one kind, as
    tiny-lfm2 and tiny-jamba have (``tests/test_jamba.py`` pins the 80)."""
    from dynamo_exp_tpu.aot.lattice import build_manifest

    keys = {}
    for name in ("tiny-qwen3", "tiny-lfm2", "tiny-jamba", "tiny-olmo-hybrid"):
        cfg = EngineConfig(
            model=PRESETS[name], max_decode_slots=64, page_size=16, num_pages=1152,
            max_model_len=4096,
        )
        keys[name] = sorted(map(str, build_manifest(
            cfg, "pallas", {"tp": 1}, "x", interpret=False
        ).ragged_keys()))
    assert keys["tiny-olmo-hybrid"] == keys["tiny-qwen3"] == keys["tiny-jamba"] == keys["tiny-lfm2"]
    assert len(keys["tiny-olmo-hybrid"]) == 80


def test_jamba_and_lfm2_scan_forms_stand():
    """``_scan_form`` still sends jamba to its scan kernel where the widths
    tile and lfm2 to none."""
    assert llama._scan_form(TINY_JAMBA, "pallas", 8, 32, False) == llama.ScanForm(True, 32, False, False)
    assert llama._scan_form(TINY_JAMBA, "xla", 8, 32, False) == llama.ScanForm(False, None, False, False)
    assert llama._scan_form(TINY_LFM2, "pallas", 8, None, False) == llama.ScanForm(False, None, False, False)
    assert llama._scan_form(CFG, "pallas", 8, 32, True) == llama.ScanForm(True, 32, False, True)
    assert llama._scan_form(CFG, "pallas", 4, None, False).kernel is False


# ------------------------------------------------------------------ loader
def test_checkpoint_names_round_trip(tmp_path):
    """A checkpoint under the assumed tensor names (Olmo 2's and
    flash-linear-attention's) loads into the stacks by kind: written from
    seeded parameters, read back equal; ``A_log`` and ``dt_bias`` stay
    float32."""
    from safetensors.numpy import save_file

    from dynamo_exp_tpu.models.loader import load_params
    from dynamo_exp_tpu.models.synth import write_config

    cfg = TINY_OLMO_HYBRID  # bfloat16: the float32 parameters must stay so
    params = init_params(jax.random.PRNGKey(SEED), cfg)
    as_np = lambda x: np.asarray(x.astype(jnp.float32))  # noqa: E731
    layers = {k: as_np(v) for k, v in params["layers"].items()}
    out = {
        "model.embed_tokens.weight": as_np(params["embed"]),
        "model.norm.weight": as_np(params["final_norm"]),
        "lm_head.weight": as_np(params["lm_head"]).T,
    }
    H, dk, dv = cfg.linear_num_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    cuts = {"q": slice(0, H * dk), "k": slice(H * dk, 2 * H * dk), "v": slice(2 * H * dk, None)}
    nth = {"linear": 0, "attn": 0}
    for i, op in enumerate(cfg.op_kinds):
        p = f"model.layers.{i}."
        out[p + "post_attention_layernorm.weight"] = layers["post_attn_norm"][i]
        out[p + "post_feedforward_layernorm.weight"] = layers["post_ffn_norm"][i]
        for key, name in (("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj")):
            out[f"{p}mlp.{name}.weight"] = layers[key][i].T
        o = nth[op]
        nth[op] += 1
        if op == "attn":
            for key, name in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"), ("wo", "o_proj")):
                out[f"{p}self_attn.{name}.weight"] = layers[key][o].T
            out[p + "self_attn.q_norm.weight"] = layers["q_norm"][o]
            out[p + "self_attn.k_norm.weight"] = layers["k_norm"][o]
            continue
        m = p + "linear_attn."
        for x, cut in cuts.items():
            out[f"{m}{x}_proj.weight"] = layers["gdn_qkv"][o][:, cut].T
            out[f"{m}{x}_conv1d.weight"] = layers["gdn_conv_w"][o][:, cut].T[:, None, :]
        out[m + "a_proj.weight"] = layers["gdn_ab"][o][:, :H].T
        out[m + "b_proj.weight"] = layers["gdn_ab"][o][:, H:].T
        out[m + "A_log"] = layers["gdn_A_log"][o]
        out[m + "dt_bias"] = layers["gdn_dt_b"][o]
        out[m + "g_proj.weight"] = layers["gdn_gate"][o].T
        out[m + "o_norm.weight"] = layers["gdn_norm"][o]
        out[m + "o_proj.weight"] = layers["gdn_out"][o].T
    save_file({k: np.ascontiguousarray(v) for k, v in out.items()}, str(tmp_path / "model.safetensors"))
    write_config(str(tmp_path), cfg)
    loaded, got_cfg = load_params(str(tmp_path))
    assert got_cfg == cfg
    assert set(loaded["layers"]) == set(params["layers"])
    for k, v in params["layers"].items():
        assert loaded["layers"][k].dtype == v.dtype, k
        np.testing.assert_array_equal(
            np.asarray(loaded["layers"][k], np.float32), as_np(v), err_msg=k
        )
    for name in ("gdn_A_log", "gdn_dt_b"):
        assert loaded["layers"][name].dtype == np.float32
    np.testing.assert_array_equal(np.asarray(loaded["lm_head"], np.float32), as_np(params["lm_head"]))
