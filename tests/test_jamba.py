"""jamba (AI21-Jamba2-3B) on the normal path: a selective-scan (Mamba-1)
layer kind with its chunked scan kernel, a recurrent state of two parts
beside the paged KV, attention without rotary positions. Tiny sizes,
seeded weights, float32, so the engine and the plain reference
(``benchmark/references/jamba.py``) differ by float32 rounding alone.

TOLERANCES. Both sides compute in float32 with the reference at
``highest`` matmul precision and the engine at the CPU's default (also
float32 products): log-probabilities agree to ~1e-5, so ``LOGP_TOL`` 1e-3
stands a hundred times above the rounding and far below any fault (a scan
state left at zero moves them by 1e-2 and more: the test that zeroes it
says so). The kernel in interpret mode against its oracle: the same
float32 operations in another order, ``KERNEL_TOL`` 1e-5 on values of
order 1. Token streams are compared exactly: float32 logits of random
weights tie nowhere.
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
from dynamo_exp_tpu.models import ModelConfig, llama
from dynamo_exp_tpu.models.config import TINY_JAMBA, TINY_LFM2
from dynamo_exp_tpu.ops import selective_scan as ss
from dynamo_exp_tpu.protocols.common import BackendInput

# Jitted: a shape and set of options compiles once a file (tests/programs.py).
from .programs import forward, forward_ragged, init_params

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmark"))
from references import jamba as reference  # noqa: E402

CFG = dataclasses.replace(TINY_JAMBA, dtype="float32")
HF = {
    "model_type": "jamba", "attn_layer_period": 4, "attn_layer_offset": 1,
    "expert_layer_offset": 1, "expert_layer_period": 2, "hidden_act": "silu",
    "hidden_size": CFG.hidden_size, "intermediate_size": CFG.intermediate_size,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 8,
    "mamba_expand": 2, "mamba_proj_bias": False, "max_position_embeddings": 512,
    "num_attention_heads": CFG.num_heads, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": CFG.num_layers, "num_key_value_heads": 1, "num_logits_to_keep": 1,
    "rms_norm_eps": 1e-6, "sliding_window": None, "tie_word_embeddings": True,
    "use_mamba_kernels": True, "vocab_size": CFG.vocab_size, "torch_dtype": "float32",
}
# The catalog's row of AI21-Jamba2-3B (model-configs guide), every key.
CATALOG = {
    "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
    "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba", "num_attention_heads": 20,
    "num_experts": 1, "num_experts_per_tok": 1, "num_hidden_layers": 28,
    "num_key_value_heads": 1, "num_logits_to_keep": 1, "rms_norm_eps": 1e-06,
    "sliding_window": None, "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536,
}
PS = 8
LOGP_TOL = 1e-3
KERNEL_TOL = 1e-5
SEED = 3
PROMPT = [int(t) for t in np.random.default_rng(0).integers(2, 256, 80)]
# The forwards called outright read pools of pages of 16 on tables of 4 (64
# tokens a row: every context here is shorter): a chunk of the interpreted
# kernel is then 4 page copies to lower, not 16 (tests/ragged_cases.py
# "SHAPES"). The engines keep pages of ``PS`` (the snapshot test counts them).
FPS = 16
TABLE = jnp.arange(4, dtype=jnp.int32)[None] + 1


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(SEED), CFG)


def _pools(entries=4):
    k, v = llama.init_kv_cache(CFG, 17, FPS, dtype=jnp.float32)
    return k, v, llama.init_seq_state(CFG, entries)


def _logp(logits):
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


# ------------------------------------------------------------------ config
def test_config_reads_the_published_keys():
    assert ModelConfig.from_hf_config(HF) == CFG
    assert CFG.op_kinds == ("mamba", "attn", "mamba", "mamba")
    assert CFG.layer_runs == (
        ("mamba", "dense", 0, 1, 0, 0), ("attn", "dense", 1, 1, 0, 1),
        ("mamba", "dense", 2, 2, 1, 2),
    )
    assert CFG.has_seq_state and not CFG.use_rope and CFG.mamba_inner == 128


def test_catalog_row_builds_the_whole_model():
    """``from_hf_config`` builds Jamba2-3B from the catalog's keys alone:
    five runs (7 mamba, attn, 13 mamba, attn, 6 mamba), the published
    widths, and the parameter count the issue reckons."""
    cfg = ModelConfig.from_hf_config(CATALOG)
    assert [(r[0], r[3]) for r in cfg.layer_runs] == [
        ("mamba", 7), ("attn", 1), ("mamba", 13), ("attn", 1), ("mamba", 6),
    ]
    assert (cfg.num_mamba_layers, cfg.num_attn_layers, cfg.mamba_inner) == (26, 2, 5120)
    assert (cfg.head_dim_, cfg.num_kv_heads, cfg.q_per_kv) == (128, 1, 20)
    assert cfg.mamba_dt_rank == 160 and cfg.tie_word_embeddings and not cfg.is_moe
    shapes = jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) == 3_029_337_472
    state = jax.eval_shape(lambda: llama.init_seq_state(cfg, 1))
    assert sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state)) == 26 * (
        5120 * 16 * 4 + 3 * 5120 * 2
    )
    assert state.ssm.dtype == jnp.float32 and state.conv.dtype == jnp.bfloat16


@pytest.mark.parametrize("key,value", [
    ("num_experts", 16), ("mamba_proj_bias", True), ("mamba_conv_bias", False),
    ("sliding_window", 4096),
])
def test_unsupported_jamba_keys_raise(key, value):
    with pytest.raises(ValueError, match="not supported"):
        ModelConfig.from_hf_config({**HF, key: value})


def test_random_mamba_parameters_are_the_familys(params):
    """``A_log = log(1..S)`` a channel, ``D = 1``, the step's bias the
    inverse softplus of a step in ``MAMBA_DT_RANGE``; all three float32."""
    layers = params["layers"]
    a = np.asarray(jnp.exp(layers["mamba_A_log"]))
    assert a.shape == (3, 16, 128) and np.allclose(a[0, :, 5], np.arange(1, 17), rtol=1e-6)
    assert float(jnp.abs(layers["mamba_D"] - 1).max()) == 0
    step = np.asarray(jax.nn.softplus(layers["mamba_dt_b"]))
    lo, hi = llama.MAMBA_DT_RANGE
    assert step.min() >= lo * 0.999 and step.max() <= hi * 1.001
    bf16 = init_params(jax.random.PRNGKey(SEED), TINY_JAMBA)["layers"]
    for name in ("mamba_A_log", "mamba_D", "mamba_dt_b"):
        assert bf16[name].dtype == jnp.float32
    assert bf16["mamba_in"].dtype == jnp.bfloat16


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
    with ``pallas`` the scan kernel in interpret mode), then decode steps
    through ``forward``: the uncut forward's logits throughout."""
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


def test_a_scan_state_left_at_zero_shows(params):
    """What the tolerance is far below: the second chunk computed from a
    zero scan state (the convolution's inputs kept)."""
    T, cut = 24, 8
    want = reference.log_probs(params, HF, PROMPT[:T], range(cut, T))
    k, v, st = _pools()
    _, k, v, st = forward(
        params, CFG, jnp.asarray(PROMPT[:cut])[None], jnp.arange(cut, dtype=jnp.int32)[None],
        TABLE, k, v, seq_state=st, state_slots=jnp.asarray([1]),
    )
    logits, *_ = forward(
        params, CFG, jnp.asarray(PROMPT[cut:T])[None], jnp.arange(cut, T, dtype=jnp.int32)[None],
        TABLE, k, v, seq_state=st._replace(ssm=jnp.zeros_like(st.ssm)),
        state_slots=jnp.asarray([1]),
    )
    assert np.abs(_logp(logits[0]) - want).max() > 10 * LOGP_TOL


# ------------------------------------------------------------------ kernel
def _scan_case(rng, spans, e=256, s=16, layers=2, entries=6):
    """A ragged stream of ``spans`` = (row, first position, tokens), each
    padded to the tile, then one tile of padding."""
    row_of, pos = [], []
    for r, p0, n in spans:
        pad = -n % ss.TILE
        row_of += [r] * (n + pad)
        pos += list(range(p0, p0 + n)) + [-1] * pad
    row_of += [len(spans)] * ss.TILE
    pos += [-1] * ss.TILE
    n = len(pos)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)  # noqa: E731
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (n, e))), jnp.float32)
    a = -jnp.tile(jnp.arange(1, s + 1, dtype=jnp.float32)[:, None], (1, e))
    return dict(
        x=f(n, e), dt=dt, a=a, b=f(n, s), c=f(n, s), d=jnp.ones((e,), jnp.float32),
        pool=f(layers, entries, s, e), row_of=jnp.asarray(row_of, jnp.int32),
        positions=jnp.asarray(pos, jnp.int32),
    )


@pytest.mark.parametrize("spans,src,dst", [
    ([(0, 0, 8)], [7], [0]),  # a span of one tile, from position 0
    ([(0, 40, 5)], [4], [0]),  # resuming from the pool, ending inside a tile
    ([(0, 0, 16), (1, 40, 5), (2, 8, 8), (3, 0, 3)], [15, 20, 31, 34, 7], [0, 1, 2, 3, 5]),
    ([(0, 16, 48)], [47, 31], [0, 4]),  # a snapshot at a block boundary inside the span
])
def test_scan_kernel_matches_its_oracle(spans, src, dst):
    """The kernel's own code in interpret mode against the ``jax.numpy``
    oracle at ragged spans: outputs at every real slot, every pool entry
    (written or not), the other layer untouched."""
    case = _scan_case(np.random.default_rng(len(spans)), spans)
    entries = case["pool"].shape[1]
    slots = jnp.asarray([r for r, _, _ in spans] + [entries], jnp.int32)
    pad = [0] * (2 * len(slots) - len(src))
    io = dict(
        slots=slots, src=jnp.asarray(src + pad, jnp.int32),
        dst=jnp.asarray(dst + [entries] * len(pad), jnp.int32),
    )
    args = [case[k] for k in ("x", "dt", "a", "b", "c", "d", "pool")]
    tail = (io["slots"], case["row_of"], case["positions"], io["src"], io["dst"])
    y_ref, pool_ref = ss.selective_scan_ref(*args, 1, *tail)
    y, pool = ss.selective_scan_kernel(*args, jnp.int32(1), *tail, interpret=True)
    live = np.asarray(case["positions"]) >= 0
    assert np.abs(np.asarray(y) - np.asarray(y_ref))[live].max() < KERNEL_TOL
    assert np.abs(np.asarray(pool) - np.asarray(pool_ref)).max() < KERNEL_TOL
    assert np.array_equal(np.asarray(pool[0]), np.asarray(case["pool"][0]))
    assert pool.dtype == jnp.float32
    written = sorted(set(dst))
    untouched = [e for e in range(entries) if e not in written]
    assert np.array_equal(np.asarray(pool[1, untouched]), np.asarray(case["pool"][1, untouched]))


def test_scan_step_matches_its_oracle():
    case = _scan_case(np.random.default_rng(9), [(0, 0, 8)])
    r = 4
    args = [case[k][:r] if k in ("x", "dt", "b", "c") else case[k]
            for k in ("x", "dt", "a", "b", "c", "d", "pool")]
    pos = jnp.asarray([0, 5, 9, -1], jnp.int32)
    slots = jnp.asarray([0, 1, 2, 6], jnp.int32)
    y, pool = ss.selective_scan_step(*args, 0, slots, pos, slots)
    y_ref, pool_ref = ss.selective_scan_ref(
        *args, 0, slots, jnp.arange(r), pos, jnp.arange(r), slots
    )
    assert np.abs(np.asarray(y) - np.asarray(y_ref))[:3].max() < KERNEL_TOL
    assert np.abs(np.asarray(pool) - np.asarray(pool_ref)).max() < KERNEL_TOL


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("slots", [4096, 64])
def test_scan_kernel_compiles_for_v5e_at_the_published_widths(one_chip, slots):
    """Mosaic takes the kernel at Jamba2-3B's widths (E 5120, S 16, the
    pool of 26 layers x 320 entries) for a 4096-slot mixed dispatch and a
    64-slot one, and the pool is updated in place (aliased, no copy of
    2.7 GB among the temporaries)."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    f, i = jnp.float32, jnp.int32
    n, e, s = slots, 5120, 16
    pool = (26, 320, s, e)
    args = (
        sds((n, e), f), sds((n, e), f), sds((s, e), f), sds((n, s), f), sds((n, s), f),
        sds((e,), f), sds(pool, f), sds((), i), sds((65,), i), sds((n,), i), sds((n,), i),
        sds((130,), i), sds((130,), i),
    )
    compiled = jax.jit(ss.selective_scan_kernel, donate_argnums=(6,)).lower(*args).compile()
    mem = compiled.memory_analysis()
    pool_bytes = int(np.prod(pool)) * 4
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 8
    assert "ssm_scan" in compiled.as_text()


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
    eng = _engine(pallas_interpret=request.param == "pallas")
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
    assert engine.seq_state.ssm.shape == (3, 20, 16, 128)
    assert engine.seq_state.conv.shape == (3, 20, 3, 128)
    assert engine.seq_state.ssm.dtype == jnp.float32
    assert engine.k_cache.shape[0] == 1  # pages for the attention layer alone
    got = await _run(engine, PROMPT[:40], 20)
    assert got == _greedy_reference(engine.params, PROMPT[:40], 20)


async def test_concurrent_requests_share_mixed_dispatches(engine):
    """Three requests at once: prompt chunks of some ride with decode rows
    of others in one stream (the packed tail on the kernel engine)."""
    prompts = [PROMPT[:9], PROMPT[10:29], PROMPT[30:52]]
    got = await asyncio.gather(*(_run(engine, p, 30) for p in prompts))
    for p, g in zip(prompts, got):
        assert g == _greedy_reference(engine.params, p, 30)


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
    assert engine.kv.prefix_hits["shared"] - before[1] == 4  # 32 of its tokens


async def test_a_snapshot_an_extended_chunk_wrote_restores(engine):
    """A chunk that took its bucket's empty page leaves its snapshot where
    chunks of ``prefill_chunk`` would not have, and a second asking
    restores from it (``tests/test_chunk_fill.py`` has the steps)."""
    from .test_chunk_fill import restore_from_an_extended_chunks_snapshot

    await restore_from_an_extended_chunks_snapshot(
        engine, _run, _greedy_reference, PROMPT[50:59], PROMPT[2:43]
    )


@pytest.mark.parametrize("impl", ["xla", "pallas"])
async def test_preempt_and_resume(impl):
    """Two requests on a pool that holds one and a half: one is preempted
    and re-admitted with its state restored or recomputed; both streams
    equal the reference's and every entry is handed back."""
    # a table of 8 pages (no sequence here passes 53 tokens): 8 page copies a
    # chunk of the interpreted kernel to lower, not 16
    eng = _engine(num_pages=12, max_model_len=64, pallas_interpret=impl == "pallas")
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


async def test_scan_tokens_are_counted_and_the_pool_is_published(engine):
    from dynamo_exp_tpu.telemetry import get_telemetry

    tel = get_telemetry()
    nbytes = sum(a.nbytes for a in jax.tree.leaves(engine.seq_state))
    assert nbytes == 3 * 20 * (16 * 128 * 4 + 3 * 128 * 4)
    assert tel.seq_state_pool_bytes._value.get() == nbytes
    before = tel.ssm_scan_tokens.labels("mixed")._value.get()
    await _run(engine, PROMPT[40:60], 2)
    assert tel.ssm_scan_tokens.labels("mixed")._value.get() >= before + 20
    assert b"dynamo_ssm_scan_tokens_total" in tel.render()


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
    with pytest.raises(ValueError, match="per-sequence state.*selective-scan"):
        refuse_seq_state_tiers(cfg, None if kw else tier)
    # tp > 1 is refused for the scan alone: a convolution's state shards nowhere either
    # way, and lfm2_moe serves under tp.
    if tier == "tp":
        refuse_seq_state_tiers(dataclasses.replace(cfg, model=TINY_LFM2))


def test_ring_prefill_refuses_a_model_with_state(params):
    with pytest.raises(ValueError, match="ring prefill walks attention layers alone"):
        llama.forward_ring_prefill(
            params, CFG, jnp.zeros((1, 8), jnp.int32), jnp.arange(8)[None],
            jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("sp", "tp")),
        )


def test_kernel_engine_refuses_a_page_size_the_tiles_do_not_divide():
    from dynamo_exp_tpu.parallel import single_device_mesh

    cfg = EngineConfig(
        model=CFG, max_decode_slots=2, page_size=4, num_pages=16, max_model_len=64,
        prefill_chunk=16, kv_dtype="float32", pallas_interpret=True,
    )
    with pytest.raises(ValueError, match="multiples of ragged_q_tile"):
        TPUEngine(cfg, mesh=single_device_mesh(), seed=SEED)


# -------------------------------------------------- what stayed what it was
def test_a_convolution_models_pool_is_one_array_and_its_lattice_stands():
    """lfm2_moe's pool is the bare array it was (the benchmark's
    ``layer_kinds`` maps it with ``jnp.zeros_like``), and the state's
    generalisation added no axis to the variant lattice: tiny-lfm2 and
    tiny-jamba have the keys of a model of one kind."""
    from dynamo_exp_tpu.aot.lattice import build_manifest
    from dynamo_exp_tpu.models import PRESETS

    pool = llama.init_seq_state(TINY_LFM2, 5)
    assert isinstance(pool, jax.Array) and pool.shape == (4, 5, 2, 64)
    assert llama.seq_state_entries(pool) == 5
    keys = {}
    for name in ("tiny-qwen3", "tiny-lfm2", "tiny-jamba"):
        cfg = EngineConfig(
            model=PRESETS[name], max_decode_slots=64, page_size=16, num_pages=1152,
            max_model_len=4096,
        )
        keys[name] = sorted(map(str, build_manifest(
            cfg, "pallas", {"tp": 1}, "x", interpret=False
        ).ragged_keys()))
    assert keys["tiny-lfm2"] == keys["tiny-qwen3"] == keys["tiny-jamba"]
    assert len(keys["tiny-jamba"]) == 80


# ------------------------------------------------------------------ loader
def test_checkpoint_names_round_trip(tmp_path, params):
    """A checkpoint under AI21's tensor names loads into the stacks by
    kind: written from seeded parameters, read back equal; ``A_log``
    (held transposed), ``D`` and the step's bias stay float32."""
    from safetensors.numpy import save_file

    from dynamo_exp_tpu.models.loader import load_params
    from dynamo_exp_tpu.models.synth import write_config

    cfg = TINY_JAMBA  # bfloat16: the float32 parameters must stay so
    params = init_params(jax.random.PRNGKey(SEED), cfg)
    as_np = lambda x: np.asarray(x.astype(jnp.float32))  # noqa: E731
    layers = {k: as_np(v) for k, v in params["layers"].items()}
    out = {
        "model.embed_tokens.weight": as_np(params["embed"]),
        "model.final_layernorm.weight": as_np(params["final_norm"]),
    }
    nth = {"mamba": 0, "attn": 0}
    for i, op in enumerate(cfg.op_kinds):
        p = f"model.layers.{i}."
        out[p + "input_layernorm.weight"] = layers["attn_norm"][i]
        out[p + "pre_ff_layernorm.weight"] = layers["mlp_norm"][i]
        for key, name in (("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj")):
            out[f"{p}feed_forward.{name}.weight"] = layers[key][i].T
        o = nth[op]
        nth[op] += 1
        if op == "attn":
            for key, name in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"), ("wo", "o_proj")):
                out[f"{p}self_attn.{name}.weight"] = layers[key][o].T
            continue
        m = p + "mamba."
        out[m + "in_proj.weight"] = layers["mamba_in"][o].T
        out[m + "conv1d.weight"] = layers["mamba_conv_w"][o].T[:, None, :]
        out[m + "conv1d.bias"] = layers["mamba_conv_b"][o]
        out[m + "x_proj.weight"] = layers["mamba_x"][o].T
        out[m + "dt_proj.weight"] = layers["mamba_dt_w"][o].T
        out[m + "dt_proj.bias"] = layers["mamba_dt_b"][o]
        out[m + "A_log"] = layers["mamba_A_log"][o].T  # published [E, S]
        out[m + "D"] = layers["mamba_D"][o]
        for key, name in (("mamba_dt_norm", "dt"), ("mamba_b_norm", "b"), ("mamba_c_norm", "c")):
            out[f"{m}{name}_layernorm.weight"] = layers[key][o]
        out[m + "out_proj.weight"] = layers["mamba_out"][o].T
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
    for name in ("mamba_A_log", "mamba_D", "mamba_dt_b"):
        assert loaded["layers"][name].dtype == np.float32
    np.testing.assert_array_equal(
        np.asarray(loaded["embed"], np.float32), as_np(params["embed"])
    )
