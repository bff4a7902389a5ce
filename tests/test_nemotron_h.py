"""nemotron_h (NVIDIA-Nemotron-3-Nano-30B-A3B) on the normal path: layers of
one half (a Mamba-2 mixer, routed experts or attention each), the Mamba-2
recurrence's four forms with a float32 matrix state beside the paged KV,
experts of two matrices (``relu(up x)^2 down``) beside a wide shared expert,
a held share of a 128-wide router. Tiny sizes, seeded weights, float32, so
the engine and the plain reference (``benchmark/references/nemotron_h.py``)
differ by float32 rounding alone.

TOLERANCES. Both sides compute in float32: log-probabilities agree to ~1e-5,
so ``LOGP_TOL`` 1e-3 stands a hundred times above the rounding and far below
any fault (a state left at zero moves them by 1e-2 and more: the test that
zeroes it says so). Token streams are compared exactly.
"""

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
from dynamo_exp_tpu.engine.config import refuse_seq_state_tiers
from dynamo_exp_tpu.models import ModelConfig, llama
from dynamo_exp_tpu.models.config import TINY_JAMBA, TINY_NEMOTRON_H
from dynamo_exp_tpu.ops import moe, ssd
from dynamo_exp_tpu.protocols.common import BackendInput

from .programs import forward, forward_ragged, init_params

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmark"))
from references import nemotron_h as reference  # noqa: E402

CFG = dataclasses.replace(TINY_NEMOTRON_H, dtype="float32")
PATTERN = "MEM*EMEM*"
HF = {
    "model_type": "nemotron_h", "vocab_size": CFG.vocab_size, "hidden_size": 64,
    "intermediate_size": 32, "num_hidden_layers": 9, "hybrid_override_pattern": PATTERN,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "layer_norm_epsilon": 1e-5, "max_position_embeddings": 512,
    "tie_word_embeddings": False, "mamba_num_heads": 4, "mamba_head_dim": 64,
    "n_groups": 2, "ssm_state_size": 128, "conv_kernel": 4, "chunk_size": 128, "expand": 2,
    "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu", "use_conv_bias": True,
    "mamba_proj_bias": False, "use_bias": False, "attention_bias": False, "mlp_bias": False,
    "n_routed_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 64, "n_shared_experts": 1, "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "sliding_window": None, "torch_dtype": "float32",
}
PS = 16
LOGP_TOL = 1e-3
SEED = 5
PROMPT = [int(t) for t in np.random.default_rng(0).integers(2, 256, 80)]
TABLE = jnp.arange(4, dtype=jnp.int32)[None] + 1


def _catalog() -> dict:
    path = os.path.join(
        os.path.dirname(__file__), "..", "benchmark", "configs",
        "nemotron-3-nano-30b-a3b-l13e64.json",
    )
    with open(path) as f:
        return json.load(f)


def _catalog_cfg() -> ModelConfig:
    return ModelConfig.from_hf_config(_catalog())


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(SEED), CFG)


def _pools(entries=4):
    k, v = llama.init_kv_cache(CFG, 17, PS, dtype=jnp.float32)
    return k, v, llama.init_seq_state(CFG, entries)


def _logp(logits):
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


# ------------------------------------------------------------------ config
def test_config_reads_the_pattern_as_pairs():
    assert ModelConfig.from_hf_config(HF) == CFG
    assert CFG.num_layers == 6 and CFG.num_block_layers == 9
    assert CFG.op_kinds == ("mamba2", "mamba2", "attn", "mamba2", "mamba2", "attn")
    assert CFG.ff_kinds == ("routed", "none", "routed", "routed", "none", "none")
    assert [r[:2] for r in CFG.layer_runs] == [
        ("mamba2", "routed"), ("mamba2", "none"), ("attn", "routed"),
        ("mamba2", "routed"), ("mamba2", "none"), ("attn", "none"),
    ]
    assert not CFG.use_rope and not CFG.gated_experts and CFG.shared_width == 64
    assert CFG.has_seq_state and CFG.num_mamba_layers == 0 and CFG.num_mamba2_layers == 4


def test_the_benchmarks_cut_and_the_whole_model():
    """The benchmark's file: 13 of the published layers as 8 pairs in 6 runs,
    64 of 128 experts held; with the published values put back for the keys
    ``reduced`` names, the whole model's 52 layers and 31.58 B parameters."""
    file = _catalog()
    cut = ModelConfig.from_hf_config(file)
    assert cut.num_block_layers == 13 and cut.num_layers == 8 and len(cut.layer_runs) == 6
    assert (cut.num_mamba2_layers, cut.num_routed_layers, cut.num_attn_layers) == (6, 5, 2)
    assert (cut.num_experts, cut.experts_held, cut.first_expert_held) == (128, 64, 0)
    assert cut.counts_picks and cut.shared_width == 3712 and cut.mamba2_inner == 4096
    assert sorted(file["reduced"]) == sorted(
        ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts", "vocab_size"]
    )
    count = lambda c: sum(  # noqa: E731
        int(np.prod(x.shape)) for x in jax.tree.leaves(
            jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0), c))
        )
    )
    pad = 2 * 64 * 2688 * 64  # a routed layer's: each expert's 1856 stored as 1920
    assert count(cut) == 3_926_018_560 + 5 * pad
    whole = {**file, **file["published"]}
    whole.pop("n_routed_experts_global")
    full = ModelConfig.from_hf_config(whole)
    assert full.num_block_layers == 52 and full.num_layers == 29
    assert (full.num_mamba2_layers, full.num_routed_layers, full.num_attn_layers) == (23, 23, 6)
    assert count(full) == 31_577_940_288 + 23 * 2 * pad
    pool = jax.eval_shape(lambda: llama.init_seq_state(cut, 160))
    assert pool.conv.shape == (6, 160, 3, 6144) and pool.ssm.shape == (6, 160, 128, 4096)
    assert str(pool.ssm.dtype) == "float32"


@pytest.mark.parametrize("changed,match", [
    ({"hybrid_override_pattern": "MEM-EMEM*"}, "layers of kind '-'"),
    ({"hybrid_override_pattern": "EMM*EMEM*"}, "layer 0 is an 'E' that follows no"),
    ({"hybrid_override_pattern": "MEE*EMEM*"}, "layer 2 is an 'E' that follows no"),
    ({"num_hidden_layers": 8}, "names 9 layers"),
    ({"n_group": 2}, "group-limited selection"),
    ({"mlp_hidden_act": "silu"}, "mlp_hidden_act"),
    ({"n_routed_experts_global": 4}, "outside the router's range"),
])
def test_unsupported_keys_raise(changed, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_config({**HF, **changed})


@pytest.mark.parametrize("tier", ["host_cache_pages", "spec_mode", "tp", "migration"])
def test_what_cannot_carry_the_state_refuses(tier):
    kw = {
        "host_cache_pages": {"host_cache_pages": 8}, "spec_mode": {"spec_mode": "ngram"},
        "tp": {"tp": 2},
    }.get(tier, {})
    cfg = EngineConfig(model=CFG, max_decode_slots=2, page_size=PS, num_pages=16, **kw)
    with pytest.raises(ValueError, match="per-sequence state.*Mamba-2"):
        refuse_seq_state_tiers(cfg, None if kw else tier)


def test_a_pool_holds_one_kinds_parts():
    both = dataclasses.replace(
        CFG, layer_types=("mamba2", "mamba", *CFG.layer_types[2:]),
    )
    with pytest.raises(ValueError, match="mamba and mamba2 layers has no state pool"):
        llama.init_seq_state(both, 2)


def test_random_parameters_are_the_familys(params):
    layers = params["layers"]
    assert "w_gate" not in layers and "shared_gate" not in layers
    assert layers["w_up"].shape == (3, 8, 64, 32) and layers["shared_up"].shape == (3, 64, 64)
    assert layers["mlp_norm"].shape == (3, 64) and layers["attn_norm"].shape == (6, 64)
    a = np.exp(np.asarray(layers["m2_A_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0
    step = np.log1p(np.exp(np.asarray(layers["m2_dt_b"])))
    assert step.min() >= 1e-3 * 0.999 and step.max() <= 1e-1 * 1.001
    for k in ("m2_A_log", "m2_D", "m2_dt_b", "router_bias"):
        assert layers[k].dtype == jnp.float32


def test_experts_are_stored_in_whole_lane_groups():
    """A width past one lane group that is not whole (1856; 160 here) is
    stored padded with zeros, which add nothing: the grouped-matmul kernel
    tiles it and XLA keeps the stack row-major."""
    cfg = dataclasses.replace(CFG, moe_intermediate_size=160)
    assert cfg.expert_stack_width == 256 and CFG.expert_stack_width == 32
    assert _catalog_cfg().expert_stack_width == 1920
    layers = llama.init_params(jax.random.PRNGKey(0), cfg)["layers"]
    assert layers["w_up"].shape == (3, 8, 64, 256) and layers["w_down"].shape == (3, 8, 256, 64)
    assert float(jnp.abs(layers["w_up"][..., 160:]).max()) == 0.0
    assert float(jnp.abs(layers["w_down"][:, :, 160:]).max()) == 0.0
    assert float(jnp.abs(layers["w_up"][..., :160]).min()) > 0.0
    x = jnp.asarray(np.random.default_rng(3).normal(size=(12, 64)), jnp.float32)
    args = (layers["router"][0], None)
    got = moe.moe_ffn(x, *args, layers["w_up"][0], layers["w_down"][0], 2, act="relu2")
    want = moe.moe_ffn(
        x, *args, layers["w_up"][0][..., :160], layers["w_down"][0][:, :160], 2, act="relu2"
    )
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


# ----------------------------------------------------------- the recurrence
def _ssd_case(seed=0, h=4, p=64, g=2, s=128):
    """A stream of three rows at the least widths the chunk kernel tiles: one
    resumed from its entry (150 tokens from position 5: a whole chunk and a
    part), one from position 0 (137 tokens), one of one token, spans aligned
    to 8 and padding behind."""
    rng = np.random.default_rng(seed)
    n, entries = 320, 6
    row_of, pos = np.zeros(n, np.int32), -np.ones(n, np.int32)
    i = 0
    for r, (start, ln) in enumerate([(5, 150), (0, 137), (7, 1)]):
        row_of[i:i + ln], pos[i:i + ln] = r, np.arange(start, start + ln)
        i += -(-ln // 8) * 8
    f32 = np.float32
    x = rng.normal(size=(n, h * p)).astype(f32)
    dt = np.log1p(np.exp(rng.normal(size=(n, h)).astype(f32) - 2))
    a = -rng.uniform(1, 16, size=(h,)).astype(f32)
    b, c = (rng.normal(size=(n, g * s)).astype(f32) for _ in range(2))
    d = rng.normal(size=(h,)).astype(f32)
    pool = rng.normal(size=(2, entries, s, h * p)).astype(f32)
    # each row's own entry after its last token, and two snapshots inside chunks
    src = np.array([149, 40, 152 + 136, 152 + 130, 296], np.int32)
    dst = np.array([2, 3, 0, 5, 4], np.int32)
    args = [x, dt, a, b, c, d, pool, 1, np.array([2, 0, 4], np.int32), row_of, pos, src, dst]
    return [jnp.asarray(v) for v in args]


def test_the_chunk_kernel_is_the_recurrence():
    """Spans that are no multiple of the chunk, two rows and a lone token in
    one stream, writes inside chunks (the kernel interpreted): outputs and
    every written entry are the token-by-token recurrence's; the entry no
    write names is untouched."""
    x, dt, a, b, c, d, pool, layer, slots, row_of, pos, src, dst = _ssd_case()
    y_ref, pool_ref = ssd.ssd_ref(x, dt, a, b, c, d, pool, layer, slots, row_of, pos, src, dst)
    y, got = ssd.ssd_kernel(
        jnp.concatenate([x, b, c], axis=1), dt, a, d, pool, layer, slots, row_of, pos, src, dst,
        interpret=True,
    )
    live = np.asarray(pos) >= 0
    assert np.abs(np.asarray(y)[live] - np.asarray(y_ref)[live]).max() < 1e-3
    assert np.abs(np.asarray(got) - np.asarray(pool_ref)).max() < 1e-5
    assert float(jnp.abs(got[1, 1] - pool[1, 1]).max()) == 0.0
    assert float(jnp.abs(got[1, 2] - pool[1, 2]).max()) > 0.1
    assert ssd.kernel_supported(64, 64, 8, 128, 8) and ssd.kernel_supported(4, 64, 2, 128, 8)
    assert not ssd.kernel_supported(4, 32, 2, 16, 8) and not ssd.kernel_supported(64, 64, 8, 128, 4)


@pytest.mark.parametrize("form", ["plain", "kernel"])
def test_the_one_token_step_is_the_recurrence(form):
    x, dt, a, b, c, d, pool, layer, slots, row_of, pos, src, dst = _ssd_case()
    y_ref, pool_ref = ssd.ssd_ref(x, dt, a, b, c, d, pool, layer, slots, row_of, pos, src, dst)
    at = jnp.asarray([296, 3, 152])  # the lone token, a padding row, a first token
    step = ssd.ssd_step if form == "plain" else (
        lambda *args: ssd.ssd_step_kernel(*args, interpret=True)
    )
    y, got = step(
        x[at], dt[at], a, b[at], c[at], d, pool, layer, jnp.asarray([4, 2, 1]),
        jnp.asarray([7, -1, 0]), jnp.asarray([4, 9, 5]),
    )
    assert np.abs(np.asarray(y[0]) - np.asarray(y_ref[296])).max() < 1e-4
    assert np.abs(np.asarray(got[1, 4]) - np.asarray(pool_ref[1, 4])).max() < 1e-5
    # a first token starts from zeros whatever its entry holds; padding writes nothing
    first, _ = ssd._one_step(
        jnp.zeros((128, 4, 64)), x[152].reshape(4, 64), dt[152], a, b[152].reshape(2, 128),
        c[152].reshape(2, 128),
    )
    assert np.abs(np.asarray(got[1, 5]) - np.asarray(first.reshape(128, 256))).max() < 1e-6
    assert float(jnp.abs(got[1, 2] - pool[1, 2]).max()) == 0.0


# ------------------------------------------------------------------ forward
@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_forward_matches_reference(params, phase):
    k, v, st = _pools()
    T = 40
    ids = jnp.asarray(PROMPT[:T])[None]
    if phase == "prefill":
        logits, *_ = forward(
            params, CFG, ids, jnp.arange(T, dtype=jnp.int32)[None], TABLE, k, v,
            seq_state=st, state_slots=jnp.asarray([1]),
        )
        got = _logp(logits[0])
    else:
        got = []
        for t in range(12):
            logits, k, v, st = forward(
                params, CFG, ids[:, t:t + 1], jnp.asarray([[t]], jnp.int32), TABLE, k, v,
                seq_state=st, state_slots=jnp.asarray([1]),
            )
            got.append(_logp(logits[0, 0]))
        got, T = np.stack(got), 12
    want = reference.log_probs(params, HF, PROMPT[:T], range(T))
    assert np.abs(got - want).max() < LOGP_TOL


def test_the_pools_state_is_the_references(params):
    k, v, st = _pools()
    T = 21
    *_, st = forward(
        params, CFG, jnp.asarray(PROMPT[:T])[None], jnp.arange(T, dtype=jnp.int32)[None],
        TABLE, k, v, seq_state=st, state_slots=jnp.asarray([2]),
    )
    want = reference.scan_state(params, HF, 0, params["embed"][jnp.asarray(PROMPT[:T])])
    assert np.abs(np.asarray(st.ssm[0, 2]) - want).max() < 1e-5 * np.abs(want).max()
    assert st.conv.shape == (4, 4, 3, 768) and st.ssm.shape == (4, 4, 128, 256)


def _stream(spans, tail=()):
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
    k, v, st = pools
    return forward_ragged(
        params, CFG, *stream, table, k, v, jnp.asarray(out_idx, jnp.int32),
        attn_impl=impl, q_tile=8, interpret=True, seq_state=st,
        state_rows=tuple(jnp.asarray(a, jnp.int32) for a in state_rows), **kw,
    )


def test_chunks_then_a_mixed_dispatch_then_decode(params):
    """Through ``forward_ragged`` with the kernels' forms (the chunk kernel,
    the in-place step and the attention kernel, all interpreted):
    a prompt in chunks of 16 and 24 tokens, the state through the pool; then
    ONE stream of its next 5 tokens with a snapshot inside the span and, in
    the packed tail, a decode row of a second sequence; then a decode step
    from the state that left, and the snapshot continued: the uncut
    forward's logits throughout."""
    k, v, st = _pools(entries=8)
    tables = jnp.stack([jnp.arange(1 + 4 * i, 5 + 4 * i) for i in range(3)]).astype(jnp.int32)
    other = PROMPT[50:60]
    _, k, v, st = forward(  # the second sequence's first 9 tokens, entry 2
        params, CFG, jnp.asarray(other[:9])[None], jnp.arange(9, dtype=jnp.int32)[None],
        tables[1:2], k, v, seq_state=st, state_slots=jnp.asarray([2]),
    )
    pools, got, at = (k, v, st), [], 0
    for n in (16, 24):
        pad = [(0, -1, 2)] * (24 - n)
        logits, *pools = _ragged(
            params, "pallas", pools, _stream([(PROMPT[at:at + n], at, 0)], tail=pad), tables,
            ([1, 2, 8], [n - 1, 0, 0], [1, 8, 8]), range(24),
        )
        got.append(_logp(logits)[:n])
        at += n
    stream = _stream([(PROMPT[40:45], 40, 0)], tail=[(other[9], 9, 1)] + [(0, -1, 2)] * 7)
    rows = ([1, 2, 8], [4, 8, 0, 2, 0, 0], [1, 2, 8, 5, 8, 8])  # + row 0 after its 43rd token
    logits, k, v, st = _ragged(
        params, "pallas", pools, stream, tables, rows, [0, 1, 2, 3, 4, 8], packed_from=8,
    )
    got.append(_logp(logits)[:5])
    want = reference.log_probs(params, HF, PROMPT[:45], range(45))
    assert np.abs(np.concatenate(got) - want).max() < LOGP_TOL
    want = reference.log_probs(params, HF, other, [9])[0]
    assert np.abs(_logp(logits)[5] - want).max() < LOGP_TOL
    for entry, seq, first, n in ((1, PROMPT, 45, 3), (5, PROMPT, 43, 4), (2, other + PROMPT[:2], 10, 2)):
        row = 1 if entry == 2 else 0
        lg, *_ = forward(
            params, CFG, jnp.asarray(seq[first:first + n])[None],
            jnp.arange(first, first + n, dtype=jnp.int32)[None], tables[row:row + 1], k, v,
            seq_state=st, state_slots=jnp.asarray([entry]),
        )
        want = reference.log_probs(params, HF, seq[:first + n], range(first, first + n))
        assert np.abs(_logp(lg[0]) - want).max() < LOGP_TOL, entry


def test_a_state_left_at_zero_shows(params):
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


# ------------------------------------------------------------------ experts
@pytest.mark.parametrize("held_from", [None, 4])
def test_moe_ffn_without_a_gate_matrix(held_from):
    """``moe_ffn`` with no gate matrix against ``moe_ffn_reference``'s
    two-matrix form; a held half against the reference's picks on it."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(24, 64)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(64, 8)) / 8, jnp.float32)
    up = jnp.asarray(rng.normal(size=(8, 64, 32)) / 8, jnp.float32)
    down = jnp.asarray(rng.normal(size=(8, 32, 64)) / 6, jnp.float32)
    want = moe.moe_ffn_reference(x, router, None, up, down, 2, act="relu2")
    if held_from is None:
        got = moe.moe_ffn(x, router, None, up, down, 2, act="relu2")
    else:
        got = sum(
            moe.moe_ffn(x, router, None, up[lo:lo + 4], down[lo:lo + 4], 2, act="relu2", held_from=lo)
            for lo in (0, 4)
        )
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-4
    silu = moe.moe_ffn_reference(x, router, None, up, down, 2)
    assert np.abs(np.asarray(silu) - np.asarray(want)).max() > 1e-2


def test_two_shares_add_up_to_the_whole_layer(params):
    """The guide's share test: the routed parts of the two shares (experts
    0-3 and 4-7 of the router's 8) plus the shared expert counted once equal
    the uncut layer, in the engine's ``_ffn_block`` and in the reference."""
    layers = params["layers"]
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 20, 64)), jnp.float32)
    lp = {k: layers[k][1] for k in (
        "mlp_norm", "router", "router_bias", "w_up", "w_down", "shared_up", "shared_down",
    )}
    whole = llama._ffn_block(x, lp, CFG, 1e-5, lambda y: y, None) - x
    no_shared = {k: v for k, v in lp.items() if not k.startswith("shared")}
    parts = []
    for lo in (0, 4):
        half = dataclasses.replace(CFG, num_experts_held=4, first_expert_held=lo)
        lp_half = {**no_shared, "w_up": lp["w_up"][lo:lo + 4], "w_down": lp["w_down"][lo:lo + 4]}
        parts.append(llama._ffn_block(x, lp_half, half, 1e-5, lambda y: y, None) - x)
    only_shared = whole - (
        llama._ffn_block(x, no_shared, CFG, 1e-5, lambda y: y, None) - x
    )
    assert np.abs(np.asarray(parts[0] + parts[1] + only_shared - whole)).max() < 1e-5
    assert float(jnp.abs(parts[0]).max()) > 1e-3 and float(jnp.abs(parts[1]).max()) > 1e-3
    # the reference: layer 4 of the pattern is this E layer (the second)
    want, _margin, here = reference.experts_layer(params, HF, 4, x[0], added=True)
    assert here == 1.0
    assert np.abs(np.asarray(whole[0]) - want).max() < 1e-4
    share = 0.0
    for lo in (0, 4):
        hf = {**HF, "n_routed_experts": 4, "n_routed_experts_global": 8, "first_routed_expert": lo}
        cut = {**params, "layers": {
            **layers, "w_up": layers["w_up"][:, lo:lo + 4], "w_down": layers["w_down"][:, lo:lo + 4],
        }}
        y, _m, here = reference.experts_layer(cut, hf, 4, x[0], added=True)
        share += here
        want = want - y
    shared = np.asarray(only_shared[0])
    assert abs(share - 1.0) < 1e-6
    assert np.abs(want + shared).max() < 1e-4  # whole - share0 - share1 = -shared, once too many


# ------------------------------------------------------------------ engine
def _engine(**kw) -> TPUEngine:
    from dynamo_exp_tpu.parallel import single_device_mesh

    cfg = EngineConfig(
        model=CFG, max_decode_slots=4, page_size=PS, num_pages=kw.pop("num_pages", 64),
        max_model_len=kw.pop("max_model_len", 64), prefill_chunk=16, decode_window=8,
        kv_dtype="float32", eos_token_ids=[], preempt_stall_grace_s=0.05, **kw,
    )
    return TPUEngine(cfg, mesh=single_device_mesh(), seed=SEED)


@pytest.fixture(scope="module")
def engine():
    eng = _engine(pallas_interpret=True)
    assert eng._attn_impl == "pallas"
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


async def test_engine_serves_chunks_and_windows(engine):
    """Through the scheduler, the page manager and the state pool: a 40-token
    prompt in chunks of 16 (the chunk kernel, the state crossing two dispatch
    boundaries), then 16 tokens through 8-step windows (the in-place step),
    equal to the reference's greedy continuation; beside it a second request
    whose chunks ride with the first's decode rows."""
    from dynamo_exp_tpu.telemetry import get_telemetry

    tel = get_telemetry()
    assert engine.seq_state.ssm.shape == (4, 20, 128, 256)
    assert engine.seq_state.conv.shape == (4, 20, 3, 768)
    assert engine.k_cache.shape[0] == 2  # pages for the attention layers alone
    nbytes = sum(a.nbytes for a in jax.tree.leaves(engine.seq_state))
    assert tel.seq_state_pool_bytes._value.get() == nbytes
    before = tel.mamba2_tokens.labels("mixed")._value.get()
    scans = tel.ssm_scan_tokens.labels("mixed")._value.get()
    prompts = [PROMPT[:40], PROMPT[41:60]]
    got = await asyncio.gather(*(_run(engine, p, 16) for p in prompts))
    for p, g in zip(prompts, got):
        assert g == _greedy_reference(engine.params, p, 16)
    assert tel.mamba2_tokens.labels("mixed")._value.get() >= before + 59
    assert tel.ssm_scan_tokens.labels("mixed")._value.get() == scans
    assert b"dynamo_mamba2_tokens_total" in tel.render()


async def test_second_asking_restores_the_state(engine):
    base = PROMPT[5:38]  # 33 tokens: boundaries 16 and 32
    await _run(engine, base, 4)
    before = dict(engine.kv.state_hits), engine.kv.prefix_hits["shared"]
    prompt = base + PROMPT[:7]
    got = await _run(engine, prompt, 8)
    assert got == _greedy_reference(engine.params, prompt, 8)
    assert engine.kv.state_hits["restored"] == before[0]["restored"] + 1
    assert engine.kv.prefix_hits["shared"] - before[1] == 32 // PS


async def test_preempt_and_resume():
    """Two requests on a pool that holds one and a half (the XLA engine: the
    oracle's forms): one is preempted and re-admitted with its state restored
    or recomputed; both streams equal the reference's."""
    eng = _engine(num_pages=96 // PS)
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


# -------------------------------------------------- what stayed what it was
def test_scan_forms():
    assert llama._scan_form(CFG, "pallas", 8, 32, True) == llama.ScanForm(True, 32, False, True)
    assert llama._scan_form(CFG, "xla", 8, 32, False) == llama.ScanForm(False, None, False, False)
    assert llama._scan_form(TINY_JAMBA, "pallas", 8, 32, False) == llama.ScanForm(True, 32, False, False)


@pytest.mark.parametrize("name", ["jamba2-3b", "lfm2-8b-a1b-l16", "glm-5-l5e16"])
def test_other_families_keep_their_stacks(name):
    path = os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs", f"{name}.json")
    with open(path) as f:
        cfg = ModelConfig.from_hf_config(json.load(f))
    assert cfg.ff_types is None and cfg.gated_experts and cfg.num_mamba2_layers == 0
    assert cfg.num_block_layers == cfg.num_layers
    assert {r[1] for r in cfg.layer_runs} <= {"dense", "routed"}
    shapes = jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0), cfg))["layers"]
    assert "w_gate" in shapes and not any(k.startswith("m2_") for k in shapes)
    assert shapes["mlp_norm"].shape[0] == cfg.num_layers


# ------------------------------------------------------------------ loader
def test_checkpoint_names_round_trip(tmp_path):
    """A checkpoint under the assumed tensor names (the family's
    ``backbone.layers.N.{norm,mixer}``) loads into the stacks by kind: written
    from seeded parameters, read back equal; ``in_proj`` split into ``z |
    xBC`` and ``dt``; ``A_log``, ``D`` and ``dt_bias`` stay float32."""
    from safetensors.numpy import save_file

    from dynamo_exp_tpu.models.loader import load_params
    from dynamo_exp_tpu.models.synth import write_config

    cfg = TINY_NEMOTRON_H  # bfloat16: the float32 parameters must stay so
    params = init_params(jax.random.PRNGKey(SEED), cfg)
    as_np = lambda x: np.asarray(x.astype(jnp.float32))  # noqa: E731
    layers = {k: as_np(v) for k, v in params["layers"].items()}
    out = {
        "backbone.embeddings.weight": as_np(params["embed"]),
        "backbone.norm_f.weight": as_np(params["final_norm"]),
        "lm_head.weight": as_np(params["lm_head"]).T,
    }
    for i in range(len(PATTERN)):
        kind, norm, nth = reference.where(PATTERN, i)
        p, m = f"backbone.layers.{i}.", f"backbone.layers.{i}.mixer."
        out[p + "norm.weight"] = layers["mlp_norm" if kind == "E" else "attn_norm"][norm]
        if kind == "M":
            w_in = np.concatenate([layers["m2_in"][nth], layers["m2_dt_w"][nth]], axis=1)
            out[m + "in_proj.weight"] = w_in.T
            out[m + "conv1d.weight"] = layers["m2_conv_w"][nth].T[:, None, :]
            out[m + "conv1d.bias"] = layers["m2_conv_b"][nth]
            for name, key in (("dt_bias", "m2_dt_b"), ("A_log", "m2_A_log"), ("D", "m2_D")):
                out[m + name] = layers[key][nth]
            out[m + "norm.weight"] = layers["m2_norm"][nth]
            out[m + "out_proj.weight"] = layers["m2_out"][nth].T
        elif kind == "*":
            for key, name in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"), ("wo", "o_proj")):
                out[f"{m}{name}.weight"] = layers[key][nth].T
        else:
            out[m + "gate.weight"] = layers["router"][nth].T
            out[m + "gate.e_score_correction_bias"] = layers["router_bias"][nth]
            out[m + "shared_experts.up_proj.weight"] = layers["shared_up"][nth].T
            out[m + "shared_experts.down_proj.weight"] = layers["shared_down"][nth].T
            for e in range(cfg.num_experts):
                out[f"{m}experts.{e}.up_proj.weight"] = layers["w_up"][nth, e].T
                out[f"{m}experts.{e}.down_proj.weight"] = layers["w_down"][nth, e].T
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
    for name in ("m2_A_log", "m2_D", "m2_dt_b", "router_bias"):
        assert loaded["layers"][name].dtype == np.float32
    np.testing.assert_array_equal(np.asarray(loaded["lm_head"], np.float32), as_np(params["lm_head"]))
