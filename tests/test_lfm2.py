"""lfm2_moe (LFM2-8B-A1B) on the normal path: layers of two kinds in one
stack, the short convolution's state beside the paged KV, the sigmoid
router with a selection bias. Tiny sizes, seeded weights, float32, so the
engine and the plain reference (``benchmark/references/lfm2_moe.py``)
differ by float32 rounding alone.

TOLERANCES. Both sides compute in float32 with the reference at
``highest`` matmul precision and the engine at the CPU's default (also
float32 products): log-probabilities agree to ~1e-5, so ``LOGP_TOL`` 1e-3
stands a hundred times above the rounding and far below any fault (a
state left at zero moves them by 1e-1 and more: the test that zeroes it
says so). Token streams are compared exactly: float32 logits of random
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
from dynamo_exp_tpu.models.config import TINY_LFM2
from dynamo_exp_tpu.ops.moe import moe_router
from dynamo_exp_tpu.protocols.common import BackendInput

# Jitted: a shape and set of options compiles once a file (tests/programs.py).
from .programs import forward, forward_ragged, init_params

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmark"))
from references import lfm2_moe as reference  # noqa: E402

CFG = dataclasses.replace(TINY_LFM2, dtype="float32")
HF = {
    "model_type": "lfm2_moe", "layer_types": list(CFG.layer_types),
    "num_dense_layers": CFG.num_dense_layers, "num_hidden_layers": CFG.num_layers,
    "hidden_size": CFG.hidden_size, "intermediate_size": CFG.intermediate_size,
    "num_attention_heads": CFG.num_heads, "num_key_value_heads": CFG.num_kv_heads,
    "vocab_size": CFG.vocab_size, "norm_eps": CFG.rms_norm_eps,
    "rope_theta": CFG.rope_theta, "num_experts": CFG.num_experts,
    "num_experts_per_tok": CFG.num_experts_per_tok, "norm_topk_prob": True,
    "moe_intermediate_size": CFG.moe_intermediate_size, "routed_scaling_factor": 1.0,
    "use_expert_bias": True, "conv_L_cache": 3, "conv_bias": False,
    "max_position_embeddings": 512, "torch_dtype": "float32",
}
PS = 8
LOGP_TOL = 1e-3
SEED = 3
PROMPT = [int(t) for t in np.random.default_rng(0).integers(2, 256, 40)]


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(SEED), CFG)


def _pools(entries=4):
    k, v = llama.init_kv_cache(CFG, 17, PS, dtype=jnp.float32)
    return k, v, llama.init_seq_state(CFG, entries)


def _logp(logits):
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


TABLE = jnp.arange(16, dtype=jnp.int32)[None] + 1


def test_config_reads_the_published_keys():
    assert ModelConfig.from_hf_config(HF) == CFG
    assert CFG.op_kinds == ("conv", "conv", "attn", "conv", "conv", "attn")
    assert CFG.ff_kinds == ("dense", "dense", "routed", "routed", "routed", "routed")
    assert (CFG.num_attn_layers, CFG.num_conv_layers, CFG.num_routed_layers) == (2, 4, 4)
    assert CFG.layer_runs == (
        ("conv", "dense", 0, 2, 0, 0), ("attn", "routed", 2, 1, 0, 0),
        ("conv", "routed", 3, 2, 2, 1), ("attn", "routed", 5, 1, 1, 3),
    )


@pytest.mark.parametrize("model_type", ["lfm2", "deepseek_v3", "not_a_model"])
def test_unknown_model_type_raises(model_type):
    with pytest.raises(ValueError, match="unsupported model_type"):
        ModelConfig.from_hf_config({**HF, "model_type": model_type})


def test_a_model_of_one_kind_is_one_run():
    from dynamo_exp_tpu.models import PRESETS

    for name in ("tiny", "tiny-qwen3", "tiny-moe"):
        cfg = PRESETS[name]
        (run,) = cfg.layer_runs
        assert run[2:] == (0, cfg.num_layers, 0, 0) and not cfg.has_seq_state


@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_forward_matches_reference(params, phase):
    """The engine's forward against the reference's logits: the prompt in
    one piece, then six steps through the cache and the state."""
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


@pytest.mark.parametrize("cut", [1, 2, 3, 4])
def test_state_through_chunked_prefill(params, cut):
    """A prompt cut after ``cut`` tokens (every way the two carried
    products can straddle the boundary) gives the uncut forward's
    logits."""
    T = 12
    want = reference.log_probs(params, HF, PROMPT[:T], range(T))
    k, v, st = _pools()
    got = []
    for a, b in ((0, cut), (cut, T)):
        pad = T - (b - a)  # every piece in T slots: one compiled program for all cuts
        logits, k, v, st = forward(
            params, CFG, jnp.asarray(PROMPT[a:b] + [0] * pad)[None],
            jnp.asarray(list(range(a, b)) + [-1] * pad, jnp.int32)[None],
            TABLE, k, v, seq_state=st, state_slots=jnp.asarray([1]),
        )
        got.append(_logp(logits[0])[: b - a])
    assert np.abs(np.concatenate(got) - want).max() < LOGP_TOL


def test_a_state_left_at_zero_shows(params):
    """What the tolerance is far below: the second chunk computed from a
    zero state (the fault a prefix hit without its state would be)."""
    T, cut = 12, 4
    want = reference.log_probs(params, HF, PROMPT[:T], range(cut, T))
    k, v, st = _pools()
    _, k, v, _ = forward(
        params, CFG, jnp.asarray(PROMPT[:cut])[None], jnp.arange(cut, dtype=jnp.int32)[None],
        TABLE, k, v, seq_state=st, state_slots=jnp.asarray([1]),
    )
    logits, *_ = forward(
        params, CFG, jnp.asarray(PROMPT[cut:T])[None], jnp.arange(cut, T, dtype=jnp.int32)[None],
        TABLE, k, v, seq_state=jnp.zeros_like(st), state_slots=jnp.asarray([1]),
    )
    assert np.abs(_logp(logits[0]) - want).max() > 100 * LOGP_TOL


@pytest.mark.parametrize("stream", ["prefill+decode+padding"])
def test_mixed_stream_with_padding(params, stream):
    """One ragged stream holding a prefill chunk (its span padded to the
    kernel's alignment), a decode row of another sequence, padding rows
    and padding slots: each row's logits are its own uncut forward's, and
    padding writes no state."""
    k, v, st = _pools(entries=6)
    # Sequence B (row 1) has 9 tokens behind it; its state sits in entry 3.
    other = PROMPT[20:30]
    table = jnp.stack([jnp.arange(1, 9), jnp.arange(9, 17)]).astype(jnp.int32)
    _, k, v, st = forward(
        params, CFG, jnp.asarray(other[:9])[None], jnp.arange(9, dtype=jnp.int32)[None],
        table[1:], k, v, seq_state=st, state_slots=jnp.asarray([3]),
    )
    st = st.at[:, 5].set(7.0)  # an entry no row owns: must stay as it is
    # Row 0: sequence A's first 5 tokens, padded to 8; row 1: B's 10th token.
    tokens = jnp.asarray(PROMPT[:5] + [0] * 3 + [other[9]] + [0] * 7, jnp.int32)
    positions = jnp.asarray([0, 1, 2, 3, 4, -1, -1, -1, 9] + [-1] * 7, jnp.int32)
    row_of = jnp.asarray([0] * 8 + [1] + [2] * 7, jnp.int32)
    full_table = jnp.concatenate([table, jnp.zeros((1, 8), jnp.int32)])
    out_idx = jnp.asarray([4, 8], jnp.int32)
    rows = (
        jnp.asarray([0, 3, 6], jnp.int32),  # row 2 is padding: past the pool
        jnp.asarray([4, 8, 0, 3, 0, 0], jnp.int32),
        jnp.asarray([0, 3, 6, 4, 6, 6], jnp.int32),  # + a snapshot of A after token 3
    )
    logits, k, v, st = forward_ragged(
        params, CFG, tokens, positions, row_of, full_table, k, v, out_idx,
        seq_state=st, state_rows=rows,
    )
    got = _logp(logits)
    assert np.abs(got[0] - reference.log_probs(params, HF, PROMPT[:5], [4])[0]).max() < LOGP_TOL
    assert np.abs(got[1] - reference.log_probs(params, HF, other, [9])[0]).max() < LOGP_TOL
    assert float(jnp.abs(st[:, 5] - 7.0).max()) == 0.0
    # The snapshot (entry 4) is A's state after 4 tokens: continue from it.
    logits, *_ = forward(
        params, CFG, jnp.asarray(PROMPT[4:7])[None], jnp.arange(4, 7, dtype=jnp.int32)[None],
        table[:1], k, v, seq_state=st, state_slots=jnp.asarray([4]),
    )
    want = reference.log_probs(params, HF, PROMPT[:7], [4, 5, 6])
    assert np.abs(_logp(logits[0]) - want).max() < LOGP_TOL


@pytest.mark.parametrize("rule", ["bias_chooses", "score_weighs", "renormalised"])
def test_sigmoid_router(rule):
    """A bias that changes the selection, weights still from ``s``."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    bias = jnp.asarray(llama.ROUTER_BIAS_SCALE * rng.standard_normal(8), jnp.float32)
    s = np.asarray(jax.nn.sigmoid(x @ w))
    wts, ids = moe_router(x, w, 2, True, score="sigmoid", select_bias=bias)
    plain_w, plain_ids = moe_router(x, w, 2, True, score="sigmoid")
    ids, wts = np.asarray(ids), np.asarray(wts)
    if rule == "bias_chooses":
        want = np.argsort(-(s + np.asarray(bias)), axis=-1)[:, :2]
        assert (np.sort(ids, -1) == np.sort(want, -1)).all()
        changed = (np.sort(ids, -1) != np.sort(np.asarray(plain_ids), -1)).any(-1)
        assert changed.sum() >= 5  # of 64: the mechanism is exercised
    elif rule == "score_weighs":
        picked = np.take_along_axis(s, ids, -1)
        assert np.allclose(wts, picked / (picked.sum(-1, keepdims=True) + 1e-6), atol=1e-6)
    else:
        raw, _ = moe_router(x, w, 2, False, score="sigmoid", select_bias=bias, scale=2.5)
        assert np.allclose(np.asarray(raw), 2.5 * np.take_along_axis(s, ids, -1), atol=1e-6)
        assert np.allclose(wts.sum(-1), 1.0, atol=1e-4)


def test_random_bias_changes_selections(params):
    """The seeded selection bias of ``--random-weights`` is not zero and
    moves some rows' chosen experts (else the mechanism would idle)."""
    bias = params["layers"]["router_bias"]
    assert bias.dtype == jnp.float32 and float(jnp.abs(bias).min()) > 0
    x = jnp.asarray(np.random.default_rng(2).standard_normal((256, CFG.hidden_size)), jnp.float32)
    router = params["layers"]["router"][0]
    _, with_bias = moe_router(x, router, 2, True, score="sigmoid", select_bias=bias[0])
    _, without = moe_router(x, router, 2, True, score="sigmoid")
    assert (np.sort(np.asarray(with_bias), -1) != np.sort(np.asarray(without), -1)).any(-1).sum() > 10


# ------------------------------------------------------------------ engine
def _engine(**kw) -> TPUEngine:
    from dynamo_exp_tpu.parallel import single_device_mesh

    cfg = EngineConfig(
        model=CFG, max_decode_slots=4, page_size=PS, num_pages=kw.pop("num_pages", 64),
        max_model_len=256, prefill_chunk=16, decode_window=8, kv_dtype="float32",
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


async def test_engine_serves_windows_and_chunks(engine):
    """Through the scheduler: a 40-token prompt in chunks of 16 (mixed
    program), then 20 tokens through 8-step windows, equal to the
    reference's greedy continuation."""
    assert engine.seq_state.shape == (4, 20, 2, CFG.hidden_size)
    assert engine.k_cache.shape[0] == 2  # pages for the attention layers alone
    got = await _run(engine, PROMPT, 20)
    assert got == _greedy_reference(engine.params, PROMPT, 20)


@pytest.mark.parametrize("asking", ["again", "longer"])
async def test_second_asking_from_cached_pages(engine, asking):
    """A prompt whose head is in cached pages is served from them with
    the state that belongs to the hit's last token, and gives the uncut
    forward's tokens."""
    base = PROMPT[5:38]  # 33 tokens: boundaries 16 (a chunk's end) and 32
    await _run(engine, base, 4)
    before = dict(engine.kv.state_hits), engine.kv.prefix_hits["shared"]
    prompt = base if asking == "again" else base + PROMPT[:7]
    got = await _run(engine, prompt, 12)
    assert got == _greedy_reference(engine.params, prompt, 12)
    assert engine.kv.state_hits["restored"] == before[0]["restored"] + 1
    assert engine.kv.prefix_hits["shared"] - before[1] == 4  # 32 of its tokens


async def test_hit_is_shortened_to_a_held_state(engine):
    """Pages matched past the deepest boundary a state is held for are
    not taken: blocks 0..2 match, a state is held at the end of block 1
    (a chunk's end) and of none after, so 16 tokens come from the cache."""
    first = PROMPT[3:36]  # snapshots at 16 (chunk end) and 32 (deepest of the last chunk)
    await _run(engine, first, 2)
    prompt = first[:30] + [1, 1, 1]  # shares 3 whole blocks (24 tokens)
    before = dict(engine.kv.state_hits)
    got = await _run(engine, prompt, 6)
    assert got == _greedy_reference(engine.params, prompt, 6)
    assert engine.kv.state_hits["shortened"] == before["shortened"] + 1


async def test_a_snapshot_an_extended_chunk_wrote_restores(engine):
    """A chunk that took its bucket's empty page leaves its snapshot where
    chunks of ``prefill_chunk`` would not have, and a second asking
    restores from it (``tests/test_chunk_fill.py`` has the steps)."""
    from .test_chunk_fill import restore_from_an_extended_chunks_snapshot

    await restore_from_an_extended_chunks_snapshot(
        engine, _run, _greedy_reference, PROMPT[20:29], PROMPT[1:40]
    )


async def test_preempt_and_resume():
    """Two requests on a pool that holds one and a half: one is preempted,
    re-admitted onto its own cached pages with the state restored (or
    recomputed), and both streams equal the reference's."""
    eng = _engine(num_pages=12)
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


@pytest.mark.parametrize("tier", [
    "host_cache_pages", "kv_store_dir", "spec_mode", "disaggregated transfer", "migration",
])
def test_tiers_that_move_pages_alone_refuse(tier, tmp_path):
    kw = {
        "host_cache_pages": {"host_cache_pages": 8}, "spec_mode": {"spec_mode": "ngram"},
        "kv_store_dir": {"host_cache_pages": 8, "kv_store_dir": str(tmp_path)},
    }.get(tier, {})
    cfg = EngineConfig(model=CFG, max_decode_slots=2, page_size=PS, num_pages=16, **kw)
    with pytest.raises(ValueError, match="per-sequence state"):
        if kw:
            TPUEngine(cfg)
        else:
            refuse_seq_state_tiers(cfg, tier)
    # The same tiers take a model that holds pages alone.
    from dynamo_exp_tpu.models import TINY

    refuse_seq_state_tiers(dataclasses.replace(cfg, model=TINY), None if kw else tier)


@pytest.mark.parametrize("component", ["MigrationSink", "ReclaimController", "PrefillWorker"])
def test_components_round_the_engine_refuse(component):
    import types

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
    with pytest.raises(ValueError, match="per-sequence state"):
        build()


@pytest.mark.parametrize("preset,impl,variants,greedy,keys", [
    ("tiny-qwen3", "pallas", 80, 20, "72faa11f68134285"),
    ("tiny-qwen3", "xla", 240, 60, "d73130351465f0ac"),
    ("tiny-moe", "pallas", 80, 20, "72faa11f68134285"),
    ("tiny-moe", "xla", 240, 60, "d73130351465f0ac"),
])
def test_uniform_models_keep_their_lattice(preset, impl, variants, greedy, keys):
    """Models of one kind did not move: the lattice keys and variant
    counts read on the parent of PR 31 (commit bf0abdf), at the benchmark's
    slots, page size, pool and length."""
    import hashlib
    import json

    from dynamo_exp_tpu.aot.lattice import build_manifest
    from dynamo_exp_tpu.models import PRESETS

    cfg = EngineConfig(
        model=PRESETS[preset], max_decode_slots=64, page_size=16, num_pages=1152,
        max_model_len=4096,
    )
    m = build_manifest(cfg, impl, {"tp": 1}, "x", interpret=False)
    assert len(m.ragged) == variants
    assert sum(1 for k in m.ragged_keys() if not (k[3] or k[4] or k[5])) == greedy
    digest = hashlib.sha256(json.dumps(sorted(map(str, m.ragged_keys()))).encode())
    assert digest.hexdigest()[:16] == keys


def test_checkpoint_names_round_trip(tmp_path, params):
    """A checkpoint under LiquidAI's tensor names loads into the stacks by
    kind: written from seeded parameters, read back equal."""
    from safetensors.numpy import save_file

    from dynamo_exp_tpu.models.loader import load_params
    from dynamo_exp_tpu.models.synth import write_config

    layers = {k: np.asarray(v) for k, v in params["layers"].items()}
    out = {
        "model.embed_tokens.weight": np.asarray(params["embed"]),
        "model.embedding_norm.weight": np.asarray(params["final_norm"]),
    }
    nth = {"conv": 0, "attn": 0, "dense": 0, "routed": 0}
    for i, (op, ff) in enumerate(zip(CFG.op_kinds, CFG.ff_kinds)):
        p = f"model.layers.{i}."
        out[p + "operator_norm.weight"] = layers["attn_norm"][i]
        out[p + "ffn_norm.weight"] = layers["mlp_norm"][i]
        o, f = nth[op], nth[ff]
        if op == "conv":
            out[p + "conv.in_proj.weight"] = layers["conv_in"][o].T
            out[p + "conv.conv.weight"] = layers["conv_w"][o].T[:, None, :]
            out[p + "conv.out_proj.weight"] = layers["conv_out"][o].T
        else:
            for key, name in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"), ("wo", "out_proj")):
                out[f"{p}self_attn.{name}.weight"] = layers[key][o].T
            out[p + "self_attn.q_layernorm.weight"] = layers["q_norm"][o]
            out[p + "self_attn.k_layernorm.weight"] = layers["k_norm"][o]
        if ff == "dense":
            for key, name in (("dense_gate", "w1"), ("dense_up", "w3"), ("dense_down", "w2")):
                out[f"{p}feed_forward.{name}.weight"] = layers[key][f].T
        else:
            out[p + "feed_forward.gate.weight"] = layers["router"][f].T
            out[p + "feed_forward.expert_bias"] = layers["router_bias"][f]
            for key, name in (("w_gate", "w1"), ("w_up", "w3"), ("w_down", "w2")):
                for e in range(CFG.num_experts):
                    out[f"{p}feed_forward.experts.{e}.{name}.weight"] = layers[key][f, e].T
        nth[op] += 1
        nth[ff] += 1
    save_file({k: np.ascontiguousarray(v) for k, v in out.items()}, str(tmp_path / "model.safetensors"))
    write_config(str(tmp_path), CFG)
    loaded, cfg = load_params(str(tmp_path))
    assert cfg == CFG
    assert set(loaded["layers"]) == set(params["layers"])
    for k, v in params["layers"].items():
        assert loaded["layers"][k].dtype == v.dtype, k
        np.testing.assert_array_equal(loaded["layers"][k], np.asarray(v), err_msg=k)
    np.testing.assert_array_equal(loaded["embed"], np.asarray(params["embed"]))
