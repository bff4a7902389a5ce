"""``exaone_moe`` (K-EXAONE-236B-A23B): sliding and full attention layers in
one stack, the sliding width inside the attention kernel, a page pool by
layer kind whose sliding pages go back behind the width, rotary on the
sliding layers alone, a held share of bias-selected sigmoid experts beside
a shared one. Tiny sizes, seeded weights, float32, against the plain
reference (``benchmark/references/exaone_moe.py``: the mask written as a
mask, no cache)."""

import asyncio
import dataclasses
import os
import random
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
from dynamo_exp_tpu.engine.config import refuse_sliding_pool_tiers
from dynamo_exp_tpu.engine.kv_manager import KvPageManager, SlidingPagePool
from dynamo_exp_tpu.models import ModelConfig, llama
from dynamo_exp_tpu.models.config import (
    MISTRAL_7B, PRESETS, TINY_EXAONE_MOE, TINY_GLM_DSA, TINY_MIMO, TINY_PANGU,
)
from dynamo_exp_tpu.ops import moe
from dynamo_exp_tpu.protocols.common import BackendInput

# Jitted: a shape and set of options compiles once a file (tests/programs.py).
from .programs import forward, forward_ragged, init_params

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmark"))
from references import exaone_moe as reference  # noqa: E402

CFG = dataclasses.replace(TINY_EXAONE_MOE, dtype="float32")
W = CFG.sliding_window  # 8
HF = {
    "model_type": "exaone_moe", "vocab_size": CFG.vocab_size,
    "hidden_size": CFG.hidden_size, "intermediate_size": CFG.intermediate_size,
    "num_hidden_layers": CFG.num_layers, "num_attention_heads": CFG.num_heads,
    "num_key_value_heads": CFG.num_kv_heads, "head_dim": 16,
    "max_position_embeddings": 512, "rms_norm_eps": CFG.rms_norm_eps,
    "rope_parameters": {"rope_theta": CFG.rope_theta, "rope_type": "default"},
    "tie_word_embeddings": False, "torch_dtype": "float32", "hidden_act": "silu",
    "norm_topk_prob": True, "layer_types": list(CFG.layer_types),
    "sliding_window": W, "sliding_windows": [W, W, W, 0, W],
    "first_k_dense_replace": 1,
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "num_experts": CFG.num_experts_held, "num_experts_global": CFG.num_experts,
    "first_routed_expert": CFG.first_expert_held, "num_shared_experts": 1,
    "num_experts_per_tok": CFG.num_experts_per_tok,
    "moe_intermediate_size": CFG.moe_intermediate_size,
    "routed_scaling_factor": CFG.routed_scaling_factor, "scoring_func": "sigmoid",
    "n_group": 1, "topk_group": 1, "num_nextn_predict_layers": 0,
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
    assert cfg.op_kinds == ("attn",) * 5
    assert cfg.attn_forms == ("sliding", "sliding", "sliding", "full", "sliding")
    assert cfg.ff_kinds == ("dense", "routed", "routed", "routed", "routed")
    assert cfg.kv_by_kind and cfg.attn_kernel_form and cfg.num_sliding_layers == 4
    # a run ends where the feed-forward's kind or attention's form changes
    assert cfg.layer_runs == (
        ("attn", "dense", 0, 1, 0, 0), ("attn", "routed", 1, 2, 1, 0),
        ("attn", "routed", 3, 1, 3, 2), ("attn", "routed", 4, 1, 4, 3),
    )
    assert cfg.num_experts == 8 and cfg.experts_held == 4 and cfg.first_expert_held == 4
    assert cfg.use_expert_bias and cfg.rope_sliding_only and cfg.qk_norm


def test_the_published_widths_give_the_cut_of_the_issue():
    import json

    path = os.path.join(
        os.path.dirname(__file__), "..", "benchmark", "configs", "k-exaone-236b-l8e16.json"
    )
    with open(path) as f:
        cfg = ModelConfig.from_hf_config(json.load(f))
    assert [r[3] for r in cfg.layer_runs] == [1, 2, 1, 3, 1]  # S-dense / S S / F / S S S / F
    assert cfg.kv_pool_widths == (1024, 1024) and cfg.num_sliding_layers == 6
    shapes = jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert n == 3_865_420_672  # 7.73 GB bfloat16 (the router's bias, float32, counted)


@pytest.mark.parametrize("key,value,why", [
    ("num_nextn_predict_layers", 1, "multi-token-prediction"),
    ("hidden_act", "gelu", "hidden_act"),
    ("n_group", 2, "group-limited"),
    ("topk_group", 2, "group-limited"),
    ("attention_bias", True, "attention_bias"),
    ("scoring_func", "softmax", "scoring_func"),
    ("rope_parameters", {"rope_theta": 1e6, "rope_type": "yarn"}, "rope_type"),
    ("sliding_windows", [W, W, W, W, W], "disagrees"),
    ("mlp_layer_types", ["dense", "sparse", "dense", "sparse", "sparse"], "mlp_layer_types"),
    ("layer_types", ["sliding_attention"] * 4, "layer_types"),
    ("first_routed_expert", 6, "outside the router"),
    ("sliding_window", None, "sliding_window"),
])
def test_reader_refuses_what_it_does_not_compute(key, value, why):
    with pytest.raises(ValueError, match=why):
        ModelConfig.from_hf_config({**HF, key: value})


def test_parameters_and_pools_by_kind(params):
    layers = params["layers"]
    assert layers["wq"].shape == (5, 64, 64) and layers["q_norm"].shape == (5, 16)
    assert layers["router"].shape == (4, 64, 8) and layers["w_gate"].shape == (4, 4, 64, 32)
    assert layers["router_bias"].shape == (4, 8) and layers["dense_gate"].shape == (1, 64, 128)
    assert layers["shared_gate"].shape == (4, 64, 32)
    k, v = llama.init_kv_cache(CFG, 32, PS, sliding_pages=12)
    assert [a.shape for a in k] == [a.shape for a in v] == [(1, 32, PS, 32), (4, 12, PS, 32)]
    # one width everywhere (mistral) stays one run, one pool, one table
    assert not MISTRAL_7B.kv_by_kind and len(MISTRAL_7B.layer_runs) == 1
    assert set(MISTRAL_7B.attn_forms) == {"sliding"} and MISTRAL_7B.attn_kernel_form
    assert not PRESETS["gemma2-9b"].attn_kernel_form  # a soft-cap keeps the gather


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
    8 and several pages of 4; the sliding pool is a RING of 8 pages a row
    (a page index maps to ``j % 8``), so every page behind the width has
    been overwritten by the time a later chunk reads."""
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
    """K after its norm and rotation on a sliding layer, K after its norm
    and NO rotation on a full layer, V; whatever no token wrote stays zero."""
    n = 23
    full, sliding = _tables(1, 8, 8)
    k, v = llama.init_kv_cache(CFG, 8, PS, sliding_pages=8)
    _, k, v = forward(
        params, CFG, jnp.asarray([PROMPT[:n]], jnp.int32), jnp.arange(n)[None],
        (full, sliding), k, v,
    )
    entering, _ = reference.hidden_states(params, HF, PROMPT[:n], range(5))
    nth = {"sliding": 0, "full": 0}
    for layer, form in enumerate(CFG.attn_forms):
        kind = int(form == "sliding")
        table = (full, sliding)[kind][0]
        pool_k = np.asarray(k[kind][nth[form]])[np.asarray(table)].reshape(-1, 32)
        pool_v = np.asarray(v[kind][nth[form]])[np.asarray(table)].reshape(-1, 32)
        want_k, want_v = reference.kv_rows(params, HF, layer, entering[layer])
        assert np.abs(pool_k[:n] - want_k).max() < 1e-4, layer
        assert np.abs(pool_v[:n] - want_v).max() < 1e-4, layer
        assert (pool_k[n:] == 0).all() and (pool_v[n:] == 0).all()
        nth[form] += 1
    # the full layer's keys are NOT rotated: rotating them changes them
    u = reference._rms_norm(
        jnp.asarray(entering[3]), params["layers"]["attn_norm"][3], CFG.rms_norm_eps
    )
    _, rotated, _ = reference._qkv(
        u, reference._attn_params(params["layers"], 3), reference.block_constants(HF),
        True, jnp.arange(n),
    )
    assert np.abs(np.asarray(rotated).reshape(n, -1) - reference.kv_rows(params, HF, 3, entering[3])[0]).max() > 0.1


@pytest.mark.parametrize("fault", ["width_127", "width_129", "rope_on_full", "released_early"])
def test_a_fault_shows(params, fault):
    """The controls the chip's comparison has, at the tiny size: the width
    off by one either way, rotation applied on the full layer, a sliding
    page given back one page early."""
    n = 40
    want = reference.log_probs(params, HF, PROMPT[:n], range(n))
    cfg = CFG
    if fault.startswith("width"):
        cfg = dataclasses.replace(CFG, sliding_window=W + (1 if fault == "width_129" else -1))
    if fault == "rope_on_full":
        cfg = dataclasses.replace(CFG, rope_sliding_only=False)
    full, sliding = _tables(1, 10, 10)
    k, v = llama.init_kv_cache(cfg, 10, PS, sliding_pages=10)
    logits, k, v = forward(
        params, cfg, jnp.asarray([PROMPT[:n - 1]], jnp.int32), jnp.arange(n - 1)[None],
        (full, sliding), k, v,
    )
    if fault == "released_early":
        # the page that still holds position (n - 1) - 7's neighbours
        gone = int(sliding[0, (n - 1 - (W - 1)) // PS])
        k = (k[0], k[1].at[:, gone].set(0.0))
    last, *_ = forward(
        params, cfg, jnp.asarray([[PROMPT[n - 1]]], jnp.int32), jnp.asarray([[n - 1]]),
        (full, sliding), k, v,
    )
    assert np.abs(_logp(last[0, 0]) - want[n - 1]).max() > 10 * LOGP_TOL


# ------------------------------------------------------------ the page pools
def test_pages_go_back_exactly_behind_the_width():
    pool = SlidingPagePool(32, 4, 8)
    ids: list[int] = []
    pool.extend(ids, 22)  # positions 0..22: six pages
    assert len(ids) == 6 and pool.held == 6
    # next query at 23 sees 16..23: pages 0..3 (positions 0..15) go back
    first = pool.release_behind(ids, 0, 23)
    assert first == 4 and ids[:4] == [0] * 4 and pool.held == 2
    # at 24 it sees 17..24: page 4 (16..19) still holds 17..19
    assert pool.release_behind(ids, first, 24) == 4
    assert pool.release_behind(ids, first, 27) == 5  # sees 20..27
    pool.release(ids, 5)
    assert pool.held == 0 and not ids and not pool.ledger_check()
    assert pool.released_total == 5


@pytest.mark.parametrize("seed", range(6))
def test_no_page_a_live_query_can_see_is_ever_given_back(seed):
    """Random chunk schedules over several rows: after every consumed
    dispatch each row holds every page a query at or after its next position
    can see, and nothing before them; both free lists are whole at the end."""
    rng = random.Random(seed)
    ps, width = rng.choice([(4, 8), (4, 5), (8, 3), (16, 128)])
    pool = SlidingPagePool(4096, ps, width)
    rows = [{"ids": [], "first": 0, "pos": 0, "len": rng.randint(1, 40 * ps)} for _ in range(5)]
    while any(r["pos"] < r["len"] for r in rows):
        for r in rows:
            if r["pos"] >= r["len"] or rng.random() < 0.3:
                continue
            n = min(rng.randint(1, 3 * ps), r["len"] - r["pos"])
            pool.extend(r["ids"], r["pos"] + n - 1)  # before the dispatch
            r["pos"] += n
            r["first"] = pool.release_behind(r["ids"], r["first"], r["pos"])  # consumed
            lo = max(r["pos"] - (width - 1), 0)  # the next query's first key
            assert r["first"] == lo // ps
            held = r["ids"][r["first"]:]
            assert len(set(held)) == len(held) and not set(held) & set(pool._free)
            assert len(r["ids"]) == (r["pos"] - 1) // ps + 1
        assert not pool.ledger_check()
        assert pool.held == sum(len(r["ids"]) - r["first"] for r in rows)
    assert not pool.audit({str(i): r["ids"][r["first"]:] for i, r in enumerate(rows)})
    for r in rows:
        pool.release(r["ids"], r["first"])
    assert pool.held == 0 and sorted(pool._free) == list(range(4096))


def test_the_manager_counts_both_pools():
    kv = KvPageManager(16, 4, sharing=False, sliding=SlidingPagePool(8, 4, 8))
    assert not kv.ledger_check()
    ids: list[int] = []
    kv.sliding.extend(ids, 11)
    assert kv.sliding.usage == 3 / 8 and kv.usage == 0.0
    kv.sliding.held += 1  # a lost page shows in the same ledger
    assert any("sliding-page conservation" in v for v in kv.ledger_check())
    kv.sliding.held -= 1
    report = kv.audit({}, {"seq:a": ids, "seq:b": ids[:1]})
    assert [v["kind"] for v in report["violations"]] == ["shared_sliding_page"]
    with pytest.raises(RuntimeError, match="ran dry"):
        kv.sliding.extend([], 8 * 4)


def test_the_sliding_pool_is_sized_by_the_dispatch_not_the_context():
    cfg = EngineConfig(
        model=PRESETS["tiny-exaone-moe"], max_decode_slots=32, page_size=16,
        num_pages=24576, max_model_len=32768, prefill_chunk=512, decode_window=8,
    )
    cfg.model = dataclasses.replace(cfg.model, sliding_window=128)
    assert cfg.sliding_pool_pages == 8192 // 16 + 32 * (8 + 2 + 2) == 896
    assert cfg.sliding_pool_pages < cfg.max_pages_per_seq  # one row's context is more
    assert EngineConfig(model=PRESETS["tiny"]).sliding_pool_pages is None


# ------------------------------------------------------------------ the share
def _whole(cfg, params):
    """The uncut layer's parameters: every expert, of which ``params`` holds
    ``cfg``'s share; the others seeded here."""
    lo, n = cfg.first_expert_held, cfg.experts_held
    full = init_params(
        jax.random.PRNGKey(11), dataclasses.replace(cfg, num_experts_held=0, first_expert_held=0)
    )
    layers = dict(full["layers"])
    for name in ("router", "router_bias", "shared_gate", "shared_up", "shared_down"):
        if name in params["layers"]:
            layers[name] = params["layers"][name]
    for name in llama.EXPERT_STACKS:
        layers[name] = layers[name].at[:, lo:lo + n].set(params["layers"][name])
    return layers


@pytest.mark.parametrize(
    "preset", [TINY_EXAONE_MOE, TINY_GLM_DSA, TINY_PANGU, TINY_MIMO], ids=lambda c: c.model_type
)
def test_the_shares_of_all_ranks_add_up_to_the_uncut_layer(preset):
    """Every rank's share of the first routed layer, each by ``moe_ffn``
    told which experts it holds, plus the shared expert ONCE (mimo_v2_flash
    has none to count), equals the layer with every expert held (the
    guide's section 4 test)."""
    cfg = dataclasses.replace(preset, dtype="float32")
    held = cfg.experts_held
    layers = _whole(cfg, init_params(jax.random.PRNGKey(SEED), cfg))
    h = jnp.asarray(np.random.default_rng(7).standard_normal((96, cfg.hidden_size)), jnp.float32)
    router = dict(
        score="sigmoid", scale=cfg.routed_scaling_factor, norm_eps=cfg.router_norm_eps,
        **({"select_bias": layers["router_bias"][0]} if cfg.use_expert_bias else {}),
    )
    stacks = [layers[k][0] for k in llama.EXPERT_STACKS]
    whole = moe.moe_ffn(h, layers["router"][0], *stacks, cfg.num_experts_per_tok, True, **router)
    parts = sum(
        moe.moe_ffn(
            h, layers["router"][0], *(s[lo:lo + held] for s in stacks),
            cfg.num_experts_per_tok, True, held_from=lo, **router,
        )
        for lo in range(0, cfg.num_experts, held)
    )
    assert cfg.num_experts // held >= 2
    assert np.abs(np.asarray(parts - whole)).max() < 1e-4
    assert float(jnp.abs(whole).max()) > 0.1


def test_the_references_share_is_the_engines(params):
    """The reference given the same share: the second half of the first
    routed layer equals ``_ffn_block``'s, and the reference's uncut layer is
    what the two shares and the shared expert add up to."""
    x = jnp.asarray(np.random.default_rng(7).standard_normal((96, 64)), jnp.float32)
    lw = params["layers"]
    lp = {"mlp_norm": lw["mlp_norm"][1]}
    lp.update((k, lw[k][0]) for k in (
        "router", "router_bias", "shared_gate", "shared_up", "shared_down", *llama.EXPERT_STACKS
    ))
    got = llama._ffn_block(x[None], lp, CFG, CFG.rms_norm_eps, lambda y: y, None)[0]
    want, margin, here = reference.ffn_half(params, HF, 1, x)
    assert np.abs(np.asarray(got) - want).max() < 1e-4 and 0.2 < here < 0.8
    assert margin.shape == (96,) and (margin >= 0).all()
    whole = {**params, "layers": _whole(CFG, params)}
    uncut, _, all_held = reference.ffn_half(
        whole, {**HF, "num_experts": 8, "first_routed_expert": 0}, 1, x, normed=False
    )
    assert all_held == 1.0
    shared = reference._swiglu(
        llama.rms_norm(x, lw["mlp_norm"][1], CFG.rms_norm_eps),
        lw["shared_gate"][0], lw["shared_up"][0], lw["shared_down"][0],
    )
    total = np.asarray(shared)
    for lo in (0, 4):
        part = {k: whole["layers"][k][:, lo:lo + 4] for k in llama.EXPERT_STACKS}
        y, _, _ = reference.ffn_half(
            {**whole, "layers": {**whole["layers"], **part}},
            {**HF, "num_experts": 4, "first_routed_expert": lo}, 1, x, normed=False,
        )
        total = total + y - np.asarray(shared)
    assert np.abs(total - uncut).max() < 1e-4


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


def _counter(name, **labels):
    from dynamo_exp_tpu.telemetry import get_telemetry

    total = 0.0
    for metric in get_telemetry().registry.collect():
        for s in metric.samples:
            if s.name == name and all(s.labels.get(k) == v for k, v in labels.items()):
                total += s.value
    return total


async def test_engine_serves_rows_of_unequal_length_through_both_pools(engine):
    """Through the scheduler: prompts of 61, 5 and 37 tokens in chunks of
    16 (mixed program), then windows of 4 steps, each equal to the
    reference's greedy continuation; sliding pages went back while the rows
    lived; the counters say what the queries could see."""
    assert [a.shape for a in engine.k_cache] == [(1, 96, PS, 32), (4, engine.cfg.sliding_pool_pages, PS, 32)]
    names = ("dynamo_attn_query_tokens_total", "dynamo_attn_key_positions_total")
    before = {(n, k): _counter(n, kind=k) for n in names for k in ("sliding", "full")}
    released = _counter("dynamo_kv_sliding_pages_released_total")
    prompts = [PROMPT[:61], PROMPT[61:66], PROMPT[30:67]]
    got = await asyncio.gather(*(_run(engine, p, 12) for p in prompts))
    for p, g in zip(prompts, got):
        assert g == _greedy_reference(engine.params, p, 12)
    took = {key: _counter(key[0], kind=key[1]) - v for key, v in before.items()}
    fed = sum(len(p) + 11 for p in prompts)
    assert took[names[0], "sliding"] >= 4 * fed and took[names[0], "full"] >= fed
    # a sliding layer's query sees 8 keys at the most, a full layer's its context
    assert took[names[1], "sliding"] <= W * took[names[0], "sliding"]
    assert took[names[1], "full"] > 20 * took[names[0], "full"]
    assert _counter("dynamo_kv_sliding_pages_released_total") - released >= 61 // PS - 3
    assert engine.kv.sliding.held == 0 and not engine.kv.ledger_check()
    assert engine.kv_audit()["ok"]
    assert engine.kv.sliding.peak_held <= engine.cfg.sliding_pool_pages


async def test_a_shared_prefix_is_asked_twice_for_the_references_logits(engine):
    """Two requests with a shared 64-token prefix (the second after the
    first has finished and given its sliding pages back): a cache by kind
    grants no hit, and both streams are the reference's."""
    hits = dict(engine.kv.prefix_hits)
    first, second = PROMPT[:64] + [9, 8, 7], PROMPT[:64] + [5, 6]
    for prompt in (first, second, first):
        assert await _run(engine, prompt, 6) == _greedy_reference(engine.params, prompt, 6)
    assert not engine.kv.sharing
    assert engine.kv.prefix_hits["shared"] == hits["shared"]


def test_pool_info_says_what_each_pool_holds(engine):
    from dynamo_exp_tpu.telemetry import get_telemetry

    samples = {
        s.name: s.labels for m in get_telemetry().registry.collect() for s in m.samples
        if s.name in ("dynamo_kv_pool_info", "dynamo_kv_sliding_pool_info")
    }
    full, sliding = samples["dynamo_kv_pool_info"], samples["dynamo_kv_sliding_pool_info"]
    assert full["layers"] == "1" and full["pages"] == "96" and full["token_bytes"] == str(2 * 32 * 4)
    assert sliding["layers"] == "4" and sliding["sliding_width"] == "8"
    assert sliding["page_bytes"] == str(2 * 4 * PS * 32 * 4)
    assert sliding["pages"] == str(engine.cfg.sliding_pool_pages)
    # the margin behind that arithmetic, once the loop has published it
    engine._last_gauge_pub = 0.0
    engine._maybe_publish_gauges()
    peak = _counter("dynamo_kv_sliding_pool_peak_pages")
    assert 0 < peak == engine.kv.sliding.peak_held <= engine.cfg.sliding_pool_pages


def test_engine_through_the_kernel():
    """The same path with the kernel (interpreted) in both programs: tiles
    of 8 and the one-query form, each with the width. A stack of two layers
    (a sliding one with the dense feed-forward, a full one with the routed)
    on two slots of 64 positions, and its own event loop: every run of
    layers traces and runs an interpreted kernel a program, and the whole
    stack's four on the other tests' engine outlast the runner's limit for
    an async test on a loaded machine (77 s alone; this takes 40)."""
    hf = {
        **HF, "num_hidden_layers": 2, "layer_types": ["sliding_attention", "full_attention"],
        "sliding_windows": [W, 0], "mlp_layer_types": ["dense", "sparse"],
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


async def test_preempt_resume_and_abort_leave_both_free_lists_whole():
    """Two requests on a full pool that holds one and a half: one is
    preempted and re-prefilled; a third is cancelled mid-prefill; both
    pools' free lists are whole afterwards."""
    eng = _engine(num_pages=26)
    eng.start()
    try:
        p1, p2, n = PROMPT[:19], PROMPT[20:39], 44
        r1, r2 = await asyncio.gather(_run(eng, p1, n), _run(eng, p2, n))
        assert eng.preempted > 0
        assert r1 == _greedy_reference(eng.params, p1, n)
        assert r2 == _greedy_reference(eng.params, p2, n)
        task = asyncio.ensure_future(_run(eng, PROMPT[:90], 4))
        await asyncio.sleep(0.05)
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)
        for _ in range(100):
            if not eng.sched.has_work():
                break
            await asyncio.sleep(0.05)
        assert eng.kv.sliding.held == 0 and eng.kv.active_pages == 0
        assert not eng.kv.ledger_check() and eng.kv_audit()["ok"]
    finally:
        eng.stop()


# ---------------------------------------------------------------- refusals
@pytest.mark.parametrize("tier", [
    "host_cache_pages", "kv_store_dir", "spec_mode", "sp", "tp",
    "disaggregated transfer", "migration",
])
def test_tiers_refuse_a_cache_by_kind(tier, tmp_path):
    kw = {
        "host_cache_pages": {"host_cache_pages": 8}, "spec_mode": {"spec_mode": "ngram"},
        "kv_store_dir": {"host_cache_pages": 8, "kv_store_dir": str(tmp_path)},
        "sp": {"sp": 2}, "tp": {"tp": 2},
    }.get(tier, {})
    cfg = EngineConfig(model=CFG, max_decode_slots=2, page_size=PS, num_pages=16, **kw)
    with pytest.raises(ValueError, match="4 sliding-attention layers .width 8. in a page pool of their own"):
        if kw:
            TPUEngine(cfg)
        else:
            refuse_sliding_pool_tiers(cfg, tier)
    refuse_sliding_pool_tiers(EngineConfig(model=PRESETS["tiny"], host_cache_pages=8))


def test_ring_prefill_refuses(params):
    with pytest.raises(ValueError, match="sliding"):
        llama.forward_ring_prefill(
            params, CFG, jnp.zeros((1, 8), jnp.int32), jnp.arange(8)[None],
            types.SimpleNamespace(shape={"sp": 2, "tp": 1}),
        )


def test_a_sliding_width_resolves_to_the_kernel_and_a_cap_to_the_gather():
    from dynamo_exp_tpu.engine.engine import resolve_attn_impl
    from dynamo_exp_tpu.parallel import single_device_mesh

    mesh = single_device_mesh()

    def resolved(model):
        cfg = EngineConfig(model=model, attention_impl="pallas", pallas_interpret=True)
        return resolve_attn_impl(cfg, mesh)[0]

    assert resolved(CFG) == "pallas"
    assert resolved(MISTRAL_7B) == "pallas"
    assert resolved(PRESETS["gemma2-9b"]) == "xla"
    assert resolved(dataclasses.replace(PRESETS["tiny"], query_pre_attn_scalar=16.0)) == "xla"


def test_the_scopes_are_in_the_program(params):
    """A sliding run's kernel call stands under ``swa_attn`` (inside
    ``attend``), a full run's under ``attend`` alone."""
    full, sliding = _tables(1, 4, 4)
    k, v = llama.init_kv_cache(CFG, 4, PS, sliding_pages=4)
    text = jax.jit(
        lambda p, k, v: llama.forward(
            p, CFG, jnp.zeros((1, 1), jnp.int32), jnp.zeros((1, 1), jnp.int32),
            (full, sliding), k, v, attn_impl="pallas", interpret=True,
        )
    ).lower(params, k, v).as_text(debug_info=True)
    assert "attend/swa_attn" in text
    assert text.count("swa_attn") < text.count("attend")


def test_checkpoint_names_round_trip(tmp_path, params):
    """A checkpoint under the family's tensor names with all 8 experts in
    it loads into the stacks by kind, the held share alone; one that carries
    EXAONE 4.0's ``post_feedforward_layernorm`` is refused."""
    from safetensors.numpy import save_file

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
    for i, ff in enumerate(CFG.ff_kinds):
        p = f"model.layers.{i}."
        out[p + "input_layernorm.weight"] = own["attn_norm"][i]
        out[p + "post_attention_layernorm.weight"] = own["mlp_norm"][i]
        for key, name in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"), ("wo", "o_proj")):
            out[f"{p}self_attn.{name}.weight"] = own[key][i].T
        out[p + "self_attn.q_norm.weight"] = own["q_norm"][i]
        out[p + "self_attn.k_norm.weight"] = own["k_norm"][i]
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
                out[f"{p}mlp.experts.{e}.{name}.weight"] = np.asarray(whole[f"w_{key}"][f, e]).T
    path = tmp_path / "ok"
    path.mkdir()
    save_file({k: np.ascontiguousarray(v) for k, v in out.items()}, str(path / "model.safetensors"))
    write_config(str(path), CFG)
    loaded, cfg = load_params(str(path))
    assert cfg == CFG and set(loaded["layers"]) == set(params["layers"])
    for k, v in params["layers"].items():
        np.testing.assert_array_equal(loaded["layers"][k], np.asarray(v), err_msg=k)
    bad = tmp_path / "exaone4"
    bad.mkdir()
    out["model.layers.0.post_feedforward_layernorm.weight"] = own["mlp_norm"][0]
    save_file({k: np.ascontiguousarray(v) for k, v in out.items()}, str(bad / "model.safetensors"))
    write_config(str(bad), CFG)
    with pytest.raises(ValueError, match="post_feedforward_layernorm"):
        load_params(str(bad))
