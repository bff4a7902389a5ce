"""``ouro`` (Ouro-2.6B, a looped language model): a stack of layers run
several times a token over ONE set of weights, a layer slot of the cache for
every (pass, layer), the final norm closing every pass, an exit gate held and
not evaluated. Tiny sizes (4 layers x 3 passes, so passes != layers != the 4
heads and a transposed index shows), seeded weights, float32, against the
plain reference (``benchmark/references/ouro.py``: no cache, no kernel)."""

import asyncio
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
from dynamo_exp_tpu.engine.config import refuse_looped_tiers
from dynamo_exp_tpu.models import ModelConfig, llama
from dynamo_exp_tpu.models.config import PRESETS, TINY_OURO, TINY_QWEN3
from dynamo_exp_tpu.protocols.common import BackendInput

# Jitted: a shape and set of options compiles once a file (tests/programs.py).
from .programs import forward, forward_ragged, init_params

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmark"))
from references import ouro as reference  # noqa: E402

CFG = dataclasses.replace(TINY_OURO, dtype="float32")
L, T = CFG.num_layers, CFG.loop_passes  # 4, 3
HF = {
    "model_type": "ouro", "vocab_size": CFG.vocab_size, "hidden_size": CFG.hidden_size,
    "intermediate_size": CFG.intermediate_size, "num_hidden_layers": L,
    "num_attention_heads": CFG.num_heads, "num_key_value_heads": CFG.num_kv_heads,
    "head_dim": 16, "hidden_act": "silu", "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "rope_scaling": None, "sliding_window": None, "use_sliding_window": False,
    "layer_types": ["full_attention"] * L, "max_window_layers": L,
    "max_position_embeddings": 512, "tie_word_embeddings": False,
    "torch_dtype": "float32", "total_ut_steps": T, "early_exit_threshold": 1,
}
PS = 4
LOGP_TOL = 1e-3  # float32 all through: the sound program reads ~1e-5, every control over 0.1
SEED = 5
PROMPT = [int(t) for t in np.random.default_rng(0).integers(2, 256, 96)]


@pytest.fixture(scope="module")
def params():
    """Seeded weights with the norms' scales drawn too: scales of one would
    hide a norm taken for another."""
    p = init_params(jax.random.PRNGKey(SEED), CFG)
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED + 1), 8))

    def drawn(w):
        return 1.0 + 0.3 * jax.random.normal(next(keys), w.shape, w.dtype)

    layers = {
        k: drawn(w) if k.endswith("_norm") else w for k, w in p["layers"].items()
    }
    return {**p, "layers": layers, "final_norm": drawn(p["final_norm"])}


def _logp(logits):
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


def _impl(impl):
    return {"attn_impl": "pallas", "interpret": True} if impl == "pallas" else {}


# ------------------------------------------------------------------ reader
def test_config_reads_the_published_keys():
    assert ModelConfig.from_hf_config(HF) == CFG and PRESETS["tiny-ouro"] == TINY_OURO
    assert CFG.post_norms and CFG.pre_norms and not CFG.qk_norm
    assert (CFG.num_attn_layers, CFG.kv_layer_slots, len(CFG.layer_runs)) == (L, T * L, 1)
    assert TINY_QWEN3.loop_passes == 1 and TINY_QWEN3.kv_layer_slots == TINY_QWEN3.num_layers


def test_the_published_widths_give_the_numbers_of_the_issue():
    """Ouro-2.6B from the catalog row's keys: 192 layer slots, 1.5 MiB of K
    and V a token, 2,667,974,657 parameters of which 2,049 the gate's."""
    published = ModelConfig.from_hf_config({
        **HF, "vocab_size": 49152, "hidden_size": 2048, "intermediate_size": 5632,
        "num_hidden_layers": 48, "num_attention_heads": 16, "num_key_value_heads": 16,
        "head_dim": 128, "layer_types": ["full_attention"] * 48, "total_ut_steps": 4,
        "torch_dtype": "bfloat16", "max_position_embeddings": 65536,
    })
    assert (published.loop_passes, published.kv_layer_slots, published.q_per_kv) == (4, 192, 1)
    k, v = jax.eval_shape(lambda: llama.init_kv_cache(published, 2, 16))
    assert k.shape == v.shape == (192, 2, 16, 2048)
    assert (k.size + v.size) * 2 // (2 * 16) == 1_572_864
    shapes = jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0), published))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 2_667_974_657
    assert shapes["exit_gate_w"].shape == (2048,) and shapes["exit_gate_b"].shape == (1,)
    assert shapes["layers"]["wq"].shape[0] == 48  # ONE set of layers


@pytest.mark.parametrize("key,value,why", [
    ("early_exit_threshold", 0.9, "early_exit_threshold"),
    ("total_ut_steps", 0, "total_ut_steps"),
    ("use_sliding_window", True, "use_sliding_window"),
    ("sliding_window", 4096, "sliding_window"),
    ("layer_types", ["full_attention"] * 3 + ["sliding_attention"], "layer_types"),
    ("layer_types", ["full_attention"] * 3, "layer_types"),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}, "rope_scaling"),
    ("hidden_act", "gelu", "hidden_act"),
])
def test_reader_refuses_what_it_does_not_compute(key, value, why):
    with pytest.raises(ValueError, match=why):
        ModelConfig.from_hf_config({**HF, key: value})


def test_parameters_and_pools(params):
    """L layers of weights whatever the passes; passes x L layer slots; the
    gate in the tree, in the model's dtype, and in the shardings."""
    layers = params["layers"]
    assert {k: v.shape[0] for k, v in layers.items()} == dict.fromkeys(layers, L)
    assert {"attn_norm", "post_attn_norm", "mlp_norm", "post_ffn_norm"} <= set(layers)
    assert params["exit_gate_w"].shape == (64,) and params["exit_gate_b"].shape == (1,)
    assert params["exit_gate_w"].dtype == params["embed"].dtype
    specs = llama.param_shardings(CFG)
    assert jax.tree.structure(specs, is_leaf=lambda x: not isinstance(x, dict)) == (
        jax.tree.structure(params)
    )
    k, v = llama.init_kv_cache(CFG, 8, PS)
    assert k.shape == v.shape == (T * L, 8, PS, 64)


# ------------------------------------------- through the cache, against the reference
def _serve(params, cfg, impl, lengths=(45, 23), steps=4, chunk=16, pools=CFG):
    """Rows of unequal length prefilled in chunks of ``chunk`` in one stream,
    a chunk running every pass before the next is fed, then ``steps`` tokens
    decoded a step at a time: each position's log-probabilities, a row."""
    seqs = [PROMPT[7 * r: 7 * r + n + steps] for r, n in enumerate(lengths)]
    rows = len(lengths)
    table = jnp.asarray(
        np.random.default_rng(3).permutation(rows * 16).reshape(rows, 16), jnp.int32
    )
    k, v = llama.init_kv_cache(pools, rows * 16, PS)
    out = [np.zeros((len(s), cfg.vocab_size), np.float32) for s in seqs]
    row = np.repeat(np.arange(rows, dtype=np.int32), chunk)
    for a in range(0, max(lengths), chunk):
        toks = np.zeros(rows * chunk, np.int32)
        pos = np.full(rows * chunk, -1, np.int32)
        for r, n in enumerate(lengths):
            b = min(a + chunk, n)
            if b > a:
                toks[r * chunk: r * chunk + b - a] = seqs[r][a:b]
                pos[r * chunk: r * chunk + b - a] = np.arange(a, b)
        logits, k, v = forward_ragged(
            params, cfg, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(row), table,
            k, v, jnp.arange(rows * chunk, dtype=jnp.int32), q_tile=8, **_impl(impl),
        )
        got = _logp(logits)
        for at in np.flatnonzero(pos >= 0):
            out[row[at]][pos[at]] = got[at]
    for t in range(steps):
        logits, k, v = forward(
            params, cfg, jnp.asarray([[s[n + t]] for s, n in zip(seqs, lengths)], jnp.int32),
            jnp.asarray([[n + t] for n in lengths], jnp.int32), table, k, v, **_impl(impl),
        )
        got = _logp(logits[:, 0])
        for r, n in enumerate(lengths):
            out[r][n + t] = got[r]
    return seqs, out


def _worst(params, seqs, got) -> float:
    return max(
        float(np.abs(g - reference.log_probs(params, HF, s, range(len(s)))).max())
        for s, g in zip(seqs, got)
    )


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_chunked_prefill_then_decode_through_every_pass_slot(params, impl):
    """Two rows (45 and 23 tokens) in chunks of 16, then 4 decoded tokens:
    every position's log-probabilities equal the reference's full forward,
    on the gather and through the interpreted kernel."""
    seqs, got = _serve(params, CFG, impl)
    assert _worst(params, seqs, got) < LOGP_TOL


def test_the_gate_is_held_and_moves_no_logit(params):
    """Other gate weights, the same logits; the reference's four (here
    three) ``lambda_t`` are a sigmoid's and differ from pass to pass."""
    ids = PROMPT[:20]
    lam = reference.exit_gates(params, HF, ids)
    assert lam.shape == (T, 20) and ((lam > 0) & (lam < 1)).all()
    assert np.abs(lam[0] - lam[1]).max() > 1e-3
    other = {**params, "exit_gate_w": params["exit_gate_w"] * -3.0,
             "exit_gate_b": params["exit_gate_b"] + 2.0}
    assert np.abs(reference.exit_gates(other, HF, ids) - lam).max() > 0.05
    np.testing.assert_array_equal(
        reference.log_probs(other, HF, ids, [19]), reference.log_probs(params, HF, ids, [19])
    )
    table = jnp.arange(8, dtype=jnp.int32)[None]
    args = (jnp.asarray([ids], jnp.int32), jnp.arange(20, dtype=jnp.int32)[None], table)
    a = forward(params, CFG, *args, *llama.init_kv_cache(CFG, 8, PS))[0]
    b = forward(other, CFG, *args, *llama.init_kv_cache(CFG, 8, PS))[0]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


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


async def _run(eng, prompt, n, logprobs=None):
    b = BackendInput(token_ids=list(prompt))
    b.stop_conditions.max_tokens = n
    b.stop_conditions.ignore_eos = True
    b.sampling_options.logprobs = logprobs
    stream = await eng.generate(b.to_dict())
    tokens, lps = [], []
    async for item in stream:
        tokens.extend(item.get("token_ids", []))
        lps.extend(item.get("logprobs") or [])
    return (tokens, lps) if logprobs is not None else tokens


def _greedy_reference(params, prompt, n):
    ids = list(prompt)
    for _ in range(n):
        ids.append(int(reference.log_probs(params, HF, ids, [len(ids) - 1])[0].argmax()))
    return ids[len(prompt):]


def _samples(*names):
    from dynamo_exp_tpu.telemetry import get_telemetry

    return [
        s for m in get_telemetry().registry.collect() for s in m.samples if s.name in names
    ]


def _value(name, **labels):
    return sum(
        s.value for s in _samples(name)
        if all(s.labels.get(k) == v for k, v in labels.items())
    )


async def test_engine_serves_the_looped_model_and_counts_its_work(engine):
    """Through the scheduler: prompts of 61, 5 and 37 tokens in chunks of 16
    (mixed program), then windows of 4 steps, each equal to the reference's
    greedy continuation; every live token counted as passes x layers layer
    applications, its queries as full-attention queries of every layer-pass."""
    assert engine.k_cache.shape == engine.v_cache.shape == (T * L, 96, PS, 64)
    # kind="full" as they are read below: a worker that ran a model of sliding
    # layers before this file has counted kind="sliding" in the same registry
    before = {
        "dynamo_layer_passes_total": _value("dynamo_layer_passes_total"),
        "dynamo_attn_query_tokens_total": _value("dynamo_attn_query_tokens_total", kind="full"),
        "dynamo_attn_key_positions_total": _value("dynamo_attn_key_positions_total", kind="full"),
    }
    fed = _value("dynamo_ragged_slots_total", kind="live")
    prompts = [PROMPT[:61], PROMPT[61:66], PROMPT[30:67]]
    got = await asyncio.gather(*(_run(engine, p, 12) for p in prompts))
    for p, g in zip(prompts, got):
        assert g == _greedy_reference(engine.params, p, 12)
    assert not engine.kv.ledger_check() and engine.kv_audit()["ok"]
    fed = _value("dynamo_ragged_slots_total", kind="live") - fed
    assert fed >= sum(len(p) + 11 for p in prompts)
    assert _value("dynamo_layer_passes_total") - before["dynamo_layer_passes_total"] == fed * T * L
    # a window's step past a row's token budget is fed and not counted as a query
    queries = _value("dynamo_attn_query_tokens_total", kind="full")
    asked = sum(len(p) + 11 for p in prompts)
    assert asked * T * L <= queries - before["dynamo_attn_query_tokens_total"] <= fed * T * L
    assert _value("dynamo_attn_key_positions_total", kind="full") > (
        before["dynamo_attn_key_positions_total"] + queries
    )


def test_pool_and_loop_info_say_what_the_cache_holds(engine):
    pool, = (s.labels for s in _samples("dynamo_kv_pool_info"))
    # a token: 3 passes x 4 layers x 4 heads x 16 x (K + V) x 4 B, from the arrays
    token_bytes = (engine.k_cache.nbytes + engine.v_cache.nbytes) // (96 * PS)
    assert token_bytes == T * L * 64 * 2 * 4
    assert pool["layers"] == str(T * L) and pool["token_bytes"] == str(token_bytes)
    loop, = (s.labels for s in _samples("dynamo_model_loop_info"))
    assert loop == {"passes": str(T), "weight_layers": str(L), "kv_layers": str(T * L)}


@pytest.mark.parametrize("model", [TINY_QWEN3, CFG], ids=["tiny-qwen3", "tiny-ouro"])
def test_a_pages_bytes_are_the_arrays(model):
    """``kv_page_nbytes`` (the migration planner's cost unit) and the host
    pool's page shape take the cache's layer slots: the pools' bytes a page."""
    model = dataclasses.replace(model, dtype="float32")
    eng = _engine(model, num_pages=8, max_model_len=32, max_decode_slots=2)
    assert eng.kv_page_nbytes() == (eng.k_cache.nbytes + eng.v_cache.nbytes) // 8
    assert eng._page_bytes["full"] == eng.kv_page_nbytes()


async def test_a_prefix_hit_gives_the_logits_of_a_cold_run(engine):
    """A prompt of 9 pages asked twice: the second asking takes its leading
    pages from the cache, every (pass, layer) slot of them, and gives the
    cold run's tokens and log-probabilities."""
    prompt = PROMPT[40:77]
    hits = engine.kv.prefix_hits["shared"]
    cold = await _run(engine, prompt, 6, logprobs=1)
    assert engine.kv.prefix_hits["shared"] == hits
    warm = await _run(engine, prompt, 6, logprobs=1)
    assert engine.kv.prefix_hits["shared"] - hits >= len(prompt) // PS - 1
    assert warm[0] == cold[0] == _greedy_reference(engine.params, prompt, 6)
    np.testing.assert_allclose(warm[1], cold[1], atol=1e-5, rtol=0)


def test_engine_through_the_kernel():
    """The same path with the kernel (interpreted) in both programs: the
    tiled call and the one-query call at one query head a KV head, over the
    slots of every pass. Two layers x 3 passes on two slots."""
    hf = {**HF, "num_hidden_layers": 2, "layer_types": ["full_attention"] * 2}
    eng = _engine(
        ModelConfig.from_hf_config(hf), attention_impl="pallas", pallas_interpret=True,
        max_decode_slots=2, max_model_len=32,
    )
    eng.start()
    try:
        assert eng._attn_impl == "pallas" and eng.k_cache.shape[0] == 2 * T
        prompt = PROMPT[3:24]  # a chunk of 16 and one of 5, then two windows
        want = list(prompt)
        for _ in range(5):
            want.append(int(reference.log_probs(eng.params, hf, want, [len(want) - 1])[0].argmax()))
        assert asyncio.run(_run(eng, prompt, 5)) == want[len(prompt):]
        assert not eng.kv.ledger_check()
    finally:
        eng.stop()


@pytest.mark.parametrize("kw,tier", [
    ({"host_cache_pages": 8}, "host offload"), ({"spec_mode": "ngram"}, "speculative"),
    ({"sp": 2}, "ring prefill"), ({"tp": 2}, "tensor parallelism"),
    ({}, "migration"), ({}, "disaggregated transfer"),
])
def test_tiers_refuse_a_looped_model(kw, tier):
    cfg = EngineConfig(model=CFG, max_decode_slots=2, page_size=PS, num_pages=16, **kw)
    with pytest.raises(ValueError, match=f"4 layers 3 times a token.*12 a page.*{tier}"):
        refuse_looped_tiers(cfg, None if kw else tier)
    if kw:
        with pytest.raises(ValueError, match="3 times a token"):
            TPUEngine(cfg)
    refuse_looped_tiers(dataclasses.replace(cfg, model=TINY_QWEN3), None if kw else tier)


def test_the_manifest_key_moves_with_the_passes():
    """A cached compile of a one-pass model of the same widths, or of
    another number of passes, is not taken for this one's."""
    from dynamo_exp_tpu.aot.lattice import build_manifest

    def key(model):
        ecfg = EngineConfig(model=model, page_size=PS, num_pages=16, max_decode_slots=2)
        return build_manifest(ecfg, "xla", {"tp": 1}, "0").hash()

    keys = {key(dataclasses.replace(CFG, loop_passes=t)) for t in (1, 2, 3)}
    assert len(keys) == 3 and key(CFG) in keys


def test_checkpoint_names_round_trip(tmp_path, params):
    """A checkpoint under the family's tensor names (four norms a layer, the
    closing norm, the gate ``[1, D]`` with its bias) loads into the stacks."""
    from safetensors.numpy import save_file

    from dynamo_exp_tpu.models.loader import load_params
    from dynamo_exp_tpu.models.synth import write_config

    own = {k: np.asarray(v) for k, v in params["layers"].items()}
    out = {
        "model.embed_tokens.weight": np.asarray(params["embed"]),
        "model.norm.weight": np.asarray(params["final_norm"]),
        "lm_head.weight": np.asarray(params["lm_head"]).T,
        "model.early_exit_gate.weight": np.asarray(params["exit_gate_w"])[None],
        "model.early_exit_gate.bias": np.asarray(params["exit_gate_b"]),
    }
    names = {
        "attn_norm": "input_layernorm", "post_attn_norm": "input_layernorm_2",
        "mlp_norm": "post_attention_layernorm", "post_ffn_norm": "post_attention_layernorm_2",
        "wq": "self_attn.q_proj", "wk": "self_attn.k_proj", "wv": "self_attn.v_proj",
        "wo": "self_attn.o_proj", "w_gate": "mlp.gate_proj", "w_up": "mlp.up_proj",
        "w_down": "mlp.down_proj",
    }
    assert set(names) == set(own)
    for i in range(L):
        for key, name in names.items():
            w = own[key][i]
            out[f"model.layers.{i}.{name}.weight"] = w.T if w.ndim == 2 else w
    save_file({k: np.ascontiguousarray(v) for k, v in out.items()}, str(tmp_path / "model.safetensors"))
    write_config(str(tmp_path), CFG)
    loaded, cfg = load_params(str(tmp_path))
    assert cfg == CFG
    want = jax.tree.map(np.asarray, params)
    assert jax.tree.structure(loaded) == jax.tree.structure(want)
    jax.tree.map(np.testing.assert_array_equal, loaded, want)


# ---------------------------------------------------------------- controls
# Each is a wrong loop that a tolerance has to refuse. They patch the model's
# own functions, so they stand at the end of the file and clear JAX's caches
# round the faulty program (tests/programs.py).
def _loop_with(slot0_of=lambda t: t * L, close=True):
    """``llama._loop_passes`` with a fault in the slots or without the norm
    between passes."""

    def loop(params, cfg, carry, run_layers):
        def one_pass(t, carry):
            x, *rest = run_layers(carry, slot0_of(t))
            if close:
                x = jax.lax.cond(
                    t + 1 < cfg.loop_passes,
                    lambda x: llama.rms_norm(x, params["final_norm"], cfg.rms_norm_eps),
                    lambda x: x, x,
                )
            return (x, *rest)

        return jax.lax.fori_loop(0, cfg.loop_passes, one_pass, carry)

    return loop


def _transposed_writes(plain):
    """``write_kv_pages`` taking slot ``t * L + l`` to ``l * T + t``: the
    write alone, the read stays where it was."""

    def write(*args, layer, **kw):
        return plain(*args, layer=(layer % L) * T + layer // L, **kw)

    return write


_CONTROLS = {
    "one_pass_fewer": {"cfg": dataclasses.replace(CFG, loop_passes=T - 1)},
    "every_pass_in_pass_0s_slots": {"_loop_passes": _loop_with(slot0_of=lambda t: 0 * t)},
    "no_norm_between_passes": {"_loop_passes": _loop_with(close=False)},
    "slots_transposed_on_the_write": {"write_kv_pages": _transposed_writes(llama.write_kv_pages)},
}


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("control", list(_CONTROLS))
def test_a_wrong_loop_fails_the_tolerance(params, monkeypatch, control, impl):
    """(i) T - 1 passes, (ii) every pass writing and reading pass 0's slots,
    (iii) no norm between passes, (iv) slots transposed on the write alone:
    each reads far over the tolerance the sound program keeps, on the gather
    and through the interpreted kernel."""
    fault = dict(_CONTROLS[control])
    cfg = fault.pop("cfg", CFG)
    for name, faulty in fault.items():
        monkeypatch.setattr(llama, name, faulty)
    jax.clear_caches()
    try:
        # one row: a chunk of 16, one of 7 that reads the first's slots, and
        # on the gather two decoded tokens (the kernel's one-query program is
        # another 4 s of interpreted lowering a control)
        seqs, got = _serve(params, cfg, impl, lengths=(23,), steps=2 * (impl == "xla"))
        assert _worst(params, seqs, got) > 100 * LOGP_TOL
    finally:
        monkeypatch.undo()
        jax.clear_caches()
