"""A top-2-of-8 routed model through the served path against the plain
reference (``benchmark/reference.py``: float32, no kernel, no cache, no
batching), with the comparison the benchmark's Mixtral cell is judged by
(``benchmark/comparisons/routed_experts.py``) and two faults on the
engine's side that it must refuse.

The engine here is the real one (``TPUEngine`` on the CPU: scheduler,
page pool, one mixed dispatch for the prompt, then decode windows
through the cache) asked for log-probabilities; logits are compared, not
tokens, because with random weights the largest logit changes on
rounding. A cut of ``benchmark/tests/test_comparisons.py`` (18 cases at
16 layers, two minutes) to three cases at 4 layers.

TOLERANCES are the comparison's own and their reasons are written beside
them there. In short: the served walk's largest difference cannot be
held to anything for a routed model (a routing flip between a bfloat16
and a float32 residual moves a step by tenths; PERF.md, PR 26), so the
precision is held where no flip can happen: the expert block on the
same bfloat16 rows (``BLOCK_TOL`` 0.04 of what the block adds: bfloat16
rounding of the output and of ``silu(gate) * up`` reads under 0.02,
float8 expert weights 0.09) and a layer's first half and the head
(``FIRST_HALF_TOL``); the served walk's median (``MEDIAN_TOL`` 0.3)
guards the wiring, which a wrong routing rule moves by whole units.
"""

import asyncio
import dataclasses
import os
import sys
import threading

import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")

HF = {
    "architectures": ["MixtralForCausalLM"], "model_type": "mixtral", "hidden_act": "silu",
    "hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 4, "num_local_experts": 8,
    "num_experts_per_tok": 2, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
    "max_position_embeddings": 512, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16", "vocab_size": 2048,
}
SEED = 2**31 + 5


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules, importable as it imports them."""
    added = [p for p in (BENCH,) if p not in sys.path]
    sys.path[:0] = added
    import reference
    from comparisons import routed_experts
    from lib import modeldir

    yield reference, routed_experts, modeldir
    for p in added:
        sys.path.remove(p)


@pytest.fixture(scope="module")
def loop():
    loop = asyncio.new_event_loop()
    thread = threading.Thread(target=loop.run_forever, daemon=True)
    thread.start()
    yield loop
    loop.call_soon_threadsafe(loop.stop)
    thread.join(timeout=5)


def _served(fault, tok, loop):
    """The engine with ``fault`` and ``ask(content, n)`` through it."""
    from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
    from dynamo_exp_tpu.models.config import ModelConfig
    from dynamo_exp_tpu.parallel import single_device_mesh
    from dynamo_exp_tpu.protocols.common import BackendInput

    mcfg = ModelConfig.from_hf_config(HF)
    if fault == "top1":
        mcfg = dataclasses.replace(mcfg, num_experts_per_tok=1)
    cfg = EngineConfig(
        model=mcfg, max_decode_slots=2, page_size=16, num_pages=32,
        max_model_len=256, eos_token_ids=[],
    )
    engine = TPUEngine(cfg, mesh=single_device_mesh(), seed=SEED % 2**31)
    sound = engine.params
    if fault == "fp8_experts":
        layers = dict(sound["layers"])
        for k in ("w_gate", "w_up", "w_down"):
            layers[k] = layers[k].astype(jnp.float8_e4m3fn).astype(layers[k].dtype)
        engine.params = {**sound, "layers": layers}

    async def generate(ids, n):
        b = BackendInput(token_ids=ids)
        b.stop_conditions.max_tokens = n
        b.stop_conditions.ignore_eos = True
        b.sampling_options.logprobs = 5
        stream = await engine.generate(b.to_dict())
        toks, lps, tops = [], [], []
        async for item in stream:
            toks += item.get("token_ids", [])
            lps += item.get("logprobs") or []
            tops += item.get("top_logprobs") or []
        return toks, lps, tops

    def ask(content, n_tokens):
        ids = tok.encode(f"<|user|>{content}</s><|assistant|>", add_special_tokens=False).ids
        toks, lps, tops = asyncio.run_coroutine_threadsafe(generate(ids, n_tokens), loop).result(300)
        entries = [
            {
                "token": tok.decode([t], skip_special_tokens=True), "logprob": float(lp),
                "top_logprobs": [{"logprob": float(x)} for x in top.values()],
            }
            for t, lp, top in zip(toks, lps, tops)
        ]
        return len(ids), entries

    return engine, sound, ask


@pytest.mark.parametrize("fault", [None, "fp8_experts", "top1"])
def test_the_served_path_agrees_with_the_plain_reference_and_each_fault_is_refused(
    fault, bench, loop, tmp_path_factory
):
    from tokenizers import Tokenizer

    reference, routed_experts, modeldir = bench
    model_dir = modeldir.prepare(str(tmp_path_factory.mktemp("model")), HF)
    tokenizer_file = os.path.join(model_dir, "tokenizer.json")
    engine, sound, ask = _served(fault, Tokenizer.from_file(tokenizer_file), loop)
    engine.start()
    try:
        out = routed_experts.compare({
            "engine": engine, "ask": ask, "params": sound, "hf": HF, "seed": SEED,
            "tokenizer_file": tokenizer_file, "reference": reference,
        })
    finally:
        engine.stop()
    block = max(c["max_rel_err"] for c in out["block"])
    first_half = max(c["max_rel_err"] for c in out["first_half"])
    print(fault, out["median_abs_logprob_diff"], out["max_abs_logprob_diff"], block, first_half)
    assert out["steps"] == 32  # four prompts prefilled, eight steps each through the cache
    if fault is None:
        assert out["ok"], out["why"]
        assert block < routed_experts.BLOCK_TOL / 1.5
        assert first_half < routed_experts.FIRST_HALF_TOL / 1.5
        assert out["median_abs_logprob_diff"] < routed_experts.MEDIAN_TOL / 2
        return
    assert not out["ok"]
    # A fault in the experts leaves a layer's first half and the head as they were.
    assert first_half < routed_experts.FIRST_HALF_TOL
    if fault == "fp8_experts":  # the precision below the served one: small, and seen
        assert 1.5 * routed_experts.BLOCK_TOL < block < 0.2
        assert out["median_abs_logprob_diff"] < routed_experts.MEDIAN_TOL
    else:  # a wrong routing rule moves every step and every row
        assert block > 10 * routed_experts.BLOCK_TOL
        assert out["median_abs_logprob_diff"] > routed_experts.MEDIAN_TOL
