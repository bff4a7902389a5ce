"""``comparisons/block_diffusion.py`` has teeth: on the CPU, a tiny
``sdar_moe`` (3 layers, hidden 128, 8 experts top-2, blocks of 4, bfloat16)
served by the real engine (scheduler, paged cache, the block and the mixed
program) in the server's place, sound and against each control of
``rehearse/check_seeds_sdar.py`` (made on the reference's side, as on the
chip); and the reference's replay of a served trajectory is the
trajectory."""

import asyncio
import importlib.util
import os
import threading

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

import comparisons  # noqa: E402  (conftest.py put benchmark/ on the path)
from comparisons import block_diffusion  # noqa: E402
from lib import modeldir  # noqa: E402
from references import sdar_moe as reference  # noqa: E402

HF = {
    "model_type": "sdar_moe", "hidden_act": "silu", "hidden_size": 128,
    "intermediate_size": 256, "moe_intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "num_hidden_layers": 3, "num_experts": 8,
    "num_experts_per_tok": 2, "norm_topk_prob": True, "rms_norm_eps": 1e-6,
    "rope_theta": 1000000, "max_position_embeddings": 512, "tie_word_embeddings": False,
    "mlp_only_layers": [], "decoder_sparse_step": 1, "torch_dtype": "bfloat16",
    "vocab_size": 2048,
}
SEED = 2**31 + 7


def _load(name: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "rehearse", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """The comparison's ``ctx`` round a running engine: ``ask`` goes
    through ``engine.generate`` with log-probabilities, as the HTTP path
    does, and renders entries as the preprocessor does."""
    from tokenizers import Tokenizer

    from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
    from dynamo_exp_tpu.models.config import ModelConfig
    from dynamo_exp_tpu.parallel import single_device_mesh
    from dynamo_exp_tpu.protocols.common import BackendInput

    model_dir = modeldir.prepare(str(tmp_path_factory.mktemp("model")), HF)
    tokenizer_file = os.path.join(model_dir, "tokenizer.json")
    tok = Tokenizer.from_file(tokenizer_file)
    cfg = EngineConfig(
        model=ModelConfig.from_pretrained(model_dir), max_decode_slots=4, page_size=16,
        num_pages=64, max_model_len=256, prefill_chunk=32, eos_token_ids=[],
    )
    engine = TPUEngine(cfg, mesh=single_device_mesh(), seed=SEED % 2**31)
    engine.start()
    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()

    async def generate(ids, n):
        b = BackendInput(token_ids=ids)
        b.stop_conditions.max_tokens, b.stop_conditions.ignore_eos = n, True
        b.sampling_options.logprobs = 5
        out = {"token_ids": [], "logprobs": [], "top_logprobs": [], "fixed_at_pass": []}
        async for item in await engine.generate(b.to_dict()):
            for k in out:
                out[k] += item.get(k) or []
        return out

    def ask(content, n_tokens):
        ids = tok.encode(f"<|user|>{content}</s><|assistant|>", add_special_tokens=False).ids
        out = asyncio.run_coroutine_threadsafe(generate(ids, n_tokens), loop).result(300)
        entries = [
            {
                "token": tok.decode([t], skip_special_tokens=True), "logprob": lp, "pass": s,
                "top_logprobs": [{"logprob": x} for x in tops.values()],
            }
            for t, lp, tops, s in zip(
                out["token_ids"], out["logprobs"], out["top_logprobs"], out["fixed_at_pass"]
            )
        ]
        return len(ids), entries

    yield {
        "engine": engine, "ask": ask, "params": engine.params, "hf": HF, "seed": SEED,
        "tokenizer_file": tokenizer_file, "reference": reference, "generate": (
            lambda ids, n: asyncio.run_coroutine_threadsafe(generate(ids, n), loop).result(300)
        ),
    }
    engine.stop()
    loop.call_soon_threadsafe(loop.stop)


def test_the_configuration_names_this_comparison_and_reference():
    import json

    with open(os.path.join(BENCH, "configs", "sdar-30b-a3b-l6.json")) as f:
        comparison, ref = comparisons.resolve(json.load(f))
    assert comparison is block_diffusion and ref is reference
    for name in ("log_probs", "hidden_states", "ffn_half", "attn_half_and_head",
                 "denoise_log_probs", "pass_inputs"):
        assert callable(getattr(ref, name)), name


def test_the_reference_shares_no_code_with_the_program():
    with open(os.path.join(BENCH, "references", "sdar_moe.py")) as f:
        source = f.read()
    assert "import dynamo_exp_tpu" not in source and "from dynamo_exp_tpu" not in source
    assert 'default_matmul_precision("highest")' in source


CONTROLS = ("causal_bound", "no_write_pass", "shifted", "no_renorm", "fp8_experts")


@pytest.mark.parametrize("control", (None,) + CONTROLS)
def test_block_diffusion_passes_the_sound_program_and_fails_each_control(served, control):
    check = _load("check_seeds_sdar")
    ctx = {k: v for k, v in served.items() if k != "generate"}
    if control is None:
        out = block_diffusion.compare(ctx)
    else:
        with check.control(control, ctx) as faulty:
            out = block_diffusion.compare(faulty)
    print(control, {k: v for k, v in out.items() if k not in ("block", "step_diffs", "first_half")})
    block = max(out["block"], key=lambda c: c["max_rel_err"])
    first_half = max(out["first_half"], key=lambda c: c["max_rel_err"])
    if control is None:
        assert out["ok"], out["why"]
        assert out["steps"] == sum(
            (-p) % 4 + 4 * block_diffusion.BLOCKS for p in block_diffusion.PROBE_LENGTHS
        )
        assert out["median_abs_logprob_diff"] < block_diffusion.MEDIAN_TOL / 2
        assert block["max_rel_err"] < block_diffusion.BLOCK_TOL / 1.5
        assert first_half["max_rel_err"] < block_diffusion.FIRST_HALF_TOL / 1.5
        assert [c["layer"] for c in out["first_half"]] == [0, 1, 2]
        assert {c["rows"] for c in out["block"]} == {256, 1024}
        # Random weights never pass 0.9: every block takes its four passes.
        assert set(out["tokens_by_pass"]) == {"0", "1", "2", "3"}
        return
    assert not out["ok"], control
    served_fails = out["median_abs_logprob_diff"] > block_diffusion.MEDIAN_TOL
    if control in ("no_write_pass", "shifted"):
        # Only the served path knows of passes and of where a token's
        # distribution lies; the two halves are sound.
        assert served_fails
        assert block["max_rel_err"] < block_diffusion.BLOCK_TOL
        assert first_half["max_rel_err"] < block_diffusion.FIRST_HALF_TOL
    elif control == "causal_bound":
        assert first_half["max_rel_err"] > 10 * block_diffusion.FIRST_HALF_TOL
        assert block["max_rel_err"] < block_diffusion.BLOCK_TOL
    elif control == "no_renorm":
        assert block["max_rel_err"] > 10 * block_diffusion.BLOCK_TOL
        assert first_half["max_rel_err"] < block_diffusion.FIRST_HALF_TOL
    else:  # fp8_experts: the precision below the served one, small and seen
        assert 1.5 * block_diffusion.BLOCK_TOL < block["max_rel_err"] < 0.3
        assert first_half["max_rel_err"] < block_diffusion.FIRST_HALF_TOL


def test_a_control_leaves_the_reference_plain(served):
    check = _load("check_seeds_sdar")
    ids = list(range(5, 45))
    ctx = {"params": served["params"], "hf": HF, "reference": reference}

    def top5(c):
        return np.sort(c["reference"].log_probs(c["params"], c["hf"], ids, [38])[0])[-5:]

    base = top5(ctx)
    for name in ("causal_bound", "shifted", "no_renorm"):
        with check.control(name, ctx) as faulty:
            assert np.abs(top5(faulty) - base).max() > 1e-3, name
        assert np.array_equal(top5(ctx), base), name
    with check.control("no_write_pass", ctx):
        assert reference.written_block([7, 8, 9, 10], [0, 2, 1, 2], 99) == [7, 99, 9, 99]
    assert reference.written_block([7, 8, 9, 10], [0, 2, 1, 2], 99) == [7, 8, 9, 10]


@pytest.mark.parametrize("n_prompt", [40, 41, 42, 43])
def test_the_replay_of_a_served_trajectory_gives_its_log_probabilities(served, n_prompt):
    """``denoise_log_probs`` on the served tokens and passes reads what the
    engine reported, and the engine's greedy choice at every pass."""
    ids = [int(t) for t in np.random.default_rng(n_prompt).integers(300, 2000, n_prompt)]
    n = (-n_prompt) % 4 + 12
    out = served["generate"](ids, n)
    assert len(out["token_ids"]) == n and set(out["fixed_at_pass"]) <= {0, 1, 2, 3}
    ref = reference.denoise_log_probs(
        served["params"], HF, ids, out["token_ids"], out["fixed_at_pass"])
    # bfloat16 against float32: rounding, and a router's flip now and then.
    diffs = [abs(float(ref[i][t]) - lp) for i, (t, lp) in enumerate(zip(out["token_ids"], out["logprobs"]))]
    assert float(np.median(diffs)) < block_diffusion.MEDIAN_TOL / 2, diffs
    with pytest.raises(ValueError, match="end inside a block"):
        list(reference.pass_inputs(ids, out["token_ids"][:-1], out["fixed_at_pass"][:-1], HF))
