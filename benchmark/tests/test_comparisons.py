"""A configuration finds its comparison and reference by name, and the
comparisons have teeth: on the CPU, a 16-layer top-2-of-8 model at hidden
256, ``models/llama.py::forward`` in the server's place (one device, XLA
attention), the program sound and with six deliberate faults, each on the
engine's side: the reference stays plain."""

import dataclasses
import glob
import json
import os
import types

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

import comparisons  # noqa: E402  (conftest.py put benchmark/ on the path)
import reference  # noqa: E402
from comparisons import logprob_walk, routed_experts  # noqa: E402
from lib import correct, modeldir  # noqa: E402

HF = {
    "architectures": ["MixtralForCausalLM"], "model_type": "mixtral", "hidden_act": "silu",
    "hidden_size": 256, "intermediate_size": 512, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 16, "num_local_experts": 8,
    "num_experts_per_tok": 2, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
    "max_position_embeddings": 512, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16", "vocab_size": 2048,
}
SEED = 2**31 + 5
SWAPPED_LAYER = 8  # the middle layer, one of the three the block part reads


# ------------------------------------------------------------ resolution
@pytest.mark.parametrize(
    "path", sorted(glob.glob(os.path.join(BENCH, "configs", "*.json"))), ids=os.path.basename
)
def test_every_configuration_resolves_its_comparison_and_reference(path):
    with open(path) as f:
        config = json.load(f)
    comparison, ref = comparisons.resolve(config)
    assert callable(comparison.compare) and callable(ref.log_probs) and ref.LOGPROB_TOL > 0
    want = config.get("comparison", "logprob_walk")
    assert comparison.__name__ == f"comparisons.{want}"
    if want == "routed_experts":
        assert callable(ref.hidden_states) and callable(ref.ffn_half)
        assert callable(ref.attn_half_and_head)
        assert config["num_experts_per_tok"] >= 1


def test_no_key_means_the_walk_and_reference_py():
    comparison, ref = comparisons.resolve({"model_type": "qwen3"})
    assert comparison is logprob_walk and ref is reference


@pytest.mark.parametrize("config, word", [
    ({"comparison": "no_such_kind"}, "comparisons/no_such_kind.py"),
    ({"reference": "no_such_reference"}, "references/no_such_reference.py"),
    ({"comparison": "../run_cell"}, "not a module's name"),
])
def test_an_unknown_name_is_an_error(config, word):
    with pytest.raises(ValueError, match=word):
        comparisons.resolve(config)


def test_an_unknown_kind_ends_the_run_before_the_engine_is_built(tmp_path):
    import shutil
    import subprocess
    import sys

    root = tmp_path / "repo"
    shutil.copytree(
        os.path.dirname(BENCH), root,
        ignore=shutil.ignore_patterns(
            ".git", ".work", "__pycache__", ".jax_cache", "chiprun_out", "tests", "docs", ".claude",
        ),
    )
    cfg = root / "benchmark" / "configs" / "tiny-qwen3.json"
    config = json.loads(cfg.read_text())
    cfg.write_text(json.dumps({**config, "comparison": "no_such_kind"}))
    proc = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run_cell.py"), "--workload",
         "tiny-qwen3.rehearsal-open", "--seed", "1", "--seconds", "2"],
        cwd=root, env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""},
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0 and "no_such_kind" in proc.stderr
    assert not proc.stdout.strip() and "engine" not in proc.stderr.lower()


# ----------------------------------------------------------------- teeth
def build_model(model_dir: str, hf: dict):
    """The sound weights, the tokenizer's file, and ``program(fault)``:
    the engine's forward with that fault, as the comparison's ``engine``
    and ``ask``."""
    import jax
    import jax.numpy as jnp
    from tokenizers import Tokenizer

    from dynamo_exp_tpu.models import llama
    from dynamo_exp_tpu.models.config import ModelConfig

    model_dir = modeldir.prepare(model_dir, hf)
    tokenizer_file = os.path.join(model_dir, "tokenizer.json")
    tok = Tokenizer.from_file(tokenizer_file)
    mcfg = ModelConfig.from_hf_config(hf)
    sound = llama.init_params(jax.random.PRNGKey(SEED % 2**31), mcfg)
    page, width = 16, 64

    def program(fault):
        cfg, params = mcfg, sound
        layers = dict(sound["layers"])
        if fault == "top1":
            cfg = dataclasses.replace(mcfg, num_experts_per_tok=1)
        elif fault == "no_renorm":
            cfg = dataclasses.replace(mcfg, norm_topk_prob=False)
        elif fault == "fp8_experts":
            for k in ("w_gate", "w_up", "w_down"):
                layers[k] = layers[k].astype(jnp.float8_e4m3fn).astype(layers[k].dtype)
        elif fault == "fp8_attention":
            for k in ("wq", "wk", "wv", "wo"):
                layers[k] = layers[k].astype(jnp.float8_e4m3fn).astype(layers[k].dtype)
        elif fault == "fp8_head":
            head = sound["lm_head"]
            params = {**sound, "lm_head": head.astype(jnp.float8_e4m3fn).astype(head.dtype)}
        elif fault == "swapped_w_down":
            w = layers["w_down"]
            layers["w_down"] = w.at[SWAPPED_LAYER, 0].set(w[SWAPPED_LAYER, 1]).at[
                SWAPPED_LAYER, 1].set(w[SWAPPED_LAYER, 0])
        params = {**params, "layers": layers}

        @jax.jit
        def top5(ids, n):
            k, v = llama.init_kv_cache(cfg, width // page, page)
            pos = jnp.where(jnp.arange(width) < n, jnp.arange(width), -1)
            logits, _, _ = llama.forward(
                params, cfg, ids[None], pos[None].astype(jnp.int32),
                jnp.arange(width // page, dtype=jnp.int32)[None], k, v,
                last_positions=(n - 1)[None],
            )
            return jax.lax.top_k(jax.nn.log_softmax(logits[0, 0]), 5)

        def ask(content, n_tokens):
            ids = tok.encode(f"<|user|>{content}</s><|assistant|>", add_special_tokens=False).ids
            n_prompt, entries = len(ids), []
            for _ in range(n_tokens):
                padded = jnp.asarray(ids + [0] * (width - len(ids)), jnp.int32)
                lp, idx = top5(padded, jnp.asarray(len(ids), jnp.int32))
                lp, idx = np.asarray(lp), np.asarray(idx)
                entries.append({
                    "token": tok.decode([int(idx[0])], skip_special_tokens=True),
                    "logprob": float(lp[0]),
                    "top_logprobs": [{"logprob": float(x)} for x in lp],
                })
                ids.append(int(idx[0]))
            return n_prompt, entries

        engine = types.SimpleNamespace(
            params=params, mesh=None, cfg=types.SimpleNamespace(model=cfg)
        )
        return engine, ask

    return sound, tokenizer_file, program


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    return build_model(str(tmp_path_factory.mktemp("model")), HF)


def ctx_for(model, fault):
    sound, tokenizer_file, program = model
    engine, ask = program(fault)
    return {
        "engine": engine, "ask": ask, "params": sound, "hf": HF, "seed": SEED,
        "tokenizer_file": tokenizer_file, "reference": reference,
    }


FAULTS = ("top1", "no_renorm", "fp8_experts", "swapped_w_down", "fp8_attention", "fp8_head")


@pytest.mark.parametrize("fault", (None,) + FAULTS)
def test_routed_experts_passes_the_sound_program_and_fails_each_fault(model, fault):
    ctx = ctx_for(model, fault)
    out = routed_experts.compare(ctx)
    print(fault, {k: v for k, v in out.items() if k not in ("block", "step_diffs")})
    block = max(out["block"], key=lambda c: c["max_rel_err"])
    first_half = max(out["first_half"], key=lambda c: c["max_rel_err"])
    if fault is None:
        assert out["ok"] and out["steps"] == 32, out["why"]
        assert out["median_abs_logprob_diff"] < routed_experts.MEDIAN_TOL / 2
        assert block["max_rel_err"] < routed_experts.BLOCK_TOL / 1.5
        assert first_half["max_rel_err"] < routed_experts.FIRST_HALF_TOL / 1.5
        assert [c["layer"] for c in out["first_half"]] == [0, 8, 15]
        return
    assert not out["ok"]
    if fault in ("top1", "no_renorm"):  # a wrong routing rule moves every step
        assert out["median_abs_logprob_diff"] > routed_experts.MEDIAN_TOL
    if fault in ("fp8_attention", "fp8_head"):
        # The precision below the served one, outside the experts: the part
        # where no flip can happen tells it, the served median does not.
        assert first_half["max_rel_err"] > 1.5 * routed_experts.FIRST_HALF_TOL
        assert block["max_rel_err"] < routed_experts.BLOCK_TOL
        assert out["median_abs_logprob_diff"] < routed_experts.MEDIAN_TOL
        return
    # What is wrong in the experts leaves the first half as it was.
    assert first_half["max_rel_err"] < routed_experts.FIRST_HALF_TOL
    if fault == "fp8_experts":  # the precision below the served one: small, and seen
        assert 1.5 * routed_experts.BLOCK_TOL < block["max_rel_err"] < 0.2
    else:
        assert block["max_rel_err"] > 10 * routed_experts.BLOCK_TOL
    if fault == "swapped_w_down":
        assert block["layer"] == SWAPPED_LAYER
        sound_layers = [c for c in out["block"] if c["layer"] != SWAPPED_LAYER]
        assert all(c["max_rel_err"] < routed_experts.BLOCK_TOL for c in sound_layers)


def test_the_walks_largest_difference_cannot_be_held_to_a_tenth_of_a_routed_model(model):
    """Over a few probe seeds the sound program's largest difference
    passes the dense models' 0.1 (routing flips, which ``logprob_walk``
    would call a fault) while its median stays under MEDIAN_TOL, yet
    moves from seed to seed by more than a factor of two."""
    ctx = ctx_for(model, None)
    assert not logprob_walk.compare(ctx)["ok"]
    worst, medians = [], []
    for seed in range(SEED, SEED + 4):
        walk = correct.walk_reference({**ctx, "seed": seed}, routed_experts.PROBES)
        worst.append(max(walk["step_diffs"]))
        medians.append(float(np.median(walk["step_diffs"])))
    print("largest", worst, "medians", medians)
    assert min(worst) > reference.LOGPROB_TOL
    assert max(medians) < routed_experts.MEDIAN_TOL and max(medians) > 2 * min(medians)


def test_check_seeds_makes_its_controls_on_the_references_side_and_leaves_it_plain(model):
    """``rehearse/check_seeds.py``'s controls (what a tolerance's upper
    reading is taken with on the chip) move the reference's answer, and
    the reference computes as before once a control has ended."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_seeds", os.path.join(BENCH, "rehearse", "check_seeds.py"))
    check_seeds = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(check_seeds)
    sound = model[0]
    ids = list(range(5, 45))
    ctx = {"params": sound, "hf": HF, "reference": reference}

    def top5(c):
        return np.sort(c["reference"].log_probs(c["params"], c["hf"], ids, [len(ids) - 1])[0])[-5:]

    base = top5(ctx)
    for name in check_seeds.CONTROLS:
        with check_seeds.control(name, ctx) as faulty:
            assert np.abs(top5(faulty) - base).max() > 0.02, name
        assert np.array_equal(top5(ctx), base), name
    with pytest.raises(ValueError, match="unknown control"):
        with check_seeds.control("int3", ctx):
            pass
