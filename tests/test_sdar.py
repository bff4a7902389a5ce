"""sdar_moe (SDAR-30B-A3B-Chat) on the normal path: generation by diffusion
over blocks (a row's step is a pass over a block of 4 positions) on a
qwen3_moe stack. Tiny sizes (2 layers, hidden 64, 8 experts top-2, blocks
of 4), seeded weights, float32, so the engine and the plain reference
(``benchmark/references/sdar_moe.py``) differ by float32 rounding alone.

TOLERANCES. Both sides compute in float32, the reference at ``highest``
matmul precision and the engine at the CPU's default (also float32
products): log-probabilities agree to ~2e-6, so ``LOGP_TOL`` 1e-3 stands
hundreds of times above the rounding and far below any fault (a causal
bound in the block bound's place, a block left unwritten or a shift by
one move them by 1e-1 and more: ``benchmark/tests/test_block_diffusion.py``
reads each). Token streams are compared exactly: float32 logits of random
weights tie nowhere. ``THRESHOLD`` 0.012 against a vocabulary of 256
(uniform: 0.0039) is one that some positions' confidence passes and
others' does not, so the dynamic rule takes both of its branches.
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
from dynamo_exp_tpu.engine.config import refuse_block_model_tiers
from dynamo_exp_tpu.models import ModelConfig, llama
from dynamo_exp_tpu.ops import expert_gmm
from dynamo_exp_tpu.ops.ragged_attention import (
    ragged_paged_attention,
    ragged_paged_attention_ref,
)
from dynamo_exp_tpu.ops.sampling import UNFIXED, denoise_counts, denoise_select
from dynamo_exp_tpu.parallel import single_device_mesh
from dynamo_exp_tpu.protocols.common import BackendInput, SamplingOptions
from dynamo_exp_tpu.telemetry import get_telemetry

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmark"))
from references import sdar_moe as reference  # noqa: E402

from .ragged_cases import flat_batch  # noqa: E402

B = 4
THRESHOLD = 0.012
HF = {
    "model_type": "sdar_moe", "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 128, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256, "rms_norm_eps": 1e-6,
    "rope_theta": 1e6, "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "max_position_embeddings": 512, "torch_dtype": "float32", "tie_word_embeddings": False,
    "mlp_only_layers": [], "decoder_sparse_step": 1,
    "block_length": B, "denoising_steps": 4, "mask_token_id": 255,
    "remasking_strategy": "low_confidence_dynamic", "confidence_threshold": THRESHOLD,
}
PS = 8
LOGP_TOL = 1e-3
SEED = 3
PROMPT = [int(t) for t in np.random.default_rng(0).integers(2, 250, 90)]


def _hf(**kw) -> dict:
    return {**HF, **kw}


def _engine(hf=HF, **kw) -> TPUEngine:
    cfg = EngineConfig(
        model=ModelConfig.from_hf_config(hf), max_decode_slots=4,
        page_size=kw.pop("page_size", PS), num_pages=kw.pop("num_pages", 64),
        max_model_len=kw.pop("max_model_len", 256),
        prefill_chunk=kw.pop("prefill_chunk", 16), decode_window=8, kv_dtype="float32",
        eos_token_ids=kw.pop("eos_token_ids", []), preempt_stall_grace_s=0.05, **kw,
    )
    return TPUEngine(cfg, mesh=single_device_mesh(), seed=SEED)


@pytest.fixture(scope="module")
def engine():
    eng = _engine()
    eng.start()
    yield eng
    eng.stop()


async def _run(eng, prompt, n, logprobs=None, stop_ids=None, after=None, **sampling):
    """``after``: (tokens, event) sets the event once that many tokens
    have come."""
    b = BackendInput(token_ids=list(prompt))
    b.stop_conditions.max_tokens = n
    b.stop_conditions.ignore_eos = stop_ids is None
    if stop_ids is not None:
        b.stop_conditions.stop_token_ids = list(stop_ids)
    b.sampling_options = SamplingOptions(logprobs=logprobs, **sampling)
    stream = await eng.generate(b.to_dict())
    out = {"token_ids": [], "logprobs": [], "top_logprobs": [], "fixed_at_pass": [],
           "chunks": [], "final": None}
    async for item in stream:
        for k in ("token_ids", "logprobs", "top_logprobs", "fixed_at_pass"):
            out[k] += item.get(k) or []
        if item.get("token_ids"):
            out["chunks"].append(len(item["token_ids"]))
        if after is not None and len(out["token_ids"]) >= after[0]:
            after[1].set()
        if item.get("finish_reason"):
            out["final"] = item
    return out


def _held_to_the_reference(params, hf, prompt, out, greedy=True):
    """Every served token against the reference's full forward at the pass
    that fixed it: its log-probability, its top alternatives, and (greedy)
    that it was the pass's most likely. Tokens cut off a last block are
    left out: what that block fixed past the cut was not served."""
    n = len(out["token_ids"])
    n -= (len(prompt) + n) % B
    if n <= 0:
        return
    ref = reference.denoise_log_probs(
        params, hf, prompt, out["token_ids"][:n], out["fixed_at_pass"][:n])
    for i in range(n):
        token = out["token_ids"][i]
        assert abs(float(ref[i][token]) - out["logprobs"][i]) < LOGP_TOL, i
        for t, lp in out["top_logprobs"][i].items():
            assert abs(float(ref[i][int(t)]) - lp) < LOGP_TOL, (i, t)
        if greedy:
            assert int(ref[i].argmax()) == token, i


# ------------------------------------------------------------- config
def test_config_reads_the_generation_keys_and_the_familys_defaults(tmp_path):
    published = {k: v for k, v in HF.items() if k not in (
        "block_length", "denoising_steps", "mask_token_id", "remasking_strategy",
        "confidence_threshold")}
    cfg = ModelConfig.from_hf_config({**published, "vocab_size": 151936})
    assert (cfg.block_length, cfg.denoising_steps, cfg.mask_token_id) == (4, 4, 151669)
    assert cfg.remasking_strategy == "low_confidence_dynamic"
    assert cfg.confidence_threshold == 0.9 and cfg.qk_norm and cfg.num_experts == 8
    # A small vocabulary keeps the mask token inside it.
    assert ModelConfig.from_hf_config(published).mask_token_id == 255
    # generation_config.json beside config.json has the say.
    (tmp_path / "config.json").write_text(json.dumps(published))
    (tmp_path / "generation_config.json").write_text(json.dumps(
        {"block_length": 8, "denoising_steps": 2, "temperature": 0.7}))
    cfg = ModelConfig.from_pretrained(str(tmp_path))
    assert (cfg.block_length, cfg.denoising_steps) == (8, 2)
    # Every other family: one token a step.
    assert ModelConfig.from_hf_config({**published, "model_type": "qwen3_moe"}).block_length == 0
    for bad in ({"block_length": 3}, {"denoising_steps": 0}, {"remasking_strategy": "random"}):
        with pytest.raises(ValueError):
            ModelConfig.from_hf_config({**HF, **bad})


# ------------------------------------------------------------- attention
def _dense_block_attention(q, k, v, table, row_of, positions, Hkv, block):
    """A dense mask in plain numpy: query at ``p`` of row ``r`` sees the
    row's kv positions up to ``p | (block - 1)``."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    N, H, D = q.shape
    ps = k.shape[1]
    out = np.zeros((N, H, D))
    for i in range(N):
        p = int(positions[i])
        if p < 0:
            continue
        last = p | (block - 1)
        pages = np.asarray(table)[int(row_of[i]), : last // ps + 1]
        kk = k[pages].reshape(-1, Hkv, D)[: last + 1]
        vv = v[pages].reshape(-1, Hkv, D)[: last + 1]
        for h in range(H):
            g = h // (H // Hkv)
            s = kk[:, g] @ q[i, h] * D**-0.5
            w = np.exp(s - s.max())
            out[i, h] = (w / w.sum()) @ vv[:, g]
    return out


@pytest.mark.parametrize("spans", [
    # (query_len, kv_len): a span's first position is kv_len - query_len.
    [(4, 24), (8, 8), (4, 52)],  # whole blocks, on tile boundaries
    [(3, 23), (5, 9), (1, 18)],  # spans that start and end inside a block
    [(13, 29)],                  # one row, a chunk wider than a tile
])
@pytest.mark.parametrize("impl", ["reference", "kernel"])
def test_block_bound_against_a_dense_mask(spans, impl):
    """The bound a query may see as an argument: ``positions | (B - 1)``
    gives attention that is causal across blocks and full inside one, in
    the pure-JAX reference and in the Pallas kernel (interpreted), on
    spans that start off a tile and off a block; without the argument
    both stay causal by position."""
    H, Hkv, D, ps, pmax = 8, 4, 64, 16, 8
    q, k, v, table, row_of, positions = flat_batch(
        1, spans, H, Hkv, D, 64, ps, pmax, q_tile=4)
    bounds = jnp.where(positions >= 0, positions | (B - 1), -1)
    if impl == "kernel":
        run = lambda **kw: ragged_paged_attention(  # noqa: E731
            q, k, v, table, row_of, positions, num_kv_heads=Hkv, q_tile=4,
            interpret=True, **kw)
    else:
        run = lambda **kw: ragged_paged_attention_ref(  # noqa: E731
            q, k, v, table, row_of, positions, num_kv_heads=Hkv, **kw)
    live = np.asarray(positions) >= 0
    want = _dense_block_attention(q, k, v, table, row_of, positions, Hkv, B)
    np.testing.assert_allclose(np.asarray(run(bounds=bounds))[live], want[live], atol=2e-5)
    causal = _dense_block_attention(q, k, v, table, row_of, positions, Hkv, 1)
    np.testing.assert_allclose(np.asarray(run())[live], causal[live], atol=2e-5)
    assert np.abs(want[live] - causal[live]).max() > 1e-2
    assert not np.asarray(run(bounds=bounds))[~live].any()  # padding stays zeros


# ------------------------------------------------------------ the rule
@pytest.mark.parametrize("dynamic", [False, True])
def test_denoise_select_is_the_published_rule(dynamic):
    """Against a plain numpy reading of it, over random confidences,
    partly fixed blocks and every pass of the schedule."""
    rng = np.random.default_rng(5)
    R, V, T, tau = 64, 16, 4, 0.25
    logits = rng.standard_normal((R, B, V)).astype(np.float32) * 2.5
    fixed_at = np.where(rng.random((R, B)) < 0.3, 0, UNFIXED).astype(np.int32)
    passes = rng.integers(0, T, R).astype(np.int32)
    x0, newly, lp = (np.asarray(a) for a in denoise_select(
        jnp.asarray(logits), jnp.asarray(fixed_at), jnp.asarray(passes),
        steps=T, dynamic=dynamic, threshold=tau))
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    assert (x0 == p.argmax(-1)).all()
    np.testing.assert_allclose(np.exp(lp), p.max(-1), rtol=1e-5)
    assert denoise_counts(4, 4) == [1, 1, 1, 1] and denoise_counts(4, 3) == [2, 1, 1]
    assert denoise_counts(8, 3) == [3, 3, 2]
    for r in range(R):
        masked = fixed_at[r] == UNFIXED
        conf = np.where(masked, p[r].max(-1), -1.0)
        n = denoise_counts(B, T)[passes[r]]
        order = sorted(range(B), key=lambda j: (-conf[j], j))
        want = np.zeros(B, bool)
        want[[j for j in order[:n] if masked[j]]] = True
        high = masked & (conf > tau)
        if dynamic and high.sum() >= n:
            want = high
        if passes[r] >= T - 1:
            want = masked
        assert (newly[r] == want).all(), (r, newly[r], want)
    assert not newly[fixed_at != UNFIXED].any()


# ------------------------------------------------------------------ engine
@pytest.mark.parametrize("strategy, threshold", [
    ("low_confidence_dynamic", THRESHOLD), ("low_confidence_static", 0.9),
])
async def test_engine_against_the_reference_at_every_pass(strategy, threshold):
    """Prefill in chunks, then blocks through the paged cache, several
    requests at once (so the mixed program carries single passes beside
    prompt chunks and the block program whole blocks), greedy and seeded
    sampling: every token's log-probability and top alternatives are the
    reference's full forward's at the pass that fixed it."""
    hf = _hf(remasking_strategy=strategy, confidence_threshold=threshold)
    eng = _engine(hf)
    eng.start()
    try:
        jobs = [(41, 15, {}), (40, 16, {}), (3, 9, {}),
                (70, 22, {"temperature": 0.8, "seed": 5}), (18, 6, {}),
                (33, 11, {"temperature": 0.7, "top_k": 20, "seed": 7})]
        outs = await asyncio.gather(*[
            _run(eng, PROMPT[:p], n, logprobs=3, **kw) for p, n, kw in jobs])
        passes = []
        for (p, n, kw), out in zip(jobs, outs):
            assert len(out["token_ids"]) == n == out["final"]["completion_tokens"]
            assert len(out["fixed_at_pass"]) == n
            _held_to_the_reference(eng.params, hf, PROMPT[:p], out, greedy=not kw)
            passes += out["fixed_at_pass"]
        if strategy == "low_confidence_static":
            # Four passes a block, one position each.
            assert sorted(set(passes)) == [0, 1, 2, 3]
        else:
            # Some confidences pass the threshold (several positions fixed
            # by one pass) and some do not (later passes exist).
            assert passes.count(0) > len(passes) // 2 and max(passes) >= 1
        programs = {
            dict(labels)["program"]
            for labels in _samples("dynamo_ragged_dispatch_total")
        }
        assert {"block", "mixed"} <= programs
    finally:
        eng.stop()


def _samples(name: str) -> dict:
    out = {}
    for metric in get_telemetry().registry.collect():
        for s in metric.samples:
            if s.name == name:
                out[tuple(sorted(s.labels.items()))] = s.value
    return out


def _total(name: str, **match) -> float:
    return sum(
        v for labels, v in _samples(name).items()
        if all(dict(labels).get(k) == x for k, x in match.items())
    )


@pytest.mark.parametrize("n_prompt", [16, 17, 18, 19])
@pytest.mark.parametrize("n_tokens", [8, 9, 10, 11])
async def test_every_remainder_of_prompt_and_budget(engine, n_prompt, n_tokens):
    """Exactly ``max_tokens`` tokens whatever the remainders mod 4: the
    prompt's tail opens the first block already fixed, the last block is
    cut on emission; chunks carry several tokens; usage is exact."""
    out = await _run(engine, PROMPT[:n_prompt], n_tokens, logprobs=2)
    assert len(out["token_ids"]) == n_tokens
    final = out["final"]
    assert final["finish_reason"] == "length"
    assert (final["prompt_tokens"], final["completion_tokens"]) == (n_prompt, n_tokens)
    first = (-n_prompt) % B or B  # what the block the prompt's tail opens still holds
    want, left = [], n_tokens
    for size in [first] + [B] * n_tokens:
        if left <= 0:
            break
        want.append(min(size, left))
        left -= want[-1]
    assert out["chunks"] == want
    _held_to_the_reference(engine.params, HF, PROMPT[:n_prompt], out)


@pytest.mark.parametrize("logprobs", [None, 2])
async def test_a_stop_token_inside_a_block_cuts_it(engine, logprobs):
    """And with it the block's pack: the kept positions' log-probabilities,
    alternatives and passes are the uncut block's, position for position."""
    cold = await _run(engine, PROMPT[:21], 20, logprobs=logprobs)
    stop = cold["token_ids"][9]
    at = cold["token_ids"].index(stop)
    out = await _run(engine, PROMPT[:21], 20, logprobs=logprobs, stop_ids=[stop])
    assert out["token_ids"] == cold["token_ids"][: at + 1]
    assert out["final"]["finish_reason"] == "eos"
    assert out["final"]["completion_tokens"] == at + 1
    if logprobs is None:
        assert not out["logprobs"] and not out["fixed_at_pass"]
        return
    assert (21 + at + 1) % B, "the stop is to fall inside a block"
    assert out["fixed_at_pass"] == cold["fixed_at_pass"][: at + 1]
    assert out["logprobs"] == pytest.approx(cold["logprobs"][: at + 1], abs=LOGP_TOL)
    for got, want in zip(out["top_logprobs"], cold["top_logprobs"]):
        assert len(got) == logprobs and got == pytest.approx(want, abs=LOGP_TOL)


@pytest.mark.parametrize("prefill_chunk", [4, 16, 64])
@pytest.mark.parametrize("fill", [True, False])
async def test_tokens_do_not_depend_on_chunking(engine, prefill_chunk, fill, monkeypatch):
    """Whatever ``prefill_chunk`` and with PR 34's fill on or off: every
    chunk ends on a block boundary, so the prompt's K/V are the same."""
    want = await _run(engine, PROMPT[:75], 12)
    eng = _engine(prefill_chunk=prefill_chunk)
    ends = []
    plan = eng._plan_chunks

    def watched(remaining, admitted, singles, spans):
        chunks = plan(remaining, admitted, singles, spans)
        if not fill:
            chunks = [min(n, eng.cfg.prefill_chunk) for n in remaining]
        ends.extend(chunks)
        return chunks

    monkeypatch.setattr(eng, "_plan_chunks", watched)
    eng.start()
    try:
        a, b = await asyncio.gather(
            _run(eng, PROMPT[:75], 12), _run(eng, PROMPT[5:43], 9))
    finally:
        eng.stop()
    assert a["token_ids"] == want["token_ids"]
    assert ends and all(n % B == 0 for n in ends)
    assert len(b["token_ids"]) == 9


async def test_a_prefix_hit_gives_the_cold_runs_tokens(engine):
    """A second asking takes the prompt's whole pages from the cache and
    restarts at a block's start; so does a longer prompt over them."""
    base = PROMPT[3:50]  # 47 tokens: 5 full pages of 8
    cold = await _run(engine, base, 14, logprobs=2)
    hits = engine.kv.prefix_hits["shared"]
    again = await _run(engine, base, 14, logprobs=2)
    assert again["token_ids"] == cold["token_ids"]
    assert engine.kv.prefix_hits["shared"] - hits == 5
    longer = base + PROMPT[60:70]
    fresh = _engine()
    fresh.start()
    try:
        want = await _run(fresh, longer, 10)
    finally:
        fresh.stop()
    got = await _run(engine, longer, 10, logprobs=2)
    assert got["token_ids"] == want["token_ids"]
    _held_to_the_reference(engine.params, HF, longer, got)
    # Pages are shared only once every position in them is final: none
    # holds a block in flight, so no partial tail is ever attached.
    assert engine.kv.partial_tail is False


@pytest.mark.parametrize("sampling", [{}, {"temperature": 0.9, "seed": 11}])
async def test_preemption_and_resume_give_the_cold_runs_tokens(sampling):
    """An 8-page pool under two requests that need 12: a row is preempted
    between blocks and resumed by re-prefilling what it has; draws are
    keyed by (seed, position, pass), so the stream is the cold run's."""
    eng = _engine(num_pages=8)
    eng.start()
    try:
        p1, p2 = PROMPT[:9], PROMPT[20:30]
        o1 = await _run(eng, p1, 36, **sampling)
        o2 = await _run(eng, p2, 36, **sampling)
        before = eng.preempted
        r1, r2 = await asyncio.gather(
            _run(eng, p1, 36, **sampling), _run(eng, p2, 36, **sampling))
        assert eng.preempted > before
        assert r1["token_ids"] == o1["token_ids"] and r2["token_ids"] == o2["token_ids"]
        assert r1["final"]["completion_tokens"] == 36
    finally:
        eng.stop()


async def test_counters_say_blocks_and_passes(engine):
    before = {"blocks": _total("dynamo_diffusion_blocks_total")}
    denoise = _total("dynamo_diffusion_passes_total", kind="denoise")
    write = _total("dynamo_diffusion_passes_total", kind="write")
    out = await _run(engine, PROMPT[:24], 16, logprobs=0)
    blocks = _total("dynamo_diffusion_blocks_total") - before["blocks"]
    assert blocks == 4
    # (Every position of a finished block was fixed once: the positions
    # fixed are 4 a block, which is why no counter of its own says so.)
    # A lone request's blocks are written by the opening pass of the block
    # after them (tests/test_block_write.py), the last one never: no pass
    # fixes nothing.
    assert _total("dynamo_diffusion_passes_total", kind="write") == write
    took = _total("dynamo_diffusion_passes_total", kind="denoise") - denoise
    assert took == sum(max(out["fixed_at_pass"][i : i + B]) + 1 for i in range(0, 16, B))
    assert _total("dynamo_dispatch_seconds_count", kind="ragged", program="block") > 0
    assert _total("dynamo_moe_rows_total", kind="live") > 0


# ----------------------------------------------------------------- the edge
async def test_streamed_chunks_usage_and_the_pass_key(tmp_path):
    """Through preprocessor -> backend -> engine, as the HTTP service
    builds it: a chunk carries several tokens' entries, each with the pass
    that fixed it; usage and finish_reason are exact for a prompt and a
    budget that are no multiples of 4; a model whose step is a token has
    no such key (tests/test_logprobs.py)."""
    from .fixtures import build_tiny_model_dir

    from dynamo_exp_tpu.http.service import build_pipeline_engine
    from dynamo_exp_tpu.model_card import ModelDeploymentCard
    from dynamo_exp_tpu.protocols.aggregator import aggregate_chat_stream

    model_dir = build_tiny_model_dir(str(tmp_path / "m"))
    with open(os.path.join(model_dir, "config.json")) as f:
        vocab = json.load(f)["vocab_size"]
    with open(os.path.join(model_dir, "config.json"), "w") as f:
        json.dump({**HF, "vocab_size": vocab, "confidence_threshold": 0.9}, f)
    mdc = ModelDeploymentCard.from_local_path(model_dir, "tiny-sdar")
    cfg = EngineConfig(
        model=ModelConfig.from_pretrained(model_dir), max_decode_slots=2, page_size=PS,
        num_pages=64, max_model_len=128, eos_token_ids=[],
    )
    assert cfg.model.block_length == B and cfg.model.mask_token_id < vocab
    eng = TPUEngine(cfg, mesh=single_device_mesh(), seed=0)
    eng.start()
    try:
        oai = build_pipeline_engine(mdc, eng)
        req = {
            "model": "tiny-sdar", "messages": [{"role": "user", "content": "hello there"}],
            "max_tokens": 10, "ignore_eos": True, "logprobs": True, "top_logprobs": 2,
            "stream": True, "stream_options": {"include_usage": True},
        }
        chunks = [c async for c in await oai.generate(req)]
        entries = [
            e for c in chunks if c.choices and getattr(c.choices[0], "logprobs", None)
            for e in c.choices[0].logprobs["content"]
        ]
        assert len(entries) == 10
        assert all(e["pass"] in (0, 1, 2, 3) and len(e["top_logprobs"]) == 2 for e in entries)
        usage = [c.usage for c in chunks if c.usage is not None][-1]
        assert usage.completion_tokens == 10 and usage.prompt_tokens > 0
        finish = [c.choices[0].finish_reason for c in chunks if c.choices and c.choices[0].finish_reason]
        assert finish == ["length"]

        async def replay():
            for c in chunks:
                yield c

        full = await aggregate_chat_stream(replay())
        assert [e["pass"] for e in full.choices[0].logprobs["content"]] == [e["pass"] for e in entries]
    finally:
        eng.stop()


async def test_a_tiny_sdar_moe_serves_over_the_cli(tmp_path, capsys):
    """``run.py in=batch out=tpu`` (the engine ``in=http`` builds, by the
    same ``build_tpu_engine``) from a model directory whose ``config.json``
    is sdar_moe's published keys, with no flag other models lack."""
    from dynamo_exp_tpu.run import main_async, parse_args

    from .fixtures import build_tiny_model_dir

    model_dir = build_tiny_model_dir(str(tmp_path / "m"))
    with open(os.path.join(model_dir, "config.json")) as f:
        vocab = json.load(f)["vocab_size"]
    published = {k: v for k, v in HF.items() if k not in (
        "block_length", "denoising_steps", "mask_token_id", "remasking_strategy",
        "confidence_threshold")}
    with open(os.path.join(model_dir, "config.json"), "w") as f:
        json.dump({**published, "vocab_size": vocab}, f)
    prompts = tmp_path / "in.jsonl"
    prompts.write_text(
        "\n".join(json.dumps({"text": t}) for t in ["hello there, how are you", "x"]))
    await main_async(parse_args([
        f"in=batch:{prompts}", "out=tpu", "--model-path", model_dir, "--random-weights",
        "--max-tokens", "9", "--max-decode-slots", "2", "--page-size", "8",
        "--max-model-len", "128", "--kv-dtype", "float32",
    ]))
    stats = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert stats["requests"] == 2 and 2 <= stats["output_tokens"] <= 18


# ---------------------------------------------------------------- refusals
@pytest.mark.parametrize("kw, word", [
    ({"spec_mode": "ngram"}, "speculative decoding"),
    ({"sp": 2}, "ring prefill"),
    ({"tp": 2}, "tensor parallelism"),
    ({"page_size": 6}, "multiple of the block length"),
    ({"prefill_chunk": 30}, "multiple of the block length"),
    ({"max_model_len": 254}, "multiple of the block length"),
])
def test_what_assumes_a_token_a_step_is_refused(kw, word):
    base = dict(
        model=ModelConfig.from_hf_config(HF), max_decode_slots=2, page_size=8,
        num_pages=32, max_model_len=256,
    )
    cfg = EngineConfig(**{**base, **kw})
    with pytest.raises(ValueError, match=word):
        refuse_block_model_tiers(cfg)
    # A model whose step is a token passes, whatever the configuration.
    refuse_block_model_tiers(dataclasses.replace(
        cfg, model=ModelConfig.from_hf_config({**HF, "model_type": "qwen3_moe"})))


@pytest.mark.parametrize("tier", ["disaggregated transfer", "migration"])
def test_the_hand_off_tiers_are_refused(tier):
    cfg = EngineConfig(model=ModelConfig.from_hf_config(HF), page_size=8, max_model_len=256)
    with pytest.raises(ValueError, match=tier):
        refuse_block_model_tiers(cfg, tier)
    refuse_block_model_tiers(None, tier)


def test_the_engine_refuses_at_construction():
    with pytest.raises(ValueError, match="speculative decoding"):
        _engine(spec_mode="ngram")


# -------------------------------------------------------- experts and tiles
def test_tiles_of_the_accepted_shapes_are_pinned_and_many_groups_take_smaller_blocks():
    """Mixtral's (8 experts, [4096, 3584] a chip under --tp 4) and LFM2's
    (32 experts, [2048, 1792]) decode windows are one block of at most 256
    rows, with or without the group count. Since PR 64 a tile multiplies
    its own group's sub-blocks of 128 rows, so "many groups take smaller
    blocks" no longer holds: a block is as long as VMEM holds (in whole
    sub-blocks, as few blocks as fit and of one length), a block pass's
    2048 rows over 128 experts two blocks of 1024."""
    for m in (16, 64, 128, 256):
        for groups in (None, 8):
            assert expert_gmm.tiles(m, 4096, 3584, 2, groups) == (m, 512, 3584)
            assert expert_gmm.tiles(m, 3584, 4096, 2, groups) == (m, 512, 4096)
        for groups in (None, 32):
            assert expert_gmm.tiles(m, 2048, 1792, 2, groups) == (m, 512, 1792)
            assert expert_gmm.tiles(m, 1792, 2048, 2, groups) == (m, 256, 2048)
    assert expert_gmm.tiles(40, 768, 2048, 2) == (48, 384, 2048)
    # 244 sorted rows (LFM2's window, its padding rows left out) are one block
    # of two sub-blocks, ragged: the rows are padded to 256 by nobody.
    assert expert_gmm.tiles(244, 2048, 1792, 2, 32) == (256, 512, 1792)
    # Thousands of rows: with or without a group count the longest block VMEM holds.
    assert expert_gmm.tiles(2048, 2048, 768, 2) == (1024, 512, 768)
    assert expert_gmm.tiles(2048, 2048, 768, 2, 128) == (1024, 512, 768)
    assert expert_gmm.tiles(2048, 768, 2048, 2, 128) == (1024, 384, 2048)
    assert expert_gmm.tiles(512, 2048, 768, 2, 128) == (512, 512, 768)
    assert expert_gmm.tiles(4096, 2048, 768, 2, 128) == (1408, 512, 768)  # 3 blocks
    assert expert_gmm.tiles(4096, 2048, 1792, 2, 32) == (1024, 512, 1792)
    # A Mixtral chip's matrix: half its columns leave room for 768 rows where
    # all of them leave 384, and 3 + 7 fetches of a matrix (and the rows
    # twice) are fewer bytes than 6 + 7.
    assert expert_gmm.tiles(2048, 4096, 3584, 2, 8) == (768, 512, 1792)
    # A forced block (tests, the tool's sweep): whole sub-blocks that VMEM holds.
    assert expert_gmm.tiles(2048, 4096, 3584, 2, 8, tm=256) == (256, 512, 3584)
    for tm in (200, 2048):
        with pytest.raises(ValueError):
            expert_gmm.tiles(2048, 4096, 3584, 2, 8, tm=tm)


def test_the_block_program_reads_experts_in_place_at_any_row_count():
    """The kernel (interpreted) at 2048 sorted rows over 8 groups gives
    ``ragged_dot``'s numbers, and the forwards' rule says so whatever the
    rows: a block program's pass and a mixed dispatch alike."""
    from dynamo_exp_tpu.ops import moe

    cfg = ModelConfig.from_hf_config(_hf(hidden_size=128, head_dim=32))
    assert llama.routed_experts_form(cfg, None, True) == "inplace"
    assert llama.routed_experts_form(cfg, None, False) == "sliced"
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((1024, 128)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((128, 8)), jnp.float32)
    w1, w3 = (jnp.asarray(rng.standard_normal((8, 128, 128)) * 0.1, jnp.float32) for _ in range(2))
    w2 = jnp.asarray(rng.standard_normal((8, 128, 128)) * 0.1, jnp.float32)
    live = jnp.arange(1024) % 7 != 0
    args = (x, router, w1, w3, w2, 2, True, live)
    got = moe.moe_ffn(*args, interpret=True)
    want = moe.moe_ffn(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


# -------------------------------------------------------------------- aot
def test_manifest_lists_the_block_variants_and_traffic_compiles_nothing():
    # Not a coroutine: the prewarm compiles six interpreted programs (over
    # a minute alone, two on a loaded machine), and that is no part of
    # what the runner's limit on an awaiting test is for. The traffic is.
    from dynamo_exp_tpu.aot.compile import manifest_for_engine

    # pages of 32 on a table of 4: four page copies a chunk of each interpreted
    # program to lower, not sixteen (tests/ragged_cases.py "SHAPES")
    eng = _engine(_hf(hidden_size=128, head_dim=32), pallas_interpret=True,
                  attention_impl="pallas", page_size=32, max_model_len=128, num_pages=16)
    manifest = manifest_for_engine(eng, include_lp=False)
    windowed = [v for v in manifest.ragged if v.windowed]
    assert {v.nb for v in windowed} == {1, 2, 4} and all(v.pages is None for v in windowed)
    manifest.ragged = [v for v in manifest.ragged if not v.full_sampler and v.nb <= 64]
    manifest.move_buckets = []
    eng.prewarm(manifest)
    built = set(eng._ragged_fns)
    assert {k for k in built if k[2]} == {(n, None, True, False, False, False) for n in (1, 2, 4)}
    async def traffic():
        return await asyncio.gather(*[
            _run(eng, PROMPT[:p], n) for p, n in ((41, 15), (40, 8), (3, 9))])

    tiles = _total("dynamo_attn_tile_rows_total")
    eng.start()
    try:
        outs = asyncio.run(asyncio.wait_for(traffic(), timeout=120))
        assert [len(o["token_ids"]) for o in outs] == [15, 8, 9]
        assert set(eng._ragged_fns) == built  # a second dispatch compiled nothing
        assert _total("dynamo_moe_dispatch_total", experts="inplace") > 0
    finally:
        eng.stop()
    # The block programs' attention calls walked their tiles as rows on one
    # copy pipeline (a bucket of more than one tile), the KV heads together:
    # the live tiles were counted with the kernel's rules, and the tokens
    # are the ones the reference's gather serves.
    assert _total("dynamo_attn_tile_rows_total") > tiles
    assert _total("dynamo_attn_tile_rows_total", fetch="behind", schedule="together") > 0
    eng = _engine(_hf(hidden_size=128, head_dim=32), page_size=32, max_model_len=128,
                  num_pages=16)
    eng.start()
    try:
        by_gather = asyncio.run(asyncio.wait_for(traffic(), timeout=120))
    finally:
        eng.stop()
    assert [o["token_ids"] for o in outs] == [o["token_ids"] for o in by_gather]


# ----------------------------------------------------------------- loader
def test_loader_maps_sdar_moe_tensor_names_as_qwen3_moe(tmp_path):
    """A synthetic checkpoint under the published names
    (``mlp.experts.N.{gate,up,down}_proj``, ``mlp.gate``, per-head q/k
    norms): every array lands where the forward reads it."""
    from safetensors.numpy import save_file

    from dynamo_exp_tpu.models.loader import load_params

    hf = {k: v for k, v in HF.items()}
    cfg = ModelConfig.from_hf_config(hf)
    rng = np.random.default_rng(4)
    D, hd, I, E, V = cfg.hidden_size, cfg.head_dim_, cfg.moe_intermediate_size, 8, cfg.vocab_size
    tensors = {
        "model.embed_tokens.weight": rng.standard_normal((V, D)),
        "model.norm.weight": rng.standard_normal(D),
        "lm_head.weight": rng.standard_normal((V, D)),
    }
    for l in range(cfg.num_layers):
        p = f"model.layers.{l}."
        tensors.update({
            p + "input_layernorm.weight": rng.standard_normal(D),
            p + "post_attention_layernorm.weight": rng.standard_normal(D),
            p + "self_attn.q_proj.weight": rng.standard_normal((cfg.num_heads * hd, D)),
            p + "self_attn.k_proj.weight": rng.standard_normal((cfg.num_kv_heads * hd, D)),
            p + "self_attn.v_proj.weight": rng.standard_normal((cfg.num_kv_heads * hd, D)),
            p + "self_attn.o_proj.weight": rng.standard_normal((D, cfg.num_heads * hd)),
            p + "self_attn.q_norm.weight": rng.standard_normal(hd),
            p + "self_attn.k_norm.weight": rng.standard_normal(hd),
            p + "mlp.gate.weight": rng.standard_normal((E, D)),
        })
        for e in range(E):
            tensors[p + f"mlp.experts.{e}.gate_proj.weight"] = rng.standard_normal((I, D))
            tensors[p + f"mlp.experts.{e}.up_proj.weight"] = rng.standard_normal((I, D))
            tensors[p + f"mlp.experts.{e}.down_proj.weight"] = rng.standard_normal((D, I))
    tensors = {k: v.astype(np.float32) for k, v in tensors.items()}
    save_file(tensors, str(tmp_path / "model.safetensors"))
    (tmp_path / "config.json").write_text(json.dumps(hf))
    params, loaded = load_params(str(tmp_path))
    assert loaded.model_type == "sdar_moe" and loaded.block_length == B
    layers = params["layers"]
    assert layers["w_gate"].shape == (2, E, D, I) and layers["w_down"].shape == (2, E, I, D)
    np.testing.assert_array_equal(
        np.asarray(layers["w_up"][1, 5]), tensors["model.layers.1.mlp.experts.5.up_proj.weight"].T)
    np.testing.assert_array_equal(
        np.asarray(layers["router"][0]), tensors["model.layers.0.mlp.gate.weight"].T)
    np.testing.assert_array_equal(
        np.asarray(layers["k_norm"][1]), tensors["model.layers.1.self_attn.k_norm.weight"])
    np.testing.assert_array_equal(np.asarray(params["lm_head"]), tensors["lm_head.weight"].T)
    # What was loaded computes what the reference computes from it.
    ids = PROMPT[:24]
    want = reference.log_probs(params, hf, ids, list(range(24)))
    k, v = llama.init_kv_cache(loaded, 4, 8, dtype=jnp.float32)
    logits, _, _ = llama.forward_ragged(
        params, loaded, jnp.asarray(ids, jnp.int32), jnp.arange(24, dtype=jnp.int32),
        jnp.zeros(24, jnp.int32), jnp.arange(4, dtype=jnp.int32)[None], k, v,
        jnp.arange(24, dtype=jnp.int32),
    )
    got = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    assert np.abs(got - want).max() < LOGP_TOL
