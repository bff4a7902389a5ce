"""Logprobs end-to-end: engine produces chosen-token + top-N logprobs
of the model distribution, and the OpenAI layer shapes them per spec
(chat chunk choices[].logprobs.content, legacy completions fields,
stream=false aggregation)."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
from dynamo_exp_tpu.models import TINY, forward, init_kv_cache
from dynamo_exp_tpu.parallel import single_device_mesh
from dynamo_exp_tpu.protocols.common import BackendInput
from dynamo_exp_tpu.telemetry import get_telemetry

PS = 8


def tiny_engine():
    cfg = EngineConfig(
        model=TINY, max_decode_slots=2, page_size=PS, num_pages=64,
        max_model_len=128, eos_token_ids=[],
    )
    return TPUEngine(cfg, mesh=single_device_mesh(), seed=0)


async def test_engine_logprobs_match_oracle():
    engine = tiny_engine()
    engine.start()
    try:
        prompt = [5, 9, 17, 3, 11]
        b = BackendInput(token_ids=prompt)
        b.stop_conditions.max_tokens = 4
        b.stop_conditions.ignore_eos = True
        b.sampling_options.logprobs = 2  # top-2 + chosen

        stream = await engine.generate(b.to_dict())
        toks: list[int] = []
        lps: list[float] = []
        tops: list[dict] = []
        async for item in stream:
            toks += item.get("token_ids", [])
            lps += item.get("logprobs") or []
            tops += item.get("top_logprobs") or []
        assert len(lps) == len(toks) == 4
        assert len(tops) == 4 and all(len(t) == 2 for t in tops)

        # Oracle: greedy logprob per step from the bare forward.
        params = engine.params
        k, v = init_kv_cache(TINY, num_pages=16, page_size=PS)
        table = jnp.arange(8, dtype=jnp.int32)[None, :] + 1
        logits, k, v = forward(
            params, TINY,
            jnp.array([prompt], jnp.int32),
            jnp.arange(len(prompt), dtype=jnp.int32)[None, :], table, k, v,
        )
        cur = logits[0, -1]
        # Tolerance: the ragged engine computes first-token logits at a
        # static [slots+1, V] lm_head matmul while this oracle uses a
        # batch-1 dot — XLA:CPU lowers the two shapes through different
        # kernels, which lands within bf16 rounding (~4e-3 observed),
        # not bitwise. Greedy argmax is asserted exactly.
        for step, (tok, lp) in enumerate(zip(toks, lps)):
            full = np.asarray(jax.nn.log_softmax(cur.astype(jnp.float32)))
            assert tok == int(full.argmax())  # greedy
            assert abs(full[tok] - lp) < 2e-2
            # top dict contains the chosen (greedy) token with same lp.
            top = {int(a): float(x) for a, x in tops[step].items()}
            assert tok in top and abs(top[tok] - lp) < 2e-2
            pos = len(prompt) + step
            logits, k, v = forward(
                params, TINY,
                jnp.array([[tok]], jnp.int32),
                jnp.array([[pos]], jnp.int32), table, k, v,
            )
            cur = logits[0, 0]
    finally:
        engine.stop()


async def test_engine_no_logprobs_by_default():
    engine = tiny_engine()
    engine.start()
    try:
        b = BackendInput(token_ids=[5, 9, 17])
        b.stop_conditions.max_tokens = 2
        b.stop_conditions.ignore_eos = True
        stream = await engine.generate(b.to_dict())
        async for item in stream:
            assert "logprobs" not in item and "top_logprobs" not in item
    finally:
        engine.stop()


async def test_openai_chat_and_completion_logprob_shapes(tmp_path):
    """Through the preprocessor→backend→engine chain: chat chunks carry
    choices[].logprobs.content entries with token text/bytes/top_logprobs,
    completions carry the legacy fields, and aggregation merges both."""
    import sys

    sys.path.insert(0, str(__import__("os").path.dirname(__file__)))
    from fixtures import build_tiny_model_dir

    from dynamo_exp_tpu.http.service import build_pipeline_engine
    from dynamo_exp_tpu.model_card import ModelDeploymentCard
    from dynamo_exp_tpu.protocols.aggregator import (
        aggregate_chat_stream,
        aggregate_completion_stream,
    )

    model_dir = build_tiny_model_dir(str(tmp_path / "m"))
    mdc = ModelDeploymentCard.from_local_path(model_dir, "tiny")
    cfg = EngineConfig(
        model=__import__(
            "dynamo_exp_tpu.models.config", fromlist=["ModelConfig"]
        ).ModelConfig.from_pretrained(model_dir),
        max_decode_slots=2, page_size=PS, num_pages=64, max_model_len=128,
        eos_token_ids=[],
    )
    engine = TPUEngine(cfg, mesh=single_device_mesh(), seed=0)
    engine.start()
    try:
        oai = build_pipeline_engine(mdc, engine)

        chat_req = {
            "model": "tiny",
            "messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 3,
            "ignore_eos": True,
            "logprobs": True,
            "top_logprobs": 2,
        }
        chunks = []
        stream = await oai.generate(chat_req)
        async for c in stream:
            chunks.append(c)
        lp_chunks = [
            c for c in chunks
            if c.choices and getattr(c.choices[0], "logprobs", None)
        ]
        assert lp_chunks, "no chat chunk carried logprobs"
        entry = lp_chunks[0].choices[0].logprobs["content"][0]
        assert {"token", "logprob", "bytes", "top_logprobs"} <= set(entry)
        assert len(entry["top_logprobs"]) == 2

        async def _replay(items):
            for c in items:
                yield c

        full = await aggregate_chat_stream(_replay(chunks))
        assert full.choices[0].logprobs["content"]

        comp_req = {
            "model": "tiny",
            "prompt": "hello world",
            "max_tokens": 3,
            "ignore_eos": True,
            "logprobs": 2,
        }
        chunks = []
        stream = await oai.generate(comp_req)
        async for c in stream:
            chunks.append(c)
        lp_chunks = [
            c for c in chunks
            if c.choices and getattr(c.choices[0], "logprobs", None)
        ]
        assert lp_chunks, "no completion chunk carried logprobs"
        lp = lp_chunks[0].choices[0].logprobs
        assert lp["tokens"] and len(lp["token_logprobs"]) == len(lp["tokens"])
        full = await aggregate_completion_stream(_replay(chunks))
        assert len(full.choices[0].logprobs["tokens"]) == 3
    finally:
        engine.stop()


async def test_top_logprobs_over_limit_rejected(tmp_path):
    """top_logprobs beyond the device's static top-N is a 400-class
    error, not silent truncation."""
    import sys

    sys.path.insert(0, str(__import__("os").path.dirname(__file__)))
    import pytest
    from fixtures import build_tiny_model_dir

    from dynamo_exp_tpu.model_card import ModelDeploymentCard
    from dynamo_exp_tpu.preprocessor.preprocessor import (
        InvalidRequestError,
        OpenAIPreprocessor,
    )
    from dynamo_exp_tpu.protocols.openai import ChatCompletionRequest

    model_dir = build_tiny_model_dir(str(tmp_path / "m"))
    mdc = ModelDeploymentCard.from_local_path(model_dir, "tiny")
    pre = OpenAIPreprocessor(mdc)
    req = ChatCompletionRequest.model_validate({
        "model": "tiny",
        "messages": [{"role": "user", "content": "hi"}],
        "logprobs": True,
        "top_logprobs": 12,
    })
    with pytest.raises(InvalidRequestError, match="top_logprobs"):
        pre.preprocess_chat(req)


# ------------------------------------------- one emission rule, four row kinds
# What a row's sampled tokens turn into (``TPUEngine._emit_row``) is one
# rule whichever program sampled them. A decode window's rows are held to
# the model above and a finished block's to its reference
# (``tests/test_sdar.py``); here the other kinds are held to the window's:
# the same request, its tokens emitted by another kind of row, streams the
# same tokens, log-probability pack and finish reason.
REPEAT_PROMPT = [5, 9, 17, 3] * 5  # the n-gram drafter hits on it


def _row_kind_engine(**kw):
    cfg = EngineConfig(
        model=TINY, max_decode_slots=2, page_size=PS, num_pages=64,
        max_model_len=128, eos_token_ids=[], prefill_chunk=8, **kw,
    )
    return TPUEngine(cfg, mesh=single_device_mesh(), seed=0)


async def _stream(engine, prompt, n, stop_ids=()):
    b = BackendInput(token_ids=list(prompt))
    b.stop_conditions.max_tokens = n
    b.stop_conditions.ignore_eos = not stop_ids
    b.stop_conditions.stop_token_ids = list(stop_ids)
    b.sampling_options.logprobs = 2
    out = {"tokens": [], "lps": [], "tops": [], "reason": None}
    async for item in await engine.generate(b.to_dict()):
        out["tokens"] += item.get("token_ids", [])
        out["lps"] += item.get("logprobs") or []
        out["tops"] += item.get("top_logprobs") or []
        out["reason"] = item.get("finish_reason") or out["reason"]
    return out


def _assert_same_emission(got, want, upto=None):
    """``got`` is ``want`` (cut after ``upto`` tokens): tokens and reason
    exactly, log-probabilities to the rounding of another program's
    matmul shapes (see the oracle test above; two alternatives that
    close may change places, so the second is held to its value)."""
    n = len(want["tokens"]) if upto is None else upto
    assert got["tokens"] == want["tokens"][:n]
    assert len(got["lps"]) == len(got["tops"]) == n
    for tok, g, w, gt, wt in zip(
        got["tokens"], got["lps"], want["lps"], got["tops"], want["tops"]
    ):
        assert abs(g - w) < 2e-2
        assert len(gt) == len(wt) == 2
        assert abs(gt[tok] - g) < 1e-6 and abs(wt[tok] - w) < 1e-6
        for a, b in zip(sorted(gt.values()), sorted(wt.values())):
            assert abs(a - b) < 2e-2


async def _a_windows_rows(stop_at=None):
    """The reference kind: the request alone, every token off a window."""
    engine = _row_kind_engine()
    engine.start()
    try:
        free = await _stream(engine, REPEAT_PROMPT, 16)
        assert free["reason"] == "length" and len(free["tokens"]) == 16
        if stop_at is None:
            return engine, free, None
        stop = free["tokens"][stop_at]
        cut = free["tokens"].index(stop) + 1
        return engine, free, (stop, cut)
    finally:
        engine.stop()


@pytest.mark.parametrize("kind", ["window-stop", "mixed-decode", "spec", "spec-stop"])
async def test_a_rows_emission_does_not_depend_on_its_kind(kind):
    tel = get_telemetry()
    # Token 12 of 16 is the first of its value, past a window's first step
    # and inside the span the drafter's one hit has verified.
    base, free, stop = await _a_windows_rows(12 if kind.endswith("stop") else None)
    stop_ids, upto = ([stop[0]], stop[1]) if stop else ((), None)
    if kind == "window-stop":
        # A stop the host finds inside a window: the kept tokens' pack.
        base.start()
        try:
            got = await _stream(base, REPEAT_PROMPT, 16, stop_ids)
        finally:
            base.stop()
    elif kind == "mixed-decode":
        # Submitted beside a prompt of 64 tokens, 8 a dispatch: the row's
        # own 20 are in after three, and it decodes one token a dispatch
        # beside the other's chunks.
        engine = _row_kind_engine()
        rows0 = tel.decode_batch_rows.labels("mixed")._sum.get()
        engine.start()
        try:
            got, _ = await asyncio.gather(
                _stream(engine, REPEAT_PROMPT, 16),
                _stream(engine, range(30, 94), 2),
            )
        finally:
            engine.stop()
        assert tel.decode_batch_rows.labels("mixed")._sum.get() - rows0 >= 3
    else:
        # Speculative rows: the accepted prefix and the correction.
        engine = _row_kind_engine(spec_mode="ngram")
        engine.start()
        try:
            got = await _stream(engine, REPEAT_PROMPT, 16, stop_ids)
            assert engine.spec_row_dispatches > 0
        finally:
            engine.stop()
    _assert_same_emission(got, free, upto)
    assert got["reason"] == ("eos" if stop else "length")
