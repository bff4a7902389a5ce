"""glm_moe_dsa (GLM-5) through the ENGINE: the scheduler's chunks and windows
against the reference's greedy continuation, what the counters say was scored
and attended, prefix hits over both pools, the same path through the
interpreted kernels, preempt and resume. The reader, the selection's ops and
the forwards called outright: ``tests/test_glm_dsa.py``, whose constants
these tests share (split from it at PR 55: docs/testing.md "Where a new
kernel's cases go": a file that nears 250 s is split by the call its tests
drive)."""

import asyncio
import types

import numpy as np

import pytest

from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
from dynamo_exp_tpu.protocols.common import BackendInput

from .test_glm_dsa import CFG, HF, PROMPT, PS, TOPK, _engine, reference


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
        ids.append(int(reference.log_probs(
            params, HF, ids, [len(ids) - 1], pad_to=128)[0].argmax()))
    return ids[len(prompt):]


def _counter(name, **labels):
    from dynamo_exp_tpu.telemetry import get_telemetry

    total = 0.0
    for metric in get_telemetry().registry.collect():
        for s in metric.samples:
            if s.name == name and all(s.labels.get(k) == v for k, v in labels.items()):
                total += s.value
    return total


_COUNTERS = (
    "dynamo_mla_query_tokens_total", "dynamo_mla_attended_pairs_total",
    "dynamo_dsa_index_pairs_total", "dynamo_dsa_selected_pairs_total",
    "dynamo_dsa_query_tokens_total",
)


async def test_engine_serves_windows_and_chunks(engine):
    """Through the scheduler: a 40-token prompt in chunks of 16 (mixed
    program), then 20 tokens through 8-step windows, equal to the
    reference's greedy continuation; both pools are written; the counters
    say what was scored and what was attended."""
    assert engine.k_cache.shape == engine.v_cache.shape == (3, 64, PS, 128)
    before = {k: _counter(k) for k in _COUNTERS}
    # this worker's earlier files may have served latent prompts (test_pangu.py)
    expanded = _counter("dynamo_mla_query_tokens_total", form="expanded")
    prompt = PROMPT[:40]
    got = await _run(engine, prompt, 20)
    assert got == _greedy_reference(engine.params, prompt, 20)
    took = {k: _counter(k) - before[k] for k in _COUNTERS}
    fed = 40 + 19  # every token but the last sampled one is fed
    assert took["dynamo_mla_query_tokens_total"] >= fed
    assert took["dynamo_dsa_query_tokens_total"] == took["dynamo_mla_query_tokens_total"]
    assert took["dynamo_dsa_index_pairs_total"] >= fed * (fed + 1) // 2
    kept = TOPK * (TOPK + 1) // 2 + (fed - TOPK) * TOPK
    assert took["dynamo_dsa_selected_pairs_total"] >= kept
    assert took["dynamo_mla_attended_pairs_total"] == took["dynamo_dsa_selected_pairs_total"]
    assert took["dynamo_dsa_selected_pairs_total"] < took["dynamo_dsa_index_pairs_total"]
    assert _counter("dynamo_dsa_query_tokens_total", selecting="selecting") >= fed - TOPK
    assert _counter("dynamo_dsa_query_tokens_total", program="window", selecting="all") == 0
    assert _counter("dynamo_mla_query_tokens_total", form="expanded") == expanded


def test_the_counters_arithmetic():
    """``_count_latent_reads`` on a hand-made stream and window."""
    eng = types.SimpleNamespace(
        cfg=EngineConfig(model=CFG, max_decode_slots=2, page_size=PS, num_pages=16),
        _attn_impl="xla", _attn_interpret=False,
    )
    for method in ("_count_latent_reads", "_latent_form_queries"):
        setattr(eng, method, types.MethodType(getattr(TPUEngine, method), eng))
    names = ("dynamo_dsa_index_pairs_total", "dynamo_dsa_selected_pairs_total")
    before = [_counter(n, program="mixed") for n in names]
    sel = _counter("dynamo_dsa_query_tokens_total", program="mixed", selecting="selecting")
    positions = np.asarray([0, 1, 2, -1, 14, 15, 16, 17, 40, -1], np.int32)
    row_of = np.asarray([0, 0, 0, 0, 1, 1, 1, 1, 2, 2], np.int32)
    eng._count_latent_reads("mixed", positions, row_of)
    pairs, kept = (_counter(n, program="mixed") - b for n, b in zip(names, before))
    assert pairs == 1 + 2 + 3 + 15 + 16 + 17 + 18 + 41
    assert kept == 1 + 2 + 3 + 15 + 16 + 16 + 16 + 16
    assert _counter("dynamo_dsa_query_tokens_total", program="mixed", selecting="selecting") - sel == 3
    before = [_counter(n, program="window") for n in names]
    eng._count_latent_reads("window", np.asarray([13, 50, 3]), steps=np.asarray([8, 8, 0]))
    pairs, kept = (_counter(n, program="window") - b for n, b in zip(names, before))
    assert pairs == sum(range(14, 22)) + sum(range(51, 59))
    assert kept == 14 + 15 + 16 * 6 + 16 * 8
    # The form each query took, by the program's own rule
    # (``latent_form_queries``: 8 queries of a row in the tiled part, indexer
    # or not): a selecting row's chunk of 8 counts as expanded, the row of 5
    # beside it and the packed tail's decode row as absorbed.
    eng._attn_impl, eng._attn_interpret = "pallas", True
    assert eng._latent_form_queries() == 8
    forms = ("expanded", "absorbed")
    before = [_counter("dynamo_mla_query_tokens_total", program="mixed", form=f) for f in forms]
    positions = np.asarray(list(range(30, 38)) + [0, 1, 2, 3, 4, -1, -1, -1] + [50], np.int32)
    row_of = np.asarray([0] * 8 + [1] * 8 + [2], np.int32)
    eng._count_latent_reads("mixed", positions, row_of, tiled=16)
    expanded, absorbed = (
        _counter("dynamo_mla_query_tokens_total", program="mixed", form=f) - b
        for f, b in zip(forms, before)
    )
    assert (expanded, absorbed) == (8, 6)


def test_pool_info_says_what_a_token_holds(engine):
    from dynamo_exp_tpu.telemetry import get_telemetry

    (labels,) = [
        s.labels for m in get_telemetry().registry.collect() for s in m.samples
        if s.name == "dynamo_kv_pool_info"
    ]
    assert labels["pools"] == "2" and labels["width"] == "128" and labels["index_width"] == "128"
    assert labels["pad_lanes"] == str(128 - 56)
    assert labels["token_bytes"] == str(3 * (128 + 128) * 4)  # layers x both rows x float32


@pytest.mark.parametrize("asking", ["again", "longer"])
async def test_second_asking_from_cached_pages_of_both_pools(engine, asking):
    """A prompt whose head is in cached pages is served from them: latent
    rows AND index keys another request wrote (a page is one slot in both
    pools), selected among by the new request's queries."""
    base = PROMPT[5:38]  # 33 tokens: four whole pages
    await _run(engine, base, 4)
    before = engine.kv.prefix_hits["shared"]
    prompt = base if asking == "again" else base + PROMPT[:7]
    got = await _run(engine, prompt, 12)
    assert got == _greedy_reference(engine.params, prompt, 12)
    assert engine.kv.prefix_hits["shared"] - before == 4


async def test_engine_through_the_kernels():
    """The same path with both kernels (interpreted) in both programs: the
    scores kernel and the masked attention kernel at tiles of 8 and at the
    one-query form, the experts' kernel in place."""
    # pages of 32 on a table of 4: four page copies a chunk of each interpreted
    # kernel to lower, not sixteen (tests/ragged_cases.py "SHAPES")
    eng = _engine(
        attention_impl="pallas", pallas_interpret=True, page_size=32, max_model_len=128,
        num_pages=16,
    )
    eng.start()
    try:
        prompt = PROMPT[3:40]
        got = await _run(eng, prompt, 10)
        assert got == _greedy_reference(eng.params, prompt, 10)
        # the prompt's chunks of 8 queries and more took the expanded form
        assert eng._attn_impl == "pallas" and eng._latent_form_queries() == 8
    finally:
        eng.stop()


async def test_preempt_and_resume():
    """Two requests on a pool that holds one and a half: one is preempted,
    re-admitted onto its own cached pages, and both streams equal the
    reference's."""
    eng = _engine(num_pages=14)
    eng.start()
    try:
        p1, p2, n = PROMPT[:19], PROMPT[20:39], 44
        r1, r2 = await asyncio.gather(_run(eng, p1, n), _run(eng, p2, n))
        assert eng.preempted > 0
        assert r1 == _greedy_reference(eng.params, p1, n)
        assert r2 == _greedy_reference(eng.params, p2, n)
        assert not eng.kv.ledger_check()
    finally:
        eng.stop()
