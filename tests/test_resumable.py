"""Resumable generation: mid-stream failover with token journaling,
deterministic continuation, and KV handoff leases.

Three layers under test (docs/fault_tolerance.md "Resumable streams"):

- **request plane** (seeded chaos harness): a decode worker killed at
  token K mid-stream — ``crash_at_token(k)`` / ``drain_timeout`` — is
  resumed on a surviving instance via the router's replay journal, and
  the spliced stream is identical to an uninterrupted run; recovery
  respects ``max_recoveries`` and end-to-end deadlines.
- **engine** (real TPUEngine on the CPU mesh): a continuation request
  (prompt + already-generated tokens re-prefilled in one batched
  dispatch) produces exactly the tokens the uninterrupted run would
  have — greedy AND seeded sampling (counter-based RNG keyed by
  (seed, absolute position)); KV handoff leases pin extracted pages and
  the engine-loop reaper reclaims them when the decode side never
  confirms delivery.
- **SSE** (full HTTP pipeline): the client-facing stream is gap-free and
  duplicate-free by sequence index across a mid-stream worker kill.

Run with ``make chaos`` (fixed seed sets) or plain pytest.
"""

import asyncio
import json
import os
import random

import pytest

from dynamo_exp_tpu.runtime import (
    Annotated,
    AsyncEngineContext,
    DeadlineExceededError,
    DistributedRuntime,
    PushRouter,
    RecoveryExhaustedError,
    ReplayJournal,
    RouterMode,
)
from dynamo_exp_tpu.runtime.transports.chaos import (
    ChaosDiscovery,
    ChaosRequestPlane,
    ChaosSchedule,
)
from dynamo_exp_tpu.runtime.transports.inproc import (
    InProcDiscovery,
    InProcRequestPlane,
)
from dynamo_exp_tpu.telemetry import get_telemetry

pytestmark = pytest.mark.chaos

SEEDS = tuple(
    int(s) for s in os.environ.get("CHAOS_SEEDS", "7,21,1337").split(",")
)

PROMPT = [11, 12, 13]
MAX_TOKENS = 10


# ------------------------------------------------------------------ helpers
def next_token(context_tokens: list[int], seed: int = 0) -> int:
    """Pure next-token function: 'greedy decoding' for a fake worker —
    depends only on the full context (and the sampling seed), exactly
    the property a re-prefilled continuation must reproduce."""
    return (sum(context_tokens) * 31 + len(context_tokens) + seed) % 97 + 3


def make_engine_worker(wid: str, calls: list, step_delay_s: float = 0.0):
    """A worker with real engine semantics over BackendInput dicts:
    token_ids are all prompt (journaled continuation tokens included),
    generation continues from the full context, one token per frame."""

    async def handler(request, context=None):
        calls.append(wid)
        toks = list(request["token_ids"])
        sc = request.get("stop_conditions") or {}
        so = request.get("sampling_options") or {}
        seed = so.get("seed") or 0
        n = sc.get("max_tokens", MAX_TOKENS)
        for _ in range(n):
            if step_delay_s:
                await asyncio.sleep(step_delay_s)
            t = next_token(toks, seed)
            toks.append(t)
            yield Annotated.from_data({"token_ids": [t]}).to_dict()
        yield Annotated.from_data(
            {
                "finish_reason": "length",
                "prompt_tokens": len(request["token_ids"]),
                "completion_tokens": n,
            }
        ).to_dict()

    return handler


def chaos_runtime(schedule: ChaosSchedule) -> DistributedRuntime:
    return DistributedRuntime(
        discovery=ChaosDiscovery(InProcDiscovery(), schedule),
        request_plane=ChaosRequestPlane(InProcRequestPlane(), schedule),
    )


async def serve_two(drt, calls, **worker_kw):
    ep = drt.namespace("resume").component("worker").endpoint("generate")
    a = await ep.serve_endpoint(make_engine_worker("a", calls, **worker_kw))
    b = await ep.serve_endpoint(make_engine_worker("b", calls, **worker_kw))
    client = await ep.client()
    await client.wait_for_instances(2, timeout=2)
    return a, b, client


def make_router(client, seed=0, **kw):
    kw.setdefault("mode", RouterMode.ROUND_ROBIN)
    kw.setdefault("backoff_base_s", 0.001)
    return PushRouter(client, rng=random.Random(seed), **kw)


def request_body(**sampling) -> dict:
    req = {
        "token_ids": list(PROMPT),
        "stop_conditions": {"max_tokens": MAX_TOKENS},
    }
    if sampling:
        req["sampling_options"] = sampling
    return req


async def collect_tokens(stream):
    tokens, final = [], None
    async for item in stream:
        tokens.extend(item.get("token_ids", []))
        if item.get("finish_reason"):
            final = item
    return tokens, final


def expected_greedy(seed: int = 0) -> list[int]:
    toks = list(PROMPT)
    out = []
    for _ in range(MAX_TOKENS):
        t = next_token(toks, seed)
        toks.append(t)
        out.append(t)
    return out


# -------------------------------------------- mid-stream failover (tentpole)
@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("k", [1, 4, MAX_TOKENS - 1])
async def test_greedy_stream_identical_after_crash_at_token_k(seed, k):
    """Acceptance: kill the decode worker after K tokens mid-stream; the
    request completes on the survivor with a token stream identical to
    an uninterrupted run — no duplicates, no gaps, correct usage."""
    sched = ChaosSchedule(seed)
    drt = chaos_runtime(sched)
    calls: list = []
    a, b, client = await serve_two(drt, calls)
    router = make_router(client, seed)
    sched.crash_at_token(k, instance_id=a.instance_id)

    tokens, final = await collect_tokens(await router.generate(request_body()))

    assert tokens == expected_greedy()
    assert calls == ["a", "b"]  # one failover dispatch, no more
    assert final["finish_reason"] == "length"
    # Usage reflects the client's view, not the continuation's.
    assert final["prompt_tokens"] == len(PROMPT)
    assert final["completion_tokens"] == MAX_TOKENS
    # The failure registered against the dead instance.
    assert client.health.breaker(a.instance_id).consecutive_failures == 1
    await drt.close()


async def test_crash_between_last_token_and_finish_frame():
    """k == max_tokens: the budget is spent when the stream dies — the
    router closes the stream locally (synthetic length finish) instead
    of re-prefilling the whole sequence to generate nothing."""
    sched = ChaosSchedule(SEEDS[0])
    drt = chaos_runtime(sched)
    calls: list = []
    a, b, client = await serve_two(drt, calls)
    router = make_router(client)
    sched.crash_at_token(MAX_TOKENS, instance_id=a.instance_id)

    tokens, final = await collect_tokens(await router.generate(request_body()))

    assert tokens == expected_greedy()
    assert calls == ["a"]  # no re-dispatch for a spent budget
    assert final["finish_reason"] == "length"
    assert final["completion_tokens"] == MAX_TOKENS
    await drt.close()


@pytest.mark.parametrize("seed", SEEDS)
async def test_sampled_continuation_replays_deterministically(seed):
    """Two chaos runs with the same seeds produce bit-identical sampled
    streams across a mid-stream crash: the router pins the RNG seed in
    the journal, and the continuation replays it."""

    async def one_run():
        sched = ChaosSchedule(seed)
        drt = chaos_runtime(sched)
        calls: list = []
        a, b, client = await serve_two(drt, calls)
        router = make_router(client, seed)
        sched.crash_at_token(3, instance_id=a.instance_id)
        tokens, final = await collect_tokens(
            await router.generate(request_body(temperature=0.9))
        )
        injected = list(sched.injected)
        await drt.close()
        return tokens, final, calls, injected

    t1, f1, c1, i1 = await one_run()
    t2, f2, c2, i2 = await one_run()
    assert t1 == t2 and len(t1) == MAX_TOKENS
    assert f1 == f2 and c1 == c2 == ["a", "b"]
    # Same faults at the same points (instance ids are run-global
    # lease-derived counters — compare op:kind shapes).
    strip = lambda log: [":".join(x.split(":")[::2]) for x in log]
    assert strip(i1) == strip(i2)


async def test_recovery_bounded_by_max_recoveries_then_surfaces():
    """Every instance keeps dying mid-stream: after ``max_recoveries``
    failovers the break surfaces as RecoveryExhaustedError (HTTP 502)."""
    sched = ChaosSchedule(SEEDS[0])
    drt = chaos_runtime(sched)
    calls: list = []
    a, b, client = await serve_two(drt, calls)
    router = make_router(client, max_recoveries=1)
    sched.crash_at_token(2, times=2)  # initial stream AND the continuation

    stream = await router.generate(request_body())
    with pytest.raises(RecoveryExhaustedError, match="max_recoveries=1"):
        await collect_tokens(stream)
    assert calls == ["a", "b"]
    await drt.close()


async def test_no_recovery_after_deadline():
    """A stream that breaks after the request's end-to-end deadline has
    passed must NOT be resumed — the client has already given up."""
    sched = ChaosSchedule(SEEDS[0])
    drt = chaos_runtime(sched)
    calls: list = []
    a, b, client = await serve_two(drt, calls, step_delay_s=0.03)
    router = make_router(client)
    sched.crash_at_token(2, instance_id=a.instance_id)

    ctx = AsyncEngineContext()
    ctx.start_timeout(0.04)  # expires before the crash at ~0.06s
    stream = await router.generate(request_body(), ctx)
    with pytest.raises(DeadlineExceededError):
        await collect_tokens(stream)
    assert calls == ["a"]  # never re-dispatched
    await drt.close()


async def test_drain_timeout_resumes_and_labels_reason():
    """A drain whose grace period expires mid-stream is a resumable
    break, counted under reason="drain" on the recovery counter."""
    sched = ChaosSchedule(SEEDS[0])
    drt = chaos_runtime(sched)
    calls: list = []
    a, b, client = await serve_two(drt, calls)
    router = make_router(client)
    sched.drain_timeout(instance_id=a.instance_id, after_tokens=4)
    counter = get_telemetry().request_recoveries.labels("drain")
    before = counter._value.get()

    tokens, final = await collect_tokens(await router.generate(request_body()))

    assert tokens == expected_greedy()
    assert calls == ["a", "b"]
    assert counter._value.get() == before + 1
    await drt.close()


async def test_recovery_never_returns_to_previously_broken_instance():
    """Exclusion is cumulative across recoveries: with a permanently
    crashing first instance and a second that breaks once, the second
    recovery must land on the third (never-broken) instance instead of
    burning the last recovery on a known-bad one."""
    sched = ChaosSchedule(SEEDS[0])
    drt = chaos_runtime(sched)
    calls: list = []
    ep = drt.namespace("resume").component("worker").endpoint("generate")
    a = await ep.serve_endpoint(make_engine_worker("a", calls))
    b = await ep.serve_endpoint(make_engine_worker("b", calls))
    c = await ep.serve_endpoint(make_engine_worker("c", calls))
    client = await ep.client()
    await client.wait_for_instances(3, timeout=2)
    # STATIC always picks the first healthy instance, so without the
    # cumulative-exclusion fix the second recovery would return to the
    # still-crashing `a` and exhaust the budget.
    router = make_router(client, mode=RouterMode.STATIC, max_recoveries=2)
    sched.crash_at_token(2, instance_id=a.instance_id, times=-1)
    sched.crash_at_token(4, instance_id=b.instance_id, times=1)

    tokens, final = await collect_tokens(await router.generate(request_body()))

    assert tokens == expected_greedy()
    assert calls == ["a", "b", "c"]
    assert final["finish_reason"] == "length"
    await drt.close()


async def test_explicit_target_without_selector_stays_committed():
    """generate_direct without a continuation selector keeps the old
    contract: a mid-stream break on the explicit target surfaces."""
    sched = ChaosSchedule(SEEDS[0])
    drt = chaos_runtime(sched)
    calls: list = []
    a, b, client = await serve_two(drt, calls)
    router = make_router(client)
    sched.crash_at_token(2, instance_id=a.instance_id)

    stream = await router.generate_direct(request_body(), a.instance_id)
    with pytest.raises(ConnectionError, match="crashed at token"):
        await collect_tokens(stream)
    assert calls == ["a"]
    await drt.close()


async def test_continuation_selector_enables_kv_style_failover():
    """With a continuation selector installed (the KvPushRouter wiring),
    even an explicit-target stream resumes — on the instance the
    selector picks from the survivors."""
    sched = ChaosSchedule(SEEDS[0])
    drt = chaos_runtime(sched)
    calls: list = []
    a, b, client = await serve_two(drt, calls)
    seen: list = []

    async def reselect(token_ids, exclude):
        # The continuation's token_ids include the journaled tokens —
        # the overlap estimate a KV router would price.
        seen.append((len(token_ids), set(exclude)))
        assert a.instance_id in exclude
        return b.instance_id

    router = make_router(client, continuation_selector=reselect)
    sched.crash_at_token(4, instance_id=a.instance_id)

    stream = await router.generate_direct(request_body(), a.instance_id)
    tokens, final = await collect_tokens(stream)

    assert tokens == expected_greedy()
    assert calls == ["a", "b"]
    assert seen == [(len(PROMPT) + 4, {a.instance_id})]
    await drt.close()


# ------------------------------------------------------------ journal units
def test_journal_pins_seed_and_builds_continuation():
    rng = random.Random(0)
    req = {
        "token_ids": [1, 2, 3],
        "stop_conditions": {"max_tokens": 8, "min_tokens": 4},
        "sampling_options": {"temperature": 0.7},
    }
    j = ReplayJournal.for_request(req, rng)
    seed = j.request["sampling_options"]["seed"]
    assert seed is not None  # pinned for replay
    assert req["sampling_options"].get("seed") is None  # caller untouched

    for t in (7, 8, 9):
        j.record({"token_ids": [t]})
    j.recoveries += 1
    cont = j.continuation_request()
    assert cont["token_ids"] == [1, 2, 3, 7, 8, 9]
    assert cont["resume_offset"] == 3
    assert cont["stop_conditions"]["max_tokens"] == 5
    assert cont["stop_conditions"]["min_tokens"] == 1
    assert cont["sampling_options"]["seed"] == seed


def test_journal_dedup_trims_replayed_indices():
    """A misbehaving continuation that re-emits journaled tokens is
    trimmed by sequence index — duplicate-free output, counted."""
    j = ReplayJournal.for_request({"token_ids": [1]}, random.Random(0))
    j.record({"token_ids": [10, 11]})
    j.begin_continuation()
    # Continuation (wrongly) replays index 1 before new tokens 12, 13.
    j._stream_base = 1  # stream claims to start at index 1
    before = get_telemetry().tokens_deduplicated._value.get()
    out = j.record(
        {"token_ids": [11, 12, 13], "logprobs": [-1.0, -2.0, -3.0]}
    )
    # Per-token payloads are trimmed in lockstep with token_ids.
    assert out == {"token_ids": [12, 13], "logprobs": [-2.0, -3.0]}
    assert j.tokens == [10, 11, 12, 13]
    assert get_telemetry().tokens_deduplicated._value.get() == before + 1
    # A fully duplicate frame vanishes.
    j._stream_base, j._stream_pos = 0, 0
    assert j.record({"token_ids": [10]}) is None


# ------------------------------------------- engine: continuation + leases
PS = 8


@pytest.fixture(scope="module")
def resume_engine():
    from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
    from dynamo_exp_tpu.models import TINY
    from dynamo_exp_tpu.parallel import single_device_mesh

    cfg = EngineConfig(
        model=TINY,
        max_decode_slots=4,
        page_size=PS,
        num_pages=64,
        max_model_len=128,
        eos_token_ids=[],
        kv_dtype="float32",
        kv_lease_ttl_s=0.25,  # fast reaper for the orphan tests
    )
    eng = TPUEngine(cfg, mesh=single_device_mesh(), seed=0)
    eng.start()
    yield eng
    eng.stop()


async def run_engine(eng, token_ids, max_tokens, resume_offset=None, **sampling):
    from dynamo_exp_tpu.protocols.common import BackendInput, SamplingOptions

    b = BackendInput(token_ids=list(token_ids))
    b.stop_conditions.max_tokens = max_tokens
    b.stop_conditions.ignore_eos = True
    b.resume_offset = resume_offset
    if sampling:
        b.sampling_options = SamplingOptions(**sampling)
    stream = await eng.generate(b.to_dict())
    tokens = []
    async for item in stream:
        tokens.extend(item.get("token_ids", []))
    return tokens


async def test_engine_greedy_continuation_token_identical(resume_engine):
    """Satellite acceptance: re-prefilling prompt + the first k generated
    tokens and continuing greedily yields exactly the uninterrupted
    run's remaining tokens — for k=1, mid-stream, and k=max_tokens-1."""
    prompt = [5, 9, 17, 23, 4, 31, 8, 2, 44, 6]
    n = 10
    full = await run_engine(resume_engine, prompt, n)
    assert len(full) == n
    for k in (1, 5, n - 1):
        cont = await run_engine(resume_engine, prompt + full[:k], n - k)
        assert full[:k] + cont == full, f"continuation diverged at k={k}"


async def test_engine_seeded_sampling_continuation_identical(resume_engine):
    """Counter-based RNG: with a pinned seed, a sampled continuation
    replays the exact draws of the uninterrupted run — the draw for the
    token at absolute position p depends only on (seed, p), never on
    window layout, batch shape, or which prefill computed the context."""
    prompt = [7, 3, 19, 28, 41, 13]
    n = 10
    so = dict(temperature=0.9, top_p=0.9, seed=12345)
    full = await run_engine(resume_engine, prompt, n, **so)
    rerun = await run_engine(resume_engine, prompt, n, **so)
    assert full == rerun  # deterministic end-to-end
    for k in (1, 4, n - 1):
        cont = await run_engine(resume_engine, prompt + full[:k], n - k, **so)
        assert full[:k] + cont == full, f"sampled continuation diverged at k={k}"


async def test_engine_penalized_continuation_restores_counts(resume_engine):
    """A continuation marked with ``resume_offset`` rebuilds the penalty
    counts from the journaled tail, so the splice token and every draw
    after it are penalized exactly like the uninterrupted run's. Greedy + presence penalty on
    the TINY model is a sharp probe: the unpenalized greedy chain
    repeats tokens, so missing counts visibly change the argmax."""
    prompt = [6, 14, 27, 35, 9]
    n, k = 10, 4
    so = dict(presence_penalty=5.0)
    full = await run_engine(resume_engine, prompt, n, **so)
    marked = await run_engine(
        resume_engine, prompt + full[:k], n - k, resume_offset=k, **so
    )
    # Counts restored → the spliced stream is token-identical to the
    # uninterrupted run, the splice token included: it draws through
    # the journaled tail's counts as the decode row it was.
    assert marked == full[k:]
    # Without the marker the journaled tail is plain prompt (no counts):
    # the penalty forgets those tokens and the continuation diverges —
    # proof the reconstruction actually feeds the sampler.
    unmarked = await run_engine(resume_engine, prompt + full[:k], n - k, **so)
    assert marked != unmarked


async def _wait_until(predicate, timeout_s=3.0):
    deadline = asyncio.get_running_loop().time() + timeout_s
    while not predicate():
        if asyncio.get_running_loop().time() > deadline:
            return False
        await asyncio.sleep(0.02)
    return True


async def test_engine_lease_reaper_reclaims_orphaned_extract(resume_engine):
    """Acceptance: after a simulated decode death between extract and
    inject (nobody ever confirms delivery), the prefill engine's page
    occupancy returns to its pre-request level within one lease
    period — the reaper, not a leak."""
    from dynamo_exp_tpu.protocols.common import BackendInput

    eng = resume_engine
    prompt = [3 + (i * 7) % 90 for i in range(3 * PS + 5)]
    n_pages = (len(prompt) + PS - 1) // PS
    baseline = eng.kv.active_pages
    reclaimed_before = eng.kv.lease_reclaimed_pages

    tok, pages, lease_id = await eng.prefill_extract(
        BackendInput(token_ids=prompt).to_dict()
    )
    assert lease_id and len(pages) == n_pages
    # The extract sequence has finished, yet the pages stay pinned.
    assert eng.kv.active_leases == 1
    assert eng.kv.active_pages == baseline + n_pages

    # No confirm arrives: the engine-loop reaper reclaims at TTL.
    assert await _wait_until(lambda: eng.kv.active_pages == baseline)
    assert eng.kv.active_leases == 0
    assert eng.kv.lease_reclaimed_pages == reclaimed_before + n_pages


async def test_engine_lease_confirm_releases_without_reclaim(resume_engine):
    """The happy path: a delivery ack confirms the lease — pages return
    to the pool immediately and the reaper counter does not move."""
    from dynamo_exp_tpu.protocols.common import BackendInput

    eng = resume_engine
    prompt = [4 + (i * 11) % 90 for i in range(2 * PS + 3)]
    n_pages = (len(prompt) + PS - 1) // PS
    baseline = eng.kv.active_pages
    reclaimed_before = eng.kv.lease_reclaimed_pages

    tok, pages, lease_id = await eng.prefill_extract(
        BackendInput(token_ids=prompt).to_dict()
    )
    assert eng.kv.active_pages == baseline + n_pages
    eng.confirm_kv_lease(lease_id)
    assert await _wait_until(lambda: eng.kv.active_pages == baseline)
    assert eng.kv.active_leases == 0
    assert eng.kv.lease_reclaimed_pages == reclaimed_before  # no reap


async def test_prefill_worker_leaves_lease_to_reaper_on_delivery_failure(
    resume_engine,
):
    """Worker-level: KV delivery to a dead decode worker fails → the
    lease is NOT confirmed (the reaper owns cleanup), and the pull loop
    survives."""
    from dynamo_exp_tpu.disagg import PrefillWorker, RemotePrefillRequest
    from dynamo_exp_tpu.disagg.protocol import kv_signature
    from dynamo_exp_tpu.runtime.transports.inproc import InProcWorkQueue

    eng = resume_engine
    baseline = eng.kv.active_pages
    worker = PrefillWorker(eng, InProcWorkQueue())
    req = RemotePrefillRequest(
        request_id="dead-decode-1",
        token_ids=[5 + (i * 13) % 90 for i in range(PS + 3)],
        return_addr="127.0.0.1:1",  # nothing listens: delivery fails
        page_size=PS,
        model=kv_signature(eng.cfg),
    )
    await worker._serve_one(req.to_bytes())
    assert worker.failed == 1 and worker.served == 0
    # Lease left behind for the reaper, which then restores occupancy.
    assert await _wait_until(lambda: eng.kv.active_pages == baseline)
    assert eng.kv.active_leases == 0


# ------------------------------------------------- SSE layer (full pipeline)
async def test_sse_stream_gapless_and_duplicate_free_across_failover(
    tiny_model_dir,
):
    """Acceptance: HTTP → preprocessor → backend → push router over the
    chaos plane; the decode worker dies mid-stream; the client's SSE
    stream is identical to an uninterrupted run with strictly increasing
    sequence indices and exact usage."""
    from aiohttp.test_utils import TestClient, TestServer

    from dynamo_exp_tpu.http import HttpService, build_pipeline_engine
    from dynamo_exp_tpu.model_card import ModelDeploymentCard

    mdc = ModelDeploymentCard.from_local_path(tiny_model_dir, display_name="tiny")

    async def run_sse(crash_at: int | None):
        sched = ChaosSchedule(SEEDS[0])
        drt = chaos_runtime(sched)
        calls: list = []
        a, b, client = await serve_two(drt, calls)
        if crash_at is not None:
            sched.crash_at_token(crash_at, instance_id=a.instance_id)
        router = make_router(client)
        svc = HttpService()
        svc.manager.add_completion_model(
            "tiny", build_pipeline_engine(mdc, router)
        )
        http = TestClient(TestServer(svc.app))
        await http.start_server()
        r = await http.post(
            "/v1/completions",
            json={
                "model": "tiny",
                "prompt": list(PROMPT),
                "max_tokens": MAX_TOKENS,
                "stream": True,
                "stream_options": {"include_usage": True},
            },
        )
        assert r.status == 200
        raw = (await r.read()).decode()
        await http.close()
        await drt.close()
        chunks = [
            json.loads(line[6:])
            for line in raw.split("\n")
            if line.startswith("data: ") and line != "data: [DONE]"
        ]
        text = "".join(
            c["choices"][0].get("text") or "" for c in chunks if c.get("choices")
        )
        seq = [c["seq_index"] for c in chunks if c.get("seq_index") is not None]
        usage = next((c["usage"] for c in chunks if c.get("usage")), None)
        assert raw.strip().endswith("data: [DONE]")  # stream closed cleanly
        return text, seq, usage, calls

    clean_text, clean_seq, clean_usage, clean_calls = await run_sse(None)
    text, seq, usage, calls = await run_sse(4)

    assert calls == ["a", "b"] and clean_calls == ["a"]
    # Unbroken: the spliced stream is byte-identical to the clean run.
    assert text == clean_text and len(text) > 0
    # Gap-free, duplicate-free by sequence index; all tokens accounted.
    assert seq == sorted(set(seq)) and seq == clean_seq
    assert seq[-1] == MAX_TOKENS
    assert usage == clean_usage
    assert usage["prompt_tokens"] == len(PROMPT)
    assert usage["completion_tokens"] == MAX_TOKENS


async def test_sse_layer_drops_duplicate_seq_index_chunks():
    """Defense in depth: chunks arriving at the HTTP layer with an
    already-emitted sequence index are dropped before the wire."""
    from aiohttp.test_utils import TestClient, TestServer

    from dynamo_exp_tpu.http import HttpService
    from dynamo_exp_tpu.runtime import ResponseStream

    def chunk(text, si):
        return {
            "id": "c",
            "object": "text_completion",
            "created": 1,
            "model": "tiny",
            "choices": [{"index": 0, "text": text}],
            "seq_index": si,
        }

    class ReplayingEngine:
        async def generate(self, request, context=None):
            ctx = context or AsyncEngineContext()

            async def _gen():
                yield chunk("a", 1)
                yield chunk("b", 2)
                yield chunk("b", 2)  # duplicate splice artifact
                yield chunk("a", 1)  # stale replay
                yield chunk("c", 3)

            return ResponseStream(_gen(), ctx)

    svc = HttpService()
    svc.manager.add_completion_model("tiny", ReplayingEngine())
    http = TestClient(TestServer(svc.app))
    await http.start_server()
    r = await http.post(
        "/v1/completions",
        json={"model": "tiny", "prompt": "x", "stream": True},
    )
    raw = (await r.read()).decode()
    await http.close()
    texts = [
        json.loads(line[6:])["choices"][0]["text"]
        for line in raw.split("\n")
        if line.startswith("data: ") and line != "data: [DONE]"
        if json.loads(line[6:]).get("choices")
    ]
    assert texts == ["a", "b", "c"]
