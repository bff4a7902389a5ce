"""How tokens reach the HTTP thread (docs/engine_perf.md): the loop
thread's emits wait in an outbox and travel one ``call_soon_threadsafe``
a dispatch and asyncio loop, made where the loop thread is about to wait.

Held here: a stream is what posting every tuple by itself gives; the
postings are at most one a ``sync`` or ``park``; the outbox is empty
whenever the loop thread blocks or exits, crash included; on the mixed ->
mixed path a dispatch's tuples are posted right after the next launch;
two asyncio loops get a posting each; the posting is booked to
``consume.emit``.
"""

import asyncio
import threading
import time

import pytest

from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
from dynamo_exp_tpu.models import TINY, ModelConfig
from dynamo_exp_tpu.parallel import single_device_mesh
from dynamo_exp_tpu.protocols.common import BackendInput, FinishReason, SamplingOptions
from dynamo_exp_tpu.telemetry.dispatch import DispatchProfiler

from .test_engine_phases import _total as _counter
from .test_sdar import HF as SDAR_HF

PS = 8
WAITS = ("sync", "park")


def _cfg(**over) -> EngineConfig:
    base = dict(
        model=TINY, max_decode_slots=4, page_size=PS, num_pages=64,
        max_model_len=128, prefill_chunk=16, decode_window=4,
        eos_token_ids=[], kv_dtype="float32", prefix_sharing=False,
    )
    return EngineConfig(**(base | over))


def _engine(**over) -> TPUEngine:
    return TPUEngine(_cfg(**over), mesh=single_device_mesh(), seed=0)


async def _stream(engine, prompt, n, logprobs=None) -> list[dict]:
    """Every item of one request's stream, as the engine yields them."""
    b = BackendInput(token_ids=list(prompt))
    b.stop_conditions.max_tokens = n
    b.stop_conditions.ignore_eos = True
    b.sampling_options = SamplingOptions(logprobs=logprobs)
    return [item async for item in await engine.generate(b.to_dict())]


def _tokens(items: list[dict]) -> list[int]:
    return [t for item in items for t in item.get("token_ids") or []]


def _ends_well(items: list[dict], reason: str = "length") -> None:
    """Chunks of tokens, then the one finish, last."""
    assert all(i.get("token_ids") and not i.get("finish_reason") for i in items[:-1])
    assert items[-1]["finish_reason"] == reason and not items[-1].get("token_ids")


def _post_at_once(loop, put, item) -> None:
    """``TPUEngine._post`` as it was before the outbox: a tuple a call."""
    loop.call_soon_threadsafe(put, item)


def _record_waits(monkeypatch, engine, seen: list) -> None:
    """``(phase, tuples in the outbox)`` at every entry of a phase the
    loop thread waits in."""
    orig = engine._phase

    def phase(name):
        if name in WAITS:
            seen.append((name, sum(map(len, engine._outbox.values()))))
        return orig(name)

    monkeypatch.setattr(engine, "_phase", phase)


# -------------------------------------------------- (1) the same stream
PATHS = {
    # One request: chunked prefill in mixed dispatches, the first token,
    # then decode windows (chained), with log-probabilities riding.
    "window": dict(over={}, prompt=list(range(20, 60)), n=14, logprobs=2),
    # The same consumed dispatch by dispatch, nothing chained over.
    "unchained": dict(
        over=dict(chained_decode=False), prompt=list(range(20, 60)), n=14,
        logprobs=None,
    ),
    # Draft spans verified in mixed dispatches: several tokens a tuple.
    "speculative": dict(
        over=dict(spec_mode="ngram", kv_dtype="bfloat16"),
        prompt=[5, 9, 17, 3] * 5, n=16, logprobs=None,
    ),
    # A block model: a block of 4 a tuple, the passes beside them.
    "block": dict(
        over=dict(
            model=ModelConfig.from_hf_config(SDAR_HF), max_model_len=256,
            decode_window=8,
        ),
        prompt=list(range(2, 47)), n=14, logprobs=1,
    ),
}


@pytest.mark.parametrize("path", PATHS)
async def test_a_stream_is_tuple_for_tuple_what_posting_each_gives(path, monkeypatch):
    """Greedy decoding, one request at a time on one engine with nothing
    shared between askings: first every tuple posted by itself, as the
    parent does, then through the outbox."""
    case = PATHS[path]
    engine = _engine(**case["over"])
    engine.start()
    try:
        with monkeypatch.context() as m:
            m.setattr(engine, "_post", _post_at_once)
            want = await _stream(engine, case["prompt"], case["n"], case["logprobs"])
        postings = _counter("dynamo_emit_postings_total")
        got = await _stream(engine, case["prompt"], case["n"], case["logprobs"])
        assert _counter("dynamo_emit_postings_total") > postings
    finally:
        engine.stop()
    assert got == want
    _ends_well(got)
    assert len(_tokens(got)) == case["n"]
    if path == "speculative":
        assert engine.spec_accepted_tokens > 0
    if path == "block":
        assert all(len(i["fixed_at_pass"]) == len(i["token_ids"]) for i in got[:-1])


async def test_concurrent_streams_carry_what_each_gives_alone(monkeypatch):
    """Decode rows riding other requests' prefill chunks (the mixed
    program's decode rows): where a tuple is cut depends on who arrived
    when, what a request is sent does not."""
    prompts = [list(range(10 + 7 * i, 50 + 9 * i)) for i in range(4)]
    engine = _engine()
    engine.start()
    try:
        with monkeypatch.context() as m:
            m.setattr(engine, "_post", _post_at_once)
            alone = [await _stream(engine, p, 12) for p in prompts]
        together = await asyncio.gather(*[
            _stream(engine, p, 12) for p in prompts
        ])
    finally:
        engine.stop()
    for got, want in zip(together, alone):
        _ends_well(got)
        assert _tokens(got) == _tokens(want)
        assert got[-1] == want[-1]


# ------------------------------------- (2), (3) postings, and the outbox
async def test_at_most_one_posting_a_wait_and_one_item_a_tuple(monkeypatch):
    """Four requests at once: every tuple the streams took was counted
    once, the postings are no more than the loop's syncs and parks (and
    its end), far fewer than the tuples, and the outbox was empty each
    time the loop thread went to wait."""
    engine = _engine()
    waits: list = []
    flushes: list = []
    orig_flush = engine._flush_outbox

    def flush():
        flushes.append(len(engine._outbox))
        orig_flush()
        assert not engine._outbox

    monkeypatch.setattr(engine, "_flush_outbox", flush)
    _record_waits(monkeypatch, engine, waits)
    postings0 = _counter("dynamo_emit_postings_total")
    items0 = _counter("dynamo_emit_items_total")
    engine.start()
    try:
        streams = await asyncio.gather(*[
            _stream(engine, range(10 + 7 * i, 40 + 9 * i), 16) for i in range(4)
        ])
        time.sleep(0.12)  # a few parks on the idle path
    finally:
        engine.stop()
    for items in streams:
        _ends_well(items)
        assert len(_tokens(items)) == 16
    postings = _counter("dynamo_emit_postings_total") - postings0
    tuples = sum(len(items) for items in streams)
    assert _counter("dynamo_emit_items_total") - items0 == tuples
    # One asyncio loop: a flush that found something made one posting.
    assert postings == sum(1 for n in flushes if n)
    assert set(flushes) <= {0, 1}
    assert postings <= len(waits) + 1
    assert postings * 2 <= tuples, (postings, tuples)
    assert waits and all(n == 0 for _, n in waits), waits
    assert {name for name, _ in waits} == set(WAITS)
    assert engine._outbox == {} and engine._outbox_owner is None


@pytest.mark.filterwarnings("ignore::pytest.PytestUnhandledThreadExceptionWarning")
async def test_a_crash_ends_every_stream_and_leaves_nothing_behind(monkeypatch):
    """The program fails with three requests decoding and one waiting:
    ``_fail_all``'s errors go through the outbox and out with the
    loop's last posting; no stream hangs."""
    engine = _engine(max_decode_slots=3)
    calls = 0
    orig = engine._run_program

    def run_program(fn, *args):
        nonlocal calls
        calls += 1
        if calls == 6:
            raise RuntimeError("injected device failure")
        return orig(fn, *args)

    monkeypatch.setattr(engine, "_run_program", run_program)
    engine.start()
    try:
        streams = await asyncio.wait_for(
            asyncio.gather(*[
                _stream(engine, range(10 + 7 * i, 40 + 9 * i), 64) for i in range(4)
            ]),
            timeout=120,
        )
        engine._thread.join(timeout=30)
        assert engine.loop_crashed and not engine._thread.is_alive()
        assert engine._outbox == {} and engine._outbox_owner is None
    finally:
        engine.stop()
    for items in streams:
        _ends_well(items, "error")
    assert any(_tokens(items) for items in streams)


async def test_stop_ends_the_loop_with_an_empty_outbox_and_later_emits_post_at_once():
    """``stop()`` with a request mid-decode: what the loop had emitted is
    posted at its exit; an emit made after the thread is joined, off the
    loop thread, goes out by itself."""
    engine = _engine()
    engine.start()
    b = BackendInput(token_ids=list(range(20, 40)))
    b.stop_conditions.max_tokens = 100
    b.stop_conditions.ignore_eos = True
    stream = await engine.generate(b.to_dict())
    first = await asyncio.wait_for(anext(aiter(stream)), timeout=120)
    assert first["token_ids"]
    seq = next(s for s in engine.sched.slots if s is not None)
    await asyncio.to_thread(engine.stop)
    assert engine._thread is None
    assert engine._outbox == {} and engine._outbox_owner is None
    postings = _counter("dynamo_emit_postings_total")
    items = _counter("dynamo_emit_items_total")
    seq.emit([], FinishReason.ERROR)  # this thread is not the loop's: as a teardown's
    assert engine._outbox == {}
    assert _counter("dynamo_emit_postings_total") == postings + 1
    assert _counter("dynamo_emit_items_total") == items + 1
    rest = await asyncio.wait_for(_drain(stream), timeout=30)
    assert rest[-1]["finish_reason"] == "error"


async def _drain(stream) -> list[dict]:
    return [item async for item in stream]


# --------------------------------------------- (4) when a posting is made
async def test_a_dispatchs_tuples_are_posted_after_the_next_launch(monkeypatch):
    """Mixed -> mixed: one request decodes while another's long prompt
    prefills chunk by chunk, so each dispatch is consumed in the
    iteration that launched it. What dispatch k's consume emitted waits
    through the next assemble and launch and is posted before the wait
    of dispatch k+1's sync: exactly one launch lies between."""
    engine = _engine(max_decode_slots=2, max_model_len=256, num_pages=96)
    log: list[tuple] = []
    orig_run, orig_post = engine._run_program, engine._post
    orig_flush, orig_mixed = engine._flush_outbox, engine._consume_mixed

    def run_program(fn, *args):
        log.append(("launch",))
        return orig_run(fn, *args)

    def post(loop, put, item):
        log.append(("emit",))
        orig_post(loop, put, item)

    def flush():
        if engine._outbox:
            log.append(("post", sum(map(len, engine._outbox.values()))))
        orig_flush()

    def consume_mixed(pending):
        log.append(("consume_mixed",))
        orig_mixed(pending)

    monkeypatch.setattr(engine, "_run_program", run_program)
    monkeypatch.setattr(engine, "_post", post)
    monkeypatch.setattr(engine, "_flush_outbox", flush)
    monkeypatch.setattr(engine, "_consume_mixed", consume_mixed)
    engine.start()
    try:
        decoding = asyncio.ensure_future(_stream(engine, range(20, 30), 40))
        while not any(e[0] == "emit" for e in log):
            await asyncio.sleep(0.01)
        late = await _stream(engine, range(30, 190), 4)  # ten chunks of 16
        first = await decoding
    finally:
        engine.stop()
    _ends_well(first)
    _ends_well(late)
    # Runs of mixed dispatches back to back: launch, consume_mixed
    # (post of what the one before emitted, then this one's emits).
    kinds = [e[0] for e in log]
    pairs = 0
    for i, kind in enumerate(kinds):
        if kind != "consume_mixed" or kinds[i - 1] != "launch":
            continue
        # This dispatch's emits, up to the next posting.
        j = i + 1
        if j < len(kinds) and kinds[j] == "post":
            j += 1  # the one before's
        emits = 0
        while j < len(kinds) and kinds[j] == "emit":
            emits, j = emits + 1, j + 1
        if not emits or kinds[j : j + 3] != ["launch", "consume_mixed", "post"]:
            continue  # the run of mixed dispatches ended here
        assert log[j + 2] == ("post", emits)
        pairs += 1
    assert pairs >= 5, kinds


# ------------------------------------------------ (5) two asyncio loops
def test_two_asyncio_loops_get_one_posting_each():
    """The outbox as the loop thread sees it, driven by hand: tuples for
    two asyncio loops interleaved, one flush, one callback a loop, each
    loop's tuples in emission order; a closed loop's are dropped."""
    engine = _engine()
    loops = [asyncio.new_event_loop() for _ in range(3)]
    calls = {id(loop): 0 for loop in loops}
    for loop in loops:
        orig = loop.call_soon_threadsafe

        def counted(cb, *args, _loop=loop, _orig=orig):
            calls[id(_loop)] += 1
            return _orig(cb, *args)

        loop.call_soon_threadsafe = counted
    got: dict[int, list] = {0: [], 1: [], 2: []}
    postings0 = _counter("dynamo_emit_postings_total")
    items0 = _counter("dynamo_emit_items_total")
    engine._outbox_owner = threading.get_ident()
    try:
        for n in range(10):
            engine._post(loops[n % 2], got[n % 2].append, n)
        engine._post(loops[2], got[2].append, 99)
        loops[2].close()
        assert sum(calls.values()) == 0
        engine._flush_outbox()
    finally:
        engine._outbox_owner = None
    assert engine._outbox == {}
    assert [calls[id(loop)] for loop in loops[:2]] == [1, 1]
    for loop in loops[:2]:
        loop.call_soon(loop.stop)
        loop.run_forever()
        loop.close()
    assert got == {0: [0, 2, 4, 6, 8], 1: [1, 3, 5, 7, 9], 2: []}
    assert _counter("dynamo_emit_postings_total") - postings0 == 3
    assert _counter("dynamo_emit_items_total") - items0 == 11


async def test_requests_from_two_asyncio_loops_both_end():
    """A second asyncio loop on its own thread asks the same engine
    while this one does: every stream ends, its finish last."""
    engine = _engine()
    engine.start()
    try:
        other = asyncio.to_thread(
            asyncio.run, _stream(engine, range(40, 70), 12)
        )
        mine, theirs = await asyncio.gather(
            _stream(engine, range(10, 35), 12), other
        )
    finally:
        engine.stop()
    for items in (mine, theirs):
        _ends_well(items)
        assert len(_tokens(items)) == 12


# --------------------------------------- (6) booked to ``consume.emit``
async def test_a_posting_is_a_stretch_of_consume_emit_before_the_wait(monkeypatch):
    """Every posting opens phase ``consume`` with part ``emit`` inside
    it, outside any other phase, and the next phase is the wait it was
    made for; the parts still sum to their phases
    (tests/test_engine_phases.py holds the same on /metrics)."""
    events: list[str] = []
    orig_phase, orig_part = DispatchProfiler.phase, DispatchProfiler.part

    class Recorded:
        def __init__(self, inner, label):
            self.inner, self.label = inner, label

        def __enter__(self):
            events.append("+" + self.label)
            return self.inner.__enter__()

        def __exit__(self, *exc):
            events.append("-" + self.label)
            return self.inner.__exit__(*exc)

    monkeypatch.setattr(
        DispatchProfiler, "phase",
        lambda self, name: Recorded(orig_phase(self, name), name),
    )
    monkeypatch.setattr(
        DispatchProfiler, "part",
        lambda self, phase, name: Recorded(
            orig_part(self, phase, name), f"{phase}.{name}"
        ),
    )
    engine = _engine()
    orig_flush = engine._flush_outbox

    def flush():
        posting = bool(engine._outbox)
        events.append("flush" if posting else "flush-empty")
        orig_flush()
        if posting:
            events.append("flushed")

    monkeypatch.setattr(engine, "_flush_outbox", flush)
    engine.start()
    try:
        await asyncio.gather(*[
            _stream(engine, range(10 + 7 * i, 40 + 9 * i), 12) for i in range(3)
        ])
    finally:
        engine.stop()
    stretch = ["flush", "+consume", "+consume.emit", "-consume.emit", "-consume", "flushed"]
    seen = 0
    for i, e in enumerate(events):
        if e != "flush":
            continue
        assert events[i : i + 6] == stretch, events[i : i + 8]
        after = events[i + 6] if i + 6 < len(events) else "end"
        assert after in ("+sync", "+park", "end"), after
        seen += 1
    assert seen >= 3
    # No emission is booked anywhere but under ``consume``.
    wall = engine.profiler.phase_seconds()
    parts = engine.profiler.part_seconds()
    assert parts["consume", "emit"] > 0.0
    assert sum(s for (ph, _), s in parts.items() if ph == "consume") == pytest.approx(
        wall["consume"], rel=1e-6
    )
