"""What an asking read and ran, in the tracing the engine has (PERF.md,
PR 27): ragged dispatches counted by their whole variant key, a routed
model's live and padding rows, and the flight recorder's admission
record of every page with how it was obtained and which dispatch wrote
it. All host counts: nothing here reads the device."""

import asyncio
import time

import pytest

from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
from dynamo_exp_tpu.models import TINY, TINY_MOE
from dynamo_exp_tpu.parallel import single_device_mesh
from dynamo_exp_tpu.protocols.common import BackendInput
from dynamo_exp_tpu.telemetry import get_telemetry

PS = 8


def _engine(model):
    cfg = EngineConfig(
        model=model, max_decode_slots=2, page_size=PS, num_pages=64,
        max_model_len=128, eos_token_ids=[],
    )
    return TPUEngine(cfg, mesh=single_device_mesh(), seed=0)


async def _ask(engine, prompt, n):
    b = BackendInput(token_ids=prompt)
    b.stop_conditions.max_tokens = n
    b.stop_conditions.ignore_eos = True
    stream = await engine.generate(b.to_dict())
    out = []
    async for item in stream:
        out += item.get("token_ids", [])
    return out


def _samples(name: str) -> dict:
    """{labels (sorted tuple): value} of one counter in /metrics' text."""
    out = {}
    for line in get_telemetry().render().decode().splitlines():
        if line.startswith(name + "{") or line.startswith(name + " "):
            head, value = line.rsplit(" ", 1)
            labels = head[len(name):].strip("{}")
            out[tuple(sorted(x for x in labels.split(",") if x))] = float(value)
    return out


def _delta(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items() if v != before.get(k, 0.0)}


async def _counted(name: str, before: dict, dispatches: int) -> dict:
    """What ``name`` grew by since ``before``, read once the asking's
    ``dispatches`` are all counted (a dispatch counts when it is
    launched, on the loop's thread): a bounded poll, not a sleep that a
    busy machine outlasts."""
    deadline = time.monotonic() + 10.0
    while True:
        grew = _delta(before, _samples(name))
        if sum(grew.values()) >= dispatches or time.monotonic() >= deadline:
            return grew
        await asyncio.sleep(0.01)


async def test_dispatches_are_counted_by_variant_key_and_a_routed_models_rows_by_kind():
    engine = _engine(TINY_MOE)
    engine.start()
    try:
        d0 = _samples("dynamo_ragged_dispatch_total")
        m0 = _samples("dynamo_moe_rows_total")
        prompt = list(range(5, 5 + 21))  # two full pages and a tail of five
        first = await _ask(engine, prompt, 9)
        d1 = await _counted("dynamo_ragged_dispatch_total", d0, 2)
        m1 = _delta(m0, _samples("dynamo_moe_rows_total"))
        mixed = {k: v for k, v in d1.items() if 'program="mixed"' in k}
        window = {k: v for k, v in d1.items() if 'program="window"' in k}
        # One prefill of 21 tokens at the 32 bucket, then windows of 8 at 1 row.
        assert list(mixed.values()) == [1.0] and 'nb="32"' in list(mixed)[0]
        assert sum(window.values()) == 1.0 and all('nb="1"' in k for k in window)
        for k in d1:
            assert {x.split("=")[0] for x in k} == {
                "program", "nb", "pages", "want_lp", "full_sampler", "chained"}
        assert m1[('kind="live"',)] == 21 + 8 and m1[('kind="pad"',)] == 11
        # The cached asking: one live token and 15 padding rows in its prefill.
        m2 = _samples("dynamo_moe_rows_total")
        again = await _ask(engine, prompt, 9)
        m3 = await _counted("dynamo_moe_rows_total", m2, 1 + 8 + 15)
        assert again == first
        assert m3[('kind="live"',)] == 1 + 8 and m3[('kind="pad"',)] == 15
    finally:
        engine.stop()


@pytest.mark.parametrize("model", [TINY, TINY_MOE], ids=["dense", "routed"])
async def test_every_models_slots_are_counted_by_program_and_kind(model):
    """``dynamo_ragged_slots_total``: live + pad is the token bucket of
    a mixed dispatch and the row bucket times the steps of a window."""
    engine = _engine(model)
    engine.start()
    try:
        s0 = _samples("dynamo_ragged_slots_total")
        d0 = _samples("dynamo_ragged_dispatch_total")
        await _ask(engine, list(range(5, 5 + 21)), 9)
        d1 = await _counted("dynamo_ragged_dispatch_total", d0, 2)
        s1 = _delta(s0, _samples("dynamo_ragged_slots_total"))
        # One prefill of 21 tokens at the 32 bucket; 8 more tokens from
        # one window of 8 steps at 1 row.
        assert s1 == {
            ('kind="live"', 'program="mixed"'): 21, ('kind="pad"', 'program="mixed"'): 11,
            ('kind="live"', 'program="window"'): 8,
        }
        for program, steps in (("mixed", 1), ("window", 8)):
            slots = sum(v for k, v in s1.items() if f'program="{program}"' in k)
            nb = sum(
                v * int(dict(x.split("=") for x in k)["nb"].strip('"'))
                for k, v in d1.items() if f'program="{program}"' in k
            )
            assert slots == nb * steps
    finally:
        engine.stop()


async def test_a_dense_model_counts_no_expert_rows():
    engine = _engine(TINY)
    engine.start()
    try:
        m0 = _samples("dynamo_moe_rows_total")
        await _ask(engine, [5, 9, 17, 3, 11], 3)
        await asyncio.sleep(0.1)
        assert _delta(m0, _samples("dynamo_moe_rows_total")) == {}
    finally:
        engine.stop()


async def test_the_flight_recorder_says_which_pages_an_admission_attached_and_who_wrote_them():
    engine = _engine(TINY_MOE)
    engine.start()
    try:
        prompt = list(range(5, 5 + 21))
        await _ask(engine, prompt, 6)
        await _ask(engine, prompt, 6)
        events = engine.flight.snapshot()
        cold, warm = [e for e in events if e["kind"] == "admit"]
        assert [p[1] for p in cold["pages"]] == ["fresh"] * 3 and cold["cached"] == 0
        assert all(p[2] == "" for p in cold["pages"])
        assert [p[1] for p in warm["pages"]] == ["matched", "matched", "tail"]
        assert warm["cached"] == 20
        assert [p[0] for p in warm["pages"]] == [p[0] for p in cold["pages"]]
        # Every attached page names the prefill that wrote it: the cold
        # asking's one dispatch of 21 tokens at the 32 bucket.
        for _pid, _how, writer in warm["pages"]:
            assert writer == "mixed:nb=32:computed=21:cached=0"
        dispatches = [e for e in events if e["kind"] == "dispatch" and e.get("dispatch") == "ragged"]
        assert all("pages" in e and "want_lp" in e for e in dispatches)
        # The tail's sole holder writes on in place: nothing was copied.
        assert not [e for e in events if e["kind"] == "cow"]
    finally:
        engine.stop()
