"""Engine flight recorder + watchdog (docs/observability.md "Engine
flight recorder & watchdog").

Acceptance path: a seeded, chaos-injected engine stall (two sequences
whose KV growth drains a tiny pool with preemption disabled — the
permanent-wedge shape PR 5's fixes made otherwise unreachable) must
produce EXACTLY ONE flight dump whose event sequence is identical
across same-seed runs, and ``llmctl flight`` must render it into a
per-slot timeline naming the stalled slots. Plus: no false positive
under a slow-but-progressing workload, watchdog/ring units, and the
dump render.

Determinism protocol (the PR-3/PR-5 gotcha applies: admission of
concurrent submissions is an OS race): the stall phase pre-queues its
sequences into the submit queue while the engine is stopped and clears
the ring, so the loop drains them in one deterministic pass; only the
per-event wall timestamp ``t`` differs between runs and is popped
before comparison.
"""

import asyncio
import json
import os
import time

import pytest

from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
from dynamo_exp_tpu.engine.scheduler import Sequence
from dynamo_exp_tpu.models import TINY
from dynamo_exp_tpu.parallel import single_device_mesh
from dynamo_exp_tpu.protocols.common import BackendInput
from dynamo_exp_tpu.telemetry.flight import (
    HOST_KINDS,
    FlightRecorder,
    Watchdog,
    load_dumps,
    render_flight,
)

pytestmark = pytest.mark.chaos

PS = 8
SEEDS = tuple(
    int(s) for s in os.environ.get("CHAOS_SEEDS", "7,21,1337").split(",")
)[:1]


# ------------------------------------------------------------------- units
def test_ring_bounds_and_order():
    fr = FlightRecorder(capacity=16)
    for i in range(20):
        fr.record("e", i=i)
    evs = fr.snapshot()
    assert len(evs) == 16
    assert [e["i"] for e in evs] == list(range(4, 20))
    assert [e["seq"] for e in evs] == list(range(4, 20))
    fr.clear()
    assert fr.snapshot() == [] and fr.seq == 0


def test_dump_and_load_roundtrip(tmp_path):
    fr = FlightRecorder()
    fr.record("admit", req="r1", slot=0)
    fr.record("stall_start", req="r1", slot=0)
    path = str(tmp_path / "f.jsonl")
    fr.dump(path, "watchdog", snapshot={"waiting": 2, "slots": []})
    fr.dump(path, "sigusr1")  # second block appends
    blocks = load_dumps(path)
    assert len(blocks) == 2
    assert blocks[0]["header"]["reason"] == "watchdog"
    assert [e["kind"] for e in blocks[0]["events"]] == [
        "admit", "stall_start",
    ]
    assert blocks[0]["snapshot"]["waiting"] == 2
    assert blocks[1]["snapshot"] is None


def test_render_names_stalled_slot():
    block = {
        "header": {"reason": "watchdog", "t": 10.0},
        "events": [
            {"seq": 0, "t": 10.0, "kind": "admit", "req": "req-a", "slot": 1},
            {"seq": 1, "t": 10.5, "kind": "stall_start", "req": "req-a",
             "slot": 1},
            {"seq": 2, "t": 10.2, "kind": "dispatch", "dispatch": "decode",
             "rows": 1},
        ],
        "snapshot": {
            "t": 11.0,
            "waiting": 1,
            "slots": [
                {"slot": 1, "req": "req-a", "state": "active",
                 "generated": 5, "pages": 4, "stalled": True},
            ],
        },
    }
    out = render_flight(block)
    assert "reason=watchdog" in out
    assert "slot 1" in out and "req-a" in out
    assert "STALLED" in out
    assert "stall_start" in out and "dispatch=decode" in out
    assert "waiting=1" in out


def test_watchdog_fires_once_per_stall_episode():
    progress = {"n": 0}
    busy = {"v": True}
    dumps = []
    wd = Watchdog(
        stall_s=0.1,
        progress=lambda: progress["n"],
        has_work=lambda: busy["v"],
        dump_fn=dumps.append,
        poll_s=0.02,
    )
    wd.start()
    try:
        # Progressing: no dump.
        for _ in range(8):
            progress["n"] += 1
            time.sleep(0.03)
        assert dumps == []
        # Frozen with work queued: exactly one dump per episode.
        time.sleep(0.3)
        assert dumps == ["watchdog"]
        time.sleep(0.2)
        assert dumps == ["watchdog"]
        # Progress resumes, then freezes again: a second episode.
        progress["n"] += 1
        time.sleep(0.05)
        time.sleep(0.3)
        assert dumps == ["watchdog", "watchdog"]
    finally:
        wd.stop()


def test_watchdog_idle_never_dumps():
    dumps = []
    wd = Watchdog(
        stall_s=0.05,
        progress=lambda: 0,
        has_work=lambda: False,  # frozen but idle: nothing is wedged
        dump_fn=dumps.append,
        poll_s=0.01,
    )
    wd.start()
    time.sleep(0.2)
    wd.stop()
    assert dumps == []


# ------------------------------------------------------- engine stall chaos
def _stall_cfg(dump_path: str, **over) -> EngineConfig:
    base = dict(
        model=TINY,
        max_decode_slots=2,
        page_size=PS,
        num_pages=8,  # 64 tokens total: two growing rows drain it
        max_model_len=128,
        eos_token_ids=[],
        kv_dtype="float32",
        decode_window=4,
        preempt_stall_grace_s=-1.0,  # chaos: preemption disabled -> wedge
        watchdog_stall_s=-1.0,  # enabled only after warmup
        flight_dump_path=dump_path,
    )
    return EngineConfig(**(base | over))


def _stall_seq(rid: str, prompt: list[int], max_tokens: int) -> Sequence:
    b = BackendInput(token_ids=list(prompt))
    b.stop_conditions.max_tokens = max_tokens
    b.stop_conditions.ignore_eos = True
    return Sequence(
        request_id=rid,
        prompt=list(prompt),
        stop=b,
        emit=lambda *a, **k: None,
        is_cancelled=lambda: False,
        submitted_at=time.time(),
        sample_seed=7,
    )


def _warmup(engine: TPUEngine, seed: int) -> None:
    """Compile every variant the stall phase touches (prefill rows 1+2,
    decode rows 1+2 at the small page buckets) so no multi-second
    compile pauses the loop once the watchdog is armed. Takes the engine
    stopped and leaves it stopped. Each round's sequences are in the
    submit queue before the loop starts, as the stall phase's are: its
    first pass admits them together, so the two rows of the first round
    share their dispatches in every run. (Submitted to a running loop
    they shared one or took two by an OS race, and the stall phase's
    admission records name the dispatch that wrote the pages it reuses.)"""
    import threading

    import numpy as np

    rs = np.random.RandomState(seed)
    for n_rows in (2, 1):
        finished = []
        for i in range(n_rows):
            prompt = [int(t) for t in rs.randint(10, TINY.vocab_size - 10, size=16)]
            seq = _stall_seq(f"warm-{n_rows}-{i}", prompt, max_tokens=8)
            done = threading.Event()
            seq.emit = lambda _toks, reason, _lp=None, done=done: (
                done.set() if reason is not None else None
            )
            finished.append(done)
            engine._submit_q.put(seq)
        engine.start()
        try:
            for done in finished:
                assert done.wait(timeout=120), "warm-up request never finished"
        finally:
            engine.stop()


def _run_stall_once(tmp_path, seed: int, tag: str) -> tuple[list, dict, str]:
    """One full seeded stall episode; returns (event lines sans wall
    time, snapshot, dump path)."""
    import numpy as np

    dump_path = str(tmp_path / f"flight_{tag}.jsonl")
    engine = TPUEngine(
        _stall_cfg(dump_path), mesh=single_device_mesh(), seed=0
    )
    _warmup(engine, seed)
    # Re-arm: pre-queue the stall workload while the loop is down, so
    # the first iteration drains and admits it deterministically.
    engine.flight.clear()
    engine.cfg.watchdog_stall_s = 1.5
    rs = np.random.RandomState(seed)
    for rid in ("req-a", "req-b"):
        prompt = [int(t) for t in rs.randint(10, TINY.vocab_size - 10, size=16)]
        engine._submit_q.put(_stall_seq(rid, prompt, max_tokens=100))
    engine.start()
    try:
        deadline = time.monotonic() + 30
        while not os.path.exists(dump_path) and time.monotonic() < deadline:
            time.sleep(0.05)
        # Exactly one dump: give a second stall period a chance to
        # (wrongly) fire again, then read the file once.
        time.sleep(2.0)
        assert os.path.exists(dump_path), "watchdog never dumped"
    finally:
        engine.stop()
    blocks = load_dumps(dump_path)
    assert len(blocks) == 1, f"expected exactly one dump, got {len(blocks)}"
    events = []
    for ev in blocks[0]["events"]:
        if ev["kind"] in HOST_KINDS:
            continue  # what the host did to the loop (a pause, a stall): no run's twice
        d = dict(ev)
        d.pop("t", None)  # the only cross-run-variable field
        events.append(d)
    return events, blocks[0]["snapshot"], dump_path


@pytest.mark.nightly
@pytest.mark.parametrize("seed", SEEDS)
def test_injected_stall_dumps_once_and_is_seed_deterministic(
    tmp_path, seed, capsys
):
    events1, snap1, dump_path = _run_stall_once(tmp_path, seed, "run1")
    events2, snap2, _ = _run_stall_once(tmp_path, seed, "run2")

    # The wedge really is the KV stall: both rows hard-stalled, work
    # queued, nothing moving.
    stalled = [s for s in snap1["slots"] if s["stalled"]]
    assert len(stalled) == 2
    kinds = [e["kind"] for e in events1]
    assert "admit" in kinds and "stall_start" in kinds
    assert any(e["kind"] == "dispatch" for e in events1)

    # Bit-identical event sequence across same-seed runs (wall time
    # popped; everything else — order, kinds, payloads, seq — equal).
    assert json.dumps(events1) == json.dumps(events2)
    # Snapshot agrees on the deterministic scheduler state too.
    assert snap1["slots"] == snap2["slots"]
    assert snap1["waiting"] == snap2["waiting"]

    # llmctl flight renders a per-slot timeline naming the stalled slot.
    from dynamo_exp_tpu.llmctl import main as llmctl_main

    assert llmctl_main(["flight", dump_path]) == 0
    out = capsys.readouterr().out
    assert "reason=watchdog" in out
    assert "req-a" in out and "req-b" in out
    assert "STALLED" in out
    for s in stalled:
        assert f"slot {s['slot']}" in out


@pytest.mark.nightly
def test_no_false_positive_under_slow_but_progressing_workload(tmp_path):
    """A workload that keeps making progress — however slowly — must
    never trigger the watchdog, even with a tight stall threshold
    (warmup happens before the watchdog is armed, so compiles can't
    masquerade as stalls)."""
    dump_path = str(tmp_path / "flight_fp.jsonl")
    engine = TPUEngine(
        _stall_cfg(dump_path, num_pages=64, preempt_stall_grace_s=0.5),
        mesh=single_device_mesh(),
        seed=0,
    )
    _warmup(engine, 3)
    engine.cfg.watchdog_stall_s = 0.6
    engine.start()
    try:

        async def trickle():
            import numpy as np

            rs = np.random.RandomState(1)
            for _ in range(3):
                b = BackendInput(
                    token_ids=[
                        int(t)
                        for t in rs.randint(10, TINY.vocab_size - 10, size=16)
                    ]
                )
                b.stop_conditions.max_tokens = 48
                b.stop_conditions.ignore_eos = True
                stream = await engine.generate(b.to_dict())
                async for _ in stream:
                    pass
                await asyncio.sleep(0.15)

        asyncio.run(trickle())
        time.sleep(0.8)  # one more full stall window while idle
    finally:
        engine.stop()
    assert not os.path.exists(dump_path), "watchdog false positive"


def test_llmctl_flight_list_and_errors(tmp_path, capsys):
    from dynamo_exp_tpu.llmctl import main as llmctl_main

    missing = str(tmp_path / "nope.jsonl")
    assert llmctl_main(["flight", missing]) == 2
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert llmctl_main(["flight", str(empty)]) == 1

    fr = FlightRecorder()
    fr.record("admit", req="r", slot=0)
    path = str(tmp_path / "ok.jsonl")
    fr.dump(path, "sigusr1")
    fr.dump(path, "crash")
    assert llmctl_main(["flight", path, "--list"]) == 0
    out = capsys.readouterr().out
    assert "reason=sigusr1" in out and "reason=crash" in out
    assert llmctl_main(["flight", path, "--index", "5"]) == 1
