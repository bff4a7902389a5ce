"""What the device waits behind off the engine loop's thread
(docs/observability.md "Off the loop thread"): the HTTP thread's chunk
clock, the collector hook, the stall witness and the launch histogram,
each against something measured beside it."""

import asyncio
import gc
import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from dynamo_exp_tpu.engines.echo import EchoEngineCore
from dynamo_exp_tpu.http import HttpService, build_pipeline_engine
from dynamo_exp_tpu.model_card import ModelDeploymentCard
from dynamo_exp_tpu.telemetry import Telemetry
from dynamo_exp_tpu.telemetry import dispatch as dispatch_mod
from dynamo_exp_tpu.telemetry.dispatch import HTTP_PARTS, HttpChunkClock
from dynamo_exp_tpu.telemetry.flight import (
    HOST_KINDS,
    STALL_CAUSES,
    FlightRecorder,
    HostWitness,
)


def value(tel: Telemetry, name: str, **labels) -> float:
    v = tel.registry.get_sample_value(name, labels)
    return 0.0 if v is None else v


# ------------------------------------------------------------- the chunk clock
class StampedClock(HttpChunkClock):
    """The clock, with the wall time of every open stretch taken beside
    it: from the call that opened it to the call that closed it."""

    def __init__(self, tel):
        super().__init__(tel)
        self.outer = 0.0
        self._opened_at = None

    def _open(self, part):
        self._opened_at = time.monotonic()
        super()._open(part)

    def idle(self):
        was_open = self._part is not None
        super().idle()
        if was_open:
            self.outer += time.monotonic() - self._opened_at


def test_parts_sum_to_the_stretches_wall_time():
    tel = Telemetry()
    clock = StampedClock(tel)
    for _ in range(5):
        clock.taken()
        clock.taken()  # the backend above the engine's generator: same chunk
        time.sleep(0.004)
        clock.lap("frame")
        time.sleep(0.002)
        clock.lap("write")
        time.sleep(0.001)
        clock.idle()
        clock.idle()  # the generator below, back at its queue
        time.sleep(0.003)  # waiting for the next tuple: nobody's
    clock.flush()
    parts = {
        p: value(tel, "dynamo_http_chunk_seconds_total", part=p) for p in HTTP_PARTS
    }
    assert value(tel, "dynamo_http_chunks_total") == 5
    assert parts["detokenize"] >= 5 * 0.004
    assert parts["frame"] >= 5 * 0.002 and parts["write"] >= 5 * 0.001
    assert parts["detokenize"] > parts["frame"] > parts["write"]
    # The clock's own reads lie inside the outer stamps: never more, and
    # less by microseconds a stretch.
    assert sum(parts.values()) <= clock.outer
    assert sum(parts.values()) == pytest.approx(clock.outer, abs=5 * 200e-6)
    cpu = sum(
        value(tel, "dynamo_http_chunk_cpu_seconds_total", part=p) for p in HTTP_PARTS
    )
    assert 0.0 <= cpu <= sum(parts.values())  # it slept: wall without CPU


def test_a_lap_with_nothing_open_opens_and_a_second_flush_adds_nothing():
    tel = Telemetry()
    clock = HttpChunkClock(tel)
    clock.idle()  # nothing open: nothing booked
    clock.lap("frame")  # an engine without the generators below
    clock.lap("write")
    clock.idle()
    clock.flush()
    clock.flush()
    assert value(tel, "dynamo_http_chunks_total") == 1
    assert value(tel, "dynamo_http_chunk_seconds_total", part="detokenize") == 0.0
    assert value(tel, "dynamo_http_chunk_seconds_total", part="frame") > 0.0
    with pytest.raises(KeyError):
        clock.lap("compress")
        clock.idle()


@pytest.mark.asyncio
async def test_streamed_chunks_are_clocked_through_the_whole_pipeline(
    tiny_model_dir, monkeypatch
):
    """HTTP -> preprocessor -> backend -> echo core, streaming: every
    part is booked, the parts sum to the wall time the handler had a
    chunk in hand, and that is inside the request's own wall time."""
    tel = Telemetry()
    clock = StampedClock(tel)
    monkeypatch.setattr(dispatch_mod, "_HTTP_CLOCK", clock)
    mdc = ModelDeploymentCard.from_local_path(tiny_model_dir, display_name="tiny")
    svc = HttpService()
    svc.manager.add_chat_model("tiny", build_pipeline_engine(mdc, EchoEngineCore()))
    client = TestClient(TestServer(svc.app))
    await client.start_server()
    t0 = time.monotonic()
    r = await client.post(
        "/v1/chat/completions",
        json={
            "model": "tiny", "stream": True,
            "messages": [{"role": "user", "content": "the quick brown fox jumps"}],
        },
    )
    raw = (await r.read()).decode()
    request_wall = time.monotonic() - t0
    await client.close()
    frames = [
        json.loads(line[6:]) for line in raw.split("\n")
        if line.startswith("data: ") and line != "data: [DONE]"
    ]
    assert len(frames) > 3
    clock.flush()
    parts = {
        p: value(tel, "dynamo_http_chunk_seconds_total", part=p) for p in HTTP_PARTS
    }
    assert all(v > 0 for v in parts.values()), parts
    # One stretch a frame at least (a request's finish is a stretch too).
    assert value(tel, "dynamo_http_chunks_total") >= len(frames)
    assert sum(parts.values()) <= clock.outer <= request_wall
    assert sum(parts.values()) == pytest.approx(
        clock.outer, abs=value(tel, "dynamo_http_chunks_total") * 200e-6
    )


# ------------------------------------------------------------------ collections
def test_the_gc_hooks_seconds_are_a_forced_collections():
    tel = Telemetry()
    witness = HostWitness(tel)
    ring = FlightRecorder()
    handle = witness.attach(ring)
    try:
        # Something for the oldest generation to walk, so that the
        # collection is long against the clocks' own cost.
        keep = [[i, {"k": i}] for i in range(300_000)]
        gc.disable()  # no collection of its own between the two readings
        try:
            witness.publish()
            before = value(tel, "dynamo_gc_pause_seconds_total", generation="2")
            n_before = value(tel, "dynamo_gc_collections_total", generation="2")
            t0 = time.monotonic()
            gc.collect(2)
            outer = time.monotonic() - t0
        finally:
            gc.enable()
        witness.publish()
        booked = value(tel, "dynamo_gc_pause_seconds_total", generation="2") - before
        assert value(tel, "dynamo_gc_collections_total", generation="2") == n_before + 1
        assert 0.0 < booked <= outer
        assert booked == pytest.approx(outer, rel=0.35, abs=2e-3)
        assert value(tel, "dynamo_gc_pause_max_seconds") >= booked > 0
        notes = [e for e in ring.snapshot() if e["kind"] == "gc_pause"]
        assert notes and notes[-1]["generation"] == 2
        assert notes[-1]["thread"] == threading.current_thread().name
        assert notes[-1]["seconds"] == pytest.approx(booked, abs=1e-5)
        assert "seq" not in notes[-1] and notes[-1]["after"] == -1
        del keep
    finally:
        witness.detach(handle)
    assert witness._on_gc not in gc.callbacks
    # Every child series from the start: a window without one reads 0.
    for g in "012":
        assert tel.registry.get_sample_value(
            "dynamo_gc_pause_seconds_total", {"generation": g}
        ) is not None


def test_the_hook_takes_no_lock_and_the_witness_books_for_it():
    """A collection starts between two bytecodes of any thread, inside a
    ``with lock:`` too: the hook may only leave what it timed behind."""
    tel = Telemetry()
    witness = HostWitness(tel)
    ring = FlightRecorder()
    handle = witness.attach(ring)
    try:
        with ring._lock:  # a thread caught mid-record
            witness._on_gc("start", {"generation": 2})
            witness._on_gc("stop", {"generation": 2, "collected": 7})
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if any(e["kind"] == "gc_pause" for e in ring.snapshot()):
                break
            time.sleep(0.01)
        note = next(e for e in ring.snapshot() if e["kind"] == "gc_pause")
        assert note["collected"] == 7
        assert value(tel, "dynamo_gc_collections_total", generation="2") >= 1
    finally:
        witness.detach(handle)


# ----------------------------------------------------------------------- stalls
def stalls_of(ring) -> list[dict]:
    return [e for e in ring.snapshot() if e["kind"] == "host_stall"]


def test_a_thread_that_holds_the_gil_is_a_busy_stall():
    # A machine under load may give the spin less CPU than wall time, and the
    # stall is then no GIL stall alone: spin again, longer (a longer hold
    # outlasts a short preemption), a bounded number of times. Never a skip: a
    # slow machine takes nothing from the count. Where no spin got three
    # quarters of its wall time (six busy workers gave 0.70 at all four holds,
    # PR 55's own run), the stall is still held to everything below but its
    # cause, which is then held to the witness's OWN rule on its own two
    # clocks (``busy`` iff the process's CPU clock moved half the time lost).
    readings = []
    for hold in (0.3, 0.6, 1.2, 2.4):
        tel = Telemetry()
        witness = HostWitness(tel)
        ring = FlightRecorder()
        ring.record("dispatch", dispatch="ragged", bucket=4)
        handle = witness.attach(
            ring, lambda: {"dispatch_seq": 0, "program": "window", "bucket": 4}
        )
        try:
            time.sleep(0.1)  # the witness is asleep, in step
            spun = ran = 0.0
            n = 4_000_000
            while spun < hold:  # one call that keeps the GIL for ``hold`` s
                n *= 2
                t0, c0 = time.monotonic(), time.process_time()
                sum(range(n))
                spun, ran = time.monotonic() - t0, time.process_time() - c0
            time.sleep(0.1)
        finally:
            witness.detach(handle)
        stalls = stalls_of(ring)
        assert stalls, f"a {hold} s hold of the GIL went unseen"
        worst = max(stalls, key=lambda e: e["seconds"])
        readings.append((round(ran, 2), round(spun, 2)))
        if ran >= 0.75 * spun:
            break
    margin = worst["process_cpu_s"] - 0.5 * worst["seconds"]  # both rounded to 1e-6
    assert worst["cause"] == ("busy" if margin >= 0 else "descheduled") or abs(margin) < 1e-5, (
        worst, readings,
    )
    if ran >= 0.75 * spun:  # the spin had its time: a GIL stall alone
        assert worst["cause"] == "busy", (worst, readings)
    cause = worst["cause"]
    assert 0.1 < worst["seconds"] <= spun + 0.5
    # Which dispatch it fell in: the engine's context, and the ring's place.
    assert (worst["dispatch_seq"], worst["program"], worst["after"]) == (0, "window", 0)
    assert value(tel, "dynamo_host_stall_seconds_total", cause=cause) >= worst["seconds"] - 1e-6
    assert value(tel, "dynamo_host_stalls_total", cause=cause) >= 1


@pytest.mark.skipif(not hasattr(signal, "SIGSTOP"), reason="no job control")
def test_a_stopped_process_is_a_descheduled_stall():
    tel = Telemetry()
    witness = HostWitness(tel)
    ring = FlightRecorder()
    handle = witness.attach(ring)
    try:
        time.sleep(0.1)
        # Another process stops this one for 300 ms: nothing here runs,
        # the process's CPU clock stands still.
        stopper = subprocess.Popen([
            sys.executable, "-c",
            "import os, signal, sys, time\n"
            "pid = int(sys.argv[1])\n"
            "os.kill(pid, signal.SIGSTOP)\n"
            "time.sleep(0.3)\n"
            "os.kill(pid, signal.SIGCONT)\n",
            str(os.getpid()),
        ])
        assert stopper.wait(timeout=30) == 0
        time.sleep(0.15)
    finally:
        witness.detach(handle)
    stalls = stalls_of(ring)
    assert stalls, "300 ms of SIGSTOP went unseen"
    worst = max(stalls, key=lambda e: e["seconds"])
    assert worst["cause"] == "descheduled", worst
    assert worst["seconds"] > 0.15
    assert value(tel, "dynamo_host_stall_seconds_total", cause="descheduled") > 0.15


@pytest.mark.parametrize(
    "late_s, cpu_s, cause",
    [
        (0.30, 0.29, "busy"),  # the process ran throughout
        (0.30, 0.15, "busy"),  # for half of it: another thread had the GIL
        (0.30, 0.149, "descheduled"),
        (2.00, 0.01, "descheduled"),  # PR 44's machine stall
    ],
)
def test_the_cause_is_read_off_the_process_cpu_clock(late_s, cpu_s, cause):
    tel = Telemetry()
    witness = HostWitness(tel)
    ring = FlightRecorder()
    witness._sinks[1] = (ring, None)  # a ring and no thread: the step alone
    assert witness.stalled(late_s, cpu_s) == cause
    other = next(c for c in STALL_CAUSES if c != cause)
    assert value(tel, "dynamo_host_stall_seconds_total", cause=cause) == late_s
    assert value(tel, "dynamo_host_stalls_total", cause=cause) == 1
    assert value(tel, "dynamo_host_stall_seconds_total", cause=other) == 0.0
    (note,) = stalls_of(ring)
    assert note["seconds"] == late_s and note["cause"] == cause
    assert note["start"] == pytest.approx(time.time() - late_s, abs=1.0)


def test_notes_take_no_sequence_number():
    """The loop's events keep the numbers a same-seed run gives them,
    whatever the host did in between."""
    ring = FlightRecorder(capacity=16)
    ring.record("admit", req="a")
    ring.note("host_stall", cause="busy", seconds=0.2)
    ring.record("dispatch", bucket=1)
    ring.note("gc_pause", generation=2, seconds=0.1, thread="t")
    evs = ring.snapshot()
    assert [e["kind"] for e in evs] == ["admit", "host_stall", "dispatch", "gc_pause"]
    assert [e.get("seq") for e in evs] == [0, None, 1, None]
    assert [e.get("after") for e in evs] == [None, 0, None, 1]
    assert ring.seq == 2
    assert {e["kind"] for e in evs if "seq" not in e} == set(HOST_KINDS)
    for _ in range(40):  # a note is evicted like any entry
        ring.record("consume")
    assert all("seq" in e for e in ring.snapshot())


def test_one_witness_serves_every_engine_of_a_process():
    witness = HostWitness(Telemetry())
    a, b = FlightRecorder(), FlightRecorder()
    ha, hb = witness.attach(a), witness.attach(b)
    thread = witness._thread
    assert gc.callbacks.count(witness._on_gc) == 1 and thread.is_alive()
    witness.stalled(0.2, 0.0)
    assert len(stalls_of(a)) == len(stalls_of(b)) == 1
    witness.detach(ha)
    assert witness._thread is thread and thread.is_alive()
    witness.detach(hb)
    assert not thread.is_alive() and witness._on_gc not in gc.callbacks
    witness.stalled(0.2, 0.0)  # nobody attached: counted, noted nowhere
    assert len(stalls_of(b)) == 1


# ------------------------------------------------------------ inside an engine
def test_a_forced_stall_of_a_running_engine_names_the_dispatch_it_fell_in():
    """The acceptance run without a scratch wrapper: the lost seconds on
    ``dynamo_host_stall_seconds_total``, the dispatch in the ring, the
    launch histogram counting every ragged launch."""
    from dynamo_exp_tpu.telemetry import get_telemetry
    from dynamo_exp_tpu.telemetry.flight import host_witness

    from .test_decode_compaction import collect, make_engine

    tel = get_telemetry()

    def launches() -> float:
        return sum(
            value(tel, "dynamo_launch_seconds_count", program=p)
            for p in ("window", "mixed", "block")
        )

    eng = make_engine(max_decode_slots=2)
    n0 = launches()
    stalled0 = value(tel, "dynamo_host_stall_seconds_total", cause="descheduled")
    eng.start()
    try:
        assert host_witness()._thread is not None
        tokens, _ = asyncio.run(collect(eng, [5, 9, 17, 3, 11], 12))
        assert len(tokens) == 12
        dispatches = [e for e in eng.flight.snapshot() if e["kind"] == "dispatch"]
        assert launches() - n0 == len(dispatches) > 0
        assert value(tel, "dynamo_launch_seconds_sum", program="window") > 0
        host_witness().stalled(2.0, 0.01)  # the machine stood still for 2 s
        note = [e for e in eng.flight.snapshot() if e["kind"] == "host_stall"][-1]
        assert note["cause"] == "descheduled" and note["seconds"] == 2.0
        assert note["dispatch_seq"] == dispatches[-1]["seq"]
        assert (note["program"], note["bucket"]) == ("window", dispatches[-1]["bucket"])
        assert (
            value(tel, "dynamo_host_stall_seconds_total", cause="descheduled")
            == stalled0 + 2.0
        )
    finally:
        eng.stop()
    assert eng._witness_handle is None
