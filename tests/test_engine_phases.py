"""Engine phases and the device trace (docs/observability.md).

One instrumentation, read three ways: the loop's phases
(``DispatchProfiler.phase`` → ``dynamo_engine_phase_seconds_total`` and
``dyn/<phase>`` trace annotations), the ``program`` label of the
dispatch histograms, JAX's own compile events, prompt tokens by source,
the KV pools' geometry, and named scopes that are metadata only.
"""

import asyncio
import contextlib
import functools
import re
import time

import jax
import jax.numpy as jnp
import pytest
from prometheus_client import CollectorRegistry

from dynamo_exp_tpu.aot.lattice import resolve_ragged_key
from dynamo_exp_tpu.aot.warmup import variant_call_args
from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
from dynamo_exp_tpu.models import TINY, TINY_QWEN3
from dynamo_exp_tpu.parallel import single_device_mesh
from dynamo_exp_tpu.protocols.common import BackendInput
from dynamo_exp_tpu.telemetry import Telemetry, get_telemetry
from dynamo_exp_tpu.telemetry.dispatch import (
    NO_PROGRAM,
    OTHER_PART,
    PHASE_PARTS,
    PHASES,
    PROGRAMS,
    DispatchProfiler,
)

PS = 8


def _cfg(**over) -> EngineConfig:
    base = dict(
        model=TINY,
        max_decode_slots=2,
        page_size=PS,
        num_pages=64,
        max_model_len=128,
        eos_token_ids=[],
        kv_dtype="float32",
        decode_window=4,
    )
    return EngineConfig(**(base | over))


async def _generate(engine, prompt, max_tokens=8):
    b = BackendInput(token_ids=list(prompt))
    b.stop_conditions.max_tokens = max_tokens
    b.stop_conditions.ignore_eos = True
    stream = await engine.generate(b.to_dict())
    n = 0
    async for item in stream:
        n += len(item.get("token_ids", []))
    return n


def _samples(name: str) -> dict[tuple, float]:
    """``{sorted label items: value}`` of one sample name on the
    process-wide registry."""
    out = {}
    for family in get_telemetry().registry.collect():
        for s in family.samples:
            if s.name == name:
                out[tuple(sorted(s.labels.items()))] = s.value
    return out


def _total(name: str, **match) -> float:
    return sum(
        v for labels, v in _samples(name).items()
        if all(dict(labels).get(k) == val for k, val in match.items())
    )


# ------------------------------------------------------------------- units
def test_phase_adds_its_host_time_and_launch_rides_begin_end():
    tel = Telemetry(CollectorRegistry())
    prof = DispatchProfiler(tel)
    assert set(prof.phase_seconds()) == set(PHASES)
    with prof.phase("intake"):
        time.sleep(0.002)
    t0 = prof.begin("ragged", "window")
    time.sleep(0.002)
    t_disp = prof.end("ragged", t0)
    prof.consume("ragged", t_disp, "window")
    # A page move is not a launch: no program, no phase.
    prof.end("kv_move", prof.begin("kv_move"))
    got = prof.phase_seconds()
    assert got["intake"] >= 0.002 and got["launch"] >= 0.002
    assert sum(got.values()) == pytest.approx(got["intake"] + got["launch"])
    rendered = tel.render().decode()
    assert 'dynamo_engine_phase_seconds_total{phase="intake"}' in rendered
    assert 'dynamo_engine_phase_seconds_total{phase="launch"}' in rendered
    assert (
        'dynamo_dispatch_seconds_count{kind="ragged",program="window"} 1.0'
        in rendered
    )


def test_parts_of_a_phase_sum_to_it_and_other_takes_the_rest():
    tel = Telemetry(CollectorRegistry())
    prof = DispatchProfiler(tel)
    with prof.phase("consume"):
        time.sleep(0.002)  # no part's: other, with what add_parts leaves
        with prof.part("consume", "finish"):
            time.sleep(0.002)
        prof.add_parts("consume", tokens=0.0004, emit=0.0003)
    t0 = prof.begin("ragged", "mixed")
    with prof.part("launch", "h2d"):
        time.sleep(0.001)
    with prof.part("launch", "call"):
        time.sleep(0.001)
    prof.end("ragged", t0)
    with prof.phase("intake"):  # a phase without parts books none
        pass
    phases, parts = prof.phase_seconds(), prof.part_seconds()
    assert {ph for ph, _ in parts} == set(PHASE_PARTS)
    for phase, names in PHASE_PARTS.items():
        assert {p for ph, p in parts if ph == phase} == {*names, OTHER_PART}
        assert sum(
            s for (ph, _), s in parts.items() if ph == phase
        ) == pytest.approx(phases[phase], rel=1e-9)
    assert all(s >= 0.0 for s in parts.values())
    assert parts["consume", "finish"] >= 0.002
    assert parts["consume", "tokens"] == 0.0004
    assert parts["consume", "emit"] == 0.0003
    assert parts["consume", OTHER_PART] >= 0.001
    assert parts["consume", "pages"] == 0.0
    assert parts["launch", "h2d"] >= 0.001 and parts["launch", "call"] >= 0.001
    # A part is booked at its phase's exit, never before: one opened
    # and closed with the phase still open has not reached /metrics.
    with prof.phase("admit"):
        with prof.part("admit", "record"):
            pass
        assert prof.part_seconds()["admit", "record"] == 0.0
    assert prof.part_seconds()["admit", "record"] > 0.0
    rendered = tel.render().decode()
    assert (
        'dynamo_engine_phase_part_seconds_total{part="finish",phase="consume"}'
        in rendered
    )
    assert 'dynamo_engine_phase_cpu_seconds_total{phase="consume"}' in rendered


@pytest.mark.parametrize(
    "phase, part",
    [
        ("consume", "other"),  # what is left, not a part to open
        ("consume", "h2d"),  # another phase's
        ("launch", "transfer"),
        ("sync", "wait"),  # a phase without parts
    ],
)
def test_an_unknown_part_raises(phase, part):
    prof = DispatchProfiler(Telemetry(CollectorRegistry()))
    with pytest.raises(KeyError):
        prof.part(phase, part)
    with pytest.raises(KeyError):
        prof.add_parts(phase, **{part: 0.1})


def test_a_sleeping_phase_takes_wall_time_and_no_cpu_time():
    """The CPU counter's own control: a phase that sleeps reads its
    wall time and next to no CPU time; one that computes reads both."""
    prof = DispatchProfiler(Telemetry(CollectorRegistry()))
    with prof.phase("park"):
        time.sleep(0.05)
    with prof.phase("intake"):
        t_end = time.thread_time() + 0.02
        while time.thread_time() < t_end:
            pass
    wall, cpu = prof.phase_seconds(), prof.phase_cpu_seconds()
    assert wall["park"] >= 0.05 and cpu["park"] < 0.1 * wall["park"]
    assert cpu["intake"] >= 0.02 and wall["intake"] >= cpu["intake"] * 0.99
    assert set(cpu) == set(PHASES)


def test_jax_compile_listener_counts_a_forced_retrace():
    tel = Telemetry(CollectorRegistry())
    tel.watch_jax_compiles()
    tel.watch_jax_compiles()  # registered once

    def count(stage):
        return tel.jax_compile_events.labels(stage)._value.get()

    f = jax.jit(lambda x: x * 2 + 1)
    x3, x5 = jnp.ones(3), jnp.ones(5)  # making them compiles too: first
    f(x3).block_until_ready()
    first = {s: count(s) for s in ("trace", "lower", "backend_compile")}
    assert all(v >= 1 for v in first.values())
    f(x3).block_until_ready()  # cached: nothing is traced again
    assert {s: count(s) for s in first} == first
    f(x5).block_until_ready()  # a new shape forces a re-trace
    assert count("trace") > first["trace"]  # the jaxpr and what it calls
    assert count("lower") == first["lower"] + 1
    assert count("backend_compile") == first["backend_compile"] + 1
    assert tel.jax_compile_seconds.labels("lower")._value.get() > 0.0


# --------------------------------------------------- phases round the loop
async def test_phases_cover_the_loop_and_do_not_nest(monkeypatch):
    """A recording shim round ``phase`` and ``begin``/``end``: every
    phase name occurs, none opens while another is open, and together
    they account for nearly all of the loop thread's time."""
    events: list[tuple[str, str, float]] = []
    orig_phase = DispatchProfiler.phase
    orig_begin, orig_end = DispatchProfiler.begin, DispatchProfiler.end

    class Recorded:
        def __init__(self, inner, name):
            self.inner, self.name = inner, name

        def __enter__(self):
            events.append(("enter", self.name, time.monotonic()))
            return self.inner.__enter__()

        def __exit__(self, *exc):
            out = self.inner.__exit__(*exc)
            events.append(("exit", self.name, time.monotonic()))
            return out

    def begin(self, kind, program=NO_PROGRAM):
        if program != NO_PROGRAM:
            events.append(("enter", "launch", time.monotonic()))
        return orig_begin(self, kind, program)

    def end(self, kind, t0, fresh=False):
        launching = self._launch_ann is not None
        out = orig_end(self, kind, t0, fresh)
        if launching:
            events.append(("exit", "launch", time.monotonic()))
        return out

    monkeypatch.setattr(
        DispatchProfiler, "phase",
        lambda self, name: Recorded(orig_phase(self, name), name),
    )
    monkeypatch.setattr(DispatchProfiler, "begin", begin)
    monkeypatch.setattr(DispatchProfiler, "end", end)

    before = _total("dynamo_engine_phase_seconds_total")
    engine = TPUEngine(_cfg(), mesh=single_device_mesh(), seed=0)
    engine.start()
    try:
        await _generate(engine, range(20, 44), max_tokens=12)
        await _generate(engine, range(60, 70), max_tokens=6)
        time.sleep(0.12)  # a few parks on the idle path
    finally:
        engine.stop()

    open_phase, covered = None, 0.0
    for what, name, t in events:
        if what == "enter":
            assert open_phase is None, f"{name} opened inside {open_phase[0]}"
            open_phase = (name, t)
        else:
            assert open_phase is not None and open_phase[0] == name
            covered += t - open_phase[1]
            open_phase = None
    assert {name for _, name, _ in events} == set(PHASES)
    span = events[-1][2] - events[0][2]
    assert covered / span > 0.8, (covered, span)
    # The same seconds reached /metrics and the profiler's own totals.
    counted = _total("dynamo_engine_phase_seconds_total") - before
    assert counted == pytest.approx(
        sum(engine.profiler.phase_seconds().values()), rel=1e-6
    )
    assert counted == pytest.approx(covered, rel=0.2)


async def test_an_engines_parts_sum_to_their_phases_on_metrics():
    """Two requests through mixed dispatches, windows, first tokens and
    finishes: on /metrics every part of a phase is there, none is
    negative, and the parts of each phase add up to the phase, the
    outbox's postings (stretches of ``consume.emit`` of their own,
    before a sync or a park) among them."""
    name = "dynamo_engine_phase_part_seconds_total"
    before = _samples(name)
    postings = _total("dynamo_emit_postings_total")
    phase_before = _samples("dynamo_engine_phase_seconds_total")
    engine = TPUEngine(_cfg(), mesh=single_device_mesh(), seed=0)
    engine.start()
    try:
        await _generate(engine, range(20, 44), max_tokens=12)
        await _generate(engine, range(60, 70), max_tokens=6)
    finally:
        engine.stop()
    assert _total("dynamo_emit_postings_total") - postings >= 4
    after = _samples(name)
    phase_after = _samples("dynamo_engine_phase_seconds_total")
    got = {
        (dict(k)["phase"], dict(k)["part"]): v - before.get(k, 0.0)
        for k, v in after.items()
    }
    for phase, names in PHASE_PARTS.items():
        mine = {part: s for (ph, part), s in got.items() if ph == phase}
        assert set(mine) == {*names, OTHER_PART}, phase
        assert all(s >= 0.0 for s in mine.values()), mine
        key = (("phase", phase),)
        assert sum(mine.values()) == pytest.approx(
            phase_after[key] - phase_before.get(key, 0.0), rel=1e-6
        ), phase
        # Every named part ran at least once in this traffic.
        assert all(mine[part] > 0.0 for part in names), mine
    assert got == pytest.approx(engine.profiler.part_seconds(), rel=1e-6)


async def test_a_parked_engine_takes_no_cpu_time_in_park():
    engine = TPUEngine(_cfg(), mesh=single_device_mesh(), seed=0)
    engine.start()
    try:
        await _generate(engine, range(20, 30), max_tokens=4)
        time.sleep(0.4)  # eight parks of 50 ms on the idle path
    finally:
        engine.stop()
    wall = engine.profiler.phase_seconds()
    cpu = engine.profiler.phase_cpu_seconds()
    assert wall["park"] >= 0.3
    assert cpu["park"] < 0.1 * wall["park"], (cpu["park"], wall["park"])
    # A phase's CPU time is a part of its wall time (the two clocks'
    # resolutions apart), and a working engine's loop took some.
    assert all(cpu[p] <= wall[p] + 0.005 for p in PHASES), (cpu, wall)
    assert sum(cpu.values()) > 0.0
    on_metrics = _samples("dynamo_engine_phase_cpu_seconds_total")
    assert {dict(k)["phase"] for k in on_metrics} == set(PHASES)


async def test_the_handoff_histogram_counts_every_posted_tuple():
    """One observation for each tuple the loop posts: a request's
    chunks of tokens and the finish that ends it."""
    name = "dynamo_engine_emit_handoff_seconds_count"
    engine = TPUEngine(_cfg(), mesh=single_device_mesh(), seed=0)
    engine.start()
    try:
        posted = 0
        for prompt, n in ((range(20, 44), 12), (range(60, 70), 6)):
            b = BackendInput(token_ids=list(prompt))
            b.stop_conditions.max_tokens = n
            b.stop_conditions.ignore_eos = True
            before = _total(name)
            stream = await engine.generate(b.to_dict())
            items = [item async for item in stream]
            # A tuple carries tokens or the finish reason, never both.
            assert all(
                bool(i.get("token_ids")) != bool(i.get("finish_reason"))
                for i in items
            )
            assert _total(name) - before == len(items) >= 3
            posted += len(items)
        assert _total("dynamo_engine_emit_handoff_seconds_sum") >= 0.0
    finally:
        engine.stop()
    # An engine without the profiler stamps and observes nothing.
    before = _total(name)
    engine = TPUEngine(
        _cfg(profile_dispatches=False), mesh=single_device_mesh(), seed=0
    )
    engine.start()
    try:
        assert await _generate(engine, range(20, 30), max_tokens=4) == 4
    finally:
        engine.stop()
    assert _total(name) == before and posted > 0


async def test_program_label_splits_ragged_and_sums_to_it():
    def snap():
        return {
            name: _samples(name)
            for name in (
                "dynamo_dispatch_seconds_count",
                "dynamo_decode_batch_rows_count",
                "dynamo_decode_batch_rows_sum",
            )
        }

    def delta(after, before, name, **match):
        def total(s):
            return sum(
                v for labels, v in s[name].items()
                if all(dict(labels).get(k) == x for k, x in match.items())
            )
        return total(after) - total(before)

    before = snap()
    engine = TPUEngine(_cfg(), mesh=single_device_mesh(), seed=0)
    engine.start()
    try:
        # 20 prompt tokens in 8-token chunks: mixed dispatches, then
        # pure-decode windows of 4.
        await _generate(engine, range(20, 40), max_tokens=13)
        ragged_count = engine.metrics()["dispatch"]["ragged"]["count"]
    finally:
        engine.stop()
    after = snap()
    name = "dynamo_dispatch_seconds_count"
    window = delta(after, before, name, kind="ragged", program="window")
    mixed = delta(after, before, name, kind="ragged", program="mixed")
    assert window >= 3 and mixed >= 1
    # What a reader matching only {kind: ragged} sums is both programs.
    assert window + mixed == delta(after, before, name, kind="ragged")
    assert window + mixed == ragged_count
    programs = {
        dict(labels).get("program")
        for labels in after[name]
        if dict(labels).get("kind") == "ragged"
    }
    assert programs <= set(PROGRAMS)
    # A window's rows are its true rows; a prefill-only mixed batch has
    # no decode row, which is what pulled the unlabelled mean down.
    rows = "dynamo_decode_batch_rows"
    n_window = delta(after, before, rows + "_count", program="window")
    assert n_window == window
    assert delta(after, before, rows + "_sum", program="window") == n_window
    assert delta(after, before, rows + "_count", program="mixed") == mixed


# ------------------------------------------------ counters beside old ones
async def test_prompt_tokens_by_source_against_a_shared_prefix_pair():
    tel = get_telemetry()

    def count(source):
        return tel.prompt_tokens.labels(source)._value.get()

    engine = TPUEngine(
        _cfg(prefix_sharing=True), mesh=single_device_mesh(), seed=0
    )
    engine.start()
    try:
        first = list(range(10, 42))  # 32 tokens = 4 pages
        c0, m0 = count("cached"), count("computed")
        await _generate(engine, first, max_tokens=4)
        assert (count("cached") - c0, count("computed") - m0) == (0, 32)
        # Same first three pages, then its own tail.
        second = first[:24] + list(range(100, 108))
        c1, m1 = count("cached"), count("computed")
        pages1 = dict(engine.kv.prefix_hits)
        await _generate(engine, second, max_tokens=4)
        cached, computed = count("cached") - c1, count("computed") - m1
        assert cached + computed == len(second)
        hit_pages = sum(
            engine.kv.prefix_hits[k] - pages1[k]
            for k in ("shared", "restore", "persist")
        )
        assert hit_pages == 3 and cached == hit_pages * PS
    finally:
        engine.stop()


def test_kv_pool_info_names_the_pools_geometry():
    cfg = _cfg(num_pages=48)
    TPUEngine(cfg, mesh=single_device_mesh(), seed=0)
    (labels,) = [dict(k) for k in _samples("dynamo_kv_pool_info")]
    assert labels == {
        "layers": str(TINY.num_layers),
        "pages": "48",
        "page_size": str(PS),
        "width": str(TINY.num_kv_heads * TINY.head_dim_),
        "dtype": "float32",
        "pools": "2",
        "pad_lanes": "0",
        "token_bytes": str(2 * TINY.num_layers * TINY.num_kv_heads * TINY.head_dim_ * 4),
        "index_width": "0",
    }


# ----------------------------------------------- scopes are metadata only
SCOPES = ("qkv", "kv_write", "attend", "attn_out", "ffn", "logits", "sample")


def _lowered(windowed: bool, **over):
    cfg = _cfg(model=TINY_QWEN3, **over)
    engine = TPUEngine(cfg, mesh=single_device_mesh(), seed=0)
    nb = cfg.ragged_tokens_bucket_for(2 if windowed else 16, mixed=not windowed)
    key = resolve_ragged_key(
        cfg, engine._attn_impl, nb, 2, windowed, False, False
    )
    fn = engine._ragged_fn_from_key(key)
    return fn.lower(*variant_call_args(engine, key))


@pytest.mark.parametrize("windowed", [True, False], ids=["window", "mixed"])
def test_named_scopes_change_no_operation(monkeypatch, windowed):
    """Lowering ``ragged_window`` and ``ragged_mixed`` at ``tiny-qwen3``
    with and without the scopes gives the same program once the
    metadata (locations) is left out; with them, every scope is in the
    metadata."""
    scoped = _lowered(windowed)
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        bare = _lowered(windowed)
    assert scoped.as_text() == bare.as_text()
    with_locations = scoped.as_text(debug_info=True)
    assert f"jit(ragged_{'window' if windowed else 'mixed'})" in with_locations
    for scope in SCOPES:
        assert re.search(rf'["/]{scope}["/]', with_locations), scope
    assert not re.search(r'["/]qkv["/]', bare.as_text(debug_info=True))


# ------------------------------------- the pools are written where they are
@pytest.mark.parametrize("windowed", [True, False], ids=["window", "mixed"])
def test_programs_hold_no_second_copy_of_the_pools(windowed):
    """The KV pools ride both loops (the decode window's steps, the
    layers) as carried state that is written in place: a compiled
    program's temporaries are smaller than ONE pool. Carried as the layer
    scan's inputs and stacked outputs they were three pools' worth (a
    copy of both for the window's carry, a fresh stacked output): the
    form that cost a third to a half of the device's time (PERF.md §6,
    PR 25). The pool (4096 pages, 8 MiB) dwarfs the activations."""
    compiled = _lowered(windowed, num_pages=4096).compile()
    one_pool = (
        TINY_QWEN3.num_layers * 4096 * PS
        * TINY_QWEN3.num_kv_heads * TINY_QWEN3.head_dim_ * 4
    )
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * one_pool  # both donated, reused
    assert mem.temp_size_in_bytes < one_pool // 4


# ------------------------------------------------ one launch, five callers
# Which dispatches of a run are a launch of each kind, by the ``dispatch``
# flight event, and the fields that event has beside the five every launch
# gives it.
LAUNCH_FIELDS = {"dispatch", "bucket", "windowed", "pages", "want_lp"}
LAUNCHES = {
    "window": ("token", lambda d: d["windowed"] and not d.get("chained"), {"rows"}),
    "chained-window": (
        "token", lambda d: d["windowed"] and d.get("chained"), {"rows", "chained"},
    ),
    "mixed": ("token", lambda d: not d["windowed"], {"rows", "tokens"}),
    "block": ("block", lambda d: d["windowed"], {"program", "rows", "chained"}),
    "mixed-block": ("block", lambda d: not d["windowed"], {"rows", "tokens"}),
}


@functools.cache
def _launch_record(model: str) -> dict:
    """One request through a token model's engine or a block model's:
    the calls of ``_run_program`` and the ``dispatch`` flight events in
    the order they came, what the launch counters grew by, and the
    profiler's parts."""
    from .test_sdar import PROMPT, _engine as block_engine

    engine = (
        block_engine() if model == "block"
        else TPUEngine(_cfg(), mesh=single_device_mesh(), seed=0)
    )
    log: list = []
    run_program, record = engine._run_program, engine.flight.record

    def spy_run(fn, *args):
        log.append("run")
        return run_program(fn, *args)

    def spy_record(kind, **data):
        if kind == "dispatch" and data.get("dispatch") == "ragged":
            log.append(data)
        return record(kind, **data)

    engine._run_program, engine.flight.record = spy_run, spy_record
    counters = ("dynamo_launch_transfers_total", "dynamo_ragged_dispatch_total")
    before = [_total(name) for name in counters]
    engine.start()
    try:
        # Prompt chunks (mixed), then decode alone: a window or a block,
        # and the ones chained onto it.
        prompt = PROMPT[:40] if model == "block" else range(20, 40)
        assert asyncio.run(_generate(engine, prompt, max_tokens=21)) == 21
    finally:
        engine.stop()
    return {
        "log": log,
        "grew": [_total(name) - b for name, b in zip(counters, before)],
        "parts": engine.profiler.part_seconds(),
    }


@pytest.mark.parametrize("kind", LAUNCHES)
def test_every_launch_is_the_one_launch(kind):
    """Each of the five callers of ``TPUEngine._launch``: every dispatch
    calls its program through ``_run_program`` exactly once (a block
    model's too: the sequence-state pool is threaded there, and a test
    that watches launches watches that), hands over one buffer, books
    the lookup and the launch's two parts, and records a ``dispatch``
    event with the fields that kind has."""
    model, is_kind, own = LAUNCHES[kind]
    rec = _launch_record(model)
    log = rec["log"]
    events = [d for d in log if d != "run"]
    assert any(is_kind(d) for d in events), [sorted(d) for d in events]
    # run, event, run, event, ...: one call of the program a dispatch.
    assert log[0::2] == ["run"] * len(events) and log[1::2] == events
    assert rec["grew"] == [len(events), len(events)]
    for d in filter(is_kind, events):
        assert set(d) == LAUNCH_FIELDS | own, (kind, sorted(d))
        assert d["bucket"] >= d["rows"] >= 1 and d["want_lp"] is False
    for part in (("assemble", "lookup"), ("launch", "h2d"), ("launch", "call")):
        assert rec["parts"][part] > 0.0, part


# --------------------------------- the parts change no program either
def _launched(profile: bool) -> dict[str, str]:
    """The text each ragged program lowers to from the arguments an
    engine's launches really pass it (a launch's one packed buffer is on
    the device before the call; ``TPUEngine._run_program`` sees it), and,
    under ``<name>/padding``, from ``variant_call_args``' for the same
    variant."""
    engine = TPUEngine(
        _cfg(model=TINY_QWEN3, profile_dispatches=profile),
        mesh=single_device_mesh(), seed=0,
    )
    seen: dict[str, tuple] = {}
    run_program = engine._run_program

    def spy(fn, *args):
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
            args,
        )
        seen.setdefault(fn.__name__, (fn, shapes))
        return run_program(fn, *args)

    engine._run_program = spy
    engine.start()
    try:
        asyncio.run(_generate(engine, range(20, 40), max_tokens=13))
    finally:
        engine.stop()
    out = {name: fn.lower(*shapes).as_text() for name, (fn, shapes) in seen.items()}
    by_fn = {fn: key for key, fn in engine._ragged_fns.items()}
    for name, (fn, _shapes) in seen.items():
        out[f"{name}/padding"] = fn.lower(
            *variant_call_args(engine, by_fn[fn])
        ).as_text()
    return out


def test_the_parts_and_the_packed_transfer_change_no_program():
    """With the profiler (phases, parts, CPU clock) and without it an
    engine launches both ragged programs with arguments of the same
    shapes and types, and they lower to the same text: the text the
    prewarm's and the AOT compiler's padding arguments lower to."""
    with_parts, bare = _launched(True), _launched(False)
    assert set(bare) == {
        "ragged_window", "ragged_mixed",
        "ragged_window/padding", "ragged_mixed/padding",
    }
    assert with_parts == bare
    for name in ("ragged_window", "ragged_mixed"):
        assert bare[name] == bare[f"{name}/padding"]
