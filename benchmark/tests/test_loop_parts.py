"""The metrics that read inside the engine loop's phases
(``telemetry/dispatch.py`` PHASE_PARTS): ``prom_counter_ratio`` on two
scraped texts, ``trace_idle_by_part`` and ``lib/xplane_parts.py`` on a
hand-made ``.xplane.pb``, and the older readers on a trace with and
without ``dynpart/`` events: they read the same."""

import importlib
import json
import os

import pytest

from lib import stats, xplane_meta as xm, xplane_parts as xp

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def metric(name, ctx):
    """A metric's value as ``run_cell.py::_per_layer`` reads it: from
    the reader its own file names."""
    with open(os.path.join(BENCH, "layer_metrics", f"{name}.json")) as f:
        reader = json.load(f)["reader"]
    return importlib.import_module(f"readers.{reader['kind']}").read(reader, ctx)


# ------------------------------------------------------- counters, scraped
def scrape(phase, cpu, part, handoff=None) -> str:
    lines = [f'dynamo_engine_phase_seconds_total{{phase="{p}"}} {v}' for p, v in phase.items()]
    lines += [f'dynamo_engine_phase_cpu_seconds_total{{phase="{p}"}} {v}' for p, v in cpu.items()]
    lines += [
        f'dynamo_engine_phase_part_seconds_total{{part="{b}",phase="{a}"}} {v}'
        for (a, b), v in part.items()
    ]
    if handoff:
        lines += [
            f"dynamo_engine_emit_handoff_seconds_count {handoff[0]}",
            f"dynamo_engine_emit_handoff_seconds_sum {handoff[1]}",
        ]
    return "# HELP x y\n" + "\n".join(lines) + "\n"


def test_counter_ratios_read_the_window_between_two_scrapes():
    before = scrape(
        {"consume": 10.0, "launch": 4.0, "sync": 50.0, "park": 30.0, "admit": 1.0},
        {"consume": 8.0, "launch": 4.0, "sync": 0.1, "park": 0.0, "admit": 1.0},
        {("consume", "emit"): 5.0, ("consume", "other"): 5.0, ("launch", "h2d"): 3.0},
        handoff=(100, 0.2),
    )
    after = scrape(
        {"consume": 16.0, "launch": 6.0, "sync": 80.0, "park": 31.0, "admit": 2.0},
        {"consume": 11.0, "launch": 5.5, "sync": 0.2, "park": 0.0, "admit": 1.5},
        # A part that first ran inside the window has no series before it.
        {("consume", "emit"): 7.0, ("consume", "other"): 9.0, ("launch", "h2d"): 4.5,
         ("consume", "finish"): 0.5},
        handoff=(300, 1.0),
    )
    ctx = {"prom_before": stats.parse_prometheus(before),
           "prom_after": stats.parse_prometheus(after)}
    # Host-work phases moved 6 + 2 + 1 = 9 s of wall and 3 + 1.5 + 0.5 = 5 of
    # CPU; sync and park, where the thread is meant to wait, stay out.
    assert metric("loop_offcpu_share", ctx) == pytest.approx((1 - 5 / 9) * 100)
    # A part over ALL phases' seconds (40 in the window), like
    # loop_host_work_share, so that the parts are pieces of it.
    assert metric("loop_host_work_share", ctx) == pytest.approx(9 / 40 * 100)
    assert metric("consume_emit_loop_share", ctx) == pytest.approx(2 / 40 * 100)
    assert metric("launch_h2d_loop_share", ctx) == pytest.approx(1.5 / 40 * 100)
    assert metric("consume_finish_loop_share", ctx) == pytest.approx(0.5 / 40 * 100)
    # A part that did not run in the window read 0, not nothing.
    assert metric("admit_record_loop_share", ctx) == 0.0
    assert metric("consume_pages_loop_share", ctx) == 0.0
    assert metric("assemble_build_loop_share", ctx) == 0.0
    assert metric("emit_handoff_mean_ms", ctx) == pytest.approx(0.8 / 200 * 1000)


def test_counter_ratios_read_nothing_from_a_program_without_the_counters():
    """The parent of the PR that added them: phases and no more."""
    text = scrape({"consume": 10.0, "sync": 50.0}, {}, {})
    later = scrape({"consume": 16.0, "sync": 80.0}, {}, {})
    ctx = {"prom_before": stats.parse_prometheus(text),
           "prom_after": stats.parse_prometheus(later)}
    for name in ("loop_offcpu_share", "consume_emit_loop_share", "launch_h2d_loop_share",
                 "admit_record_loop_share", "emit_handoff_mean_ms"):
        assert metric(name, ctx) is None, name
    assert metric("loop_host_work_share", ctx) is not None
    # With the counters and a window in which the loop did not move: nothing.
    still = scrape({"consume": 10.0}, {"consume": 8.0}, {("consume", "emit"): 5.0})
    same = {"prom_before": stats.parse_prometheus(still),
            "prom_after": stats.parse_prometheus(still)}
    assert metric("loop_offcpu_share", same) is None
    assert metric("consume_emit_loop_share", same) is None
    read = importlib.import_module("readers.prom_counter_ratio").read
    side = {"metric": "dynamo_engine_phase_seconds_total", "match": [{}]}
    assert read({"numerator": side, "denominator": side}, ctx) == 1.0
    assert read({"numerator": side, "denominator": side, "one_minus": True}, ctx) == 0.0


# ------------------------------------------------------- a hand-made trace
# Host clock = device clock + 46 (bounds 40 and 52: 12 wide). The device
# runs [100,500) and [600,900) of a slice [0,1000) and idles between.
PHASES = [(0, 140, "dyn/consume"), (140, 20, "dyn/launch"), (160, 400, "dyn/sync"),
          (560, 100, "dyn/admit"), (660, 340, "dyn/sync")]
PARTS = [(60, 70, "dynpart/consume.finish"), (140, 10, "dynpart/launch.h2d"),
         (150, 10, "dynpart/launch.call"), (560, 40, "dynpart/admit.schedule"),
         (600, 50, "dynpart/admit.record")]


def write_trace(path, host_events) -> str:
    """A ``.xplane.pb`` of one chip and one host thread, in the schema
    ``xplane_meta`` declares: times in ns, one metadata entry a name."""
    space = xm._schema()()

    def plane_of(name):
        plane = space.planes.add(name=name)
        stat = plane.stat_metadata.add(key=1)
        stat.value.id, stat.value.name = 1, "run_id"
        return plane, {}

    def add(plane, ids, line, start, dur, name, run_id=None):
        if name not in ids:
            ids[name] = len(ids) + 1
            meta = plane.event_metadata.add(key=ids[name])
            meta.value.id, meta.value.name = ids[name], name
        ev = line.events.add(metadata_id=ids[name], offset_ps=start * 1000, duration_ps=dur * 1000)
        if run_id is not None:
            ev.stats.add(metadata_id=1, uint64_value=run_id)

    dev, ids = plane_of("/device:TPU:0")
    modules, ops = dev.lines.add(name="XLA Modules"), dev.lines.add(name="XLA Ops")
    for run_id, (start, dur) in enumerate([(100, 400), (600, 300)], 1):
        add(dev, ids, modules, start, dur, f"jit_ragged_window({run_id})", run_id)
        add(dev, ids, ops, start, dur, f"%fusion.{run_id} = bf16[8]{{0}} fusion(bf16[8]{{0}} %p)")
    host, ids = plane_of("/host:CPU")
    loop, runtime = host.lines.add(name="python3"), host.lines.add(name="tpu-runtime")
    for start, dur, name in host_events:
        add(host, ids, loop, start, dur, name)
    for start, name, run_id in [(140, "DoEnqueueProgram", 1), (552, "CompleteCallbacks", 1),
                                (640, "DoEnqueueProgram", 2), (958, "CompleteCallbacks", 2)]:
        add(host, ids, runtime, start, 5, name, run_id)
    # A part's name on another thread is not the loop's.
    add(host, ids, runtime, 0, 1000, "dynpart/consume.finish")
    with open(path, "wb") as f:
        f.write(space.SerializeToString())
    return str(path)


def as_a_run(tmp_path, monkeypatch, host_events) -> dict:
    """The trace where ``find_trace`` looks for a run's, caches empty."""
    where = tmp_path / "cell" / "run" / "trace" / "plugins" / "profile" / "t"
    where.mkdir(parents=True)
    write_trace(where / "host.xplane.pb", host_events)
    monkeypatch.setattr(xm, "WORK", str(tmp_path))
    monkeypatch.setattr(xm, "_CACHE", {})
    monkeypatch.setattr(xp, "_CACHE", {})
    return {"trace": {"chips": {0: {"idle_share": 0.3}}}, "prom_after": {}, "prom_before": {}}


def test_idle_time_is_cut_along_the_parts_of_the_loops_thread(tmp_path, monkeypatch):
    ctx = as_a_run(tmp_path, monkeypatch, PHASES + PARTS)
    split = xm.current()["idle"]
    assert split["thread"] == "python3"
    assert split["offset"] == {"ns": 46, "width_ns": 12, "pairs": 4}
    assert (split["window_ns"], split["idle_ns"]) == (1000, 300)
    # Idle [0,100), [500,600), [900,1000) on the device is [46,146),
    # [546,646), [946,1046) on the host.
    assert split["by_phase"] == {"consume": 94, "sync": 14 + 54, "admit": 86}
    assert xp.current() == {
        "window_ns": 1000,
        # [60,130) whole; launch.h2d's [140,146) is shorter than the offset
        # is wide; admit.schedule [560,600), admit.record [600,646).
        "by_part": {"consume.finish": 70, "admit.schedule": 40, "admit.record": 46},
    }
    assert metric("idle_consume_finish_share", ctx) == pytest.approx(7.0)
    assert metric("idle_admit_record_share", ctx) == pytest.approx(4.6)
    assert metric("idle_launch_h2d_share", ctx) == 0.0  # there, and met no gap
    # A part's idle time is a piece of its phase's.
    assert metric("idle_consume_finish_share", ctx) <= 9.4
    assert metric("idle_host_work_share", ctx) == pytest.approx(18.0)
    # Without a trace (a CPU rehearsal) nothing is read.
    assert metric("idle_consume_finish_share", {"trace": None}) is None


def test_a_trace_without_parts_reads_nothing_and_raises_nothing(tmp_path, monkeypatch):
    """The parent of the PR that added them, under this benchmark."""
    ctx = as_a_run(tmp_path, monkeypatch, PHASES)
    assert xp.current() is None
    for name in ("idle_consume_finish_share", "idle_launch_h2d_share", "idle_admit_record_share"):
        assert metric(name, ctx) is None
    assert metric("idle_host_work_share", ctx) == pytest.approx(18.0)
    # Nor from a trace without phases (no split to take the window from).
    assert xp.idle_by_part({"idle": None}, {"python3": [(0, 1, "consume.finish")]}) is None


def test_the_phase_readers_read_the_same_with_and_without_part_events(tmp_path):
    """``dynpart/`` is not ``dyn/``: ``xplane_meta.parse`` takes no part
    for a phase, so ``idle_by_phase`` books each idle nanosecond once
    and the four accepted idle metrics do not move."""
    with_parts = xm.parse(write_trace(tmp_path / "a.xplane.pb", PHASES + PARTS))
    bare = xm.parse(write_trace(tmp_path / "b.xplane.pb", PHASES))
    assert with_parts["phases"] == bare["phases"]
    assert {n for _, _, n in with_parts["phases"]["python3"]} == {"consume", "launch", "sync", "admit"}
    a, b = xm.idle_by_phase(with_parts), xm.idle_by_phase(bare)
    assert a == b
    assert a["unattributed_ns"] == 300 - 94 - 68 - 86 >= 0
    # Had the parts been named dyn/<phase>/<part> they would have been
    # phases, nested, and every one of their nanoseconds booked twice.
    nested = [(s, d, n.replace("dynpart/", "dyn/")) for s, d, n in PARTS]
    twice = xm.idle_by_phase(xm.parse(write_trace(tmp_path / "c.xplane.pb", PHASES + nested)))
    assert twice["unattributed_ns"] < a["unattributed_ns"]
    assert sum(twice["by_phase"].values()) > sum(a["by_phase"].values())


# -------------------------------------------------- in the harness, on the CPU
def test_traced_rehearsal_prints_the_counter_metrics_and_no_idle_part():
    from test_rehearsal import result_of, run_cell

    out = result_of(*run_cell("tiny-qwen3.rehearsal-sessions", 1, seconds=6))
    m = out["metrics"]
    assert out["correct"] is True
    shares = [
        "consume_emit_loop_share", "consume_pages_loop_share", "consume_finish_loop_share",
        "launch_h2d_loop_share", "admit_record_loop_share", "assemble_build_loop_share",
    ]
    for name in shares:
        assert m[name]["value"] > 0 and m[name]["unit"] == "%", name
    # Pieces of the loop's host-work share, and not all of it.
    assert sum(m[n]["value"] for n in shares) < m["loop_host_work_share"]["value"]
    assert -1.0 < m["loop_offcpu_share"]["value"] < 100.0
    assert m["emit_handoff_mean_ms"]["value"] > 0 and m["emit_handoff_mean_ms"]["unit"] == "ms"
    # No device, no trace: the parts of the idle time are a chip run's.
    assert not {
        "idle_consume_finish_share", "idle_launch_h2d_share", "idle_admit_record_share",
    } & set(m)
