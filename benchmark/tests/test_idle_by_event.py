"""The metrics that read what the device waits behind off the engine
loop's thread (``dynhttp/``, ``dyngc/``, ``dynstall/`` events and their
counters): ``trace_idle_by_event`` on a hand-made ``.xplane.pb`` whose
overlaps are known to the nanosecond, the counter metrics on two scraped
texts, and the older idle readers on a trace with and without the three
families of events: they read the same."""

import importlib

import pytest

from lib import stats, xplane_meta as xm, xplane_parts as xp
from readers import trace_idle_by_event as ev
from test_loop_parts import PARTS, PHASES, metric

# The slice of ``test_loop_parts``: host clock = device clock + 46, 12 wide;
# the device idles [0,100), [500,600), [900,1000), which is [46,146),
# [546,646), [946,1046) on the host; the loop's phases give those pieces
# consume [46,140), sync [160..): [546,560), admit [560,646), sync [946,1000).
LOOP, HTTP, WITNESS = "python3", "asyncio-http", "host-witness"
EVENTS = [
    # A chunk's parts on the HTTP thread, across the idle piece in sync.
    (HTTP, 540, 8, "dynhttp/detokenize", {}),
    (HTTP, 548, 6, "dynhttp/frame", {}),
    (HTTP, 554, 20, "dynhttp/write", {}),  # [554,574): 6 of it in sync's piece
    # A collection on the HTTP thread inside the last idle piece, and a
    # chunk's event left open through it.
    (HTTP, 950, 40, "dynhttp/detokenize", {}),  # [950,990)
    (HTTP, 960, 20, "dyngc/gen2", {}),  # [960,980)
    # A young collection on the loop's own thread while it worked, and
    # one on the HTTP thread while the loop admitted, inside the stall.
    (LOOP, 100, 10, "dyngc/gen0", {}),  # [100,110): consume, not sync
    (HTTP, 590, 10, "dyngc/gen1", {}),  # [590,600): admit, not sync
    # A stall the witness saw at 640, 60 ns late: [580,640), admit's piece.
    (WITNESS, 640, 1, "dynstall/descheduled", {"late_ns": 60}),
    # One that began before the slice: [-30,30) meets no idle piece.
    (WITNESS, 30, 1, "dynstall/busy", {"late_ns": 60}),
]


def write_trace(path, loop_events, other_events=()) -> str:
    """``test_loop_parts.write_trace`` with events on other host lines and
    with integer stats (a ``dynstall/`` mark's ``late_ns``)."""
    space = xm._schema()()
    stat_ids = {"run_id": 1, "late_ns": 2}

    def plane_of(name):
        plane = space.planes.add(name=name)
        for stat_name, key in stat_ids.items():
            stat = plane.stat_metadata.add(key=key)
            stat.value.id, stat.value.name = key, stat_name
        return plane, {}

    def add(plane, ids, line, start, dur, name, run_id=None, **more):
        if name not in ids:
            ids[name] = len(ids) + 1
            meta = plane.event_metadata.add(key=ids[name])
            meta.value.id, meta.value.name = ids[name], name
        e = line.events.add(metadata_id=ids[name], offset_ps=start * 1000, duration_ps=dur * 1000)
        if run_id is not None:
            e.stats.add(metadata_id=1, uint64_value=run_id)
        for key, value in more.items():
            e.stats.add(metadata_id=stat_ids[key], int64_value=value)

    dev, ids = plane_of("/device:TPU:0")
    modules, ops = dev.lines.add(name="XLA Modules"), dev.lines.add(name="XLA Ops")
    for run_id, (start, dur) in enumerate([(100, 400), (600, 300)], 1):
        add(dev, ids, modules, start, dur, f"jit_ragged_window({run_id})", run_id)
        add(dev, ids, ops, start, dur, f"%fusion.{run_id} = bf16[8]{{0}} fusion(bf16[8]{{0}} %p)")
    host, ids = plane_of("/host:CPU")
    lines = {LOOP: host.lines.add(name=LOOP), "tpu-runtime": host.lines.add(name="tpu-runtime")}
    for start, dur, name in loop_events:
        add(host, ids, lines[LOOP], start, dur, name)
    for start, name, run_id in [(140, "DoEnqueueProgram", 1), (552, "CompleteCallbacks", 1),
                                (640, "DoEnqueueProgram", 2), (958, "CompleteCallbacks", 2)]:
        add(host, ids, lines["tpu-runtime"], start, 5, name, run_id)
    add(host, ids, lines["tpu-runtime"], 0, 1000, "dynpart/consume.finish")
    for thread, start, dur, name, more in other_events:
        if thread not in lines:
            lines[thread] = host.lines.add(name=thread)
        add(host, ids, lines[thread], start, dur, name, **more)
    with open(path, "wb") as f:
        f.write(space.SerializeToString())
    return str(path)


HAS_IT = stats.parse_prometheus("dynamo_http_chunks_total 12.0\n")


def as_a_run(tmp_path, monkeypatch, other_events, prom=HAS_IT) -> dict:
    where = tmp_path / "cell" / "run" / "trace" / "plugins" / "profile" / "t"
    where.mkdir(parents=True)
    write_trace(where / "host.xplane.pb", PHASES + PARTS, other_events)
    monkeypatch.setattr(xm, "WORK", str(tmp_path))
    for module in (xm, xp, ev):
        monkeypatch.setattr(module, "_CACHE", {})
    return {"trace": {"chips": {0: {"idle_share": 0.3}}}, "prom_after": prom, "prom_before": {}}


def test_overlap_with_the_idle_pieces_is_read_to_the_nanosecond(tmp_path, monkeypatch):
    ctx = as_a_run(tmp_path, monkeypatch, EVENTS)
    split = ev.current()
    assert split["window_ns"] == 1000
    assert split["by_family"] == {
        # gen2 [960,980) in sync's [946,1000); gen0 [100,110) fell in
        # consume and gen1 [590,600) in admit, which are not sync.
        "gc": 20,
        # [580,640) in admit's [560,646), whole: the collection inside it
        # claimed no idle time (it was not in sync), so none is taken from
        # the stall. The one from before the slice reaches [−30,30),
        # before the first idle piece [46,..).
        "stall": 60,
        # write [554,574) meets sync's [546,560) for 6, frame [548,554) for
        # 6, detokenize [540,548) for 2; detokenize [950,990) meets sync's
        # [946,1000) for 40 less the collection's 20.
        "http": 6 + 6 + 2 + 20,
    }
    assert metric("idle_sync_gc_share", ctx) == pytest.approx(2.0)
    assert metric("idle_stall_share", ctx) == pytest.approx(6.0)
    assert metric("idle_sync_http_share", ctx) == pytest.approx(3.4)
    # Pieces of what the older readers show, and no more than they show.
    sync = metric("idle_sync_share", ctx)
    assert metric("idle_sync_gc_share", ctx) + metric("idle_sync_http_share", ctx) <= sync
    assert (
        sum(metric(n, ctx) for n in ("idle_sync_gc_share", "idle_sync_http_share", "idle_stall_share"))
        <= sync + metric("idle_host_work_share", ctx) + metric("idle_park_share", ctx)
    )
    # Without a trace (a CPU rehearsal) nothing is read.
    assert metric("idle_stall_share", {"trace": None, "prom_after": HAS_IT}) is None


def test_a_program_without_the_events_reads_nothing_and_one_with_them_zero(tmp_path, monkeypatch):
    """The parent under this benchmark: no series, no events, None and
    no raise. The change in a slice that saw no collection and no stall:
    0.0, not None (the series says the program has the instrument)."""
    ctx = as_a_run(tmp_path, monkeypatch, [], prom={})
    for name in ("idle_sync_http_share", "idle_sync_gc_share", "idle_stall_share"):
        assert metric(name, ctx) is None
    ctx = as_a_run(tmp_path / "b", monkeypatch, EVENTS[:3])
    assert metric("idle_sync_gc_share", ctx) == 0.0
    assert metric("idle_stall_share", ctx) == 0.0
    assert metric("idle_sync_http_share", ctx) == pytest.approx(1.4)


def test_the_older_idle_readers_read_the_same_with_and_without_the_events(tmp_path, monkeypatch):
    """``dynhttp/``, ``dyngc/`` and ``dynstall/`` are not ``dyn/`` and not
    ``dynpart/``: ``xplane_meta.parse`` takes none for a phase and
    ``xplane_parts.parse`` none for a part, on the loop's line or any."""
    with_events = write_trace(tmp_path / "a.xplane.pb", PHASES + PARTS, EVENTS)
    bare = write_trace(tmp_path / "b.xplane.pb", PHASES + PARTS)
    a, b = xm.parse(with_events), xm.parse(bare)
    assert a["phases"] == b["phases"]
    assert xm.idle_by_phase(a) == xm.idle_by_phase(b)
    assert xp.parse(with_events) == xp.parse(bare)
    a["idle"], b["idle"] = xm.idle_by_phase(a), xm.idle_by_phase(b)
    assert xp.idle_by_part(a, xp.parse(with_events)) == xp.idle_by_part(b, xp.parse(bare))
    # And through the readers, every accepted idle metric.
    older = (
        "idle_host_work_share", "idle_sync_share", "idle_park_share",
        "idle_consume_finish_share", "idle_launch_h2d_share", "idle_admit_record_share",
    )
    ctx = as_a_run(tmp_path / "with", monkeypatch, EVENTS)
    read_with = {n: metric(n, ctx) for n in older}
    unattributed = importlib.import_module("readers.trace_idle_by_phase").read(
        {"phases": ["unattributed"]}, ctx
    )
    ctx = as_a_run(tmp_path / "bare", monkeypatch, [])
    assert {n: metric(n, ctx) for n in older} == read_with
    assert importlib.import_module("readers.trace_idle_by_phase").read(
        {"phases": ["unattributed"]}, ctx
    ) == unattributed


def test_pieces_are_cut_as_cut_gaps_cuts_and_intervals_add_up():
    gaps = [(0, 100), (500, 100), (900, 100)]
    phases = sorted((s, d, n[len("dyn/"):]) for s, d, n in PHASES)
    pieces = ev.idle_pieces(gaps, phases, 46, 12)
    by_phase = {}
    for p0, p1, name in pieces:
        by_phase[name] = by_phase.get(name, 0) + p1 - p0
    assert (by_phase, 300 - sum(by_phase.values())) == xm.cut_gaps(gaps, phases, 46, 12)
    assert ev.merged([(5, 9), (1, 3), (2, 6), (9, 9)]) == [(1, 9)]
    assert ev.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22), (29, 40)]) == [(0, 2), (4, 8), (22, 29)]
    assert ev.intersect([(0, 10), (20, 30)], [(5, 25)]) == [(5, 10), (20, 25)]


def test_a_collection_off_the_planes_clock_is_refused(tmp_path):
    """One clock: a ``dyngc/`` event made by anything but the call that
    makes the ``dyn/`` events would lie seconds (or years) from them."""
    good = ev.parse(write_trace(tmp_path / "a.xplane.pb", PHASES, EVENTS))
    ev.check_one_clock(good)
    assert {t for _s, _e, t in good["events"]["gc"]} == {HTTP, LOOP}
    assert len(good["events"]["gc"]) == 3
    assert sorted(good["events"]["stall"]) == [(-30, 30, WITNESS), (580, 640, WITNESS)]
    far = 5_000_000_000
    off = ev.parse(write_trace(
        tmp_path / "b.xplane.pb", PHASES, [(HTTP, far, 10, "dyngc/gen1", {})]
    ))
    with pytest.raises(ValueError, match="outside the host plane"):
        ev.check_one_clock(off)
    own_line = ev.parse(write_trace(
        tmp_path / "c.xplane.pb", PHASES + [(3 * far, 5, "dyn/park")],
        [(LOOP, far + far // 2, 10, "dyngc/gen1", {})],
    ))
    ev.check_one_clock(own_line)  # among its line's events, however sparse


# ------------------------------------------------------- counters, scraped
def test_counter_metrics_read_the_window_and_nothing_from_the_parent():
    before = stats.parse_prometheus(
        'dynamo_http_chunks_total 100.0\n'
        'dynamo_http_chunk_cpu_seconds_total{part="detokenize"} 0.010\n'
        'dynamo_http_chunk_cpu_seconds_total{part="frame"} 0.005\n'
        'dynamo_http_chunk_cpu_seconds_total{part="write"} 0.005\n'
        'dynamo_gc_pause_seconds_total{generation="0"} 0.5\n'
        'dynamo_gc_pause_seconds_total{generation="2"} 1.0\n'
        'dynamo_host_stall_seconds_total{cause="busy"} 0.0\n'
        'dynamo_host_stall_seconds_total{cause="descheduled"} 0.0\n'
        'dynamo_launch_seconds_count{program="window"} 10.0\n'
        'dynamo_launch_seconds_sum{program="window"} 0.02\n'
    )
    after = stats.parse_prometheus(
        'dynamo_http_chunks_total 300.0\n'
        'dynamo_http_chunk_cpu_seconds_total{part="detokenize"} 0.030\n'
        'dynamo_http_chunk_cpu_seconds_total{part="frame"} 0.015\n'
        'dynamo_http_chunk_cpu_seconds_total{part="write"} 0.015\n'
        'dynamo_gc_pause_seconds_total{generation="0"} 0.75\n'
        'dynamo_gc_pause_seconds_total{generation="2"} 1.25\n'
        'dynamo_host_stall_seconds_total{cause="busy"} 0.3\n'
        'dynamo_host_stall_seconds_total{cause="descheduled"} 2.0\n'
        'dynamo_launch_seconds_count{program="window"} 20.0\n'
        'dynamo_launch_seconds_sum{program="window"} 0.05\n'
        'dynamo_launch_seconds_count{program="mixed"} 10.0\n'
        'dynamo_launch_seconds_sum{program="mixed"} 0.05\n'
    )
    ctx = {"prom_before": before, "prom_after": after}
    assert metric("http_chunk_cpu_ms_mean", ctx) == pytest.approx(0.04 / 200 * 1000)
    assert metric("gc_pause_in_window_s", ctx) == pytest.approx(0.5)
    assert metric("host_stall_in_window_s", ctx) == pytest.approx(2.3)
    assert metric("launch_mean_ms", ctx) == pytest.approx(0.08 / 20 * 1000)
    # A window without a stall reads 0.0: the series are there from the start.
    calm = {"prom_before": before, "prom_after": before}
    assert metric("host_stall_in_window_s", calm) == 0.0
    assert metric("gc_pause_in_window_s", calm) == 0.0
    # The parent: no such series, nothing read, nothing raised.
    parent = {"prom_before": {}, "prom_after": stats.parse_prometheus("dynamo_x_total 1.0\n")}
    for name in ("http_chunk_cpu_ms_mean", "gc_pause_in_window_s",
                 "host_stall_in_window_s", "launch_mean_ms"):
        assert metric(name, parent) is None, name
