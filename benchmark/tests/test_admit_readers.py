"""The metrics PR 65 reads the life of an admission by (phase ``admit``
in seven parts, its CPU against its wall, its attempts against its
admissions, the pages it takes by source, every phase's piece of the
device's idle time) and the six parts the loop already booked and no
metric read: each data file on two scraped texts and on a hand-made
``.xplane.pb``, on the accepted readers.

``BENCHMARK.json`` may list 128 per-layer metrics and listed 124: the
ones it has no room for wait in ``layer_metrics_pending.json``, entry for
entry as they would be appended, and are read here all the same."""

import json
import os

import pytest
from test_loop_parts import BENCH, as_a_run, metric, write_trace

from lib import stats, xplane_meta as xm, xplane_parts as xp

ROOT = os.path.dirname(BENCH)
ADMIT_PARTS = ("schedule", "hash", "match", "take", "register", "bind")
IDLE_BY_PHASE = (
    "idle_admit_share", "idle_consume_share", "idle_assemble_share",
    "idle_launch_share", "idle_loop_rest_share",
)
COUNTER_METRICS = (
    "admit_loop_share", *(f"admit_{p}_loop_share" for p in ADMIT_PARTS),
    "admit_offcpu_share", "admit_mean_ms", "admit_us_per_prompt_token",
    "admit_attempts_per_admission", "admit_evicted_take_share",
)
ALREADY_BOOKED = (
    "consume_tokens_loop_share", "consume_first_token_loop_share",
    "consume_other_loop_share", "assemble_plan_loop_share",
    "assemble_lookup_loop_share", "launch_call_loop_share",
)
TRACE_METRICS = (*IDLE_BY_PHASE, *(f"idle_admit_{p}_share" for p in ADMIT_PARTS))
NEW = (*COUNTER_METRICS, *TRACE_METRICS, *ALREADY_BOOKED)
SOURCE_OF = {
    "prom_counter_ratio": "program_counter", "prom_ratio": "program_counter",
    "prom_hist_mean": "program_counter", "trace_idle_by_phase": "device_trace",
    "trace_idle_by_part": "device_trace",
}


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


# ------------------------------------------------- listed, or waiting for room
def test_every_new_metric_is_listed_once_or_waits_with_its_entry_ready():
    bench = load(ROOT, "BENCHMARK.json")
    listed = {m["name"]: m for m in bench["per_layer"]}
    pending = {m["name"]: m for m in load(BENCH, "layer_metrics_pending.json")["per_layer"]}
    assert len(bench["per_layer"]) <= 128
    assert not set(listed) & set(pending)
    mine = {name: listed.get(name) or pending.get(name) for name in NEW}
    assert all(mine.values()), [n for n, m in mine.items() if not m]
    assert set(pending) <= set(NEW)
    layers = {m["layer"] for m in bench["per_layer"]}
    ends = {m["name"] for m in bench["end_to_end"]}
    for name, entry in mine.items():
        reader = load(BENCH, "layer_metrics", f"{name}.json")["reader"]
        assert os.path.exists(os.path.join(BENCH, "readers", reader["kind"] + ".py"))
        # Every cell reports it: selected by this key alone (run_cell.load_cell).
        assert set(entry) == {"name", "unit", "better", "source", "layer", "moves"}, name
        assert entry["source"] == SOURCE_OF[reader["kind"]], name
        assert entry["layer"] in layers and entry["moves"] in ends, name


# ------------------------------------------------------- counters, scraped
def scrape(phase, cpu, part, attempts, admitted, taken, prompt) -> str:
    lines = [f'dynamo_engine_phase_seconds_total{{phase="{p}"}} {v}' for p, v in phase.items()]
    lines += [f'dynamo_engine_phase_cpu_seconds_total{{phase="{p}"}} {v}' for p, v in cpu.items()]
    lines += [
        f'dynamo_engine_phase_part_seconds_total{{part="{b}",phase="{a}"}} {v}'
        for (a, b), v in part.items()
    ]
    lines += [f'dynamo_admit_attempts_total{{outcome="{o}"}} {v}' for o, v in attempts.items()]
    if admitted:
        lines += [f'dynamo_admit_seconds_bucket{{le="+Inf"}} {admitted[0]}',
                  f"dynamo_admit_seconds_count {admitted[0]}",
                  f"dynamo_admit_seconds_sum {admitted[1]}"]
    lines += [f'dynamo_kv_pages_taken_total{{source="{s}"}} {v}' for s, v in taken.items()]
    lines += [f'dynamo_prompt_tokens_total{{source="{s}"}} {v}' for s, v in prompt.items()]
    return "# HELP x y\n" + "\n".join(lines) + "\n"


def ctx_of(before: str, after: str) -> dict:
    return {"prom_before": stats.parse_prometheus(before),
            "prom_after": stats.parse_prometheus(after)}


def test_the_counter_metrics_read_the_window_between_two_scrapes():
    before = scrape(
        {"admit": 1.0, "consume": 10.0, "assemble": 3.0, "launch": 4.0, "sync": 50.0, "park": 32.0},
        {"admit": 0.9, "consume": 8.0},
        {("admit", "schedule"): 0.2, ("admit", "hash"): 0.1, ("admit", "take"): 0.3,
         ("consume", "tokens"): 2.0, ("consume", "other"): 1.0, ("launch", "call"): 1.0},
        {"admitted": 10, "no_slot": 100, "no_pages": 0},
        (10, 0.5), {"free": 1000}, {"cached": 0, "computed": 20000},
    )
    after = scrape(
        {"admit": 3.0, "consume": 16.0, "assemble": 5.0, "launch": 6.0, "sync": 80.0, "park": 40.0},
        {"admit": 2.4, "consume": 11.0},
        # Parts and outcomes that first ran inside the window have no series before it.
        {("admit", "schedule"): 0.3, ("admit", "hash"): 0.5, ("admit", "match"): 0.1,
         ("admit", "take"): 1.3, ("admit", "register"): 0.2, ("admit", "bind"): 0.1,
         ("admit", "record"): 0.05, ("admit", "other"): 0.05,
         ("consume", "tokens"): 3.0, ("consume", "first_token"): 0.5, ("consume", "other"): 1.5,
         ("assemble", "plan"): 0.4, ("assemble", "lookup"): 0.1, ("launch", "call"): 2.0},
        {"admitted": 30, "no_slot": 130, "no_pages": 10, "rejected": 0, "dropped": 0},
        (30, 2.3), {"free": 1300, "evicted": 700}, {"cached": 5000, "computed": 55000},
    )
    ctx = ctx_of(before, after)
    # All phases moved 50 s in the window, admit 2 of them.
    assert metric("admit_loop_share", ctx) == pytest.approx(2 / 50 * 100)
    want = {"schedule": 0.1, "hash": 0.4, "match": 0.1, "take": 1.0, "register": 0.2, "bind": 0.1}
    for part, seconds in want.items():
        assert metric(f"admit_{part}_loop_share", ctx) == pytest.approx(seconds / 50 * 100), part
    # The seven and other are the phase: its own pieces sum to it.
    pieces = sum(metric(f"admit_{p}_loop_share", ctx) for p in ADMIT_PARTS)
    assert pieces + metric("admit_record_loop_share", ctx) + 0.05 / 50 * 100 == pytest.approx(
        metric("admit_loop_share", ctx)
    )
    assert metric("admit_offcpu_share", ctx) == pytest.approx((1 - 1.5 / 2) * 100)
    assert metric("admit_mean_ms", ctx) == pytest.approx(1.8 / 20 * 1000)
    assert metric("admit_us_per_prompt_token", ctx) == pytest.approx(2 / 40000 * 1e6)
    # 20 admissions, 30 turns behind a full batch, 10 behind a dry pool.
    assert metric("admit_attempts_per_admission", ctx) == pytest.approx(60 / 20)
    assert metric("admit_evicted_take_share", ctx) == pytest.approx(700 / 1000 * 100)
    assert metric("consume_tokens_loop_share", ctx) == pytest.approx(1 / 50 * 100)
    assert metric("consume_first_token_loop_share", ctx) == pytest.approx(0.5 / 50 * 100)
    assert metric("consume_other_loop_share", ctx) == pytest.approx(0.5 / 50 * 100)
    assert metric("assemble_plan_loop_share", ctx) == pytest.approx(0.4 / 50 * 100)
    assert metric("assemble_lookup_loop_share", ctx) == pytest.approx(0.1 / 50 * 100)
    assert metric("launch_call_loop_share", ctx) == pytest.approx(1 / 50 * 100)
    # The same text at both ends: a window in which nothing moved reads nothing.
    still = ctx_of(after, after)
    for name in (*COUNTER_METRICS, *ALREADY_BOOKED):
        assert metric(name, still) is None, name


def test_the_parent_reads_what_it_books_and_nothing_of_the_new_series():
    """The program before PR 65 under this benchmark: ``schedule`` is all
    of ``admit_next`` there, the four new parts read 0, the three new
    series nothing, and nothing raises."""
    def old(t):
        return scrape(
            {"admit": 1.0 * t, "consume": 10.0 * t, "sync": 50.0 * t},
            {"admit": 0.5 * t, "consume": 8.0 * t},
            {("admit", "schedule"): 0.8 * t, ("admit", "bind"): 0.1 * t,
             ("admit", "record"): 0.05 * t, ("admit", "other"): 0.05 * t,
             ("consume", "tokens"): 2.0 * t, ("consume", "other"): 1.0 * t,
             ("assemble", "plan"): 0.4 * t, ("assemble", "lookup"): 0.1 * t,
             ("launch", "call"): 1.0 * t},
            {}, None, {}, {"cached": 0, "computed": 20000 * t},
        )

    ctx = ctx_of(old(1), old(2))
    for name in ("admit_mean_ms", "admit_attempts_per_admission", "admit_evicted_take_share"):
        assert metric(name, ctx) is None, name
    for part in ("hash", "match", "take", "register"):
        assert metric(f"admit_{part}_loop_share", ctx) == 0.0
    assert metric("admit_schedule_loop_share", ctx) == pytest.approx(0.8 / 61 * 100)
    assert metric("admit_loop_share", ctx) == pytest.approx(1 / 61 * 100)
    assert metric("admit_offcpu_share", ctx) == pytest.approx(50.0)
    assert metric("admit_us_per_prompt_token", ctx) == pytest.approx(50.0)
    for name in ALREADY_BOOKED:
        assert metric(name, ctx) is not None, name


# ------------------------------------------------------- a hand-made trace
# ``test_loop_parts.write_trace``'s device: busy [100,500) and [600,900) of
# [0,1000), host clock 46 ahead and 12 wide, so idle on the host's clock is
# [46,146), [546,646) and [946,1046). Every piece below is 12 or longer but
# sync's [140,146), which no phase is given.
PHASES = [
    (0, 100, "dyn/consume"), (100, 20, "dyn/assemble"), (120, 20, "dyn/launch"),
    (140, 390, "dyn/sync"), (530, 28, "dyn/intake"), (558, 92, "dyn/admit"),
    (650, 15, "dyn/kv_admin"), (665, 275, "dyn/sync"), (940, 20, "dyn/publish"),
    (960, 40, "dyn/consume"),
]
OLD_PARTS = [
    (40, 50, "dynpart/consume.finish"), (120, 14, "dynpart/launch.h2d"),
    (134, 6, "dynpart/launch.call"), (634, 16, "dynpart/admit.record"),
]
ADMIT_EVENTS = [
    (558, 12, "dynpart/admit.schedule"), (570, 14, "dynpart/admit.hash"),
    (584, 12, "dynpart/admit.match"), (596, 14, "dynpart/admit.take"),
    (610, 12, "dynpart/admit.register"), (622, 12, "dynpart/admit.bind"),
]
OLDER_IDLE = (
    "idle_admit_record_share", "idle_host_work_share", "idle_sync_share",
    "idle_park_share", "idle_consume_finish_share", "idle_launch_h2d_share",
)


def test_idle_time_is_read_by_phase_and_by_part_of_admit(tmp_path, monkeypatch):
    ctx = as_a_run(tmp_path, monkeypatch, PHASES + OLD_PARTS + ADMIT_EVENTS)
    split = xm.current()["idle"]
    assert (split["window_ns"], split["idle_ns"]) == (1000, 300)
    assert split["by_phase"] == {
        "consume": 54 + 40, "assemble": 20, "launch": 20, "intake": 12, "admit": 88,
        "publish": 14,
    }
    assert metric("idle_admit_share", ctx) == pytest.approx(8.8)
    assert metric("idle_consume_share", ctx) == pytest.approx(9.4)
    assert metric("idle_assemble_share", ctx) == pytest.approx(2.0)
    assert metric("idle_launch_share", ctx) == pytest.approx(2.0)
    assert metric("idle_loop_rest_share", ctx) == pytest.approx(2.6)
    # One split: the five are idle_host_work_share, to the nanosecond.
    assert sum(metric(name, ctx) for name in IDLE_BY_PHASE) == pytest.approx(
        metric("idle_host_work_share", ctx)
    )
    want = {"schedule": 12, "hash": 14, "match": 12, "take": 14, "register": 12, "bind": 12}
    for part, ns in want.items():
        assert metric(f"idle_admit_{part}_share", ctx) == pytest.approx(ns / 10), part
    # The parts of a phase are pieces of it: the six and record's at most admit's.
    pieces = sum(metric(f"idle_admit_{p}_share", ctx) for p in ADMIT_PARTS)
    assert pieces + metric("idle_admit_record_share", ctx) == pytest.approx(8.8)
    assert pieces + metric("idle_admit_record_share", ctx) <= metric("idle_admit_share", ctx) + 1e-9
    # Without a trace (a CPU rehearsal) nothing is read.
    for name in TRACE_METRICS:
        assert metric(name, {"trace": None}) is None, name


def test_the_older_idle_readings_are_the_same_with_and_without_admits_events(tmp_path, monkeypatch):
    with_events = as_a_run(tmp_path / "a", monkeypatch, PHASES + OLD_PARTS + ADMIT_EVENTS)
    new = {name: metric(name, with_events) for name in OLDER_IDLE}
    parts_with = xp.current()["by_part"]
    without = as_a_run(tmp_path / "b", monkeypatch, PHASES + OLD_PARTS)
    old = {name: metric(name, without) for name in OLDER_IDLE}
    assert new == old
    assert old["idle_admit_record_share"] == pytest.approx(1.2)
    assert old["idle_park_share"] == 0.0 and old["idle_sync_share"] == 0.0
    assert {k: v for k, v in parts_with.items() if not k.startswith("admit.")} | {
        "admit.record": 12
    } == xp.current()["by_part"]
    # A program whose trace has no event of the new names (the parent):
    # the part readers read 0 there, as of a part that met no gap, and
    # the phase readers what they read.
    for part in ("hash", "match", "take", "register", "bind"):
        assert metric(f"idle_admit_{part}_share", without) == 0.0
    assert metric("idle_admit_share", without) == pytest.approx(8.8)


def test_a_trace_without_parts_reads_the_phases_alone(tmp_path, monkeypatch):
    ctx = as_a_run(tmp_path, monkeypatch, PHASES)
    for part in ADMIT_PARTS:
        assert metric(f"idle_admit_{part}_share", ctx) is None
    assert sum(metric(name, ctx) for name in IDLE_BY_PHASE) == pytest.approx(24.8)
    # And one file apart: the xplane the older tests write has no such names.
    path = write_trace(tmp_path / "x.xplane.pb", PHASES)
    assert xp.parse(path).get("python3") is None


# -------------------------------------------------- in the harness, on the CPU
def test_traced_rehearsal_prints_the_listed_counter_metrics():
    from test_rehearsal import result_of, run_cell

    bench = load(ROOT, "BENCHMARK.json")
    listed = [m["name"] for m in bench["per_layer"] if m["name"] in NEW]
    out = result_of(*run_cell("tiny-qwen3.rehearsal-sessions", 1, seconds=6))
    m = out["metrics"]
    assert out["correct"] is True
    for name in listed:
        if name in TRACE_METRICS:
            assert name not in m, name  # no device, no trace
        else:
            assert name in m, name
    if "admit_attempts_per_admission" in listed:
        assert m["admit_attempts_per_admission"]["value"] >= 1.0
    if "admit_offcpu_share" in listed:
        # The CPU clock of a sandbox ticks coarsely: a number, not its sign.
        assert m["admit_offcpu_share"]["value"] < 100.0
