"""``lib/xplane_meta.py`` and the three readers over it: on hand-made
tuples, and on the engine trace recorded on a TPU v5e beside this file
(``rehearse/record_engine_trace.py``: the ``tiny-qwen3`` engine, a few
mixed dispatches and chained decode windows, then the idle path)."""

import importlib
import os
import shutil

import pytest

from lib import stats
from lib import trace_reduce as tr
from lib import xplane_meta as xm
from lib.xplane_meta import Op

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACE = os.path.join(DATA, "engine_trace.xplane.pb")
PHASES = {
    "intake", "kv_admin", "admit", "assemble", "launch", "sync", "consume",
    "publish", "park",
}
POOL = {"layers": "4", "pages": "64", "page_size": "8", "width": "32", "dtype": "bfloat16"}


def read(kind, ctx, **spec):
    return importlib.import_module(f"readers.{kind}").read(spec, ctx)


# ------------------------------------------------------------ HLO strings
def test_split_hlo_takes_the_result_type_without_layouts_and_the_opcode():
    line = (
        "%copy.9 = bf16[256,512]{1,0:T(8,128)(2,1)S(1)} copy(bf16[256,512]{1,0} %p), "
        'metadata={op_name="x"}'
    )
    assert xm.split_hlo(line) == ("bf16[256,512]", "copy")
    line = (
        "%while = (s32[]{:T(128)}, bf16[4,64,8,32]{3,2,1,0:T(8,128)(2,1)}) "
        "while((s32[], bf16[4,64,8,32]) %t), condition=%c, body=%b"
    )
    result, opcode = xm.split_hlo(line)
    assert opcode == "while"
    assert xm.result_arrays(result) == [("s32", ()), ("bf16", (4, 64, 8, 32))]
    assert xm.split_hlo("jit_step(123)") == ("", "")


# ----------------------------------------------------------- clock offset
def test_clock_offset_lies_between_launch_and_completion_bounds():
    # Device clock 1000 behind the host's: a run at device [5000, 6000) was
    # enqueued at host 5900 (so offset >= 900) and its callbacks ran at host
    # 7100 (offset <= 1100). A second pair is looser on both sides.
    runs = [(5000, 1000, "jit_a", 1), (9000, 1000, "jit_a", 2)]
    host = [
        (5900, 10, "DoEnqueueProgram", 1), (7100, 10, "CompleteCallbacks", 1),
        (9500, 10, "DoEnqueueProgram", 2), (11400, 10, "CompleteCallbacks", 2),
        (1, 1, "CompleteCallbacks", 99),  # a run outside the trace: no pair
    ]
    assert xm.clock_offset(runs, host) == {"ns": 1000, "width_ns": 200, "pairs": 4}


def test_clock_offset_needs_a_pair_on_both_sides():
    runs = [(0, 100, "p", 1), (1000, 100, "p", 2)]
    complete = [(640, 5, "CompleteCallbacks", 1), (1600, 5, "CompleteCallbacks", 2)]
    assert xm.clock_offset(runs, complete) is None
    assert xm.clock_offset(runs, []) is None
    assert xm.clock_offset([(0, 1, "p", None)], complete) is None
    got = xm.clock_offset(runs, complete + [(480, 5, "DoEnqueueProgram", 1)])
    assert got == {"ns": 490, "width_ns": 20, "pairs": 3}


# --------------------------------------------------------- classification
def op(name, opcode, result, scope="", program="jit_ragged_window", start=0, dur=10):
    return Op(start, dur, name, opcode, result, scope, program)


def test_shipped_rules_class_by_name_shape_and_scope():
    rules, pools = xm.load_rules(), xm.pool_shapes(POOL)
    assert pools == {(4, 64, 8, 32), (1, 64, 8, 32), (64, 8, 32)}
    base = "jit(ragged_window)/while/body/closed_call/while/body/closed_call"

    def cls(o):
        return xm.classify(o, rules, pools)

    kernel = op("ragged_paged_attention.8[tpu_custom_call]", "custom-call",
                "bf16[32,32,128]", f"{base}/attend/jit(ragged_paged_attention)/pallas_call")
    assert cls(kernel) == "attn_kernel"
    # Whole-pool copy with no scope path at all: the shape alone decides.
    assert cls(op("copy.67", "copy", "bf16[4,64,8,32]")) == "kv_pool_copy"
    assert cls(op("bitcast_dynamic-update-slice_fusion.4", "fusion", "bf16[4,64,8,32]",
                  f"{base}/dynamic_update_slice")) == "kv_pool_copy"
    assert cls(op("dynamic-slice_bitcast_fusion.5", "fusion", "bf16[64,8,32]",
                  "jit(ragged_window)/while/body/squeeze")) == "kv_pool_copy"
    assert cls(op("fusion.9", "fusion", "(bf16[1,64,8,32], s32[])")) == "kv_pool_copy"
    # The in-place KV write has a pool-shaped result too: same class by
    # definition (it takes microseconds), with or without its scope.
    assert cls(op("fusion.169", "fusion", "bf16[64,8,32]", f"{base}/kv_write/scatter")) == "kv_pool_copy"
    # A while carries the pools in its result and moves nothing.
    assert cls(op("while.46", "while", "(s32[], bf16[4,64,8,32], bf16[4,64,8,32])")) == "other"
    # A gathered context is not a pool: same width, another page count.
    assert cls(op("copy.56", "copy", "bf16[2048,8,32]")) == "other"
    for scope, want in (
        ("ffn/dot_general", "ffn"), ("moe/ragged_dot", "moe"), ("qkv/rsqrt", "qkv"),
        ("attn_out/dot_general", "attn_out"), ("attend/gather", "attn_xla"),
    ):
        assert cls(op("fusion.1", "fusion", "bf16[32,1,64]", f"{base}/{scope}")) == want
    assert cls(op("f", "fusion", "f32[32,512]", "jit(ragged_window)/while/body/closed_call/logits/dot_general")) == "logits"
    # A collective books as one wherever it stands, either half of it, fused or not.
    assert cls(op("all-reduce.3", "all-reduce", "bf16[64,4096]", f"{base}/moe/add")) == "collective"
    assert cls(op("all-gather-start.1", "all-gather-start", "bf16[64,32000]", f"{base}/logits/x")) == "collective"
    assert cls(op("all-reduce-scatter.2", "fusion", "bf16[64,1024]", f"{base}/attn_out/dot_general")) == "collective"
    # XLA's ragged_dot custom call keeps no scope path; its name says what it is.
    assert cls(op("ragged-dot-none.2[tpu_custom_call]", "custom-call", "bf16[128,3584]")) == "moe"
    assert cls(op("f", "fusion", "s32[32]", "jit(ragged_mixed)/sample/argmax")) == "sample"
    # A name that merely contains a scope's letters is not that scope.
    assert cls(op("f", "fusion", "bf16[8]", "jit(x)/diffn/add")) == "other"
    assert cls(op("f", "fusion", "bf16[8]")) == "other"
    # Without the pools' geometry nothing is pool-shaped.
    assert xm.classify(op("copy.67", "copy", "bf16[4,64,8,32]"), rules, set()) == "other"


def test_self_time_by_class_takes_children_out_of_their_parent():
    rules, pools = xm.load_rules(), xm.pool_shapes(POOL)
    ops = [
        op("while.1", "while", "(s32[], bf16[4,64,8,32])", start=0, dur=100),
        op("copy.1", "copy", "bf16[4,64,8,32]", start=10, dur=30),
        op("fusion.2", "fusion", "bf16[8,64]", "j/ffn/dot_general", start=50, dur=20),
        op("fusion.2", "fusion", "bf16[8,64]", "j/ffn/dot_general", start=200, dur=7,
           program="jit_ragged_mixed"),
    ]
    meta = {"chips": {0: {"ops": ops, "runs": []}}}
    assert xm.time_by(meta, lambda o: xm.classify(o, rules, pools)) == {
        "other": 50, "kv_pool_copy": 30, "ffn": 27,
    }
    assert xm.time_by(meta, lambda o: o.program) == {
        "jit_ragged_window": 100, "jit_ragged_mixed": 7,
    }


# ------------------------------------------------------------ idle gaps
def test_cut_gaps_books_each_piece_once_and_short_pieces_to_no_phase():
    # Host clock = device clock + 1000. Device idle [0,100) and [500,560).
    phases = [(1000, 40, "consume"), (1040, 5, "publish"), (1045, 50, "launch"),
              (1400, 300, "sync")]
    by_phase, unattributed = xm.cut_gaps([(0, 100), (500, 60)], phases, 1000, 10)
    # [0,40) consume; [40,45) publish is shorter than the width; [45,95)
    # launch; [95,100) in no phase; [500,560) inside sync.
    assert by_phase == {"consume": 40, "launch": 50, "sync": 60}
    assert unattributed == 5 + 5
    assert sum(by_phase.values()) + unattributed == 160


def synthetic_meta():
    ops = [op("fusion.1", "fusion", "bf16[8]", "j/ffn/x", start=100, dur=400),
           op("copy.1", "copy", "bf16[4,64,8,32]", start=600, dur=300,
              program="jit_ragged_mixed")]
    meta = {
        "chips": {0: {"ops": ops, "runs": [(100, 400, "jit_ragged_window", 1),
                                           (600, 300, "jit_ragged_mixed", 2)]}},
        # Host clock 50 ahead. The slice runs host-side from 0 to 1000.
        "host_all": [(0, 1000)],
        "phases": {"python3": [(0, 140, "park"), (140, 20, "launch"), (160, 470, "sync"),
                               (630, 30, "consume"), (660, 340, "sync")],
                   "other-thread": [(0, 1, "park")]},
        "host_runs": [(140, 5, "DoEnqueueProgram", 1), (552, 5, "CompleteCallbacks", 1),
                      (640, 5, "DoEnqueueProgram", 2), (958, 5, "CompleteCallbacks", 2)],
    }
    meta["idle"] = xm.idle_by_phase(meta)
    return meta


def test_idle_shares_and_unattributed_add_up_to_the_idle_share(monkeypatch):
    meta = synthetic_meta()
    split = meta["idle"]
    assert split["thread"] == "python3"
    assert split["offset"] == {"ns": 46, "width_ns": 12, "pairs": 4}
    # Window [0, 1000): idle [0,100), [500,600), [900,1000) on the device.
    assert (split["window_ns"], split["idle_ns"]) == (1000, 300)
    monkeypatch.setattr(xm, "current", lambda: meta)
    ctx = {"trace": {"chips": {0: {"idle_share": 0.3}}}, "prom_after": {}}
    shares = {
        name: read("trace_idle_by_phase", ctx, phases=phases)
        for name, phases in (
            ("host_work", ["intake", "kv_admin", "admit", "assemble", "launch", "consume", "publish"]),
            ("sync", ["sync"]), ("park", ["park"]), ("unattributed", ["unattributed"]),
        )
    }
    assert sum(shares.values()) == pytest.approx(read("trace_idle_share", ctx))
    assert shares["park"] == pytest.approx(9.4)  # device [0,94) is host [46,140)
    assert shares["host_work"] == pytest.approx(1.6)  # consume, [584,600) on the device
    assert shares["sync"] == pytest.approx(13.8)  # [500,584) and [900,954)
    # [94,100) is launch but shorter than the offset is wide; [954,1000) is
    # after the host's last phase.
    assert shares["unattributed"] == pytest.approx(5.2)
    # The other two readers, on the same operations.
    pool_ctx = {**ctx, "prom_after": {("dynamo_kv_pool_info", tuple(sorted(POOL.items()))): 1.0}}
    assert read("trace_class_share", pool_ctx, **{"class": "kv_pool_copy"}) == pytest.approx(300 / 7)
    assert read("trace_class_share", pool_ctx, **{"class": "ffn"}) == pytest.approx(400 / 7)
    assert read("trace_program_share", ctx, pattern="^jit_ragged_mixed") == pytest.approx(300 / 7)


def test_readers_return_nothing_where_the_program_gives_nothing(monkeypatch):
    meta = synthetic_meta()
    monkeypatch.setattr(xm, "current", lambda: meta)
    traced = {"trace": {"chips": {0: {"idle_share": 0.3}}}, "prom_after": {}, "prom_before": {}}
    # No dynamo_kv_pool_info (the parent): the pool class cannot be told.
    assert read("trace_class_share", traced, **{"class": "kv_pool_copy"}) is None
    assert read("trace_class_share", traced, **{"class": "ffn"}) is not None
    # No dyn/* annotations (the parent): nothing to cut the gaps along.
    bare = {**meta, "phases": {}}
    bare["idle"] = xm.idle_by_phase(bare)
    monkeypatch.setattr(xm, "current", lambda: bare)
    assert read("trace_idle_by_phase", traced, phases=["sync"]) is None
    assert read("trace_program_share", traced, pattern="^jit_ragged_mixed") is not None
    # No trace at all (a CPU rehearsal).
    for kind, spec in (("trace_class_share", {"class": "ffn"}),
                       ("trace_program_share", {"pattern": "x"}),
                       ("trace_idle_by_phase", {"phases": ["sync"]})):
        assert read(kind, {"trace": None}, **spec) is None
    # A counter the program does not export has not read 0.
    spec = {"metric": "dynamo_jax_compile_events_total", "match": {"stage": "lower"}}
    assert read("prom_counter_seen_delta", traced, **spec) is None
    key = ("dynamo_jax_compile_events_total", (("stage", "lower"),))
    seen = {"prom_before": {key: 40.0}, "prom_after": {key: 42.0}}
    assert read("prom_counter_seen_delta", seen, **spec) == 2.0


# --------------------------------------------------- the recorded trace
@pytest.fixture(scope="module")
def recorded():
    return xm.parse(TRACE)


def test_recorded_trace_agrees_with_trace_reduce_on_every_event(recorded):
    plain = tr.load(TRACE)
    assert sorted(recorded["chips"]) == sorted(plain["devices"]) == [0]
    assert sorted((o.start, o.dur, o.name) for o in recorded["chips"][0]["ops"]) == sorted(
        plain["devices"][0]
    )
    assert sorted(recorded["host_all"]) == sorted((s, d) for s, d, _ in plain["host"])
    assert os.path.getsize(TRACE) < 400_000


def test_recorded_trace_has_programs_scopes_and_phases(recorded):
    chip = recorded["chips"][0]
    programs = {r[2] for r in chip["runs"]}
    assert {"jit_ragged_mixed", "jit_ragged_window", "jit_penalty_init_row"} <= programs
    assert all(r[3] is not None for r in chip["runs"])
    by_program = xm.time_by(recorded, lambda o: o.program)
    assert set(by_program) - {""} <= programs and by_program["jit_ragged_window"] > 0
    scopes = {o.scope for o in chip["ops"]}
    for name in ("qkv", "kv_write", "attend", "attn_out", "ffn", "logits"):
        assert any(f"/{name}/" in s for s in scopes), name
    assert any(s.startswith("jit(ragged_window)/while/body/") for s in scopes)
    (thread,) = recorded["phases"]  # only the engine loop's thread annotates
    names = {p for _, _, p in recorded["phases"][thread]}
    assert {"assemble", "launch", "sync", "consume", "park"} <= names <= PHASES
    phases = sorted(recorded["phases"][thread])
    for (s0, d0, _), (s1, _, _) in zip(phases, phases[1:]):
        assert s0 + d0 <= s1  # phases do not nest or overlap


def test_recorded_trace_clock_offset_and_idle_split(recorded, tmp_path, monkeypatch):
    off = xm.clock_offset(recorded["chips"][0]["runs"], recorded["host_runs"])
    # TPU v5e, jax 0.9: the host's clock ran 1-2 ms ahead of the device's.
    assert off["pairs"] >= 16 and 0 < off["width_ns"] < 600_000
    assert 200_000 < off["ns"] < 5_000_000
    # Through find_trace/current, as a run does.
    run_trace = tmp_path / "tiny-qwen3" / "run" / "trace" / "plugins" / "profile" / "t"
    run_trace.mkdir(parents=True)
    shutil.copy(TRACE, run_trace / "host.xplane.pb")
    monkeypatch.setattr(xm, "WORK", str(tmp_path))
    monkeypatch.setattr(xm, "_CACHE", {})
    assert xm.find_trace() == str(run_trace / "host.xplane.pb")
    with open(os.path.join(DATA, "engine_trace.metrics.txt")) as f:
        prom = stats.parse_prometheus(f.read())
    assert xm.pool_info({"prom_after": prom}) == {
        "layers": "2", "pages": "64", "page_size": "8", "width": "32", "dtype": "bfloat16",
    }
    ctx = {"trace": tr.reduce(tr.load(TRACE)), "prom_after": prom}
    idle = read("trace_idle_share", ctx)
    parts = {
        k: read("trace_idle_by_phase", ctx, phases=v)
        for k, v in {
            "host": ["intake", "kv_admin", "admit", "assemble", "launch", "consume", "publish"],
            "sync": ["sync"], "park": ["park"], "unattributed": ["unattributed"],
        }.items()
    }
    assert sum(parts.values()) == pytest.approx(idle, abs=1e-6)
    assert parts["park"] > parts["sync"] > 0  # a tiny model: the device mostly waits
    assert parts["unattributed"] < idle / 5
    # Device time by class and by program.
    shares = {
        c: read("trace_class_share", ctx, **{"class": c})
        for c in ("kv_pool_copy", "ffn", "qkv", "attn_out", "attn_xla", "logits", "other", "attn_kernel")
    }
    assert sum(shares.values()) == pytest.approx(100.0, abs=1.0)  # sample is the rest
    assert shares["attn_kernel"] == 0.0  # head_dim 16: the engine took the XLA path
    assert shares["kv_pool_copy"] > 5 and shares["attn_xla"] > 5 and shares["ffn"] > 2
    window = read("trace_program_share", ctx, pattern="^jit_ragged_window")
    mixed = read("trace_program_share", ctx, pattern="^jit_ragged_mixed")
    assert window > mixed > 0 and window + mixed < 100.0


# -------------------------------------------------- in the harness, on the CPU
def test_traced_rehearsal_prints_the_new_counter_metrics_and_no_device_metric():
    from test_rehearsal import result_of, run_cell

    out = result_of(*run_cell("tiny-qwen3.rehearsal-sessions", 1, seconds=6))
    m = out["metrics"]
    assert out["correct"] is True
    for name in ("window_dispatch_mean_ms", "mixed_dispatch_mean_ms", "loop_host_work_share"):
        assert m[name]["value"] > 0, name
    assert m["decode_window_rows_mean"]["value"] >= m["decode_rows_mean"]["value"]
    assert 0 < m["loop_host_work_share"]["value"] < 100
    assert m["prefix_hit_token_share"]["value"] > 30
    assert m["preemptions_in_window"]["value"] == 0
    assert m["relowerings_in_window"]["value"] >= 0
    assert not {
        "kv_pool_copy_time_share", "mixed_program_time_share", "idle_host_work_share",
        "idle_sync_share", "idle_park_share",
    } & set(m)
