"""What ``trace_reduce.load`` drops from a profiler trace, read a second
time: each device operation's opcode, result type, scope path and the
program run that contains it; the engine loop's ``dyn/<phase>`` events in
the host plane; and how far the device's clock is from the host's.

``jax.profiler.ProfileData`` exposes event-level stats only. The scope
path (``jit(ragged_window)/while/body/closed_call/ffn/dot_general``, the
``jax.named_scope`` names of ``models/llama.py``) is in the plane's
*event metadata*, as the stat ``tf_op``, beside ``hlo_category`` and
``shape_with_layout``. So the ``.xplane.pb`` is decoded here with
``google.protobuf`` and a schema declared in this module — the field
numbers of the public ``xplane.proto`` (tsl/profiler/protobuf) — and not
through a TensorFlow or xprof import, which a chip host may lack.

Times are nanoseconds on the same base ``trace_reduce.load`` uses
(``line.timestamp_ns + offset_ps / 1000``), so intervals computed here
and there agree. Device lines carry the device's clock and host lines
the host's; ``clock_offset`` estimates the difference from the
``run_id`` that a device's ``XLA Modules`` events share with the host's
launch and ``CompleteCallbacks`` events.

Everything that classifies is pure (tuples and dicts in, numbers out)
so that it can be checked on synthetic input; ``parse`` turns a file
into that input, and ``current`` finds and parses a run's trace once a
process.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
from collections import namedtuple

from lib import trace_reduce

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORK = os.path.join(HERE, ".work")
CLASSES_FILE = os.path.join(HERE, "trace_classes.json")
MODULES_LINE = "XLA Modules"
_STAT_VALUES = ("str_value", "int64_value", "uint64_value", "double_value")
PHASE_PREFIX = "dyn/"
HOST_ENQUEUE, HOST_COMPLETE = "DoEnqueueProgram", "CompleteCallbacks"

# One device operation: times on the device's clock; ``name`` as
# ``trace_reduce.short_name`` spells it; ``result`` without layouts
# (``bf16[36,1152,16,1024]`` or a tuple of such); ``scope`` the tf_op path
# ("" where the compiler kept none); ``program`` the jitted program's name
# (``jit_ragged_window``), "" if no run contains the operation.
Op = namedtuple("Op", "start dur name opcode result scope program")


# ---------------------------------------------------------------- schema
def _schema():
    """Message classes for the subset of ``xplane.proto`` read here.
    A ``map<int64, M>`` field is declared as what it is on the wire: a
    repeated message with ``key`` = 1 and ``value`` = 2."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    scalar = {
        "int64": F.TYPE_INT64, "uint64": F.TYPE_UINT64, "double": F.TYPE_DOUBLE,
        "string": F.TYPE_STRING, "bytes": F.TYPE_BYTES,
    }
    pkg = "dynamo_bench_xplane"
    messages = {
        "XStat": [
            ("metadata_id", 1, "int64"), ("double_value", 2, "double"),
            ("uint64_value", 3, "uint64"), ("int64_value", 4, "int64"),
            ("str_value", 5, "string"), ("bytes_value", 6, "bytes"),
            ("ref_value", 7, "uint64"),
        ],
        "XEvent": [
            ("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"),
            ("duration_ps", 3, "int64"), ("stats", 4, "*XStat"),
            ("num_occurrences", 5, "int64"),
        ],
        "XLine": [
            ("id", 1, "int64"), ("name", 2, "string"), ("timestamp_ns", 3, "int64"),
            ("events", 4, "*XEvent"), ("duration_ps", 9, "int64"),
            ("display_id", 10, "int64"), ("display_name", 11, "string"),
        ],
        "XEventMetadata": [
            ("id", 1, "int64"), ("name", 2, "string"), ("metadata", 3, "bytes"),
            ("display_name", 4, "string"), ("stats", 5, "*XStat"),
        ],
        "XStatMetadata": [
            ("id", 1, "int64"), ("name", 2, "string"), ("description", 3, "string"),
        ],
        "EventMetadataEntry": [("key", 1, "int64"), ("value", 2, "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, "int64"), ("value", 2, "XStatMetadata")],
        "XPlane": [
            ("id", 1, "int64"), ("name", 2, "string"), ("lines", 3, "*XLine"),
            ("event_metadata", 4, "*EventMetadataEntry"),
            ("stat_metadata", 5, "*StatMetadataEntry"), ("stats", 6, "*XStat"),
        ],
        "XSpace": [("planes", 1, "*XPlane")],
    }
    fd = descriptor_pb2.FileDescriptorProto(
        name=f"{pkg}.proto", package=pkg, syntax="proto2"  # HasField on scalars
    )
    for name, fields in messages.items():
        msg = fd.message_type.add(name=name)
        for fname, number, kind in fields:
            repeated, kind = kind.startswith("*"), kind.lstrip("*")
            f = msg.field.add(
                name=fname, number=number,
                label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL,
            )
            if kind in scalar:
                f.type = scalar[kind]
            else:
                f.type, f.type_name = F.TYPE_MESSAGE, f".{pkg}.{kind}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName(f"{pkg}.XSpace"))


# ----------------------------------------------------------- HLO strings
_LAYOUT = re.compile(r"\{[^{}]*\}")
_ARRAY = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_MODULE = re.compile(r"^(.*?)\((\d+)\)$")


def split_hlo(line: str) -> tuple[str, str]:
    """``%copy.9 = bf16[8,4]{1,0:T(8,128)} copy(bf16[8,4]{1,0} %p), ...``
    -> (``bf16[8,4]``, ``copy``): the result type without layouts and the
    opcode. The type ends at the first space outside every bracket."""
    _, _, rest = line.partition(" = ")
    depth, end = 0, len(rest)
    for i, ch in enumerate(rest):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            end = i
            break
    result = _LAYOUT.sub("", rest[:end])
    opcode = rest[end + 1:].split("(", 1)[0].strip()
    return result, opcode


def result_arrays(result: str) -> list[tuple[str, tuple[int, ...]]]:
    """``(bf16[2,3], s32[])`` -> ``[("bf16", (2, 3)), ("s32", ())]``"""
    return [
        (dt, tuple(int(d) for d in dims.split(",") if d))
        for dt, dims in _ARRAY.findall(result)
    ]


# ----------------------------------------------------------------- parse
def parse(path: str) -> dict:
    """``{"chips": {chip: {"ops": [Op], "runs": [(start, dur, program,
    run_id)]}}, "host_all": [(start, dur)], "phases": {thread: [(start,
    dur, phase)]}, "host_runs": [(start, dur, event name, run_id)]}``"""
    space = _schema()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = {"chips": {}, "host_all": [], "phases": {}, "host_runs": []}
    for plane in space.planes:
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        metas = {e.key: e.value for e in plane.event_metadata}

        def stats_of(holder, names=stat_names):
            got = {}
            for st in holder.stats:
                key = names.get(st.metadata_id)
                if key is None:
                    continue
                for field in _STAT_VALUES:
                    if st.HasField(field):
                        got[key] = getattr(st, field)
                        break
                else:
                    if st.HasField("ref_value"):
                        got[key] = names.get(st.ref_value, "")
            return got

        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m:
            chip = out["chips"].setdefault(int(m.group(1)), {"ops": [], "runs": []})
            programs: dict[str, str] = {}  # program_id -> program name
            for line in plane.lines:
                if line.name != MODULES_LINE:
                    continue
                for ev in line.events:
                    name = metas[ev.metadata_id].name
                    mm = _MODULE.match(name)
                    program = mm.group(1) if mm else name
                    if mm:
                        programs[mm.group(2)] = program
                    chip["runs"].append((
                        _start_ns(line, ev), ev.duration_ps // 1000, program,
                        stats_of(ev).get("run_id"),
                    ))
            described: dict[int, tuple] = {}  # metadata id -> fields of Op
            for line in plane.lines:
                if line.name != trace_reduce.OPS_LINE:
                    continue
                for ev in line.events:
                    about = described.get(ev.metadata_id)
                    if about is None:
                        meta = metas[ev.metadata_id]
                        st = stats_of(meta)
                        result, opcode = split_hlo(meta.name)
                        about = described[ev.metadata_id] = (
                            trace_reduce.short_name(meta.name), opcode, result,
                            str(st.get("tf_op", "")).rstrip(":"),
                            programs.get(str(st.get("program_id", "")), ""),
                        )
                    chip["ops"].append(
                        Op(_start_ns(line, ev), ev.duration_ps // 1000, *about)
                    )
        elif plane.name == trace_reduce.HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    meta = metas[ev.metadata_id]
                    start, dur = _start_ns(line, ev), ev.duration_ps // 1000
                    out["host_all"].append((start, dur))
                    if meta.name.startswith(PHASE_PREFIX):
                        out["phases"].setdefault(line.name, []).append(
                            (start, dur, meta.name[len(PHASE_PREFIX):])
                        )
                    elif ev.stats:
                        run_id = stats_of(ev).get("run_id")
                        if run_id is not None:
                            out["host_runs"].append((start, dur, meta.name, run_id))
    return out


def _start_ns(line, ev) -> int:
    return line.timestamp_ns + ev.offset_ps // 1000


# ---------------------------------------------------------- clock offset
def clock_offset(device_runs, host_runs) -> dict | None:
    """Host clock minus device clock, from pairs that share a ``run_id``.

    A program starts on the device after the host began to enqueue it
    (``DoEnqueueProgram``), and ends before the host ran its completion
    callbacks (``CompleteCallbacks``). With ``d`` the device's times and
    ``h`` the host's, every enqueue gives a lower bound ``h_enqueue_start
    - d_start`` and every completion an upper bound ``h_complete_start -
    d_end``. The offset lies between the largest lower bound (a program
    enqueued on an idle device starts at once) and the smallest upper
    bound (the promptest callback): ``ns`` is the midpoint of that
    interval and ``width_ns`` its length. None unless both sides have a
    pair."""
    by_id = {run_id: (s, d) for s, d, _p, run_id in device_runs if run_id is not None}
    lower, upper = [], []
    for s, _d, name, run_id in host_runs:
        if run_id not in by_id:
            continue
        ds, dd = by_id[run_id]
        if name == HOST_ENQUEUE:
            lower.append(s - ds)
        elif name == HOST_COMPLETE:
            upper.append(s - (ds + dd))
    if not upper or not lower:
        return None
    lo, hi = sorted((max(lower), min(upper)))
    return {"ns": (lo + hi) // 2, "width_ns": hi - lo, "pairs": len(lower) + len(upper)}


# -------------------------------------------------------- classification
def load_rules(path: str = CLASSES_FILE) -> list[dict]:
    with open(path) as f:
        rules = json.load(f)["rules"]
    for rule in rules:
        for key in ("program", "opcode", "scope", "name"):
            if key in rule:
                rule[key] = re.compile(rule[key])
    return rules


def pool_shapes(pool: dict | None) -> set[tuple[int, ...]]:
    """The shapes an operation's result has when it is a whole stacked KV
    pool or one layer's pool, from ``dynamo_kv_pool_info``'s labels."""
    if not pool:
        return set()
    L, P, S, W = (int(pool[k]) for k in ("layers", "pages", "page_size", "width"))
    return {(L, P, S, W), (1, P, S, W), (P, S, W)}


def classify(op: Op, rules: list[dict], pools: set) -> str:
    """The class of the first rule all of whose keys match."""
    for rule in rules:
        if any(
            key in rule and not rule[key].search(getattr(op, key))
            for key in ("program", "opcode", "scope", "name")
        ):
            continue
        if rule.get("result") == "pool" and not any(
            dims in pools for _dt, dims in result_arrays(op.result)
        ):
            continue
        return rule["class"]
    return "other"


def self_times(ops) -> list[tuple[Op, int]]:
    """Each operation with its self time: its duration less what the
    operations nested inside it take (``trace_reduce.self_time_ns``, kept
    by event and not by name)."""
    out: list[list] = []
    stack: list[list] = []  # [end, entry]
    for op in sorted(ops, key=lambda o: (o.start, -o.dur)):
        while stack and stack[-1][0] <= op.start:
            stack.pop()
        if stack:
            stack[-1][1][1] -= op.dur
        entry = [op, op.dur]
        out.append(entry)
        stack.append([op.start + op.dur, entry])
    return [(op, max(ns, 0)) for op, ns in out]


def time_by(meta: dict, key) -> dict[str, int]:
    """Self time summed over chips, grouped by ``key(op)``. Operations
    that differ only in when they ran share one ``key`` call: a trace has
    a hundred thousand events of a few hundred kinds."""
    kinds = meta.get("_self_by_kind")
    if kinds is None:
        kinds = meta["_self_by_kind"] = {}
        for chip in meta["chips"].values():
            for op, ns in self_times(chip["ops"]):
                kinds[op[2:]] = kinds.get(op[2:], 0) + ns
    total: dict[str, int] = {}
    for kind, ns in kinds.items():
        k = key(Op(0, 0, *kind))
        total[k] = total.get(k, 0) + ns
    return total


# ------------------------------------------------------- idle by phase
def idle_by_phase(meta: dict) -> dict | None:
    """Idle time of the idlest chip, cut along the engine thread's
    phases. Returns ``{"window_ns", "idle_ns", "by_phase": {phase: ns},
    "unattributed_ns", "offset"}`` or None without phases or an offset.
    The window and the gaps are ``trace_reduce.reduce``'s: first to last
    event of any device or host line."""
    chips = {c: v["ops"] for c, v in meta["chips"].items() if v["ops"]}
    if not chips or not meta["phases"]:
        return None
    thread = max(meta["phases"], key=lambda t: len(meta["phases"][t]))
    phases = sorted(meta["phases"][thread])
    runs = [r for v in meta["chips"].values() for r in v["runs"]]
    offset = clock_offset(runs, meta["host_runs"])
    if offset is None:
        return None
    lo = min(o.start for ops in chips.values() for o in ops)
    hi = max(o.start + o.dur for ops in chips.values() for o in ops)
    if meta["host_all"]:
        lo = min(lo, min(s for s, _ in meta["host_all"]))
        hi = max(hi, max(s + d for s, d in meta["host_all"]))
    as_events = {c: [(o.start, o.dur, "") for o in ops] for c, ops in chips.items()}
    worst = max(chips, key=lambda c: (hi - lo) - trace_reduce.union_ns(as_events[c]))
    gaps = trace_reduce.gaps_ns(as_events[worst], lo, hi)
    by_phase, unattributed = cut_gaps(gaps, phases, offset["ns"], offset["width_ns"])
    return {
        "window_ns": hi - lo, "idle_ns": sum(n for _, n in gaps), "by_phase": by_phase,
        "unattributed_ns": unattributed, "offset": offset, "thread": thread,
    }


def cut_gaps(gaps, phases, offset_ns: int, width_ns: int):
    """Cut each idle gap (device clock) along the phases (host clock):
    ``({phase: ns}, unattributed ns)``. A piece shorter than the offset's
    width could as well belong to the neighbouring phase and is not
    attributed; nor is idle time no phase covers."""
    by_phase: dict[str, int] = {}
    attributed, idle, at = 0, 0, 0
    for start, length in sorted(gaps):
        idle += length
        g0, g1 = start + offset_ns, start + length + offset_ns
        while at < len(phases) and phases[at][0] + phases[at][1] <= g0:
            at += 1
        k = at
        while k < len(phases) and phases[k][0] < g1:
            ps, pd, name = phases[k]
            piece = min(g1, ps + pd) - max(g0, ps)
            if piece > 0 and piece >= width_ns:
                by_phase[name] = by_phase.get(name, 0) + piece
                attributed += piece
            k += 1
    return by_phase, idle - attributed


# ------------------------------------------------------------- one run
_CACHE: dict[str, dict] = {}


def find_trace() -> str | None:
    """The newest ``.xplane.pb`` under ``benchmark/.work/*/run/trace``:
    ``run_cell.py`` empties a run's trace directory before it profiles,
    so the newest file is this run's."""
    found = glob.glob(
        os.path.join(WORK, "*", "run", "trace", "plugins", "profile", "*", "*.xplane.pb")
    )
    return max(found, key=os.path.getmtime) if found else None


def current() -> dict | None:
    """This run's trace, parsed once a process; None when there is none."""
    path = find_trace()
    if path is None:
        return None
    if path not in _CACHE:
        meta = parse(path)
        split = idle_by_phase(meta)
        meta["idle"] = split
        if split:
            off = split["offset"]
            print(
                f"[bench] trace clock offset (host - device): {off['ns']} ns, "
                f"width {off['width_ns']} ns, from {off['pairs']} run_id pairs; "
                f"engine thread {split['thread']!r}; idle {split['idle_ns']} ns of "
                f"{split['window_ns']} ns, unattributed {split['unattributed_ns']} ns",
                file=sys.stderr, flush=True,
            )
        _CACHE.clear()
        _CACHE[path] = meta
    return _CACHE[path]


def pool_info(ctx: dict) -> dict | None:
    """The labels of ``dynamo_kv_pool_info`` in the window's last scrape."""
    for (name, labels), _v in (ctx.get("prom_after") or {}).items():
        if name == "dynamo_kv_pool_info":
            return dict(labels)
    return None
