"""The named parts of the engine loop's phases in a profiler trace:
``dynpart/<phase>.<part>`` events (``telemetry/dispatch.py`` PHASE_PARTS)
in the host plane, on the line of the thread whose ``dyn/<phase>`` events
``xplane_meta`` cuts the idle gaps along.

``xplane_meta.parse`` keeps the ``dyn/`` events alone (a part lies inside
its phase, and ``cut_gaps`` takes what it is given for intervals that do
not overlap), so the file is read a second time here, for these names
only. The window, the chip and its idle gaps are ``idle_by_phase``'s,
the clock offset is the one it estimated, and the gaps are cut by the
same ``cut_gaps``: a part's idle time is a piece of its phase's.
"""

from __future__ import annotations

from lib import trace_reduce, xplane_meta

PART_PREFIX = "dynpart/"


def parse(path: str) -> dict[str, list[tuple[int, int, str]]]:
    """``{thread: [(start, dur, "<phase>.<part>")]}`` of the host plane,
    times as ``xplane_meta.parse`` gives them."""
    space = xplane_meta._schema()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out: dict[str, list] = {}
    for plane in space.planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        names = {e.key: e.value.name for e in plane.event_metadata}
        for line in plane.lines:
            for ev in line.events:
                name = names.get(ev.metadata_id, "")
                if name.startswith(PART_PREFIX):
                    out.setdefault(line.name, []).append((
                        xplane_meta._start_ns(line, ev), ev.duration_ps // 1000,
                        name[len(PART_PREFIX):],
                    ))
    return out


def idle_gaps(meta: dict) -> list[tuple[int, int]]:
    """The idle gaps ``xplane_meta.idle_by_phase`` cuts: of the idlest
    chip, inside the window from the first to the last event of any
    device or host line."""
    chips = {c: v["ops"] for c, v in meta["chips"].items() if v["ops"]}
    lo = min(o.start for ops in chips.values() for o in ops)
    hi = max(o.start + o.dur for ops in chips.values() for o in ops)
    if meta["host_all"]:
        lo = min(lo, min(s for s, _ in meta["host_all"]))
        hi = max(hi, max(s + d for s, d in meta["host_all"]))
    as_events = {c: [(o.start, o.dur, "") for o in ops] for c, ops in chips.items()}
    worst = max(chips, key=lambda c: (hi - lo) - trace_reduce.union_ns(as_events[c]))
    return trace_reduce.gaps_ns(as_events[worst], lo, hi)


def idle_by_part(meta: dict, parts: dict) -> dict | None:
    """``{"window_ns", "by_part": {"<phase>.<part>": ns}}``: the idle
    time of ``meta["idle"]`` (``idle_by_phase``'s split) that fell inside
    each part. None without that split or without a part event on the
    phases' thread (a program that has none)."""
    split = meta.get("idle")
    if not split:
        return None
    events = parts.get(split["thread"])
    if not events:
        return None
    gaps = idle_gaps(meta)
    if sum(n for _, n in gaps) != split["idle_ns"]:
        raise ValueError("these are not the gaps idle_by_phase cut")
    off = split["offset"]
    by_part, _rest = xplane_meta.cut_gaps(gaps, sorted(events), off["ns"], off["width_ns"])
    return {"window_ns": split["window_ns"], "by_part": by_part}


_CACHE: dict[str, dict | None] = {}


def current() -> dict | None:
    """``idle_by_part`` of this run's trace, read once a process."""
    path = xplane_meta.find_trace()
    meta = xplane_meta.current()
    if path is None or meta is None:
        return None
    if path not in _CACHE:
        _CACHE.clear()
        _CACHE[path] = idle_by_part(meta, parse(path))
    return _CACHE[path]
