"""Share of the traced slice in which the idlest chip ran nothing *and*
something off the engine loop's thread had the process: a chunk on its
way through the HTTP thread (``dynhttp/<part>`` events), a collection of
the cyclic collector (``dyngc/gen<n>``) or a whole-process stall
(``dynstall/<cause>``), on **any** line of the host plane
(``family: "http" | "gc" | "stall"``; ``telemetry/dispatch.py``
HttpChunkClock, ``telemetry/flight.py`` HostWitness).

The window, the chip, its idle gaps, the clock offset and its width are
``xplane_meta.idle_by_phase``'s (the gap list by ``lib/xplane_parts``),
and only the pieces of a gap that ``cut_gaps`` gives a phase count here
(``idle_pieces`` cuts by the same rule and is held to it). ``http`` and
``gc`` count inside the loop's ``sync`` alone: pieces of
``idle_sync_share``. ``stall`` counts inside any phase. No idle
nanosecond is claimed twice: a collection claims first, then a stall,
then a chunk (a collection holds the GIL, so the stall witness wakes
late behind it and a chunk's event stays open through it), each only of
the idle time it may count and the ones before it left. So a long
collection while the loop is in ``admit`` is no ``idle_sync_gc_share``
and all ``idle_stall_share`` (cause ``busy``), and together the three
are no more than ``idle_sync_share + idle_host_work_share +
idle_park_share``.

A ``dynstall/`` event is a mark at the late wake (a trace event cannot
be dated back): its ``late_ns`` says how far back the stall reaches.

One clock: every event of the three families is made by the call that
makes the ``dyn/`` events, so the offset that moves the engine thread's
events moves these. ``check_one_clock`` holds a trace to it: every
``dyngc/`` event lies among the other events of the host plane, not
before the first nor after the last, and on a line that has other events
of its own, among those (both within ``SLACK_NS``: a collection may be
the first thing a slice sees; another time base would be off by far more).

None when the program has none of this (no ``dynamo_http_chunks_total``
series: the parent) or ``trace_idle_by_phase`` reads None; 0.0 when it
has and no such event met an idle piece.
"""

from __future__ import annotations

import sys

from lib import trace_reduce, xplane_meta, xplane_parts
from lib.stats import sample_sum

FAMILIES = {"http": "dynhttp/", "gc": "dyngc/", "stall": "dynstall/"}
ORDER = ("gc", "stall", "http")  # precedence where events overlap
SYNC_ONLY = ("gc", "http")
PROGRAM_HAS_IT = "dynamo_http_chunks_total"
SLACK_NS = 1_000_000_000


def parse(path: str) -> dict:
    """``{"events": {family: [(start, end, thread)]}, "others": {thread:
    (first start, last end)}}`` of the host plane, times as
    ``xplane_meta.parse`` gives them. A ``dynstall/`` mark becomes the
    interval its ``late_ns`` reaches back over."""
    space = xplane_meta._schema()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    events: dict[str, list] = {f: [] for f in FAMILIES}
    others: dict[str, tuple[int, int]] = {}
    for plane in space.planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        names = {e.key: e.value.name for e in plane.event_metadata}
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        for line in plane.lines:
            for ev in line.events:
                name = names.get(ev.metadata_id, "")
                start = xplane_meta._start_ns(line, ev)
                end = start + ev.duration_ps // 1000
                family = next(
                    (f for f, p in FAMILIES.items() if name.startswith(p)), None
                )
                if family is None:
                    lo, hi = others.get(line.name, (start, end))
                    others[line.name] = (min(lo, start), max(hi, end))
                    continue
                if family == "stall":
                    late = next(
                        (st.int64_value or st.uint64_value for st in ev.stats
                         if stat_names.get(st.metadata_id) == "late_ns"), 0,
                    )
                    start, end = start - int(late), start
                events[family].append((start, end, line.name))
    return {"events": events, "others": others}


def check_one_clock(parsed: dict) -> None:
    """Raise ``ValueError`` where a ``dyngc/`` event cannot be on the
    clock of the lines it lies among (see the module's text)."""
    others = parsed["others"]
    if not others:
        return
    first = min(lo for lo, _ in others.values())
    last = max(hi for _, hi in others.values())
    for start, end, thread in parsed["events"]["gc"]:
        if start < first - SLACK_NS or end > last + SLACK_NS:
            raise ValueError(
                f"dyngc/ event [{start}, {end}) on {thread!r} outside the "
                f"host plane's other events [{first}, {last})"
            )
        if thread in others:
            lo, hi = others[thread]
            if end < lo - SLACK_NS or start > hi + SLACK_NS:
                raise ValueError(
                    f"dyngc/ event [{start}, {end}) far from the other "
                    f"events of its own line {thread!r} [{lo}, {hi})"
                )


def merged(intervals) -> list[tuple[int, int]]:
    """The union of ``(start, end)`` intervals as disjoint sorted ones."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def subtract(intervals, holes) -> list[tuple[int, int]]:
    """``intervals`` less ``holes`` (both disjoint and sorted)."""
    out, k = [], 0
    for s, e in intervals:
        at = s
        while k < len(holes) and holes[k][1] <= at:
            k += 1
        j = k
        while j < len(holes) and holes[j][0] < e:
            if holes[j][0] > at:
                out.append((at, holes[j][0]))
            at = max(at, holes[j][1])
            j += 1
        if at < e:
            out.append((at, e))
    return out


def intersect(a, b) -> list[tuple[int, int]]:
    """The intersection of two disjoint sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_pieces(gaps, phases, offset_ns: int, width_ns: int):
    """``[(start, end, phase)]`` on the host's clock: the pieces of the
    idle gaps (device clock) that ``xplane_meta.cut_gaps`` gives a phase,
    by its rule (a piece shorter than the offset's width is nobody's)."""
    out, at = [], 0
    for start, length in sorted(gaps):
        g0, g1 = start + offset_ns, start + length + offset_ns
        while at < len(phases) and phases[at][0] + phases[at][1] <= g0:
            at += 1
        k = at
        while k < len(phases) and phases[k][0] < g1:
            ps, pd, name = phases[k]
            p0, p1 = max(g0, ps), min(g1, ps + pd)
            if p1 - p0 > 0 and p1 - p0 >= width_ns:
                out.append((p0, p1, name))
            k += 1
    return out


def idle_by_event(meta: dict, parsed: dict) -> dict | None:
    """``{"window_ns", "by_family": {family: ns}}`` or None without
    ``meta["idle"]`` (``idle_by_phase``'s split)."""
    split = meta.get("idle")
    if not split:
        return None
    gaps = xplane_parts.idle_gaps(meta)
    if sum(n for _, n in gaps) != split["idle_ns"]:
        raise ValueError("these are not the gaps idle_by_phase cut")
    off = split["offset"]
    phases = sorted(meta["phases"][split["thread"]])
    pieces = idle_pieces(gaps, phases, off["ns"], off["width_ns"])
    by_phase: dict[str, int] = {}
    for p0, p1, name in pieces:
        by_phase[name] = by_phase.get(name, 0) + p1 - p0
    if by_phase != split["by_phase"]:
        raise ValueError("idle_pieces does not cut as cut_gaps did")
    idle_any = merged((p0, p1) for p0, p1, _ in pieces)
    idle_sync = merged((p0, p1) for p0, p1, name in pieces if name == "sync")
    claimed: list[tuple[int, int]] = []  # idle time a family before took
    by_family = {}
    for family in ORDER:
        mine = subtract(
            intersect(
                merged((s, e) for s, e, _t in parsed["events"][family]),
                idle_sync if family in SYNC_ONLY else idle_any,
            ),
            claimed,
        )
        by_family[family] = sum(e - s for s, e in mine)
        claimed = merged(claimed + mine)
    return {"window_ns": split["window_ns"], "by_family": by_family}


_CACHE: dict[str, dict | None] = {}


def current() -> dict | None:
    """``idle_by_event`` of this run's trace, read and checked once a
    process."""
    path = xplane_meta.find_trace()
    meta = xplane_meta.current()
    if path is None or meta is None:
        return None
    if path not in _CACHE:
        parsed = parse(path)
        check_one_clock(parsed)
        split = idle_by_event(meta, parsed)
        seen = {
            f: (len(evs), sum(e - s for s, e, _t in evs), len({t for _s, _e, t in evs}))
            for f, evs in parsed["events"].items()
        }
        print(
            "[bench] off the loop thread (events, ns, host lines): "
            + ", ".join(f"{FAMILIES[f]} {n} / {ns} / {lines}" for f, (n, ns, lines) in seen.items())
            + f"; idle ns met: {split['by_family'] if split else None}",
            file=sys.stderr, flush=True,
        )
        _CACHE.clear()
        _CACHE[path] = split
    return _CACHE[path]


def read(spec: dict, ctx: dict):
    if sample_sum(ctx.get("prom_after") or {}, PROGRAM_HAS_IT) is None:
        return None
    split = current() if ctx.get("trace") else None
    if not split or split["window_ns"] <= 0:
        return None
    return split["by_family"][spec["family"]] / split["window_ns"] * 100.0
