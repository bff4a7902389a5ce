"""From a profiler trace (``.xplane.pb``) to numbers: per-chip busy time,
time by device operation, the longest idle gaps and what the host was
doing in them.

The reduction works on plain tuples ``(start_ns, duration_ns, name)`` so
that it can be checked without a profiler; ``load`` turns a trace file
into them with nothing but ``jax.profiler.ProfileData``.

What the planes of a TPU trace look like (jax 0.9, TPU v5e; the recorded
one is ``tests/data/small_trace.xplane.pb``, made by
``rehearse/record_trace.py``): one plane a chip named ``/device:TPU:<n>``
whose line ``XLA Ops`` holds one event for every operation the chip ran,
named by its whole HLO line (a ``while`` holds the events of its body,
so events nest), a line ``XLA Modules`` with one event a program run, and
one plane ``/host:CPU`` with a line a thread. Times are nanoseconds from
the start of the trace; in the recorded trace the device's clock runs
about a millisecond ahead of the host's, so a gap is attributed to the
host only when it is much longer than that.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(event_name: str) -> str:
    """``%fusion.55 = bf16[...] fusion(...)`` -> ``fusion.55``: the trace
    names a device operation by its whole HLO line. A custom call keeps
    its target, which is what tells a kernel from a buffer allocation."""
    head = event_name.split(" = ", 1)[0].strip().lstrip("%")
    m = _TARGET.search(event_name)
    return f"{head}[{m.group(1)}]" if m else head


def find_xplane(trace_dir: str) -> str:
    found = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> dict:
    """``{"devices": {chip: [(start, dur, name)]}, "host": [(start, dur, name)]}``"""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: dict[int, list] = {}
    host: list = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(int(m.group(1)), []).extend(
                        (int(e.start_ns), int(e.duration_ns), short_name(e.name))
                        for e in line.events
                    )
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(
                    (int(e.start_ns), int(e.duration_ns), f"{line.name}: {e.name}")
                    for e in line.events
                )
    return {"devices": devices, "host": host}


def describe(path: str, top: int = 12) -> dict:
    """Planes, lines, event counts and most frequent names: what to read
    by hand before trusting the reduction on a new device or jax."""
    from collections import Counter

    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            events = list(line.events)
            names = Counter(e.name for e in events)
            lines[line.name] = {
                "events": len(events), "top_names": names.most_common(top),
            }
        out[plane.name] = lines
    return out


def union_ns(events) -> int:
    """Total length of the union of the events' intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, d, _ in sorted(events):
        e = s + d
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(events, lo: int, hi: int) -> list[tuple[int, int]]:
    """(start, length) of every interval of [lo, hi) no event covers."""
    out, at = [], lo
    for s, d, _ in sorted(events):
        if s > at:
            out.append((at, min(s, hi) - at))
        at = max(at, s + d)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi - at))
    return [(s, n) for s, n in out if n > 0]


def self_time_ns(events) -> dict[str, int]:
    """Time by name with each event's children taken out of it, so a
    ``while`` is charged only what its body does not account for."""
    out: dict[str, int] = {}
    stack: list[list] = []  # [end, name, self]

    def close(until):
        while stack and stack[-1][0] <= until:
            _end, name, self_ns = stack.pop()
            out[name] = out.get(name, 0) + max(self_ns, 0)

    for s, d, name in sorted(events, key=lambda e: (e[0], -e[1])):
        close(s)
        if stack:
            stack[-1][2] -= d
        stack.append([s + d, name, d])
    close(float("inf"))
    return out


def _host_activity(host_events, start: int, length: int) -> str:
    """The host event that covers most of [start, start+length): what
    the host was doing while the device waited."""
    best, best_overlap = "unattributed", 0
    end = start + length
    for s, d, name in host_events:
        overlap = min(end, s + d) - max(start, s)
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


def reduce(trace: dict, top_ops: int = 10, top_gaps: int = 5) -> dict:
    """Per-chip busy seconds, the traced window, time by operation
    (summed over chips, seconds) and the longest idle gaps."""
    devices = {c: ev for c, ev in trace["devices"].items() if ev}
    if not devices:
        return {"chips": {}, "window_s": 0.0, "busy_s": 0.0}
    lo = min(s for ev in devices.values() for s, _, _ in ev)
    hi = max(s + d for ev in devices.values() for s, d, _ in ev)
    host = trace.get("host") or []
    if host:
        lo = min(lo, min(s for s, _, _ in host))
        hi = max(hi, max(s + d for s, d, _ in host))
    window_ns = hi - lo
    chips, ops_total, worst_gaps = {}, {}, []
    for chip, ev in sorted(devices.items()):
        busy = union_ns(ev)
        chips[chip] = {"busy_s": busy / 1e9, "idle_share": 1.0 - busy / window_ns}
        for name, ns in self_time_ns(ev).items():
            ops_total[name] = ops_total.get(name, 0) + ns
    worst = max(chips, key=lambda c: chips[c]["idle_share"])
    for s, n in sorted(gaps_ns(devices[worst], lo, hi), key=lambda g: -g[1])[:top_gaps]:
        worst_gaps.append([_host_activity(host, s, n), n / 1e9])
    ranked = sorted(ops_total.items(), key=lambda kv: -kv[1])
    return {
        "chips": chips,
        "window_s": window_ns / 1e9,
        "busy_s": sum(c["busy_s"] for c in chips.values()) / len(chips),
        "worst_chip": worst,
        "ops_s": {k: v / 1e9 for k, v in ranked},
        "device_ops": [[k, v / 1e9] for k, v in ranked[:top_ops]],
        "idle_gaps": worst_gaps,
    }
