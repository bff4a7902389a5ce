"""``lib/window_time.py::kernel_seconds`` on a slice that may hold a STALL.

The chip hosts stand still for 1-4 s at random moments (ROADMAP S7), and a
traced slice that holds such a gap is mostly idle where the window is not:
``window_time`` spreads the slice's busy share over the whole window, so
the kernel's seconds come out a fraction of what they were and a share of a
roofline reads several times too high (a slice of 5.5 s behind one gap of
4.2 s under the runtime's ``ReadSyncFlag`` thread read this cell's expert
kernel at 228 % and its scores kernel at 50 % where every other slice reads
44-50 and 10-12: my chip run, PR 61). Here the slice's idle gaps of
``LONG_GAP_S`` or more are taken out of the slice's length before the share
is taken: a slice without one reads as ``window_time`` reads it, and a
stall elsewhere in the window leaves the kernel's seconds a little high,
the share a little low, never high."""
from __future__ import annotations

import re

from lib.stats import counter_delta

LONG_GAP_S = 1.0


def kernel_seconds(pattern: str, ctx: dict) -> float | None:
    """None without a trace, without such a call or without the loop's
    phase counter (``window_time.kernel_seconds``'s cases)."""
    trace = ctx.get("trace") or {}
    if not trace.get("ops_s") or not trace.get("window_s"):
        return None
    window_s = counter_delta(
        ctx.get("prom_before") or {}, ctx.get("prom_after") or {},
        "dynamo_engine_phase_seconds_total", {},
    )
    named = re.compile(pattern)
    busy = sum(trace["ops_s"].values())
    hit = sum(v for k, v in trace["ops_s"].items() if named.search(k))
    steady = trace["window_s"] - sum(
        seconds for _host, seconds in trace.get("idle_gaps") or () if seconds >= LONG_GAP_S
    )
    if busy <= 0 or hit <= 0 or window_s <= 0 or steady <= 0:
        return None
    return hit / busy * trace["busy_s"] / steady * window_s
