"""The time a kernel's calls took OVER THE WHOLE WINDOW, for a roofline
reader whose work is counted over the whole window too (so that a slice's
edges cannot push a share over 100 %): the kernel's share of device busy
time in the traced slice (operations whose name matches ``pattern``) times
the slice's busy share times the window's seconds, which the engine loop's
phases sum to (``dynamo_engine_phase_seconds_total``, every phase)."""
from __future__ import annotations

import re

from lib.stats import counter_delta


def kernel_seconds(pattern: str, ctx: dict) -> float | None:
    """None without a trace, without such a call or without the loop's
    phase counter."""
    trace = ctx.get("trace") or {}
    if not trace.get("ops_s") or not trace.get("window_s"):
        return None
    window_s = counter_delta(
        ctx.get("prom_before") or {}, ctx.get("prom_after") or {},
        "dynamo_engine_phase_seconds_total", {},
    )
    named = re.compile(pattern)  # as ``trace_name_share`` reads a kernel
    busy = sum(trace["ops_s"].values())
    hit = sum(v for k, v in trace["ops_s"].items() if named.search(k))
    if busy <= 0 or hit <= 0 or window_s <= 0:
        return None
    return hit / busy * trace["busy_s"] / trace["window_s"] * window_s
