"""How near the selective-scan kernel runs to the chip's roofline
(``ops/selective_scan.py``; its custom call is named ``ssm_scan``): for
every call in the device trace the least time its shapes allow, summed,
over the time the calls took, summed over chips. The bound is HBM's alone
(``lib/ssm_scan_calls.py`` says what is counted and why no operation
peak applies). None without a trace or without such a call (the parent's
program, a model without Mamba layers, a CPU rehearsal)."""
import json
import os

from lib import ssm_scan_calls, xplane_meta


def read(spec: dict, ctx: dict):
    path = xplane_meta.find_trace() if ctx.get("trace") else None
    if path is None:
        return None
    calls = ssm_scan_calls.scan_calls(path)
    took = sum(c["ns"] for c in calls) / 1e9
    if took <= 0:
        return None
    import jax

    with open(os.path.join(xplane_meta.HERE, "peaks.json")) as f:
        peak = json.load(f)["devices"][jax.devices()[0].device_kind]
    least = sum(ssm_scan_calls.least_seconds(c, peak)[0] * c["count"] for c in calls)
    return least / took * 100.0
