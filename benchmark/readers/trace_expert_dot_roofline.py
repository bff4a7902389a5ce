"""How near the routed experts' matrix products run to the chip's
roofline: for every ``ragged-dot`` call in the device trace the least
time its own shapes allow (the larger of its bytes over the HBM peak and
its operations over the bfloat16 peak of ``peaks.json``;
``lib/expert_calls.py`` reckons both), summed, over the time the calls
took, summed over chips. None without a trace or without such a call (a
dense model, a CPU rehearsal)."""
import json
import os

from lib import expert_calls, xplane_meta


def read(spec: dict, ctx: dict):
    path = xplane_meta.find_trace() if ctx.get("trace") else None
    if path is None:
        return None
    calls = expert_calls.ragged_dot_calls(path)
    took = sum(c["ns"] for c in calls) / 1e9
    if took <= 0:
        return None
    import jax

    with open(os.path.join(xplane_meta.HERE, "peaks.json")) as f:
        peak = json.load(f)["devices"][jax.devices()[0].device_kind]
    least = sum(expert_calls.least_seconds(c, peak)[0] * c["count"] for c in calls)
    return least / took * 100.0
