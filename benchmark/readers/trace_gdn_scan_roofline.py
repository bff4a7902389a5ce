"""How near the gated-delta-rule kernel runs to the chip's roofline
(``ops/gated_delta.py``; its custom call is named ``gated_delta_chunk``):
for every call in the device trace the least time the recurrence's own
work allows, summed, over the time the calls took, summed over chips
(``lib/gdn_scan_calls.py`` says what is counted). The heads come from the
configuration ``spec["config"]`` names; a call's slots count at the share
of mixed dispatches' slots that held a token
(``dynamo_ragged_slots_total{program="mixed"}`` over the window; whole
where the program exports no such counter). None without a trace or
without such a call (the parent's program, a model without
linear-attention layers, a CPU rehearsal)."""
import json
import os

from lib import gdn_scan_calls, xplane_meta
from lib.stats import counter_delta


def live_share(ctx: dict) -> float:
    def slots(kind):
        return counter_delta(
            ctx.get("prom_before") or {}, ctx.get("prom_after") or {},
            "dynamo_ragged_slots_total", {"program": "mixed", "kind": kind},
        )

    live, pad = slots("live"), slots("pad")
    return live / (live + pad) if live + pad > 0 else 1.0


def read(spec: dict, ctx: dict):
    path = xplane_meta.find_trace() if ctx.get("trace") else None
    if path is None:
        return None
    calls = gdn_scan_calls.chunk_calls(path)
    took = sum(c["ns"] for c in calls) / 1e9
    if took <= 0:
        return None
    import jax

    with open(os.path.join(xplane_meta.HERE, "peaks.json")) as f:
        peak = json.load(f)["devices"][jax.devices()[0].device_kind]
    with open(os.path.join(xplane_meta.HERE, "configs", f"{spec['config']}.json")) as f:
        heads = int(json.load(f)["linear_num_value_heads"])
    live = live_share(ctx)
    least = sum(
        gdn_scan_calls.least_seconds(c, heads, peak, live)[0] * c["count"] for c in calls
    )
    return least / took * 100.0
