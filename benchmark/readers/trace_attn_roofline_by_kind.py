"""``trace_attn_roofline_by_form`` for a model whose attention shapes differ
by the layers' form (``lib/attn_calls_by_kind.py``: the form's own KV heads,
keys and values of different widths): the least time that form's work
allows over the time the kernel's calls of that form took, both over the
whole window. The time is told apart as there: a sliding layer's call
stands under the ``swa_attn`` scope and a full layer's does not. None
without a trace, without the counters (the parent, which has no such model)
or without such a call."""
import json
import os
import re

from lib import attn_calls_by_kind, xplane_meta
from lib.stats import counter_delta

_SLIDING = re.compile(r"(^|/)swa_attn(/|$)")


def read(spec: dict, ctx: dict):
    trace = ctx.get("trace") or {}
    meta = xplane_meta.current() if trace else None
    if not meta or not trace.get("window_s") or not trace.get("busy_s"):
        return None
    before, after = ctx.get("prom_before") or {}, ctx.get("prom_after") or {}
    form = spec["form"]

    def delta(name):
        return counter_delta(before, after, name, {"kind": form})

    keys = delta("dynamo_attn_key_positions_total")
    window_s = counter_delta(before, after, "dynamo_engine_phase_seconds_total", {})
    if keys <= 0 or window_s <= 0:
        return None
    named = re.compile(spec["pattern"])

    def kind(op):
        if not named.search(op.name or ""):
            return "rest"
        return "sliding" if _SLIDING.search(op.scope or "") else "full"

    by = xplane_meta.time_by(meta, kind)
    busy = sum(by.values())
    if busy <= 0 or by.get(form, 0) <= 0:
        return None
    took = by[form] / busy * trace["busy_s"] / trace["window_s"] * window_s
    with open(os.path.join(xplane_meta.HERE, "configs", f"{spec['config']}.json")) as f:
        hf = json.load(f)
    import jax

    with open(os.path.join(xplane_meta.HERE, "peaks.json")) as f:
        peak = json.load(f)["devices"][jax.devices()[0].device_kind]
    least, _bound = attn_calls_by_kind.least_seconds(
        keys, delta("dynamo_attn_context_tokens_total"),
        delta("dynamo_attn_query_tokens_total"), hf, peak, form,
    )
    return least / took * 100.0
