"""How near latent attention's kernel calls run to the chip's roofline: the
least time the attention's own work allows (``lib/mla_attn_calls.py``: the
published form's operations, the pool's real bytes) over the time the
kernel's calls took, BOTH OVER THE WHOLE WINDOW so that a slice's edges
cannot push the share over 100 %:

- the work from the window's counter deltas (``dynamo_mla_*_total``);
- the time from ``lib/window_time.py``: the share of the traced slice's
  busy time in operations named ``spec["pattern"]``, scaled to the window.

The least time is the larger of the summed bytes over the HBM peak and the
summed operations over the bf16 peak: never more than the calls' own least
times summed, so it reads low, never high. None without a trace, without
the counters (the parent; a model without a latent cache) or without such
a call."""
import json
import os

from lib import mla_attn_calls, window_time, xplane_meta
from lib.stats import counter_delta


def read(spec: dict, ctx: dict):
    took = window_time.kernel_seconds(spec["pattern"], ctx)
    if took is None:
        return None
    before, after = ctx.get("prom_before") or {}, ctx.get("prom_after") or {}

    def delta(name):
        return counter_delta(before, after, name, {})

    pairs = delta("dynamo_mla_attended_pairs_total")
    if pairs <= 0:
        return None
    context, queries = delta("dynamo_mla_context_tokens_total"), delta("dynamo_mla_query_tokens_total")
    pool = xplane_meta.pool_info(ctx) or {}
    with open(os.path.join(xplane_meta.HERE, "configs", f"{spec['config']}.json")) as f:
        hf = json.load(f)
    row_bytes = (
        int(pool["token_bytes"]) // int(pool["layers"]) if pool.get("token_bytes")
        else mla_attn_calls.lanes(hf)[0] * 2
    )
    import jax

    with open(os.path.join(xplane_meta.HERE, "peaks.json")) as f:
        peak = json.load(f)["devices"][jax.devices()[0].device_kind]
    least, _bound = mla_attn_calls.least_seconds(pairs, context, queries, hf, row_bytes, peak)
    return least / took * 100.0
