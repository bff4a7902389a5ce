"""How near the indexer's scoring kernel runs to the chip's roofline: the
least time the scored pairs allow (``lib/dsa_index_calls.py``) over the time
the kernel's calls took, BOTH OVER THE WHOLE WINDOW as
``trace_mla_attn_roofline`` takes them: the work from the window's counter
deltas (``dynamo_dsa_index_pairs_total`` and the latent counters beside
it), the time from ``lib/window_time.py`` (operations named
``spec["pattern"]``). None without a trace, without the counters (the
parent; a model without an indexer) or without such a call."""
import json
import os

from lib import dsa_index_calls, window_time, xplane_meta
from lib.stats import counter_delta


def read(spec: dict, ctx: dict):
    took = window_time.kernel_seconds(spec["pattern"], ctx)
    if took is None:
        return None
    before, after = ctx.get("prom_before") or {}, ctx.get("prom_after") or {}

    def delta(name):
        return counter_delta(before, after, name, {})

    pairs = delta("dynamo_dsa_index_pairs_total")
    if pairs <= 0:
        return None
    context, queries = delta("dynamo_mla_context_tokens_total"), delta("dynamo_mla_query_tokens_total")
    with open(os.path.join(xplane_meta.HERE, "configs", f"{spec['config']}.json")) as f:
        hf = json.load(f)
    pool = xplane_meta.pool_info(ctx) or {}
    key_bytes = int(pool.get("index_width") or -(-int(hf["index_head_dim"]) // 128) * 128) * 2
    import jax

    with open(os.path.join(xplane_meta.HERE, "peaks.json")) as f:
        peak = json.load(f)["devices"][jax.devices()[0].device_kind]
    least, _bound = dsa_index_calls.least_seconds(pairs, context, queries, hf, key_bytes, peak)
    return least / took * 100.0
