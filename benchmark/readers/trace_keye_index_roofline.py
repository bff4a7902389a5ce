"""How near the indexer's scoring kernel of a grouped-query model under a
learned selection runs to the chip's roofline: the least time the scored
pairs allow (``lib/keye_calls.py``) over the time the kernel's calls took,
BOTH OVER THE WHOLE WINDOW as ``trace_dsa_index_roofline`` takes them: the
work from the window's counter deltas (``dynamo_dsa_index_pairs_total``, a
layer's; ``dynamo_attn_context_tokens_total`` and
``dynamo_attn_query_tokens_total`` of the full layers, summed over them),
the time from ``lib/steady_window_time.py`` (operations named
``spec["pattern"]``; a stall in the traced slice taken out of its length).
None without a trace, without the counters (the parent, which has no such
model) or without such a call."""
import json
import os

from lib import keye_calls, steady_window_time, xplane_meta
from lib.stats import counter_delta


def counted(ctx: dict, pairs_metric: str):
    """(a layer's pairs of ``pairs_metric``, the full layers' context and
    query tokens) over the window, or None where the first did not move."""
    before, after = ctx.get("prom_before") or {}, ctx.get("prom_after") or {}
    pairs = counter_delta(before, after, pairs_metric, {})
    if pairs <= 0:
        return None
    full = {"kind": "full"}
    return (
        pairs, counter_delta(before, after, "dynamo_attn_context_tokens_total", full),
        counter_delta(before, after, "dynamo_attn_query_tokens_total", full),
    )


def config_and_peak(spec: dict):
    with open(os.path.join(xplane_meta.HERE, "configs", f"{spec['config']}.json")) as f:
        hf = json.load(f)
    import jax

    with open(os.path.join(xplane_meta.HERE, "peaks.json")) as f:
        return hf, json.load(f)["devices"][jax.devices()[0].device_kind]


def read(spec: dict, ctx: dict):
    took = steady_window_time.kernel_seconds(spec["pattern"], ctx)
    work = counted(ctx, "dynamo_dsa_index_pairs_total") if took is not None else None
    if work is None:
        return None
    pairs, context, queries = work
    hf, peak = config_and_peak(spec)
    pool = xplane_meta.pool_info(ctx) or {}
    width = int(hf["sa_config"]["indexer_head_dim"])
    key_bytes = int(pool.get("index_width") or -(-width // 128) * 128) * 2
    least, _bound = keye_calls.least_seconds(
        keye_calls.index_operations(pairs, hf),
        keye_calls.index_bytes(pairs, context, queries, hf, key_bytes), peak,
    )
    return least / took * 100.0
