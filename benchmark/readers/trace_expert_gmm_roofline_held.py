"""``expert_gmm_roofline_share`` for a configuration that holds a share of
its experts: the least time the held picks' work allows
(``lib/held_expert_calls.py``: the picks and the experts they reach, both
counted on the device) over the time the grouped-matmul kernel's calls
took, BOTH OVER THE WHOLE WINDOW as ``trace_mla_attn_roofline`` takes them:
the work from the window's counter deltas, the time from
``lib/window_time.py`` (operations named ``spec["pattern"]``). ``K`` and ``N`` are the configuration's
``hidden_size`` and ``spec["width_key"]``. None without a trace, without the
counters (the parent: it holds every expert it routes over) or without
such a call."""
import json
import os

from lib import held_expert_calls, window_time, xplane_meta
from lib.stats import counter_delta


def read(spec: dict, ctx: dict):
    took = window_time.kernel_seconds(spec["pattern"], ctx)
    before, after = ctx.get("prom_before") or {}, ctx.get("prom_after") or {}
    picks = counter_delta(before, after, "dynamo_moe_picks_total", {"where": "held"})
    reads = counter_delta(before, after, "dynamo_moe_held_expert_reads_total", {})
    if took is None or picks <= 0 or reads <= 0:
        return None
    with open(os.path.join(xplane_meta.HERE, "configs", f"{spec['config']}.json")) as f:
        config = json.load(f)
    import jax

    with open(os.path.join(xplane_meta.HERE, "peaks.json")) as f:
        peak = json.load(f)["devices"][jax.devices()[0].device_kind]
    least, _bound = held_expert_calls.least_seconds(
        picks, reads, int(config["hidden_size"]), int(config[spec["width_key"]]), peak
    )
    return least / took * 100.0
