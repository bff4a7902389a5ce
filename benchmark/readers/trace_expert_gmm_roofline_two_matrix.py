"""``trace_expert_gmm_roofline_held_steady`` for experts of two matrices
(``lib/two_matrix_expert_calls.py``: two products a pick, two matrices a
held expert read): the work as the programs count it on the device over the
time the grouped-matmul kernel's calls took
(``lib/steady_window_time.py``), both over the whole window. ``K`` and ``N``
are the configuration's ``hidden_size`` and ``spec["width_key"]``. None
without a trace, without the counters or without such a call."""
import json
import os

from lib import steady_window_time, two_matrix_expert_calls, xplane_meta
from lib.stats import counter_delta


def read(spec: dict, ctx: dict):
    took = steady_window_time.kernel_seconds(spec["pattern"], ctx)
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
    least, _bound = two_matrix_expert_calls.least_seconds(
        picks, reads, int(config["hidden_size"]), int(config[spec["width_key"]]), peak
    )
    return least / took * 100.0
