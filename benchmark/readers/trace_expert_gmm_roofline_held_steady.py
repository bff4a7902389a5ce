"""``trace_expert_gmm_roofline_held`` (the work as the programs count it on
the device: ``lib/held_expert_calls.py``, letter for letter) with the
kernel's seconds from ``lib/steady_window_time.py``, which a stall in the
traced slice cannot shrink: the accepted reader read 228 % in a slice that
held one (my chip run, PR 61; it read 186.6 % for another cell in the
ledger's PR 59 rows: ROADMAP S8 owes its time base this repair, a
``benchmark`` issue's). None without a trace, without the counters (a
program that does not count its picks) or without such a call."""
import json
import os

from lib import held_expert_calls, steady_window_time, xplane_meta
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
    least, _bound = held_expert_calls.least_seconds(
        picks, reads, int(config["hidden_size"]), int(config[spec["width_key"]]), peak
    )
    return least / took * 100.0
