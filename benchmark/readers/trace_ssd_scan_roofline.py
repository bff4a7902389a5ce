"""How near Mamba-2's recurrence runs to the chip's roofline
(``ops/ssd.py``, all its forms: the scope ``ssd_scan``): the least time the
recurrence's own work allows (``lib/ssd_scan_calls.py``: the tokens the
program counted through it, ``dynamo_mamba2_tokens_total`` over the window,
times the configuration's Mamba-2 layers) over the device time under the
scope, both over the whole window. None without a trace, without the counter
(the parent's program, a model without such layers, a CPU rehearsal) or
without an operation under the scope."""
import json
import os

from lib import ssd_scan_calls, xplane_meta
from lib.stats import counter_delta


def read(spec: dict, ctx: dict):
    took = ssd_scan_calls.scope_seconds(ctx)
    tokens = counter_delta(
        ctx.get("prom_before") or {}, ctx.get("prom_after") or {},
        "dynamo_mamba2_tokens_total", {},
    )
    if took is None or tokens <= 0:
        return None
    with open(os.path.join(xplane_meta.HERE, "configs", f"{spec['config']}.json")) as f:
        config = json.load(f)
    import jax

    with open(os.path.join(xplane_meta.HERE, "peaks.json")) as f:
        peak = json.load(f)["devices"][jax.devices()[0].device_kind]
    least, _bound = ssd_scan_calls.least_seconds(
        tokens * ssd_scan_calls.mamba2_layers(config), config, peak
    )
    return least / took * 100.0
