"""``trace_expert_gmm_roofline`` for a configuration that counts its
experts under another key than Mixtral's ``num_local_experts``: the
configuration (``spec["config"]``) and the key (``spec["experts_key"]``)
come from the metric's file. The calls are found, and their operations
and bytes reckoned, by the accepted reader's ``kernel_calls`` and
``lib/expert_calls.py::least_seconds``: lhs and result once and
``min(E, M)`` matrices of K x N, operations ``2 * M * K * N``, the larger
of bytes over the HBM peak and operations over the bfloat16 peak. None
without a trace or without such a call."""
import json
import os

from lib import expert_calls, xplane_meta
from readers.trace_expert_gmm_roofline import kernel_calls


def read(spec: dict, ctx: dict):
    path = xplane_meta.find_trace() if ctx.get("trace") else None
    if path is None:
        return None
    with open(os.path.join(xplane_meta.HERE, "configs", f"{spec['config']}.json")) as f:
        experts = int(json.load(f)[spec["experts_key"]])
    calls = kernel_calls(path, experts)
    took = sum(c["ns"] for c in calls) / 1e9
    if took <= 0:
        return None
    import jax

    with open(os.path.join(xplane_meta.HERE, "peaks.json")) as f:
        peak = json.load(f)["devices"][jax.devices()[0].device_kind]
    least = sum(expert_calls.least_seconds(c, peak)[0] * c["count"] for c in calls)
    return least / took * 100.0
