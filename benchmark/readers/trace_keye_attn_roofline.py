"""How near the attention kernel of a grouped-query model under a learned
selection runs to the chip's roofline: the least time the SELECTED pairs
allow (``lib/keye_calls.py``: ``dynamo_dsa_selected_pairs_total``, a
layer's; each row's K and V once a dispatch and layer) over the time the
kernel's calls took, both over the whole window
(``readers/trace_keye_index_roofline.py`` reads the counters and the peak
the same way). None without a trace, without the counters or without such a
call."""
from lib import keye_calls, steady_window_time
from readers.trace_keye_index_roofline import config_and_peak, counted


def read(spec: dict, ctx: dict):
    took = steady_window_time.kernel_seconds(spec["pattern"], ctx)
    work = counted(ctx, "dynamo_dsa_selected_pairs_total") if took is not None else None
    if work is None:
        return None
    pairs, context, queries = work
    hf, peak = config_and_peak(spec)
    least, _bound = keye_calls.least_seconds(
        keye_calls.attn_operations(pairs, hf), keye_calls.attn_bytes(context, queries, hf), peak,
    )
    return least / took * 100.0
