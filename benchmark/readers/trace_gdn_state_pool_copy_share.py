"""Device time in operations, other than the delta-rule kernel, control
flow and views, whose result has the shape of either part of a
linear-attention model's sequence-state pool or of one layer's slice of
one, over device busy time: ``trace_seq_state_pool_copy_share`` for the
gated delta rule's parts. The pool's shapes come from the configuration
``spec["config"]`` names: ``[L_linear, entries, dk, H * dv]`` (the matrix
state) and ``[L_linear, entries, K-1, H * (2 dk + dv)]`` (the
convolutions' inputs), ``entries`` = 5 x ``--max-decode-slots`` (one live
entry a slot and four snapshots, ``engine/engine.py``); a layer's slice is
``[entries, ..]`` or ``[1, entries, ..]``; and a part of the whole pool cut
along its lanes counts with it (``[L_linear, entries, dk, fewer lanes]``:
XLA:TPU split a gather of whole 2.2 MB states over three such slices of
the pool, each materialised, until PR 39 made the gather's slice a
sublane group). The rows' in-place scatters have the pool's shape too and
are counted, so the reading bounds the copies from above. None without a
trace; 0.0 where no such operation is in it."""
import json
import os

from lib import gdn_scan_calls, xplane_meta
from readers.trace_seq_state_pool_copy_share import _VIEWS


def pool_shapes(config: dict) -> set[tuple[int, ...]]:
    args = config["serve_args"]
    entries = 5 * int(args[args.index("--max-decode-slots") + 1])
    linear = sum(1 for t in config["layer_types"] if t == "linear_attention")
    h, dk, dv = (
        int(config[k]) for k in
        ("linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim")
    )
    parts = ((dk, h * dv), (int(config["linear_conv_kernel_dim"]) - 1, h * (2 * dk + dv)))
    shapes = set()
    for part in parts:
        shapes |= {(linear, entries, *part), (1, entries, *part), (entries, *part)}
    return shapes


def read(spec: dict, ctx: dict):
    meta = xplane_meta.current() if ctx.get("trace") else None
    if not meta:
        return None
    with open(os.path.join(xplane_meta.HERE, "configs", f"{spec['config']}.json")) as f:
        shapes = pool_shapes(json.load(f))
    lane_cut = {dims[:3] for dims in shapes if len(dims) == 4 and dims[0] > 1}

    def kind(op):
        if gdn_scan_calls.KERNEL.match(op.name) or op.opcode in _VIEWS:
            return "rest"
        hit = any(
            dims in shapes or (len(dims) == 4 and dims[:3] in lane_cut)
            for _dt, dims in xplane_meta.result_arrays(op.result)
        )
        return "copy" if hit else "rest"

    by = xplane_meta.time_by(meta, kind)
    total = sum(by.values())
    return by.get("copy", 0) / total * 100.0 if total > 0 else None
