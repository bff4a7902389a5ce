"""Device time in operations, other than the selective-scan kernel,
control flow and views, whose result has the shape of either part of a
selective-scan model's sequence-state pool or of one layer's slice of
one, over device busy time: what ``kv_pool_copy_time_share`` is for the
pages. The pool's shapes come from the configuration ``spec["config"]``
names: ``[L_mamba, entries, S, E]`` (the scan's state) and ``[L_mamba,
entries, K-1, E]`` (the convolution's inputs), ``entries`` = 5 x
``--max-decode-slots`` (one live entry a slot and four snapshots,
``engine/engine.py``); a layer's slice is ``[entries, ..]`` or ``[1,
entries, ..]``. The rows' in-place scatters have the pool's shape too and
are counted, so the reading bounds the copies from above. None without a
trace; 0.0 where no such operation is in it."""
import json
import os

from lib import ssm_scan_calls, xplane_meta

_VIEWS = ("while", "conditional", "call", "tuple", "get-tuple-element", "parameter", "bitcast")


def pool_shapes(config: dict) -> set[tuple[int, ...]]:
    args = config["serve_args"]
    entries = 5 * int(args[args.index("--max-decode-slots") + 1])
    period, offset = int(config["attn_layer_period"]), int(config["attn_layer_offset"])
    mamba = sum(1 for i in range(int(config["num_hidden_layers"])) if i % period != offset)
    inner = int(config["mamba_expand"]) * int(config["hidden_size"])
    parts = ((int(config["mamba_d_state"]), inner), (int(config["mamba_d_conv"]) - 1, inner))
    shapes = set()
    for part in parts:
        shapes |= {(mamba, entries, *part), (1, entries, *part), (entries, *part)}
    return shapes


def read(spec: dict, ctx: dict):
    meta = xplane_meta.current() if ctx.get("trace") else None
    if not meta:
        return None
    with open(os.path.join(xplane_meta.HERE, "configs", f"{spec['config']}.json")) as f:
        shapes = pool_shapes(json.load(f))

    def kind(op):
        if ssm_scan_calls.KERNEL.match(op.name) or op.opcode in _VIEWS:
            return "rest"
        hit = any(dims in shapes for _dt, dims in xplane_meta.result_arrays(op.result))
        return "copy" if hit else "rest"

    by = xplane_meta.time_by(meta, kind)
    total = sum(by.values())
    return by.get("copy", 0) / total * 100.0 if total > 0 else None
