"""Device time in operations, other than the recurrence's two kernels
(``ssd_chunk`` and ``ssd_step``, whose result IS the pool, aliased), control flow and views,
whose result has the shape of either part of a Mamba-2 model's
sequence-state pool or of one layer's slice of one, over device busy time:
``trace_seq_state_pool_copy_share`` for this pool's two shapes, ``[L_M,
entries, S, H * P]`` float32 (the matrix state) and ``[L_M, entries, K-1, H *
P + 2 G S]`` (the convolution's inputs), ``entries`` = 5 x
``--max-decode-slots``; a layer's slice is ``[entries, ..]`` or ``[1,
entries, ..]``; a part of the whole pool cut along its lanes counts with it.
The convolution's in-place scatter of the rows' inputs has its part's shape
too and is counted, so the reading bounds the copies from above. None
without a trace; 0.0 where no such operation is in it."""
import json
import os

from lib import ssd_scan_calls, xplane_meta
from readers.trace_seq_state_pool_copy_share import _VIEWS


def pool_shapes(config: dict) -> set[tuple[int, ...]]:
    args = config["serve_args"]
    entries = 5 * int(args[args.index("--max-decode-slots") + 1])
    layers = ssd_scan_calls.mamba2_layers(config)
    h, p, g, s = ssd_scan_calls.widths(config)
    parts = ((s, h * p), (int(config["conv_kernel"]) - 1, h * p + 2 * g * s))
    shapes = set()
    for part in parts:
        shapes |= {(layers, entries, *part), (1, entries, *part), (entries, *part)}
    return shapes


def read(spec: dict, ctx: dict):
    meta = xplane_meta.current() if ctx.get("trace") else None
    if not meta:
        return None
    with open(os.path.join(xplane_meta.HERE, "configs", f"{spec['config']}.json")) as f:
        shapes = pool_shapes(json.load(f))
    lane_cut = {dims[:3] for dims in shapes if len(dims) == 4 and dims[0] > 1}

    def kind(op):
        if ssd_scan_calls.KERNELS.match(op.name) or op.opcode in _VIEWS:
            return "rest"
        hit = any(
            dims in shapes or (len(dims) == 4 and dims[:3] in lane_cut)
            for _dt, dims in xplane_meta.result_arrays(op.result)
        )
        return "copy" if hit else "rest"

    by = xplane_meta.time_by(meta, kind)
    total = sum(by.values())
    return by.get("copy", 0) / total * 100.0 if total > 0 else None
