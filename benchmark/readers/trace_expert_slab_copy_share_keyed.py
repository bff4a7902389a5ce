"""``trace_expert_slab_copy_share`` for a configuration whose keys are not
Mixtral's: the experts a layer, their width and the number of routed
layers come from the keys the metric's file names (``experts_key``,
``width_key``, and ``dense_layers_key`` for layers ahead of the routed
ones that have no expert matrices). Device time in operations, other than
the ``expert_gmm`` kernel, control flow and views, whose result has the
shape of one layer's expert matrix on a chip ([E, D, I/tp] or
[E, I/tp, D]) or of the whole stack [L_routed, E, ..], over device busy
time. None without a trace; 0.0 where no such copy is left."""
import json
import os

from lib import xplane_meta
from readers.trace_expert_slab_copy_share import KERNEL
from readers.trace_expert_weight_copy_share import _VIEWS


def slab_shapes(config: dict, spec: dict) -> set[tuple[int, ...]]:
    args = config["serve_args"]
    tp = int(args[args.index("--tp") + 1]) if "--tp" in args else 1
    routed = int(config["num_hidden_layers"]) - int(config.get(spec.get("dense_layers_key", ""), 0))
    E, D = int(config[spec["experts_key"]]), int(config["hidden_size"])
    width = int(config[spec["width_key"]]) // tp
    layer = {(E, D, width), (E, width, D)}
    return layer | {(routed, *dims) for dims in layer}


def read(spec: dict, ctx: dict):
    meta = xplane_meta.current() if ctx.get("trace") else None
    if not meta:
        return None
    with open(os.path.join(xplane_meta.HERE, "configs", f"{spec['config']}.json")) as f:
        shapes = slab_shapes(json.load(f), spec)

    def kind(op):
        if KERNEL.match(op.name) or _VIEWS.match(op.opcode):
            return "rest"
        hit = any(dims in shapes for _dt, dims in xplane_meta.result_arrays(op.result))
        return "copy" if hit else "rest"

    by = xplane_meta.time_by(meta, kind)
    total = sum(by.values())
    return by.get("copy", 0) / total * 100.0 if total > 0 else None
