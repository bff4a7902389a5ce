"""Device time spent copying one layer's expert weights, over device busy
time (self time, summed over chips): operations other than the
``ragged-dot`` calls themselves, control flow and views, whose result has
the shape of the calls' three-dimensional operand (one layer's expert
matrix on a chip, ``lib/expert_calls.py``) or of its transpose. Stacked
weights are sliced a layer at a time for ``ragged_dot``, whose custom
call takes no slice in place: each slice is a copy of the matrix (PERF.md,
PR 26). None without a trace or without such a call."""
import re

from lib import expert_calls, xplane_meta

_VIEWS = re.compile(r"^(while|conditional|call|tuple|get-tuple-element|parameter|bitcast)$")


def read(spec: dict, ctx: dict):
    meta = xplane_meta.current() if ctx.get("trace") else None
    path = xplane_meta.find_trace() if meta else None
    if not meta or path is None:
        return None
    shapes = set()
    for call in expert_calls.ragged_dot_calls(path):
        e, k, n = call["rhs"][1]
        shapes |= {(e, k, n), (e, n, k)}
    if not shapes:
        return None

    def kind(op):
        if expert_calls.RAGGED_DOT.match(op.name) or _VIEWS.match(op.opcode):
            return "rest"
        hit = any(dims in shapes for _dt, dims in xplane_meta.result_arrays(op.result))
        return "copy" if hit else "rest"

    by = xplane_meta.time_by(meta, kind)
    total = sum(by.values())
    return by.get("copy", 0) / total * 100.0 if total > 0 else None
