"""Self time of the device operations under one ``jax.named_scope`` of the
program (``spec["scope"]``, a regular expression searched in an
operation's scope path, as a rule of ``trace_classes.json`` would search
it) over device busy time, summed over chips. For a scope the accepted
class table does not name. Control flow and views are left out of the hit
as the table's own rule leaves them out (a ``while`` holds its body's
time already). None without a trace; 0.0 where the trace has no such
operation."""
import re

from lib import xplane_meta

_CONTROL = re.compile(r"^(while|conditional|call|tuple|get-tuple-element|parameter|bitcast)$")


def read(spec: dict, ctx: dict):
    meta = xplane_meta.current() if ctx.get("trace") else None
    if not meta:
        return None
    scope = re.compile(spec["scope"])

    def kind(op):
        hit = scope.search(op.scope or "") and not _CONTROL.match(op.opcode or "")
        return "hit" if hit else "rest"

    by = xplane_meta.time_by(meta, kind)
    total = sum(by.values())
    return by.get("hit", 0) / total * 100.0 if total > 0 else None
