"""Device busy time inside the runs of the programs whose name matches a
pattern (the trace's ``XLA Modules`` line: ``jit_ragged_mixed``,
``jit_ragged_window``, ``jit_kv_cow_pages``) over device busy time,
summed over chips."""
import re

from lib import xplane_meta


def read(spec: dict, ctx: dict):
    meta = xplane_meta.current() if ctx.get("trace") else None
    if not meta:
        return None
    by_program = xplane_meta.time_by(meta, lambda op: op.program)
    total = sum(by_program.values())
    if total <= 0:
        return None
    pat = re.compile(spec["pattern"])
    return sum(v for k, v in by_program.items() if pat.search(k)) / total * 100.0
