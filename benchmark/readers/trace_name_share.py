"""Share of device busy time spent in operations whose name matches a
pattern, from the device trace (self time, summed over chips)."""
import re


def read(spec: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace or not trace.get("ops_s"):
        return None
    pat = re.compile(spec["pattern"])
    total = sum(trace["ops_s"].values())
    hit = sum(v for k, v in trace["ops_s"].items() if pat.search(k))
    if total <= 0:
        return None
    return hit / total * 100.0
