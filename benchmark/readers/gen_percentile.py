"""A percentile of one series of the load generator's own records."""
from lib.stats import percentile


def read(spec: dict, ctx: dict):
    xs = ctx["gen"].get(spec["series"]) or []
    if not xs:
        return None
    return percentile(xs, spec["q"]) * spec.get("scale", 1.0)
