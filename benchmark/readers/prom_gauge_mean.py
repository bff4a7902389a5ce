"""Mean of a Prometheus gauge over the scrapes taken each second of the
window."""
from lib.stats import sample_sum


def read(spec: dict, ctx: dict):
    xs = [
        v for s in ctx["gauge_scrapes"]
        if (v := sample_sum(s, spec["metric"], spec.get("match"))) is not None
    ]
    if not xs:
        return None
    return sum(xs) / len(xs) * spec.get("scale", 1.0)
