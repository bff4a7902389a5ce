"""Increase of a Prometheus counter inside the window."""
from lib.stats import counter_delta


def read(spec: dict, ctx: dict):
    return counter_delta(
        ctx["prom_before"], ctx["prom_after"], spec["metric"], spec.get("match")
    ) * spec.get("scale", 1.0)
