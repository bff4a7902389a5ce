"""Mean of a Prometheus histogram's observations inside the window:
delta(_sum) / delta(_count) between the scrapes at its two ends."""
from lib.stats import histogram_mean_delta


def read(spec: dict, ctx: dict):
    v = histogram_mean_delta(
        ctx["prom_before"], ctx["prom_after"], spec["metric"], spec.get("match")
    )
    return None if v is None else v * spec.get("scale", 1.0)
