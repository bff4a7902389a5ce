"""Ratio of counter increases inside the window: the sum over
``numerator`` label sets over the sum over ``denominator`` label sets."""
from lib.stats import counter_delta


def read(spec: dict, ctx: dict):
    def total(matches):
        return sum(
            counter_delta(ctx["prom_before"], ctx["prom_after"], spec["metric"], m)
            for m in matches
        )

    den = total(spec["denominator"])
    if den <= 0:
        return None
    return total(spec["numerator"]) / den * spec.get("scale", 1.0)
