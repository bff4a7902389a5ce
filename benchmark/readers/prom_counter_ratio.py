"""Ratio of the increases of two counters inside the window: the sum
over ``numerator``'s label sets of its metric over the same of
``denominator`` (each ``{"metric": name, "match": [labels, ...]}``),
times ``scale``; with ``one_minus`` the complement, ``(1 - ratio) *
scale``. ``prom_ratio`` divides within one metric; this one across two.
None where the program exports either metric not at all (a counter that
is not there has not read 0) or the denominator did not move."""
from lib.stats import counter_delta, sample_sum


def read(spec: dict, ctx: dict):
    def total(side):
        if sample_sum(ctx["prom_after"], side["metric"]) is None:
            return None
        return sum(
            counter_delta(ctx["prom_before"], ctx["prom_after"], side["metric"], m)
            for m in side["match"]
        )

    num, den = total(spec["numerator"]), total(spec["denominator"])
    if num is None or den is None or den <= 0:
        return None
    ratio = num / den
    if spec.get("one_minus"):
        ratio = 1.0 - ratio
    return ratio * spec.get("scale", 1.0)
