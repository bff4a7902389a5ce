"""``prom_counter_delta``, and None where the program exports no such
series: a counter that is not there has not read 0 (an absent series
reads 0 there, which is right for a labelled child that is created on
first use and wrong for a counter the program does not have)."""
from lib.stats import sample_sum
from readers import prom_counter_delta


def read(spec: dict, ctx: dict):
    if sample_sum(ctx["prom_after"], spec["metric"], spec.get("match")) is None:
        return None
    return prom_counter_delta.read(spec, ctx)
