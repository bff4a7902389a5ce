"""Mean over the scrapes taken each second of the window of one gauge's sum
over its label sets (``spec["numerator"]``, with ``numerator_match``) over
another's (``spec["denominator"]``), scrape by scrape; scrapes where the
denominator is 0 (nothing in flight) are left out. None where a series is
not there (a program that does not export it)."""
from lib.stats import sample_sum


def read(spec: dict, ctx: dict):
    xs = []
    for s in ctx["gauge_scrapes"]:
        num = sample_sum(s, spec["numerator"], spec.get("numerator_match"))
        den = sample_sum(s, spec["denominator"], spec.get("denominator_match"))
        if num is None or den is None:
            continue
        if den > 0:
            xs.append(num / den)
    if not xs:
        return None
    return sum(xs) / len(xs) * spec.get("scale", 1.0)
