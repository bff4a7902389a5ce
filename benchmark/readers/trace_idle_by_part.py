"""Share of the traced slice in which the idlest chip ran nothing *and*
the engine loop's thread was inside one of the named parts of a phase
(``dynpart/<phase>.<part>`` annotations; ``parts: ["consume.finish"]``).
A piece of what ``trace_idle_by_phase`` books to the part's phase: the
same window, chip, gaps, clock offset and cut. None when the trace has
no such annotation on the phases' thread (a program without them) or
``trace_idle_by_phase`` reads None; 0.0 when it has and none of these
parts met an idle gap."""
from lib import xplane_parts


def read(spec: dict, ctx: dict):
    split = xplane_parts.current() if ctx.get("trace") else None
    if not split or split["window_ns"] <= 0:
        return None
    ns = sum(split["by_part"].get(p, 0) for p in spec["parts"])
    return ns / split["window_ns"] * 100.0
