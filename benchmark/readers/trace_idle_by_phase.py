"""Share of the traced slice in which the idlest chip ran nothing *and*
the engine loop's thread was inside one of the named phases
(``dyn/<phase>`` annotations, moved onto the device's clock by the
estimated offset). ``phases: ["unattributed"]`` reads the idle time no
phase could be given: pieces shorter than the offset's width, and idle
time outside every phase. None when the trace has no phase annotations
(a program without them) or no ``run_id`` pair to estimate the offset."""
from lib import xplane_meta


def read(spec: dict, ctx: dict):
    meta = xplane_meta.current() if ctx.get("trace") else None
    split = meta.get("idle") if meta else None
    if not split or split["window_ns"] <= 0:
        return None
    ns = sum(
        split["unattributed_ns"] if p == "unattributed" else split["by_phase"].get(p, 0)
        for p in spec["phases"]
    )
    return ns / split["window_ns"] * 100.0
