"""1 - (union of device-operation intervals / traced slice), on the chip
that idles most."""


def read(spec: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace or not trace.get("chips"):
        return None
    return max(c["idle_share"] for c in trace["chips"].values()) * 100.0
