"""Self time of one class of device operations (``trace_classes.json``)
over device busy time, summed over chips. None without a trace, or when
the class needs the pools' shape and the program does not export
``dynamo_kv_pool_info``."""
from lib import xplane_meta


def read(spec: dict, ctx: dict):
    meta = xplane_meta.current() if ctx.get("trace") else None
    if not meta:
        return None
    rules = xplane_meta.load_rules()
    pool = xplane_meta.pool_info(ctx)
    needs_pool = any(
        r["class"] == spec["class"] and r.get("result") == "pool" for r in rules
    )
    if needs_pool and pool is None:
        return None
    pools = xplane_meta.pool_shapes(pool)
    by_class = xplane_meta.time_by(
        meta, lambda op: xplane_meta.classify(op, rules, pools)
    )
    total = sum(by_class.values())
    if total <= 0:
        return None
    return by_class.get(spec["class"], 0) / total * 100.0
