"""A number an info series carries as a label: ``spec["label"]`` of the one
sample of ``spec["metric"]`` in the window's last scrape (a constant-1
gauge such as ``dynamo_kv_pool_info``). None where the series or the label
is not there (a program that does not export it)."""


def read(spec: dict, ctx: dict):
    for (name, labels), _v in (ctx.get("prom_after") or {}).items():
        if name == spec["metric"]:
            value = dict(labels).get(spec["label"])
            return None if value is None else float(value)
    return None
