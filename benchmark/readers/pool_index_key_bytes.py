"""Bytes of index key a cached token holds over all layers, from
``dynamo_kv_pool_info`` in the window's last scrape: ``index_width`` (the
lanes the key takes in the cache) x the pool's item size (``dtype``) x
``layers``. None where the series or the label is not there, or the model
caches no index key (``index_width`` 0)."""
from lib import xplane_meta

_ITEM_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "float8_e4m3fn": 1, "int8": 1}


def read(spec: dict, ctx: dict):
    pool = xplane_meta.pool_info(ctx) or {}
    lanes, item = int(pool.get("index_width") or 0), _ITEM_BYTES.get(pool.get("dtype"))
    if not lanes or item is None or not pool.get("layers"):
        return None
    return float(lanes * item * int(pool["layers"]))
