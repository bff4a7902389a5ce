#!/usr/bin/env python3
"""What a launch's host-to-device transfers cost, field by field against packed.

    chiprun -- python3 tools/h2d_cost.py [--chips 1] [--iterations 300]
    chiprun --chips 4 -- python3 tools/h2d_cost.py --chips 4 --cases mixtral-mixed,mixtral-window

At each benchmark cell's real launch shapes (``aot/launch.py``'s layout
at the cell's sizes) this times, on the host that drives the chip:

- ``fields_s``: one ``jnp.asarray`` a field, the form every launch had
  before PR 36 (the fields a greedy launch passed then, the unused
  speculative ones included);
- ``packed_s``: the fields copied into one buffer plus one
  ``jax.device_put`` of it with the mesh's replicated sharding (``pack_s``
  is the copy alone; the engine's builders write into the buffer's views
  from the start and pay none);
- ``call_fields_s`` / ``call_packed_s``: a jitted program's own call with
  that many small uncommitted arguments against one replicated buffer it
  unpacks (the call returns before the device is done: host seconds).

A micro-benchmark of the host's side of a transfer, not code a cell runs:
its numbers are this machine's, medians over ``--iterations`` launches with
nothing else running, and say whether the cost is per transfer
(PERF.md §6, PR 36).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# cell -> (max_model_len / page_size, has_seq_state, mixed nb, window nb):
# the buckets each cell's window mostly launches (PERF.md §5).
CELLS = {
    "jamba": (1024, True, 2048, 32),
    "lfm2": (256, True, 1024, 64),
    "qwen3": (256, False, 512, 32),
    "mixtral": (256, False, 1024, 64),
}


def fields_of(cell: str, windowed: bool):
    """(layout as launched now, host arrays of the launch before PR 36)."""
    import numpy as np

    from dynamo_exp_tpu.aot.launch import launch_layout

    pmax, has_state, mixed_nb, window_nb = CELLS[cell]
    cfg = SimpleNamespace(
        model=SimpleNamespace(has_seq_state=has_state), max_decode_slots=64,
        max_pages_per_seq=pmax, device_stop_width=8, spec_max_draft=8,
    )
    nb = window_nb if windowed else mixed_nb
    layout = launch_layout(cfg, (nb, None, windowed, False, False, False))
    before = launch_layout(cfg, (nb, None, windowed, False, False, not windowed))
    rng = np.random.default_rng(0)
    arrays = {
        f.name: rng.integers(0, 1000, f.shape).astype(np.int32)
        for f in before if f.name != "chained"
    }
    return layout, arrays


def median_s(fn, iterations: int) -> float:
    for _ in range(20):
        fn()
    out = []
    for _ in range(iterations):
        t0 = time.perf_counter()
        fn()
        out.append(time.perf_counter() - t0)
    return statistics.median(out)


def measure(case: str, mesh, iterations: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from dynamo_exp_tpu.aot.launch import unpack_launch

    cell, program = case.split("-")
    layout, arrays = fields_of(cell, program == "window")
    replicated = NamedSharding(mesh, P())
    hosts = list(arrays.values())

    def pack():
        buf = np.zeros(layout[-1].offset + layout[-1].size, np.int32)
        for f in layout:
            if f.name in arrays:
                buf[f.offset : f.offset + f.size] = arrays[f.name].reshape(-1)
        return buf

    held = []  # keep the last results alive across the timed call

    def by_field():
        held[:] = [jnp.asarray(a) for a in hosts]

    def packed():
        held[:] = [jax.device_put(pack(), replicated)]

    w = jax.device_put(jnp.ones((8,), jnp.float32), replicated)  # a parameter

    @jax.jit
    def program_fields(w, *xs):
        return w[0] + sum(x.reshape(-1)[0] for x in xs)

    @jax.jit
    def program_packed(w, buf):
        return w[0] + sum(x.reshape(-1)[0] for x in unpack_launch(layout, buf).values())

    def timed_call(make, fn):
        """Median host seconds of ``fn``'s call alone; the transfer before
        it and the wait after it are outside the clock."""
        out = []
        for i in range(iterations + 20):
            make()
            jax.block_until_ready(held)
            t0 = time.perf_counter()
            y = fn(w, *held)
            dt = time.perf_counter() - t0
            jax.block_until_ready(y)
            if i >= 20:
                out.append(dt)
        return statistics.median(out)

    res = {
        "case": case, "chips": mesh.size, "fields": len(hosts),
        "bytes": sum(a.nbytes for a in hosts),
        "packed_bytes": (layout[-1].offset + layout[-1].size) * 4,
        "fields_s": median_s(by_field, iterations),
        "packed_s": median_s(packed, iterations),
        "pack_s": median_s(pack, iterations),
        "call_fields_s": timed_call(by_field, program_fields),
        "call_packed_s": timed_call(packed, program_packed),
    }
    res["fields_over_packed"] = res["fields_s"] / res["packed_s"]
    return res


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--iterations", type=int, default=300)
    ap.add_argument(
        "--cases",
        default="jamba-mixed,jamba-window,lfm2-mixed,lfm2-window,qwen3-mixed,qwen3-window",
    )
    args = ap.parse_args()

    import jax
    import numpy as np
    from jax.sharding import Mesh

    devices = jax.devices()[: args.chips]
    mesh = Mesh(np.array(devices), ("tp",))
    out_dir = os.path.join(ROOT, "chiprun_out", "h2d_cost")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"chips{args.chips}.jsonl"), "a") as f:
        for case in args.cases.split(","):
            res = measure(case, mesh, args.iterations)
            res["platform"] = devices[0].platform
            res["device_kind"] = devices[0].device_kind
            line = json.dumps(res)
            print(line, flush=True)
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
