#!/usr/bin/env python3
"""Time the gated delta rule's one-token step alone, plain against the
in-place kernel, on the chip: the table behind PERF.md §6, PR 46.

    chiprun -- python3 tools/gdn_step.py --config olmo-hybrid-7b-l8

No engine and no weights: the call ``models/llama.py::_gated_delta`` makes
for every step of a decode window and for the packed tail of a mixed
stream, at the configuration's linear-attention widths
(``benchmark/configs/<name>.json``: heads, key and value widths, linear
layers) over a state pool of ``--entries`` entries a layer. A case
``rows:live`` (``--cases``, default a window of 32 rows with 31 live, the
cell's packed tail of 32 slots with 30 live, and a tail of 64 with 30) has
its live rows first, each on its own entry at a position past 0, the rest
padding (position -1, an entry past the pool). One timed call is a jitted
loop of ``--steps`` steps over every linear layer in turn with the pool
donated, as a window's program runs them, so a launch's host time is
spread over ``steps x layers`` calls; the time a layer and step is the
median of ``--calls`` such loops on the host's clock around
``block_until_ready`` over that count. Beside it: the bytes a live row's
state moves once each way and their time at the HBM peak
(``benchmark/peaks.json``), and the largest difference between the two
forms' outputs and pools after one step from the same pool. One JSON line a
case on stdout and in ``chiprun_out/gdn_step/<config>.jsonl``. It fails
off the chip: a CPU time is not a device time (``--allow-cpu`` rehearses
the control flow with the kernel interpreted and writes
``<config>.cpu-rehearsal.jsonl``, never the chip's file).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)
sys.path.insert(1, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", required=True)
    ap.add_argument("--cases", default="32:31,32:30,64:30", help="rows:live, ...")
    ap.add_argument("--entries", type=int, default=160)
    ap.add_argument("--steps", type=int, default=8, help="steps a timed loop, each over every layer")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--seed", type=int, default=46)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse the control flow off the chip (times mean nothing)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import run_cell
    from dynamo_exp_tpu.models import ModelConfig
    from dynamo_exp_tpu.ops import gated_delta as gd

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.allow_cpu:
        print(f"no chip: platform={dev.platform}", file=sys.stderr)
        return 1
    path = args.config if args.config.endswith(".json") else os.path.join(
        BENCH, "configs", f"{args.config}.json"
    )
    name = os.path.basename(path).removesuffix(".json")
    mcfg = ModelConfig.from_hf_config(run_cell.hf_config(run_cell.load_json(path)))
    h, dk, dv = mcfg.linear_num_heads, mcfg.linear_key_head_dim, mcfg.linear_value_head_dim
    layers, entries = mcfg.num_linear_layers, args.entries
    if not layers or not gd.supported(h, dk, dv, gd.TILE):
        raise SystemExit(f"{name} has no linear-attention layer the kernel takes")
    hbm = run_cell.load_json(BENCH, "peaks.json")["devices"].get(dev.device_kind, {}).get("hbm_gbps")
    state_bytes = dk * h * dv * 4

    forms = {
        "plain": gd.gated_delta_step,
        "kernel": lambda *a: gd.gated_delta_step_kernel(*a, interpret=not on_chip),
    }

    def loop(step):
        def run(q, k, v, g, beta, pool, slots, positions, dst):
            def body(i, carry):
                pool, acc = carry
                o, pool = step(q, k, v, g, beta, pool, i % layers, slots, positions, dst)
                return pool, acc + o
            return jax.lax.fori_loop(
                0, args.steps * layers, body, (pool, jnp.zeros((q.shape[0], h * dv), jnp.float32))
            )
        return jax.jit(run, donate_argnums=(5,))

    out_dir = os.path.join(ROOT, "chiprun_out", "gdn_step")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"{name}{'' if on_chip else '.cpu-rehearsal'}.jsonl")
    rng = np.random.default_rng(args.seed)
    with open(out_path, "w") as out:
        for case in args.cases.split(","):
            rows, live = (int(x) for x in case.split(":"))
            if live > min(rows, entries):
                raise SystemExit(f"{case}: more live rows than rows or entries")
            q = rng.standard_normal((rows, h, dk))
            k = rng.standard_normal((rows, h, dk))
            q /= np.linalg.norm(q, axis=-1, keepdims=True) * dk**0.5
            k /= np.linalg.norm(k, axis=-1, keepdims=True)
            ins = [jnp.asarray(x, jnp.float32) for x in (
                q.reshape(rows, -1), k.reshape(rows, -1), rng.standard_normal((rows, h * dv)),
                -np.exp(rng.uniform(np.log(1e-3), np.log(3.0), (rows, h))),
                2.0 * rng.uniform(0.0, 1.0, (rows, h)) ** 0.5,
            )]
            alive = np.arange(rows) < live
            slots = jnp.asarray(np.where(alive, np.arange(rows), entries), jnp.int32)
            positions = jnp.asarray(np.where(alive, 100 + np.arange(rows), -1), jnp.int32)
            key = jax.random.PRNGKey(args.seed)
            fresh = jax.jit(lambda: jax.random.normal(key, (layers, entries, dk, h * dv), jnp.float32))
            line = {
                "config": name, "rows": rows, "live": live, "heads": h, "dk": dk, "dv": dv,
                "layers": layers, "entries": entries, "steps": args.steps, "calls": args.calls,
                "platform": dev.platform, "device_kind": dev.device_kind,
                "state_bytes_moved": 2 * live * state_bytes,
            }
            if hbm:
                line["hbm_bound_ms"] = 2 * live * state_bytes / (hbm * 1e9) * 1e3
            once = {}
            for form, step in forms.items():
                o, pool = jax.jit(step, donate_argnums=(5,))(*ins, fresh(), 1, slots, positions, slots)
                once[form] = (np.asarray(o)[:live], np.asarray(pool[1, :live]))
                del o, pool
                fn, pool, times = loop(step), fresh(), []
                for i in range(args.calls + 2):  # the first call compiles, the second loads
                    t0 = time.perf_counter()
                    pool, acc = fn(*ins, pool, slots, positions, slots)
                    jax.block_until_ready((pool, acc))
                    if i >= 2:
                        times.append(time.perf_counter() - t0)
                del pool, acc
                line[f"{form}_ms"] = statistics.median(times) / (args.steps * layers) * 1e3
            line["speedup"] = line["plain_ms"] / line["kernel_ms"]
            line["o_max_diff"] = float(np.abs(once["plain"][0] - once["kernel"][0]).max())
            line["state_max_diff"] = float(np.abs(once["plain"][1] - once["kernel"][1]).max())
            text = json.dumps(line)
            print(text, flush=True)
            out.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
