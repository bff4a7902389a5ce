#!/usr/bin/env python3
"""Time the indexer's scores call alone at several query blocks, on the
chip: the table behind ``ops/sparse_index.py::index_block`` (PERF.md §6,
PR 50).

    chiprun -- python3 tools/index_scores_block.py --config glm-5-l5e16

No engine and no weights: the call ``models/llama.py::_selection_tiled``
makes for the tiled part of one mixed dispatch, with the configuration's
index heads and width (``index_n_heads`` x ``index_head_dim``;
``benchmark/configs/<name>.json``), the page size and table width of its
``serve_args`` and a pool of one layer's index keys. The stream is a
long-context one: ``--spans`` gives each prefilling row's chunk as
``tokens@first_position`` (default three rows that end 4 k, 8 k and 15 k
into their prompts), each span aligned to the tile of 8, the rest padding
(``--pad`` slots more, as the expanded call asks), random queries, weights
and keys. For every ``--blocks`` entry that is a multiple of the tile and
every ``--products`` entry that fits it: the call's time on the host's clock
around ``block_until_ready`` (median of ``--calls``), the operations of the
pairs asked for (``2 x heads x width`` a query-context pair) as a share of the
bf16 peak and the bytes (a row's keys once, the queries and weights once,
the scores' whole layout written once) as a share of HBM's
(``benchmark/peaks.json``), the share of live slots whose block is read in
one pass, and whether the scores equal the per-tile pass's bit for bit.
The one-query call (``q_tile`` 1: a window's step, a mixed stream's packed
tail) over ``--tail-rows`` decode rows ``--tail-context`` tokens into their
contexts is timed once beside them.
``--hash`` prints a digest of the scores too, for a comparison with another
commit's kernel on the same inputs (``stream`` builds them from the seed).
One JSON line a block on stdout and in
``chiprun_out/index_scores_block/<config>.jsonl``. It fails off the chip: a
CPU time is not a device time (``--allow-cpu`` rehearses the control flow in
interpret mode at the sizes given and writes ``<config>.cpu-rehearsal.jsonl``,
never the chip's file).
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

TILE = 8  # EngineConfig.ragged_q_tile


def stream(spans, slots: int, heads: int, width: int, ps: int, pmax: int, seed: int,
           tile: int = TILE):
    """``index_scores``' arguments for ``spans`` ((tokens, first position) a
    row, aligned to ``tile``) in a stream of ``slots``: (q, w, pool, table,
    row_of, bounds), bfloat16 queries and keys, every row on pages of its own."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    row_of = np.zeros(slots, np.int32)
    bounds = np.full(slots, -1, np.int32)
    table = np.zeros((len(spans), pmax), np.int32)
    at, page = 0, 1
    for r, (n, first) in enumerate(spans):
        aligned = -(-n // tile) * tile
        if at + aligned > slots or first + n > pmax * ps:
            raise SystemExit(f"span {n}@{first} outgrows {slots} slots or {pmax * ps} positions")
        row_of[at : at + aligned] = r
        bounds[at : at + n] = first + np.arange(n)
        pages = -(-(first + n) // ps)
        table[r, :pages] = page + rng.permutation(pages)
        at, page = at + aligned, page + pages
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    lanes = -(-width // 128) * 128
    pool = jnp.pad(
        jax.random.normal(keys[0], (page, ps, width), jnp.bfloat16),
        ((0, 0), (0, 0), (0, lanes - width)),
    )
    q = jax.random.normal(keys[1], (slots, heads, width), jnp.bfloat16)
    w = jax.random.normal(keys[2], (slots, heads), jnp.float32) * heads ** -0.5
    return q, w, pool, jnp.asarray(table), jnp.asarray(row_of), jnp.asarray(bounds)


def digest(scores) -> str:
    import numpy as np

    return hashlib.sha256(np.asarray(scores).tobytes()).hexdigest()[:16]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", required=True)
    ap.add_argument("--nb", type=int, default=4096, help="slots of the stream's tiled part")
    ap.add_argument("--pad", type=int, default=256, help="slots of no query behind them")
    ap.add_argument("--spans", default="2048@2048,1536@6656,512@14848")
    ap.add_argument("--blocks", default="8,64,128,256")
    ap.add_argument("--products", default="32", help="slots a product, each tried a block")
    ap.add_argument("--tail-rows", type=int, default=32,
                    help="decode rows of the one-query call timed beside them (0: none)")
    ap.add_argument("--tail-context", type=int, default=9000)
    ap.add_argument("--seed", type=int, default=50)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--hash", action="store_true")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse off the chip in interpret mode; no device time comes of it")
    args = ap.parse_args()

    import jax
    import numpy as np

    from dynamo_exp_tpu.ops import sparse_index as si
    from dynamo_exp_tpu.ops.ragged_attention import merged_slot_counts

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.allow_cpu:
        raise SystemExit(f"no chip here ({dev.platform}): a CPU time is not a device time")
    with open(os.path.join(ROOT, "benchmark", "configs", f"{args.config}.json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peak = json.load(f)["devices"].get(dev.device_kind)
    if on_chip and peak is None:
        raise SystemExit(f"benchmark/peaks.json has no {dev.device_kind!r}")
    serve = dict(zip(config["serve_args"][::2], config["serve_args"][1::2]))
    ps = int(serve["--page-size"])
    pmax = -(-int(serve["--max-model-len"]) // ps)
    heads, width = config["index_n_heads"], config["index_head_dim"]
    spans = [tuple(int(x) for x in s.split("@")) for s in args.spans.split(",")]
    q, w, pool, table, row_of, bounds = case = stream(
        spans, args.nb + args.pad, heads, width, ps, pmax, args.seed
    )
    live = np.asarray(bounds) >= 0
    pairs = int((np.asarray(bounds)[live] + 1).sum())
    context = sum(first + n for n, first in spans)
    shape = si.tiled_shape(q.shape[0], TILE, ps, pmax)
    operations = 2 * heads * width * pairs
    moved = (
        context * pool.shape[2] * pool.dtype.itemsize
        + int(live.sum()) * heads * (width * q.dtype.itemsize + 4)
        + int(np.prod(shape)) * 4
    )
    name = args.config if on_chip else f"{args.config}.cpu-rehearsal"
    out_dir = os.path.join(ROOT, "chiprun_out", "index_scores_block")
    os.makedirs(out_dir, exist_ok=True)
    by_tile = None

    def timed(call, case):
        times, scores = [], None
        for i in range(args.calls + 2):  # the first call compiles, the second loads
            t0 = time.perf_counter()
            scores = jax.block_until_ready(call(*case))
            if i >= 2:
                times.append(time.perf_counter() - t0)
        return statistics.median(times), scores

    with open(os.path.join(out_dir, f"{name}.jsonl"), "w") as out:
        for block in (int(b) for b in args.blocks.split(",")):
            if block % TILE:
                continue
            products = [int(p) for p in args.products.split(",")] if block > TILE else [TILE]
            for product in (p for p in products if p <= block):
                call = jax.jit(lambda *a, block=block, product=product: si._index_scores(
                    *a, TILE, block, not on_chip, product
                ))
                seconds, scores = timed(call, case)
                if by_tile is None:
                    by_tile = np.asarray(scores).view(np.uint32)
                merged, tile = merged_slot_counts(
                    np.asarray(row_of)[: args.nb], np.asarray(bounds)[: args.nb], TILE, block
                )
                line = {
                    "config": args.config, "device": dev.device_kind, "platform": dev.platform,
                    "block": block, "product": product, "rule": si.index_block(TILE, heads),
                    "slots": int(q.shape[0]), "live": int(live.sum()), "pairs": pairs,
                    "context": context, "scores_shape": list(shape),
                    "block_slot_share": 100.0 * merged / max(merged + tile, 1),
                    "equals_tile_pass": bool(
                        np.array_equal(np.asarray(scores).view(np.uint32), by_tile)
                    ),
                }
                if args.hash:
                    line["sha256"] = digest(scores)
                if on_chip:
                    line.update(
                        call_ms=seconds * 1e3,
                        ops_share_of_bf16_peak=100 * operations / seconds / (peak["bf16_tflops"] * 1e12),
                        bytes_share_of_hbm_peak=100 * moved / seconds / (peak["hbm_gbps"] * 1e9),
                    )
                print(json.dumps(line), flush=True)
                out.write(json.dumps(line) + "\n")
        if args.tail_rows:
            # a window's step, a mixed stream's packed tail: a row a slot
            rows = [(1, args.tail_context - 1 - 37 * r) for r in range(args.tail_rows)]
            tail = stream(rows, args.tail_rows, heads, width, ps, pmax, args.seed + 1, tile=1)
            seconds, scores = timed(jax.jit(lambda *a: si.index_scores(
                *a, q_tile=1, interpret=not on_chip
            )), tail)
            pairs = int((np.asarray(tail[5]) + 1).sum())
            line = {
                "config": args.config, "device": dev.device_kind, "platform": dev.platform,
                "q_tile": 1, "rows": args.tail_rows, "pairs": pairs,
            }
            if args.hash:
                line["sha256"] = digest(scores)
            if on_chip:
                line.update(
                    call_ms=seconds * 1e3,
                    ops_share_of_bf16_peak=100 * 2 * heads * width * pairs / seconds
                    / (peak["bf16_tflops"] * 1e12),
                )
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
