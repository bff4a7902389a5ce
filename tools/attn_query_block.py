#!/usr/bin/env python3
"""Time the attention kernel's tiled call alone at several query blocks,
on the chip: the table behind ``ops/ragged_attention.py::query_block``
(PERF.md §6, PR 40).

    chiprun -- python3 tools/attn_query_block.py --config olmo-hybrid-7b-l8
    chiprun -- python3 tools/attn_query_block.py --config qwen3-4b

No engine and no weights: the call ``forward_ragged`` makes for the tiled
part of one mixed dispatch of ``--nb`` slots, with a chip's share of the
configuration's heads (``num_attention_heads`` and ``num_key_value_heads``
over ``--tp`` of its ``serve_args``; ``benchmark/configs/<name>.json``), its
page size and a page pool of one layer no larger than its ``--num-pages``.
The stream is a long-document one: ``--spans`` gives each prefilling row's
chunk as ``tokens@first_position`` (default a 512-token chunk deep in one
prompt and the rest of the tiles from the start of another, as
``_plan_chunks`` hands the bucket's room to the earliest row), each span
aligned to the tile of 8, random queries and pages. For every ``--blocks``
entry that is a multiple of the tile: the call's time on the host's clock
around ``block_until_ready`` (median of ``--calls``), the context bytes its
passes read by ``block_plan``'s rule and their time at the HBM peak
(``benchmark/peaks.json``), the share of live slots in merged blocks, and
the largest difference from the output at a block of one tile. The packed
tail's one-query call over ``--tail-rows`` decode rows is timed once beside
them. One JSON line a block on stdout and in
``chiprun_out/attn_query_block/<config>.jsonl``.

A latent-attention configuration (``--config
openpangu-ultra-moe-718b-l5e16``) has two forms of the tiled call instead of
several blocks (``models/llama.py::_mla_half``), and the table is the rule's
(``latent_form_queries``): one row's chunk of ``--chunks`` queries at the
end of a context of ``--contexts`` tokens in a stream of 2048 slots, the
absorbed call alone, the absorbed call with the two products beside it
(``mla_absorb``), and the expanded call whole (its plan, paddings and the
expansion inside the kernel), each a median of ``--calls``; the operations
each form executes and the largest difference between the two results. A
configuration with an indexer (``--config glm-5-l5e16``) gives both forms
one drawn selection of ``index_topk`` positions a query (``keep``). It fails off the chip: a
CPU time is not a device time (``--allow-cpu`` rehearses the control flow
at the sizes given and writes ``<config>.cpu-rehearsal.jsonl``, never the
chip's file).
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

TILE = 8  # EngineConfig.ragged_q_tile


def build_stream(spans: list[tuple[int, int]], tiled: int, ps: int, pmax: int, rng):
    """(row_of, positions, table, pages used) of the tiled part: each
    span ``(tokens, first position)`` a row, aligned to the tile, the
    rest padding; every row on pages of its own."""
    import numpy as np

    row_of = np.zeros(tiled, np.int32)
    positions = np.full(tiled, -1, np.int32)
    table = np.zeros((len(spans), pmax), np.int32)
    at, page = 0, 1
    for r, (n, first) in enumerate(spans):
        width = -(-n // TILE) * TILE
        if at + width > tiled:
            raise SystemExit(f"spans outgrow the {tiled} tiled slots")
        row_of[at : at + width] = r
        positions[at : at + n] = first + np.arange(n)
        pages = -(-(first + n) // ps)
        table[r, :pages] = page + rng.permutation(pages)
        at, page = at + width, page + pages
    return row_of, positions, table, page


def timed(fn, calls: int) -> tuple[float, object]:
    import jax

    times, out = [], None
    for i in range(calls + 2):  # the first call compiles, the second loads
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        if i >= 2:
            times.append(time.perf_counter() - t0)
    return statistics.median(times), out


def draw_selection(key, positions, pad: int, ps: int, pmax: int, topk: int, interpret: bool,
                   tile: int = TILE):
    """A model with an indexer: drawn index scores in the scores kernel's
    layout for the stream and ``pad`` slots of no query behind it (what the
    call asks: ``EXPANDED_SELECTION_PAD``, ``selected_pad``), and the exact
    top ``topk`` a query (``sparse_index.select_tiled``)."""
    import jax
    import jax.numpy as jnp

    from dynamo_exp_tpu.ops import sparse_index as si

    shape = si.tiled_shape(positions.shape[0] + pad, tile, ps, pmax)
    bounds = jnp.pad(positions, (0, pad), constant_values=-1)
    drawn = jax.random.normal(key, shape, jnp.float32)
    T, C, qt, S = shape
    at = (jnp.arange(C)[:, None, None] * S + jnp.arange(S)[None, None])[None]
    scores = jnp.where(at <= bounds.reshape(T, 1, qt, 1), drawn, -jnp.inf)
    tau, allow = si.select_tiled(scores, bounds.reshape(T, qt), topk, interpret=interpret)
    return scores, tau.reshape(-1), allow.reshape(-1)


def latent_forms(args, name, config, mcfg, ecfg, opts, dev) -> int:
    """The latent model's table: both forms of the tiled call over one
    row's chunk, by chunk length and context."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_exp_tpu.models.llama import latent_form_queries, latent_value_lanes
    import functools

    from dynamo_exp_tpu.ops.ragged_attention import (
        _EXPANDED_QUERIES, EXPANDED_SELECTION_PAD, expanded_plan, ragged_paged_attention,
        ragged_paged_attention_expanded,
    )

    ps, H = opts.page_size, mcfg.num_heads
    r, dn, dr, dv = (mcfg.kv_lora_rank, mcfg.qk_nope_head_dim,
                     mcfg.qk_rope_head_dim, mcfg.v_head_dim)
    lanes, vl = mcfg.latent_lanes, latent_value_lanes(mcfg)
    chunks = [int(c) for c in args.chunks.split(",")]
    contexts = [int(c) for c in args.contexts.split(",")]
    n = max(2048, -(-max(chunks) // TILE) * TILE)
    interpret = dev.platform != "tpu"
    rule = latent_form_queries(mcfg, TILE, ps, jnp.bfloat16, interpret)
    pmax = -(-max(contexts) // ps)
    rng = np.random.default_rng(args.seed)
    dt = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
    pool = jnp.pad(
        jax.random.normal(keys[0], (pmax + 1, ps, r + dr), dt),
        ((0, 0), (0, 0), (0, lanes - r - dr)),
    )
    q = jax.random.normal(keys[1], (n, H, dn + dr), dt)
    w_ukv = jax.random.normal(keys[2], (r, H * (dn + dv)), dt) * r ** -0.5
    table = jnp.asarray((1 + rng.permutation(pmax))[None].astype(np.int32))
    row_of = jnp.zeros((n,), jnp.int32)
    scale = (dn + dr) ** -0.5
    w3 = w_ukv.reshape(r, H, dn + dv)
    w_abs = jnp.concatenate([
        jnp.pad(jnp.swapaxes(w3[..., :dn], 0, 2), ((0, 0), (0, 0), (0, lanes - r))),
        jnp.broadcast_to(
            jnp.pad(jnp.eye(dr, dtype=dt), ((0, 0), (r, lanes - r - dr)))[:, None], (dr, H, lanes)
        ),
    ])

    def absorbed(q, positions, keep=None):
        return ragged_paged_attention(
            q, pool, None, table, row_of, positions, num_kv_heads=1, q_tile=TILE,
            sm_scale=scale, interpret=interpret, value_dim=vl,
            **({} if keep is None else {"keep": (keep[0][: n // TILE], keep[1][:n], keep[2][:n])}),
        )

    @jax.jit
    def absorbed_whole(q, positions, keep=None):
        o = absorbed(jnp.einsum("nhd,dhc->nhc", q, w_abs), positions, keep)
        return jnp.einsum("nhc,chv->nhv", o[..., :r], w3[..., dn:])

    @functools.partial(jax.jit, static_argnames=("q_max", "q_sub", "kv_chunk"))
    def expanded(q, positions, keep=None, q_max=None, q_sub=None, kv_chunk=None):
        plan_sizes = {k: v for k, v in (("q_max", q_max), ("q_sub", q_sub)) if v}
        _, items = expanded_plan(row_of, positions, 1, TILE, TILE, **plan_sizes)
        return ragged_paged_attention_expanded(
            q, pool, w_ukv, table, positions, items, rank=r, nope=dn, q_tile=TILE,
            sm_scale=scale, interpret=interpret, **plan_sizes,
            **({"kv_chunk": kv_chunk} if kv_chunk else {}),
            **({} if keep is None else {"keep": keep}),
        )

    selection = jax.jit(lambda positions: draw_selection(
        keys[3], positions, EXPANDED_SELECTION_PAD, ps, pmax, mcfg.index_topk, interpret
    ))

    # other sizes of a pass than the module's: "q_max,q_sub,kv_chunk;..."
    tried = [
        dict(zip(("q_max", "q_sub", "kv_chunk"), (int(x) for x in v.split(","))))
        for v in args.passes.split(";") if v
    ]

    q_abs = jax.block_until_ready(jnp.einsum("nhd,dhc->nhc", q, w_abs))
    head = {
        "config": name, "platform": dev.platform, "device_kind": dev.device_kind,
        "heads": H, "widths": [r, dn, dr, dv], "lanes": [lanes, vl], "page_size": ps,
        "stream_slots": n, "rule_min_queries": rule, "seed": args.seed, "calls": args.calls,
    }
    print(json.dumps(head), flush=True)
    out_dir = os.path.join(ROOT, "chiprun_out", "attn_query_block")
    os.makedirs(out_dir, exist_ok=True)
    suffix = "" if dev.platform == "tpu" else ".cpu-rehearsal"
    with open(os.path.join(out_dir, f"{name}{suffix}.jsonl"), "w") as f:
        f.write(json.dumps(head) + "\n")
        for context in contexts:
            for chunk in chunks:
                if chunk > context:
                    continue
                pos = np.full(n, -1, np.int32)
                pos[:chunk] = context - chunk + np.arange(chunk)
                pos = jnp.asarray(pos)
                keep = jax.block_until_ready(selection(pos)) if mcfg.has_indexer else None
                a_ms, _ = timed(lambda: absorbed(q_abs, pos, keep), args.calls)
                w_ms, o_a = timed(lambda: absorbed_whole(q, pos, keep), args.calls)
                x_ms, o_x = timed(lambda: expanded(q, pos, keep), args.calls)
                pairs = chunk * context - chunk * (chunk - 1) // 2
                diff = np.abs(
                    np.asarray(o_a[:chunk], np.float32) - np.asarray(o_x[:chunk], np.float32)
                ).max()
                passes = -(-chunk // _EXPANDED_QUERIES)
                other = {
                    ",".join(str(x) for x in sizes.values()):
                        1e3 * timed(lambda: expanded(q, pos, keep, **sizes), args.calls)[0]
                    for sizes in tried
                }
                line = {
                    "chunk": chunk, "context": context, "pairs": pairs,
                    "absorbed_kernel_ms": 1e3 * a_ms, "absorbed_with_products_ms": 1e3 * w_ms,
                    "expanded_ms": 1e3 * x_ms, "expanded_wins": x_ms < w_ms,
                    "absorbed_gflop": pairs * H * 2 * (lanes + vl) / 1e9,
                    "expanded_gflop": (pairs * H * 2 * (dn + dr + dv)
                                       + passes * context * H * 2 * r * (dn + dv)) / 1e9,
                    **({"selected": True, "index_topk": mcfg.index_topk} if keep else {}),
                    "max_abs_diff_between_forms": float(diff),
                    **({"expanded_ms_at_other_passes": other} if other else {}),
                }
                print(json.dumps(line), flush=True)
                f.write(json.dumps(line) + "\n")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--chunks", default="64,128,160,256,512,2048",
                    help="a latent configuration: the row's queries in the call")
    ap.add_argument("--passes", default="",
                    help="a latent configuration: other sizes of the expanded call's pass to time, "
                         "'q_max,q_sub,kv_chunk;...' (ops/ragged_attention.py::_EXPANDED_*)")
    ap.add_argument("--contexts", default="2048,4096,16384",
                    help="a latent configuration: the context the chunk ends")
    ap.add_argument("--config", required=True)
    ap.add_argument("--nb", type=int, default=4096, help="the mixed dispatch's token bucket")
    ap.add_argument("--spans", default="512@6144,3520@0")
    ap.add_argument("--tail-rows", type=int, default=30)
    ap.add_argument("--tail-context", type=int, default=4200)
    ap.add_argument("--blocks", default="8,32,64,128")
    ap.add_argument("--seed", type=int, default=40)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse the control flow off the chip (times mean nothing)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import run_cell
    from dynamo_exp_tpu import run as server
    from dynamo_exp_tpu.engine import EngineConfig
    from dynamo_exp_tpu.models import ModelConfig
    from dynamo_exp_tpu.models.llama import ragged_query_block
    from dynamo_exp_tpu.ops.ragged_attention import (
        block_plan, merged_slot_counts, ragged_paged_attention, selected_pad,
    )

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        print(f"no chip: platform={dev.platform}", file=sys.stderr)
        return 1
    path = args.config if args.config.endswith(".json") else os.path.join(
        BENCH, "configs", f"{args.config}.json"
    )
    name = os.path.basename(path).removesuffix(".json")
    config = run_cell.load_json(path)
    mcfg = ModelConfig.from_hf_config(run_cell.hf_config(config))
    opts = server.parse_args(["in=http", "out=tpu", *config["serve_args"]])
    ecfg = EngineConfig(model=mcfg, max_decode_slots=opts.max_decode_slots, page_size=opts.page_size)
    if mcfg.is_latent:
        return latent_forms(args, name, config, mcfg, ecfg, opts, dev)
    heads, kv_heads, hd = mcfg.num_heads // opts.tp, mcfg.num_kv_heads // opts.tp, mcfg.head_dim_
    ps = opts.page_size
    tiled = args.nb - ecfg.ragged_packed_slots(args.nb)
    spans = [tuple(int(x) for x in s.split("@")) for s in args.spans.split(",")]
    rng = np.random.default_rng(args.seed)
    pmax = -(-max(max(first + n for n, first in spans), args.tail_context + 1) // ps)
    row_of, positions, table, used = build_stream(spans, tiled, ps, pmax, rng)
    tail_pages = -(-(args.tail_context + 1) // ps)
    pool_pages = used + tail_pages
    if pool_pages > opts.num_pages:
        raise SystemExit(f"{pool_pages} pages wanted, the configuration's pool holds {opts.num_pages}")
    dt = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
    q = jax.random.normal(keys[0], (tiled, heads, hd), dt)
    # a model with an indexer keeps its index key behind the heads' keys
    k = jax.random.normal(keys[1], (pool_pages, ps, kv_heads * hd + mcfg.index_lanes), dt)
    v = jax.random.normal(keys[2], (pool_pages, ps, kv_heads * hd), dt)
    interpret = dev.platform != "tpu"
    token_bytes = 2 * kv_heads * hd * k.dtype.itemsize
    hbm = run_cell.load_json(BENCH, "peaks.json")["devices"].get(dev.device_kind, {}).get("hbm_gbps")
    rule = ragged_query_block(mcfg, TILE, mcfg.num_kv_heads)
    selected = mcfg.has_indexer  # the call under a drawn selection (``keep``)
    head = {
        "config": name, "platform": dev.platform, "device_kind": dev.device_kind,
        "heads_per_chip": [heads, kv_heads, hd], "page_size": ps, "nb": args.nb, "tiled_slots": tiled,
        "spans": spans, "live_slots": int((positions >= 0).sum()),
        "context_mean": float((positions[positions >= 0] + 1).mean()),
        "kv_bytes_a_token": token_bytes, "query_block_rule": rule, "seed": args.seed, "calls": args.calls,
        **({"selected": True, "index_topk": mcfg.index_topk} if selected else {}),
    }
    print(json.dumps(head), flush=True)
    out_dir = os.path.join(ROOT, "chiprun_out", "attn_query_block")
    os.makedirs(out_dir, exist_ok=True)
    suffix = "" if dev.platform == "tpu" else ".cpu-rehearsal"
    dev_args = (jnp.asarray(table), jnp.asarray(row_of), jnp.asarray(positions))
    base = None
    # ONE selection for every block, run to whole blocks of the largest
    keep = {}
    if selected:
        pad = max(selected_pad(tiled, int(b)) for b in args.blocks.split(","))
        keep["keep"] = jax.block_until_ready(jax.jit(lambda: draw_selection(
            keys[3], dev_args[2], pad, ps, pmax, mcfg.index_topk, interpret
        ))())
        # the one-tile form takes the stream's own tiles and no more
        one_tile = {"keep": jax.block_until_ready(
            (keep["keep"][0][: tiled // TILE], keep["keep"][1][:tiled], keep["keep"][2][:tiled])
        )}
    with open(os.path.join(out_dir, f"{name}{suffix}.jsonl"), "w") as f:
        f.write(json.dumps(head) + "\n")
        for qb in (int(b) for b in args.blocks.split(",")):
            if qb % TILE:
                continue
            ms, out = timed(lambda: ragged_paged_attention(
                q, k, v, *dev_args, num_kv_heads=kv_heads, q_tile=TILE, q_block=qb, interpret=interpret,
                **(one_tile if selected and qb == TILE else keep),
            ), args.calls)
            out = np.asarray(out, np.float32)
            base = out if base is None else base
            *_, tile_kvlen, _row, block_kvlen = block_plan(row_of, positions, TILE, qb, xp=np)
            by_tile = tile_kvlen.reshape(block_kvlen.shape[0], -1).sum(axis=1)
            tokens = int(np.where(block_kvlen >= 0, block_kvlen, by_tile).sum())
            merged, walked = merged_slot_counts(row_of, positions, TILE, qb)
            line = {
                "q_block": qb, "is_rule": qb == rule, "call_ms": 1e3 * ms,
                "context_gb_read": tokens * token_bytes / 1e9,
                "hbm_bound_ms": None if hbm is None else tokens * token_bytes / hbm / 1e6,
                "merged_slot_share": 100.0 * merged / max(merged + walked, 1),
                "max_abs_diff_vs_first": float(np.abs(out - base).max()),
            }
            print(json.dumps(line), flush=True)
            f.write(json.dumps(line) + "\n")
        # The packed tail's call: one query a cell, each row its own context.
        n = args.tail_rows
        tail_table = np.tile(used + np.arange(tail_pages, dtype=np.int32), (n, 1))
        tail_args = (
            jnp.asarray(tail_table), jnp.arange(n, dtype=jnp.int32),
            jnp.full((n,), args.tail_context, jnp.int32),
        )
        qt = jax.random.normal(keys[3], (n, heads, hd), dt)
        tail_keep = {}
        if selected:
            tail_keep["keep"] = jax.block_until_ready(jax.jit(lambda: draw_selection(
                keys[3], tail_args[2], 0, ps, tail_pages, mcfg.index_topk, interpret, tile=1
            ))())
        ms, _ = timed(lambda: ragged_paged_attention(
            qt, k, v, *tail_args, num_kv_heads=kv_heads, q_tile=1, interpret=interpret,
            **tail_keep,
        ), args.calls)
        line = {"tail_rows": n, "tail_context": args.tail_context, "call_ms": 1e3 * ms}
        print(json.dumps(line), flush=True)
        f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
