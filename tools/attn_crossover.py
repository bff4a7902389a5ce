#!/usr/bin/env python3
"""Time a decode step of the windowed program in both attention forms,
at every row bucket, on the chip: the table behind
``aot/lattice.py::resolve_ragged_key``, which since PR 30 builds every
window of a kernel engine on the kernel (PERF.md §6, PR 30).

    chiprun -- python3 tools/attn_crossover.py --config qwen3-4b
    chiprun --chips 4 -- python3 tools/attn_crossover.py --config mixtral-8x7b-l16

One engine with the configuration's widths, random weights and its
``serve_args`` (``benchmark/configs/<name>.json``); for each row bucket
the two programs an ``attention_impl`` of ``"pallas"`` and of ``"xla"``
would build for a pure-decode window whose page bucket is the smallest
(1024 tokens), run over the same live rows: contexts drawn from
``--contexts`` by ``--seed``, each row on pages of its own as far as the
pool goes. A call is ``decode_window`` whole steps (attention, relayouts,
projections, feed-forward, head, sampling), timed on the host's clock
around ``block_until_ready``; a step is a call over ``decode_window``.
One JSON line a row bucket on stdout and in
``chiprun_out/attn_crossover/<config>.jsonl``. It fails off the chip: a
CPU time is not a device time (``--allow-cpu`` rehearses the control
flow and writes ``<config>.cpu-rehearsal.jsonl``, never the chip's file).

Behind them, a line a row bucket for **the one-query call alone**
(``ops/ragged_attention.py::ragged_paged_attention`` at ``q_tile=1``, what
a window's step calls once a layer): a chip's share of the heads, random
queries and pages, every row live at a context of exactly 1, 3 and 32
chunks of 128 tokens (``--one-query-chunks``), ``--one-query-reps`` calls
chained inside one program (a call's result is the next one's queries) so
that the host's dispatch is not in the number. A straight line through the
three, ``time a row = a_row + a_chunk x chunks``, splits the call into what
a row costs whatever it reads (its first fetch where nothing hides it, its
state's zeroing, the divide and the transpose) and what a chunk costs (16
page copies and a product pair a KV head): PERF.md §6, PR 52 and PR 54.
``--one-query-rows 1,8,32`` times the same call at those rows a grid cell
beside the rule's (``one_query_block``; 1 is the one-row cell every call
was before PR 52, whose chunks are worked head by head: the reference for
the schedule ``one_query_schedule`` gives a call, which a line names), the
table behind the rule. A cache by kind (``mimo-v2-flash-l11e32``,
``k-exaone-236b-l8e16``) gets a line a form and row bucket, each form at
its own KV heads, widths, window and sink; where values are narrower than
keys the next call's queries are the result beside the old queries' last
lanes. ``--contexts ''`` skips the window lines and builds no engine. Not
a latent pool.

``--tile N`` times **the tiled call at one tile a row** in its place (a
block model's pass: ``--config sdar-30b-a3b-l6 --tile 4``): every row a tile
of N queries at the end of its context, each seeing its whole tile
(``bounds``), no block named, so the kernel's own rule picks the form (tiles
walked as rows on one copy pipeline where their chunk is worked for the KV
heads together, else a tile a grid cell, as every such call was before PR
56); ``--one-query-rows 1`` is the tile a cell. The same line,
``a tile + a chunk x chunks``. ``--variant copies`` and ``--variant
products`` time the call with its products, or its page copies, taken out
(PR 52's and PR 54's split of a chunk's time; results mean nothing, and only
a call whose chunks are worked for all the KV heads together has the seam).
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


def draw_contexts(kind: str, nb: int, rng) -> list[int]:
    """``uniform``: 100–1000 tokens, flat. ``chat``: the chat cells'
    prompt (lognormal, median 160, sigma 1.0) plus half an output
    (median 140, sigma 0.8), clipped to 100–1000, and one row at 1000:
    what fixes a window's page bucket is its longest row."""
    if kind == "uniform":
        return [int(c) for c in rng.integers(100, 1001, nb)]
    ctx = rng.lognormal(5.075, 1.0, nb) + 0.5 * rng.lognormal(4.942, 0.8, nb)
    ctx = [int(min(max(c, 100), 1000)) for c in ctx]
    ctx[0] = 1000
    return ctx


def live_window_args(engine, key: tuple, contexts: list[int], rng) -> tuple[list, int]:
    """``aot.warmup.variant_call_args`` with every row live: row ``i``
    feeds a token at position ``contexts[i]`` and stays in the window
    for all its steps (no stop token, no capacity or budget gate).
    Returns the arguments and the pages the rows' tables name (past
    the pool's size they wrap onto pages another row holds)."""
    import numpy as np

    from dynamo_exp_tpu.aot.launch import launch_buffer
    from dynamo_exp_tpu.aot.warmup import variant_call_args

    cfg = engine.cfg
    nb = key[0]
    packed, fields = launch_buffer(cfg, key)
    fields["tokens"][:] = rng.integers(0, cfg.model.vocab_size, nb)
    fields["positions"][:] = contexts
    fields["max_pos"][:] = cfg.max_model_len - 1
    table = fields["table"]
    order, at = rng.permutation(np.arange(1, cfg.num_pages)), 0
    for i, c in enumerate(contexts):
        n = (c + cfg.decode_window) // cfg.page_size + 1
        table[i, :n] = order[(at + np.arange(n)) % len(order)]
        at += n
    # Each live row its own state entry (a field only such a model has).
    fields["state_slots"][:] = np.arange(nb) % cfg.max_decode_slots
    return list(variant_call_args(engine, key, packed)), at


def time_program(engine, key: tuple, args: list, calls: int) -> list[float]:
    import jax

    fn = engine._ragged_fn_from_key(key)
    out = []
    has_state = engine.seq_state is not None  # the pool rides last, donated
    for i in range(calls + 2):
        args[0], args[1], args[2] = engine.params, engine.k_cache, engine.v_cache
        if has_state:
            args[-1] = engine.seq_state
        t0 = time.perf_counter()
        res = fn(*args)
        if has_state:
            *res, engine.seq_state = res
        ys, engine.k_cache, engine.v_cache, _t, _p = res
        jax.block_until_ready((ys, engine.k_cache))
        if i >= 2:  # the first call compiles, the second loads
            out.append(time.perf_counter() - t0)
    return out


def one_query_forms(mcfg, ecfg) -> list[dict]:
    """The shapes of the model's one-query calls on one chip, a form of
    attention each: one for a model of one pool pair, ``full`` and
    ``sliding`` for a cache by kind (their KV heads, the sliding form's
    window, sink and smaller pool)."""
    forms = sorted(set(mcfg.attn_forms) - {""}) if mcfg.kv_by_kind else [""]
    return [
        {
            "form": form,
            "heads": mcfg.num_heads // ecfg.tp,
            "kv_heads": mcfg.kv_heads_of(form) // ecfg.tp,
            "width": mcfg.head_dim_,
            "value_width": mcfg.value_head_dim_,
            "sliding": mcfg.sliding_window if form == "sliding" else None,
            "sink": form in mcfg.sink_forms,
            "num_pages": ecfg.sliding_pool_pages if form == "sliding" else ecfg.num_pages,
        }
        for form in forms
    ]


def one_query_lines(args, shape: dict, ps: int, dev):
    """The one-query call alone at one form's ``shape``
    (``one_query_forms``), a line a row bucket: its time at each context of
    ``--one-query-chunks`` and the fit a row / a chunk. A sliding form
    walks its window, not its context: one chunk at a context of one, two
    at a longer one, whose query is put mid-chunk so that its window
    straddles; the fit is over the chunks walked."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_exp_tpu.ops import ragged_attention as ra

    tile = args.tile
    attention = ra.ragged_paged_attention
    if args.variant != "whole":
        # Patched where the kernel's body looks them up; the jitted entry
        # point would keep an earlier trace, so the call is its plain form.
        attention = attention.__wrapped__
        if args.variant == "copies":
            ra._heads_together = lambda q, k_of, v_of, live, state, **_: state
        else:
            ra._chunk_copies = lambda *_, **__: []

    heads, kv_heads, hd, vd = shape["heads"], shape["kv_heads"], shape["width"], shape["value_width"]
    sliding, num_pages, chunk = shape["sliding"], shape["num_pages"], 128
    chunks = [int(c) for c in args.one_query_chunks.split(",")]
    forms = [None] + [int(r) for r in args.one_query_rows.split(",") if r]
    interpret = dev.platform != "tpu"
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
    k = jax.random.normal(keys[0], (num_pages, ps, kv_heads * hd), jnp.bfloat16)
    v = jax.random.normal(keys[1], (num_pages, ps, kv_heads * vd), jnp.bfloat16)
    more = {"sliding": sliding}
    if shape["sink"]:
        more["sink"] = jax.random.normal(keys[3], (heads,), jnp.float32)
    pages = max(chunks) * chunk // ps

    def chained(q_block):
        kw = dict(more) if q_block is None else {"q_block": q_block, **more}

        @jax.jit
        def run(q, k, v, table, positions):  # the pools as arguments: a closure's are constants of the program
            rows = jnp.arange(q.shape[0], dtype=jnp.int32) // tile
            # a block model's query sees its whole tile
            seen = {"bounds": positions | (tile - 1)} if tile > 1 else {}

            def call(_, x):
                out = attention(
                    x, k, v, table, rows, positions, num_kv_heads=kv_heads, q_tile=tile,
                    interpret=interpret, **kw, **seen,
                )
                # the next call's queries: where values are narrower than
                # keys, the lanes the result has not stay the queries'
                return out if vd == hd else jnp.concatenate([out, x[..., vd:]], axis=-1)

            return jax.lax.fori_loop(0, args.one_query_reps, call, q)

        return run

    for nb in (int(r) for r in args.rows.split(",")):
        rng = np.random.default_rng([args.seed, nb, 2])
        # each row on pages of its own as far as the pool goes
        table = (1 + (np.arange(nb * pages) % (num_pages - 1))).reshape(nb, pages)
        table = jnp.asarray(rng.permutation(table.reshape(-1)).reshape(nb, pages), jnp.int32)
        q = jax.random.normal(keys[2], (nb * tile, heads, hd), jnp.bfloat16)
        for q_block in forms:
            if q_block is not None and (q_block > nb or tile > 1 and q_block > 1):
                continue  # tiles: the rule's, or a tile a cell
            run = chained(q_block and q_block * tile)
            line = {
                "one_query": True, "form": shape["form"], "nb": nb, "rows_a_cell": q_block or "rule",
                "tile": tile, "variant": args.variant,
            }
            rows_a_cell = q_block or ra.one_query_block(nb, heads, hd, vd, tile, kv_heads)
            line["rows_a_cell_is"] = rows_a_cell
            line["schedule"] = ra.one_query_schedule(rows_a_cell, kv_heads, heads * tile)
            walked = []
            for c in chunks:
                at = c * chunk - 1
                if sliding is not None and c > 1:
                    at -= chunk // 2 + 1  # its window's first key then lies inside a page
                walked.append(c if sliding is None else min(c, 2))
                # a tile's queries end at ``at``; one query is its own tile
                positions = jnp.tile(at - tile + 1 + jnp.arange(tile, dtype=jnp.int32), nb)
                times = []
                for i in range(args.calls + 2):  # the first call compiles, the second loads
                    t0 = time.perf_counter()
                    jax.block_until_ready(run(q, k, v, table, positions))
                    if i >= 2:
                        times.append(time.perf_counter() - t0)
                line[f"call_us_at_{c}_chunks"] = 1e6 * statistics.median(times) / args.one_query_reps
            if len(set(walked)) > 1:
                a_chunk, a_row = np.polyfit(walked, [line[f"call_us_at_{c}_chunks"] / nb for c in chunks], 1)
                line["us_a_row"], line["us_a_chunk"] = float(a_row), float(a_chunk)
            yield line


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", required=True)
    ap.add_argument("--rows", default="1,2,4,8,16,32,64")
    ap.add_argument("--contexts", default="uniform,chat",
                    help="context draws of the window lines ('' skips them: the one-query lines alone)")
    ap.add_argument("--seed", type=int, default=30)
    ap.add_argument("--calls", type=int, default=12)
    ap.add_argument("--one-query-chunks", default="1,3,32",
                    help="contexts of the one-query call alone, in chunks of 128 tokens ('' skips it)")
    ap.add_argument("--one-query-rows", default="",
                    help="rows a grid cell to time beside the rule's, e.g. 1,8,32")
    ap.add_argument("--one-query-reps", type=int, default=128)
    ap.add_argument("--tile", type=int, default=1,
                    help="time the tiled call at one tile of this many queries a row (a block model's pass)")
    ap.add_argument("--variant", default="whole", choices=("whole", "copies", "products"),
                    help="the call as it is, or with its products / its page copies taken out")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse the control flow off the chip (tiny sizes; times mean nothing)")
    args = ap.parse_args()

    import jax
    import numpy as np

    import run_cell
    from dynamo_exp_tpu import run as server
    from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
    from dynamo_exp_tpu.models import ModelConfig

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        print(f"no chip: platform={dev.platform}", file=sys.stderr)
        return 1
    # A path to a file of the same form rehearses a tiny model off the chip.
    path = args.config if args.config.endswith(".json") else os.path.join(
        BENCH, "configs", f"{args.config}.json"
    )
    name = os.path.basename(path).removesuffix(".json")
    config = run_cell.load_json(path)
    mcfg = ModelConfig.from_hf_config(run_cell.hf_config(config))
    opts = server.parse_args(["in=http", "out=tpu", *config["serve_args"]])
    ecfg = EngineConfig(
        model=mcfg, max_decode_slots=opts.max_decode_slots, page_size=opts.page_size,
        num_pages=opts.num_pages, max_model_len=min(opts.max_model_len, mcfg.max_position_embeddings),
        tp=opts.tp, kv_dtype=opts.kv_dtype,
        # Both forms come from one engine: "pallas" makes the engine
        # resolve the kernel (interpreted off the chip), and a key with a
        # page bound is the program attention_impl="xla" builds.
        attention_impl="pallas", pallas_interpret=dev.platform != "tpu",
    )
    kinds = [c for c in args.contexts.split(",") if c]
    if kinds:
        engine = TPUEngine(ecfg)
        if engine._attn_impl != "pallas":
            print("the kernel cannot take this layout", file=sys.stderr)
            return 1
    pages = ecfg.ragged_page_bucket_for(1)
    head = {
        "config": name, "platform": dev.platform, "device_kind": dev.device_kind,
        "device_count": jax.device_count(), "tp": ecfg.tp,
        "heads_per_chip": [mcfg.num_heads // ecfg.tp, mcfg.num_kv_heads // ecfg.tp, mcfg.head_dim_],
        "page_bucket_tokens": pages * ecfg.page_size, "decode_window": ecfg.decode_window,
        "num_pages": ecfg.num_pages, "seed": args.seed, "calls": args.calls,
    }
    print(json.dumps(head), flush=True)
    out_dir = os.path.join(ROOT, "chiprun_out", "attn_crossover")
    os.makedirs(out_dir, exist_ok=True)
    # A rehearsal off the chip never lands on the chip's record.
    suffix = "" if dev.platform == "tpu" else ".cpu-rehearsal"
    with open(os.path.join(out_dir, f"{name}{suffix}.jsonl"), "w") as f:
        f.write(json.dumps(head) + "\n")
        for kind in kinds:
            for nb in (int(r) for r in args.rows.split(",")):
                rng = np.random.default_rng([args.seed, nb])
                room = ecfg.max_model_len - ecfg.decode_window - 1  # a tiny rehearsal model's
                contexts = [min(c, room) for c in draw_contexts(kind, nb, rng)]
                line = {"contexts": kind, "nb": nb, "context_mean": statistics.mean(contexts),
                        "context_max": max(contexts)}
                for form, key in (
                    ("kernel", (nb, None, True, False, False, False)),
                    ("gather", (nb, pages, True, False, False, False)),
                ):
                    call_args, used = live_window_args(engine, key, contexts, np.random.default_rng([args.seed, nb, 1]))
                    times = time_program(engine, key, call_args, args.calls)
                    line[f"{form}_step_ms"] = 1e3 * statistics.median(times) / ecfg.decode_window
                    line[f"{form}_step_ms_min"] = 1e3 * min(times) / ecfg.decode_window
                    line[f"{form}_step_ms_max"] = 1e3 * max(times) / ecfg.decode_window
                line["pages_used"] = used
                line["gather_over_kernel"] = line["gather_step_ms"] / line["kernel_step_ms"]
                print(json.dumps(line), flush=True)
                f.write(json.dumps(line) + "\n")
        if args.one_query_chunks and not mcfg.is_latent:
            for shape in one_query_forms(mcfg, ecfg):
                for line in one_query_lines(args, shape, ecfg.page_size, dev):
                    print(json.dumps(line), flush=True)
                    f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
