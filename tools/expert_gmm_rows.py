#!/usr/bin/env python3
"""The grouped expert matmul by row count: the kernel reading a layer of the
stack in place against ``ragged_dot`` on the layer's copy.

    chiprun -- python3 tools/expert_gmm_rows.py [--config sdar,lfm2,mixtral-shard]
        [--rows 256,2048,4096] [--live-share 0.5] [--tm-sweep] [--gmm-only]

At a configuration's expert shapes (``--config``: SDAR-30B-A3B's 128
experts of [2048, 768], LFM2-8B-A1B's 32 of [2048, 1792], one chip's shard
of Mixtral-8x7B's 8 of [4096, 3584]; or ``--experts --hidden --width
--top-k`` for any other), bfloat16, a stack of ``--layers`` layers, and
``--rows`` sorted rows (256: a decode window's; thousands: a prompt
chunk's) of which the first ``--live-share`` are spread over the experts as
a router over seeded scores spreads them (top-k of E) and the rest lie past
the last group (a held call's block, half of whose rows are live:
``--experts 16 --hidden 7680 --width 2048 --top-k 8 --live-share 0.5`` is
openPangu's), this times one matrix product on an idle chip, the gate/up
orientation [hidden, width] and the down one: ``--reps`` products chained
inside one program (each takes the one before it as a zero addend, so none
is hoisted), the program's time over ``--reps``, the median of
``--iterations`` such programs, so that a call's host overhead (about a
millisecond here) is not in the number:

- ``gmm``: ``ops/expert_gmm.py`` reading layer 3 of the stack in place
  with the row block ``tiles`` gives (``tm``), which is what every routed
  forward on a TPU runs (``ops/moe.py::experts_in_place``);
- ``gmm_tm<N>`` (``--tm-sweep``): the same with a row block of N
  (``expert_gmm``'s ``tm``), where VMEM holds it;
- ``ragged_dot_slice``: ``jax.lax.ragged_dot`` on layer 3 taken out of
  the stack inside the program (the copy a forward makes off the TPU's
  kernel, and the mixed program's prefill made until PR 60);
- ``ragged_dot_layer``: the same on a layer that was never in a stack (no
  copy: what ``ragged_dot`` alone costs).

Beside each, the least the shapes allow (``hbm_s``: the bytes of the
experts that have a row, once, over the chip's HBM rate; ``mxu_s``: the
operations over its bf16 rate) and what the kernel's tiling makes of it
(``expert_gmm.fetches_and_product_rows``: ``matrix_fetches``, a matrix for
every (group, row block) pair that holds a row, and ``product_rows``, the
rows its tiles push through the MXU). A micro-benchmark, not code a cell
runs (PERF.md section 6, PR 37, PR 60 and PR 64).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


# (experts, hidden, width a chip, experts a token)
CONFIGS = {
    "sdar": (128, 2048, 768, 8),
    "lfm2": (32, 2048, 1792, 4),
    "mixtral-shard": (8, 4096, 3584, 2),  # 14336 over tp=4
    # What a chip HOLDS of an expert-parallel layer (``--live-share 0.5``:
    # ``ops/moe.py::held_block_rows`` gathers twice an even router's share).
    "pangu-held": (16, 7680, 2048, 8),
    "exaone-held": (8, 6144, 2048, 8),
    "nemotron-held": (64, 2688, 1920, 6),
}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", default="sdar", help="names of CONFIGS, comma-separated")
    ap.add_argument("--experts", type=int)
    ap.add_argument("--hidden", type=int)
    ap.add_argument("--width", type=int)
    ap.add_argument("--top-k", type=int)
    ap.add_argument("--rows", default="256,2048", help="sorted rows a call, comma-separated")
    ap.add_argument("--live-share", type=float, default=1.0,
                    help="the share of a call's rows that lie in a group")
    ap.add_argument("--tm-sweep", action="store_true")
    ap.add_argument("--gmm-only", action="store_true", help="no ragged_dot beside it")
    ap.add_argument("--layers", type=int, default=6)
    ap.add_argument("--iterations", type=int, default=10)
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_exp_tpu.ops import expert_gmm

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU: these are device times")
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peak = json.load(f)["devices"][jax.devices()[0].device_kind]
    L = args.layers

    def timed(product, x, w, s):
        @jax.jit
        def chained(x, w, s):
            def body(_, x):
                return x + (product(x, w, s)[:, :1] * 0).astype(x.dtype)

            return jax.lax.fori_loop(0, args.reps, body, x)

        jax.block_until_ready(chained(x, w, s))
        took = []
        for _ in range(args.iterations):
            t = time.perf_counter()
            jax.block_until_ready(chained(x, w, s))
            took.append((time.perf_counter() - t) / args.reps)
        return statistics.median(took)

    def in_place(x, w, s, **kw):
        return expert_gmm.expert_gmm(x, w, s, 3, **kw)

    explicit = (args.experts, args.hidden, args.width, args.top_k)
    shapes = (
        {"e{}h{}w{}k{}".format(*explicit): explicit} if all(explicit)
        else {name: CONFIGS[name] for name in args.config.split(",")}
    )
    out = []
    for name, (E, hidden, width, top_k) in shapes.items():
        for K, N in ((hidden, width), (width, hidden)):
            key = jax.random.PRNGKey(K)
            stack = jax.random.normal(key, (L, E, K, N), jnp.bfloat16) * 0.02
            layer = jnp.array(stack[3])
            for M in (int(m) for m in args.rows.split(",")):
                rng = np.random.default_rng(0)
                scores = rng.standard_normal((int(M * args.live_share) // top_k, E))
                ids = np.argsort(-scores, axis=1)[:, :top_k].reshape(-1)
                sizes = jnp.asarray(np.bincount(ids, minlength=E), jnp.int32)
                lhs = jax.random.normal(key, (M, K), jnp.bfloat16)
                fetched = int((sizes > 0).sum())
                chosen = expert_gmm.tiles(M, K, N, 2, E)[0]
                fetches, product_rows = expert_gmm.fetches_and_product_rows(
                    np.asarray(sizes), chosen)
                row = {
                    "config": name, "experts": E, "k": K, "n": N, "rows": M,
                    "live_rows": int(sizes.sum()),
                    "largest_group": int(sizes.max()), "tm": chosen,
                    "matrix_fetches": fetches, "product_rows": product_rows,
                    "hbm_s": (fetched * K * N * 2 + M * K * 2 + M * N * 2)
                    / (peak["hbm_gbps"] * 1e9),
                    "mxu_s": 2 * M * K * N / (peak["bf16_tflops"] * 1e12),
                    "gmm": timed(in_place, lhs, stack, sizes),
                }
                for tm in (128, 256, 512, 1024, 2048, 4096) if args.tm_sweep else ():
                    try:
                        if tm == chosen or expert_gmm.tiles(M, K, N, 2, E, tm)[0] != tm:
                            continue  # the served block, or more rows than the call has
                    except ValueError:  # more than VMEM holds
                        continue
                    row[f"gmm_tm{tm}"] = timed(
                        functools.partial(in_place, tm=tm), lhs, stack, sizes)
                if not args.gmm_only:
                    row["ragged_dot_slice"] = timed(
                        lambda x, w, s: jax.lax.ragged_dot(x, w[3], s), lhs, stack, sizes)
                    row["ragged_dot_layer"] = timed(jax.lax.ragged_dot, lhs, layer, sizes)
                out.append(row)
                print(json.dumps(row), flush=True)
            del stack, layer
    os.makedirs(os.path.join(ROOT, "chiprun_out", "expert_gmm_rows"), exist_ok=True)
    name = "-".join(shapes) + "-rows" + args.rows.replace(",", "_")
    if args.live_share != 1.0:
        name += f"-live{args.live_share:g}"
    with open(os.path.join(ROOT, "chiprun_out", "expert_gmm_rows", f"{name}.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
