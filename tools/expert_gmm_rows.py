#!/usr/bin/env python3
"""The grouped expert matmul at many rows over many groups: the kernel's
row tile against ``ragged_dot``.

    chiprun -- python3 tools/expert_gmm_rows.py [--rows 2048] [--experts 128]

At SDAR-30B-A3B's expert shapes (128 experts of [2048, 768] and
[768, 2048], bfloat16, a stack of ``--layers`` layers) and ``--rows``
sorted rows spread over the experts as a softmax router over seeded
scores spreads them (top-8 of 128: every expert gets some), this times
one matrix product on an idle chip: ``--reps`` products chained inside one
program (each takes the one before it as a zero addend, so none is
hoisted), the program's time over ``--reps``, the median of
``--iterations`` such programs, so that a call's host overhead (about a
millisecond here) is not in the number:

- ``gmm_tm<N>``: ``ops/expert_gmm.py`` reading layer 3 of the stack in
  place with a row tile of N (``ROWS_OVER_MEAN_GROUP`` set to N over the
  mean group's 16 rows); ``chosen`` marks the one ``tiles`` gives;
- ``ragged_dot_slice``: ``jax.lax.ragged_dot`` on layer 3 taken out of
  the stack inside the program (the copy the mixed program's prefill
  makes: ``ops/moe.py::IN_PLACE_MAX_ROWS``);
- ``ragged_dot_layer``: the same on a layer that was never in a stack (no
  copy: what ``ragged_dot`` alone costs).

Beside each, the least the shapes allow (``hbm_s``: the experts' bytes
once over the chip's HBM rate; ``mxu_s``: the operations over its bf16
rate). A micro-benchmark, not code a cell runs (PERF.md section 6, PR 37).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rows", type=int, default=2048)
    ap.add_argument("--experts", type=int, default=128)
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
    E, M, L = args.experts, args.rows, args.layers
    rng = np.random.default_rng(0)
    scores = rng.standard_normal((M // 8, E))
    ids = np.argsort(-scores, axis=1)[:, :8].reshape(-1)
    sizes = jnp.asarray(np.bincount(ids, minlength=E), jnp.int32)
    out = {"rows": M, "experts": E, "largest_group": int(sizes.max()), "results": []}
    for K, N in ((2048, 768), (768, 2048)):
        key = jax.random.PRNGKey(K)
        lhs = jax.random.normal(key, (M, K), jnp.bfloat16)
        stack = jax.random.normal(key, (L, E, K, N), jnp.bfloat16) * 0.02
        layer = jnp.array(stack[3])
        hbm_s = (E * K * N * 2 + M * K * 2 + M * N * 2) / (peak["hbm_gbps"] * 1e9)
        mxu_s = 2 * M * K * N / (peak["bf16_tflops"] * 1e12)

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

        row = {"k": K, "n": N, "hbm_s": hbm_s, "mxu_s": mxu_s}
        as_served = expert_gmm.ROWS_OVER_MEAN_GROUP
        chosen = expert_gmm.tiles(M, K, N, 2, E)[0]
        mean = max(M // E, 1)
        for tm in (32, 64, 128, 256):
            expert_gmm.ROWS_OVER_MEAN_GROUP = max(tm // mean, 1)
            expert_gmm.expert_gmm.clear_cache()
            assert expert_gmm.tiles(M, K, N, 2, E)[0] == tm, (tm, expert_gmm.tiles(M, K, N, 2, E))
            row[f"gmm_tm{tm}" + ("_chosen" if tm == chosen else "")] = timed(
                lambda x, w, s: expert_gmm.expert_gmm(x, w, s, 3), lhs, stack, sizes
            )
        expert_gmm.ROWS_OVER_MEAN_GROUP = as_served
        expert_gmm.expert_gmm.clear_cache()
        row["ragged_dot_slice"] = timed(
            lambda x, w, s: jax.lax.ragged_dot(x, w[3], s), lhs, stack, sizes)
        row["ragged_dot_layer"] = timed(jax.lax.ragged_dot, lhs, layer, sizes)
        out["results"].append(row)
        print(json.dumps(row), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out", "expert_gmm_rows"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "expert_gmm_rows", f"rows{M}.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
