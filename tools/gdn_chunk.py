#!/usr/bin/env python3
"""Time the gated delta rule's chunk kernel alone on the chip, whole and
with parts of it taken out: the table behind PERF.md §6, PR 59.

    chiprun -- python3 tools/gdn_chunk.py --config olmo-hybrid-7b-l8

No engine and no weights: the call ``models/llama.py::_gated_delta`` makes
for the tiled part of a mixed dispatch, ``gated_delta_kernel`` over a
stream of q | k | v as the mixer's convolution leaves it, at the
configuration's linear-attention widths (``benchmark/configs/<name>.json``)
over a state pool of ``--entries`` entries a layer. A case ``slots:spans``
(``--cases``, default the cell's: 2,048 and 4,096 slots, each as one span
and as eight) lays ``spans`` prompts of equal length over the stream's
tiled part, resumed from their entries, each with the write of its own
entry after its last token and a snapshot at a block boundary 16 tokens
into a chunk; the last 32 slots are the packed tail, whose decode rows the
kernel is handed as padding. One timed call is a jitted loop of ``--steps``
passes over every linear layer in turn with the pool donated, as a mixed
program runs them; the time a call is the median of ``--calls`` such loops
on the host's clock around ``block_until_ready`` over that count, the time
a chunk that over the chunks that hold a token.

Beside the kernel as it stands (``whole``), the same call with:

- ``no_products``: every product's result replaced by the operand of its
  shape, so the copies and the vector unit's work alone;
- ``no_inverse``: the triangular inverse replaced by ``I - A``, so what the
  triangular inverse costs is ``whole`` less this;
- ``no_copies``: every DMA of the kernel left out, so what the kernel
  waits for them is ``whole`` less this.

Only ``whole`` is compared with ``gated_delta_ref`` (outputs of the live
slots and every entry of the layer's pool; streams of up to 2,048 slots:
the oracle stacks a state a token, 9 GB at 4,096), and the tool FAILS
where either leaves its tolerance: ``tests/test_gated_delta.py``'s 2e-5
for the interpreted kernel, whose products are float32's; on the chip 1e-4
for the states (six bfloat16 passes are not quite float32: 2e-5 to 4e-5
read at these sizes, before PR 59's kernel and after) and 1e-2 for the
outputs, whose products are one bfloat16 pass by design (1.5e-3 read). A
fault (a wrong entry, a slot of another row, a write one slot off) is 1e-2
and more in the states. One JSON line a
case on stdout and in ``chiprun_out/gdn_chunk/<config>.jsonl``. It fails
off the chip: a CPU time is not a device time (``--allow-cpu`` rehearses
the control flow with the kernel interpreted, at ``--cases 256:1,256:2``
say, and writes ``<config>.cpu-rehearsal.jsonl``, never the chip's file).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)
sys.path.insert(1, ROOT)

KERNEL_TOL = 2e-5  # tests/test_gated_delta.py: the interpreted kernel, outputs and states
CHIP_STATE_TOL, CHIP_OUTPUT_TOL = 1e-4, 1e-2  # module docstring
ORACLE_SLOTS = 2048  # the longest stream gated_delta_ref is asked for
TAIL = 32  # the cell's packed tail: ragged_packed_slots at 32 decode slots
BLOCK = 16  # the cell's page: a snapshot falls on a multiple of it


def stream(slots: int, spans: int, tile: int, chunk: int):
    """(row_of, positions, the rows' entries, src, dst, a span's tokens) of
    ``spans`` equal prompts over ``slots - TAIL`` slots, each resumed at
    position 1,024."""
    import numpy as np

    length = (slots - TAIL) // spans // tile * tile
    row_of = np.full(slots, spans, np.int32)
    positions = np.full(slots, -1, np.int32)
    src, dst = [], []
    for r in range(spans):
        at = r * length
        row_of[at:at + length] = r
        positions[at:at + length] = 1024 + np.arange(length)
        src += [at + length - 1, at + length // 2 // chunk * chunk + BLOCK - 1]
        dst += [r, spans + r]
    return row_of, positions, np.arange(spans + 1, dtype=np.int32), src, dst, length


@contextlib.contextmanager
def variant(gd, name: str):
    """``gd``'s kernel with a part taken out (module docstring)."""
    import jax.numpy as jnp

    class NoCopy:
        def start(self):
            pass

        wait = start

    class NoCopies:
        def __getattr__(self, attr):
            return getattr(pltpu, attr)

        @staticmethod
        def make_async_copy(*_a, **_k):
            return NoCopy()

    def operand(a, b, contract, _precision):
        shape = (a.shape[1 - contract[0]], b.shape[1 - contract[1]])
        return next(x for x in (b, a, jnp.zeros(shape, a.dtype)) if x.shape == shape)

    def no_inverse(a, rows, cols, *_a, **_k):
        return (rows == cols) - a
        yield

    pltpu = gd.pltpu
    patch = {
        "whole": {}, "no_products": {"_dot": operand},
        "no_inverse": {"inverse_steps": no_inverse}, "no_copies": {"pltpu": NoCopies()},
    }[name]
    kept = {k: getattr(gd, k) for k in patch}
    for k, v in patch.items():
        setattr(gd, k, v)
    try:
        yield
    finally:
        for k, v in kept.items():
            setattr(gd, k, v)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", required=True)
    ap.add_argument("--cases", default="2048:1,2048:8,4096:1,4096:8", help="slots:spans, ...")
    ap.add_argument("--variants", default="whole,no_products,no_inverse,no_copies")
    ap.add_argument("--entries", type=int, default=160)
    ap.add_argument("--steps", type=int, default=2, help="passes a timed loop, each over every layer")
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--seed", type=int, default=59)
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

    kernel = gd.gated_delta_kernel.__wrapped__

    def timed_loop():  # a function of its own a variant: traced anew under its patch
        def loop(qkv, g, beta, pool, *io):
            def body(i, carry):
                pool, acc = carry
                o, pool = kernel(qkv, g, beta, pool, i % layers, *io, interpret=not on_chip)
                return pool, acc + o[:8]
            return jax.lax.fori_loop(
                0, args.steps * layers, body, (pool, jnp.zeros((8, h * dv), jnp.float32))
            )
        return jax.jit(loop, donate_argnums=(3,))

    out_dir = os.path.join(ROOT, "chiprun_out", "gdn_chunk")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"{name}{'' if on_chip else '.cpu-rehearsal'}.jsonl")
    rng = np.random.default_rng(args.seed)
    failed = False
    with open(out_path, "w") as out:
        for case in args.cases.split(","):
            slots, spans = (int(x) for x in case.split(":"))
            if 2 * spans > entries:
                raise SystemExit(f"{case}: an entry and a snapshot a span, of {entries} entries")
            row_of, positions, rows, src, dst, length = stream(slots, spans, gd.TILE, gd.CHUNK)
            qk = rng.standard_normal((slots, 2 * h, dk))
            qk *= np.exp(rng.uniform(np.log(0.2), np.log(5.0), (slots, 2 * h, 1)))
            ins = [jnp.asarray(x, jnp.float32) for x in (
                np.concatenate([qk.reshape(slots, -1), rng.standard_normal((slots, h * dv))], 1),
                -np.exp(rng.uniform(np.log(1e-3), np.log(3.0), (slots, h))),
                2.0 * rng.uniform(0.0, 1.0, (slots, h)) ** 0.5,
            )]
            io = [jnp.asarray(x, jnp.int32) for x in (rows, row_of, positions, src, dst)]
            key = jax.random.PRNGKey(args.seed)
            fresh = jax.jit(lambda: jax.random.normal(key, (layers, entries, dk, h * dv), jnp.float32))
            chunks = spans * -(-length // gd.CHUNK)
            line = {
                "config": name, "slots": slots, "spans": spans, "span_tokens": length,
                "chunk": gd.CHUNK, "live_chunks": chunks, "heads": h, "dk": dk, "dv": dv,
                "layers": layers, "entries": entries, "steps": args.steps, "calls": args.calls,
                "platform": dev.platform, "device_kind": dev.device_kind,
            }
            ok = True
            if slots <= ORACLE_SLOTS:
                o, pool = jax.jit(lambda *a: kernel(*a, interpret=not on_chip))(*ins, fresh(), 1, *io)
                o_ref, pool_ref = jax.jit(gd.gated_delta_ref)(
                    *gd.unit_heads(ins[0], h, dk), *ins[1:], fresh(), 1, *io
                )
                live = positions >= 0
                line["o_max_diff"] = float(np.abs(np.asarray(o) - np.asarray(o_ref))[live].max())
                line["state_max_diff"] = float(np.abs(np.asarray(pool[1]) - np.asarray(pool_ref[1])).max())
                line["state_max"] = float(np.abs(np.asarray(pool_ref[1, : 2 * spans])).max())
                del o, pool, o_ref, pool_ref
                ok = (
                    line["o_max_diff"] < (CHIP_OUTPUT_TOL if on_chip else KERNEL_TOL)
                    and line["state_max_diff"] < (CHIP_STATE_TOL if on_chip else KERNEL_TOL)
                )
                line["within_tolerance"] = ok
            for v in args.variants.split(","):
                with variant(gd, v):
                    fn, pool, times = timed_loop(), fresh(), []
                    for i in range(args.calls + 2):  # the first call compiles, the second loads
                        t0 = time.perf_counter()
                        pool, acc = fn(*ins, pool, *io)
                        jax.block_until_ready((pool, acc))
                        if i == 0:
                            compile_s = time.perf_counter() - t0
                        if i >= 2:
                            times.append(time.perf_counter() - t0)
                del pool, acc
                line[f"{v}_ms"] = statistics.median(times) / (args.steps * layers) * 1e3
                line[f"{v}_compile_s"] = compile_s
                line[f"{v}_us_a_chunk"] = line[f"{v}_ms"] / chunks * 1e3
            failed |= not ok
            text = json.dumps(line)
            print(text, flush=True)
            out.write(text + "\n")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
