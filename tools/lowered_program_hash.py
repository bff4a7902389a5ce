#!/usr/bin/env python3
"""What a configuration's mixed forward hands the compiler, as hashes, so
that two trees can be compared without a chip: the lowered program
(StableHLO of ``models/llama.py::forward_ragged`` at 1,024 slots with a
packed tail of 64, lowered for a described ``v5e:2x2`` chip) with its
kernels' payloads taken out, and each Pallas kernel's own module printed
WITHOUT source locations (a kernel's payload embeds the file lines of its
call sites, which move with any edit above them). A second line,
``whole_spans``: the same stream with NO packed tail (``packed_from=None``),
the forward the comparisons under ``benchmark/comparisons/`` make of whole
prompts a row, whose tiled call is the only one and names a tile a cell. A
block model (``block_length``) gets a third: a pass of its block program
(``engine/block.py::_block_program``'s forward: 64 rows, a tile of
``block_length`` slots a row, no block named, the experts read in place),
whose attention calls no mixed forward makes.

    JAX_PLATFORMS=cpu python3 tools/lowered_program_hash.py <tree> qwen3-4b olmo-hybrid-7b-l8 ...

Run it on two trees copied in turn to the SAME path (a path's length is in
the text) and ``diff`` the lines: equal lines are the same programs and the
same kernels. Nothing is compiled and nothing runs; it says nothing about
time. (PR 45 used it to show that the expanded form of latent attention
left every other configuration's attention calls as they were: PERF.md §6.)
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

SLOTS, ROWS, PAGES_A_ROW, PAGE_SIZE, PAGES, TAIL = 1024, 33, 64, 16, 512, 64


def main() -> int:
    tree = os.path.abspath(sys.argv[1])
    sys.path.insert(0, os.path.join(tree, "benchmark"))
    sys.path.insert(1, tree)

    import jax
    import jax.numpy as jnp
    from jax._src import tpu_custom_call
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import run_cell
    from dynamo_exp_tpu.models import ModelConfig, llama

    # No chip is attached: the one rule that asks where a program runs
    # (``ops/moe.py::experts_in_place``) gets the described chip's answer, so
    # that a routed model's experts lower as they do there (the kernel on the
    # stacks) and not as the CPU's ``ragged_dot`` on a slice.
    jax.default_backend = lambda: "tpu"

    kernels: list[str] = []
    plain = tpu_custom_call._lower_mosaic_module_to_asm

    def spy(module, **kw):
        asm = module.operation.get_asm(enable_debug_info=False)
        kernels.append(hashlib.sha256(asm.encode()).hexdigest()[:16])
        return plain(module, **kw)

    tpu_custom_call._lower_mosaic_module_to_asm = spy
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def described(tree_):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree_
        )

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    for name in sys.argv[2:]:
        config = run_cell.load_json(os.path.join(tree, "benchmark", "configs", f"{name}.json"))
        mcfg = ModelConfig.from_hf_config(run_cell.hf_config(config))
        params = described(jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0), mcfg)))
        k, v = described(jax.eval_shape(
            lambda: llama.init_kv_cache(mcfg, PAGES, PAGE_SIZE, dtype=jnp.bfloat16)
        ))
        state = jax.eval_shape(lambda: llama.init_seq_state(mcfg, ROWS))
        state = described(state) if state is not None else None

        def lowered(packed_from):
            def forward(params, tokens, positions, row_of, table, k, v, out_idx, state, rows):
                return llama.forward_ragged(
                    params, mcfg, tokens, positions, row_of, table, k, v, out_idx,
                    attn_impl="pallas", q_tile=8, packed_from=packed_from, seq_state=state,
                    state_rows=rows if state is not None else None,
                )

            table = i32(ROWS, PAGES_A_ROW)
            if mcfg.kv_by_kind:  # a table a pool: (full, sliding)
                table = (table, table)
            return jax.jit(forward).lower(
                params, i32(SLOTS), i32(SLOTS), i32(SLOTS), table, k, v,
                i32(ROWS), state, (i32(ROWS), i32(2 * ROWS), i32(2 * ROWS)),
            ).as_text()

        def report(program: str, text: str) -> None:
            text = "\n".join(
                re.sub(r'backend_config = "[^"]*"', 'backend_config = "<kernel>"', line)
                if "tpu_custom_call" in line else line
                for line in text.splitlines()
            )
            print(json.dumps({
                "config": name, "program": program,
                "program_without_kernel_payloads": hashlib.sha256(text.encode()).hexdigest()[:20],
                "kernels_without_locations": list(kernels), "chars": len(text),
            }), flush=True)
            kernels.clear()

        report("mixed", lowered(SLOTS - TAIL))
        report("whole_spans", lowered(None))
        if mcfg.block_length:
            B = mcfg.block_length

            def block_pass(params, tokens, positions, row_of, table, k, v, out_idx):
                return llama.forward_ragged(
                    params, mcfg, tokens, positions, row_of, table, k, v, out_idx,
                    attn_impl="pallas", q_tile=B, project=False,
                )

            n = 64 * B
            report("block_pass", jax.jit(block_pass).lower(
                params, i32(n), i32(n), i32(n), i32(64, PAGES_A_ROW), k, v, i32(n),
            ).as_text())
    return 0


if __name__ == "__main__":
    sys.exit(main())
