#!/usr/bin/env python3
"""``compile_v5e.py`` for a configuration whose sequences carry state
beside their pages (``ModelConfig.has_seq_state``): that file's stand-in
engine has no ``seq_state`` to hand the programs, so a model of
convolution, selective-scan or linear-attention layers cannot be bound
there. One chip (such a model refuses ``tp > 1``), no collectives to count.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse/compile_v5e_state.py \\
        --config olmo-hybrid-7b-l8 [--variants w32,m64,m4096,m8192]

For each program: the per-device ``memory_analysis()`` (arguments,
outputs, aliased, temporaries) and how many kernels the compiler kept
(``tpu_custom_call``). Nothing runs; a pass is never reported as a chip
run. Only one process at a time can load libtpu here.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, BENCH)
sys.path.insert(2, os.path.dirname(BENCH))

from compile_v5e import parse_variant  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", required=True)
    ap.add_argument("--variants", default="w32,m64,m4096,m8192")
    args = ap.parse_args()

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import run_cell
    from dynamo_exp_tpu import run as server
    from dynamo_exp_tpu.aot.lattice import resolve_ragged_key
    from dynamo_exp_tpu.aot.warmup import variant_call_args
    from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
    from dynamo_exp_tpu.models import ModelConfig
    from dynamo_exp_tpu.models.llama import init_params, init_seq_state

    config = run_cell.load_json(BENCH, "configs", f"{args.config}.json")
    mcfg = ModelConfig.from_hf_config(run_cell.hf_config(config))
    if not mcfg.has_seq_state:
        raise SystemExit(f"{args.config} carries no state beside its pages: compile_v5e.py")
    opts = server.parse_args(["in=http", "out=tpu", *config["serve_args"]])
    ecfg = EngineConfig(
        model=mcfg, max_decode_slots=opts.max_decode_slots, page_size=opts.page_size,
        num_pages=opts.num_pages,
        max_model_len=min(opts.max_model_len, mcfg.max_position_embeddings),
        tp=opts.tp, kv_dtype=opts.kv_dtype,
    )
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def described(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree
        )

    params = described(jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), mcfg)))
    pool = jax.ShapeDtypeStruct(
        (mcfg.num_attn_layers, ecfg.num_pages, ecfg.page_size,
         mcfg.num_kv_heads * mcfg.head_dim_),
        ecfg.kv_dtype_jnp, sharding=chip,
    )
    entries = 5 * ecfg.max_decode_slots  # a live entry a slot, four snapshots (engine.py)
    state = described(jax.eval_shape(lambda: init_seq_state(mcfg, entries)))
    size = lambda tree: sum(  # noqa: E731
        int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(tree)
    )
    print(json.dumps({
        "config": args.config,
        "parameters": sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params)),
        "weights_gb": size(params) / 1e9, "kv_pool_gb": 2 * size(pool) / 1e9,
        "state_pool_gb": size(state) / 1e9, "state_entries": entries,
    }))
    stand_in = types.SimpleNamespace(
        cfg=ecfg, mesh=None, params=params, k_cache=pool, v_cache=pool, seq_state=state,
        _attn_impl="pallas", _attn_interpret=False, _ragged_fns={},
    )
    for name in ("_ragged_fn_from_key", "_windowed_program", "_mixed_program", "_ragged_align"):
        setattr(stand_in, name, types.MethodType(getattr(TPUEngine, name), stand_in))
    for tag in args.variants.split(","):
        windowed, short, nb = parse_variant(tag)
        pages = ecfg.ragged_page_bucket_for(1) if short else ecfg.max_pages_per_seq
        key = resolve_ragged_key(ecfg, "pallas", nb, pages, windowed, False, False, False)
        fn = stand_in._ragged_fn_from_key(key)
        t0 = time.monotonic()
        compiled = fn.lower(*variant_call_args(stand_in, key)).compile()
        mem = compiled.memory_analysis()
        print(json.dumps({
            "variant": tag, "key": [str(k) for k in key],
            "compile_s": round(time.monotonic() - t0, 1),
            "argument_gb": mem.argument_size_in_bytes / 1e9,
            "output_gb": mem.output_size_in_bytes / 1e9,
            "alias_gb": mem.alias_size_in_bytes / 1e9,
            "temp_gb": mem.temp_size_in_bytes / 1e9,
            "tpu_custom_calls": compiled.as_text().count("tpu_custom_call"),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
