#!/usr/bin/env python3
"""``compile_v5e_latent.py`` for a configuration whose K pool's row holds
more than the heads' keys (a grouped-query model with an indexer, its index
key behind them: ``ModelConfig.kv_row_parts``), whose row widths
``compile_v5e.py`` cannot describe (it sizes both pools ``Hkv x D``) and
which ``compile_v5e_latent.py`` refuses (no latent cache). Compile-only for a
described ``v5e:2x2`` chip, in the sandbox, with no chip:

    JAX_PLATFORMS=cpu python3 benchmark/rehearse/compile_v5e_indexed.py \\
        --config keye-vl-2.0-30b-a3b-l5 [--variants w32,m4096,m8192]

Prints each program's per-device ``memory_analysis()`` (arguments, outputs,
temporaries): what the largest mixed dispatch needs beside the weights is
what bounds ``--num-pages``. That the attention kernel's copies take the
keys' lanes of a wider row and the scores kernel the index key's is shown by
the compile succeeding. Nothing runs; a pass is never reported as a chip run.
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
    ap.add_argument("--num-pages", type=int, default=0,
                    help="try another pool size than the configuration's")
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
    from dynamo_exp_tpu.models.llama import init_kv_cache, init_params

    config = run_cell.load_json(BENCH, "configs", f"{args.config}.json")
    mcfg = ModelConfig.from_hf_config(run_cell.hf_config(config))
    if mcfg.is_latent or not mcfg.has_indexer or mcfg.kv_by_kind:
        raise SystemExit(f"{args.config} has no index key in its K row: compile_v5e*.py")
    opts = server.parse_args(["in=http", "out=tpu", *config["serve_args"]])
    ecfg = EngineConfig(
        model=mcfg, max_decode_slots=opts.max_decode_slots, page_size=opts.page_size,
        num_pages=args.num_pages or opts.num_pages,
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
    k_pool, v_pool = described(jax.eval_shape(
        lambda: init_kv_cache(mcfg, ecfg.num_pages, ecfg.page_size, dtype=ecfg.kv_dtype_jnp)
    ))
    size = lambda tree: sum(  # noqa: E731
        int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(tree)
    )
    print(json.dumps({
        "config": args.config,
        "parameters": sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params)),
        "weights_gb": size(params) / 1e9, "k_pool_shape": list(k_pool.shape),
        "v_pool_shape": list(v_pool.shape), "row_parts": mcfg.kv_row_parts(),
        "pools_gb": size((k_pool, v_pool)) / 1e9,
        "bytes_per_token": size((k_pool, v_pool)) // (ecfg.num_pages * ecfg.page_size),
    }))
    stand_in = types.SimpleNamespace(
        cfg=ecfg, mesh=None, params=params, k_cache=k_pool, v_cache=v_pool,
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
