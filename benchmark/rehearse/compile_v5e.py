#!/usr/bin/env python3
"""Compile a configuration's serving programs for a described ``v5e:2x2``
topology, in the sandbox, with no chip (on-chip-measurement guide §2.3).

    JAX_PLATFORMS=cpu python3 benchmark/rehearse/compile_v5e.py \\
        --config mixtral-8x7b-l16 [--variants w64,m64,m4096]

For each program it prints per-device ``memory_analysis()`` (arguments,
outputs, temporaries) and, from ``as_text()``, the collectives XLA put in
and whether an all-gather touches an expert-weight-sized operand: GSPMD
gathering expert weights for ``ragged_dot`` would turn a 25 ms step into
hundreds. Nothing runs; this says nothing about results or times, and a
pass is never reported as a chip run.

The engine builds its programs as methods that read a handful of
attributes (``cfg``, ``mesh``, the resolved attention implementation), so
a stand-in object with those attributes and ``ShapeDtypeStruct``
parameters carrying the engine's own shardings is enough: no array is
ever placed. Only one process at a time can load libtpu here.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys
import time
import types

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.dirname(BENCH))


def parse_variant(tag: str) -> tuple[bool, bool, int]:
    """``w64`` = decode window at 64 rows through the kernel; ``x64`` = the
    same over short contexts (the XLA gather the engine takes up to 1k
    tokens); ``m4096`` = mixed dispatch of 4096 tokens."""
    return tag[0] in "wx", tag[0] == "x", int(tag[1:])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", required=True)
    ap.add_argument("--variants", default="w64,m64,m1024,m8192")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="try another KV pool size than the configuration's")
    args = ap.parse_args()

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    import run_cell
    from dynamo_exp_tpu import run as server
    from dynamo_exp_tpu.aot.lattice import resolve_ragged_key
    from dynamo_exp_tpu.aot.warmup import variant_call_args
    from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
    from dynamo_exp_tpu.models import ModelConfig
    from dynamo_exp_tpu.models.llama import (
        init_params,
        kv_cache_shardings,
        param_shardings,
    )

    config = run_cell.load_json(BENCH, "configs", f"{args.config}.json")
    mcfg = ModelConfig.from_hf_config(run_cell.hf_config(config))
    opts = server.parse_args(["in=http", "out=tpu", *config["serve_args"]])
    ecfg = EngineConfig(
        model=mcfg, max_decode_slots=opts.max_decode_slots, page_size=opts.page_size,
        num_pages=args.num_pages or opts.num_pages, max_model_len=min(opts.max_model_len, mcfg.max_position_embeddings),
        tp=opts.tp, kv_dtype=opts.kv_dtype,
    )
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[: ecfg.tp]).reshape(1, 1, 1, ecfg.tp), ("dp", "sp", "ep", "tp"))

    def sds(shape_dtype, spec):
        return jax.ShapeDtypeStruct(
            shape_dtype.shape, shape_dtype.dtype, sharding=NamedSharding(mesh, spec)
        )

    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), mcfg))
    params = jax.tree.map(
        sds, shapes, param_shardings(mcfg), is_leaf=lambda x: isinstance(x, P)
    )
    pool = jax.ShapeDtypeStruct(
        (mcfg.num_layers, ecfg.num_pages, ecfg.page_size, mcfg.num_kv_heads * mcfg.head_dim_),
        ecfg.kv_dtype_jnp,
    )
    k_spec, v_spec = kv_cache_shardings()
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    print(json.dumps({
        "config": args.config, "tp": ecfg.tp, "parameters": n_params,
        "weights_gb_per_device": n_params * 2 / ecfg.tp / 1e9,
        "kv_pool_gb_per_device": 2 * int(np.prod(pool.shape)) * 2 / ecfg.tp / 1e9,
    }))
    stand_in = types.SimpleNamespace(
        cfg=ecfg, mesh=mesh, params=params, k_cache=sds(pool, k_spec),
        v_cache=sds(pool, v_spec), _attn_impl="pallas", _attn_interpret=False,
        _ragged_fns={},
    )
    for name in ("_ragged_fn_from_key", "_windowed_program", "_mixed_program", "_ragged_align"):
        setattr(stand_in, name, types.MethodType(getattr(TPUEngine, name), stand_in))

    expert_bytes = (
        mcfg.num_experts * mcfg.hidden_size * mcfg.expert_intermediate_size * 2
        if mcfg.is_moe else 0
    )
    for tag in args.variants.split(","):
        windowed, short, nb = parse_variant(tag)
        pages = ecfg.ragged_page_bucket_for(1) if short else ecfg.max_pages_per_seq
        key = resolve_ragged_key(ecfg, "pallas", nb, pages, windowed, False, False, False)
        fn = stand_in._ragged_fn_from_key(key)
        t0 = time.monotonic()
        compiled = fn.lower(*variant_call_args(stand_in, key)).compile()
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        collectives = collections.Counter(
            m.group(1) for m in re.finditer(
                r"= \S+ (all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute)(?:-start)?\(", text
            )
        )
        # An all-gather whose result is as large as one layer's experts.
        big_gathers = []
        for m in re.finditer(r"= (\w+)\[([\d,]*)\][^\n]* all-gather(?:-start)?\(", text):
            dims = [int(d) for d in m.group(2).split(",") if d]
            size = int(np.prod(dims)) * (2 if m.group(1) == "bf16" else 4)
            if expert_bytes and size >= expert_bytes // ecfg.tp:
                big_gathers.append({"shape": dims, "bytes": size})
        print(json.dumps({
            "variant": tag, "key": [str(k) for k in key],
            "compile_s": round(time.monotonic() - t0, 1),
            "argument_gb": mem.argument_size_in_bytes / 1e9,
            "output_gb": mem.output_size_in_bytes / 1e9,
            "alias_gb": mem.alias_size_in_bytes / 1e9,
            "temp_gb": mem.temp_size_in_bytes / 1e9,
            "collectives": dict(collectives),
            "tpu_custom_calls": text.count("tpu_custom_call"),
            "expert_weight_sized_all_gathers": big_gathers,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
