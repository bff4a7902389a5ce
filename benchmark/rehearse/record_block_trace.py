#!/usr/bin/env python3
"""Record the small trace of a block-diffusion model's engine that
``tests/test_sdar_readers.py`` reads the block program's metrics from: a
fraction of a second of a tiny ``sdar_moe`` (3 layers, hidden 128, 8
experts top-2, blocks of 4) on one chip — mixed dispatches (prompt chunks
beside single passes) and chained block dispatches — slimmed as
``record_engine_trace.py`` slims its own. Run on the chip:

    python3 benchmark/rehearse/record_block_trace.py chiprun_out/bench/block_trace

and copy ``block_trace.xplane.pb`` to ``benchmark/tests/data/``.
"""

import asyncio
import glob
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import record_engine_trace as base  # noqa: E402  (puts the repo and benchmark/ on the path)

HF = {
    "model_type": "sdar_moe", "hidden_act": "silu", "hidden_size": 128,
    "intermediate_size": 256, "moe_intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 64, "num_hidden_layers": 3, "num_experts": 8,
    "num_experts_per_tok": 2, "norm_topk_prob": True, "rms_norm_eps": 1e-6,
    "rope_theta": 1000000, "max_position_embeddings": 512, "tie_word_embeddings": False,
    "mlp_only_layers": [], "decoder_sparse_step": 1, "torch_dtype": "bfloat16",
    "vocab_size": 2048,
}


async def traffic(engine, first):
    """Three requests at once: two mixed dispatches (16-token chunks, then
    single passes beside the longest prompt's), then chained blocks."""
    await asyncio.gather(
        base.ask(engine, range(first, first + 23), 12),
        base.ask(engine, range(first + 30, first + 72), 16),
        base.ask(engine, range(first + 80, first + 92), 8),
    )


def main(out_dir: str) -> None:
    import jax

    from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
    from dynamo_exp_tpu.models import ModelConfig

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU: the recorded trace is a device trace")
    cfg = EngineConfig(
        model=ModelConfig.from_hf_config(HF), max_decode_slots=4, page_size=16,
        num_pages=64, max_model_len=256, eos_token_ids=[], prefill_chunk=16,
    )
    engine = TPUEngine(cfg, seed=0)
    engine.start()
    try:
        for first in (30, 300):  # every shape the traced round uses, twice
            asyncio.run(traffic(engine, first))
        time.sleep(0.2)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(out_dir, profiler_options=opts)
        asyncio.run(traffic(engine, 700))
        time.sleep(0.12)
        jax.profiler.stop_trace()
    finally:
        engine.stop()
    found = sorted(glob.glob(os.path.join(out_dir, "plugins", "profile", "*", "*.xplane.pb")))
    trace = os.path.join(out_dir, "block_trace.xplane.pb")
    base.slim(found[-1], trace)
    print(found[-1], os.path.getsize(found[-1]), "->", trace, os.path.getsize(trace))


if __name__ == "__main__":
    main(sys.argv[1])
