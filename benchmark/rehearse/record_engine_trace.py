#!/usr/bin/env python3
"""Record the small engine trace that ``tests/test_xplane_meta.py`` checks
``lib/xplane_meta.py`` and the three trace readers against: a fraction
of a second of the ``tiny-qwen3`` engine on one chip — mixed dispatches
(prompts that share a prefix), chained decode windows, and the idle
path — with the engine loop's
``dyn/<phase>`` annotations in the host plane and the model's named
scopes in the operations' metadata. Run on the chip:

    python3 benchmark/rehearse/record_engine_trace.py chiprun_out/bench/engine_trace

and copy the two files it prints to ``benchmark/tests/data/``:
``engine_trace.xplane.pb`` and ``engine_trace.metrics.txt`` (the
``/metrics`` text after the run: the readers take the pools' geometry
from ``dynamo_kv_pool_info`` there). The trace is written slimmed, to
keep the repository small: without the ``/host:metadata`` plane (the
programs' HLO protos, two thirds of the file), without the operations'
per-event stats, and with only the metadata stats a reader uses
(``tf_op``, ``program_id``, ``hlo_category``). Every event, name and time
is as recorded; ``jax.profiler.ProfileData`` reads the result.
"""

import asyncio
import glob
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]
KEPT_STATS = {"tf_op", "program_id", "hlo_category"}


async def ask(engine, prompt, max_tokens):
    from dynamo_exp_tpu.protocols.common import BackendInput

    b = BackendInput(token_ids=list(prompt))
    b.stop_conditions.max_tokens = max_tokens
    b.stop_conditions.ignore_eos = True
    stream = await engine.generate(b.to_dict())
    async for _ in stream:
        pass


async def traffic(engine, base):
    """Three requests at once, two of which share their first 20
    tokens: two mixed dispatches (16-token chunks), then a few chained
    windows of 4 as the rows finish one after another."""
    shared = list(range(base, base + 20))
    await asyncio.gather(
        ask(engine, shared + [7, 8, 9], 9),
        ask(engine, shared + [11, 12], 14),
        ask(engine, range(base + 40, base + 52), 6),
    )


def slim(src: str, dst: str) -> None:
    from lib import trace_reduce, xplane_meta

    space = xplane_meta._schema()()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    planes = [p for p in space.planes if p.name != "/host:metadata"]
    del space.planes[:]
    for plane in planes:
        kept = space.planes.add()
        kept.CopyFrom(plane)
        names = {e.key: e.value.name for e in kept.stat_metadata}
        for entry in kept.event_metadata:
            stats = [
                type(st).FromString(st.SerializeToString())
                for st in entry.value.stats if names.get(st.metadata_id) in KEPT_STATS
            ]
            del entry.value.stats[:]
            entry.value.stats.extend(stats)
        if trace_reduce.DEVICE_PLANE.match(kept.name):
            for line in kept.lines:
                if line.name != xplane_meta.MODULES_LINE:  # a run keeps its run_id
                    for ev in line.events:
                        del ev.stats[:]
    with open(dst, "wb") as f:
        f.write(space.SerializeToString())


def main(out_dir: str) -> None:
    import jax

    from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
    from dynamo_exp_tpu.models import TINY_QWEN3
    from dynamo_exp_tpu.telemetry import get_telemetry

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU: the recorded trace is a device trace")
    cfg = EngineConfig(
        model=TINY_QWEN3, max_decode_slots=4, page_size=8, num_pages=64,
        max_model_len=128, eos_token_ids=[], decode_window=4, prefill_chunk=16,
    )
    engine = TPUEngine(cfg, seed=0)
    engine.start()
    try:
        for base in (30, 100):  # every shape the traced round uses, twice
            asyncio.run(traffic(engine, base))
        time.sleep(0.2)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(out_dir, profiler_options=opts)
        asyncio.run(traffic(engine, 170))
        time.sleep(0.12)  # the idle path: two whole parks of 50 ms
        jax.profiler.stop_trace()
    finally:
        engine.stop()
    found = sorted(glob.glob(os.path.join(out_dir, "plugins", "profile", "*", "*.xplane.pb")))
    trace = os.path.join(out_dir, "engine_trace.xplane.pb")
    slim(found[-1], trace)
    metrics = os.path.join(out_dir, "engine_trace.metrics.txt")
    with open(metrics, "wb") as f:
        f.write(
            b"\n".join(
                line for line in get_telemetry().render().splitlines()
                if b"_bucket{" not in line and b"_created" not in line
            ) + b"\n"
        )
    print(found[-1], os.path.getsize(found[-1]), "->", trace, os.path.getsize(trace), metrics)


if __name__ == "__main__":
    main(sys.argv[1])
