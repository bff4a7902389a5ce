#!/usr/bin/env python3
"""Record the small device trace that ``tests/test_trace_reduce.py``
checks the reduction against: a few matrix products and a ``while`` loop
on one chip, some tens of milliseconds. Run on the chip:

    python3 benchmark/rehearse/record_trace.py chiprun_out/bench/small_trace

and copy the ``.xplane.pb`` it prints to ``benchmark/tests/data/``.
"""

import glob
import os
import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU: the recorded trace is a device trace")

    @jax.jit
    def step(x, w):
        def body(i, x):
            return jnp.tanh(x @ w)
        return jax.lax.fori_loop(0, 4, body, x)

    x = jnp.ones((256, 512), jnp.bfloat16)
    w = jnp.ones((512, 512), jnp.bfloat16) * 0.01
    step(x, w).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    for _ in range(3):
        step(x, w).block_until_ready()
        time.sleep(0.005)
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(out_dir, "plugins", "profile", "*", "*.xplane.pb"))
    print(found, [os.path.getsize(f) for f in found])


if __name__ == "__main__":
    main(sys.argv[1])
