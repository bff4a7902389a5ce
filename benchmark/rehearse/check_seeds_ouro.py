#!/usr/bin/env python3
"""``check_seeds.py`` with the controls a looped model needs: the same
command line, the same output file.

    python3 benchmark/rehearse/check_seeds_ouro.py \\
        --workload ouro-2.6b.reason-short-saturate --tag ouro-seeds \\
        --seeds 11,12,13 --control one_pass_fewer,pass0_kv,no_norm_between,\\
slots_transposed_on_write,bf16_accumulation --control-seeds 11

Four are made on the REFERENCE's side, as ``check_seeds.py``'s own are (the
reference computes with the fault against the sound program;
``references/ouro.py::block_constants`` ends with the switches they turn):

- ``one_pass_fewer``: ``total_ut_steps - 1`` passes;
- ``pass0_kv``: every pass after the first reads pass 0's keys and values
  in its own's place (a cache shared between passes);
- ``no_norm_between``: the final norm closes the last pass alone;
- ``bf16_accumulation``: THE LOWER-PRECISION CONTROL: the norms' mean and
  root and the softmax in bfloat16 where the model states float32.

One is made on the ENGINE's side (the served programs are built anew under
the fault and dropped after it):

- ``slots_transposed_on_write``: pass ``t``'s layer ``l`` WRITES slot
  ``l * passes + t`` and reads ``t * layers + l`` as ever.

``fp8_attention``, ``fp8_head``, ``fp8_experts`` (the feed-forward's matrices)
and ``fp8_weights`` (all three: the contract's control for a bfloat16
configuration) are ``check_seeds.py``'s.
"""

from __future__ import annotations

import contextlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import check_seeds  # noqa: E402
from check_seeds_glm5 import _patched, _reference_with  # noqa: E402

CONSTANTS = {
    "one_pass_fewer": lambda c: {**c, "passes": c["passes"] - 1},
    "pass0_kv": lambda c: {**c, "own_kv": False},
    "no_norm_between": lambda c: {**c, "close": False},
    "bf16_accumulation": lambda c: {**c, "acc": "bfloat16"},
}
ENGINE_SIDE = ("slots_transposed_on_write",)
plain_control = check_seeds.control


@contextlib.contextmanager
def control(name: str, ctx: dict):
    """``ctx`` with the fault ``name``."""
    from dynamo_exp_tpu.models import llama

    if name in CONSTANTS:
        def faulty(plain):
            return lambda hf: CONSTANTS[name](plain(hf))
        with _reference_with(ctx["reference"], "block_constants", faulty):
            yield ctx
    elif name == "slots_transposed_on_write":
        engine = ctx["engine"]
        layers, passes = engine.cfg.model.num_attn_layers, engine.cfg.model.loop_passes

        def faulty(plain):
            def write(*args, layer, **kw):
                return plain(*args, layer=(layer % layers) * passes + layer // layers, **kw)
            return write

        kept = dict(engine._ragged_fns)
        engine._ragged_fns.clear()  # the probes' programs are traced under the fault
        try:
            with _patched(llama, "write_kv_pages", faulty):
                yield ctx
        finally:
            engine._ragged_fns.clear()
            engine._ragged_fns.update(kept)
    else:
        with plain_control(name, ctx) as faulty_ctx:
            yield faulty_ctx


if __name__ == "__main__":
    check_seeds.CONTROLS = (
        *CONSTANTS, *ENGINE_SIDE, "fp8_attention", "fp8_head", "fp8_experts", "fp8_weights",
    )
    check_seeds.control = control
    code = check_seeds.main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
