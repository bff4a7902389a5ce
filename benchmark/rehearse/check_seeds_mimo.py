#!/usr/bin/env python3
"""``check_seeds.py`` with the controls a model of ``attention_by_kind``
needs: the same command line, the same output file.

    python3 benchmark/rehearse/check_seeds_mimo.py \\
        --workload mimo-v2-flash.agent-longout-saturate --tag mimo-seeds \\
        --seeds 11,12,13 --control no_sink,sink_on_full,rotary_66,rotary_all,\\
sliding_base_on_full,no_value_scale,other_kinds_heads,k_fp8 --control-seeds 11

One is made on the ENGINE's side (``references/mimo_v2_flash.py`` stays
plain and sound; the comparison's parts 1-3 call ``models/llama.py`` anew):

- ``k_fp8``: K rounded to float8 (e4m3) as it is cached, in both pools: THE
  LOWER-PRECISION CONTROL (part 2's rows, part 3's logits).

The others are made on the REFERENCE's side, as ``check_seeds.py``'s own
are (the reference computes with the fault against the sound program):

- ``no_sink``: no layer has a sink;
- ``sink_on_full``: the full layers take one too (the first sliding
  layer's logits);
- ``rotary_66`` / ``rotary_all``: 66 (one pair more than the published 64),
  or all 192, of a head's values turn;
- ``sliding_base_on_full``: a full layer turns at the sliding layers' base;
- ``no_value_scale``: the values are cached as projected;
- ``other_kinds_heads``: a full layer's query heads are grouped 8 a KV
  head, the sliding kind's table, not its own 16;
- ``no_bias``: the experts chosen by the scores without the selection bias;
- ``fp8_experts``, ``top1``: ``check_seeds.py``'s.
"""

from __future__ import annotations

import contextlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import check_seeds  # noqa: E402
from check_seeds_glm5 import _patched, _reference_with  # noqa: E402

ENGINE_SIDE = ("k_fp8",)
CONSTANTS = {
    "no_sink": lambda c: {**c, "sink": (False,) * len(c["sink"])},
    "rotary_66": lambda c: {**c, "rotary": c["rotary"] + 2},  # one pair more than the 64
    "rotary_all": lambda c: {**c, "rotary": c["head_dim"]},
    "sliding_base_on_full": lambda c: {**c, "theta": (c["theta"][1], c["theta"][1])},
    "no_value_scale": lambda c: {**c, "v_scale": 1.0},
}
REFERENCE_SIDE = (*CONSTANTS, "sink_on_full", "other_kinds_heads", "no_bias")
plain_control = check_seeds.control


def _regrouped(plain):
    """``_attend`` with a full layer's query heads grouped as a sliding
    layer's are: head ``h`` reads KV head ``(h // other) % kv_heads``, where
    ``other`` is the sliding kind's query heads a KV head."""
    import jax.numpy as jnp
    import numpy as np

    def attend(q, q_pos, k, v, start, c, sliding, size, sink=None):
        if sliding:
            return plain(q, q_pos, k, v, start, c, sliding, size, sink)
        heads, kv = c["heads"], k.shape[1]
        other = heads // c["kv_heads"][1]
        order = np.argsort([(h // other) % kv for h in range(heads)], kind="stable")
        back = np.argsort(order)
        o = plain(
            q[:, order], q_pos, k, v, start, c, sliding, size,
            None if sink is None else jnp.asarray(sink)[order],
        )
        return o.reshape(q.shape[0], heads, -1)[:, back].reshape(q.shape[0], -1)

    return attend


@contextlib.contextmanager
def control(name: str, ctx: dict):
    """``ctx`` with the fault ``name``."""
    from dynamo_exp_tpu.models import llama

    reference = ctx["reference"]
    if name == "k_fp8":
        def faulty(plain):
            def write(k_cache, v_cache, k_new, v_new, *rest, **kw):
                return plain(k_cache, v_cache, check_seeds._fp8(k_new), v_new, *rest, **kw)
            return write
        with _patched(llama, "write_kv_pages", faulty):
            yield ctx
    elif name in CONSTANTS:
        def faulty(plain):
            return lambda hf: CONSTANTS[name](plain(hf))
        with _reference_with(reference, "block_constants", faulty):
            yield ctx
    elif name == "sink_on_full":
        def faulty(plain):
            def params(layers, c, i):
                lp = plain(layers, c, i)
                return lp if "sink" in lp else {**lp, "sink": layers["sink"][0]}
            return params
        with _reference_with(reference, "_attn_params", faulty):
            yield ctx
    elif name == "other_kinds_heads":
        with _reference_with(reference, "_attend", _regrouped):
            yield ctx
    elif name == "no_bias":
        def faulty(plain):
            return lambda g, bias, **kw: plain(g, 0 * bias, **kw)
        with _reference_with(reference, "_route", faulty):
            yield ctx
    else:
        with plain_control(name, ctx) as faulty_ctx:
            yield faulty_ctx


if __name__ == "__main__":
    check_seeds.CONTROLS = ENGINE_SIDE + REFERENCE_SIDE + ("fp8_experts", "top1")
    check_seeds.control = control
    code = check_seeds.main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
