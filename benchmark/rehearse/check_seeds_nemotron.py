#!/usr/bin/env python3
"""``check_seeds.py`` with the controls a model of ``mamba2_experts`` needs
beside its own (``fp8_attention``, ``fp8_head``): the same command line, the
same output file.

    python3 benchmark/rehearse/check_seeds_nemotron.py \\
        --workload nemotron-3-nano-30b-a3b.longdoc-fast-saturate --tag nemotron-seeds \\
        --seeds 11,12,13 --control bf16_state,zero_state,norm_before_gate \\
        --control-seeds 11,12

Each control is made on the reference's side (``references/nemotron_h.py``
stays plain): the reference computes with the fault against the sound
program.

- ``bf16_state``: the recurrence's state rounded to bfloat16 after every
  token (the precision below the float32 the configuration states for it);
- ``zero_state``: the state zeroed where the second prefill chunk begins (a
  state dropped at a dispatch boundary);
- ``norm_before_gate``: the mixer's output normed first and gated after;
- ``one_norm``: one RMS over all 4096 channels in place of 8 groups;
- ``relu``: ``relu`` for ``relu^2`` in every expert;
- ``no_shared``: the shared expert left out;
- ``no_scale``: the routed scaling factor (2.5) left out;
- ``bias_as_weight``: the selection bias added to the weights too (weights
  from ``s + b`` where they are ``s``'s);
- ``fp8_mamba``: the mixer's two large projections rounded to fp8 (e4m3);
- ``fp8_experts``: every expert's and the shared expert's matrices in fp8;
- ``fp8_weights``: all of them, attention's and the head's too (the
  contract's control for a bfloat16 configuration).
"""

from __future__ import annotations

import contextlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import check_seeds  # noqa: E402
from check_seeds_jamba import _patched, _with_layers  # noqa: E402

OWN = (
    "bf16_state", "zero_state", "norm_before_gate", "one_norm", "relu", "no_shared",
    "no_scale", "bias_as_weight", "fp8_mamba", "fp8_experts", "fp8_weights",
)
plain_control = check_seeds.control


@contextlib.contextmanager
def control(name: str, ctx: dict):
    import jax
    import jax.numpy as jnp

    from comparisons.mamba2_experts import CHUNK

    reference = ctx["reference"]
    layers = ctx["params"]["layers"]
    fp8 = check_seeds._fp8
    if name == "fp8_mamba":
        yield _with_layers(ctx, **{k: fp8(layers[k]) for k in ("m2_in", "m2_out")})
    elif name == "fp8_experts":
        # A second copy of the expert stacks does not fit beside the first:
        # they are rounded where the reference takes them up, an expert at a time.
        def faulty(plain):
            return lambda h, w_up, w_down: plain(h, fp8(w_up), fp8(w_down))
        with _patched(reference, "_two_matrix", faulty):
            yield ctx
    elif name == "fp8_weights":
        with contextlib.ExitStack() as stack:
            for part in ("fp8_attention", "fp8_head", "fp8_mamba", "fp8_experts"):
                ctx = stack.enter_context(control(part, ctx))
            yield ctx
    elif name in ("bf16_state", "zero_state"):
        def faulty(_plain):
            def carried(s, t):
                if name == "zero_state":
                    return jnp.where(t == CHUNK, 0.0, s)
                # not astype: XLA drops a convert pair it may compute in excess
                return jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
            return carried
        with _patched(reference, "_carried", faulty):
            yield ctx
    elif name in ("norm_before_gate", "one_norm"):
        def faulty(plain):
            def gated_norm(y, z, w, eps, groups):
                if name == "one_norm":
                    return plain(y, z, w, eps, 1)
                t = y.shape[0]
                y = y.reshape(t, groups, -1)
                y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
                return y.reshape(t, -1) * reference._f32(w) * jax.nn.silu(z)
            return gated_norm
        with _patched(reference, "_gated_norm", faulty):
            yield ctx
    elif name == "relu":
        with _patched(reference, "_expert_act", lambda _plain: jax.nn.relu):
            yield ctx
    elif name == "no_shared":
        with _patched(reference, "_shared", lambda _plain: lambda h, lp: jnp.zeros_like(h)):
            yield ctx
    elif name == "no_scale":
        yield {**ctx, "hf": {**ctx["hf"], "routed_scaling_factor": 1.0}}
    elif name == "bias_as_weight":
        def faulty(plain):
            return lambda s, bias, **kw: plain(s + bias, jnp.zeros_like(bias), **kw)
        with _patched(reference, "_route", faulty):
            yield ctx
    else:
        with plain_control(name, ctx) as faulty_ctx:
            yield faulty_ctx


if __name__ == "__main__":
    check_seeds.CONTROLS = check_seeds.CONTROLS + OWN
    check_seeds.control = control
    code = check_seeds.main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
