#!/usr/bin/env python3
"""``check_seeds.py`` with the controls a model of ``layer_kinds`` needs
beside its own (``fp8_attention``, ``fp8_head``, ``fp8_experts``,
``fp8_weights``, ``top1``): the same command line, the same output file.

    python3 benchmark/rehearse/check_seeds_lfm2.py --workload lfm2-8b-a1b.chat-saturate \\
        --tag lfm2-seeds --seeds 11,12,13 --control fp8_experts,fp8_conv,zero_state \\
        --control-seeds 11,12

Each control is made on the reference's side (``references/lfm2_moe.py``
stays plain): the reference computes with the fault against the sound
program.

- ``fp8_conv``: the convolution's two projections rounded to fp8 (e4m3);
- ``select_by_score``: the experts chosen by ``s`` alone (no bias);
- ``weights_from_biased``: the chosen experts weighed by ``s + b``;
- ``no_renorm``: the weights not divided by their sum (+1e-6);
- ``zero_state``: from token ``CONV_PREFILL`` on (the comparison's first
  decode step) a convolution sees zeros where the tokens before it were;
- ``reversed_taps``: the convolution's taps in the other order.
"""

from __future__ import annotations

import contextlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import check_seeds  # noqa: E402

OWN = (
    "fp8_conv", "select_by_score", "weights_from_biased", "no_renorm", "zero_state",
    "reversed_taps",
)
plain_control = check_seeds.control


@contextlib.contextmanager
def _patched(reference, name: str, faulty):
    plain = getattr(reference, name)
    setattr(reference, name, faulty(plain))
    check_seeds._retrace(reference)
    try:
        yield
    finally:
        setattr(reference, name, plain)
        check_seeds._retrace(reference)


@contextlib.contextmanager
def control(name: str, ctx: dict):
    import jax
    import jax.numpy as jnp

    from comparisons.layer_kinds import CONV_PREFILL

    reference = ctx["reference"]
    layers = dict(ctx["params"]["layers"])
    if name == "fp8_conv":
        layers.update({k: check_seeds._fp8(layers[k]) for k in ("conv_in", "conv_out")})
        yield {**ctx, "params": {**ctx["params"], "layers": layers}}
    elif name == "reversed_taps":
        layers["conv_w"] = layers["conv_w"][:, ::-1]
        yield {**ctx, "params": {**ctx["params"], "layers": layers}}
    elif name == "no_renorm":
        yield {**ctx, "hf": {**ctx["hf"], "norm_topk_prob": False}}
    elif name == "select_by_score":
        with _patched(reference, "_route", lambda plain: lambda s, bias, **kw: plain(s, 0.0, **kw)):
            yield ctx
    elif name == "weights_from_biased":
        def faulty(_plain):
            def route(s, bias, *, top_k, norm, scale):
                biased = s + bias
                best, top_i = jax.lax.top_k(biased, top_k + 1)
                w, top_i = best[:, :top_k], top_i[:, :top_k]
                if norm:
                    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
                one_hot = jax.nn.one_hot(top_i, s.shape[-1], dtype=jnp.float32)
                return jnp.sum(one_hot * (w * scale)[..., None], axis=1), best[:, top_k - 1] - best[:, top_k]
            return route
        with _patched(reference, "_route", faulty):
            yield ctx
    elif name == "zero_state":
        def faulty(plain):
            def conv(z, taps):
                late = plain(z.at[:CONV_PREFILL].set(0.0), taps)
                return jnp.where((jnp.arange(z.shape[0]) >= CONV_PREFILL)[:, None], late, plain(z, taps))
            return conv
        with _patched(reference, "_causal_conv", faulty):
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
