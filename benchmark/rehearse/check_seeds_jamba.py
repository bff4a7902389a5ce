#!/usr/bin/env python3
"""``check_seeds.py`` with the controls a model of ``state_space`` needs
beside its own (``fp8_attention``, ``fp8_head``): the same command line,
the same output file.

    python3 benchmark/rehearse/check_seeds_jamba.py --workload jamba2-3b.longdoc-saturate \\
        --tag jamba-seeds --seeds 11,12,13 --control bf16_state,zero_state,no_softplus \\
        --control-seeds 11,12

Each control is made on the reference's side (``references/jamba.py``
stays plain): the reference computes with the fault against the sound
program.

- ``bf16_state``: the scan's state rounded to bfloat16 after every token
  (the precision below the float32 the configuration states for it);
- ``zero_state``: the scan's state zeroed where the second prefill chunk
  begins (a state dropped at a dispatch boundary);
- ``unsigned_a``: ``A = exp(A_log)``, the sign left out;
- ``no_softplus``: the step taken as projected, without the softplus;
- ``no_inner_norms``: the step's, B's and C's projections not normed;
- ``reversed_taps``: the convolution's taps in the other order;
- ``no_d``: the skip term ``D * c`` left out;
- ``rope``: rotary positions (base 10000) applied to q and k in attention;
- ``fp8_mamba``: the mixer's two large projections rounded to fp8 (e4m3).
"""

from __future__ import annotations

import contextlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import check_seeds  # noqa: E402

OWN = (
    "bf16_state", "zero_state", "unsigned_a", "no_softplus", "no_inner_norms",
    "reversed_taps", "no_d", "rope", "fp8_mamba",
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


def _with_layers(ctx: dict, **changed) -> dict:
    layers = {**ctx["params"]["layers"], **changed}
    return {**ctx, "params": {**ctx["params"], "layers": layers}}


@contextlib.contextmanager
def control(name: str, ctx: dict):
    import jax
    import jax.numpy as jnp

    from comparisons.state_space import CHUNK

    reference = ctx["reference"]
    layers = ctx["params"]["layers"]
    if name == "fp8_mamba":
        yield _with_layers(ctx, **{k: check_seeds._fp8(layers[k]) for k in ("mamba_in", "mamba_out")})
    elif name == "reversed_taps":
        yield _with_layers(ctx, mamba_conv_w=layers["mamba_conv_w"][:, ::-1])
    elif name == "no_d":
        yield _with_layers(ctx, mamba_D=jnp.zeros_like(layers["mamba_D"]))
    elif name == "unsigned_a":
        # -exp(A_log + i*pi) = +exp(A_log) is not to be had from A_log: the sign goes
        # where the reference takes its exponential.
        def faulty(plain):
            return lambda dt, c, a, b, cc, d: plain(dt, c, -a, b, cc, d)
        with _patched(reference, "_scan", faulty):
            yield ctx
    elif name in ("bf16_state", "zero_state"):
        def faulty(_plain):
            def scan(dt, c, a, b, cc, d):
                def step(h, t):
                    i, dt_t, c_t, b_t, cc_t = t
                    if name == "zero_state":
                        h = jnp.where(i == CHUNK, 0.0, h)
                    h = jnp.exp(dt_t[None] * a) * h + (dt_t * c_t)[None] * b_t[:, None]
                    if name == "bf16_state":
                        # not astype: XLA drops a convert pair it may compute in excess
                        h = jax.lax.reduce_precision(h, exponent_bits=8, mantissa_bits=7)
                    return h, jnp.sum(h * cc_t[:, None], axis=0) + d * c_t
                last, y = jax.lax.scan(
                    step, jnp.zeros_like(a), (jnp.arange(dt.shape[0]), dt, c, b, cc)
                )
                return y, last
            return scan
        with _patched(reference, "_scan", faulty):
            yield ctx
    elif name == "no_softplus":
        with _patched(reference, "_softplus", lambda _plain: lambda x: x):
            yield ctx
    elif name == "no_inner_norms":
        with _patched(reference, "_inner_norm", lambda _plain: lambda x, w, eps: x):
            yield ctx
    elif name == "rope":
        def faulty(_plain):
            def attention(h, lp, *, hd):
                T = h.shape[0]
                pos = jnp.arange(T, dtype=jnp.float32)
                inv = 1.0 / 10000.0 ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
                ang = pos[:, None] * inv[None]
                cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

                def rope(x):
                    a, b = x[..., : hd // 2], x[..., hd // 2:]
                    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)

                f32 = reference._f32
                q = rope((h @ f32(lp["wq"])).reshape(T, -1, hd))
                k = rope((h @ f32(lp["wk"])).reshape(T, -1, hd))
                v = (h @ f32(lp["wv"])).reshape(T, -1, hd)
                group = q.shape[1] // k.shape[1]
                k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
                at = jnp.arange(T)
                scores = jnp.einsum("thd,shd->hts", q, k) * hd**-0.5
                scores = jnp.where(at[None, :, None] >= at[None, None, :], scores, -jnp.inf)
                attn = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
                return attn.reshape(T, -1) @ f32(lp["wo"])
            return attention
        with _patched(reference, "_attention", faulty):
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
