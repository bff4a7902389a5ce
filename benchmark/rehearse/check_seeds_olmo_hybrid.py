#!/usr/bin/env python3
"""``check_seeds.py`` with the controls a model of ``linear_attention``
needs beside its own (``fp8_head``): the same command line, the same
output file.

    python3 benchmark/rehearse/check_seeds_olmo_hybrid.py \\
        --workload olmo-hybrid-7b.longdoc-saturate --tag olmo-seeds --seeds 11,12,13 \\
        --control bf16_state,zero_state,beta_no_2 --control-seeds 11,12

Each control is made on the reference's side (``references/olmo_hybrid.py``
stays plain): the reference computes with the fault against the sound
program.

- ``fp8_mixer``: the mixer's three large projections (q/k/v's, the
  output gate's, the output's) rounded to fp8 (e4m3);
- ``bf16_state``: the matrix state rounded to bfloat16 after every chunk
  of 64 prefilled tokens and after every decoded token (the precision
  below the float32 the configuration states for it, at the moments a
  program that held it in bfloat16 would round it);
- ``bf16_state_prefill``: the same in the prefilled chunks alone, the
  decoded tokens sound: what a chunked kernel that kept its state in
  bfloat16 would do, held by the limit on the state after prefill and by
  no other;
- ``zero_state``: the state zeroed where the second prefill chunk begins
  (a state dropped at a dispatch boundary);
- ``zero_state_late``: the state zeroed where token ``PREFILL`` (2048)
  begins: a state dropped at the last boundary between the long probe's
  dispatches, some 50 tokens before its first decoded token;
- ``bf16_conv_history``: NOT A FAULT, a diagnosis: a token from
  ``PREFILL`` on reads the three convolution inputs before it rounded to
  bfloat16, as the one-step form reads them from the pool's entry. It
  must pass, and the state after decode then reads what the state after
  prefill does, which says where the one-step form's 0.003 comes from;
- ``beta_no_2``: the step ``sigmoid`` alone, ``linear_allow_neg_eigval``
  not honoured;
- ``no_decay``: ``a_t = 1``, the decay left out;
- ``no_qk_norm``: q and k as the convolution leaves them, not normed to
  length 1;
- ``reversed_taps``: the convolutions' taps in the other order;
- ``no_gate``: the output's gate left out;
- ``rope``: rotary positions (base 10000) applied to q and k in the full
  layers;
- ``fp8_attention`` and ``fp8_head`` are ``check_seeds.py``'s.
"""

from __future__ import annotations

import contextlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import check_seeds  # noqa: E402

OWN = (
    "fp8_mixer", "bf16_state", "bf16_state_prefill", "zero_state", "zero_state_late",
    "bf16_conv_history", "beta_no_2", "no_decay", "no_qk_norm", "reversed_taps", "no_gate",
    "rope",
)
KERNEL_CHUNK = 64  # ops/gated_delta.py CHUNK: where a bfloat16 state would be rounded
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

    from comparisons.state_space import CHUNK, PREFILL

    reference = ctx["reference"]
    layers = ctx["params"]["layers"]
    if name == "fp8_mixer":
        yield _with_layers(
            ctx, **{k: check_seeds._fp8(layers[k]) for k in ("gdn_qkv", "gdn_gate", "gdn_out")}
        )
    elif name == "reversed_taps":
        yield _with_layers(ctx, gdn_conv_w=layers["gdn_conv_w"][:, ::-1])
    elif name == "no_decay":  # exp(A_log) = 0: g = 0, a = 1
        yield _with_layers(ctx, gdn_A_log=jnp.full_like(layers["gdn_A_log"], -jnp.inf))
    elif name == "beta_no_2":
        yield {**ctx, "hf": {**ctx["hf"], "linear_allow_neg_eigval": False}}
    elif name in ("bf16_state", "bf16_state_prefill", "zero_state", "zero_state_late"):
        def faulty(_plain):
            def delta_rule(q, k, v, g, beta):
                def step(s, t):
                    i, q_t, k_t, v_t, g_t, b_t = t
                    if name.startswith("zero_state"):
                        s = jnp.where(i == (CHUNK if name == "zero_state" else PREFILL), 0.0, s)
                    s = jnp.exp(g_t)[:, None, None] * s
                    err = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
                    s = s + k_t[:, :, None] * err[:, None, :]
                    if name.startswith("bf16_state"):
                        # not astype: XLA drops a convert pair it may compute in excess
                        rounded = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
                        at_end = ((i + 1) % KERNEL_CHUNK == 0) & (i < PREFILL)
                        if name == "bf16_state":
                            at_end |= i >= PREFILL
                        s = jnp.where(at_end, rounded, s)
                    return s, jnp.einsum("hkv,hk->hv", s, q_t)
                zero = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
                last, o = jax.lax.scan(
                    step, zero, (jnp.arange(q.shape[0]), q, k, v, g, beta)
                )
                return o, last
            return delta_rule
        with _patched(reference, "_delta_rule", faulty):
            yield ctx
    elif name == "bf16_conv_history":
        def faulty(_plain):
            def causal_conv(z, taps):
                K, T = taps.shape[0], z.shape[0]
                held = jax.lax.reduce_precision(z, exponent_bits=8, mantissa_bits=7)
                zeros = jnp.zeros((K - 1, z.shape[1]), z.dtype)
                stream, entry = jnp.concatenate([zeros, z]), jnp.concatenate([zeros, held])
                decoded = (jnp.arange(T) >= PREFILL)[:, None]
                return taps[K - 1] * z + sum(
                    taps[j] * jnp.where(decoded, entry[j : j + T], stream[j : j + T])
                    for j in range(K - 1)
                )
            return causal_conv
        with _patched(reference, "_causal_conv", faulty):
            yield ctx
    elif name == "no_qk_norm":
        with _patched(reference, "_unit", lambda _plain: lambda x: x):
            yield ctx
    elif name == "no_gate":
        with _patched(reference, "_out_gate", lambda _plain: lambda x: jnp.ones_like(x)):
            yield ctx
    elif name == "rope":
        def faulty(_plain):
            def attention(x, lp, *, hd, eps):
                T = x.shape[0]
                pos = jnp.arange(T, dtype=jnp.float32)
                inv = 1.0 / 10000.0 ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
                ang = pos[:, None] * inv[None]
                cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

                def rope(y):
                    a, b = y[..., : hd // 2], y[..., hd // 2:]
                    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)

                f32, norm = reference._f32, reference._rms_norm
                q = rope(norm(x @ f32(lp["wq"]), lp["q_norm"], eps).reshape(T, -1, hd))
                k = rope(norm(x @ f32(lp["wk"]), lp["k_norm"], eps).reshape(T, -1, hd))
                v = (x @ f32(lp["wv"])).reshape(T, -1, hd)
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
