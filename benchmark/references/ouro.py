"""Plain float32 reference of ``ouro`` (Ouro-2.6B, a looped language model:
arXiv:2510.25741, "Scaling Latent Reasoning via Looped Language Models").

Written from the published ``config.json``'s keys and the family's
description, in straightforward ``jax.numpy``: no kernel, no cache, no
paging, no batching. One sequence, positions ``s = 0..S-1``, stream ``h``
``[S, D]``; ``N(x; w) = w * x / sqrt(mean(x^2) + rms_norm_eps)`` (plain
weights, no ``1 +``); ``L = num_hidden_layers``, ``T = total_ut_steps``:

    h = E[tokens]                                   # no embedding scale
    for t in 0 .. T-1:                              # the SAME L layers' weights in every pass
        for l in 0 .. L-1:
            a = N(h; n1_l)
            q, k, v = a Wq_l, a Wk_l, a Wv_l        # heads of head_dim, no bias, no per-head norm
            q, k = rope(q, s), rope(k, s)           # whole head, half-split pairs, base rope_theta,
                                                    # the token's position in EVERY pass
            o = softmax(q k^T / sqrt(head_dim)) v   # causal, over THIS pass's keys and values only
            h = h + N(o Wo_l; n2_l)                 # sandwich: a norm on the half's output too
            m = N(h; n3_l)
            h = h + N((silu(m Wg_l) * (m Wu_l)) Wd_l; n4_l)
        h = N(h; n_final)                           # closes EVERY pass; the next pass's input
        lambda_t = sigmoid(h . w_gate + b_gate)     # the exit gate: one logit a token
    logits = h W_head                               # the last pass's h, normed once

The exit rule, for the record: ``p_t = lambda_t * prod_{s<t}(1 - lambda_s)``,
the rest of the mass on the last pass; a token leaves at the first pass
whose cumulative ``p`` reaches ``early_exit_threshold``. At the published
threshold 1 that is the last pass for every token: the gate moves no logit,
``log_probs`` does not evaluate it, and ``exit_gates`` returns the four
``lambda_t`` for the tests.

Departures from the published code, each deliberate: every matrix product
runs in float32 at ``jax.default_matmul_precision("highest")`` on the
bfloat16 weights as served, where the published code computes in bfloat16;
a sequence is filled up to one of ``LENGTHS`` behind its last token (under
the causal mask the filling changes nothing before it), so that every step
of a walk is one compiled program a length.
ASSUMED, because the catalog's row has no key for them
(``benchmark/configs/ouro-2.6b.json``, ``assumed``): the four norms' sandwich
placement and the final norm closing every pass; keys and values of a pass
seen by that pass alone; the same positions in every pass; the gate a linear
map ``D -> 1`` with a bias, after the pass's norm.

The weights are the engine's own arrays (``models/llama.py::init_params``:
``attn_norm`` n1, ``post_attn_norm`` n2, ``mlp_norm`` n3, ``post_ffn_norm``
n4, ``exit_gate_w`` / ``exit_gate_b``), used in place: layer ``l`` of every
stack is sliced inside the loop over layers, in each pass, so one layer at a
time is raised to float32 (0.21 GB at the published widths) beside an engine
that fills the chip.

``block_constants`` ends with three switches the sound model fixes
(``own_kv``, ``close``, ``acc``): the seeds check's controls turn them
(``rehearse/check_seeds_ouro.py``), nothing else does.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Largest allowed |engine top log-probability - reference log-probability|.
# Ouro-2.6B on the chip (benchmark/rehearse/check_seeds_ouro.py, my chip run,
# PR 57): the sound program 0.378-0.881 over twelve probe seeds (two probes of
# 8 decode steps each, the largest of 16 steps' five top log-probabilities),
# ten times Qwen3-4B's 0.04-0.06. Why so far: 192 layer applications of seeded
# random weights are a chaotic map, and the engine rounds the stream, every
# norm's output and every product to bfloat16 (8 bits) on the way: the same
# experiment on the CPU at a width of 256 reads 0.07 at 12 layers x 2 passes,
# 0.14-0.23 at 48 x 2 and 0.9-1.9 at 48 x 4 (float32 all through: 3e-6), so
# the distance is the rounding's, grown by depth x passes, not a fault of the
# loop. The controls, same run: one pass fewer, a cache shared between passes,
# no norm between passes, slots transposed on the write, and every weight in
# fp8 (e4m3: the precision below the stated bfloat16) each make the engine's
# chosen token fall OUTSIDE the reference's 64 most likely at the first steps:
# refused whatever the limit. The norms' and the softmax's accumulation in
# bfloat16 in the reference (the issue's fifth control) reads 0.779-0.842,
# INSIDE the sound program's own range: the engine rounds those operations'
# outputs to bfloat16 already, a rounding inside them is one more of the same
# size, and no limit can tell it from the sound program (PERF.md section 7).
# The limit stands between the sound program's largest reading and the
# controls' categorical failure, with room for fresh seeds (the largest of 192
# steps read 0.881; a step's differences are about half-normal with a
# deviation near 0.25, so 1.5 is six deviations).
LOGPROB_TOL = 1.5

# A sequence is filled up to the first of these that holds it: the probes'
# 56 tokens and the tests' short rows; longer rows of the tests.
LENGTHS = (64, 128, 512)


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def _rms_norm(x, w, eps, acc=jnp.float32):
    """``w * x / sqrt(mean(x^2) + eps)``, the mean and the root in ``acc``
    (float32: what the model states)."""
    xa = x.astype(acc)
    scale = jax.lax.rsqrt(jnp.mean(xa * xa, axis=-1, keepdims=True) + jnp.asarray(eps, acc))
    return _f32(xa * scale) * _f32(w)


def _rope(x, positions, theta):
    """x: [S, H, hd]; pairs (i, i + hd/2) turn by position * theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions[:, None].astype(jnp.float32) * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ _f32(w_gate)) * (h @ _f32(w_up))) @ _f32(w_down)


def block_constants(hf: dict) -> dict:
    """What the equations read of the configuration's keys."""
    if hf.get("model_type") != "ouro":
        raise ValueError(f"references/ouro.py covers 'ouro', not {hf.get('model_type')!r}")
    if hf.get("rope_scaling") or hf.get("sliding_window") is not None:
        raise ValueError("references/ouro.py has no rope scaling and no sliding window")
    if float(hf.get("early_exit_threshold", 1.0)) < 1.0:
        raise ValueError("references/ouro.py runs every pass: early_exit_threshold < 1 is not covered")
    return {
        "passes": int(hf["total_ut_steps"]),
        "hd": int(hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]),
        "eps": float(hf["rms_norm_eps"]),
        "theta": float(hf["rope_theta"]),
        # the sound model's: a pass attends over its OWN keys and values, the
        # final norm closes every pass, norms and softmax accumulate in float32
        "own_kv": True, "close": True, "acc": "float32",
    }


def _layer_params(layers, l):
    return {k: jax.lax.dynamic_index_in_dim(v, l, 0, keepdims=False) for k, v in layers.items()}


def _attention(q, k, v, hd, acc):
    """Causal softmax attention of one sequence: q [S, H, hd], k and v
    [S, Hkv, hd]; query head ``h`` reads KV head ``h // (H / Hkv)``."""
    S = q.shape[0]
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    pos = jnp.arange(S)
    scores = jnp.einsum("thd,shd->hts", q, k) * hd**-0.5
    scores = jnp.where(pos[None, :, None] >= pos[None, None, :], scores, -jnp.inf)
    probs = _f32(jax.nn.softmax(scores.astype(acc), axis=-1))
    return jnp.einsum("hts,shd->thd", probs, v).reshape(S, -1)


def _layer(h, lp, kv0, *, hd, eps, theta, acc):
    """One layer of one pass on ``h`` [S, D]. ``kv0``: None, or the (k, v)
    this layer computed in pass 0, read in the pass's own place (the control
    ``own_kv`` False). Returns the new ``h`` and this pass's (k, v)."""
    S = h.shape[0]
    pos = jnp.arange(S)
    a = _rms_norm(h, lp["attn_norm"], eps, acc)
    q = _rope((a @ _f32(lp["wq"])).reshape(S, -1, hd), pos, theta)
    k = _rope((a @ _f32(lp["wk"])).reshape(S, -1, hd), pos, theta)
    v = (a @ _f32(lp["wv"])).reshape(S, -1, hd)
    o = _attention(q, *((k, v) if kv0 is None else kv0), hd, acc)
    h = h + _rms_norm(o @ _f32(lp["wo"]), lp["post_attn_norm"], eps, acc)
    m = _rms_norm(h, lp["mlp_norm"], eps, acc)
    ffn = _swiglu(m, lp["w_gate"], lp["w_up"], lp["w_down"])
    return h + _rms_norm(ffn, lp["post_ffn_norm"], eps, acc), (k, v)


@functools.partial(
    jax.jit, static_argnames=("passes", "hd", "eps", "theta", "own_kv", "close", "acc")
)
def _forward(params, ids, *, passes, hd, eps, theta, own_kv, close, acc):
    """The looped forward on one filled sequence: (the last pass's stream
    after its norm [S, D], the gate's ``lambda_t`` [passes, S])."""
    layers = params["layers"]
    n_layers = layers["wq"].shape[0]
    acc = jnp.dtype(acc)
    kw = dict(hd=hd, eps=eps, theta=theta, acc=acc)
    h = _f32(params["embed"][ids])
    S = h.shape[0]
    kv_heads = layers["wk"].shape[-1] // hd
    # pass 0's k and v, for the control ``own_kv`` False alone (else empty)
    kept = jnp.zeros((2, 0 if own_kv else n_layers, S, kv_heads, hd), jnp.float32)

    def layer(l, carry, t):
        h, kept = carry
        lp = _layer_params(layers, l)
        new, (k, v) = _layer(h, lp, None, **kw)
        if own_kv:
            return new, kept
        # the control: pass 0 keeps its keys and values, every later pass
        # reads those in its own's place
        shared, _ = _layer(h, lp, (kept[0, l], kept[1, l]), **kw)
        first = t == 0
        kept = jnp.where(first, kept.at[0, l].set(k).at[1, l].set(v), kept)
        return jnp.where(first, new, shared), kept

    def one_pass(t, carry):
        h, kept, gates = carry
        h, kept = jax.lax.fori_loop(0, n_layers, lambda l, c: layer(l, c, t), (h, kept))
        closed = _rms_norm(h, params["final_norm"], eps, acc)
        # the control ``close`` False: the stream goes on un-normed between
        # passes; the last pass is closed all the same, ahead of the head
        h = closed if close else jnp.where(t == passes - 1, closed, h)
        lam = jax.nn.sigmoid(h @ _f32(params["exit_gate_w"]) + _f32(params["exit_gate_b"])[0])
        return h, kept, gates.at[t].set(lam)

    h, _, gates = jax.lax.fori_loop(
        0, passes, one_pass, (h, kept, jnp.zeros((passes, S), jnp.float32))
    )
    return h, gates


def _filled(token_ids) -> list[int]:
    ids = list(token_ids)
    for n in LENGTHS:
        if len(ids) <= n:
            return ids + [0] * (n - len(ids))
    raise ValueError(f"a sequence of {len(ids)} tokens exceeds LENGTHS[-1]={LENGTHS[-1]}")


def _run(params: dict, hf: dict, token_ids):
    with jax.default_matmul_precision("highest"):
        return _forward(
            params, jnp.asarray(_filled(token_ids), jnp.int32), **block_constants(hf)
        )


@jax.jit
def _head(h, head_t):
    return jax.nn.log_softmax(h @ _f32(head_t).T, axis=-1)


def log_probs(params: dict, hf: dict, token_ids, rows) -> np.ndarray:
    """log-softmax over the vocabulary at positions ``rows`` of the
    sequence ``token_ids``."""
    h, _ = _run(params, hf, token_ids)
    head_t = params["embed"] if "lm_head" not in params else params["lm_head"].T
    with jax.default_matmul_precision("highest"):
        return np.asarray(_head(h[jnp.asarray(list(rows), jnp.int32)], head_t))


def exit_gates(params: dict, hf: dict, token_ids) -> np.ndarray:
    """The exit gate's ``lambda_t`` after every pass, ``[total_ut_steps,
    len(token_ids)]``: what the served model holds and does not evaluate."""
    return np.asarray(_run(params, hf, token_ids)[1])[:, : len(list(token_ids))]
