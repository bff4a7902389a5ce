"""Plain float32 reference of the benchmark's configurations' block.

Written from the published descriptions (Qwen3 technical report and
``modeling_qwen3``; Mixtral of Experts, arXiv:2401.04088, and
``modeling_mixtral``), in straightforward ``jax.numpy``: no kernel, no
cache, no batching, no paging. It covers what the benchmark's
configurations use and nothing else:

- RMSNorm (``x / sqrt(mean(x^2) + eps) * w``);
- grouped-query causal attention, ``head_dim`` given, scale
  ``head_dim ** -0.5``, no bias;
- Qwen3's per-head RMSNorm of q and k (weight of ``head_dim``) before rope;
- rotary embedding in the half-split ("rotate_half") pairing, base
  ``rope_theta``, no scaling;
- SwiGLU feed-forward ``down(silu(gate(x)) * up(x))``;
- Mixtral's experts: router logits, softmax over ALL experts in float32,
  top-k, renormalised to sum to 1, each chosen expert a SwiGLU;
- final RMSNorm and the output head (the embedding, transposed, when
  ``tie_word_embeddings``).

Departures from the published code, each deliberate: every matrix product
runs in float32 at ``jax.default_matmul_precision("highest")`` on the
bfloat16 weights as served (the checkpoint's dtype), where the published
code computes in bfloat16; experts are evaluated densely (every expert on
every token, weighted by 0 where not chosen), which is the same sum.

The weights are the engine's own arrays, used in place and one layer at a
time (layer ``l`` of each stacked weight, inside a loop over layers), so
the reference fits beside a model that fills the chip and runs unchanged
on parameters sharded over four chips.

TOLERANCE. The engine computes in bfloat16 (8 bits of mantissa) with
float32 norms, softmax and rope, and rounds its final logits to bfloat16
(``models/llama.py::_final_logits``). With random weights the logits are
of order 1 and the top few of order 4-5, where one bfloat16 step is
2**-5 = 0.031; the log-sum-exp over the vocabulary averages the rounding
of many terms and moves little. So a top log-probability differs from
the float32 reference by about one step of the logit's own rounding plus
the error accumulated through the layers. ``LOGPROB_TOL`` is set from
what the chip showed (PERF.md, Findings) at about twice the largest
difference seen, and far below what a lower precision would give: int8
or fp8 weights (3-4 bits of mantissa less) move these logits by several
tenths. A wrong mask, a missing norm, a wrong rope pairing or a wrong
expert moves them by whole units.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Largest allowed |engine top log-probability - reference log-probability|.
# Qwen3-4B on the chip: sound 0.030-0.052 (PR 23, five probe seeds), 0.037-0.060 (my
# chip run, PR 26, twelve seeds); the reference with every weight in fp8 (e4m3)
# 0.468-0.619, with one part alone in fp8 0.175 at the least (the tied embedding;
# rehearse/check_seeds.py --control fp8_weights, three seeds).
LOGPROB_TOL = 0.1

PAD_TO = 64  # every probe is padded to this length: one compiled shape

def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def _rope(x, positions, theta):
    """x: [T, H, hd]; pairs (i, i + hd/2) rotate by position * theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions[:, None].astype(jnp.float32) * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ _f32(w_gate)) * (h @ _f32(w_up))) @ _f32(w_down)


def _attn_half(x, lp, *, hd: int, eps: float, theta: float):
    """The layer's first half on x: [T, D] float32, positions 0..T-1,
    causal: norm, attention, residual add."""
    T = x.shape[0]
    pos = jnp.arange(T)
    h = _rms_norm(x, lp["attn_norm"], eps)
    q = (h @ _f32(lp["wq"])).reshape(T, -1, hd)
    k = (h @ _f32(lp["wk"])).reshape(T, -1, hd)
    v = (h @ _f32(lp["wv"])).reshape(T, -1, hd)
    if "q_norm" in lp:
        q = _rms_norm(q, lp["q_norm"], eps)
        k = _rms_norm(k, lp["k_norm"], eps)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) * hd**-0.5
    scores = jnp.where(pos[None, :, None] >= pos[None, None, :], scores, -jnp.inf)
    attn = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    return x + attn.reshape(T, -1) @ _f32(lp["wo"])


def _ffn_half(x, lp, *, eps: float, top_k: int):
    """The layer's second half on x: [T, D] float32: norm, SwiGLU or the
    routed experts, residual add. Returns the new x and, for a routed
    layer, the router's margin a row: the k-th best score less the
    (k+1)-th best (None for a dense layer)."""
    h = _rms_norm(x, lp["mlp_norm"], eps)
    if "router" not in lp:
        return x + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"]), None
    probs = jax.nn.softmax(h @ _f32(lp["router"]), axis=-1)  # [T, E]
    best = jax.lax.top_k(probs, top_k + 1)[0]
    top_p, top_i = jax.lax.top_k(probs, top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    n_experts = probs.shape[-1]
    weight = jnp.sum(
        jax.nn.one_hot(top_i, n_experts, dtype=jnp.float32) * top_p[..., None], axis=1
    )  # [T, E], 0 where the expert was not chosen
    y = jnp.zeros_like(x)
    for e in range(n_experts):
        y = y + weight[:, e:e + 1] * _swiglu(h, lp["w_gate"][e], lp["w_up"][e], lp["w_down"][e])
    return x + y, best[:, top_k - 1] - best[:, top_k]


def _layer(x, lp, *, hd: int, eps: float, theta: float, top_k: int):
    """One decoder layer on x: [T, D] float32, positions 0..T-1, causal."""
    x = _attn_half(x, lp, hd=hd, eps=eps, theta=theta)
    return _ffn_half(x, lp, eps=eps, top_k=top_k)[0]


def _log_softmax_head(x, final_norm, head_t, *, eps: float):
    """x: [N, D]; head_t: [V, D] (the output head, one row a token)."""
    logits = _rms_norm(x, final_norm, eps) @ _f32(head_t).T
    return jax.nn.log_softmax(logits, axis=-1)


def _head_t(params):
    return params["embed"] if "lm_head" not in params else params["lm_head"].T


def block_constants(hf: dict) -> dict:
    """The numbers the block needs, from the configuration's file."""
    known = {"qwen3", "mixtral"}
    if hf.get("model_type") not in known:
        raise ValueError(
            f"reference.py covers {sorted(known)}; a configuration of type "
            f"{hf.get('model_type')!r} brings its own reference"
        )
    if hf.get("rope_scaling") or hf.get("sliding_window"):
        raise ValueError("reference.py has no rope scaling and no sliding window")
    return {
        "hd": int(hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]),
        "eps": float(hf["rms_norm_eps"]),
        "theta": float(hf["rope_theta"]),
        "top_k": int(hf.get("num_experts_per_tok", 0)),
    }


def _layer_params(layers, l):
    return {k: jax.lax.dynamic_index_in_dim(v, l, 0, keepdims=False) for k, v in layers.items()}


@functools.partial(jax.jit, static_argnames=("hd", "eps", "theta", "top_k"))
def _forward(params, ids, rows, *, hd: int, eps: float, theta: float, top_k: int):
    """Embedding, then the layers one at a time (layer ``l`` of every
    stacked weight is sliced inside the loop, so only one layer is ever
    held in float32), then the head at positions ``rows``."""
    layers = params["layers"]
    n_layers = layers["wq"].shape[0]

    def body(l, x):
        return _layer(x, _layer_params(layers, l), hd=hd, eps=eps, theta=theta, top_k=top_k)

    x = jax.lax.fori_loop(0, n_layers, body, _f32(params["embed"][ids]))
    return _log_softmax_head(x[rows], params["final_norm"], _head_t(params), eps=eps)


def _padded(token_ids) -> list[int]:
    ids = list(token_ids)
    if len(ids) > PAD_TO:
        raise ValueError(f"probe of {len(ids)} tokens exceeds PAD_TO={PAD_TO}")
    return ids + [0] * (PAD_TO - len(ids))


def log_probs(params: dict, hf: dict, token_ids, rows) -> np.ndarray:
    """log-softmax over the vocabulary at positions ``rows`` of the
    sequence ``token_ids`` (padded to PAD_TO; padding follows the real
    tokens, so under the causal mask it changes nothing before it)."""
    c = block_constants(hf)
    with jax.default_matmul_precision("highest"):
        out = _forward(
            params, jnp.asarray(_padded(token_ids), jnp.int32),
            jnp.asarray(list(rows), jnp.int32), **c,
        )
    return np.asarray(out)


# What follows serves ``comparisons/routed_experts.py``: the hidden state
# where a layer and each of its halves begin, and each half on rows given.
@functools.partial(jax.jit, static_argnames=("hd", "eps", "theta", "top_k", "at"))
def _hidden_states(params, ids, *, hd, eps, theta, top_k, at: tuple):
    layers = params["layers"]
    n_layers = layers["wq"].shape[0]
    where = jnp.asarray(at, jnp.int32)

    def body(l, carry):
        x, entering, before_ffn = carry
        lp = _layer_params(layers, l)
        mid = _attn_half(x, lp, hd=hd, eps=eps, theta=theta)
        here = (where == l)[:, None, None]
        entering = jnp.where(here, x[None], entering)
        before_ffn = jnp.where(here, mid[None], before_ffn)
        return _ffn_half(mid, lp, eps=eps, top_k=top_k)[0], entering, before_ffn

    x0 = _f32(params["embed"][ids])
    kept0 = jnp.zeros((len(at),) + x0.shape, jnp.float32)
    return jax.lax.fori_loop(0, n_layers, body, (x0, kept0, kept0))[1:]


def hidden_states(params: dict, hf: dict, token_ids, layers) -> tuple[np.ndarray, np.ndarray]:
    """For the sequence ``token_ids``, the float32 hidden state entering
    each layer in ``layers`` and the one entering that layer's second
    half (norm, feed-forward, residual): two arrays
    [len(layers), len(token_ids), D]."""
    c = block_constants(hf)
    n = len(list(token_ids))
    with jax.default_matmul_precision("highest"):
        entering, before_ffn = _hidden_states(
            params, jnp.asarray(_padded(token_ids), jnp.int32),
            at=tuple(int(l) for l in layers), **c,
        )
    return np.asarray(entering)[:, :n], np.asarray(before_ffn)[:, :n]


@functools.partial(jax.jit, static_argnames=("hd", "eps", "theta"))
def _attn_half_and_head_of(params, x, layer, *, hd, eps, theta):
    mid = _attn_half(_f32(x), _layer_params(params["layers"], layer), hd=hd, eps=eps, theta=theta)
    return _log_softmax_head(mid, params["final_norm"], _head_t(params), eps=eps)


def attn_half_and_head(params: dict, hf: dict, layer: int, x) -> np.ndarray:
    """The first half of layer ``layer`` (norm, attention, residual) on
    ``x`` ([T, D], any float type, taken as it is: one sequence at
    positions 0..T-1, causal), then the final norm and the head:
    log-softmax over the vocabulary, float32 [T, V]."""
    c = block_constants(hf)
    with jax.default_matmul_precision("highest"):
        out = _attn_half_and_head_of(
            params, x, int(layer), hd=c["hd"], eps=c["eps"], theta=c["theta"]
        )
    return np.asarray(out)


@functools.partial(jax.jit, static_argnames=("eps", "top_k"))
def _ffn_half_of(layers, x, layer, *, eps, top_k):
    return _ffn_half(_f32(x), _layer_params(layers, layer), eps=eps, top_k=top_k)


def ffn_half(params: dict, hf: dict, layer: int, x):
    """The second half of layer ``layer`` on the rows ``x`` ([N, D], any
    float type, taken as it is): (the rows after the residual add,
    float32 [N, D]; the router's margin a row, or None for a dense
    layer)."""
    c = block_constants(hf)
    with jax.default_matmul_precision("highest"):
        y, margin = _ffn_half_of(
            params["layers"], x, int(layer), eps=c["eps"], top_k=c["top_k"]
        )
    return np.asarray(y), (None if margin is None else np.asarray(margin))
