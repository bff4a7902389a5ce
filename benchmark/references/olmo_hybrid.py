"""Plain float32 reference of Ai2's ``olmo_hybrid`` (Olmo-Hybrid-7B).

Written from the published description (the ``config.json`` named in the
configuration's file, whose ``linear_*`` keys are Qwen3-Next's spelling of
a Gated DeltaNet layer, arXiv:2412.06464, with the negative eigenvalues of
arXiv:2411.12537 that ``linear_allow_neg_eigval`` names as
flash-linear-attention's ``GatedDeltaNet`` does), in straightforward
``jax.numpy``: no kernel, no cache, no state carried from call to call, no
batching, no chunking, no paging. One sequence, positions 0..T-1. ``x`` is
the residual stream, ``D`` the hidden size, ``H`` heads of ``dk`` by ``dv``
in a linear layer, ``K = linear_conv_kernel_dim``, ``RMS_w`` an RMS norm
with a learned scale and ``rms_norm_eps``:

- every layer (Olmo 2's block): ``h = x + RMS(Mix_i(x))``,
  ``y = h + RMS(W_down(silu(W_gate h) * W_up h))``: each half reads the
  stream un-normed and its *output* is normed before the add; logits
  ``RMS(x_L) W_head`` (untied);
- full attention (``layer_types[i] == "full_attention"``):
  ``q = RMS_wq(W_q x)``, ``k = RMS_wk(W_k x)``, each norm over the whole
  projection; ``v = W_v x``; ``num_attention_heads`` heads of ``head_dim``,
  ``num_key_value_heads`` KV heads; **no rotary or other positional term**;
  causal ``softmax(q k^T / sqrt(head_dim)) v``; ``W_o``;
- linear attention (``"linear_attention"``): ``z^q = W_q x``,
  ``z^k = W_k x`` (``H dk`` each), ``z^v = W_v x`` (``H dv``); each through
  its own depthwise causal convolution of ``K`` taps, no bias, then SiLU:
  ``c_t = silu(sum_j w_j * z_{t-(K-1)+j})``, zeros before the start; a
  head: ``q_t = c^q / |c^q| / sqrt(dk)``, ``k_t = c^k / |c^k|`` (1e-6 under
  the root), ``v_t = c^v``; ``beta_t = sigmoid(W_b x)``, doubled where
  ``linear_allow_neg_eigval``; ``g_t = -exp(A_log) softplus(W_a x +
  dt_bias)``, ``a_t = exp(g_t)``; the state ``S`` in ``R^{dk x dv}``,
  zeros at position 0:
  ``S_t = a_t S_{t-1} + beta_t k_t (v_t - a_t S_{t-1}^T k_t)^T``,
  ``o_t = S_t^T q_t``, a ``lax.scan`` a token at a time;
  ``Mix = W_o(RMS_{w,dv}(o_t) * silu(W_g x))``, the norm a head at a time
  with one learned scale of ``dv``.

Departures from the published code, each deliberate: every matrix product
runs in float32 at ``jax.default_matmul_precision("highest")`` on the
bfloat16 weights as served, where the published code computes in bfloat16
(its state alone in float32); the recurrence is the token recurrence
itself and not the published kernel's chunked form (the same numbers).
ASSUMED, because the catalog's row has no key for them (the
configuration's file lists each under ``assumed``): Olmo 2's norm
placement and its q/k norms over the whole projection; no positional term
(``rope_theta: null``); the mixer's structure from the ``linear_*`` keys
(separate convolutions without bias, L2-normed q and k, the gated output
norm a head); ``head_dim = hidden_size / num_attention_heads``.

The weights are the engine's own arrays, stacked by the kind of layer
that has them (``models/llama.py::init_params``: attention's over the
attention layers, ``gdn_*`` over the linear layers with q, k and v's
projections and taps side by side, the output norms and the MLP over
all), used in place a layer at a time, so the reference fits beside a
model that fills the chip. Which stack and which index a layer reads is
worked out here, from ``layer_types`` alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PAD_TO = 64  # a probe is padded to a multiple of this: few compiled shapes
# What ``comparisons/logprob_walk.py`` would hold this reference to. The
# configuration names ``comparisons/linear_attention.py``, which brings its
# own limits; this is that module's WALK_TOL.
LOGPROB_TOL = 0.3

_EVERY = ("post_attn_norm", "post_ffn_norm", "w_gate", "w_up", "w_down")
_ATTN = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
_LINEAR = (
    "gdn_qkv", "gdn_conv_w", "gdn_ab", "gdn_A_log", "gdn_dt_b", "gdn_gate",
    "gdn_norm", "gdn_out",
)


def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def _unit(x):
    """Each head's vector (the last axis) to length 1."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _out_gate(x):
    """The output's gate of a linear-attention layer."""
    return jax.nn.silu(x)


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ _f32(w_gate)) * (h @ _f32(w_up))) @ _f32(w_down)


def _attention(x, lp, *, hd: int, eps: float):
    """Causal attention on x: [T, D], no positional term, q and k normed
    over the whole projection."""
    T = x.shape[0]
    pos = jnp.arange(T)
    q = _rms_norm(x @ _f32(lp["wq"]), lp["q_norm"], eps).reshape(T, -1, hd)
    k = _rms_norm(x @ _f32(lp["wk"]), lp["k_norm"], eps).reshape(T, -1, hd)
    v = (x @ _f32(lp["wv"])).reshape(T, -1, hd)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) * hd**-0.5
    scores = jnp.where(pos[None, :, None] >= pos[None, None, :], scores, -jnp.inf)
    attn = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    return attn.reshape(T, -1) @ _f32(lp["wo"])


def _causal_conv(z, taps):
    """``sum_j taps[j] * z_{t-(K-1)+j}`` over z: [T, C], zeros before the
    start; taps [K, C], the last weighs ``z_t``."""
    K, T = taps.shape[0], z.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, z.shape[1]), z.dtype), z])
    return sum(taps[j] * padded[j : j + T] for j in range(K))


def _delta_rule(q, k, v, g, beta):
    """The gated delta rule a token at a time: q, k [T, H, dk]; v
    [T, H, dv]; g, beta [T, H]. ``S`` is [H, dk, dv] float32. Returns
    (o [T, H, dv], the state after the last token)."""
    def step(s, t):
        q_t, k_t, v_t, g_t, b_t = t
        s = jnp.exp(g_t)[:, None, None] * s
        err = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * err[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    zero = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    last, o = jax.lax.scan(step, zero, (q, k, v, g, beta))
    return o, last


def _linear_attention(x, lp, *, eps: float, heads: int, dk: int, dv: int, neg: bool):
    """The linear-attention mixer on x: [T, D] (module docstring): (its
    output [T, D], the state [H, dk, dv] after the last token)."""
    T = x.shape[0]
    c = jax.nn.silu(_causal_conv(x @ _f32(lp["gdn_qkv"]), _f32(lp["gdn_conv_w"])))
    q = _unit(c[:, : heads * dk].reshape(T, heads, dk)) * dk**-0.5
    k = _unit(c[:, heads * dk : 2 * heads * dk].reshape(T, heads, dk))
    v = c[:, 2 * heads * dk :].reshape(T, heads, dv)
    ab = x @ _f32(lp["gdn_ab"])
    g = -jnp.exp(_f32(lp["gdn_A_log"])) * jax.nn.softplus(ab[:, :heads] + _f32(lp["gdn_dt_b"]))
    beta = jax.nn.sigmoid(ab[:, heads:]) * (2.0 if neg else 1.0)
    o, last = _delta_rule(q, k, v, g, beta)
    o = _rms_norm(o, lp["gdn_norm"], eps).reshape(T, heads * dv)
    return (o * _out_gate(x @ _f32(lp["gdn_gate"]))) @ _f32(lp["gdn_out"]), last


def _mix(x, lp, c: dict, op: str):
    if op == "attn":
        return _attention(x, lp, hd=c["hd"], eps=c["eps"]), None
    return _linear_attention(
        x, lp, eps=c["eps"], heads=c["heads"], dk=c["dk"], dv=c["dv"], neg=c["neg"]
    )


def _op_half(x, lp, c: dict, op: str):
    return x + _rms_norm(_mix(x, lp, c, op)[0], lp["post_attn_norm"], c["eps"])


def _ffn_half(x, lp, c: dict):
    return x + _rms_norm(
        _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"]), lp["post_ffn_norm"], c["eps"]
    )


def _log_softmax_head(x, params, eps):
    logits = _rms_norm(x, params["final_norm"], eps) @ _f32(params["lm_head"])
    return jax.nn.log_softmax(logits, axis=-1)


def block_constants(hf: dict) -> dict:
    """The numbers the block needs, from the configuration's file."""
    if hf.get("model_type") != "olmo_hybrid":
        raise ValueError(
            f"references/olmo_hybrid.py covers olmo_hybrid, not {hf.get('model_type')!r}"
        )
    rope = hf.get("rope_parameters") or {}
    if hf.get("rope_theta", rope.get("rope_theta")) is not None:
        raise ValueError("references/olmo_hybrid.py has no positional term")
    if hf.get("tie_word_embeddings", False):
        raise ValueError("references/olmo_hybrid.py has an untied head")
    if hf["linear_num_key_heads"] != hf["linear_num_value_heads"]:
        raise ValueError("references/olmo_hybrid.py has one key head a value head")
    names = {"linear_attention": "linear", "full_attention": "attn"}
    return {
        "hd": int(hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]),
        "eps": float(hf["rms_norm_eps"]),
        "heads": int(hf["linear_num_value_heads"]),
        "dk": int(hf["linear_key_head_dim"]),
        "dv": int(hf["linear_value_head_dim"]),
        "neg": bool(hf.get("linear_allow_neg_eigval", False)),
        "kinds": tuple(names[t] for t in hf["layer_types"]),
    }


def _where(kinds, i: int) -> int:
    """Layer ``i``'s ordinal among the layers of its operator kind: where
    its operator's parameters lie in their stack."""
    return sum(1 for k in kinds[:i] if k == kinds[i])


def _layer_params(layers, op: str, i, nth) -> dict:
    """One layer's parameters out of the stacks (the indices may be
    traced): the norms and the MLP at ``i``, the operator's at ``nth``."""
    def at(name, n):
        return jax.lax.dynamic_index_in_dim(layers[name], n, 0, keepdims=False)

    lp = {k: at(k, i) for k in _EVERY}
    lp.update((k, at(k, nth)) for k in (_ATTN if op == "attn" else _LINEAR))
    return lp


def _static(c: dict):
    return tuple(sorted(c.items()))


# One compiled program a kind of layer and a half, the layer's indices
# traced, so that the whole stack's slices are never held at once.
@functools.partial(jax.jit, static_argnames=("c", "op"))
def _op_half_of(layers, x, i, nth, *, c, op):
    return _op_half(_f32(x), _layer_params(layers, op, i, nth), dict(c), op)


@functools.partial(jax.jit, static_argnames=("c",))
def _linear_state_of(layers, x, i, nth, *, c):
    c = dict(c)
    return _mix(_f32(x), _layer_params(layers, "linear", i, nth), c, "linear")[1]


@functools.partial(jax.jit, static_argnames=("c", "op"))
def _ffn_half_of(layers, x, i, *, c, op):
    return _ffn_half(_f32(x), _layer_params(layers, op, i, 0), dict(c))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_of(params, x, *, eps):
    return _log_softmax_head(_f32(x), params, eps)


def _forward(params, ids, rows, c: dict, at: tuple = ()):
    """Embedding, the layers one after another, the head at ``rows``; and
    the hidden state entering each layer in ``at`` and that layer's
    second half."""
    x = _f32(params["embed"][ids])
    entering, before_ffn = [], []
    for i, op in enumerate(c["kinds"]):
        mid = _op_half_of(params["layers"], x, i, _where(c["kinds"], i), c=_static(c), op=op)
        if i in at:
            entering.append(x)
            before_ffn.append(mid)
        x = _ffn_half_of(params["layers"], mid, i, c=_static(c), op=op)
    return _head_of(params, x[rows], eps=c["eps"]), entering, before_ffn


def _padded(token_ids) -> list[int]:
    ids = list(token_ids)
    return ids + [0] * (-len(ids) % PAD_TO)


def log_probs(params: dict, hf: dict, token_ids, rows) -> np.ndarray:
    """log-softmax over the vocabulary at positions ``rows`` of the
    sequence ``token_ids`` (padded to a multiple of PAD_TO; padding
    follows the real tokens, so under the causal mask, the causal
    convolutions and the recurrence it changes nothing before it)."""
    with jax.default_matmul_precision("highest"):
        out = _forward(
            params, jnp.asarray(_padded(token_ids), jnp.int32),
            jnp.asarray(list(rows), jnp.int32), block_constants(hf),
        )[0]
    return np.asarray(out)


def hidden_states(params: dict, hf: dict, token_ids, layers) -> tuple[np.ndarray, np.ndarray]:
    """For the sequence ``token_ids``, the float32 hidden state entering
    each layer in ``layers`` (ascending) and the one entering that layer's
    second half: two arrays [len(layers), len(token_ids), D]."""
    n = len(list(token_ids))
    with jax.default_matmul_precision("highest"):
        _, entering, before_ffn = _forward(
            params, jnp.asarray(_padded(token_ids), jnp.int32), jnp.zeros((1,), jnp.int32),
            block_constants(hf), at=tuple(int(l) for l in layers),
        )
    return np.stack([np.asarray(e)[:n] for e in entering]), np.stack([np.asarray(b)[:n] for b in before_ffn])


def op_half_and_head(params: dict, hf: dict, layer: int, x, rows=None) -> np.ndarray:
    """The first half of layer ``layer`` (its operator, the output's norm,
    residual) on ``x`` ([T, D], any float type, taken as it is: one
    sequence at positions 0..T-1), then the final norm and the head at
    ``rows`` (all where None): log-softmax over the vocabulary, float32
    [len(rows), V]."""
    c = block_constants(hf)
    op = c["kinds"][int(layer)]
    with jax.default_matmul_precision("highest"):
        mid = _op_half_of(
            params["layers"], _f32(jnp.asarray(x)), int(layer), _where(c["kinds"], int(layer)),
            c=_static(c), op=op,
        )
        if rows is not None:
            mid = mid[jnp.asarray(list(rows), jnp.int32)]
        out = _head_of(params, mid, eps=c["eps"])
    return np.asarray(out)


def linear_state(params: dict, hf: dict, layer: int, x) -> np.ndarray:
    """The delta rule's state ``[H, dk, dv]`` (float32) of linear layer
    ``layer`` after the last token of ``x`` ([T, D]: one sequence at
    positions 0..T-1 entering the layer): what a sequence carries on from
    there. (The engine's pool holds it ``[dk, H * dv]``.)"""
    c = block_constants(hf)
    if c["kinds"][int(layer)] != "linear":
        raise ValueError(f"layer {layer} is not a linear-attention layer")
    with jax.default_matmul_precision("highest"):
        out = _linear_state_of(
            params["layers"], jnp.asarray(x), int(layer), _where(c["kinds"], int(layer)),
            c=_static(c),
        )
    return np.asarray(out)
