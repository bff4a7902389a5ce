"""Plain float32 reference of LiquidAI's ``lfm2_moe`` (LFM2-8B-A1B).

Written from the published description (the model card and
``modeling_lfm2_moe`` of ``huggingface.co/LiquidAI/LFM2-8B-A1B``), in
straightforward ``jax.numpy``: no kernel, no cache, no state carried from
call to call, no batching, no paging. One sequence, positions 0..T-1.
``u`` is a half-layer's normed input, ``D`` the hidden size:

- layer ``i``: ``r = h + Op_i(RMSNorm(h))``, ``h' = r + FF_i(RMSNorm(r))``;
  ``Op_i`` is the short convolution or attention by ``layer_types[i]``,
  ``FF_i`` a dense SwiGLU for ``i < num_dense_layers`` and the routed
  experts otherwise; logits ``RMSNorm(h_L) @ embed.T``;
- short convolution: ``[B, C, X] = split3(u @ W_in)`` (D -> 3D);
  ``z_t = B_t * X_t``; ``c_t = sum_{j<K} w_j * z_{t-(K-1)+j}`` (depthwise,
  causal, one weight a channel and tap, zeros before the sequence's
  start; ``K = conv_L_cache``); ``y_t = (C_t * c_t) @ W_out``;
- attention: q, k, v without bias; RMSNorm (weight of ``head_dim``) over
  each head of q and k; rotary in the half-split pairing, base
  ``rope_theta``; causal ``softmax(q k^T / sqrt(head_dim)) v``; ``W_o``;
- routed experts: ``s = sigmoid(u @ W_r)`` in float32; the experts are the
  top-k of ``s + b`` (``use_expert_bias``) and their weights ``s`` there
  (the bias chooses, the unbiased score weighs); with ``norm_topk_prob``
  ``w / (sum(w) + 1e-6)``; times ``routed_scaling_factor``; each chosen
  expert a SwiGLU ``W2(silu(W1 u) * W3 u)``.

Departures from the published code, each deliberate: every matrix product
runs in float32 at ``jax.default_matmul_precision("highest")`` on the
bfloat16 weights as served, where the published code computes in
bfloat16; experts are evaluated densely (every expert on every token,
weighted by 0 where not chosen), which is the same sum. ASSUMED, because
the catalog's row of the published ``config.json`` lacks both keys: the
output head is the embedding (the family's ``tie_embedding`` default) and
the convolution runs over all ``hidden_size`` channels (``conv_dim``).

The weights are the engine's own arrays, stacked by the kind of layer
that has them (``models/llama.py::init_params``: attention's over the
attention layers, ``conv_in``/``conv_w``/``conv_out`` over the
convolution layers, ``dense_*`` over the dense layers, the router, its
bias and the experts over the routed layers), used in place a layer and
an expert at a time, so the reference fits beside a model that fills the
chip. Which stack and which index a layer reads is worked out here, from
``layer_types`` and ``num_dense_layers`` alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PAD_TO = 64  # every probe is padded to this length: one compiled shape
# What ``comparisons/logprob_walk.py`` would hold this reference to. No
# configuration asks for that: a routed model's walk cannot hold the
# precision (``comparisons/layer_kinds.py``, which lfm2-8b-a1b-l16 names
# and which brings its own limits); this is that module's MEDIAN_TOL.
LOGPROB_TOL = 0.5


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


def _attention(h, lp, *, hd: int, eps: float, theta: float):
    T = h.shape[0]
    pos = jnp.arange(T)
    q = _rms_norm((h @ _f32(lp["wq"])).reshape(T, -1, hd), lp["q_norm"], eps)
    k = _rms_norm((h @ _f32(lp["wk"])).reshape(T, -1, hd), lp["k_norm"], eps)
    v = (h @ _f32(lp["wv"])).reshape(T, -1, hd)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) * hd**-0.5
    scores = jnp.where(pos[None, :, None] >= pos[None, None, :], scores, -jnp.inf)
    attn = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    return attn.reshape(T, -1) @ _f32(lp["wo"])


def _causal_conv(z, taps):
    """``c_t = sum_j taps[j] * z_{t-(K-1)+j}``; z: [T, D], taps: [K, D]."""
    K, T = taps.shape[0], z.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, z.shape[1]), z.dtype), z])
    return sum(taps[j] * padded[j:j + T] for j in range(K))


def _short_conv(h, lp):
    gate_b, gate_c, x = jnp.split(h @ _f32(lp["conv_in"]), 3, axis=-1)
    return (gate_c * _causal_conv(gate_b * x, _f32(lp["conv_w"]))) @ _f32(lp["conv_out"])


def _route(s, bias, *, top_k: int, norm: bool, scale: float):
    """``s``: [T, E] sigmoid scores. Returns the weight of every expert
    ([T, E], 0 where not chosen) and the margin a row: the k-th best of
    ``s + bias`` less the (k+1)-th."""
    chosen_by = s + bias
    best = jax.lax.top_k(chosen_by, top_k + 1)[0]
    top_i = jax.lax.top_k(chosen_by, top_k)[1]
    w = jnp.take_along_axis(s, top_i, axis=-1)
    if norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    w = w * scale
    weight = jnp.sum(jax.nn.one_hot(top_i, s.shape[-1], dtype=jnp.float32) * w[..., None], axis=1)
    return weight, best[:, top_k - 1] - best[:, top_k]


def _op_half(x, lp, c):
    """The layer's first half on x: [T, D] float32: norm, the operator the
    layer's parameters are of, residual add."""
    h = _rms_norm(x, lp["attn_norm"], c["eps"])
    if "conv_in" in lp:
        return x + _short_conv(h, lp)
    return x + _attention(h, lp, hd=c["hd"], eps=c["eps"], theta=c["theta"])


def _ffn_half(x, lp, c):
    """The layer's second half on x: [T, D] float32: norm, SwiGLU or the
    routed experts, residual add. Returns the new x and, for a routed
    layer, the router's margin a row (None for a dense layer). The
    experts are taken out of the stacks one at a time, inside a loop, so
    that one expert's matrices are held in float32 and no more."""
    h = _rms_norm(x, lp["mlp_norm"], c["eps"])
    if "router" not in lp:
        return x + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"]), None
    s = jax.nn.sigmoid(h @ _f32(lp["router"]))
    bias = _f32(lp["router_bias"]) if "router_bias" in lp else 0.0
    weight, margin = _route(s, bias, top_k=c["top_k"], norm=c["norm"], scale=c["scale"])
    stacks, nth = lp["experts"]  # the routed layers' [L_routed, E, ..] stacks, this layer's index

    def add_expert(e, y):
        w = [
            jax.lax.dynamic_slice(m, (nth, e, 0, 0), (1, 1, *m.shape[2:]))[0, 0] for m in stacks
        ]
        return y + jnp.take(weight, e, axis=1)[:, None] * _swiglu(h, *w)

    y = jax.lax.fori_loop(0, s.shape[-1], add_expert, jnp.zeros_like(x))
    return x + y, margin


def _log_softmax_head(x, params, eps):
    logits = _rms_norm(x, params["final_norm"], eps) @ _f32(params["embed"]).T
    return jax.nn.log_softmax(logits, axis=-1)


def block_constants(hf: dict) -> dict:
    """The numbers the block needs, from the configuration's file."""
    if hf.get("model_type") != "lfm2_moe":
        raise ValueError(f"references/lfm2_moe.py covers lfm2_moe, not {hf.get('model_type')!r}")
    if hf.get("conv_bias"):
        raise ValueError("references/lfm2_moe.py has no convolution bias")
    kinds = tuple(
        ("conv" if t == "conv" else "attn", "dense" if i < hf["num_dense_layers"] else "routed")
        for i, t in enumerate(hf["layer_types"])
    )
    if len(kinds) != hf["num_hidden_layers"]:
        raise ValueError("layer_types does not name num_hidden_layers layers")
    return {
        "hd": int(hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]),
        "eps": float(hf["norm_eps"]),
        "theta": float(hf["rope_theta"]),
        "top_k": int(hf["num_experts_per_tok"]),
        "norm": bool(hf["norm_topk_prob"]),
        "scale": float(hf["routed_scaling_factor"]),
        "bias": bool(hf["use_expert_bias"]),
        "kinds": kinds,
    }


_OP_KEYS = {"attn": ("wq", "wk", "wv", "wo", "q_norm", "k_norm"), "conv": ("conv_in", "conv_w", "conv_out")}
_DENSE = {"w_gate": "dense_gate", "w_up": "dense_up", "w_down": "dense_down"}
_EXPERTS = ("w_gate", "w_up", "w_down")


def _where(kinds, i: int) -> tuple[int, int]:
    """Layer ``i``'s ordinal among the layers of its operator kind and of
    its feed-forward kind: where its parameters lie in their stacks."""
    op, ff = kinds[i]
    return sum(1 for k in kinds[:i] if k[0] == op), sum(1 for k in kinds[:i] if k[1] == ff)


def _layer_params(layers, op: str, ff: str, i, nth_op, nth_ff) -> dict:
    """One layer's parameters out of the stacks (the indices may be
    traced): the norms at ``i``, the operator's at ``nth_op``, the
    feed-forward's at ``nth_ff``; the experts stay in their stacks."""
    def at(name, n):
        return jax.lax.dynamic_index_in_dim(layers[name], n, 0, keepdims=False)

    lp = {"attn_norm": at("attn_norm", i), "mlp_norm": at("mlp_norm", i)}
    lp.update((k, at(k, nth_op)) for k in _OP_KEYS[op])
    if ff == "dense":
        lp.update((k, at(d, nth_ff)) for k, d in _DENSE.items())
    else:
        lp["router"] = at("router", nth_ff)
        if "router_bias" in layers:
            lp["router_bias"] = at("router_bias", nth_ff)
        lp["experts"] = (tuple(layers[k] for k in _EXPERTS), nth_ff)
    return lp


def _static(c: dict):
    return tuple(sorted(c.items()))


# One compiled program a kind of layer and a half, the layer's indices
# traced: a program that held every layer's slices at once did not fit
# beside the weights (18.6 of 15.75 GB; my chip run, PR 31).
@functools.partial(jax.jit, static_argnames=("c", "op", "ff"))
def _op_half_of(layers, x, i, nth_op, *, c, op, ff):
    return _op_half(x, _layer_params(layers, op, "dense", i, nth_op, 0), dict(c))


@functools.partial(jax.jit, static_argnames=("c", "op", "ff"))
def _ffn_half_of(layers, x, i, nth_ff, *, c, op, ff):
    lp = _layer_params(layers, op, ff, i, 0, nth_ff)
    return _ffn_half(_f32(x), lp, dict(c))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_of(params, x, *, eps):
    return _log_softmax_head(_f32(x), params, eps)


def _forward(params, ids, rows, c: dict, at: tuple = ()):
    """Embedding, the layers one after another, the head at ``rows``; and
    the hidden state entering each layer in ``at`` and that layer's
    second half."""
    x = _f32(params["embed"][ids])
    entering, before_ffn = [], []
    for i, (op, ff) in enumerate(c["kinds"]):
        nth_op, nth_ff = _where(c["kinds"], i)
        mid = _op_half_of(params["layers"], x, i, nth_op, c=_static(c), op=op, ff=ff)
        if i in at:
            entering.append(x)
            before_ffn.append(mid)
        x = _ffn_half_of(params["layers"], mid, i, nth_ff, c=_static(c), op=op, ff=ff)[0]
    return _head_of(params, x[rows], eps=c["eps"]), entering, before_ffn


def _padded(token_ids) -> list[int]:
    ids = list(token_ids)
    if len(ids) > PAD_TO:
        raise ValueError(f"probe of {len(ids)} tokens exceeds PAD_TO={PAD_TO}")
    return ids + [0] * (PAD_TO - len(ids))


def log_probs(params: dict, hf: dict, token_ids, rows) -> np.ndarray:
    """log-softmax over the vocabulary at positions ``rows`` of the
    sequence ``token_ids`` (padded to PAD_TO; padding follows the real
    tokens, so under the causal mask and the causal convolution it
    changes nothing before it)."""
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


def op_half_and_head(params: dict, hf: dict, layer: int, x) -> np.ndarray:
    """The first half of layer ``layer`` (norm, its operator, residual) on
    ``x`` ([T, D], any float type, taken as it is: one sequence at
    positions 0..T-1), then the final norm and the head: log-softmax over
    the vocabulary, float32 [T, V]."""
    c = block_constants(hf)
    op, ff = c["kinds"][int(layer)]
    with jax.default_matmul_precision("highest"):
        mid = _op_half_of(
            params["layers"], _f32(jnp.asarray(x)), int(layer), _where(c["kinds"], int(layer))[0],
            c=_static(c), op=op, ff=ff,
        )
        out = _head_of(params, mid, eps=c["eps"])
    return np.asarray(out)


def ffn_half(params: dict, hf: dict, layer: int, x):
    """The second half of layer ``layer`` on the rows ``x`` ([N, D], any
    float type, taken as it is): (the rows after the residual add,
    float32 [N, D]; the router's margin a row, or None for a dense
    layer)."""
    c = block_constants(hf)
    op, ff = c["kinds"][int(layer)]
    with jax.default_matmul_precision("highest"):
        y, margin = _ffn_half_of(
            params["layers"], jnp.asarray(x), int(layer), _where(c["kinds"], int(layer))[1],
            c=_static(c), op=op, ff=ff,
        )
    return np.asarray(y), (None if margin is None else np.asarray(margin))
