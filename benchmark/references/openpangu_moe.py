"""Plain float32 reference of ``pangu_ultra_moe`` (openPangu-Ultra-MoE-718B).

Written from the published ``config.json``'s keys and the family's
description (multi-head latent attention as DeepSeek-V2/V3 define it, whose
key names the row carries; sigmoid-routed experts beside a shared expert;
``sandwich_norm``), in straightforward ``jax.numpy``: no kernel, no cache,
no paging, no batching, no absorbed form. One sequence, positions 0..T-1.
``x`` the residual stream, ``RMS_w`` an RMS norm with scale ``w``, eps
``rms_norm_eps``:

- a layer: ``a = RMS_post_attn(Attn(RMS_input(x)))``, ``h = x + a``,
  ``f = RMS_post_mlp(FF(RMS_pre_mlp(h)))``, ``y = h + f`` (four norms a
  layer); logits ``RMS_final(x_L) @ lm_head``;
- ``Attn(u)``, ``H`` heads, a token at position ``t``:
  ``c_q = RMS_qa(u W_dq)``; ``q = c_q W_uq`` -> a head ``[q_nope | q_rope]``;
  ``[c_kv | k_r] = u W_dkv``; ``c_kv = RMS_kva(c_kv)``; ``k_r = RoPE_t(k_r)``
  (one rotary key for every head), ``q_rope = RoPE_t(q_rope)``;
  ``[k_nope_h | v_h] = c_kv W_ukv`` a head; scores
  ``(q_nope_h . k_nope_h(j) + q_rope_h . k_r(j)) / sqrt(nope + rope)`` for
  ``j <= t``; ``o_h = sum_j softmax_j v_h(j)``; ``concat_h(o_h) W_o``: the
  EXPANDED form, every head's K and V built from the latents;
- ``FF`` of a routed layer: ``s = sigmoid(u W_r)`` over all
  ``n_routed_experts_global`` experts; the experts are the top
  ``num_experts_per_tok`` of ``s``; ``w_k = s_k / (sum of the chosen +
  1e-20) * routed_scaling_factor``; ``FF(u) = SwiGLU_shared(u) + sum_k w_k
  SwiGLU_{e_k}(u)``; of the first ``first_k_dense_replace`` layers one
  SwiGLU of ``intermediate_size``.

THE SHARE. The configuration is one rank's share of an expert-parallel
layer: the arrays hold experts ``[first_routed_expert, first_routed_expert
+ n_routed_experts)`` of the ``n_routed_experts_global`` the router scores,
and the sum over the chosen runs over those of them that are held, each
with its weight as normalised over ALL the chosen (what this rank would
hand to the exchange; the shared expert is added once). With every expert
held that is the whole layer. The vocabulary is the slice the arrays hold.

Departures from the published code, each deliberate: every matrix product
runs in float32 at ``jax.default_matmul_precision("highest")`` on the
bfloat16 weights as served, where the published code computes in bfloat16;
the held experts are evaluated densely (every held expert on every token,
weighted by 0 where not chosen), which is the same sum; the long-sequence
entry computes attention for the asked rows alone, a block of heads at a
time, which is the same attention. ASSUMED, because the catalog's row has
no key for them (``benchmark/configs/openpangu-*.json``, ``assumed``):
sigmoid scores with no selection bias and no group limit; the rotary
pairing half-split (``(i, i + d/2)``) with no scaling; ``W_ukv``'s columns
a head ``[k_nope | v]``, ``W_uq``'s ``[q_nope | q_rope]``, ``W_dkv``'s
``[c_kv | k_r]``.

The weights are the engine's own arrays (``models/llama.py::init_params``:
the four norms over all layers, latent attention's seven tensors over the
attention layers, ``dense_*`` over the dense layers, the router, the shared
expert and the held experts over the routed layers), used in place a layer
and an expert at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PAD_TO = 64  # every probe is padded to this length: one compiled shape
# What ``comparisons/logprob_walk.py`` would hold this reference to; no
# configuration asks for that (a routed model's walk cannot hold the
# precision: ``comparisons/latent_attention.py`` brings its own limits).
LOGPROB_TOL = 0.5
HEAD_BLOCK = 16  # heads expanded at a time by ``attn_rows_and_head``


def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def _rope(x, positions, theta):
    """x: [T, ..., d]; pairs (i, i + d/2) rotate by position * theta^(-2i/d)."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    ang = ang.reshape(ang.shape[0], *(1,) * (x.ndim - 2), d // 2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ _f32(w_gate)) * (h @ _f32(w_up))) @ _f32(w_down)


def _latents(u, lp, c, positions):
    """What a token leaves behind: ``c_kv`` after its norm [T, r] and
    ``k_r`` after RoPE [T, rope]."""
    ckr = u @ _f32(lp["w_dkv"])
    c_kv = _rms_norm(ckr[:, : c["r"]], lp["kv_a_norm"], c["eps"])
    return c_kv, _rope(ckr[:, c["r"]:], positions, c["theta"])


def _queries(u, lp, c, positions):
    """[T, H, nope] and [T, H, rope] (after RoPE)."""
    c_q = _rms_norm(u @ _f32(lp["w_dq"]), lp["q_a_norm"], c["eps"])
    q = (c_q @ _f32(lp["w_uq"])).reshape(u.shape[0], -1, c["nope"] + c["rope"])
    return q[..., : c["nope"]], _rope(q[..., c["nope"]:], positions, c["theta"])


def _attend(q_nope, q_rope, q_pos, c_kv, k_r, w_ukv, c):
    """The expanded form for queries at ``q_pos`` over the whole sequence's
    latents: [len(q_pos), H * v]. ``w_ukv`` [r, H', nope + v] float32: the
    heads ``q_nope``/``q_rope`` are of."""
    kv = jnp.einsum("tc,chd->thd", c_kv, w_ukv)  # [T, H', nope + v]
    k_nope, v = kv[..., : c["nope"]], kv[..., c["nope"]:]
    scores = jnp.einsum("qhd,thd->hqt", q_nope, k_nope) + jnp.einsum("qhd,td->hqt", q_rope, k_r)
    scores = scores * (c["nope"] + c["rope"]) ** -0.5
    seen = q_pos[None, :, None] >= jnp.arange(c_kv.shape[0])[None, None, :]
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqt,thd->qhd", probs, v)


def _attention(u, lp, c):
    T = u.shape[0]
    pos = jnp.arange(T)
    c_kv, k_r = _latents(u, lp, c, pos)
    q_nope, q_rope = _queries(u, lp, c, pos)
    w_ukv = _f32(lp["w_ukv"]).reshape(c["r"], -1, c["nope"] + c["v"])
    o = _attend(q_nope, q_rope, pos, c_kv, k_r, w_ukv, c)
    return o.reshape(T, -1) @ _f32(lp["wo"])


def _route(s, *, top_k: int, norm: bool, scale: float):
    """``s``: [T, E] sigmoid scores over every expert. The weight of every
    expert ([T, E], 0 where not chosen) and the margin a row: the k-th best
    score less the (k+1)-th."""
    best = jax.lax.top_k(s, top_k + 1)[0]
    w, top_i = jax.lax.top_k(s, top_k)
    if norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * scale
    weight = jnp.sum(jax.nn.one_hot(top_i, s.shape[-1], dtype=jnp.float32) * w[..., None], axis=1)
    return weight, best[:, top_k - 1] - best[:, top_k]


def _op_half(x, lp, c):
    """The layer's first half on x: [T, D] float32."""
    a = _attention(_rms_norm(x, lp["attn_norm"], c["eps"]), lp, c)
    return x + _rms_norm(a, lp["post_attn_norm"], c["eps"])


def _ffn_sum(x, lp, c):
    """``FF(RMS_pre_mlp(x))`` before ``post_mlp_layernorm``: (the sum [T, D],
    the router's margin a row or None, the share of the chosen that are
    held or None). The held experts leave their stacks one at a time."""
    h = _rms_norm(x, lp["mlp_norm"], c["eps"])
    if "router" not in lp:
        return _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"]), None, None
    s = jax.nn.sigmoid(h @ _f32(lp["router"]))
    weight, margin = _route(s, top_k=c["top_k"], norm=c["norm"], scale=c["scale"])
    stacks, nth = lp["experts"]  # the routed layers' [L_routed, E_held, ..] stacks
    held = stacks[0].shape[1]

    def add_expert(e, y):
        w = [
            jax.lax.dynamic_slice(m, (nth, e, 0, 0), (1, 1, *m.shape[2:]))[0, 0] for m in stacks
        ]
        return y + jnp.take(weight, c["first"] + e, axis=1)[:, None] * _swiglu(h, *w)

    y = jax.lax.fori_loop(0, held, add_expert, jnp.zeros_like(x))
    if "shared_gate" in lp:
        y = y + _swiglu(h, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    here = jnp.sum(weight[:, c["first"]: c["first"] + held] > 0) / (weight.shape[0] * c["top_k"])
    return y, margin, here


def _ffn_half(x, lp, c):
    y, margin, here = _ffn_sum(x, lp, c)
    return x + _rms_norm(y, lp["post_ffn_norm"], c["eps"]), margin, here


def _log_softmax_head(x, params, eps):
    logits = _rms_norm(x, params["final_norm"], eps) @ _f32(params["lm_head"])
    return jax.nn.log_softmax(logits, axis=-1)


def block_constants(hf: dict) -> dict:
    """The numbers the block needs, from the configuration's file."""
    if hf.get("model_type") != "pangu_ultra_moe":
        raise ValueError(
            f"references/openpangu_moe.py covers pangu_ultra_moe, not {hf.get('model_type')!r}"
        )
    if hf.get("num_nextn_predict_layers", 0):
        raise ValueError("references/openpangu_moe.py has no multi-token-prediction module")
    if hf.get("tie_word_embeddings", False):
        raise ValueError("references/openpangu_moe.py has an untied head")
    return {
        "eps": float(hf.get("rms_norm_eps", 1e-5)),
        "theta": float(hf["rope_theta"]),
        "r": int(hf["kv_lora_rank"]),
        "nope": int(hf["qk_nope_head_dim"]),
        "rope": int(hf["qk_rope_head_dim"]),
        "v": int(hf["v_head_dim"]),
        "top_k": int(hf["num_experts_per_tok"]),
        "norm": bool(hf.get("norm_topk_prob", True)),
        "scale": float(hf.get("routed_scaling_factor", 1.0)),
        "first": int(hf.get("first_routed_expert", 0)),
        "layers": int(hf["num_hidden_layers"]),
        "dense": int(hf.get("first_k_dense_replace", 0)),
    }


_ATTN = ("w_dq", "q_a_norm", "w_uq", "w_dkv", "kv_a_norm", "w_ukv", "wo")
_NORMS = ("attn_norm", "post_attn_norm", "mlp_norm", "post_ffn_norm")
_DENSE = {"w_gate": "dense_gate", "w_up": "dense_up", "w_down": "dense_down"}
_EXPERTS = ("w_gate", "w_up", "w_down")


def _layer_params(layers, routed: bool, i, nth_ff) -> dict:
    """One layer's parameters out of the stacks (the indices may be
    traced): the norms and attention's at ``i``, the feed-forward's at
    ``nth_ff`` among the layers of its kind; the experts stay in their
    stacks."""
    def at(name, n):
        return jax.lax.dynamic_index_in_dim(layers[name], n, 0, keepdims=False)

    lp = {k: at(k, i) for k in (*_NORMS, *_ATTN)}
    if not routed:
        lp.update((k, at(d, nth_ff)) for k, d in _DENSE.items())
        return lp
    lp["router"] = at("router", nth_ff)
    lp.update((k, at(k, nth_ff)) for k in ("shared_gate", "shared_up", "shared_down") if k in layers)
    lp["experts"] = (tuple(layers[k] for k in _EXPERTS), nth_ff)
    return lp


def _static(c: dict):
    return tuple(sorted(c.items()))


def _ff_at(c: dict, i: int) -> tuple[bool, int]:
    """Whether layer ``i`` is routed, and its ordinal among the layers of
    its feed-forward kind."""
    return (i >= c["dense"]), (i - c["dense"] if i >= c["dense"] else i)


# One compiled program a half and a feed-forward kind, the layer's indices
# traced: a program that held every layer's float32 slices at once would
# not fit beside the weights.
@functools.partial(jax.jit, static_argnames=("c",))
def _op_half_of(layers, x, i, *, c):
    return _op_half(_f32(x), _layer_params(layers, False, i, 0), dict(c))


@functools.partial(jax.jit, static_argnames=("c", "routed", "normed"))
def _ffn_half_of(layers, x, i, nth_ff, *, c, routed, normed=True):
    lp = _layer_params(layers, routed, i, nth_ff)
    return (_ffn_half if normed else _ffn_sum)(_f32(x), lp, dict(c))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_of(params, x, *, eps):
    return _log_softmax_head(_f32(x), params, eps)


def _forward(params, ids, rows, c: dict, at: tuple = ()):
    """Embedding, the layers one after another, the head at ``rows``; and
    the hidden state entering each layer in ``at`` and that layer's second
    half."""
    x = _f32(params["embed"][ids])
    entering, before_ffn = [], []
    for i in range(c["layers"]):
        routed, nth = _ff_at(c, i)
        mid = _op_half_of(params["layers"], x, i, c=_static(c))
        if i in at:
            entering.append(x)
            before_ffn.append(mid)
        x = _ffn_half_of(params["layers"], mid, i, nth, c=_static(c), routed=routed)[0]
    return _head_of(params, x[rows], eps=c["eps"]), entering, before_ffn


def _padded(token_ids) -> list[int]:
    ids = list(token_ids)
    if len(ids) > PAD_TO:
        raise ValueError(f"probe of {len(ids)} tokens exceeds PAD_TO={PAD_TO}")
    return ids + [0] * (PAD_TO - len(ids))


def log_probs(params: dict, hf: dict, token_ids, rows) -> np.ndarray:
    """log-softmax over the vocabulary at positions ``rows`` of the
    sequence ``token_ids`` (padded to PAD_TO; padding follows the real
    tokens, so under the causal mask it changes nothing before it)."""
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


def ffn_half(params: dict, hf: dict, layer: int, x, normed: bool = True):
    """The second half of layer ``layer`` on the rows ``x`` ([N, D], any
    float type, taken as it is): (the rows after the residual add, float32
    [N, D]; the router's margin a row, or None for a dense layer; the share
    of the chosen experts that are held). ``normed`` False: the
    feed-forward's sum alone, before ``post_mlp_layernorm`` and without the
    residual (what the shares of an expert-parallel layer add up to)."""
    c = block_constants(hf)
    routed, nth = _ff_at(c, int(layer))
    with jax.default_matmul_precision("highest"):
        y, margin, here = _ffn_half_of(
            params["layers"], jnp.asarray(x), int(layer), nth, c=_static(c), routed=routed,
            normed=normed,
        )
    return (
        np.asarray(y), None if margin is None else np.asarray(margin),
        None if here is None else float(here),
    )


def op_half_and_head(params: dict, hf: dict, layer: int, x) -> np.ndarray:
    """The first half of layer ``layer`` on ``x`` ([T, D], taken as it is:
    one sequence at positions 0..T-1), then the final norm and the head:
    log-softmax over the vocabulary, float32 [T, V]."""
    c = block_constants(hf)
    with jax.default_matmul_precision("highest"):
        mid = _op_half_of(params["layers"], jnp.asarray(x), int(layer), c=_static(c))
        out = _head_of(params, mid, eps=c["eps"])
    return np.asarray(out)


@functools.partial(jax.jit, static_argnames=("c",))
def _latent_rows_of(layers, x, i, *, c):
    c = dict(c)
    lp = _layer_params(layers, False, i, 0)
    u = _rms_norm(_f32(x), lp["attn_norm"], c["eps"])
    return jnp.concatenate(_latents(u, lp, c, jnp.arange(x.shape[0])), axis=-1)


def latent_rows(params: dict, hf: dict, layer: int, x) -> np.ndarray:
    """What layer ``layer``'s cache holds of the sequence ``x`` ([T, D]):
    ``[c_kv after its norm | k_r after RoPE]``, float32 [T, r + rope]."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(_latent_rows_of(
            params["layers"], jnp.asarray(x), int(layer), c=_static(block_constants(hf)),
        ))


@functools.partial(jax.jit, static_argnames=("c",))
def _attn_rows_of(params, x, rows, i, *, c):
    c = dict(c)
    lp = _layer_params(params["layers"], False, i, 0)
    T = x.shape[0]
    u = _rms_norm(_f32(x), lp["attn_norm"], c["eps"])
    c_kv, k_r = _latents(u, lp, c, jnp.arange(T))
    q_nope, q_rope = _queries(u[rows], lp, c, rows)
    w_ukv = _f32(lp["w_ukv"]).reshape(c["r"], -1, c["nope"] + c["v"])
    H = w_ukv.shape[1]
    blocks = [
        _attend(
            q_nope[:, h: h + HEAD_BLOCK], q_rope[:, h: h + HEAD_BLOCK], rows, c_kv, k_r,
            w_ukv[:, h: h + HEAD_BLOCK], c,
        )
        for h in range(0, H, HEAD_BLOCK)
    ]
    a = jnp.concatenate(blocks, axis=1).reshape(rows.shape[0], -1) @ _f32(lp["wo"])
    mid = _f32(x)[rows] + _rms_norm(a, lp["post_attn_norm"], c["eps"])
    return _log_softmax_head(mid, params, c["eps"])


def attn_rows_and_head(params: dict, hf: dict, layer: int, x, rows) -> np.ndarray:
    """``op_half_and_head`` at the positions ``rows`` alone of a long
    sequence ``x`` ([T, D]): every token's latents, the asked rows'
    queries, attention in the expanded form a block of ``HEAD_BLOCK`` heads
    at a time. float32 [len(rows), V]."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(_attn_rows_of(
            params, jnp.asarray(x), jnp.asarray(list(rows), jnp.int32), int(layer),
            c=_static(block_constants(hf)),
        ))
