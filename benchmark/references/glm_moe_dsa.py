"""Plain float32 reference of ``glm_moe_dsa`` (GLM-5).

Written from the published ``config.json``'s keys and the family's
description (multi-head latent attention as DeepSeek-V2/V3 define it; the
learned sparse selection of DeepSeek-V3.2-Exp, whose key names the row
carries: ``index_n_heads``, ``index_head_dim``, ``index_topk``,
``indexer_rope_interleave``; sigmoid-routed experts chosen through a
selection bias, ``noaux_tc``, beside a shared expert), in straightforward
``jax.numpy``: no kernel, no cache, no paging, no batching, no absorbed
form, no threshold. One sequence, positions 0..T-1. ``x`` the residual
stream, ``RMS_w`` an RMS norm with scale ``w``, eps ``rms_norm_eps``:

- a layer: ``h = x + Attn(RMS_input(x))``, ``y = h + FF(RMS_post_attn(h))``
  (two norms a layer); logits ``RMS_final(x_L) @ lm_head``;
- ``Attn(u)``, ``H`` heads, a token at position ``t``:
  ``c_q = RMS_qa(u W_dq)``; ``q = c_q W_uq`` -> a head ``[q_nope | q_rope]``;
  ``[c_kv | k_r] = u W_dkv``; ``c_kv = RMS_kva(c_kv)``; ``k_r = RoPE_t(k_r)``
  (one rotary key for every head), ``q_rope = RoPE_t(q_rope)``;
  ``[k_nope_h | v_h] = c_kv W_ukv`` a head; scores
  ``(q_nope_h . k_nope_h(s) + q_rope_h . k_r(s)) / sqrt(nope + rope)``;
- the indexer, a layer's own: ``q_I = c_q W_Iq`` -> ``index_n_heads`` heads
  of ``index_head_dim``, the FIRST ``qk_rope_head_dim`` lanes rotary;
  ``k_I = LayerNorm(u W_Ik)`` (scale and bias, eps 1e-6), its first lanes
  rotary likewise; ``w = u W_Iw * index_n_heads^-1/2 * index_head_dim^-1/2``;
  ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])`` for ``s <= t``;
- the selection: ``S_t`` = the ``min(index_topk, t + 1)`` positions ``s <=
  t`` of largest ``I[t, s]`` (``lax.top_k``, exact);
- ``o_h = sum_{s in S_t} softmax_{s in S_t}(score) v_h(s)``;
  ``concat_h(o_h) W_o``;
- ``FF`` of a routed layer: ``s = sigmoid(u W_r)`` over all
  ``n_routed_experts_global`` experts; the experts are the top
  ``num_experts_per_tok`` of ``s + b`` (``e_score_correction_bias``);
  ``w_k = s_k / (sum of the chosen s + 1e-20) * routed_scaling_factor``;
  ``FF(u) = SwiGLU_shared(u) + sum_k w_k SwiGLU_{e_k}(u)``; of the first
  ``first_k_dense_replace`` layers one SwiGLU of ``intermediate_size``.

ROTARY PAIRING. The row says interleaved for both rotations
(``rope_interleave``, ``indexer_rope_interleave``): pairs ``(2i, 2i + 1)``.
The weights are the engine's own arrays, whose rotary blocks lie in the
engine's order (``models/loader.py::rotary_layout`` re-orders the columns
of ``W_uq``, ``W_dkv``, ``W_Iq``, ``W_Ik`` and of the key's LayerNorm at
load); ``_published`` takes every such block back to the published order
before anything here multiplies by it, and this file rotates pairs ``(2i,
2i + 1)``. Rows a caller compares with the engine's cache come back through
``engine_order``.

THE SHARE: as ``references/openpangu_moe.py`` takes it. The arrays hold
experts ``[first_routed_expert, first_routed_expert + n_routed_experts)``
of the ``n_routed_experts_global`` the router scores and biases; the sum
over the chosen runs over the held, each with its weight as normalised over
ALL the chosen; the shared expert is added once.

Departures from the published code, each deliberate: every product runs in
float32 at ``jax.default_matmul_precision("highest")`` on the bfloat16
weights as served; the held experts are evaluated densely; the published
code's Hadamard rotation of ``q_I`` and ``k_I`` (there for fp8) is
orthogonal and left out (the dot products are the same) and nothing is
fp8; the long-sequence entry computes the asked rows alone, a block of
heads at a time, and can be GIVEN a selection in place of its own.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PAD_TO = 64  # a probe is padded to a multiple of this: few compiled shapes
LOGPROB_TOL = 0.5  # ``references/openpangu_moe.py``'s: no configuration asks
HEAD_BLOCK = 16  # heads expanded at a time by the long-sequence entry
INDEX_NORM_EPS = 1e-6


def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def _layer_norm(x, w, b, eps=INDEX_NORM_EPS):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w) + _f32(b)


def _rope(x, positions, theta):
    """x: [T, ..., d]; pairs (2i, 2i + 1) rotate by position * theta^(-2i/d)."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    ang = ang.reshape(ang.shape[0], *(1,) * (x.ndim - 2), d // 2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ _f32(w_gate)) * (h @ _f32(w_up))) @ _f32(w_down)


def _columns(n: int, starts, width: int, order) -> np.ndarray:
    cols = np.arange(n)
    for a in starts:
        cols[a:a + width] = a + np.asarray(order)
    return cols


def _pairs_from_halves(width: int) -> np.ndarray:
    """Published column 2i is engine column i, 2i + 1 is i + width / 2."""
    half = np.arange(width // 2)
    return np.stack([half, half + width // 2], axis=1).reshape(-1)


def _rotary_starts(c: dict) -> dict:
    return {
        "w_uq": [h * (c["nope"] + c["rope"]) + c["nope"] for h in range(c["heads"])],
        "w_dkv": [c["r"]],
        "idx_wq": [j * c["di"] for j in range(c["hi"])],
        "idx_wk": [0], "idx_k_norm": [0], "idx_k_bias": [0],
    }


def _published(lp: dict, c: dict) -> dict:
    """A layer's tensors with every rotary block in the published order."""
    out = dict(lp)
    for key, starts in _rotary_starts(c).items():
        cols = _columns(lp[key].shape[-1], starts, c["rope"], _pairs_from_halves(c["rope"]))
        out[key] = jnp.take(lp[key], jnp.asarray(cols), axis=-1)
    return out


def engine_order(rows: np.ndarray, start: int, width: int) -> np.ndarray:
    """``rows`` ([.., n]) with the rotary block at ``start`` taken from the
    published order to the engine's (what its cache holds)."""
    order = np.argsort(_pairs_from_halves(width))
    return np.take(rows, _columns(rows.shape[-1], [start], width, order), axis=-1)


def _latents(u, lp, c, positions):
    """``c_kv`` after its norm [T, r] and ``k_r`` after RoPE [T, rope]."""
    ckr = u @ _f32(lp["w_dkv"])
    c_kv = _rms_norm(ckr[:, : c["r"]], lp["kv_a_norm"], c["eps"])
    return c_kv, _rope(ckr[:, c["r"]:], positions, c["theta"])


def _c_q(u, lp, c):
    return _rms_norm(u @ _f32(lp["w_dq"]), lp["q_a_norm"], c["eps"])


def _queries(c_q, lp, c, positions):
    """[T, H, nope] and [T, H, rope] (after RoPE)."""
    q = (c_q @ _f32(lp["w_uq"])).reshape(c_q.shape[0], -1, c["nope"] + c["rope"])
    return q[..., : c["nope"]], _rope(q[..., c["nope"]:], positions, c["theta"])


def _index_keys(u, lp, c, positions):
    """[T, Di]: LayerNorm, then RoPE on the first ``rope`` lanes."""
    k = _layer_norm(u @ _f32(lp["idx_wk"]), lp["idx_k_norm"], lp["idx_k_bias"])
    return jnp.concatenate(
        [_rope(k[:, : c["rope"]], positions, c["theta"]), k[:, c["rope"]:]], axis=-1
    )


def _index_queries(u, c_q, lp, c, positions):
    """``q_I`` [T, Hi, Di] (first lanes rotary) and ``w`` [T, Hi]."""
    q = (c_q @ _f32(lp["idx_wq"])).reshape(u.shape[0], c["hi"], c["di"])
    q = jnp.concatenate(
        [_rope(q[..., : c["rope"]], positions, c["theta"]), q[..., c["rope"]:]], axis=-1
    )
    return q, (u @ _f32(lp["idx_wp"])) * c["hi"] ** -0.5 * c["di"] ** -0.5


def _index_scores(q_i, w_i, k_i, q_pos, c=None):
    """``I[t, s]`` [Q, T], ``-inf`` where ``s > t``."""
    dots = jax.nn.relu(jnp.einsum("qjd,sd->qjs", q_i, k_i))
    scores = jnp.einsum("qjs,qj->qs", dots, w_i)
    seen = q_pos[:, None] >= jnp.arange(k_i.shape[0])[None, :]
    # + 0.0: a sum of zeros weighed negatively is -0.0, which top_k would
    # order below 0.0; the two are one score
    return jnp.where(seen, scores + 0.0, -jnp.inf)


def _select(scores, q_pos, topk: int):
    """(``S_t`` as [Q, T] bool; the margin a query: its ``topk``-th largest
    score less the next, ``inf`` where it sees no more than ``topk``)."""
    T = scores.shape[1]
    seen = q_pos[:, None] >= jnp.arange(T)[None, :]
    if T <= topk:
        return seen, jnp.full(q_pos.shape, jnp.inf)
    best, at = jax.lax.top_k(scores, topk + 1)
    selecting = q_pos >= topk
    chosen = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], at[:, :topk]
    ].set(True)
    keep = jnp.where(selecting[:, None], chosen, seen)
    margin = jnp.where(selecting, best[:, topk - 1] - best[:, topk], jnp.inf)
    return keep & seen, margin


def _attend(q_nope, q_rope, keep, c_kv, k_r, w_ukv, c):
    """The expanded form over the positions ``keep`` ([Q, T] bool) allows:
    [Q, H', v]. ``w_ukv`` [r, H', nope + v] float32."""
    kv = jnp.einsum("tc,chd->thd", c_kv, w_ukv)  # [T, H', nope + v]
    k_nope, v = kv[..., : c["nope"]], kv[..., c["nope"]:]
    scores = jnp.einsum("qhd,thd->hqt", q_nope, k_nope) + jnp.einsum("qhd,td->hqt", q_rope, k_r)
    scores = scores * (c["nope"] + c["rope"]) ** -0.5
    probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqt,thd->qhd", probs, v)


def _attention_rows(u, lp, c, rows, given=None):
    """Attention's output [len(rows), D] at the positions ``rows`` of the
    sequence ``u`` ([T, D], normed), the selection it used and its margins.
    ``given`` ([len(rows), T] bool): a selection to use in place of its own."""
    T = u.shape[0]
    pos = jnp.arange(T)
    c_kv, k_r = _latents(u, lp, c, pos)
    c_q = _c_q(u[rows], lp, c)
    q_nope, q_rope = _queries(c_q, lp, c, rows)
    q_i, w_i = _index_queries(u[rows], c_q, lp, c, rows)
    scores = _index_scores(q_i, w_i, _index_keys(u, lp, c, pos), rows)
    keep, margin = _select(scores, rows, c["topk"])
    if given is not None:
        keep = given & (rows[:, None] >= pos[None, :])
    w_ukv = _f32(lp["w_ukv"]).reshape(c["r"], -1, c["nope"] + c["v"])
    H = w_ukv.shape[1]
    blocks = [
        _attend(
            q_nope[:, h: h + HEAD_BLOCK], q_rope[:, h: h + HEAD_BLOCK], keep, c_kv, k_r,
            w_ukv[:, h: h + HEAD_BLOCK], c,
        )
        for h in range(0, H, HEAD_BLOCK)
    ]
    o = jnp.concatenate(blocks, axis=1).reshape(rows.shape[0], -1) @ _f32(lp["wo"])
    return o, keep, margin, scores


def _route(s, bias, *, top_k: int, norm: bool, scale: float):
    """``s``: [T, E] sigmoid scores over every expert; the experts are the
    top of ``s + bias``. The weight of every expert ([T, E], 0 where not
    chosen) and the margin a row: the k-th best biased score less the
    (k+1)-th."""
    chosen_by = s + _f32(bias)
    best, top_i = jax.lax.top_k(chosen_by, top_k + 1)
    top_i = top_i[:, :top_k]
    w = jnp.take_along_axis(s, top_i, axis=-1)
    if norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * scale
    weight = jnp.sum(jax.nn.one_hot(top_i, s.shape[-1], dtype=jnp.float32) * w[..., None], axis=1)
    return weight, best[:, top_k - 1] - best[:, top_k]


def _op_half(x, lp, c):
    """The layer's first half on x: [T, D] float32."""
    T = x.shape[0]
    u = _rms_norm(x, lp["attn_norm"], c["eps"])
    return x + _attention_rows(u, lp, c, jnp.arange(T))[0]


def _ffn_sum(x, lp, c):
    """``FF(RMS_post_attn(x))``: (the sum [T, D], the router's margin a row
    or None, the share of the chosen that are held or None)."""
    h = _rms_norm(x, lp["mlp_norm"], c["eps"])
    if "router" not in lp:
        return _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"]), None, None
    s = jax.nn.sigmoid(h @ _f32(lp["router"]))
    weight, margin = _route(
        s, lp["router_bias"], top_k=c["top_k"], norm=c["norm"], scale=c["scale"]
    )
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
    return x + y, margin, here


def _log_softmax_head(x, params, eps):
    logits = _rms_norm(x, params["final_norm"], eps) @ _f32(params["lm_head"])
    return jax.nn.log_softmax(logits, axis=-1)


def block_constants(hf: dict) -> dict:
    """The numbers the block needs, from the configuration's file."""
    if hf.get("model_type") != "glm_moe_dsa":
        raise ValueError(
            f"references/glm_moe_dsa.py covers glm_moe_dsa, not {hf.get('model_type')!r}"
        )
    if hf.get("num_nextn_predict_layers", 0):
        raise ValueError("references/glm_moe_dsa.py has no multi-token-prediction module")
    if hf.get("tie_word_embeddings", False):
        raise ValueError("references/glm_moe_dsa.py has an untied head")
    return {
        "eps": float(hf.get("rms_norm_eps", 1e-5)),
        "theta": float((hf.get("rope_parameters") or {}).get("rope_theta") or hf["rope_theta"]),
        "heads": int(hf["num_attention_heads"]),
        "r": int(hf["kv_lora_rank"]),
        "nope": int(hf["qk_nope_head_dim"]),
        "rope": int(hf["qk_rope_head_dim"]),
        "v": int(hf["v_head_dim"]),
        "hi": int(hf["index_n_heads"]),
        "di": int(hf["index_head_dim"]),
        "topk": int(hf["index_topk"]),
        "top_k": int(hf["num_experts_per_tok"]),
        "norm": bool(hf.get("norm_topk_prob", True)),
        "scale": float(hf.get("routed_scaling_factor", 1.0)),
        "first": int(hf.get("first_routed_expert", 0)),
        "layers": int(hf["num_hidden_layers"]),
        "dense": int(hf.get("first_k_dense_replace", 0)),
    }


_ATTN = (
    "w_dq", "q_a_norm", "w_uq", "w_dkv", "kv_a_norm", "w_ukv", "wo",
    "idx_wq", "idx_wk", "idx_k_norm", "idx_k_bias", "idx_wp",
)
_NORMS = ("attn_norm", "mlp_norm")
_DENSE = {"w_gate": "dense_gate", "w_up": "dense_up", "w_down": "dense_down"}
_EXPERTS = ("w_gate", "w_up", "w_down")


def _layer_params(layers, routed: bool, i, nth_ff, c, attention: bool = True) -> dict:
    """One layer's parameters out of the stacks (the indices may be
    traced), the rotary blocks in the published order; the experts stay in
    their stacks."""
    def at(name, n):
        return jax.lax.dynamic_index_in_dim(layers[name], n, 0, keepdims=False)

    lp = {k: at(k, i) for k in _NORMS}
    if attention:
        lp.update(_published({k: at(k, i) for k in _ATTN}, c))
    if not routed:
        lp.update((k, at(d, nth_ff)) for k, d in _DENSE.items())
        return lp
    lp["router"], lp["router_bias"] = at("router", nth_ff), at("router_bias", nth_ff)
    lp.update((k, at(k, nth_ff)) for k in ("shared_gate", "shared_up", "shared_down") if k in layers)
    lp["experts"] = (tuple(layers[k] for k in _EXPERTS), nth_ff)
    return lp


def _static(c: dict):
    return tuple(sorted(c.items()))


def _ff_at(c: dict, i: int) -> tuple[bool, int]:
    """Whether layer ``i`` is routed, and its ordinal among the layers of
    its feed-forward kind."""
    return (i >= c["dense"]), (i - c["dense"] if i >= c["dense"] else i)


@functools.partial(jax.jit, static_argnames=("c",))
def _op_half_of(layers, x, i, *, c):
    c = dict(c)
    return _op_half(_f32(x), _layer_params(layers, False, i, 0, c), c)


@functools.partial(jax.jit, static_argnames=("c", "routed", "normed"))
def _ffn_half_of(layers, x, i, nth_ff, *, c, routed, normed=True):
    c = dict(c)
    lp = _layer_params(layers, routed, i, nth_ff, c, attention=False)
    return (_ffn_half if normed else _ffn_sum)(_f32(x), lp, c)


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


def _padded(token_ids, pad_to: int | None) -> list[int]:
    ids = list(token_ids)
    pad_to = -(-len(ids) // PAD_TO) * PAD_TO if pad_to is None else pad_to
    if len(ids) > pad_to:
        raise ValueError(f"probe of {len(ids)} tokens exceeds the padded length {pad_to}")
    return ids + [0] * (pad_to - len(ids))


def log_probs(params: dict, hf: dict, token_ids, rows, pad_to: int | None = None) -> np.ndarray:
    """log-softmax over the vocabulary at positions ``rows`` of the
    sequence ``token_ids`` (padded to ``pad_to``, the next multiple of
    PAD_TO unless given; padding follows the real tokens, so under the causal mask it changes
    nothing before it: a later token is never selected by an earlier
    query)."""
    with jax.default_matmul_precision("highest"):
        out = _forward(
            params, jnp.asarray(_padded(token_ids, pad_to), jnp.int32),
            jnp.asarray(list(rows), jnp.int32), block_constants(hf),
        )[0]
    return np.asarray(out)


def hidden_states(params: dict, hf: dict, token_ids, layers, pad_to: int | None = None):
    """For the sequence ``token_ids``, the float32 hidden state entering
    each layer in ``layers`` (ascending) and the one entering that layer's
    second half: two arrays [len(layers), len(token_ids), D]."""
    n = len(list(token_ids))
    with jax.default_matmul_precision("highest"):
        _, entering, before_ffn = _forward(
            params, jnp.asarray(_padded(token_ids, pad_to), jnp.int32),
            jnp.zeros((1,), jnp.int32), block_constants(hf),
            at=tuple(int(l) for l in layers),
        )
    return np.stack([np.asarray(e)[:n] for e in entering]), np.stack([np.asarray(b)[:n] for b in before_ffn])


def ffn_half(params: dict, hf: dict, layer: int, x, normed: bool = True):
    """The second half of layer ``layer`` on the rows ``x`` ([N, D], taken
    as it is): (the rows after the residual add, float32 [N, D]; the
    router's margin a row, or None for a dense layer; the share of the
    chosen experts that are held). ``normed`` False: the feed-forward's sum
    alone, without the residual (what the shares of an expert-parallel
    layer add up to)."""
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
    """The first half of layer ``layer`` on ``x`` ([T, D]: one sequence at
    positions 0..T-1), then the final norm and the head: log-softmax over
    the vocabulary, float32 [T, V]."""
    c = block_constants(hf)
    with jax.default_matmul_precision("highest"):
        mid = _op_half_of(params["layers"], jnp.asarray(x), int(layer), c=_static(c))
        out = _head_of(params, mid, eps=c["eps"])
    return np.asarray(out)


@functools.partial(jax.jit, static_argnames=("c",))
def _pool_rows_of(layers, x, i, *, c):
    c = dict(c)
    lp = _layer_params(layers, False, i, 0, c)
    u = _rms_norm(_f32(x), lp["attn_norm"], c["eps"])
    pos = jnp.arange(x.shape[0])
    return jnp.concatenate(_latents(u, lp, c, pos), axis=-1), _index_keys(u, lp, c, pos)


def pool_rows(params: dict, hf: dict, layer: int, x) -> tuple[np.ndarray, np.ndarray]:
    """What layer ``layer``'s two caches hold of the sequence ``x`` ([T,
    D]), IN THE ENGINE'S ORDER of the rotary lanes: ``[c_kv after its norm
    | k_r after RoPE]`` float32 [T, r + rope], and the index key after its
    LayerNorm and RoPE, float32 [T, index_head_dim]."""
    c = block_constants(hf)
    with jax.default_matmul_precision("highest"):
        latent, key = _pool_rows_of(params["layers"], jnp.asarray(x), int(layer), c=_static(c))
    return (
        engine_order(np.asarray(latent), c["r"], c["rope"]),
        engine_order(np.asarray(key), 0, c["rope"]),
    )


@functools.partial(jax.jit, static_argnames=("c", "own"))
def _attn_rows_of(params, x, rows, given, i, *, c, own):
    c = dict(c)
    lp = _layer_params(params["layers"], False, i, 0, c)
    u = _rms_norm(_f32(x), lp["attn_norm"], c["eps"])
    a, keep, margin, scores = _attention_rows(u, lp, c, rows, None if own else given)
    return _log_softmax_head(_f32(x)[rows] + a, params, c["eps"]), keep, margin, scores


def attn_rows_and_head(params: dict, hf: dict, layer: int, x, rows, selection=None) -> dict:
    """``op_half_and_head`` at the positions ``rows`` alone of a long
    sequence ``x`` ([T, D]): every token's latents and index keys, the asked
    rows' queries, their index scores and selection (or ``selection``, [len(
    rows), T] bool, GIVEN in its place), attention in the expanded form a
    block of ``HEAD_BLOCK`` heads at a time. ``log_probs`` float32 [len(
    rows), V]; ``keep`` the selection used [len(rows), T]; ``margin`` the
    reference's own ``index_topk``-th score less the next, a row; ``scores``
    its index scores."""
    rows = jnp.asarray(list(rows), jnp.int32)
    own = selection is None
    given = jnp.zeros((rows.shape[0], x.shape[0]), bool) if own else jnp.asarray(selection)
    with jax.default_matmul_precision("highest"):
        out = _attn_rows_of(
            params, jnp.asarray(x), rows, given, int(layer),
            c=_static(block_constants(hf)), own=own,
        )
    log_probs_, keep, margin, scores = (np.asarray(o) for o in out)
    return {"log_probs": log_probs_, "keep": keep, "margin": margin, "scores": scores}
