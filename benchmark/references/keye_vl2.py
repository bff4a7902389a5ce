"""Plain float32 reference of ``KeyeVL2``'s language model
(Keye-VL-2.0-30B-A3B).

Written from the published ``config.json``'s keys and the family's
description (Qwen3-MoE's decoder under DeepSeek-Sparse-Attention's indexer,
``sa_config``; rotary by sections, ``rope_scaling.mrope_section``), in
straightforward ``jax.numpy``: no kernel, no cache, no paging, no batching,
no threshold. One sequence, positions 0..T-1 unless three components a
token are given. ``x`` the residual stream, ``RMS_w`` an RMS norm with
scale ``w``, eps ``rms_norm_eps``:

- a layer: ``h = x + Attn(RMS_input(x))``, ``y = h + FF(RMS_post_attn(h))``;
  logits ``RMS_final(x_L) @ lm_head``;
- ``Attn(u)``: ``q = u W_q`` (H heads of D), ``k = u W_k``, ``v = u W_v``
  (Hkv heads of D); ``q``, ``k`` each RMS-normed a head (``q_norm``,
  ``k_norm``); then the rotary by sections: of a head's D/2 rotary pairs
  ``(i, i + D/2)`` the first ``mrope_section[0]`` turn by the temporal
  position, the next ``mrope_section[1]`` by the height, the last by the
  width, pair ``i`` at ``theta^(-2i/D)``; a text token's three are equal,
  which is the plain rotary;
- the indexer, a layer's own: ``q_I = u W_Iq`` -> ``indexer_num_heads``
  heads of ``indexer_head_dim``; ``k_I = LayerNorm(u W_Ik)`` (ONE key head,
  scale and bias, eps 1e-6); ``w = u W_Iw * heads^-1/2 * width^-1/2``; ALL
  lanes of ``q_I`` and ``k_I`` rotary by the temporal position at the
  model's base over that width, pairs ``(i, i + width/2)``;
  ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])`` for ``s <= t``;
- the selection: ``S_t`` = the ``min(topk, t + 1)`` positions ``s <= t`` of
  largest ``I[t, s]`` (``lax.top_k``, exact, a tie to the lower position);
- ``o_h = sum_{s in S_t} softmax_{s in S_t}(q_h . k_g(s) / sqrt(D)) v_g(s)``,
  ``g`` the KV head of query head ``h`` (H / Hkv query heads a KV head, every
  head the same set); ``concat_h(o_h) W_o``;
- ``FF(u)``: ``p = softmax(u W_r)`` over all ``num_experts`` in float32, the
  top ``num_experts_per_tok``, divided by their sum (``norm_topk_prob``);
  ``FF(u) = sum_k p_k SwiGLU_{e_k}(u)``. No shared expert, no bias.

ROTARY PAIRING: half-split, ``(i, i + d/2)``, the engine's own
(``ops/rope.py::apply_rope``; Qwen's and HF's ``rotate_half``), so no
column is re-ordered on either side.

What the row does not give and the configuration's file lists under
``assumed``: where ``q_I`` is projected from (the normed stream), which
index lanes are rotary (all, by the temporal component), that
``q_chunk_size`` / ``kv_chunk_size`` are tile sizes of the published code
and change no sum, the key's LayerNorm and the weights' two factors
(DeepSeek-V3.2's), the q/k norms (Qwen3-MoE's).

Departures from the published code, each deliberate: every product runs in
float32 at ``jax.default_matmul_precision("highest")`` on the bfloat16
weights as served; the experts are evaluated densely; the Hadamard rotation
of ``q_I`` and ``k_I`` (there for fp8) is orthogonal and left out and
nothing is fp8; the long-sequence entry computes the asked rows alone, a
block of KV heads at a time, and can be GIVEN a selection in place of its
own; the vision tower is not part of the language model and is left out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PAD_TO = 64  # a probe is padded to a multiple of this: few compiled shapes
LOGPROB_TOL = 0.5  # ``references/openpangu_moe.py``'s: no configuration asks
KV_HEAD_BLOCK = 1  # KV heads (with their query heads) attended at a time by the long entry
ROW_BLOCK = 64  # asked rows attended at a time by the long entry
INDEX_NORM_EPS = 1e-6


def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def _layer_norm(x, w, b, eps=INDEX_NORM_EPS):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w) + _f32(b)


def _rotate(x, ang):
    """x: [T, ..., d] turned by ``ang`` [T, d / 2]: pairs (i, i + d / 2)."""
    half = x.shape[-1] // 2
    ang = ang.reshape(ang.shape[0], *(1,) * (x.ndim - 2), half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _inv_freq(d: int, theta: float):
    return 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)


def _rope(x, positions, theta):
    """The plain rotary: every pair by ``positions`` [T]."""
    return _rotate(x, _f32(positions)[:, None] * _inv_freq(x.shape[-1], theta)[None, :])


def _mrope(x, pos3, theta, sections):
    """The rotary by sections: ``pos3`` [3, T]; pair ``i`` turns by the
    component whose section holds it."""
    if not sections:
        return _rope(x, pos3[0], theta)
    owner = np.repeat(np.arange(len(sections)), sections)  # [d / 2]
    ang = _f32(pos3).T[:, owner] * _inv_freq(x.shape[-1], theta)[None, :]  # [T, d / 2]
    return _rotate(x, ang)


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ _f32(w_gate)) * (h @ _f32(w_up))) @ _f32(w_down)


def _keys_values(u, lp, c, pos3):
    """K after its norm and rotary [T, Hkv, D] and V [T, Hkv, D]."""
    T = u.shape[0]
    k = (u @ _f32(lp["wk"])).reshape(T, -1, c["d"])
    k = _mrope(_rms_norm(k, lp["k_norm"], c["eps"]), pos3, c["theta"], c["sections"])
    return k, (u @ _f32(lp["wv"])).reshape(T, -1, c["d"])


def _queries(u, lp, c, pos3):
    q = (u @ _f32(lp["wq"])).reshape(u.shape[0], -1, c["d"])
    return _mrope(_rms_norm(q, lp["q_norm"], c["eps"]), pos3, c["theta"], c["sections"])


def _index_keys(u, lp, c, pos3):
    """[T, Di]: LayerNorm, then the rotary over all lanes by the temporal
    position."""
    k = _layer_norm(u @ _f32(lp["idx_wk"]), lp["idx_k_norm"], lp["idx_k_bias"])
    if not c.get("index_rope", True):
        return k
    return _rope(k, pos3[0], c["theta"])


def _index_queries(u, lp, c, pos3):
    """``q_I`` [T, Hi, Di] (rotary) and ``w`` [T, Hi]."""
    q = (u @ _f32(lp["idx_wq"])).reshape(u.shape[0], c["hi"], c["di"])
    if c.get("index_rope", True):
        q = _rope(q, pos3[0], c["theta"])
    return q, (u @ _f32(lp["idx_wp"])) * c["hi"] ** -0.5 * c["di"] ** -0.5


def _index_scores(q_i, w_i, k_i, q_pos, c=None):
    """``I[t, s]`` [Q, T], ``-inf`` where ``s > t``."""
    dots = jnp.einsum("qjd,sd->qjs", q_i, k_i)
    if c is None or c.get("relu", True):
        dots = jax.nn.relu(dots)
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


def _attend(q, keep, k, v, c):
    """Softmax attention over the positions ``keep`` ([Q, T] bool) allows:
    ``q`` [Q, G, qpk, D] against ``k``, ``v`` [T, G, D] -> [Q, G, qpk, D]."""
    scores = jnp.einsum("qgpd,tgd->gpqt", q, k) * c["d"] ** -0.5
    probs = jax.nn.softmax(jnp.where(keep[None, None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("gpqt,tgd->qgpd", probs, v)


def _attention_rows(u, lp, c, rows, pos3=None, given=None):
    """Attention's output [len(rows), D] at the positions ``rows`` of the
    sequence ``u`` ([T, D], normed), the selection it used, its margins and
    its index scores. ``given`` ([len(rows), T] bool): a selection to use in
    place of its own. Computed a block of KV heads and of rows at a time."""
    T = u.shape[0]
    pos = jnp.arange(T)
    pos3 = jnp.broadcast_to(pos, (3, T)) if pos3 is None else pos3
    k, v = _keys_values(u, lp, c, pos3)
    q = _queries(u[rows], lp, c, pos3[:, rows])
    q_i, w_i = _index_queries(u[rows], lp, c, pos3[:, rows])
    scores = _index_scores(q_i, w_i, _index_keys(u, lp, c, pos3), rows, c)
    keep, margin = _select(scores, rows, c["topk"])
    if given is not None:
        keep = given & (rows[:, None] >= pos[None, :])
    R, Hkv = rows.shape[0], k.shape[1]
    q = q.reshape(R, Hkv, -1, c["d"])
    out = []
    for a in range(0, R, ROW_BLOCK):
        out.append(jnp.concatenate([
            _attend(
                q[a : a + ROW_BLOCK, g : g + KV_HEAD_BLOCK], keep[a : a + ROW_BLOCK],
                k[:, g : g + KV_HEAD_BLOCK], v[:, g : g + KV_HEAD_BLOCK], c,
            )
            for g in range(0, Hkv, KV_HEAD_BLOCK)
        ], axis=1))
    o = jnp.concatenate(out).reshape(R, -1) @ _f32(lp["wo"])
    return o, keep, margin, scores


def _route(logits, *, top_k: int, norm: bool):
    """``logits`` [T, E] float32; the weight of every expert ([T, E], 0
    where not chosen) and the margin a row: the k-th best probability less
    the (k+1)-th, as a share of the k-th (over 128 experts a probability is
    ~0.008, and what a bfloat16 input moves it by is a share of it, not an
    amount: ``references/sdar_moe.py``'s)."""
    p = jax.nn.softmax(logits, axis=-1)
    best, top_i = jax.lax.top_k(p, top_k + 1)
    w, top_i = best[:, :top_k], top_i[:, :top_k]
    if norm:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(top_i, p.shape[-1], dtype=jnp.float32) * w[..., None], axis=1)
    return weight, (best[:, top_k - 1] - best[:, top_k]) / best[:, top_k - 1]


def _op_half(x, lp, c, pos3=None):
    """The layer's first half on x: [T, D] float32."""
    u = _rms_norm(x, lp["attn_norm"], c["eps"])
    return x + _attention_rows(u, lp, c, jnp.arange(x.shape[0]), pos3)[0]


def _ffn_sum(x, lp, c):
    """``FF(RMS_post_attn(x))``: (the sum [T, D], the router's margin a
    row)."""
    h = _rms_norm(x, lp["mlp_norm"], c["eps"])
    weight, margin = _route(h @ _f32(lp["router"]), top_k=c["top_k"], norm=c["norm"])
    stacks, nth = lp["experts"]  # the [L, E, ..] stacks

    def add_expert(e, y):
        w = [
            jax.lax.dynamic_slice(m, (nth, e, 0, 0), (1, 1, *m.shape[2:]))[0, 0] for m in stacks
        ]
        return y + jnp.take(weight, e, axis=1)[:, None] * _swiglu(h, *w)

    return jax.lax.fori_loop(0, stacks[0].shape[1], add_expert, jnp.zeros_like(x)), margin


def _ffn_half(x, lp, c):
    y, margin = _ffn_sum(x, lp, c)
    return x + y, margin


def _log_softmax_head(x, params, eps):
    logits = _rms_norm(x, params["final_norm"], eps) @ _f32(params["lm_head"])
    return jax.nn.log_softmax(logits, axis=-1)


def block_constants(hf: dict) -> dict:
    """The numbers the block needs, from the configuration's file."""
    if hf.get("model_type") != "KeyeVL2":
        raise ValueError(f"references/keye_vl2.py covers KeyeVL2, not {hf.get('model_type')!r}")
    if hf.get("tie_word_embeddings", False):
        raise ValueError("references/keye_vl2.py has an untied head")
    if hf.get("use_sliding_window", False):
        raise ValueError("references/keye_vl2.py has no sliding window")
    sa = hf["sa_config"]
    heads = int(hf["num_attention_heads"])
    return {
        "eps": float(hf.get("rms_norm_eps", 1e-6)),
        "theta": float(hf["rope_theta"]),
        "d": int(hf.get("head_dim") or hf["hidden_size"] // heads),
        "sections": tuple(int(n) for n in (hf.get("rope_scaling") or {}).get("mrope_section") or ()),
        "hi": int(sa["indexer_num_heads"]),
        "di": int(sa["indexer_head_dim"]),
        "topk": int(sa["topk"]),
        "top_k": int(hf["num_experts_per_tok"]),
        "norm": bool(hf.get("norm_topk_prob", False)),
        "layers": int(hf["num_hidden_layers"]),
    }


_ATTN = (
    "wq", "wk", "wv", "wo", "q_norm", "k_norm",
    "idx_wq", "idx_wk", "idx_k_norm", "idx_k_bias", "idx_wp",
)
_NORMS = ("attn_norm", "mlp_norm")
_EXPERTS = ("w_gate", "w_up", "w_down")


def _layer_params(layers, i, attention: bool = True) -> dict:
    """One layer's parameters out of the stacks (the index may be traced);
    the experts stay in their stacks."""
    def at(name):
        return jax.lax.dynamic_index_in_dim(layers[name], i, 0, keepdims=False)

    lp = {k: at(k) for k in _NORMS}
    if attention:
        lp.update((k, at(k)) for k in _ATTN)
    lp["router"] = at("router")
    lp["experts"] = (tuple(layers[k] for k in _EXPERTS), i)
    return lp


def _static(c: dict):
    return tuple(sorted(c.items()))


@functools.partial(jax.jit, static_argnames=("c",))
def _op_half_of(layers, x, i, pos3, *, c):
    return _op_half(_f32(x), _layer_params(layers, i), dict(c), pos3)


@functools.partial(jax.jit, static_argnames=("c", "normed"))
def _ffn_half_of(layers, x, i, *, c, normed=True):
    lp = _layer_params(layers, i, attention=False)
    return (_ffn_half if normed else _ffn_sum)(_f32(x), lp, dict(c))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_of(params, x, *, eps):
    return _log_softmax_head(_f32(x), params, eps)


def _forward(params, ids, rows, c: dict, at: tuple = (), pos3=None, embeds=None):
    """Embedding, the layers one after another, the head at ``rows``; and
    the hidden state entering each layer in ``at`` and that layer's second
    half."""
    x = _f32(params["embed"][ids]) if embeds is None else _f32(embeds)
    T = x.shape[0]
    pos3 = jnp.broadcast_to(jnp.arange(T), (3, T)) if pos3 is None else jnp.asarray(pos3)
    entering, before_ffn = [], []
    for i in range(c["layers"]):
        mid = _op_half_of(params["layers"], x, i, pos3, c=_static(c))
        if i in at:
            entering.append(x)
            before_ffn.append(mid)
        x = _ffn_half_of(params["layers"], mid, i, c=_static(c))[0]
    return _head_of(params, x[rows], eps=c["eps"]), entering, before_ffn


def _padded(token_ids, pad_to: int | None) -> list[int]:
    ids = list(token_ids)
    pad_to = -(-len(ids) // PAD_TO) * PAD_TO if pad_to is None else pad_to
    if len(ids) > pad_to:
        raise ValueError(f"probe of {len(ids)} tokens exceeds the padded length {pad_to}")
    return ids + [0] * (pad_to - len(ids))


def log_probs(params: dict, hf: dict, token_ids, rows, pad_to: int | None = None,
              positions=None) -> np.ndarray:
    """log-softmax over the vocabulary at positions ``rows`` of the
    sequence ``token_ids`` (padded to ``pad_to``, the next multiple of
    PAD_TO unless given; padding follows the real tokens, so under the causal
    mask it changes nothing before it: a later token is never selected by an
    earlier query). ``positions`` ([3, len(token_ids)]): the tokens' three
    rotary components where they are not ``0..T-1`` three times."""
    ids = _padded(token_ids, pad_to)
    pos3 = None
    if positions is not None:
        positions = np.asarray(positions)
        pos3 = np.zeros((3, len(ids)), np.int32)
        pos3[:, : positions.shape[1]] = positions
    with jax.default_matmul_precision("highest"):
        out = _forward(
            params, jnp.asarray(ids, jnp.int32), jnp.asarray(list(rows), jnp.int32),
            block_constants(hf), pos3=pos3,
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
    router's margin a row; the share of the chosen experts this device
    holds: all of them, 1.0). ``normed`` False: the experts' sum alone."""
    c = block_constants(hf)
    with jax.default_matmul_precision("highest"):
        y, margin = _ffn_half_of(
            params["layers"], jnp.asarray(x), int(layer), c=_static(c), normed=normed
        )
    return np.asarray(y), np.asarray(margin), 1.0


def op_half_and_head(params: dict, hf: dict, layer: int, x, positions=None) -> np.ndarray:
    """The first half of layer ``layer`` on ``x`` ([T, D]: one sequence at
    positions 0..T-1; ``positions`` [3, T]: its three rotary components
    where they are not that three times), then the final norm and the
    head: log-softmax over the vocabulary, float32 [T, V]."""
    c = block_constants(hf)
    T = x.shape[0]
    pos3 = jnp.broadcast_to(jnp.arange(T), (3, T)) if positions is None else jnp.asarray(positions)
    with jax.default_matmul_precision("highest"):
        mid = _op_half_of(params["layers"], jnp.asarray(x), int(layer), pos3, c=_static(c))
        out = _head_of(params, mid, eps=c["eps"])
    return np.asarray(out)


@functools.partial(jax.jit, static_argnames=("c",))
def _pool_rows_of(layers, x, i, *, c):
    c = dict(c)
    lp = _layer_params(layers, i)
    u = _rms_norm(_f32(x), lp["attn_norm"], c["eps"])
    T = x.shape[0]
    pos3 = jnp.broadcast_to(jnp.arange(T), (3, T))
    k, v = _keys_values(u, lp, c, pos3)
    return k.reshape(T, -1), v.reshape(T, -1), _index_keys(u, lp, c, pos3)


def pool_rows(params: dict, hf: dict, layer: int, x) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """What layer ``layer``'s cache holds of the sequence ``x`` ([T, D]): K
    after its norm and rotary [T, Hkv x D], V [T, Hkv x D] and the index key
    after its LayerNorm and rotary [T, indexer_head_dim], float32."""
    with jax.default_matmul_precision("highest"):
        rows = _pool_rows_of(
            params["layers"], jnp.asarray(x), int(layer), c=_static(block_constants(hf))
        )
    return tuple(np.asarray(r) for r in rows)


@functools.partial(jax.jit, static_argnames=("c", "own"))
def _attn_rows_of(params, x, rows, given, i, *, c, own):
    c = dict(c)
    lp = _layer_params(params["layers"], i)
    u = _rms_norm(_f32(x), lp["attn_norm"], c["eps"])
    a, keep, margin, scores = _attention_rows(u, lp, c, rows, None, None if own else given)
    return _log_softmax_head(_f32(x)[rows] + a, params, c["eps"]), keep, margin, scores


def attn_rows_and_head(params: dict, hf: dict, layer: int, x, rows, selection=None,
                       fault: dict | None = None) -> dict:
    """``op_half_and_head`` at the positions ``rows`` alone of a long
    sequence ``x`` ([T, D]): every token's K, V and index keys, the asked
    rows' queries, their index scores and selection (or ``selection``, [len(
    rows), T] bool, GIVEN in its place), attention a KV head and a block of
    rows at a time. ``log_probs`` float32 [len(rows), V]; ``keep`` the
    selection used [len(rows), T]; ``margin`` the reference's own ``topk``-th
    score less the next, a row; ``scores`` its index scores. ``fault``
    (``rehearse/check_seeds_keye.py``'s controls): constants put in place of
    the configuration's (``topk``, ``relu``, ``sections``, ``index_rope``)."""
    rows = jnp.asarray(list(rows), jnp.int32)
    own = selection is None
    given = jnp.zeros((rows.shape[0], x.shape[0]), bool) if own else jnp.asarray(selection)
    c = {**block_constants(hf), **(fault or {})}
    with jax.default_matmul_precision("highest"):
        out = _attn_rows_of(params, jnp.asarray(x), rows, given, int(layer), c=_static(c), own=own)
    log_probs_, keep, margin, scores = (np.asarray(o) for o in out)
    return {"log_probs": log_probs_, "keep": keep, "margin": margin, "scores": scores}
