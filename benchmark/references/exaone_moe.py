"""Plain float32 reference of ``exaone_moe`` (K-EXAONE-236B-A23B).

Written from the published ``config.json``'s keys and the family's
description, in straightforward ``jax.numpy``: no kernel, no cache, no
paging, no batching; the sliding mask is written as a mask. One sequence,
positions ``t = 0..T-1``, stream ``x`` ``[T, D]``, ``RMS_w`` an RMS norm with
scale ``w`` and eps ``rms_norm_eps``:

- a layer: ``h = x + Attn_l(RMS_input(x))``, ``y = h + FF_l(RMS_post_attn(h))``
  (two norms a layer, each BEFORE its half); logits ``RMS_final(x_L) @ lm_head``;
- ``Attn_l(u)``: ``q = u W_q`` -> ``[T, H, d]``, ``k = u W_k``, ``v = u W_v`` ->
  ``[T, Hkv, d]``, no biases; ``q = RMS_qnorm(q)``, ``k = RMS_knorm(k)`` a head
  at a time over its ``d`` values, before any rotation.
  ``layer_types[l] == "sliding_attention"``: ``q, k = RoPE_t(q, k)`` over all
  ``d`` values (base ``rope_parameters.rope_theta``, no scaling, half-split
  pairs ``(i, i + d/2)``: HF's ``rotate_half``), and query ``t`` sees the keys
  ``t - sliding_window < s <= t`` (``sliding_window`` keys with its own).
  ``"full_attention"``: NO rotation at all and ``s <= t``. Scores
  ``q . k / sqrt(d)``, softmax in float32, query head ``h`` reads KV head
  ``h // (H / Hkv)``; ``concat_heads(P v) W_o``;
- ``FF_l`` of a dense layer (the first ``first_k_dense_replace``): one SwiGLU
  of ``intermediate_size``. Of a sparse layer: ``g = sigmoid(u W_r)`` over all
  ``num_experts_global`` experts in float32; the experts are the
  ``num_experts_per_tok`` largest of ``g + e_score_correction_bias``
  (``n_group = topk_group = 1``: no group limit; a tie to the lower id as
  ``lax.top_k``); ``w_k = g_k / (sum of the chosen g + 1e-20) *
  routed_scaling_factor`` (the UNBIASED scores); ``FF(u) = SwiGLU_shared(u) +
  sum_k w_k SwiGLU_{e_k}(u)``, each of ``moe_intermediate_size``.

THE SHARE. The configuration is one rank's share of an expert-parallel
layer: the arrays hold experts ``[first_routed_expert, first_routed_expert +
num_experts)`` of the ``num_experts_global`` the router scores, and the sum
over the chosen runs over those of them that are held, each with its weight
as normalised over ALL the chosen (what this rank would hand to the
exchange; the shared expert is added once). With every expert held that is
the whole layer. The vocabulary is the slice the arrays hold.

Departures from the published code, each deliberate: every matrix product
runs in float32 at ``jax.default_matmul_precision("highest")`` on the
bfloat16 weights as served, where the published code computes in bfloat16;
the held experts are evaluated densely (every held expert on every token,
weighted by 0 where not chosen), which is the same sum; attention is
computed a block of ``QUERY_BLOCK`` queries at a time against the keys that
block can see, which is the same attention and lets 32 k positions fit;
a sequence is filled up to one of ``LENGTHS`` behind its last token and a
block of rows to one shape, so that few programs are compiled.
ASSUMED, because the catalog's row has no key for them
(``benchmark/configs/k-exaone-*.json``, ``assumed``): the two norms stand
before each half (the published ``exaone_moe`` decoder layer is
DeepSeek-V3's / Qwen2-MoE's; EXAONE 4.0 norms each half's output instead);
rotary on the sliding layers only (EXAONE 4.0's published rule for its
hybrid models, which K-EXAONE's report keeps).

The weights are the engine's own arrays (``models/llama.py::init_params``:
the two norms and attention's tensors over all layers, ``dense_*`` over the
dense layers, the router with its bias, the shared expert and the held
experts over the routed layers), used in place a layer and an expert at a
time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# A sequence is filled up to the first of these lengths that holds it (beyond
# them to a multiple of FFN_BLOCK): the probes' 64 tokens; a CPU rehearsal's
# 512; the served long probe's ~8,210 and the timed read's 4,104; the timed
# read's 16,008. Every length is a set of compiled programs at ``highest``
# precision, which is most of what the comparison costs a machine with
# nothing cached.
LENGTHS = (128, 1024, 9216, 16384)
LOGPROB_TOL = 0.5  # ``references/openpangu_moe.py``'s: no configuration asks
QUERY_BLOCK = 1024  # queries attention takes at a time, at the most
SCORE_BYTES = 256 << 20  # and no more than hold their scores in this
FFN_BLOCK = 1024  # rows a feed-forward takes at a time (18,432 wide in float32)


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def _rope(x, positions, theta):
    """Half-split rotary over all of the last axis: pairs ``(i, i + d/2)``."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None]  # [T, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ _f32(w_gate)) * (h @ _f32(w_up))) @ _f32(w_down)


def block_constants(hf: dict) -> dict:
    """What the equations read of the configuration's keys."""
    rope = hf.get("rope_parameters") or {}
    held = int(hf["num_experts"])
    types = tuple(hf["layer_types"])
    return {
        "layers": int(hf["num_hidden_layers"]),
        "heads": int(hf["num_attention_heads"]),
        "kv_heads": int(hf["num_key_value_heads"]),
        "head_dim": int(hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]),
        "eps": float(hf.get("rms_norm_eps", 1e-5)),
        "theta": float(rope.get("rope_theta") or hf.get("rope_theta", 10000.0)),
        # which layers mask by the width, and which turn q and k by their
        # positions: the same layers (two entries, so that a control can
        # part them)
        "sliding": tuple(t == "sliding_attention" for t in types),
        "rotary": tuple(t == "sliding_attention" for t in types),
        "width": int(hf.get("sliding_window") or 0),
        "dense": int(hf.get("first_k_dense_replace", 0)),
        "top_k": int(hf["num_experts_per_tok"]),
        "norm": bool(hf.get("norm_topk_prob", True)),
        "scale": float(hf.get("routed_scaling_factor", 1.0)),
        "held": held,
        "routed": int(hf.get("num_experts_global", held)),
        "first": int(hf.get("first_routed_expert", 0)),
        "shared": int(hf.get("num_shared_experts", 0) or 0),
    }


def _static(c: dict):
    return tuple(sorted(c.items()))


# ----------------------------------------------------------------- attention
def _q(u, lp, c, rotary: bool, positions):
    """The queries of the normed rows ``u`` at ``positions``: [T, H, d]."""
    q = (u @ _f32(lp["wq"])).reshape(u.shape[0], c["heads"], c["head_dim"])
    q = _rms_norm(q, lp["q_norm"], c["eps"])
    # a full-attention layer has no positional term
    return _rope(q, positions, c["theta"]) if rotary else q


def _kv(u, lp, c, rotary: bool, positions):
    """Their keys and values: [T, Hkv, d] each."""
    T, d = u.shape[0], c["head_dim"]
    k = _rms_norm((u @ _f32(lp["wk"])).reshape(T, c["kv_heads"], d), lp["k_norm"], c["eps"])
    if rotary:
        k = _rope(k, positions, c["theta"])
    return k, (u @ _f32(lp["wv"])).reshape(T, c["kv_heads"], d)


def _qkv(u, lp, c, rotary: bool, positions):
    return _q(u, lp, c, rotary, positions), *_kv(u, lp, c, rotary, positions)


def _attend(q, q_pos, k, v, start, c, sliding: bool, size: int):
    """``q`` [Q, H, d] at positions ``q_pos`` against the ``size`` keys and
    values from position ``start`` on (``k``, ``v`` [T, Hkv, d]): the mask
    written as a mask. The keys a block reads are cut to a fixed length, not
    to what it can see."""
    start = jnp.clip(start, 0, k.shape[0] - size)
    k = jax.lax.dynamic_slice_in_dim(k, start, size)
    v = jax.lax.dynamic_slice_in_dim(v, start, size)
    k_pos = start + jnp.arange(size, dtype=jnp.int32)
    g = c["heads"] // c["kv_heads"]
    qh = q.reshape(q.shape[0], c["kv_heads"], g, -1)
    s = jnp.einsum("qhgd,shd->hgqs", qh, k) * c["head_dim"] ** -0.5
    seen = k_pos[None, :] <= q_pos[:, None]
    if sliding:
        seen = seen & (k_pos[None, :] > q_pos[:, None] - c["width"])
    p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hgqs,shd->qhgd", p, v)
    return o.reshape(q.shape[0], -1)


# Every step below is ONE compiled program a sequence length (and block):
# taken an operation at a time, a comparison compiled some 400 of them.
@functools.partial(jax.jit, static_argnames=("c", "rotary"))
def _kv_of(x, lp, *, c, rotary: bool):
    """Every token's keys and values of the stream ``x`` [T, D]."""
    c = dict(c)
    u = _rms_norm(x, lp["attn_norm"], c["eps"])
    return _kv(u, lp, c, rotary, jnp.arange(x.shape[0], dtype=jnp.int32))


@functools.partial(jax.jit, static_argnames=("c", "sliding", "rotary", "size"))
def _attn_block(x, lp, k, v, at, start, *, c, sliding: bool, rotary: bool, size: int):
    """``x[at] + Attn(RMS(x))[at]`` for one block of queries ``at``."""
    c = dict(c)
    xa = x[at]
    q = _q(_rms_norm(xa, lp["attn_norm"], c["eps"]), lp, c, rotary, at)
    return xa + _attend(q, at, k, v, start, c, sliding, size) @ _f32(lp["wo"])


def _attn_half_rows(x, lp, c, layer: int, rows):
    """``x[rows] + Attn_layer(RMS(x))[rows]`` at the positions ``rows``
    (ascending) of the stream ``x`` [T, D]: every token's keys and values,
    the asked rows' queries a block at a time: a full layer's against every
    key (the mask hides what lies ahead), a sliding layer's against the
    ``block + width - 1`` keys from its first query's first visible one on.
    The last block is filled up with copies of its last query, which are
    dropped."""
    T = x.shape[0]
    sliding, rotary = c["sliding"][layer], c["rotary"][layer]
    k, v = _kv_of(x, lp, c=_static(c), rotary=rotary)
    rows = np.asarray(rows, np.int32)
    # a block's scores [H, block, keys] float32 stay under SCORE_BYTES
    keys = min(T, c["width"] + QUERY_BLOCK) if sliding else T
    block = max(8, min(QUERY_BLOCK, SCORE_BYTES // (4 * c["heads"] * keys)))
    block = min(block, -(-len(rows) // 8) * 8)
    size = min(T, block + c["width"] - 1) if sliding else T

    def one(at, start, size):
        return _attn_block(
            x, lp, k, v, jnp.asarray(at), start, c=_static(c), sliding=sliding,
            rotary=rotary, size=size,
        )

    outs = []
    for a in range(0, len(rows), block):
        at = rows[a : a + block]
        n = len(at)
        at = np.concatenate([at, np.full(block - n, at[-1], np.int32)])
        if sliding and int(at[-1]) - int(at[0]) >= block:  # scattered rows: one at a time
            outs.extend(
                one([r], max(int(r) - c["width"] + 1, 0), min(T, c["width"])) for r in at[:n]
            )
            continue
        start = max(int(at[0]) - c["width"] + 1, 0) if sliding else 0
        outs.append(one(at, start, size)[:n])
    return jnp.concatenate(outs)


_ATTN = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm")
_DENSE = {"w_gate": "dense_gate", "w_up": "dense_up", "w_down": "dense_down"}
_ROUTED = ("router", "router_bias", "shared_gate", "shared_up", "shared_down",
           "w_gate", "w_up", "w_down")


def _attn_params(layers, i: int) -> dict:
    return {k: layers[k][i] for k in _ATTN}


def _ffn_params(layers, c: dict, i: int) -> dict:
    lp = {"mlp_norm": layers["mlp_norm"][i]}
    if i < c["dense"]:
        lp.update((k, layers[src][i]) for k, src in _DENSE.items())
    else:
        lp.update((k, layers[k][i - c["dense"]]) for k in _ROUTED if k in layers)
    return lp


# -------------------------------------------------------------- feed-forward
def _route(g, bias, *, top_k: int, norm: bool, scale: float):
    """The chosen experts' ids [N, k] and weights [N, k] of sigmoid scores
    ``g`` [N, E], and the margin between the k-th and the (k+1)-th biased
    score."""
    biased = g + bias[None]
    top, ids = jax.lax.top_k(biased, top_k + 1)
    margin = top[:, top_k - 1] - top[:, top_k]
    ids = ids[:, :top_k]
    w = jnp.take_along_axis(g, ids, axis=1)
    if norm:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return ids, w * scale, margin


def _ffn_sum(h, lp, c, routed: bool):
    """The feed-forward's sum on the normed rows ``h``: (sum, margin, how
    many of a row's chosen experts are held)."""
    if not routed:
        return _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"]), None, None
    g = jax.nn.sigmoid(h @ _f32(lp["router"]))
    ids, w, margin = _route(
        g, _f32(lp["router_bias"]), top_k=c["top_k"], norm=c["norm"], scale=c["scale"],
    )
    out = jnp.zeros_like(h)
    if c["shared"]:
        out = out + _swiglu(h, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    here = 0
    for e in range(c["held"]):  # densely: weight 0 where not chosen
        w_e = jnp.sum(jnp.where(ids == c["first"] + e, w, 0.0), axis=1, keepdims=True)
        here = here + jnp.sum(ids == c["first"] + e, axis=1)
        out = out + w_e * _swiglu(h, lp["w_gate"][e], lp["w_up"][e], lp["w_down"][e])
    return out, margin, here


@functools.partial(jax.jit, static_argnames=("c", "routed", "normed"))
def _ffn_half_of(lp, x, *, c, routed, normed=True):
    c = dict(c)
    x = _f32(x)
    h = _rms_norm(x, lp["mlp_norm"], c["eps"])
    y, margin, here = _ffn_sum(h, lp, c, routed)
    return (x + y if normed else y), margin, here


def _ffn_rows(lp, x, c: dict, routed: bool, normed: bool = True):
    """``_ffn_half_of`` on the rows ``x`` [N, D] in blocks of ONE shape a
    call (``LENGTHS[0]`` rows where that holds them all, else FFN_BLOCK; the
    last block filled up with rows of zeros, which are dropped): (the rows,
    the margins, the held counts), the last two None for a dense layer."""
    n = x.shape[0]
    block = LENGTHS[0] if n <= LENGTHS[0] else FFN_BLOCK
    x = jnp.pad(jnp.asarray(x), ((0, -n % block), (0, 0)))
    outs = [
        _ffn_half_of(lp, x[a : a + block], c=_static(c), routed=routed, normed=normed)
        for a in range(0, x.shape[0], block)
    ]
    return tuple(
        None if part[0] is None else jnp.concatenate(part)[:n] for part in zip(*outs)
    )


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_of(params, x, rows, *, eps):
    x = _rms_norm(_f32(x[rows]), params["final_norm"], eps)
    return jax.nn.log_softmax(x @ _f32(params["lm_head"]), axis=-1)


@jax.jit
def _embedded(params, ids):
    return _f32(params["embed"][ids])


# -------------------------------------------------------------- whole model
def _forward(params, ids, rows, c: dict, at: tuple = ()):
    """Embedding, the layers one after another, the head at ``rows``; and
    the hidden state entering each layer in ``at`` and that layer's second
    half. Every layer computes every row."""
    x = _embedded(params, ids)
    every = np.arange(x.shape[0])
    entering, before_ffn = [], []
    layers = params["layers"]
    for i in range(c["layers"]):
        mid = _attn_half_rows(x, _attn_params(layers, i), c, i, every)
        if i in at:
            entering.append(x)
            before_ffn.append(mid)
        x = _ffn_rows(_ffn_params(layers, c, i), mid, c, i >= c["dense"])[0]
    return _head_of(params, x, jnp.asarray(rows), eps=c["eps"]), entering, before_ffn


def _length(n: int) -> int:
    return next((m for m in LENGTHS if m >= n), -(-n // FFN_BLOCK) * FFN_BLOCK)


def _padded(token_ids) -> list[int]:
    ids = list(token_ids)
    return ids + [0] * (_length(len(ids)) - len(ids))


def _padded_rows(x):
    """The stream ``x`` [T, D] in float32, filled up with rows of zeros
    (whose keys and values are zeros, behind every real query)."""
    x = _f32(x)
    return jnp.pad(x, ((0, _length(x.shape[0]) - x.shape[0]), (0, 0)))


def log_probs(params: dict, hf: dict, token_ids, rows) -> np.ndarray:
    """log-softmax over the vocabulary at positions ``rows`` of the
    sequence ``token_ids`` (filled up to one of LENGTHS; the filling follows
    the real tokens, so under the causal mask it changes nothing before it)."""
    with jax.default_matmul_precision("highest"):
        out = _forward(
            params, jnp.asarray(_padded(token_ids), jnp.int32), list(rows),
            block_constants(hf),
        )[0]
    return np.asarray(out)


def hidden_states(params: dict, hf: dict, token_ids, layers) -> tuple[np.ndarray, np.ndarray]:
    """For the sequence ``token_ids``, the float32 hidden state entering
    each layer in ``layers`` (ascending) and the one entering that layer's
    second half: two arrays [len(layers), len(token_ids), D]."""
    n = len(list(token_ids))
    with jax.default_matmul_precision("highest"):
        _, entering, before_ffn = _forward(
            params, jnp.asarray(_padded(token_ids), jnp.int32), [0],
            block_constants(hf), at=tuple(int(l) for l in layers),
        )
    return (
        np.stack([np.asarray(e)[:n] for e in entering]),
        np.stack([np.asarray(b)[:n] for b in before_ffn]),
    )


def ffn_half(params: dict, hf: dict, layer: int, x, normed: bool = True):
    """The second half of layer ``layer`` on the rows ``x`` ([N, D], any
    float type, taken as it is): (the rows after the residual add, float32
    [N, D]; the router's margin a row, or None for a dense layer; the share
    of the chosen experts that are held). ``normed`` False: the
    feed-forward's sum alone, without the residual (what the shares of an
    expert-parallel layer add up to)."""
    c = block_constants(hf)
    with jax.default_matmul_precision("highest"):
        y, margin, here = _ffn_rows(
            _ffn_params(params["layers"], c, int(layer)), x, c,
            int(layer) >= c["dense"], normed,
        )
    return (
        np.asarray(y), None if margin is None else np.asarray(margin),
        None if here is None else float(np.mean(np.asarray(here))) / c["top_k"],
    )


def attn_half_and_head(params: dict, hf: dict, layer: int, x) -> np.ndarray:
    """Layer ``layer``'s first half on the sequence ``x`` ([T, D]), then the
    final norm and the head: log-probabilities float32 [T, V]."""
    return attn_rows_and_head(params, hf, (int(layer),), x, range(len(x)))


def kv_rows(params: dict, hf: dict, layer: int, x) -> tuple[np.ndarray, np.ndarray]:
    """What layer ``layer`` caches of the sequence ``x`` ([T, D]) entering
    it: K after its norm and, on a sliding layer, its rotation (none on a
    full layer), and V; float32 [T, Hkv * d] each, heads side by side."""
    c = block_constants(hf)
    n = len(x)
    with jax.default_matmul_precision("highest"):
        k, v = _kv_of(
            _padded_rows(x), _attn_params(params["layers"], int(layer)), c=_static(c),
            rotary=c["rotary"][int(layer)],
        )
    return np.asarray(k)[:n].reshape(n, -1), np.asarray(v)[:n].reshape(n, -1)


def attn_half(params: dict, hf: dict, layer: int, x) -> np.ndarray:
    """The stream ``x`` ([T, D]) after layer ``layer``'s first half: float32
    [T, D] (what a layer stacked behind it, with no feed-forward between,
    is given)."""
    c = block_constants(hf)
    n, x = len(x), _padded_rows(x)
    with jax.default_matmul_precision("highest"):
        return np.asarray(_attn_half_rows(
            x, _attn_params(params["layers"], int(layer)), c, int(layer),
            np.arange(x.shape[0]),
        ))[:n]


def attn_rows_and_head(params: dict, hf: dict, layers, x, rows) -> np.ndarray:
    """The first halves of ``layers`` one after another (no feed-forward
    between them) on the long sequence ``x`` ([T, D]), then the final norm
    and the head at the positions ``rows`` (ascending): every layer but the
    last computes every position, the last the asked rows alone. float32
    [len(rows), V]."""
    c = block_constants(hf)
    x = _padded_rows(x)
    rows = [int(r) for r in rows]
    every = np.arange(x.shape[0])
    layers = [int(l) for l in layers]
    with jax.default_matmul_precision("highest"):
        for n, i in enumerate(layers):
            last = n == len(layers) - 1
            x = _attn_half_rows(
                x, _attn_params(params["layers"], i), c, i,
                np.asarray(rows) if last else every,
            )
        return np.asarray(_head_of(params, x, jnp.arange(len(rows)), eps=c["eps"]))
