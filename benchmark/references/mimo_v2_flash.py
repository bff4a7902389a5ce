"""Plain float32 reference of ``mimo_v2_flash`` (MiMo-V2-Flash).

Written from the published ``config.json``'s keys and the family's
description, in straightforward ``jax.numpy``: no kernel, no cache, no
paging, no batching; the sliding mask is written as a mask and the sink as
one more column of the softmax. One sequence, positions ``t = 0..T-1``,
stream ``x`` ``[T, D]``, ``RMS_w`` an RMS norm with scale ``w`` and eps
``layernorm_epsilon``:

- a layer: ``h = x + Attn_l(RMS_input(x))``, ``y = h + FF_l(RMS_post_attn(h))``
  (two norms a layer, each BEFORE its half); logits ``RMS_final(x_L) @ lm_head``;
- layer ``l`` is FULL attention where ``hybrid_layer_pattern[l] == 0`` and
  SLIDING where 1. ``Attn_l(u)``: ``q = u W_q`` -> ``[T, H, d_qk]``, ``k = u
  W_k`` -> ``[T, Hkv, d_qk]``, ``v = attention_value_scale * (u W_v)`` -> ``[T,
  Hkv, d_v]``, no biases, no per-head norm; ``Hkv`` is ``num_key_value_heads``
  in a full layer and ``swa_num_key_value_heads`` in a sliding one (``H``,
  ``d_qk = head_dim`` and ``d_v = v_head_dim`` are the same in both). The
  first ``r = int(head_dim * partial_rotary_factor)`` values of each head of
  q and k turn by the position (half-split pairs ``(i, i + r/2)``, HF's
  ``rotate_half`` over the leading ``r``), base ``rope_theta`` in a full layer
  and ``swa_rope_theta`` in a sliding one, no scaling; the other ``d_qk - r``
  stay. Scores ``q . k / sqrt(d_qk)``; a full layer's query ``t`` sees ``s <=
  t``, a sliding layer's ``t - sliding_window < s <= t``; query head ``h``
  reads KV head ``h // (H / Hkv)``. Where the layer's form has a sink
  (``add_swa_attention_sink_bias`` / ``add_full_attention_sink_bias``) one
  learned float32 logit ``b_h`` a query head joins the softmax as a further
  column that carries no value: ``p[t, s] = exp(s[t, s] - m) / (sum_s
  exp(s[t, s] - m) + exp(b_h - m))``. ``concat_heads(P v) W_o`` (``H * d_v``
  rows);
- ``FF_l`` of a dense layer (the leading layers whose ``moe_layer_freq`` is
  0): one SwiGLU of ``intermediate_size``. Of a routed layer: ``g = sigmoid(u
  W_r)`` over all ``n_routed_experts_global`` experts in float32; the experts
  are the ``num_experts_per_tok`` largest of ``g + e_score_correction_bias``
  (``n_group = topk_group = 1``: no group limit; a tie to the lower id as
  ``lax.top_k``); ``w_k = g_k / (sum of the chosen g + 1e-20) *
  routed_scaling_factor`` (null: 1; the UNBIASED scores); ``FF(u) = sum_k w_k
  SwiGLU_{e_k}(u)``, each of ``moe_intermediate_size``; NO shared expert
  (``n_shared_experts`` null).

THE SHARE. The configuration is one rank's share of an expert-parallel
layer: the arrays hold experts ``[first_routed_expert, first_routed_expert +
n_routed_experts)`` of the ``n_routed_experts_global`` the router scores, and
the sum over the chosen runs over those of them that are held, each with its
weight as normalised over ALL the chosen (what this rank would hand to the
exchange). With every expert held that is the whole layer. The vocabulary is
the slice the arrays hold.

Departures from the published code, each deliberate: every matrix product
runs in float32 at ``jax.default_matmul_precision("highest")`` on the
bfloat16 weights as served, where the published code computes in bfloat16;
the held experts are evaluated densely (every held expert on every token,
weighted by 0 where not chosen), which is the same sum; attention is
computed a block of ``QUERY_BLOCK`` queries at a time against the keys that
block can see, which is the same attention and lets 16 k positions fit;
a sequence is filled up to one of ``LENGTHS`` behind its last token and a
block of rows to one shape, so that few programs are compiled (every step
is one compiled program a sequence length).
ASSUMED, because the catalog's row has no key for them
(``benchmark/configs/mimo-v2-flash-*.json``, ``assumed``): which values of
a head turn (the leading ``r``) and how they pair; that the value scale
multiplies the values before the weighted sum; the window's convention
(``sliding_window`` keys with the query's own); no per-head q/k norm; two
pre-norms a layer.

The weights are the engine's own arrays (``models/llama.py::init_params``:
the two norms, ``wq`` and ``wo`` over all layers; ``wk`` / ``wv`` over the
full layers and ``swa_wk`` / ``swa_wv`` over the sliding ones; ``sink`` over
the layers whose form has one; ``dense_*`` over the dense layers, the router
with its bias and the held experts over the routed layers), used in place a
layer and an expert at a time.
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


def _rope(x, positions, theta, r: int):
    """Half-split rotary over the first ``r`` values of the last axis: pairs
    ``(i, i + r/2)``; the values behind them stay."""
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = positions.astype(jnp.float32)[:, None] * inv[None]  # [T, r/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : r // 2], x[..., r // 2 : r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., r:]], axis=-1)


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ _f32(w_gate)) * (h @ _f32(w_up))) @ _f32(w_down)


def block_constants(hf: dict) -> dict:
    """What the equations read of the configuration's keys."""
    held = int(hf["n_routed_experts"])
    sliding = tuple(bool(p) for p in hf["hybrid_layer_pattern"])
    freq = list(hf["moe_layer_freq"])
    d = int(hf["head_dim"])
    scale = hf.get("routed_scaling_factor")
    has_sink = {
        True: bool(hf.get("add_swa_attention_sink_bias", False)),
        False: bool(hf.get("add_full_attention_sink_bias", False)),
    }
    return {
        "layers": int(hf["num_hidden_layers"]),
        "heads": int(hf["num_attention_heads"]),
        # by the layer's form: (full, sliding)
        "kv_heads": (
            int(hf["num_key_value_heads"]),
            int(hf.get("swa_num_key_value_heads", hf["num_key_value_heads"])),
        ),
        "theta": (
            float(hf.get("rope_theta", 10000.0)),
            float(hf.get("swa_rope_theta", hf.get("rope_theta", 10000.0))),
        ),
        "head_dim": d,
        "v_dim": int(hf.get("v_head_dim", d)),
        "rotary": int(d * float(hf.get("partial_rotary_factor", 1.0))),
        "v_scale": float(hf.get("attention_value_scale") or 1.0),
        "eps": float(hf.get("layernorm_epsilon", 1e-5)),
        # which layers mask by the width, and which have a sink (two
        # entries, so that a control can part them)
        "sliding": sliding,
        "sink": tuple(has_sink[s] for s in sliding),
        "width": int(hf.get("sliding_window") or 0),
        "dense": freq.index(1) if 1 in freq else len(freq),
        "top_k": int(hf["num_experts_per_tok"]),
        "norm": bool(hf.get("norm_topk_prob", True)),
        "scale": 1.0 if scale is None else float(scale),
        "held": held,
        "routed": int(hf.get("n_routed_experts_global", held)),
        "first": int(hf.get("first_routed_expert", 0)),
        "shared": int(hf.get("n_shared_experts") or 0),
    }


def _static(c: dict):
    return tuple(sorted(c.items()))


# ----------------------------------------------------------------- attention
def _q(u, lp, c, kind: int, positions):
    """The queries of the normed rows ``u`` at ``positions``: [T, H, d_qk];
    ``kind`` 1: a sliding layer (its rotary base), 0: a full one."""
    q = (u @ _f32(lp["wq"])).reshape(u.shape[0], c["heads"], c["head_dim"])
    return _rope(q, positions, c["theta"][kind], c["rotary"])


def _kv(u, lp, c, kind: int, positions):
    """Their keys [T, Hkv, d_qk] and values [T, Hkv, d_v], the values
    scaled: what a layer caches."""
    T = u.shape[0]
    k = (u @ _f32(lp["wk"])).reshape(T, c["kv_heads"][kind], c["head_dim"])
    v = (u @ _f32(lp["wv"])).reshape(T, c["kv_heads"][kind], c["v_dim"])
    return _rope(k, positions, c["theta"][kind], c["rotary"]), c["v_scale"] * v


def _qkv(u, lp, c, kind: int, positions):
    return _q(u, lp, c, kind, positions), *_kv(u, lp, c, kind, positions)


def _attend(q, q_pos, k, v, start, c, sliding: bool, size: int, sink=None):
    """``q`` [Q, H, d_qk] at positions ``q_pos`` against the ``size`` keys
    and values from position ``start`` on (``k`` [T, Hkv, d_qk], ``v`` [T,
    Hkv, d_v]): the mask written as a mask, the sink ([H], or None) as one
    more column of the softmax that no value stands behind. The keys a block
    reads are cut to a fixed length, not to what it can see."""
    start = jnp.clip(start, 0, k.shape[0] - size)
    k = jax.lax.dynamic_slice_in_dim(k, start, size)
    v = jax.lax.dynamic_slice_in_dim(v, start, size)
    k_pos = start + jnp.arange(size, dtype=jnp.int32)
    kv_heads = k.shape[1]
    g = c["heads"] // kv_heads
    qh = q.reshape(q.shape[0], kv_heads, g, -1)
    s = jnp.einsum("qhgd,shd->hgqs", qh, k) * c["head_dim"] ** -0.5
    seen = k_pos[None, :] <= q_pos[:, None]
    if sliding:
        seen = seen & (k_pos[None, :] > q_pos[:, None] - c["width"])
    s = jnp.where(seen[None, None], s, -jnp.inf)
    if sink is not None:
        column = jnp.broadcast_to(
            _f32(sink).reshape(kv_heads, g, 1, 1), (*s.shape[:-1], 1)
        )
        s = jnp.concatenate([s, column], axis=-1)
    p = jax.nn.softmax(s, axis=-1)[..., :size]
    o = jnp.einsum("hgqs,shd->qhgd", p, v)
    return o.reshape(q.shape[0], -1)


# Every step below is ONE compiled program a sequence length (and block):
# taken an operation at a time, a comparison compiled some 400 of them.
@functools.partial(jax.jit, static_argnames=("c", "kind"))
def _kv_of(x, lp, *, c, kind: int):
    """Every token's keys and values of the stream ``x`` [T, D]."""
    c = dict(c)
    u = _rms_norm(x, lp["attn_norm"], c["eps"])
    return _kv(u, lp, c, kind, jnp.arange(x.shape[0], dtype=jnp.int32))


@functools.partial(jax.jit, static_argnames=("c", "sliding", "size"))
def _attn_block(x, lp, k, v, at, start, *, c, sliding: bool, size: int):
    """``x[at] + Attn(RMS(x))[at]`` for one block of queries ``at``; the
    layer has a sink where ``lp`` carries one."""
    c = dict(c)
    xa = x[at]
    q = _q(_rms_norm(xa, lp["attn_norm"], c["eps"]), lp, c, int(sliding), at)
    o = _attend(q, at, k, v, start, c, sliding, size, lp.get("sink"))
    return xa + o @ _f32(lp["wo"])


def _attn_half_rows(x, lp, c, layer: int, rows):
    """``x[rows] + Attn_layer(RMS(x))[rows]`` at the positions ``rows``
    (ascending) of the stream ``x`` [T, D]: every token's keys and values,
    the asked rows' queries a block at a time: a full layer's against every
    key (the mask hides what lies ahead), a sliding layer's against the
    ``block + width - 1`` keys from its first query's first visible one on.
    The last block is filled up with copies of its last query, which are
    dropped."""
    T = x.shape[0]
    sliding = c["sliding"][layer]
    k, v = _kv_of(x, lp, c=_static(c), kind=int(sliding))
    rows = np.asarray(rows, np.int32)
    # a block's scores [H, block, keys] float32 stay under SCORE_BYTES
    keys = min(T, c["width"] + QUERY_BLOCK) if sliding else T
    block = max(8, min(QUERY_BLOCK, SCORE_BYTES // (4 * c["heads"] * keys)))
    block = min(block, -(-len(rows) // 8) * 8)
    size = min(T, block + c["width"] - 1) if sliding else T

    def one(at, start, size):
        return _attn_block(
            x, lp, k, v, jnp.asarray(at), start, c=_static(c), sliding=sliding,
            size=size,
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


_DENSE = {"w_gate": "dense_gate", "w_up": "dense_up", "w_down": "dense_down"}
_ROUTED = ("router", "router_bias", "shared_gate", "shared_up", "shared_down",
           "w_gate", "w_up", "w_down")


def _attn_params(layers, c: dict, i: int) -> dict:
    """Layer ``i``'s attention tensors out of the stacks: the norm, ``wq``
    and ``wo`` at ``i``; ``wk`` / ``wv`` at its ordinal among the layers of
    its form, in that form's stack, where the forms' KV heads differ (one
    stack over all layers where not); the sink at its ordinal among the
    layers that have one."""
    lp = {k: layers[k][i] for k in ("attn_norm", "wq", "wo")}
    sliding = c["sliding"][i]
    if "swa_wk" in layers:
        nth = c["sliding"][:i].count(sliding)
        stack = "swa_" if sliding else ""
        lp.update(wk=layers[stack + "wk"][nth], wv=layers[stack + "wv"][nth])
    else:
        lp.update(wk=layers["wk"][i], wv=layers["wv"][i])
    if c["sink"][i]:
        lp["sink"] = layers["sink"][sum(c["sink"][:i])]
    return lp


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
        mid = _attn_half_rows(x, _attn_params(layers, c, i), c, i, every)
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
    it: K after the rotation of its leading values at the base of the
    layer's form, and V scaled; float32 [T, Hkv * d_qk] and [T, Hkv * d_v],
    heads side by side, ``Hkv`` the form's."""
    c = block_constants(hf)
    n = len(x)
    with jax.default_matmul_precision("highest"):
        k, v = _kv_of(
            _padded_rows(x), _attn_params(params["layers"], c, int(layer)), c=_static(c),
            kind=int(c["sliding"][int(layer)]),
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
            x, _attn_params(params["layers"], c, int(layer)), c, int(layer),
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
                x, _attn_params(params["layers"], c, i), c, i,
                np.asarray(rows) if last else every,
            )
        return np.asarray(_head_of(params, x, jnp.arange(len(rows)), eps=c["eps"]))
