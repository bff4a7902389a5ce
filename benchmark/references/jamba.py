"""Plain float32 reference of AI21's ``jamba`` (AI21-Jamba2-3B).

Written from the published description (the ``config.json`` named in the
configuration's file and the family's ``JambaMambaMixer`` /
``JambaAttentionDecoderLayer``), in straightforward ``jax.numpy``: no
kernel, no cache, no state carried from call to call, no batching, no
chunking, no paging. One sequence, positions 0..T-1. ``u`` is a
half-layer's normed input, ``D`` the hidden size, ``E = mamba_expand * D``
the inner width, ``S = mamba_d_state``, ``R = mamba_dt_rank``,
``K = mamba_d_conv``:

- layer ``i``: ``r = h + Op_i(RMSNorm(h, input_layernorm))``,
  ``h' = r + MLP(RMSNorm(r, pre_ff_layernorm))``; ``Op_i`` is attention
  where ``i % attn_layer_period == attn_layer_offset`` and the Mamba mixer
  otherwise; ``MLP(u) = (silu(u W_gate) * (u W_up)) W_down``; logits
  ``RMSNorm(h_L, final_layernorm) @ embed.T`` (tied head);
- attention: ``q = u W_q`` (heads of ``head_dim``), ``k = u W_k``,
  ``v = u W_v`` (``num_key_value_heads`` heads), no bias, no q/k norm,
  **no rotary or other positional term**; causal
  ``softmax(q k^T / sqrt(head_dim)) v``; ``W_o``;
- Mamba mixer: ``[x, g] = split2(u W_in)`` (D -> 2E, no bias);
  ``c_t = silu(b_conv + sum_{j<K} w_conv[j] * x_{t-(K-1)+j})`` (depthwise,
  causal, zeros before the sequence's start);
  ``[tau, B, C] = split(c_t W_x)`` (E -> R + S + S);
  ``tau = RMSNorm(tau, dt_layernorm)``, ``B = RMSNorm(B, b_layernorm)``,
  ``C = RMSNorm(C, c_layernorm)`` (Jamba's inner norms, ``rms_norm_eps``);
  ``dt_t = softplus(tau W_dt + b_dt)`` (R -> E); ``A = -exp(A_log)``;
  ``h_t = exp(dt_t A) * h_{t-1} + (dt_t * c_t) B_t`` (``[E, S]``,
  ``h_{-1} = 0``), a ``lax.scan`` a token at a time;
  ``y_t = h_t C_t + D * c_t``; output ``(y_t * silu(g_t)) W_out``.

Departures from the published code, each deliberate: every matrix product
runs in float32 at ``jax.default_matmul_precision("highest")`` on the
bfloat16 weights as served, where the published code computes in bfloat16
(its scan alone in float32); the scan is the recurrence itself and not
the published kernel's chunked form (the same numbers); ``A_log`` is held
``[S, E]``, the published tensor transposed. ASSUMED, because the
catalog's row does not carry them and the family's published classes do:
``head_dim = hidden_size / num_attention_heads``; no positional term in
attention; the three inner norms; ``mamba_dt_rank`` given as a number.

The weights are the engine's own arrays, stacked by the kind of layer
that has them (``models/llama.py::init_params``: attention's over the
attention layers, ``mamba_*`` over the Mamba layers, the norms and the
MLP over all), used in place a layer at a time, so the reference fits
beside a model that fills the chip. Which stack and which index a layer
reads is worked out here, from ``attn_layer_period``/``offset`` alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PAD_TO = 64  # a probe is padded to a multiple of this: few compiled shapes
# What ``comparisons/logprob_walk.py`` would hold this reference to. The
# configuration names ``comparisons/state_space.py``, which brings its own
# limits; this is that module's WALK_TOL.
LOGPROB_TOL = 0.1

_ATTN = ("wq", "wk", "wv", "wo")
_MAMBA = (
    "mamba_in", "mamba_conv_w", "mamba_conv_b", "mamba_x", "mamba_dt_w",
    "mamba_dt_b", "mamba_A_log", "mamba_D", "mamba_dt_norm", "mamba_b_norm",
    "mamba_c_norm", "mamba_out",
)


def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def _inner_norm(x, w, eps):
    """Jamba's norms inside the mixer (the step's, B's, C's)."""
    return _rms_norm(x, w, eps)


def _softplus(x):
    return jax.nn.softplus(x)


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ _f32(w_gate)) * (h @ _f32(w_up))) @ _f32(w_down)


def _attention(h, lp, *, hd: int):
    """Causal attention on h: [T, D], no positional term."""
    T = h.shape[0]
    pos = jnp.arange(T)
    q = (h @ _f32(lp["wq"])).reshape(T, -1, hd)
    k = (h @ _f32(lp["wk"])).reshape(T, -1, hd)
    v = (h @ _f32(lp["wv"])).reshape(T, -1, hd)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) * hd**-0.5
    scores = jnp.where(pos[None, :, None] >= pos[None, None, :], scores, -jnp.inf)
    attn = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    return attn.reshape(T, -1) @ _f32(lp["wo"])


def _causal_conv(x, taps, bias):
    """``bias + sum_j taps[j] * x_{t-(K-1)+j}`` over x: [T, E], zeros
    before the start; taps [K, E], the last weighs ``x_t``."""
    K, T = taps.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x])
    return bias + sum(taps[j] * padded[j : j + T] for j in range(K))


def _scan(dt, c, a, b, cc, d):
    """The selective scan a token at a time: dt, c [T, E]; a [S, E]
    (negative); b, cc [T, S]; d [E]. ``h`` is [S, E] float32. Returns
    (y [T, E], the state after the last token)."""
    def step(h, t):
        dt_t, c_t, b_t, cc_t = t
        h = jnp.exp(dt_t[None] * a) * h + (dt_t * c_t)[None] * b_t[:, None]
        return h, jnp.sum(h * cc_t[:, None], axis=0) + d * c_t

    last, y = jax.lax.scan(step, jnp.zeros_like(a), (dt, c, b, cc))
    return y, last


def _mamba(h, lp, *, eps: float, rank: int, states: int):
    """The Mamba mixer on h: [T, D] (module docstring): (its output
    [T, D], the scan's state [S, E] after the last token)."""
    x, g = jnp.split(h @ _f32(lp["mamba_in"]), 2, axis=-1)
    c = jax.nn.silu(_causal_conv(x, _f32(lp["mamba_conv_w"]), _f32(lp["mamba_conv_b"])))
    tbc = c @ _f32(lp["mamba_x"])
    tau = _inner_norm(tbc[:, :rank], lp["mamba_dt_norm"], eps)
    b = _inner_norm(tbc[:, rank : rank + states], lp["mamba_b_norm"], eps)
    cc = _inner_norm(tbc[:, rank + states :], lp["mamba_c_norm"], eps)
    dt = _softplus(tau @ _f32(lp["mamba_dt_w"]) + _f32(lp["mamba_dt_b"]))
    y, last = _scan(dt, c, -jnp.exp(_f32(lp["mamba_A_log"])), b, cc, _f32(lp["mamba_D"]))
    return (y * jax.nn.silu(g)) @ _f32(lp["mamba_out"]), last


def _op_half(x, lp, c: dict, op: str):
    h = _rms_norm(x, lp["attn_norm"], c["eps"])
    if op == "attn":
        return x + _attention(h, lp, hd=c["hd"])
    return x + _mamba(h, lp, eps=c["eps"], rank=c["rank"], states=c["states"])[0]


def _ffn_half(x, lp, c: dict):
    h = _rms_norm(x, lp["mlp_norm"], c["eps"])
    return x + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])


def _log_softmax_head(x, params, eps):
    logits = _rms_norm(x, params["final_norm"], eps) @ _f32(params["embed"]).T
    return jax.nn.log_softmax(logits, axis=-1)


def block_constants(hf: dict) -> dict:
    """The numbers the block needs, from the configuration's file."""
    if hf.get("model_type") != "jamba":
        raise ValueError(f"references/jamba.py covers jamba, not {hf.get('model_type')!r}")
    if hf.get("num_experts", 1) > 1:
        raise ValueError("references/jamba.py has no routed experts")
    if hf.get("mamba_proj_bias") or not hf.get("mamba_conv_bias", True):
        raise ValueError("references/jamba.py has a convolution bias and no projection bias")
    period, offset = int(hf["attn_layer_period"]), int(hf["attn_layer_offset"])
    return {
        "hd": int(hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]),
        "eps": float(hf["rms_norm_eps"]),
        "rank": int(hf["mamba_dt_rank"]),
        "states": int(hf["mamba_d_state"]),
        "kinds": tuple(
            "attn" if i % period == offset else "mamba"
            for i in range(int(hf["num_hidden_layers"]))
        ),
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

    lp = {k: at(k, i) for k in ("attn_norm", "mlp_norm", "w_gate", "w_up", "w_down")}
    lp.update((k, at(k, nth)) for k in (_ATTN if op == "attn" else _MAMBA))
    return lp


def _static(c: dict):
    return tuple(sorted(c.items()))


# One compiled program a kind of layer and a half, the layer's indices
# traced, so that the whole stack's slices are never held at once.
@functools.partial(jax.jit, static_argnames=("c", "op"))
def _op_half_of(layers, x, i, nth, *, c, op):
    return _op_half(_f32(x), _layer_params(layers, op, i, nth), dict(c), op)


@functools.partial(jax.jit, static_argnames=("c",))
def _scan_state_of(layers, x, i, nth, *, c):
    c = dict(c)
    lp = _layer_params(layers, "mamba", i, nth)
    h = _rms_norm(_f32(x), lp["attn_norm"], c["eps"])
    return _mamba(h, lp, eps=c["eps"], rank=c["rank"], states=c["states"])[1]


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
    convolution and the scan it changes nothing before it)."""
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
    """The first half of layer ``layer`` (norm, its operator, residual) on
    ``x`` ([T, D], any float type, taken as it is: one sequence at
    positions 0..T-1), then the final norm and the head at ``rows`` (all
    where None): log-softmax over the vocabulary, float32 [len(rows), V]."""
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


def scan_state(params: dict, hf: dict, layer: int, x) -> np.ndarray:
    """The scan's state ``[S, E]`` (float32) of Mamba layer ``layer`` after
    the last token of ``x`` ([T, D]: one sequence at positions 0..T-1
    entering the layer): what a sequence carries on from there."""
    c = block_constants(hf)
    if c["kinds"][int(layer)] != "mamba":
        raise ValueError(f"layer {layer} is not a Mamba layer")
    with jax.default_matmul_precision("highest"):
        out = _scan_state_of(
            params["layers"], jnp.asarray(x), int(layer), _where(c["kinds"], int(layer)),
            c=_static(c),
        )
    return np.asarray(out)
