"""Plain float32 reference of NVIDIA's ``nemotron_h``
(NVIDIA-Nemotron-3-Nano-30B-A3B).

Written from the published description (the ``config.json`` named in the
configuration's file and the family's ``NemotronHBlock`` /
``NemotronHMamba2Mixer`` / ``NemotronHAttention`` / ``NemotronHMOE``), in
straightforward ``jax.numpy``: no kernel, no cache, no state carried from
call to call, no batching, no chunking, no paging. One sequence, positions
0..T-1. ``D`` the hidden size, ``H`` Mamba heads of ``P`` channels
(``E = H P``), ``S`` states, ``G`` groups, ``K`` taps:

- layer ``i`` of kind ``hybrid_override_pattern[i]`` is ONE half:
  ``h' = h + Mixer_i(RMSNorm(h, norm_i, layer_norm_epsilon))``; logits
  ``RMSNorm(h_L, norm_f) @ lm_head`` (untied), embeddings not scaled;
- ``M``, the Mamba-2 mixer: ``[z | xBC | dt] = u W_in`` (widths ``E | E + 2
  G S | H``, no bias); ``xBC = silu(b_conv + sum_{j<K} w_conv[j] *
  xBC_{t-(K-1)+j})`` (ONE depthwise causal convolution, zeros before the
  start); ``[x | B | C] = split(xBC)``; ``dt = softplus(dt + dt_bias)`` (the
  limits ``(0, inf)`` clip nothing); ``A_h = -exp(A_log_h)``; for head ``h``
  of group ``g = h // (H / G)``: ``S_t = exp(dt_t A_h) S_{t-1} + dt_t B_{g,t}
  x_{h,t}^T`` (``[S, P]`` float32, ``S_{-1} = 0``), a ``lax.scan`` a token at
  a time; ``y_{h,t} = S_t^T C_{g,t} + D_h x_{h,t}``; ``y = GroupRMSNorm(y *
  silu(z))``: the gate BEFORE the norm, each of the ``G`` groups of ``E / G``
  channels normed apart, one weight of ``E``; output ``y W_out``;
- ``*``, attention: ``q = u W_q`` (heads of ``head_dim``), ``k``, ``v``
  (``num_key_value_heads`` heads), no bias, **no rotary or other positional
  term**, causal ``softmax(q k^T / sqrt(head_dim)) v``; ``W_o``;
- ``E``, the experts: ``s = sigmoid(u W_gate)``; the ``num_experts_per_tok``
  experts of largest ``s + e_score_correction_bias`` (``n_group =
  topk_group = 1``: the group step is the identity); weights ``s`` there
  over their sum + 1e-20, times ``routed_scaling_factor``; expert ``e`` is
  ``relu(u W_up_e)^2 W_down_e``; the shared expert the same form at
  ``moe_shared_expert_intermediate_size``, added once.

THE SHARE. Where the file gives ``n_routed_experts_global``, the router is
that wide and the arrays hold experts ``[first_routed_expert,
first_routed_expert + n_routed_experts)``: the sum over the chosen runs over
those of them that are held, each weighed as normalised over all of the
token's picks; a pick elsewhere adds nothing; the shared expert once.
Summed over the ranks' shares with the shared expert counted once that is
the whole layer. The vocabulary is the slice the arrays hold.

Departures from the published code, each deliberate: every matrix product
runs in float32 at ``jax.default_matmul_precision("highest")`` on the
bfloat16 weights as served, where the published code computes in bfloat16
(its scan alone in float32); the scan is the recurrence itself and not the
published kernel's chunked form (the same numbers); ``in_proj`` is held in
two arrays (``[z | xBC]`` and ``dt``), the published tensor's columns;
the held experts are evaluated densely (every held expert on every token,
weight 0 where not chosen). ASSUMED, because the catalog's row does not
carry them and the family's published classes do: no positional term in
attention (``rope_theta`` and ``partial_rotary_factor`` steer nothing
there); the mixer's inner width is ``mamba_num_heads * mamba_head_dim``
(``expand`` steers nothing); the norm inside the mixer is grouped and
follows the gate.

The weights are the engine's own arrays, stacked by the kind of layer that
has them (``models/llama.py::init_params``: the layers' norms over the
(mixer, experts) pairs for ``M`` and ``*`` and over the routed layers for
``E``, ``m2_*`` over the Mamba-2 layers, attention's over the attention
layers, the router, the shared expert and the held experts over the routed
layers), used in place a layer at a time. Which stack and which index a
layer reads is worked out here, from the pattern alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PAD_TO = 64  # a probe is padded to a multiple of this: few compiled shapes
# What ``comparisons/logprob_walk.py`` would hold this reference to; the
# configuration names ``comparisons/mamba2_experts.py``, which brings its own.
LOGPROB_TOL = 0.3

_ATTN = ("wq", "wk", "wv", "wo")
_MAMBA2 = (
    "m2_in", "m2_dt_w", "m2_conv_w", "m2_conv_b", "m2_dt_b", "m2_A_log", "m2_D",
    "m2_norm", "m2_out",
)
_ROUTED = ("router", "router_bias", "shared_up", "shared_down")
_EXPERTS = ("w_up", "w_down")


def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def _expert_act(x):
    """``mlp_hidden_act`` relu2."""
    return jnp.square(jax.nn.relu(x))


def _two_matrix(h, w_up, w_down):
    return _expert_act(h @ _f32(w_up)) @ _f32(w_down)


def _gated_norm(y, z, w, eps, groups: int):
    """The mixer's output norm: the gate first, then each group's RMS."""
    y = y * jax.nn.silu(z)
    t = y.shape[0]
    y = y.reshape(t, groups, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    return y.reshape(t, -1) * _f32(w)


def _attention(h, lp, *, hd: int):
    """Causal attention on h: [T, D], no positional term. A query head at a
    time (``lax.map``): 32 heads' scores over a 4096-token sequence are 2 GB
    at once, which the chip has not beside the model."""
    T = h.shape[0]
    pos = jnp.arange(T)
    q = (h @ _f32(lp["wq"])).reshape(T, -1, hd)
    k = (h @ _f32(lp["wk"])).reshape(T, -1, hd)
    v = (h @ _f32(lp["wv"])).reshape(T, -1, hd)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)

    def head(qkv):
        q_h, k_h, v_h = qkv  # [T, hd] each
        scores = (q_h @ k_h.T) * hd**-0.5
        scores = jnp.where(pos[:, None] >= pos[None, :], scores, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v_h

    attn = jax.lax.map(head, tuple(jnp.swapaxes(x, 0, 1) for x in (q, k, v)))  # [H, T, hd]
    return jnp.swapaxes(attn, 0, 1).reshape(T, -1) @ _f32(lp["wo"])


def _causal_conv(x, taps, bias):
    """``bias + sum_j taps[j] * x_{t-(K-1)+j}`` over x: [T, C], zeros before
    the start; taps [K, C], the last weighs ``x_t``."""
    K, T = taps.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x])
    return bias + sum(taps[j] * padded[j : j + T] for j in range(K))


def _carried(s, t):
    """The state as token ``t`` finds it (the controls' seam: identity)."""
    return s


def _scan(x, dt, a, b, c, d):
    """The recurrence a token at a time: x [T, H, P]; dt [T, H]; a, d [H];
    b, c [T, G, S]. The state is [S, H, P] float32. Returns (y [T, H, P],
    the state after the last token)."""
    per = x.shape[1] // b.shape[1]

    def step(s, t):
        i, x_t, dt_t, b_t, c_t = t
        s = _carried(s, i)
        bh = jnp.repeat(b_t, per, axis=0).T[:, :, None]  # [S, H, 1]
        ch = jnp.repeat(c_t, per, axis=0).T[:, :, None]
        s = jnp.exp(dt_t * a)[None, :, None] * s + bh * (dt_t[:, None] * x_t)[None]
        return s, jnp.sum(s * ch, axis=0) + d[:, None] * x_t

    s0 = jnp.zeros((b.shape[-1], *x.shape[1:]), jnp.float32)
    last, y = jax.lax.scan(step, s0, (jnp.arange(x.shape[0]), x, dt, b, c))
    return y, last


def _mamba2(h, lp, c: dict):
    """The Mamba-2 mixer on h: [T, D] (module docstring): (its output
    [T, D], the state [S, H * P] after the last token)."""
    T = h.shape[0]
    H, P, G, S = c["heads"], c["head_dim"], c["groups"], c["states"]
    E = H * P
    zx = h @ _f32(lp["m2_in"])
    z, xbc = zx[:, :E], zx[:, E:]
    dt = jax.nn.softplus(h @ _f32(lp["m2_dt_w"]) + _f32(lp["m2_dt_b"]))
    xbc = jax.nn.silu(_causal_conv(xbc, _f32(lp["m2_conv_w"]), _f32(lp["m2_conv_b"])))
    x = xbc[:, :E].reshape(T, H, P)
    b = xbc[:, E : E + G * S].reshape(T, G, S)
    cc = xbc[:, E + G * S :].reshape(T, G, S)
    y, last = _scan(x, dt, -jnp.exp(_f32(lp["m2_A_log"])), b, cc, _f32(lp["m2_D"]))
    y = _gated_norm(y.reshape(T, E), z, lp["m2_norm"], c["eps"], G)
    return y @ _f32(lp["m2_out"]), last.reshape(S, E)


def _route(s, bias, *, top_k: int, scale: float):
    """``s``: [T, E] sigmoid scores over every expert. The weight of every
    expert ([T, E], 0 where not chosen) and the margin a row: the k-th best
    of ``s + bias`` less the (k+1)-th."""
    chosen_by = s + bias
    best, top_i = jax.lax.top_k(chosen_by, top_k + 1)
    top_i = top_i[:, :top_k]
    w = jnp.take_along_axis(s, top_i, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scale
    weight = jnp.sum(jax.nn.one_hot(top_i, s.shape[-1], dtype=jnp.float32) * w[..., None], axis=1)
    return weight, best[:, top_k - 1] - best[:, top_k]


def _shared(h, lp):
    return _two_matrix(h, lp["shared_up"], lp["shared_down"])


def _experts(h, lp, c: dict):
    """The ``E`` mixer on h: [T, D]: (the sum [T, D], the router's margin a
    row, the share of the chosen that are held). The held experts leave
    their stacks one at a time."""
    s = jax.nn.sigmoid(h @ _f32(lp["router"]))
    weight, margin = _route(s, _f32(lp["router_bias"]), top_k=c["top_k"], scale=c["scale"])
    stacks, nth = lp["experts"]  # the routed layers' [L_routed, E_held, ..] stacks
    held = stacks[0].shape[1]

    def add_expert(e, y):
        w = [
            jax.lax.dynamic_slice(m, (nth, e, 0, 0), (1, 1, *m.shape[2:]))[0, 0] for m in stacks
        ]
        return y + jnp.take(weight, c["first"] + e, axis=1)[:, None] * _two_matrix(h, *w)

    y = jax.lax.fori_loop(0, held, add_expert, jnp.zeros_like(h))
    y = y + _shared(h, lp)
    here = jnp.sum(weight[:, c["first"]: c["first"] + held] > 0) / (weight.shape[0] * c["top_k"])
    return y, margin, here


def _log_softmax_head(x, params, eps):
    logits = _rms_norm(x, params["final_norm"], eps) @ _f32(params["lm_head"])
    return jax.nn.log_softmax(logits, axis=-1)


def block_constants(hf: dict) -> dict:
    """The numbers the layers need, from the configuration's file."""
    if hf.get("model_type") != "nemotron_h":
        raise ValueError(
            f"references/nemotron_h.py covers nemotron_h, not {hf.get('model_type')!r}"
        )
    pattern = str(hf["hybrid_override_pattern"])
    if set(pattern) - set("ME*") or len(pattern) != int(hf["num_hidden_layers"]):
        raise ValueError(f"references/nemotron_h.py reads M, E and * layers, not {pattern!r}")
    if hf.get("tie_word_embeddings", False):
        raise ValueError("references/nemotron_h.py has an untied head")
    if hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1:
        raise ValueError("references/nemotron_h.py has no group-limited selection")
    return {
        "pattern": pattern,
        "eps": float(hf.get("layer_norm_epsilon", 1e-5)),
        "hd": int(hf["head_dim"]),
        "heads": int(hf["mamba_num_heads"]),
        "head_dim": int(hf["mamba_head_dim"]),
        "groups": int(hf["n_groups"]),
        "states": int(hf["ssm_state_size"]),
        "top_k": int(hf["num_experts_per_tok"]),
        "scale": float(hf.get("routed_scaling_factor", 1.0)),
        "first": int(hf.get("first_routed_expert", 0)),
    }


def where(pattern: str, i: int) -> tuple[str, int, int]:
    """Layer ``i``: (its letter, the index of its norm in the stack that
    has it, its ordinal among the layers of its letter). ``M`` and ``*``
    take ``attn_norm`` at their ordinal among both; ``E`` takes ``mlp_norm``
    at its ordinal among ``E``."""
    kind = pattern[i]
    nth = pattern[:i].count(kind)
    norm = nth if kind == "E" else sum(ch != "E" for ch in pattern[:i])
    return kind, norm, nth


def _layer_params(layers, kind: str, norm, nth) -> dict:
    """One layer's parameters out of the stacks (the indices may be
    traced); the experts stay in their stacks."""
    def at(name, n):
        return jax.lax.dynamic_index_in_dim(layers[name], n, 0, keepdims=False)

    lp = {"norm": at("mlp_norm" if kind == "E" else "attn_norm", norm)}
    if kind == "E":
        lp.update((k, at(k, nth)) for k in _ROUTED)
        lp["experts"] = (tuple(layers[k] for k in _EXPERTS), nth)
    else:
        lp.update((k, at(k, nth)) for k in (_MAMBA2 if kind == "M" else _ATTN))
    return lp


def _layer(x, lp, c: dict, kind: str):
    """``x + Mixer(RMSNorm(x))``; beside it the state (``M``), or the margin
    and the held share (``E``)."""
    h = _rms_norm(x, lp["norm"], c["eps"])
    if kind == "M":
        y, last = _mamba2(h, lp, c)
        return x + y, last
    if kind == "*":
        return x + _attention(h, lp, hd=c["hd"]), None
    y, margin, here = _experts(h, lp, c)
    return x + y, (margin, here)


def _static(c: dict):
    return tuple(sorted(c.items()))


# One compiled program a kind of layer, the layer's indices traced, so that
# a 13-layer walk is three programs and the stacks' slices are never held
# at once.
@functools.partial(jax.jit, static_argnames=("c", "kind", "added"))
def _layer_of(layers, x, norm, nth, *, c, kind, added=False):
    x = _f32(x)
    y, extra = _layer(x, _layer_params(layers, kind, norm, nth), dict(c), kind)
    return (y - x if added else y), extra


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_of(params, x, *, eps):
    return _log_softmax_head(_f32(x), params, eps)


def _forward(params, ids, rows, c: dict, at: tuple = ()):
    """Embedding, the layers one after another, the head at ``rows``; and
    the hidden state entering each layer in ``at``."""
    x = _f32(params["embed"][ids])
    entering = []
    for i in range(len(c["pattern"])):
        kind, norm, nth = where(c["pattern"], i)
        if i in at:
            entering.append(x)
        x = _layer_of(params["layers"], x, norm, nth, c=_static(c), kind=kind)[0]
    return _head_of(params, x[rows], eps=c["eps"]), entering


def _padded(token_ids) -> list[int]:
    ids = list(token_ids)
    return ids + [0] * (-len(ids) % PAD_TO)


def log_probs(params: dict, hf: dict, token_ids, rows) -> np.ndarray:
    """log-softmax over the vocabulary at positions ``rows`` of the
    sequence ``token_ids`` (padded to a multiple of PAD_TO; padding follows
    the real tokens, so under the causal mask, the causal convolution and
    the scan it changes nothing before it)."""
    with jax.default_matmul_precision("highest"):
        out = _forward(
            params, jnp.asarray(_padded(token_ids), jnp.int32),
            jnp.asarray(list(rows), jnp.int32), block_constants(hf),
        )[0]
    return np.asarray(out)


def hidden_states(params: dict, hf: dict, token_ids, layers) -> np.ndarray:
    """For the sequence ``token_ids``, the float32 hidden state entering
    each layer in ``layers`` (ascending): [len(layers), len(token_ids), D]."""
    n = len(list(token_ids))
    with jax.default_matmul_precision("highest"):
        _, entering = _forward(
            params, jnp.asarray(_padded(token_ids), jnp.int32), jnp.zeros((1,), jnp.int32),
            block_constants(hf), at=tuple(int(l) for l in layers),
        )
    return np.stack([np.asarray(e)[:n] for e in entering])


def layer_and_head(params: dict, hf: dict, layer: int, x, rows=None) -> np.ndarray:
    """Layer ``layer`` (norm, its mixer, residual) on ``x`` ([T, D], any
    float type, taken as it is: one sequence at positions 0..T-1), then the
    final norm and the head at ``rows`` (all where None): log-softmax over
    the vocabulary, float32 [len(rows), V]."""
    c = block_constants(hf)
    kind, norm, nth = where(c["pattern"], int(layer))
    with jax.default_matmul_precision("highest"):
        out = _layer_of(params["layers"], jnp.asarray(x), norm, nth, c=_static(c), kind=kind)[0]
        if rows is not None:
            out = out[jnp.asarray(list(rows), jnp.int32)]
        out = _head_of(params, out, eps=c["eps"])
    return np.asarray(out)


def scan_state(params: dict, hf: dict, layer: int, x) -> np.ndarray:
    """The state ``[S, H * P]`` (float32) of Mamba-2 layer ``layer`` after the
    last token of ``x`` ([T, D]: one sequence at positions 0..T-1 entering
    the layer): what a sequence carries on from there."""
    c = block_constants(hf)
    kind, norm, nth = where(c["pattern"], int(layer))
    if kind != "M":
        raise ValueError(f"layer {layer} is not a Mamba-2 layer")
    with jax.default_matmul_precision("highest"):
        last = _layer_of(params["layers"], jnp.asarray(x), norm, nth, c=_static(c), kind=kind)[1]
    return np.asarray(last)


def experts_layer(params: dict, hf: dict, layer: int, x, added: bool = False):
    """``E`` layer ``layer`` on the rows ``x`` ([N, D], any float type, taken
    as it is): (the rows after the residual add, float32 [N, D]; the
    router's margin a row; the share of the chosen experts that are held).
    ``added``: what the layer adds alone, without the residual (what the
    shares of an expert-parallel layer add up to, the shared expert in
    each)."""
    c = block_constants(hf)
    kind, norm, nth = where(c["pattern"], int(layer))
    if kind != "E":
        raise ValueError(f"layer {layer} is not an expert layer")
    with jax.default_matmul_precision("highest"):
        y, (margin, here) = _layer_of(
            params["layers"], jnp.asarray(x), norm, nth, c=_static(c), kind=kind, added=added,
        )
    return np.asarray(y), np.asarray(margin), float(here)
