"""Plain float32 reference of JetLM's ``sdar_moe`` (SDAR-30B-A3B-Chat): a
``qwen3_moe`` stack that generates by diffusion over blocks.

Written from the published description (the model card and modelling code
of ``huggingface.co/JetLM/SDAR-30B-A3B-Chat``), in straightforward
``jax.numpy``: no kernel, no cache, no state carried from call to call,
no batching, no paging. One sequence, positions 0..T-1, ``B`` the block
length, ``z`` a half-layer's normed input:

- layer: ``h = x + W_o Attn(q, k, v)``, q, k, v from ``RMSNorm(x)`` without
  bias; RMSNorm (weight of ``head_dim``) over each head of q and k; rotary
  in the half-split pairing, base ``rope_theta``, at the absolute
  position; ``softmax(q k^T / sqrt(head_dim)) v`` under the mask **``i``
  sees ``j`` iff ``j // B <= i // B``** (causal across blocks, full inside
  a block); then ``y = h + MoE(RMSNorm(h))``;
- routed experts: ``p = softmax(z @ W_r)`` over all experts in float32,
  the ``top_k`` largest, their weights divided by their sum
  (``norm_topk_prob``), each chosen expert a SwiGLU
  ``W_down (silu(W_gate z) * W_up z)``; no shared expert, every layer
  routed;
- final RMSNorm, untied head: ``logits[i]`` is the distribution of the
  token AT position ``i`` (a masked position predicts itself: no shift).

Generation (``denoise_log_probs`` replays it): a block's open positions
hold the mask token; a pass runs the model over everything up to the
block's end and fixes some masked positions (the served path says which,
by the pass each token was fixed at); a token's log-probability is
``log softmax(logits[i])[token]`` of the pass that fixed it. What a pass
sees of its own block: the tokens fixed by earlier passes, and the mask
token elsewhere.

Departures from the published code, each deliberate: every matrix product
runs in float32 at ``jax.default_matmul_precision("highest")`` on the
bfloat16 weights as served; experts are evaluated densely (every expert on
every token, weighted by 0 where not chosen), which is the same sum.
ASSUMED, because the catalog's row of the published ``config.json`` lacks
them (``benchmark/configs/sdar-30b-a3b-l6.json``, ``assumed``): the block
length, the mask token's id, and "no shift"; this module takes the first
two from ``hf`` where it has them and from ``DEFAULTS`` otherwise.

The weights are the engine's own arrays, stacked over the layers
(``models/llama.py::init_params``), used in place a layer and an expert
at a time, and a sequence is computed in blocks of ``ROW_BLOCK`` rows
through the experts, so the reference fits beside a model that fills the
chip. It shares no code with ``dynamo_exp_tpu/``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PAD_TO = 128  # every sequence is padded to this length: one compiled shape
ROW_BLOCK = 128  # rows through the experts at once
DEFAULTS = {"block_length": 4, "mask_token_id": 151669}
# What ``comparisons/logprob_walk.py`` would hold this reference to. No
# configuration asks for that (a routed model's walk cannot hold the
# precision); this is ``comparisons/block_diffusion.py``'s MEDIAN_TOL.
LOGPROB_TOL = 0.3


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


def _attention(h, lp, n, *, hd: int, eps: float, theta: float, block: int):
    """``n``: the sequence's real length; rows from ``n`` on are padding,
    which nothing sees (a block's mask would otherwise show a real row
    the padding that shares its block)."""
    T = h.shape[0]
    pos = jnp.arange(T)
    q = _rms_norm((h @ _f32(lp["wq"])).reshape(T, -1, hd), lp["q_norm"], eps)
    k = _rms_norm((h @ _f32(lp["wk"])).reshape(T, -1, hd), lp["k_norm"], eps)
    v = (h @ _f32(lp["wv"])).reshape(T, -1, hd)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) * hd**-0.5
    scores = jnp.where((_sees(pos, block) & (pos[None, :] < n))[None], scores, -jnp.inf)
    attn = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    return attn.reshape(T, -1) @ _f32(lp["wo"])


def _sees(pos, block: int):
    """[T, T] bool: whether position ``i`` (rows) sees ``j`` (columns):
    causal across blocks, full inside a block."""
    return pos[None, :] // block <= pos[:, None] // block


def _route(p, *, top_k: int, norm: bool):
    """``p``: [T, E] softmax scores. The weight of every expert ([T, E],
    0 where not chosen) and the margin a row: the k-th best less the
    (k+1)-th, as a share of the k-th (the difference of their logits, to
    first order: over 128 experts a score is ~0.008, and what a bfloat16
    input moves it by is a share of it, not an amount)."""
    best, top_i = jax.lax.top_k(p, top_k + 1)
    w, top_i = best[:, :top_k], top_i[:, :top_k]
    if norm:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(top_i, p.shape[-1], dtype=jnp.float32) * w[..., None], axis=1)
    return weight, (best[:, top_k - 1] - best[:, top_k]) / best[:, top_k - 1]


def _attn_half(x, lp, n, c):
    h = _rms_norm(x, lp["attn_norm"], c["eps"])
    return x + _attention(
        h, lp, n, hd=c["hd"], eps=c["eps"], theta=c["theta"], block=c["block"]
    )


def _ffn_half(x, lp, c):
    """The layer's second half on x: [T, D] float32. The experts are taken
    out of the stacks one at a time, inside a loop, so that one expert's
    matrices are held in float32 and no more."""
    h = _rms_norm(x, lp["mlp_norm"], c["eps"])
    p = jax.nn.softmax(h @ _f32(lp["router"]), axis=-1)
    weight, margin = _route(p, top_k=c["top_k"], norm=c["norm"])
    stacks, nth = lp["experts"]

    def add_expert(e, y):
        w = [
            jax.lax.dynamic_slice(m, (nth, e, 0, 0), (1, 1, *m.shape[2:]))[0, 0] for m in stacks
        ]
        return y + jnp.take(weight, e, axis=1)[:, None] * _swiglu(h, *w)

    y = jax.lax.fori_loop(0, p.shape[-1], add_expert, jnp.zeros_like(x))
    return x + y, margin


def block_constants(hf: dict) -> dict:
    """The numbers the block needs, from the configuration's file."""
    if hf.get("model_type") != "sdar_moe":
        raise ValueError(f"references/sdar_moe.py covers sdar_moe, not {hf.get('model_type')!r}")
    if hf.get("mlp_only_layers") or hf.get("decoder_sparse_step", 1) != 1:
        raise ValueError("references/sdar_moe.py has every layer routed")
    vocab = int(hf["vocab_size"])
    return {
        "hd": int(hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]),
        "eps": float(hf["rms_norm_eps"]),
        "theta": float(hf["rope_theta"]),
        "top_k": int(hf["num_experts_per_tok"]),
        "norm": bool(hf.get("norm_topk_prob", False)),
        "block": int(hf.get("block_length", DEFAULTS["block_length"])),
        "mask": min(int(hf.get("mask_token_id", DEFAULTS["mask_token_id"])), vocab - 1),
        "layers": int(hf["num_hidden_layers"]),
    }


_ATTN = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm")
_EXPERTS = ("w_gate", "w_up", "w_down")


def _static(c: dict):
    return tuple(sorted(c.items()))


def _at(layers, name, i):
    return jax.lax.dynamic_index_in_dim(layers[name], i, 0, keepdims=False)


# One compiled program a half, the layer's index traced: a program that
# held every layer's slices at once would not fit beside the weights.
@functools.partial(jax.jit, static_argnames=("c",))
def _attn_half_of(layers, x, i, n, *, c):
    return _attn_half(_f32(x), {k: _at(layers, k, i) for k in _ATTN}, n, dict(c))


@functools.partial(jax.jit, static_argnames=("c",))
def _ffn_half_of(layers, x, i, *, c):
    lp = {
        "mlp_norm": _at(layers, "mlp_norm", i), "router": _at(layers, "router", i),
        "experts": (tuple(layers[k] for k in _EXPERTS), i),
    }
    return _ffn_half(_f32(x), lp, dict(c))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_of(params, x, *, eps):
    logits = _rms_norm(_f32(x), params["final_norm"], eps) @ _f32(params["lm_head"])
    return jax.nn.log_softmax(logits, axis=-1)


def _ffn_rows(layers, x, i, c):
    """``_ffn_half_of`` over ``x`` ([T, D]) in blocks of ROW_BLOCK rows
    (a row's experts depend on that row alone)."""
    outs, margins = [], []
    for r in range(0, x.shape[0], ROW_BLOCK):
        y, m = _ffn_half_of(layers, x[r : r + ROW_BLOCK], i, c=_static(c))
        outs.append(y)
        margins.append(m)
    return jnp.concatenate(outs), jnp.concatenate(margins)


def _forward(params, ids, n, rows, c: dict, at: tuple = ()):
    """Embedding, the layers one after another, the head at ``rows``; and
    the hidden state entering each layer in ``at`` and that layer's
    second half."""
    x = _f32(params["embed"][ids])
    entering, before_ffn = [], []
    for i in range(c["layers"]):
        mid = _attn_half_of(params["layers"], x, i, n, c=_static(c))
        if i in at:
            entering.append(x)
            before_ffn.append(mid)
        x = _ffn_rows(params["layers"], mid, i, c)[0]
    return _head_of(params, x[rows], eps=c["eps"]), entering, before_ffn


def _padded(token_ids) -> list[int]:
    ids = list(token_ids)
    if len(ids) > PAD_TO:
        raise ValueError(f"sequence of {len(ids)} tokens exceeds PAD_TO={PAD_TO}")
    return ids + [0] * (PAD_TO - len(ids))


def log_probs(params: dict, hf: dict, token_ids, rows) -> np.ndarray:
    """log-softmax over the vocabulary at positions ``rows`` of the
    sequence ``token_ids`` under the block mask: ``out[r]`` is the
    distribution of the token AT ``rows[r]``, seen from a sequence that
    holds ``token_ids`` (mask tokens where the caller put them)."""
    ids = list(token_ids)
    with jax.default_matmul_precision("highest"):
        out = _forward(
            params, jnp.asarray(_padded(ids), jnp.int32), len(ids),
            jnp.asarray(list(rows), jnp.int32), block_constants(hf),
        )[0]
    return np.asarray(out)


def hidden_states(params: dict, hf: dict, token_ids, layers) -> tuple[np.ndarray, np.ndarray]:
    """For the sequence ``token_ids``, the float32 hidden state entering
    each layer in ``layers`` (ascending) and the one entering that layer's
    second half: two arrays [len(layers), len(token_ids), D]."""
    ids = list(token_ids)
    n = len(ids)
    with jax.default_matmul_precision("highest"):
        _, entering, before_ffn = _forward(
            params, jnp.asarray(_padded(ids), jnp.int32), n, jnp.zeros((1,), jnp.int32),
            block_constants(hf), at=tuple(int(l) for l in layers),
        )
    return np.stack([np.asarray(e)[:n] for e in entering]), np.stack([np.asarray(b)[:n] for b in before_ffn])


def attn_half_and_head(params: dict, hf: dict, layer: int, x) -> np.ndarray:
    """The first half of layer ``layer`` (norm, attention under the block
    mask, residual) on ``x`` ([T, D], any float type, taken as it is: one
    sequence at positions 0..T-1, every row real), then the final norm and
    the head: log-softmax over the vocabulary, float32 [T, V]."""
    c = block_constants(hf)
    x = jnp.asarray(x)
    with jax.default_matmul_precision("highest"):
        mid = _attn_half_of(params["layers"], x, int(layer), x.shape[0], c=_static(c))
        out = _head_of(params, mid, eps=c["eps"])
    return np.asarray(out)


def ffn_half(params: dict, hf: dict, layer: int, x):
    """The second half of layer ``layer`` on the rows ``x`` ([N, D], any
    float type, taken as it is): (the rows after the residual add,
    float32 [N, D]; the router's margin a row)."""
    with jax.default_matmul_precision("highest"):
        y, margin = _ffn_rows(params["layers"], jnp.asarray(x), int(layer), block_constants(hf))
    return np.asarray(y), np.asarray(margin)


def written_block(tokens: list[int], passes: list[int], mask: int) -> list[int]:
    """What a finished block's K/V were computed from, as every later
    block sees it: its final tokens (the pass that writes it runs over
    them once nothing is masked). ``passes``: the pass that fixed each,
    for a control that leaves the block as its last denoising pass had it
    (``rehearse/check_seeds_sdar.py``)."""
    del passes, mask
    return list(tokens)


def pass_inputs(prompt, tokens, fixed_at_pass, hf: dict):
    """The sequences the served trajectory's passes ran on. ``tokens``
    follow ``prompt``; ``fixed_at_pass[i]`` is the denoising pass that
    fixed ``tokens[i]``. Yields (ids, rows, which): ``ids`` the sequence a
    pass saw, up to its block's end (the tokens before the block, the
    block's tokens fixed by earlier passes, the mask token elsewhere),
    ``rows`` the positions that pass fixed and ``which`` their indices in
    ``tokens``. The served tokens have to end where a block does: a last
    block cut short by ``max_tokens`` ran whole on the served path, and
    what it fixed past the cut, and when, was not served. ``tokens`` is
    read as each pass is drawn: a caller that learns a token's id only from
    the pass that fixed it (``comparisons/block_diffusion.py``) fills it in
    before it draws the next."""
    c = block_constants(hf)
    B, mask = c["block"], c["mask"]
    P, end = len(prompt), len(prompt) + len(tokens)
    if end % B:
        raise ValueError(f"{P} prompt + {len(tokens)} served tokens end inside a block of {B}")

    def token(i: int) -> int:  # read when a pass is drawn, not before
        return prompt[i] if i < P else tokens[i - P]

    def fixed_at(i: int) -> int:  # the prompt was never masked
        return -1 if i < P else fixed_at_pass[i - P]

    before: list[int] = []  # every earlier block, as it was written
    for start in range(0, end, B):
        block = range(start, start + B)
        served = [i for i in block if i >= P]
        for s in sorted({fixed_at(i) for i in served}):
            ids = before + [token(i) if fixed_at(i) < s else mask for i in block]
            rows = [i for i in served if fixed_at(i) == s]
            yield ids, rows, [i - P for i in rows]
        final = [token(i) for i in block]
        before += final if not served else written_block(
            final, [fixed_at(i) for i in block], mask
        )


def denoise_log_probs(params: dict, hf: dict, prompt, tokens, fixed_at_pass) -> np.ndarray:
    """For each served token, the log-softmax over the vocabulary at its
    position in the pass that fixed it, replayed from the served tokens
    and their passes (``pass_inputs``): [len(tokens), V] float32. Row
    ``i`` at ``tokens[i]`` is the log-probability the served path should
    have reported for it."""
    out = [None] * len(tokens)
    for ids, rows, which in pass_inputs(prompt, tokens, fixed_at_pass, hf):
        lp = log_probs(params, hf, ids, rows)
        for j, i in enumerate(which):
            out[i] = lp[j]
    return np.stack(out)
