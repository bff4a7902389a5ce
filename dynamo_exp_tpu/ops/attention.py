"""Paged attention over a page-table-indexed KV cache (XLA reference path).

Design (TPU-first, replaces what vLLM's PagedAttention CUDA kernels gave the
reference for free — see SURVEY.md §2.9):

- The KV cache is a global page pool per layer: ``[num_pages, page_size,
  num_kv_heads, head_dim]``. Sequences own pages via a per-slot page table.
- Write-then-gather: a step first scatters its new K/V into the pool at
  (page_table[pos // ps], pos % ps), then attention gathers the sequence's
  pages and masks by position. Prefill (B=1, T=bucket) and decode
  (B=slots, T=1) share one code path, so prefix-cache hits need no special
  attention kernel — cached pages are simply already written.
- Static shapes throughout: page tables are fixed width, masks handle the
  ragged reality, so XLA compiles once per (B, T, Pmax) bucket.

This module is the always-correct XLA path and the CPU-mesh test oracle.
The gather is bounded by the caller (``forward(attn_pages=...)`` slices
the page table to the live context), and the QK/PV matmuls run in the
cache dtype (bfloat16) with float32 accumulation on the MXU. The fast
path for prefill AND decode is the ragged Pallas kernel in
``ops/ragged_attention.py``, which this path cross-checks in tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def write_kv_pages(
    k_cache: jnp.ndarray,  # [L, P, ps, Hkv*D] (heads collapsed into lanes)
    v_cache: jnp.ndarray,
    k_new: jnp.ndarray,  # [N, Hkv*D] flattened new tokens
    v_new: jnp.ndarray,
    page_ids: jnp.ndarray,  # [N] int32 page id (within a layer) per new token
    offsets: jnp.ndarray,  # [N] int32 in-page offset per new token
    valid: jnp.ndarray,  # [N] bool — False rows are dropped
    layer: jnp.ndarray | int | None = None,  # which layer of the pool
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter new K/V rows into the page pools at ``[layer, page,
    offset]``: one scatter into the whole ``[L, P, ps, Hkv*D]`` pool, so
    a pool that is loop-carried state (``models/llama.py``) is updated
    in place. ``layer=None`` takes one layer's ``[P, ps, Hkv*D]`` pool.

    Invalid rows are given page id ``P``, out of range of the *page*
    axis, which XLA's ``mode="drop"`` scatter discards — no write happens
    for them at all. (The sentinel must be out of range of the axis it
    indexes: on a pool flattened to ``[L*P, ...]`` page ``P`` would be
    layer 1's page 0.)

    The pool keeps (kv head, head_dim) collapsed into one trailing
    dimension: TPU tiling pads the last dim to 128 lanes, so a separate
    D=64 axis would double every pool's HBM footprint (and every
    gather's traffic); Hkv*D is 128-aligned for the shapes we serve.
    """
    num_pages = k_cache.shape[-3]
    # Out-of-range page id for invalid rows => XLA drops the scatter row.
    safe_pages = jnp.where(valid, page_ids, num_pages)
    idx = (safe_pages, offsets) if layer is None else (layer, safe_pages, offsets)
    k_cache = k_cache.at[idx].set(k_new.astype(k_cache.dtype), mode="drop")
    if v_new is not None:  # a latent pool's row holds its values too
        v_cache = v_cache.at[idx].set(v_new.astype(v_cache.dtype), mode="drop")
    return k_cache, v_cache


def paged_attention(
    q: jnp.ndarray,  # [B, T, H, D]
    k_cache: jnp.ndarray,  # [P, ps, Hkv*D]
    v_cache: jnp.ndarray,
    page_table: jnp.ndarray,  # [B, Pmax] int32
    q_positions: jnp.ndarray,  # [B, T] int32 global position of each query
    sm_scale: float | None = None,
    window: int | jnp.ndarray | None = None,
    softcap: float | None = None,
    value_dim: int | None = None,
    keep: jnp.ndarray | None = None,  # [B, T, S] bool: a learned selection
    sink: jnp.ndarray | None = None,  # [H] a learned logit a query head
    kv_heads: int | None = None,  # the K row's heads, where it holds more behind them
) -> jnp.ndarray:
    """Causal attention of queries against their sequences' pages.

    Returns [B, T, H, D] (with ``value_dim``: [B, T, H, value_dim], the
    values being the first ``value_dim`` lanes of each head's key row and
    ``v_cache`` unread: a latent pool, ``ops/ragged_attention.py``).
    Positions beyond a query's own position are
    masked, so garbage in not-yet-written slots never leaks; a padding
    query (position -1) has nothing to attend to and returns exact
    zeros (its softmax would otherwise be uniform over every gathered
    slot: the mean of whatever those pages hold). ``window``
    (mistral/gemma2 sliding-window attention) additionally masks keys
    older than ``q_pos - window + 1`` — it may be a traced scalar, so a
    scan over layers can alternate window widths (gemma2). ``softcap``
    applies gemma2's tanh cap to the scores before masking. ``keep``
    (learned sparse attention, ``ops/sparse_index.py``) additionally masks
    every position a query's selection left out, for all its heads.
    The value pool's heads may be narrower than the keys' (``v_cache``'s
    row is ``Hkv`` heads of its own width, the result's). ``sink`` (a
    learned logit a query head) joins the softmax's denominator as one
    more key that carries no value. ``kv_heads``: the K row's heads, where
    the row holds more behind their keys (a grouped-query model's index
    key, ``ModelConfig.kv_row_parts``), which is then left unread.
    """
    B, T, H, D = q.shape
    P, ps, _ = k_cache.shape
    Hkv = k_cache.shape[2] // D
    S = page_table.shape[1] * ps
    scale = sm_scale if sm_scale is not None else D ** -0.5

    # Gather this batch's pages: [B, Pmax, ps, Hkv*D] -> [B, S, Hkv, D]
    if kv_heads is not None and kv_heads * D != k_cache.shape[2]:
        Hkv = kv_heads
        k = k_cache[page_table][..., : Hkv * D].reshape(B, S, Hkv, D)
    else:
        k = k_cache[page_table].reshape(B, S, Hkv, D)
    if value_dim is None:
        v = v_cache[page_table].reshape(B, S, Hkv, v_cache.shape[2] // Hkv)
    else:
        v = k[..., :value_dim]

    # QK/PV matmuls run on the MXU in the cache dtype (bfloat16 in
    # production) with float32 accumulation; softmax stays float32.
    qpk = H // Hkv
    qg = q.reshape(B, T, Hkv, qpk, D).astype(k.dtype)
    scores = (
        jnp.einsum(
            "bthqd,bshd->bhqts", qg, k, preferred_element_type=jnp.float32
        )
        * scale
    )  # [B,Hkv,qpk,T,S] f32

    if softcap is not None:
        scores = jnp.tanh(scores / softcap) * softcap
    kv_pos = jnp.arange(S, dtype=jnp.int32)[None, None, None, None, :]
    qp = q_positions[:, None, None, :, None]
    mask = kv_pos <= qp  # causal by position
    if window is not None:
        mask &= kv_pos > qp - window
    if keep is not None:
        mask &= keep[:, None, None]
    scores = jnp.where(mask, scores, -1e30)

    if sink is None:
        probs = jax.nn.softmax(scores, axis=-1)
    else:
        column = jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(1, Hkv, qpk, 1, 1),
            (*scores.shape[:-1], 1),
        )
        probs = jax.nn.softmax(
            jnp.concatenate([scores, column], axis=-1), axis=-1
        )[..., :-1]
    out = jnp.einsum(
        "bhqts,bshd->bthqd",
        probs.astype(v.dtype),
        v,
        preferred_element_type=jnp.float32,
    )
    out = jnp.where(q_positions[:, :, None, None, None] >= 0, out, 0.0)
    return out.reshape(B, T, H, v.shape[-1]).astype(q.dtype)


def dense_causal_attention(
    q: jnp.ndarray,  # [B, T, H, D]
    k: jnp.ndarray,  # [B, T, Hkv, D]
    v: jnp.ndarray,
    sm_scale: float | None = None,
) -> jnp.ndarray:
    """Plain causal attention (no cache) — used by tests as the oracle and
    by the ring-attention building block."""
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    qpk = H // Hkv
    scale = sm_scale if sm_scale is not None else D ** -0.5
    qg = q.reshape(B, T, Hkv, qpk, D).astype(k.dtype)
    scores = (
        jnp.einsum(
            "bthqd,bshd->bhqts", qg, k, preferred_element_type=jnp.float32
        )
        * scale
    )
    i = jnp.arange(T)[:, None]
    j = jnp.arange(T)[None, :]
    scores = jnp.where(j <= i, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bhqts,bshd->bthqd",
        probs.astype(v.dtype),
        v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, T, H, D).astype(q.dtype)
