"""Ragged paged attention: one kernel for mixed prefill + decode.

This replaces ``ops/paged_decode.py`` (single-query-per-row decode) and
the chunked-prefill attention path with ONE kernel family that takes
per-row ``(query_len, kv_len, page table)`` — the Ragged Paged
Attention design (PAPERS.md, arXiv 2604.15464): chunked-prefill rows,
decode rows, and speculative-verify rows all ride the same dispatch.

Layout: queries arrive as a **flat token stream** ``q[N, H, D]`` with
per-token ``positions[N]`` (absolute context position, ``-1`` =
padding) and ``row_of[N]`` (which batch row owns the token). Rows own
KV pages via ``page_table[R, Pmax]``; a token at position ``p`` attends
causally to its row's kv positions ``<= p``, or ``<= bounds[i]`` where
the caller gives each query its own bound (a block-diffusion model sees
its whole block: ``p | (B - 1)``; padding stays -1). Total compute therefore
tracks the *true* total query tokens — a lone decode row costs one
token, a mixed batch costs the sum, never ``rows x max_chunk``.

Two implementations with identical semantics:

- :func:`ragged_paged_attention_ref` — pure JAX (gather + masked
  softmax), the always-correct CPU/tier-1 path and the kernel's test
  oracle. It re-gathers the owning row's pages per query token, so its
  HBM traffic is ``N * S``; fine for the CPU mesh, not the fast path.
- :func:`ragged_paged_attention` — the Pallas TPU kernel. Grid over
  ``q_tile``-sized slices of the flat stream; the caller aligns each
  row's query span to ``q_tile`` so every grid cell belongs to exactly
  one row (``tile_row``), DMAs only that row's live pages
  (``ceil(kv_len / page_size)``, double-buffered), and runs a
  flash-style online softmax in VMEM scratch. ``q_tile=1`` degenerates
  to the old per-row decode kernel (one query per grid cell — the
  shape pure-decode windows dispatch, and the one a mixed dispatch
  reads its decode rows through: ``forward_ragged`` calls the kernel
  twice, at ``q_tile`` over the stream's aligned spans and at 1 over
  its packed tail, where rows of one token lie side by side).

HBM traffic per dispatch per layer is ``sum_rows(tiles_row * kv_row)``
tokens instead of the XLA gather's ``N * S``: the kernel reads each
row's context once per query tile, never the page-bucket envelope.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tokens per double-buffered DMA chunk: amortises DMA issue cost and
# matches the MXU's 128-lane tiling for the score matmul.
_CHUNK_TOKENS = 128


def ragged_supported(
    page_size: int, num_kv_heads: int, head_dim: int, kv_dtype
) -> bool:
    """Whether this KV layout compiles on real TPU hardware.

    Mosaic tiles the last two dims of every VMEM buffer ((8, 128) for
    f32, (16, 128) for bf16) and rejects DMA slices that aren't
    tile-aligned, so the collapsed lane dim (Hkv*D) must be a multiple
    of 128 and the page size a multiple of the sublane tile. Callers
    fall back to the pure-JAX reference otherwise (interpret mode has
    no such constraint).

    This gate is part of the *resolved* attention implementation, which
    is part of the AOT compile-manifest key (docs/aot.md): a layout
    that resolves differently on another host produces a different
    manifest hash, so a warm boot can never load executables built for
    the other implementation."""
    sublane = 16 if jnp.dtype(kv_dtype).itemsize == 2 else 8
    return (num_kv_heads * head_dim) % 128 == 0 and page_size % sublane == 0


# --------------------------------------------------------------- reference
def ragged_paged_attention_ref(
    q: jnp.ndarray,  # [N, H, D] flat query stream
    k_cache: jnp.ndarray,  # [P, ps, Hkv*D] (heads collapsed into lanes)
    v_cache: jnp.ndarray,
    page_table: jnp.ndarray,  # [R, PB] int32 (caller slices to the bucket)
    row_of: jnp.ndarray,  # [N] int32 owning row per query token
    positions: jnp.ndarray,  # [N] int32 absolute position, -1 = padding
    num_kv_heads: int | None = None,
    sm_scale: float | None = None,
    window: int | jnp.ndarray | None = None,
    softcap: float | None = None,
    bounds: jnp.ndarray | None = None,  # [N] last kv position seen; None = positions
) -> jnp.ndarray:
    """Pure-JAX ragged paged attention (the CPU/tier-1 parity path).

    Returns ``[N, H, D]`` in q's dtype; padding tokens (position -1)
    return exact zeros, like the kernel's. Matmuls run in the cache
    dtype with float32 accumulation, softmax in float32 — the same
    numeric contract as ``ops/attention.paged_attention``, so a
    one-token row here is bit-identical to the old decode gather.
    ``window``/``softcap`` carry the sliding-window and tanh-capped
    score variants (mistral/gemma2) exactly as ``paged_attention``
    does — the Pallas kernel does not implement them, so those model
    families stay on this path (the engine's attn resolution enforces
    it).

    Implementation note: each flat token becomes its own T=1 batch row
    of :func:`ops.attention.paged_attention` (its row's page table
    gathered per token). Delegating keeps the reduction SHAPES — and
    therefore the float rounding — identical to the decode window's
    per-step attention, which is what keeps a mixed dispatch's logits
    bit-equal to the step-by-step schedule even at exact bf16 argmax
    ties (the greedy identity suites exercise exactly such ties on
    repetitive prompts)."""
    from .attention import paged_attention

    out = paged_attention(
        q[:, None],  # [N, 1, H, D]
        k_cache,
        v_cache,
        page_table[row_of],  # [N, PB]
        (positions if bounds is None else bounds)[:, None],  # [N, 1]
        sm_scale=sm_scale,
        window=window,
        softcap=softcap,
    )
    return out[:, 0]


# ------------------------------------------------------------------ kernel
def _ragged_kernel(
    # scalar prefetch (SMEM)
    tile_row_ref,  # [T] int32 — owning batch row per query tile
    tile_kvlen_ref,  # [T] int32 — kv tokens the tile attends over (0=skip)
    positions_ref,  # [N] int32 — last kv position each flat query sees
    table_ref,  # [R, Pmax] int32 — page ids per row
    # inputs
    q_ref,  # [QT, H, D] VMEM — this tile's queries
    k_hbm,  # [P, ps, Hkv*D] — page pool, stays in HBM
    v_hbm,
    # output
    o_ref,  # [QT, H, D] VMEM
    # scratch
    k_buf,  # [2, cp, ps, Hkv*D] VMEM double buffer
    v_buf,
    acc_ref,  # [H*QT, D] f32 — output accumulator, rows = (kv head, g, i)
    m_ref,  # [H*QT, 128] f32 — running max (lane-replicated)
    l_ref,  # [H*QT, 128] f32 — running sum (lane-replicated)
    sems,  # DMA semaphores [2, 2*cp]
    *,
    ps: int,
    cp: int,
    hkv: int,
    hd: int,
    qpk: int,
    qt: int,
    pmax: int,
    scale: float,
):
    t = pl.program_id(0)
    row = tile_row_ref[t]
    kvlen = tile_kvlen_ref[t]
    n_chunks = pl.cdiv(kvlen, ps * cp)

    def chunk_dmas(c, slot):
        """The 2*cp page copies of chunk ``c`` into buffer ``slot``.

        Page indices beyond the row's table are clamped to a valid
        entry: the DMA still runs (keeping semaphore accounting static)
        and the tokens are masked out of the softmax below. (Hkv, D)
        are pre-collapsed into one lane dimension so every copy slices
        only leading (untiled) dims — Mosaic rejects slices of a lane
        dim narrower than the 128-lane tile."""
        dmas = []
        base = c * cp
        for j in range(cp):
            idx = jnp.minimum(base + j, pmax - 1)
            pid = table_ref[row, idx]
            dmas.append(
                pltpu.make_async_copy(
                    k_hbm.at[pid], k_buf.at[slot, j], sems.at[slot, 2 * j]
                )
            )
            dmas.append(
                pltpu.make_async_copy(
                    v_hbm.at[pid], v_buf.at[slot, j], sems.at[slot, 2 * j + 1]
                )
            )
        return dmas

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, -1e30)
    l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(n_chunks > 0)
    def _():
        for dma in chunk_dmas(0, 0):
            dma.start()

    # Per-query absolute positions (the causal bound), read from SMEM.
    # Padding queries carry -1: nothing satisfies kv_pos <= -1, so with
    # ``p`` masked below their softmax sum stays 0 and the final divide
    # maps them to zeros, whatever the pages the tile brought in hold.
    # Score rows are laid out (kv head, group, query): each head's
    # block is contiguous, and within it the query index varies
    # fastest — so row r's causal bound is query r % QT's position.
    # Built as a column from the start: a flat [qpk*QT] vector tiled
    # from [QT] outgrows one lane tile at 20 query heads a group
    # (Mosaic: "input offsets outside of the first tile").
    query = jax.lax.rem(
        jax.lax.broadcasted_iota(jnp.int32, (qpk * qt, 1), 0), qt
    )
    pos_rows = jnp.zeros((qpk * qt, 1), jnp.int32)
    for i in range(qt):
        pos_rows = jnp.where(query == i, positions_ref[t * qt + i], pos_rows)

    # [QT, H, D] -> [H', QT, D] with H' rows ordered (kv head, group):
    # per-kv-head slices are then contiguous row blocks.
    q = jnp.swapaxes(q_ref[...].astype(jnp.float32), 0, 1)  # [H, QT, D]
    S = cp * ps

    def body(c, _):
        slot = jax.lax.rem(c, 2)
        next_slot = jax.lax.rem(c + 1, 2)

        @pl.when(c + 1 < n_chunks)
        def _():
            for dma in chunk_dmas(c + 1, next_slot):
                dma.start()

        for dma in chunk_dmas(c, slot):
            dma.wait()

        tok_idx = c * S + jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
        k = k_buf[slot].reshape(S, hkv * hd)  # [S, Hkv*D]
        v = v_buf[slot].reshape(S, hkv * hd)
        for h in range(hkv):
            rows = slice(h * qpk * qt, (h + 1) * qpk * qt)
            cols = slice(h * hd, (h + 1) * hd)
            qh = q[h * qpk : (h + 1) * qpk].reshape(qpk * qt, hd)
            kh = k[:, cols].astype(jnp.float32)  # [S, D]
            s = (
                jax.lax.dot_general(
                    qh,
                    kh,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                * scale
            )  # [qpk*QT, S]
            live = tok_idx <= pos_rows
            s = jnp.where(live, s, -1e30)
            m_prev = m_ref[rows, :1]  # [qpk*QT, 1]
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            # The mask is finite, so a query with nothing to attend to
            # (padding inside a live tile) has m_new == -1e30 and every
            # exp(s - m_new) == 1: masking s alone would give it the
            # mean of whatever V slots the tile's chunks hold (the row's
            # context, its last page's slots past the length, clamped
            # table entries). A live query's masked p is already 0.
            p = jnp.where(live, jnp.exp(s - m_new), 0.0)
            l_ref[rows, :] = l_ref[rows, :] * alpha + jnp.sum(
                p, axis=1, keepdims=True
            )
            pv = jax.lax.dot_general(
                p.astype(v.dtype),
                v[:, cols],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [qpk*QT, D]
            acc_ref[rows, :] = acc_ref[rows, :] * alpha + pv
            m_ref[rows, :] = jnp.broadcast_to(m_new, m_ref[rows, :].shape)
        return 0

    jax.lax.fori_loop(0, n_chunks, body, 0)

    l = l_ref[:, :1]
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc_ref[...] / l_safe  # [H*QT, D], rows (kv head, g, i)
    out = out.reshape(hkv, qpk, qt, hd).transpose(2, 0, 1, 3)
    o_ref[...] = out.reshape(qt, hkv * qpk, hd).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("num_kv_heads", "q_tile", "sm_scale", "interpret"),
)
def ragged_paged_attention(
    q: jnp.ndarray,  # [N, H, D] flat query stream (N % q_tile == 0)
    k_cache: jnp.ndarray,  # [P, ps, Hkv*D] (heads collapsed into lanes)
    v_cache: jnp.ndarray,
    page_table: jnp.ndarray,  # [R, Pmax] int32
    row_of: jnp.ndarray,  # [N] int32 owning row per query token
    positions: jnp.ndarray,  # [N] int32 absolute position, -1 = padding
    num_kv_heads: int | None = None,
    q_tile: int = 8,
    sm_scale: float | None = None,
    interpret: bool = False,
    bounds: jnp.ndarray | None = None,  # [N] last kv position seen; None = positions
) -> jnp.ndarray:
    """Ragged paged attention over a flat query stream (Pallas TPU).

    Within one call the caller aligns each row's query span to
    ``q_tile`` flat slots (padding tokens carry position -1), so every
    tile belongs to exactly one row — each grid cell DMAs only that
    row's live pages and computes ``q_tile`` queries against them.
    ``q_tile=1`` needs no alignment: any slot is a cell of its own (one
    query per row, the old paged-decode kernel's grid; a decode window's
    step and the packed tail of a mixed stream). Returns [N, H, D] in
    q's dtype. Padding slots return exact zeros, inside a live tile as
    in a fully-empty one (inactive rows): routed experts sort every row
    of a dispatch together
    (``ops/moe.py``), so a padding row must not carry what memory no
    request owns into the rows beside it.

    The caller guarantees the fed tokens' K/V are already written
    (write-then-gather), so a tile's DMA bound is its max position + 1.
    ``bounds`` replaces the positions as what a query may see (its own
    position included; -1 for padding): the kernel reads nothing else of
    them, so the tile's DMA bound follows.
    """
    if bounds is not None:
        positions = bounds
    N, H, D = q.shape
    _, ps, fused = k_cache.shape
    Hkv = num_kv_heads if num_kv_heads is not None else fused // D
    pmax = page_table.shape[1]
    qpk = H // Hkv
    scale = sm_scale if sm_scale is not None else D**-0.5
    cp = max(1, min(_CHUNK_TOKENS // ps, pmax))
    qt = q_tile
    n_tiles = N // qt

    # Per-tile row + DMA bound, derived on device from the flat stream
    # (alignment makes every tile single-row; padding positions are -1
    # so the max is the tile's true causal horizon).
    tile_row = row_of.reshape(n_tiles, qt)[:, 0]
    tile_kvlen = positions.reshape(n_tiles, qt).max(axis=1) + 1

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec(
                (qt, H, D), lambda t, *_: (t, 0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (qt, H, D), lambda t, *_: (t, 0, 0), memory_space=pltpu.VMEM
        ),
        scratch_shapes=[
            pltpu.VMEM((2, cp, ps, Hkv * D), k_cache.dtype),
            pltpu.VMEM((2, cp, ps, Hkv * D), v_cache.dtype),
            pltpu.VMEM((H * qt, D), jnp.float32),
            pltpu.VMEM((H * qt, 128), jnp.float32),
            pltpu.VMEM((H * qt, 128), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2 * cp)),
        ],
    )
    kernel = functools.partial(
        _ragged_kernel,
        ps=ps,
        cp=cp,
        hkv=Hkv,
        hd=D,
        qpk=qpk,
        qt=qt,
        pmax=pmax,
        scale=scale,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, H, D), q.dtype),
        interpret=interpret,
    )(tile_row, tile_kvlen, positions, page_table, q, k_cache, v_cache)


def ragged_decode_attention(
    q: jnp.ndarray,  # [B, H, D] — one query per row
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    page_table: jnp.ndarray,  # [B, Pmax] int32
    lengths: jnp.ndarray,  # [B] int32 tokens to attend over (0 = inactive)
    num_kv_heads: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Pure-decode shape of the ragged kernel: one query per row at
    ``q_tile=1`` (row b attends its own ``lengths[b]`` tokens; rows
    with length 0 return zeros). This is the shape every step of a
    compiled decode window dispatches."""
    B = q.shape[0]
    row_of = jnp.arange(B, dtype=jnp.int32)
    return ragged_paged_attention(
        q,
        k_cache,
        v_cache,
        page_table,
        row_of,
        lengths - 1,  # position of the newest written token
        num_kv_heads=num_kv_heads,
        q_tile=1,
        interpret=interpret,
    )
