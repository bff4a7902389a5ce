"""Learned sparse attention's selector: index scores over a row's paged
index keys, and the exact k-th largest of them a query.

A model with an *indexer* (``ModelConfig.index_topk`` > 0: glm_moe_dsa)
lets a query at position ``t`` attend to the ``min(index_topk, t + 1)``
context positions ``s <= t`` of largest **index score**

    ``I[t, s] = sum_j w[t, j] * relu(q_I[t, j] . k_I[s])``

(``j`` over ``index_n_heads``; ``q_I``, ``w`` the query token's, ``k_I`` the
context token's key, one a token and layer, cached in the **second page
pool** beside the latent row: ``models/llama.py::_mla_half`` computes all
three). Two things are computed here, each in two implementations of one
meaning:

- the scores: :func:`index_scores_ref` (pure JAX: a row's pages gathered
  a query; the CPU path and the kernel's oracle) and :func:`index_scores`
  (a Pallas TPU kernel: a grid cell is a tile of ``q_tile`` stream slots of
  one row, walks that row's live pages of the index pool double-buffered
  as the attention kernel walks the latent pool, and writes the tile's
  scores a context chunk at a time). A position a query may not see reads
  ``-inf``.
- the selection: the exact ``k``-th largest score a query, by bisection
  on the scores' bit patterns (32 counting passes: no sort, no
  approximation): :func:`kth_largest` (pure JAX, a query a row) and
  :func:`kth_largest_tiled` (a Pallas TPU kernel over the scores kernel's
  layout: a grid cell reads one tile's scores into fast memory once and
  counts there, over the tile's live chunks alone). :func:`select` and
  :func:`select_tiled` give each query its threshold ``tau`` and how many
  positions that score exactly ``tau`` it may keep (``allow``: ``k`` less
  those above; ``lax.top_k`` breaks a tie by the lower position, and so do
  the readers), or ``-inf`` and no limit where it sees no more than ``k``
  positions. The attention then keeps
  ``s`` where ``I[t, s] > tau[t]``, and the first ``allow[t]`` positions
  where it is equal (:func:`kept` says it in ``jax.numpy``): the kernel
  masks inside its pass over the row's pages
  (``ops/ragged_attention.py``, ``keep``), the gather masks its scores
  (``ops/attention.py::paged_attention``, ``keep``).

The kernel's scores are laid out as the attention kernel reads them:
``[tiles, chunks, q_tile, chunk tokens]`` (:func:`tiled_shape`), chunk
``c`` holding context positions ``[c * S, (c + 1) * S)`` with ``S`` the
attention kernel's chunk (:func:`chunk_pages` pages), so either kernel
addresses a chunk by a leading index. :func:`by_query` lays them out a
query a row for a reader.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ragged_attention import _CHUNK_TOKENS


def chunk_pages(page_size: int, pmax: int) -> int:
    """Pages of one double-buffered chunk of the attention kernel's walk
    (``ragged_paged_attention``'s ``cp``)."""
    return max(1, min(_CHUNK_TOKENS // page_size, pmax))


def tiled_shape(n: int, q_tile: int, page_size: int, pmax: int) -> tuple[int, ...]:
    """Shape of the kernel's scores for ``n`` stream slots over tables of
    ``pmax`` pages: ``[tiles, chunks, q_tile, chunk tokens]``."""
    cp = chunk_pages(page_size, pmax)
    return n // q_tile, -(-pmax // cp), q_tile, cp * page_size


def by_query(scores: jnp.ndarray) -> jnp.ndarray:
    """Tiled scores ``[T, C, qt, S]`` as ``[T * qt, C * S]``: a query a
    row, context positions in order."""
    T, C, qt, S = scores.shape
    return jnp.swapaxes(scores, 1, 2).reshape(T * qt, C * S)


# --------------------------------------------------------------- reference
def index_scores_ref(
    q_i: jnp.ndarray,  # [B, T, Hi, Di]
    w_i: jnp.ndarray,  # [B, T, Hi] float32
    pool: jnp.ndarray,  # [P, ps, lanes >= Di]: index keys, zeros behind Di
    page_table: jnp.ndarray,  # [B, Pmax] int32
    bounds: jnp.ndarray,  # [B, T] int32 last context position seen, -1 = none
) -> jnp.ndarray:
    """Index scores of each query against its row's pages: ``[B, T, S]``
    float32, ``S = Pmax * ps``, ``-inf`` at positions past the query's
    bound. The products run in the pool's dtype with float32 accumulation,
    as attention's do."""
    B = q_i.shape[0]
    ps = pool.shape[1]
    S = page_table.shape[1] * ps
    k = pool[page_table].reshape(B, S, -1)[..., : q_i.shape[-1]]
    dots = jnp.einsum(
        "bthd,bsd->bths", q_i.astype(k.dtype), k,
        preferred_element_type=jnp.float32,
    )
    scores = jnp.einsum(
        "bths,bth->bts", jax.nn.relu(dots), w_i.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    seen = jnp.arange(S, dtype=jnp.int32) <= bounds[..., None]
    return jnp.where(seen, scores, -jnp.inf)


def _sortable(scores: jnp.ndarray) -> jnp.ndarray:
    """float32 -> uint32 whose unsigned order is the floats' (``-0.0`` as
    ``+0.0``; no NaN comes here)."""
    bits = jax.lax.bitcast_convert_type(scores + 0.0, jnp.int32)
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return jax.lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(0x80000000)


def _unsortable(key: jnp.ndarray) -> jnp.ndarray:
    bits = jax.lax.bitcast_convert_type(key ^ jnp.uint32(0x80000000), jnp.int32)
    bits = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


# A query that keeps every position it sees: no limit on ties.
NO_LIMIT = float(2**30)


def kth_largest(scores: jnp.ndarray, k: int, count: bool = False):
    """The exact ``k``-th largest value over the last axis (float32,
    ``-inf`` allowed, at least ``k`` entries): the largest ``v`` with
    ``count(scores >= v) >= k``, built bit by bit from the scores'
    order-preserving bit patterns in 32 counting passes. With ``count``
    also how many entries lie strictly above it (int32)."""
    keys = _sortable(scores)

    def bit(i, ans):
        cand = ans | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        at_least = jnp.sum(keys >= cand[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(at_least >= k, cand, ans)

    ans = jax.lax.fori_loop(0, 32, bit, jnp.zeros(scores.shape[:-1], jnp.uint32))
    if not count:
        return _unsortable(ans)
    above = jnp.sum(keys > ans[..., None], axis=-1, dtype=jnp.int32)
    return _unsortable(ans), above


def _selection(tau, above, bounds, k: int):
    """``(tau, allow)`` of a selection from each query's ``k``-th largest
    score and the number above it: those where the query sees more than
    ``k`` positions (``bounds >= k``), else ``-inf`` and ``NO_LIMIT``."""
    selecting = bounds >= k
    return (
        jnp.where(selecting, tau, -jnp.inf),
        jnp.where(selecting, (k - above).astype(jnp.float32), NO_LIMIT),
    )


def select(scores: jnp.ndarray, bounds: jnp.ndarray, k: int):
    """Each query's selection as a threshold: ``(tau, allow)``, float32,
    the scores' shape without the last axis (as ``bounds`` is). Where the
    query sees more than ``k`` positions (``bounds >= k``): the ``k``-th
    largest of its scores, and ``k`` less the scores above it, the
    positions AT it that are kept, lowest first. Otherwise ``-inf`` and
    ``NO_LIMIT``: all it sees."""
    return _selection(*kth_largest(scores, k, count=True), bounds, k)


def kept(scores: jnp.ndarray, tau: jnp.ndarray, allow: jnp.ndarray) -> jnp.ndarray:
    """Which positions (last axis, in order) a selection keeps: the scores
    above ``tau`` and the first ``allow`` equal to it. bool, ``scores``'
    shape; ``tau``, ``allow`` lack the last axis."""
    tie = scores == tau[..., None]
    upto = jnp.cumsum(tie.astype(jnp.float32), axis=-1)
    return (scores > tau[..., None]) | (tie & (upto <= allow[..., None]))


# ------------------------------------------------------------------ kernel
def _scores_kernel(
    # scalar prefetch (SMEM)
    tile_row_ref,  # [T] int32 owning row a tile
    tile_kvlen_ref,  # [T] int32 context tokens the tile's queries see (0 = none)
    table_ref,  # [R, Pmax] int32
    # inputs
    pos_ref,  # [qt, 1] int32: last position each query sees
    q_ref,  # [qt, Hi, lanes]: the tile's index queries, zeros behind Di
    w_ref,  # [Hi * qt, 1] float32: the weight of score row (head, query)
    k_hbm,  # [P, ps, lanes]: the index pool, stays in HBM
    # output
    o_ref,  # [C, qt, S] float32
    # scratch
    k_buf,  # [2, cp, ps, lanes]
    sems,  # DMA semaphores [2, cp]
    *,
    qt: int,
    hi: int,
    ps: int,
    cp: int,
    pmax: int,
):
    """A grid cell is one tile of ``qt`` slots of one row: one
    double-buffered pass over the row's first ``kvlen`` index keys, every
    chunk's scores ``[Hi * qt, S]`` cut at zero, weighed and summed over
    the heads on the vector unit (rows lie (head, query): ``Hi`` slabs of
    ``qt`` rows added up), masked at each query's bound and stored as
    chunk ``c`` of the tile."""
    t = pl.program_id(0)
    row, kvlen = tile_row_ref[t], tile_kvlen_ref[t]
    S = cp * ps
    n_chunks = pl.cdiv(kvlen, S)
    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, jnp.float32)

    def chunk_dmas(c, slot):
        dmas = []
        for j in range(cp):
            pid = table_ref[row, jnp.minimum(c * cp + j, pmax - 1)]
            dmas.append(pltpu.make_async_copy(
                k_hbm.at[pid], k_buf.at[slot, j], sems.at[slot, j]
            ))
        return dmas

    @pl.when(n_chunks > 0)
    def _():
        for dma in chunk_dmas(0, 0):
            dma.start()

    lanes = q_ref.shape[-1]
    # [qt, Hi, lanes] -> rows (head, query), in the pool's dtype (exact:
    # the queries came in it)
    q = jnp.swapaxes(q_ref[...].astype(jnp.float32), 0, 1)
    q = q.reshape(hi * qt, lanes).astype(k_buf.dtype)
    weigh = jnp.broadcast_to(w_ref[...], (hi * qt, S))
    pos = pos_ref[...]

    def body(c, _):
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < n_chunks)
        def _():
            for dma in chunk_dmas(c + 1, jax.lax.rem(c + 1, 2)):
                dma.start()

        for dma in chunk_dmas(c, slot):
            dma.wait()
        k = k_buf[slot].reshape(S, lanes)
        dots = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [Hi * qt, S]
        weighed = jnp.maximum(dots, 0.0) * weigh
        if qt == 1:
            scores = weighed.sum(axis=0, keepdims=True)
        else:
            scores = weighed.reshape(hi, qt, S).sum(axis=0)  # [qt, S]
        tok = c * S + jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
        o_ref[c] = jnp.where(tok <= pos, scores, -jnp.inf)
        return 0

    jax.lax.fori_loop(0, n_chunks, body, 0)


@functools.partial(jax.jit, static_argnames=("q_tile", "interpret"))
def index_scores(
    q_i: jnp.ndarray,  # [N, Hi, Di] flat query stream (N % q_tile == 0)
    w_i: jnp.ndarray,  # [N, Hi] float32
    pool: jnp.ndarray,  # [P, ps, lanes >= Di]
    page_table: jnp.ndarray,  # [R, Pmax] int32
    row_of: jnp.ndarray,  # [N] int32
    bounds: jnp.ndarray,  # [N] int32 last context position seen, -1 = none
    q_tile: int = 8,
    interpret: bool = False,
) -> jnp.ndarray:
    """Index scores over a flat query stream whose tiles of ``q_tile``
    slots are one row's each (any slot its own at ``q_tile`` 1), as the
    attention kernel's caller lays it (Pallas TPU). Returns
    :func:`tiled_shape` float32; a position past a query's bound, and every
    position of a padding slot, reads ``-inf``."""
    N, Hi, Di = q_i.shape
    _, ps, lanes = pool.shape
    pmax = page_table.shape[1]
    qt = q_tile
    T, C, _, S = tiled_shape(N, qt, ps, pmax)
    cp = chunk_pages(ps, pmax)
    if Di < lanes:
        q_i = jnp.pad(q_i, ((0, 0), (0, 0), (0, lanes - Di)))
    q_i = q_i.astype(pool.dtype)
    tile_row = row_of.reshape(T, qt)[:, 0]
    tile_kvlen = bounds.reshape(T, qt).max(axis=1) + 1
    # a tile's weights as a column over its score rows, laid (head, query)
    weigh = jnp.swapaxes(w_i.astype(jnp.float32).reshape(T, qt, Hi), 1, 2)
    weigh = weigh.reshape(T, Hi * qt, 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((None, qt, 1), lambda t, *_: (t, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((qt, Hi, lanes), lambda t, *_: (t, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((None, Hi * qt, 1), lambda t, *_: (t, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (None, C, qt, S), lambda t, *_: (t, 0, 0, 0), memory_space=pltpu.VMEM
        ),
        scratch_shapes=[
            pltpu.VMEM((2, cp, ps, lanes), pool.dtype),
            pltpu.SemaphoreType.DMA((2, cp)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_scores_kernel, qt=qt, hi=Hi, ps=ps, cp=cp, pmax=pmax),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, C, qt, S), jnp.float32),
        interpret=interpret,
        name="dsa_index_scores",
    )(tile_row, tile_kvlen, page_table, bounds.reshape(T, qt, 1), q_i, weigh, pool)


_INT_MIN = -(2**31)
_OUT_LANES = 128  # a tile's thresholds leave the kernel a full lane row each


def _kth_kernel(
    tile_kvlen_ref,  # [T] int32 (SMEM): context tokens the tile's queries see
    s_ref,  # [C, qt, S] float32: the tile's scores
    tau_ref,  # [qt, _OUT_LANES] float32: each query's k-th largest score
    above_ref,  # [qt, _OUT_LANES] float32: how many of its scores lie above it
    key_buf,  # [C, qt, S] int32 scratch: the scores as integers of their order
    *,
    k: int,
):
    """A grid cell is one tile: its scores' live chunks become integers
    whose signed order is the floats' (``_sortable`` less its last flip),
    once, and the 32 counting passes of :func:`kth_largest` and the count
    above the answer run over them in fast memory. The answer is built as
    the unsigned pattern ``_sortable`` orders by; a candidate is compared
    as the signed integer that pattern is with its top bit flipped."""
    C, qt, S = s_ref.shape
    # Chunks go through the passes ``step`` at a time, so a pass is a few
    # long iterations and not one short one a chunk; the chunks behind the
    # last live one that this rounds in are ``-inf`` like every unseen score.
    step = math.gcd(C, 8)
    n_steps = pl.cdiv(tile_kvlen_ref[pl.program_id(0)], S * step)

    def to_key(b, _):
        for c in range(step):
            bits = jax.lax.bitcast_convert_type(s_ref[b * step + c] + 0.0, jnp.int32)
            key_buf[b * step + c] = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
        return 0

    jax.lax.fori_loop(0, n_steps, to_key, 0)

    def count(floor, strict):  # a query's live keys at (above) ``floor``
        floor = jnp.broadcast_to(floor, (qt, S))

        def chunks(b, acc):
            for c in range(step):
                key = key_buf[b * step + c]
                acc = acc + jnp.where(key > floor if strict else key >= floor, 1.0, 0.0)
            return acc

        acc = jax.lax.fori_loop(0, n_steps, chunks, jnp.zeros((qt, S), jnp.float32))
        return acc.sum(axis=1, keepdims=True)

    def bit(i, ans):
        cand = ans | jnp.left_shift(jnp.int32(1), jnp.int32(31) - i)
        return jnp.where(count(cand ^ jnp.int32(_INT_MIN), False) >= k, cand, ans)

    ans = jax.lax.fori_loop(0, 32, bit, jnp.zeros((qt, 1), jnp.int32))
    key = ans ^ jnp.int32(_INT_MIN)
    above = count(key, True)
    bits = jnp.where(key < 0, key ^ jnp.int32(0x7FFFFFFF), key)
    tau = jax.lax.bitcast_convert_type(bits, jnp.float32)
    tau_ref[...] = jnp.broadcast_to(tau, tau_ref.shape)
    above_ref[...] = jnp.broadcast_to(above, above_ref.shape)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def kth_largest_tiled(
    scores: jnp.ndarray,  # [T, C, qt, S] float32 (:func:`index_scores`)
    bounds: jnp.ndarray,  # [T, qt] int32 last context position seen, -1 = none
    k: int,
    interpret: bool = False,
):
    """:func:`kth_largest` with ``count`` over tiled scores (Pallas TPU),
    a query a ``[T, qt]`` entry: ``(k-th largest, number above it)``,
    float32 and int32. Only a tile's live chunks are read (those that hold
    a position one of its queries sees; the rest are ``-inf`` by
    :func:`index_scores`), so the answer is :func:`kth_largest`'s wherever
    the query sees at least ``k`` positions, and not to be read elsewhere."""
    T, C, qt, S = scores.shape
    out = jax.ShapeDtypeStruct((T, qt, _OUT_LANES), jnp.float32)
    line = pl.BlockSpec(
        (None, qt, _OUT_LANES), lambda t, *_: (t, 0, 0), memory_space=pltpu.VMEM
    )
    tau, above = pl.pallas_call(
        functools.partial(_kth_kernel, k=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(T,),
            in_specs=[pl.BlockSpec(
                (None, C, qt, S), lambda t, *_: (t, 0, 0, 0), memory_space=pltpu.VMEM
            )],
            out_specs=[line, line],
            scratch_shapes=[pltpu.VMEM((C, qt, S), jnp.int32)],
        ),
        out_shape=[out, out],
        interpret=interpret,
        name="dsa_kth_largest",
    )(bounds.max(axis=1) + 1, scores)
    return tau[..., 0], above[..., 0].astype(jnp.int32)


def select_tiled(scores: jnp.ndarray, bounds: jnp.ndarray, k: int, interpret: bool = False):
    """:func:`select` over tiled scores ``[T, C, qt, S]`` and ``bounds``
    ``[T, qt]`` by :func:`kth_largest_tiled`: ``(tau, allow)``, ``[T, qt]``."""
    return _selection(*kth_largest_tiled(scores, bounds, k, interpret=interpret), bounds, k)
