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
  (a Pallas TPU kernel). A grid cell serves a **query block** of
  :func:`index_block` consecutive stream slots, a whole number of the
  stream's tiles of ``q_tile`` slots of one row (128 slots, sixteen tiles,
  from the call's shapes alone; the tile itself where ``q_tile`` is 1).
  Where every live tile of the block belongs to one row
  (``ragged_attention.block_plan``, the attention kernel's own rule,
  derived on the device from ``row_of`` and the bounds) the cell makes ONE
  double-buffered pass over that row's live pages of the index pool for all
  its queries, each masked at its own bound: a chunk of keys is fetched
  once for 128 queries, and its scores are products of 1,024 score rows
  (32 heads of 32 slots) and not of 256. A block that holds several rows (a
  span's first and last) walks its tiles one pass each. Either way a
  score is the same sum in the same order, bit for bit. The scores go to
  HBM a group of eight context chunks a copy while the next is computed;
  where the block is one tile they are the cell's output block, as they
  were. A position a query may not see reads ``-inf``: past its bound in
  a chunk some query of its pass sees, and in every chunk behind, which
  the cell fills from a constant buffer.
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
    lane0: int = 0,  # the index key's first lane in the pool's row
) -> jnp.ndarray:
    """Index scores of each query against its row's pages: ``[B, T, S]``
    float32, ``S = Pmax * ps``, ``-inf`` at positions past the query's
    bound. The products run in the pool's dtype with float32 accumulation,
    as attention's do. ``lane0``: where the index key lies in a row that
    holds more than it (a grouped-query model's K row:
    ``ModelConfig.kv_row_parts``)."""
    B = q_i.shape[0]
    ps = pool.shape[1]
    S = page_table.shape[1] * ps
    k = pool[page_table].reshape(B, S, -1)[..., lane0 : lane0 + q_i.shape[-1]]
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
# Stream slots a grid cell of the scores kernel serves where the stream has
# tiles of one row: sixteen tiles of 8, so a chunk of keys is fetched once
# for 128 queries (swept on the chip at 8 / 64 / 128 / 256:
# tools/index_scores_block.py; 256 is 5 % faster a call and leaves twice the
# slots in blocks of two rows; PERF.md §6, PR 50).
_BLOCK_SLOTS = 128
# What a cell of that many slots may hold in VMEM (of a v5e core's 128 MiB).
_BLOCK_VMEM_BYTES = 24 << 20
# Slots of a block whose score rows (every head's) make one product against a
# chunk's keys: ``Hi x _PRODUCT_SLOTS`` rows, summed over the heads as a tile's
# ``Hi x q_tile`` are.
_PRODUCT_SLOTS = 32
# Chunks a step of a pass where the block is one tile: a tile's product and sum
# are a chain of short operations, and eight chunks' chains run side by side.
_TILE_STEP_CHUNKS = 8


def _cell_vmem_bytes(block: int, n_heads: int, lanes: int, item: int) -> int:
    """VMEM a cell of ``block`` slots holds, from its shapes: the queries
    (double-buffered by the pipeline, heads padded to a sublane tile) and
    their float32 turn to rows by head, the same by head in the pool's
    dtype, a weight a score row as a column and as a chunk's lanes, and two
    groups of eight chunks of scores on their way out beside one of ``-inf``."""
    heads = -(-n_heads // 16) * 16
    queries = block * lanes * (2 * heads * item + n_heads * (8 + item))
    weights = block * n_heads * (2 * 128 + _CHUNK_TOKENS) * 4
    return queries + weights + 3 * 8 * block * _CHUNK_TOKENS * 4


def index_block(q_tile: int, n_heads: int, lanes: int = 128, item: int = 2) -> int:
    """Stream slots a grid cell of :func:`index_scores` serves in one pass
    over a row's index keys: a whole number of ``q_tile``s, ``_BLOCK_SLOTS``
    where a cell of ``n_heads`` index heads of ``lanes`` (``item`` bytes a
    value) holds that in VMEM, else the largest halving of it that does.
    From the call's shapes alone: the kernel and the engine's counter
    (``dynamo_dsa_index_slots_total``) both ask here. A stream of tiles of
    one slot (a window's step, a mixed stream's packed tail) has a row a
    slot and nothing to read together: the block is the tile."""
    if q_tile == 1:
        return 1
    block = max(1, _BLOCK_SLOTS // q_tile) * q_tile
    while block > q_tile and _cell_vmem_bytes(block, n_heads, lanes, item) > _BLOCK_VMEM_BYTES:
        block = max(1, block // q_tile // 2) * q_tile
    return block


def _scores_kernel(
    # scalar prefetch (SMEM): ``ragged_attention.block_plan``'s four, and the table
    tile_row_ref,  # [B * nt] int32 owning row a tile
    tile_kvlen_ref,  # [B * nt] int32 context tokens the tile's queries see (0 = none)
    block_row_ref,  # [B] int32
    block_kvlen_ref,  # [B] int32 context tokens of the block's one pass; -1 = by tile
    table_ref,  # [R, Pmax] int32
    # inputs
    pos_ref,  # [QB, 1] int32: last position each query sees
    q_ref,  # [QB, Hi, lanes]: the block's index queries, zeros behind Di
    w_ref,  # [nt, Hi * qt, 1] float32: a tile's weight of score row (head, query)
    k_hbm,  # [P, ps, lanes]: the index pool, stays in HBM
    # output: the cell's tile [C, qt, S] in VMEM where the block is one tile,
    # else all the scores [T, C, qt, S] in HBM, written a group of chunks a copy
    o_ref,
    # scratch
    k_buf,  # [2, U * cp, ps, lanes]: a step's chunks of keys
    sems,  # DMA semaphores [2, U * cp]
    *block_scratch,  # a block of several tiles: ``_index_scores`` names them
    qt: int,
    nt: int,
    hi: int,
    ps: int,
    cp: int,
    pmax: int,
    n_tiles: int,
    n_chunks_max: int,
    sub: int,
    lane0: int = 0,
):
    """A grid cell is a block of ``nt`` tiles of ``qt`` slots. Where its
    live tiles are one row's (``block_kvlen`` >= 0) it makes ONE
    double-buffered pass over that row's first ``block_kvlen`` index keys
    for all ``nt * qt`` queries; where they are several rows' it walks its
    tiles, each a pass of its own over its row. Either way a chunk's scores
    are one product of ``[Hi * w, lanes]`` score rows (laid (head, query):
    ``w`` the tile's ``qt`` slots, or ``sub`` slots of the block at a time)
    cut at zero, weighed and summed over the heads on the vector unit (``Hi``
    slabs of ``w`` rows added up), masked at each query's own bound and stored
    as chunk ``c`` of the slots' tiles: the same arithmetic a score whichever
    pass computes it. Chunks behind a pass's last are filled with ``-inf``."""
    b = pl.program_id(0)
    S = cp * ps
    lanes = q_ref.shape[-1]
    C = n_chunks_max

    U = k_buf.shape[1] // cp  # chunks a step of a pass

    def walk(row, kvlen, chunk, rolled: bool = False):
        """One double-buffered pass over ``row``'s first ``kvlen`` index
        keys, ``U`` chunks a step (their products and sums do not wait for
        one another): ``chunk(c, keys [S, lanes])`` a chunk, also the ones
        behind the last live one that make its step whole (no query sees
        them). Every DMA it starts it waits for, so passes follow one
        another on the same buffers. A chunk's page copies are issued side
        by side and the step's chunks in a loop (all sixty-four in a list
        are as much to trace and lower as the rest of the kernel, in every
        variant's program; all in a loop issue a third slower); ``rolled``
        loops over the pages too (a block's tiles by themselves: few slots,
        and a second list for them is a quarter of the kernel's trace)."""
        n_steps = pl.cdiv(kvlen, S * U)

        def pages(s, slot, act: str):
            first = s * (U * cp)

            def page(at):
                pid = table_ref[row, jnp.minimum(first + at, pmax - 1)]
                # a row that holds more than the index key: its lanes alone
                keys = k_hbm.at[pid] if k_hbm.shape[-1] == lanes else (
                    k_hbm.at[pid, :, pl.ds(lane0, lanes)]
                )
                getattr(pltpu.make_async_copy(
                    keys, k_buf.at[slot, at], sems.at[slot, at],
                ), act)()

            def of_chunk(u, _):
                for j in range(1 if rolled else cp):
                    page(u + j if rolled else u * cp + j)
                return 0

            if U == 1 and not rolled:
                of_chunk(0, 0)
            else:
                jax.lax.fori_loop(0, U * cp if rolled else U, of_chunk, 0)

        @pl.when(n_steps > 0)
        def _():
            pages(0, 0, "start")

        def body(s, _):
            slot = s & 1

            @pl.when(s + 1 < n_steps)
            def _():
                pages(s + 1, 1 - slot, "start")

            pages(s, slot, "wait")
            for u in range(U):
                chunk(s * U + u, k_buf[slot, u * cp : (u + 1) * cp].reshape(S, lanes))
            return 0

        jax.lax.fori_loop(0, n_steps, body, 0)

    def by_head(q):
        """Queries ``[w, Hi, lanes]`` as score rows (head, query), in the
        pool's dtype (exact: the queries came in it)."""
        q = jnp.swapaxes(q.astype(jnp.float32), 0, 1)
        return q.reshape(hi * q.shape[1], lanes).astype(k_buf.dtype)

    def scores(q, weigh, pos, c, k):
        """Chunk ``c``'s scores ``[w, S]`` of the score rows ``q`` ([Hi * w,
        lanes]) with weights ``weigh`` ([Hi * w, S]) against the keys ``k``,
        ``-inf`` past the bounds ``pos`` ([w, 1])."""
        w = q.shape[0] // hi
        dots = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [Hi * w, S]
        weighed = jnp.maximum(dots, 0.0) * weigh
        if w == 1:
            summed = weighed.sum(axis=0, keepdims=True)
        else:
            summed = weighed.reshape(hi, w, S).sum(axis=0)
        tok = c * S + jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
        return jnp.where(tok <= pos, summed, -jnp.inf)

    if nt == 1:
        o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, jnp.float32)
        q = by_head(q_ref[...])
        weigh = jnp.broadcast_to(w_ref[0], (hi * qt, S))
        pos = pos_ref[...]

        def chunk(c, k):
            o_ref[c] = scores(q, weigh, pos, c, k)

        walk(tile_row_ref[b], tile_kvlen_ref[b], chunk)
        return

    q_s, weigh_s, o_buf, fill_buf, out_sems = block_scratch
    G = fill_buf.shape[1]  # chunks a copy out covers, 1 / 2 / 4 / 8: C is whole such groups
    shift = G.bit_length() - 1
    fill_buf[...] = jnp.full(fill_buf.shape, -jnp.inf, jnp.float32)

    def write(first, tiles: int, row, kvlen, stage, rolled: bool = False):
        """The pass of ``tiles`` tiles from tile ``first`` of the stream over
        ``row``'s first ``kvlen`` keys. ``stage(c, keys, at)`` puts a live
        chunk's scores into ``o_buf[at]``; a group of ``G`` chunks is one
        copy out (``G`` x 4 KB a tile and piece: a chunk a copy ran at a
        fifth of that), made while the next group is computed, the last
        group's chunks behind the live ones ``-inf``; the groups behind it are
        copied from ``fill_buf`` meanwhile."""
        n_chunks = pl.cdiv(kvlen, S)  # a step is a chunk here (U == 1)
        n_groups = pl.cdiv(n_chunks, G)

        def to(g):
            return o_ref.at[pl.ds(first, tiles), pl.ds(g * G, G)]

        def out_copy(g, slot):
            return pltpu.make_async_copy(
                o_buf.at[slot, pl.ds(0, tiles)], to(g), out_sems.at[slot]
            )

        def fill(act: str):
            def group(g, _):
                getattr(pltpu.make_async_copy(
                    fill_buf.at[pl.ds(0, tiles)], to(g), out_sems.at[2]
                ), act)()
                return 0

            jax.lax.fori_loop(n_groups, C // G, group, 0)

        def chunk(c, k):
            g, i = c >> shift, c & (G - 1)
            slot = g & 1

            @pl.when((i == 0) & (g >= 2))
            def _():
                out_copy(g - 2, slot).wait()

            stage(c, k, (slot, pl.ds(0, tiles), i))

            @pl.when(i == G - 1)
            def _():
                out_copy(g, slot).start()

        fill("start")
        walk(row, kvlen, chunk, rolled)

        @pl.when(n_chunks & (G - 1) > 0)
        def _():
            slot = (n_groups - 1) & 1

            def behind(i, _):
                o_buf[slot, pl.ds(0, tiles), i] = jnp.full((tiles, qt, S), -jnp.inf, jnp.float32)
                return 0

            jax.lax.fori_loop(n_chunks & (G - 1), G, behind, 0)
            out_copy(n_groups - 1, slot).start()

        for back in (2, 1):
            @pl.when(n_groups >= back)
            def _(back=back):
                out_copy(n_groups - back, (n_groups - back) & 1).wait()
        fill("wait")

    sub_tiles = sub // qt

    def whole(first, tiles: int):
        """The block's one pass (its first ``tiles`` tiles are the
        stream's): the score rows of ``sub`` slots a product, and their
        weights as the lanes of a chunk, laid out once."""
        for j in range(nt // sub_tiles):
            q_s[j] = by_head(q_ref[j * sub : (j + 1) * sub])

        def spread(h, _):
            head = pl.ds(pl.multiple_of(h * qt, qt), qt)
            for j in range(nt // sub_tiles):
                column = w_ref[j * sub_tiles : (j + 1) * sub_tiles, head, :]
                weigh_s[j, pl.ds(pl.multiple_of(h * sub, sub), sub), :] = (
                    jnp.broadcast_to(column.reshape(sub, 1), (sub, S))
                )
            return 0

        jax.lax.fori_loop(0, hi, spread, 0)

        def stage(c, k, at):
            # one after another in one basic block: a product runs while the
            # one before it is weighed and summed
            for j in range(nt // sub_tiles):
                if j * sub_tiles < tiles:
                    o_buf[at[0], j * sub_tiles : (j + 1) * sub_tiles, at[2]] = scores(
                        q_s[j], weigh_s[j], pos_ref[j * sub : (j + 1) * sub, :], c, k
                    ).reshape(sub_tiles, qt, S)

        write(first, tiles, block_row_ref[b], block_kvlen_ref[b], stage)

    def by_tile(first, tiles: int):
        def tile(j, _):
            t = first + j
            at = pl.ds(pl.multiple_of(j * qt, qt), qt)
            q = by_head(q_ref[at])
            weigh = jnp.broadcast_to(w_ref[j], (hi * qt, S))

            def stage(c, k, to):
                o_buf[to] = scores(q, weigh, pos_ref[at, :], c, k)[None]

            write(t, 1, tile_row_ref[t], tile_kvlen_ref[t], stage, rolled=True)
            return 0

        jax.lax.fori_loop(0, tiles, tile, 0)

    # The stream's last block may hold fewer tiles than ``nt``: what lies
    # behind them is no part of the scores.
    n_blocks = -(-n_tiles // nt)
    last = n_tiles - (n_blocks - 1) * nt
    for form, taken in ((whole, block_kvlen_ref[b] >= 0), (by_tile, block_kvlen_ref[b] < 0)):
        if last == nt:
            pl.when(taken)(functools.partial(form, b * nt, nt))
        else:
            is_last = b == n_blocks - 1
            pl.when(taken & jnp.logical_not(is_last))(functools.partial(form, b * nt, nt))
            pl.when(taken & is_last)(functools.partial(form, b * nt, last))


def _index_scores(q_i, w_i, pool, page_table, row_of, bounds, q_tile, block, interpret,
                  product=_PRODUCT_SLOTS, step_chunks=_TILE_STEP_CHUNKS, lane0=None):
    """:func:`index_scores` at a block of ``block`` slots a grid cell, ``product``
    of them a product (``step_chunks`` chunks a step where the block is a tile)."""
    from .ragged_attention import block_plan

    N, Hi, Di = q_i.shape
    _, ps, lanes = pool.shape
    if lane0 is not None:  # the index key's lanes of a row that holds more
        lanes = key_lanes(Di)
    pmax = page_table.shape[1]
    block = min(block, N)  # a stream shorter than a block is one block
    qt, nt = q_tile, block // q_tile
    T, C, _, S = tiled_shape(N, qt, ps, pmax)
    cp = chunk_pages(ps, pmax)
    if Di < lanes:
        q_i = jnp.pad(q_i, ((0, 0), (0, 0), (0, lanes - Di)))
    q_i = q_i.astype(pool.dtype)
    # The last block of a stream that is no whole number of blocks reads past
    # the queries' end: its plan, bounds and weights are padded here, what it
    # computes there is masked and never written.
    plan = block_plan(row_of, bounds, qt, block)
    B = plan[2].shape[0]
    bounds = jnp.pad(bounds, (0, B * block - N), constant_values=-1)
    # a tile's weights as a column over its score rows, laid (head, query)
    weigh = jnp.swapaxes(w_i.astype(jnp.float32).reshape(T, qt, Hi), 1, 2)
    weigh = jnp.pad(weigh.reshape(T, Hi * qt, 1), ((0, B * nt - T), (0, 0), (0, 0)))
    # the most tiles of a product that divide the block's
    sub = qt * max(d for d in range(1, nt + 1) if nt % d == 0 and d * qt <= max(product, qt))
    shape = dict(
        qt=qt, nt=nt, hi=Hi, ps=ps, cp=cp, pmax=pmax, n_tiles=T, n_chunks_max=C, sub=sub,
        **({} if lane0 is None else {"lane0": lane0}),
    )
    # chunks a step of a pass: whole steps make the table's chunks
    step = math.gcd(C, step_chunks) if nt == 1 else 1
    scratch_shapes = [
        pltpu.VMEM((2, step * cp, ps, lanes), pool.dtype),
        pltpu.SemaphoreType.DMA((2, step * cp)),
    ]
    params = {}
    if nt == 1:
        out_spec = pl.BlockSpec(
            (None, C, qt, S), lambda t, *_: (t, 0, 0, 0), memory_space=pltpu.VMEM
        )
    else:
        out_spec = pl.BlockSpec(memory_space=pl.ANY)
        scratch_shapes += [
            pltpu.VMEM((block // sub, Hi * sub, lanes), pool.dtype),  # score rows a product
            pltpu.VMEM((block // sub, Hi * sub, S), jnp.float32),  # their weights over a chunk
            pltpu.VMEM((2, nt, math.gcd(C, 8), qt, S), jnp.float32),  # scores on their way out
            pltpu.VMEM((nt, math.gcd(C, 8), qt, S), jnp.float32),  # -inf
            pltpu.SemaphoreType.DMA((3,)),  # the two staged groups', the fill's
        ]
        params["compiler_params"] = pltpu.CompilerParams(vmem_limit_bytes=max(
            32 << 20, 2 * _cell_vmem_bytes(block, Hi, lanes, pool.dtype.itemsize)
        ))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((None, block, 1), lambda t, *_: (t, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((block, Hi, lanes), lambda t, *_: (t, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((nt, Hi * qt, 1), lambda t, *_: (t, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=out_spec,
        scratch_shapes=scratch_shapes,
    )
    return pl.pallas_call(
        functools.partial(_scores_kernel, **shape),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, C, qt, S), jnp.float32),
        interpret=interpret,
        name="dsa_index_scores",
        **params,
    )(*plan, page_table, bounds.reshape(B, block, 1), q_i, weigh, pool)


def key_lanes(width: int) -> int:
    """An index key of ``width`` values padded to whole groups of 128 lanes
    (``ModelConfig.index_lanes``)."""
    return -(-width // 128) * 128


@functools.partial(jax.jit, static_argnames=("q_tile", "interpret", "lane0"))
def index_scores(
    q_i: jnp.ndarray,  # [N, Hi, Di] flat query stream (N % q_tile == 0)
    w_i: jnp.ndarray,  # [N, Hi] float32
    pool: jnp.ndarray,  # [P, ps, lanes >= Di]
    page_table: jnp.ndarray,  # [R, Pmax] int32
    row_of: jnp.ndarray,  # [N] int32
    bounds: jnp.ndarray,  # [N] int32 last context position seen, -1 = none
    q_tile: int = 8,
    interpret: bool = False,
    lane0: int | None = None,
) -> jnp.ndarray:
    """Index scores over a flat query stream whose tiles of ``q_tile``
    slots are one row's each (any slot its own at ``q_tile`` 1), as the
    attention kernel's caller lays it (Pallas TPU). A grid cell serves
    :func:`index_block` slots, read in one pass over their row's keys where
    they are one row's (``ragged_attention.block_plan``, derived here on the
    device). Returns :func:`tiled_shape` float32; a position past a query's
    bound, and every position of a padding slot, reads ``-inf``. ``lane0``
    (None: the pool's rows are index keys and nothing else): the index key's
    first lane in a row that holds more (a grouped-query model's K row,
    ``ModelConfig.kv_row_parts``): the kernel copies :func:`key_lanes` lanes
    from there and leaves the rest in HBM."""
    lanes = pool.shape[2] if lane0 is None else key_lanes(q_i.shape[2])
    block = index_block(q_tile, q_i.shape[1], lanes, pool.dtype.itemsize)
    return _index_scores(
        q_i, w_i, pool, page_table, row_of, bounds, q_tile, block, interpret,
        lane0=lane0,
    )


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
