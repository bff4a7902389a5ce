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
- :func:`ragged_paged_attention` — the Pallas TPU kernel. The caller
  aligns each row's query span to ``q_tile`` slots of the flat stream,
  so every tile belongs to exactly one row (at ``q_tile=1`` any slot is a
  row of one query). One online-softmax pass (:func:`_attend`: a
  double-buffered walk over a row's live pages, ``ceil(kv_len /
  page_size)``, flash-style, each query masked at its own bound) in
  **three forms, by what a grid cell is**:

  1. **A block of tiles** (:func:`_block_kernel`; the caller names
     ``q_block``, a whole number of tiles: :func:`query_block`, enough that
     a KV head's score product has about the MXU's 128 rows, or under a
     learned selection over a grouped-query pool
     :func:`selected_query_block`'s 64 slots). A mixed
     stream's aligned spans. Where every live tile of the block belongs to
     one row (:func:`block_plan`, derived on the device from ``row_of`` and
     the positions) the cell makes ONE pass over that row's context for all
     its queries; a block that holds several rows (a span's first and last)
     walks its tiles one pass each.
  2. **Rows walked one after another on ONE copy pipeline**
     (:func:`_rows_kernel`; PR 52). A row is one query (``q_tile=1``: the
     shape pure-decode windows dispatch, and the one a mixed dispatch reads
     its decode rows through: ``forward_ragged`` calls the kernel twice, at
     ``q_tile`` in blocks over the stream's aligned spans and at 1 over its
     packed tail, where rows of one token lie side by side) or, where the
     caller names no block and the tiles' chunk is worked for the KV heads
     together (below), ONE TILE of ``q_tile`` queries (PR 56: a block
     model's pass, a tile of ``block_length`` queries a row and the rows
     side by side; in its opening pass the block before and the block, two
     tiles that read the same pages, are two rows of the walk). A cell
     serves :func:`one_query_block`'s consecutive rows (from the call's
     heads, lanes and tile: a window's whole bucket at most models' widths,
     32 of SDAR's tiles of 4): while a row's last chunk is multiplied, the
     first chunk of the next row that has something to read
     (:func:`one_query_chain`, derived on the device from the positions) is
     on its way, and the cell's query and output blocks travel once for all
     its rows. A row's arithmetic is the pass's at one query or one tile,
     state zeroed at its start: to the bit what a cell of that row alone
     gives. Before PR 52 every row was a cell of its own and its first
     fetch, 16 page copies behind ~1 us of latency, had nothing to hide
     behind (PERF.md §6, PR 52); a block model's tiles were until PR 56.
  3. **One tile** (:func:`_tile_kernel`): a stream of one row, a call under
     a learned selection over a LATENT pool (``keep``, whose scores come a
     tile a cell there) or of one query a row, a
     block named as one tile (a model of 20 query heads a KV head, a latent
     pool's 128 heads; a stream of whole prompts that is no block model's,
     ``models/llama.py::forward_ragged``; the oracle ``q_block=q_tile``),
     and a call that names none whose tiles would go by head
     (:func:`one_query_block` answers 1).

  Inside a cell of form 2 a chunk is worked **for all the call's KV heads
  together, in phases** (:func:`_heads_together`; PR 54): the ``Hkv`` score
  products side by side, ONE mask / running maximum / exponential / row
  sum over the stacked score rows ``[H * q_tile, S]`` (kv head, group,
  query: the position mask, the sliding bound and the sink's start differ
  by query and not by head), the ``Hkv`` value products, ONE ``acc * alpha
  + pv``; the running ``m``, ``l`` and ``acc`` are the chunk loop's carried
  values and the pass touches the state's references not at all. The walk
  by head, every head's pair of products and softmax update through
  ``m_ref`` / ``l_ref`` / ``acc_ref`` in turn, left each head's products
  waiting for the one before: the call was bound by that chain, not by its
  fetches (PERF.md §6, PR 54). :func:`one_query_schedule` is **the rule
  that picks the schedule**, from the call's shapes alone: ``together``
  where a cell walks more than one row, the call has more than one KV head
  and its stacked score rows are at most the MXU's 128 (as far as the form
  was measured: PERF.md §6, PR 56); ``by_head`` for ONE KV head (a latent
  pool's absorbed form, 20 query heads on one: no chain to break), for more
  stacked rows than that (a row of one query; a row of one tile is then
  form 3's), and in forms 1 and 3. The results are the same to the bit.

**A latent pool** (multi-head latent attention, ``models/llama.py::
_mla_half``): both implementations take ``value_dim``, with which a
token's values are the first ``value_dim`` lanes of its key row, no value
pool is read (``v_cache`` None) and the output is ``[N, H, value_dim]``.
The pool then has one head (``num_kv_heads`` 1) whose row is ``[c_kv |
k_r]`` padded to whole lane groups, the queries are the absorbed ones of
the same width, and all ``H`` query heads share the one pass over the
row's context: 128 heads of a tile of 8 are 1024 score rows, eight times
what fills the MXU, so a grid cell there is one tile
(:func:`query_block`) and its products run in the pool's dtype with
float32 accumulation (exact on bfloat16 inputs, at the MXU's bfloat16
rate). That is the **absorbed** form: ``2 * (lanes + value_dim)``
operations a query-key pair and head, 3.6 x the published form's at 640 and
512 lanes against 192 + 128, and the right one for a row of few queries.

A row of many queries in a tiled stream (a prompt chunk) takes the
**expanded** form, :func:`ragged_paged_attention_expanded`, a call of its
own that no other model's trace reaches: a grid cell is a group of 16
heads and a *pass* of at most 1,024 of one row's query slots
(:func:`expanded_plan`, derived on the device from ``row_of`` and the
positions, so the split between the forms rides the stream as data); it
walks the row's live pages as ``_attend`` does and turns each chunk of
latents into the group's K and V in VMEM (one product with its columns of
``W_ukv``, rounded to the pool's dtype), scores ``q_nope . k_nope +
q_rope . k_r`` against them and sums ``v``: ``2 * (nope + rope + v)``
operations a pair and head plus ``2 * rank * (nope + v)`` a context token
and head ONCE A PASS. Nothing expanded is ever written to HBM, and what is
read there is the latent row: the call is compute-bound by construction.
The scores are two products where ``nope`` is whole lane groups
(openPangu's 128, the rotary one against the row's own ``[k_r | 0]``
lanes) and ONE over the published head ``[k_nope | k_r]`` where only
``nope + rope`` is (GLM-5's 192 + 64 = 256: :func:`_expanded_joint`).
:func:`expanded_min_queries` is where the two forms cost the same (160
queries at openPangu's published widths, 360 at GLM-5's): the rule both
the program and the engine's counter apply (:func:`expanded_rows`).

**Under a learned selection** (``keep``, ``ops/sparse_index.py``: a model
with an indexer) both forms apply the same mask, ``sparse_index.kept``:
position ``s`` where a query's index score is over its threshold ``tau``,
or equal to it and among the first ``allow`` such positions of the walk,
every head alike. The absorbed call multiplies ``2 * (lanes +
value_dim)`` a pair over ALL causal pairs for the kept few (2,304 at
GLM-5's widths where the published form needs 1,024 over the kept
third); the expanded call multiplies every causal pair of the (256
queries, 512 tokens) blocks it visits too (a mask drops no product), at
the published form's 1,024 plus the expansion's share. There the index
scores of a block come by DMA from the scores kernel's own layout while
the chunk is expanded, the count of ties before each position is a ``[256,
512] x [512, 512]`` product of 0 / 1 in the pool's dtype with float32
sums (exact), and the ties a query has passed in earlier chunks are a
column of scratch beside its running maximum and sum, because the kernel
walks chunks outside and sub-blocks inside.

Over a GROUPED-QUERY pool (``keye_vl2``: the selection masks a read of K
and V pages) the tiled call under ``keep`` is form 1 at a block of its own,
:func:`selected_query_block`'s 64 slots (PR 62; until then one tile a
cell, every tile re-reading its row's whole context: 5.2 GB of K and V a
layer and 4,096-slot dispatch at Keye's widths). The thresholds and numbers
of ties ride the block as ``[w, 1]`` columns, the index scores stay in HBM
in the scores kernel's layout and a pass copies its tiles' scores of a
chunk behind the chunk's pages (:class:`_CopiedScores`: the block's 8
tiles ONE strided copy, a pass of one tile that tile's); the ties a query
has passed are a ``[w, 1]`` carry, their count one ``[w, 128] x [128,
128]`` product a chunk. A query's softmax sees the chunks, the mask and the
order it sees in a cell of its tile alone: the same result to the bit. The
calls of one query a row (a window's step, a packed tail) stay a row a cell.

**Shapes by layer kind** (``mimo_v2_flash``): the value pool's heads may be
narrower than the keys' (the call takes the value pool's own row: keys of
192 lanes a head, values of 128, the result at the values' width), and
``sink`` gives every query head a learned logit that joins the softmax's
denominator and carries no value: a pass's running maximum and sum start
at ``(sink, 1)`` where they otherwise start at ``(-1e30, 0)``, which is the
sink as one more key seen first; from there the pass is the same, in all
three forms of the call.

HBM traffic per dispatch per layer is ``sum_rows(blocks_row * kv_row)``
tokens instead of the XLA gather's ``N * S``: the kernel reads a row's
context once per query block of its span (once per tile in a block it
shares with another row), never the page-bucket envelope. At one tile
of 8 a block that traffic bound the call: a 4096-slot stream of
long-document chunks read 18 GB a layer at 30 KV heads, 1.2 GB at a
block of 128 (PERF.md §6, PR 40).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
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


def latent_supported(page_size: int, lanes: int, value_dim: int, kv_dtype) -> bool:
    """:func:`ragged_supported` for a latent pool: one head of ``lanes``
    (the row ``[c_kv | k_r]``, padded to whole lane groups), whose values
    are its first ``value_dim`` lanes, which must end on a lane group's
    boundary too (the kernel slices them out of the row in VMEM)."""
    return ragged_supported(page_size, 1, lanes, kv_dtype) and value_dim % 128 == 0


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
    value_dim: int | None = None,  # a latent pool: values = the row's first lanes
    keep: jnp.ndarray | None = None,  # [N, S] bool: a learned selection
    sink: jnp.ndarray | None = None,  # [H] a learned logit a query head
) -> jnp.ndarray:
    """Pure-JAX ragged paged attention (the CPU/tier-1 parity path).

    Returns ``[N, H, D]`` in q's dtype; padding tokens (position -1)
    return exact zeros, like the kernel's. Matmuls run in the cache
    dtype with float32 accumulation, softmax in float32 — the same
    numeric contract as ``ops/attention.paged_attention``, so a
    one-token row here is bit-identical to the old decode gather.
    ``window``/``softcap`` carry the sliding and tanh-capped score
    variants (mistral/gemma2) exactly as ``paged_attention`` does. The
    Pallas kernel has the first as ``sliding`` and not the second, so a
    capped or pre-scaled family stays on this path
    (``ModelConfig.attn_kernel_form``; the engine's attn resolution
    enforces it).

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
        value_dim=value_dim,
        kv_heads=num_kv_heads,
        **({} if keep is None else {"keep": keep[:, None]}),
        **({} if sink is None else {"sink": sink}),
    )
    return out[:, 0]


# ------------------------------------------------------------------ kernel
# Rows of the MXU: what a head's score product should have to fill it.
_MXU_ROWS = 128
# Above every row index: the row of a tile that holds no query.
_NO_ROW = 2**30


def query_block(q_tile: int, q_per_kv: int) -> int:
    """Stream slots a grid cell of the tiled call serves in one pass over
    a row's context: enough tiles that a KV head's score product
    (``q_per_kv`` query heads of every slot) has about the MXU's rows,
    a whole number of ``q_tile``s and never fewer than one. From the
    call's shapes alone: the kernel's caller and the engine's counter
    (``dynamo_attn_query_slots_total``) both ask here. At 128 query heads
    a KV head (a latent pool's one head) a tile of 8 is already 1024 score
    rows, whose float32 states are what bounds a cell's VMEM
    (:func:`_block_vmem_bytes`): the block is the tile."""
    want = max(q_tile, _MXU_ROWS // q_per_kv)
    return -(-want // q_tile) * q_tile


# Stream slots a grid cell of the tiled call serves under a learned
# selection over a grouped-query pool: eight tiles of 8, so a block of one
# row reads its context, its index scores and its thresholds once for 64
# queries. Swept on the chip at 8 / 16 / 32 / 64 / 128 at Keye's widths
# (tools/attn_query_block.py; PERF.md §6, PR 62): 24.9 / 15.2 / 13.7 / 9.71 /
# 9.65 ms a 4,032-slot call; 64 and 128 cost the same (the pass is bound by
# its vector work a score element, not by its reads) and 64 leaves more
# slots in blocks of one row (96.7 % of the cell's against 94.0).
_SELECTED_SLOTS = 64
# What a cell of that many slots may hold in VMEM (of a v5e core's 128 MiB;
# Mosaic is given twice the estimate).
_SELECTED_VMEM_BYTES = 24 << 20


def selected_query_block(q_tile: int, heads: int, width: int, kv_heads: int) -> int:
    """:func:`query_block` for a call under a learned selection (``keep``)
    over a grouped-query pool, a rule of its own: ``_SELECTED_SLOTS`` in
    whole tiles where a cell of ``heads`` query heads of ``width`` lanes on
    ``kv_heads`` (two bytes a value) holds that in VMEM
    (:func:`_block_vmem_bytes`), else the largest halving of it that does.
    :func:`query_block`'s 128 score rows a KV head are 16 slots at 8 query
    heads a KV head, where the call is still half again as slow (PERF.md
    §6, PR 62); the unselected callers keep that rule. A stream of
    tiles of one slot has nothing to read together: the block is the tile.
    From the call's shapes alone: the program and the engine's counter
    (``dynamo_attn_query_slots_total``) both ask here
    (``models/llama.py::ragged_query_block``)."""
    if q_tile == 1:
        return 1
    block = max(1, _SELECTED_SLOTS // q_tile) * q_tile
    while block > q_tile and _block_vmem_bytes(
        block, heads, width, kv_heads * width, 1, _CHUNK_TOKENS, 2, 2
    ) > _SELECTED_VMEM_BYTES:
        block = max(1, block // q_tile // 2) * q_tile
    return block


def selected_pad(n: int, q_block: int) -> int:
    """Slots of no query a caller lays behind a stream part's learned
    selection of ``n`` slots for a call in blocks of ``q_block``: to whole
    blocks, because the last block's passes copy their tiles' index scores
    wherever the part ends."""
    return (-n) % q_block


def _to_whole(positions, slots: int, xp):
    """``positions`` padded with ``-1`` (no query) to whole cells of
    ``slots``."""
    pad = (-positions.shape[0]) % slots
    if not pad:
        return positions
    return xp.concatenate([positions, xp.full((pad,), -1, positions.dtype)])


def block_plan(row_of, positions, q_tile: int, q_block: int, xp=jnp):
    """What each tile and each query block of a flat stream reads:
    ``(tile_row, tile_kvlen, block_row, block_kvlen)``, the stream
    padded with ``-1`` to whole blocks.

    A tile is live where one of its positions is. A block whose live
    tiles all belong to one row (a tile of all ``-1`` belongs to any)
    is read in one pass of ``block_kvlen`` tokens of ``block_row``; a
    block with several rows has ``block_kvlen`` -1 and walks its tiles,
    each by its own ``(tile_row, tile_kvlen)``. ``xp``: ``jnp`` inside
    the program, ``numpy`` for the engine's count of the same rule."""
    nt = q_block // q_tile
    pad = (-positions.shape[0]) % q_block
    positions = _to_whole(positions, q_block, xp)
    if pad:
        row_of = xp.concatenate([row_of, xp.zeros((pad,), row_of.dtype)])
    # Alignment makes every tile single-row; padding positions are -1,
    # so the max is the tile's true causal horizon.
    tile_row = row_of.reshape(-1, q_tile)[:, 0]
    tile_kvlen = positions.reshape(-1, q_tile).max(axis=1) + 1
    rows, lens = tile_row.reshape(-1, nt), tile_kvlen.reshape(-1, nt)
    live = lens > 0
    last = xp.where(live, rows, -1).max(axis=1)
    first = xp.where(live, rows, _NO_ROW).min(axis=1)
    block_kvlen = xp.where(first >= last, lens.max(axis=1), -1)
    return tile_row, tile_kvlen, xp.maximum(last, 0), block_kvlen


def merged_slot_counts(row_of, positions, q_tile: int, q_block: int):
    """Live slots of a stream's tiled part (host arrays) that lie in a
    block read in one pass, and those whose block walks its tiles:
    :func:`block_plan`'s rule, as the kernel applies it. Where a block
    is one tile nothing merges."""
    live = np.asarray(positions) >= 0
    if q_block == q_tile:
        return 0, int(live.sum())
    *_, block_kvlen = block_plan(
        np.asarray(row_of), np.asarray(positions), q_tile, q_block, xp=np
    )
    pad = (-live.shape[0]) % q_block
    by_block = np.pad(live, (0, pad)).reshape(-1, q_block).sum(axis=1)
    merged = int(by_block[block_kvlen >= 0].sum())
    return merged, int(by_block.sum()) - merged


def sliding_starts(positions, q_tile: int, q_block: int, sliding: int, xp=jnp):
    """Where a sliding layer's walks start: ``(tile_first, block_first)``,
    the smallest position any live query of each tile, and of each query
    block, sees (its own minus ``sliding - 1``, never below 0; 0 for a
    tile or block of no query, which walks nothing). ``positions`` as
    :func:`block_plan` takes them, padded alike. A merged block's one
    pass then covers the union of its queries' windows: for 1,024
    consecutive queries of one row ``1,023 + sliding`` positions, not the
    context. ``xp``: ``jnp`` inside the program, ``numpy`` for the
    engine's count of the same rule."""
    positions = _to_whole(positions, q_block, xp)
    lowest = xp.where(positions >= 0, positions, _NO_ROW).reshape(-1, q_tile)
    tile_low = lowest.min(axis=1)
    block_low = tile_low.reshape(-1, q_block // q_tile).min(axis=1)

    def first(low):
        return xp.where(
            low < _NO_ROW, xp.maximum(low - (sliding - 1), 0), 0
        ).astype(positions.dtype)

    return first(tile_low), first(block_low)


# Elements of its query block and of its output block together that a cell
# of the one-query call may hold (the pipeline keeps two of each: 4 MiB in
# bfloat16): 128 rows of 32 heads of 128 lanes, 8 of a latent pool's 128
# absorbed heads.
_ONE_QUERY_IO_LANES = 1 << 20
# Its floor: a sublane tile's rows, and the smallest window bucket that has
# rows to put behind one another.
_ONE_QUERY_ROWS = 8


def one_query_block(
    n: int, heads: int, width: int, value_width: int, q_tile: int = 1,
    kv_heads: int = 1,
) -> int:
    """Rows a grid cell of the one-query call serves on one copy pipeline,
    from the call's shapes alone: as many as keep the cell's query and
    output blocks (``heads`` padded to a sublane tile of 16, of ``width``
    and ``value_width`` lanes) inside ``_ONE_QUERY_IO_LANES``, a power of
    two and never under ``_ONE_QUERY_ROWS``; the whole stream where it has
    no more rows than that. A row of one TILE of ``q_tile`` queries (a block
    model's pass: ``n`` tiles) is walked as a row of one query is, and its
    cell's blocks are ``q_tile`` slots a row: the same slots hold that many
    fewer tiles. Tiles are walked so only where their chunk is worked for
    the call's ``kv_heads`` together (:func:`one_query_schedule`: the one
    form of the walk that was timed, PERF.md §6, PR 56); where it is not
    (one KV head, more than 128 stacked score rows, not even two tiles'
    room: a latent pool's 128 heads of a tile of 8) the answer is 1, a cell
    a tile (:func:`_tile_kernel`, as before PR 56). At one query a row the
    KV heads decide nothing here. The kernel's caller and the engine's
    counters (``dynamo_attn_one_query_rows_total``,
    ``dynamo_attn_tile_rows_total``) both ask here."""
    a_slot = -(-heads // 16) * 16 * (width + value_width)
    fit = max(_ONE_QUERY_ROWS, _ONE_QUERY_IO_LANES // a_slot)
    rows = min(n, max(1, (1 << (fit.bit_length() - 1)) // q_tile))
    if q_tile > 1 and one_query_schedule(rows, kv_heads, heads * q_tile) == "by_head":
        return 1
    return rows


def one_query_chain(positions, rows: int, xp=jnp, q_tile: int = 1):
    """The order a cell of the one-query call fetches in: ``(lead, after)``,
    the stream padded with ``-1`` to whole cells of ``rows`` slots. ``lead``
    [cells]: the first slot of each cell that has something to read (counted
    within the cell; ``rows`` where none has), whose first chunk the cell
    starts with nothing before it. ``after`` [cells * rows]: for each slot
    the next such slot behind it in its cell (``rows``: none), whose first
    chunk is started behind this slot's last. Slots of no query are stepped
    over: they cost no copy. At ``q_tile`` > 1 a row is a tile of that many
    slots (``rows`` tiles a cell), live where one of its queries is. ``xp``:
    ``jnp`` inside the program, ``numpy`` for the engine's count of the same
    rule."""
    if q_tile > 1:  # a tile's bound: -1 where it holds no query
        positions = positions.reshape(-1, q_tile).max(axis=1)
    at = xp.arange(rows, dtype=positions.dtype)
    live = xp.where(_to_whole(positions, rows, xp).reshape(-1, rows) >= 0, at, rows)
    # [cell, i, k]: slot k where it is live and behind i, else ``rows``
    behind = xp.where(at[None, :, None] < at[None, None, :], live[:, None, :], rows)
    return live.min(axis=1), behind.min(axis=2).reshape(-1)


def one_query_fetches(positions, rows: int) -> tuple[int, int]:
    """Of the live slots of a one-query call's stream (host array; a
    window gives ``[steps, slots]``, a step a call), those whose first
    chunk is fetched behind another row of their cell and those whose
    fetch nothing hides, the first live one of each cell of ``rows``:
    :func:`one_query_chain`'s rule, as the kernel walks it. At one row a
    cell every fetch is exposed. A block program's stream comes as its
    tiles' bounds, a tile a row (-1: no query in it)."""
    live = np.asarray(positions) >= 0
    live = live.reshape(-1, live.shape[-1])
    live = np.pad(live, ((0, 0), (0, (-live.shape[1]) % rows)))
    exposed = int(live.reshape(-1, rows).any(axis=1).sum())
    return int(live.sum()) - exposed, exposed


def one_query_schedule(rows: int, kv_heads: int, stacked: int) -> str:
    """How a pass of the one-query call works a chunk, from the call's
    shapes alone: ``"together"`` (all its KV heads' score products, ONE
    softmax update over their stacked rows, all their value products, the
    running state carried as values: :func:`_heads_together`) where a cell
    walks ``rows`` > 1 slots (:func:`_rows_kernel`) of more than one KV
    head, and the pass's ``stacked`` score rows (its query heads, times the
    queries of a row that is a tile) are no more than the MXU's 128: as far
    as it was measured (SDAR's 32 heads of a tile of 4, where the state as
    values is 16 vector registers an array and spills, and a chunk still
    costs 0.68 us against 0.86 in two halves of 64 rows and 1.30 by head:
    PERF.md §6, PR 56); ``"by_head"`` (head after head through the state's
    references) for one KV head, which has no chain to break and keeps its
    text, for more rows than that and for a cell of one row (the tile
    kernel: a stream of one slot, a call of one query a row under a learned
    selection, the oracle ``q_block=q_tile``, and a tile whose chunk would
    not be worked together: :func:`one_query_block` answers 1 there). The
    query heads a
    KV head decide nothing: at 16, 8, 4 and 1 of them the call alone
    measured 40–48 % faster a chunk together (PERF.md §6, PR 54). The
    kernel's caller and the engine's counters
    (``dynamo_attn_one_query_heads_total``, ``dynamo_attn_tile_rows_total``)
    both ask here."""
    few = rows > 1 and kv_heads > 1 and stacked <= _MXU_ROWS
    return "together" if few else "by_head"


def _chunk_copies(row, base, slot, table_ref, k_hbm, v_hbm, k_buf, v_buf, sems,
                 *, cp: int, pmax: int):
    """The ``cp`` page copies (``2 * cp`` with a value pool) of one chunk,
    entries ``base`` on of ``row``'s table, into buffer ``slot``.

    Page indices beyond the row's table are clamped to a valid
    entry: the DMA still runs (keeping semaphore accounting static)
    and the tokens are masked out of the softmax below. (Hkv, D)
    are pre-collapsed into one lane dimension so every copy slices
    only leading (untiled) dims — Mosaic rejects slices of a lane
    dim narrower than the 128-lane tile."""
    dmas = []
    # A K pool whose row holds more than the heads' keys (a model with an
    # indexer under grouped-query attention: its index key rides behind
    # them): the copy takes the keys' lanes, whole groups of 128, and leaves
    # the rest in HBM.
    lanes = k_buf.shape[-1]
    for j in range(cp):
        idx = jnp.minimum(base + j, pmax - 1)
        pid = table_ref[row, idx]
        page = k_hbm.at[pid]
        if k_hbm.shape[-1] != lanes:
            page = k_hbm.at[pid, :, pl.ds(0, lanes)]
        dmas.append(
            pltpu.make_async_copy(page, k_buf.at[slot, j], sems.at[slot, 2 * j])
        )
        if v_hbm is not None:
            dmas.append(
                pltpu.make_async_copy(
                    v_hbm.at[pid], v_buf.at[slot, j], sems.at[slot, 2 * j + 1]
                )
            )
    return dmas


def _side_by_side(product, hkv: int, qpk: int):
    """``hkv`` products laid side by side as score rows ``[hkv * qpk, ...]``
    (kv head, group), none waiting for another. ``product(h, lo, hi)`` is KV
    head ``h``'s product for the rows ``lo .. hi`` of that stack: the whole
    sublane tiles of 8 its ``qpk`` rows lie in, so every piece is whole
    vector registers and the stack a concatenation of them. Where ``qpk`` is
    no whole number of tiles a tile holds several heads' rows: each head's
    product covers the tile, a row's result depends on no other row, and a
    select by the row's index takes each head's own (the MXU streams a
    tile of 8 rows whatever the product has)."""
    H = hkv * qpk
    tiles: list = [None] * -(-H // 8)
    for h in range(hkv):
        lo, hi = h * qpk // 8 * 8, min(H, -(-(h + 1) * qpk // 8) * 8)
        out = product(h, lo, hi)
        for t in range(lo // 8, -(-hi // 8)):
            piece = out[t * 8 - lo : min(t * 8 + 8, hi) - lo]
            if tiles[t] is not None:  # rows from h's first on are h's, or a later head's
                row = t * 8 + jax.lax.broadcasted_iota(jnp.int32, piece.shape, 0)
                piece = jnp.where(row >= h * qpk, piece, tiles[t])
            tiles[t] = piece
    return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=0)


def _heads_together(q, k_of, v_of, live, state, *, hkv, qpk, scale):
    """One chunk of a pass of one row for ALL its KV heads, in phases
    (:func:`one_query_schedule`): the ``hkv`` score products, ONE mask /
    running maximum / exponential / row sum over the stacked score rows
    ``[H, S]``, the ``hkv`` value products, ONE ``acc * alpha + pv``.
    ``q`` [H, D] float32 rows (kv head, group; ``qpk`` of them a KV head:
    a row of a tile's queries has (group, query) there), ``k_of(h)`` [S, D]
    and ``v_of(h)`` [S, Dv] a head's keys and values of the chunk as
    fetched, ``live`` the positions a score row sees: [1, S] for one query
    (the same for every head) or a mask a score row [H, S] for a tile's
    (it differs by query and not by head), ``state`` ``(m, l, acc)`` as
    values ([H, 1], [H, 1], [H, Dv] float32).
    Every score row sees the products, operands and order of chunks of
    ``_attend``'s walk by head: the result is that walk's to the bit.
    Returns the new state."""
    m_prev, l_prev, acc = state

    def scores(h, lo, hi):
        return jax.lax.dot_general(
            q[lo:hi], k_of(h).astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    s = _side_by_side(scores, hkv, qpk) * scale  # [H, S]
    s = jnp.where(live, s, -1e30)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # a query of no key yet: ``_attend``'s note on the finite mask
    p = jnp.where(live, jnp.exp(s - m_new), 0.0)
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    rounded = {}  # a tile of p in the values' dtype, once for its heads

    def values(h, lo, hi):
        vh = v_of(h)
        if (lo, hi) not in rounded:
            rounded[lo, hi] = p[lo:hi].astype(vh.dtype)
        return jax.lax.dot_general(
            rounded[lo, hi], vh, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    return m_new, l_new, acc * alpha + _side_by_side(values, hkv, qpk)


class _CopiedScores:
    """A pass's index scores, fetched a chunk at a time from the scores
    kernel's own layout in HBM (``sparse_index.tiled_shape``: chunk-major
    inside a tile, so a chunk of ``tiles`` tiles is that many pieces of
    ``[q_tile, S]``, ONE strided copy) into a double buffer beside the
    chunk's pages: ``_attend``'s ``keep[0]`` where a cell is a block of
    tiles. A block's whole scores in VMEM would be 8 MB at a table of
    16,384 tokens, twice that pipelined, and would grow with the table;
    the copy reads the live chunks alone and holds two of them."""

    def __init__(self, hbm, first, tiles: int, buf, sems):
        # hbm [T, C, qt, S]; buf [2, >= tiles, qt, S]; sems [2]
        self.hbm, self.first, self.tiles, self.buf, self.sems = hbm, first, tiles, buf, sems

    def copy(self, c, slot):
        return pltpu.make_async_copy(
            self.hbm.at[pl.ds(self.first, self.tiles), c],
            self.buf.at[slot, pl.ds(0, self.tiles)], self.sems.at[slot],
        )

    def read(self, slot):
        """The chunk in buffer ``slot`` a query a row: [tiles * qt, S]."""
        _, _, qt, S = self.buf.shape
        return self.buf[slot, : self.tiles].reshape(self.tiles * qt, S)


def _attend(
    q_ref,  # [W, H, D] VMEM — the pass's queries
    o_ref,  # [W, H, Dv] VMEM
    pos_rows,  # () -> [qpk*W, 1] int32: last kv position a score row sees
    #            ([H*W, 1], every head's, where ``together`` and W > 1)
    row,  # scalar: the batch row whose pages the pass walks
    kvlen,  # scalar: kv tokens it walks (0 = none)
    table_ref,  # [R, Pmax] int32 SMEM — page ids per row
    k_hbm,  # [P, ps, Hkv*D] — page pool, stays in HBM
    v_hbm,  # None with ``vd``: the values are lanes of the key row
    k_buf,  # [2, cp, ps, Hkv*D] VMEM double buffer
    v_buf,  # None with ``vd``
    acc_ref,  # [>= H*W, Dv] f32 — output accumulator, rows = (kv head, g, i)
    m_ref,  # [>= H*W, 128] f32 — running max (lane-replicated)
    l_ref,  # [>= H*W, 128] f32 — running sum (lane-replicated)
    sems,  # DMA semaphores [2, 2*cp]
    *,
    w: int,
    ps: int,
    cp: int,
    hkv: int,
    hd: int,
    qpk: int,
    pmax: int,
    scale: float,
    vd: int | None = None,
    keep=None,
    sliding: int | None = None,
    first=None,
    chain=None,
    vw: int | None = None,
    sink=None,
    together: bool = False,
):
    """One double-buffered pass over ``row``'s first ``kvlen`` tokens
    for ``w`` queries: flash-style online softmax, ``qpk * w`` score
    rows a KV head. Every DMA it starts it waits for, so passes follow
    one another in a grid cell on the same buffers and semaphores.
    ``chain`` (a cell of the one-query call, :func:`_rows_kernel`) makes
    the passes of a cell ONE copy pipeline: ``(slot0, after_row,
    after_page, after_live)``, scalars. The pass's first chunk is then
    already on its way into buffer ``slot0`` (the pass before it, or the
    cell, started it), and behind its LAST chunk it starts the first chunk
    of the pass that follows, page ``after_page`` on of ``after_row``'s
    table, where ``after_live`` says there is one: still every copy
    started is waited for, by the pass it was started for. The pass must
    have something to walk.
    ``together`` (static; with ``chain``, a row of one query or of one
    tile: :func:`one_query_schedule`) works each chunk for all the KV heads at
    once (:func:`_heads_together`) with the running maximum, sum and accumulator
    carried as the chunk loop's values, where the pass otherwise walks the
    heads one after another through ``m_ref`` / ``l_ref`` / ``acc_ref``,
    which such a pass neither reads nor writes; the copies, the order of
    chunks, every product's operands and the end's divide and transpose
    are the same.
    ``sliding`` (a sliding layer's width, static) with ``first`` (a
    scalar: the smallest position any of the pass's queries sees): the
    walk starts at ``first``'s page and not at the row's, a chunk is
    ``cp`` pages from there, and a query masks the positions at or
    before its own minus ``sliding``; what lies before ``first``'s page
    is neither copied nor multiplied.
    ``vw`` (static; a value pool whose heads are narrower than the keys'):
    a head's values are ``vw`` lanes of the value row, not ``hd``.
    ``sink`` ([H, 128] float32 in VMEM, lane-replicated: a learned logit a
    query head): the logit joins every query's softmax as one more key
    that carries no value, so the running state starts at ``(m, l) = (sink,
    1)`` and not at ``(-1e30, 0)``: from there the pass is the same, and a
    query of no key still ends as zeros.
    ``vd`` (a latent pool): a head's values are the first ``vd`` lanes of
    its key row, and the two products take their operands in the pool's
    dtype (module docstring). ``keep`` (a learned selection,
    ``ops/sparse_index.py``): ``(idx_ref [chunks, w, S], tau_ref [w, 1],
    allow_ref [w, 1])``, float32 in VMEM; query ``i`` sees position ``s`` of
    chunk ``c`` only where ``idx_ref[c, i, s] > tau_ref[i]``, or equal to
    it and among the first ``allow_ref[i]`` such positions of the walk
    (``sparse_index.kept``), every head alike. In a block of tiles the
    first is a :class:`_CopiedScores`: the scores stay in HBM and a chunk of
    them comes with the chunk's pages."""
    dvh = hd if vw is None else vw  # lanes of a head in the value pool
    dv = dvh if vd is None else vd
    if sliding is None:
        n_chunks = pl.cdiv(kvlen, ps * cp)
    else:
        page0 = first // ps  # the walk's first page
        n_chunks = pl.cdiv(kvlen - page0 * ps, ps * cp)
    # The score rows of this pass: all the state's where it is the cell's
    # only one, the first ``H * w`` in a pass of one tile of a block.
    n = hkv * qpk * w
    whole = n == acc_ref.shape[0]
    head = slice(None) if whole else slice(0, n)
    used = ... if whole else (head, slice(None))

    pools = (table_ref, k_hbm, v_hbm, k_buf, v_buf, sems)

    # A selection whose scores come by copy (a block's: ``_CopiedScores``)
    # rides the chunk's page copies: started and waited for with them.
    copied = keep[0] if keep is not None and isinstance(keep[0], _CopiedScores) else None

    def chunk_dmas(c, slot):
        """The page copies of this pass's chunk ``c`` into buffer ``slot``."""
        base = c * cp if sliding is None else c * cp + page0
        dmas = _chunk_copies(row, base, slot, *pools, cp=cp, pmax=pmax)
        return dmas if copied is None else [*dmas, copied.copy(c, slot)]

    def sink_rows():
        # score rows lie (head, query): a head's logit for each of its ``w``
        return jnp.broadcast_to(
            sink[...][:, None, :], (hkv * qpk, w, 128)
        ).reshape(n, 128)

    if together:
        # The state as values, carried through the chunk loop; at one query
        # a pass a head's sink is its one score row's.
        if sink is None:
            m0 = jnp.full((n, 1), -1e30, jnp.float32)
        else:
            m0 = sink[:, :1] if w == 1 else sink_rows()[:, :1]
        state = (
            m0,
            jnp.full((n, 1), 0.0 if sink is None else 1.0, jnp.float32),
            jnp.zeros((n, dv), jnp.float32),
        )
    else:
        acc_ref[used] = jnp.zeros((n, dv), jnp.float32)
        if sink is None:
            m_ref[used] = jnp.full((n, 128), -1e30, jnp.float32)
            l_ref[used] = jnp.zeros((n, 128), jnp.float32)
        else:
            m_ref[used] = sink_rows()
            l_ref[used] = jnp.ones((n, 128), jnp.float32)

    if chain is None:
        @pl.when(n_chunks > 0)
        def _():
            for dma in chunk_dmas(0, 0):
                dma.start()
    else:
        slot0, after_row, after_page, after_live = chain

    pos_rows = pos_rows()
    if keep is not None:
        # [s', s] = 1 where s' <= s: a product with it counts a chunk's ties
        # up to each position (0 / 1 and small counts: exact in any pass)
        upto = (
            jax.lax.broadcasted_iota(jnp.int32, (cp * ps, cp * ps), 0)
            <= jax.lax.broadcasted_iota(jnp.int32, (cp * ps, cp * ps), 1)
        ).astype(jnp.float32)
    # [W, H, D] -> [H', W, D] with H' rows ordered (kv head, group):
    # per-kv-head slices are then contiguous row blocks.
    q = jnp.swapaxes(q_ref[...].astype(jnp.float32), 0, 1)  # [H, W, D]
    if vd is not None:
        # Back to the pool's dtype once the rows lie (head, query): exact,
        # the queries came in it.
        q = q.reshape(hkv * qpk * w, hd).astype(k_buf.dtype)
    S = cp * ps

    def body(c, carry):
        if chain is None:
            slot = jax.lax.rem(c, 2)
            next_slot = jax.lax.rem(c + 1, 2)

            @pl.when(c + 1 < n_chunks)
            def _():
                for dma in chunk_dmas(c + 1, next_slot):
                    dma.start()
        else:
            # One set of copies for what comes next, whichever it is: this
            # row's chunk c + 1, or the first chunk of the row that follows.
            slot = jax.lax.rem(c + slot0, 2)
            next_slot = 1 - slot
            more = c + 1 < n_chunks
            own = (c + 1) * cp if sliding is None else (c + 1) * cp + page0

            @pl.when(more | after_live)
            def _():
                for dma in _chunk_copies(
                    jnp.where(more, row, after_row),
                    jnp.where(more, own, after_page),
                    next_slot, *pools, cp=cp, pmax=pmax,
                ):
                    dma.start()

        for dma in chunk_dmas(c, slot):
            dma.wait()

        tok_idx = c * S + jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
        if sliding is not None:
            tok_idx = tok_idx + page0 * ps
        if together:
            # one query, every head's: the mask is [1, S]; a tile's queries
            # each their own: a mask a stacked score row (``pos_rows`` [n, 1])
            pos = pos_rows[:1] if w == 1 else pos_rows
            live = tok_idx <= pos
            if sliding is not None:
                live = live & (tok_idx > pos - sliding)

            def lanes_of(buf, width):
                # a head's lanes of the chunk, read where they lie
                return lambda h: buf[slot, :, :, h * width : (h + 1) * width].reshape(S, width)

            return _heads_together(
                q.reshape(n, hd), lanes_of(k_buf, hd), lanes_of(v_buf, dvh),
                live, carry, hkv=hkv, qpk=qpk * w, scale=scale,
            )
        k = k_buf[slot].reshape(S, hkv * hd)  # [S, Hkv*D]
        v = v_buf[slot].reshape(S, hkv * dvh) if vd is None else None
        ties_seen = carry  # by head: the state is the references'
        if keep is not None:
            # [w, S] -> a head's score rows (group, query): the same for
            # every head. Through float32: whole (8, 128) tiles stack.
            idx = keep[0][c] if copied is None else copied.read(slot)
            tau = keep[1][...]
            tie = jnp.where(idx == tau, 1.0, 0.0)
            among = ties_seen + jax.lax.dot_general(
                tie, upto, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            mark = jnp.where(
                (idx > tau) | ((tie > 0.5) & (among <= keep[2][...])), 1.0, 0.0
            )
            ties_seen = ties_seen + jnp.sum(tie, axis=1, keepdims=True)
            kept = jnp.broadcast_to(mark[None], (qpk, w, S)).reshape(qpk * w, S) > 0.5
        for h in range(hkv):
            rows = slice(h * qpk * w, (h + 1) * qpk * w)
            cols = slice(h * hd, (h + 1) * hd)
            if vd is None:
                qh = q[h * qpk : (h + 1) * qpk].reshape(qpk * w, hd)
                kh = k[:, cols].astype(jnp.float32)  # [S, D]
                vh = v[:, h * dvh : (h + 1) * dvh]
            else:
                qh, kh = q[rows], k[:, cols]
                vh = k[:, h * hd : h * hd + vd]
            s = (
                jax.lax.dot_general(
                    qh,
                    kh,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                * scale
            )  # [qpk*W, S]
            live = tok_idx <= pos_rows
            if sliding is not None:
                live = live & (tok_idx > pos_rows - sliding)
            if keep is not None:
                live = live & kept
            s = jnp.where(live, s, -1e30)
            m_prev = m_ref[rows, :1]  # [qpk*W, 1]
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            # The mask is finite, so a query with nothing to attend to
            # (padding inside a live tile) has m_new == -1e30 and every
            # exp(s - m_new) == 1: masking s alone would give it the
            # mean of whatever V slots the pass's chunks hold (the row's
            # context, its last page's slots past the length, clamped
            # table entries). A live query's masked p is already 0: a
            # chunk wholly past its own bound, which a pass over a block
            # of tiles walks for the later ones' sake, leaves its state
            # as it was (``alpha`` 1, ``p`` 0).
            p = jnp.where(live, jnp.exp(s - m_new), 0.0)
            l_ref[rows, :] = l_ref[rows, :] * alpha + jnp.sum(
                p, axis=1, keepdims=True
            )
            pv = jax.lax.dot_general(
                p.astype(vh.dtype),
                vh,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [qpk*W, Dv]
            acc_ref[rows, :] = acc_ref[rows, :] * alpha + pv
            m_ref[rows, :] = jnp.broadcast_to(m_new, m_ref[rows, :].shape)
        return 0 if keep is None else ties_seen

    if together:
        _, l, acc = jax.lax.fori_loop(0, n_chunks, body, state)
    else:
        jax.lax.fori_loop(
            0, n_chunks, body,
            0 if keep is None else jnp.zeros((w, 1), jnp.float32),
        )
        l = l_ref[head, :1]
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = (acc if together else acc_ref[used]) / l_safe  # [H*W, Dv], rows (kv head, g, i)
    out = out.reshape(hkv, qpk, w, dv).transpose(2, 0, 1, 3)
    o_ref[...] = out.reshape(w, hkv * qpk, dv).astype(o_ref.dtype)


def _pools_out_scratch(refs, vd):
    """A kernel's references behind its queries, as ``_attend`` names them:
    (k_hbm, v_hbm, o_ref, (k_buf, v_buf, acc, m, l, sems)). With ``vd`` the
    call has neither a value pool nor its buffer."""
    if vd is None:
        k_hbm, v_hbm, o_ref, k_buf, v_buf, *state = refs
        return k_hbm, v_hbm, o_ref, (k_buf, v_buf, *state)
    k_hbm, o_ref, k_buf, *state = refs
    return k_hbm, None, o_ref, (k_buf, None, *state)


def _tile_bounds(positions_ref, t, qt: int, rows: int):
    """The bounds of the ``qt`` queries of tile ``t``, read from SMEM, as a
    column of ``rows`` score rows ([rows, 1] int32) in which the query
    index varies fastest: row ``r`` sees what query ``r % qt`` does. Built
    as a column from the start: a flat vector tiled from ``[qt]`` outgrows
    one lane tile at 20 query heads a group (Mosaic: "input offsets outside
    of the first tile")."""
    query = jax.lax.rem(jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0), qt)
    column = jnp.zeros((rows, 1), jnp.int32)
    for i in range(qt):
        column = jnp.where(query == i, positions_ref[t * qt + i], column)
    return column


def _tile_kernel(
    # scalar prefetch (SMEM)
    tile_row_ref,  # [T] int32 — owning batch row per query tile
    tile_kvlen_ref,  # [T] int32 — kv tokens the tile attends over (0=skip)
    positions_ref,  # [N] int32 — last kv position each flat query sees
    table_ref,
    # inputs, output, scratch: _attend's
    q_ref,  # [QT, H, D] VMEM — this tile's queries
    *refs,  # _pools_out_scratch's
    qt: int,
    qpk: int,
    kept: bool = False,
    sinks: bool = False,
    **shape,
):
    """A grid cell is one tile of ``qt`` slots, all of one row. With
    ``sinks`` the heads' sink logits come first among ``refs``
    (``_attend``'s ``sink``). With
    ``kept`` the tile's index scores and thresholds come first among
    ``refs`` (``_attend``'s ``keep``). With ``sliding`` (in ``shape``) a
    fifth prefetched scalar array comes behind the table: each tile's
    first visible position (``sliding_starts``)."""
    more = {}  # what only some calls hand the pass: its start, the sinks
    if shape.get("sliding") is not None:
        tile_first_ref, q_ref, *refs = q_ref, *refs
        more["first"] = tile_first_ref[pl.program_id(0)]
    if sinks:
        more["sink"], *refs = refs
    keep = None
    if kept:
        *keep, refs = refs[0], refs[1], refs[2], refs[3:]
    k_hbm, v_hbm, o_ref, scratch = _pools_out_scratch(refs, shape.get("vd"))
    t = pl.program_id(0)
    # Per-query absolute positions (the causal bound), read from SMEM.
    # Padding queries carry -1: nothing satisfies kv_pos <= -1, so with
    # ``p`` masked below their softmax sum stays 0 and the final divide
    # maps them to zeros, whatever the pages the tile brought in hold.
    # Score rows are laid out (kv head, group, query): each head's
    # block is contiguous, and within it the query index varies
    # fastest — so row r's causal bound is query r % QT's position.
    _attend(
        q_ref, o_ref, lambda: _tile_bounds(positions_ref, t, qt, qpk * qt),
        tile_row_ref[t], tile_kvlen_ref[t],
        table_ref, k_hbm, v_hbm, *scratch, w=qt, qpk=qpk, keep=keep,
        **more, **shape,
    )


def _block_kernel(
    # scalar prefetch (SMEM): block_plan's four, and the page table
    tile_row_ref,  # [B*nt] int32
    tile_kvlen_ref,  # [B*nt] int32
    block_row_ref,  # [B] int32
    block_kvlen_ref,  # [B] int32 — kv tokens of the one pass; -1 = by tile
    table_ref,
    # inputs, output, scratch: _attend's, behind the block's positions
    pos_ref,  # [QB, 1] int32 VMEM
    q_ref,  # [QB, H, D] VMEM — this block's queries
    *refs,  # _pools_out_scratch's
    qt: int,
    nt: int,
    qpk: int,
    sinks: bool = False,
    kept: bool = False,
    **shape,
):
    """A grid cell is a block of ``nt`` tiles. Where they belong to one
    row it reads that row's context once for all ``nt * qt`` queries;
    where to several, tile after tile as :func:`_tile_kernel` does. With
    ``sliding`` (in ``shape``) two more prefetched scalar arrays come
    behind the table (``sliding_starts``: each tile's and each block's
    first visible position), and the one pass covers the union of its
    queries' windows. With ``kept`` (a learned selection over a
    grouped-query pool) the block's thresholds and numbers of ties ([QB, 1]
    float32 in VMEM) and the stream's index scores (in HBM, the scores
    kernel's layout) come first among ``refs`` and a double buffer for a
    chunk of them and its semaphores last: a pass copies its tiles' scores
    of a chunk behind the chunk's pages (:class:`_CopiedScores`), the one
    pass all the block's, a pass of one tile that tile's, each with its own
    ``tau`` and ``allow``."""
    tile_first = block_first = None
    if shape.get("sliding") is not None:
        tile_first, block_first, pos_ref, q_ref, *refs = pos_ref, q_ref, *refs
    if sinks:  # the heads' sink logits, first behind the queries
        shape["sink"], *refs = refs
    if kept:
        tau_ref, allow_ref, scores_hbm, *refs, idx_buf, idx_sems = refs
    k_hbm, v_hbm, o_ref, scratch = _pools_out_scratch(refs, shape.get("vd"))
    b = pl.program_id(0)
    merged_len = block_kvlen_ref[b]

    def start(ref, at):
        return {} if ref is None else {"first": ref[at]}

    def keep(tile, tiles, at):
        """``_attend``'s ``keep`` for the ``tiles`` tiles from the block's
        ``tile`` on, the block's slots ``at``."""
        if not kept:
            return {}
        scores = _CopiedScores(scores_hbm, b * nt + tile, tiles, idx_buf, idx_sems)
        return {"keep": (scores, tau_ref.at[at], allow_ref.at[at])}

    def score_rows(at):
        """() -> the positions ``pos_ref[at]`` [W, 1] as score rows
        [qpk*W, 1]: the query index varies fastest inside a head."""
        def read():
            pos = pos_ref[at, :]
            return jnp.concatenate([pos] * qpk, axis=0) if qpk > 1 else pos

        return read

    @pl.when(merged_len >= 0)
    def _():
        _attend(
            q_ref, o_ref, score_rows(slice(None)), block_row_ref[b],
            merged_len, table_ref, k_hbm, v_hbm, *scratch, w=nt * qt,
            qpk=qpk, **start(block_first, b), **keep(0, nt, slice(None)),
            **shape,
        )

    @pl.when(merged_len < 0)
    def _():
        def tile(j, _):
            t = b * nt + j
            at = pl.ds(pl.multiple_of(j * qt, qt), qt)
            _attend(
                q_ref.at[at], o_ref.at[at], score_rows(at),
                tile_row_ref[t], tile_kvlen_ref[t], table_ref, k_hbm,
                v_hbm, *scratch, w=qt, qpk=qpk, **start(tile_first, t),
                **keep(j, 1, at), **shape,
            )
            return 0

        jax.lax.fori_loop(0, nt, tile, 0)


def _rows_kernel(
    # scalar prefetch (SMEM): the stream's rows and bounds, one_query_chain's
    # two, and the page table
    row_ref,  # [C*rb] int32 — owning batch row per row of the stream
    pos_ref,  # [C*rb] int32 — last kv position a row reads to (-1: no query)
    lead_ref,  # [C] int32
    after_ref,  # [C*rb] int32
    table_ref,
    # inputs, output, scratch: _attend's
    q_ref,  # [rb*qt, H, D] VMEM — this cell's queries, ``qt`` a row
    *refs,  # _pools_out_scratch's
    rb: int,
    qpk: int,
    together: bool,
    qt: int = 1,
    sinks: bool = False,
    **shape,
):
    """A grid cell of the one-query call is ``rb`` consecutive slots, each
    a row of one query, walked one after another on ONE copy pipeline
    (``_attend``'s ``chain``): the cell starts its first live row's first
    chunk, and from then on whatever is multiplied, the chunk that follows
    it, of the same row or the first of the next live one, is on its way
    into the other buffer. A row's own arithmetic is ``_attend``'s at one
    query, state zeroed at its start, a chunk worked for all the KV heads
    ``together`` where the caller's reading of ``one_query_schedule`` says
    so (``_attend``'s ``together``). A slot of no query copies nothing and
    is zeros.
    At ``qt`` > 1 a row of the stream is a TILE of ``qt`` slots of one
    batch row (a block model's pass: its block, or in the opening pass the
    block before and then its block, two rows of the stream that read the
    same pages), walked as a row of one query is, always ``together``
    (``one_query_block`` sends every other tiled call to a tile a cell):
    ``pos_ref`` has each
    tile's bound, the largest of its queries' (what ``_tile_kernel`` reads
    to), and one more prefetched array, last, every slot's own, which masks
    it (a padding query inside a live tile sees nothing and is zeros).
    With ``sliding`` (in ``shape``) a sixth prefetched array comes behind
    the table: each row's first visible position (``sliding_starts``),
    whose page its walk starts at."""
    first_ref = slot_pos_ref = None
    if shape.get("sliding") is not None:
        first_ref, q_ref, *refs = q_ref, *refs
    if qt > 1:
        slot_pos_ref, q_ref, *refs = q_ref, *refs
    sink = {}
    if sinks:  # the heads' sink logits, first behind the queries
        sink["sink"], *refs = refs
    k_hbm, v_hbm, o_ref, scratch = _pools_out_scratch(refs, shape.get("vd"))
    k_buf, v_buf, *_, sems = scratch
    ps, cp = shape["ps"], shape["cp"]
    cell = pl.program_id(0)
    base = cell * rb

    def first_page(t):
        return 0 if first_ref is None else first_ref[t] // ps

    lead = lead_ref[cell]

    @pl.when(lead < rb)
    def _():
        t = base + lead
        for dma in _chunk_copies(
            row_ref[t], first_page(t), 0, table_ref, k_hbm, v_hbm, k_buf,
            v_buf, sems, cp=cp, pmax=shape["pmax"],
        ):
            dma.start()

    def row(j, slot0):
        t = base + j
        pos = pos_ref[t]
        if qt == 1:
            at = pl.ds(j, 1)
            bounds = lambda: jnp.full((qpk, 1), pos, jnp.int32)  # noqa: E731
        else:
            at = pl.ds(pl.multiple_of(j * qt, qt), qt)
            # score rows (kv head, group, query), all the heads': tiles are
            # walked here only with their chunk worked for the heads together
            n = shape["hkv"] * qpk * qt
            bounds = lambda: _tile_bounds(slot_pos_ref, t, qt, n)  # noqa: E731
        # The pass walks from its first page to its bound: at least a chunk
        # where it has a query, so a copy started for it is waited for.
        n_chunks = jnp.where(
            pos >= 0, pl.cdiv(pos + 1 - first_page(t) * ps, ps * cp), 0
        )

        @pl.when(pos >= 0)
        def _():
            after = after_ref[t]
            then = base + jnp.minimum(after, rb - 1)
            start = {} if first_ref is None else {"first": first_ref[t]}
            _attend(
                q_ref.at[at], o_ref.at[at], bounds, row_ref[t],
                pos + 1, table_ref, k_hbm, v_hbm, *scratch, w=qt, qpk=qpk,
                chain=(slot0, row_ref[then], first_page(then), after < rb),
                together=together, **start, **sink, **shape,
            )

        @pl.when(pos < 0)
        def _():
            o_ref[at] = jnp.zeros((qt, *o_ref.shape[1:]), o_ref.dtype)

        return jax.lax.rem(slot0 + n_chunks, 2)

    jax.lax.fori_loop(0, rb, row, 0)


def _block_vmem_bytes(qb, H, D, kv_lanes, cp, ps, q_item, kv_item) -> int:
    """VMEM a block cell holds, from its shapes: the query and output
    blocks (double-buffered by the pipeline, heads padded to a sublane
    tile), the K/V double buffers, the three softmax states, and the
    float32 queries and finished output as values."""
    heads = -(-H // 16) * 16
    io = 2 * 2 * qb * heads * D * q_item
    kv = 2 * 2 * cp * ps * kv_lanes * kv_item
    state = H * qb * (D + 2 * 128) * 4
    values = 2 * H * qb * D * 4
    return io + kv + state + values


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_kv_heads", "q_tile", "sm_scale", "interpret", "q_block",
        "value_dim", "sliding",
    ),
)
def ragged_paged_attention(
    q: jnp.ndarray,  # [N, H, D] flat query stream (N % q_tile == 0)
    k_cache: jnp.ndarray,  # [P, ps, Hkv*D] (heads collapsed into lanes)
    v_cache: jnp.ndarray | None,  # None with ``value_dim``
    page_table: jnp.ndarray,  # [R, Pmax] int32
    row_of: jnp.ndarray,  # [N] int32 owning row per query token
    positions: jnp.ndarray,  # [N] int32 absolute position, -1 = padding
    num_kv_heads: int | None = None,
    q_tile: int = 8,
    sm_scale: float | None = None,
    interpret: bool = False,
    bounds: jnp.ndarray | None = None,  # [N] last kv position seen; None = positions
    q_block: int | None = None,  # slots a grid cell serves; None: rows, the rule's
    value_dim: int | None = None,  # a latent pool: values = the row's first lanes
    keep: tuple[jnp.ndarray, ...] | None = None,  # a learned selection
    sliding: int | None = None,  # a sliding layer's width (keys a query sees)
    sink: jnp.ndarray | None = None,  # [H] a learned logit a query head
) -> jnp.ndarray:
    """Ragged paged attention over a flat query stream (Pallas TPU).

    Within one call the caller aligns each row's query span to
    ``q_tile`` flat slots (padding tokens carry position -1), so every
    tile belongs to exactly one row. A grid cell serves ``q_block``
    consecutive slots (a multiple of ``q_tile``; :func:`query_block`
    sizes it for a stream of long spans). At ``q_block == q_tile`` a
    cell is one tile: it DMAs only that row's live pages and computes
    ``q_tile`` queries against them. With no ``q_block`` (a block model's
    pass: a tile a row, rows side by side) a cell walks
    :func:`one_query_block`'s tiles one after another on one copy pipeline,
    as the one-query call walks its rows, each tile's arithmetic the
    one-tile cell's to the bit (one tile a cell where that rule answers 1:
    tiles are walked only with their chunk worked for the KV heads
    together). At more, a cell whose live tiles
    all belong to one row (:func:`block_plan`, derived here on the
    device) walks that row's pages once for all its queries, each
    masked at its own bound, and a cell that holds several rows walks
    its tiles one after another: either way a query's softmax sees the
    same chunks in the same order as in a cell of its tile alone.
    ``q_tile=1`` needs no alignment: any slot is a row of one query (a
    decode window's step and the packed tail of a mixed stream), and a
    cell walks ``q_block`` of them on one copy pipeline, each row's first
    chunk fetched behind the row before it (None:
    :func:`one_query_block`'s, from the call's shapes; 1: a cell a row,
    the old paged-decode kernel's grid, which a call with ``keep`` always
    takes). Returns [N, H, D] in q's dtype. Padding slots return exact
    zeros, inside a live tile as in a fully-empty one (inactive rows):
    routed experts sort every row of a dispatch together
    (``ops/moe.py``), so a padding row must not carry what memory no
    request owns into the rows beside it.

    The caller guarantees the fed tokens' K/V are already written
    (write-then-gather), so a tile's DMA bound is its max position + 1.
    ``bounds`` replaces the positions as what a query may see (its own
    position included; -1 for padding): the kernel reads nothing else of
    them, so the tile's DMA bound follows.

    ``value_dim`` (a latent pool, module docstring): no ``v_cache``; a
    head's values are the first ``value_dim`` lanes of its key row and the
    result is [N, H, value_dim].

    ``sliding`` (static; a sliding layer): query ``t`` sees the keys
    ``t - sliding < s <= t`` and no others. A cell's walk starts at the
    page of the smallest position its live queries see
    (:func:`sliding_starts`) and ends at its bound as ever, so a decode
    row reads ``sliding`` keys whatever its context, and the pages behind
    may have been given back (the table's entries there are never read).
    None: the program is the one without the argument, to the letter.

    ``keep`` (learned sparse attention): ``(scores, tau, allow)`` of
    ``ops/sparse_index.py``, the stream's index scores tiled as
    ``sparse_index.tiled_shape`` and ``select``'s threshold and number of
    ties a query ([N] each); a query attends only the positions
    ``sparse_index.kept`` names. Over a grouped-query pool the tiled call
    takes a block of several tiles (:func:`selected_query_block` is the
    rule) as the unselected call does, each query masked by its own
    threshold, to the bit what a cell of its tile alone gives; the scores
    and the two columns then run to whole blocks (:func:`selected_pad`
    slots of no query behind the stream, of anything). Over a latent pool
    (``value_dim``) and at one query a row a cell is one tile.

    **Values narrower than keys**: the value pool's row is ``Hkv`` heads of
    its own width (``v_cache.shape[-1] // Hkv``), which the result then has:
    [N, H, Dv]. The key's width is the queries'.

    ``sink`` ([H] float32, or None): a learned logit a query head that
    joins the softmax's denominator and carries no value:
    ``p[j] = exp(s[j] - m) / (sum_j exp(s[j] - m) + exp(sink - m))``, in all
    three forms of the call. None: the program is the one without it.
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
    latent = value_dim is not None
    Dv = value_dim if latent else v_cache.shape[-1] // Hkv
    # Lanes of a K row the call reads: the heads' keys. A row may hold more
    # behind them (``_chunk_copies``).
    key_lanes = fused if latent else Hkv * D
    # Rows a cell of the one-query call walks on one copy pipeline, a row a
    # query or, where the caller names no block, a tile; 1: a cell is a tile
    # (a learned selection's rows of one query come a row a cell).
    rb = 1
    if keep is None and q_block is None:
        rb = one_query_block(N // qt, H, D, Dv, qt, Hkv)
    elif keep is None and qt == 1:
        rb = q_block
    # Slots a cell of the tiled forms serves (the rows form has rows, not tiles).
    qb = qt if rb > 1 or q_block is None else q_block
    nt = qb // qt
    shape = dict(ps=ps, cp=cp, hkv=Hkv, hd=D, qpk=qpk, pmax=pmax, scale=scale)
    pools = (k_cache,) if latent else (k_cache, v_cache)
    if not latent and Dv != D:
        shape["vw"] = Dv
    # The heads' sink logits: one more input behind the queries, whole in
    # every cell and lane-replicated as the running maximum it starts.
    sink_specs, sink_args = [], ()
    if sink is not None:
        if latent or keep is not None:
            raise ValueError("a sink rides neither a latent pool nor a selection")
        shape["sinks"] = True
        sink_specs = [pl.BlockSpec(
            (H, 128), lambda t, *_: (0, 0), memory_space=pltpu.VMEM
        )]
        sink_args = (jnp.broadcast_to(
            sink.astype(jnp.float32)[:, None], (H, 128)
        ),)
    starts = ()
    if sliding is not None:
        if keep is not None or bounds is not None:
            raise ValueError("a sliding layer has neither a selection nor bounds")
        shape["sliding"] = sliding
        starts = sliding_starts(positions, qt, qb, sliding)
    params = {}
    if latent:
        shape["vd"] = value_dim
        # 128 heads of a tile hold about Mosaic's default 16 MiB.
        params["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=max(32 << 20, 2 * _block_vmem_bytes(
                qb, H, max(D, Dv), Hkv * D, cp, ps, q.dtype.itemsize,
                k_cache.dtype.itemsize,
            ))
        )

    def query_spec(width=D, slots=qb):
        return pl.BlockSpec(
            (slots, H, width), lambda t, *_: (t, 0, 0), memory_space=pltpu.VMEM
        )

    def scratch_for(queries):
        """``_attend``'s buffers and states for passes of ``queries``."""
        return [
            *(
                pltpu.VMEM((2, cp, ps, lanes), pool.dtype)
                for pool, lanes in zip(pools, (key_lanes, Hkv * Dv))
            ),
            pltpu.VMEM((H * queries, Dv), jnp.float32),
            pltpu.VMEM((H * queries, 128), jnp.float32),
            pltpu.VMEM((H * queries, 128), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2 * cp)),
        ]

    pool_specs = [pl.BlockSpec(memory_space=pl.ANY) for _ in pools]
    out_shape = jax.ShapeDtypeStruct((N, H, Dv), q.dtype)
    if rb > 1:
        # One query a row, or one tile, ``rb`` rows a cell on one copy
        # pipeline. The last cell of a stream that is no whole number of
        # them reads past the queries' end: its rows are padded here to no
        # query, and what it writes there is dropped.
        lead, after = one_query_chain(positions, rb, q_tile=qt)
        n_cells = lead.shape[0]
        over = (0, n_cells * rb - N // qt)
        rows, bound, slots = row_of, positions, ()
        if qt > 1:
            # a tile's row and bound (``_tile_kernel``'s), and every slot's own
            shape["qt"] = qt
            rows = row_of.reshape(-1, qt)[:, 0]
            bound = positions.reshape(-1, qt).max(axis=1)
            slots = (jnp.pad(positions, (0, over[1] * qt), constant_values=-1),)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5 + len(starts[:1]) + len(slots),
            grid=(n_cells,),
            in_specs=[query_spec(slots=rb * qt), *sink_specs, *pool_specs],
            out_specs=query_spec(Dv, rb * qt),
            scratch_shapes=scratch_for(qt),
        )
        return pl.pallas_call(
            functools.partial(
                _rows_kernel, rb=rb, **shape,
                together=one_query_schedule(rb, Hkv, H * qt) == "together",
            ),
            grid_spec=grid_spec,
            out_shape=out_shape,
            interpret=interpret,
            **params,
        )(
            jnp.pad(rows, over), jnp.pad(bound, over, constant_values=-1),
            lead, after, page_table, *(jnp.pad(s, over) for s in starts[:1]),
            *slots, q, *sink_args, *pools,
        )
    scratch_shapes = scratch_for(qb)
    if nt == 1:
        # Per-tile row + DMA bound, derived on device from the flat stream
        # (alignment makes every tile single-row; padding positions are -1
        # so the max is the tile's true causal horizon).
        n_tiles = N // qt
        tile_row = row_of.reshape(n_tiles, qt)[:, 0]
        tile_kvlen = positions.reshape(n_tiles, qt).max(axis=1) + 1
        kept_specs, kept_args = [], ()
        if keep is not None:
            scores, tau, allow = keep
            shape["kept"] = True
            column = pl.BlockSpec(
                (None, qt, 1), lambda t, *_: (t, 0, 0), memory_space=pltpu.VMEM
            )
            kept_specs = [
                pl.BlockSpec(
                    (None, *scores.shape[1:]), lambda t, *_: (t, 0, 0, 0),
                    memory_space=pltpu.VMEM,
                ),
                column, column,
            ]
            kept_args = (
                scores, tau.reshape(n_tiles, qt, 1), allow.reshape(n_tiles, qt, 1)
            )
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4 + len(starts[:1]),
            grid=(n_tiles,),
            in_specs=[query_spec(), *sink_specs, *kept_specs, *pool_specs],
            out_specs=query_spec(Dv),
            scratch_shapes=scratch_shapes,
        )
        return pl.pallas_call(
            functools.partial(_tile_kernel, qt=qt, **shape),
            grid_spec=grid_spec,
            out_shape=out_shape,
            interpret=interpret,
            **params,
        )(
            tile_row, tile_kvlen, positions, page_table, *starts[:1], q,
            *sink_args, *kept_args, *pools,
        )
    if keep is not None and latent:
        raise ValueError(
            "a learned selection over a latent pool rides a call whose block is one tile"
        )

    # The last block of a stream that is no whole number of blocks reads
    # past the queries' end: its plan and positions are padded here to
    # -1, what it computes there is masked, and what it writes dropped.
    plan = block_plan(row_of, positions, qt, qb)
    n_blocks = plan[2].shape[0]
    over = (0, n_blocks * qb - N)
    pos_col = jnp.pad(positions, over, constant_values=-1)[:, None]
    column = pl.BlockSpec((qb, 1), lambda t, *_: (t, 0), memory_space=pltpu.VMEM)
    kept_specs, kept_args = [], ()
    if keep is not None:
        # Over a grouped-query pool the selection rides the block: a column
        # of thresholds and one of ties a block, and the scores left in HBM
        # for the passes to copy a chunk at a time (``_CopiedScores``). A
        # last block that is not whole copies tiles past the stream's: the
        # caller lays slots of no query behind its selection.
        scores, tau, allow = keep
        T, _, _, S_i = scores.shape
        if T < n_blocks * nt:
            raise ValueError(
                f"a selection of {T * qt} slots for a stream of {N} in blocks "
                f"of {qb}: the call asks scores for {n_blocks * nt * qt} "
                "(selected_pad)"
            )
        shape["kept"] = True
        kept_specs = [column, column, pl.BlockSpec(memory_space=pl.ANY)]
        kept_args = tuple(
            jnp.pad(a[:N].astype(jnp.float32), over)[:, None] for a in (tau, allow)
        ) + (scores,)
        scratch_shapes += [
            pltpu.VMEM((2, nt, qt, S_i), jnp.float32), pltpu.SemaphoreType.DMA((2,)),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5 + len(starts),
        grid=(n_blocks,),
        in_specs=[column, query_spec(), *sink_specs, *kept_specs, *pool_specs],
        out_specs=query_spec(Dv),
        scratch_shapes=scratch_shapes,
    )
    vmem = _block_vmem_bytes(
        qb, H, D, Hkv * D, cp, ps, q.dtype.itemsize, k_cache.dtype.itemsize
    )
    # Mosaic's default scoped VMEM is 16 MiB; 30 heads of 128 queries hold
    # more than that.
    params.setdefault("compiler_params", pltpu.CompilerParams(
        vmem_limit_bytes=max(16 << 20, 2 * vmem)
    ))
    return pl.pallas_call(
        functools.partial(_block_kernel, qt=qt, nt=nt, **shape),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        **params,
    )(*plan, page_table, *starts, pos_col, q, *sink_args, *kept_args, *pools)


# ------------------------------------------- a latent pool, expanded form
# Query slots of one row that a grid cell of the expanded call serves with
# one pass over that row's context (one expansion of it for the cell's
# heads), and the slots of it a score product takes at a time.
_EXPANDED_QUERIES = 1024
_EXPANDED_SUB = 256
# Slots of no query a caller lays behind a stream's learned selection
# (``ragged_paged_attention_expanded``, ``keep``): a sub-block's window of
# the scores then lies inside them wherever its item ends.
EXPANDED_SELECTION_PAD = _EXPANDED_SUB
# Context tokens the expanded call expands and scores at a time: the
# softmax's per-query bookkeeping (a column a query: as many vector
# registers as a whole score tile) is paid once a chunk.
_EXPANDED_CHUNK = 512
# Query rows the expanded call lays out by head, or back by row, at a time.
_EXPANDED_TURN = 32
# Heads a cell of the expanded call holds: a whole sublane tile of a
# bfloat16 ``[slots, heads, width]`` stream, and 4 MB of ``W_ukv``.
_EXPANDED_HEADS = 16
# Heads of them whose products and softmax the kernel's code lays side by
# side (the others follow in a loop): all 16 were 13 % faster than one and
# 4 MB more code a call, which a variant's warm load pays (PERF.md §6, PR 45).
_EXPANDED_HEADS_AT_ONCE = 4


def expanded_min_queries(
    lanes: int, value_lanes: int, rank: int, nope: int, rope: int, v: int,
    q_tile: int,
) -> int:
    """Queries of one row in a call from which the expanded form of latent
    attention costs fewer operations than the absorbed one, in whole tiles.
    A query-key pair and head costs ``2 * (lanes + value_lanes)`` absorbed
    (scores over the pool's row, values over its first lanes) and ``2 *
    (nope + rope + v)`` expanded; building a context token's K and V costs
    ``2 * rank * (nope + v)`` a head, once for all the row's queries that
    share a pass. From the widths alone: the program
    (``models/llama.py::latent_form_queries``) and the engine's counter ask
    the same function."""
    saved = 2 * (lanes + value_lanes) - 2 * (nope + rope + v)
    if saved <= 0:
        return 2**30
    least = -(-2 * rank * (nope + v) // saved)
    return -(-least // q_tile) * q_tile


def expanded_supported(
    page_size: int, lanes: int, rank: int, nope: int, rope: int, v: int,
    heads: int, kv_dtype,
) -> bool:
    """Whether the expanded call compiles on real TPU hardware at these
    widths: the pool's row splits into ``c_kv`` and ``[k_r | padding]`` on a
    lane group's boundary, a head's K and V are whole lane groups (K as
    ``k_nope`` alone, or as ``[k_nope | k_r]`` where ``nope`` is no whole
    number of groups and ``nope + rope`` is: :func:`_expanded_joint`), and
    the heads come in whole cells."""
    return (
        latent_supported(page_size, lanes, rank, kv_dtype)
        and rank % 128 == 0 and v % 128 == 0
        and (nope % 128 == 0 or (nope + rope) % 128 == 0)
        and rope <= lanes - rank
        and heads % _EXPANDED_HEADS == 0
    )


def _expanded_joint(nope: int, rope: int) -> bool:
    """Which of its two score layouts the expanded call takes, from the
    widths: *split* where ``nope`` is whole lane groups (``q_nope . k_nope +
    q_rope . k_r``, the second product against the row's own ``[k_r | 0]``
    lanes: 128 + 64 at openPangu's widths), *joint* where only ``nope +
    rope`` is (ONE product over the published head ``[k_nope | k_r]``: 192
    + 64 = 256 at GLM-5's, two whole groups with no padding)."""
    return nope % 128 != 0 and (nope + rope) % 128 == 0


def expanded_rows(row_of, positions, min_queries: int, n_rows: int, xp=jnp):
    """Which rows of a call's stream take the expanded form ([n_rows]
    bool): those with at least ``min_queries`` live queries in it. ``xp``:
    ``jnp`` inside the program, ``numpy`` for the engine's counter."""
    hit = (row_of[:, None] == xp.arange(n_rows)[None]) & (positions >= 0)[:, None]
    return hit.sum(axis=0) >= min_queries


def expanded_items(n: int, n_rows: int, min_queries: int, q_max: int) -> int:
    """Most passes (:func:`expanded_plan`'s items) a stream of ``n`` slots
    can hold: a row's span in pieces of at most ``q_max`` slots, and no
    more rows than ``n // min_queries`` take the form."""
    return n // q_max + min(n_rows, n // min_queries)


def expanded_plan(
    row_of, positions, n_rows: int, q_tile: int, min_queries: int,
    q_max: int = _EXPANDED_QUERIES, q_sub: int = _EXPANDED_SUB,
):
    """What the expanded call reads, from the stream alone (on the device,
    once a forward: every layer reads the same stream): ``takes`` [N] bool,
    the slots of the live tiles of rows that take the form (a tile's padding
    slots included), and the passes (*items*) ``(row, start, slots, kvlen,
    sub_kvlen, sub_first)``, each ``[items]`` but the last two, which give
    the kv tokens the item's every ``q_sub`` slots see and the first
    position among them (``[items * (q_max // q_sub)]``; a chunk of context
    wholly before it needs no mask). A row's span (its live tiles, which
    lie side by side in the stream) is cut into the fewest pieces of at
    most ``q_max`` slots, of equal length in whole tiles; an item past the
    last has no slots. Comparisons against the rows' and the items'
    indices and reductions over the stream: no scatter, whose code and
    time on the chip were most of what the form cost outside its kernel."""
    n = positions.shape[0]
    items = expanded_items(n, n_rows, min_queries, q_max)
    subs = q_max // q_sub
    slot = jnp.arange(n, dtype=jnp.int32)
    live = positions >= 0
    live_tile = jnp.repeat(live.reshape(-1, q_tile).any(axis=1), q_tile)

    def over(index, size, values, least: bool, where=None):
        """``[size]``: the least or largest of ``values`` over the slots
        whose ``index`` is each of ``range(size)`` (and ``where``)."""
        hit = index[:, None] == jnp.arange(size, dtype=jnp.int32)[None]
        if where is not None:
            hit = hit & where[:, None]
        if least:
            return jnp.where(hit, values[:, None], 2**30).min(axis=0)
        return jnp.where(hit, values[:, None], 0).max(axis=0)

    takes = expanded_rows(row_of, positions, min_queries, n_rows)[row_of]
    span = takes & live_tile
    first = jnp.minimum(over(row_of, n_rows, slot, True, span), n)
    extent = jnp.maximum(over(row_of, n_rows, slot + 1, False, span) - first, 0)
    pieces = -(-extent // q_max)
    length = -(-extent // jnp.maximum(pieces, 1) // q_tile) * q_tile
    base = jnp.cumsum(pieces) - pieces
    item = jnp.where(
        span,
        base[row_of] + (slot - first[row_of]) // jnp.maximum(length[row_of], 1),
        items,
    )
    start = over(item, items, slot, True)
    slots = jnp.maximum(over(item, items, slot + 1, False) - start, 0)
    start = jnp.where(slots > 0, start, 0)
    row = over(item, items, row_of, False)
    kvlen = over(item, items, positions + 1, False)
    sub = jnp.where(
        span, item * subs + (slot - start[jnp.minimum(item, items - 1)]) // q_sub,
        items * subs,
    )
    sub_kvlen = over(sub, items * subs, positions + 1, False)
    sub_first = over(sub, items * subs, positions, True, live)
    return span, (row, start, slots, kvlen, sub_kvlen, sub_first)


def _expanded_kernel(
    # scalar prefetch (SMEM): expanded_plan's items, and the page table
    item_row_ref,
    item_start_ref,
    item_slots_ref,
    item_kvlen_ref,
    sub_kvlen_ref,
    sub_first_ref,
    table_ref,
    # inputs, output and scratch: ``_expanded_refs`` names them
    *refs,
    qt: int,
    qs: int,
    qmax: int,
    G: int,
    rank: int,
    nope: int,
    v: int,
    ps: int,
    cp: int,
    pmax: int,
    scale: float,
    at_once: int,
    rope: int = 0,
    kept: int = 0,
):
    """A grid cell is (a group of ``G`` heads, an item): one pass over the
    item's row's context, each chunk of latents expanded once to the
    group's K and V (rounded to the pool's dtype, as the published form
    rounds them) for all the item's queries, ``qs`` of them a score
    product. ``_attend``'s softmax, chunk by chunk in the same order.

    ``rope`` > 0 is the *joint* score layout (:func:`_expanded_joint`): a
    head's K is ``[k_nope | k_r]``, ``nope + rope`` wide, and the queries
    come as the model makes them; ``w_ref`` a head is then ``[W_uk | 0 |
    W_uv]`` with ``rope`` columns of zeros, into which the chunk's ``k_r``
    is added before the one rounding (placed there by a 0/1 product once a
    chunk: exact).

    ``kept`` > 0 (a learned selection; the number of the scores' chunks
    that make one of this kernel's): every (sub-block, chunk) masks, a
    query seeing position ``s`` only where ``sparse_index.kept`` says. The
    chunk's block of index scores of each sub-block comes by DMA while the
    chunk is expanded; the ties at the threshold a query has passed in
    earlier chunks are a column of scratch beside ``m`` and ``l``."""
    r = _expanded_refs(refs, rope > 0, kept > 0)
    pos_hbm, q_hbm, k_hbm, w_ref, o_hbm = r["pos"], r["q"], r["k"], r["w"], r["o"]
    pos_buf, q_raw, qn_s, qr_s, kv_s = (
        r["pos_buf"], r["q_raw"], r["qn_s"], r.get("qr_s"), r["kv_s"]
    )
    k_buf, acc_ref, m_ref, l_ref, o_buf = (
        r["k_buf"], r["acc"], r["m"], r["l"], r["o_buf"]
    )
    sems, io_sems = r["sems"], r["io_sems"]
    g, i = pl.program_id(0), pl.program_id(1)
    slots = item_slots_ref[i]
    subs = qmax // qs
    kw = nope + rope  # a head's K as the score product takes it

    @pl.when(slots > 0)
    def _():
        row, kvlen = item_row_ref[i], item_kvlen_ref[i]
        start = pl.multiple_of(item_start_ref[i], qt)
        n_sub = pl.cdiv(slots, qs)
        heads = pl.ds(pl.multiple_of(g * G, G), G)
        S = cp * ps
        n_chunks = pl.cdiv(kvlen, S)

        def chunk_dmas(c, slot):
            base = c * cp
            return [
                pltpu.make_async_copy(
                    k_hbm.at[table_ref[row, jnp.minimum(base + j, pmax - 1)]],
                    k_buf.at[slot, j], sems.at[slot, j],
                )
                for j in range(cp)
            ]

        @pl.when(n_chunks > 0)
        def _():
            for dma in chunk_dmas(0, 0):
                dma.start()

        pos_dma = pltpu.make_async_copy(
            pos_hbm.at[pl.ds(start, qmax)], pos_buf, io_sems.at[0]
        )
        pos_dma.start()
        if kept:
            sel_dma = pltpu.make_async_copy(
                r["sel"].at[pl.ds(start, qmax)], r["sel_buf"], r["keep_sems"].at[subs]
            )
            sel_dma.start()
            # [s', s] = 1 where s' <= s: a product with it counts a chunk's
            # ties up to each position (0 / 1 operands, float32 sums: exact)
            r["upto"][...] = jnp.where(
                jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
                <= jax.lax.broadcasted_iota(jnp.int32, (S, S), 1), 1.0, 0.0,
            ).astype(r["upto"].dtype)

        def sub_at(j):
            return pl.ds(pl.multiple_of(j * qs, qs), qs)

        def move_tiles(j, dma):
            """Start, then wait for, ``dma(t)`` of each whole tile ``t`` of
            sub-block ``j`` that lies in the item."""
            tiles = jnp.minimum(slots - j * qs, qs) // qt
            jax.lax.fori_loop(0, tiles, lambda t, _: (dma(t).start(), 0)[1], 0)
            jax.lax.fori_loop(0, tiles, lambda t, _: (dma(t).wait(), 0)[1], 0)

        # Rows the kernel turns between [rows, heads] and [heads, rows] at a
        # time: the code of a turn is a unit's, not a sub-block's.
        unit = min(qs, _EXPANDED_TURN)
        assert qs % unit == 0, (qs, unit)

        def unit_at(u, base):
            return pl.ds(pl.multiple_of(base + u * unit, unit), unit)

        def load(j, _):
            """Sub-block j's queries, tile by tile (a tile's start is any
            multiple of ``qt`` in the stream), laid out by head."""
            move_tiles(j, lambda t: pltpu.make_async_copy(
                q_hbm.at[pl.ds(start + j * qs + t * qt, qt), heads],
                q_raw.at[pl.ds(t * qt, qt)], io_sems.at[1],
            ))

            def by_head(u, _):  # [unit, G, D] -> [G, unit, D], a unit of rows
                x = jnp.swapaxes(
                    q_raw[unit_at(u, 0), :, :].astype(jnp.float32), 0, 1
                )
                at = unit_at(u, j * qs)
                if rope:
                    qn_s[:, at, :] = x.astype(qn_s.dtype)
                else:
                    qn_s[:, at, :] = x[..., :nope].astype(qn_s.dtype)
                    qr_s[:, at, :] = x[..., nope:].astype(qr_s.dtype)
                acc_ref[:, at, :] = jnp.zeros((G, unit, v), jnp.float32)
                m_ref[:, at, :] = jnp.full((G, unit, 128), -1e30, jnp.float32)
                l_ref[:, at, :] = jnp.zeros((G, unit, 128), jnp.float32)
                if kept:
                    r["ties"][at, :] = jnp.zeros((unit, 128), jnp.float32)
                return 0

            jax.lax.fori_loop(0, qs // unit, by_head, 0)
            return 0

        jax.lax.fori_loop(0, n_sub, load, 0)
        pos_dma.wait()
        if kept:
            sel_dma.wait()

        def idx_dma(c, j):
            """Chunk ``c``'s index scores of sub-block ``j``'s tiles (the
            scores are padded by a sub-block's tiles: the window is theirs
            wherever the item ends)."""
            return pltpu.make_async_copy(
                r["scores"].at[
                    pl.ds((start + j * qs) // qt, qs // qt), pl.ds(c * kept, kept)
                ],
                r["idx_buf"].at[j], r["keep_sems"].at[j],
            )

        def chunk(c, _):
            slot = jax.lax.rem(c, 2)

            @pl.when(c + 1 < n_chunks)
            def _():
                for dma in chunk_dmas(c + 1, jax.lax.rem(c + 1, 2)):
                    dma.start()

            if kept:
                # the sub-blocks that see the chunk (``sub`` waits under the
                # same condition); the copies land while the chunk expands
                for j in range(subs):
                    @pl.when(sub_kvlen_ref[i * subs + j] > c * S)
                    def _(j=j):
                        idx_dma(c, j).start()

            for dma in chunk_dmas(c, slot):
                dma.wait()
            latent = k_buf[slot].reshape(S, k_buf.shape[-1])
            # K and V of the group's heads, a head at a time: one product
            # with its columns of W_ukv, rounded once to the pool's dtype.
            c_kv = latent[:, :rank]
            if rope:
                # [S, kw] float32: k_r at lanes [nope, kw), zeros before
                tail = k_buf.shape[-1] - rank
                place = jnp.where(
                    (jax.lax.broadcasted_iota(jnp.int32, (tail, kw), 0) + nope
                     == jax.lax.broadcasted_iota(jnp.int32, (tail, kw), 1)),
                    1.0, 0.0,
                ).astype(latent.dtype)
                k_placed = jax.lax.dot_general(
                    latent[:, rank:], place, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )

            def expand(h, _):
                kv = jax.lax.dot_general(
                    c_kv, w_ref[h], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                if rope:
                    kv_s[h, :, :kw] = (kv[:, :kw] + k_placed).astype(kv_s.dtype)
                    kv_s[h, :, kw:] = kv[:, kw:].astype(kv_s.dtype)
                else:
                    kv_s[h] = kv.astype(kv_s.dtype)
                return 0

            jax.lax.fori_loop(0, G, expand, 0)
            if not rope:
                k_rope = latent[:, rank:]  # [S, rope | padding], every head's
            tok_idx = c * S + jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)

            def update(j, masked: bool):
                at = sub_at(j)
                if masked:
                    inside = (
                        j * qs + jax.lax.broadcasted_iota(jnp.int32, (qs, 1), 0)
                        < slots
                    )
                    live = tok_idx <= jnp.where(inside, pos_buf[at, :1], -1)
                if kept:
                    idx_dma(c, j).wait()
                    # [tiles, kept, qt, S / kept] -> [qs, S]: a query a row
                    idx = r["idx_buf"][j]
                    idx = jnp.concatenate(
                        [idx[:, k].reshape(qs, S // kept) for k in range(kept)], axis=1
                    ) if kept > 1 else idx.reshape(qs, S)
                    tau = r["sel_buf"][at, :1]
                    allow = r["sel_buf"][at, 64:65]
                    tie = idx == tau
                    ones = jnp.where(tie, 1.0, 0.0)
                    among = r["ties"][at, :1] + jax.lax.dot_general(
                        ones.astype(r["upto"].dtype), r["upto"][...],
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                    live = live & ((idx > tau) | (tie & (among <= allow)))
                    r["ties"][at, :] = r["ties"][at, :] + jnp.sum(
                        ones, axis=1, keepdims=True
                    )

                def head(h, _):
                    if rope:
                        vh = kv_s[h, :, kw:]
                        s = jax.lax.dot_general(
                            qn_s[h, at, :], kv_s[h, :, :kw], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                        ) * scale
                    else:
                        kh, vh = kv_s[h, :, :nope], kv_s[h, :, nope:]
                        s = (jax.lax.dot_general(
                            qn_s[h, at, :], kh, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                        ) + jax.lax.dot_general(
                            qr_s[h, at, :], k_rope, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                        )) * scale
                    if masked:
                        s = jnp.where(live, s, -1e30)
                    m_prev = m_ref[h, at, :1]
                    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
                    alpha = jnp.exp(m_prev - m_new)
                    p = jnp.exp(s - m_new)
                    if masked:
                        p = jnp.where(live, p, 0.0)  # _attend's
                    l_ref[h, at, :] = l_ref[h, at, :] * alpha + jnp.sum(
                        p, axis=1, keepdims=True
                    )
                    acc_ref[h, at, :] = acc_ref[h, at, :] * alpha + (
                        jax.lax.dot_general(
                            p.astype(vh.dtype), vh, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32,
                        )
                    )
                    m_ref[h, at, :] = jnp.broadcast_to(m_new, (qs, 128))
                    return 0

                # The heads a few at a time, not all side by side: a cell's
                # code is a few heads', and a variant's executable loads
                # fast; a few, so that one's products overlap another's
                # softmax.
                def some(i, _):
                    for k in range(at_once):
                        head(i * at_once + k, 0)
                    return 0

                jax.lax.fori_loop(0, G // at_once, some, 0)

            def sub(j, _):
                # A sub-block wholly before the chunk sees none of it; one
                # wholly behind it sees all of it and masks nothing (what
                # a slot without a query computes there is dropped at the
                # store), unless a selection says what it sees.
                seen, first = sub_kvlen_ref[i * subs + j], sub_first_ref[i * subs + j]
                if kept:
                    @pl.when(seen > c * S)
                    def _():
                        update(j, masked=True)

                    return 0

                @pl.when((seen > c * S) & (first >= (c + 1) * S - 1))
                def _():
                    update(j, masked=False)

                @pl.when((seen > c * S) & (first < (c + 1) * S - 1))
                def _():
                    update(j, masked=True)

                return 0

            jax.lax.fori_loop(0, n_sub, sub, 0)
            return 0

        jax.lax.fori_loop(0, n_chunks, chunk, 0)

        def store(j, _):
            def by_row(u, _):  # [G, unit, v] -> [unit, G, v]
                at = unit_at(u, j * qs)
                l = l_ref[:, at, :1]
                out = acc_ref[:, at, :] / jnp.where(l == 0.0, 1.0, l)
                out = jnp.where((pos_buf[at, :1] >= 0)[None], out, 0.0)
                o_buf[unit_at(u, 0), :, :] = jnp.swapaxes(out, 0, 1).astype(o_buf.dtype)
                return 0

            jax.lax.fori_loop(0, qs // unit, by_row, 0)

            move_tiles(j, lambda t: pltpu.make_async_copy(
                o_buf.at[pl.ds(t * qt, qt)],
                o_hbm.at[pl.ds(start + j * qs + t * qt, qt), heads],
                io_sems.at[1],
            ))
            return 0

        jax.lax.fori_loop(0, n_sub, store, 0)


def _expanded_refs(refs, joint: bool, kept: bool) -> dict:
    """``_expanded_kernel``'s references by name, in the order the call
    lays them: inputs, the output, scratch.

    - ``pos`` [N + q_max, 128] int32 HBM: last kv position each slot sees
      (lane-replicated); ``q`` [N, H, D] HBM: the published form's queries
      (split layout: zeros behind the rotary part as behind the pool's
      ``k_r``); ``k`` [P, ps, lanes] HBM: the latent pool; ``w`` [G, rank,
      kw + v] VMEM: this cell's heads of W_ukv; with a selection ``scores``
      [T + q_sub / qt, C, qt, S_a] float32 HBM and ``sel`` [N + q_max, 128]
      float32 HBM (a slot's ``tau`` in lanes [0, 64), ``allow`` behind).
    - ``o`` [N, H, v] HBM.
    - ``pos_buf`` [q_max, 128]; ``q_raw`` [q_sub, G, D]; ``qn_s`` [G, q_max,
      kw] queries by head, the pool's dtype (split: ``qr_s`` [G, q_max,
      lanes - rank] the rotary part beside it); ``kv_s`` [G, S, kw + v] a
      chunk's K and V by head; ``k_buf`` [2, cp, ps, lanes]; ``acc`` [G,
      q_max, v], ``m``, ``l`` [G, q_max, 128] float32; ``o_buf`` [q_sub, G,
      v]; ``sems`` [2, cp] a chunk's pages; ``io_sems`` [2] positions,
      query and result tiles; with a selection ``idx_buf`` [subs, q_sub /
      qt, kept, qt, S_a] float32, ``sel_buf`` [q_max, 128] float32, ``ties``
      [q_max, 128] float32, ``upto`` [S, S] and ``keep_sems`` [subs + 1]."""
    names = ["pos", "q", "k", "w"]
    if kept:
        names += ["scores", "sel"]
    names += ["o", "pos_buf", "q_raw", "qn_s"]
    if not joint:
        names.append("qr_s")
    names += ["kv_s", "k_buf", "acc", "m", "l", "o_buf", "sems", "io_sems"]
    if kept:
        names += ["idx_buf", "sel_buf", "ties", "upto", "keep_sems"]
    assert len(names) == len(refs), (names, len(refs))
    return dict(zip(names, refs))


@functools.partial(
    jax.jit,
    static_argnames=(
        "rank", "nope", "q_tile", "sm_scale", "interpret", "q_max", "q_sub",
        "kv_chunk",
    ),
)
def ragged_paged_attention_expanded(
    q: jnp.ndarray,  # [N, H, nope + rope] the published form's queries
    k_cache: jnp.ndarray,  # [P, ps, lanes] the latent pool
    w_ukv: jnp.ndarray,  # [rank, H * (nope + v)], a head's [W_uk | W_uv]
    page_table: jnp.ndarray,  # [R, Pmax] int32
    positions: jnp.ndarray,  # [N] int32, -1 = padding
    plan: tuple,  # expanded_plan's items for this stream, at q_max and q_sub
    *,
    rank: int,
    nope: int,
    q_tile: int,
    sm_scale: float,
    interpret: bool = False,
    q_max: int = _EXPANDED_QUERIES,  # slots a pass serves at most
    q_sub: int = _EXPANDED_SUB,  # slots of them a score product takes
    kv_chunk: int = _EXPANDED_CHUNK,  # context tokens expanded at a time
    keep: tuple[jnp.ndarray, ...] | None = None,  # a learned selection
) -> jnp.ndarray:
    """Latent attention in the **expanded** form for the rows of a tiled
    stream that :func:`expanded_plan` gave passes (those with at least its
    ``min_queries`` live queries in the stream): ``o [N, H, v]`` holds the
    result at the slots the plan's ``takes`` marks (those rows' padding
    slots exact zeros) and nothing a caller may read anywhere else: the
    caller serves the other rows in the absorbed form
    (:func:`ragged_paged_attention` at ``value_dim``, their positions
    alone) and picks by ``takes``.

    A head's key is ``[c_kv W_uk_h | k_r]`` and its value ``c_kv W_uv_h``,
    built from the row's pages (``[c_kv | k_r | 0]`` a token) a chunk at a
    time inside the kernel, rounded to the pool's dtype, and never stored:
    scores ``(q_nope . k_nope + q_rope . k_r) * sm_scale`` in the pool's
    dtype with float32 accumulation (one product or two:
    :func:`_expanded_joint`), softmax in float32 (``_expanded_kernel``).
    The stream's contract is the tiled call's: a row's span is whole tiles
    of ``q_tile`` slots side by side, written before it is read.

    ``keep`` (learned sparse attention): ``(scores, tau, allow)`` of
    ``ops/sparse_index.py`` as :func:`ragged_paged_attention` takes them for
    this stream, the scores' tiles and the two columns each longer by (at
    least) ``q_sub`` slots of anything, so that a sub-block's window of them
    lies inside wherever its item ends; a query attends only the positions
    ``sparse_index.kept`` names."""
    N, H, D = q.shape
    _, ps, lanes = k_cache.shape
    v = w_ukv.shape[1] // H - nope
    pmax = page_table.shape[1]
    qmax, qs = q_max, q_sub
    G = _EXPANDED_HEADS if H % _EXPANDED_HEADS == 0 else H
    cp = max(1, min(kv_chunk // ps, pmax))
    rope = D - nope if _expanded_joint(nope, D - nope) else 0
    kw = nope + rope
    kept = 0
    if keep is not None:
        # A chunk of this call is a whole number of the scores' chunks, as
        # many as divide their count: a chunk addresses them by a leading
        # index.
        scores, tau, allow = keep
        T, C, _, S_a = scores.shape
        if (T * q_tile - N) < qs or tau.shape[0] < N or allow.shape[0] < N:
            raise ValueError(
                f"a selection of {T * q_tile} slots for a stream of {N}: the "
                f"expanded call asks {qs} slots more (EXPANDED_SELECTION_PAD)"
            )
        kept = max(k for k in range(1, max(1, cp * ps // S_a) + 1) if C % k == 0)
        cp = kept * S_a // ps
    S = cp * ps
    # What the form costs outside its kernel, a layer: two paddings and a
    # layout of W_ukv (the plan is the forward's, not the layer's).
    with jax.named_scope("mla_expand"):
        if not rope:
            # The rotary part meets the row's ``[k_r | 0]`` lanes: zeros behind it.
            q = jnp.pad(q, ((0, 0), (0, 0), (0, nope + lanes - rank - D)))
            D = q.shape[-1]
        # A column a slot, replicated over a lane group: any window of it
        # is whole tiles for the DMA.
        pos_col = jnp.broadcast_to(
            jnp.pad(positions, (0, qmax), constant_values=-1)[:, None],
            (N + qmax, 128),
        )
        w_heads = w_ukv.reshape(rank, H, nope + v)
        if rope:
            # [W_uk | 0 | W_uv]: the zeros are where ``k_r`` goes
            w_heads = jnp.concatenate([
                w_heads[..., :nope], jnp.zeros((rank, H, rope), w_ukv.dtype),
                w_heads[..., nope:],
            ], axis=-1)
        # A head's [W_uk | W_uv] as one block of a cell's operand.
        w_heads = jnp.swapaxes(w_heads, 0, 1)
        kept_args = ()
        if kept:
            column = jnp.where(
                jnp.arange(128)[None] < 64, tau[:N, None], allow[:N, None]
            ).astype(jnp.float32)
            kept_args = (scores, jnp.pad(column, ((0, qmax), (0, 0))))
    items = plan[0].shape[0]
    kv_item, q_item = k_cache.dtype.itemsize, q.dtype.itemsize
    vmem = (
        2 * rank * G * (kw + v) * w_ukv.dtype.itemsize  # W_ukv, pipelined
        + G * qmax * (nope + (lanes - rank)) * kv_item  # queries by head
        + G * qmax * (v + 2 * 128) * 4  # softmax states
        + S * G * (kw + v) * kv_item  # a chunk's K and V
        + 2 * S * (kw + v) * 4  # a head's, as values
        + 2 * qs * max(G, 16) * (-(-D // 128) * 128 + v) * (q_item + 4)
        + 2 * cp * ps * lanes * kv_item + qmax * 512
        + 8 * qs * S * 4  # scores and probabilities of a head in flight
    )
    if kept:
        # the index scores of a chunk, the tie matrix, two columns, a mask
        vmem += qmax * S * 4 + S * S * kv_item + 2 * qmax * 512 + 4 * qs * S * 4
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    scratch_shapes = [
        pltpu.VMEM((qmax, 128), jnp.int32),
        pltpu.VMEM((qs, G, D), q.dtype),
        pltpu.VMEM((G, qmax, kw), k_cache.dtype),
        *([] if rope else [pltpu.VMEM((G, qmax, lanes - rank), k_cache.dtype)]),
        pltpu.VMEM((G, S, kw + v), k_cache.dtype),
        pltpu.VMEM((2, cp, ps, lanes), k_cache.dtype),
        pltpu.VMEM((G, qmax, v), jnp.float32),
        pltpu.VMEM((G, qmax, 128), jnp.float32),
        pltpu.VMEM((G, qmax, 128), jnp.float32),
        pltpu.VMEM((qs, G, v), q.dtype),
        pltpu.SemaphoreType.DMA((2, cp)),
        pltpu.SemaphoreType.DMA((2,)),
    ]
    if kept:
        scratch_shapes += [
            pltpu.VMEM((qmax // qs, qs // q_tile, kept, q_tile, S // kept), jnp.float32),
            pltpu.VMEM((qmax, 128), jnp.float32),
            pltpu.VMEM((qmax, 128), jnp.float32),
            pltpu.VMEM((S, S), k_cache.dtype),
            pltpu.SemaphoreType.DMA((qmax // qs + 1,)),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(H // G, items),
        in_specs=[
            any_space, any_space, any_space,
            pl.BlockSpec(
                (G, rank, kw + v), lambda g, i, *_: (g, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            *([any_space, any_space] if kept else []),
        ],
        out_specs=any_space,
        scratch_shapes=scratch_shapes,
    )
    o = pl.pallas_call(
        functools.partial(
            _expanded_kernel, qt=q_tile, qs=qs, qmax=qmax, G=G, rank=rank,
            nope=nope, v=v, ps=ps, cp=cp, pmax=pmax, scale=sm_scale,
            at_once=math.gcd(G, _EXPANDED_HEADS_AT_ONCE), rope=rope, kept=kept,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, H, v), q.dtype),
        interpret=interpret,
        name="ragged_paged_attention_expanded",
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(32 << 20, min(100 << 20, vmem * 5 // 4)),
        ),
    )(*plan, page_table, pos_col, q, k_cache, w_heads, *kept_args)
    return o


def ragged_decode_attention(
    q: jnp.ndarray,  # [B, H, D] — one query per row
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    page_table: jnp.ndarray,  # [B, Pmax] int32
    lengths: jnp.ndarray,  # [B] int32 tokens to attend over (0 = inactive)
    num_kv_heads: int | None = None,
    interpret: bool = False,
    sm_scale: float | None = None,
    value_dim: int | None = None,
    keep: tuple[jnp.ndarray, ...] | None = None,
    sliding: int | None = None,
    sink: jnp.ndarray | None = None,
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
        sm_scale=sm_scale,
        value_dim=value_dim,
        keep=keep,
        sliding=sliding,
        sink=sink,
    )
