"""Ragged paged attention, the kernels against the reference: the Pallas
kernel (interpret mode) in its three forms, a tile a grid cell, a block of
tiles a cell (``q_block``) and rows walked on one pipeline a cell (one
query a row at ``q_tile=1``, one tile a row where no block is named),
against the pure-JAX reference across ragged (query_len, kv_len) shapes:
page-boundary straddling spans, rows-1, inactive rows, GQA grouping, bf16
pools, sliding widths, sinks, values narrower than keys, a latent pool; and
the bit-for-bit streams (PR 44's digests, PR 52's and PR 54's cells of
rows). Tiles walked as rows (PR 56): ``tests/test_ragged_tile_rows.py``. The
expanded call: ``tests/test_ragged_expanded.py``; the host's
rules: ``tests/test_ragged_rules.py``; engines and the tp dispatches:
``tests/test_ragged_attention.py``. What each parametrised case crosses is
said at its table (docs/testing.md "Conventions").
"""

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_exp_tpu.ops.ragged_attention import (
    merged_slot_counts,
    one_query_block,
    one_query_schedule,
    query_block,
    ragged_decode_attention,
    ragged_paged_attention,
    ragged_paged_attention_ref,
)

from .ragged_cases import extras, flat_batch, padded


# ------------------------------------------------------------ kernel parity
_TILE_SPANS = [
    # (query_len, kv_len) per row: decode, chunk, spec-verify-ish.
    [(1, 17), (5, 5), (3, 20)],
    # Page-boundary cases: span ends exactly on a page boundary,
    # span crosses one, kv exactly page-aligned.
    [(8, 16), (9, 25), (1, 8)],
    # rows-1: a single row, chunk wider than one q tile.
    [(13, 13)],
    # Inactive row (0 queries is impossible flat — 1-query row with
    # deep kv next to a fresh full-prefill row).
    [(1, 64), (32, 32)],
]


# A sliding layer's width (keys a query sees): none, one that is no whole
# number of pages of 16, one that is. Behind the plain cases: a learned
# sink logit a query head in the softmax, and values narrower than keys.
# STATIC facts a case crosses (one compile each, 6 in all): ``sliding``
# (none / inside a page / whole pages), ``sink``, ``vw`` (narrow values);
# 2 query heads a KV head (no whole sublane tile), several KV heads. What the
# four streams cross is data: every stream is padded to 36 slots and 3 rows
# on a table of 4 pages (one chunk of 64 tokens, 4 page copies).
@pytest.mark.parametrize("spans,sliding,extra", [
    *((spans, sliding, "") for sliding in (None, 5, 16) for spans in _TILE_SPANS),
    (_TILE_SPANS[0], None, "sink"),
    (_TILE_SPANS[1], 5, "sink+narrow"),
    (_TILE_SPANS[3], 16, "narrow"),
])
def test_kernel_matches_reference_ragged(spans, sliding, extra):
    H, Hkv, D, ps, pmax = 8, 4, 64, 16, 4
    Dv, sink = extras(extra, H, D)
    q, k, v, table, row_of, positions = padded(
        flat_batch(0, spans, H, Hkv, D, 64, ps, pmax, q_tile=4, Dv=Dv), 36, 3
    )
    got = ragged_paged_attention(
        q, k, v, table, row_of, positions, num_kv_heads=Hkv, q_tile=4,
        interpret=True, sliding=sliding, **sink,
    )
    want = ragged_paged_attention_ref(
        q, k, v, table, row_of, positions, num_kv_heads=Hkv, window=sliding,
        **sink,
    )
    live = np.asarray(positions) >= 0
    assert got.shape == (q.shape[0], H, Dv or D)
    np.testing.assert_allclose(
        np.asarray(got)[live], np.asarray(want)[live], atol=2e-5
    )
    if sink:  # the sink is no small thing: without it the result is another
        plain = ragged_paged_attention_ref(
            q, k, v, table, row_of, positions, num_kv_heads=Hkv, window=sliding,
        )
        assert np.abs(np.asarray(plain) - np.asarray(want))[live].max() > 1e-2


# What a window's step, or a mixed stream's packed tail, may look like to a
# grid cell of the one-query call: (lengths a row, rows a cell; None: what
# the call's shapes give, here all the rows in one cell).
_DECODE_ROWS = {
    "short": ([1, 17, 0, 5], None),
    # dead rows first, in the middle and last; rows of one chunk (of 128
    # tokens: 2 pages of 64 at "1x2"), of two and of three side by side
    "dead_and_long_in_one_cell": ([0, 0, 5, 128, 0, 129, 300, 1, 0], None),
    # the same stream in cells of 4, 4 and 1: no whole number of cells, a
    # cell whose first live row is two slots in, a last cell that is dead
    "cells_of_four": ([0, 0, 5, 128, 0, 129, 300, 1, 0], 4),
    # a dead row first, in the middle and last of one cell; rows of two and
    # of three chunks (under a window of 120: each straddles two)
    "dead_first_middle_last": ([0, 129, 0, 260, 0], None),
}

# (KV heads) x (query heads a KV head) of the one-query call: (H, Hkv, D,
# page size, table width). "4x1" is the short rows' (through
# ``ragged_decode_attention``), "1x2" one KV head; the others are the
# layouts whose chunks are worked for all KV heads together
# (``one_query_schedule``): MiMo's full and sliding forms' and K-EXAONE's
# heads at small widths, Qwen3's at a head narrower than a lane tile, Olmo's
# one query head a KV head over more than a tile of 8, on pages of a whole
# chunk (a trace of the interpreted kernel costs by its page copies). A
# Mixtral shard's 2 x 4 is ``test_gqa_grouping``'s.
_DECODE_LAYOUTS = {
    "4x1": (4, 4, 32, 8, 3), "1x2": (2, 1, 32, 64, 5),
    "4x16": (64, 4, 24, 128, 3), "8x8": (64, 8, 24, 128, 3),
    "8x4": (32, 8, 16, 128, 3), "10x1": (10, 10, 32, 128, 3),
}


@pytest.mark.parametrize("sliding,rows,extra,layout", [
    (None, "short", "", "4x1"), (3, "short", "", "4x1"), (8, "short", "", "4x1"),
    (None, "dead_and_long_in_one_cell", "", "1x2"),
    (8, "dead_and_long_in_one_cell", "", "1x2"),
    (None, "cells_of_four", "", "1x2"),
    (None, "short", "sink+narrow", "4x1"),
    (8, "dead_and_long_in_one_cell", "sink+narrow", "1x2"),
    (None, "cells_of_four", "sink", "1x2"),
    (None, "dead_first_middle_last", "narrow", "4x16"),
    (120, "dead_first_middle_last", "sink+narrow", "8x8"),
    (None, "dead_first_middle_last", "", "8x4"),
    (None, "dead_first_middle_last", "", "10x1"),
])
def test_decode_shape_matches_reference_and_zeroes_inactive(sliding, rows, extra, layout):
    """q_tile=1, one query per row — the compiled decode window's
    per-step shape. Rows with length 0 return exact zeros. With a sliding
    width a row's walk starts at its newest ``sliding`` keys' page, each
    row's its own: the pages before it may hold anything (here: NaN).
    The rows of a cell share one copy pipeline (a row's first chunk is
    fetched behind the row before it): to the bit what a cell a row gives.
    A cell of several rows of several KV heads works a chunk for all its KV
    heads together, the state carried as values; a cell a row (``q_block=1``)
    and a call of one KV head walk head by head through the state's
    references: the same products in the same order of chunks, so the same
    bits. (At ONE query head a KV head the interpreter on the CPU is a
    rounding apart: XLA:CPU multiplies a lone row by its matrix-vector
    emitter, which sums in another order than the product of the 8-row tile
    the head's row lies in. On the chip a row's product does not depend on
    the rows beside it: PERF.md §6, PR 54.)"""
    lengths, q_block = _DECODE_ROWS[rows]
    short = rows == "short"
    H, Hkv, D, ps, pmax = _DECODE_LAYOUTS[layout]
    Dv, sink = extras(extra, H, D)
    spans = [(1, max(ln, 1)) for ln in lengths]
    pool = 128 if ps < 64 else 32  # pages: of half a chunk or more, a row has few
    q, k, v, table, row_of, positions = flat_batch(
        2, spans, H, Hkv, D, pool, ps, pmax, q_tile=1, Dv=Dv
    )
    n = len(lengths)
    lens = jnp.asarray(lengths, jnp.int32)
    want = ragged_paged_attention_ref(
        q, k, v, table, jnp.arange(n, dtype=jnp.int32), lens - 1,
        num_kv_heads=Hkv, window=sliding, **sink,
    )
    if sliding is not None:
        # the pages that lie wholly behind a row's window: one page of NaN
        # that no other entry of the table names
        gone = pool - 1
        for r, ln in enumerate(lengths):
            table = table.at[r, : max(ln - sliding, 0) // ps].set(gone)
        k, v = k.at[gone].set(jnp.nan), v.at[gone].set(jnp.nan)
    if short:
        got = ragged_decode_attention(
            q, k, v, table, lens, num_kv_heads=Hkv, interpret=True,
            sliding=sliding, **sink,
        )
    else:
        got, a_row = (
            ragged_paged_attention(
                q, k, v, table, jnp.arange(n, dtype=jnp.int32), lens - 1,
                num_kv_heads=Hkv, q_tile=1, interpret=True, sliding=sliding,
                q_block=block, **sink,
            )
            for block in (q_block, 1)
        )
        rows_a_cell = q_block or one_query_block(n, H, D, Dv or D)
        together = one_query_schedule(rows_a_cell, Hkv, H) == "together"
        assert together == (Hkv > 1) and one_query_schedule(1, Hkv, H) == "by_head"
        if H == Hkv > 1:
            np.testing.assert_allclose(np.asarray(got), np.asarray(a_row), rtol=0, atol=1e-6)
        else:
            np.testing.assert_array_equal(np.asarray(got), np.asarray(a_row))
    out = np.asarray(got)
    active = np.asarray(lengths) > 0
    np.testing.assert_allclose(
        out[active], np.asarray(want)[active], atol=2e-5
    )
    assert (out[~active] == 0.0).all()


@pytest.mark.parametrize("q_tile", [4, 1])
def test_gqa_grouping(q_tile):
    # 8 query heads over 2 kv heads: groups must read their own kv head.
    # At one query a cell's rows (here a row's consecutive tokens: a span
    # that ran on into a packed tail) follow each other on one pipeline.
    spans = [(2, 23), (3, 7)]
    H, Hkv, D, ps, pmax = 8, 2, 32, 16, 2
    q, k, v, table, row_of, positions = flat_batch(
        3, spans, H, Hkv, D, 16, ps, pmax, q_tile=q_tile
    )
    got = ragged_paged_attention(
        q, k, v, table, row_of, positions, num_kv_heads=Hkv, q_tile=q_tile,
        interpret=True,
    )
    want = ragged_paged_attention_ref(
        q, k, v, table, row_of, positions, num_kv_heads=Hkv
    )
    live = np.asarray(positions) >= 0
    np.testing.assert_allclose(
        np.asarray(got)[live], np.asarray(want)[live], atol=2e-5
    )
    if q_tile == 1:
        a_row = ragged_paged_attention(
            q, k, v, table, row_of, positions, num_kv_heads=Hkv, q_tile=1,
            interpret=True, q_block=1,
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(a_row))


def test_bfloat16_cache():
    spans = [(1, 19), (6, 60), (2, 33)]
    H, Hkv, D, ps, pmax = 4, 4, 64, 32, 2
    q, k, v, table, row_of, positions = flat_batch(
        4, spans, H, Hkv, D, 32, ps, pmax, q_tile=2, dtype=jnp.bfloat16
    )
    got = ragged_paged_attention(
        q, k, v, table, row_of, positions, num_kv_heads=Hkv, q_tile=2,
        interpret=True,
    )
    want = ragged_paged_attention_ref(
        q, k, v, table, row_of, positions, num_kv_heads=Hkv
    )
    live = np.asarray(positions) >= 0
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live],
        np.asarray(want, np.float32)[live],
        atol=2e-2,
    )


# (query_len, kv_len) a row, laid out one span after another in tiles of
# 8, then padding slots to ``_BLOCK_SLOTS`` (3.75 blocks of 128: the last
# block is cut short; one shape for every stream, so a layout compiles
# once); pages of 64, so a chunk of 128 tokens is two page copies.
_BLOCK_SLOTS = 480
_BLOCK_STREAMS = {
    # Spans that start and end inside a block, whatever the block.
    "inside": [(24, 24), (40, 200), (16, 16)],
    # Two rows in one block of 16 or more: it walks its tiles.
    "two_rows": [(8, 30), (8, 90)],
    # 37 = four tiles and five: padding queries inside a live tile, then
    # tiles of nothing but padding to the end of the block and beyond.
    "padded_tail": [(37, 200)],
    # A span of many blocks whose contexts cross chunks at 128, 256, 384.
    "long": [(300, 420)],
    # Of everything: a fresh prompt, a short span, a late chunk.
    "mixed": [(300, 300), (5, 40), (130, 400)],
}
_LAYOUTS = {"1to1": (3, 3), "4to1": (8, 2), "20to1": (20, 1)}


def _block_stream(name, H, Hkv, D=32, seed=7, Dv=None):
    spans = _BLOCK_STREAMS[name]
    return padded(
        flat_batch(seed, spans, H, Hkv, D, 32, 64, 8, q_tile=8, Dv=Dv), _BLOCK_SLOTS, 3
    )


# A sliding layer's width: none, or 20 keys, no whole page (widths of whole
# pages: the tile and the one-query cases above). Behind the plain cases: a
# sink and narrow values in a merged block, in one that walks its tiles, and
# under a sliding width. STATIC facts a case crosses (one compile of the
# block and of the tile kernel each, 9 in all): the layout's ``q_per_kv``
# (1 on three KV heads, blocks of 128; 4 on two, blocks of 32; 20 on one, a
# block is a tile: none a whole sublane tile of 8), ``sliding``, ``sink``,
# ``vw``. What the five streams cross is data (a block of one row merged, a
# block of several rows walked tile by tile, padding inside a live tile,
# chunk boundaries at 128 / 256 / 384 inside a row's context, a row of no
# query in the table).
@pytest.mark.parametrize("layout,stream,sliding,extra", [
    *(
        (layout, stream, sliding, "") for sliding in (None, 20)
        for layout in _LAYOUTS for stream in _BLOCK_STREAMS
    ),
    ("4to1", "mixed", None, "sink+narrow"),
    ("4to1", "long", 20, "sink+narrow"),
    ("1to1", "two_rows", 20, "sink"),
])
def test_block_kernel_matches_reference_and_the_tile_kernel(layout, stream, sliding, extra):
    """A grid cell of a block of tiles (``q_block`` from the head layout:
    128, 32 and 8 slots) against the reference at the tile kernel's
    tolerance, against the tile kernel's own output of the same stream
    (a query walks the same chunks in the same order either way; with a
    sliding width a merged block's walk starts at the union of its
    queries' windows and a tile's at its own, so the chunks differ and
    the two agree as closely as either does with the reference), and
    exact zeros at every padding slot."""
    H, Hkv = _LAYOUTS[layout]
    Dv, sink = extras(extra, H, 32)
    q, k, v, table, row_of, positions = _block_stream(stream, H, Hkv, Dv=Dv)
    qb = query_block(8, H // Hkv)
    assert qb == {"1to1": 128, "4to1": 32, "20to1": 8}[layout]
    got = ragged_paged_attention(
        q, k, v, table, row_of, positions, num_kv_heads=Hkv, q_tile=8,
        interpret=True, q_block=qb, sliding=sliding, **sink,
    )
    by_tile = ragged_paged_attention(
        q, k, v, table, row_of, positions, num_kv_heads=Hkv, q_tile=8,
        interpret=True, q_block=8, sliding=sliding, **sink,
    )
    want = ragged_paged_attention_ref(
        q, k, v, table, row_of, positions, num_kv_heads=Hkv, window=sliding,
        **sink,
    )
    live = np.asarray(positions) >= 0
    got = np.asarray(got)
    assert got.shape == (*q.shape[:2], Dv or 32)
    np.testing.assert_allclose(got[live], np.asarray(want)[live], atol=2e-5)
    np.testing.assert_allclose(
        got, np.asarray(by_tile), atol=2e-6 if sliding is None else 2e-5
    )
    assert (got[~live] == 0.0).all()
    # What the stream was built to meet, by the kernel's own rule.
    merged, walked = merged_slot_counts(row_of, positions, 8, qb)
    assert merged + walked == live.sum()
    if qb == 8:
        assert merged == 0
    elif stream == "two_rows":
        assert walked == live.sum()
    elif stream in ("padded_tail", "long"):
        assert merged == live.sum()
    elif stream == "inside":
        assert walked  # 80 slots of three rows: one block at 128
    else:
        assert merged and walked


@pytest.mark.parametrize("q_block", [None, 4, 16], ids=["rows", "tile", "block16"])
def test_block_bounds_reach_the_block_kernel(q_block):
    """``bounds`` (a block-diffusion model sees its whole block of 4) at
    the block program's call (no block named: tiles walked as rows on one
    pipeline, at two KV heads, which it works together), at a cell of one
    tile, and at sdar's mixed program's block of 16: the kernel reads the
    bounds and nothing else of the positions."""
    spans = [(8, 8), (12, 40), (4, 24)]
    H, Hkv, D, ps, pmax = 8, 2 if q_block is None else 1, 32, 16, 3
    q, k, v, table, row_of, positions = flat_batch(
        8, spans, H, Hkv, D, 32, ps, pmax, q_tile=4
    )
    bounds = jnp.where(positions >= 0, positions | 3, -1)
    got = ragged_paged_attention(
        q, k, v, table, row_of, positions, num_kv_heads=Hkv, q_tile=4,
        interpret=True, bounds=bounds, q_block=q_block,
    )
    want = ragged_paged_attention_ref(
        q, k, v, table, row_of, positions, num_kv_heads=Hkv, bounds=bounds
    )
    causal = ragged_paged_attention_ref(
        q, k, v, table, row_of, positions, num_kv_heads=Hkv
    )
    live = np.asarray(positions) >= 0
    np.testing.assert_allclose(
        np.asarray(got)[live], np.asarray(want)[live], atol=2e-5
    )
    assert np.abs(np.asarray(want) - np.asarray(causal))[live].max() > 1e-3


# ---------------------------------------------------- a latent pool beside
def _golden_case(qpk: int, hkv: int, D: int = 128, ps: int = 16):
    """Seeded inputs of three rows (a span of five tiles deep in its
    context, a short span with padding, a span of two tiles) at ``qpk``
    query heads a KV head."""
    rng = np.random.default_rng(qpk)
    pages, rows, pmax = 40, 3, 12
    k = jnp.asarray(rng.standard_normal((pages, ps, hkv * D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((pages, ps, hkv * D)), jnp.bfloat16)
    table = jnp.asarray(rng.permutation(pages)[: rows * pmax].reshape(rows, pmax), jnp.int32)
    pos = np.concatenate(
        [np.arange(100, 140), np.r_[np.arange(5), -1, -1, -1], np.arange(30, 46)]
    ).astype(np.int32)
    row = np.concatenate([np.zeros(40), np.ones(8), np.full(16, 2)]).astype(np.int32)
    q = jnp.asarray(rng.standard_normal((64, hkv * qpk, D)), jnp.bfloat16)
    return q, k, v, table, jnp.asarray(row), jnp.asarray(pos)


@pytest.mark.parametrize("qpk,hkv,digest", [
    (1, 2, "b8b9bcd7444d11a4"), (8, 1, "b1996551425473f8"), (20, 1, "78e02f78dc374ff9"),
])
def test_kernel_bits_unchanged_beside_the_latent_form(qpk, hkv, digest):
    """The kernel's readings at 1, 8 and 20 query heads a KV head, in
    tiles (walked as rows on one pipeline, and a tile a cell), in merged
    blocks and one query a cell, are to the bit what the
    parent of PR 44 (commit b4124f8) gave for these inputs: the latent
    form (``value_dim``) took nothing from the two-pool form's
    arithmetic."""
    import hashlib

    q, k, v, table, row, pos = _golden_case(qpk, hkv)
    for qt, qb in ((8, None), (8, 8), (8, query_block(8, qpk)), (1, None)):
        out = ragged_paged_attention(
            q, k, v, table, row, pos, num_kv_heads=hkv, q_tile=qt, interpret=True,
            q_block=qb,
        )
        got = hashlib.sha256(np.asarray(out.astype(jnp.float32)).tobytes()).hexdigest()
        assert got[:16] == digest, (qt, qb)


@pytest.mark.parametrize("q_tile", [8, 1])
def test_latent_pool_at_published_widths(q_tile):
    """One pool of 640 lanes (576 values and padding), the values its
    first 512, 128 query heads over the one latent head: the kernel
    against the reference, padding slots exact zeros."""
    rng = np.random.default_rng(5)
    pages, ps, lanes, vd, H = 24, 16, 640, 512, 128
    row_vals = rng.standard_normal((pages, ps, 576))
    pool = jnp.asarray(np.pad(row_vals, ((0, 0), (0, 0), (0, lanes - 576))), jnp.bfloat16)
    table = jnp.asarray(rng.permutation(pages)[:20].reshape(2, 10), jnp.int32)
    pos = np.r_[np.arange(120, 136), np.arange(3), [-1] * 5].astype(np.int32)
    row = np.r_[np.zeros(16), np.ones(8)].astype(np.int32)
    q = np.pad(rng.standard_normal((24, H, 576)) * 0.2, ((0, 0), (0, 0), (0, lanes - 576)))
    q = jnp.asarray(q, jnp.bfloat16)
    args = (q, pool, None, table, jnp.asarray(row), jnp.asarray(pos))
    kw = dict(num_kv_heads=1, sm_scale=192 ** -0.5, value_dim=vd)
    want = ragged_paged_attention_ref(*args, **kw)
    got = ragged_paged_attention(*args, q_tile=q_tile, interpret=True, **kw)
    assert got.shape == (24, H, vd)
    assert float(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)).max()) < 2e-2
    assert float(jnp.abs(got[19:].astype(jnp.float32)).max()) == 0.0
    if q_tile == 1:
        # cells of 8 rows at these widths (one_query_block), the last one
        # with five dead rows behind its three: to the bit a cell a row
        a_row = ragged_paged_attention(*args, q_tile=1, interpret=True, q_block=1, **kw)
        np.testing.assert_array_equal(
            np.asarray(got.astype(jnp.float32)), np.asarray(a_row.astype(jnp.float32))
        )

