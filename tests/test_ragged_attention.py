"""Ragged paged attention: one kernel + one dispatch for mixed
prefill+decode (docs/engine_perf.md "One ragged dispatch").

Three layers of proof, all on the CPU mesh:

1. **Kernel parity** — the Pallas kernel (interpret mode) against the
   pure-JAX reference across ragged (query_len, kv_len) shapes:
   page-boundary straddling spans, rows-1, inactive rows, GQA
   grouping, bf16 pools, the q_tile-aligned flat layout, and the tp>1
   shard_map dispatches ``models/llama`` uses.
2. **Engine identity** — mixed ragged batches (chunked prefill + decode
   + staggered arrivals) emit greedy/seeded/penalized streams
   token-identical to a two-program oracle that replays the seed
   engine's schedule semantics (bucketed whole-prompt prefill, then
   strict one-token decode steps) straight through the model forward.
3. **Scheduling** — a late-arriving prompt joins the in-flight batch
   (its chunk rides the very next compute dispatch, one mixed program
   with the decode rows) instead of waiting behind a separate prefill
   program, and the steady-state compiled-variant count is a small
   constant (the collapsed lattice's recompile guard).
"""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
from dynamo_exp_tpu.models import TINY
from dynamo_exp_tpu.ops.attention import paged_attention
from dynamo_exp_tpu.ops.ragged_attention import (
    block_plan,
    merged_slot_counts,
    one_query_block,
    one_query_chain,
    one_query_fetches,
    one_query_schedule,
    query_block,
    ragged_decode_attention,
    ragged_paged_attention,
    ragged_paged_attention_ref,
    ragged_supported,
)
from dynamo_exp_tpu.parallel import single_device_mesh
from dynamo_exp_tpu.protocols.common import BackendInput, SamplingOptions

PS = 8


# ------------------------------------------------------------ kernel parity
def _flat_batch(rng, spans, H, Hkv, D, P, ps, pmax, q_tile, dtype=jnp.float32,
                Dv=None):
    """Build a q_tile-aligned flat query stream from per-row
    ``(q_len, kv_len)`` spans plus a scrambled page pool. ``Dv``: the
    value pool's heads are that wide, not ``D``."""
    # drawn on the host: a jitted draw compiles once a shape, ~0.3 s each
    draw = np.random.RandomState(rng)

    def normal(*shape):
        return jnp.asarray(draw.standard_normal(shape), dtype)

    k = normal(P, ps, Hkv * D)
    v = normal(P, ps, Hkv * (Dv or D))
    perm = np.random.RandomState(rng).permutation(P)
    table = np.zeros((len(spans), pmax), np.int32)
    used = 0
    row_of, positions = [], []
    for r, (q_len, kv_len) in enumerate(spans):
        n = max(1, -(-max(kv_len, 1) // ps))
        table[r, :n] = perm[used : used + n]
        used += n
        poss = list(range(kv_len - q_len, kv_len))
        pad = (-q_len) % q_tile
        row_of += [r] * (q_len + pad)
        positions += poss + [-1] * pad
    N = len(row_of)
    q = normal(N, H, D)
    return (
        q,
        k,
        v,
        jnp.asarray(table),
        jnp.asarray(row_of, jnp.int32),
        jnp.asarray(positions, jnp.int32),
    )


def _narrow(D: int) -> int:
    """A value head narrower than the keys' ``D``: about two thirds of it
    (192 -> 128 at the published widths), whole groups of 8."""
    return D * 2 // 3 // 8 * 8


def _extras(extra: str, H: int, D: int, seed: int = 11):
    """What a case named ``extra`` adds to a kernel-against-reference test
    (``""``, ``"sink"``, ``"narrow"`` or ``"sink+narrow"``): (the value
    head's width for ``_flat_batch``, the keyword both implementations
    take). The sinks are of the scores' size, so that leaving them out, or
    giving one head another's, moves every output."""
    sink = {}
    if "sink" in extra:
        sink["sink"] = 2.0 * jax.random.normal(
            jax.random.PRNGKey(seed), (H,), jnp.float32
        )
    return (_narrow(D) if "narrow" in extra else None), sink


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
@pytest.mark.parametrize("spans,sliding,extra", [
    *((spans, sliding, "") for sliding in (None, 5, 16) for spans in _TILE_SPANS),
    (_TILE_SPANS[0], None, "sink"),
    (_TILE_SPANS[1], 5, "sink+narrow"),
    (_TILE_SPANS[3], 16, "narrow"),
])
def test_kernel_matches_reference_ragged(spans, sliding, extra):
    H, Hkv, D, ps, pmax = 8, 4, 64, 16, 8
    Dv, sink = _extras(extra, H, D)
    q, k, v, table, row_of, positions = _flat_batch(
        0, spans, H, Hkv, D, 64, ps, pmax, q_tile=4, Dv=Dv
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


def test_reference_matches_paged_attention_per_token():
    """The reference IS the battle-tested paged_attention per token
    (bit-equal reductions keep mixed dispatches argmax-stable against
    the step-by-step schedule even at exact bf16 ties)."""
    spans = [(4, 12), (1, 30)]
    H, Hkv, D, ps, pmax = 4, 2, 32, 8, 8
    q, k, v, table, row_of, positions = _flat_batch(
        1, spans, H, Hkv, D, 32, ps, pmax, q_tile=1
    )
    ref = ragged_paged_attention_ref(
        q, k, v, table, row_of, positions, num_kv_heads=Hkv
    )
    direct = paged_attention(
        q[:, None], k, v, table[row_of], positions[:, None]
    )[:, 0]
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(direct))


# What a window's step, or a mixed stream's packed tail, may look like to a
# grid cell of the one-query call: (lengths a row, rows a cell; None: what
# the call's shapes give, here all the rows in one cell).
_DECODE_ROWS = {
    "short": ([1, 17, 0, 5], None),
    # dead rows first, in the middle and last; rows of one chunk (of 128
    # tokens: 8 pages of 16), of two and of three side by side
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
    "4x1": (4, 4, 32, 8, 8), "1x2": (2, 1, 32, 16, 20),
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
    Dv, sink = _extras(extra, H, D)
    spans = [(1, max(ln, 1)) for ln in lengths]
    pool = 128 if ps < 128 else 16  # pages: of a whole chunk, a row has few
    q, k, v, table, row_of, positions = _flat_batch(
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
        together = one_query_schedule(rows_a_cell, Hkv) == "together"
        assert together == (Hkv > 1) and one_query_schedule(1, Hkv) == "by_head"
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
    H, Hkv, D, ps, pmax = 8, 2, 32, 16, 8
    q, k, v, table, row_of, positions = _flat_batch(
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
    H, Hkv, D, ps, pmax = 4, 4, 64, 32, 16
    q, k, v, table, row_of, positions = _flat_batch(
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


def test_ragged_supported_layout_gate():
    assert ragged_supported(16, 4, 64, jnp.bfloat16)  # 256-lane, ps%16
    assert not ragged_supported(16, 1, 64, jnp.bfloat16)  # 64 lanes
    assert not ragged_supported(12, 4, 64, jnp.float32)  # ps % 8 != 0


def test_tp_shard_map_decode_dispatch():
    """The tp>1 path in models/llama._pallas_decode: heads sharded over
    the mesh, page pool kv-head-sharded, full tables replicated."""
    from dynamo_exp_tpu.models.llama import _pallas_decode
    from dynamo_exp_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(tp=4)
    lengths = [11, 0, 37, 25]
    spans = [(1, max(ln, 1)) for ln in lengths]
    H, Hkv, D, ps, pmax = 8, 4, 64, 16, 8
    q, k, v, table, row_of, positions = _flat_batch(
        5, spans, H, Hkv, D, 32, ps, pmax, q_tile=1
    )
    lens = jnp.asarray(lengths, jnp.int32)
    got = _pallas_decode(q, k, v, table, lens, Hkv, mesh, interpret=True)
    want = ragged_paged_attention_ref(
        q, k, v, table, jnp.arange(4, dtype=jnp.int32), lens - 1,
        num_kv_heads=Hkv,
    )
    active = np.asarray(lengths) > 0
    np.testing.assert_allclose(
        np.asarray(got)[active], np.asarray(want)[active], atol=2e-5
    )


def test_tp_shard_map_ragged_dispatch():
    """The tp>1 path for mixed batches (models/llama._pallas_ragged)."""
    from dynamo_exp_tpu.models.llama import _pallas_ragged
    from dynamo_exp_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(tp=4)
    spans = [(3, 11), (1, 26)]
    H, Hkv, D, ps, pmax = 8, 4, 64, 16, 8
    q, k, v, table, row_of, positions = _flat_batch(
        6, spans, H, Hkv, D, 32, ps, pmax, q_tile=4
    )
    got = _pallas_ragged(
        q, k, v, table, row_of, positions, Hkv, 4, mesh, interpret=True
    )
    want = ragged_paged_attention_ref(
        q, k, v, table, row_of, positions, num_kv_heads=Hkv
    )
    live = np.asarray(positions) >= 0
    np.testing.assert_allclose(
        np.asarray(got)[live], np.asarray(want)[live], atol=2e-5
    )


# ------------------------------------------------------------ query blocks
@pytest.mark.parametrize(
    "heads, kv_heads, q_tile, want",
    [
        (30, 30, 8, 128),  # olmo-hybrid: one query head a KV head
        (32, 8, 8, 32),  # qwen3-4b, lfm2-8b-a1b
        (8, 2, 8, 32),  # a mixtral shard under tp 4
        (32, 4, 8, 16),  # sdar's mixed program
        (20, 1, 8, 8),  # jamba2-3b: a tile fills the rows already
        (2, 2, 12, 132),  # a whole number of tiles, rounded up
        (64, 8, 8, 16),  # mimo-v2-flash's sliding layers
        (64, 4, 8, 8),  # and its full ones, in the same model
    ],
)
def test_query_block_follows_from_the_head_layout(heads, kv_heads, q_tile, want):
    qb = query_block(q_tile, heads // kv_heads)
    assert qb == want and qb % q_tile == 0


# (query_len, kv_len) a row, laid out one span after another in tiles of
# 8, then padding slots to ``_BLOCK_SLOTS`` (3.75 blocks of 128: the last
# block is cut short; one shape for every stream, so a layout compiles
# once); pages of 8, so a chunk is 128 tokens.
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
    q, k, v, table, row_of, positions = _flat_batch(
        seed, spans, H, Hkv, D, 256, 8, 64, q_tile=8, Dv=Dv
    )
    pad = _BLOCK_SLOTS - q.shape[0]
    # Padding slots carry queries too (the embedding of token 0): ones,
    # so that a leak would show.
    q = jnp.concatenate([q, jnp.ones((pad, H, D), q.dtype)])
    row_of = jnp.pad(row_of, (0, pad))
    positions = jnp.pad(positions, (0, pad), constant_values=-1)
    table = jnp.pad(table, ((0, 3 - table.shape[0]), (0, 0)))
    return q, k, v, table, row_of, positions


# A sliding layer's width: none, or 20 keys, no whole number of pages of 8
# (widths of whole pages: the tile and the one-query cases above). Behind
# the plain cases: a sink and narrow values in a merged block, in one that
# walks its tiles, and under a sliding width.
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
    Dv, sink = _extras(extra, H, 32)
    q, k, v, table, row_of, positions = _block_stream(stream, H, Hkv, Dv=Dv)
    qb = query_block(8, H // Hkv)
    assert qb == {"1to1": 128, "4to1": 32, "20to1": 8}[layout]
    got = ragged_paged_attention(
        q, k, v, table, row_of, positions, num_kv_heads=Hkv, q_tile=8,
        interpret=True, q_block=qb, sliding=sliding, **sink,
    )
    by_tile = ragged_paged_attention(
        q, k, v, table, row_of, positions, num_kv_heads=Hkv, q_tile=8,
        interpret=True, sliding=sliding, **sink,
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


@pytest.mark.parametrize("q_block", [None, 16], ids=["tile", "block16"])
def test_block_bounds_reach_the_block_kernel(q_block):
    """``bounds`` (a block-diffusion model sees its whole block of 4) at
    a cell of one tile, the block program's call, and at sdar's mixed
    program's block of 16: the kernel reads the bounds and nothing else
    of the positions."""
    spans = [(8, 8), (12, 40), (4, 24)]
    H, Hkv, D, ps, pmax = 8, 1, 32, 8, 8
    q, k, v, table, row_of, positions = _flat_batch(
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


def test_tp_shard_map_block_dispatch():
    """The tp>1 path at a query block: a shard's heads a KV head are the
    model's, so every shard takes the block the whole model would."""
    from dynamo_exp_tpu.models.llama import _pallas_ragged
    from dynamo_exp_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(tp=4)
    spans = [(70, 90), (3, 11), (40, 40)]
    H, Hkv, D, ps, pmax = 8, 4, 64, 16, 8
    q, k, v, table, row_of, positions = _flat_batch(
        9, spans, H, Hkv, D, 32, ps, pmax, q_tile=8
    )
    qb = query_block(8, H // Hkv)
    assert qb == 64 == query_block(8, (H // 4) // (Hkv // 4))
    got = _pallas_ragged(
        q, k, v, table, row_of, positions, Hkv, 8, mesh, True, qb
    )
    want = ragged_paged_attention_ref(
        q, k, v, table, row_of, positions, num_kv_heads=Hkv
    )
    live = np.asarray(positions) >= 0
    np.testing.assert_allclose(
        np.asarray(got)[live], np.asarray(want)[live], atol=2e-5
    )
    assert (np.asarray(got)[~live] == 0.0).all()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_host_count_and_device_rule_agree_on_a_drawn_stream(seed):
    """The engine counts merged slots on the host with ``numpy``; the
    kernel plans its blocks inside the program with ``jax.numpy``: one
    function, and here the same answer block for block on a drawn
    stream of spans, padding tiles between them and rows in any order."""
    rng = np.random.default_rng(seed)
    row_of, positions = [], []
    for r in rng.permutation(12):
        n = int(rng.choice([1, 5, 8, 40, 128, 300]))
        first = int(rng.integers(0, 500))
        width = -(-n // 8) * 8 + 8 * int(rng.integers(0, 3))
        row_of += [int(r)] * width
        positions += list(range(first, first + n)) + [-1] * (width - n)
    row_of = np.asarray(row_of, np.int32)
    positions = np.asarray(positions, np.int32)
    kinds = set()
    for qb in (16, 32, 128):
        on_host = block_plan(row_of, positions, 8, qb, xp=np)
        on_device = jax.jit(
            lambda r, p: block_plan(r, p, 8, qb)  # noqa: B023
        )(jnp.asarray(row_of), jnp.asarray(positions))
        for a, b in zip(on_host, on_device):
            np.testing.assert_array_equal(a, np.asarray(b))
        merged, walked = merged_slot_counts(row_of, positions, 8, qb)
        assert merged + walked == (positions >= 0).sum()
        # A merged block's tiles are all its row's or padding.
        tile_row, tile_kvlen, block_row, block_kvlen = on_host
        for b in np.flatnonzero(block_kvlen >= 0):
            tiles = slice(b * qb // 8, (b + 1) * qb // 8)
            rows = tile_row[tiles][tile_kvlen[tiles] > 0]
            assert (rows == block_row[b]).all()
            assert block_kvlen[b] == tile_kvlen[tiles].max()
        kinds |= set(np.sign(block_kvlen))
    assert kinds >= {-1, 1}  # blocks of one row and of several were drawn


@pytest.mark.parametrize("seed,kv_heads", [(0, 2), (1, 2), (2, 2), (3, 1)])
def test_host_count_and_kernel_chain_agree_on_a_drawn_window_and_tail(seed, kv_heads):
    """``dynamo_attn_one_query_rows_total``: the engine counts on the host
    with ``numpy`` which rows of a one-query call have their first chunk
    fetched behind another row; the kernel walks ``one_query_chain``'s
    ``(lead, after)``, planned inside the program with ``jax.numpy``: one
    function, the same answer slot for slot on a drawn window (rows that
    park after some of its steps, a bucket wider than its rows) and a drawn
    packed tail, and a walk of the chain as the kernel makes it (a cell
    starts its ``lead``, every row starts its ``after``) visits each live
    row once: started by the cell it is exposed, by a row behind.
    ``dynamo_attn_one_query_heads_total``: the same rows under the schedule
    their call's chunks are worked in, which the engine asks of the one
    rule the kernel's caller asks (``one_query_schedule``): TINY's 4 query
    heads on ``kv_heads`` KV heads, ``together`` at 2 wherever a cell has
    more than one row, ``by_head`` at 1."""
    from types import SimpleNamespace

    from dynamo_exp_tpu.models.llama import one_query_rows
    from dynamo_exp_tpu.telemetry import get_telemetry

    model = dataclasses.replace(TINY, num_kv_heads=kv_heads)
    engine = SimpleNamespace(_attn_impl="pallas", cfg=SimpleNamespace(model=model, tp=1))

    def counted():
        return {
            label: get_telemetry().attn_one_query_heads.labels(label)._value.get()
            for label in ("together", "by_head")
        }

    rng = np.random.default_rng(seed)
    nb, steps = int(rng.choice([8, 16, 32])), 8
    window = np.full((steps, nb), -1, np.int32)
    for r in rng.permutation(nb)[: int(rng.integers(1, nb + 1))]:
        window[: int(rng.integers(1, steps + 1)), r] = int(rng.integers(0, 900))
    tail = np.where(rng.random(24) < 0.6, rng.integers(0, 900, 24), -1).astype(np.int32)
    for positions in (window, tail):
        before = counted()
        TPUEngine._count_one_query_rows(engine, positions)
        n = positions.shape[-1]
        schedule = one_query_schedule(one_query_rows(model, n), kv_heads)
        assert schedule == ("together" if kv_heads > 1 and n > 1 else "by_head")
        grew = {label: after - before[label] for label, after in counted().items()}
        assert grew.pop(schedule) == (positions >= 0).sum() and not any(grew.values())
        for rows in (one_query_block(positions.shape[-1], 4, 32, 32), 5, 1):
            behind = exposed = 0
            for stream in positions.reshape(-1, positions.shape[-1]):
                lead, after = one_query_chain(stream, rows, xp=np)
                if stream is positions.reshape(-1, positions.shape[-1])[0]:
                    on_device = one_query_chain(jnp.asarray(stream), rows)
                    np.testing.assert_array_equal(lead, np.asarray(on_device[0]))
                    np.testing.assert_array_equal(after, np.asarray(on_device[1]))
                padded = np.pad(stream, (0, (-len(stream)) % rows), constant_values=-1)
                seen = []
                for cell, at in enumerate(lead):
                    exposed += at < rows
                    while at < rows:
                        seen.append(cell * rows + at)
                        at = after[cell * rows + at]
                        behind += at < rows
                assert seen == list(np.flatnonzero(padded >= 0))
            assert (behind, exposed) == one_query_fetches(positions, rows)
            if rows == 1:
                assert behind == 0
    assert one_query_block(64, 32, 128, 128) == 64  # a bucket of Qwen3's: one cell
    assert one_query_block(256, 32, 128, 128) == 128
    assert one_query_block(32, 128, 640, 512) == 8  # a latent pool's absorbed heads
    assert one_query_block(1, 32, 128, 128) == 1


def test_engine_counts_every_tiled_slot_under_one_pass_label():
    """``dynamo_attn_query_slots_total``: a kernel engine's mixed
    builder counts the live slots it laid into the stream's tiled part,
    each under ``merged`` or ``tile``; an engine on the reference counts
    nothing."""
    from dynamo_exp_tpu.telemetry import get_telemetry

    def read(name="dynamo_attn_query_slots_total"):
        out = {}
        for line in get_telemetry().render().decode().splitlines():
            if line.startswith(name + "{"):
                out[line.split('"')[1]] = float(line.rsplit(" ", 1)[1])
        return out

    rows_total = "dynamo_attn_one_query_rows_total"
    heads_total = "dynamo_attn_one_query_heads_total"

    for interpret in (True, False):
        eng = _mixed_engine(
            max_decode_slots=4, pallas_interpret=interpret, prefill_chunk=16
        )
        seen = []
        count = eng._count_attn_passes

        def spy(nb, row_of, positions, count=count, seen=seen, eng=eng):
            tiled = nb - eng.cfg.ragged_packed_slots(nb)
            seen.append(int((positions[:tiled] >= 0).sum()))
            count(nb, row_of, positions)

        eng._count_attn_passes = spy
        offered = []
        count_rows = eng._count_one_query_rows

        def spy_rows(positions, count_rows=count_rows, offered=offered):
            offered.append(int((np.asarray(positions) >= 0).sum()))
            count_rows(positions)

        eng._count_one_query_rows = spy_rows
        before, rows_before, heads_before = read(), read(rows_total), read(heads_total)
        eng.start()
        try:
            prompt = list(np.random.RandomState(5).randint(3, 200, size=70))
            toks, _ = asyncio.run(_collect(eng, prompt, 4))
            assert len(toks) == 4
        finally:
            eng.stop()
        grew = {k: v - before.get(k, 0.0) for k, v in read().items()}
        rows = {
            k: v - rows_before.get(k, 0.0) for k, v in read(rows_total).items()
        }
        heads = {
            k: v - heads_before.get(k, 0.0) for k, v in read(heads_total).items()
        }
        assert seen and sum(seen) > 0
        if interpret:
            # ``dynamo_attn_one_query_heads_total``: the same rows, by the
            # schedule of their call (TINY's 2 KV heads: together wherever
            # a cell has more than one row)
            assert sum(heads.values()) == sum(offered) and heads["together"] > 0
            # TINY has 4 query heads on 2 KV heads: blocks of 64.
            assert sum(grew.values()) == sum(seen)
            assert set(grew) == {"merged", "tile"}
            # ``dynamo_attn_one_query_rows_total``: every live row the
            # one-query call was offered (a window's steps; a packed tail,
            # where the prompt's chunks ran on into it a token a slot),
            # each under ``behind`` or ``exposed``
            assert sum(rows.values()) == sum(offered) > 0
            assert rows["exposed"] > 0 and rows["behind"] > 0
            # a window of two steps over a bucket of 4 (one cell): three
            # live rows, then two
            count_rows(np.array([[5, 9, -1, 7], [6, -1, -1, 8]]))
            after = read(rows_total)
            assert after["behind"] - rows_before.get("behind", 0.0) == rows["behind"] + 3
            assert after["exposed"] - rows_before.get("exposed", 0.0) == rows["exposed"] + 2
        else:
            assert not any(grew.values()) and not any(rows.values())
            assert not any(heads.values())


# --------------------------------------------------- two-program oracle
def _oracle_stream(
    engine: TPUEngine,
    prompt: list[int],
    n_steps: int,
    seed: int,
    sampling: "SamplingOptions",
) -> list[int]:
    """Replay of the seed two-program engine's semantics for ONE
    request, straight through the model forward: bucketed whole-prompt
    prefill samples the first token at the prompt's last absolute
    position WITHOUT penalties (the prefill rule), then strict
    one-token decode steps sample through the running penalty counts
    (the decode-window rule), every draw keyed by (seed, position).
    Counter-based sampling makes this independent of batch shape and
    window layout — exactly what the ragged engine must reproduce."""
    from dynamo_exp_tpu.models import forward
    from dynamo_exp_tpu.ops.sampling import (
        apply_penalties,
        sample_tokens_seeded,
    )

    # Jitted: an eager forward compiles its layer scan anew every call.
    forward = jax.jit(forward, static_argnums=(1,))

    cfg = engine.cfg.model
    params = engine.params
    from dynamo_exp_tpu.models.llama import init_kv_cache

    pmax = 32
    k, v = init_kv_cache(
        cfg, num_pages=pmax + 1, page_size=PS, dtype=engine.cfg.kv_dtype_jnp
    )
    table = jnp.arange(pmax, dtype=jnp.int32)[None, :] + 1
    so = sampling
    seeds = jnp.asarray([seed & 0x7FFFFFFF], jnp.int32)
    temp = jnp.asarray(
        [so.temperature if so.temperature is not None else 0.0], jnp.float32
    )
    top_k = jnp.asarray([so.top_k or 0], jnp.int32)
    top_p = jnp.asarray(
        [so.top_p if so.top_p is not None else 1.0], jnp.float32
    )
    freq = jnp.asarray([so.frequency_penalty or 0.0], jnp.float32)
    pres = jnp.asarray([so.presence_penalty or 0.0], jnp.float32)
    rep = jnp.asarray([so.repetition_penalty or 1.0], jnp.float32)
    counts = jnp.zeros((1, cfg.vocab_size), jnp.int32)

    logits, k, v = forward(
        params, cfg,
        jnp.asarray([prompt], jnp.int32),
        jnp.arange(len(prompt), dtype=jnp.int32)[None, :],
        table, k, v,
    )
    pos = len(prompt) - 1
    tok = int(
        sample_tokens_seeded(
            logits[:, pos], seeds, jnp.asarray([pos], jnp.int32),
            temp, top_k, top_p,
        )[0]
    )
    out = [tok]
    counts = counts.at[0, tok].add(1)
    while len(out) < n_steps:
        pos = len(prompt) + len(out) - 1
        logits, k, v = forward(
            params, cfg,
            jnp.asarray([[tok]], jnp.int32),
            jnp.asarray([[pos]], jnp.int32),
            table, k, v,
        )
        shaped = apply_penalties(logits[:, 0], counts, freq, pres, rep)
        tok = int(
            sample_tokens_seeded(
                shaped, seeds, jnp.asarray([pos], jnp.int32),
                temp, top_k, top_p,
            )[0]
        )
        out.append(tok)
        counts = counts.at[0, tok].add(1)
    return out


def _mixed_engine(**kw) -> TPUEngine:
    cfg = EngineConfig(
        model=TINY,
        max_decode_slots=kw.pop("max_decode_slots", 4),
        page_size=PS,
        num_pages=kw.pop("num_pages", 64),
        max_model_len=kw.pop("max_model_len", 128),
        eos_token_ids=[],
        **kw,
    )
    return TPUEngine(cfg, mesh=single_device_mesh(), seed=0)


async def _collect(engine, prompt, max_tokens, seed=None, **sampling):
    b = BackendInput(token_ids=list(prompt))
    b.stop_conditions.max_tokens = max_tokens
    b.stop_conditions.ignore_eos = True
    if sampling or seed is not None:
        b.sampling_options = SamplingOptions(seed=seed, **sampling)
    stream = await engine.generate(b.to_dict())
    toks = []
    async for item in stream:
        toks.extend(item.get("token_ids", []))
    return toks, b.sampling_options


def test_mixed_batch_identity_vs_two_program_oracle():
    """Greedy + seeded + penalized requests admitted in a staggered
    burst — so prefill chunks, decode steps, and both sampler
    partitions share ragged dispatches — each emit the exact stream
    the two-program oracle derives for them alone."""
    eng = _mixed_engine()
    eng.start()
    try:
        rs = np.random.RandomState(0)
        reqs = [
            # (sampling kwargs, seed)
            ({}, None),  # greedy
            ({"temperature": 0.8, "top_k": 20}, 7),  # seeded
            (
                {
                    "temperature": 0.7,
                    "frequency_penalty": 0.4,
                    "presence_penalty": 0.2,
                    "repetition_penalty": 1.2,
                },
                11,
            ),  # penalized
            ({}, None),  # second greedy row keeps the partition busy
        ]
        prompts = [
            list(rs.randint(3, 200, size=6 + 3 * i))
            for i in range(len(reqs))
        ]

        async def burst():
            jobs = []
            for p, (sampling, seed) in zip(prompts, reqs):
                jobs.append(
                    asyncio.create_task(
                        _collect(eng, p, 12, seed=seed, **sampling)
                    )
                )
                # Stagger: later requests arrive while earlier ones are
                # mid-prefill/decode, forcing mixed dispatches.
                await asyncio.sleep(0.05)
            return await asyncio.gather(*jobs)

        results = asyncio.run(burst())
        for p, (toks, so) in zip(prompts, results):
            want = _oracle_stream(eng, p, 12, so.seed or 0, so)
            assert toks == want, (p, toks, want)
        # The burst really exercised mixed (non-windowed) dispatches.
        assert any(not key[2] for key in eng._ragged_fns)
    finally:
        eng.stop()


def test_seeded_identity_concurrent_vs_alone():
    """The same seeded request produces the same stream alone and in a
    concurrent mixed batch (counter-based draws never see layout)."""
    eng = _mixed_engine()
    eng.start()
    try:
        prompt = list(np.random.RandomState(1).randint(3, 200, size=9))

        async def alone():
            return (await _collect(eng, prompt, 10, seed=5, temperature=0.9))[0]

        async def crowded():
            noise = [
                _collect(
                    eng,
                    list(np.random.RandomState(s).randint(3, 200, size=7)),
                    10,
                )
                for s in range(3)
            ]
            me = _collect(eng, prompt, 10, seed=5, temperature=0.9)
            results = await asyncio.gather(me, *noise)
            return results[0][0]

        assert asyncio.run(alone()) == asyncio.run(crowded())
    finally:
        eng.stop()


# ------------------------------------------------------------- late join
def test_late_prompt_joins_in_flight_batch():
    """A prompt admitted mid-decode reaches its first token without
    waiting for the established rows to finish: its chunk rides the
    next compute dispatch together with the decode rows (ONE mixed
    ragged program — checked in the flight ring), and its short stream
    completes while the long rows are still running."""
    eng = _mixed_engine(max_decode_slots=4)
    eng.start()
    try:

        async def run():
            rs = np.random.RandomState(5)
            order: list[str] = []

            async def tagged(tag, coro):
                out = await coro
                order.append(tag)
                return out

            longs = [
                asyncio.create_task(
                    tagged(
                        "long",
                        _collect(eng, list(rs.randint(3, 200, size=9)), 64),
                    )
                )
                for _ in range(2)
            ]
            # Wait until the pair is demonstrably decoding (windows
            # stepping), then inject.
            steps0 = eng.steps
            while eng.steps < steps0 + 2 * eng.cfg.decode_window:
                await asyncio.sleep(0.01)
            late = asyncio.create_task(
                tagged("late", _collect(eng, [7, 8, 9, 10], 6))
            )
            await asyncio.gather(late, *longs)
            return order

        order = asyncio.run(run())
        # The 6-token latecomer must not be serialized behind the
        # 64-token pair.
        assert order[0] == "late", order
        # Flight ring: after the latecomer's admit, the very next
        # compute dispatch is a MIXED ragged batch (prefill span +
        # decode rows in one program) — it joined the in-flight batch,
        # it did not wait for a window boundary or a separate prefill
        # program.
        events = eng.flight.snapshot()
        admit_at = max(
            i
            for i, e in enumerate(events)
            if e["kind"] == "admit" and e["prompt"] == 4
        )
        next_disp = next(
            e
            for e in events[admit_at + 1 :]
            if e["kind"] == "dispatch" and e.get("dispatch") == "ragged"
        )
        assert next_disp["windowed"] is False and next_disp["rows"] >= 2
    finally:
        eng.stop()


# --------------------------------------------------------- recompile guard
@pytest.mark.parametrize("interpret", [False, True], ids=["gather", "kernel"])
def test_steady_state_variant_count_small_constant(interpret):
    """The collapsed lattice in numbers: a full mixed workload
    envelope (both sampler partitions, all occupancies, staggered
    arrivals) compiles a small constant number of ragged variants, and
    steady-state traffic never grows the cache again.

    The expected set is enumerated from ``aot/lattice.py`` — the SAME
    key function the engine's ``_ragged_fn`` dispatches through — so
    the offline lattice is regression-pinned against the live engine:
    any compiled key the manifest failed to enumerate fails here before
    it can become a prewarm blind spot (docs/aot.md).

    Both forms under ``attention_impl="auto"``: the CPU's resolved
    gather, and the kernel (interpreted) as a TPU resolves it. Since
    PR 30 the form is the engine's alone — no window of a kernel engine
    is keyed by a page bound, whatever its rows or contexts (these
    contexts are all under 1024 tokens, where windows took the gather
    before)."""
    from dynamo_exp_tpu.aot import manifest_for_engine

    eng = _mixed_engine(max_decode_slots=4, pallas_interpret=interpret)
    assert eng._attn_impl == ("pallas" if interpret else "xla")
    eng.start()
    try:
        rs = np.random.RandomState(3)

        def prompt():
            return list(rs.randint(3, 200, size=10))

        async def mix(n_greedy, n_sampled):
            jobs = [_collect(eng, prompt(), 8) for _ in range(n_greedy)]
            jobs += [
                _collect(eng, prompt(), 8, seed=s, temperature=0.8)
                for s in range(n_sampled)
            ]
            return await asyncio.gather(*jobs)

        # Warmup the envelope until the cache stabilizes (whether N
        # concurrent submissions share one admit pass is an OS race).
        for n in (1, 2, 4):
            asyncio.run(mix(n, 0))
            asyncio.run(mix(0, n))
        asyncio.run(mix(2, 2))
        for _ in range(5):
            before = len(eng._ragged_fns)
            asyncio.run(mix(4, 0))
            asyncio.run(mix(0, 4))
            asyncio.run(mix(2, 2))
            if len(eng._ragged_fns) == before:
                break
        variants = len(eng._ragged_fns)
        # Every live-compiled key must be a member of the offline
        # lattice (the warm-boot manifest covers everything the loop
        # can dispatch) ...
        lattice = manifest_for_engine(eng).ragged_keys()
        stray = set(eng._ragged_fns) - lattice
        assert not stray, f"keys the AOT lattice failed to enumerate: {stray}"
        # ... and the envelope's compiled subset stays a small constant
        # (well under the full lattice: traffic only walks the shapes
        # it needs).
        assert variants <= len(lattice), (variants, len(lattice))
        assert variants <= 16, dict.fromkeys(eng._ragged_fns)
        # The lattice itself, counted: 3 window row buckets (1, 2, 4)
        # and 9 mixed token buckets (16 .. 4096), each x 2 samplers x
        # 2 log-probability forms, x the page axis — one bucket at this
        # model length on the gather, none at all on the kernel.
        assert len(lattice) == (3 + 9) * 4 == 48
        assert {k[1] for k in lattice} == ({None} if interpret else {16})
        assert any(k[2] for k in eng._ragged_fns)  # windows were served
        for _ in range(3):
            asyncio.run(mix(2, 2))
        assert len(eng._ragged_fns) == variants
    finally:
        eng.stop()


# ------------------------------------------------------ engine pallas e2e
@pytest.mark.nightly
@pytest.mark.parametrize(
    "attention_impl, n_requests",
    [("pallas", 1), ("auto", 4)],
    ids=["explicit-1row", "auto-4rows"],
)
def test_engine_decodes_with_pallas_interpret(
    tiny_model_dir, attention_impl, n_requests
):
    """End-to-end: an engine on the (interpreted) kernel produces the
    same greedy tokens as the XLA engine, token for token — the ragged
    kernel serving real windowed decode dispatches. The second case is
    the ``auto`` rule as a TPU resolves it, at a full row bucket over
    contexts of a few dozen tokens: since PR 30 those windows read
    their contexts through the kernel too (they took the gather under
    ``auto`` while the page bucket was <= 1024 tokens)."""
    from dynamo_exp_tpu.models.config import ModelConfig

    mcfg = ModelConfig(
        num_layers=2,
        hidden_size=64,
        num_heads=4,
        num_kv_heads=2,
        intermediate_size=128,
        vocab_size=128,
        max_position_embeddings=256,
        dtype="float32",
    )
    prompts = [list(range(1 + i, 20 + 3 * i)) for i in range(n_requests)]

    def run(impl):
        cfg = EngineConfig(
            model=mcfg,
            max_decode_slots=max(2, n_requests),
            page_size=8,
            num_pages=64,
            max_model_len=128,
            attention_impl=impl,
            pallas_interpret=impl != "xla",
            enable_kv_events=False,
        )
        eng = TPUEngine(cfg, seed=7)

        async def one(prompt):
            stream = await eng.generate(
                {
                    "token_ids": prompt,
                    "stop_conditions": {"max_tokens": 12},
                    "sampling_options": {"temperature": 0.0},
                }
            )
            toks = []
            async for out in stream:
                toks.extend(out.get("token_ids") or [])
            return toks

        async def go():
            return await asyncio.gather(*(one(p) for p in prompts))

        try:
            out = asyncio.run(asyncio.wait_for(go(), timeout=300))
            return out, {k[:3] for k in eng._ragged_fns if k[2]}
        finally:
            eng.stop()

    kernel_toks, kernel_windows = run(attention_impl)
    xla_toks, xla_windows = run("xla")
    assert kernel_toks == xla_toks
    assert all(len(t) == 12 for t in kernel_toks)
    # Every window of the kernel engine was keyed without a page bound,
    # at a row bucket of several rows too; every window of the other
    # with one.
    assert kernel_windows and {p for _, p, _ in kernel_windows} == {None}
    assert max(nb for nb, _, _ in kernel_windows) >= min(n_requests, 2)
    assert {p for _, p, _ in xla_windows} == {16}


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
    tiles, in merged blocks and one query a cell, are to the bit what the
    parent of PR 44 (commit b4124f8) gave for these inputs: the latent
    form (``value_dim``) took nothing from the two-pool form's
    arithmetic."""
    import hashlib

    from dynamo_exp_tpu.ops.ragged_attention import query_block

    q, k, v, table, row, pos = _golden_case(qpk, hkv)
    for qt, qb in ((8, None), (8, query_block(8, qpk)), (1, None)):
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


# ------------------------------------------- a latent pool, expanded form
_TINY_WIDTHS = dict(H=4, r=40, dn=16, dr=16, dv=16, lanes=128)
_PUBLISHED_WIDTHS = dict(H=2, r=512, dn=128, dr=64, dv=128, lanes=640)  # a few heads
# (live queries, kv length) a row; a span is padded to whole tiles of 8
_EXPANDED_STREAMS = {
    # one row cut into three passes (q_max 16) of two score products each,
    # deep in a context an earlier dispatch wrote
    "one_row_several_blocks": [(40, 100)],
    # both sides of the rule in one stream: 24 queries expanded, 5 absorbed
    "expanded_beside_absorbed": [(24, 60), (5, 30)],
    # a context of more than two 64-token chunks of latents
    "crosses_the_chunk": [(19, 150), (8, 129)],
    # the rule's two sides: one query under the threshold, and at it
    "under_and_at_the_threshold": [(7, 40), (8, 40)],
    # a prompt's first chunk: nothing before it
    "from_the_start": [(33, 33), (16, 16)],
}


def _latent_case(spans, seed, *, H, r, dn, dr, dv, lanes, ps=8, tail_tiles=2):
    """A latent pool and the published form's queries over ``spans``, with
    ``tail_tiles`` padding tiles behind them; beside them the pools a
    two-pool reference reads (every head's ``[c_kv W_uk | k_r]``, its
    ``c_kv W_uv`` padded to the key's width) and the absorbed queries."""
    rng = np.random.default_rng(seed)
    pmax = max(-(-kv // ps) for _, kv in spans)
    pages = 1 + sum(-(-kv // ps) for _, kv in spans)
    table = np.zeros((len(spans) + 1, pmax), np.int32)
    row_of, positions, page = [], [], 1
    for i, (n, kv) in enumerate(spans):
        need = -(-kv // ps)
        table[i, :need] = page + rng.permutation(need)
        page += need
        pad = (-n) % 8
        row_of += [i] * (n + pad)
        positions += list(range(kv - n, kv)) + [-1] * pad
    row_of += [len(spans)] * 8 * tail_tiles
    positions += [-1] * 8 * tail_tiles
    pool = np.zeros((pages, ps, lanes), np.float32)
    pool[..., : r + dr] = rng.standard_normal((pages, ps, r + dr))
    q = rng.standard_normal((len(row_of), H, dn + dr)).astype(np.float32)
    w = (rng.standard_normal((r, H, dn + dv)) * r ** -0.5).astype(np.float32)
    c, k_r = pool[..., :r], pool[..., r : r + dr]
    kx = np.concatenate([
        np.einsum("psc,chd->pshd", c, w[..., :dn]),
        np.broadcast_to(k_r[:, :, None], (pages, ps, H, dr)),
    ], axis=-1).reshape(pages, ps, -1)
    vx = np.pad(
        np.einsum("psc,chd->pshd", c, w[..., dn:]), ((0, 0),) * 3 + ((0, dn + dr - dv),)
    ).reshape(pages, ps, -1)
    q_abs = np.zeros((len(row_of), H, lanes), np.float32)
    q_abs[..., :r] = np.einsum("nhd,chd->nhc", q[..., :dn], w[..., :dn])
    q_abs[..., r : r + dr] = q[..., dn:]
    arrays = dict(
        q=q, pool=pool, w_ukv=w.reshape(r, -1), table=table, row_of=np.asarray(row_of, np.int32),
        positions=np.asarray(positions, np.int32), kx=kx, vx=vx, q_abs=q_abs, w=w,
    )
    return {k: jnp.asarray(a) for k, a in arrays.items()}


@pytest.mark.parametrize("widths", [_TINY_WIDTHS, _PUBLISHED_WIDTHS], ids=["tiny", "published"])
@pytest.mark.parametrize("stream", list(_EXPANDED_STREAMS))
def test_expanded_form_matches_reference_and_the_absorbed_form(stream, widths):
    """The expanded call over a latent pool against the two-pool reference
    over every head's K and V built outright, and against the absorbed
    call on the same pool, row by row on each side of the rule: rows of at
    least 8 queries through the expanded call (in passes of at most 16
    slots, 8 a score product, 64 tokens of context a chunk, so that a row is
    several of each), the others
    through the absorbed one at ``-1`` where the expanded call serves;
    padding slots are exact zeros in what the two give together."""
    from dynamo_exp_tpu.ops.ragged_attention import (
        expanded_plan, ragged_paged_attention_expanded,
    )

    spans = _EXPANDED_STREAMS[stream]
    c = _latent_case(spans, 11, **widths)
    r, dn, dr, dv = (widths[k] for k in ("r", "dn", "dr", "dv"))
    scale = (dn + dr) ** -0.5
    want = ragged_paged_attention_ref(
        c["q"], c["kx"], c["vx"], c["table"], c["row_of"], c["positions"],
        num_kv_heads=widths["H"], sm_scale=scale,
    )[..., :dv]
    takes, items = expanded_plan(c["row_of"], c["positions"], len(spans) + 1, 8, 8, 16, 8)
    o_x = ragged_paged_attention_expanded(
        c["q"], c["pool"], c["w_ukv"], c["table"], c["positions"], items,
        rank=r, nope=dn, q_tile=8, sm_scale=scale, interpret=True,
        q_max=16, q_sub=8, kv_chunk=64,
    )
    takes = np.asarray(takes)
    by_row = {i: n >= 8 for i, (n, _) in enumerate(spans)}
    rows = np.asarray(c["row_of"])
    tile_live = np.repeat((np.asarray(c["positions"]).reshape(-1, 8) >= 0).any(axis=1), 8)
    assert (takes == (np.vectorize(lambda i: by_row.get(i, False))(rows) & tile_live)).all()

    def absorbed(positions):
        o = ragged_paged_attention(
            c["q_abs"], c["pool"], None, c["table"], c["row_of"], positions,
            num_kv_heads=1, q_tile=8, sm_scale=scale, interpret=True,
            value_dim=min(widths["lanes"], -(-r // 128) * 128),
        )
        return jnp.einsum("nhc,chv->nhv", o[..., :r], c["w"][..., dn:])

    both = jnp.where(takes[:, None, None], o_x, absorbed(jnp.where(takes, -1, c["positions"])))
    assert float(jnp.abs(both - want).max()) < 2e-5
    # the absorbed form of every row: the same mathematics, reordered
    assert float(jnp.abs(absorbed(c["positions"]) - want).max()) < 2e-5
    assert float(jnp.abs(both[np.asarray(c["positions"]) < 0]).max()) == 0.0


def test_expanded_form_in_bfloat16_rounds_k_and_v_once():
    """At the published head widths in bfloat16: the expanded call against
    the reference over K and V rounded to bfloat16 once (the published
    model's own rounding), float32 softmax."""
    from dynamo_exp_tpu.ops.ragged_attention import (
        expanded_plan, ragged_paged_attention_expanded,
    )

    w = _PUBLISHED_WIDTHS
    c = _latent_case([(24, 150), (16, 40)], 12, **w)
    bf = {k: v.astype(jnp.bfloat16) for k, v in c.items() if v.dtype == jnp.float32}
    kx = jnp.einsum(
        "psc,chd->pshd", bf["pool"][..., : w["r"]], bf["w"], preferred_element_type=jnp.float32
    ).astype(jnp.bfloat16)
    pages, ps = kx.shape[:2]
    k_r = jnp.broadcast_to(
        bf["pool"][:, :, None, w["r"] : w["r"] + w["dr"]], (pages, ps, w["H"], w["dr"])
    )
    keys = jnp.concatenate([kx[..., : w["dn"]], k_r], axis=-1).reshape(pages, ps, -1)
    values = jnp.pad(kx[..., w["dn"]:], ((0, 0),) * 3 + ((0, w["dr"]),)).reshape(pages, ps, -1)
    scale = (w["dn"] + w["dr"]) ** -0.5
    want = ragged_paged_attention_ref(
        bf["q"], keys, values, c["table"], c["row_of"], c["positions"],
        num_kv_heads=w["H"], sm_scale=scale,
    )[..., : w["dv"]]
    takes, items = expanded_plan(c["row_of"], c["positions"], 3, 8, 8)
    got = ragged_paged_attention_expanded(
        bf["q"], bf["pool"], bf["w_ukv"], c["table"], c["positions"], items,
        rank=w["r"], nope=w["dn"], q_tile=8, sm_scale=scale, interpret=True,
    )
    assert bool(takes[:40].all()) and not bool(takes[40:].any())
    diff = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))[:40]
    assert float(diff.max()) < 2e-2


def test_the_expanded_rule_follows_from_the_widths():
    """160 queries at openPangu's published widths and a tile of 8 (33.6 M
    operations of expansion a context token over 1,664 saved a pair and
    head: 157.5), 360 at GLM-5's (2 x 512 x 448 over 1,280: 358.4), a tile
    at tiny-pangu's; never where the absorbed form costs no more a pair.
    The kernel takes a head whose ``nope`` is whole lane groups, or whose
    ``nope + rope`` is (GLM-5's 192 + 64)."""
    from dynamo_exp_tpu.ops.ragged_attention import expanded_min_queries, expanded_supported

    assert expanded_min_queries(640, 512, 512, 128, 64, 128, 8) == 160
    assert expanded_min_queries(640, 512, 512, 192, 64, 256, 8) == 360
    assert expanded_min_queries(128, 128, 40, 16, 16, 16, 8) == 8
    assert expanded_min_queries(128, 128, 64, 64, 64, 128, 8) >= 2**30
    assert expanded_supported(16, 640, 512, 192, 64, 256, 64, jnp.bfloat16)
    assert not expanded_supported(16, 640, 512, 192, 32, 256, 64, jnp.bfloat16)
    assert expanded_supported(16, 640, 512, 128, 64, 128, 128, jnp.bfloat16)
    assert not expanded_supported(16, 128, 40, 16, 16, 16, 4, jnp.bfloat16)
    assert not expanded_supported(16, 640, 512, 128, 64, 128, 8, jnp.bfloat16)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_expanded_plan_and_host_rule_agree_on_a_drawn_stream(seed):
    """The device's plan of a drawn stream against the host's rule
    (``expanded_rows`` in numpy, what the engine's counter asks): the same
    rows take the form; their passes cover each span once, in pieces of at
    most ``q_max`` slots, the last shorter by less than a tile a piece, each
    with its row,
    its kv length and its score products' own."""
    from dynamo_exp_tpu.ops.ragged_attention import (
        expanded_items, expanded_plan, expanded_rows,
    )

    rng = np.random.default_rng(seed)
    n_rows, q_max, q_sub, least = 9, 64, 16, 24
    row_of, positions = [], []
    for r in rng.permutation(n_rows - 1)[:6]:
        n, first = int(rng.integers(1, 150)), int(rng.integers(0, 300))
        pad = (-n) % 8
        row_of += [r] * (n + pad)
        positions += list(range(first, first + n)) + [-1] * pad
    row_of += [n_rows - 1] * 24
    positions += [-1] * 24
    row_of, positions = np.asarray(row_of, np.int32), np.asarray(positions, np.int32)
    takes, row, start, slots, kvlen, sub_kvlen, sub_first = (
        np.asarray(a) for a in jax.tree.leaves(expanded_plan(
            jnp.asarray(row_of), jnp.asarray(positions), n_rows, 8, least, q_max, q_sub
        ))
    )
    host = expanded_rows(row_of, positions, least, n_rows, xp=np)
    tile_live = np.repeat((positions.reshape(-1, 8) >= 0).any(axis=1), 8)
    assert (takes == (host[row_of] & tile_live)).all()
    assert len(row) == expanded_items(len(row_of), n_rows, least, q_max)
    covered = np.zeros(len(row_of), int)
    for i in np.flatnonzero(slots):
        at = slice(start[i], start[i] + slots[i])
        covered[at] += 1
        assert slots[i] <= q_max and slots[i] % 8 == 0 and (row_of[at] == row[i]).all()
        assert kvlen[i] == positions[at].max() + 1
        for j in range(q_max // q_sub):
            part = positions[start[i] + j * q_sub : start[i] + min((j + 1) * q_sub, slots[i])]
            at = i * (q_max // q_sub) + j
            assert sub_kvlen[at] == (part.max() + 1 if len(part) else 0)
            assert sub_first[at] == (part[part >= 0].min() if (part >= 0).any() else 2**30)
    assert (covered == takes).all()
    for r in np.flatnonzero(host):
        pieces = slots[(row == r) & (slots > 0)]
        assert pieces.max() - pieces.min() < 8 * len(pieces)


# ------------------------- the expanded form under a learned selection
_GLM_WIDTHS = dict(H=2, r=512, dn=192, dr=64, dv=256, lanes=640)  # the joint layout
# (spans, index_topk, distinct score values (0: all different), the call's
# q_max / q_sub / kv_chunk)
_KEPT_STREAMS = {
    # a row wholly under index_topk (it keeps all it sees) beside one over it
    "under_beside_over": ([(24, 24), (24, 150)], 32, 0, (16, 8, 64)),
    # a handful of distinct scores: ties at the threshold in every chunk of
    # 128 tokens and every sub-block of 8 queries; lower positions win
    "ties_straddle_chunks_and_sub_blocks": ([(24, 300)], 40, 3, (32, 8, 128)),
    # one span in three passes, each with its own count of ties
    "a_span_in_passes": ([(40, 200)], 24, 4, (16, 8, 64)),
    # tiles with padding slots, a row under the rule beside them
    "padding_slots": ([(19, 150), (5, 90), (13, 129)], 16, 5, (16, 8, 64)),
    # a chunk of this call is two of the scores' chunks
    "two_score_chunks_a_chunk": ([(24, 500)], 64, 6, (16, 8, 256)),
}


def _kept_case(spans, widths, topk, values, seed=21, q_sub=8):
    """``_latent_case`` with a selection: drawn index scores in the scores
    kernel's layout (``-inf`` past a query's bound; ``values`` distinct
    ones, so that many tie), the threshold and the ties a query may keep,
    all longer by ``q_sub`` slots as the expanded call asks; and the kept
    positions a query as the reference takes them."""
    from dynamo_exp_tpu.ops import sparse_index as si

    c = _latent_case(spans, seed, **widths)
    ps, pmax = c["pool"].shape[1], c["table"].shape[1]
    n = c["positions"].shape[0]
    T, C, qt, S = si.tiled_shape(n, 8, ps, pmax)
    rng = np.random.default_rng(seed + 1)
    drawn = rng.standard_normal((n, C * S)).astype(np.float32)
    if values:
        drawn = np.floor(rng.random((n, C * S)) * values).astype(np.float32)
    seen = np.arange(C * S)[None] <= np.asarray(c["positions"])[:, None]
    by_query = jnp.asarray(np.where(seen, drawn, -np.inf))
    tau, allow = si.select(by_query, c["positions"], topk)
    c["kept"] = si.kept(by_query, tau, allow)[:, : pmax * ps]
    tiled = jnp.swapaxes(by_query.reshape(T, qt, C, S), 1, 2)
    assert bool((si.by_query(tiled) == by_query).all())
    c["keep"] = (
        jnp.pad(tiled, ((0, q_sub // 8), (0, 0), (0, 0), (0, 0)), constant_values=7.0),
        jnp.pad(tau, (0, q_sub)), jnp.pad(allow, (0, q_sub)),
    )
    c["keep_tiles"] = (tiled, tau, allow)
    return c


@pytest.mark.parametrize("widths", [_TINY_WIDTHS, _GLM_WIDTHS], ids=["tiny", "glm5"])
@pytest.mark.parametrize("stream", list(_KEPT_STREAMS))
def test_expanded_form_applies_the_selection_as_the_reference_does(stream, widths):
    """The expanded call with ``keep`` against the two-pool reference given
    the same kept positions, and against the absorbed call with the same
    ``keep``: the rows of at least 8 queries through the expanded call, the
    others absorbed; in the split score layout (tiny widths) and the joint
    one (GLM-5's head of 192 + 64)."""
    from dynamo_exp_tpu.ops.ragged_attention import (
        expanded_plan, ragged_paged_attention_expanded,
    )

    spans, topk, values, (q_max, q_sub, kv_chunk) = _KEPT_STREAMS[stream]
    c = _kept_case(spans, widths, topk, values, q_sub=q_sub)
    r, dn, dr, dv = (widths[k] for k in ("r", "dn", "dr", "dv"))
    scale = (dn + dr) ** -0.5
    want = ragged_paged_attention_ref(
        c["q"], c["kx"], c["vx"], c["table"], c["row_of"], c["positions"],
        num_kv_heads=widths["H"], sm_scale=scale, keep=c["kept"],
    )[..., :dv]
    takes, items = expanded_plan(
        c["row_of"], c["positions"], len(spans) + 1, 8, 8, q_max, q_sub
    )
    o_x = ragged_paged_attention_expanded(
        c["q"], c["pool"], c["w_ukv"], c["table"], c["positions"], items,
        rank=r, nope=dn, q_tile=8, sm_scale=scale, interpret=True,
        q_max=q_max, q_sub=q_sub, kv_chunk=kv_chunk, keep=c["keep"],
    )
    o_a = ragged_paged_attention(
        c["q_abs"], c["pool"], None, c["table"], c["row_of"],
        jnp.where(takes, -1, c["positions"]), num_kv_heads=1, q_tile=8,
        sm_scale=scale, interpret=True,
        value_dim=min(widths["lanes"], -(-r // 128) * 128), keep=c["keep_tiles"],
    )
    o_a = jnp.einsum("nhc,chv->nhv", o_a[..., :r], c["w"][..., dn:])
    both = jnp.where(takes[:, None, None], o_x, o_a)
    assert bool(takes.any()) and float(jnp.abs(both - want).max()) < 3e-5
    assert float(jnp.abs(both[np.asarray(c["positions"]) < 0]).max()) == 0.0
    # the selection is felt: the dense result differs
    dense = ragged_paged_attention_ref(
        c["q"], c["kx"], c["vx"], c["table"], c["row_of"], c["positions"],
        num_kv_heads=widths["H"], sm_scale=scale,
    )[..., :dv]
    assert float(jnp.abs(dense - want).max()) > 1e-3


@pytest.mark.parametrize("stream", ["one_row_several_blocks", "crosses_the_chunk"])
def test_joint_layout_matches_reference_without_a_selection(stream):
    """GLM-5's head widths with no ``keep``: one 256-wide score product,
    ``k_r`` placed behind ``k_nope`` before the one rounding."""
    from dynamo_exp_tpu.ops.ragged_attention import (
        _expanded_joint, expanded_plan, ragged_paged_attention_expanded,
    )

    w = _GLM_WIDTHS
    assert _expanded_joint(w["dn"], w["dr"]) and not _expanded_joint(128, 64)
    spans = _EXPANDED_STREAMS[stream]
    c = _latent_case(spans, 13, **w)
    scale = (w["dn"] + w["dr"]) ** -0.5
    want = ragged_paged_attention_ref(
        c["q"], c["kx"], c["vx"], c["table"], c["row_of"], c["positions"],
        num_kv_heads=w["H"], sm_scale=scale,
    )[..., : w["dv"]]
    takes, items = expanded_plan(c["row_of"], c["positions"], len(spans) + 1, 8, 8, 16, 8)
    got = ragged_paged_attention_expanded(
        c["q"], c["pool"], c["w_ukv"], c["table"], c["positions"], items,
        rank=w["r"], nope=w["dn"], q_tile=8, sm_scale=scale, interpret=True,
        q_max=16, q_sub=8, kv_chunk=64,
    )
    assert float(jnp.abs(jnp.where(takes[:, None, None], got - want, 0.0)).max()) < 3e-5


# --------------------------- openPangu's kernels beside the selection's plumbing
def _mixed_program_kernels(cfg):
    """The kernel bodies (jaxpr text, in program order) of ``cfg``'s mixed
    forward through the kernels at 48 slots with a packed tail of 8."""
    import hashlib

    from jax._src import core

    from dynamo_exp_tpu.models import llama

    def kernels(jaxpr, out):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append(hashlib.sha256(str(eqn.params["jaxpr"]).encode()).hexdigest()[:16])
            for sub in core.jaxprs_in_params(eqn.params):
                kernels(sub, out)
        return out

    params = jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    k, v = jax.eval_shape(lambda: llama.init_kv_cache(cfg, 17, 8, dtype=jnp.float32))

    def forward(params, tokens, positions, row_of, table, k, v, out_idx):
        return llama.forward_ragged(
            params, cfg, tokens, positions, row_of, table, k, v, out_idx,
            attn_impl="pallas", interpret=True, q_tile=8, packed_from=40,
        )

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    traced = jax.make_jaxpr(forward)(params, i32(48), i32(48), i32(48), i32(3, 16), k, v, i32(4))
    return kernels(traced.jaxpr, [])


@pytest.mark.parametrize("kernel,digest", [
    ("expanded", "7d8ed2a8608d9026"), ("absorbed_tiles", "243b96dd1ee6bd56"),
    ("absorbed_one_query", "0eda8565ee3ac724"),
])
def test_no_selection_leaves_the_latent_kernels_as_they_were(kernel, digest):
    """tiny-pangu's mixed program traced through the kernels: the body of
    each of its three attention kernels (a layer run's expanded call, its
    absorbed tiled call, its one-query call; two runs) is, to the letter,
    what the parent of PR 48 (commit c47eca4) traced. ``keep=None`` and the
    split score layout take nothing from and add nothing to the kernels of
    a latent model without an indexer, while tiny-glm-dsa's differ (they
    carry the selection). The one-query call's body is PR 52's, a block of
    rows a grid cell on one copy pipeline (``_rows_kernel``; c4ce710ae86c0081
    before it, a row a cell): its TEXT changed there and its results did
    not, which ``test_latent_pool_at_published_widths[1]`` and the decode
    tests hold it to bit for bit; the two tiled calls, which trace the same
    ``_attend``, read as they did."""
    from dynamo_exp_tpu.models.config import TINY_GLM_DSA, TINY_PANGU

    at = ["expanded", "absorbed_tiles", "absorbed_one_query"].index(kernel)
    got = _mixed_program_kernels(TINY_PANGU)
    assert len(got) == 6 and got[at] == got[at + 3] == digest
    # a model with an indexer: the scores and threshold kernels of the tiled
    # part once, then both forms, then the tail's three
    glm = _mixed_program_kernels(TINY_GLM_DSA)
    assert len(glm) == 2 * 7 and digest not in glm
