"""Ragged paged attention, tiles walked as rows (PR 56): a call that names
no block (a block model's pass: a tile of ``block_length`` queries a row,
rows side by side) walks ``one_query_block``'s tiles a grid cell on ONE copy
pipeline, a chunk worked for all the KV heads together, as the one-query
call walks its rows, where ``one_query_schedule`` says ``together`` (and is
a tile a cell, as before PR 56, where it does not): to the bit the same
stream at a tile a cell (``q_block=q_tile``, the tile kernel), and the
reference's at its tolerance. Interpret mode. A file of its own by
docs/testing.md's rule (``tests/test_ragged_kernels.py`` is near its 250 s);
the other forms of the call are there, the host's rules in
``tests/test_ragged_rules.py``.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_exp_tpu.ops.ragged_attention import (
    one_query_block,
    one_query_schedule,
    ragged_paged_attention,
    ragged_paged_attention_ref,
)

from .ragged_cases import extras

# What each tile of the stream holds, ``rows_a_cell`` = 3 tiles a grid cell
# (ten tiles: three whole cells and one of a single tile): (batch row, first
# position, live queries; None: a tile of no query). Dead tiles first in a
# cell, in its middle and last; padding queries inside a live tile; two
# consecutive tiles of one row (a block model's opening pass: the block
# before, then the block); contexts of one chunk of 128 tokens and of two.
_TILES = [
    None, (0, 40, "all"), (0, 40, "next"),
    (1, 0, "part"), None, (2, 150, "all"),
    (3, 3, "all"), None, None,
    (1, 130, "all"),
]


def _tile_stream(qt: int, H: int, Hkv: int, D: int = 32, ps: int = 64, pmax: int = 3):
    """``_TILES`` at a tile of ``qt``: ``flat_batch``'s arrays with the
    stream laid out by hand."""
    draw = np.random.RandomState(qt * 100 + H)
    pages = 4 * pmax + 1
    k, v = (jnp.asarray(draw.standard_normal((pages, ps, Hkv * D)), jnp.float32) for _ in "kv")
    table = jnp.asarray(draw.permutation(pages - 1)[: 4 * pmax].reshape(4, pmax) + 1, jnp.int32)
    row_of, positions = [], []
    for tile in _TILES:
        row, first, live = tile or (0, 0, None)
        first += qt if live == "next" else 0
        n = {None: 0, "part": max(qt - 1, 1) if qt > 1 else 1}.get(live, qt)
        row_of += [row] * qt
        positions += [*range(first, first + n), *[-1] * (qt - n)]
    q = jnp.asarray(draw.standard_normal((len(row_of), H, D)), jnp.float32)
    return q, k, v, table, jnp.asarray(row_of, jnp.int32), jnp.asarray(positions, jnp.int32)


def _rows_against_a_tile_a_cell(monkeypatch, stream, **kw):
    """The stream's output with its tiles walked as rows, three a grid cell
    (the rule's answer cut to three where it is more than one: at these
    widths it is the whole stream), so that the stream is no whole number of
    cells; held to the same call at a tile a cell TO THE BIT, and its
    padding slots to exact zeros."""
    from dynamo_exp_tpu.ops import ragged_attention as ra

    a_tile = ragged_paged_attention(*stream, q_block=kw["q_tile"], interpret=True, **kw)
    monkeypatch.setattr(ra, "one_query_block", lambda *a: min(one_query_block(*a), 3))
    # the jitted entry point would keep the rule's answer of an earlier trace
    got = np.asarray(ragged_paged_attention.__wrapped__(*stream, interpret=True, **kw))
    np.testing.assert_array_equal(got, np.asarray(a_tile))
    assert (got[np.asarray(stream[-1]) < 0] == 0.0).all()
    return got


# STATIC facts a case crosses (two compiles each, of the rows form and of the
# tile kernel): the tile (2, 4, 8), the KV heads (1, 2, 4) and the query heads
# a KV head (1, 4, 8), so score rows a head that are part of a sublane tile, a
# whole one and several; at one KV head, and at 4 KV heads x 8 x a tile of 8
# (256 stacked rows, past the rule's 128), the rule answers a tile a cell and
# the call is the tile kernel's own. The stream is one, data.
@pytest.mark.parametrize("qpk", [1, 4, 8])
@pytest.mark.parametrize("hkv", [1, 2, 4])
@pytest.mark.parametrize("qt", [2, 4, 8])
def test_tiles_walked_as_rows_equal_a_tile_a_cell(qt, hkv, qpk, monkeypatch):
    """A call that names no block (a block model's pass) walks its tiles
    as the one-query call walks its rows, ``one_query_block``'s tiles a grid
    cell on one copy pipeline and a chunk worked for all the KV heads
    together where ``one_query_schedule`` says so: every tile's arithmetic
    is the one-tile cell's (``q_block=q_tile``, ``_tile_kernel``), state
    zeroed at its start, so the stream's output is that call's to the bit,
    under a block model's ``bounds`` (a query sees its whole tile), with
    padding queries and dead tiles exact zeros."""
    H = hkv * qpk
    stream = _tile_stream(qt, H, hkv)
    positions = stream[-1]
    bounds = jnp.where(positions >= 0, positions | (qt - 1), -1)
    got = _rows_against_a_tile_a_cell(
        monkeypatch, stream, num_kv_heads=hkv, q_tile=qt, bounds=bounds
    )
    together = hkv > 1 and H * qt <= 128
    assert one_query_schedule(3, hkv, H * qt) == ("together" if together else "by_head")
    assert one_query_block(10, H, 32, 32, qt, hkv) == (10 if together else 1)
    want = ragged_paged_attention_ref(*stream, num_kv_heads=hkv, bounds=bounds)
    live = np.asarray(positions) >= 0
    np.testing.assert_allclose(got[live], np.asarray(want)[live], atol=2e-5)


@pytest.mark.parametrize("sliding,extra", [(20, "sink"), (None, "sink+narrow"), (16, "")])
def test_tiles_walked_as_rows_under_a_window_and_sinks(sliding, extra, monkeypatch):
    """The same walk with a sliding width (a tile's walk starts at its own
    first visible page) and with sink logits and values narrower than keys,
    at 2 KV heads of 4 query heads and a tile of 4: to the bit a tile a
    cell."""
    q, k, v, *rest = _tile_stream(4, 8, 2)
    Dv, sink = extras(extra, 8, 32)
    if Dv:
        v = v[..., : 2 * Dv]
    stream = (q, k, v, *rest)
    got = _rows_against_a_tile_a_cell(
        monkeypatch, stream, num_kv_heads=2, q_tile=4, sliding=sliding, **sink
    )
    want = ragged_paged_attention_ref(*stream, num_kv_heads=2, window=sliding, **sink)
    live = np.asarray(rest[-1]) >= 0
    np.testing.assert_allclose(got[live], np.asarray(want)[live], atol=2e-5)
