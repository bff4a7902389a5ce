"""The tiled attention call under a learned selection over GROUPED-QUERY
pools (``keep``; Keye-VL-2.0's form) at a block of several tiles
(``_block_kernel``, PR 62): a block of one row makes one pass over that
row's context for all its queries, each masked at its own bound and by its
own threshold, its tiles' index scores copied a chunk at a time from the
scores kernel's layout. The result is the one-tile form's to the bit and
the gather's within the file's tolerance."""

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_exp_tpu.ops import sparse_index as si
from dynamo_exp_tpu.ops.ragged_attention import (
    block_plan,
    ragged_paged_attention,
    ragged_paged_attention_ref,
    selected_pad,
    selected_query_block,
)

QT, PS, CHUNK = 8, 16, 128
H, HKV, D = 16, 2, 64  # 8 query heads a KV head, as Keye has


def selected_case(spans, topk, values=0, seed=5, tail=0):
    """A stream of ``spans`` ``(tokens, first position)``, a row each on
    pages of its own, each aligned to the tile and ``tail`` slots of no
    query behind; grouped-query pools (the K row with 128 lanes more behind
    the keys, as a model with an indexer keeps them); drawn index scores in
    the scores kernel's layout (``values`` distinct ones, so that many tie;
    0: all different) and their exact selection of ``topk`` a query."""
    rng = np.random.default_rng(seed)
    row_of, positions = [], []
    pmax = -(-max(first + n for n, first in spans) // PS)
    table = np.zeros((len(spans), pmax), np.int32)
    page = 1
    for r, (n, first) in enumerate(spans):
        width = -(-n // QT) * QT
        row_of += [r] * width
        positions += list(range(first, first + n)) + [-1] * (width - n)
        pages = -(-(first + n) // PS)
        table[r, :pages] = page + rng.permutation(pages)
        page += pages
    row_of += [len(spans) - 1] * tail
    positions += [-1] * tail
    n = len(row_of)
    positions = np.asarray(positions, np.int32)
    k = rng.standard_normal((page, PS, HKV * D + 128)).astype(np.float32)
    v = rng.standard_normal((page, PS, HKV * D)).astype(np.float32)
    q = rng.standard_normal((n, H, D)).astype(np.float32)
    T, C, qt, S = si.tiled_shape(n, QT, PS, pmax)
    drawn = rng.standard_normal((n, C * S)).astype(np.float32)
    if values:
        drawn = np.floor(rng.random((n, C * S)) * values).astype(np.float32)
    seen = np.arange(C * S)[None] <= positions[:, None]
    by_query = jnp.asarray(np.where(seen, drawn, -np.inf))
    tau, allow = si.select(by_query, jnp.asarray(positions), topk)
    tiled = jnp.swapaxes(by_query.reshape(T, qt, C, S), 1, 2)
    return dict(
        q=jnp.asarray(q), k=jnp.asarray(k), v=jnp.asarray(v), table=jnp.asarray(table),
        row_of=jnp.asarray(row_of, jnp.int32), positions=jnp.asarray(positions),
        keep=(tiled, tau, allow), mask=si.kept(by_query, tau, allow)[:, : pmax * PS],
        tau=np.asarray(tau), allow=np.asarray(allow), by_query=np.asarray(by_query),
    )


def call(c, q_block, keep=None):
    return np.asarray(ragged_paged_attention(
        c["q"], c["k"], c["v"], c["table"], c["row_of"], c["positions"],
        num_kv_heads=HKV, q_tile=QT, q_block=q_block, interpret=True,
        keep=c["keep"] if keep is None else keep,
    ))


def padded(keep, n, q_block, value=7.0):
    """The selection run to whole blocks (``selected_pad``): tiles of
    anything, which no query reads."""
    scores, tau, allow = keep
    pad = selected_pad(n, q_block)
    return (
        jnp.pad(scores, ((0, pad // QT), (0, 0), (0, 0), (0, 0)), constant_values=value),
        jnp.pad(tau, (0, pad)), jnp.pad(allow, (0, pad)),
    )


# (spans, topk, distinct score values, slots of no query behind, q_block,
#  what of block_plan's answer the case rests on)
CASES = {
    # two blocks wholly inside one row: one pass each for 32 queries
    "a_block_inside_one_row": ([(64, 200)], 24, 0, 0, 32, "merged"),
    # the first block holds row 0's end and row 1's start (by tile), the
    # second is row 1's alone (one pass)
    "a_block_of_two_rows": ([(24, 150), (40, 90)], 24, 0, 0, 32, "both"),
    # 72 slots in blocks of 32: the last block is padded to no query
    "no_whole_number_of_blocks": ([(72, 170)], 24, 0, 0, 32, "merged"),
    # the first queries see no more than topk positions (tau -inf, every
    # position kept) beside selecting ones in one block
    "under_topk_beside_selecting": ([(32, 20)], 36, 0, 0, 32, "merged"),
    # three distinct scores: ties at the threshold in every chunk of 128
    # tokens, the kept ones the lowest positions across chunk boundaries
    "ties_cross_chunk_boundaries": ([(32, 280)], 140, 3, 0, 32, "merged"),
    # a span of 19 tokens: padding slots inside a live tile of a live block,
    # and a tile of no query at its end
    "padding_slots_in_a_live_block": ([(19, 150)], 24, 4, 8, 32, "merged"),
    # the rule's own block at these widths (64 slots), three rows in its
    # blocks; and sixteen tiles a block, the scores kernel's
    "the_rules_block": ([(130, 140), (40, 60), (88, 33)], 40, 5, 0, None, "both"),
    "a_block_of_sixteen_tiles": ([(130, 140), (40, 60), (88, 33)], 40, 5, 0, 128, "both"),
}


@pytest.mark.parametrize("name", list(CASES))
def test_selected_block_equals_the_one_tile_form_to_the_bit(name):
    spans, topk, values, tail, q_block, plan = CASES[name]
    q_block = q_block or selected_query_block(QT, H, D, HKV)
    c = selected_case(spans, topk, values, tail=tail)
    n = c["positions"].shape[0]
    *_, block_kvlen = block_plan(
        np.asarray(c["row_of"]), np.asarray(c["positions"]), QT, q_block, xp=np
    )
    assert (block_kvlen >= 0).any()
    assert (block_kvlen < 0).any() == (plan == "both")
    tile = call(c, QT)
    block = call(c, q_block, padded(c["keep"], n, q_block))
    np.testing.assert_array_equal(block, tile)
    want = np.asarray(ragged_paged_attention_ref(
        c["q"], c["k"][..., : HKV * D], c["v"], c["table"], c["row_of"], c["positions"],
        num_kv_heads=HKV, keep=c["mask"],
    ))
    np.testing.assert_allclose(block, want, rtol=2e-5, atol=2e-5)
    dead = np.asarray(c["positions"]) < 0
    assert dead.any() == bool(tail or any(s[0] % QT for s in spans))
    assert not block[dead].any()  # exact zeros
    # what each case is there to show, read off its selection
    live = ~dead
    if name == "under_topk_beside_selecting":
        assert np.isinf(c["tau"][live]).any() and np.isfinite(c["tau"][live]).any()
    if name == "ties_cross_chunk_boundaries":
        ties = (c["by_query"] == c["tau"][:, None])[live]
        first, last = ties.argmax(axis=1), ties.shape[1] - 1 - ties[:, ::-1].argmax(axis=1)
        assert (first // CHUNK < last // CHUNK).all()  # a query's ties lie in several chunks
        assert ((c["allow"][live] >= 1) & (c["allow"][live] < ties.sum(axis=1))).all()


def test_selection_must_run_to_whole_blocks():
    """The last block's passes copy their tiles' scores wherever the stream
    ends: a selection short of whole blocks is refused, not read past."""
    c = selected_case([(72, 170)], 24)
    with pytest.raises(ValueError, match="selected_pad"):
        call(c, 32)


def test_latent_selection_keeps_its_tile():
    """A latent pool's selection rides cells of one tile (its rows of many
    queries take the expanded call): a block of several is refused."""
    rng = np.random.default_rng(3)
    n, lanes, vd, pmax = 32, 256, 128, 4
    pool = jnp.asarray(rng.standard_normal((5, PS, lanes)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((n, 4, lanes)), jnp.float32)
    shape = si.tiled_shape(n, QT, PS, pmax)
    keep = (jnp.zeros(shape), jnp.full((n,), -jnp.inf), jnp.full((n,), si.NO_LIMIT))
    args = (
        q, pool, None, jnp.arange(1, 5, dtype=jnp.int32)[None],
        jnp.zeros((n,), jnp.int32), jnp.arange(20, 20 + n, dtype=jnp.int32),
    )
    with pytest.raises(ValueError, match="latent pool.*one tile"):
        ragged_paged_attention(
            *args, num_kv_heads=1, q_tile=QT, q_block=32, interpret=True,
            value_dim=vd, keep=keep,
        )
    one = ragged_paged_attention(
        *args, num_kv_heads=1, q_tile=QT, q_block=QT, interpret=True, value_dim=vd, keep=keep,
    )
    assert one.shape == (n, 4, vd)
