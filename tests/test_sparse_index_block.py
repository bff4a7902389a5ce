"""The scores kernel's query block (``ops/sparse_index.py::index_scores``):
a block of one row's tiles read in one pass over that row's index keys
gives the scores the per-tile pass gives, bit for bit; the selection over
them is the reference's; and the host counts the passes as the device plans
them. Interpret mode, tiny widths."""

import dataclasses
import types

import numpy as np
import pytest

import jax.numpy as jnp

from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
from dynamo_exp_tpu.models import llama
from dynamo_exp_tpu.models.config import TINY_GLM_DSA
from dynamo_exp_tpu.ops import sparse_index as si
from dynamo_exp_tpu.ops.ragged_attention import block_plan, merged_slot_counts

# Pages of 32 tokens: a chunk of the interpreted kernel is four page copies to
# lower, not sixteen (tests/ragged_cases.py "SHAPES").
PS, HI, DI, LANES = 32, 4, 32, 128


def _case(spans, pages, q_tile=8, seed=0, slots=None, rows=None):
    """A stream of ``spans`` (queries, first position[, bounds]) a row, each
    aligned to ``q_tile``, over tables of ``pages`` pages; ``slots`` pads the
    stream with slots of no query, ``rows`` the table with rows of none."""
    rng = np.random.default_rng(seed)
    n_rows = rows or len(spans)
    pool = np.zeros((n_rows * pages + 1, PS, LANES), np.float32)
    pool[..., :DI] = rng.standard_normal((*pool.shape[:2], DI))
    table = (rng.permutation(n_rows * pages).reshape(n_rows, pages) + 1).astype(np.int32)
    row_of, bounds = [], []
    for r, (n, first, *given) in enumerate(spans):
        pad = (-n) % q_tile
        row_of += [r] * (n + pad)
        bounds += (given[0] if given else list(range(first, first + n))) + [-1] * pad
    if slots:
        row_of += [0] * (slots - len(row_of))
        bounds += [-1] * (slots - len(bounds))
    n = len(row_of)
    q = rng.standard_normal((n, HI, DI)).astype(np.float32)
    w = rng.standard_normal((n, HI)).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (
        q, w, pool, table, np.asarray(row_of, np.int32), np.asarray(bounds, np.int32)
    ))


# name: (spans, q_tile, block, whether block_plan gives each block one pass).
# Every stream at tiles of 8 is laid in 64 slots (two blocks of 32) over a
# table of 2 rows of 8 pages: one shape, so the three kernels (a block a cell,
# a tile a cell, the rule's own block) compile once for the five; what a
# stream crosses is in its data.
STREAMS = {
    # every block is one row's: 64 queries deep in a context of 200
    "one_row": ([(64, 136)], 8, 32, [True, True]),
    # the second block holds the first row's end and the second's start
    "two_rows_a_block": ([(40, 60), (24, 0)], 8, 32, [True, False]),
    # a block whose last tiles hold no query, and a block of none (one pass
    # of nothing: no tile of it names a second row)
    "trailing_padding_tiles": ([(13, 30)], 8, 32, [True, True]),
    # the context ends inside a chunk, and the stream inside a block
    "ragged_context_and_stream": ([(56, 131)], 8, 32, [True, True]),
    # queries of one block bounded apart (a block model's), one at -1
    "bounds_apart": ([(32, 0, [90, 3, -1, 40] * 8)], 8, 32, [True, True]),
    # a stream of one slot a row: the block is the tile
    "q_tile_1": ([(1, 77), (1, 0), (1, 30)], 1, 1, [True] * 3),
}
PAGES = 8


@pytest.mark.parametrize("stream", list(STREAMS))
def test_block_pass_equals_the_gather_and_the_tile_pass_bit_for_bit(stream):
    spans, qt, block, merged = STREAMS[stream]
    pages = PAGES
    shape = dict(slots=64, rows=2) if qt == 8 else {}
    q, w, pool, table, row_of, bounds = _case(spans, pages, qt, **shape)
    plan = block_plan(row_of, bounds, qt, block)
    assert (np.asarray(plan[3]) >= 0).tolist() == merged
    got = si._index_scores(q, w, pool, table, row_of, bounds, qt, block, True)
    assert got.shape == si.tiled_shape(len(row_of), qt, PS, pages)
    by_tile = si._index_scores(q, w, pool, table, row_of, bounds, qt, qt, True)
    assert np.array_equal(np.asarray(got).view(np.uint32), np.asarray(by_tile).view(np.uint32))
    # the rule's own block (the whole stream here: shorter than a block)
    ruled = si.index_scores(q, w, pool, table, row_of, bounds, q_tile=qt, interpret=True)
    assert np.array_equal(np.asarray(ruled).view(np.uint32), np.asarray(by_tile).view(np.uint32))
    want = np.asarray(si.index_scores_ref(
        q[:, None], w[:, None], pool, table[row_of], bounds[:, None]
    )[:, 0])
    got = np.asarray(si.by_query(got))
    seen = want > -np.inf
    assert np.array_equal(got[:, : want.shape[1]] > -np.inf, seen)
    assert np.all(got[:, want.shape[1]:] == -np.inf)  # the last chunk's lanes past the table
    assert np.abs(np.where(seen, got[:, : want.shape[1]], 0) - np.where(seen, want, 0)).max() < 1e-4


def test_block_rule_reads_shapes_alone():
    assert si.index_block(8, 32) == 128  # GLM-5's indexer on the engine's tile
    assert si.index_block(1, 32) == 1  # a slot a row: nothing to read together
    assert si.index_block(8, 4, LANES, 4) == 128
    assert si.index_block(24, 32) == 120  # whole tiles
    wide = si.index_block(8, 256)  # eight times the heads: what VMEM holds
    assert 8 <= wide < 128 and wide % 8 == 0
    assert si._cell_vmem_bytes(wide, 256, 128, 2) <= si._BLOCK_VMEM_BYTES


def test_selection_over_the_block_pass_is_the_references():
    """``select_tiled`` and ``_kept_tiled`` over the kernel's scores equal
    ``select`` and ``kept`` over the gather's, where a query selects."""
    topk = 16
    q, w, pool, table, row_of, bounds = _case([(40, 60), (24, 0)], PAGES, slots=64, rows=2)
    want = si.index_scores_ref(q[:, None], w[:, None], pool, table[row_of], bounds[:, None])[:, 0]
    tau_w, allow_w = si.select(want, bounds, topk)
    kept_w = np.asarray(si.kept(want, tau_w, allow_w))
    scores = si._index_scores(q, w, pool, table, row_of, bounds, 8, 32, True)
    tau, allow = si.select_tiled(scores, bounds.reshape(-1, 8), topk, interpret=True)
    tau, allow = tau.reshape(-1), allow.reshape(-1)
    selecting = np.asarray(bounds) >= topk
    assert selecting.any() and not selecting.all()
    assert np.array_equal(np.asarray(allow), np.asarray(allow_w))
    assert np.array_equal(np.isinf(np.asarray(tau)), ~selecting)
    at = jnp.arange(len(row_of), dtype=jnp.int32)
    kept = np.asarray(llama._kept_tiled((scores, tau, allow), at, 8))[:, : want.shape[1]]
    seen = np.arange(want.shape[1])[None] <= np.asarray(bounds)[:, None]
    assert np.array_equal(kept & seen, kept_w & seen)
    assert (kept & seen).sum(axis=1).tolist() == np.minimum(np.asarray(bounds) + 1, topk).tolist()
    # a threshold is one of the query's own scores, to the last bit of the
    # gather's where the two sum the heads alike, and always within rounding
    assert np.abs(np.asarray(tau)[selecting] - np.asarray(tau_w)[selecting]).max() < 1e-4


def _counted(label):
    from dynamo_exp_tpu.telemetry import get_telemetry

    return sum(
        s.value for m in get_telemetry().registry.collect() for s in m.samples
        if s.name == "dynamo_dsa_index_slots_total" and s.labels.get("pass") == label
    )


def test_host_counts_the_scores_passes_as_the_device_plans_them():
    """``dynamo_dsa_index_slots_total``: the builders' count
    (``engine.py::_count_index_passes``: ``merged_slot_counts`` at the scores
    kernel's block) against ``block_plan`` on the device, on a stream of
    spans that start and end inside blocks; a stream that scores nothing
    counts nothing, and neither does the reference engine."""
    cfg = EngineConfig(
        model=dataclasses.replace(TINY_GLM_DSA, dtype="float32"),
        max_decode_slots=2, page_size=PS, num_pages=16,
    )
    eng = types.SimpleNamespace(cfg=cfg, _attn_impl="pallas", _attn_interpret=True)
    for method in ("_count_index_passes", "_latent_form_queries"):
        setattr(eng, method, types.MethodType(getattr(TPUEngine, method), eng))
    nb = 1024
    tiled = nb - cfg.ragged_packed_slots(nb)
    block = si.index_block(8, cfg.model.index_n_heads, cfg.model.index_lanes, 4)
    assert tiled % block and block == 128  # the stream ends inside a block
    spans = [(300, 2000), (90, 0), (130, 512), (7, 9)]
    *_, row_of, bounds = _case(spans, 1, slots=nb)
    row_of, bounds = np.array(row_of), np.array(bounds)
    before = _counted("block"), _counted("tile")
    eng._count_index_passes(nb, row_of, bounds)
    on_host = _counted("block") - before[0], _counted("tile") - before[1]
    *_, block_kvlen = block_plan(jnp.asarray(row_of[:tiled]), jnp.asarray(bounds[:tiled]), 8, block)
    live = np.pad(bounds[:tiled] >= 0, (0, -tiled % block)).reshape(-1, block).sum(axis=1)
    in_block = int(live[np.asarray(block_kvlen) >= 0].sum())
    assert on_host == (in_block, int(live.sum()) - in_block)
    assert on_host == merged_slot_counts(row_of[:tiled], bounds[:tiled], 8, block)
    assert on_host[0] > 0 and on_host[1] > 0 and sum(on_host) == sum(n for n, *_ in spans)
    # no query past index_topk: the part skips the kernel (``_selection_tiled``)
    eng._count_index_passes(nb, row_of, np.minimum(bounds, cfg.model.index_topk - 1))
    eng._attn_impl = "xla"
    eng._count_index_passes(nb, row_of, bounds)
    assert (_counted("block") - before[0], _counted("tile") - before[1]) == on_host
