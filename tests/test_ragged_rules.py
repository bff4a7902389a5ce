"""Ragged paged attention, the reference and the host's rules: the pure-JAX
reference against ``paged_attention``, the layout gates, and every plan the
engine counts on the host with ``numpy`` and the kernels make inside the
program with ``jax.numpy`` (``block_plan``, ``one_query_chain``,
``expanded_plan``): one function each, the same answer slot for slot. No
kernel runs here (the kernels: ``tests/test_ragged_kernels.py`` and
``tests/test_ragged_expanded.py``; engines: ``tests/test_ragged_attention.py``).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_exp_tpu.engine import TPUEngine
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
    ragged_paged_attention_ref,
    ragged_supported,
)

from .ragged_cases import flat_batch


def test_reference_matches_paged_attention_per_token():
    """The reference IS the battle-tested paged_attention per token
    (bit-equal reductions keep mixed dispatches argmax-stable against
    the step-by-step schedule even at exact bf16 ties)."""
    spans = [(4, 12), (1, 30)]
    H, Hkv, D, ps, pmax = 4, 2, 32, 8, 8
    q, k, v, table, row_of, positions = flat_batch(
        1, spans, H, Hkv, D, 32, ps, pmax, q_tile=1
    )
    ref = ragged_paged_attention_ref(
        q, k, v, table, row_of, positions, num_kv_heads=Hkv
    )
    direct = paged_attention(
        q[:, None], k, v, table[row_of], positions[:, None]
    )[:, 0]
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(direct))


def test_ragged_supported_layout_gate():
    assert ragged_supported(16, 4, 64, jnp.bfloat16)  # 256-lane, ps%16
    assert not ragged_supported(16, 1, 64, jnp.bfloat16)  # 64 lanes
    assert not ragged_supported(12, 4, 64, jnp.float32)  # ps % 8 != 0


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


@pytest.mark.parametrize(
    "config, want",
    [
        # Keye-VL-2.0: a selection over grouped-query pools, 32 heads on 4 KV
        # heads of 128: the selected call's own block (``query_block`` would
        # answer 16 there)
        ("keye-vl-2.0-30b-a3b-l5", 64),
        # GLM-5: a selection over a latent pool, 64 heads on ONE KV head: the
        # tile (its rows of many queries take the expanded call)
        ("glm-5-l5e16", 8),
        # unselected models keep ``query_block``'s answer
        ("qwen3-4b", 32),
        ("sdar-30b-a3b-l6", 16),
        ("olmo-hybrid-7b-l8", 128),
    ],
)
def test_a_models_query_block_by_its_selection_and_pool(config, want):
    """``models/llama.py::ragged_query_block``, which the program and the
    engine's counter both ask: from the call's shapes alone, by whether the
    model selects and what its pool is."""
    import json
    import os

    from dynamo_exp_tpu.models import ModelConfig
    from dynamo_exp_tpu.models.llama import ragged_query_block
    from dynamo_exp_tpu.ops.ragged_attention import selected_query_block

    configs = os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs")
    with open(os.path.join(configs, f"{config}.json")) as f:
        cfg = ModelConfig.from_hf_config(json.load(f))
    got = ragged_query_block(cfg, 8, cfg.num_kv_heads)
    assert got == ragged_query_block(cfg, 8) == want
    if cfg.has_indexer and not cfg.is_latent:
        assert got == selected_query_block(8, cfg.num_heads, cfg.head_dim_, cfg.num_kv_heads)
        assert query_block(8, cfg.num_heads // cfg.num_kv_heads) == 16
    elif not cfg.has_indexer:
        assert got == query_block(8, cfg.num_heads // cfg.num_kv_heads)
    # a tile of one slot has nothing to read together; heads too wide for a
    # cell's VMEM halve the block, in whole tiles
    assert selected_query_block(1, 32, 128, 4) == 1
    assert selected_query_block(8, 128, 128, 16) == 32
    assert selected_query_block(24, 32, 128, 4) == 48


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
        schedule = one_query_schedule(one_query_rows(model, n), kv_heads, model.num_heads)
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


# (query heads, KV heads, key lanes, value lanes) a chip holds, by cell; the
# rows a cell of a bucket of 64 and the schedule, as PR 55's tree answers at
# one query a row (its two functions, asked at these shapes).
_ONE_QUERY_LAYOUTS = {
    "qwen3-4b": ((32, 8, 128, 128), 64, "together"),
    "mixtral-8x7b, a share of four": ((8, 2, 128, 128), 64, "together"),
    "lfm2-8b-a1b": ((32, 8, 64, 64), 64, "together"),
    "jamba2-3b": ((20, 1, 128, 128), 64, "by_head"),
    "sdar-30b-a3b": ((32, 4, 128, 128), 64, "together"),
    "olmo-hybrid-7b": ((30, 30, 128, 128), 64, "together"),
    "openpangu, the absorbed form": ((128, 1, 640, 512), 8, "by_head"),
    "glm-5, the absorbed form": ((64, 1, 640, 512), 8, "by_head"),  # 14 fit: the power of two
    "k-exaone-236b": ((64, 8, 128, 128), 64, "together"),
    "mimo-v2-flash, full": ((64, 4, 192, 128), 32, "together"),
    "mimo-v2-flash, sliding": ((64, 8, 192, 128), 32, "together"),
}


@pytest.mark.parametrize("layout", _ONE_QUERY_LAYOUTS)
def test_the_rules_of_a_row_of_one_query_and_of_one_tile(layout):
    """``one_query_block`` and ``one_query_schedule`` at ``q_tile`` 1 answer
    what they answered before they knew of tiles, layout by layout; at a
    tile a cell's query and output blocks are that many slots a row, so the
    same slots hold that many fewer tiles, one where not even two fit (the
    call is then the tile kernel), and tiles are walked so only with their
    chunk worked for all the KV heads together: more than one KV head and
    stacked score rows, heads x tile, the MXU's 128 at most; else the
    answer is one tile a cell, the tile kernel as before PR 56."""
    (heads, kv_heads, width, value_width), rows, schedule = _ONE_QUERY_LAYOUTS[layout]
    assert one_query_block(64, heads, width, value_width) == rows
    assert one_query_block(64, heads, width, value_width, 1) == rows
    assert one_query_schedule(rows, kv_heads, heads) == schedule
    assert one_query_schedule(1, kv_heads, heads) == "by_head"  # a cell of one row
    for tile in (2, 4, 8):
        tiles = one_query_block(64, heads, width, value_width, tile, kv_heads)
        slots = one_query_block(64 * tile, heads, width, value_width)
        fit = max(1, min(64, slots // tile))
        together = fit > 1 and kv_heads > 1 and heads * tile <= 128
        assert tiles == (fit if together else 1)
        want = "together" if together else "by_head"
        assert one_query_schedule(tiles, kv_heads, heads * tile) == want
    if layout == "sdar-30b-a3b":
        # a block pass: 64 tiles of 4 (128 in the opening pass), 32 a cell,
        # 4 x 8 x 4 = 128 stacked rows: exactly on the bound
        assert one_query_block(64, heads, width, value_width, 4, kv_heads) == 32
        assert one_query_block(128, heads, width, value_width, 4, kv_heads) == 32
        assert one_query_schedule(32, kv_heads, heads * 4) == "together"
        # at one KV head the tiles would go by head: a tile a cell
        assert one_query_block(64, heads // 4, width, value_width, 4, 1) == 1
    if layout == "jamba2-3b":
        # its mixed program names its block, one tile (``query_block``): the
        # tile kernel; had it named none, one KV head's 160 stacked rows
        # would go by head, so a tile a cell all the same
        assert query_block(8, heads // kv_heads) == 8
        assert one_query_block(64, heads, width, value_width, 8, kv_heads) == 1
    if "absorbed" in layout:
        assert one_query_block(64, heads, width, value_width, 8, kv_heads) == 1


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_host_count_and_kernel_chain_agree_on_a_drawn_block_stream(seed):
    """``dynamo_attn_tile_rows_total``: a block program's passes, as the
    dispatch reports them at its consume (passes made a row, blocks
    written), rebuilt into the streams the program fed its attention calls
    (the opening pass two tiles a row, the others one), counted with
    ``one_query_fetches`` over the tiles' bounds: the same answer as a walk of
    ``one_query_chain``'s ``(lead, after)`` over the tiles' bounds, as the
    kernel makes it, planned with ``numpy`` and with ``jax.numpy``; every
    live tile under the schedule ``one_query_schedule`` gives its call."""
    from types import SimpleNamespace

    from dynamo_exp_tpu.engine.block import BlockDecode
    from dynamo_exp_tpu.models.llama import one_query_rows
    from dynamo_exp_tpu.telemetry import get_telemetry

    B = 4
    model = dataclasses.replace(TINY, num_kv_heads=2, block_length=B)
    engine = SimpleNamespace(_attn_impl="pallas", cfg=SimpleNamespace(model=model, tp=1))
    counter = get_telemetry().attn_tile_rows

    def counted():
        return {
            (fetch, schedule): counter.labels(fetch, schedule)._value.get()
            for fetch in ("behind", "exposed") for schedule in ("together", "by_head")
        }

    rng = np.random.default_rng(seed)
    nb = int(rng.choice([1, 4, 16, 64]))
    n_denoise = np.where(rng.random(nb) < 0.8, rng.integers(1, 5, nb), 0)
    wrote = (rng.random(nb) < 0.5) & (n_denoise > 0) | (rng.random(nb) < 0.1)
    before = counted()
    BlockDecode._count_tile_rows(engine, n_denoise, wrote.astype(np.int32))
    grew = {k: v - before[k] for k, v in counted().items()}
    # the streams, as ``_block_program`` builds them
    start = 8 * B * (1 + np.arange(nb))
    block = start[:, None] + np.arange(B)
    opening = np.stack(
        [np.where(wrote[:, None], block - B, -1), np.where(n_denoise[:, None] > 0, block, -1)],
        axis=1,
    ).reshape(-1)
    streams = [opening] + [
        np.where(n_denoise[:, None] > i, block, -1).reshape(-1)
        for i in range(1, int(n_denoise.max(initial=0)))
    ]
    want = dict.fromkeys(grew, 0)
    for stream in streams:
        tiles = stream.size // B
        rows = one_query_rows(model, tiles, 1, B)
        assert rows == one_query_block(
            tiles, model.num_heads, model.head_dim_, model.head_dim_, B, 2
        )
        schedule = one_query_schedule(rows, 2, model.num_heads * B)
        lead, after = one_query_chain(stream.astype(np.int32), rows, xp=np, q_tile=B)
        on_device = one_query_chain(jnp.asarray(stream, jnp.int32), rows, q_tile=B)
        np.testing.assert_array_equal(lead, np.asarray(on_device[0]))
        np.testing.assert_array_equal(after, np.asarray(on_device[1]))
        seen = []
        for cell, at in enumerate(lead):
            want["exposed", schedule] += at < rows
            while at < rows:
                seen.append(cell * rows + at)
                at = after[cell * rows + at]
                want["behind", schedule] += at < rows
        live = stream.reshape(-1, B).max(axis=1) >= 0
        assert seen == list(np.flatnonzero(live))
        assert one_query_fetches(stream.reshape(-1, B).max(axis=1), rows) == (
            len(seen) - (lead < rows).sum(), (lead < rows).sum()
        )
    assert grew == want
    assert sum(grew.values()) == n_denoise.sum() + wrote.sum()


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

