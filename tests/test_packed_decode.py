"""A decode row takes one slot of a mixed dispatch, not a tile of eight.

A kernel engine's mixed stream of ``nb`` slots is two static parts
(``EngineConfig.ragged_packed_slots``, ``TPUEngine._mixed_bucket_for``;
docs/engine_perf.md "One ragged dispatch"): tiles of ``ragged_q_tile``
slots, one row a tile, for prefill chunks and draft spans, then a packed
tail read through the kernel's one-query form, where the decode rows lie
side by side. These tests hold the layout's arithmetic, the forward over
it (``forward_ragged(packed_from=...)``: the kernel in interpret mode
against the tiled layout and against the reference), the state of a model
that has one, and an interpret-mode kernel engine serving the mix.

TOLERANCES. A bfloat16 model's logits in the packed layout against the
tiled one: the kernel computes a query's scores in float32 from the same
pages either way, so the two agree to the rounding of one bfloat16 value
on the way out (2**-8 of the logits' largest magnitude; on the CPU they
are in fact bit-equal). A float32 model against the reference gather:
the kernel tests' 2e-5 (``tests/test_ragged_kernels.py``) holds for the
attention output; through two layers and the head the logits agree to
1e-4 of their spread.
"""

import asyncio
import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
from dynamo_exp_tpu.models import TINY, TINY_MOE, TINY_QWEN3, init_kv_cache
from dynamo_exp_tpu.parallel import single_device_mesh
from dynamo_exp_tpu.protocols.common import BackendInput

# Jitted: a shape and set of options compiles once a file (tests/programs.py).
from .programs import forward, forward_ragged, init_params

PS = 8


# ------------------------------------------------------------- the layout
def _engine(model=TINY, slots=64, **kw) -> TPUEngine:
    cfg = EngineConfig(
        model=model, max_decode_slots=slots, page_size=kw.pop("page_size", PS),
        num_pages=kw.pop("num_pages", 4 * slots + 32),
        max_model_len=kw.pop("max_model_len", 256),
        eos_token_ids=[], **kw,
    )
    return TPUEngine(cfg, mesh=single_device_mesh(), seed=0)


@pytest.fixture(scope="module")
def kernel_engine():
    """The benchmark cells' 64 slots on the (interpreted) kernel; never
    started: only the layout is asked."""
    return _engine(pallas_interpret=True)


def _layout(eng, aligned: int, rows: int) -> tuple[int, int]:
    """(token bucket, first decode slot) as ``_build_mixed`` asks them."""
    nb = eng._mixed_bucket_for(aligned, rows)
    return nb, eng._decode_from(nb, aligned)


def _tiled_bucket(cfg, rows: int, chunk: int) -> int:
    """The bucket before PR 32: every span, a decode row's one token
    too, padded to the kernel's tile."""
    tile = cfg.ragged_q_tile
    return cfg.ragged_tokens_bucket_for(-(-chunk // tile) * tile + tile * rows, mixed=True)


@pytest.mark.parametrize("cell,rows,chunk,before,after", [
    ("lfm2-8b-a1b.chat-saturate", 61, 304, 1024, 512),
    ("mixtral-8x7b.chat-saturate", 40, 160, 512, 256),
    ("mixtral-8x7b.chat-saturate/short-prompt", 30, 64, 512, 128),
    ("qwen3-4b.chat-steady", 23, 160, 512, 256),
    ("qwen3-4b.sessions-prefix", 6, 64, 128, 128),
])
def test_a_cells_typical_mixed_dispatch_lands_in_the_bucket_the_rule_says(
    kernel_engine, cell, rows, chunk, before, after
):
    """ISSUE 32's arithmetic from the ledger's counters: R decode rows
    beside one chunk of n prompt tokens, at 64 slots."""
    eng = kernel_engine
    assert eng._ragged_align() == 8 and _tiled_bucket(eng.cfg, rows, chunk) == before
    aligned = -(-chunk // 8) * 8
    nb, dec_from = _layout(eng, aligned, rows)
    assert nb == after
    # Decode rows lie in the packed tail, after the chunk's tiles.
    assert dec_from == max(aligned, nb - eng.cfg.ragged_packed_slots(nb))
    assert dec_from + rows <= nb


@pytest.mark.parametrize("slots", [2, 4, 64, 48])
def test_no_dispatch_takes_a_larger_bucket_than_its_tiled_layout_did(slots):
    eng = _engine(slots=slots, pallas_interpret=True, num_pages=64)
    cfg = eng.cfg
    smaller = 0
    for rows in range(slots + 1):
        for aligned in range(0, 1100, 8):
            nb, dec_from = _layout(eng, aligned, rows)
            packed = eng.cfg.ragged_packed_slots(nb)
            tiled = cfg.ragged_tokens_bucket_for(max(aligned + 8 * rows, 1), mixed=True)
            assert nb <= tiled, (rows, aligned)
            smaller += nb < tiled
            # The split is whole tiles before it, from nb alone; the
            # decode rows fit behind whatever the spans take.
            assert (nb - packed) % 8 == 0 and 0 < packed <= nb // 2
            assert dec_from >= nb - packed and dec_from >= aligned
            assert dec_from + rows <= nb
    assert smaller > 0


def test_an_engine_on_the_reference_packs_tight_as_before():
    eng = _engine()  # the CPU resolves the XLA form
    assert eng._attn_impl == "xla" and eng._ragged_align() == 1
    assert _layout(eng, 160, 23) == (256, 160)
    assert _layout(eng, 0, 1) == (16, 0)


def test_the_packed_tail_is_a_function_of_the_bucket_alone():
    cfg = EngineConfig(model=TINY, max_decode_slots=64)
    tails = {nb: cfg.ragged_packed_slots(nb) for nb in (16, 32, 64, 128, 256, 512, 1024, 4096)}
    assert tails == {16: 8, 32: 8, 64: 16, 128: 32, 256: 64, 512: 64, 1024: 64, 4096: 64}


# ----------------------------------------------- the forward over a layout
def _context(cfg, dtype, n_pages=24):
    """Pools full of random context, and a table giving row r pages
    4r+1 .. 4r+4 (page 0 is what zero table entries read)."""
    k0, v0 = init_kv_cache(cfg, n_pages, PS, dtype=dtype)
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    k = jax.random.normal(ks[0], k0.shape, jnp.float32).astype(dtype)
    v = jax.random.normal(ks[1], v0.shape, jnp.float32).astype(dtype)
    table = jnp.zeros((6, 4), jnp.int32)
    for r in range(5):
        table = table.at[r].set(jnp.arange(4 * r + 1, 4 * r + 5))
    return k, v, table


# Row 0 prefills positions 5..16 (12 tokens); rows 1..3 decode at 9, 30, 17.
CHUNK = list(range(5, 17))
DECODE = [(1, 9), (2, 30), (3, 17)]


def _stream(nb: int, dec_from: int, dec_stride: int, pad_id: int = 0):
    """The chunk at slot 0, then the decode rows from ``dec_from`` every
    ``dec_stride`` slots; padding slots belong to row 5."""
    tokens = np.full(nb, pad_id, np.int32)
    positions = np.full(nb, -1, np.int32)
    row_of = np.full(nb, 5, np.int32)
    tokens[: len(CHUNK)] = [40 + p for p in CHUNK]
    positions[: len(CHUNK)] = CHUNK
    row_of[:16] = 0
    out_idx = [len(CHUNK) - 1]
    for i, (r, pos) in enumerate(DECODE):
        at = dec_from + i * dec_stride
        tokens[at], positions[at] = 90 + r, pos
        row_of[at : at + dec_stride] = r
        out_idx.append(at)
    return tuple(jnp.asarray(a) for a in (tokens, positions, row_of, np.asarray(out_idx, np.int32)))


def _logits(params, cfg, stream, pools, **kw):
    tokens, positions, row_of, out_idx = stream
    k, v, table = pools
    out, *_ = forward_ragged(
        params, cfg, tokens, positions, row_of, table, k, v, out_idx, **kw
    )
    return np.asarray(out)


TILED = dict(nb=64, dec_from=16, dec_stride=8)  # 16 + 3 x 8 = 40 -> 64
PACKED = dict(nb=32, dec_from=24, dec_stride=1)  # tiles to 24, tail of 8


@pytest.mark.parametrize("model", [TINY_QWEN3, TINY_MOE], ids=["dense", "routed"])
def test_packed_rows_read_what_the_tiled_layout_and_the_reference_read(model):
    params = init_params(jax.random.PRNGKey(2), model)
    pools = _context(model, jnp.bfloat16)
    kernel = dict(attn_impl="pallas", q_tile=8, interpret=True)
    tiled = _logits(params, model, _stream(**TILED), pools, **kernel)
    packed = _logits(params, model, _stream(**PACKED), pools, packed_from=24, **kernel)
    assert np.isfinite(packed).all() and np.ptp(packed, axis=1).min() > 0
    assert np.abs(packed - tiled).max() <= 2.0 ** -8 * np.abs(tiled).max()

    f32 = dataclasses.replace(model, dtype="float32")
    params = init_params(jax.random.PRNGKey(2), f32)
    pools = _context(f32, jnp.float32)
    packed = _logits(params, f32, _stream(**PACKED), pools, packed_from=24, **kernel)
    ref = _logits(params, f32, _stream(**PACKED), pools, attn_impl="xla")
    assert np.abs(packed - ref).max() <= 1e-4 * np.ptp(ref)


def test_a_chunk_that_outgrows_the_tiles_runs_on_into_the_packed_tail():
    """The same stream with the split inside the chunk (8 of its 12
    tokens in a tile, 4 in one-query cells, its tile padding too): any
    one slot is a cell the kernel's one-query form serves."""
    model = dataclasses.replace(TINY_QWEN3, dtype="float32")
    params = init_params(jax.random.PRNGKey(2), model)
    pools = _context(model, jnp.float32)
    stream = _stream(nb=32, dec_from=16, dec_stride=1)
    got = _logits(
        params, model, stream, pools, packed_from=8,
        attn_impl="pallas", q_tile=8, interpret=True,
    )
    ref = _logits(params, model, stream, pools, attn_impl="xla")
    assert np.abs(got - ref).max() <= 1e-4 * np.ptp(ref)


def test_packed_rows_do_not_turn_with_their_padding_neighbours_or_page_zero():
    """tests/test_padding_rows.py's rule in the packed tail, through a
    routed model (its experts sort every slot of the stream together):
    page 0, the pages of the padding row and the padding slots' token
    ids filled two ways give every live row bit-equal logits."""
    params = init_params(jax.random.PRNGKey(2), TINY_MOE)
    k, v, table = _context(TINY_MOE, jnp.bfloat16)
    logits = []
    for fill, pad_id in ((0.25, 0), (64.0, 7)):
        pools = (k.at[:, 0].set(fill).at[:, 17:].set(fill), v.at[:, 0].set(fill).at[:, 17:].set(fill), table)
        logits.append(_logits(
            params, TINY_MOE, _stream(pad_id=pad_id, **PACKED), pools,
            packed_from=24, attn_impl="pallas", q_tile=8, interpret=True,
        ))
    assert np.array_equal(logits[0], logits[1])


def test_a_padding_slot_between_one_query_cells_is_exact_zeros():
    """What the packed tail's attention returns for its unused slots
    (the kernel at ``q_tile=1``, padding between and after live cells)."""
    from dynamo_exp_tpu.ops.ragged_attention import ragged_paged_attention

    H, HKV, D = 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    k, v = (jax.random.normal(key, (8, PS, HKV * D), jnp.float32) for key in ks[1:])
    table = jnp.asarray([[3, 5, 0, 0], [2, 0, 0, 0], [0, 0, 0, 0]], jnp.int32)
    positions = jnp.asarray([12, -1, 3, -1, -1, -1, -1, -1], jnp.int32)
    row_of = jnp.asarray([0, 2, 1, 2, 2, 2, 2, 2], jnp.int32)
    q = jax.random.normal(ks[0], (8, H, D), jnp.float32)
    out = np.asarray(ragged_paged_attention(
        q, k, v, table, row_of, positions, num_kv_heads=HKV, q_tile=1, interpret=True,
    ))
    live = np.asarray(positions) >= 0
    assert np.abs(out[live]).min(axis=(1, 2)).max() > 0 and not out[~live].any()


# ----------------------------------------------------- a model with state
def test_packed_rows_of_a_model_with_state_read_their_own_entry_only():
    """An LFM2-shaped model: three sequences, each with its own history
    in its own pool entry, take one decode step side by side in the
    packed tail beside a fourth's first chunk. Each row's logits are its
    own uncut forward's, and the entry it leaves continues its own
    sequence (the convolution's taps walk the stream by ``row_of``: a
    neighbour one slot away is another row)."""
    import os
    import sys

    from dynamo_exp_tpu.models import llama
    from dynamo_exp_tpu.models.config import TINY_LFM2

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmark"))
    from references import lfm2_moe as reference
    from .test_lfm2 import HF, LOGP_TOL

    cfg = dataclasses.replace(TINY_LFM2, dtype="float32")
    params = init_params(jax.random.PRNGKey(3), cfg)
    k, v = llama.init_kv_cache(cfg, 24, PS, dtype=jnp.float32)
    st = llama.init_seq_state(cfg, 8)
    rng = np.random.default_rng(1)
    seqs = [[int(t) for t in rng.integers(2, 256, n)] for n in (11, 7, 14)]
    fresh = [int(t) for t in rng.integers(2, 256, 5)]
    table = jnp.stack([jnp.arange(4 * r + 1, 4 * r + 5) for r in range(5)]).astype(jnp.int32)
    for r, ids in enumerate(seqs):  # each history but its last token, entry r + 1
        n = len(ids) - 1  # in 16 slots, padding behind it: the three are one program
        _, k, v, st = forward(
            params, cfg, jnp.asarray(ids[:-1] + [0] * (16 - n))[None],
            jnp.asarray(list(range(n)) + [-1] * (16 - n), jnp.int32)[None],
            table[r + 1 : r + 2], k, v, seq_state=st, state_slots=jnp.asarray([r + 1]),
        )
    nb, tail = 32, 24
    tokens = np.zeros(nb, np.int32)
    positions = np.full(nb, -1, np.int32)
    row_of = np.full(nb, 4, np.int32)
    tokens[:5], positions[:5], row_of[:8] = fresh, np.arange(5), 0
    for r, ids in enumerate(seqs):
        tokens[tail + r], positions[tail + r], row_of[tail + r] = ids[-1], len(ids) - 1, r + 1
    out_idx = jnp.asarray([4, tail, tail + 1, tail + 2], jnp.int32)
    none = st.shape[1]
    rows = (
        jnp.asarray([0, 1, 2, 3, none], jnp.int32),
        jnp.asarray([4, tail, tail + 1, tail + 2], jnp.int32),
        jnp.asarray([0, 1, 2, 3], jnp.int32),
    )
    logits, k, v, st = forward_ragged(
        params, cfg, jnp.asarray(tokens), jnp.asarray(positions), jnp.asarray(row_of),
        table, k, v, out_idx, attn_impl="pallas", q_tile=8, packed_from=tail,
        interpret=True, seq_state=st, state_rows=rows,
    )
    got = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    for i, ids in enumerate([fresh] + seqs):
        want = reference.log_probs(params, HF, ids, [len(ids) - 1])[0]
        assert np.abs(got[i] - want).max() < LOGP_TOL, i
    # One more token a sequence from the entries the dispatch left.
    for r, ids in enumerate(seqs):
        nxt = ids + [17 + r]
        logits, *_ = forward(
            params, cfg, jnp.asarray(nxt[-1:])[None], jnp.asarray([[len(ids)]], jnp.int32),
            table[r + 1 : r + 2], k, v, seq_state=st, state_slots=jnp.asarray([r + 1]),
        )
        want = reference.log_probs(params, HF, nxt, [len(ids)])[0]
        got = np.asarray(jax.nn.log_softmax(logits[0], axis=-1))[0]
        assert np.abs(got - want).max() < LOGP_TOL, r


# -------------------------------------------- an engine serving the mix
F32 = dataclasses.replace(TINY_QWEN3, dtype="float32")


async def _ask(eng, prompt, n, arrived=None, **sampling):
    b = BackendInput(token_ids=list(prompt))
    b.stop_conditions.max_tokens = n
    b.stop_conditions.ignore_eos = True
    d = b.to_dict()
    d["sampling_options"].update(sampling)
    stream = await eng.generate(d)
    if arrived is not None:
        arrived.set()  # submitted: the loop's next intake finds it
    out = []
    async for item in stream:
        out += item.get("token_ids", [])
    return out


async def _serve(eng, sampling):
    """Two requests decoding when a third arrives, then a fourth: mixed
    dispatches that carry decode rows beside a prompt's chunk.

    Which rows ride together must not turn on how fast the two threads
    run (a compiled window takes 10 ms, so the first two could be done
    before a busy machine lets this coroutine see them decode: the test
    failed that way once in a few whole runs). So the loop thread waits,
    before the first dispatch at which a late request is due, until
    that request has been submitted."""
    rs = np.random.RandomState(11)
    prompts = [list(rs.randint(3, 200, size=n)) for n in (21, 9, 13, 30)]

    def decoding() -> int:
        return sum(s is not None and s.generated > 0 for s in eng.sched.slots)

    # When each late request is due, and whether it has been submitted.
    late = [
        (lambda: eng.steps >= 12, threading.Event()),  # both in their windows
        (lambda: decoding() >= 3, threading.Event()),  # the third decodes too
    ]
    dispatch = eng._dispatch_ragged

    def held(prefill_rows):
        for due, arrived in late:
            if due() and not arrived.is_set():
                arrived.wait(timeout=60)
        return dispatch(prefill_rows)

    eng._dispatch_ragged = held
    first = [asyncio.ensure_future(_ask(eng, p, 40, **sampling)) for p in prompts[:2]]
    asked = []
    for (due, arrived), prompt, n in zip(late, prompts[2:], (24, 16)):
        while not due():
            await asyncio.sleep(0.01)
        asked.append(asyncio.ensure_future(_ask(eng, prompt, n, arrived, **sampling)))
    return await asyncio.wait_for(asyncio.gather(*first, *asked), timeout=300)


@pytest.mark.parametrize("sampling", [{"temperature": 0.0}, {"temperature": 0.8, "seed": 5}],
                         ids=["greedy", "seeded"])
def test_a_kernel_engine_serving_a_late_arrival_emits_the_reference_engines_tokens(sampling):
    streams, mixed = {}, {}
    for form in ("kernel", "xla"):
        # pages of 32 on a table of 4: four page copies a chunk of the
        # interpreted kernel to lower, not sixteen (tests/ragged_cases.py "SHAPES")
        eng = _engine(
            F32, slots=4, kv_dtype="float32", prefill_chunk=16, num_pages=32,
            page_size=32, max_model_len=128,
            pallas_interpret=form == "kernel", chained_decode=False,
        )
        assert eng._attn_impl == ("pallas" if form == "kernel" else "xla")
        eng.start()
        try:
            streams[form] = asyncio.run(_serve(eng, sampling))
            mixed[form] = [
                e for e in eng.flight.snapshot()
                if e["kind"] == "dispatch" and e.get("windowed") is False
            ]
        finally:
            eng.stop()
    assert streams["kernel"] == streams["xla"]
    assert [len(s) for s in streams["kernel"]] == [40, 40, 24, 16]
    # The third request's first chunk (13 tokens: two tiles) rode with the
    # two decoding rows, one slot each from the 32 bucket's tail at 24:
    # the stream ends at 26 where two more tiles would have ended at 32.
    assert any(
        (e["rows"], e["bucket"], e["tokens"]) == (3, 32, 26) for e in mixed["kernel"]
    ), mixed["kernel"]
    assert any((e["rows"], e["tokens"]) == (3, 15) for e in mixed["xla"])
