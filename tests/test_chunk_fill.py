"""A prompt chunk is sized by the bucket its dispatch already pays for.

``cfg.prefill_chunk`` is the share of a mixed dispatch every prefilling
row is guaranteed. The token bucket those shares take is a power of two,
and ``TPUEngine._plan_chunks`` hands what it would leave empty to rows
with prompt left beyond their share: whole pages, the row admitted
earliest first, never a larger bucket (docs/engine_perf.md "One ragged
dispatch"). These tests hold the planner's arithmetic on both layouts
(the kernel's tiles and packed tail, the reference's tight packing) and
an engine on the CPU serving prompts several chunks long beside decoding
rows: the same tokens as with the filling switched off, and the counter
``dynamo_prefill_chunk_tokens_total{kind}`` that says how often it
engaged. The models with sequence state have theirs in
``tests/test_lfm2.py`` and ``tests/test_jamba.py``.
"""

import asyncio
import itertools

import numpy as np
import pytest

from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
from dynamo_exp_tpu.models import TINY
from dynamo_exp_tpu.parallel import single_device_mesh
from dynamo_exp_tpu.telemetry import get_telemetry

from .test_packed_decode import F32, _ask
from .test_packed_decode import _engine as _small_engine

PAGE, CHUNK = 16, 512


# ------------------------------------------------------------- the planner
@pytest.fixture(scope="module")
def engines():
    """The benchmark cells' 64 slots, 16-token pages and 512-token chunks
    on each layout; never started: only the planner is asked."""
    def build(**kw):
        cfg = EngineConfig(
            model=TINY, max_decode_slots=64, page_size=PAGE, num_pages=160,
            max_model_len=256, eos_token_ids=[], **kw,
        )
        return TPUEngine(cfg, mesh=single_device_mesh(), seed=0)

    return {"kernel": build(pallas_interpret=True), "reference": build()}


def _extent(eng, chunks, spans) -> int:
    align = eng._ragged_align()
    return sum(-(-n // align) * align for n in itertools.chain(chunks, spans))


def _shares(eng, remaining) -> list[int]:
    return [min(n, eng.cfg.prefill_chunk) for n in remaining]


def _check(eng, remaining, admitted, rows, spans, chunks):
    """What must hold of any plan, whatever it gave to whom."""
    cfg = eng.cfg
    shares = _shares(eng, remaining)
    nb = eng._mixed_bucket_for(_extent(eng, shares, spans), rows)
    aligned = _extent(eng, chunks, spans)
    # The bucket is the one the shares alone took.
    assert eng._mixed_bucket_for(aligned, rows) == nb
    filled = []
    for n, left, share in zip(chunks, remaining, shares):
        assert share <= n <= left  # nobody gets less than the guaranteed share
        assert (n - share) % cfg.page_size == 0 or n == left
        filled.append(n > share)
    # Everything fits, the decode rows inside the packed tail of a kernel
    # engine, and a chunk that grew did not push them back.
    dec_from = eng._decode_from(nb, aligned)
    assert dec_from + rows <= nb
    if any(filled) and rows and eng._attn_impl == "pallas":
        assert dec_from == nb - cfg.ragged_packed_slots(nb)
    # Earliest first: once a row is left with prompt, a row admitted
    # later gets at most what a whole page could not use.
    by_age = sorted(range(len(chunks)), key=admitted.__getitem__)
    starved = False
    for i in by_age:
        if starved:
            assert chunks[i] - shares[i] < cfg.page_size
        starved = starved or (filled[i] and chunks[i] < remaining[i])
    return nb


# (remaining prompt a row, admitted, decode rows, spec spans,
#  kernel (nb, chunks), reference (nb, chunks))
TABLE = {
    # jamba2-3b.longdoc-saturate: rows of >= 1024 left beside 29 decode rows.
    "longdoc-1-row": ([5000], [1.0], 29, [], (1024, [960]), (1024, [992])),
    "longdoc-2-rows": ([5000, 4000], [1.0, 2.0], 29, [],
                       (2048, [1472, 512]), (2048, [1504, 512])),
    "longdoc-3-rows": ([5000, 4000, 1024], [1.0, 2.0, 3.0], 29, [],
                       (2048, [960, 512, 512]), (2048, [992, 512, 512])),
    "longdoc-8-rows": ([2048] * 8, [float(i) for i in range(8)], 29, [],
                       (8192, [2048, 2048, 1472] + [512] * 5),
                       (8192, [2048, 2048, 1504] + [512] * 5)),
    # The earliest admitted is not the first slot; it ends its prompt (88
    # tokens over its share, no whole pages) and the next oldest long
    # row takes the rest in whole pages.
    "earliest-first": ([5000, 600, 100], [3.0, 1.0, 2.0], 29, [],
                       (2048, [1280, 600, 100]), (2048, [1312, 600, 100])),
    # No decode rows: a kernel engine's spans may run the bucket's length.
    "no-decode-rows": ([5000, 100], [1.0, 2.0], 0, [],
                       (1024, [912, 100]), (1024, [912, 100])),
    # Draft spans lie in the same stream and take their tiles first.
    "spec-spans": ([3000], [1.0], 3, [5, 5, 3],
                   (1024, [928]), (1024, [1008])),
    # The shares already run on into the packed tail (960 tiled slots of
    # 1024): nothing to hand out on the kernel; the reference has 14 slots
    # left, under a page.
    "spans-in-the-tail": ([5000, 488], [1.0, 2.0], 10, [],
                          (1024, [512, 488]), (1024, [512, 488])),
    # Chat traffic: no row has more than its share left.
    "short-prompts": ([300, 512, 17], [1.0, 2.0, 3.0], 23, [],
                      (1024, [300, 512, 17]), (1024, [300, 512, 17])),
    "decode-and-drafts-alone": ([], [], 12, [4], (64, []), (16, [])),
}


@pytest.mark.parametrize("layout", ["kernel", "reference"])
@pytest.mark.parametrize("case", list(TABLE))
def test_a_dispatchs_chunks_fill_the_bucket_its_shares_took(engines, layout, case):
    remaining, admitted, rows, spans, *want = TABLE[case]
    eng = engines[layout]
    assert eng._attn_impl == ("pallas" if layout == "kernel" else "xla")
    chunks = eng._plan_chunks(remaining, admitted, rows, spans)
    nb = _check(eng, remaining, admitted, rows, spans, chunks)
    assert (nb, chunks) == want[layout == "reference"]
    if all(n <= CHUNK for n in remaining):
        assert chunks == remaining  # nothing changes


@pytest.mark.parametrize("layout", ["kernel", "reference"])
def test_the_cells_typical_dispatches_lose_their_padding(engines, layout):
    """ISSUE 34's four: 1, 2, 3 and 8 chunks of 512 beside 29 decode rows
    were 47 / 49 / 24 / 50 % padding; filled, under 4 %."""
    eng = engines[layout]
    for k, before in ((1, 0.47), (2, 0.49), (3, 0.24), (8, 0.50)):
        remaining = [4096] * k
        nb = eng._mixed_bucket_for(_extent(eng, _shares(eng, remaining), []), 29)
        assert abs(1 - (k * CHUNK + 29) / nb - before) < 0.01
        chunks = eng._plan_chunks(remaining, list(range(k)), 29, [])
        assert 1 - (sum(chunks) + 29) / nb < 0.04


@pytest.mark.parametrize("layout", ["kernel", "reference"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_any_mix_keeps_its_bucket_its_shares_and_its_order(engines, layout, seed):
    eng = engines[layout]
    rs = np.random.RandomState(seed)
    grew = 0
    for _ in range(400):
        k = rs.randint(0, 9)
        remaining = [int(rs.choice([rs.randint(1, 512), rs.randint(513, 9000)])) for _ in range(k)]
        admitted = list(rs.permutation(k).astype(float))
        rows = int(rs.randint(0, 64 - k + 1))
        spans = [int(rs.randint(2, 6)) for _ in range(rs.randint(0, 3) if rows + k < 62 else 0)]
        chunks = eng._plan_chunks(remaining, admitted, rows, spans)
        _check(eng, remaining, admitted, rows, spans, chunks)
        grew += chunks != _shares(eng, remaining)
    assert grew > 100


def test_a_chunk_the_tile_does_not_divide_keeps_the_rule():
    """``prefill_chunk`` 20 on tiles and pages of 8: a share is 24 slots."""
    eng = _small_engine(slots=4, pallas_interpret=True, prefill_chunk=20, num_pages=64)
    # 24 slots + 2 rows -> 32 (tail 8, tiled 24): no room.
    assert eng._plan_chunks([23], [1.0], 2, []) == [20]
    # 24 + 16 + 2 -> 64 (tail 8 at 4 slots, tiled 56): two pages, to the
    # one row that has prompt left, whichever is older.
    for admitted, want in (([1.0, 2.0], [36, 9]), ([2.0, 1.0], [36, 9])):
        chunks = eng._plan_chunks([100, 9], admitted, 2, [])
        assert chunks == want
        _check(eng, [100, 9], admitted, 2, [], chunks)
    # Alone: 20 -> 28 is one page and one more tile of the 32 bucket.
    assert eng._plan_chunks([100], [1.0], 0, []) == [28]
    assert eng._mixed_bucket_for(32, 0) == 32


# -------------------------------------------- an engine serving the mix
def _counts() -> dict:
    tel = get_telemetry()
    return {k: tel.prefill_chunk_tokens.labels(k)._value.get() for k in ("share", "fill")}


def _since(before: dict) -> dict:
    return {k: v - before[k] for k, v in _counts().items()}


async def restore_from_an_extended_chunks_snapshot(engine, run, greedy_reference, beside, base):
    """For a model with sequence state (``tests/test_lfm2.py``,
    ``tests/test_jamba.py``; chunks of 16, pages of 8): ``base`` arrives
    while ``beside`` decodes. Its share of 16 and that row take the 32
    bucket, whose empty page the chunk takes too, so the first chunk is 24
    tokens and its snapshot lies at the block boundary 24, where chunks
    of 16 would have kept 16 and 32. A second asking that shares three
    blocks restores from it."""
    from dynamo_exp_tpu.engine.scheduler import SeqState

    before = _counts()
    row = asyncio.ensure_future(run(engine, beside, 120))
    while not any(s is not None and s.state is SeqState.ACTIVE for s in engine.sched.slots):
        await asyncio.sleep(0.01)
    first = await run(engine, base, 4)
    filled = _since(before)["fill"]
    hits = dict(engine.kv.state_hits), engine.kv.prefix_hits["shared"]
    prompt = base[:29] + [1, 1, 1]  # shares 3 whole blocks (24 tokens)
    got = await run(engine, prompt, 8)
    assert not row.done()  # every dispatch above carried its row
    assert filled >= 8
    assert engine.kv.state_hits["restored"] == hits[0]["restored"] + 1
    assert engine.kv.prefix_hits["shared"] - hits[1] == 3
    assert first == greedy_reference(engine.params, base, 4)
    assert got == greedy_reference(engine.params, prompt, 8)
    assert len(await row) == 120


async def _serve_long(eng, sampling):
    """Two requests decoding when two prompts of several chunks arrive."""
    rs = np.random.RandomState(7)
    prompts = [list(rs.randint(3, 200, size=n)) for n in (11, 14, 93, 70)]
    first = [asyncio.ensure_future(_ask(eng, p, 60, **sampling)) for p in prompts[:2]]
    while eng.steps < 6:  # both are in their windows
        await asyncio.sleep(0.01)
    late = [asyncio.ensure_future(_ask(eng, p, 12, **sampling)) for p in prompts[2:]]
    return await asyncio.wait_for(asyncio.gather(*first, *late), timeout=300)


@pytest.mark.parametrize("sampling", [{"temperature": 0.0}, {"temperature": 0.8, "seed": 5}],
                         ids=["greedy", "seeded"])
def test_long_prompts_beside_decoding_rows_emit_the_unfilled_engines_tokens(sampling):
    streams, counted, computed = {}, {}, {}
    for form in ("kernel", "xla", "unfilled"):
        eng = _small_engine(
            F32, slots=4, kv_dtype="float32", prefill_chunk=16, num_pages=96,
            pallas_interpret=form == "kernel", chained_decode=False,
        )
        plan, sizes = eng._plan_chunks, []
        if form == "unfilled":  # the filling switched off, in this test only
            plan = lambda remaining, *_: [min(n, 16) for n in remaining]  # noqa: E731

        def planned(*args, plan=plan, sizes=sizes):
            sizes.extend(out := plan(*args))
            return out

        eng._plan_chunks = planned
        before = _counts()
        eng.start()
        try:
            streams[form] = asyncio.run(_serve_long(eng, sampling))
        finally:
            eng.stop()
        counted[form], computed[form] = _since(before), sizes
    assert streams["kernel"] == streams["xla"] == streams["unfilled"]
    assert [len(s) for s in streams["kernel"]] == [60, 60, 12, 12]
    for form in streams:
        # Every prompt token prefilled is in one kind or the other.
        assert sum(counted[form].values()) == 11 + 14 + 93 + 70, counted
    assert counted["unfilled"]["fill"] == 0 and max(computed["unfilled"]) == 16
    # Two shares of 16 and two decode rows take 64 slots; the older prompt
    # got the 16 (kernel: 48 tiled) or 24 (reference: 62 tight) left over.
    assert counted["kernel"]["fill"] > 40 and counted["xla"]["fill"] > 40
    assert max(computed["kernel"]) >= 32 and max(computed["xla"]) >= 32


async def test_short_prompts_count_no_fill():
    eng = _small_engine(F32, slots=4, kv_dtype="float32", prefill_chunk=16, num_pages=64)
    eng.start()
    try:
        before = _counts()
        first = asyncio.ensure_future(_ask(eng, list(range(5, 14)), 30))
        while eng.steps < 4:
            await asyncio.sleep(0.01)
        await asyncio.gather(first, _ask(eng, list(range(20, 36)), 4), _ask(eng, [7, 8, 9], 4))
        assert _since(before) == {"share": 9 + 16 + 3, "fill": 0}
    finally:
        eng.stop()
