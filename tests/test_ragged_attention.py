"""Ragged paged attention: one kernel + one dispatch for mixed
prefill+decode (docs/engine_perf.md "One ragged dispatch").

The engine's side of it, all on the CPU mesh (the kernels against the
reference: ``tests/test_ragged_kernels.py`` and
``tests/test_ragged_expanded.py``; the host's rules:
``tests/test_ragged_rules.py``):

1. **Dispatches** — the tp>1 shard_map dispatches ``models/llama`` uses,
   and the kernel bodies a latent model's mixed program traces.
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

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
from dynamo_exp_tpu.ops.ragged_attention import (
    query_block,
    ragged_paged_attention_ref,
)

from .ragged_cases import collect, flat_batch, mixed_engine, oracle_stream


# ------------------------------------------------- the tp>1 dispatches
def test_tp_shard_map_decode_dispatch():
    """The tp>1 path in models/llama._pallas_decode: heads sharded over
    the mesh, page pool kv-head-sharded, full tables replicated."""
    from dynamo_exp_tpu.models.llama import _pallas_decode
    from dynamo_exp_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(tp=4)
    lengths = [11, 0, 37, 25]
    spans = [(1, max(ln, 1)) for ln in lengths]
    H, Hkv, D, ps, pmax = 8, 4, 64, 16, 8
    q, k, v, table, row_of, positions = flat_batch(
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
    q, k, v, table, row_of, positions = flat_batch(
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


def test_tp_shard_map_block_dispatch():
    """The tp>1 path at a query block: a shard's heads a KV head are the
    model's, so every shard takes the block the whole model would."""
    from dynamo_exp_tpu.models.llama import _pallas_ragged
    from dynamo_exp_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(tp=4)
    spans = [(70, 90), (3, 11), (40, 40)]
    H, Hkv, D, ps, pmax = 8, 4, 64, 16, 8
    q, k, v, table, row_of, positions = flat_batch(
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
        # pages of 32 on a table of 4: four page copies a chunk of the
        # interpreted kernel, not sixteen (tests/ragged_cases.py "SHAPES")
        eng = mixed_engine(
            max_decode_slots=4, pallas_interpret=interpret, prefill_chunk=16,
            page_size=32,
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
            toks, _ = asyncio.run(collect(eng, prompt, 4))
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
def test_mixed_batch_identity_vs_two_program_oracle():
    """Greedy + seeded + penalized requests admitted in a staggered
    burst — so prefill chunks, decode steps, and both sampler
    partitions share ragged dispatches — each emit the exact stream
    the two-program oracle derives for them alone."""
    eng = mixed_engine()
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
                        collect(eng, p, 12, seed=seed, **sampling)
                    )
                )
                # Stagger: later requests arrive while earlier ones are
                # mid-prefill/decode, forcing mixed dispatches.
                await asyncio.sleep(0.05)
            return await asyncio.gather(*jobs)

        results = asyncio.run(burst())
        for p, (toks, so) in zip(prompts, results):
            want = oracle_stream(eng, p, 12, so.seed or 0, so)
            assert toks == want, (p, toks, want)
        # The burst really exercised mixed (non-windowed) dispatches.
        assert any(not key[2] for key in eng._ragged_fns)
    finally:
        eng.stop()


def test_seeded_identity_concurrent_vs_alone():
    """The same seeded request produces the same stream alone and in a
    concurrent mixed batch (counter-based draws never see layout)."""
    eng = mixed_engine()
    eng.start()
    try:
        prompt = list(np.random.RandomState(1).randint(3, 200, size=9))

        async def alone():
            return (await collect(eng, prompt, 10, seed=5, temperature=0.9))[0]

        async def crowded():
            noise = [
                collect(
                    eng,
                    list(np.random.RandomState(s).randint(3, 200, size=7)),
                    10,
                )
                for s in range(3)
            ]
            me = collect(eng, prompt, 10, seed=5, temperature=0.9)
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
    eng = mixed_engine(max_decode_slots=4)
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
                        collect(eng, list(rs.randint(3, 200, size=9)), 64),
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
                tagged("late", collect(eng, [7, 8, 9, 10], 6))
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

    # The kernel engine on pages of 32 (a table of 4: four page copies a
    # chunk to lower, not sixteen; its keys carry no page bound either way).
    eng = mixed_engine(
        max_decode_slots=4, pallas_interpret=interpret, page_size=32 if interpret else 8
    )
    assert eng._attn_impl == ("pallas" if interpret else "xla")
    eng.start()
    try:
        rs = np.random.RandomState(3)

        def prompt():
            return list(rs.randint(3, 200, size=10))

        async def mix(n_greedy, n_sampled):
            jobs = [collect(eng, prompt(), 8) for _ in range(n_greedy)]
            jobs += [
                collect(eng, prompt(), 8, seed=s, temperature=0.8)
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
            page_size=32,  # four page copies a chunk of the interpreted kernel
            num_pages=32,
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
    assert {p for _, p, _ in xla_windows} == {128 // 32}


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
