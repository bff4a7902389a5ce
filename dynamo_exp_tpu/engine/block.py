"""Generation by diffusion over blocks, on the engine's normal path
(``ModelConfig.block_length`` B > 0; docs/architecture.md "A step is a
token or a block pass").

A decoding row's unit of work is a *pass* over its block of B positions:
the block's open positions hold the mask token, a pass runs the model
over all B against the cache of everything before the block, and the
confidence rule (``ops/sampling.py::denoise_select``) fixes some of them.
After at most ``denoising_steps`` passes nothing is masked: the block's
tokens are emitted at once (``max_tokens`` and a stop token cut the last
block on emission) and the row opens its next. Every pass writes the
block's K/V into the row's pages before attention reads them (as a prompt
chunk's are), so a block in flight keeps no state on the device; but what
the last denoising pass wrote came of an input that still held mask
tokens. The final tokens' K/V are written by the FIRST pass of the block
after it, in the same forward: a query sees kv positions up to the end
of its own block, so a stream of the row's previous block (final tokens)
and then its new block (first pass) gives the one exactly the K/V a pass
of its own would write and the other exactly the logits it would read
after such a pass. Until then the row's newest block is *unwritten*
(``Sequence.unwritten``): the prefix index, offload and reclaim stop
short of it, and a row that finishes never has its last block written,
since nothing reads it.

What rides which program:

- **block** (``_block_program``; the ``windowed`` variants of a block
  model's lattice, so ``aot/`` and the benchmark's warm-up build them
  unchanged): a pure-decode dispatch. Every row's whole block, rows tiled
  ``B`` slots a tile, the routed experts reading their weights in place
  at any row count: an opening pass of two tiles a row (the unwritten
  block, where there is one, and this block's first pass), then the
  other passes chained on the device in one ``while_loop`` until nothing
  is masked (at most ``denoising_steps`` in all). The next block's inputs
  are known on the host (its start, all mask) but for the tokens of the
  block in flight, which stay on the device: block N+1 is launched before
  the host syncs on block N, with N's returned tokens as its carry
  (``_dispatch_chained_block``).
- **mixed** (``_mixed_block_program``): prompt chunks beside decoding rows,
  each of which rides as a span of B tokens making ONE pass, so prefill
  does not stall decode: a row with an unwritten block a pass that only
  writes it (the one pass left that fixes no token), any other row a
  denoising pass. The host keeps the block's state (``Sequence.blk_*``)
  between dispatches and the next dispatch of either kind goes on from it.

Both programs go through the engine's one launch and a finished block
through its one emission rule (docs/engine_perf.md "The life of a
dispatch"): the builders here fill the launch buffer and the rows and call
``TPUEngine._launch``; ``_consume_block`` takes what the passes fixed into
the host's block and hands a block with nothing masked left to
``TPUEngine._emit_row``, closing it (``_close_block``) between the appends
and the page registration, which stops short of an unwritten block.

A prompt of P tokens is prefilled up to its last block boundary
(``Sequence.prefill_target``); the ``P mod B`` tokens left open the first
block, already fixed (``CONTEXT``). Prefill samples nothing: logits are
the distribution of the token AT a position, and a row's first tokens
come with its first block. Chunks begin and end on block boundaries.
Penalties (frequency, presence, repetition) are not applied: a block's
positions are fixed out of order, and no published form says what counts
then. Temperature, top-k and top-p are, each draw keyed by (seed,
position, pass).
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..aot.launch import SAMPLER_FIELDS, launch_layout, unpack_launch
from ..models.llama import final_logits, forward_ragged
from ..ops.sampling import CONTEXT, TOP_LOGPROBS, UNFIXED, denoise_select
from ..telemetry import get_telemetry
from .scheduler import SeqState, Sequence, _RaggedRow


def block_pass(logits, tokens, fixed_at, passes, rows, mcfg, sampler, lps):
    """One denoising pass's outcome for the rows ``rows`` marks ([R]
    bool): ``tokens`` and ``fixed_at`` ([R, B]) with what the confidence
    rule fixed, and ``lps`` (None, or the log-probability, top ids and top
    log-probabilities [R, B, ..] so far) with that pass's at those
    positions."""
    with jax.named_scope("denoise_select"):
        out = denoise_select(
            logits, fixed_at, passes,
            steps=mcfg.denoising_steps,
            dynamic=mcfg.remasking_strategy == "low_confidence_dynamic",
            threshold=mcfg.confidence_threshold,
            sampler=sampler,
            top_n=TOP_LOGPROBS if lps is not None else None,
        )
        x0, newly = out[0], out[1] & rows[:, None]
        tokens = jnp.where(newly, x0, tokens)
        fixed_at = jnp.where(newly, passes[:, None], fixed_at)
        if lps is not None:
            lp, tid, tlp = lps
            lps = (
                jnp.where(newly, out[2], lp),
                jnp.where(newly[..., None], out[3], tid),
                jnp.where(newly[..., None], out[4], tlp),
            )
    return tokens, fixed_at, lps


def _no_lps(rows: int, block: int):
    return (
        jnp.zeros((rows, block), jnp.float32),
        jnp.zeros((rows, block, TOP_LOGPROBS), jnp.int32),
        jnp.zeros((rows, block, TOP_LOGPROBS), jnp.float32),
    )


class BlockDecode:
    """``TPUEngine``'s block-diffusion half: the two programs, their
    builders (one of them chained) and the consume. A mixin: everything
    else (scheduler, pages, prefix cache, launch buffer, the launch and
    the emission rule, telemetry) is the engine's own."""

    # ---------------------------------------------------------------- programs
    def _block_program(self, nb, pages, impl, full_sampler, want_lp):
        """The pure-decode variant of a block model: ``nb`` rows, a block
        each, every denoising pass of every block in one program. The
        opening pass runs ``2B`` slots a row: first the block before
        this one where that is final and not in the pages yet (``prev``;
        padding elsewhere), then this block's first pass. A pass writes
        its slots' K/V before attention reads them and a query sees
        nothing past its own block, so the earlier block's K/V are what a
        pass of its own would write and this block's logits what they
        would be after one. A ``while_loop`` makes the other passes at
        ``B`` slots a row and ends when nothing is masked: the block it
        returns is final and its K/V are the next dispatch's to write.

        Takes and returns what ``_windowed_program`` does (the counts it
        leaves alone included), so ``aot/warmup.py``'s and
        ``aot/compile.py``'s calls fit both; of the carry it reads the
        tokens ([nb, B]: the blocks the dispatch before returned, where
        ``chained``)."""
        interpret, mesh = self._attn_interpret, self.mesh
        mcfg = self.cfg.model
        B, T = mcfg.block_length, mcfg.denoising_steps
        layout = launch_layout(
            self.cfg, (nb, pages, True, full_sampler, want_lp, False)
        )
        rows = jnp.arange(nb, dtype=jnp.int32)
        offsets = jnp.arange(B, dtype=jnp.int32)[None, :]
        # A pass's stream and the slots that reach the head: a block a
        # row, or in the opening pass two, the second of which does.
        row_of, every = jnp.repeat(rows, B), jnp.arange(nb * B, dtype=jnp.int32)
        row_of2 = jnp.repeat(rows, 2 * B)
        own = (rows[:, None] * (2 * B) + B + offsets).reshape(-1)

        def run(params, k, v, carry_tokens, packed):
            f = unpack_launch(layout, packed)
            start, table = f["positions"], f["table"]
            live = start >= 0
            sampler = None
            if full_sampler:
                sampler = (
                    f["seeds"], start[:, None] + offsets, f["temp"],
                    f["top_k"], f["top_p"],
                )

            def masked(fixed_at):
                return live & jnp.any(fixed_at == UNFIXED, axis=1)

            def denoise(tokens, fixed_at, passes, k, v, lps, before=None):
                """One denoising pass of the rows with a masked position;
                ``before``: (tokens, positions) [nb, B] of the tile that
                precedes each row's block in the stream."""
                todo = masked(fixed_at)
                pos = jnp.where(todo[:, None], start[:, None] + offsets, -1)
                fed = jnp.where(fixed_at != UNFIXED, tokens, mcfg.mask_token_id)
                of, out = row_of, every
                if before is not None:
                    fed = jnp.concatenate([before[0], fed], axis=1)
                    pos = jnp.concatenate([before[1], pos], axis=1)
                    of, out = row_of2, own
                hidden, k, v = forward_ragged(
                    params, mcfg, fed.reshape(-1), pos.reshape(-1), of,
                    table, k, v, out, attn_pages=pages, attn_impl=impl,
                    q_tile=B, mesh=mesh, interpret=interpret,
                    project=False,
                )
                logits = final_logits(params, mcfg, hidden)
                tokens, fixed_at, lps = block_pass(
                    logits.reshape(nb, B, -1), tokens, fixed_at, passes,
                    todo, mcfg, sampler, lps,
                )
                return tokens, fixed_at, passes + todo.astype(jnp.int32), k, v, lps

            writes = live & (f["prev"] != 0)
            before = (
                jnp.where(f["chained"][0] != 0, carry_tokens, f["prev_tokens"]),
                jnp.where(writes[:, None], start[:, None] - B + offsets, -1),
            )
            had = jnp.maximum(f["passes"], 0)
            opened = denoise(
                f["tokens"], f["fixed_at"], had, k, v,
                _no_lps(nb, B) if want_lp else None, before,
            )
            c = jax.lax.while_loop(
                lambda c: (c[0] < T) & jnp.any(masked(c[2])),
                lambda c: (c[0] + 1, *denoise(*c[1:])),
                (jnp.int32(1), *opened),
            )
            _i, tokens, fixed_at, passes, k, v, lps = c
            ys = (tokens, fixed_at, passes - had, writes.astype(jnp.int32))
            return (ys + lps if want_lp else ys), k, v

        if full_sampler:

            @partial(jax.jit, donate_argnums=(1, 2, 6))
            def ragged_block(params, k, v, carry_tokens, carry_positions,
                             packed, counts_all):
                ys, k, v = run(params, k, v, carry_tokens, packed)
                return ys, k, v, counts_all, carry_tokens, carry_positions

        else:

            @partial(jax.jit, donate_argnums=(1, 2))
            def ragged_block(params, k, v, carry_tokens, carry_positions,
                             packed):
                ys, k, v = run(params, k, v, carry_tokens, packed)
                return ys, k, v, carry_tokens, carry_positions

        return ragged_block

    def _mixed_block_program(self, nb, pages, impl, full_sampler, want_lp):
        """The mixed variant of a block model: one ragged forward over
        prompt chunks (which sample nothing) and decoding rows' blocks,
        ONE pass each: the confidence rule on the rows that have masked
        positions, and the others' K/V written for good (a row's closed
        block that no opening pass of ``_block_program`` has written)."""
        interpret, mesh = self._attn_interpret, self.mesh
        mcfg = self.cfg.model
        B = mcfg.block_length
        B1 = self.cfg.max_decode_slots + 1
        q_tile = self._ragged_align()
        packed_from = (
            nb - self.cfg.ragged_packed_slots(nb) if impl == "pallas" else None
        )
        layout = launch_layout(
            self.cfg, (nb, pages, False, full_sampler, want_lp, False)
        )

        def run(params, k, v, packed):
            f = unpack_launch(layout, packed)
            blk_idx, fixed_at, passes = f["blk_idx"], f["fixed_at"], f["passes"]
            logits, k, v = forward_ragged(
                params, mcfg, f["tokens"], f["positions"], f["row_of"],
                f["table"], k, v, blk_idx.reshape(-1), attn_pages=pages,
                attn_impl=impl, q_tile=q_tile, packed_from=packed_from,
                mesh=mesh, interpret=interpret,
            )
            live = passes >= 0
            denoise = live & jnp.any(fixed_at == UNFIXED, axis=1)
            sampler = None
            if full_sampler:
                sampler = (
                    f["seeds"], f["positions"][blk_idx], f["temp"],
                    f["top_k"], f["top_p"],
                )
            tokens, fixed_at, lps = block_pass(
                logits.reshape(B1, B, -1), f["tokens"][blk_idx], fixed_at,
                jnp.maximum(passes, 0), denoise, mcfg, sampler,
                _no_lps(B1, B) if want_lp else None,
            )
            ys = (
                tokens, fixed_at, denoise.astype(jnp.int32),
                (live & ~denoise).astype(jnp.int32),
            )
            return (ys + lps if want_lp else ys), k, v

        if full_sampler:

            @partial(jax.jit, donate_argnums=(1, 2, 4))
            def ragged_mixed_block(params, k, v, packed, counts_all):
                ys, k, v = run(params, k, v, packed)
                return ys, k, v, counts_all

        else:

            @partial(jax.jit, donate_argnums=(1, 2))
            def ragged_mixed_block(params, k, v, packed):
                ys, k, v = run(params, k, v, packed)
                return ys, k, v

        return ragged_mixed_block

    # ------------------------------------------------------------- row state
    def _block_of(self, seq: Sequence) -> int:
        """The start of ``seq``'s block, opening it where none is: the
        tokens past the last block boundary (a prompt's tail) are its
        context, every other position the mask token."""
        mcfg = self.cfg.model
        B = mcfg.block_length
        start = len(seq.tokens) // B * B
        if seq.blk_tokens is None:
            pre = seq.tokens[start:]
            seq.blk_tokens = pre + [mcfg.mask_token_id] * (B - len(pre))
            seq.blk_fixed_at = [CONTEXT] * len(pre) + [UNFIXED] * (B - len(pre))
            seq.blk_lp = [None] * B
            seq.blk_passes = 0
        return start

    def _fill_block_row(self, seq: Sequence, r: int, f: dict, sampler) -> None:
        f["fixed_at"][r] = seq.blk_fixed_at
        f["passes"][r] = seq.blk_passes
        f["table"][r, : len(seq.page_ids)] = seq.page_ids
        self._row_sampler_args(seq, r, sampler)

    # ---------------------------------------------------------------- builders
    def _build_block(self, part, full_sampler: bool, solo: bool, chained=None):
        """Build + dispatch one pure-decode batch of a block model (no
        host sync): every row's block from where the host has it through
        its last denoising pass, and the write of the block before it
        where that one is not in the pages. ``part``: (row, seq, block
        start) a row; start -1 sits the dispatch out. With ``chained``
        (the pending dispatch before it) the rows are that one's, each
        block is the one after the block in flight, and the block to
        write is the one in flight: its tokens are what ``chained``
        returns, on the device."""
        with self._phase("assemble"), self._part("assemble", "build"):
            cfg = self.cfg
            B, ps = cfg.model.block_length, cfg.page_size
            nb = chained.nb if chained else cfg.ragged_tokens_bucket_for(len(part))
            packed, f = self._launch_buffer(
                nb, True, full_sampler,
                like=chained.packed if chained else None,
            )
            sampler = tuple(f[name] for name in SAMPLER_FIELDS)
            rows: list[_RaggedRow] = []
            max_pages = 1
            for r, seq, start in part:
                f["positions"][r] = start
                f["prev"][r] = 0
                if start >= 0:
                    if chained:  # a fresh block: nothing fixed, no pass made
                        f["fixed_at"][r] = UNFIXED
                        f["passes"][r] = 0
                        f["table"][r, : len(seq.page_ids)] = seq.page_ids
                        f["prev"][r] = 1  # the block in flight, on the device
                    else:
                        f["tokens"][r] = seq.blk_tokens
                        self._fill_block_row(seq, r, f, sampler)
                        if seq.unwritten:
                            f["prev"][r] = 1
                            f["prev_tokens"][r] = seq.tokens[start - B : start]
                    max_pages = max(max_pages, (start + B + ps - 1) // ps)
                rows.append(
                    _RaggedRow(
                        seq, "decode", r, n_valid=B if start >= 0 else 0,
                        start=start,
                    )
                )
            f["chained"][0] = chained is not None
            want_lp = chained.want_lp if chained else any(
                self._wants_logprobs(e.seq) is not None for e in rows
            )
        live = sum(1 for e in rows if e.start >= 0)
        get_telemetry().decode_batch_rows.labels("block").observe(live)
        # Of the window's carry a block model reads the tokens alone: the
        # blocks the dispatch in flight returns, on the device.
        return self._launch(  # dynlint: recompile-hazard(a chained block reuses the dispatched bucket)
            nb, max_pages, packed, rows, windowed=True,
            full_sampler=full_sampler, want_lp=want_lp, solo=solo,
            carry=(chained.ys[0], self._no_carry(nb)) if chained else None,
            program="block", rows=live, chained=chained is not None or None,
        )

    def _dispatch_chained_block(self, pending):
        """Dispatch block N+1 over block N's rows before the host syncs on
        N: a fresh block's inputs are its start and the mask token, both
        known here; the cache it reads and block N's final tokens, whose
        K/V its opening pass writes, are on the device. A row whose
        budget or model length block N exhausts sits the dispatch out
        (start -1); a stop token inside block N is found at its consume,
        and the row's block N+1 is then computed for nothing. None (chain
        break) when the pool cannot cover a row."""
        cfg = self.cfg
        B, ps = cfg.model.block_length, cfg.page_size
        part = []
        for e in pending.rows:
            seq, start = e.seq, e.start + B
            emits = B - (seq.blk_fixed_at or []).count(CONTEXT)
            sc = seq.stop.stop_conditions
            left = (sc.max_tokens or cfg.default_max_tokens) - seq.generated
            if (
                e.start < 0 or left <= emits
                or start + B > cfg.max_model_len
                or seq.state is not SeqState.ACTIVE
                or seq.pending_finish is not None
            ):
                part.append((e.row, seq, -1))
                continue
            self.sched.ensure_pages_until(seq, start + B - 1)
            if len(seq.page_ids) * ps < start + B:
                return None  # pool dry: consume + rebuild instead
            part.append((e.row, seq, start))
        if all(start < 0 for _r, _s, start in part):
            return None
        return self._build_block(part, pending.full_sampler, True, pending)

    def _build_mixed_block(self, dec, pf, full_sampler: bool):
        """Build + dispatch one mixed batch of a block model (no host
        sync): each prefilling row's next chunk (cut at block boundaries)
        and each decoding row's block as a span of B tokens making one
        pass. ``_build_mixed``'s stream, without sampling rows."""
        cfg = self.cfg
        B, ps = cfg.model.block_length, cfg.page_size
        with self._phase("assemble"), self._part("assemble", "plan"):
            sizes = self._plan_chunks(
                [seq.prefill_target(B) - seq.prefill_sent for seq in pf],
                [seq.admitted_at for seq in pf], 0, [B] * len(dec),
            )
        with self._phase("assemble"), self._part("assemble", "build"):
            align = self._ragged_align()
            up = lambda n: -(-n // align) * align  # noqa: E731
            nb = self._mixed_bucket_for(
                sum(map(up, sizes)) + up(B) * len(dec), 0
            )
            packed, f = self._launch_buffer(nb, False, full_sampler)
            tokens, positions, row_of = f["tokens"], f["positions"], f["row_of"]
            sampler = tuple(f[name] for name in SAMPLER_FIELDS)
            at = 0

            def add_span(toks, first_pos: int, r: int) -> int:
                nonlocal at
                start, n = at, len(toks)
                tokens[start : start + n] = toks
                positions[start : start + n] = np.arange(first_pos, first_pos + n)
                row_of[start : start + up(n)] = r
                at += up(n)
                return start

            rows: list[_RaggedRow] = []
            chunks: list[tuple[Sequence, int, int]] = []
            max_pages = 1
            r = 0
            for seq, n in zip(pf, sizes):
                self._apply_uploads(seq)
                first = seq.prefill_sent
                add_span(seq.prompt[first : first + n], first, r)
                seq.prefill_sent = first + n
                chunks.append((seq, first, n))
                f["table"][r, : len(seq.page_ids)] = seq.page_ids
                max_pages = max(max_pages, (seq.prefill_sent + ps - 1) // ps)
                rows.append(
                    _RaggedRow(
                        seq, "prefill", r,
                        completing=seq.prefill_sent == seq.prefill_target(B),
                    )
                )
                r += 1
            mask = cfg.model.mask_token_id
            for seq, start, _cap in dec:
                if seq.unwritten:
                    # The block before first: a pass that only writes it.
                    start -= B
                    qs = add_span(seq.tokens[start : start + B], start, r)
                    f["fixed_at"][r] = CONTEXT
                    f["passes"][r] = 0
                    f["table"][r, : len(seq.page_ids)] = seq.page_ids
                else:
                    fed = [
                        t if at_pass != UNFIXED else mask
                        for t, at_pass in zip(seq.blk_tokens, seq.blk_fixed_at)
                    ]
                    qs = add_span(fed, start, r)
                    self._fill_block_row(seq, r, f, sampler)
                f["blk_idx"][r] = qs + np.arange(B)
                max_pages = max(max_pages, (start + B + ps - 1) // ps)
                rows.append(_RaggedRow(seq, "decode", r, n_valid=B, start=start))
                r += 1
            assert self._mixed_bucket_for(at, 0) == nb, (at, nb)
            want_lp = any(
                self._wants_logprobs(e.seq) is not None for e in rows
            )
        pending = self._launch(
            nb, max_pages, packed, rows, windowed=False,
            full_sampler=full_sampler, want_lp=want_lp,
            live=int((positions >= 0).sum()), rows=len(rows), tokens=at,
        )
        self._count_attn_passes(nb, row_of, positions)
        self._note_prefill_chunks(pf, chunks, nb)
        self.steps += 1
        get_telemetry().decode_batch_rows.labels("mixed").observe(len(dec))
        return pending

    # ----------------------------------------------------------------- consume
    def _count_tile_rows(self, n_denoise, wrote) -> None:
        """Of a block program's attention calls, the live tiles by how a
        grid cell fetches their first chunk and by the schedule their pass
        works a chunk in (``dynamo_attn_tile_rows_total``): the kernel's own
        rules (``ops/ragged_attention.py::one_query_chain`` at
        ``one_query_block``'s tiles a cell, ``one_query_schedule``; each
        form of attention by its share of the layers) on what the dispatch
        says it made. A row with something masked rides every pass until
        nothing is, so row ``r`` was live in the first ``n_denoise[r]``
        passes; the opening pass's stream is two tiles a row, the block
        before (live where the pass wrote it) and the block. The reference
        has no tiles and counts nothing."""
        if self._attn_impl != "pallas":
            return
        from ..models.llama import one_query_rows, one_query_schedules
        from ..ops.ragged_attention import one_query_fetches

        model, tp = self.cfg.model, self.cfg.tp
        B = model.block_length
        opening = np.stack([wrote > 0, n_denoise > 0], axis=1).reshape(1, -1)
        later = np.arange(1, max(int(n_denoise.max(initial=0)), 1))
        counter = get_telemetry().attn_tile_rows
        for live in (opening, n_denoise[None, :] > later[:, None]):
            if not live.any():
                continue
            tiles = live.shape[1]
            fetches = one_query_fetches(
                np.where(live, 0, -1), one_query_rows(model, tiles, tp, B)
            )
            for schedule, share in one_query_schedules(model, tiles, tp, B).items():
                for fetch, count in zip(("behind", "exposed"), fetches):
                    counter.labels(fetch, schedule).inc(count * share)

    @staticmethod
    def _block_lp_pack(blk_lp, fixed_at, at, n_top: int, n: int):
        """``_emit_row``'s pack of a finished block's first ``n`` emitted
        positions (``at``), from what the host kept as each was fixed:
        the log-probabilities, the alternatives and, third, the pass
        each position was fixed at. None where a position has none."""
        at = at[:n]
        if any(blk_lp[j] is None for j in at):
            return None
        return (
            [blk_lp[j][0] for j in at],
            [blk_lp[j][1] for j in at] if n_top > 0 else None,
            [fixed_at[j] for j in at],
        )

    def _close_block(self, seq: Sequence, start: int) -> None:
        """A finished block's tokens are the row's: it opens its next,
        remembering that this one's K/V are not in the pages."""
        seq.close_block()
        seq.unwritten = len(seq.tokens) - start
        get_telemetry().diffusion_blocks.inc()

    def _consume_block(self, pending) -> None:
        """Host sync of one dispatch of a block model, of either program:
        each decoding row's block takes what its pass(es) fixed; a block
        with nothing masked left is emitted whole (cut by ``check_stop``)
        and the row opens its next, remembering that this one's K/V are
        not in the pages (``Sequence.unwritten``) until a later dispatch
        says it wrote them; a prompt whose last chunk this was joins
        decode. A stop found while a chained successor is in flight
        defers the finish until that one is consumed
        (``_finish_deferred``), as a window's does."""
        program = "block" if pending.windowed else "mixed"
        wrote_by = "next_first_pass" if pending.windowed else "own_pass"
        self._flush_outbox()
        with self._phase("sync"):
            ys = [np.asarray(y) for y in pending.ys]  # dynlint: sync-point(ragged consume)
        toks, fixed_at, n_denoise, wrote = ys[:4]
        if pending.want_lp:
            lps, top_ids, top_lps = ys[4:7]
        if self.profiler is not None:
            self.profiler.consume("ragged", pending.dispatched_at, program)
        B = self.cfg.model.block_length
        tel = get_telemetry()
        finishes: list[Sequence] = []
        emitted = wasted = passes_denoise = written = 0
        with self._phase("consume"):
            clock = time.monotonic
            parts = dict.fromkeys(
                ("tokens", "pages", "emit", "first_token"), 0.0
            )
            for e in pending.rows:
                seq, r = e.seq, e.row
                if e.kind == "prefill":
                    if e.completing:
                        t0 = clock()
                        self._promote_to_decode(seq)
                        self.sched.register_full_pages(seq)
                        parts["first_token"] += clock() - t0
                    continue
                if e.start < 0:
                    continue  # sat a chained dispatch out
                d, w = int(n_denoise[r]), int(wrote[r])
                passes_denoise += d
                written += w
                if w and seq.unwritten:
                    seq.unwritten = 0  # the block before is in the pages
                    tel.diffusion_block_writes.labels(wrote_by).inc()
                if (
                    seq.state is not SeqState.ACTIVE
                    or seq.pending_finish is not None
                ):
                    wasted += (d + w) * B  # past this row's stop
                    continue
                t0 = clock()
                start = self._block_of(seq)
                if e.start < start:  # the mixed program's pass wrote the block before
                    self.sched.register_full_pages(seq)
                    parts["pages"] += clock() - t0
                    continue
                assert start == e.start, (seq.request_id, e.start)
                n_top = self._wants_logprobs(seq)
                for j in range(B):
                    at_pass = int(fixed_at[r, j])
                    if seq.blk_fixed_at[j] != UNFIXED or at_pass == UNFIXED:
                        continue
                    seq.blk_tokens[j] = int(toks[r, j])
                    seq.blk_fixed_at[j] = at_pass
                    if n_top is not None and pending.want_lp:
                        seq.blk_lp[j] = (
                            float(lps[r, j]),
                            {
                                int(t): float(l) for t, l in
                                zip(top_ids[r, j, :n_top], top_lps[r, j, :n_top])
                            },
                        )
                seq.blk_passes += d
                parts["tokens"] += clock() - t0
                if UNFIXED in seq.blk_fixed_at:
                    continue
                # Nothing masked: the block is final. Emit what is not
                # context; the block closes once its tokens are the row's
                # and before its pages are registered, which stops short of
                # a block nothing has written.
                at = [j for j in range(B) if seq.blk_fixed_at[j] != CONTEXT]
                kept, reason = self._emit_row(
                    seq, [seq.blk_tokens[j] for j in at],
                    partial(self._block_lp_pack, seq.blk_lp, seq.blk_fixed_at, at),
                    parts, between=partial(self._close_block, seq, start),
                )
                emitted += kept
                if reason is not None:
                    seq.pending_finish = reason
                    finishes.append(seq)
            tel.diffusion_passes.labels("denoise").inc(passes_denoise)
            if pending.windowed:
                # The program's slots, known now that its passes are: every
                # pass carried the whole bucket, the opening one twice
                # (the blocks before, written as they rode it).
                loops = int(n_denoise.max(initial=0))
                live = (passes_denoise + written) * B
                self._count_slots(
                    "block", live, pending.nb * B * (loops + 1) - live
                )
                self._count_tile_rows(n_denoise, wrote)
                self.steps += loops
            else:
                tel.diffusion_passes.labels("write").inc(written)
            self.flight.record(
                "consume", dispatch="ragged", tokens=emitted, wasted=wasted
            )
            if wasted:
                self.wasted_steps += wasted
                tel.decode_wasted_steps.inc(wasted)
            if self.profiler is not None:
                self.profiler.add_parts("consume", **parts)
        self._finish_deferred(finishes)
