"""Continuous-batching scheduler: sequences, decode slots, admission.

This is the TPU replacement for the scheduling vLLM provided the
reference for free (SURVEY.md §2.9). Single-writer: all mutation happens
on the engine loop thread.

Policy (v2): **batched, chunked, decode-interleaved prefill.** Waiting
prompts are admitted to slots as soon as pages are available and then
prefilled in bucketed chunks, several sequences per dispatch — so a
burst of arrivals shares prefill forwards instead of serializing, and a
long prompt is fed a chunk at a time so decode steps interleave between
chunks instead of stalling behind one giant forward: ``prefill_chunk``
tokens guaranteed a dispatch, and whatever the dispatch's token bucket
would have left empty besides (``engine._plan_chunks``).
Decode runs every loop iteration over all ACTIVE slots; sequences whose
prompt is still being chunked sit in PREFILL state and don't join decode
until their first token is sampled.
"""

from __future__ import annotations

import enum
import time
from collections import deque
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Callable

if TYPE_CHECKING:
    import numpy as np

from itertools import islice

from ..protocols.common import BackendInput, FinishReason
from ..telemetry import get_telemetry
from ..tokens import chain_hash, compute_block_hash
from .config import EngineConfig
from .kv_manager import KvPageManager, no_part
from .tiering import KvFootprintForecast, select_packed_index


@dataclass
class RemoteKv:
    """Prefill computed elsewhere (disaggregation): the first sampled
    token plus the prompt's KV pages, host-bounced as numpy arrays of
    shape [L, page_size, Hkv, D] each (reference capability:
    ``RemotePrefillParams`` + NIXL block writes,
    ``/root/reference/container/deps/vllm/…patch:4175+``)."""

    first_token: int
    pages: "list[tuple[np.ndarray, np.ndarray]]"
    # Suffix-only transfer (docs/prefix_sharing.md): ``pages[i]`` is
    # prompt page ``skip_pages + i`` — the decode side already holds
    # the first ``skip_pages`` pages (pinned under ``pin_lease`` since
    # the routing decision; the engine releases the pin at inject).
    skip_pages: int = 0
    pin_lease: str | None = None


class SeqState(enum.Enum):
    WAITING = "waiting"  # queued, no slot/pages yet
    PREFILL = "prefill"  # slot + pages bound, prompt being chunked in
    ACTIVE = "active"  # decoding (device slot live)
    FINISHED = "finished"


@dataclass
class Sequence:
    """One in-flight request's full engine-side state."""

    request_id: str
    prompt: list[int]
    stop: "BackendInput"
    # emit(tokens, finish_reason, logprobs_pack=None) — the third arg is
    # the optional (per-token logprobs, top alternatives) payload.
    emit: Callable[..., None]
    is_cancelled: Callable[[], bool]
    state: SeqState = SeqState.WAITING
    slot: int = -1
    # A model whose sequences carry state beside their pages: the entry of
    # the state pool this sequence keeps its own in while bound, and the
    # entry its prefix hit restores from before the first dispatch
    # (``KvPageManager.take_state_slot`` / ``Allocation.state_restore``).
    state_slot: int = -1
    state_restore: int | None = None
    page_ids: list[int] = field(default_factory=list)
    # A cache by kind (``ModelConfig.kv_by_kind``): the row's pages of the
    # sliding layers' pool by page-in-sequence, taken a dispatch at a time,
    # and the first of them still held: those before it went back behind
    # the width (``kv_manager.SlidingPagePool``). Empty for any other model.
    sliding_ids: list[int] = field(default_factory=list)
    sliding_first: int = 0
    cached_len: int = 0  # prefix reused from the page pool
    tokens: list[int] = field(default_factory=list)  # prompt + confirmed
    generated: int = 0
    # Chunked prefill progress: prompt tokens already *dispatched* to the
    # device (including the reused prefix). The prompt is fully in flight
    # once prefill_sent == len(prompt).
    prefill_sent: int = 0
    # Chained hash state for registering full pages (router events + reuse).
    parent_hash: int | None = None
    hashed_pages: int = 0  # count of pages already registered
    # Set when the pool ran dry mid-decode; slot idles until a page frees.
    stalled: bool = False
    # When a *hard* stall began (the row cannot even feed its next
    # token): the KV-pressure preemption grace clock. 0.0 = not stalled.
    stalled_since: float = 0.0
    # Admission priority class (0=low, 1=normal, 2=high): the edge sheds
    # low first; KV-pressure preemption victimizes low first.
    priority: int = 1
    # End-to-end deadline (unix seconds, 0 = none), captured from the
    # request context at submission so the engine can reap expired work
    # from the waiting queue before it wastes prefill.
    deadline_unix: float = 0.0
    # KV-pressure preemptions suffered so far (bounded per request by
    # EngineConfig.max_preemptions_per_seq).
    preemptions: int = 0
    # Stop discovered while a chained decode window was still in flight:
    # the finish (and its page release) is deferred until that window is
    # consumed, so the device can't write into reallocated pages. The
    # on-device stop already flipped the row's position to -1, making
    # the in-flight window's output for it pure discard.
    pending_finish: "FinishReason | None" = None
    # A block-diffusion model's block in flight (``engine/block.py``):
    # its tokens, for each position the pass that fixed it
    # (``ops/sampling.py`` UNFIXED / CONTEXT), the log-probability pack of
    # that pass, and the denoising passes it has had. None between
    # blocks: preemption, resume and a prefix hit all restart a row at a
    # block's start, since a block in flight has no state worth keeping.
    blk_tokens: "list[int] | None" = None
    blk_fixed_at: "list[int] | None" = None
    blk_lp: "list | None" = None
    blk_passes: int = 0
    # The newest tokens of ``tokens`` whose K/V are not in the pages: a
    # block-diffusion model's last emitted block (B) until the next
    # dispatch writes it, which it does before it reads it. Whatever
    # trusts the pages (the prefix index, offload) stops short of them.
    unwritten: int = 0
    # G2→G1 injections the engine must dispatch before this prefill:
    # (page_id, seq_hash, k_page, v_page) per page (see kv_manager).
    pending_uploads: list = field(default_factory=list)
    # Prefix sharing (docs/prefix_sharing.md): attached pages another
    # sequence is still filling — this sequence's first prefill dispatch
    # waits until every one is filled (or claims orphans left by a dead
    # filler and re-fills them itself).
    wait_fill: list = field(default_factory=list)
    # Shared partial-tail page (radix partial_match attach): must be
    # made private (copy-on-write) before this sequence's first decode
    # write lands in it. -1 = none / already resolved.
    shared_tail_pid: int = -1
    # Prompt pages already marked filled with the page manager (the
    # engine marks [fill_marked, prefill_sent//ps) after each chunk
    # dispatch; claims of orphaned pages rewind it).
    fill_marked: int = 0
    # Chained hashes of all full prompt pages (from Allocation) so
    # register_full_pages never rehashes prompt tokens.
    prompt_hashes: list[int] = field(default_factory=list)
    # Disaggregation: KV pages precomputed by a remote prefill worker —
    # the engine injects them and skips the prefill compute entirely.
    remote_kv: "RemoteKv | None" = None
    # Prefill-extraction mode (this engine IS the remote prefill worker):
    # after prefill, gather the prompt's KV pages and hand them here as
    # (first_token, [(k_page, v_page), ...]).
    extract_cb: "Callable[[int, list], None] | None" = None
    # Suffix-only extraction: leading prompt pages the decode side
    # already holds (pinned there) — not gathered, not shipped.
    extract_skip: int = 0
    # Telemetry: the request's trace context (captured from the
    # submitting task's contextvar — the engine loop thread doesn't
    # share it) plus unix-time stage stamps the engine fills in.
    trace: "object | None" = None
    submitted_at: float = 0.0
    admitted_at: float = 0.0
    first_token_at: float = 0.0
    last_emit_at: float = 0.0
    # Set when the prompt KV was injected from a remote prefill worker.
    remote_prefilled: bool = False
    # Effective sampling seed (request's sampling_options.seed, or one
    # the engine drew at submission). Sampling is counter-based per row —
    # every draw is keyed by (sample_seed, absolute token position) — so
    # a request replayed with the same seed reproduces its tokens on any
    # instance, any batch shape (the failover-replay guarantee).
    sample_seed: int = 0
    # Speculative decoding (docs/speculative.md): this request's verify
    # dispatches, draft tokens proposed/accepted, and tokens emitted
    # through speculation — the decode span reports the per-request
    # tokens-per-dispatch the simulator's service-time fit consumes.
    spec_dispatches: int = 0
    spec_draft_tokens: int = 0
    spec_accepted_tokens: int = 0
    spec_emitted_tokens: int = 0
    # Predictive KV tiering (docs/engine_perf.md "Predictive KV
    # tiering"). Cached prompt block hashes for the admission footprint
    # forecast and the prefetch planner (invalidated by preemption
    # surgery — the prompt changes).
    forecast_hashes: "list[int] | None" = None
    # Packed admission: times this waiting sequence has been bypassed
    # by a smaller forecast (bounded by packing_max_defers).
    packing_defers: int = 0
    # Proactive offload: the row's swap record while its cold pages
    # live in the host tier (None = fully resident), when the swap
    # began, and how many times this row has been swapped out.
    swap: "object | None" = None
    swapped_since: float = 0.0
    swaps: int = 0
    # Request anatomy accumulators (telemetry/anatomy.py, docs/
    # observability.md "Request anatomy"). All loop-stamped wall-time
    # sums that survive preemption (preempt() resets decode state but
    # never these), so the finish-time decomposition covers the
    # request's whole life: first-admission queue wait, per-life
    # prefill/decode wall, compile stall inside prefill, swap/stall
    # windows inside decode, and preempt->re-admit requeue time.
    # anat_compile_mark carries the profiler's compile-seconds total at
    # admission; anat_preempted_at the wall time of the last preempt
    # (0 = not currently preempted); anat_page_s the page-residency
    # integral (final page count x slot-resident wall, accumulated at
    # each preempt/finish).
    anat_queue_s: float = 0.0
    anat_prefill_s: float = 0.0
    anat_decode_s: float = 0.0
    anat_compile_s: float = 0.0
    anat_swap_s: float = 0.0
    anat_preempt_s: float = 0.0
    anat_page_s: float = 0.0
    anat_compile_mark: float = 0.0
    anat_preempted_at: float = 0.0

    @property
    def pos(self) -> int:
        """Next token position to be confirmed-written."""
        return len(self.tokens)

    @property
    def written(self) -> int:
        """Positions whose K/V the row's pages hold: all but the newest
        token's (it lands on the next step) and a block model's newest
        block where no dispatch has written it (``unwritten``)."""
        return max(0, len(self.tokens) - self.unwritten - 1)

    def last_token(self) -> int:
        return self.tokens[-1]

    def prefill_target(self, block: int) -> int:
        """Prompt tokens prefill writes: all of them, or for a model that
        generates by diffusion over blocks of ``block`` positions the
        whole blocks among them (the rest open the first block, already
        fixed: their K/V is that block's to write)."""
        n = len(self.prompt)
        return n // block * block if block else n

    def close_block(self) -> None:
        """No block in flight (emitted, or dropped by a preemption)."""
        self.blk_tokens = self.blk_fixed_at = self.blk_lp = None
        self.blk_passes = 0


@dataclass
class _RaggedRow:
    """One row of a ragged dispatch (docs/engine_perf.md "One ragged
    dispatch"): a chunked-prefill span, a decode step/window, or a
    speculative verify span — all in the same flat query stream."""

    seq: Sequence
    kind: str  # "decode" | "prefill" | "spec"
    row: int  # per-row array index in the dispatch
    n_valid: int = 0  # decode: window steps the host may keep
    completing: bool = False  # prefill: prompt finishes this chunk
    counted: bool = False  # prefill: the first token drew as a decode row
    n_drafts: int = 0  # spec: drafts fed for verification
    start: int = -1  # a block model's decode row: its block's first position


def select_preemption_victim(candidates, max_preemptions: int):
    """Pure KV-pressure victim policy: lowest priority first, youngest
    (latest-submitted) on ties — the work with the least sunk cost and
    the weakest claim. Sequences at their preemption bound are exempt
    (they would otherwise live-lock re-prefilling forever), as are
    extract-mode sequences (disagg prefill workers: their one token is
    already sampled) and rows with a deferred finish in flight.

    Shared verbatim by the engine scheduler and the cluster simulator
    (``dynamo_exp_tpu/sim/``): ``candidates`` is any iterable of
    objects with the Sequence policy surface (``state``,
    ``pending_finish``, ``extract_cb``, ``preemptions``, ``priority``,
    ``submitted_at``). Returns None when nothing qualifies."""
    eligible = [
        s
        for s in candidates
        if s is not None
        and s.state is SeqState.ACTIVE
        and s.pending_finish is None
        and s.extract_cb is None
        and s.preemptions < max_preemptions
    ]
    if not eligible:
        return None
    return min(eligible, key=lambda s: (s.priority, -s.submitted_at))


# What a turn of ``Scheduler.admit_next``'s loop can come to: the
# ``outcome`` label space of ``dynamo_admit_attempts_total``.
ADMIT_OUTCOMES = ("admitted", "no_slot", "no_pages", "rejected", "dropped")


class Scheduler:
    def __init__(self, cfg: EngineConfig, kv: KvPageManager, flight=None):
        self.cfg = cfg
        self.kv = kv
        attempts = get_telemetry().admit_attempts
        self._attempts = {o: attempts.labels(o) for o in ADMIT_OUTCOMES}
        self.waiting: deque[Sequence] = deque()
        self.slots: list[Sequence | None] = [None] * cfg.max_decode_slots
        self.active_count = 0  # PREFILL + ACTIVE (slot holders)
        # Flight recorder (telemetry/flight.py, engine-owned): finish /
        # preemption events land in the ring alongside the loop's
        # dispatch events. None = recording off.
        self.flight = flight
        # Set by the engine: () -> dict of dispatch-profiler attrs to
        # attach to the decode span (sim/fit.py fits from them).
        self.span_attrs: Callable[[], dict] | None = None
        # Set by the engine: (seq, reason, now, was_bound) -> None,
        # called at finish before page release — the request-anatomy
        # assembly tap (telemetry/anatomy.py).
        self.on_finish: Callable | None = None
        # Footprint-packed admission (docs/engine_perf.md "Predictive
        # KV tiering"): None = plain first-fit FIFO.
        self.forecast = KvFootprintForecast(kv, cfg) if cfg.kv_packing else None

    # --------------------------------------------------------------- intake
    def submit(self, seq: Sequence) -> None:
        self.waiting.append(seq)

    def reap_waiting(self, now: float | None = None) -> int:
        """Drop cancelled and deadline-expired sequences *anywhere* in
        the waiting deque — not just at the head — so dead requests
        neither inflate queue-depth gauges / admission bounds nor waste
        a prefill when their turn comes. Returns the number reaped."""
        if not self.waiting:
            return 0
        now = time.time() if now is None else now
        kept: list[Sequence] = []
        reaped = 0
        for seq in self.waiting:
            if seq.is_cancelled():
                seq.state = SeqState.FINISHED
                seq.emit([], FinishReason.CANCELLED)
                reaped += 1
            elif seq.deadline_unix and now >= seq.deadline_unix:
                # Mirror of the prefill worker's pre-compute drop (PR 2):
                # the client has already given up; admitting would burn a
                # slot and a prefill on undeliverable work.
                seq.state = SeqState.FINISHED
                get_telemetry().deadline_exceeded.labels(
                    "engine_admission"
                ).inc()
                seq.emit([], FinishReason.ERROR)
                reaped += 1
            else:
                kept.append(seq)
        if reaped:
            self.waiting = deque(kept)
        return reaped

    def has_work(self) -> bool:
        return self.active_count > 0 or bool(self.waiting)

    def free_slot(self) -> int | None:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    def admit_next(self, part: Callable = no_part) -> Sequence | None:
        """Bind the next admissible waiting sequence to a slot + pages
        and put it in PREFILL state. Returns None if nothing can be
        admitted right now.

        ``part`` is the engine loop's opener of the named parts of its
        phase ``admit`` (``KvPageManager.allocate_sequence``): every
        turn of the loop below is ``schedule`` up to the allocation,
        whose parts are the page manager's, then ``bind`` and (uploads
        only) ``register``; and it counts once, by its outcome, in
        ``dynamo_admit_attempts_total``."""
        attempts = self._attempts
        while self.waiting:
            with part("schedule"):
                if self.waiting[0].is_cancelled():
                    seq = self.waiting.popleft()
                    seq.state = SeqState.FINISHED
                    seq.emit([], FinishReason.CANCELLED)
                    attempts["dropped"].inc()
                    continue
                head = self.waiting[0]
                if head.deadline_unix and time.time() >= head.deadline_unix:
                    # The engine-loop reap is throttled; never let expired
                    # work slip through admission in between scans.
                    self.waiting.popleft()
                    head.state = SeqState.FINISHED
                    get_telemetry().deadline_exceeded.labels(
                        "engine_admission"
                    ).inc()
                    head.emit([], FinishReason.ERROR)
                    attempts["dropped"].inc()
                    continue
                slot = self.free_slot()
                if slot is None:
                    attempts["no_slot"].inc()
                    return None
                seq = self._pick_admission()
                ps = self.kv.page_size
                if (
                    len(seq.prompt) > self.cfg.max_model_len
                    # A prompt needing more pages than the pool *has* can
                    # never be allocated — reject instead of waiting forever.
                    # Reachable from small prompts: a preempted sequence's
                    # continuation prompt is its full generated context,
                    # which can outgrow a pool smaller than max_model_len.
                    or (len(seq.prompt) + ps - 1) // ps > self.kv.num_pages
                    or self.cfg.bucket_for(
                        min(len(seq.prompt), self.cfg.prefill_chunk)
                    )
                    is None
                ):
                    self._remove_waiting(seq)
                    seq.state = SeqState.FINISHED
                    seq.emit([], FinishReason.ERROR)
                    attempts["rejected"].inc()
                    continue
            alloc = self.kv.allocate_sequence(
                seq.prompt, self.cfg.max_pages_per_seq, seq.request_id, part
            )
            if alloc is None:
                # Pool exhausted; retry after some decode frees.
                attempts["no_pages"].inc()
                return None
            with part("bind"):
                self._remove_waiting(seq)
                seq.page_ids, seq.cached_len = alloc.page_ids, alloc.cached_len
                seq.pending_uploads = alloc.uploads
                seq.prompt_hashes = alloc.hashes
                seq.wait_fill = list(alloc.wait_fill)
                seq.shared_tail_pid = (
                    alloc.shared_tail[0] if alloc.shared_tail else -1
                )
                # Registered full pages this sequence resumes its hash chain
                # after (G1 matches + G2 uploads; never the partial tail —
                # that page registers under ITS OWN chain once this
                # sequence's tokens complete it post-COW).
                seq.hashed_pages = alloc.cached_pages
                seq.fill_marked = alloc.cached_pages
                seq.parent_hash = (
                    alloc.hashes[seq.hashed_pages - 1]
                    if seq.hashed_pages else None
                )
                seq.tokens = list(seq.prompt)
                seq.prefill_sent = seq.cached_len
                block = self.cfg.model.block_length
                if block:  # chunks begin and end where blocks do
                    seq.prefill_sent = (
                        min(seq.cached_len, seq.prefill_target(block))
                        // block * block
                    )
                seq.slot = slot
                seq.state_slot = self.kv.take_state_slot()
                seq.state_restore = alloc.state_restore
                seq.state = SeqState.PREFILL
                self.slots[slot] = seq
                self.active_count += 1
            if seq.pending_uploads:
                with part("register"):
                    self._register_uploads(seq, alloc.hashes)
            attempts["admitted"].inc()
            return seq
        return None

    def _remove_waiting(self, seq: Sequence) -> None:
        """Drop one sequence from the waiting deque by identity (packed
        admission can pick past the head)."""
        for i, s in enumerate(self.waiting):
            if s is seq:
                del self.waiting[i]
                return

    def _pick_admission(self) -> Sequence:
        """The next sequence to try to admit: the head under plain
        first-fit, or — with footprint packing on — the first waiting
        sequence whose *lifetime* KV forecast fits the current
        free-page headroom (docs/engine_perf.md "Predictive KV
        tiering"). An oversize head that would be admitted only to
        hard-stall mid-decode defers behind smaller work; when nothing's
        forecast fits, the head is returned so packing never refuses an
        admission first-fit would have made. Priority and starvation
        guards live in :func:`~.tiering.select_packed_index`."""
        head = self.waiting[0]
        if self.forecast is None or len(self.waiting) == 1:
            return head
        headroom = self.forecast.headroom()
        cand = list(islice(self.waiting, self.cfg.packing_scan_limit))
        entries = [
            (
                self.forecast.forecast(s).fresh_pages <= headroom,
                s.priority,
                s.packing_defers,
            )
            for s in cand
        ]
        idx = select_packed_index(entries, self.cfg.packing_max_defers)
        if idx is None or idx == 0:
            return head
        for s in cand[:idx]:
            s.packing_defers += 1
        return cand[idx]

    def _register_uploads(self, seq: Sequence, hashes: list[int]) -> None:
        """Pages coming back from the host tier are about to be device-
        resident again: register them so G1 matching + the router index
        see them. Content lands at the inject dispatch (engine
        ``_apply_uploads``), so they register as pending fills — a
        same-prefix admission in between shares them but waits."""
        ps = self.kv.page_size
        first = seq.hashed_pages - len(seq.pending_uploads)
        parent = hashes[first - 1] if first > 0 else None
        for j, (pid, seq_hash, _, _) in enumerate(seq.pending_uploads):
            i = first + j
            block = seq.prompt[i * ps : (i + 1) * ps]
            self.kv.register_full_page(
                pid, seq_hash, parent_hash=parent, tokens=block,
                content_ready=False,
            )
            self.kv.begin_fill(pid, seq.request_id)
            parent = seq_hash

    # --------------------------------------------------------- fill gating
    def fill_ready(self, seq: Sequence) -> bool:
        """True once every attached shared page this sequence depends on
        has had its fill dispatched. Orphans (filler died first) are
        claimed here: this sequence re-fills fully covered blocks itself
        (identical content by determinism); an orphaned partial tail is
        detached onto a fresh private page instead — other holders may
        still need the original."""
        if not seq.wait_fill:
            return True
        ps = self.kv.page_size
        still: list[int] = []
        for pid in seq.wait_fill:
            state = self.kv.fill_state(pid)
            if state == "filled":
                continue
            if state == "pending":
                still.append(pid)
                continue
            # Orphaned: adopt or detach.
            idx = seq.page_ids.index(pid)
            if (idx + 1) * ps <= len(seq.prompt) and pid != seq.shared_tail_pid:
                self.kv.claim_fill(pid, seq.request_id)
                seq.prefill_sent = min(seq.prefill_sent, idx * ps)
                seq.cached_len = min(seq.cached_len, idx * ps)
                seq.fill_marked = min(seq.fill_marked, idx)
            else:
                fresh = self.kv.allocate_page()
                if fresh is None:
                    still.append(pid)  # pool dry: retry next iteration
                    continue
                seq.page_ids[idx] = fresh
                self.kv.release_sequence([pid])
                seq.shared_tail_pid = -1
                seq.prefill_sent = min(seq.prefill_sent, idx * ps)
                seq.cached_len = min(seq.cached_len, idx * ps)
        seq.wait_fill = still
        return not still

    # ------------------------------------------------------------- lifecycle
    def ensure_pages_until(self, seq: Sequence, position: int) -> bool:
        """Before a decode window writes up to ``position`` (inclusive):
        allocate every page the window will cross into. Returns False if
        the pool runs dry (the sequence sits this window out); pages
        allocated before the dry pop stay bound to the sequence, so the
        next attempt only needs the remainder."""
        ps = self.kv.page_size
        need = min(position, self.cfg.max_model_len - 1) // ps + 1
        while len(seq.page_ids) < need:
            pid = self.kv.allocate_page()
            if pid is None:
                break
            seq.page_ids.append(pid)
        # a cache by kind: the sliding pool's pages as far as the full one's
        self.ensure_sliding_until(seq, len(seq.page_ids) * ps - 1)
        return len(seq.page_ids) >= need

    def ensure_sliding_until(self, seq: Sequence, position: int) -> None:
        """A cache by kind: the row's sliding pages cover ``position``
        (inclusive) before the dispatch that writes up to it. The pool is
        sized so that this cannot fail (``SlidingPagePool``)."""
        if self.kv.sliding is not None and position >= 0:
            self.kv.sliding.extend(seq.sliding_ids, position)

    def release_sliding_behind(self, seq: Sequence) -> None:
        """A cache by kind, after a dispatch of the row is consumed: its
        sliding pages that no later query of it can see go back. The next
        query stands at the next prompt token to be fed while the row
        prefills, and at the newest token (the next one fed) once it
        decodes."""
        if self.kv.sliding is None or not seq.sliding_ids:
            return
        next_pos = (
            seq.prefill_sent if seq.state is SeqState.PREFILL
            else len(seq.tokens) - 1
        )
        seq.sliding_first = self.kv.sliding.release_behind(
            seq.sliding_ids, seq.sliding_first, next_pos
        )

    def _release_sliding(self, seq: Sequence) -> None:
        if self.kv.sliding is not None:
            self.kv.sliding.release(seq.sliding_ids, seq.sliding_first)
            seq.sliding_first = 0

    def register_full_pages(self, seq: Sequence) -> None:
        """Register every newly completed page for reuse + router events.

        Only the pages below the row's written frontier
        (``Sequence.written``) are whole."""
        ps = self.kv.page_size
        full = seq.written // ps
        while seq.hashed_pages < full:
            i = seq.hashed_pages
            block = seq.tokens[i * ps : (i + 1) * ps]
            if i < len(seq.prompt_hashes):
                # Pure-prompt page: the chained hash was already computed
                # at allocation; decode-era pages hash incrementally.
                seq_hash = seq.prompt_hashes[i]
            else:
                local = compute_block_hash(block)
                seq_hash = chain_hash(seq.parent_hash, local)
            self.kv.register_full_page(
                seq.page_ids[i], seq_hash, parent_hash=seq.parent_hash, tokens=block
            )
            seq.parent_hash = seq_hash
            seq.hashed_pages += 1

    def finish(self, seq: Sequence, reason: FinishReason) -> None:
        if seq.state == SeqState.FINISHED:
            return
        now = time.time()
        was_bound = seq.state in (SeqState.PREFILL, SeqState.ACTIVE)
        if seq.first_token_at and seq.extract_cb is None:
            # Close the request's decode span (first token -> finish).
            # Extract-mode sequences (disagg prefill workers) never
            # decode — their work ends at the first token.
            # Runs on the engine loop thread, so the trace context is
            # the one captured at submission, not a contextvar.
            get_telemetry().emit_stage(
                "decode",
                seq.first_token_at,
                now,
                seq.trace,
                generated_tokens=seq.generated,
                finish_reason=getattr(reason, "value", str(reason)),
                spec_tokens_per_dispatch=(
                    round(seq.spec_emitted_tokens / seq.spec_dispatches, 4)
                    if seq.spec_dispatches
                    else None
                ),
                pages=len(seq.page_ids),
                priority=seq.priority,
                swap_stall_s=(
                    round(seq.anat_swap_s, 6) if seq.anat_swap_s else None
                ),
                **(self.span_attrs() if self.span_attrs is not None else {}),
            )
        if self.flight is not None:
            self.flight.record(
                "finish",
                req=seq.request_id,
                slot=seq.slot if was_bound else None,
                reason=getattr(reason, "value", str(reason)),
                generated=seq.generated,
                pages=len(seq.page_ids),
                priority=seq.priority,
            )
        # Anatomy hook (engine._record_anatomy): runs before page
        # release so the page count is still real, with the same
        # ``now`` the decode span closed on.
        if self.on_finish is not None:
            self.on_finish(seq, reason, now, was_bound)
        seq.state = SeqState.FINISHED
        self._drop_unwritten(seq)
        if seq.slot >= 0 and was_bound:
            self.slots[seq.slot] = None
            self.active_count -= 1
            seq.slot = -1
            self._release_state(seq)
        # Fills this sequence owed but never dispatched orphan first so
        # sharers can claim them; THEN the refs drop (a zero-ref
        # unfilled page unregisters instead of parking as matchable).
        self.kv.abort_fills(seq.request_id, seq.page_ids)
        self.kv.release_sequence(seq.page_ids)
        self._release_sliding(seq)
        seq.emit([], reason)

    @staticmethod
    def _drop_unwritten(seq: Sequence) -> None:
        """A row leaves its pages (finished, or preempted: it comes back
        prefilling) with a block no dispatch has written: none will."""
        if seq.unwritten:
            get_telemetry().diffusion_block_writes.labels("never").inc()
            seq.unwritten = 0

    def _release_state(self, seq: Sequence) -> None:
        self.kv.release_state_slot(seq.state_slot)
        seq.state_slot, seq.state_restore = -1, None

    # ------------------------------------------------------------ preemption
    def preemption_victim(self, max_preemptions: int) -> Sequence | None:
        """The sequence KV-pressure preemption evicts next (policy in
        :func:`select_preemption_victim`, shared with the simulator)."""
        return select_preemption_victim(self.slots, max_preemptions)

    def preempt(self, seq: Sequence) -> None:
        """Unbind an ACTIVE sequence from its slot, release its pages,
        and requeue it as a deterministic continuation of itself.

        The released *registered* pages park in the reclaimable LRU
        (write-back to the host offload tier on eviction), so a prompt
        re-admission soon after usually prefix-hits most of its own
        context. The continuation re-enters as a fresh request whose
        prompt is the full generated context; counter-based sampling —
        every draw keyed by (seed, absolute position) — makes the
        resumed stream token-identical to the uninterrupted run, so the
        client-facing SSE stream stays gapless (the continuation emits
        only tokens past the splice). Requeues at the *back* of the
        waiting deque: re-admitting immediately would revive the pages
        just parked and starve the stalled rows the preemption was
        meant to feed."""
        k = seq.generated
        now = time.time()
        # Anatomy: close this life's decode segment and any open swap /
        # stall window, book the page-residency integral for the pages
        # about to be released, and mark preemption limbo — requeue
        # time until re-admission (or finish) counts as ``preemption``.
        if seq.first_token_at:
            seq.anat_decode_s += max(now - seq.first_token_at, 0.0)
        elif seq.admitted_at:
            seq.anat_prefill_s += max(now - seq.admitted_at, 0.0)
        if seq.swapped_since:
            seq.anat_swap_s += max(now - seq.swapped_since, 0.0)
        elif seq.stalled_since:
            seq.anat_swap_s += max(now - seq.stalled_since, 0.0)
        if seq.admitted_at:
            seq.anat_page_s += len(seq.page_ids) * max(
                now - seq.admitted_at, 0.0
            )
        seq.anat_preempted_at = now
        if self.flight is not None:
            self.flight.record(
                "preempt",
                req=seq.request_id,
                slot=seq.slot,
                generated=k,
                freed_pages=len(seq.page_ids),
            )
        if seq.slot >= 0:
            self.slots[seq.slot] = None
            self.active_count -= 1
            seq.slot = -1
            self._release_state(seq)
        self.kv.abort_fills(seq.request_id, seq.page_ids)
        self.kv.release_sequence(seq.page_ids)
        self._release_sliding(seq)
        seq.page_ids = []
        stop = seq.stop.model_copy(deep=True)
        sc = stop.stop_conditions
        orig_max = (
            sc.max_tokens
            if sc.max_tokens is not None
            else self.cfg.default_max_tokens
        )
        sc.max_tokens = max(orig_max - k, 1)
        if sc.min_tokens:
            sc.min_tokens = max(sc.min_tokens - k, 0)
        # Cumulative across preemptions: ``resume_offset`` marks how much
        # of the new prompt is journaled *completion* tokens, so the
        # sampler's penalty counts rebuild over all of them at re-prefill
        # (engine._finish_first_token).
        stop.resume_offset = (seq.stop.resume_offset or 0) + k
        stop.token_ids = list(seq.tokens)
        seq.stop = stop
        seq.prompt = list(seq.tokens)
        seq.tokens = []
        seq.generated = 0
        seq.prefill_sent = 0
        seq.cached_len = 0
        seq.stalled = False
        seq.stalled_since = 0.0
        seq.pending_finish = None
        seq.close_block()
        self._drop_unwritten(seq)
        seq.pending_uploads = []
        seq.wait_fill = []
        seq.shared_tail_pid = -1
        seq.fill_marked = 0
        seq.prompt_hashes = []
        seq.hashed_pages = 0
        seq.parent_hash = None
        seq.remote_kv = None
        seq.remote_prefilled = False
        # Tiering state: the continuation's prompt is new (forecast
        # hashes stale), its queue history resets, and any swap record
        # dies with the old page table (host-tier entries it referenced
        # simply age out of the LRU as unmatched cache).
        seq.forecast_hashes = None
        seq.packing_defers = 0
        seq.swap = None
        seq.swapped_since = 0.0
        seq.preemptions += 1
        seq.state = SeqState.WAITING
        self.waiting.append(seq)

    # -------------------------------------------------------------- stopping
    def check_stop(self, seq: Sequence, token: int) -> FinishReason | None:
        sc = seq.stop.stop_conditions
        min_tokens = sc.min_tokens or 0
        if seq.generated >= min_tokens:
            if not sc.ignore_eos and (
                token in self.cfg.eos_token_ids or token in sc.stop_token_ids
            ):
                return FinishReason.EOS
        max_tokens = sc.max_tokens or self.cfg.default_max_tokens
        if seq.generated >= max_tokens:
            return FinishReason.LENGTH
        if seq.pos >= self.cfg.max_model_len:
            return FinishReason.LENGTH
        return None

    # --------------------------------------------------------------- metrics
    def metrics(self) -> dict:
        """ForwardPassMetrics equivalent (reference:
        ``lib/llm/src/kv_router/protocols.rs:43-55``)."""
        running = sum(
            1 for s in self.slots if s is not None and s.state is SeqState.ACTIVE
        )
        return {
            "request_active_slots": self.active_count,
            "request_total_slots": self.cfg.max_decode_slots,
            "request_stalled_slots": sum(
                1 for s in self.slots if s is not None and s.stalled
            ),
            # Proactive offload (docs/engine_perf.md "Predictive KV
            # tiering"): ACTIVE rows whose cold pages currently live in
            # the host tier, awaiting swap-in.
            "request_swapped_slots": sum(
                1 for s in self.slots if s is not None and s.swap is not None
            ),
            "kv_active_blocks": self.kv.active_pages,
            "kv_total_blocks": self.kv.num_pages,
            "num_requests_waiting": len(self.waiting),
            "gpu_cache_usage_perc": self.kv.usage,
            "gpu_prefix_cache_hit_rate": self.kv.hit_rate(),
            # Engine-level gauges (telemetry): scheduler depth and decode
            # batch fill; the KV-tier gauges ride in via kv.gauges().
            "num_requests_running": running,
            "decode_batch_utilization": running / max(self.cfg.max_decode_slots, 1),
            **self.kv.gauges(),
        }
