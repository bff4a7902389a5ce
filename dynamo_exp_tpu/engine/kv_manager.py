"""Host-side KV page pool: allocation, content-addressed prefix sharing,
LRU eviction, and KV event emission.

Capability parity with the reference's KV block manager
(``/root/reference/lib/llm/src/kv/reuse.rs:50-760`` — the
``AvailableBlocks`` match/take/update actor — and ``kv/manager.rs:22-168``
G1/G2 tiers), redesigned for the TPU engine:

- Device pages live in the paged pools allocated by ``models/llama.py``;
  this manager only tracks *ids* — all data movement happens inside the
  jitted forward (writes) or via host offload (``offload.py``).
- Reuse is content-addressed by the chained sequence hash of each full
  page (``tokens.py``), indexed in a radix tree
  (:class:`~dynamo_exp_tpu.kv.PrefixIndex`), so a new request's prompt
  prefix maps onto pages already resident in HBM. Matched pages are
  ref-counted and **shared across live sequences** — a page leaves G1
  only at refcount zero (docs/prefix_sharing.md).
- Sharing extends to pages still *being filled*: prompt pages are
  registered at allocation (``filled=False`` until their prefill chunk
  is dispatched), so a burst of same-prefix admissions attaches one
  copy instead of prefilling N. A filler that dies orphans its pending
  pages; a waiting sharer claims and re-fills them (deterministic
  forward ⇒ identical content).
- A prompt ending *inside* a registered block can attach that block as
  a shared partial tail (radix ``partial_match``); the first divergent
  write — the sequence's own decode into the shared page — triggers
  copy-on-write (:meth:`make_private`).
- Every registered/evicted full page emits a KV event (stored/removed)
  through a callback — the feed for the KV-aware router's radix index
  (reference: ``lib/llm/src/kv_router/publisher.rs:34-139``).
"""

from __future__ import annotations

import contextlib
import time
import uuid
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Callable, Sequence

from ..kv import PrefixIndex
from ..tokens import compute_block_hashes_for_seq

if TYPE_CHECKING:
    import numpy as np

    from .offload import HostKvPool


_NO_PART = contextlib.nullcontext()


def no_part(name: str, **stats):
    """The part-opener of a caller that times nothing (see
    ``allocate_sequence``): one shared no-op."""
    return _NO_PART


@dataclass
class PageRecord:
    page_id: int
    seq_hash: int | None = None  # None until the page is registered
    ref_count: int = 0
    # Content state for allocation-time registration: a prompt page is
    # registered (matchable) the moment it is allocated, but ``filled``
    # flips only once the write that materializes it has been
    # *dispatched* (stream order then protects later readers). While
    # unfilled, ``filler`` names the request responsible for the write;
    # a dead filler leaves it "" (orphaned) for a sharer to claim.
    filled: bool = True
    filler: str = ""
    # Which dispatch last wrote prompt tokens into this page (the
    # engine's own tag: program, bucket, tokens computed and found
    # cached), for the flight recorder's admission record. "" = only
    # decode steps, or nothing yet.
    writer: str = ""


@dataclass
class Allocation:
    """Result of ``allocate_sequence``.

    ``page_ids`` covers ceil(len(tokens)/page_size) pages; ``cached_len``
    counts tokens whose KV the sequence need not recompute — G1-matched
    + G2-uploaded full pages plus a shared partial tail, capped at
    len(tokens)-1 so prefill always computes the last token's logits;
    ``cached_pages`` counts the registered full pages among them (the
    scheduler's hash-chain resume point); ``uploads`` lists
    (page_id, seq_hash, k_page, v_page) host pages the engine must
    inject before prefill; ``wait_fill`` lists attached pages another
    sequence is still filling (the engine defers this sequence's first
    prefill dispatch until they are filled); ``shared_tail`` is the
    (page_id, covered_tokens) partial-tail attach, COWed before the
    first divergent write; ``hashes`` are the chained sequence hashes
    of every full prompt page (computed once here so the scheduler
    never rehashes the prompt)."""

    page_ids: list[int]
    cached_len: int
    uploads: list
    hashes: list[int]
    cached_pages: int = 0
    wait_fill: list[int] = field(default_factory=list)
    shared_tail: tuple[int, int] | None = None
    # Sequence state (a model with ``has_seq_state``): the pool entry
    # holding the state that belongs to token ``cached_len - 1``, which the
    # engine copies into the sequence's own entry before its first
    # dispatch. None where nothing is cached (or the model has no state).
    state_restore: int | None = None


@dataclass
class KvLease:
    """A pin on extracted pages during a disaggregation KV handoff.

    The prefill worker extracts a sequence's pages for the wire while
    the owning sequence finishes — without a lease the pages would park
    in the reclaimable LRU and could be evicted (or, under the handoff
    contract, be considered delivered) before the decode worker confirms
    receipt. The lease takes one extra reference per page; delivery
    confirmation (``confirm_lease``) releases it, and the reaper
    (``reap_expired``) reclaims orphans when the decode instance dies
    between extract and inject — so failover never strands HBM. The
    decode side reuses the same pin for suffix-only transfers: matched
    local prefix pages stay resident between the routing decision and
    the admission that re-references them.

    State machine (docs/fault_tolerance.md "Resumable streams"):
    GRANTED → CONFIRMED (transfer acked end-to-end) | EXPIRED (reaped).
    """

    lease_id: str
    page_ids: list[int]
    expires_at: float  # manager-clock seconds


@dataclass
class KvEvent:
    """Stored/removed notification for the router's radix index."""

    kind: str  # "stored" | "removed"
    seq_hashes: list[int]
    parent_hash: int | None = None
    token_blocks: list[list[int]] | None = None  # only on stored
    ts: float = field(default_factory=time.time)


class SlidingPagePool:
    """The sliding layers' page pool of a cache by kind
    (``ModelConfig.kv_by_kind``), by id: a page is free or held by ONE row,
    never shared, registered or parked, because a sliding layer's keys say
    nothing without their ``width - 1`` predecessors and are of no use to
    another request's prefix.

    A row's pages are a list indexed by page-in-sequence
    (``Sequence.sliding_ids``) of which the entries before
    ``Sequence.sliding_first`` have been given back (0 stands there: the
    kernel's walk starts behind them and the gather masks them):

    - :meth:`extend` takes pages until the list covers a position, before
      the dispatch that writes it;
    - :meth:`release_behind`, once a dispatch is consumed, gives back the
      pages that lie wholly before ``next_pos - (width - 1)``: the row's
      next query stands at ``next_pos`` or later and sees no key before
      that (``t - width < s``), so no query of the row can see them again.
      A window chained onto the consumed one is already in flight, and its
      queries stand later still;
    - :meth:`release` gives back all a row holds (finish, preemption).

    So a row holds the pages of ``[next_pos - (width - 1), last position
    its dispatch in flight writes]``, and the pool is sized from the
    dispatch, not from the context (``EngineConfig.sliding_pool_pages``):
    it cannot run dry, and :meth:`extend` raises if it does. Single-writer
    as the manager that owns it: the engine loop's thread."""

    def __init__(self, num_pages: int, page_size: int, width: int):
        self.num_pages = num_pages
        self.page_size = page_size
        self.width = width
        self._free: list[int] = list(range(num_pages - 1, -1, -1))
        self.held = 0
        self.peak_held = 0
        self.released_total = 0  # pages given back behind the width

    @property
    def usage(self) -> float:
        return self.held / max(self.num_pages, 1)

    def extend(self, ids: list[int], position: int) -> None:
        """Take pages until ``ids`` covers ``position``."""
        need = position // self.page_size + 1
        while len(ids) < need:
            if not self._free:
                raise RuntimeError(
                    f"the sliding page pool ({self.num_pages} pages) ran dry: "
                    "it is sized from the largest dispatch "
                    "(EngineConfig.sliding_pool_pages), so this is a fault "
                    "of that arithmetic or a row that kept pages it cannot see"
                )
            ids.append(self._free.pop())
            self.held += 1
        self.peak_held = max(self.peak_held, self.held)

    def release_behind(self, ids: list[int], first: int, next_pos: int) -> int:
        """Give back ``ids[first:keep]``, the held pages wholly before the
        first position a query at ``next_pos`` sees; returns ``keep``, the
        row's new first held page."""
        keep = min(max(next_pos - (self.width - 1), 0) // self.page_size, len(ids))
        for j in range(first, keep):
            self._free.append(ids[j])
            ids[j] = 0
        if keep > first:
            self.held -= keep - first
            self.released_total += keep - first
        return max(first, keep)

    def release(self, ids: list[int], first: int) -> None:
        """Give back every page a row still holds."""
        self._free.extend(ids[first:])
        self.held -= len(ids) - first
        del ids[:]

    def ledger_check(self) -> list[str]:
        if len(self._free) + self.held != self.num_pages or self.held < 0:
            return [
                f"sliding-page conservation broken: free={len(self._free)} "
                f"held={self.held} != pool={self.num_pages}"
            ]
        return []

    def audit(self, holders: dict[str, Sequence[int]]) -> list[dict]:
        """Full scan: every page is free or held by exactly one of
        ``holders`` (name -> the page ids it believes it holds)."""
        owner: dict[int, str] = {}
        violations: list[dict] = []

        def flag(pid, kind, detail, names):
            violations.append({
                "page": pid, "kind": kind, "detail": detail,
                "holders": sorted(names), "pool": "sliding",
            })

        for name, pids in holders.items():
            for pid in pids:
                if pid in owner:
                    flag(pid, "shared_sliding_page",
                         "a sliding page has one holder", [owner[pid], name])
                owner[pid] = name
        free = set(self._free)
        if len(free) != len(self._free):
            flag(None, "double_release", "a page appears twice in the free list", [])
        for pid in range(self.num_pages):
            if (pid in free) == (pid in owner):
                flag(
                    pid, "state_overlap" if pid in free else "leaked_ref",
                    "free and held" if pid in free else "neither free nor held",
                    [owner[pid]] if pid in owner else [],
                )
        return violations


class KvPageManager:
    """Tracks ownership and reuse of the device page pool by id.

    Not thread-safe by design: owned by the engine loop thread, the same
    single-writer discipline the reference uses for its block pool actor.
    """

    def __init__(
        self,
        num_pages: int,
        page_size: int,
        event_cb: Callable[[KvEvent], None] | None = None,
        host_pool: "HostKvPool | None" = None,
        on_evict: Callable[[int, int], None] | None = None,
        clock: Callable[[], float] = time.monotonic,
        sharing: bool = True,
        g3_store=None,
        state_slots: int = 0,
        state_snapshots: int = 0,
        partial_tail: bool = True,
        sliding: "SlidingPagePool | None" = None,
    ):
        self.num_pages = num_pages
        self.page_size = page_size
        # The second pool of a cache by kind (None: one pool): pages of the
        # sliding layers, private to a row and given back behind the width.
        # Everything else here (sharing, leases, tiers, ``usage``) is of
        # the full layers' pool, whose pages hold a context for as long as
        # the row lives; the engine builds such a model's manager with
        # ``sharing=False`` (a prefix hit would need the sliding layers'
        # keys of the ``width - 1`` positions before the boundary, which
        # were given back).
        self.sliding = sliding
        # Whether a prompt that ends inside a block another sequence
        # registered attaches that page read-shared (``allocate_sequence``).
        # Off for a model that generates by diffusion over blocks: its
        # prompt's tail is rewritten with the first block's every pass.
        self.partial_tail = partial_tail
        self.event_cb = event_cb
        self.clock = clock
        # Fleet-wide prefix sharing (docs/prefix_sharing.md). False is
        # the private-copy baseline: no cross-sequence reuse at all —
        # every admission materializes its own pages (the reference
        # of tests/test_prefix_sharing.py).
        self.sharing = sharing
        # G2 tier: evicted device pages are offloaded (via ``on_evict``,
        # which the engine wires to a device gather + CopyStream) and
        # matched back in from ``host_pool`` on later prompts.
        self.host_pool = host_pool
        self.on_evict = on_evict
        # G3 tier (docs/fault_tolerance.md "Durable KV"): the persistent
        # checksummed page store. Admission extends a G1+G2 match into
        # it; each fetched page is checksum-verified by the store and
        # promoted through the host pool (a corrupt page quarantines
        # there and just shortens the restored prefix).
        self.g3_store = g3_store
        self._records: dict[int, PageRecord] = {
            i: PageRecord(i) for i in range(num_pages)
        }
        self._free: list[int] = list(range(num_pages - 1, -1, -1))
        # seq_hash -> page_id for every registered full page still
        # resident, plus the radix index over the same blocks (the
        # structure the partial-tail lookup and the router share).
        self._by_hash: dict[int, int] = {}
        self.index = PrefixIndex()
        # Zero-ref registered pages, LRU order (oldest first).
        self._reclaimable: OrderedDict[int, None] = OrderedDict()
        # Disaggregation handoff leases, by lease id (single-writer like
        # everything else here: only the engine loop thread touches them).
        self._leases: dict[str, KvLease] = {}
        self.lease_reclaimed_pages = 0  # pages freed by the reaper
        # Metrics counters.
        self.hits = 0
        self.misses = 0
        # G2 (host offload tier) hit/miss: of the pages a prompt needed
        # beyond its G1 device match, how many the host tier supplied.
        self.offload_hits = 0
        self.offload_misses = 0
        # Prefix-sharing counters (docs/prefix_sharing.md): page-granular
        # hit breakdown at admission, copy-on-write copies, and the
        # high-water mark of resident pages (tests/test_prefix_sharing.py
        # reads pages-per-request off the peak).
        # "persist" = pages restored from the G3 store at admission —
        # the restart re-attachment proof the identity tests read.
        self.prefix_hits = {"shared": 0, "restore": 0, "persist": 0, "miss": 0}
        self.cow_copies = 0
        self.peak_active_pages = 0
        # Incrementally tracked (refcount 1→2 / 2→1 crossings), so the
        # gauge and the high-water mark never scan the pool.
        self.live_shared = 0
        self.peak_shared_pages = 0
        # Conservation ledger (docs/observability.md "KV conservation
        # auditor"): pages currently referenced (ref_count >= 1) and the
        # refcount grand total, both maintained at the SAME transitions
        # that move pages between the free list, the parked LRU, and the
        # held set — so ``ledger_check`` is pure counter arithmetic
        # (O(1), no pool scan, no device work). A double-release or a
        # lost reference breaks the arithmetic within the very mutation
        # that caused it.
        self._held_pages = 0
        self._ref_total = 0
        # Leases reclaimed by the most recent reap_expired() call —
        # (lease_id, pages) pairs the engine reads to close lease spans.
        self.last_reaped: list[tuple[str, int]] = []
        # Sequence state beside the pages (a model whose layers are not all
        # attention: ``ModelConfig.has_seq_state``; 0 slots = none). The
        # device pool (``models/llama.py::init_seq_state``) has
        # ``state_slots + state_snapshots`` entries and this manager
        # tracks them by id, as it does pages:
        #
        # - entries ``[0, state_slots)`` are live: one a bound sequence,
        #   taken at admission and released at finish or preemption, in
        #   the same ledger as the pages (``ledger_check``);
        # - the rest are snapshots: the state as it stood at the end of a
        #   registered block, keyed by that block's chained hash, written
        #   by the prefill dispatch that crossed the boundary and kept
        #   LRU. A prefix hit is only as deep as the deepest matched block
        #   that has one: the next token's convolution needs the products
        #   of the very tokens the hit skips, so a hit without them would
        #   serve wrong numbers without any error.
        #
        # WHY THIS FORM. A state beside every cached block would make every
        # hit whole, at 96 KiB beside a block's 128 KiB of K and V (12
        # convolution layers, 4 attention) and a write of it every 16
        # tokens of every prefill; for a scanned state it is 9.32 MB
        # beside a block's 16 KiB (26 Mamba layers, 2 attention). Taking
        # no hit at all costs a re-prefill of every shared prompt. One snapshot a prefill dispatch and row,
        # at the deepest block boundary the chunk crosses, costs one more
        # row in a scatter the forward makes anyway, and covers what
        # prefix sharing is for: a prompt asked again, a resumed sequence,
        # and prompts that share a head of whole chunks.
        self.state_slots = state_slots
        self.state_snapshots = state_snapshots
        self._free_state: list[int] = list(range(state_slots - 1, -1, -1))
        self._held_state = 0
        self._snap_by_hash: OrderedDict[int, int] = OrderedDict()  # LRU
        self._free_snaps: list[int] = list(
            range(state_slots + state_snapshots - 1, state_slots - 1, -1)
        )
        # Admissions by what their prefix match came to for want of a
        # state: taken whole, shortened to a deeper-held boundary, or not
        # taken (pages matched and no boundary held).
        self.state_hits = {"restored": 0, "shortened": 0, "not_taken": 0}
        # Pages handed out by where they came from: the free list, or a
        # parked page reclaimed (its registration leaves the index).
        self.pages_taken = {"free": 0, "evicted": 0}

    # ---------------------------------------------------------------- stats
    @property
    def free_pages(self) -> int:
        return len(self._free) + len(self._reclaimable)

    @property
    def active_pages(self) -> int:
        return self.num_pages - self.free_pages

    @property
    def usage(self) -> float:
        return self.active_pages / max(self.num_pages, 1)

    @property
    def shared_pages(self) -> int:
        """Pages currently attached by more than one holder."""
        return self.live_shared

    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def offload_hit_rate(self) -> float:
        total = self.offload_hits + self.offload_misses
        return self.offload_hits / total if total else 0.0

    def gauges(self) -> dict:
        """Engine-level KV gauges for the telemetry registry."""
        return {
            "hbm_page_occupancy": self.usage,
            "offload_hit_rate": self.offload_hit_rate(),
            "kv_shared_pages": self.shared_pages,
            # G2 tier occupancy (docs/engine_perf.md "Predictive KV
            # tiering"): host-resident pages, so fleet views see
            # host-tier pressure (mirrored as dynamo_kv_host_pages).
            "kv_host_pages": self.host_pool.resident if self.host_pool else 0,
            # G3 tier occupancy (docs/fault_tolerance.md "Durable KV"):
            # store-resident pages (mirrored as dynamo_kv_store_pages).
            "kv_store_pages": self.g3_store.resident if self.g3_store else 0,
            "seq_state_slots_in_use": self._held_state,
        }

    # -------------------------------------------------------- sequence state
    def take_state_slot(self) -> int:
        """The pool entry a sequence being bound keeps its state in (-1
        for a model without state). There are as many as decode slots,
        so a sequence that got a slot gets one."""
        if not self.state_slots:
            return -1
        self._held_state += 1
        return self._free_state.pop()

    def release_state_slot(self, slot: int) -> None:
        if slot >= 0:
            self._held_state -= 1
            self._free_state.append(slot)

    def snapshot_entry(self, seq_hash: int) -> int | None:
        """The pool entry the state at the end of block ``seq_hash`` is to
        be written to by the dispatch being assembled, or None where one
        is held already (it is then only marked used). The oldest snapshot
        goes when none is free. Registered at once: the caller's dispatch
        precedes any admission that could restore from it."""
        if not self.state_snapshots or not self.sharing:
            return None
        if seq_hash in self._snap_by_hash:
            self._snap_by_hash.move_to_end(seq_hash)
            return None
        if self._free_snaps:
            entry = self._free_snaps.pop()
        else:
            _, entry = self._snap_by_hash.popitem(last=False)
        self._snap_by_hash[seq_hash] = entry
        return entry

    def _deepest_state(self, hashes: list[int]) -> int:
        """How many of the matched blocks ``hashes`` a hit may take: up to
        the deepest whose end has a snapshot."""
        for j in range(len(hashes), 0, -1):
            if hashes[j - 1] in self._snap_by_hash:
                return j
        return 0

    def _note_active(self) -> None:
        active = self.active_pages
        if active > self.peak_active_pages:
            self.peak_active_pages = active

    # ------------------------------------------------------------ allocation
    def match_prefix(
        self, tokens: Sequence[int], require_filled: bool = False
    ) -> tuple[list[int], list[int]]:
        """Longest resident prefix of ``tokens`` in full pages.

        Returns (page_ids, seq_hashes) of the matched prefix — does NOT
        take references; call ``allocate_sequence`` to commit.
        ``require_filled`` stops the walk at the first page whose
        content has not been dispatched yet (the disagg pin path must
        only count bytes that exist)."""
        return self._match_hashes(
            compute_block_hashes_for_seq(tokens, self.page_size),
            require_filled=require_filled,
        )

    def _match_hashes(
        self, hashes: list[int], require_filled: bool = False
    ) -> tuple[list[int], list[int]]:
        pages: list[int] = []
        matched: list[int] = []
        for h in self.index.match_hashes(hashes):
            pid = self._by_hash.get(h)
            if pid is None:
                break  # index/by-hash drift would be a bug; stay safe
            if require_filled and not self._records[pid].filled:
                break
            pages.append(pid)
            matched.append(h)
        return pages, matched

    def allocate_sequence(
        self,
        tokens: Sequence[int],
        max_pages: int,
        request_id: str = "",
        part: Callable = no_part,
    ) -> Allocation | None:
        """Pages for a new sequence: attach the longest device-resident
        (G1) shared prefix — including pages still being filled and a
        partial tail inside a registered block — extend it from the
        host tier (G2), then fresh pages for the rest of the prompt.
        Freshly allocated full prompt pages are registered immediately
        (``filled=False``) so concurrent same-prefix admissions share
        them instead of re-prefilling.

        Returns an ``Allocation`` or None if the pool can't satisfy the
        request right now (caller re-queues).

        ``part`` is the caller's opener of a named stretch (``name ->
        context manager``; keywords are the stats of a trace event):
        the engine loop's, for the parts ``hash``, ``match``, ``take``
        and ``register`` of its phase ``admit``. A try that returns None
        has opened ``hash`` and ``match`` all the same.
        """
        ps = self.page_size
        n_tokens = len(tokens)
        need_total = (n_tokens + ps - 1) // ps
        if need_total > max_pages:
            return None  # exceeds per-sequence capacity; caller must reject
        with part("hash", req=request_id, prompt_tokens=n_tokens):
            hashes = compute_block_hashes_for_seq(tokens, ps)
        with part("match"):
            state_restore: int | None = None
            if self.sharing and self.state_slots:
                # Filled pages only: a snapshot's writer has dispatched every
                # block before its boundary, so nothing is lost, and a sharer
                # that had to re-fill an orphan would rewind to a position it
                # holds no state for. No partial tail for the same reason.
                matched_pages, matched_hashes = self._match_hashes(
                    hashes, require_filled=True
                )
                # The prompt's last token is always computed.
                reach = min(len(matched_pages), (n_tokens - 1) // ps)
                keep = self._deepest_state(matched_hashes[:reach])
                if matched_pages:
                    outcome = (
                        "restored" if keep == reach
                        else "shortened" if keep else "not_taken"
                    )
                    self.state_hits[outcome] += 1
                matched_pages = matched_pages[:keep]
                matched_hashes = matched_hashes[:keep]
                if keep:
                    self._snap_by_hash.move_to_end(matched_hashes[-1])
                    state_restore = self._snap_by_hash[matched_hashes[-1]]
            elif self.sharing:
                matched_pages, matched_hashes = self._match_hashes(hashes)
            else:
                matched_pages, matched_hashes = [], []
            # Extend the match into the host tier — match first (no copies);
            # pages are fetched only once the allocation is known to succeed,
            # so a pool-exhausted retry loop never repeats the memcpys.
            g2_hashes: list[int] = []
            if self.sharing and self.host_pool is not None:
                g2_hashes = self.host_pool.match_chain(
                    hashes[len(matched_pages) :]
                )
            # Extend further into the G3 persistent store (membership only;
            # bytes are checksum-verified at fetch below) — the path a
            # returning conversation re-attaches through after a restart.
            g3_hashes: list[int] = []
            if self.sharing and self.g3_store is not None:
                g3_hashes = self.g3_store.match_chain(
                    hashes[len(matched_pages) + len(g2_hashes) :]
                )
            # Shared partial tail: the prompt ends inside a block some other
            # sequence registered — attach that page read-shared; the owner
            # COWs it before its first divergent (decode) write.
            shared_tail: tuple[int, int] | None = None
            tail_tokens = tokens[(n_tokens // ps) * ps :]
            if (
                self.sharing
                and self.partial_tail
                and not self.state_slots
                and tail_tokens
                and not g2_hashes
                and not g3_hashes
                and len(matched_pages) == n_tokens // ps
            ):
                parent = matched_hashes[-1] if matched_hashes else None
                hit = self.index.partial_match(parent, tail_tokens)
                if hit is not None:
                    tpid = self._by_hash.get(hit[0])
                    if tpid is not None:
                        shared_tail = (tpid, hit[1])
            need_fresh = (
                need_total - len(matched_pages) - (1 if shared_tail else 0)
            )
            # Matched parked pages are about to leave the reclaimable LRU
            # (_ref_page below); counting them as takeable here would let
            # _take_free pop an empty LRU and crash the engine loop.
            attach = matched_pages + ([shared_tail[0]] if shared_tail else [])
            parked_matches = sum(
                1 for pid in attach if self._records[pid].ref_count == 0
            )
            if need_fresh > self._available_for_take() - parked_matches:
                return None
        with part("take"):
            # fetch() copies each page out under the pool lock, so a
            # concurrent LRU eviction can't corrupt it before injection; a
            # miss (evicted since match) just shortens the restored prefix.
            host_pages: list[tuple[int, "np.ndarray", "np.ndarray"]] = []
            for h in g2_hashes:
                data = self.host_pool.fetch(h)
                if data is None:
                    break
                host_pages.append((h, data[0], data[1]))
            # G3 fetches only extend an UNBROKEN chain (a mid-chain G2
            # eviction makes the G3 tail unmatchable). Each fetch is
            # checksum-verified inside the store — a corrupt page
            # quarantines, returns None, and the restored prefix shortens
            # (the block re-prefills from the prompt, token-identically).
            # Verified bytes promote through the host pool so sibling
            # admissions hit RAM next time.
            persist_pages: list[tuple[int, "np.ndarray", "np.ndarray"]] = []
            if len(host_pages) == len(g2_hashes):
                for h in g3_hashes:
                    data = self.g3_store.fetch(h)
                    if data is None:
                        break
                    persist_pages.append((h, data[0], data[1]))
                    if self.host_pool is not None:
                        self.host_pool.store(h, data[0], data[1])
            restore_pages = host_pages + persist_pages
            for pid in attach:  # commit the reuse
                self._ref_page(pid)
            fresh = [self._take_free() for _ in range(need_fresh)]
            uploads = [
                (fresh[j], h, k, v) for j, (h, k, v) in enumerate(restore_pages)
            ]
        # Register this sequence's own full prompt pages NOW (pending
        # fill): a same-prefix request admitted next can share them.
        # Upload pages are registered by the scheduler with the chain
        # walk it already does (_register_uploads); pages past the
        # uploads are this request's to compute.
        with part("register"):
            if self.sharing:
                self._register_prompt_pages(
                    tokens, hashes, fresh, len(matched_pages),
                    len(restore_pages), request_id,
                )
            self.hits += len(attach) + len(restore_pages)
            self.misses += need_fresh - len(restore_pages)
            if self.host_pool is not None:
                self.offload_hits += len(host_pages)
                self.offload_misses += need_fresh - len(restore_pages)
            self.prefix_hits["shared"] += len(attach)
            self.prefix_hits["restore"] += len(host_pages)
            self.prefix_hits["persist"] += len(persist_pages)
            self.prefix_hits["miss"] += need_fresh - len(restore_pages)
            cached_pages = len(matched_pages) + len(restore_pages)
            cached = cached_pages * ps + (shared_tail[1] if shared_tail else 0)
            cached = min(cached, n_tokens - 1)
            wait_fill = [
                pid for pid in attach if not self._records[pid].filled
            ]
            page_ids = matched_pages + fresh
            if shared_tail:
                page_ids = matched_pages + fresh + [shared_tail[0]]
            self._note_active()
            return Allocation(
                page_ids,
                cached,
                uploads,
                hashes,
                cached_pages=cached_pages,
                wait_fill=wait_fill,
                shared_tail=shared_tail,
                state_restore=state_restore,
            )

    def _register_prompt_pages(
        self,
        tokens: Sequence[int],
        hashes: list[int],
        fresh: list[int],
        first_block: int,
        skip: int,
        request_id: str,
    ) -> None:
        """Register the full prompt pages among ``fresh[skip:]`` as
        pending fills of ``request_id``; ``fresh[j]`` is the prompt's
        block ``first_block + j``."""
        ps = self.page_size
        n_tokens = len(tokens)
        for j in range(skip, len(fresh)):
            block_idx = first_block + j
            if (block_idx + 1) * ps > n_tokens:
                break  # partial tail block: never registered early
            h = hashes[block_idx]
            if h in self._by_hash:
                continue  # racing duplicate content: stay private
            rec = self._records[fresh[j]]
            rec.seq_hash = h
            rec.filled = False
            rec.filler = request_id
            self._by_hash[h] = fresh[j]
            block = list(tokens[block_idx * ps : (block_idx + 1) * ps])
            parent = hashes[block_idx - 1] if block_idx else None
            self.index.insert(parent, h, tokens=block, payload=fresh[j])
            if self.event_cb:
                self.event_cb(
                    KvEvent(
                        "stored", [h], parent_hash=parent,
                        token_blocks=[block],
                    )
                )

    def allocate_page(self) -> int | None:
        """One fresh page (decode crossing a page boundary)."""
        if self._available_for_take() < 1:
            return None
        pid = self._take_free()
        self._note_active()
        return pid

    # -------------------------------------------- tiering accessors
    def match_resident_hashes(self, hashes: list[int]) -> list[int]:
        """Device-resident (G1) prefix of a block-hash chain — the
        footprint forecast's and the prefetch planner's read-only
        match (no refs taken)."""
        pages, _ = self._match_hashes(hashes)
        return pages

    def page_ref(self, page_id: int) -> int:
        return self._records[page_id].ref_count

    def page_hash(self, page_id: int) -> int | None:
        return self._records[page_id].seq_hash

    def resident_page(self, seq_hash: int) -> int | None:
        """The device page holding this registered, *filled* block (or
        None) — swap-in re-attaches through this instead of fetching
        from the host tier when the content never left the device."""
        pid = self._by_hash.get(seq_hash)
        if pid is None or not self._records[pid].filled:
            return None
        return pid

    def attach_page(self, page_id: int) -> None:
        """Take one reference on a resident page (swap-in re-attach of
        a still-parked or shared block)."""
        self._ref_page(page_id)

    def lease_active(self, lease_id: str) -> bool:
        return lease_id in self._leases

    # ------------------------------------------------------------- lifecycle
    def register_full_page(
        self,
        page_id: int,
        seq_hash: int,
        parent_hash: int | None = None,
        tokens: list[int] | None = None,
        content_ready: bool = True,
    ) -> None:
        """A page just got its page_size-th token (or was pre-registered
        for a pending fill): make it reusable and announce it to the
        router index. ``content_ready=False`` registers the page as
        matchable while its data is still on the way (G2 uploads before
        injection); the engine marks it filled at the injecting
        dispatch."""
        rec = self._records[page_id]
        if rec.seq_hash == seq_hash:
            if content_ready:
                rec.filled = True
                rec.filler = ""
            return
        if rec.seq_hash is not None:
            # Re-registration under different content (tests / page
            # repurposing): the stale index entry must go first.
            self._unregister(page_id)
        # A different page may already hold this content (two requests with
        # the same prompt racing); keep the first registration authoritative.
        if seq_hash not in self._by_hash:
            rec.seq_hash = seq_hash
            rec.filled = content_ready
            self._by_hash[seq_hash] = page_id
            self.index.insert(
                parent_hash, seq_hash, tokens=tokens, payload=page_id
            )
            if self.event_cb:
                self.event_cb(
                    KvEvent(
                        "stored",
                        [seq_hash],
                        parent_hash=parent_hash,
                        token_blocks=[tokens] if tokens else None,
                    )
                )

    # ------------------------------------------------------- fill lifecycle
    def mark_filled(self, page_ids: Sequence[int]) -> None:
        """The write materializing these pages has been dispatched:
        waiting sharers may dispatch reads (device stream order now
        protects them)."""
        for pid in page_ids:
            rec = self._records[pid]
            rec.filled = True
            rec.filler = ""

    def begin_fill(self, page_id: int, request_id: str) -> None:
        """Mark a registered page as pending content from ``request_id``
        (G2 upload awaiting its inject dispatch)."""
        rec = self._records[page_id]
        rec.filled = False
        rec.filler = request_id

    def note_writer(self, page_ids: Sequence[int], tag: str) -> None:
        """``tag`` names the prefill dispatch that just wrote into
        these pages (read back by :meth:`page_writer` when a later
        admission attaches them)."""
        for pid in page_ids:
            self._records[pid].writer = tag

    def page_writer(self, page_id: int) -> str:
        return self._records[page_id].writer

    def fill_state(self, page_id: int) -> str:
        """"filled" | "pending" (live filler) | "orphaned" (filler died
        before dispatching the write; a sharer must claim + re-fill)."""
        rec = self._records[page_id]
        if rec.filled:
            return "filled"
        return "pending" if rec.filler else "orphaned"

    def claim_fill(self, page_id: int, request_id: str) -> None:
        """A sharer adopts an orphaned page: it will re-prefill the
        block itself (deterministic forward ⇒ identical content)."""
        rec = self._records[page_id]
        if not rec.filled:
            rec.filler = request_id

    def abort_fills(self, request_id: str, page_ids: Sequence[int]) -> None:
        """The filler is going away (finish/cancel/preempt) with writes
        not yet dispatched: orphan its pending pages so sharers can
        claim them. Call BEFORE releasing the refs."""
        for pid in page_ids:
            rec = self._records[pid]
            if not rec.filled and rec.filler == request_id:
                rec.filler = ""

    def make_private(self, page_id: int) -> int | None:
        """Copy-on-write entry point: the caller is about to write a
        divergent value into ``page_id``.

        - Sole holder: the page just leaves the index (content offloads
          to G2 first — it is still a valid block for future prompts)
          and is returned as-is.
        - Shared: allocate a replacement page; the caller must device-
          copy content old→new, swap its table entry, and drop its ref
          on the old page. Returns None when the pool is dry (caller
          treats it as a hard stall and retries).
        """
        rec = self._records[page_id]
        if rec.ref_count <= 1:
            if rec.seq_hash is not None:
                if self.on_evict is not None and rec.filled:
                    self.on_evict(page_id, rec.seq_hash)
                self._unregister(page_id)
            rec.filled = True
            rec.filler = ""
            return page_id
        new_pid = self.allocate_page()
        if new_pid is None:
            return None
        self.cow_copies += 1
        return new_pid

    def release_sequence(self, page_ids: Sequence[int]) -> None:
        """Sequence finished: drop refs. Registered *filled* pages park
        in the LRU (still matchable); unfilled registered pages — a
        fill that never happened — unregister (their bytes are garbage)
        and return to the free list with the rest."""
        for pid in page_ids:
            rec = self._records[pid]
            if rec.ref_count > 0:
                rec.ref_count -= 1
                self._ref_total -= 1
                if rec.ref_count == 1:
                    self.live_shared -= 1
                if rec.ref_count == 0:
                    self._held_pages -= 1
            if rec.ref_count == 0:
                if rec.seq_hash is not None and rec.filled:
                    self._reclaimable[pid] = None
                    self._reclaimable.move_to_end(pid)
                else:
                    if rec.seq_hash is not None:
                        self._unregister(pid)
                    self._free.append(pid)

    # ---------------------------------------------------------------- leases
    @property
    def active_leases(self) -> int:
        return len(self._leases)

    def grant_lease(self, page_ids: Sequence[int], ttl_s: float) -> str:
        """Pin ``page_ids`` (one extra ref each) for a KV handoff in
        flight; returns the lease id the wire protocol carries. Must be
        called while the pages are still referenced (before the owning
        sequence is released), i.e. on the engine loop thread — or, for
        the decode-side suffix-transfer pin, on registered resident
        pages the match just returned."""
        for pid in page_ids:
            self._ref_page(pid)
        lease = KvLease(
            lease_id=uuid.uuid4().hex,
            page_ids=list(page_ids),
            expires_at=self.clock() + ttl_s,
        )
        self._leases[lease.lease_id] = lease
        return lease.lease_id

    def confirm_lease(self, lease_id: str) -> bool:
        """Delivery confirmed: drop the lease's pins. Registered pages
        park in the reclaimable LRU exactly as a finished sequence's
        would. Unknown/already-reaped ids are a no-op (the confirm raced
        the reaper)."""
        lease = self._leases.pop(lease_id, None)
        if lease is None:
            return False
        self.release_sequence(lease.page_ids)
        return True

    def reap_expired(self, now: float | None = None) -> int:
        """Reclaim every expired lease's pages; returns pages freed.
        Engine-loop-thread only (mutates the free lists).
        ``last_reaped`` names the leases this call reclaimed so the
        caller can close their trace spans (`llmctl trace` shows the
        reap as the lease's terminal hop)."""
        now = self.clock() if now is None else now
        reclaimed = 0
        self.last_reaped = []
        for lid in [
            lid for lid, l in self._leases.items() if now >= l.expires_at
        ]:
            lease = self._leases.pop(lid)
            self.release_sequence(lease.page_ids)
            reclaimed += len(lease.page_ids)
            self.last_reaped.append((lid, len(lease.page_ids)))
        self.lease_reclaimed_pages += reclaimed
        return reclaimed

    # ------------------------------------------------- conservation ledger
    def ledger_check(self) -> list[str]:
        """Cheap conservation invariant (docs/observability.md "KV
        conservation auditor"): every page is exactly one of
        {free, parked, held}, and refcount totals conserve across
        attach/COW/release/evict/reap. Pure counter arithmetic over
        already-maintained ints — O(1), no pool scan, no host sync —
        so the engine loop runs it every iteration. Returns violation
        descriptions (empty = conserved); ``audit()`` is the on-demand
        full scan that names the leaking holder."""
        violations: list[str] = []
        free, parked = len(self._free), len(self._reclaimable)
        held = self._held_pages
        if free + parked + held != self.num_pages:
            violations.append(
                f"page conservation broken: free={free} parked={parked} "
                f"held={held} sum={free + parked + held} != "
                f"pool={self.num_pages}"
            )
        if not 0 <= self.live_shared <= held:
            violations.append(
                f"shared-page count out of range: live_shared="
                f"{self.live_shared} held={held}"
            )
        # Every held page carries >= 1 ref; every shared page >= 2.
        if self._ref_total < held + self.live_shared:
            violations.append(
                f"refcount total below holder floor: ref_total="
                f"{self._ref_total} < held={held} + shared="
                f"{self.live_shared}"
            )
        if self._ref_total < 0 or held < 0:
            violations.append(
                f"negative ledger counter: ref_total={self._ref_total} "
                f"held={held}"
            )
        if len(self._free_state) + self._held_state != self.state_slots or (
            len(self._free_snaps) + len(self._snap_by_hash) != self.state_snapshots
        ):
            violations.append(
                f"sequence-state conservation broken: free={len(self._free_state)} "
                f"held={self._held_state} of {self.state_slots} slots; "
                f"free={len(self._free_snaps)} kept={len(self._snap_by_hash)} "
                f"of {self.state_snapshots} snapshots"
            )
        lease_pins = sum(len(l.page_ids) for l in self._leases.values())
        if lease_pins > self._ref_total:
            violations.append(
                f"lease pins exceed refcount total: lease_pins="
                f"{lease_pins} ref_total={self._ref_total}"
            )
        if self.sliding is not None:
            violations.extend(self.sliding.ledger_check())
        return violations

    def audit(
        self, holders: dict[str, Sequence[int]] | None = None,
        sliding_holders: dict[str, Sequence[int]] | None = None,
    ) -> dict:
        """Full on-demand conservation audit (``llmctl audit``): scan
        the pool, classify every page into exactly one state, and — when
        ``holders`` maps holder names (``seq:<request_id>``) to the page
        ids they believe they hold — cross-check per-page refcounts
        against the holder set so a leak is *named*, not just counted.
        Leases are joined in automatically as ``lease:<id>`` holders.
        ``sliding_holders`` (a cache by kind): the same for the sliding
        pool's pages, each of which has one holder or is free.
        Read-only; safe (best-effort) from a non-loop thread for flight
        dumps."""
        free_list = list(self._free)
        free = set(free_list)
        parked = set(self._reclaimable)
        expected: dict[int, list[str]] = {}
        all_holders: dict[str, Sequence[int]] = dict(holders or {})
        for lid, lease in self._leases.items():
            all_holders[f"lease:{lid}"] = lease.page_ids
        for name, pids in all_holders.items():
            for pid in pids:
                expected.setdefault(pid, []).append(name)
        counts = {"free": 0, "parked": 0, "active": 0, "shared": 0,
                  "leased": sum(len(l.page_ids) for l in self._leases.values())}
        violations: list[dict] = []

        def flag(pid: int, kind: str, detail: str) -> None:
            violations.append(
                {
                    "page": pid,
                    "kind": kind,
                    "detail": detail,
                    "holders": sorted(expected.get(pid, [])),
                }
            )

        if len(free) != len(free_list):
            dupes = sorted(
                pid for pid in free if free_list.count(pid) > 1
            )
            for pid in dupes:
                flag(pid, "double_release", "page appears twice in the free list")
        for pid, rec in self._records.items():
            states = []
            if pid in free:
                states.append("free")
            if pid in parked:
                states.append("parked")
            if rec.ref_count > 0:
                states.append("active")
            if len(states) != 1:
                flag(
                    pid, "state_overlap" if states else "unaccounted",
                    f"page in states {states or ['none']} "
                    f"(ref_count={rec.ref_count})",
                )
            if rec.ref_count > 0:
                counts["active"] += 1
                if rec.ref_count >= 2:
                    counts["shared"] += 1
            elif pid in parked:
                counts["parked"] += 1
            elif pid in free:
                counts["free"] += 1
            if rec.ref_count < 0:
                flag(pid, "negative_refcount", f"ref_count={rec.ref_count}")
            want = len(expected.get(pid, []))
            if all_holders and rec.ref_count != want and (
                rec.ref_count > 0 or want > 0
            ):
                kind = "leaked_ref" if rec.ref_count > want else "lost_ref"
                flag(
                    pid, kind,
                    f"ref_count={rec.ref_count} but {want} live holder(s)",
                )
        for check in self.ledger_check():
            violations.append(
                {"page": None, "kind": "counter", "detail": check,
                 "holders": []}
            )
        if self.sliding is not None and sliding_holders is not None:
            violations.extend(self.sliding.audit(sliding_holders))
        return {
            "ok": not violations,
            "counts": counts,
            "pool": self.num_pages,
            "leases": len(self._leases),
            "held_pages": self._held_pages,
            "ref_total": self._ref_total,
            "violations": violations,
        }

    # -------------------------------------------------------------- internal
    def _available_for_take(self) -> int:
        return len(self._free) + len(self._reclaimable)

    def _ref_page(self, pid: int) -> None:
        rec = self._records[pid]
        if rec.ref_count == 0:
            self._reclaimable.pop(pid, None)
            self._held_pages += 1
        rec.ref_count += 1
        self._ref_total += 1
        if rec.ref_count == 2:
            self.live_shared += 1
            if self.live_shared > self.peak_shared_pages:
                self.peak_shared_pages = self.live_shared
        self._note_active()

    def _take_free(self) -> int:
        if self._free:
            pid = self._free.pop()
            self.pages_taken["free"] += 1
        else:
            # Evict the least-recently-used parked page.
            pid, _ = self._reclaimable.popitem(last=False)
            self.pages_taken["evicted"] += 1
            self._evict(pid)
        rec = self._records[pid]
        rec.ref_count = 1
        self._held_pages += 1
        self._ref_total += 1
        rec.seq_hash = None
        rec.filled = True
        rec.filler = ""
        rec.writer = ""
        return pid

    def _evict(self, pid: int) -> None:
        rec = self._records[pid]
        if rec.seq_hash is not None:
            if self.on_evict is not None:
                # Offload to G2 before the page can be overwritten: the
                # engine dispatches the on-device gather synchronously
                # here (stream order protects it from the next forward).
                self.on_evict(pid, rec.seq_hash)
            self._unregister(pid)

    def _unregister(self, pid: int) -> None:
        """Drop a page's registration from the hash map + radix index
        and announce the removal. Content is NOT offloaded here — the
        eviction path does that first when the bytes are worth keeping."""
        rec = self._records[pid]
        if rec.seq_hash is None:
            return
        self._by_hash.pop(rec.seq_hash, None)
        self.index.remove(rec.seq_hash)
        if self.event_cb:
            self.event_cb(KvEvent("removed", [rec.seq_hash]))
        rec.seq_hash = None
        rec.filled = True
        rec.filler = ""
