"""The TPU execution engine: continuous batching on a paged KV cache.

This replaces the reference's wrapped GPU engines (vLLM/sglang/TRT-LLM —
``/root/reference/lib/engines/``, SURVEY.md §2.3/§2.9) with an in-process
JAX engine:

- **Two small families of compiled programs** drive everything: decode
  *windows* (``lax.scan`` over ``decode_window`` steps with sampled
  tokens fed back on-device, keyed by row bucket / attention impl /
  page bucket / sampler variant — one host sync per window, which is
  what survives a high-latency host↔device link) and batched chunked
  prefill (keyed by row bucket × token bucket × page bucket). Static
  shapes, no recompiles in steady state; KV pools are donated and
  carried through both loops (window steps, layers), so XLA updates
  them in place in HBM (docs/engine_perf.md).
- **Decode cost tracks occupancy, not the slot envelope**
  (docs/engine_perf.md): ACTIVE rows are compacted into the smallest
  row bucket and partitioned greedy-vs-sampler; stop detection (EOS /
  stop ids / budget) runs on-device inside the window so finished rows
  park at position -1 instead of writing garbage KV; KV pages move in
  batched multi-page gathers/scatters (one dispatch per sequence or
  eviction burst); and in steady state the next window launches from
  the previous window's device carry before the host syncs, so emit
  processing overlaps device compute.
- **The host loop is the scheduler** (reference's "hard part #3",
  SURVEY.md §7): stop flags, admissions, page allocation, and KV event
  emission all happen between steps on the loop thread — never inside a
  compiled region. The host's ``check_stop`` stays authoritative; the
  on-device stop is an optimization, not the source of truth.
- **Prefix caching is free at the attention level**: reused pages are
  already resident; prefill just starts its positions after the cached
  prefix (write-then-gather attention reads them like any other page).
- **Tensor parallelism** comes from param/cache shardings over the
  engine's mesh; XLA inserts the ICI collectives.

The engine exposes the same ``AsyncEngine`` seam the rest of the stack
uses (``BackendInput`` dict in → ``LLMEngineOutput`` dict stream out), so
the preprocessor/backend/router layers are engine-agnostic, matching the
reference's ``ExecutionContext`` contract (``lib/llm/src/backend.rs:60``).
"""

from __future__ import annotations

import asyncio
import contextlib
import logging
import os
import queue
import random
import threading
import time
from collections import OrderedDict, defaultdict
from dataclasses import dataclass
from functools import partial
from typing import AsyncIterator, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..aot.launch import (
    SAMPLER_FIELDS,
    launch_buffer,
    launch_layout,
    split_tables,
    unpack_launch,
)
from ..aot.lattice import impl_for_key, resolve_ragged_key
from ..models.config import ModelConfig
from ..models.llama import (
    Params,
    forward,
    forward_ragged,
    init_kv_cache,
    init_params,
    init_seq_state,
    seq_state_entries,
    kv_cache_shardings,
    param_shardings,
    routed_experts_form,
)
from ..ops.sampling import (
    apply_penalties,
    sample_tokens_seeded,
    spec_accept_length,
    spec_verify_tokens,
    stop_token_hit,
    token_logprobs,
)
from ..parallel.mesh import build_mesh
from ..protocols.common import BackendInput, FinishReason, LLMEngineOutput
from ..runtime.engine import AsyncEngine, AsyncEngineContext, ResponseStream
from ..telemetry import current_trace, get_telemetry
from ..tokens import compute_block_hashes_for_seq
from ..telemetry.anatomy import COMPONENTS, AnatomyRing, anatomy_from_timing
from ..telemetry.dispatch import PROGRAMS, DispatchProfiler, http_chunk_clock
from ..telemetry.fingerprint import (
    FingerprintBuilder,
    WorkloadDriftWatch,
    load_fingerprint,
)
from ..kv.persistent import PersistentKvStore
from ..telemetry.flight import (
    host_witness,
    FlightRecorder,
    Watchdog,
    default_dump_path,
    register_dumper,
    unregister_dumper,
)
from .block import BlockDecode
from .config import (
    EngineConfig,
    refuse_block_model_tiers,
    refuse_latent_tiers,
    refuse_looped_tiers,
    refuse_sliding_pool_tiers,
    refuse_seq_state_tiers,
)
from .kv_manager import KvEvent, KvPageManager, SlidingPagePool, no_part
from .offload import CopyStream, HostKvPool
from .scheduler import RemoteKv, Scheduler, SeqState, Sequence, _RaggedRow
from .tiering import SwapRecord, plan_swap_entries

log = logging.getLogger(__name__)

# Process-wide KV-ledger violation registry (docs/observability.md "KV
# conservation auditor"): every engine appends the violations its
# in-loop check or stop-time audit observed. The test suites' autouse
# guard asserts this stays empty across every chaos / overload /
# prefix-sharing / resumable scenario — turning the trickiest page
# state machines into a continuously-checked invariant.
LEDGER_VIOLATIONS: list[str] = []


def resolve_attn_impl(cfg: EngineConfig, mesh: Mesh) -> tuple[str, bool]:
    """Pick the decode attention implementation. ``auto`` resolves to
    the ragged Pallas kernel only when the mesh actually sits on TPU
    (or ``pallas_interpret`` forces interpreter mode for CPU tests);
    anywhere else the length-bounded XLA gather is the correct
    choice. Layouts Mosaic can't tile (``ragged_supported``) fall
    back to XLA rather than fail at compile time on the first
    decode.

    A free function (not a method) because the resolved impl is part of
    the AOT compile-lattice key: ``llmctl aot list`` resolves it from
    (config, mesh) alone, without paying a weight init."""
    from ..ops.ragged_attention import latent_supported, ragged_supported

    impl = cfg.attention_impl
    interpret = cfg.pallas_interpret
    if impl == "auto":
        platform = mesh.devices.flat[0].platform
        impl = "pallas" if (platform == "tpu" or interpret) else "xla"
    mcfg = cfg.model
    if impl == "pallas" and not mcfg.attn_kernel_form:
        # forward() would silently refuse the kernel for these configs
        # (a softcap, a query pre-scale, a sliding width that alternates
        # inside a run of layers: ``ModelConfig.attn_kernel_form``, which
        # both paged forwards ask too); resolve xla HERE so attn_pages
        # keeps bounding the gather — otherwise decode would run the XLA
        # path with an unbounded Pmax-wide page table. A sliding width
        # that is one run's (mistral's, exaone_moe's) is the kernel's own
        # argument and stays.
        impl = "xla"
    if mcfg.is_latent:
        # A latent pool is read by the kernel or, off the TPU, by its
        # reference; never by the XLA gather on a chip: at 128 heads that
        # path builds every query's context, a dead end from a few thousand
        # tokens on. A layout the kernel cannot tile is refused here.
        from ..models.llama import latent_value_lanes

        on_tpu = mesh.devices.flat[0].platform == "tpu"
        if on_tpu and not interpret and (impl != "pallas" or not latent_supported(
            cfg.page_size, mcfg.latent_lanes, latent_value_lanes(mcfg),
            cfg.kv_dtype_jnp,
        )):
            raise ValueError(
                f"model_type {mcfg.model_type!r} reads its latent pool through "
                f"the attention kernel alone on a TPU; attention_impl={impl!r}, "
                f"page_size={cfg.page_size}, {mcfg.latent_lanes} lanes "
                f"({cfg.kv_dtype}) cannot take it"
            )
        return impl, interpret
    if impl == "pallas" and not interpret:
        tp = mesh.shape.get("tp", 1)
        # every pool's row: K's, and V's and the sliding layers' where a
        # model's are other widths (mimo_v2_flash)
        rows = {(mcfg.num_kv_heads // tp, mcfg.head_dim_)}
        if mcfg.kv_heads_by_form or mcfg.value_head_dim_ != mcfg.head_dim_:
            rows |= {
                (mcfg.kv_heads_of(form) // tp, width)
                for form in ("full", "sliding")
                for width in (mcfg.head_dim_, mcfg.value_head_dim_)
            }
        if not all(
            ragged_supported(cfg.page_size, heads, width, cfg.kv_dtype_jnp)
            for heads, width in rows
        ):
            log.warning(
                "KV layout (ps=%d, Hkv=%d/tp=%d, D=%d, %s) is not "
                "Mosaic-tileable; decode falls back to the XLA path",
                cfg.page_size,
                cfg.model.num_kv_heads,
                tp,
                cfg.model.head_dim_,
                cfg.kv_dtype,
            )
            impl = "xla"
    return impl, interpret


@dataclass
class _PendingRagged:
    """One dispatched ragged batch the host has not yet consumed.

    ``windowed=True`` is the pure-decode shape: every row fed one
    token and the program scanned ``decode_window`` steps on-device,
    returning the final carry (``tokens_dev``/``positions_dev``) — the
    exact inputs of the next window over the same rows, so a chained
    dispatch can launch window N+1 straight from device state while
    the host still owns window N's sync (``_dispatch_chained``).
    ``windowed=False`` is the mixed shape (prefill chunks, single
    decode steps, spec verify spans in one flat stream), consumed in
    the same iteration — drafts are re-planned and prompts re-chunked
    from the freshly consumed tokens, so there is nothing to chain."""

    ys: tuple  # windowed: toks [K, nb] (+lp); mixed: tok0 [B1] (+spec, +lp)
    rows: list  # [_RaggedRow]
    nb: int  # flat token bucket (array batch dim)
    windowed: bool
    full_sampler: bool
    want_lp: bool
    solo: bool  # only dispatch of its iteration -> chainable
    # Mixed batches only: the dispatch carried draft spans, so ys
    # includes the verify outputs (and the compiled variant is the
    # spec-carrying one).
    with_spec: bool = False
    # Live token-steps the dispatch routes (``ModelConfig.counts_picks``:
    # ``_count_picks``): each makes ``num_experts_per_tok``
    # picks a routed layer, and ``ys`` ends with how many were held.
    routed_rows: int = 0
    tokens_dev: object = None  # windowed carry: next window's tokens [nb]
    positions_dev: object = None  # windowed carry: next positions [nb]
    # True when some row could hit its page/model-length cap inside
    # this window (cap < wpos + K at dispatch). Its device carry
    # position flips to -1 at the cap, but the host RESUMES such a row
    # after allocating pages rather than finishing it — so a chained
    # window would feed the dead carry and emit garbage. Chaining
    # requires this to be False; stop/budget deaths are safe (the host
    # finishes those rows at consume and skips them in the successor).
    capacity_capped: bool = False
    # The host buffer a window launched with (``aot/launch.py``): a
    # window chained onto it starts from a copy, its rows' stop sets,
    # sampler rows, counts slots and sequence-state entries being these.
    packed: object = None
    # Dispatch-profiler stamp (monotonic, taken right after the dispatch
    # call returned): the consume's existing host sync closes the pair.
    dispatched_at: float = 0.0


_NO_PHASE = contextlib.nullcontext()


def _no_phase(name: str):
    """``DispatchProfiler.phase`` of an engine built without a profiler
    (``profile_dispatches=False``): nothing is timed or annotated."""
    return _NO_PHASE


def _no_part(phase: str, name: str):
    """``DispatchProfiler.part`` of such an engine."""
    return _NO_PHASE


def _deliver(batch: list) -> None:
    """One posting's emitted tuples into their requests' queues, in
    emission order, on the asyncio thread (``TPUEngine._flush_outbox``)."""
    for put, item in batch:
        put(item)
    # Behind the tasks those puts woke: what this thread spends on the
    # posting's chunks is booked once, when they have been handled.
    asyncio.get_running_loop().call_soon(http_chunk_clock().flush)


class TPUEngine(BlockDecode, AsyncEngine):
    """Continuous-batching paged-KV engine on a TPU mesh."""

    def __init__(
        self,
        cfg: EngineConfig,
        params: Params | None = None,
        mesh: Mesh | None = None,
        kv_event_cb: Callable[[KvEvent], None] | None = None,
        seed: int = 0,
    ):
        self.cfg = cfg
        refuse_seq_state_tiers(cfg)
        refuse_block_model_tiers(cfg)
        refuse_latent_tiers(cfg)
        refuse_sliding_pool_tiers(cfg)
        refuse_looped_tiers(cfg)
        self.mesh = mesh or build_mesh(tp=cfg.tp, sp=cfg.sp)
        mcfg = cfg.model

        def sharding(spec):
            return NamedSharding(self.mesh, spec)

        # Parameters and pools are born sharded: random weights come
        # out of one jit whose out_shardings are the engine's, loaded
        # weights are host arrays put straight to them. A model larger
        # than one chip never passes through one chip.
        shardings = jax.tree.map(
            sharding,
            param_shardings(mcfg),
            is_leaf=lambda x: isinstance(x, P),
        )
        if params is None:
            self.params = jax.jit(
                init_params, static_argnums=1, out_shardings=shardings
            )(jax.random.PRNGKey(seed), mcfg)
        else:
            self.params = jax.device_put(params, shardings)
        # A model whose cache is by kind (sliding and full attention layers
        # in one stack: ``ModelConfig.kv_by_kind``) has each of these as a
        # pair (full, sliding), ``num_pages`` being the full layers' and the
        # sliding pool sized from the largest dispatch
        # (``EngineConfig.sliding_pool_pages``).
        self.k_cache, self.v_cache = init_kv_cache(
            mcfg, cfg.num_pages, cfg.page_size, dtype=cfg.kv_dtype_jnp,
            shardings=tuple(sharding(s) for s in kv_cache_shardings()),
            sliding_pages=cfg.sliding_pool_pages,
        )
        # What a sequence carries beside its pages (a model with layers
        # whose operator is not softmax attention: a short convolution's
        # last products, a selective scan's state and its convolution's
        # inputs, linear attention's matrix state and its convolutions'
        # inputs; None for every other model): one entry a decode slot,
        # then the snapshots a prefix hit restores from, four a slot. An
        # entry is kilobytes for a convolution and megabytes for a
        # recurrence (9.32 MB at Jamba2-3B's 26 Mamba layers: 2.98 GB of
        # pool at 64 slots; 13.7 MB at the 6 linear layers of
        # Olmo-Hybrid-7B's cut: 2.19 GB at 32), so its bytes are published
        # with the KV pool's (``dynamo_seq_state_pool_bytes``). Replicated
        # over the mesh.
        state_slots = cfg.max_decode_slots if mcfg.has_seq_state else 0
        state_snapshots = 4 * state_slots
        # Entries of the pool; as a row's entry it means "none": a write
        # there is past the pool and dropped.
        self._state_entries = state_slots + state_snapshots
        self.seq_state = init_seq_state(
            mcfg, self._state_entries, sharding=sharding(P())
        )
        get_telemetry().seq_state_pool_bytes.set(sum(
            leaf.nbytes for leaf in jax.tree.leaves(self.seq_state)
        ))
        # The pools' geometry, from the pools themselves: a latent model
        # has one, whose lanes hold ``latent_width`` values and padding.
        # Of a cache by kind ``dynamo_kv_pool_info`` describes the full
        # layers' pools (what a context token holds for as long as its row
        # lives) and ``dynamo_kv_sliding_pool_info`` the sliding layers'.
        k_pool, v_pool = (
            (self.k_cache[0], self.v_cache[0]) if mcfg.kv_by_kind
            else (self.k_cache, self.v_cache)
        )
        layers, pages, page_size, width = k_pool.shape
        get_telemetry().set_kv_pool_info(
            layers, pages, page_size, width, str(k_pool.dtype),
            pools=sum(1 for pool in (k_pool, v_pool) if pool.size),
            pad_lanes=width - mcfg.latent_width if mcfg.is_latent else 0,
            token_bytes=(k_pool.nbytes + v_pool.nbytes)
            // (pages * page_size),
            index_width=mcfg.index_lanes,
        )
        # What each form of attention the model has is served as: its
        # layers, KV heads, a head's key and value widths, whether a sink
        # logit joins its softmax.
        get_telemetry().set_attn_form_info([
            (
                form, mcfg.attn_forms.count(form), mcfg.kv_heads_of(form),
                mcfg.head_dim_, mcfg.value_head_dim_, form in mcfg.sink_forms,
            )
            for form in ("full", "sliding")
            if form in mcfg.attn_forms and not mcfg.is_latent
        ])
        # A looped model's passes over its layers of weights, and the
        # cache's layer slots they fill (1 pass for every other model).
        get_telemetry().set_model_loop_info(
            mcfg.loop_passes, mcfg.num_layers, mcfg.kv_layer_slots
        )
        # Bytes a held page of each pool takes over its layers, K and V.
        self._page_bytes = {"full": (k_pool.nbytes + v_pool.nbytes) // pages}
        sliding_pool = None
        if mcfg.kv_by_kind:
            k_sl, v_sl = self.k_cache[1], self.v_cache[1]
            self._page_bytes["sliding"] = (
                (k_sl.nbytes + v_sl.nbytes) // k_sl.shape[1]
            )
            get_telemetry().set_kv_sliding_pool_info(
                *k_sl.shape, str(k_sl.dtype), mcfg.sliding_window,
                self._page_bytes["sliding"],
            )
            sliding_pool = SlidingPagePool(
                k_sl.shape[1], cfg.page_size, mcfg.sliding_window
            )
        get_telemetry().watch_jax_compiles()

        self.host_pool: HostKvPool | None = None
        self.copy_stream: CopyStream | None = None
        on_evict = None
        if cfg.host_cache_pages > 0:
            page_shape = (
                mcfg.kv_layer_slots,
                cfg.page_size,
                mcfg.kv_pool_widths[0],
            )
            self.host_pool = HostKvPool(
                cfg.host_cache_pages, page_shape, cfg.kv_dtype_jnp
            )

            # The CopyStream (a live thread) is created by start(), so a
            # constructed-but-never-started engine owns no threads.
            def on_evict(pid: int, seq_hash: int) -> None:
                # Coalesce: eviction bursts (a big allocation reclaiming
                # many parked pages) buffer here and flush as ONE batched
                # gather right before the next compute dispatch — stream
                # order still protects the pages from the forward that
                # overwrites them, but the burst costs one dispatch + one
                # host sync instead of one per page.
                self._pending_offloads.append((pid, seq_hash))

        # G3 persistent tier (docs/fault_tolerance.md "Durable KV &
        # corruption containment"): boot-scan the store (torn tails
        # quarantined, survivors adopted as matchable entries — the
        # restart warm cache), then wire G2's LRU demotions into it. A
        # degraded store (missing dir, ENOSPC) logs and the engine runs
        # G2-only — never a stall, never a crash.
        self.g3_store: PersistentKvStore | None = None
        if cfg.kv_store_dir:
            if self.host_pool is None:
                log.warning(
                    "kv_store_dir=%r ignored: the G3 tier rides the G2 "
                    "host pool's eviction path (set host_cache_pages > 0)",
                    cfg.kv_store_dir,
                )
            else:
                self.g3_store = PersistentKvStore(
                    cfg.kv_store_dir,
                    cfg.kv_store_pages,
                    page_shape,
                    cfg.kv_dtype_jnp,
                    chaos=cfg.kv_store_chaos,
                )
                adopted = self.g3_store.boot_scan()
                if adopted or self.g3_store.torn_pages:
                    log.info(
                        "G3 store %s: adopted %d page(s), quarantined %d "
                        "torn", cfg.kv_store_dir, adopted,
                        self.g3_store.torn_pages,
                    )
                self.host_pool.on_demote = self.g3_store.store

        self.kv = KvPageManager(
            cfg.num_pages,
            cfg.page_size,
            event_cb=kv_event_cb if cfg.enable_kv_events else None,
            host_pool=self.host_pool,
            on_evict=on_evict,
            # A cache by kind grants no prefix hit: the sliding layers'
            # keys of the ``width - 1`` positions before a boundary were
            # given back, and without them a hit would serve wrong numbers
            # (ROADMAP R2: a boundary held in both pools could be granted).
            sharing=cfg.prefix_sharing and not mcfg.kv_by_kind,
            g3_store=self.g3_store,
            state_slots=state_slots,
            state_snapshots=state_snapshots,
            partial_tail=not mcfg.block_length,
            sliding=sliding_pool,
        )
        # Observability (docs/observability.md): per-dispatch profiler
        # (host gap vs in-flight, compile attribution — pure timestamps
        # at the loop's existing sync points) and the flight recorder
        # ring the watchdog/SIGUSR1/crash paths dump.
        self.profiler = (
            DispatchProfiler(get_telemetry()) if cfg.profile_dispatches else None
        )
        self._phase = (
            self.profiler.phase if self.profiler is not None else _no_phase
        )
        self._part = (
            self.profiler.part if self.profiler is not None else _no_part
        )
        # The parts of phase ``admit``, opened by name alone: handed to
        # the scheduler and the page manager, which know no profiler.
        self._admit_part = (
            self.profiler.part_opener("admit")
            if self.profiler is not None else no_part
        )
        self.flight = FlightRecorder()
        launch_seconds = get_telemetry().launch_seconds
        self._launch_seconds = {
            p: launch_seconds.labels(p) for p in PROGRAMS
        }
        self.sched = Scheduler(cfg, self.kv, flight=self.flight)
        if self.profiler is not None:
            self.sched.span_attrs = self._decode_span_attrs

        # Multi-page movement kernels, shared by the G2 offload tier and
        # the disaggregation KV handoff (gather → wire / wire → inject).
        # ``pids`` is a page_move_bucket_for-padded [n] vector, so a whole
        # sequence (or eviction burst) moves in ONE dispatch; jit's own
        # cache keys the O(log Pmax) bucket shapes. Scatter pads repeat
        # the last (pid, page) pair — duplicate indices with identical
        # updates are deterministic.
        # Each helper is a named function, not a lambda: the name is
        # what the profiler's ``XLA Modules`` line shows for its runs
        # (``jit_kv_gather_pages`` beside ``jit_ragged_window``).
        def kv_gather_pages(k, v, pids):
            return k[:, pids], v[:, pids]

        def kv_inject_pages(k, v, pids, hk, hv):
            return k.at[:, pids].set(hk), v.at[:, pids].set(hv)

        def kv_cow_pages(k, v, src, dst):
            # a pool or, of a cache by kind, its pair (whose rows share no
            # page, so that only the prewarm's copy of page 0 onto itself
            # ever runs there)
            return jax.tree.map(lambda a: a.at[:, dst].set(a[:, src]), (k, v))

        def seq_state_copy(pool, src, dst):
            # every part of the state (``init_seq_state``'s pytree)
            return jax.tree.map(lambda a: a.at[:, dst].set(a[:, src]), pool)

        # A prefix hit's state: snapshot entry -> the sequence's own entry.
        self._copy_state = jax.jit(seq_state_copy, donate_argnums=(0,))
        self._pub_state_hits = {"restored": 0, "shortened": 0, "not_taken": 0}
        self._pub_pages_taken = {"free": 0, "evicted": 0}
        self._gather_pages = jax.jit(kv_gather_pages)
        self._inject_pages = jax.jit(kv_inject_pages, donate_argnums=(0, 1))
        # Copy-on-write page copy (docs/prefix_sharing.md): device-to-
        # device duplicate of one shared page before its first divergent
        # write. Indices ride as traced device scalars, so every COW
        # shares ONE compiled variant.
        self._cow_pages = jax.jit(kv_cow_pages, donate_argnums=(0, 1))
        # Evictions buffered by on_evict until the next compute dispatch.
        self._pending_offloads: list[tuple[int, int]] = []

        B, V = cfg.max_decode_slots, mcfg.vocab_size
        # Penalty bookkeeping, indexed by slot. Row B is a scratch row:
        # compacted decode windows gather counts through a slot map whose
        # padding rows point here, so pad scatters never touch a live
        # slot's counts.
        self._counts = jnp.zeros((B + 1, V), jnp.int32)
        # Sampling is counter-based per sequence: every draw is keyed by
        # (sequence seed, absolute token position) — see
        # ops/sampling.sample_tokens_seeded. Requests without an explicit
        # seed get one drawn here at submission; a frontend that journals
        # for failover replay pins the seed request-side instead.
        self._seed_rng = random.Random(seed + 1)
        self._attn_impl, self._attn_interpret = self._resolve_attn()
        # Attention layers by form, of a model that has sliding ones, a
        # looped model's layer-passes as full ones, and the layers of a
        # grouped-query model with an indexer (what ``_count_attn_work``
        # counts by; empty for any other model).
        forms = mcfg.attn_forms
        counted = (
            "sliding" in forms or mcfg.loop_passes > 1 or mcfg.index_key_in_k_row
        )
        self._attn_form_layers = {
            form: forms.count(form) * mcfg.loop_passes
            for form in ("sliding", "full") if form in forms
        } if counted else {}
        if mcfg.num_mamba_layers and self._attn_impl == "pallas" and (
            cfg.page_size % cfg.ragged_q_tile or cfg.prefill_chunk % cfg.ragged_q_tile
        ):
            # The scan kernel stores a state at the end of a tile: a
            # snapshot's block boundary and a chunk's end must be one
            # (ops/selective_scan.py, WRITES).
            raise ValueError(
                f"a selective-scan model on the kernel path needs page_size "
                f"({cfg.page_size}) and prefill_chunk ({cfg.prefill_chunk}) to "
                f"be multiples of ragged_q_tile ({cfg.ragged_q_tile})"
            )
        # The ONE compiled-variant cache (docs/engine_perf.md "One
        # ragged dispatch"): every device program — pure-decode windows,
        # mixed prefill+decode+spec batches — is keyed by
        # (total padded query tokens, static page bound — None on the
        # Pallas path, which reads true lengths — windowed?,
        # full-vs-greedy sampler, want_lp). This replaces the old
        # _decode_fns x _prefill_fns x _spec_fns lattice.
        self._ragged_fns: dict[tuple, Callable] = {}
        # Where a launch's packed buffer goes (``_put_launch``): every
        # chip of the mesh holds the whole of it.
        self._launch_sharding = sharding(P())
        self._no_carries: dict[tuple, jax.Array] = {}
        # Host-side speculation state (drafter + per-row adaptive
        # controller); None = speculation off.
        self._spec = None
        if cfg.spec_mode != "off":
            from ..spec import SpecManager

            self._spec = SpecManager(cfg)
        # Fresh penalty row for a slot: zero it, then count the first
        # sampled token so penalties see every generated token.
        def penalty_init_row(c, i, t):
            return c.at[i].set(0).at[i, t].add(1)

        self._init_row = jax.jit(penalty_init_row, donate_argnums=(0,))

        self._submit_q: queue.Queue[Sequence] = queue.Queue()
        self._wake = threading.Event()
        self._running = False
        self._thread: threading.Thread | None = None
        # What the loop thread has emitted and not yet handed to the
        # asyncio threads: asyncio loop -> [(put_nowait, tuple), ...] in
        # emission order, posted whole where the loop thread is about
        # to wait (_flush_outbox; docs/engine_perf.md "How tokens reach
        # the HTTP thread"). Owned by the loop thread, whose ident
        # ``_outbox_owner`` holds while it runs: an emit from any other
        # thread is posted at once.
        self._outbox: defaultdict[asyncio.AbstractEventLoop, list] = defaultdict(list)
        self._outbox_owner: int | None = None
        # Sticky: the loop thread died on an exception at least once.
        # In-flight requests were failed and a later generate() may
        # restart the loop, but the process is no longer trustworthy —
        # /health reports it and run.py exits non-zero.
        self.loop_crashed = False
        self.steps = 0  # decode step counter (metrics)
        # Warm-boot provisioning (docs/aot.md): variants loaded/built by
        # prewarm() and the boot time it took. 0/0.0 = cold boot.
        self.prewarmed_variants = 0
        self.prewarm_seconds = 0.0
        self._last_gauge_pub = 0.0  # telemetry gauge throttle
        self._last_reap = 0.0  # waiting-deque reap throttle
        # Watchdog progress: bumped once per loop iteration that did
        # real work (dispatch/consume/admit). Frozen counter + queued
        # work past the grace = dump the flight ring.
        self._progress_mark = 0
        self._watchdog: Watchdog | None = None
        self._flight_handle: int | None = None
        # The process's HostWitness notes collector pauses and stalls
        # into this engine's ring while it runs; a stall's note names the
        # newest launch (``_launch`` keeps it here).
        self._witness_handle: int | None = None
        self._last_launch: dict = {}
        # Dispatch stamp of the last page-move gather (engine-loop
        # local; the caller's sync consumes it in the same call chain).
        self._last_move_t = 0.0
        # Chained decode: the dispatched-but-unconsumed window (if any).
        self._inflight: _PendingRagged | None = None
        # Occupancy/movement counters (mirrored to /metrics counters and
        # surfaced by metrics(); tests/test_decode_compaction.py reads them).
        self.wasted_steps = 0  # window steps computed past a row's stop
        self.kv_page_moves = 0  # pages moved by batched gather/scatter
        self.kv_move_dispatches = 0  # batched-move dispatches issued
        self.preempted = 0  # sequences preempted under KV pressure
        # Speculative decoding counters (docs/speculative.md): proposed
        # draft tokens, the prefix the verify pass accepted, tokens
        # actually emitted, and verify dispatches issued — acceptance
        # rate and tokens-per-dispatch derive from these (mirrored to
        # /metrics and tests/test_spec.py).
        self.spec_dispatches = 0  # batched verify dispatches (device)
        self.spec_row_dispatches = 0  # row participations (per-row basis)
        self.spec_draft_tokens = 0
        self.spec_accepted_tokens = 0
        self.spec_emitted_tokens = 0
        # KV handoff leases: confirmations arrive from asyncio threads
        # (the prefill worker's delivery ack) but the page manager is
        # single-writer — queue them for the loop thread, which also
        # runs the expiry reaper each iteration.
        self._lease_confirm_q: queue.Queue[str] = queue.Queue()
        # Prefix pin requests (disagg suffix-only transfer): the decode
        # router asks "how much of this prompt do you already hold?" and
        # pins the answer under a lease. Served on the loop thread (the
        # manager's single writer); results travel back via futures.
        self._pin_q: queue.Queue[tuple] = queue.Queue()
        # Spot-reclamation plane (docs/fault_tolerance.md "Spot
        # reclamation & live migration"): triage snapshots, live-KV
        # extracts and survivor-side prefix seeding all mutate the page
        # manager, so they queue for the loop thread exactly like pins.
        self._reclaim_q: queue.Queue[tuple] = queue.Queue()
        # Telemetry counter snapshot (prefix sharing): the prometheus
        # prefix-hit mirror advances by delta at gauge-publish time (the
        # page manager itself is telemetry-free; COW has its own event-
        # site counter in _resolve_shared_tail).
        self._pub_prefix_hits = {
            "shared": 0, "restore": 0, "persist": 0, "miss": 0
        }
        # Published-so-far G3 corruption counters (delta mirroring, like
        # _pub_prefix_hits — the store's own counters are authoritative).
        self._pub_store_checksum_failures = 0
        self._pub_store_quarantined = 0
        # KV conservation auditor (docs/observability.md "KV
        # conservation auditor"): the loop runs the page manager's O(1)
        # counter-delta check every iteration; a *new* violation set
        # (not the same broken state re-observed) counts, dumps a
        # flight snapshot with the full named audit, and lands in the
        # module-level LEDGER_VIOLATIONS registry the test suites
        # police.
        self.kv_ledger_violations = 0
        self._ledger_last: tuple = ()
        self._ledger_dumped = False
        # Open KV-handoff lease spans: lease_id -> (TraceContext, grant
        # unix time), closed at confirm/reap so `llmctl trace` shows
        # lease grant -> confirm | reap as one hop of the request's
        # timeline. Loop-owned (grant, confirm, and reap all run here).
        self._lease_traces: dict[str, tuple] = {}
        # Predictive KV tiering (docs/engine_perf.md "Predictive KV
        # tiering"). Prefetch: in-flight G2→G1 jobs by target request
        # (loop-owned; the copy thread answers through
        # _prefetch_done_q), completed restores by target for hit
        # attribution at admission (bounded), and the scan throttle.
        self._prefetch_inflight: dict[str, dict] = {}
        self._prefetch_served: "OrderedDict[str, set]" = OrderedDict()
        self._prefetch_done_q: queue.Queue = queue.Queue()
        self._last_prefetch_scan = 0.0
        # Tiering counters (metrics() mirrors + /metrics):
        # pages restored ahead of admission, restored pages the target
        # actually attached, prefetches that completed after their
        # target admitted, proactive swap-outs, and swap-ins.
        self.prefetch_pages = 0
        self.prefetch_hits = 0
        self.prefetch_late = 0
        self.proactive_offloads = 0
        self.swap_ins = 0
        # Request anatomy + workload fingerprint plane
        # (docs/observability.md "Request anatomy"): per-component
        # latency totals (loop-written, mirrored by metrics()), the
        # bounded worst-N exemplar ring behind `llmctl slow`, the live
        # workload fingerprint builder (fed at admission/finish), and
        # the drift watch against an optionally pinned reference
        # fingerprint (DYN_WORKLOAD_REF=<fingerprint.json>).
        self.anatomy_totals: dict[str, float] = dict.fromkeys(
            COMPONENTS, 0.0
        )
        self.anatomy_requests = 0
        self.anatomy_ring = AnatomyRing(
            capacity=int(os.environ.get("DYN_ANATOMY_RING", "16") or 16)
        )
        self.fingerprint = FingerprintBuilder()
        ref = None
        ref_path = os.environ.get("DYN_WORKLOAD_REF", "")
        if ref_path:
            try:
                ref = load_fingerprint(ref_path)
            except (OSError, ValueError) as e:
                log.warning("DYN_WORKLOAD_REF unreadable (%s): %s", ref_path, e)
        self.drift_watch = WorkloadDriftWatch(self.fingerprint, ref)
        self.sched.on_finish = self._record_anatomy
        # Fleet build-info (docs/observability.md "Fleet plane"): the
        # AOT lattice manifest hash + jax version + feature flags, so
        # fleet scrapes can detect config skew between instances.
        # Computed AND published once in the single-threaded
        # construction window — the engine starts lazily on first
        # traffic, and a scrape must see the fingerprint from boot.
        self._build_info = self._compute_build_info()
        get_telemetry().set_build_info(**self._build_info)
        # Say where it ran, once: what a chip run reads instead of
        # trusting the command line. The peak covers construction, so a
        # weight or pool that passed through one device shows here.
        log.info(
            "engine placed: platform=%s device_kind=%s device_count=%d "
            "device_ids=%s attn_impl=%s peak_bytes_in_use=%s",
            self._build_info["platform"],
            self._build_info["device_kind"],
            self._build_info["device_count"],
            [d.id for d in self.mesh.devices.flat],
            self._build_info["attn_impl"],
            [
                (d.memory_stats() or {}).get("peak_bytes_in_use")
                for d in self.mesh.devices.flat
            ],
        )

    # ----------------------------------------------------------- compiled fns
    def _resolve_attn(self) -> tuple[str, bool]:
        return resolve_attn_impl(self.cfg, self.mesh)

    def _ragged_fn(
        self,
        nb: int,
        attn_pages: int | None,
        windowed: bool,
        full_sampler: bool,
        want_lp: bool,
        with_spec: bool = False,
    ):
        """One compiled ragged program (docs/engine_perf.md "One ragged
        dispatch"). The variant key is the collapsed lattice

            (total padded query tokens, page bound, windowed,
             full-vs-greedy sampler, want_lp, with_spec)

        — a single token axis where the old engine keyed three compiled
        families (decode windows by rows x impl x pages x sampler x lp,
        prefill by rows x token bucket x pages, spec verify by rows x
        draft bucket x pages x sampler x lp).

        ``windowed=True`` (pure decode: ``nb`` rows, one fed token
        each) runs ``decode_window`` steps on-device under ``lax.scan``
        with sampled tokens fed straight back — the host syncs once per
        window, which is what makes decode throughput survive a
        high-latency host-device link. Per-row stop sets / step gates
        park a finished row at position -1 mid-window (no garbage KV
        writes), and the final carry is returned so the next window can
        chain device-to-device. This path is byte-for-byte the old
        compacted decode window: compute tracks true occupancy.

        ``windowed=False`` (mixed) is one ragged forward over a flat
        query stream: chunked-prefill spans, single decode steps, and
        speculative verify spans share the dispatch
        (``models/llama.forward_ragged`` → ``ops/ragged_attention``).
        Each row samples at its last fed position with the same
        (seed, absolute position) counter keying a decode window would
        use — so a prompt's first token, a decode row's next token, and
        a verify span's accepted prefix are all bit-identical to the
        two-program schedule. Only the ``max_decode_slots + 1`` sampled
        positions (plus the spec span when speculation is on) reach the
        vocab projection, so lm_head cost stays flat in chunk width.

        Which attention form a program is built with is the engine's
        resolved implementation (``resolve_attn_impl``) and nothing
        else: on a kernel engine every window and every mixed dispatch
        reads its contexts through the kernel, whatever the row bucket
        and however short the contexts (the measured table and its
        origin: ``aot.lattice.resolve_ragged_key``). On the XLA path
        ``attn_pages`` bounds the gather; a window's is its longest
        row's page bucket. The Pallas page-bound collapse lives in
        ``resolve_ragged_key`` — ONE key function shared with the
        offline lattice enumeration, so the AOT manifest can never
        drift from what this loop dispatches."""
        key = resolve_ragged_key(
            self.cfg, self._attn_impl, nb, attn_pages, windowed,
            full_sampler, want_lp, with_spec,
        )
        return self._ragged_fn_from_key(key)

    def _ragged_fn_from_key(self, key: tuple):
        """Build (or fetch) the compiled program for an already-resolved
        variant key — the seam ``aot/`` prewarm and AOT compilation
        drive directly from manifest entries."""
        fn = self._ragged_fns.get(key)
        if fn is not None:
            return fn
        nb, pages, windowed, full_sampler, want_lp, with_spec = key
        impl = impl_for_key(key)
        # Once per variant, not per dispatch: which attention each
        # program was built with (the key's, which is the engine's).
        log.info(
            "ragged variant: nb=%d pages=%s windowed=%s full_sampler=%s "
            "want_lp=%s with_spec=%s attn=%s",
            nb, pages, windowed, full_sampler, want_lp, with_spec, impl,
        )
        if self.cfg.model.block_length:
            # Generation by diffusion over blocks (engine/block.py): the
            # same two families, a block where the others have a token.
            build = self._block_program if windowed else self._mixed_block_program
            fn = build(nb, pages, impl, full_sampler, want_lp)
        elif windowed:
            fn = self._windowed_program(nb, pages, impl, full_sampler, want_lp)
        else:
            fn = self._mixed_program(
                nb, pages, impl, full_sampler, want_lp, with_spec
            )
        self._ragged_fns[key] = fn
        return fn

    def _windowed_program(self, nb, pages, impl, full_sampler, want_lp):
        """Build the pure-decode windowed variant (see _ragged_fn)."""
        interpret, mesh = self._attn_interpret, self.mesh
        mcfg = self.cfg.model
        K = self.cfg.decode_window

        # A model with sequence state takes the pool last, donates it and
        # returns it last (the rows' entries are fields of the packed
        # buffer); for every other model there is neither.
        has_state = mcfg.has_seq_state
        count_picks = mcfg.counts_picks
        layout = launch_layout(
            self.cfg, (nb, pages, True, full_sampler, want_lp, False)
        )

        def unpack(packed, carry_tokens, carry_positions):
            # The launch's one buffer, cut apart (aot/launch.py). A
            # chained window feeds the carry of the one before it.
            f = unpack_launch(layout, packed)
            chained = f.pop("chained")[0] != 0
            f["tokens"] = jnp.where(chained, carry_tokens, f["tokens"])
            f["positions"] = jnp.where(
                chained, carry_positions, f["positions"]
            )
            return f

        def run_forward(params, tokens, positions, page_table, k, v,
                        state, state_slots):
            logits, k, v, *rest = forward(
                params, mcfg, tokens[:, None], positions[:, None],
                page_table, k, v, attn_pages=pages, attn_impl=impl,
                mesh=mesh, interpret=interpret,
                seq_state=state, state_slots=state_slots,
                count_picks=count_picks,
            )
            # the state pool, then the step's held picks: each where the
            # model has it (``ys`` carries the picks out, last)
            picks = (rest.pop(),) if count_picks else ()
            return logits[:, 0], k, v, (rest[0] if rest else None), picks  # [nb, V]

        def advance(positions, max_pos, next_tok, stop_set, eos_gate,
                    budget_gate, t, active):
            # A row leaves the window (position -1, writes dropped) when
            # it hits its page/model-length capacity, samples a token
            # from its stop set past its min-tokens gate, or exhausts
            # its remaining max_tokens budget.
            done = (
                stop_token_hit(next_tok, stop_set) & (t >= eos_gate)
            ) | (t >= budget_gate)
            return jnp.where(
                active & ~done & (positions < max_pos), positions + 1, -1
            )

        if full_sampler:

            @partial(
                jax.jit,
                donate_argnums=(1, 2, 6, 7) if has_state else (1, 2, 6),
            )
            def ragged_window(params, k, v, carry_tokens, carry_positions,
                              packed, counts_all, state=None):
                f = unpack(packed, carry_tokens, carry_positions)
                tokens, positions = f["tokens"], f["positions"]
                max_pos = f["max_pos"]
                page_table = split_tables(self.cfg, f["table"])
                seeds, slot_map = f["seeds"], f["slot_map"]
                temp, top_k, top_p = f["temp"], f["top_k"], f["top_p"]
                freq_pen, pres_pen, rep_pen = f["freq"], f["pres"], f["rep"]
                stop_set, eos_gate = f["stop_set"], f["eos_gate"]
                budget_gate = f["budget_gate"]
                state_slots = f.get("state_slots")
                # Compaction: penalty rows live slot-indexed in the
                # [B+1, V] pool; gather the stepped rows in, scatter
                # back out (pad rows map to the scratch row B).
                counts0 = counts_all[slot_map]

                def step(carry, t):
                    tokens, positions, k, v, counts, state = carry
                    logits, k, v, state, picks = run_forward(
                        params, tokens, positions, page_table, k, v,
                        state, state_slots,
                    )
                    with jax.named_scope("sample"):
                        shaped = apply_penalties(
                            logits, counts, freq_pen, pres_pen, rep_pen
                        )
                        # Counter-based draw keyed by (seed, fed
                        # position): deterministic replay across
                        # instances/windows, the property resumable
                        # streams rebuild state from.
                        next_tok = sample_tokens_seeded(
                            shaped, seeds, positions, temp, top_k, top_p
                        )
                        # OpenAI logprobs: of the MODEL distribution (raw
                        # logits, pre-penalty/temperature), chosen +
                        # top-k. Compiled only into the want_lp variant —
                        # the common no-logprobs workload pays neither the
                        # full-vocab log_softmax nor the extra per-window
                        # host transfer.
                        if want_lp:
                            lp, top_ids, top_lp = token_logprobs(
                                logits, next_tok
                            )
                        active = positions >= 0
                        counts = counts.at[
                            jnp.arange(counts.shape[0]), next_tok
                        ].add(active.astype(jnp.int32))
                    tokens = jnp.where(active, next_tok, tokens)
                    positions = advance(
                        positions, max_pos, next_tok, stop_set, eos_gate,
                        budget_gate, t, active,
                    )
                    ys = (
                        (next_tok, lp, top_ids, top_lp)
                        if want_lp
                        else (next_tok,)
                    )
                    return (tokens, positions, k, v, counts, state), ys + picks

                (tokens, positions, k, v, counts, state), ys = jax.lax.scan(
                    step, (tokens, positions, k, v, counts0, state),
                    jnp.arange(K),
                )
                counts_all = counts_all.at[slot_map].set(counts)
                # ys: toks [K,nb] (+ lp [K,nb], top_ids/top_lp
                # [K,nb,N] when want_lp).
                out = (ys, k, v, counts_all, tokens, positions)
                return out + (state,) if has_state else out

        else:

            @partial(
                jax.jit, donate_argnums=(1, 2, 6) if has_state else (1, 2)
            )
            def ragged_window(params, k, v, carry_tokens, carry_positions,
                              packed, state=None):
                f = unpack(packed, carry_tokens, carry_positions)
                tokens, positions = f["tokens"], f["positions"]
                max_pos = f["max_pos"]
                page_table = split_tables(self.cfg, f["table"])
                stop_set, eos_gate = f["stop_set"], f["eos_gate"]
                budget_gate = f["budget_gate"]
                state_slots = f.get("state_slots")
                def step(carry, t):
                    tokens, positions, k, v, state = carry
                    logits, k, v, state, picks = run_forward(
                        params, tokens, positions, page_table, k, v,
                        state, state_slots,
                    )
                    with jax.named_scope("sample"):
                        next_tok = jnp.argmax(logits, axis=-1).astype(
                            jnp.int32
                        )
                        if want_lp:
                            lp, top_ids, top_lp = token_logprobs(
                                logits, next_tok
                            )
                    active = positions >= 0
                    tokens = jnp.where(active, next_tok, tokens)
                    positions = advance(
                        positions, max_pos, next_tok, stop_set, eos_gate,
                        budget_gate, t, active,
                    )
                    ys = (
                        (next_tok, lp, top_ids, top_lp)
                        if want_lp
                        else (next_tok,)
                    )
                    return (tokens, positions, k, v, state), ys + picks

                (tokens, positions, k, v, state), ys = jax.lax.scan(
                    step, (tokens, positions, k, v, state), jnp.arange(K)
                )
                out = (ys, k, v, tokens, positions)
                return out + (state,) if has_state else out

        return ragged_window

    def _mixed_program(self, nb, pages, impl, full_sampler, want_lp,
                       with_spec):
        """Build the mixed ragged variant (see _ragged_fn): one flat
        forward over prefill + decode + spec spans, per-row sampling at
        the last fed position, and — for batches that carry draft spans
        (``with_spec``) — the verify rule (accepted prefix +
        correction, with penalty counts threaded past rejections)
        computed on-device from the same logits. Draft-free batches
        compile the spec-free program: they pay neither the extra
        vocab projections nor the verify scan. No decode scan either
        way: drafts and chunks are re-planned from the freshly
        consumed tokens every iteration."""
        interpret, mesh = self._attn_interpret, self.mesh
        mcfg = self.cfg.model
        B1 = self.cfg.max_decode_slots + 1
        spec_on = with_spec
        T_s = self.cfg.spec_max_draft + 1
        q_tile = self._ragged_align()
        # The stream's packed tail (_decode_from): static, from nb alone.
        packed_from = (
            nb - self.cfg.ragged_packed_slots(nb) if impl == "pallas" else None
        )

        has_state = mcfg.has_seq_state  # as in _windowed_program
        count_picks = mcfg.counts_picks
        layout = launch_layout(
            self.cfg, (nb, pages, False, full_sampler, want_lp, with_spec)
        )

        def state_rows_of(f):
            if not has_state:
                return None
            return f["state_slots"], f["state_src"], f["state_dst"]

        def run_forward(params, k, v, tokens, positions, row_of,
                        page_table, out_idx, state, state_rows):
            logits, k, v, *rest = forward_ragged(
                params, mcfg, tokens, positions, row_of, page_table,
                k, v, out_idx, attn_pages=pages, attn_impl=impl,
                q_tile=q_tile, packed_from=packed_from, mesh=mesh,
                interpret=interpret, seq_state=state, state_rows=state_rows,
                count_picks=count_picks,
            )
            picks = (rest.pop(),) if count_picks else ()  # _windowed_program's
            return logits, k, v, (rest[0] if rest else None), picks

        def out_indices(q_last, spec_idx):
            if not spec_on:
                return q_last
            return jnp.concatenate([q_last, spec_idx.reshape(-1)])

        def pack_spec_lp(spec_logits, targets):
            V = spec_logits.shape[-1]
            lp, tid, tlp = token_logprobs(
                spec_logits.reshape(-1, V), targets.reshape(-1)
            )
            return (
                lp.reshape(B1, T_s),
                tid.reshape(B1, T_s, -1),
                tlp.reshape(B1, T_s, -1),
            )

        if full_sampler:

            @partial(
                jax.jit,
                donate_argnums=(1, 2, 4, 5) if has_state else (1, 2, 4),
            )
            def ragged_mixed(params, k, v, packed, counts_all, state=None):
                f = unpack_launch(layout, packed)
                tokens, positions = f["tokens"], f["positions"]
                row_of = f["row_of"]
                page_table = split_tables(self.cfg, f["table"])
                q_last, pos0 = f["q_last"], f["pos0"]
                slot_map, is_decode = f["slot_map"], f["is_decode"]
                seeds = f["seeds"]
                temp, top_k, top_p = f["temp"], f["top_k"], f["top_p"]
                freq_pen, pres_pen, rep_pen = f["freq"], f["pres"], f["rep"]
                spec_idx, spec_pos = f.get("spec_idx"), f.get("spec_pos")
                spec_drafts, n_drafts = f.get("spec_drafts"), f.get("n_drafts")
                state_rows = state_rows_of(f)
                logits_all, k, v, state, picks = run_forward(
                    params, k, v, tokens, positions, row_of, page_table,
                    out_indices(q_last, spec_idx), state, state_rows,
                )
                with jax.named_scope("sample"):
                    logits0 = logits_all[:B1]
                    counts0 = counts_all[slot_map]
                    # Decode rows sample through their penalty counts (the
                    # window rule); a prompt's first token samples the raw
                    # model distribution (the prefill rule — the host
                    # initializes its counts row at consume).
                    shaped = apply_penalties(
                        logits0, counts0, freq_pen, pres_pen, rep_pen
                    )
                    dec = is_decode[:, None]
                    tok0 = sample_tokens_seeded(
                        jnp.where(dec, shaped, logits0),
                        seeds, pos0, temp, top_k, top_p,
                    )
                    counts = counts0.at[jnp.arange(B1), tok0].add(
                        is_decode.astype(jnp.int32)
                    )
                    if want_lp:
                        lp0, tid0, tlp0 = token_logprobs(logits0, tok0)
                    ys = (tok0,)
                    if spec_on:
                        spec_logits = logits_all[B1:].reshape(B1, T_s, -1)
                        targets, n_emit, counts = spec_verify_tokens(
                            spec_logits, spec_drafts, n_drafts, seeds,
                            spec_pos, temp, top_k, top_p, counts, freq_pen,
                            pres_pen, rep_pen,
                        )
                        ys = ys + (targets, n_emit)
                    counts_all = counts_all.at[slot_map].set(counts)
                    if want_lp:
                        ys = ys + (lp0, tid0, tlp0)
                        if spec_on:
                            ys = ys + pack_spec_lp(spec_logits, targets)
                out = (ys + picks, k, v, counts_all)
                return out + (state,) if has_state else out

        else:

            @partial(
                jax.jit, donate_argnums=(1, 2, 4) if has_state else (1, 2)
            )
            def ragged_mixed(params, k, v, packed, state=None):
                f = unpack_launch(layout, packed)
                tokens, positions = f["tokens"], f["positions"]
                row_of = f["row_of"]
                page_table = split_tables(self.cfg, f["table"])
                q_last, spec_idx = f["q_last"], f.get("spec_idx")
                spec_drafts, n_drafts = f.get("spec_drafts"), f.get("n_drafts")
                state_rows = state_rows_of(f)
                logits_all, k, v, state, picks = run_forward(
                    params, k, v, tokens, positions, row_of, page_table,
                    out_indices(q_last, spec_idx), state, state_rows,
                )
                with jax.named_scope("sample"):
                    logits0 = logits_all[:B1]
                    tok0 = jnp.argmax(logits0, axis=-1).astype(jnp.int32)
                    if want_lp:
                        lp0, tid0, tlp0 = token_logprobs(logits0, tok0)
                    ys = (tok0,)
                    if spec_on:
                        spec_logits = logits_all[B1:].reshape(B1, T_s, -1)
                        targets = jnp.argmax(spec_logits, axis=-1).astype(
                            jnp.int32
                        )
                        n_emit = spec_accept_length(
                            targets, spec_drafts, n_drafts
                        )
                        ys = ys + (targets, n_emit)
                    if want_lp:
                        ys = ys + (lp0, tid0, tlp0)
                        if spec_on:
                            ys = ys + pack_spec_lp(spec_logits, targets)
                out = (ys + picks, k, v)
                return out + (state,) if has_state else out

        return ragged_mixed

    def _ragged_align(self) -> int:
        """Flat-stream alignment of a row's span of several query tokens
        (a prefill chunk, a draft span): the Pallas ragged kernel
        requires every ``ragged_q_tile`` slice of the stream's tiled
        part to belong to one row; the XLA reference packs tight. A
        decode row's one token is not such a span (``_mixed_bucket_for``)."""
        return self.cfg.ragged_q_tile if self._attn_impl == "pallas" else 1

    def _mixed_bucket_for(self, aligned: int, singles: int) -> int:
        """Token bucket of a mixed dispatch, given the extent of its
        spans of several tokens (``aligned``: each already padded to
        ``_ragged_align()``) and how many decode rows it carries
        (``singles``, one token each).

        The reference packs tight: the decode rows follow the spans.
        A kernel engine's stream is two static parts (docs/engine_perf.md
        "One ragged dispatch"): tiles of ``ragged_q_tile`` slots, one
        row a tile, then ``cfg.ragged_packed_slots(nb)`` slots read one
        query a cell. The decode rows go side by side into the second
        part, one slot each (``_decode_from``); spans that outgrow the
        first part run on into the second (a cell there serves any one
        token, a padding slot included) and the decode rows follow them.
        The bucket is the smallest whose second part holds every decode
        row and whose whole holds both; never larger than
        ``aligned + 8 * singles`` would have taken."""
        cfg = self.cfg
        nb = cfg.ragged_tokens_bucket_for(
            max(aligned + singles, 1), mixed=True
        )
        if self._attn_impl == "pallas":
            while singles > cfg.ragged_packed_slots(nb):
                nb *= 2
        return nb

    def _decode_from(self, nb: int, aligned: int) -> int:
        """The slot a mixed stream's decode rows start at: behind the
        spans, and on a kernel engine no earlier than the packed tail."""
        if self._attn_impl != "pallas":
            return aligned
        return max(aligned, nb - self.cfg.ragged_packed_slots(nb))

    def _plan_chunks(
        self,
        remaining: list[int],
        admitted: list[float],
        singles: int,
        spans: list[int],
    ) -> list[int]:
        """Prompt tokens each prefilling row of a mixed dispatch feeds,
        given every row's prompt left (``remaining``) and when it was
        admitted, the decode rows beside them (``singles``) and the
        speculative spans' lengths (``spans``).

        ``cfg.prefill_chunk`` is the share every row is guaranteed. The
        token bucket those shares take (``_mixed_bucket_for``, as ever)
        is a power of two, and what it leaves empty is paid for all the
        same: a slot is a row of every projection whether a token lies
        in it or not. So the room goes to rows with prompt left beyond
        their share, in whole pages (a chunk then ends on a block
        boundary wherever the share alone would have, which a state's
        snapshot and the scan kernel's writes rest on) or up to the
        prompt's end, the row admitted earliest first: it reaches its
        first token soonest and leaves the next dispatch smaller. The
        bucket stays the one the shares took."""
        cfg = self.cfg
        align, ps = self._ragged_align(), cfg.page_size

        def up(n: int) -> int:
            return -(-n // align) * align

        chunks = [min(n, cfg.prefill_chunk) for n in remaining]
        aligned = sum(map(up, chunks)) + sum(map(up, spans))
        nb = self._mixed_bucket_for(aligned, singles)
        # Spans lie before the decode rows, which a kernel engine reads
        # from the packed tail; spans that already run on into the tail
        # leave no room.
        tiled = nb - singles
        if singles and self._attn_impl == "pallas":
            tiled = nb - cfg.ragged_packed_slots(nb)
        room = tiled - aligned
        if room <= 0:
            return chunks
        for i in sorted(range(len(chunks)), key=admitted.__getitem__):
            more = remaining[i] - chunks[i]
            if more > room:
                more = room // ps * ps
            room -= up(chunks[i] + more) - up(chunks[i])
            chunks[i] += more
        assert self._mixed_bucket_for(tiled - room, singles) == nb, (chunks, nb)
        return chunks

    def _launch_buffer(
        self, nb: int, windowed: bool, full_sampler: bool,
        with_spec: bool = False, like: np.ndarray | None = None,
    ) -> tuple[np.ndarray, dict]:
        """The one buffer a dispatch's program takes and its builder's
        arrays by name, as the all-padding batch or as ``like``, an
        earlier launch's (``aot/launch.py``)."""
        return launch_buffer(
            self.cfg, (nb, None, windowed, full_sampler, False, with_spec),
            self._state_entries, like,
        )

    def _put_launch(self, program: str, *host) -> tuple:
        """A launch's host arrays on the device, replicated over the
        mesh: the whole of ``launch.h2d``. One packed buffer a launch
        (``aot/launch.py``), so ``dynamo_launch_transfers_total`` over
        ``dynamo_ragged_dispatch_total`` reads 1; a path that hands
        over field by field again shows there as more."""
        get_telemetry().launch_transfers.labels(program).inc(len(host))
        return tuple(jax.device_put(host, self._launch_sharding))

    def _no_carry(self, nb: int, block: int = 0) -> jax.Array:
        """What a window that is not chained passes for the carry of
        tokens and of positions: zeros the program does not read
        (``chained`` is 0), on the device since the first such window.
        ``block``: a block model's tokens, a block a row."""
        shape = (nb, block) if block else (nb,)
        carry = self._no_carries.get(shape)
        if carry is None:
            carry = self._no_carries[shape] = jax.device_put(
                np.zeros(shape, np.int32), self._launch_sharding
            )
        return carry

    def _run_program(self, fn, *args) -> tuple:
        """Call a ragged program. For a model with sequence state it also
        gets the state pool, last, and the pool it returns replaces the
        engine's (the rows' entries ride the packed buffer)."""
        if self.seq_state is None:
            return fn(*args)
        *out, self.seq_state = fn(*args, self.seq_state)
        return tuple(out)

    def _launch(
        self, nb: int, max_pages: int, packed, rows: list, /, *,
        windowed: bool, full_sampler: bool, want_lp: bool,
        with_spec: bool = False, carry: tuple | None = None,
        live: int | None = None, solo: bool = False,
        capacity_capped: bool = False, routed_rows: int | None = None,
        singles: int = 0, **event,
    ) -> _PendingRagged:
        """The life of a dispatch, from its builder's packed buffer and
        rows to the pending record its consumer takes (no host sync;
        docs/engine_perf.md "The life of a dispatch"): look the program up,
        flush the offloads that must precede it, hand the buffer over,
        call, take the pools back, count, record. ``carry``: the device
        state a window or a block chained onto the dispatch before it
        starts from; None for any other, and a window or block that is
        not chained gets zeros its program does not read. ``live``,
        ``singles``: ``_count_dispatch``'s. ``routed_rows``:
        ``_PendingRagged``'s (the live count where the builder gives
        none). ``event``: the builder's own fields of the ``dispatch``
        flight event."""
        t_in = time.monotonic()
        block = self.cfg.model.block_length
        program = ("block" if block else "window") if windowed else "mixed"
        with self._phase("assemble"), self._part("assemble", "lookup"):
            n_variants = len(self._ragged_fns)
            page_bucket = self.cfg.ragged_page_bucket_for(max_pages)
            fn = self._ragged_fn(  # dynlint: recompile-hazard(a launch's bucket is its builder's, checked where _launch is called)
                nb, page_bucket, windowed, full_sampler, want_lp, with_spec,
            )
            fresh = len(self._ragged_fns) > n_variants
        if self._pending_offloads:
            with self._phase("kv_admin"):
                self._flush_offloads()
        chained = carry is not None
        if windowed and not chained:
            carry = (self._no_carry(nb, block), self._no_carry(nb))
        prof = self.profiler
        t0 = prof.begin("ragged", program) if prof is not None else 0.0
        with self._part("launch", "h2d"):
            args = (*(carry or ()), *self._put_launch(program, packed))
            if full_sampler:
                args += (self._counts,)
        with self._part("launch", "call"):
            out = self._run_program(
                fn, self.params, self.k_cache, self.v_cache, *args
            )
        # ys, the pools, the counts of a full sampler, and what a windowed
        # program carries on to one chained onto it (tokens, positions).
        ys, self.k_cache, self.v_cache, *rest = out
        if full_sampler:
            self._counts, *rest = rest
        tokens_dev, positions_dev = rest or (None, None)
        dispatched_at = (
            prof.end("ragged", t0, fresh) if prof is not None else 0.0
        )
        # From this function's entry: the lookup, the offloads and the
        # transfer lie before what ``host_gap_seconds`` ends at (``begin``).
        self._launch_seconds[program].observe(
            (dispatched_at or time.monotonic()) - t_in
        )
        key = self._count_dispatch(
            program, nb, page_bucket, windowed, full_sampler, want_lp,
            with_spec, chained=chained, live=live, singles=singles,
        )
        self.flight.record(
            "dispatch", dispatch="ragged", **event, bucket=nb,
            windowed=windowed, pages=key[1], want_lp=want_lp,
        )
        self._last_launch = {
            "dispatch_seq": self.flight.seq - 1, "program": program, "bucket": nb,
        }
        return _PendingRagged(
            ys=ys,
            rows=rows,
            nb=nb,
            windowed=windowed,
            full_sampler=full_sampler,
            want_lp=want_lp,
            solo=solo,
            with_spec=with_spec,
            tokens_dev=tokens_dev,
            positions_dev=positions_dev,
            capacity_capped=capacity_capped,
            packed=packed if windowed else None,
            dispatched_at=dispatched_at,
            routed_rows=(live or 0) if routed_rows is None else routed_rows,
        )

    def _count_picks(self, pending: _PendingRagged) -> None:
        """Of a consumed dispatch of a model whose programs count them
        (``ModelConfig.counts_picks``: one that holds a share of its
        experts, or all of them and asks: then none lies elsewhere), the
        picks that landed on held experts (the program's last
        output, a pair, or one a window's step), those that lay elsewhere
        (``dynamo_moe_picks_total``) and the held experts that had a pick
        (``dynamo_moe_held_expert_reads_total``)."""
        m = self.cfg.model
        if not m.counts_picks:
            return
        # [2], or a window's [steps, 2]: picks held, held experts with a pick
        held, reached = (
            int(n) for n in np.asarray(pending.ys[-1]).reshape(-1, 2).sum(axis=0)  # dynlint: sync-point(ragged consume)
        )
        tel = get_telemetry()
        tel.moe_picks.labels("held").inc(held)
        if m.num_experts_held:  # else none lies elsewhere
            total = pending.routed_rows * m.num_experts_per_tok * m.num_routed_layers
            tel.moe_picks.labels("elsewhere").inc(max(total - held, 0))
        tel.moe_held_expert_reads.labels(
            "window" if pending.windowed else "mixed"
        ).inc(reached)

    def _latent_form_queries(self) -> int | None:
        """Live queries of one row in a mixed stream's tiled part from
        which the row's latent attention takes the expanded form; None:
        every row is absorbed (``models/llama.py::latent_form_queries``,
        the function the program itself asks)."""
        from ..models.llama import latent_form_queries

        if self._attn_impl != "pallas":
            return None
        return latent_form_queries(
            self.cfg.model, self.cfg.ragged_q_tile, self.cfg.page_size,
            self.cfg.kv_dtype_jnp, self._attn_interpret,
        )

    def _count_latent_reads(self, program: str, positions, row_of=None,
                            steps=None, tiled: int = 0) -> None:
        """What latent attention attended and read in one dispatch, a
        layer's (``dynamo_mla_*_total``), and the form each query took. A
        mixed dispatch gives its stream's ``positions`` and ``row_of`` (a
        row's context is read once, up to its last query) and how many of
        its slots a kernel engine reads ``tiled``: a row with enough queries
        there took the expanded form for them (``ops/ragged_attention.py::
        expanded_rows``, the program's rule), everything else the absorbed
        one. A window gives each row's first position and its ``steps``
        (a step reads the row's context anew; every step is absorbed).
        A model with an indexer attends ``min(p + 1, index_topk)`` pairs a
        query and is asked to score all ``p + 1`` (``dynamo_dsa_*_total``:
        every causal pair, also where a stream part skips the scoring
        because none of its queries sees more than ``index_topk``).
        A grouped-query model with an indexer counts the ``dsa_*`` series
        alone: its attention's work is ``_count_attn_work``'s.
        Host arithmetic on what the builder holds."""
        if not (self.cfg.model.is_latent or self.cfg.model.has_indexer):
            return
        positions = np.asarray(positions, np.int64)
        expanded = 0
        topk = self.cfg.model.index_topk
        if steps is None:
            live = positions >= 0
            seen = positions[live] + 1
            queries, pairs = int(live.sum()), int(seen.sum())
            if topk:
                selecting = int((seen > topk).sum())
                kept = int(np.minimum(seen, topk).sum())
            by_row = np.zeros(int(row_of.max()) + 1 if len(row_of) else 0, np.int64)
            np.maximum.at(by_row, row_of[live], seen)
            context = int(by_row.sum())
            least = self._latent_form_queries()
            if least is not None and tiled >= least:
                from ..ops.ragged_attention import expanded_rows

                rows, at = row_of[:tiled], positions[:tiled]
                takes = expanded_rows(rows, at, least, len(by_row), xp=np)
                expanded = int((takes[rows] & (at >= 0)).sum())
        else:
            steps = np.asarray(steps, np.int64)
            queries = int(steps.sum())
            pairs = int((steps * (positions + 1) + steps * (steps - 1) // 2).sum())
            context = pairs
            if topk:
                # step j of a row sees positions + 1 + j: those over topk
                # keep topk, the others all they see
                under = np.clip(topk - positions, 0, steps)  # steps seeing <= topk
                selecting = int((steps - under).sum())
                kept = int((
                    under * (positions + 1) + under * (under - 1) // 2
                    + (steps - under) * topk
                ).sum())
        tel = get_telemetry()
        if topk:
            tel.dsa_index_pairs.labels(program).inc(pairs)
            tel.dsa_selected_pairs.labels(program).inc(kept)
            tel.dsa_query_tokens.labels(program, "selecting").inc(selecting)
            tel.dsa_query_tokens.labels(program, "all").inc(queries - selecting)
            pairs = kept  # what attention attends: the published work
        if not self.cfg.model.is_latent:
            return
        tel.mla_query_tokens.labels(program, "absorbed").inc(queries - expanded)
        if expanded:
            tel.mla_query_tokens.labels(program, "expanded").inc(expanded)
        tel.mla_attended_pairs.labels(program).inc(pairs)
        tel.mla_context_tokens.labels(program).inc(context)

    def _count_attn_work(self, positions, steps=None, row_of=None) -> None:
        """Of a model with sliding layers, of a looped model (whose
        every layer-pass counts as a full layer: a token's queries, keys
        and context are read anew in each pass) and of a grouped-query
        model with an indexer (the key positions a query MAY see stay ``t +
        1``: the ``min(t + 1, index_topk)`` it attends go to
        ``dynamo_dsa_selected_pairs_total``), the queries a dispatch
        feeds its
        attention layers, the key positions those may see and the context
        tokens a row's queries see between them, by the layers' form and
        summed over the layers of each (``dynamo_attn_query_tokens_total``,
        ``dynamo_attn_key_positions_total``,
        ``dynamo_attn_context_tokens_total``): a query at position ``t``
        sees ``t + 1`` keys in a full layer and ``min(t + 1, width)`` in a
        sliding one; a row's queries of one dispatch see its context up to
        their last, in a sliding layer from the first's ``width - 1``
        predecessors on. The work the rooflines read, whatever computes it.
        A mixed dispatch gives its stream's ``positions`` (-1: no query)
        and ``row_of``; a window each row's first position and its
        ``steps`` (a step reads anew). Host arithmetic on what the builder
        holds; no other model counts."""
        layers = self._attn_form_layers
        if not layers:
            return
        t = np.asarray(positions, np.int64)  # dynlint: sync-point(host-list conversion)
        width = self.cfg.model.sliding_window
        if steps is not None:
            n = np.asarray(steps, np.int64)  # dynlint: sync-point(host-list conversion)
            t = np.concatenate(
                [np.arange(a, a + k) for a, k in zip(t, n)] or [t[:0]]
            )
        live = t >= 0
        seen = t[live] + 1
        keys = {"full": seen}
        if width is not None:
            keys["sliding"] = np.minimum(seen, width)
        context = keys  # a window's step reads anew
        if steps is None:
            rows = np.asarray(row_of)[live]  # dynlint: sync-point(host-list conversion)
            last = np.zeros(int(rows.max()) + 1 if len(rows) else 0, np.int64)
            first = np.full(len(last), np.iinfo(np.int64).max)
            np.maximum.at(last, rows, seen)
            np.minimum.at(first, rows, seen)
            last, first = last[last > 0], first[last > 0]
            context = {"full": last}
            if width is not None:
                context["sliding"] = last - np.maximum(first - width, 0)
        tel = get_telemetry()
        for form, count in layers.items():
            tel.attn_query_tokens.labels(form).inc(count * len(seen))
            tel.attn_key_positions.labels(form).inc(count * int(keys[form].sum()))
            tel.attn_context_tokens.labels(form).inc(
                count * int(context[form].sum())
            )

    def _count_dispatch(
        self, program, nb, attn_pages, windowed, full_sampler, want_lp,
        with_spec, chained: bool, live: int, singles: int = 0,
    ) -> tuple:
        """Count one ragged dispatch under its whole variant key
        (``dynamo_ragged_dispatch_total``), its live and padding slots
        (``dynamo_ragged_slots_total``; a window's rows times its
        steps), for a selective-scan model the live ones as tokens
        through the scan (``dynamo_ssm_scan_tokens_total``; a Mamba-2
        model's through its recurrence, ``dynamo_mamba2_tokens_total``), for a
        linear-attention model the same through the delta rule
        (``dynamo_linear_attn_tokens_total``) and those of them that
        took its one-token step (``dynamo_linear_attn_step_rows_total``:
        a window's every live slot, of a mixed stream its ``singles``,
        the decode rows, where the stream has a packed tail) and, for a
        routed-expert model, the same as rows
        (``dynamo_moe_rows_total``) and the way its expert matmuls
        read their weights (``dynamo_moe_dispatch_total``). Host
        arithmetic on what the builder already holds. Returns the key."""
        key = resolve_ragged_key(
            self.cfg, self._attn_impl, nb, attn_pages, windowed,
            full_sampler, want_lp, with_spec,
        )
        tel = get_telemetry()
        tel.ragged_dispatches.labels(
            program,
            str(nb),
            "kernel" if key[1] is None else str(key[1]),
            str(int(want_lp)),
            str(int(full_sampler)),
            str(int(chained)),
        ).inc()
        if live is not None:
            self._count_slots(
                program, live,
                nb * (self.cfg.decode_window if windowed else 1) - live,
            )
            if self.cfg.model.num_linear_layers:
                # A mixed stream has a packed tail on the kernel engine; the
                # reference's is the oracle's, walked whole.
                tel.linear_attn_step_rows.labels(program).inc(
                    live if windowed
                    else singles if self._attn_impl == "pallas" else 0
                )
        if self.cfg.model.is_moe:
            tel.moe_dispatches.labels(routed_experts_form(
                self.cfg.model, self.mesh, self._attn_interpret
            )).inc()
        return key

    def _count_attn_passes(self, nb: int, row_of, positions) -> None:
        """Of a mixed stream's tiled part, the live slots whose query
        block the attention kernel reads in one pass over their row's
        context and those it walks tile by tile
        (``dynamo_attn_query_slots_total``): the kernel's own rule
        (``ops/ragged_attention.py::block_plan``) on the host's copy of
        what it takes; and its packed tail's rows by their first fetch
        (``_count_one_query_rows``). The reference has no tiles and counts
        nothing."""
        if self._attn_impl != "pallas":
            return
        from ..models.llama import ragged_query_block
        from ..ops.ragged_attention import merged_slot_counts

        cfg = self.cfg
        tiled = nb - cfg.ragged_packed_slots(nb)
        tile = cfg.ragged_q_tile
        # A model whose forms of attention differ in their KV heads has a
        # block a form: each form's rule, by its share of the layers.
        model = cfg.model
        blocks: dict[int, int] = {}
        for form in model.attn_forms:
            block = ragged_query_block(model, tile, model.kv_heads_of(form))
            blocks[block] = blocks.get(block, 0) + 1
        slots = get_telemetry().attn_query_slots
        for block, layers in blocks.items():
            merged, by_tile = merged_slot_counts(
                row_of[:tiled], positions[:tiled], tile, block
            )
            slots.labels("merged").inc(merged * layers / model.num_layers)
            slots.labels("tile").inc(by_tile * layers / model.num_layers)
        self._count_one_query_rows(positions[tiled:nb])

    def _count_one_query_rows(self, positions) -> None:
        """Of the attention kernel's one-query call over ``positions`` (a
        mixed stream's packed tail, or a window's ``[steps, rows]``; -1: no
        query), the live rows whose first chunk a grid cell fetches behind
        the row before and those whose fetch is exposed
        (``dynamo_attn_one_query_rows_total``): the kernel's own rule
        (``ops/ragged_attention.py::one_query_chain``) at the rows a cell
        the call's shapes give; and the same rows by the schedule a chunk
        of their call is worked in (``dynamo_attn_one_query_heads_total``:
        ``ops/ragged_attention.py::one_query_schedule``, each form of
        attention by its share of the layers). The reference counts
        nothing."""
        if self._attn_impl != "pallas":
            return
        from ..models.llama import one_query_rows, one_query_schedules
        from ..ops.ragged_attention import one_query_fetches

        model, n, tp = self.cfg.model, positions.shape[-1], self.cfg.tp
        behind, exposed = one_query_fetches(positions, one_query_rows(model, n, tp))
        tel = get_telemetry()
        tel.attn_one_query_rows.labels("behind").inc(behind)
        tel.attn_one_query_rows.labels("exposed").inc(exposed)
        for schedule, share in one_query_schedules(model, n, tp).items():
            tel.attn_one_query_heads.labels(schedule).inc((behind + exposed) * share)

    def _count_index_passes(self, nb: int, row_of, positions) -> None:
        """Of a mixed stream's tiled part of a model with an indexer, the
        live slots whose block the scores kernel reads in one pass over
        their row's index keys and those it walks tile by tile
        (``dynamo_dsa_index_slots_total``): the kernel's own rule
        (``ops/ragged_attention.py::block_plan`` at ``ops/sparse_index.py::
        index_block``) on the host's copy of what it takes, a part of the
        stream at a time as ``forward_ragged`` calls it (the whole tiled
        part where a row may take the expanded form, else a slab a call),
        and only the parts that score (``_selection_tiled``: a query there
        sees more than ``index_topk``)."""
        m = self.cfg.model
        if not m.has_indexer or self._attn_impl != "pallas":
            return
        from ..models.llama import LATENT_SLAB
        from ..ops.ragged_attention import merged_slot_counts
        from ..ops.sparse_index import index_block

        tile = self.cfg.ragged_q_tile
        tiled = nb - self.cfg.ragged_packed_slots(nb)
        block = index_block(
            tile, m.index_n_heads, m.index_lanes, np.dtype(self.cfg.kv_dtype_jnp).itemsize
        )
        least = self._latent_form_queries()
        whole = least is not None and tiled >= least
        step = tiled if whole else LATENT_SLAB // tile * tile
        slots = get_telemetry().dsa_index_slots
        for start in range(0, tiled, max(step, 1)):
            rows, at = row_of[start : start + step], positions[start : start + step]
            if (at >= m.index_topk).any():
                in_block, by_tile = merged_slot_counts(rows, at, tile, block)
                slots.labels("block").inc(in_block)
                slots.labels("tile").inc(by_tile)

    def _count_slots(self, program: str, live: int, pad: int) -> None:
        """``_count_dispatch``'s slots. A block program's are counted at
        its consume: how many passes it made is the device's to say."""
        tel = get_telemetry()
        tel.ragged_slots.labels(program, "live").inc(live)
        tel.ragged_slots.labels(program, "pad").inc(pad)
        if self.cfg.model.num_mamba_layers:
            tel.ssm_scan_tokens.labels(program).inc(live)
        if self.cfg.model.num_linear_layers:
            tel.linear_attn_tokens.labels(program).inc(live)
        if self.cfg.model.num_mamba2_layers:
            tel.mamba2_tokens.labels(program).inc(live)
        m = self.cfg.model
        if m.loop_passes > 1:  # the layer applications they take
            tel.layer_passes.inc(live * m.loop_passes * m.num_layers)
        if self.cfg.model.is_moe:
            tel.moe_rows.labels("live").inc(live)
            tel.moe_rows.labels("pad").inc(pad)

    # -------------------------------------------------------------- lifecycle
    def start(self) -> None:
        if self._running:
            return
        if self._thread is not None:
            if self._thread.is_alive():
                # A wedged previous loop survived a timed-out stop(): a
                # second loop thread would race it over scheduler/page/
                # inflight state the moment the old one unwedges.
                log.error(
                    "previous engine loop thread is still alive; refusing "
                    "to start a second loop"
                )
                return
            # The wedged loop later unwedged and exited, but the timed-out
            # stop() skipped its teardown: drop the stale in-flight window
            # and buffered evictions — the pages they reference belong to
            # the previous run.
            self._thread = None
            self._inflight = None  # dynlint: thread-ownership(loop thread joined before teardown flush)
            self._pending_offloads.clear()  # dynlint: thread-ownership(loop thread joined before teardown flush)
        if self.host_pool is not None and self.copy_stream is None:
            # stop() tears the copy stream down; a restarted engine needs
            # a live one before the first eviction fires on_evict. The G3
            # store rides along so prefetch fetches fall through G2→G3.
            self.copy_stream = CopyStream(self.host_pool, store=self.g3_store)
        self._running = True
        self._thread = threading.Thread(
            target=self._loop, name="tpu-engine-loop", daemon=True
        )
        self._thread.start()
        self._flight_handle = register_dumper(self._dump_flight)
        self._witness_handle = host_witness().attach(
            self.flight, lambda: self._last_launch
        )
        if self.cfg.watchdog_stall_s > 0 and self._watchdog is None:
            self._watchdog = Watchdog(
                self.cfg.watchdog_stall_s,
                progress=lambda: self._progress_mark,
                has_work=lambda: (
                    self.sched.has_work() or not self._submit_q.empty()
                ),
                dump_fn=self._dump_flight,
            )
            self._watchdog.start()

    def stop(self) -> None:
        self._running = False
        self._wake.set()
        if self._watchdog is not None:
            self._watchdog.stop()
            self._watchdog = None
        if self._flight_handle is not None:
            unregister_dumper(self._flight_handle)
            self._flight_handle = None
        if self._witness_handle is not None:
            host_witness().detach(self._witness_handle)
            self._witness_handle = None
        if self._thread:
            # The teardown below mutates loop-owned state, so it may only
            # run once the loop thread has actually exited. A wedged loop
            # (e.g. stuck in a pathological compile) keeps its state: a
            # concurrent flush would race whatever it is still doing.
            self._thread.join(timeout=30)
            if self._thread.is_alive():
                log.error(
                    "engine loop did not exit within 30s; skipping "
                    "teardown flush to avoid racing the live loop thread"
                )
                return
            self._thread = None
        self._inflight = None  # dynlint: thread-ownership(loop thread joined before teardown flush)
        if self.kv.sliding is not None:
            log.info(
                "sliding page pool: at most %d of %d pages held at once, "
                "%d given back behind the width",
                self.kv.sliding.peak_held, self.kv.sliding.num_pages,
                self.kv.sliding.released_total,
            )
        # Final conservation audit (the loop thread is joined, so the
        # page ledger is quiescent): any violation the in-loop check
        # missed — or one that appeared in the teardown path itself —
        # still lands in the registry the test suites police.
        if self.cfg.kv_ledger_check:
            # Only violation KINDS the in-loop check has NOT already
            # counted (a persistent episode must not double-report at
            # teardown; the strings embed counter values, so kind-level
            # comparison is the stable one).
            seen = set(self._ledger_last)
            final_all = self.kv.ledger_check()
            if self.g3_store is not None:
                final_all = final_all + self.g3_store.ledger_check()
            final = [
                v for v in final_all if v.split(":", 1)[0] not in seen
            ]
            if final:
                self.kv_ledger_violations += len(final)  # dynlint: thread-ownership(loop thread joined before teardown flush)
                LEDGER_VIOLATIONS.extend(final)
                get_telemetry().kv_ledger_violations.inc(len(final))
                for v in final:
                    log.error("KV ledger violation at stop: %s", v)
        # Prefix-pin requests queued after the loop's last service pass
        # must not hang their callers (disagg routing awaits them).
        self._drain_pin_q()
        if self.copy_stream is not None:
            # Flush evictions the dead loop buffered, then drain
            # (bounded) so a graceful drain doesn't silently discard
            # queued host-tier offloads — every committed page is a
            # recompute the next instance of this prefix never pays.
            # The drain also completes in-flight prefetch fetches;
            # their reservation leases are returned below (no inject —
            # there is no loop left to consume the pages).
            self._flush_offloads()
            self.copy_stream.drain()
            self.copy_stream.stop()
            self.copy_stream = None
        if self.g3_store is not None:
            # Graceful-shutdown G2→G3 drain (after the copy stream has
            # committed every pending offload into the host pool, and
            # strictly after the wedged-loop early return above): demote
            # the whole warm G2 set so the sealed manifest covers it —
            # the next boot's cache is as warm as this process was.
            for h, k_page, v_page in self.host_pool.snapshot():
                self.g3_store.store(h, k_page, v_page)
            self.g3_store.seal()
        while not self._prefetch_done_q.empty():
            try:
                job, _fetched = self._prefetch_done_q.get_nowait()
            except queue.Empty:
                break
            self._prefetch_inflight.pop(job["req"], None)  # dynlint: thread-ownership(loop thread joined before teardown flush)
            if self.kv.lease_active(job["lease"]):
                self.kv.confirm_lease(job["lease"])

    def prewarm(self, manifest=None):
        """Warm-boot provisioning (docs/aot.md): compile/load every
        compile-lattice variant BEFORE the engine accepts traffic, so
        the first dispatch of every shape is steady-state fast and the
        compile-miss counters stay flat from the very first request.

        Runs strictly pre-loop (the same single-threaded window
        ``__init__`` owns — a running engine is refused, like a second
        ``start()``): prewarm executes each variant once as an
        all-padding batch, threading the donated KV pools through, then
        seeds the dispatch profiler's variant-freshness state so a
        prewarmed kernel's first traffic dispatch is never mis-charged
        as a cold compile. With a populated persistent compilation
        cache (the entry point turns it on before building the engine:
        ``aot.enable_persistent_cache``), the compiles are
        deserializations and a boot collapses to program-load time.

        ``manifest`` defaults to this engine's own full lattice.
        Returns the :class:`~dynamo_exp_tpu.aot.warmup.PrewarmReport`.
        """
        from ..aot.warmup import prewarm_engine

        if self._running:
            raise RuntimeError(
                "prewarm() must run before the engine accepts traffic"
            )
        report = prewarm_engine(self, manifest)
        self.prewarmed_variants = report.variants
        self.prewarm_seconds = report.seconds
        tel = get_telemetry()
        tel.prewarm_seconds.set(report.seconds)
        tel.prewarm_variants.labels("ragged").inc(report.ragged_variants)
        tel.prewarm_variants.labels("move").inc(report.move_variants)
        self.flight.record(
            "prewarm",
            ragged=report.ragged_variants,
            moves=report.move_variants,
        )
        return report

    # ------------------------------------------------------------ AsyncEngine
    async def generate(
        self,
        request: dict | BackendInput,
        context: AsyncEngineContext | None = None,
        remote_kv: RemoteKv | None = None,
    ) -> ResponseStream[dict]:
        if not self._running:
            self.start()
            if not self._running:
                # start() refused (wedged previous loop): submitting
                # would enqueue work nothing will ever consume.
                raise RuntimeError(
                    "engine is not running (previous loop thread is "
                    "still alive after a timed-out stop)"
                )
        ctx = context or AsyncEngineContext()
        binput = (
            request
            if isinstance(request, BackendInput)
            else BackendInput.model_validate(request)
        )
        loop = asyncio.get_running_loop()
        out_q: asyncio.Queue = asyncio.Queue()

        put = out_q.put_nowait

        def emit(
            tokens: list[int],
            reason: FinishReason | None,
            logprobs=None,  # (lps: list[float], tops: list[dict]) | None
        ) -> None:
            # Stamped here and not at the posting: the hand-off's
            # histogram then holds the wait in the outbox too.
            self._post(loop, put, (tokens, reason, logprobs, time.monotonic()))

        seq = Sequence(
            request_id=ctx.id,
            prompt=list(binput.token_ids),
            stop=binput,
            emit=emit,
            is_cancelled=lambda: ctx.is_stopped,
            remote_kv=remote_kv,
            trace=current_trace(),
            submitted_at=time.time(),
            sample_seed=self._effective_seed(binput),
            priority=binput.priority,
            deadline_unix=ctx.deadline or 0.0,
        )
        self._submit_q.put(seq)
        self._wake.set()
        prompt_tokens = len(binput.token_ids)
        # The engine thread's posting to this thread's taking: the
        # program's own reading of what a client sees as a late chunk.
        # Like the loop's phases it follows ``profile_dispatches``.
        handoff = (
            get_telemetry().emit_handoff if self.profiler is not None else None
        )

        # This thread's handling of each tuple, from here to the
        # response's write (telemetry/dispatch.py HttpChunkClock).
        chunks = http_chunk_clock()

        async def _gen() -> AsyncIterator[dict]:
            completion = 0
            while True:
                tokens, reason, logprobs, posted = await out_q.get()
                chunks.taken()
                try:
                    if handoff is not None:
                        handoff.observe(time.monotonic() - posted)
                    if tokens:
                        completion += len(tokens)
                        yield LLMEngineOutput(
                            token_ids=tokens,
                            logprobs=logprobs[0] if logprobs else None,
                            top_logprobs=logprobs[1] if logprobs else None,
                            # A block model's pack has the passes third.
                            fixed_at_pass=(
                                logprobs[2]
                                if logprobs and len(logprobs) > 2 else None
                            ),
                        ).to_dict()
                    if reason is not None:
                        yield LLMEngineOutput(
                            finish_reason=reason,
                            prompt_tokens=prompt_tokens,
                            completion_tokens=completion,
                        ).to_dict()
                        return
                finally:
                    # Whoever iterates is back for the next tuple.
                    chunks.idle()

        return ResponseStream(_gen(), ctx)

    def _effective_seed(self, binput: BackendInput) -> int:
        """The request's pinned sampling seed, or one drawn now. With a
        pinned seed (journaling frontends always pin one for sampled
        requests), the whole token stream is a pure function of
        (weights, prompt, sampling params) — replayable anywhere."""
        s = binput.sampling_options.seed
        return int(s) if s is not None else self._seed_rng.getrandbits(31)

    async def prefill_extract(
        self,
        request: dict | BackendInput,
        context: AsyncEngineContext | None = None,
        skip_pages: int = 0,
    ) -> tuple[int, list, str]:
        """Run prefill only; hand back (first_token, kv_pages, lease_id).

        This is the prefill-worker side of disaggregation: the prompt's
        KV pages (host-bounced numpy, one (k, v) pair per page) travel to
        the decode worker, which injects them via ``generate(...,
        remote_kv=...)``. ``skip_pages`` is the decode side's pinned
        resident prefix (suffix-only transfer, docs/prefix_sharing.md):
        those pages are neither gathered nor shipped — the full prompt
        is still prefilled locally (so this worker's pool prefix-hits
        repeats), but the wire and the extract gather carry only the
        unshared suffix. Until the caller confirms delivery
        (:meth:`confirm_kv_lease`) — or the lease TTL passes and the
        reaper reclaims them — the shipped device pages stay pinned, so
        a decode worker that dies between extract and inject can never
        strand HBM.
        """
        if not self._running:
            self.start()
            if not self._running:
                raise RuntimeError(
                    "engine is not running (previous loop thread is "
                    "still alive after a timed-out stop)"
                )
        ctx = context or AsyncEngineContext()
        binput = (
            request.model_copy(deep=True)  # never mutate the caller's object
            if isinstance(request, BackendInput)
            else BackendInput.model_validate(request)
        )
        binput.stop_conditions.max_tokens = 1  # prefill produces one token
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()

        def extract_cb(token: int, pages: list, lease_id: str) -> None:
            loop.call_soon_threadsafe(
                lambda: fut.done() or fut.set_result((token, pages, lease_id))
            )

        def emit(
            tokens: list[int], reason: FinishReason | None, logprobs=None
        ) -> None:
            if reason in (FinishReason.ERROR, FinishReason.CANCELLED):
                loop.call_soon_threadsafe(
                    lambda: fut.done()
                    or fut.set_exception(RuntimeError(f"prefill failed: {reason}"))
                )

        seq = Sequence(
            request_id=ctx.id,
            prompt=list(binput.token_ids),
            stop=binput,
            emit=emit,
            is_cancelled=lambda: ctx.is_stopped,
            extract_cb=extract_cb,
            extract_skip=max(int(skip_pages), 0),
            trace=current_trace(),
            submitted_at=time.time(),
            sample_seed=self._effective_seed(binput),
            priority=binput.priority,
            deadline_unix=ctx.deadline or 0.0,
        )
        self._submit_q.put(seq)
        self._wake.set()
        return await fut

    def confirm_kv_lease(self, lease_id: str) -> None:
        """Delivery ack for an extract lease (thread-safe: queues the
        confirm for the engine loop, the page manager's single writer)."""
        self._lease_confirm_q.put(lease_id)
        self._wake.set()

    async def pin_prefix(self, token_ids: list[int]) -> tuple[int, str | None]:
        """How many full prompt pages this engine already holds — pinned.

        The disagg decode router calls this before offloading a prefill:
        the answer becomes the request's ``skip_blocks`` (the prefill
        worker ships only the unshared suffix), and the returned lease
        keeps the matched pages resident until admission re-references
        them (the engine confirms the lease at inject; the reaper is the
        TTL backstop). Thread-safe: the match + pin run on the engine
        loop, the page manager's single writer. Returns ``(0, None)``
        when the engine is not running, sharing is disabled, or it
        holds nothing."""
        if not self._running or not self.kv.sharing:
            # A prefix_sharing=False engine never re-attaches at
            # admission, so a skip would discard the whole transfer.
            return (0, None)
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        self._pin_q.put((list(token_ids), loop, fut, current_trace()))
        self._wake.set()
        if not self._running and not fut.done():
            # stop() drained the queue before our put landed: nothing
            # will ever service this entry — resolve it ourselves (the
            # done() guards make a racing resolver a no-op).
            fut.set_result((0, None))
        return await fut

    def _service_pins(self) -> None:
        """Engine-loop side of :meth:`pin_prefix`: match the resident
        *filled* prefix (bytes that exist on device now) and pin it."""
        while True:
            try:
                tokens, loop, fut, trace = self._pin_q.get_nowait()
            except queue.Empty:
                return
            pages, _ = self.kv.match_prefix(tokens, require_filled=True)
            lease = (
                self.kv.grant_lease(pages, self.cfg.kv_lease_ttl_s)
                if pages
                else None
            )
            if lease is not None and trace is not None:
                self._lease_traces[lease] = (trace, time.time())
            result = (len(pages), lease)

            def resolve(f=fut, r=result, lease=lease):
                # Runs on the caller's event loop. A future already done
                # (cancelled request) can never hand the lease back —
                # release the pin instead of waiting out its TTL.
                if f.done():
                    if lease is not None:
                        self.confirm_kv_lease(lease)
                else:
                    f.set_result(r)

            try:
                loop.call_soon_threadsafe(resolve)
            except RuntimeError:  # caller's loop closed: release the pin
                if lease is not None:
                    self.kv.confirm_lease(lease)
                    self._close_lease_span(lease, "confirmed")

    # ------------------------------------------------- spot-reclamation plane
    def kv_page_nbytes(self) -> int:
        """Host bytes one KV page occupies on the migration wire (both
        K and V, all layers) — the triage planner's cost unit."""
        m = self.cfg.model
        itemsize = 2 if self.cfg.kv_dtype == "bfloat16" else 4
        return (
            sum(m.kv_pool_widths)
            * m.kv_layer_slots
            * self.cfg.page_size
            * itemsize
        )

    async def reclaim_inflight(self) -> list[dict]:
        """Triage snapshot for the reclaim plane (docs/fault_tolerance.md
        "Spot reclamation & live migration"): every migratable in-flight
        sequence with its priority and shippable KV size. Thread-safe
        (serviced on the engine loop, the scheduler's single writer).
        Swapped-out rows and disagg extract legs are excluded — their
        KV is not cleanly device-resident, so they ride the journal."""
        return await self._reclaim_call("snapshot", None, default=[])

    async def reclaim_extract(
        self, request_id: str, ttl_s: float
    ) -> tuple[list[int], list, str] | None:
        """Live-migration extract: host-bounce the sequence's *complete*
        KV pages (one batched gather), pin them under a ``ttl_s`` lease
        (clamp it past the reclaim grace — see
        :func:`~dynamo_exp_tpu.runtime.reclaim.migration_lease_ttl_s`),
        and return ``(block_hashes, kv_pages, lease_id)``. The partial
        tail page is never shipped — the journal continuation re-prefills
        it on the survivor, which keeps migration a pure prefix-cache
        transplant. Returns None when the sequence finished or is not in
        a migratable state (the caller degrades to journal failover)."""
        return await self._reclaim_call(
            "extract", (request_id, ttl_s), default=None
        )

    async def seed_prefix(self, hashes: list[int], pages: list) -> int:
        """Survivor side of live KV migration: inject the shipped blocks
        (one batched scatter) and register them as parked, matchable
        prefix pages — refcount 0, reclaimable-LRU, identical to a
        finished sequence's pages. The migrated request's journal
        continuation then admission-matches them instead of
        re-prefilling. Returns blocks actually seeded (pool pressure may
        park a shorter — still contiguous — prefix)."""
        return await self._reclaim_call("seed", (hashes, pages), default=0)

    async def _reclaim_call(self, op: str, payload, default):
        if not self._running:
            return default
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        self._reclaim_q.put((op, payload, loop, fut))
        self._wake.set()
        if not self._running and not fut.done():
            # stop() drained the queue before our put landed (same race
            # as pin_prefix): resolve it ourselves.
            fut.set_result(default)
        return await fut

    def _service_reclaims(self) -> None:
        """Engine-loop side of the reclaim plane entry points."""
        while True:
            try:
                op, payload, loop, fut = self._reclaim_q.get_nowait()
            except queue.Empty:
                return
            try:
                if op == "snapshot":
                    result = self._reclaim_snapshot()
                elif op == "extract":
                    result = self._reclaim_extract(*payload)
                else:
                    result = self._seed_prefix(*payload)
            except Exception as err:
                log.exception("reclaim op %s failed", op)

                def fail(f=fut, e=err):
                    f.done() or f.set_exception(e)

                try:
                    loop.call_soon_threadsafe(fail)
                except RuntimeError:
                    pass
                continue

            def resolve(f=fut, r=result, op=op):
                # Runs on the caller's event loop. An extract whose
                # caller gave up (deadline hit between queue and
                # service) must hand its lease back, not wait out TTL.
                if f.done():
                    if op == "extract" and r is not None:
                        self.confirm_kv_lease(r[2])
                else:
                    f.set_result(r)

            try:
                loop.call_soon_threadsafe(resolve)
            except RuntimeError:
                if op == "extract" and result is not None:
                    self.kv.confirm_lease(result[2])
                    self._close_lease_span(result[2], "confirmed")

    def _reclaim_snapshot(self) -> list[dict]:
        ps = self.cfg.page_size
        nb = self.kv_page_nbytes()
        out = []
        for seq in self.sched.slots:
            if (
                seq is None
                or seq.state is not SeqState.ACTIVE
                or seq.swap is not None
                or seq.extract_cb is not None
            ):
                continue
            # The same bound as Scheduler.register_full_pages.
            full = min(seq.written // ps, len(seq.page_ids))
            out.append(
                {
                    "request_id": seq.request_id,
                    "priority": seq.priority,
                    "full_pages": full,
                    "kv_bytes": full * nb,
                    "tokens_generated": max(
                        0, len(seq.tokens) - len(seq.prompt)
                    ),
                }
            )
        return out

    def _reclaim_extract(
        self, request_id: str, ttl_s: float
    ) -> tuple[list[int], list, str] | None:
        seq = next(
            (
                s
                for s in self.sched.slots
                if s is not None
                and s.request_id == request_id
                and s.state is SeqState.ACTIVE
                and s.swap is None
            ),
            None,
        )
        if seq is None:
            return None
        ps = self.cfg.page_size
        full = min(seq.written // ps, len(seq.page_ids))
        if full <= 0:
            return None
        pids = seq.page_ids[:full]
        # The chained block hashes ARE the migration identity: the
        # survivor registers the pages under them, and the journal
        # continuation (same prompt + confirmed tokens) recomputes the
        # same chain at admission — content-addressed re-attachment, no
        # request-id coupling.
        hashes = compute_block_hashes_for_seq(seq.tokens, ps)[:full]
        k_b, v_b = self._gather_page_batch(pids)
        k_np, v_np = np.asarray(k_b), np.asarray(v_b)  # dynlint: sync-point(reclaim extract gather consume)
        if self.profiler is not None:
            self.profiler.consume("kv_move", self._last_move_t)
        self.flight.record("consume", dispatch="kv_move", pages=full)
        get_telemetry().kv_page_moves.labels("extract").inc(full)
        lease_id = self.kv.grant_lease(pids, ttl_s)
        if seq.trace is not None:
            self._lease_traces[lease_id] = (seq.trace, time.time())
        self.flight.record("lease_grant", req=seq.request_id, pages=full)
        pages = [
            (
                np.ascontiguousarray(k_np[:, i]),
                np.ascontiguousarray(v_np[:, i]),
            )
            for i in range(full)
        ]
        return hashes, pages, lease_id

    def _seed_prefix(self, hashes: list[int], pages: list) -> int:
        if not self.kv.sharing:
            return 0
        seeded_pids: list[int] = []
        seed_k: list = []
        seed_v: list = []
        parent: int | None = None
        for i, h in enumerate(hashes[: len(pages)]):
            if self.kv.resident_page(h) is not None:
                parent = h  # block already here: extend the chain past it
                continue
            pid = self.kv.allocate_page()
            if pid is None:
                break  # pool dry: a shorter contiguous prefix still matches
            k, v = pages[i]
            self.kv.register_full_page(pid, h, parent_hash=parent)
            seeded_pids.append(pid)
            seed_k.append(k)
            seed_v.append(v)
            parent = h
        if seeded_pids:
            self._inject_page_batch(seeded_pids, seed_k, seed_v, op="inject")
            self.kv.mark_filled(seeded_pids)
            # Park (refcount 0, reclaimable LRU, matchable) — exactly a
            # finished sequence's pages. The continuation re-references
            # them at admission; until then LRU pressure may evict them,
            # which costs re-prefill, never correctness.
            self.kv.release_sequence(seeded_pids)
        return len(seeded_pids)

    def _drain_reclaim_q(self) -> None:
        """Resolve every queued reclaim-plane request with its no-op
        answer — shutdown must never strand an awaiting controller."""
        defaults = {"snapshot": [], "extract": None, "seed": 0}
        while not self._reclaim_q.empty():
            try:
                op, _payload, loop, fut = self._reclaim_q.get_nowait()
            except queue.Empty:
                break
            try:
                loop.call_soon_threadsafe(
                    lambda f=fut, r=defaults.get(op): f.done()
                    or f.set_result(r)
                )
            except RuntimeError:
                pass

    # ------------------------------------------------------------ the outbox
    def _post(self, loop, put, item: tuple) -> None:
        """One emitted tuple on its way to ``put`` (a request's
        ``out_q.put_nowait``) on ``loop``'s thread. On the engine loop
        thread it joins the outbox and travels with the next posting;
        from any other thread (a teardown after the loop has gone) it
        is posted by itself, at once."""
        if threading.get_ident() == self._outbox_owner:
            self._outbox[loop].append((put, item))
            return
        tel = get_telemetry()
        tel.emit_postings.inc()
        tel.emit_items.inc()
        loop.call_soon_threadsafe(put, item)

    def _flush_outbox(self) -> None:
        """Post what the loop thread has emitted: one
        ``call_soon_threadsafe`` an asyncio loop, whose callback does
        every ``put_nowait`` of the batch on that loop's thread. Called
        where the loop thread is about to stop needing the GIL — before
        the wait of each ``sync``, before each ``park``, when the loop
        ends — so the threads it wakes write their chunks while this one
        sleeps and the device runs, and the outbox is empty whenever
        this thread blocks or exits. Booked to ``consume.emit``: the
        cost of emission wherever it is paid. Call outside any phase."""
        if not self._outbox:
            return
        with self._phase("consume"), self._part("consume", "emit"):
            tel = get_telemetry()
            for loop, batch in self._outbox.items():
                tel.emit_postings.inc()
                tel.emit_items.inc(len(batch))
                try:
                    loop.call_soon_threadsafe(_deliver, batch)
                except RuntimeError:
                    pass  # that asyncio loop is closed: nobody reads
            self._outbox.clear()

    # -------------------------------------------------------------- the loop
    def _loop(self) -> None:
        """One iteration = admit everything admissible, then dispatch
        ONE ragged batch (per sampler partition) carrying every slot's
        next unit of work — prefill chunks, decode steps/windows, and
        spec verify spans in one flat query stream — so a late-arriving
        prompt joins the in-flight batch the iteration it is admitted
        and decode still interleaves between the chunks of long prompts
        (docs/engine_perf.md "One ragged dispatch").

        The host pipelines against the device instead of blocking on
        ``np.asarray`` right after each dispatch: a decode window is
        left *in flight* and consumed one iteration later, and in steady
        state (no arrivals, no prefill, single partition) window N+1 is
        dispatched straight from window N's on-device carry BEFORE the
        host syncs on window N — so emits, stop checks, page
        registration, and admissions for window N overlap window N+1's
        device time. All scheduler mutation that could free pages still
        happens only when no unconsumed window could write to them."""
        # Engine-loop phases (telemetry/dispatch.py PHASES): every
        # stretch of the body below runs under one, the dispatch and
        # consume helpers open their own (assemble / launch / sync /
        # consume), and none nests. What is left outside is the loop's
        # own overhead: a few branches an iteration.
        phase, part = self._phase, self._part
        admit_part = self._admit_part
        admit_seconds = get_telemetry().admit_seconds
        self._outbox_owner = threading.get_ident()
        try:
            while self._running:
                with phase("kv_admin"):
                    # Lease bookkeeping first: confirmations queued by
                    # the prefill worker's delivery ack, then the expiry
                    # reaper (orphaned handoffs whose decode instance
                    # died). Both mutate the page manager, so they run
                    # here — its single writer — every iteration, busy
                    # or idle.
                    self._service_leases()
                    self._service_pins()
                    self._service_reclaims()
                    # Conservation auditor: O(1) counter arithmetic over
                    # the page ledger, every iteration, busy or idle — a
                    # leaked ref or double-release is caught within one
                    # loop pass of the mutation that caused it.
                    if self.cfg.kv_ledger_check:
                        self._check_ledger()
                if self._inflight is not None:
                    # Steady state: launch the next window device-to-
                    # device, then consume the previous one while the
                    # new one executes.
                    with phase("assemble"), part("assemble", "plan"):
                        can_chain = self._can_chain()
                    chain = (
                        self._dispatch_chained_block
                        if self.cfg.model.block_length
                        else self._dispatch_chained
                    )
                    nxt = chain(self._inflight) if can_chain else None
                    prev, self._inflight = self._inflight, nxt
                    self._consume_ragged(prev)
                    with phase("publish"):
                        self._maybe_publish_gauges()
                    self._progress_mark += 1  # consumed a window
                    if self._inflight is not None:
                        continue
                    # Chain broken (arrivals / prefill / stop / dry
                    # pool): fall through to the full scheduling path.
                    self.flight.record("chain_break")
                if not self.sched.has_work() and self._submit_q.empty():
                    # Flush buffered evictions before idling (the host
                    # tier must see them even with no next dispatch) and
                    # publish on the idle path too: the gauges must decay
                    # to zero after the last request finishes, not freeze
                    # on the final busy-loop snapshot. Completed
                    # prefetches whose target vanished still need their
                    # leases returned.
                    with phase("kv_admin"):
                        self._apply_prefetches()
                        self._flush_offloads()
                    with phase("publish"):
                        self._maybe_publish_gauges()
                    if self.profiler is not None:
                        # Genuinely idle: wait time must never read as
                        # host gap on the next dispatch.
                        self.profiler.mark_idle()
                    self._flush_outbox()
                    with phase("park"):
                        self._wake.wait(timeout=0.05)
                        self._wake.clear()
                    continue
                with phase("intake"):
                    self._drain_submissions()
                    self._poll_cancellations()
                    # Reap dead work anywhere in the waiting deque
                    # before it can waste a prefill or hold an admission
                    # slot. The full O(queue-depth) scan is throttled:
                    # the loop can spin at kHz when the pool is dry, and
                    # admit_next's head check still prevents a wasted
                    # prefill between scans.
                    now_m = time.monotonic()
                    if now_m - self._last_reap >= 0.02:
                        self._last_reap = now_m
                        self.sched.reap_waiting()
                # KV pressure: no window is in flight here (the chain
                # broke above or never existed), so releasing a victim's
                # pages cannot race a device write. Proactive offload
                # first (docs/engine_perf.md "Predictive KV tiering"):
                # swap a cold row's bytes to the host tier so the stall
                # clears before the preemption grace ever expires —
                # preemption stays the fallback.
                with phase("kv_admin"):
                    self._maybe_proactive_offload()
                    self._maybe_preempt()
                    # Completed G2→G1 prefetches inject before this
                    # iteration's compute dispatch (stream order then
                    # makes the restored pages readable by anything
                    # admitted below); swapped rows rejoin before
                    # admission so newcomers can't starve them.
                    self._apply_prefetches()
                    self._try_swap_in()
                    may_admit = (
                        not self._kv_pressure() and not self._swapped_rows()
                    )
                if may_admit:
                    # docs/observability.md "The life of an admission":
                    # the scheduler and the page manager open the parts
                    # up to ``bind`` themselves, a try that admits
                    # nothing too.
                    with phase("admit"):
                        while True:
                            t_admit = time.monotonic()
                            admitted = self.sched.admit_next(admit_part)
                            if admitted is None:
                                break
                            with admit_part("bind"):
                                self._on_admitted(admitted)
                            with admit_part("record"):
                                self._record_admission(admitted)
                            admit_seconds.observe(time.monotonic() - t_admit)
                with phase("kv_admin"):
                    # Plan new prefetches over whatever is STILL waiting
                    # (couldn't admit: slots full or pool pressure) —
                    # the exact window where restoring ahead of
                    # admission overlaps the current batch's compute.
                    self._plan_prefetch()
                with phase("publish"):
                    self._maybe_publish_gauges()
                progressed = False
                with phase("assemble"):
                    prefilling = [
                        s
                        for s in self.sched.slots
                        if s is not None and s.state is SeqState.PREFILL
                    ]
                    # Partition the snapshot BEFORE injecting: injection
                    # clears remote_kv and promotes the sequence to
                    # ACTIVE, so filtering afterwards would re-prefill
                    # it. Sequences attached to shared pages another
                    # sequence is still filling sit out until those
                    # fills are dispatched (fill_ready also claims
                    # orphans left by dead fillers) — device stream
                    # order then makes their reads safe.
                    ready = [
                        s for s in prefilling if self.sched.fill_ready(s)
                    ]
                    batch = [s for s in ready if s.remote_kv is None]
                    block = self.cfg.model.block_length
                    if block:
                        # A prompt whose whole blocks all came from the
                        # cache (or that has none) has nothing to prefill:
                        # it decodes from this iteration on.
                        for s in batch:
                            if s.prefill_sent >= s.prefill_target(block):
                                self._apply_uploads(s)
                                self._promote_to_decode(s)
                        batch = [s for s in batch if s.state is SeqState.PREFILL]
                remote = [s for s in ready if s.remote_kv is not None]
                if remote:
                    with phase("kv_admin"):
                        for seq in remote:
                            self._run_remote_inject(seq)
                    progressed = True
                # ONE ragged dispatch per iteration (per sampler
                # partition): prefill chunks, decode steps/windows, and
                # spec verify spans share the flat query stream, so a
                # freshly admitted prompt joins the in-flight batch
                # immediately instead of waiting behind a separate
                # prefill program (docs/engine_perf.md).
                pendings = self._dispatch_ragged(
                    batch[: self.cfg.prefill_batch]
                )
                progressed = progressed or bool(pendings)
                if (
                    len(pendings) == 1
                    and pendings[0].solo
                    and self.cfg.chained_decode
                ):
                    self._inflight = pendings[0]  # consumed next iteration
                else:
                    # Mixed batches consume in the same iteration: the
                    # next round's chunks and drafts are planned from
                    # the tokens they just confirmed.
                    for p in pendings:
                        self._consume_ragged(p)
                if progressed:
                    self._progress_mark += 1
                else:
                    # Pool dry / everything stalled: yield briefly. No
                    # progress bump — this is exactly the state the
                    # watchdog must see as frozen.
                    if self.profiler is not None:
                        self.profiler.mark_idle()
                    self._flush_outbox()
                    with phase("park"):
                        self._wake.wait(timeout=0.001)
                        self._wake.clear()
        except Exception:  # engine death must not hang clients
            log.exception("engine loop crashed; failing in-flight requests")
            self.loop_crashed = True
            self._dump_flight("crash")
            self._running = False
            self._inflight = None
            self._fail_all()
            raise
        finally:
            # Every way out: the normal end, and a crash's errors.
            self._flush_outbox()
            self._outbox_owner = None

    def _on_admitted(self, seq: Sequence) -> None:
        """Close the request's queue-wait stage (submission -> slot +
        pages bound). Runs on the engine loop thread with the trace
        captured at submission."""
        now = time.time()
        seq.admitted_at = now
        # Anatomy (telemetry/anatomy.py): a first admission closes the
        # queue-wait segment; a re-admission after preemption closes
        # the preemption-limbo segment instead. The profiler's
        # compile-seconds total is marked here so _finish_first_token
        # can attribute the delta as this request's compile stall. The
        # workload fingerprint counts every first admission (a
        # preemption continuation is the same request, not new load).
        if seq.anat_preempted_at:
            seq.anat_preempt_s += max(now - seq.anat_preempted_at, 0.0)
            seq.anat_preempted_at = 0.0
        else:
            if seq.submitted_at:
                seq.anat_queue_s += max(now - seq.submitted_at, 0.0)
            self.fingerprint.observe_admit(
                len(seq.prompt),
                seq.cached_len,
                seq.priority,
                seq.submitted_at or now,
            )
        if self.profiler is not None:
            seq.anat_compile_mark = self.profiler.compile_total_s()
        self._note_prefetch_admission(seq)
        if seq.state_restore is not None:
            # The hit's pages hold K and V of tokens this sequence will not
            # compute; the state that belongs to the last of them is the
            # snapshot's. Stream order puts the copy after the dispatch
            # that wrote the snapshot and before this sequence's first.
            self.seq_state = self._copy_state(
                self.seq_state,
                jnp.asarray(seq.state_restore, jnp.int32),
                jnp.asarray(seq.state_slot, jnp.int32),
            )
            get_telemetry().seq_state_restores.labels(
                "resume" if seq.preemptions else "prefix"
            ).inc()
        tel = get_telemetry()
        # Prompt tokens by source, from the allocation that just fed
        # kv.prefix_hits its pages: what the prefix cache saved in the
        # unit prefill is paid in.
        tel.prompt_tokens.labels("cached").inc(seq.cached_len)
        tel.prompt_tokens.labels("computed").inc(
            len(seq.prompt) - seq.cached_len
        )
        if seq.submitted_at:
            tel.queue_wait.observe(max(now - seq.submitted_at, 0.0))
            tel.emit_stage(
                "queue_wait",
                seq.submitted_at,
                now,
                seq.trace,
                prompt_tokens=len(seq.prompt),
            )

    def _record_admission(self, seq: Sequence) -> None:
        """The flight recorder's ``admit`` event of a sequence
        ``_on_admitted`` has just bound: what it got, page by page."""
        self.flight.record(
            "admit",
            req=seq.request_id,
            slot=seq.slot,
            state_slot=seq.state_slot if seq.state_slot >= 0 else None,
            state_from=seq.state_restore,
            prompt=len(seq.prompt),
            cached=seq.cached_len,
            priority=seq.priority,
            pages=self._attached_pages(seq),
        )

    def _attached_pages(self, seq: Sequence) -> list[list]:
        """What an admission bound, for the flight recorder: one
        ``[page id, how, writer]`` per page, in sequence order. ``how``
        is ``matched`` (a registered page attached read-shared),
        ``tail`` (the shared partial tail: read in place by the prompt's
        last chunk, made private or copied before the first decode
        write, which the ``cow`` event records), ``host`` (a fresh page
        a host-tier upload fills) or ``fresh`` (this request computes
        it). ``writer`` names the prefill dispatch that wrote an
        attached page (``KvPageManager.page_writer``), so an asking's
        numbers can be traced to the dispatches whose bits it read."""
        uploads = {pid for pid, *_ in seq.pending_uploads}
        out = []
        for i, pid in enumerate(seq.page_ids):
            if pid == seq.shared_tail_pid:
                how = "tail"
            elif pid in uploads:
                how = "host"
            else:
                how = "matched" if i < seq.hashed_pages else "fresh"
            out.append(
                [pid, how, "" if how == "fresh" else self.kv.page_writer(pid)]
            )
        return out

    # ------------------------------------------------------ request anatomy
    def _record_anatomy(self, seq: Sequence, reason, now: float, was_bound: bool) -> None:
        """Scheduler ``on_finish`` tap (docs/observability.md "Request
        anatomy"): close the sequence's open anatomy segments, assemble
        the decomposition from its loop-stamped accumulators (pure
        arithmetic — no device work, no new host syncs), and feed the
        per-component totals, the worst-N exemplar ring, and the
        workload fingerprint. Extract-mode sequences (disagg prefill
        workers) are internal sub-requests and skipped — their time
        shows up in the client request's remote_prefill/transfer
        spans."""
        if seq.extract_cb is not None:
            return
        if seq.anat_preempted_at:
            # Finished while in preemption limbo (e.g. cancelled from
            # the waiting deque): the requeue wait is preemption cost.
            seq.anat_preempt_s += max(now - seq.anat_preempted_at, 0.0)
            seq.anat_preempted_at = 0.0
        elif was_bound:
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
        resumed = seq.stop.resume_offset or 0
        generated = resumed + seq.generated
        ttft = None
        if seq.first_token_at and not seq.preemptions and seq.submitted_at:
            ttft = max(seq.first_token_at - seq.submitted_at, 0.0)
        itl = None
        if seq.first_token_at and seq.generated > 1:
            itl = max(now - seq.first_token_at, 0.0) / (seq.generated - 1)
        a = anatomy_from_timing(
            seq.request_id,
            queue_s=seq.anat_queue_s,
            prefill_s=seq.anat_prefill_s,
            decode_s=seq.anat_decode_s,
            compile_s=seq.anat_compile_s,
            swap_s=seq.anat_swap_s,
            preempt_s=seq.anat_preempt_s,
            gap_frac=(
                self.profiler.host_gap_fraction("ragged")
                if self.profiler is not None
                else 0.0
            ),
            edge_latency_s=max(now - seq.submitted_at, 0.0)
            if seq.submitted_at
            else 0.0,
            ttft_s=ttft,
            itl_s=itl,
            prompt_tokens=max(len(seq.prompt) - resumed, 0),
            generated_tokens=generated,
            priority=seq.priority,
            page_seconds=seq.anat_page_s,
        )
        self.anatomy_requests += 1
        tel = get_telemetry()
        for comp, v in a.components.items():
            if v > 0:
                self.anatomy_totals[comp] += v
                tel.request_seconds.labels(comp).inc(v)
        self.anatomy_ring.offer(a)
        self.fingerprint.observe_finish(
            generated,
            round(seq.spec_emitted_tokens / seq.spec_dispatches, 4)
            if seq.spec_dispatches
            else 0.0,
        )

    # --------------------------------------------------- flight / profiling
    def _decode_span_attrs(self) -> dict:
        """Dispatch-profiler attrs for the decode span (scheduler.finish
        emits it): median in-flight/host-gap per decode window plus the
        window size, so sim/fit.py can fit per-dispatch service times
        straight from span files."""
        if self.profiler is None:
            return {}
        return self.profiler.span_attrs(
            "ragged", decode_window=self.cfg.decode_window
        )

    def _flight_snapshot(self) -> dict:
        """Best-effort scheduler/slot/page state for a flight dump. May
        run on the watchdog thread while the loop is wedged — read-only,
        and a torn read beats no dump."""
        try:
            slots = []
            for i, s in enumerate(self.sched.slots):
                if s is None:
                    continue
                slots.append(
                    {
                        "slot": i,
                        "req": s.request_id,
                        "state": s.state.value,
                        "generated": s.generated,
                        "pages": len(s.page_ids),
                        "stalled": bool(s.stalled_since),
                        "swapped": s.swap is not None,
                        "preemptions": s.preemptions,
                    }
                )
            return {
                "slots": slots,
                "waiting": len(self.sched.waiting),
                "submitted_unqueued": self._submit_q.qsize(),
                "pages_active": self.kv.active_pages,
                "pages_total": self.kv.num_pages,
                "inflight_window": self._inflight is not None,
                "progress_mark": self._progress_mark,
                # Full named conservation audit (docs/observability.md
                # "KV conservation auditor"): `llmctl audit <dump>`
                # renders this block, so the snapshot a ledger violation
                # dumps already names the leaking sequence/lease.
                "kv_audit": self.kv_audit(),
            }
        except Exception:  # noqa: BLE001 - snapshot is best-effort
            log.exception("flight snapshot failed")
            return {}

    def _dump_flight(self, reason: str) -> None:
        """Dump the flight ring + snapshot (watchdog stall, SIGUSR1 via
        the process registry, or engine-loop crash)."""
        path = self.cfg.flight_dump_path or default_dump_path()
        self.flight.dump(path, reason, snapshot=self._flight_snapshot())

    def _maybe_publish_gauges(self) -> None:
        """Mirror engine gauges into the telemetry registry at most
        ~2x/second — the loop can spin thousands of times faster."""
        now = time.monotonic()
        if now - self._last_gauge_pub >= 0.5:
            self._last_gauge_pub = now
            tel = get_telemetry()
            tel.publish_engine_gauges(self.metrics())
            model = self.cfg.model
            if (
                self.kv.sliding is not None or model.loop_passes > 1
                or model.index_key_in_k_row
            ):
                self._publish_pools_by_kind(tel)
            if self.host_pool is not None:
                # G2 tier occupancy (docs/engine_perf.md "Predictive KV
                # tiering"): host-tier pressure is fleet-visible.
                tel.kv_host_pages.set(self.host_pool.resident)
            if self.g3_store is not None:
                # G3 tier occupancy + corruption counters (by delta —
                # the store's in-object counters are authoritative).
                tel.kv_store_pages.set(self.g3_store.resident)
                delta = (
                    self.g3_store.checksum_failures
                    - self._pub_store_checksum_failures
                )
                if delta:
                    tel.kv_checksum_failures.labels("store").inc(delta)
                    self._pub_store_checksum_failures += delta
                delta = self.g3_store.quarantined - self._pub_store_quarantined
                if delta:
                    tel.kv_quarantined.inc(delta)
                    self._pub_store_quarantined += delta
            # Prefix-hit counters advance by delta (the page manager is
            # telemetry-free; its in-object counters are authoritative).
            for kind, total in self.kv.prefix_hits.items():
                delta = total - self._pub_prefix_hits[kind]
                if delta:
                    tel.kv_prefix_hits.labels(kind).inc(delta)
                    self._pub_prefix_hits[kind] = total
            for outcome, total in self.kv.state_hits.items():
                delta = total - self._pub_state_hits[outcome]
                if delta:
                    tel.seq_state_prefix_hits.labels(outcome).inc(delta)
                    self._pub_state_hits[outcome] = total
            for source, total in self.kv.pages_taken.items():
                delta = total - self._pub_pages_taken[source]
                if delta:
                    tel.kv_pages_taken.labels(source).inc(delta)
                    self._pub_pages_taken[source] = total

    def _service_leases(self) -> None:
        """Engine-loop-thread lease upkeep: apply queued delivery
        confirmations, then reap expired handoff leases so a decode
        instance dying between extract and inject returns the pinned
        pages within one lease period."""
        while True:
            try:
                lid = self._lease_confirm_q.get_nowait()
                self.kv.confirm_lease(lid)
                self._close_lease_span(lid, "confirmed")
                self.flight.record("lease_confirm")
            except queue.Empty:
                break
        if self.kv.active_leases:
            reclaimed = self.kv.reap_expired()
            if reclaimed:
                for lid, pages in self.kv.last_reaped:
                    self._close_lease_span(lid, "reaped", pages=pages)
                self.flight.record("lease_reap", pages=reclaimed)
                get_telemetry().kv_lease_reclaims.inc(reclaimed)
                log.warning(
                    "reaped %d KV pages from expired handoff leases "
                    "(decode side never confirmed delivery)", reclaimed,
                )

    def _close_lease_span(
        self, lease_id: str, outcome: str, pages: int | None = None
    ) -> None:
        """Close a KV-handoff lease's trace hop: one ``kv_lease`` span
        from grant to confirm/reap, parented into the request's trace —
        `llmctl trace <id>` shows the lease lifecycle next to the
        extract→transfer→inject hops. Loop-thread only (grant, confirm,
        and reap all run here); leases granted without a trace (or from
        another engine) are a no-op."""
        entry = self._lease_traces.pop(lease_id, None)
        if entry is None:
            return
        trace, granted_at = entry
        get_telemetry().emit_stage(
            "kv_lease",
            granted_at,
            time.time(),
            trace,
            lease_id=lease_id,
            outcome=outcome,
            pages=pages,
        )

    @staticmethod
    def _violation_kinds(violations: list[str]) -> tuple:
        """Value-free episode signature: the invariant *kinds* broken
        (the text before the ':' — 'page conservation broken', …). The
        messages embed live counter values that legitimately shift
        every iteration while the engine keeps serving, so deduping on
        the full strings would re-count one persistent defect at loop
        frequency."""
        return tuple(sorted({v.split(":", 1)[0] for v in violations}))

    def _check_ledger(self) -> None:
        """One in-loop conservation check (docs/observability.md "KV
        conservation auditor"). Only a *new* violation-kind set counts —
        a persistently broken invariant re-observed each iteration
        (with drifting counter values) must not melt the counter — and
        the first violation of an episode dumps a flight snapshot
        carrying the full named audit."""
        violations = self.kv.ledger_check()
        if self.g3_store is not None:
            # G3 pages join the ledger at their demote/promote/
            # quarantine transitions — same O(1) counter-arithmetic
            # style, one extra list concat per loop iteration.
            violations = violations + self.g3_store.ledger_check()
        sig = self._violation_kinds(violations)
        if sig == self._ledger_last:
            return
        self._ledger_last = sig
        if not violations:
            self._ledger_dumped = False  # episode over: re-arm the dump
            return
        self.kv_ledger_violations += len(violations)
        LEDGER_VIOLATIONS.extend(violations)
        get_telemetry().kv_ledger_violations.inc(len(violations))
        for v in violations:
            log.error("KV ledger violation: %s", v)
        self.flight.record("ledger_violation", count=len(violations))
        if not self._ledger_dumped:
            self._ledger_dumped = True
            self._dump_flight("kv_ledger")

    def _publish_pools_by_kind(self, tel) -> None:
        """A cache by kind: each pool's share in use and bytes held, and
        the context tokens of the bound rows that those bytes hold
        (``dynamo_kv_pool_usage{pool}``, ``dynamo_kv_pool_held_bytes{pool}``,
        ``dynamo_kv_context_tokens``): held bytes over context tokens is
        what a token of context costs, which a cache blind to the layers'
        form pays at every layer; and the most the sliding pool has held at
        once (``dynamo_kv_sliding_pool_peak_pages``). A looped model
        publishes its one pool's the same way (``pool="full"``): what a
        token of context costs over every (pass, layer) slot, with the
        pages' rounding; so does a grouped-query model with an indexer: what
        a token costs with the third thing it caches, the index key in its
        K row."""
        held, usage = {"full": self.kv.active_pages}, {"full": self.kv.usage}
        if self.kv.sliding is not None:
            held["sliding"] = self.kv.sliding.held
            usage["sliding"] = self.kv.sliding.usage
            tel.kv_sliding_pool_peak_pages.set(self.kv.sliding.peak_held)
        for pool, pages in held.items():
            tel.kv_pool_usage.labels(pool).set(usage[pool])
            tel.kv_pool_held_bytes.labels(pool).set(
                pages * self._page_bytes[pool]
            )
        tel.kv_context_tokens.set(sum(
            max(len(s.tokens), s.prefill_sent)
            for s in self.sched.slots if s is not None
        ))

    def kv_audit(self) -> dict:
        """Full on-demand conservation audit: every page classified into
        exactly one of {free, parked, active, leased, shared@ref>=2},
        refcounts cross-checked against the live holder set (bound
        sequences by ``seq:<request_id>``, handoff/pin leases by
        ``lease:<id>``), so a leak is *named*. Read-only — rides the
        flight snapshot (``llmctl audit <dump>`` renders it) and the
        stop()-time final check."""
        holders: dict[str, list[int]] = {}
        sliding: dict[str, list[int]] = {}
        for s in self.sched.slots:
            if s is not None and s.page_ids:
                holders[f"seq:{s.request_id}"] = list(s.page_ids)
            if s is not None and s.sliding_ids:
                sliding[f"seq:{s.request_id}"] = s.sliding_ids[s.sliding_first:]
        for s in self.sched.waiting:
            if getattr(s, "page_ids", None):
                holders[f"seq:{s.request_id}"] = list(s.page_ids)
        report = self.kv.audit(
            holders, sliding if self.kv.sliding is not None else None
        )
        if self.g3_store is not None:
            # G3 tier joins the audit: its own conservation ledger
            # (resident == adopted + stores - evictions - quarantined)
            # rendered next to the G1 page ledger by `llmctl audit`.
            g3 = self.g3_store.ledger()
            report["g3"] = g3
            if g3["violations"]:
                report["ok"] = False
        return report

    def _compute_build_info(self) -> dict:
        """Config-skew fingerprint for fleet scrapes: the AOT lattice
        manifest hash (the compile-identity of this engine shape,
        docs/aot.md), the jax version, the feature flags that change
        serving behavior, and where the engine runs — platform,
        device kind, devices in its mesh, and the RESOLVED attention
        implementation (``pallas`` / ``pallas-interpret`` / ``xla``).
        Mirrored as the dynamo_build_info gauge and the ``build_info``
        metrics() key."""
        try:
            from ..aot.compile import manifest_for_engine

            manifest_hash = manifest_for_engine(self).hash()
        except Exception:  # noqa: BLE001 - fingerprint is best-effort
            log.warning("build-info manifest hash failed", exc_info=True)
            manifest_hash = ""
        dev = self.mesh.devices.flat[0]
        return {
            "manifest_hash": manifest_hash,
            "jax_version": jax.__version__,
            "prefix_sharing": bool(self.cfg.prefix_sharing),
            "spec": self.cfg.spec_mode,
            "platform": dev.platform,
            "device_kind": dev.device_kind,
            "device_count": int(self.mesh.devices.size),
            "attn_impl": (
                "pallas-interpret"
                if self._attn_impl == "pallas" and self._attn_interpret
                else self._attn_impl
            ),
        }

    def _drain_submissions(self) -> None:
        while True:
            try:
                self.sched.submit(self._submit_q.get_nowait())
            except queue.Empty:
                return

    # ------------------------------------------------------- overload control
    def _kv_pressure(self) -> bool:
        """True while any bound row is hard-stalled (cannot feed its
        next token because the pool is dry). Admission pauses under this
        condition: a newcomer's allocation would take the very pages the
        stalled rows are waiting for — including pages a preemption just
        parked for them."""
        return any(
            s is not None and s.stalled_since for s in self.sched.slots
        )

    def _maybe_preempt(self) -> None:
        """KV-pressure preemption (docs/fault_tolerance.md "Overload
        protection"): once a row has been hard-stalled past the grace
        period, evict the lowest-priority / youngest ACTIVE sequence —
        its pages park (reusable, offload-tier write-back on eviction)
        and it requeues as a deterministic continuation of itself, so
        its stream resumes token-identically once pressure clears.
        Bounded per request by ``max_preemptions_per_seq``; each event
        lands in the trace timeline as a ``preemption`` span."""
        grace = self.cfg.preempt_stall_grace_s
        if grace < 0:
            return
        now = time.time()
        if not any(
            s is not None
            and s.stalled_since
            and now - s.stalled_since >= grace
            for s in self.sched.slots
        ):
            return
        if self.sched.active_count <= 1 and not self.sched.waiting:
            return  # nothing to yield the freed pages to
        victim = self.sched.preemption_victim(self.cfg.max_preemptions_per_seq)
        if victim is None:
            return
        t0 = victim.stalled_since or now
        freed = len(victim.page_ids)
        generated = victim.generated
        self.sched.preempt(victim)
        self.preempted += 1
        tel = get_telemetry()
        tel.preemptions.labels("kv_pressure").inc()
        tel.emit_stage(
            "preemption",
            t0,
            now,
            victim.trace,
            generated_tokens=generated,
            freed_pages=freed,
            priority=victim.priority,
            preemption=victim.preemptions,
        )
        log.warning(
            "KV pressure: preempted request %s (priority=%d, %d tokens "
            "generated, %d pages freed, preemption %d/%d); resuming as a "
            "deterministic continuation",
            victim.request_id, victim.priority, generated, freed,
            victim.preemptions, self.cfg.max_preemptions_per_seq,
        )

    # ------------------------------------------------- predictive KV tiering
    def _swapped_rows(self) -> bool:
        """True while any ACTIVE row's cold pages live in the host tier
        (swap-in pending). Admission pauses — a newcomer's allocation
        would take the very pages the swapped rows are waiting for."""
        return any(
            s is not None and s.swap is not None for s in self.sched.slots
        )

    def _maybe_proactive_offload(self) -> None:
        """Proactive cold-tail offload (docs/engine_perf.md "Predictive
        KV tiering"): once any row has been hard-stalled past the
        (short) proactive grace — and before ``preempt_stall_grace_s``
        expires — swap the coldest eligible row's refcount-1,
        non-leased pages out to the host tier through the existing
        eviction write-back. Bytes are preserved, so the row resumes
        token-identically once pressure clears; preemption (which
        re-prefills) becomes the fallback, not the policy. At most one
        victim per iteration: every swap frees pages, so the stalled
        row re-checks before a second victim pays."""
        grace = self.cfg.proactive_offload_grace_s
        if grace < 0 or self.copy_stream is None or self.host_pool is None:
            return
        now = time.time()
        if not any(
            s is not None
            and s.stalled_since
            and now - s.stalled_since >= grace
            for s in self.sched.slots
        ):
            return
        # Victims: ACTIVE rows, not already swapped, no deferred
        # finish, not disagg-extract. A stalled row is normally exempt
        # (freeing the sole starving row's pages feeds nobody) — but
        # when SEVERAL rows are starving, swapping the coldest stalled
        # one feeds the rest, so the exemption lifts. Same cold-first
        # order as preemption: lowest priority, youngest.
        n_stalled = sum(
            1
            for s in self.sched.slots
            if s is not None and s.stalled_since
        )
        cands = [
            s
            for s in self.sched.slots
            if s is not None
            and s.state is SeqState.ACTIVE
            and s.swap is None
            and (n_stalled >= 2 or not s.stalled_since)
            and s.pending_finish is None
            and s.extract_cb is None
        ]
        for victim in sorted(cands, key=lambda s: (s.priority, -s.submitted_at)):
            swapped = self._swap_out(victim)
            if swapped:
                # Relief just landed: restart the stalled rows' grace
                # clocks so preemption only fires if the freed pages
                # were NOT enough (a cold compile can block the loop
                # past the whole grace before this swap ever ran — the
                # stale clock must not preempt in the same breath).
                for s in self.sched.slots:
                    if s is not None and s.stalled_since:
                        s.stalled_since = now
                return
            if swapped is None:
                # Copy stream saturated: every further victim would
                # dispatch a gather only to shed it — stop this pass.
                return

    def _swap_out(self, victim: Sequence) -> bool | None:
        """Swap one row's cold pages to the host tier: refcount-1
        non-leased pages either write back under their content key
        (one batched gather into the CopyStream — the eviction path)
        or, when registered, simply park in the reclaimable LRU (the
        normal eviction write-back covers them if they are taken);
        shared and leased pages stay pinned by the row's ref. The row
        keeps its slot and all host-side state — only its page table
        shrinks to the kept pages, with the :class:`SwapRecord` as the
        restore ledger. Returns False when this victim had nothing
        freeable (the caller tries the next), and None when the copy
        stream shed the write-back batch — swap bytes, unlike an
        eviction's, are not recomputable, so the pages stay resident,
        and the caller must stop burning gather dispatches on further
        victims this pass."""
        entries, off_pids, off_keys, park_pids, drop_pids = plan_swap_entries(
            victim.page_ids,
            victim.tokens[: len(victim.tokens) - victim.unwritten],
            self.cfg.page_size,
            self.kv.page_ref,
            self.kv.page_hash,
            shared_tail_pid=victim.shared_tail_pid,
        )
        freed = len(off_pids) + len(park_pids) + len(drop_pids)
        if freed == 0:
            return False
        record = SwapRecord(entries=entries, committed=not off_pids)
        if off_pids:
            k_b, v_b = self._gather_page_batch(off_pids, kind="offload")

            def _mark_committed(rec=record):
                # Copy-thread callback, post-store: the swap's bytes
                # are now fetchable from the host pool (single boolean
                # write; the loop polls it before any swap-in fetch).
                rec.committed = True

            if not self.copy_stream.offload_batch(
                off_keys, k_b, v_b, on_stored=_mark_committed
            ):
                return None  # stream saturated: keep the row resident
        # The gather (if any) is already dispatched: stream order
        # protects the page content from whatever reuses the freed
        # pages next — the same guarantee the eviction path rides.
        self.kv.release_sequence(off_pids + park_pids + drop_pids)
        victim.page_ids = [pid for kind, pid in entries if kind == "kept"]
        victim.swap = record
        victim.swapped_since = time.time()
        victim.swaps += 1
        # A stalled victim is no longer starving — it is parked in the
        # host tier (swap-in owns its liveness now). Its open stall
        # window rolls into the anatomy swap/stall accumulator so the
        # swap window (which starts now) doesn't double-count it.
        if victim.stalled_since:
            victim.anat_swap_s += max(
                victim.swapped_since - victim.stalled_since, 0.0
            )
        victim.stalled = False
        victim.stalled_since = 0.0
        self.proactive_offloads += 1
        tel = get_telemetry()
        tel.kv_proactive_offloads.inc()
        tel.kv_page_moves.labels("offload").inc(len(off_pids))
        self.flight.record(
            "swap_out",
            req=victim.request_id,
            slot=victim.slot,
            pages=freed,
            kept=len(victim.page_ids),
        )
        log.info(
            "KV pressure: proactively offloaded %d page(s) of request %s "
            "to the host tier (%d kept resident); preemption avoided",
            freed, victim.request_id, len(victim.page_ids),
        )
        return True

    def _try_swap_in(self) -> None:
        """Restore swapped rows (oldest swap first) once the pool can
        cover their non-resident pages: re-attach blocks that never
        left the device (parked, or held by a sharer), fetch the rest
        from the host tier, and rebuild the page table in one batched
        scatter. A host-tier miss (the LRU dropped a swapped page)
        falls back to preemption — the deterministic continuation
        re-prefills, so the stream is still token-identical."""
        swapped = [
            s
            for s in self.sched.slots
            if s is not None and s.swap is not None
            and s.state is SeqState.ACTIVE
        ]
        if not swapped:
            return
        if self._kv_pressure():
            # Pages freed under pressure feed the hard-stalled rows
            # FIRST (they claim them at their next dispatch); a swap-in
            # grabbing them here would ping-pong the same page between
            # a starving row and the row just swapped out for it.
            return
        # Evictions still buffered on the loop would read as host-tier
        # misses below — hand them to the copy stream first (their
        # gathers are stream-ordered ahead of anything that reuses the
        # pages, exactly as at a compute dispatch).
        self._flush_offloads()
        for seq in sorted(swapped, key=lambda s: s.swapped_since):
            rec: SwapRecord = seq.swap
            if not rec.committed:
                continue  # write-back still on the copy thread
            attach: dict[int, int] = {}
            fetch_plan: list[tuple[int, int]] = []
            for i, (kind, val) in enumerate(rec.entries):
                if kind == "hash":
                    pid = self.kv.resident_page(val)
                    if pid is not None:
                        attach[i] = pid
                    else:
                        fetch_plan.append((i, val))
                elif kind == "host":
                    fetch_plan.append((i, val))
            # Headroom: fresh pages needed PLUS the parked (ref-0)
            # blocks the re-attach below revives — both come out of
            # free_pages (a parked attach leaves the reclaimable LRU).
            parked_attaches = sum(
                1 for pid in attach.values() if self.kv.page_ref(pid) == 0
            )
            if len(fetch_plan) + parked_attaches > self.kv.free_pages:
                continue  # not enough headroom yet; retry next iteration
            # Fetch the host bytes BEFORE any mutation: a miss means the
            # host LRU dropped a swapped page — preempt instead (the
            # continuation re-prefills; counter-based sampling keeps the
            # stream token-identical).
            fetched: dict[int, tuple] = {}
            miss = False
            for i, key in fetch_plan:
                data = self.host_pool.fetch(key)
                if data is None:
                    miss = True
                    break
                fetched[i] = data
            if miss:
                if self.copy_stream is not None and self.copy_stream.pending:
                    # An eviction write-back for a released "hash" page
                    # may still be in flight on the copy thread — a
                    # retry next iteration beats a spurious preemption.
                    continue
                self._preempt_swapped(seq)
                continue
            for pid in attach.values():
                self.kv.attach_page(pid)
            new_ids: list[int] = []
            taken: list[int] = list(attach.values())
            inj: list[tuple[int, object, object]] = []
            dry = False
            for i, (kind, val) in enumerate(rec.entries):
                if kind == "kept":
                    new_ids.append(val)
                elif i in attach:
                    new_ids.append(attach[i])
                else:
                    pid = self.kv.allocate_page()
                    if pid is None:
                        dry = True  # raced our own headroom check
                        break
                    new_ids.append(pid)
                    taken.append(pid)
                    inj.append((pid, fetched[i][0], fetched[i][1]))
            if dry:
                # Undo this attempt's refs and preempt — strictly rarer
                # than a host miss, but it must not leak pages.
                self.kv.release_sequence(taken)
                self._preempt_swapped(seq)
                continue
            if inj:
                self._inject_page_batch(
                    [p for p, _, _ in inj],
                    [k for _, k, _ in inj],
                    [v for _, _, v in inj],
                    op="swap_in",
                )
            seq.page_ids = new_ids
            seq.swap = None
            if seq.swapped_since:
                # Anatomy: the swap window just closed.
                seq.anat_swap_s += max(time.time() - seq.swapped_since, 0.0)
            seq.swapped_since = 0.0
            self.swap_ins += 1
            get_telemetry().kv_swap_ins.inc()
            self.flight.record(
                "swap_in",
                req=seq.request_id,
                slot=seq.slot,
                pages=len(inj),
                attached=len(attach),
            )

    def _preempt_swapped(self, seq: Sequence) -> None:
        """Swap-in fallback: the host tier lost a swapped page, so the
        row requeues as a deterministic continuation (full re-prefill).
        Rides the normal preemption surgery — ``Scheduler.preempt``
        clears the swap record."""
        self.sched.preempt(seq)
        self.preempted += 1
        get_telemetry().preemptions.labels("swap_miss").inc()
        log.warning(
            "request %s: swapped KV state could not be restored (host-"
            "tier miss); falling back to preemption (deterministic "
            "continuation)",
            seq.request_id,
        )

    def _plan_prefetch(self) -> None:
        """Scan the head of the waiting queue for prompts whose next
        pages are host-resident and restore them AHEAD of admission:
        target pages allocate against free+parked headroom minus
        ``prefetch_reserve_pages`` (evicting a parked LRU page is
        lossless — its content writes back to the host tier first),
        are pinned under a lease while the copy thread fetches the
        bytes, and are injected + registered by
        :meth:`_apply_prefetches` before a later compute dispatch — so
        the restore's host copy overlaps device compute and the
        admission that needs the pages finds them already resident
        (a plain G1 prefix hit)."""
        cfg = self.cfg
        if (
            not cfg.kv_prefetch
            or self.host_pool is None
            or self.copy_stream is None
            or not self.kv.sharing
            or not self.sched.waiting
        ):
            return
        now = time.monotonic()
        if now - self._last_prefetch_scan < 0.01:
            return
        self._last_prefetch_scan = now
        # Budget: free + parked minus the decode-growth reserve. Taking
        # a parked page is fine — its content writes back to the host
        # tier on eviction, so prefetch trades LRU-cold cache for
        # predicted-hot cache without losing bytes.
        budget = self.kv.free_pages - cfg.prefetch_reserve_pages
        if budget <= 0:
            return
        ps = cfg.page_size
        tel = get_telemetry()
        scanned = 0
        for seq in list(self.sched.waiting):
            if budget <= 0 or scanned >= cfg.prefetch_depth:
                return
            scanned += 1
            rid = seq.request_id
            if rid in self._prefetch_inflight or rid in self._prefetch_served:
                continue
            if seq.forecast_hashes is None:
                seq.forecast_hashes = compute_block_hashes_for_seq(
                    seq.prompt, ps
                )
            hashes = seq.forecast_hashes
            if not hashes:
                continue
            matched = self.kv.match_resident_hashes(hashes)
            rest = hashes[len(matched):]
            if not rest:
                continue
            g2 = self.host_pool.match_chain(rest)
            if self.g3_store is not None and len(g2) < len(rest):
                # Extend candidacy into the G3 store: the copy stream's
                # fetch falls through G2→G3 per page (checksum-verified
                # there), so a store-resident tail restores on the same
                # overlapped path — G3→G2→G1 ahead of admission.
                g2 = g2 + self.g3_store.match_chain(rest[len(g2):])
            g2 = g2[:budget]
            if not g2:
                continue
            pids: list[int] = []
            for _ in g2:
                pid = self.kv.allocate_page()
                if pid is None:
                    break
                pids.append(pid)
            if not pids:
                return
            g2 = g2[: len(pids)]
            # Pin the reserved pages under a lease: they are audit-
            # visible holders while the fetch is in flight, and the
            # reaper returns them if anything wedges.
            lease = self.kv.grant_lease(pids, cfg.kv_lease_ttl_s)
            self.kv.release_sequence(pids)
            budget -= len(pids)
            start = len(matched)
            job = {
                "req": rid,
                "pids": pids,
                "lease": lease,
                "parent": hashes[start - 1] if start else None,
                "blocks": [
                    list(seq.prompt[(start + j) * ps : (start + j + 1) * ps])
                    for j in range(len(g2))
                ],
            }
            if not self.copy_stream.fetch_batch(g2, job, self._on_prefetched):
                # Stream saturated: give the pages back and stop
                # planning this pass.
                self.kv.confirm_lease(lease)
                tel.kv_prefetch_pages.labels("dropped").inc(len(pids))
                return
            self._prefetch_inflight[rid] = job

    def _on_prefetched(self, job: dict, fetched: list) -> None:
        """CopyStream completion callback — runs ON THE COPY THREAD;
        only queues the result for the loop thread (the page manager's
        single writer) and wakes it."""
        self._prefetch_done_q.put((job, fetched))
        self._wake.set()

    def _apply_prefetches(self) -> None:
        """Loop-thread side of the prefetch direction: register the
        fetched blocks (pending-fill), inject them in one batched
        scatter — dispatched BEFORE this iteration's compute, so stream
        order protects every later read — and park them matchable by
        confirming the reservation lease. Pages whose content got
        registered by someone else mid-fetch (the target admitted and
        prefilled) just return to the free list."""
        while True:
            try:
                job, fetched = self._prefetch_done_q.get_nowait()
            except queue.Empty:
                return
            self._prefetch_inflight.pop(job["req"], None)
            if not self.kv.lease_active(job["lease"]):
                continue  # reaped: the pages were already reclaimed
            inj: list[tuple[int, object, object]] = []
            served: set[int] = set()
            parent = job["parent"]
            for j, (h, k_pg, v_pg) in enumerate(fetched):
                served.add(h)
                if self.kv.resident_page(h) is not None:
                    parent = h
                    continue  # someone already owns this content
                pid = job["pids"][j]
                self.kv.register_full_page(
                    pid, h, parent_hash=parent, tokens=job["blocks"][j],
                    content_ready=False,
                )
                inj.append((pid, k_pg, v_pg))
                parent = h
            if inj:
                pids = [p for p, _, _ in inj]
                self._inject_page_batch(
                    pids,
                    [k for _, k, _ in inj],
                    [v for _, _, v in inj],
                    op="prefetch",
                )
                self.kv.mark_filled(pids)
                self.prefetch_pages += len(inj)
                get_telemetry().kv_prefetch_pages.labels("restored").inc(
                    len(inj)
                )
                self.flight.record("prefetch", req=job["req"], pages=len(inj))
            if served:
                self._prefetch_served[job["req"]] = served
                while len(self._prefetch_served) > 256:
                    self._prefetch_served.popitem(last=False)
            # Registered + filled pages park in the reclaimable LRU
            # (matchable by the admission that asked for them); skipped
            # pages return to the free list.
            self.kv.confirm_lease(job["lease"])

    def _note_prefetch_admission(self, seq: Sequence) -> None:
        """Hit/late attribution at admission (docs/observability.md):
        restored pages the admission's G1 match actually attached count
        as hits; a target admitted while its fetch was still in flight
        counts the prefetch late (the reactive path already covered
        it)."""
        tel = get_telemetry()
        if seq.request_id in self._prefetch_inflight:
            self.prefetch_late += 1
            tel.kv_prefetch_pages.labels("late").inc()
        served = self._prefetch_served.pop(seq.request_id, None)
        if served:
            hits = sum(
                1
                for h in seq.prompt_hashes[: seq.hashed_pages]
                if h in served
            )
            if hits:
                self.prefetch_hits += hits
                tel.kv_prefetch_pages.labels("hit").inc(hits)

    def _poll_cancellations(self) -> None:
        now = time.time()
        for s in list(self.sched.slots):
            if s is None:
                continue
            if s.is_cancelled():
                self.sched.finish(s, FinishReason.CANCELLED)
            elif s.deadline_unix and now >= s.deadline_unix:
                # Bound rows honor deadlines too — without this, a row
                # stalled at its preemption bound with an expired
                # deadline would hold its slot and pages until the
                # client disconnected.
                get_telemetry().deadline_exceeded.labels("decode").inc()
                self.sched.finish(s, FinishReason.ERROR)

    def _fail_all(self) -> None:
        for s in list(self.sched.slots):
            if s is not None:
                self.sched.finish(s, FinishReason.ERROR)
        while self.sched.waiting:
            s = self.sched.waiting.popleft()
            s.emit([], FinishReason.ERROR)
        while not self._submit_q.empty():
            try:
                self._submit_q.get_nowait().emit([], FinishReason.ERROR)
            except queue.Empty:
                break
        self._drain_pin_q()
        self._drain_reclaim_q()

    def _drain_pin_q(self) -> None:
        """Resolve every queued prefix-pin request with the no-coverage
        answer — callers await these futures unboundedly, so shutdown
        and crash paths must never strand one."""
        while not self._pin_q.empty():
            try:
                _tokens, loop, fut, _trace = self._pin_q.get_nowait()
            except queue.Empty:
                break
            try:
                loop.call_soon_threadsafe(
                    lambda f=fut: f.done() or f.set_result((0, None))
                )
            except RuntimeError:
                pass

    # ----------------------------------------------------- batched page moves
    def _gather_page_batch(self, pids: list[int], kind: str = "kv_move"):
        """ONE compiled multi-page gather: device [L, bucket, ps, HkvD]
        K/V pairs covering ``pids`` (bucket-padded with the last pid; the
        caller slices back to len(pids)). One dispatch per call — a
        3k-ISL extract moves ~190 pages here instead of 190 dispatches
        and 190 host syncs. ``kind`` labels the dispatch for the
        profiler (``kv_move`` for extract, ``offload`` for eviction
        bursts); the stamp parks in ``_last_move_t`` for whichever
        existing sync consumes it."""
        bucket = self.cfg.page_move_bucket_for(len(pids))
        padded = np.full(bucket, pids[-1], np.int32)
        padded[: len(pids)] = pids
        prof = self.profiler
        if prof is not None:
            fresh = prof.first_variant("gather", bucket)
            t0 = prof.begin(kind)
        k_b, v_b = self._gather_pages(
            self.k_cache, self.v_cache, jnp.asarray(padded)
        )
        if prof is not None:
            self._last_move_t = prof.end(kind, t0, fresh)  # dynlint: thread-ownership(loop thread joined before teardown flush)
        self.flight.record("dispatch", dispatch=kind, pages=len(pids))
        self.kv_move_dispatches += 1  # dynlint: thread-ownership(loop thread joined before teardown flush)
        self.kv_page_moves += len(pids)  # dynlint: thread-ownership(loop thread joined before teardown flush)
        return k_b, v_b

    def _inject_page_batch(self, pids: list[int], k_pages, v_pages, op: str):
        """ONE compiled multi-page scatter of host pages (list of
        [L, ps, HkvD] numpy arrays) into device pages ``pids``. Pads by
        repeating the last (pid, page) pair — duplicate scatter indices
        with identical updates are deterministic. Buffered evictions
        flush first so a page being overwritten was gathered for the
        host tier before this scatter lands."""
        self._flush_offloads()
        bucket = self.cfg.page_move_bucket_for(len(pids))
        pad = bucket - len(pids)
        pid_arr = np.full(bucket, pids[-1], np.int32)
        pid_arr[: len(pids)] = pids
        hk = np.stack(list(k_pages) + [k_pages[-1]] * pad, axis=1)
        hv = np.stack(list(v_pages) + [v_pages[-1]] * pad, axis=1)
        prof = self.profiler
        if prof is not None:
            # A scatter is never host-synced (dispatch order protects
            # it), so only the dispatch leg is profiled — adding a sync
            # here is exactly what the profiler must never do.
            fresh = prof.first_variant("scatter", bucket)
            t0 = prof.begin("kv_move")
        self.k_cache, self.v_cache = self._inject_pages(
            self.k_cache,
            self.v_cache,
            jnp.asarray(pid_arr),
            jnp.asarray(hk),
            jnp.asarray(hv),
        )
        if prof is not None:
            prof.end("kv_move", t0, fresh)
        self.flight.record(
            "dispatch", dispatch="kv_move", op=op, pages=len(pids)
        )
        self.kv_move_dispatches += 1
        self.kv_page_moves += len(pids)
        get_telemetry().kv_page_moves.labels(op).inc(len(pids))

    def _flush_offloads(self) -> None:
        """Batch-gather every eviction buffered since the last compute
        dispatch and hand the burst to the CopyStream as one item.
        Called right before anything that could overwrite the evicted
        pages (decode/prefill/inject dispatches) and on the idle path —
        stream order then guarantees the gather reads the old content."""
        if not self._pending_offloads:
            return
        moved, self._pending_offloads = self._pending_offloads, []  # dynlint: thread-ownership(loop thread joined before teardown flush)
        if self.copy_stream is None:
            return
        k_b, v_b = self._gather_page_batch(
            [pid for pid, _ in moved], kind="offload"
        )
        on_synced = None
        if self.profiler is not None:
            # The CopyStream worker's np.asarray is this dispatch's one
            # host sync; its completion callback is the consume point.
            prof, t_disp = self.profiler, self._last_move_t
            on_synced = lambda: prof.consume("offload", t_disp)  # noqa: E731
        self.copy_stream.offload_batch(
            [h for _, h in moved], k_b, v_b, on_synced=on_synced
        )
        get_telemetry().kv_page_moves.labels("offload").inc(len(moved))

    # ---------------------------------------------------------------- prefill
    def _apply_uploads(self, seq: Sequence) -> None:
        """Re-inject G2 host pages into their fresh device pages before
        the compute that attends over them (dispatch order on the device
        stream makes this safe without explicit sync) — one batched
        scatter per sequence, not one per page."""
        if not seq.pending_uploads:
            return
        upload_pids = [pid for pid, _h, _k, _v in seq.pending_uploads]
        self._inject_page_batch(
            upload_pids,
            [hk for _pid, _h, hk, _v in seq.pending_uploads],
            [hv for _pid, _h, _k, hv in seq.pending_uploads],
            op="upload",
        )
        # Content is on the stream: sharers waiting on these restored
        # pages can dispatch behind it.
        self.kv.mark_filled(upload_pids)
        seq.pending_uploads = []

    @staticmethod
    def _wants_logprobs(seq: Sequence) -> int | None:
        """The request's top_logprobs count (0 = chosen only), or None."""
        return seq.stop.sampling_options.logprobs

    @staticmethod
    def _lp_pack(n_top: int, n: int, lps, top_ids, top_lps):
        """Host-side logprob payload for emit, of the first ``n`` tokens
        of a row's arrays: per-token chosen logprob plus the top-n
        alternatives (n sliced from the static TOP_LOGPROBS the device
        computes)."""
        tops = None
        if n_top > 0:
            tops = [
                {int(t): float(l) for t, l in zip(tid[:n_top], tlp[:n_top])}
                for tid, tlp in zip(top_ids[:n], top_lps[:n])
            ]
        return ([float(x) for x in lps[:n]], tops)

    def _promote_to_decode(self, seq: Sequence) -> None:
        """A prompt's K/V is whole: close the request's prefill stage and
        make the sequence a decoding row. ``_finish_first_token``'s first
        half; a block model, whose prefill samples nothing, stops here."""
        now = time.time()
        seq.first_token_at = seq.last_emit_at = now
        tel = get_telemetry()
        start = seq.admitted_at or seq.submitted_at or now
        tel.prefill_compute.observe(max(now - start, 0.0))
        # Anatomy: close this life's prefill segment and attribute the
        # profiler's compile-seconds growth since admission as this
        # request's compile stall (clamped into prefill at assembly).
        prefill_s = max(now - start, 0.0)
        seq.anat_prefill_s += prefill_s
        compile_s = 0.0
        if self.profiler is not None:
            compile_s = max(
                self.profiler.compile_total_s() - seq.anat_compile_mark, 0.0
            )
            seq.anat_compile_s += min(compile_s, prefill_s)
        tel.emit_stage(
            "prefill",
            start,
            now,
            seq.trace,
            prompt_tokens=len(seq.prompt),
            cached_tokens=seq.cached_len,
            remote=seq.remote_prefilled or None,
            resumed_tokens=seq.stop.resume_offset or None,
            compile_s=round(compile_s, 6) if compile_s else None,
            # Dispatch-profiler medians (sim/fit.py reads these).
            **(
                self.profiler.span_attrs("ragged")
                if self.profiler is not None
                else {}
            ),
        )
        # Anatomy reconstruction from a flight dump alone needs the
        # prefill/decode boundary (telemetry.anatomy.anatomy_from_flight).
        self.flight.record("first_token", req=seq.request_id, slot=seq.slot)
        seq.state = SeqState.ACTIVE

    def _finish_first_token(
        self, seq: Sequence, token: int, lp_pack=None, counted: bool = False
    ) -> None:
        """Shared tail of the two admission paths (computed prefill or
        remote-KV injection): record + announce the first sampled token
        and promote the sequence to decode. ``lp_pack`` is None on the
        remote-KV path — the first token was sampled on the prefill
        worker, which doesn't ship its distribution. ``counted``: the
        dispatch that drew ``token`` already left the slot's penalty
        counts at journaled tail + ``token`` (``_build_mixed``)."""
        self._promote_to_decode(seq)
        if not counted:
            self._counts = self._init_row(self._counts, seq.slot, token)
            # Remote-KV continuation with penalties: the first token was
            # drawn on the prefill worker; every later draw sees the
            # counts the uninterrupted run would have.
            tail = self._journaled_tail_counts(seq)
            if tail is not None:
                self._counts = self._counts.at[seq.slot].add(jnp.asarray(tail))
        seq.tokens.append(token)
        seq.generated = 1
        self.sched.register_full_pages(seq)
        if seq.extract_cb is not None:
            pages, lease_id = self._extract_prompt_pages(seq)
            seq.extract_cb(token, pages, lease_id)
        reason = self.sched.check_stop(seq, token)
        seq.emit([token], None, lp_pack)
        if reason is not None:
            self.sched.finish(seq, reason)

    def _extract_prompt_pages(self, seq: Sequence) -> tuple[list, str]:
        """Host-bounce every prompt page (incl. the partial tail) for the
        disaggregation handoff: ONE batched gather dispatch and ONE host
        sync per sequence. Runs on the engine loop thread: the prefill
        worker's job is exactly this transfer. The device pages are
        pinned under a handoff lease (granted here, while the sequence
        still holds its refs) until the caller confirms delivery or the
        reaper reclaims them."""
        ps = self.cfg.page_size
        n_pages = (len(seq.prompt) + ps - 1) // ps
        skip = min(seq.extract_skip, n_pages)
        pids = seq.page_ids[skip:n_pages]
        if not pids:
            return [], ""
        k_b, v_b = self._gather_page_batch(pids)
        k_np, v_np = np.asarray(k_b), np.asarray(v_b)  # dynlint: sync-point(extract gather consume)
        if self.profiler is not None:
            self.profiler.consume("kv_move", self._last_move_t)
        self.flight.record("consume", dispatch="kv_move", pages=len(pids))
        get_telemetry().kv_page_moves.labels("extract").inc(len(pids))
        lease_id = self.kv.grant_lease(pids, self.cfg.kv_lease_ttl_s)
        if seq.trace is not None:
            # Open the lease's trace hop: closed (one kv_lease span)
            # when the delivery ack confirms it or the reaper reclaims
            # it, so `llmctl trace` shows grant -> confirm | reap.
            self._lease_traces[lease_id] = (seq.trace, time.time())
        self.flight.record("lease_grant", req=seq.request_id, pages=len(pids))
        return [
            (
                np.ascontiguousarray(k_np[:, i]),
                np.ascontiguousarray(v_np[:, i]),
            )
            for i in range(len(pids))
        ], lease_id

    def _run_remote_inject(self, seq: Sequence) -> None:
        """Disaggregated admission: prompt KV was computed by a remote
        prefill worker — inject it (one batched scatter) and go straight
        to decode. Suffix-only transfers (docs/prefix_sharing.md) ship
        ``rk.pages`` starting at prompt page ``rk.skip_pages``; the
        decode-side pin that guaranteed those first pages stayed
        resident is released here."""
        self._apply_uploads(seq)
        ps = self.cfg.page_size
        rk = seq.remote_kv
        if rk.pin_lease:
            # Admission re-referenced the pinned pages (or is about to
            # fall back); either way the routing-time pin has done its
            # job. The sequence's own refs keep the pages alive now.
            self.kv.confirm_lease(rk.pin_lease)
            self._close_lease_span(rk.pin_lease, "confirmed")
            rk.pin_lease = None
        n_pages = (len(seq.prompt) + ps - 1) // ps
        if rk.skip_pages and seq.cached_len // ps < rk.skip_pages:
            # The local prefix the transfer skipped is no longer fully
            # resident (pin reaped under an extreme queue wait): the
            # received suffix is useless without it. Fall back to local
            # prefill — the sequence simply stays in PREFILL.
            log.warning(
                "request %s: suffix-only KV transfer skipped %d pages "
                "but only %d are resident; prefilling locally",
                seq.request_id, rk.skip_pages, seq.cached_len // ps,
            )
            seq.remote_kv = None
            return
        start = max(seq.cached_len // ps, rk.skip_pages)
        end = min(n_pages, rk.skip_pages + len(rk.pages))
        if end > start:
            self._inject_page_batch(
                seq.page_ids[start:end],
                [rk.pages[i - rk.skip_pages][0] for i in range(start, end)],
                [rk.pages[i - rk.skip_pages][1] for i in range(start, end)],
                op="inject",
            )
            self.kv.mark_filled(seq.page_ids[start:end])
        seq.remote_kv = None  # drop the host copy the moment it's injected
        seq.remote_prefilled = True
        self._finish_first_token(seq, rk.first_token)

    # --------------------------------------------------------- ragged dispatch
    def _dispatch_ragged(
        self, prefill_rows: list[Sequence]
    ) -> list[_PendingRagged]:
        """Assemble and dispatch this iteration's ragged batch(es)
        (docs/engine_perf.md "One ragged dispatch"): every slot's next
        unit of work — a chunked-prefill span, a decode step/window, or
        a speculative verify span — rides one flat query stream per
        sampler partition. A late-arriving prompt's chunk therefore
        joins the in-flight batch the iteration it is admitted; its
        first token samples in the same dispatch that steps the decode
        rows, instead of waiting behind a separate prefill program.

        Rows are partitioned greedy-vs-full-sampler (a creative request
        must not drag greedy rows through the penalty/top-k machinery),
        so an iteration issues at most two dispatches. A partition that
        is pure decode (every row one fed token, no drafts) takes the
        ``windowed`` shape — ``decode_window`` on-device steps, host
        syncs once per window, chainable device-to-device. Returns the
        pending dispatches; [] when nothing could step (pool dry / no
        ACTIVE or ready-PREFILL rows)."""
        with self._phase("assemble"), self._part("assemble", "plan"):
            batches, solo = self._plan_ragged(prefill_rows)
        out: list[_PendingRagged] = []
        block = self.cfg.model.block_length
        for fs, dec, spec, pf, windowed in batches:
            if block and windowed:
                part = [(r, seq, start) for r, (seq, start, _) in enumerate(dec)]
                out.append(self._build_block(part, fs, solo))
            elif block:
                out.append(self._build_mixed_block(dec, pf, fs))
            elif windowed:
                out.append(self._build_windowed(dec, fs, solo))
            else:
                out.append(self._build_mixed(dec, spec, pf, fs))
        return out

    def _plan_ragged(
        self, prefill_rows: list[Sequence]
    ) -> tuple[list[tuple], bool]:
        """Partition every slot's next unit of work into this
        iteration's batches ``(full_sampler, decode rows, spec rows,
        prefill rows, windowed)``, provisioning pages as it goes; and
        whether the one batch is a chainable window."""
        cfg = self.cfg
        block = cfg.model.block_length
        ps, K = cfg.page_size, block or cfg.decode_window
        greedy: list[tuple[Sequence, int, int]] = []  # (seq, wpos, cap)
        sampler: list[tuple[Sequence, int, int]] = []
        for seq in self.sched.slots:
            if seq is None or seq.state is not SeqState.ACTIVE:
                continue
            if seq.swap is not None:
                # Proactively offloaded: the row's cold pages live in
                # the host tier; it sits dispatches out until
                # _try_swap_in restores them (token-identically).
                continue
            if seq.shared_tail_pid >= 0 and not self._resolve_shared_tail(seq):
                # The shared tail page must be private before this row's
                # first decode write lands in it, and the COW copy found
                # the pool dry: hard-stall the row (same grace clock as
                # a dry page allocation).
                seq.stalled = True
                if not seq.stalled_since:
                    seq.stalled_since = time.time()
                    self.flight.record(
                        "stall_start", req=seq.request_id, slot=seq.slot
                    )
                continue
            wpos = len(seq.tokens) - 1  # position of the token being fed
            if block:
                # A block model feeds its whole block or nothing: from the
                # block's start (engine/block.py) through its last position.
                wpos = self._block_of(seq)
                if wpos + K > cfg.max_model_len:
                    self.sched.finish(seq, FinishReason.LENGTH)
                    continue
            # Provision the whole window up front (best effort: partial
            # allocation still lets the row run until its pages end).
            self.sched.ensure_pages_until(seq, wpos + K - 1)
            cap = min(cfg.max_model_len, len(seq.page_ids) * ps) - 1
            if cap < (wpos + K - 1 if block else wpos):
                if wpos // ps >= self.kv.num_pages:
                    # The row's own context now exceeds the ENTIRE pool:
                    # no preemption or wait can ever feed its next token
                    # on this engine. The pool is this deployment's hard
                    # context capacity — close the stream with what it
                    # has (mirrors the max_model_len LENGTH) instead of
                    # stalling the slot forever.
                    log.warning(
                        "request %s reached the KV pool's context "
                        "capacity (%d pages) at %d tokens; finishing "
                        "with length",
                        seq.request_id, self.kv.num_pages, wpos,
                    )
                    self.sched.finish(seq, FinishReason.LENGTH)
                    continue
                # Hard stall: the row cannot even feed its next token.
                # Start (or keep) the preemption grace clock.
                seq.stalled = True
                if not seq.stalled_since:
                    seq.stalled_since = time.time()
                    self.flight.record(
                        "stall_start", req=seq.request_id, slot=seq.slot
                    )
                continue  # pool dry: this slot idles one window
            seq.stalled = len(seq.page_ids) * ps < min(
                wpos + K, cfg.max_model_len
            )
            if seq.stalled_since:
                # Anatomy: the page-stall window just closed.
                seq.anat_swap_s += max(time.time() - seq.stalled_since, 0.0)
                self.flight.record(
                    "stall_end", req=seq.request_id, slot=seq.slot
                )
            seq.stalled_since = 0.0  # progressing (even if window-capped)
            part = sampler if self._needs_sampler(seq) else greedy
            part.append((seq, wpos, cap))
        spec_parts: dict[bool, list] = {False: [], True: []}
        if self._spec is not None:
            greedy, spec_parts[False] = self._extract_spec_rows(greedy)
            sampler, spec_parts[True] = self._extract_spec_rows(sampler)
            if len(self._spec) > 4 * cfg.max_decode_slots:
                self._spec.retain(
                    s.request_id for s in self.sched.slots if s is not None
                )
        pf_parts: dict[bool, list[Sequence]] = {False: [], True: []}
        for seq in prefill_rows:
            pf_parts[self._needs_sampler(seq)].append(seq)
        batches = []
        for fs, dec in ((False, greedy), (True, sampler)):
            spec, pf = spec_parts[fs], pf_parts[fs]
            if not (dec or spec or pf):
                continue
            windowed = bool(dec) and not spec and not pf
            batches.append((fs, dec, spec, pf, windowed))
        # A window is chainable only when it is the iteration's single
        # dispatch — a concurrent mixed batch (like a second partition)
        # means the row set will be re-planned next round.
        solo = len(batches) == 1 and batches[0][4]
        return batches, solo

    # ------------------------------------------------------------ row helpers
    @staticmethod
    def _needs_sampler(seq: Sequence) -> bool:
        """True when the row needs the full penalty/top-k/top-p sampler
        (vs the greedy fast path)."""
        so = seq.stop.sampling_options
        return bool(
            (so.temperature or 0.0) > 0.0
            or so.frequency_penalty
            or so.presence_penalty
            or (so.repetition_penalty or 1.0) != 1.0
        )

    def _journaled_tail_counts(self, seq: Sequence) -> np.ndarray | None:
        """Penalty counts of a failover / preemption continuation: the
        re-prefilled tail of its prompt is journaled *completion* tokens,
        which the uninterrupted run had counted. None when the sequence
        is no continuation or draws without penalties."""
        resumed = seq.stop.resume_offset or 0
        if not (resumed and self._needs_sampler(seq)):
            return None
        V = self.cfg.model.vocab_size
        tail = np.clip(
            np.asarray(seq.prompt[-resumed:], np.int64),  # dynlint: sync-point(host-list conversion)
            0,
            V - 1,
        )
        return np.bincount(tail, minlength=V).astype(np.int32)

    def _stop_gates(self, seq: Sequence, g0: int) -> tuple[int, int]:
        """On-device stop gates for a row whose window starts with ``g0``
        tokens already generated. Gates are window-step indices t
        (0-based): EOS is actionable at t >= eos_gate (mirrors
        check_stop's min_tokens rule), and the row's max_tokens budget
        runs out after the token sampled at t == budget_gate."""
        sc = seq.stop.stop_conditions
        eos_gate = max((sc.min_tokens or 0) - g0 - 1, 0)
        max_tokens = sc.max_tokens or self.cfg.default_max_tokens
        budget_gate = max(max_tokens - g0 - 1, 0)
        return eos_gate, budget_gate

    def _stop_set(self, seq: Sequence) -> list[int]:
        """The row's on-device stop-token set (static for its lifetime;
        a chained window reuses the already-built array). Overflowing
        sets truncate — the host's check_stop remains authoritative."""
        sc = seq.stop.stop_conditions
        if sc.ignore_eos:
            return []
        stops = list(self.cfg.eos_token_ids) + list(sc.stop_token_ids)
        return stops[: self.cfg.device_stop_width]

    def _resolve_shared_tail(self, seq: Sequence) -> bool:
        """Copy-on-write before the first divergent write: the row's
        next decode token lands inside a page it attached read-shared
        (radix partial-tail match). Sole holder ⇒ the page just leaves
        the index (content offloads to G2 first); shared ⇒ allocate a
        replacement and duplicate it device-to-device — ONE dispatch,
        stream-ordered ahead of the decode window that diverges it.
        False when the pool can't supply the copy target (hard stall)."""
        pid = seq.shared_tail_pid
        new_pid = self.kv.make_private(pid)
        if new_pid is None:
            return False
        if new_pid != pid:
            idx = seq.page_ids.index(pid)
            self._flush_offloads()
            prof = self.profiler
            if prof is not None:
                fresh = prof.first_variant("cow", 0)
                t0 = prof.begin("kv_move")
            self.k_cache, self.v_cache = self._cow_pages(
                self.k_cache,
                self.v_cache,
                jnp.asarray(pid, jnp.int32),
                jnp.asarray(new_pid, jnp.int32),
            )
            if prof is not None:
                prof.end("kv_move", t0, fresh)
            seq.page_ids[idx] = new_pid
            self.kv.note_writer(
                [new_pid], f"cow<-{pid}:{self.kv.page_writer(pid)}"
            )
            self.kv.release_sequence([pid])
            self.kv_page_moves += 1
            self.kv_move_dispatches += 1
            get_telemetry().kv_page_moves.labels("cow").inc()
            get_telemetry().kv_cow_copies.inc()
            self.flight.record(
                "cow", req=seq.request_id, slot=seq.slot,
                src=pid, dst=new_pid,
            )
        seq.shared_tail_pid = -1
        return True

    def _row_sampler_args(self, seq: Sequence, r: int, arrs: tuple) -> None:
        """Fill row ``r`` of the per-row sampler parameter arrays
        (seeds, temp, top_k, top_p, freq, pres, rep)."""
        seeds, temp, top_k, top_p, freq, pres, rep = arrs
        so = seq.stop.sampling_options
        seeds[r] = seq.sample_seed & 0x7FFFFFFF
        temp[r] = so.temperature if so.temperature is not None else 0.0
        top_k[r] = so.top_k or 0
        top_p[r] = so.top_p if so.top_p is not None else 1.0
        freq[r] = so.frequency_penalty or 0.0
        pres[r] = so.presence_penalty or 0.0
        rep[r] = so.repetition_penalty or 1.0

    # ------------------------------------------------------------ speculation
    def _extract_spec_rows(self, part):
        """Split one decode partition into (plain rows, speculative
        rows): a row speculates when the controller wants to probe it
        AND the drafter proposes at least one token that fits the row's
        page/model-length capacity. The drafts' KV positions are
        provisioned here (best effort — a dry pool just shortens the
        draft; the verify pass still always emits >= 1 token)."""
        ps = self.cfg.page_size
        plain, spec = [], []
        for seq, wpos, cap in part:
            drafts = (
                self._spec.propose(seq)
                if self._spec.wants_draft(seq)
                else []
            )
            if drafts:
                self.sched.ensure_pages_until(seq, wpos + len(drafts))
                cap = min(
                    self.cfg.max_model_len, len(seq.page_ids) * ps
                ) - 1
                g = min(len(drafts), cap - wpos, self.cfg.spec_max_draft)
                if g >= 1:
                    spec.append((seq, wpos, cap, drafts[:g]))
                    continue
            plain.append((seq, wpos, cap))
        return plain, spec

    def _rewind_spec_pages(self, seq: Sequence) -> None:
        """Page-granular rewind after a rejection: pages provisioned for
        draft positions beyond the accepted prefix go back to the pool
        when the rejection crossed a page boundary. Only unregistered
        tail pages can be trailing here (registration stops at the last
        *full* page below the confirmed write head), so the release
        can't disturb the reuse index; the KV slots inside the kept tail
        page are overwritten in place as decode advances."""
        ps = self.cfg.page_size
        keep = (len(seq.tokens) - 1) // ps + 1
        if len(seq.page_ids) > keep:
            extra = seq.page_ids[keep:]
            del seq.page_ids[keep:]
            self.kv.release_sequence(extra)
            self.flight.record(
                "spec_rewind", req=seq.request_id, pages=len(extra)
            )

    # --------------------------------------------------------------- builders
    def _build_windowed(
        self,
        part: list[tuple[Sequence, int, int]],
        full_sampler: bool,
        solo: bool,
    ) -> _PendingRagged:
        """Build + dispatch one pure-decode windowed batch (no host
        sync): ``decode_window`` on-device steps over the compacted
        rows — the ragged family's one-query-per-row shape."""
        with self._phase("assemble"), self._part("assemble", "build"):
            cfg = self.cfg
            ps, K = cfg.page_size, cfg.decode_window
            nb = cfg.ragged_tokens_bucket_for(len(part))
            # Padding rows park at -1, map to the scratch counts row (B)
            # so their scatter can't touch a live slot, have a budget
            # gate that never fires and keep no state (an entry past
            # the pool is never written). A live row reads and writes its
            # own entry and no other's: what the delta rule's in-place
            # step asks of a call (ops/gated_delta.py).
            packed, f = self._launch_buffer(nb, True, full_sampler)
            tokens, positions = f["tokens"], f["positions"]
            max_pos, table, slot_map = f["max_pos"], f["table"], f["slot_map"]
            stop_set, eos_gate = f["stop_set"], f["eos_gate"]
            budget_gate, state_slots = f["budget_gate"], f["state_slots"]
            sampler_args = tuple(f[name] for name in SAMPLER_FIELDS)

            rows: list[_RaggedRow] = []
            max_pages = 1
            capacity_capped = False
            for r, (seq, wpos, cap) in enumerate(part):
                capacity_capped = capacity_capped or cap < wpos + K
                tokens[r] = seq.last_token()
                positions[r] = wpos
                max_pos[r] = cap
                self._table_row(table, r, seq)
                slot_map[r] = seq.slot
                state_slots[r] = seq.state_slot
                max_pages = max(max_pages, (min(wpos + K, cap + 1) + ps - 1) // ps)
                stops = self._stop_set(seq)
                stop_set[r, : len(stops)] = stops
                eos_gate[r], budget_gate[r] = self._stop_gates(seq, seq.generated)
                self._row_sampler_args(seq, r, sampler_args)
                rows.append(
                    _RaggedRow(seq, "decode", r, n_valid=min(K, cap - wpos + 1))
                )

            want_lp = any(
                self._wants_logprobs(e.seq) is not None for e in rows
            )
        self.steps += K
        get_telemetry().decode_batch_rows.labels("window").observe(len(part))
        fed = self._window_steps(nb, rows, positions, budget_gate)
        return self._launch(
            nb, max_pages, packed, rows, windowed=True,
            full_sampler=full_sampler, want_lp=want_lp,
            live=sum(e.n_valid for e in rows), solo=solo,
            capacity_capped=capacity_capped, routed_rows=fed, rows=len(part),
        )

    def _table_row(self, table, r: int, seq: Sequence) -> None:
        """Row ``r`` of a launch's page table: the sequence's pages and,
        where the cache is by kind, behind them its pages of the sliding
        pool (``aot/launch.py::split_tables``; 0 where one went back
        behind the width: no read reaches there)."""
        table[r, : len(seq.page_ids)] = seq.page_ids
        if seq.sliding_ids:
            at = self.cfg.max_pages_per_seq
            table[r, at : at + len(seq.sliding_ids)] = seq.sliding_ids

    def _window_steps(self, nb: int, rows: list, first_pos, budget_gate) -> int:
        """The tokens a window's rows really feed: a row's valid steps, cut
        where its ``max_tokens`` runs out (``_stop_gates``: the device
        parks it after step ``budget_gate``; a stop token is the device's
        to find and is not foreseen). Counts a latent model's reads
        (``_count_latent_reads``) from each row's first position in
        ``first_pos`` (indexed by row), the rows of the ``nb`` each step's
        attention call reads by their first fetch
        (``_count_one_query_rows``), and returns the steps' sum."""
        steps = [min(e.n_valid, int(budget_gate[e.row]) + 1) for e in rows]
        firsts = [first_pos[e.row] for e in rows]
        self._count_latent_reads("window", firsts, steps=steps)
        self._count_attn_work(firsts, steps)
        fed = np.full((self.cfg.decode_window, nb), -1, np.int32)
        for e, n in zip(rows, steps):
            fed[:n, e.row] = 0
        self._count_one_query_rows(fed)
        return sum(steps)

    def _build_mixed(
        self,
        dec: list[tuple[Sequence, int, int]],
        spec: list[tuple],
        pf: list[Sequence],
        full_sampler: bool,
    ) -> _PendingRagged:
        """Build + dispatch one mixed ragged batch (no host sync): a
        flat query stream carrying each prefill row's next chunk, each
        decode row's fed token, and each speculative row's
        last-token + drafts span, over one compiled ragged program.
        Decode rows advance one step here (the window resumes once the
        batch is pure decode again); prompts completing this chunk
        sample their first token in the same dispatch."""
        with self._phase("assemble"), self._part("assemble", "plan"):
            sizes = self._plan_chunks(
                [len(seq.prompt) - seq.prefill_sent for seq in pf],
                [seq.admitted_at for seq in pf],
                len(dec),
                [len(drafts) + 1 for *_, drafts in spec],
            )
        with self._phase("assemble"), self._part("assemble", "build"):
            cfg = self.cfg
            ps = cfg.page_size
            B1 = cfg.max_decode_slots + 1
            align = self._ragged_align()
            flat_tokens: list[int] = []
            flat_pos: list[int] = []
            flat_row: list[int] = []

            # The bucket is known before the spans are laid out: their
            # extents are the plan's sizes, each rounded up to its tile.
            with_spec = bool(spec)
            nb = self._mixed_bucket_for(
                sum(
                    -(-n // align) * align
                    for n in (*sizes, *(len(d) + 1 for *_, d in spec))
                ),
                len(dec),
            )
            # As the all-padding batch: flat padding maps to the scratch
            # per-row index (B1 - 1 is always free: at most
            # max_decode_slots rows hold slots) at position -1. Sequence
            # state (a model that has it): the entry each row reads, and
            # the writes (stream index -> entry): rows' own in the first
            # B1, a prefill row's snapshot in the second. An entry past
            # the pool is no write. No row reads an entry another row of
            # the dispatch writes (a row owns its entry and a snapshot's
            # is fresh): the delta rule's in-place step takes the rows
            # one after another and rests on it (ops/gated_delta.py).
            packed, f = self._launch_buffer(nb, False, full_sampler, with_spec)
            tokens, positions, row_of = f["tokens"], f["positions"], f["row_of"]
            table, q_last, pos0 = f["table"], f["q_last"], f["pos0"]
            is_decode, slot_map = f["is_decode"], f["slot_map"]
            spec_idx, spec_pos = f["spec_idx"], f["spec_pos"]
            spec_drafts, n_drafts = f["spec_drafts"], f["n_drafts"]
            sampler_arrs = tuple(f[name] for name in SAMPLER_FIELDS)
            state_slots = f["state_slots"]
            state_src, state_dst = f["state_src"], f["state_dst"]

            def add_span(toks: list[int], poss: list[int], r: int) -> int:
                """Append one row's query span to the flat stream, aligned
                to the kernel's q_tile (padding positions are -1: their
                writes drop and their scores mask out)."""
                start = len(flat_tokens)
                flat_tokens.extend(toks)
                flat_pos.extend(poss)
                flat_row.extend([r] * len(toks))
                pad = (-len(toks)) % align
                if pad:
                    flat_tokens.extend([0] * pad)
                    flat_pos.extend([-1] * pad)
                    flat_row.extend([r] * pad)
                return start

            rows: list[_RaggedRow] = []
            chunks: list[tuple[Sequence, int, int]] = []  # (seq, from, n)
            max_pages = 1
            r = 0
            for seq, n in zip(pf, sizes):
                self._apply_uploads(seq)
                start_tok = seq.prefill_sent
                qs = add_span(
                    list(seq.prompt[start_tok : start_tok + n]),
                    list(range(start_tok, start_tok + n)),
                    r,
                )
                seq.prefill_sent = start_tok + n
                chunks.append((seq, start_tok, n))
                self.sched.ensure_sliding_until(seq, start_tok + n - 1)
                self._table_row(table, r, seq)
                q_last[r] = qs + n - 1
                if seq.state_slot >= 0:
                    state_slots[r] = state_dst[r] = seq.state_slot
                    state_src[r] = q_last[r]
                    # The deepest block boundary this chunk crosses: keep
                    # the state there for a later prefix hit to restore.
                    b = seq.prefill_sent // ps * ps
                    entry = (
                        self.kv.snapshot_entry(seq.prompt_hashes[b // ps - 1])
                        if b > start_tok else None
                    )
                    if entry is not None:
                        state_src[B1 + r] = qs + b - 1 - start_tok
                        state_dst[B1 + r] = entry
                # Key the first-token draw by the absolute position of the
                # prompt's last token — identical to the draw a decode
                # window would make feeding that token, so prefill chunking
                # and continuation re-prefills replay the same sample.
                pos0[r] = start_tok + n - 1
                max_pages = max(max_pages, (seq.prefill_sent + ps - 1) // ps)
                self._row_sampler_args(seq, r, sampler_arrs)
                completing = seq.prefill_sent == len(seq.prompt)
                tail = self._journaled_tail_counts(seq) if completing else None
                if tail is not None:
                    # The splice token of a penalized continuation draws
                    # as the decode row it was in the uninterrupted run:
                    # through the counts of the journaled tail.
                    self._counts = self._counts.at[seq.slot].set(
                        jnp.asarray(tail)
                    )
                    is_decode[r] = True
                    slot_map[r] = seq.slot
                rows.append(
                    _RaggedRow(
                        seq, "prefill", r,
                        completing=completing, counted=tail is not None,
                    )
                )
                r += 1
            # A decode row's one token is no span to align: the rows take
            # their slots side by side once the spans' extent is known.
            dec_rows = np.arange(r, r + len(dec))
            for seq, wpos, _cap in dec:
                self._table_row(table, r, seq)
                if seq.state_slot >= 0:
                    state_slots[r] = state_dst[r] = seq.state_slot
                pos0[r] = wpos
                is_decode[r] = True
                slot_map[r] = seq.slot
                max_pages = max(max_pages, wpos // ps + 1)
                self._row_sampler_args(seq, r, sampler_arrs)
                rows.append(_RaggedRow(seq, "decode", r, n_valid=1))
                r += 1
            for seq, wpos, _cap, drafts in spec:
                g = len(drafts)
                qs = add_span(
                    [seq.last_token()] + list(drafts),
                    list(range(wpos, wpos + g + 1)),
                    r,
                )
                self._table_row(table, r, seq)
                q_last[r] = qs + g
                slot_map[r] = seq.slot
                spec_idx[r, : g + 1] = qs + np.arange(g + 1)
                spec_pos[r, : g + 1] = np.arange(wpos, wpos + g + 1)
                spec_drafts[r, :g] = drafts
                n_drafts[r] = g
                max_pages = max(max_pages, (wpos + g) // ps + 1)
                self._row_sampler_args(seq, r, sampler_arrs)
                rows.append(_RaggedRow(seq, "spec", r, n_drafts=g))
                r += 1

            aligned = len(flat_tokens)
            assert self._mixed_bucket_for(aligned, len(dec)) == nb, (aligned, nb)
            dec_from = self._decode_from(nb, aligned)
            total_q = dec_from + len(dec) if dec else aligned
            tokens[:aligned] = flat_tokens
            positions[:aligned] = flat_pos
            row_of[:aligned] = flat_row
            dec_at = slice(dec_from, total_q)
            tokens[dec_at] = [seq.last_token() for seq, _, _ in dec]
            positions[dec_at] = pos0[dec_rows]
            row_of[dec_at] = dec_rows
            q_last[dec_rows] = state_src[dec_rows] = np.arange(
                dec_from, total_q
            )

            want_lp = any(
                self._wants_logprobs(e.seq) is not None for e in rows
            )
        pending = self._launch(
            nb, max_pages, packed, rows, windowed=False,
            full_sampler=full_sampler, want_lp=want_lp, with_spec=with_spec,
            live=int((positions >= 0).sum()), singles=len(dec), rows=len(rows),
            tokens=total_q,
        )
        self._count_attn_passes(nb, row_of, positions)
        self._count_index_passes(nb, row_of, positions)
        self._count_latent_reads(
            "mixed", positions, row_of, tiled=nb - cfg.ragged_packed_slots(nb)
        )
        self._count_attn_work(positions, row_of=row_of)
        self._note_prefill_chunks(pf, chunks, nb)
        self.steps += 1
        if spec:
            self.spec_dispatches += 1
        get_telemetry().decode_batch_rows.labels("mixed").observe(
            len(dec) + len(spec)
        )
        return pending

    def _note_prefill_chunks(self, pf, chunks, nb: int) -> None:
        """After a mixed dispatch went out: its prompt chunks (seq, from,
        n) named on the pages they wrote, counted by share and fill, and
        the pages they covered marked filled."""
        cfg = self.cfg
        ps = cfg.page_size
        # Name this dispatch on the pages its prompt chunks wrote, for
        # the admission record of whoever attaches them later.
        tel = get_telemetry()
        for seq, start_tok, n in chunks:
            self.kv.note_writer(
                seq.page_ids[start_tok // ps : (start_tok + n - 1) // ps + 1],
                f"mixed:nb={nb}:computed={n}:cached={start_tok}",
            )
            # What the row was guaranteed, and what the bucket's empty
            # slots gave it beyond that (_plan_chunks).
            share = min(n, cfg.prefill_chunk)
            tel.prefill_chunk_tokens.labels("share").inc(share)
            tel.prefill_chunk_tokens.labels("fill").inc(n - share)
        # Pages this batch's chunks fully covered are now filled *in
        # dispatch order*: sharers gated on them may dispatch reads
        # from the next iteration on (docs/prefix_sharing.md).
        newly_filled: list[int] = []
        for seq in pf:
            n_full = seq.prefill_sent // ps
            if n_full > seq.fill_marked:
                newly_filled.extend(seq.page_ids[seq.fill_marked : n_full])
                seq.fill_marked = n_full
        if newly_filled:
            self.kv.mark_filled(newly_filled)

    # ---------------------------------------------------------------- chaining
    def _can_chain(self) -> bool:
        """Whether the next window may launch straight from the inflight
        window's device carry, before the host syncs. Requires a stable
        steady state: nothing waiting or prefilling, no cancellations,
        a single (solo, windowed) dispatch, and at least one row the
        host knows will outlive the inflight window (otherwise the
        chained window would compute only discards)."""
        p = self._inflight
        if p is None or not p.solo or not self.cfg.chained_decode:
            return False
        if p.capacity_capped:
            return False  # a capped row's carry is dead but resumable
        if not self._submit_q.empty() or self.sched.waiting:
            return False
        if self._spec is not None:
            # Speculative rows break the chain exactly like capacity-
            # capped rows: a chained window would step token-by-token
            # past positions a verify pass could cover in one dispatch,
            # and the drafter must re-plan from the freshly consumed
            # tokens each round. Rows whose drafting is backed off
            # (lookup keeps missing) chain normally.
            for e in p.rows:
                if e.seq.state is SeqState.ACTIVE and self._spec.wants_draft(
                    e.seq
                ):
                    return False
        stepped_seqs = {id(e.seq) for e in p.rows}
        now = time.time()
        for s in self.sched.slots:
            if s is None:
                continue
            if s.state is SeqState.PREFILL:
                return False
            if s.is_cancelled():
                return False
            if s.deadline_unix and now >= s.deadline_unix:
                return False  # break the chain so the deadline is enforced
            if s.state is SeqState.ACTIVE and id(s) not in stepped_seqs:
                # A row joined (finished prefill) or sat out (stalled)
                # after the chain started; chaining over the old row set
                # would starve it — rebuild a fresh compacted window.
                return False
        # What the inflight dispatch yields a row at the most: a window's
        # steps, or a block model's block.
        K = self.cfg.model.block_length or self.cfg.decode_window
        for e in p.rows:
            sc = e.seq.stop.stop_conditions
            max_tokens = sc.max_tokens or self.cfg.default_max_tokens
            if e.n_valid >= K and max_tokens - e.seq.generated > K:
                return True  # a survivor makes the chained window useful
        return False

    def _dispatch_chained(
        self, pending: _PendingRagged
    ) -> _PendingRagged | None:
        """Dispatch window N+1 over window N's rows using N's on-device
        carry (tokens/positions) as inputs — no host round-trip. The
        host view of these rows lags one window: positions advance by
        exactly ``decode_window`` for every surviving row (a row the
        device stopped carries position -1 and computes into discards
        the host skips at consume). Pages are provisioned one extra
        window ahead; returns None (chain break) when the pool can't
        cover a row."""
        with self._phase("assemble"), self._part("assemble", "build"):
            cfg = self.cfg
            ps, K = cfg.page_size, cfg.decode_window
            nb = pending.nb
            # The rows are the window's before: same stop sets, sampler
            # rows and entries; their pages only grow and every row's
            # gates are set anew. Tokens and positions are the carry's.
            packed, f = self._launch_buffer(
                nb, True, pending.full_sampler, like=pending.packed
            )
            f["chained"][0] = 1
            max_pos, table = f["max_pos"], f["table"]
            eos_gate, budget_gate = f["eos_gate"], f["budget_gate"]
            rows: list[_RaggedRow] = []
            max_pages = 1
            capacity_capped = False
            for e in pending.rows:
                seq, r = e.seq, e.row
                wpos = len(seq.tokens) - 1 + K  # host view + inflight window
                self.sched.ensure_pages_until(seq, wpos + K - 1)
                cap = min(cfg.max_model_len, len(seq.page_ids) * ps) - 1
                if cap < wpos:
                    return None  # pool dry: consume + rebuild instead
                capacity_capped = capacity_capped or cap < wpos + K
                max_pos[r] = cap
                self._table_row(table, r, seq)
                max_pages = max(max_pages, (min(wpos + K, cap + 1) + ps - 1) // ps)
                eos_gate[r], budget_gate[r] = self._stop_gates(
                    seq, seq.generated + K
                )
                rows.append(
                    _RaggedRow(seq, "decode", r, n_valid=min(K, cap - wpos + 1))
                )
        self.steps += K
        get_telemetry().decode_batch_rows.labels("window").observe(len(rows))
        fed = self._window_steps(
            nb, rows, {e.row: len(e.seq.tokens) - 1 + K for e in rows},
            budget_gate,
        )
        return self._launch(  # dynlint: recompile-hazard(chained window reuses the dispatched bucket)
            nb, max_pages, packed, rows, windowed=True,
            full_sampler=pending.full_sampler, want_lp=pending.want_lp,
            carry=(pending.tokens_dev, pending.positions_dev),
            live=sum(e.n_valid for e in rows), solo=True,
            capacity_capped=capacity_capped, routed_rows=fed, rows=len(rows),
            chained=True,
        )

    # ----------------------------------------------------------------- consume
    def _consume_ragged(self, pending: _PendingRagged) -> None:
        if self.cfg.model.block_length:
            self._consume_block(pending)
        elif pending.windowed:
            self._consume_windowed(pending)
        else:
            self._consume_mixed(pending)

    def _emit_row(
        self, seq: Sequence, tokens, lp_of, parts: dict, between=None
    ) -> tuple[int, FinishReason | None]:
        """What the tokens the device produced for a row turn into, for
        every kind of row (a window's, a mixed dispatch's decode or
        speculative row, a finished block): each is appended and counted
        until the authoritative ``check_stop`` gives a reason, the pages
        they completed are registered, and the kept ones are emitted with
        their log-probabilities where the request asked (``lp_of(n_top,
        kept)``: the pack of the first ``kept`` of them; None when the
        dispatch computed none). ``between`` runs after the appends and
        before the registration: a block closes there, so that
        ``register_full_pages`` stops short of what it has not written.
        Adds to ``parts``' ``tokens`` / ``pages`` / ``emit`` clocks
        (``PHASE_PARTS``). Returns how many were kept, and the reason;
        the finish is the consumer's: deferred under a chained successor
        (``_finish_deferred``), at once after a mixed dispatch."""
        clock = time.monotonic
        t0 = clock()
        kept: list[int] = []
        reason = None
        for token in tokens:
            token = int(token)
            kept.append(token)
            seq.tokens.append(token)
            seq.generated += 1
            reason = self.sched.check_stop(seq, token)
            if reason is not None:
                break
        if between is not None:
            between()
        t1 = clock()
        self.sched.register_full_pages(seq)
        self._release_sliding_behind(seq)
        t2 = clock()
        n_top = self._wants_logprobs(seq)
        pack = None
        if n_top is not None and lp_of is not None and kept:
            pack = lp_of(n_top, len(kept))
        if kept:
            now = time.time()
            if seq.last_emit_at:
                get_telemetry().time_between_tokens.observe(
                    max(now - seq.last_emit_at, 0.0) / len(kept)
                )
            seq.last_emit_at = now
        seq.emit(kept, None, pack)
        t3 = clock()
        parts["tokens"] += t1 - t0
        parts["pages"] += t2 - t1
        parts["emit"] += t3 - t2
        return len(kept), reason

    def _release_sliding_behind(self, seq: Sequence) -> None:
        """A cache by kind, in the consume phase's ``pages`` part: the
        row's sliding pages no later query of it can see go back to their
        pool (``Scheduler.release_sliding_behind``), counted."""
        pool = self.kv.sliding
        if pool is None or seq.state is SeqState.FINISHED:
            return
        before = pool.released_total
        self.sched.release_sliding_behind(seq)
        if pool.released_total > before:
            get_telemetry().kv_sliding_pages_released.inc(
                pool.released_total - before
            )

    def _finish_deferred(self, finishes: list[Sequence]) -> None:
        """Finish the rows a window's or a block's consume found stopped
        (``Sequence.pending_finish``). Pages about to be released must
        not have a dispatch in flight over them: the chained successor
        is synced first (its surviving rows' tokens are consumed
        normally; rows with a pending finish are skipped there)."""
        if not finishes:
            return
        succ, self._inflight = self._inflight, None
        if succ is not None:
            self._consume_ragged(succ)
        with self._phase("consume"), self._part("consume", "finish"):
            for seq in finishes:
                reason, seq.pending_finish = seq.pending_finish, None
                self.sched.finish(seq, reason)

    def _consume_windowed(self, pending: _PendingRagged) -> None:
        """Host sync of one decode window: emit kept tokens, run the
        authoritative check_stop, register completed pages. A stop found
        while a chained successor is still in flight defers the finish
        (page release) until that successor is force-consumed — the
        device already parked the row at position -1, so the successor
        writes nothing for it."""
        self._flush_outbox()
        with self._phase("sync"):
            if pending.want_lp:
                sampled, lps, top_ids, top_lps = (
                    np.asarray(y) for y in pending.ys[:4]  # dynlint: sync-point(ragged consume)
                )
            else:
                sampled = np.asarray(pending.ys[0])  # dynlint: sync-point(ragged consume)
            self._count_picks(pending)
        if self.profiler is not None:
            # The np.asarray above was this window's one host sync.
            self.profiler.consume("ragged", pending.dispatched_at, "window")
        with self._phase("consume"):
            finishes: list[Sequence] = []
            wasted = 0
            emitted = 0
            # The parts of this phase alternate row by row: summed from
            # clock reads (``_emit_row``), handed over once (PHASE_PARTS).
            parts = dict.fromkeys(("tokens", "pages", "emit"), 0.0)
            lp_of = None
            for e in pending.rows:
                seq, n_valid, row = e.seq, e.n_valid, e.row
                if seq.state is not SeqState.ACTIVE or seq.pending_finish is not None:
                    wasted += n_valid  # whole window past this row's stop
                    continue
                if pending.want_lp:
                    lp_of = partial(
                        self._lp_pack, lps=lps[:, row],
                        top_ids=top_ids[:, row], top_lps=top_lps[:, row],
                    )
                kept, reason = self._emit_row(
                    seq, sampled[:n_valid, row], lp_of, parts
                )
                wasted += n_valid - kept
                emitted += kept
                if reason is not None:
                    seq.pending_finish = reason
                    finishes.append(seq)
            self.flight.record(
                "consume", dispatch="ragged", tokens=emitted, wasted=wasted
            )
            if wasted:
                self.wasted_steps += wasted
                get_telemetry().decode_wasted_steps.inc(wasted)
            if self.profiler is not None:
                self.profiler.add_parts("consume", **parts)
        self._finish_deferred(finishes)

    def _consume_mixed(self, pending: _PendingRagged) -> None:
        """Host sync of one mixed ragged batch: decode rows emit their
        one stepped token, prompts that completed their last chunk emit
        their first token and join decode, speculative rows emit the
        device-computed accepted prefix + correction (and rewind state
        past rejections), and prompts mid-chunking emit nothing. The
        authoritative host ``check_stop`` gates every emitted token.
        Mixed batches are never chained over, so finishes (and their
        page releases) are safe immediately."""
        spec_on = pending.with_spec
        self._flush_outbox()
        with self._phase("sync"):
            ys = [np.asarray(y) for y in pending.ys]  # dynlint: sync-point(ragged consume)
            self._count_picks(pending)
        tok0 = ys[0]
        i = 1
        if spec_on:
            targets, n_emits = ys[i], ys[i + 1]
            i += 2
        if pending.want_lp:
            lp0, tid0, tlp0 = ys[i], ys[i + 1], ys[i + 2]
            i += 3
            if spec_on:
                s_lps, s_tids, s_tlps = ys[i], ys[i + 1], ys[i + 2]
        if self.profiler is not None:
            self.profiler.consume("ragged", pending.dispatched_at, "mixed")
        with self._phase("consume"):
            self.flight.record(
                "consume", dispatch="ragged", rows=len(pending.rows)
            )
            tel = get_telemetry()
            # As in _consume_windowed: the parts alternate row by row.
            clock = time.monotonic
            parts = dict.fromkeys(
                ("tokens", "pages", "emit", "first_token", "finish"), 0.0
            )
            lp_of = None
            for e in pending.rows:
                seq, r = e.seq, e.row
                if e.kind == "prefill":
                    if not e.completing:
                        if seq.sliding_ids:  # a cache by kind
                            t0 = clock()
                            self._release_sliding_behind(seq)
                            parts["pages"] += clock() - t0
                        continue
                    t0 = clock()
                    n_top = self._wants_logprobs(seq)
                    pack = (
                        self._lp_pack(n_top, 1, lp0[r:], tid0[r:], tlp0[r:])
                        if pending.want_lp and n_top is not None
                        else None
                    )
                    self._finish_first_token(
                        seq, int(tok0[r]), pack, counted=e.counted
                    )
                    self._release_sliding_behind(seq)
                    parts["first_token"] += clock() - t0
                    continue
                if seq.state is not SeqState.ACTIVE or seq.pending_finish is not None:
                    self.wasted_steps += 1
                    tel.decode_wasted_steps.inc()
                    continue
                if e.kind == "decode":
                    if pending.want_lp:
                        lp_of = partial(
                            self._lp_pack, lps=lp0[r:], top_ids=tid0[r:],
                            top_lps=tlp0[r:],
                        )
                    _, reason = self._emit_row(seq, tok0[r : r + 1], lp_of, parts)
                else:
                    # Speculative row: the device already computed the
                    # acceptance (longest draft == target prefix plus the
                    # first correction token — the same rule that gated
                    # the on-device penalty counts); emit those tokens,
                    # rewind state past rejected positions, and feed the
                    # outcome back to the adaptive controller.
                    g, n_emit = e.n_drafts, int(n_emits[r])
                    accepted = n_emit - 1
                    if pending.want_lp:
                        lp_of = partial(
                            self._lp_pack, lps=s_lps[r], top_ids=s_tids[r],
                            top_lps=s_tlps[r],
                        )
                    kept, reason = self._emit_row(
                        seq, targets[r, :n_emit], lp_of, parts
                    )
                    if n_emit - kept:
                        # Tokens past a host-detected stop: computed, discarded.
                        self.wasted_steps += n_emit - kept
                        tel.decode_wasted_steps.inc(n_emit - kept)
                    seq.spec_dispatches += 1
                    seq.spec_draft_tokens += g
                    seq.spec_accepted_tokens += accepted
                    seq.spec_emitted_tokens += kept
                    self.spec_row_dispatches += 1
                    self.spec_draft_tokens += g
                    self.spec_accepted_tokens += accepted
                    self.spec_emitted_tokens += kept
                    tel.spec_draft_tokens.inc(g)
                    tel.spec_accepted_tokens.inc(accepted)
                    tel.spec_tokens_per_dispatch.observe(kept)
                    self.flight.record(
                        "spec_accept",
                        req=seq.request_id,
                        proposed=g,
                        accepted=accepted,
                        emitted=kept,
                    )
                    self._spec.record(seq, proposed=g, accepted=accepted)
                if reason is not None:
                    t0 = clock()
                    self.sched.finish(seq, reason)
                    parts["finish"] += clock() - t0
                elif e.kind == "spec":
                    self._rewind_spec_pages(seq)
            if self.profiler is not None:
                self.profiler.add_parts("consume", **parts)

    # --------------------------------------------------------------- metrics
    def metrics(self) -> dict:
        m = self.sched.metrics()
        # Row-compacted decode counters (docs/engine_perf.md): the
        # tests of tests/test_decode_compaction.py read these; /metrics
        # exposes the prometheus mirrors.
        m["decode_steps"] = self.steps
        m["decode_wasted_steps"] = self.wasted_steps
        m["kv_page_moves"] = self.kv_page_moves
        m["kv_move_dispatches"] = self.kv_move_dispatches
        m["preemptions"] = self.preempted
        m["kv_leases_active"] = self.kv.active_leases
        m["kv_lease_reclaimed_pages"] = self.kv.lease_reclaimed_pages
        # Fleet-wide prefix sharing (docs/prefix_sharing.md): COW
        # copies, the resident-page high-water mark, and the page-
        # granular admission hit breakdown (shared G1 attach / G2
        # restore / fresh miss); the kv_shared_pages gauge rides in via
        # kv.gauges() with the other KV-tier gauges.
        m["kv_cow_copies"] = self.kv.cow_copies
        m["kv_peak_pages"] = self.kv.peak_active_pages
        m["kv_prefix_hits_shared"] = self.kv.prefix_hits["shared"]
        m["kv_prefix_hits_restore"] = self.kv.prefix_hits["restore"]
        m["kv_prefix_hits_persist"] = self.kv.prefix_hits["persist"]
        m["kv_prefix_hits_miss"] = self.kv.prefix_hits["miss"]
        # The ONE ragged variant cache (docs/engine_perf.md "One
        # ragged dispatch") replaces the old per-family mirrors.
        m["compiled_ragged_variants"] = len(self._ragged_fns)
        # Warm-boot provisioning (docs/aot.md): variants prewarm()
        # loaded before first traffic and what the boot cost — the
        # prewarm-smoke gate (`llmctl aot smoke`) reads these.
        m["prewarmed_variants"] = self.prewarmed_variants
        m["prewarm_seconds"] = round(self.prewarm_seconds, 6)
        # Per-dispatch profiler mirror (docs/observability.md): per-kind
        # host-gap / in-flight percentiles over the recent window plus
        # compile attribution — the same numbers the dynamo_dispatch_*
        # prometheus series aggregate, in pullable form for the tests
        # (tests/test_dispatch_profile.py) and `llmctl aot smoke`.
        # decode_window rides along so a per-dispatch time converts to a
        # per-token ITL.
        if self.profiler is not None:
            m["dispatch"] = self.profiler.summary()
        m["decode_window"] = self.cfg.decode_window
        # Speculative decoding (docs/speculative.md): acceptance rate =
        # accepted/draft, tokens-per-dispatch = emitted/dispatches.
        m["spec_dispatches"] = self.spec_dispatches
        # Per-ROW verify participations: tokens-per-dispatch on the
        # per-row basis the sim's service model consumes is
        # emitted / row_dispatches (a batched dispatch over N rows is N
        # row-dispatches — dividing by the device-dispatch count would
        # conflate batch occupancy with speculation speedup).
        m["spec_row_dispatches"] = self.spec_row_dispatches
        m["spec_draft_tokens"] = self.spec_draft_tokens
        m["spec_accepted_tokens"] = self.spec_accepted_tokens
        m["spec_emitted_tokens"] = self.spec_emitted_tokens
        if self.host_pool is not None:
            m["host_cache_resident"] = self.host_pool.resident
            m["host_cache_hits"] = self.host_pool.hits
            m["host_cache_stores"] = self.host_pool.stores
        if self.g3_store is not None:
            # G3 persistent tier (docs/fault_tolerance.md "Durable KV &
            # corruption containment"): occupancy, demote/promote
            # traffic, crash-recovery adoption, and the corruption-
            # containment counters the chaos suites assert on.
            g3 = self.g3_store
            m["kv_store_resident"] = g3.resident
            m["kv_store_adopted"] = g3.adopted
            m["kv_store_demotes"] = g3.stores
            m["kv_store_promotes"] = g3.hits
            m["kv_store_evictions"] = g3.evictions
            m["kv_store_quarantined"] = g3.quarantined
            m["kv_store_torn"] = g3.torn_pages
            m["kv_store_checksum_failures"] = g3.checksum_failures
            m["kv_store_errors"] = g3.store_errors
            m["kv_store_degraded"] = int(g3.degraded)
        # Wire-checksum failures on inbound KV transfers (disagg inject
        # and the reclaim migration sink both decode through the same
        # verifier; a mismatch fails the transfer and the request falls
        # back to local/journal prefill).
        from ..disagg.transfer import wire_checksum_failures

        m["kv_wire_checksum_failures"] = wire_checksum_failures()
        # Predictive KV tiering (docs/engine_perf.md "Predictive KV
        # tiering"): G2→G1 prefetch outcomes and proactive-offload
        # (swap) traffic — tests/test_kv_tiering.py reads these.
        m["kv_prefetch_pages"] = self.prefetch_pages
        m["kv_prefetch_hits"] = self.prefetch_hits
        m["kv_prefetch_late"] = self.prefetch_late
        m["kv_proactive_offloads"] = self.proactive_offloads
        m["kv_swap_ins"] = self.swap_ins
        # Fleet observability plane (docs/observability.md "Fleet
        # plane"): conservation-auditor violations (0 in any healthy
        # run), the config-skew fingerprint, and this process's per-link
        # KV transfer ledger — the exact surface FleetAggregator rolls
        # up across instances.
        m["kv_ledger_violations"] = self.kv_ledger_violations
        m["build_info"] = dict(self._build_info)
        # Request anatomy + workload fingerprint plane
        # (docs/observability.md "Request anatomy"): per-component
        # latency totals over finished requests, the worst-N exemplar
        # ring (`llmctl slow` reads this), the live workload
        # fingerprint, the multi-window SLO burn rates, and the drift
        # score vs the pinned reference (0.0 when none is pinned).
        m["anatomy_totals"] = {
            k: round(v, 6) for k, v in self.anatomy_totals.items()
        }
        m["anatomy_requests"] = self.anatomy_requests
        m["anatomy_slow"] = self.anatomy_ring.snapshot()
        fp = self.fingerprint.snapshot()
        m["workload_fingerprint"] = fp.digest()
        m["workload_requests"] = fp.n
        drift = self.drift_watch.score()
        m["workload_drift_score"] = drift
        get_telemetry().workload_drift_score.set(drift)
        from ..telemetry.fleet import get_transfer_ledger

        m["kv_links"] = get_transfer_ledger().snapshot()
        # Cold-start prior the reclaim triage planner uses on links with
        # no observed transfer yet (docs/fault_tolerance.md).
        m["kv_default_bandwidth_bps"] = (
            get_transfer_ledger().default_bandwidth_bps
        )
        return m
