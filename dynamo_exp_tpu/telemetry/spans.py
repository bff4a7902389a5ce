"""Span emission: one Telemetry hub per process.

Every finished span goes two places at once:

- the JSONL :class:`~dynamo_exp_tpu.recorder.Recorder` (when configured
  via ``configure(trace_file=...)`` or ``DYN_TRACE_FILE``) for offline
  timeline reconstruction (``llmctl trace <id>``);
- Prometheus histograms per stage in ``Telemetry.registry`` — merged
  into the existing ``/metrics`` endpoints by the HTTP service and the
  standalone metrics exporter.

The hub also owns the engine-level gauges (HBM page occupancy, offload
hit rate, scheduler depth, decode batch utilization) that the engine
loop publishes; gauge writes and span emission are thread-safe, so the
engine loop thread can emit directly with an explicit
:class:`~dynamo_exp_tpu.telemetry.context.TraceContext` instead of the
contextvar it doesn't share.
"""

from __future__ import annotations

import atexit
import contextlib
import logging
import os
import socket
import threading
import time
from dataclasses import dataclass, field
from typing import Any

from prometheus_client import CollectorRegistry, Counter, Gauge, Histogram

from .context import TraceContext, attach, current_trace, detach, new_trace
from .fleet import BUILD_INFO_LABELS

logger = logging.getLogger(__name__)

# Stage-duration buckets: KV-router decisions are sub-millisecond while
# a long decode runs tens of seconds — the defaults' 10s cap is too low.
_STAGE_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
    0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0,
)
_HANDOFF_BUCKETS = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
)
_TBT_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
)
_BYTES_BUCKETS = (
    1 << 10, 16 << 10, 256 << 10, 1 << 20, 4 << 20, 16 << 20,
    64 << 20, 256 << 20, 1 << 30,
)
# Per-dispatch timings: a decode window is ms-scale, a cold first
# compile can be tens of seconds — one bucket set spans both tails.
_DISPATCH_BUCKETS = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
    0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
)
# One admission: a page of bookkeeping to a long prompt's hundreds.
_ADMIT_BUCKETS = (
    0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
)

# Engine gauges: metrics()-dict key -> (prometheus name, help).
# jax/_src/dispatch.py's event names (jax 0.9) to the ``stage`` label.
_JAX_COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}

_ENGINE_GAUGES = (
    ("hbm_page_occupancy", "Fraction of device KV pages in use"),
    ("offload_hit_rate", "G2 host-tier hit rate (hits / (hits+misses))"),
    ("num_requests_running", "Sequences actively decoding"),
    ("num_requests_waiting", "Sequences waiting for admission"),
    ("decode_batch_utilization", "ACTIVE decode slots / total slots"),
    ("request_stalled_slots",
     "ACTIVE slots page-limited by the KV pool (idle, or window-capped "
     "but still progressing)"),
)


@dataclass
class Span:
    """One finished stage of a request."""

    stage: str
    trace_id: str
    span_id: str
    parent_span_id: str = ""
    start: float = 0.0  # unix seconds
    end: float = 0.0
    attrs: dict[str, Any] = field(default_factory=dict)

    @property
    def duration_s(self) -> float:
        return max(self.end - self.start, 0.0)

    def to_event(self) -> dict:
        return {
            "type": "span",
            "stage": self.stage,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_span_id": self.parent_span_id,
            "start": self.start,
            "end": self.end,
            "attrs": self.attrs,
        }

    @classmethod
    def from_event(cls, d: dict) -> "Span":
        return cls(
            stage=d.get("stage", "?"),
            trace_id=d.get("trace_id", ""),
            span_id=d.get("span_id", ""),
            parent_span_id=d.get("parent_span_id", ""),
            start=float(d.get("start", 0.0)),
            end=float(d.get("end", 0.0)),
            attrs=d.get("attrs", {}) or {},
        )


class Telemetry:
    """Per-process span sink + unified Prometheus registry."""

    def __init__(self, registry: CollectorRegistry | None = None):
        self.registry = registry or CollectorRegistry()
        self._recorder = None
        self._rec_lock = threading.Lock()
        # Instance identity (docs/observability.md "Fleet plane"):
        # stamped on every emitted span so a disagg request's stitched
        # trace shows *which* instance ran each hop, and carried on the
        # KV transfer wire for the per-link ledger. ``DYN_INSTANCE``
        # names it explicitly (deployments set it per pod); the
        # host:pid default keeps multi-process dev graphs distinct.
        self.instance = (
            os.environ.get("DYN_INSTANCE", "").strip()
            or f"{socket.gethostname()}:{os.getpid()}"
        )
        self.stage_duration = Histogram(
            "dynamo_stage_duration_seconds",
            "Per-stage request latency (one series per pipeline stage)",
            ["stage"],
            buckets=_STAGE_BUCKETS,
            registry=self.registry,
        )
        self.queue_wait = Histogram(
            "dynamo_engine_queue_wait_seconds",
            "Submission-to-admission wait in the engine scheduler",
            buckets=_STAGE_BUCKETS,
            registry=self.registry,
        )
        self.prefill_compute = Histogram(
            "dynamo_engine_prefill_seconds",
            "Admission-to-first-token prefill latency",
            buckets=_STAGE_BUCKETS,
            registry=self.registry,
        )
        self.time_between_tokens = Histogram(
            "dynamo_engine_time_between_tokens_seconds",
            "Decode inter-token latency (per token, window-averaged)",
            buckets=_TBT_BUCKETS,
            registry=self.registry,
        )
        self.kv_transfer_duration = Histogram(
            "dynamo_kv_transfer_duration_seconds",
            "Disagg KV page transfer wall time",
            ["direction"],  # send | recv
            buckets=_STAGE_BUCKETS,
            registry=self.registry,
        )
        self.kv_transfer_bytes = Histogram(
            "dynamo_kv_transfer_bytes",
            "Disagg KV page transfer payload size",
            ["direction"],
            buckets=_BYTES_BUCKETS,
            registry=self.registry,
        )
        self.kv_transfer_total = Counter(
            "dynamo_kv_transfers_total",
            "Disagg KV transfers by direction and outcome",
            ["direction", "outcome"],
            registry=self.registry,
        )
        self.engine_gauges = {
            key: Gauge(f"dynamo_engine_{key}", help_, registry=self.registry)
            for key, help_ in _ENGINE_GAUGES
        }
        # Occupancy-proportional decode (docs/engine_perf.md): how many
        # rows each compiled decode window actually computed, window
        # steps spent past a row's stop point, and KV pages moved by the
        # batched gather/scatter paths. The counters are incremented at
        # the engine loop's consume/move sites (prometheus counters are
        # thread-safe); the gauges ride the engine-gauge publisher.
        self.decode_batch_rows = Histogram(
            "dynamo_decode_batch_rows",
            "Decode rows of each ragged dispatch, by program: a window's "
            "true (uncompacted-slot-free) rows, or the decode and spec "
            "rows a mixed batch carries beside its prefill chunks "
            "(0 when it has none)",
            ["program"],  # window | mixed | block
            buckets=(1, 2, 4, 8, 16, 32, 64, 128),
            registry=self.registry,
        )
        # What a dispatch ran as, by the whole variant key
        # (``aot.lattice.resolve_ragged_key``), so that two askings can
        # be told apart by the programs they took, not only by row
        # counts: label sets are bounded by the compile lattice. Host
        # counts from the builders; nothing is read from the device.
        self.ragged_dispatches = Counter(
            "dynamo_ragged_dispatch_total",
            "Ragged dispatches by program variant: token/row bucket, "
            "XLA page bound ('kernel' where the Pallas kernel reads "
            "true lengths), log-probability and sampler variants, and "
            "whether a window was chained onto the one before it",
            ["program", "nb", "pages", "want_lp", "full_sampler", "chained"],
            registry=self.registry,
        )
        self.ragged_slots = Counter(
            "dynamo_ragged_slots_total",
            "Slots of ragged dispatches by program and kind: live (a "
            "real query token; a window counts each row's valid steps) "
            "or pad (what the token bucket, a span's tile and a row "
            "past its stop add: every one a row of every projection "
            "and feed-forward). Every model; dynamo_moe_rows_total is "
            "its sum over programs for a routed one",
            ["program", "kind"],  # window | mixed | block; live | pad
            registry=self.registry,
        )
        self.attn_query_slots = Counter(
            "dynamo_attn_query_slots_total",
            "Live slots of the tiled part of mixed dispatches by how the "
            "attention kernel reads their row's context: merged (the "
            "slot's query block belongs to one row and walks its pages "
            "once for every tile in it) or tile (a block of several rows, "
            "or a model whose block is one tile: the pages once a tile). "
            "Counted by the dispatch builders with the kernel's own rule "
            "(ops/ragged_attention.py: query_block, or under a learned "
            "selection over grouped-query pages selected_query_block; a "
            "latent pool under a selection stays a tile); a kernel engine "
            "only",
            ["pass"],  # merged | tile
            registry=self.registry,
        )
        self.attn_one_query_rows = Counter(
            "dynamo_attn_one_query_rows_total",
            "Live rows of the attention kernel's one-query call (every step "
            "of a decode window, the packed tail of a mixed dispatch) by how "
            "their first chunk of context is fetched: behind (started while "
            "the row before it in its grid cell was still multiplied) or "
            "exposed (the first live row of a cell, or a model whose cell is "
            "one row: nothing hides the fetch). Counted by the dispatch "
            "builders with the kernel's own rule (ops/ragged_attention.py::"
            "one_query_chain; a window's rows by the steps the host foresees); "
            "a kernel engine only",
            ["fetch"],  # behind | exposed
            registry=self.registry,
        )
        self.attn_one_query_heads = Counter(
            "dynamo_attn_one_query_heads_total",
            "Live rows of the attention kernel's one-query call by the "
            "schedule a chunk of their call's pass is worked in: together "
            "(all its KV heads' score products, ONE softmax update over "
            "their stacked rows, all their value products, the running "
            "state carried as values) or by_head (head after head through "
            "the state's references: one KV head, a latent pool's absorbed "
            "form, a cell of one row as under a learned selection or in a "
            "stream of one slot). Counted where "
            "dynamo_attn_one_query_rows_total is, with the kernel's own rule "
            "(ops/ragged_attention.py::one_query_schedule, from the call's "
            "shapes; a model whose forms of attention differ in their KV "
            "heads counts each form by its share of the layers); a kernel "
            "engine only",
            ["schedule"],  # together | by_head
            registry=self.registry,
        )
        self.attn_tile_rows = Counter(
            "dynamo_attn_tile_rows_total",
            "Live tiles of a block program's attention calls (a block "
            "model's pure-decode dispatch: every pass a call of one tile of "
            "block_length queries a row, the opening pass of two), by how "
            "the tile's first chunk of context is fetched (behind: started "
            "while the tile before it in its grid cell was still "
            "multiplied; exposed: the first live tile of a cell, or a call "
            "whose cell is one tile) and by the schedule a chunk of its "
            "pass is worked in (together: all the KV heads' products side "
            "by side, one softmax update over their stacked score rows, "
            "the running state carried as values; by_head: head after head "
            "through the state's references). Counted at the dispatch's "
            "consume, once its passes are known, with the kernel's own "
            "rules (ops/ragged_attention.py::one_query_chain, "
            "one_query_block, one_query_schedule; each form of attention "
            "by its share of the layers); a kernel engine only",
            ["fetch", "schedule"],  # behind | exposed; together | by_head
            registry=self.registry,
        )
        self.launch_transfers = Counter(
            "dynamo_launch_transfers_total",
            "Host arrays handed to the device by ragged launches, by "
            "program (TPUEngine._put_launch, the whole of launch.h2d). "
            "Over dynamo_ragged_dispatch_total it is transfers a launch: "
            "1 since a launch's fields travel as one packed buffer "
            "(aot/launch.py); 6-19 field by field before",
            ["program"],  # window | mixed
            registry=self.registry,
        )
        self.emit_postings = Counter(
            "dynamo_emit_postings_total",
            "call_soon_threadsafe calls that handed emitted tuples to an "
            "asyncio loop: one a dispatch and loop, made where the engine "
            "loop is about to wait (TPUEngine._flush_outbox: before a "
            "sync, a park, at the loop's end), and one for each emit "
            "made off the loop thread",
            registry=self.registry,
        )
        self.emit_items = Counter(
            "dynamo_emit_items_total",
            "Emitted tuples (tokens, finish reason, log-probabilities, "
            "stamp) those calls carried; over dynamo_emit_postings_total "
            "it is tuples a posting: the rows of a dispatch, 1 where "
            "every emit is posted by itself",
            registry=self.registry,
        )
        self.prefill_chunk_tokens = Counter(
            "dynamo_prefill_chunk_tokens_total",
            "Prompt tokens mixed dispatches carried by kind: share (up "
            "to prefill_chunk a prefilling row, what every row is "
            "guaranteed a dispatch) or fill (what a row took beyond "
            "that from slots its dispatch's token bucket would have "
            "left empty: engine._plan_chunks). fill / all says how "
            "often the filling engages",
            ["kind"],  # share | fill
            registry=self.registry,
        )
        self.moe_rows = Counter(
            "dynamo_moe_rows_total",
            "Rows of routed-expert models' dispatches by kind: live "
            "(a real query token; a window counts each row's valid "
            "steps) or pad (bucket and tile padding, which "
            "ops/moe.py::moe_ffn keeps out of every expert's group)",
            ["kind"],  # live | pad
            registry=self.registry,
        )
        self.moe_picks = Counter(
            "dynamo_moe_picks_total",
            "Expert picks (a live token's chosen experts, every routed "
            "layer) of a model that holds a share of the experts its "
            "router scores, or all of them with count_expert_reads "
            "(KeyeVL2: elsewhere stays 0), by where the expert lies: held "
            "(here: the row is computed) or elsewhere (another rank's: "
            "nothing is added here). held is counted on the device by the "
            "programs (ops/moe.py::moe_ffn's tally), the total by the builders",
            ["where"],  # held | elsewhere
            registry=self.registry,
        )
        self.moe_held_expert_reads = Counter(
            "dynamo_moe_held_expert_reads_total",
            "Held experts that had a live pick, summed over routed layers "
            "and dispatches (a window: over its steps too), by program: the "
            "expert matrices (three each) the grouped matmul has to fetch. "
            "Counted on the device beside dynamo_moe_picks_total{held}; a "
            "router that crowds its picks reaches far fewer than are held",
            ["program"],  # window | mixed
            registry=self.registry,
        )
        self.mla_query_tokens = Counter(
            "dynamo_mla_query_tokens_total",
            "Query tokens through multi-head latent attention by program "
            "and by the form their attention took: absorbed (scores "
            "against the latent rows, 128 heads over one latent head) or "
            "expanded (per-head K and V built from the latents inside the "
            "kernel; a row with many queries in a mixed dispatch's tiled "
            "part). A layer's: every attention layer sees as many",
            ["program", "form"],  # window | mixed; absorbed | expanded
            registry=self.registry,
        )
        self.mla_attended_pairs = Counter(
            "dynamo_mla_attended_pairs_total",
            "Query-key pairs latent attention attended, by program: a "
            "query at position p sees p + 1 keys, or the index_topk of "
            "them its selection kept where the model has an indexer (the "
            "published work, whatever form executes it). A layer's and a "
            "head's: times layers and heads for the model's",
            ["program"],
            registry=self.registry,
        )
        self.dsa_index_pairs = Counter(
            "dynamo_dsa_index_pairs_total",
            "Query-context pairs a learned-sparse-attention model's indexer "
            "is asked to score, by program: every causal pair, p + 1 index "
            "keys a query at position p (a stream part none of whose queries "
            "sees more than index_topk skips the scoring and is counted all "
            "the same). Latent or grouped-query attention alike (glm_moe_dsa, "
            "KeyeVL2). A layer's: times layers, and index heads for products",
            ["program"],
            registry=self.registry,
        )
        self.dsa_index_slots = Counter(
            "dynamo_dsa_index_slots_total",
            "Live slots of the tiled part of a learned-sparse-attention "
            "model's mixed dispatches that the indexer's scores kernel served, "
            "by how it read their row's index keys: block (the slot's block "
            "of ops/sparse_index.py::index_block slots belongs to one row and "
            "walks its keys once for every tile in it) or tile (a block of "
            "several rows: the keys once a tile). Counted by the dispatch "
            "builders with the kernel's own rule (ops/ragged_attention.py::"
            "block_plan at that block), a stream part at a time as the "
            "program calls the kernel; a part none of whose queries sees more "
            "than index_topk skips the kernel and counts nothing. A layer's; "
            "a kernel engine only",
            ["pass"],  # block | tile
            registry=self.registry,
        )
        self.dsa_selected_pairs = Counter(
            "dynamo_dsa_selected_pairs_total",
            "Query-context pairs attended after the selection, by program: "
            "min(p + 1, index_topk) a query at position p, whichever pool the "
            "masked read walks (latent rows, or grouped-query K and V pages: "
            "dynamo_attn_key_positions_total{kind=\"full\"} then keeps the "
            "p + 1 a query MAY see). A layer's",
            ["program"],
            registry=self.registry,
        )
        self.dsa_query_tokens = Counter(
            "dynamo_dsa_query_tokens_total",
            "Query tokens of a model with an indexer by program and by "
            "whether their context exceeded index_topk (selecting: the "
            "selection dropped something) or not (all: every position "
            "kept). A layer's",
            ["program", "selecting"],  # window | mixed; selecting | all
            registry=self.registry,
        )
        self.mla_context_tokens = Counter(
            "dynamo_mla_context_tokens_total",
            "Context tokens latent attention had to read, by program: a "
            "row's context once a dispatch (a window: once a step). A "
            "layer's; times the pool's bytes a token and layer for bytes",
            ["program"],
            registry=self.registry,
        )
        self.moe_dispatches = Counter(
            "dynamo_moe_dispatch_total",
            "Ragged dispatches of routed-expert models by how the expert "
            "matmuls read their weights: inplace (ops/expert_gmm.py reads "
            "the layer out of the stacked arrays) or sliced (a shape or "
            "a backend the kernel cannot take: a copy of the layer goes "
            "to ragged_dot)",
            ["experts"],  # inplace | sliced
            registry=self.registry,
        )
        # Generation by diffusion over blocks (engine/block.py; all zero
        # for a model whose step is a token). Counted at the consume of
        # each dispatch from what the device reports.
        self.diffusion_blocks = Counter(
            "dynamo_diffusion_blocks_total",
            "Blocks a block-diffusion model finished: every position "
            "fixed and the block's tokens emitted",
            registry=self.registry,
        )
        self.diffusion_block_writes = Counter(
            "dynamo_diffusion_block_writes_total",
            "Finished blocks by what put their final K/V into the pages: "
            "own_pass (a pass that only writes: the mixed program's), "
            "next_first_pass (they rode the opening pass of the block "
            "program's next dispatch over the row) or never (the row "
            "left its pages first: nothing reads them). Each block once, "
            "and the three add up to dynamo_diffusion_blocks_total",
            ["by"],  # own_pass | next_first_pass | never
            registry=self.registry,
        )
        self.diffusion_passes = Counter(
            "dynamo_diffusion_passes_total",
            "Passes over a row's block by kind: denoise (the confidence "
            "rule fixes masked positions; the block program's opening "
            "pass, which also writes the block before, is one) or write "
            "(a pass of its own that fixes nothing and leaves a block's "
            "final K/V: the mixed program's). denoise / blocks is the "
            "passes a block took; write / all the share that fixes nothing",
            ["kind"],  # denoise | write
            registry=self.registry,
        )
        # Sequence state beside the pages (a model with layers that are
        # not attention: a short convolution's products, a selective
        # scan's state; all zero for any other model). Host counts from
        # the page manager and the admission path; every part of a state
        # moves together, so one series covers them.
        self.seq_state_slots = Gauge(
            "dynamo_seq_state_slots_in_use",
            "Entries of the sequence-state pool bound to a sequence "
            "(one a decode slot holder; 0 for a model without such state)",
            registry=self.registry,
        )
        self.seq_state_pool_bytes = Gauge(
            "dynamo_seq_state_pool_bytes",
            "Device bytes of the sequence-state pool, all parts and "
            "entries (live and snapshots); 0 for a model without such state",
            registry=self.registry,
        )
        self.ssm_scan_tokens = Counter(
            "dynamo_ssm_scan_tokens_total",
            "Tokens a dispatch put through the selective scan of each Mamba "
            "layer (its live slots: dynamo_ragged_slots_total{kind=live} "
            "for a model with such layers), by program: mixed is prompt "
            "chunks and the decode rows beside them, window is decode alone",
            ["program"],  # window | mixed
            registry=self.registry,
        )
        self.mamba2_tokens = Counter(
            "dynamo_mamba2_tokens_total",
            "Tokens a dispatch put through the recurrence of each Mamba-2 "
            "layer (its live slots, as dynamo_ssm_scan_tokens_total counts "
            "a selective scan's), by program: mixed is prompt chunks (the "
            "chunked form) and the decode rows beside them, window is "
            "decode alone (the one-token step)",
            ["program"],  # window | mixed
            registry=self.registry,
        )
        self.linear_attn_tokens = Counter(
            "dynamo_linear_attn_tokens_total",
            "Tokens a dispatch put through the gated delta rule of each "
            "linear-attention layer (its live slots, as "
            "dynamo_ssm_scan_tokens_total counts a selective scan's), by "
            "program: mixed is prompt chunks and the decode rows beside "
            "them, window is decode alone",
            ["program"],  # window | mixed
            registry=self.registry,
        )
        self.linear_attn_step_rows = Counter(
            "dynamo_linear_attn_step_rows_total",
            "Of dynamo_linear_attn_tokens_total, the tokens that took the "
            "delta rule's one-token step (on the chip the in-place kernel "
            "gated_delta_step, a row's state moved once each way): every "
            "live slot of a window, the decode rows in the packed tail of a "
            "mixed stream; 0 for the mixed streams of an engine on the "
            "reference attention, which the oracle walks whole",
            ["program"],  # window | mixed
            registry=self.registry,
        )
        self.seq_state_restores = Counter(
            "dynamo_seq_state_restores_total",
            "Sequence states copied from a kept snapshot into an admitted "
            "sequence's entry, by why the sequence had tokens cached: "
            "prefix (another request's pages) or resume (its own, after a "
            "preemption)",
            ["cause"],  # prefix | resume
            registry=self.registry,
        )
        self.seq_state_prefix_hits = Counter(
            "dynamo_seq_state_prefix_hits_total",
            "Admissions of a model with sequence state whose prompt matched "
            "cached pages, by what the match came to: restored (taken as "
            "deep as it went), shortened (to the deepest block boundary a "
            "state was kept for) or not_taken (none was)",
            ["outcome"],  # restored | shortened | not_taken
            registry=self.registry,
        )
        self.decode_wasted_steps = Counter(
            "dynamo_decode_wasted_steps_total",
            "Decode window steps computed for a row past its stop point",
            registry=self.registry,
        )
        self.kv_page_moves = Counter(
            "dynamo_kv_page_moves_total",
            "KV pages moved by batched gather/scatter, by operation",
            ["op"],  # extract | inject | upload | offload | cow
            registry=self.registry,
        )
        # Fleet-wide prefix sharing (docs/prefix_sharing.md): pages
        # resident once but attached by several live sequences, copies
        # made when a sharer's first divergent write hits a shared page,
        # and the page-granular admission hit breakdown.
        self.kv_shared_pages = Gauge(
            "dynamo_kv_shared_pages",
            "Device KV pages currently attached by more than one holder",
            registry=self.registry,
        )
        self.kv_cow_copies = Counter(
            "dynamo_kv_cow_copies_total",
            "Shared KV pages duplicated copy-on-write before a "
            "divergent write",
            registry=self.registry,
        )
        self.kv_prefix_hits = Counter(
            "dynamo_kv_prefix_hits_total",
            "Prompt pages at admission by source: shared (G1 attach, "
            "refcounted), restore (G2 host-tier upload), persist (G3 "
            "persistent-store restore — the restart re-attachment "
            "path), miss (fresh prefill)",
            ["kind"],  # shared | restore | persist | miss
            registry=self.registry,
        )
        # Predictive KV tiering (docs/engine_perf.md "Predictive KV
        # tiering"): G2 tier occupancy, prefetch outcomes, and
        # proactive-offload (swap) traffic.
        self.kv_host_pages = Gauge(
            "dynamo_kv_host_pages",
            "G2 host-tier KV pages currently resident (HostKvPool "
            "occupancy — fleet views read host-tier pressure here)",
            registry=self.registry,
        )
        # G3 persistent tier (docs/fault_tolerance.md "Durable KV &
        # corruption containment"): occupancy plus the corruption-
        # containment counters (checksum failures by path, quarantines).
        self.kv_store_pages = Gauge(
            "dynamo_kv_store_pages",
            "G3 persistent-store KV pages currently resident "
            "(PersistentKvStore occupancy)",
            registry=self.registry,
        )
        self.kv_checksum_failures = Counter(
            "dynamo_kv_checksum_failures_total",
            "KV pages that failed checksum verification on a restore "
            "path: store (G3 fetch) or wire (disagg inject / reclaim "
            "migration sink) — each one was quarantined or failed the "
            "transfer, never served",
            ["path"],  # store | wire
            registry=self.registry,
        )
        self.kv_quarantined = Counter(
            "dynamo_kv_quarantined_total",
            "G3 store pages moved to quarantine after failing "
            "verification (the entry is barred from re-adoption; the "
            "block re-prefills from the journal, token-identically)",
            registry=self.registry,
        )
        self.kv_prefetch_pages = Counter(
            "dynamo_kv_prefetch_pages_total",
            "G2→G1 prefetch outcomes: restored (pages injected ahead "
            "of admission), hit (restored pages the target admission "
            "attached), late (fetch still in flight when the target "
            "admitted), dropped (copy stream saturated)",
            ["outcome"],  # restored | hit | late | dropped
            registry=self.registry,
        )
        self.kv_proactive_offloads = Counter(
            "dynamo_kv_proactive_offloads_total",
            "Rows whose cold refcount-1 pages were proactively swapped "
            "to the host tier under KV pressure (preemption avoided)",
            registry=self.registry,
        )
        self.kv_swap_ins = Counter(
            "dynamo_kv_swap_ins_total",
            "Proactively offloaded rows restored to full residency "
            "(token-identical resume from host-tier bytes)",
            registry=self.registry,
        )
        # Fault-tolerance counters (docs/fault_tolerance.md): retries and
        # failovers on the request plane, circuit-breaker churn, requests
        # abandoned at their deadline per stage, and drain lifecycle.
        self.request_retries = Counter(
            "dynamo_request_retries_total",
            "Request-plane retries after connection/stream-start failures",
            ["reason"],  # connect | stream_start
            registry=self.registry,
        )
        self.breaker_transitions = Counter(
            "dynamo_circuit_breaker_transitions_total",
            "Circuit-breaker state transitions across all tracked targets",
            ["state"],  # open | half_open | closed
            registry=self.registry,
        )
        self.deadline_exceeded = Counter(
            "dynamo_deadline_exceeded_total",
            "Requests abandoned because their end-to-end deadline expired",
            ["stage"],  # router | request_plane | prefill_queue | decode
            registry=self.registry,
        )
        self.drain_events = Counter(
            "dynamo_drain_events_total",
            "Graceful-drain lifecycle events on served instances",
            ["event"],  # started | completed
            registry=self.registry,
        )
        # Resumable streams (docs/fault_tolerance.md): mid-stream
        # failovers by cause, duplicate tokens trimmed by sequence-index
        # dedup, and HBM pages reclaimed from orphaned handoff leases.
        self.request_recoveries = Counter(
            "dynamo_request_recoveries_total",
            "Mid-stream failovers resumed on a different instance",
            ["reason"],  # stream_drop | drain | reclaim
            registry=self.registry,
        )
        self.tokens_deduplicated = Counter(
            "dynamo_tokens_deduplicated_total",
            "Duplicate-index tokens dropped while splicing a resumed stream",
            registry=self.registry,
        )
        self.kv_lease_reclaims = Counter(
            "dynamo_kv_lease_reclaims_total",
            "KV pages reclaimed from expired disagg handoff leases",
            registry=self.registry,
        )
        # Spot reclamation (docs/fault_tolerance.md "Spot reclamation &
        # live migration"): the reclaim plane's lifecycle — notice
        # received, per-sequence triage outcomes (live migration vs
        # journal failover, with deadline degradations counted
        # separately), and the KV pages actually shipped to survivors.
        self.reclaim_events = Counter(
            "dynamo_reclaim_events_total",
            "Spot-reclamation lifecycle events: notice (metadata "
            "flipped to reclaiming), migrated / failover (per-sequence "
            "triage outcomes), deadline_degraded (a planned migration "
            "fell back to journal failover at the grace deadline), "
            "completed (triage finished inside the grace window)",
            ["event"],  # notice|migrated|failover|deadline_degraded|completed
            registry=self.registry,
        )
        self.reclaim_migrated_pages = Counter(
            "dynamo_reclaim_migrated_pages_total",
            "KV pages live-migrated to survivor instances during "
            "spot reclamation",
            registry=self.registry,
        )
        self.reclaim_triage_seconds = Histogram(
            "dynamo_reclaim_triage_seconds",
            "Wall time of one reclaim triage pass (notice to last "
            "migration confirm) — must beat the grace window",
            buckets=_STAGE_BUCKETS,
            registry=self.registry,
        )
        # Overload protection (docs/fault_tolerance.md "Overload
        # protection"): edge admission sheds, the edge's live in-flight
        # count, and engine-side KV-pressure preemptions.
        self.requests_shed = Counter(
            "dynamo_requests_shed_total",
            "Requests refused by edge admission control, by priority "
            "class and HTTP status",
            ["priority", "code"],  # low|normal|high x 429|503
            registry=self.registry,
        )
        self.admission_inflight = Gauge(
            "dynamo_admission_inflight",
            "Requests currently admitted (in flight) at the HTTP edge",
            registry=self.registry,
        )
        self.preemptions = Counter(
            "dynamo_preemptions_total",
            "Engine sequences preempted and requeued as deterministic "
            "continuations, by cause",
            ["reason"],  # kv_pressure
            registry=self.registry,
        )
        # Speculative decoding (docs/speculative.md): draft tokens
        # proposed by the drafter, the prefix the target-model verify
        # pass accepted, and how many tokens each verify dispatch
        # delivered (accepted prefix + the correction token).
        self.spec_draft_tokens = Counter(
            "dynamo_spec_draft_tokens_total",
            "Draft tokens proposed to the speculative verify pass",
            registry=self.registry,
        )
        self.spec_accepted_tokens = Counter(
            "dynamo_spec_accepted_tokens_total",
            "Draft tokens accepted by the target-model verify pass",
            registry=self.registry,
        )
        self.spec_tokens_per_dispatch = Histogram(
            "dynamo_spec_tokens_per_dispatch",
            "Tokens emitted per speculative verify dispatch "
            "(accepted prefix + correction token)",
            buckets=(1, 2, 3, 4, 6, 8, 12, 16),
            registry=self.registry,
        )
        # Per-dispatch device profiling (docs/observability.md
        # "Per-dispatch device profiling"): every device dispatch —
        # prefill chunk, decode window, spec verify, KV gather/scatter
        # move, eviction offload batch — split into in-flight time
        # (dispatch -> existing host sync) and the host gap before it
        # (previous consume -> next dispatch), plus compiled-variant
        # cache behavior. Fed by telemetry.dispatch.DispatchProfiler
        # from the engine loop's existing timestamps: no added syncs.
        self.dispatch_seconds = Histogram(
            "dynamo_dispatch_seconds",
            "Device dispatch in-flight time (dispatch to the existing "
            "host sync), by dispatch kind and, for ragged dispatches, "
            "compiled program",
            # ragged | kv_move | offload x window | mixed | - (see
            # telemetry/dispatch.py)
            ["kind", "program"],
            buckets=_DISPATCH_BUCKETS,
            registry=self.registry,
        )
        self.host_gap_seconds = Histogram(
            "dynamo_host_gap_seconds",
            "Host gap between consuming a dispatch and issuing the "
            "kind's next one (~0 in the overlapped steady state)",
            ["kind", "program"],
            buckets=_DISPATCH_BUCKETS,
            registry=self.registry,
        )
        self.compile_seconds = Histogram(
            "dynamo_compile_seconds",
            "First-call duration of a fresh compiled variant "
            "(trace + compile + program load), by dispatch kind",
            ["kind"],
            buckets=_STAGE_BUCKETS,
            registry=self.registry,
        )
        self.compile_cache_misses = Counter(
            "dynamo_compile_cache_misses_total",
            "Compiled-variant cache misses, by dispatch kind (steady "
            "state should stop incrementing — see the recompile guard; "
            "a warm boot starts flat at 0, docs/aot.md)",
            ["kind"],
            registry=self.registry,
        )
        # Warm-boot provisioning (docs/aot.md): how long prewarm() took
        # to load/compile the lattice before first traffic, and how
        # many variants it covered per family. Prewarm work is recorded
        # HERE, never as compile-cache misses — miss counters measure
        # steady-state flatness, which a warm boot holds from the very
        # first dispatch.
        self.prewarm_seconds = Gauge(
            "dynamo_prewarm_seconds",
            "Wall time of the engine's warm-boot prewarm (0 = cold boot)",
            registry=self.registry,
        )
        self.prewarm_variants = Counter(
            "dynamo_prewarm_variants_total",
            "Compiled variants loaded/built by warm-boot prewarm, by "
            "family",
            ["kind"],  # ragged | move
            registry=self.registry,
        )
        # SLO/goodput attribution (docs/observability.md "SLO
        # attribution & goodput"): per-request TTFT/ITL measured at the
        # edge against --slo-ttft-ms/--slo-itl-ms targets. Shared code
        # path with the cluster simulator's SimReport counts
        # (telemetry/slo.py SloAttribution).
        self.slo_violations = Counter(
            "dynamo_slo_violations_total",
            "Completed requests that breached a latency SLO target",
            ["slo", "priority"],  # ttft|itl x low|normal|high
            registry=self.registry,
        )
        self.goodput_requests = Counter(
            "dynamo_goodput_requests_total",
            "Completed requests that met every configured SLO target",
            ["priority"],
            registry=self.registry,
        )
        # Request anatomy + workload fingerprint plane
        # (docs/observability.md "Request anatomy"): per-component
        # latency totals from the engine's per-request decomposition,
        # the multi-window SLO burn rate, and the live-vs-pinned
        # workload drift score.
        self.request_seconds = Counter(
            "dynamo_request_seconds",
            "Request wall time decomposed by anatomy component "
            "(telemetry/anatomy.py COMPONENTS) — summed across "
            "finished requests",
            ["component"],
            registry=self.registry,
        )
        self.slo_burn_rate = Gauge(
            "dynamo_slo_burn_rate",
            "Fraction of recent requests breaching each SLO axis, per "
            "burn window (fast=last 64, slow=last 1024 completed "
            "requests)",
            ["slo", "window"],
            registry=self.registry,
        )
        self.workload_drift_score = Gauge(
            "dynamo_workload_drift_score",
            "Normalized [0,1] distance between the live workload "
            "fingerprint and the pinned reference (DYN_WORKLOAD_REF); "
            "0 when no reference is pinned",
            registry=self.registry,
        )
        self.config_swaps = Counter(
            "dynamo_config_swaps",
            "Planner config-catalog swaps: drift past the alert "
            "threshold moved the fleet onto a different pre-validated "
            "tuned config (docs/tuning.md)",
            registry=self.registry,
        )
        # Fleet observability plane (docs/observability.md "Fleet
        # plane"): the KV conservation auditor's violation counter (0 in
        # any healthy run — a nonzero value names a page-accounting bug,
        # with the full audit in the flight dump it triggers), the
        # per-link KV transfer ledger mirrors, and the build-info
        # config-skew fingerprint.
        self.kv_ledger_violations = Counter(
            "dynamo_kv_ledger_violations_total",
            "KV page-ledger conservation violations detected by the "
            "in-loop auditor (every page exactly one of free/parked/"
            "active/leased/shared, refcount totals conserved)",
            registry=self.registry,
        )
        self.kv_link_bytes = Counter(
            "dynamo_kv_link_bytes_total",
            "KV lease-transfer payload bytes per (src, dst) instance "
            "link, as observed by this process's transfer ledger",
            ["src", "dst"],
            registry=self.registry,
        )
        self.kv_link_transfers = Counter(
            "dynamo_kv_link_transfers_total",
            "KV lease transfers observed per (src, dst) instance link",
            ["src", "dst"],
            registry=self.registry,
        )
        self.kv_link_bandwidth = Gauge(
            "dynamo_kv_link_bandwidth_bytes_per_s",
            "Online per-link bandwidth estimate (EWMA over observed "
            "extract->ack lease-transfer durations) — the input surface "
            "for topology-aware decode-instance selection",
            ["src", "dst"],
            registry=self.registry,
        )
        # Engine phases and the device trace (docs/observability.md):
        # what the engine loop's thread spent its time on, by phase
        # (fed by DispatchProfiler.phase, the same instrumentation that
        # writes dyn/<phase> events into a profiler trace); the KV
        # pools' geometry; JAX's own trace/lower/compile events, which
        # see the re-lowering of an already-built program that
        # dynamo_compile_cache_misses_total cannot; and prompt tokens
        # by whether admission found them cached.
        self.engine_phase_seconds = Counter(
            "dynamo_engine_phase_seconds_total",
            "Host seconds of the engine loop's thread, by loop phase "
            "(sync = waiting for a dispatch's outputs, park = no work)",
            ["phase"],  # telemetry/dispatch.py PHASES
            registry=self.registry,
        )
        self.engine_phase_cpu_seconds = Counter(
            "dynamo_engine_phase_cpu_seconds_total",
            "CPU seconds of the engine loop's thread, by loop phase: a "
            "phase's host seconds less these it did not run (waiting "
            "for the GIL, a lock or a system call, or descheduled)",
            ["phase"],
            registry=self.registry,
        )
        self.engine_phase_part_seconds = Counter(
            "dynamo_engine_phase_part_seconds_total",
            "Host seconds of the engine loop's thread inside a phase, "
            "by named part; a phase's parts (other = what the named "
            "ones leave) sum to its dynamo_engine_phase_seconds_total",
            ["phase", "part"],  # telemetry/dispatch.py PHASE_PARTS
            registry=self.registry,
        )
        # The life of an admission (docs/observability.md): counted at
        # the boundaries the parts of phase ``admit`` are timed at.
        self.admit_attempts = Counter(
            "dynamo_admit_attempts_total",
            "Turns of Scheduler.admit_next's loop by what they came to: "
            "admitted, no_slot (every decode slot taken), no_pages (the "
            "pool could not hold the prompt: it was hashed and matched "
            "for nothing), rejected (can never be served), dropped (a "
            "cancelled or expired head)",
            ["outcome"],  # engine/scheduler.py ADMIT_OUTCOMES
            registry=self.registry,
        )
        self.admit_seconds = Histogram(
            "dynamo_admit_seconds",
            "One admitted sequence, from the entry of the admit_next "
            "call that admitted it to the end of its flight record",
            buckets=_ADMIT_BUCKETS,
            registry=self.registry,
        )
        self.kv_pages_taken = Counter(
            "dynamo_kv_pages_taken_total",
            "Device pages handed out by the page manager, by source: "
            "free (the free list) or evicted (a parked page reclaimed, "
            "its registration dropped from the prefix index)",
            ["source"],  # free | evicted
            registry=self.registry,
        )
        self.emit_handoff = Histogram(
            "dynamo_engine_emit_handoff_seconds",
            "From the engine loop posting a request's tokens to the "
            "asyncio thread taking them off the request's queue",
            buckets=_HANDOFF_BUCKETS,
            registry=self.registry,
        )
        # What the device waits behind while the loop sits in ``sync``
        # (docs/observability.md "Off the loop thread"): a chunk's way
        # through the HTTP thread, collector pauses, whole-process
        # stalls, and a launch from ``_launch``'s entry. Fed by
        # telemetry/dispatch.py HttpChunkClock and telemetry/flight.py
        # HostWitness; each also writes a ``dynhttp/`` / ``dyngc/`` /
        # ``dynstall/`` event into a profiler trace while one is on.
        self.http_chunk_seconds = Counter(
            "dynamo_http_chunk_seconds_total",
            "Host seconds the HTTP thread spent on streamed chunks, by "
            "part: detokenize (from taking the engine's tuple off the "
            "request's queue to the OpenAI chunk), frame (validation, "
            "model_dump, the SSE frame), write (the response write). The "
            "parts sum to the handling of dynamo_http_chunks_total",
            ["part"],  # telemetry/dispatch.py HTTP_PARTS
            registry=self.registry,
        )
        self.http_chunk_cpu_seconds = Counter(
            "dynamo_http_chunk_cpu_seconds_total",
            "CPU seconds of the HTTP thread inside the same parts: a "
            "part's host seconds less these it did not run (it waited "
            "for the GIL or was descheduled)",
            ["part"],
            registry=self.registry,
        )
        self.http_chunks = Counter(
            "dynamo_http_chunks_total",
            "Engine tuples the HTTP thread took off a request's queue "
            "and handled (a row's tokens of one dispatch, or its finish)",
            registry=self.registry,
        )
        self.gc_pause_seconds = Counter(
            "dynamo_gc_pause_seconds_total",
            "Seconds inside the cyclic garbage collector, by generation "
            "(every thread waits: the collector holds the GIL)",
            ["generation"],  # 0 | 1 | 2
            registry=self.registry,
        )
        self.gc_collections = Counter(
            "dynamo_gc_collections_total",
            "Collections of the cyclic garbage collector, by generation",
            ["generation"],
            registry=self.registry,
        )
        self.gc_pause_max = Gauge(
            "dynamo_gc_pause_max_seconds",
            "The longest single collection since the witness was installed",
            registry=self.registry,
        )
        self.host_stall_seconds = Counter(
            "dynamo_host_stall_seconds_total",
            "Seconds a 20 ms sleeper woke late by, summed over wakes more "
            "than 100 ms late, by cause: busy (the process's CPU clock "
            "advanced by at least half the lateness: another thread held "
            "the GIL) or descheduled (it did not: the process did not run)",
            ["cause"],  # telemetry/flight.py STALL_CAUSES
            registry=self.registry,
        )
        self.host_stalls = Counter(
            "dynamo_host_stalls_total",
            "Wakes of that sleeper more than 100 ms late, by cause",
            ["cause"],
            registry=self.registry,
        )
        self.launch_seconds = Histogram(
            "dynamo_launch_seconds",
            "From TPUEngine._launch's entry (the variant lookup, the "
            "offloads that must precede, the transfer) to the return of "
            "the jitted call, by program: the part of a host gap that "
            "dynamo_host_gap_seconds ends before",
            ["program"],  # window | mixed | block
            buckets=_DISPATCH_BUCKETS,
            registry=self.registry,
        )
        self.kv_pool_info = Gauge(
            "dynamo_kv_pool_info",
            "Constant-1 geometry of each of the device KV pools (K and V; "
            "pools=1: one latent pool whose row is [c_kv | k_r] and no value "
            "pool; a latent model with an indexer has pools=2, the second "
            "holding index_width lanes of index key a token; a grouped-query "
            "model with an indexer has pools=2 of K and V, its index_width "
            "lanes of index key the LAST lanes of the K pool's row, which "
            "width counts): stacked "
            "layers, pages, tokens a page, a row's lanes (fused kv-head "
            "width, or the latent row with its pad_lanes of zero padding), "
            "element type, and token_bytes, the bytes a token holds over "
            "all pools and layers",
            ["layers", "pages", "page_size", "width", "dtype", "pools",
             "pad_lanes", "token_bytes", "index_width"],
            registry=self.registry,
        )
        self.kv_sliding_pool_info = Gauge(
            "dynamo_kv_sliding_pool_info",
            "Constant-1 geometry of the sliding layers' KV pools (K and V) "
            "of a model whose cache is by kind (sliding and full attention "
            "layers in one stack; dynamo_kv_pool_info then describes the "
            "full layers' pools): stacked layers, pages, tokens a page, a "
            "row's lanes, element type, the sliding width (keys a query "
            "sees) and page_bytes, the bytes a held page takes over those "
            "layers, K and V. No sample for any other model",
            ["layers", "pages", "page_size", "width", "dtype",
             "sliding_width", "page_bytes"],
            registry=self.registry,
        )
        self.attn_form_info = Gauge(
            "dynamo_attn_form_info",
            "Constant-1 shape of each form of softmax attention the model "
            "has (full / sliding; none for latent attention): the layers of "
            "that form, their KV heads, a head's key and value widths as "
            "cached, and sink=1 where a learned logit a query head joins "
            "the softmax's denominator in those layers",
            ["form", "layers", "kv_heads", "key_width", "value_width", "sink"],
            registry=self.registry,
        )
        self.kv_pool_usage = Gauge(
            "dynamo_kv_pool_usage",
            "Share of a pool's pages in use, by pool of a cache by kind "
            "(full: held, shared or parked for reuse, as "
            "dynamo_engine_hbm_page_occupancy; sliding: held by a row)",
            ["pool"],  # full | sliding
            registry=self.registry,
        )
        self.kv_pool_held_bytes = Gauge(
            "dynamo_kv_pool_held_bytes",
            "Bytes of a pool's pages in use over its layers, K and V, by "
            "pool of a cache by kind (pool=full alone for a looped model and "
            "for a grouped-query model with an indexer, whose K row holds "
            "its index key too)",
            ["pool"],
            registry=self.registry,
        )
        self.kv_sliding_pool_peak_pages = Gauge(
            "dynamo_kv_sliding_pool_peak_pages",
            "The most pages the sliding layers' pool has held at once since "
            "start (of dynamo_kv_sliding_pool_info's pages): the margin "
            "behind EngineConfig.sliding_pool_pages' arithmetic, since a "
            "pool that runs dry stops the engine loop",
            registry=self.registry,
        )
        self.kv_context_tokens = Gauge(
            "dynamo_kv_context_tokens",
            "Context tokens of the rows bound to decode slots (written or "
            "in flight), of a model that publishes "
            "dynamo_kv_pool_held_bytes: what those bytes are held for",
            registry=self.registry,
        )
        self.kv_sliding_pages_released = Counter(
            "dynamo_kv_sliding_pages_released_total",
            "Pages of the sliding layers' pool given back behind the width "
            "while their row lived (no later query of it sees them), in the "
            "consume phase's pages part",
            registry=self.registry,
        )
        self.attn_query_tokens = Counter(
            "dynamo_attn_query_tokens_total",
            "Queries dispatches fed the attention layers of a model with "
            "sliding layers (or of a looped model, every layer-pass a full "
            "layer), by the layers' form and summed over the layers of each",
            ["kind"],  # sliding | full
            registry=self.registry,
        )
        self.attn_key_positions = Counter(
            "dynamo_attn_key_positions_total",
            "Key positions those queries may see (t + 1 in a full layer, "
            "min(t + 1, width) in a sliding one), by the layers' form and "
            "summed over the layers of each: counted on the host from the "
            "dispatch's plan, the work a roofline reads whatever computes it",
            ["kind"],
            registry=self.registry,
        )
        self.attn_context_tokens = Counter(
            "dynamo_attn_context_tokens_total",
            "Context tokens a row's queries of one dispatch see between "
            "them (a full layer: up to the last query's position; a sliding "
            "layer: from width - 1 before the first query on), by the "
            "layers' form and summed over the layers of each: what the "
            "attention has to read at the least",
            ["kind"],
            registry=self.registry,
        )
        self.model_loop_info = Gauge(
            "dynamo_model_loop_info",
            "Constant-1 shape of the model's loop over its layers: passes a "
            "token makes through the stack (1 for every model but a looped "
            "one), the layers of weights a pass runs, and kv_layers, the "
            "layer slots of the KV pools (passes x attention layers: a slot "
            "for every (pass, layer))",
            ["passes", "weight_layers", "kv_layers"],
            registry=self.registry,
        )
        self.layer_passes = Counter(
            "dynamo_layer_passes_total",
            "Layer applications a looped model's live query tokens took "
            "(tokens x passes x layers), counted on the host from the "
            "dispatch's plan beside dynamo_ragged_slots_total{kind=live}, "
            "the tokens fed: their ratio is the layer-passes a token. No "
            "sample moves for a model whose layers run once",
            registry=self.registry,
        )
        self.jax_compile_events = Counter(
            "dynamo_jax_compile_events_total",
            "JAX compile-pipeline events in this process, by stage "
            "(trace = jaxpr tracing, lower = jaxpr to MLIR, "
            "backend_compile = XLA compile or persistent-cache load)",
            ["stage"],
            registry=self.registry,
        )
        self.jax_compile_seconds = Counter(
            "dynamo_jax_compile_seconds_total",
            "Seconds spent in JAX compile-pipeline events, by stage",
            ["stage"],
            registry=self.registry,
        )
        self._jax_compile_listener = None
        self.prompt_tokens = Counter(
            "dynamo_prompt_tokens_total",
            "Prompt tokens at admission by source: cached (served from "
            "attached or restored KV pages) or computed (prefilled)",
            ["source"],  # cached | computed
            registry=self.registry,
        )
        self.build_info = Gauge(
            "dynamo_build_info",
            "Constant-1 config-skew fingerprint: AOT lattice manifest "
            "hash, jax version, serving feature flags, and where the "
            "engine runs (platform, device kind and count, resolved "
            "attention implementation) — fleet scrapes compare label "
            "sets across instances",
            list(BUILD_INFO_LABELS),
            registry=self.registry,
        )

    # ------------------------------------------------------------ recorder
    def configure(self, trace_file: str | None) -> None:
        """Point span recording at a JSONL file (None disables).

        The recorder is bounded: it rotates at ``DYN_TRACE_ROTATE_MB``
        megabytes (default 64) keeping ``DYN_TRACE_KEEP`` older
        generations (default 4), so a long-lived worker can leave
        tracing on without growing the file forever. An atexit hook
        flushes and closes the live file once per process, so a worker
        dying between spans doesn't lose its buffered tail (torn lines
        from a hard kill are skipped at replay)."""
        from ..recorder import Recorder

        with self._rec_lock:
            if self._recorder is not None:
                self._recorder.close()
                self._recorder = None
            if trace_file:
                self._recorder = Recorder(
                    trace_file,
                    max_bytes=int(
                        _env_float("DYN_TRACE_ROTATE_MB", 64.0) * (1 << 20)
                    ),
                    max_files=int(_env_float("DYN_TRACE_KEEP", 4.0)),
                )
                self._register_atexit_flush()

    def _register_atexit_flush(self) -> None:
        if getattr(self, "_atexit_registered", False):
            return
        self._atexit_registered = True
        atexit.register(self._flush_at_exit)

    def _flush_at_exit(self) -> None:
        """Crash-flush: close the live recorder so its tail reaches the
        OS even when the process dies without calling configure(None)."""
        with self._rec_lock:
            rec, self._recorder = self._recorder, None
            if rec is not None:
                with contextlib.suppress(Exception):
                    rec.close()

    def configure_from_env(self) -> None:
        """Honor ``DYN_TRACE_FILE`` if set.

        The env var is shared by every process of a supervised graph,
        but the Recorder's size rotation assumes a single writer — two
        processes rotating one shared file clobber each other's
        generations. So each process records to ``<path>.pid<pid>`` (a
        suffix disjoint from the rotation's bare ``.N`` namespace, so a
        pid-1 container process can't be renamed over by another
        writer's rotation); ``load_spans(<path>)`` and ``llmctl trace``
        pick the siblings up automatically."""
        path = os.environ.get("DYN_TRACE_FILE", "")
        if path:
            self.configure(f"{path}.pid{os.getpid()}")

    @property
    def trace_file(self) -> str | None:
        rec = self._recorder
        return rec.path if rec is not None else None

    # ------------------------------------------------------------ emission
    def emit(self, span: Span) -> None:
        """Record one finished span (thread-safe; never raises into the
        serving path). Every span is stamped with this process's
        instance identity so a cross-instance trace renders as a
        multi-instance timeline (docs/observability.md "Fleet plane")."""
        span.attrs.setdefault("instance", self.instance)
        self.stage_duration.labels(span.stage).observe(span.duration_s)
        rec = self._recorder
        if rec is not None:
            try:
                with self._rec_lock:
                    rec.record(span.to_event(), ts=span.end)
            except Exception:  # noqa: BLE001 - tracing must not break serving
                logger.exception("span recording failed")

    def emit_stage(
        self,
        stage: str,
        start: float,
        end: float,
        trace: TraceContext | None,
        **attrs: Any,
    ) -> None:
        """Explicit-time emission for call sites that can't hold a
        ``with span(...)`` open — the engine loop thread stamps
        monotonic-derived unix times and hands them here."""
        if trace is None:
            return
        child = trace.child()
        self.emit(
            Span(
                stage=stage,
                trace_id=child.trace_id,
                span_id=child.span_id,
                parent_span_id=trace.span_id,
                start=start,
                end=end,
                attrs={k: v for k, v in attrs.items() if v is not None},
            )
        )

    # -------------------------------------------------------------- gauges
    def set_build_info(
        self,
        manifest_hash: str = "",
        jax_version: str = "",
        prefix_sharing: bool = False,
        spec: str = "off",
        platform: str = "",
        device_kind: str = "",
        device_count: int = 0,
        attn_impl: str = "",
    ) -> None:
        """Publish the constant-1 ``dynamo_build_info`` sample (clearing
        any previous label set, so one instance never exports two
        fingerprints after a live reconfigure)."""
        self.build_info.clear()
        self.build_info.labels(
            manifest_hash or "unknown",
            jax_version or "unknown",
            str(bool(prefix_sharing)).lower(),
            spec or "off",
            platform or "unknown",
            device_kind or "unknown",
            str(device_count),
            attn_impl or "unknown",
        ).set(1)

    def set_kv_pool_info(
        self, layers: int, pages: int, page_size: int, width: int, dtype: str,
        pools: int = 2, pad_lanes: int = 0, token_bytes: int = 0,
        index_width: int = 0,
    ) -> None:
        """Publish the constant-1 ``dynamo_kv_pool_info`` sample (one
        label set an instance, like ``set_build_info``)."""
        self.kv_pool_info.clear()
        self.kv_pool_info.labels(
            str(layers), str(pages), str(page_size), str(width), dtype,
            str(pools), str(pad_lanes), str(token_bytes), str(index_width),
        ).set(1)

    def set_kv_sliding_pool_info(
        self, layers: int, pages: int, page_size: int, width: int, dtype: str,
        sliding_width: int, page_bytes: int,
    ) -> None:
        """Publish the constant-1 ``dynamo_kv_sliding_pool_info`` sample."""
        self.kv_sliding_pool_info.clear()
        self.kv_sliding_pool_info.labels(
            str(layers), str(pages), str(page_size), str(width), dtype,
            str(sliding_width), str(page_bytes),
        ).set(1)

    def set_model_loop_info(self, passes: int, weight_layers: int, kv_layers: int) -> None:
        """Publish the constant-1 ``dynamo_model_loop_info`` sample."""
        self.model_loop_info.clear()
        self.model_loop_info.labels(
            str(passes), str(weight_layers), str(kv_layers)
        ).set(1)

    def set_attn_form_info(self, forms) -> None:
        """Publish the constant-1 ``dynamo_attn_form_info`` samples, one a
        form: ``(form, layers, kv_heads, key_width, value_width, sink)``."""
        self.attn_form_info.clear()
        for form, layers, heads, key, value, sink in forms:
            self.attn_form_info.labels(
                form, str(layers), str(heads), str(key), str(value), str(int(sink)),
            ).set(1)

    def watch_jax_compiles(self) -> None:
        """Count JAX's own trace / lower / backend-compile events into
        ``dynamo_jax_compile_events_total{stage}`` and
        ``dynamo_jax_compile_seconds_total{stage}``. Registers one
        ``jax.monitoring`` listener, once; called by whoever builds an
        engine, so a process that never loads jax never pays the
        import."""
        if self._jax_compile_listener is not None:
            return
        import jax.monitoring

        def on_duration(event: str, seconds: float, **_kw) -> None:
            stage = _JAX_COMPILE_STAGES.get(event)
            if stage is not None:
                self.jax_compile_events.labels(stage).inc()
                self.jax_compile_seconds.labels(stage).inc(seconds)

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        self._jax_compile_listener = on_duration

    def publish_engine_gauges(self, metrics: dict) -> None:
        """Mirror an engine ``metrics()`` dict into the engine gauges
        (unknown keys ignored, so callers can pass the full dict)."""
        for key in self.engine_gauges:
            if key in metrics:
                self.engine_gauges[key].set(float(metrics[key]))
        if "kv_shared_pages" in metrics:
            # Standalone gauge (not dynamo_engine_*-prefixed): the
            # fleet-wide prefix-sharing headline series.
            self.kv_shared_pages.set(float(metrics["kv_shared_pages"]))
        if "seq_state_slots_in_use" in metrics:
            self.seq_state_slots.set(float(metrics["seq_state_slots_in_use"]))

    def render(self) -> bytes:
        from prometheus_client import generate_latest

        return generate_latest(self.registry)


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        return float(raw)
    except ValueError:
        logger.warning("ignoring invalid %s=%r", name, raw)
        return default


class _ActiveSpan:
    """Context manager for in-task spans: opens a child of the current
    contextvar trace (or a fresh root), makes itself current inside the
    block, and emits on exit. ``attrs`` may be amended via ``set``."""

    def __init__(self, hub: "Telemetry", stage: str, attrs: dict):
        self._hub = hub
        self.stage = stage
        self.attrs = attrs
        self._token = None
        self._t0 = 0.0
        self.context: TraceContext | None = None

    def set(self, **attrs: Any) -> None:
        self.attrs.update(attrs)

    def __enter__(self) -> "_ActiveSpan":
        parent = current_trace()
        if parent is None:  # no inbound trace: this span roots a new one
            self.context = new_trace()
            self._parent_id = ""
        else:
            self.context = parent.child()
            self._parent_id = parent.span_id
        self._token = attach(self.context)
        self._t0 = time.time()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._token is not None:
            detach(self._token)
        if exc_type is not None:
            self.attrs.setdefault("error", f"{exc_type.__name__}: {exc}")
        self._hub.emit(
            Span(
                stage=self.stage,
                trace_id=self.context.trace_id,
                span_id=self.context.span_id,
                parent_span_id=self._parent_id,
                start=self._t0,
                end=time.time(),
                attrs={k: v for k, v in self.attrs.items() if v is not None},
            )
        )


# ---------------------------------------------------------------- process hub
_global: Telemetry | None = None
_global_lock = threading.Lock()


def get_telemetry() -> Telemetry:
    """The process-wide hub (created lazily; picks up DYN_TRACE_FILE)."""
    global _global
    if _global is None:
        with _global_lock:
            if _global is None:
                hub = Telemetry()
                hub.configure_from_env()
                _global = hub
    return _global


def span(stage: str, **attrs: Any) -> _ActiveSpan:
    """``with span("preprocess", tokens=n):`` — child of the current
    trace, or the root of a fresh one on an untraced path."""
    return _ActiveSpan(get_telemetry(), stage, dict(attrs))


@contextlib.contextmanager
def adopt(tc: TraceContext | None):
    """Make a deserialized wire context current for the enclosed block
    (no span is emitted — use for transport ingress points)."""
    if tc is None:
        yield
        return
    token = attach(tc)
    try:
        yield
    finally:
        detach(token)
