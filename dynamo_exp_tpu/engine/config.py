"""Engine configuration.

The reference passes engine knobs through to vLLM/sglang
(``/root/reference/launch/dynamo-run/src/flags.rs:26-238``); here they
configure our own continuous-batching TPU engine.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field

from ..models.config import ModelConfig


_TRUTHY = frozenset({"1", "true", "on", "yes"})
_FALSY = frozenset({"0", "false", "no", "off"})

# One validated table for every engine-config env knob. Each entry:
# (env name, config attr, kind). Kinds:
#   "flag"  — tri-state bool: truthy/falsy spelling sets the attr,
#             unset/empty leaves the config value alone, anything else
#             raises (a typo'd spelling must not silently no-op);
#   "grace" — DYN_KV_PROACTIVE: truthy arms proactive offload (grace
#             clamped >= 0), falsy disables it (-1.0);
#   "spec"  — DYN_SPEC: truthy -> the "ngram" drafter, falsy -> stay
#             off, any other value must be a registered drafter name
#             (the PR 7 falsy-spelling bug class, now structural: every
#             spelling is validated at construction);
#   "path"  — DYN_KV_STORE: a directory path sets the attr verbatim
#             (arming the G3 persistent tier suite-wide), a falsy
#             spelling clears it; truthy spellings raise (the knob
#             needs an actual path, not "1").
_ENV_KNOBS: tuple[tuple[str, str, str], ...] = (
    ("DYN_SPEC", "spec_mode", "spec"),
    ("DYN_KV_PACKING", "kv_packing", "flag"),
    ("DYN_KV_PREFETCH", "kv_prefetch", "flag"),
    ("DYN_KV_PROACTIVE", "proactive_offload_grace_s", "grace"),
    ("DYN_KV_STORE", "kv_store_dir", "path"),
)
# Env-name families this table owns: any OTHER name under these
# prefixes is a typo (DYN_KV_PACKNG=1 must fail loudly, not silently
# bench the wrong baseline) — except names owned by other modules.
_ENV_FAMILIES = ("DYN_KV_", "DYN_SPEC")
_ENV_EXEMPT = frozenset({
    "DYN_KV_DEFAULT_BW_BPS",  # telemetry.fleet: link-bandwidth prior
})


def _env_knob_names() -> tuple[str, ...]:
    return tuple(name for name, _, _ in _ENV_KNOBS)


def _check_unknown_env_knobs() -> None:
    """Reject unknown names in the owned DYN_* families, listing the
    accepted spellings."""
    accepted = set(_env_knob_names()) | _ENV_EXEMPT
    for name in os.environ:
        if name in accepted:
            continue
        if any(name.startswith(fam) for fam in _ENV_FAMILIES):
            raise ValueError(
                f"unknown engine env knob {name!r}; accepted: "
                f"{', '.join(sorted(accepted))}"
            )


def _parse_env_flag(name: str, raw: str) -> bool:
    low = raw.strip().lower()
    if low in _TRUTHY:
        return True
    if low in _FALSY:
        return False
    raise ValueError(
        f"{name}={raw!r} is not a recognized flag spelling; accepted: "
        f"{', '.join(sorted(_TRUTHY))} / {', '.join(sorted(_FALSY))}"
    )


def refuse_seq_state_tiers(cfg: "EngineConfig | None", tier: str | None = None) -> None:
    """The one place a model whose sequences carry state beside their
    pages (``ModelConfig.has_seq_state``: a short convolution's last
    products, a selective scan's state)
    is refused by what moves or re-reads pages alone. Each of these would
    resume or verify a sequence with a state that does not belong to its
    tokens, and serve wrong numbers without any error:

    - host offload and its prefetch / proactive swap (``engine/offload.py``,
      ``host_cache_pages``), G3 persistence (``kv/persistent.py``,
      ``kv_store_dir``): an uploaded page brings no state;
    - speculative decoding (``spec_mode``): a rejected draft has already
      advanced the state;
    - ring prefill (``sp`` > 1): it writes pages from outside the paged
      layer loop that carries the state;
    - tensor parallelism (``tp`` > 1) where the state is a selective
      scan's, a Mamba-2 mixer's or the delta rule's: the recurrence, its kernel and its pool
      are one chip's (no channel or head split);
    - ``tier`` = "disaggregated transfer" / "migration"
      (``disagg/transfer.py``, ``runtime/reclaim.py``): named by the
      component that is being built round the engine.

    With ``tier`` None the engine's own configuration is checked (called
    by ``TPUEngine.__init__``); with a name, that component is refused.
    A model without such state passes, and so does ``cfg`` None (a
    component built round a stand-in engine that has no configuration)."""
    if cfg is None or not cfg.model.has_seq_state:
        return
    armed = [tier] if tier else [
        name for name, on in (
            ("host offload (host_cache_pages)", cfg.host_cache_pages > 0),
            ("G3 persistence (kv_store_dir)", bool(cfg.kv_store_dir)),
            ("speculative decoding (spec_mode)", cfg.spec_mode != "off"),
            ("ring prefill (sp > 1)", cfg.sp > 1),
            (
                "tensor parallelism (tp > 1) of a selective scan",
                cfg.tp > 1 and cfg.model.num_mamba_layers > 0,
            ),
            (
                "tensor parallelism (tp > 1) of linear attention",
                cfg.tp > 1 and cfg.model.num_linear_layers > 0,
            ),
            (
                "tensor parallelism (tp > 1) of a Mamba-2 mixer",
                cfg.tp > 1 and cfg.model.num_mamba2_layers > 0,
            ),
        ) if on
    ]
    if armed:
        kind = (
            "selective-scan" if cfg.model.num_mamba_layers
            else "Mamba-2" if cfg.model.num_mamba2_layers
            else "linear-attention" if cfg.model.num_linear_layers
            else "short-convolution"
        )
        raise ValueError(
            f"model_type {cfg.model.model_type!r} keeps per-sequence state "
            f"beside its KV pages (what its {kind} "
            f"layers carry from token to token), which "
            f"{', '.join(armed)} would not carry: a sequence would resume with "
            "a state that is not its own. Serve it without (README.md, models/)"
        )


def _page_tiers_armed(cfg: "EngineConfig", tier: str | None) -> list[str]:
    """What ``refuse_latent_tiers``, ``refuse_sliding_pool_tiers`` and
    ``refuse_looped_tiers`` refuse alike: the named ``tier``, or those of the
    engine's own configuration that are switched on."""
    return [tier] if tier else [
        name for name, on in (
            ("host offload (host_cache_pages)", cfg.host_cache_pages > 0),
            ("G3 persistence (kv_store_dir)", bool(cfg.kv_store_dir)),
            ("speculative decoding (spec_mode)", cfg.spec_mode != "off"),
            ("ring prefill (sp > 1)", cfg.sp > 1),
            ("tensor parallelism (tp > 1)", cfg.tp > 1),
        ) if on
    ]


def refuse_latent_tiers(cfg: "EngineConfig | None", tier: str | None = None) -> None:
    """The one place a model whose cache is a latent pool
    (``ModelConfig.is_latent``: one row ``[c_kv | k_r]`` a token and layer
    in ONE pool, and no value pool; beside it, where the model has an
    indexer, a second pool of another width that holds index keys, not
    values) is refused by what carries a page shape
    it did not choose or splits heads, in ``refuse_seq_state_tiers``'
    manner. Each of these lays pages out as two pools of ``Hkv x D`` lanes,
    or divides attention by KV head, and none has run or been tested with a
    latent row:

    - host offload and its prefetch (``engine/offload.py``,
      ``host_cache_pages``), G3 persistence (``kv/persistent.py``,
      ``kv_store_dir``): their page records are a K and a V payload;
    - ring prefill (``sp`` > 1): it rotates per-head K and V;
    - tensor parallelism (``tp`` > 1): a latent cache has one head, and the
      latent projections are replicated;
    - speculative decoding (``spec_mode``): untested with the absorbed form
      over draft spans;
    - ``tier`` = "disaggregated transfer" / "migration"
      (``disagg/transfer.py``, ``runtime/reclaim.py``): the wire's page
      layout is the two pools'.

    A grouped-query model with an indexer (``KeyeVL2``: K and V pools of
    ``Hkv x D`` lanes, and ``index_lanes`` of index key behind the keys in
    the K pool's row, ``ModelConfig.kv_row_parts``) is refused here by the
    same tiers for the same reason: each would copy, split or rewind a K
    row as ``Hkv x D`` lanes of keys and drop or misplace the third thing.

    With ``tier`` None the engine's own configuration is checked; with a
    name, that component is refused. Any other model passes, and so does
    ``cfg`` None."""
    if cfg is None or not (cfg.model.is_latent or cfg.model.has_indexer):
        return
    armed = _page_tiers_armed(cfg, tier)
    if armed and not cfg.model.is_latent:
        m = cfg.model
        raise ValueError(
            f"model_type {m.model_type!r} caches three things a token: K and V "
            f"of {m.num_kv_heads} x {m.head_dim_} and {m.index_head_dim} of "
            f"index key in {m.index_lanes} lanes behind the keys in the K "
            f"pool's row, which {', '.join(armed)} does not carry or split: it "
            "moves pages as K and V of kv-head lanes. Serve it without "
            "(README.md, models/)"
        )
    if armed:
        raise ValueError(
            f"model_type {cfg.model.model_type!r} caches one latent row a "
            f"token ({cfg.model.latent_width} values in one pool, no value "
            "pool" + (
                f"; {cfg.model.index_head_dim} of index key in a second"
                if cfg.model.has_indexer else ""
            ) + f"), which {', '.join(armed)} does not carry or split: it "
            "moves pages as K and V of kv-head lanes. Serve it without "
            "(README.md, models/)"
        )


def refuse_sliding_pool_tiers(cfg: "EngineConfig | None", tier: str | None = None) -> None:
    """The one place a model whose cache is two pools by the layers' form
    (``ModelConfig.kv_by_kind``: sliding and full attention layers in one
    stack, a row's pages of the sliding pool given back behind the width)
    is refused by what copies "a row's pages" through ONE table, in
    ``refuse_seq_state_tiers``' manner. Each of these would carry the full
    layers' pages and leave the sliding layers' behind, or ask for sliding
    pages that were given back:

    - host offload and its prefetch / proactive swap (``engine/offload.py``,
      ``host_cache_pages``), G3 persistence (``kv/persistent.py``,
      ``kv_store_dir``): a page record is one pool's K and V payload;
    - speculative decoding (``spec_mode``): a rejected draft rewinds the
      row's pages by count, and the sliding pool may already have given
      back what the rewound position sees;
    - ring prefill (``sp`` > 1): it has no sliding mask and writes pages
      from outside the paged layer loop;
    - tensor parallelism (``tp`` > 1): the pools by kind have not run on a
      mesh, and nothing tests them there;
    - ``tier`` = "disaggregated transfer" / "migration"
      (``disagg/transfer.py``, ``runtime/reclaim.py``): the wire's page
      layout is one pool's.

    Prefix sharing is not refused but granted no hit (``TPUEngine`` builds
    the page manager with ``sharing=False``). With ``tier`` None the
    engine's own configuration is checked; with a name, that component is
    refused. Any other model passes, and so does ``cfg`` None."""
    if cfg is None or not cfg.model.kv_by_kind:
        return
    armed = _page_tiers_armed(cfg, tier)
    if armed:
        m = cfg.model
        raise ValueError(
            f"model_type {m.model_type!r} caches its {m.num_sliding_layers} "
            f"sliding-attention layers (width {m.sliding_window}) in a page "
            "pool of their own, whose pages go back behind the width, beside "
            f"the full-attention layers' pool, which {', '.join(armed)} does "
            "not carry: it moves a row's pages through one table. Serve it "
            "without (README.md, models/)"
        )


def refuse_looped_tiers(cfg: "EngineConfig | None", tier: str | None = None) -> None:
    """The one place a looped model (``ModelConfig.loop_passes`` > 1: the
    stack of layers run several times a token over one set of weights, a
    layer slot of the cache for every (pass, layer)) is refused by what
    takes a page's shape from the pool or writes pages from outside the
    paged layer loop, in ``refuse_seq_state_tiers``' manner. A page of such
    a model is ``passes`` times a one-pass model's of the same widths (24
    MiB at Ouro-2.6B's 192 slots and 16 tokens), and none of these has run
    or been tested with one:

    - host offload and its prefetch (``engine/offload.py``,
      ``host_cache_pages``), G3 persistence (``kv/persistent.py``,
      ``kv_store_dir``): their page records and the gather / inject
      programs carry every slot of a page, untested at this depth;
    - speculative decoding (``spec_mode``): a draft span's verify would run
      every pass over the span, untested;
    - ring prefill (``sp`` > 1): it runs the layers once, outside the
      paged layer loop that holds the passes;
    - tensor parallelism (``tp`` > 1): the loop has not run on a mesh;
    - ``tier`` = "disaggregated transfer" / "migration"
      (``disagg/transfer.py``, ``runtime/reclaim.py``): the wire's page
      would be every slot's, untested.

    With ``tier`` None the engine's own configuration is checked; with a
    name, that component is refused. Any other model passes, and so does
    ``cfg`` None."""
    if cfg is None or cfg.model.loop_passes == 1:
        return
    armed = _page_tiers_armed(cfg, tier)
    if armed:
        m = cfg.model
        raise ValueError(
            f"model_type {m.model_type!r} runs its {m.num_layers} layers "
            f"{m.loop_passes} times a token and caches a layer slot for every "
            f"(pass, layer) ({m.kv_layer_slots} a page), which "
            f"{', '.join(armed)} has not carried: nothing tests it with a "
            "looped model. Serve it without (README.md, models/)"
        )


def refuse_block_model_tiers(cfg: "EngineConfig | None", tier: str | None = None) -> None:
    """The one place a model that generates by diffusion over blocks
    (``ModelConfig.block_length`` > 0: a row's step is a pass over a block
    of positions, and a prompt yields no first token of its own) is
    refused by what assumes a step is one token, in
    ``refuse_seq_state_tiers``' manner:

    - speculative decoding (``spec_mode``): a draft span is verified one
      token after another, each seeing only what lies before it;
    - ring prefill (``sp`` > 1): its attention is causal by position;
    - tensor parallelism (``tp`` > 1): the block program has not run on a
      mesh, and nothing tests it there;
    - ``tier`` = "disaggregated transfer" / "migration": the hand-off
      carries a prompt's pages and its first sampled token, which a
      block model's prefill does not produce.

    The engine's shapes have to hold whole blocks too: a page, a prompt
    chunk and the model length are multiples of the block length, so a
    block lies in one page and a chunk ends where a block does."""
    if cfg is None or not cfg.model.block_length:
        return
    B = cfg.model.block_length
    armed = [tier] if tier else [
        name for name, on in (
            ("speculative decoding (spec_mode)", cfg.spec_mode != "off"),
            ("ring prefill (sp > 1)", cfg.sp > 1),
            ("tensor parallelism (tp > 1)", cfg.tp > 1),
            (
                f"a page_size, prefill_chunk or max_model_len that is no "
                f"multiple of the block length {B}",
                bool(
                    cfg.page_size % B or cfg.prefill_chunk % B
                    or cfg.max_model_len % B
                ),
            ),
        ) if on
    ]
    if armed:
        raise ValueError(
            f"model_type {cfg.model.model_type!r} generates by diffusion over "
            f"blocks of {B} positions (a step is a pass over a block, not a "
            f"token), which {', '.join(armed)} does not allow for. Serve it "
            "without (README.md, models/)"
        )


def default_prefill_buckets(max_len: int) -> list[int]:
    buckets = []
    b = 16
    while b < max_len:
        buckets.append(b)
        b *= 2
    buckets.append(max_len)
    return buckets


@dataclass
class EngineConfig:
    model: ModelConfig
    # Continuous-batching shape envelope (all static for XLA).
    max_decode_slots: int = 8  # B of the decode step
    page_size: int = 16  # tokens per KV page (also the reuse-hash block)
    num_pages: int = 512  # global page pool size
    max_model_len: int = 2048  # per-sequence token capacity
    prefill_buckets: list[int] = field(default_factory=list)
    # Parallelism within this engine replica.
    tp: int = 1
    sp: int = 1
    # Decode attention implementation: "auto" picks the ragged Pallas
    # kernel on TPU and the length-bounded XLA gather elsewhere.
    attention_impl: str = "auto"  # "auto" | "xla" | "pallas"
    # Run the Pallas kernel in interpreter mode (CPU correctness tests).
    pallas_interpret: bool = False
    # Prefill batching/chunking: up to ``prefill_batch`` sequences share
    # one prefill dispatch, and decode interleaves between chunks of long
    # prompts. ``prefill_chunk`` is the prompt tokens a prefilling row is
    # guaranteed a dispatch, not its ceiling: a row with more prompt left
    # also takes, in whole pages and oldest first, what the dispatch's
    # token bucket would have left empty (``engine._plan_chunks``).
    prefill_batch: int = 8
    prefill_chunk: int = 512
    # Decode steps per dispatch: one compiled window runs this many
    # steps on-device (tokens fed back without touching the host) and
    # the host syncs once per window. Amortises per-sync overhead at
    # the cost of stop-condition latency (a sequence may overshoot its
    # stop by up to window-1 discarded tokens).
    decode_window: int = 8
    # One compiled decode window per (dispatched) row bucket keeps decode
    # cost proportional to occupancy instead of max_decode_slots; rows
    # are compacted into the smallest 1/2/4/... bucket that fits the
    # ACTIVE set (see docs/engine_perf.md).
    # Static width of the per-row on-device stop-token set fed into the
    # decode window (EOS + request stop ids, -1 padded). Keeping it
    # static keeps it out of the compile key; requests with more stop
    # ids than this fall back to host-side stopping for the overflow.
    device_stop_width: int = 8
    # Keep one decode window in flight: dispatch window N+1 straight
    # from window N's on-device carry (tokens/positions) while the host
    # is still consuming window N's sampled tokens. Disable to force the
    # dispatch -> sync -> consume lockstep (debugging/equivalence runs).
    chained_decode: bool = True
    # Sampling defaults when the request leaves them unset.
    default_max_tokens: int = 256
    eos_token_ids: list[int] = field(default_factory=list)
    # KV cache dtype ("bfloat16" | "float32").
    kv_dtype: str = "bfloat16"
    # G2 host-RAM KV tier: number of host pages (0 disables offload).
    # Device-evicted pages spill here and are re-injected on prefix match
    # instead of being recomputed (reference: kv/manager.rs G1/G2 tiers).
    host_cache_pages: int = 0
    # Emit KV stored/removed events for the router index.
    enable_kv_events: bool = True
    # Fleet-wide prefix sharing (docs/prefix_sharing.md): refcounted
    # copy-on-write KV pages behind the radix prefix index — admissions
    # attach resident (even still-filling) shared prefix pages and
    # prefill only the unshared suffix. False is the private-copy
    # baseline: every admission materializes its own pages (the
    # reference of tests/test_prefix_sharing.py's identity tests, which
    # prove the token streams are equal either way).
    prefix_sharing: bool = True
    # KV-pressure preemption (docs/fault_tolerance.md "Overload
    # protection"): when the page pool is dry and an ACTIVE row has been
    # hard-stalled (cannot feed its next token) longer than this grace,
    # the engine preempts the lowest-priority / youngest ACTIVE sequence
    # — releasing its pages and requeueing it as a deterministic
    # continuation — instead of parking stalled slots forever. Negative
    # disables preemption entirely.
    preempt_stall_grace_s: float = 0.5
    # Per-request preemption bound: a sequence preempted this many times
    # is exempt from further victimization (no re-prefill live-lock).
    max_preemptions_per_seq: int = 2
    # Speculative decoding (docs/speculative.md): "off", or a drafter
    # name from the spec/ registry ("ngram" = prompt-lookup, no second
    # model). The DYN_SPEC env var overrides "off" (the chaos identity
    # suites run with DYN_SPEC=ngram to prove failover/preemption stay
    # token-identical with speculation on).
    spec_mode: str = "off"
    # Initial per-row draft length; the adaptive controller moves it
    # within [spec_min_draft, spec_max_draft] from the rolling
    # acceptance rate (spec_adaptive=False pins it — tests/test_spec.py).
    spec_draft_len: int = 4
    spec_min_draft: int = 1
    spec_max_draft: int = 8
    spec_adaptive: bool = True
    # Prompt-lookup drafter: trailing n-gram widths tried (longest
    # first) against the row's own prompt+generated context.
    spec_ngram: int = 3
    spec_ngram_min: int = 1
    # Miss backoff: after this many consecutive empty proposals the row
    # stops being probed until its context grows by spec_retry_tokens.
    spec_miss_limit: int = 4
    spec_retry_tokens: int = 32
    # Per-dispatch device profiling (docs/observability.md): host-gap /
    # in-flight / compile timing per dispatch kind, measured at the
    # loop's existing sync points — zero added host syncs. Off only for
    # A/B overhead measurement (the sync-spy smoke test).
    profile_dispatches: bool = True
    # Engine flight recorder (docs/observability.md): bounded ring of
    # loop events dumped on watchdog stall / SIGUSR1 / loop crash.
    # Dump target; empty resolves to $DYN_FLIGHT_DUMP or a per-process
    # file under the tempdir (telemetry.flight.default_dump_path).
    flight_dump_path: str = ""
    # Watchdog: dump the flight ring + a scheduler/slot/page snapshot
    # when the loop has made no progress while work is queued for this
    # long. Generous default: a cold compile of a big variant stalls
    # the loop thread legitimately for seconds. <= 0 disables.
    watchdog_stall_s: float = 30.0
    # Disaggregation KV-handoff lease TTL: extracted prompt pages stay
    # pinned in HBM this long awaiting the decode worker's delivery ack;
    # the engine-loop reaper reclaims orphans (decode instance died
    # between extract and inject) once it passes. Must comfortably cover
    # one prefill-to-decode transfer (docs/fault_tolerance.md).
    kv_lease_ttl_s: float = 30.0
    # KV conservation auditor (docs/observability.md "KV conservation
    # auditor"): run the page manager's O(1) counter-delta ledger check
    # every loop iteration; a violation increments
    # dynamo_kv_ledger_violations_total and dumps a flight snapshot
    # (with the full named audit) once per episode. Pure host-int
    # arithmetic — zero added host syncs (sync-spy-proven). Off only
    # for A/B overhead measurement.
    kv_ledger_check: bool = True
    # ---- Predictive KV tiering (docs/engine_perf.md "Predictive KV
    # tiering"). Env overrides: DYN_KV_PACKING / DYN_KV_PREFETCH /
    # DYN_KV_PROACTIVE flip each policy for whole suites without
    # touching call sites (truthy/falsy spellings like DYN_SPEC).
    #
    # Footprint-packed admission: forecast each waiting sequence's
    # lifetime KV footprint (prompt + max_tokens, minus the
    # radix-matched resident prefix) and admit the first sequence whose
    # forecast fits free-page headroom — an oversize head that would
    # only stall defers behind smaller work. Packing never refuses an
    # admission first-fit would have made; it only reorders, with
    # priority-inversion and starvation guards
    # (engine/tiering.select_packed_index).
    kv_packing: bool = True
    packing_scan_limit: int = 16  # waiting-queue prefix scanned per pass
    packing_max_defers: int = 64  # bypasses before a seq becomes a barrier
    # G2→G1 prefetch: restore host-resident prefixes of *waiting*
    # prompts ahead of admission (the CopyStream's device-bound
    # direction), so restores overlap compute instead of landing inside
    # the admission path. Active only with a host tier
    # (host_cache_pages > 0).
    kv_prefetch: bool = True
    prefetch_depth: int = 4  # waiting sequences scanned per pass
    # Headroom (free + parked pages) prefetch never consumes — decode
    # growth must always win. Prefetch MAY evict parked LRU pages
    # beyond the reserve: their content writes back to the host tier,
    # so it trades LRU-cold cache for predicted-hot cache losslessly.
    prefetch_reserve_pages: int = 4
    # Proactive cold-tail offload: once a row has been hard-stalled
    # this long (and before preempt_stall_grace_s expires), swap the
    # coldest eligible row's refcount-1 non-leased pages out to the
    # host tier — bytes preserved, resume token-identical — instead of
    # preempting. Negative disables; requires a host tier. Must be <
    # preempt_stall_grace_s to fire first (preemption stays the
    # fallback when swapping can't free enough).
    proactive_offload_grace_s: float = 0.0
    # ---- G3 persistent KV tier (docs/fault_tolerance.md "Durable KV &
    # corruption containment"). Empty disables. Pages LRU-demoted out of
    # the G2 host pool land here as checksummed, crash-recoverable files
    # keyed by the same chained block hashes; a restarted process
    # boot-scans the directory and re-attaches surviving prefixes.
    # Requires a host tier (host_cache_pages > 0) — demotion rides its
    # eviction path. DYN_KV_STORE=<dir> arms it suite-wide.
    kv_store_dir: str = ""
    # Store capacity in pages; LRU-evicted beyond this. <= 0 with a
    # kv_store_dir set is rejected at construction.
    kv_store_pages: int = 4096
    # Seeded StorageChaos schedule (tests only; never set in prod).
    kv_store_chaos: object = None

    def __post_init__(self):
        if not self.prefill_buckets:
            self.prefill_buckets = default_prefill_buckets(self.max_model_len)
        self.prefill_buckets = sorted(set(self.prefill_buckets))
        if self.kv_dtype not in ("bfloat16", "float32"):
            raise ValueError(f"unsupported kv_dtype: {self.kv_dtype!r}")
        self._apply_env_knobs()
        if self.kv_store_dir and self.kv_store_pages <= 0:
            raise ValueError(
                f"kv_store_dir={self.kv_store_dir!r} needs "
                f"kv_store_pages > 0 (got {self.kv_store_pages})"
            )
        if self.spec_max_draft < self.spec_min_draft or self.spec_min_draft < 1:
            raise ValueError(
                f"bad spec draft bounds [{self.spec_min_draft}, "
                f"{self.spec_max_draft}]"
            )
        self.spec_draft_len = min(
            max(self.spec_draft_len, self.spec_min_draft), self.spec_max_draft
        )

    def _apply_env_knobs(self) -> None:
        """Walk the validated env-knob table (suite-wide A/B toggles —
        `make chaos` SPEC_SEED_SETS etc. — without touching call
        sites). Unknown names in the owned DYN_* families and
        malformed values raise here, at construction, with the
        accepted spellings listed."""
        _check_unknown_env_knobs()
        for name, attr, kind in _ENV_KNOBS:
            raw = os.environ.get(name, "").strip()
            if not raw:
                continue
            if kind == "flag":
                setattr(self, attr, _parse_env_flag(name, raw))
            elif kind == "path":
                low = raw.lower()
                if low in _FALSY:
                    setattr(self, attr, "")
                elif low in _TRUTHY:
                    raise ValueError(
                        f"{name}={raw!r} must be a directory path (or a "
                        f"falsy spelling to disable), not a bare flag"
                    )
                else:
                    setattr(self, attr, raw)
            elif kind == "grace":
                if _parse_env_flag(name, raw):
                    self.proactive_offload_grace_s = max(
                        self.proactive_offload_grace_s, 0.0
                    )
                else:
                    self.proactive_offload_grace_s = -1.0
            else:  # "spec"
                if self.spec_mode != "off":
                    continue  # an explicit spec_mode wins
                low = raw.lower()
                if low in _TRUTHY:
                    self.spec_mode = "ngram"
                elif low not in _FALSY:
                    from ..spec import registered_drafters

                    names = registered_drafters()
                    if raw not in names:
                        raise ValueError(
                            f"{name}={raw!r} is neither a flag spelling "
                            f"nor a registered drafter; accepted: "
                            f"{', '.join(sorted(_TRUTHY | _FALSY))} / "
                            f"{', '.join(sorted(names))}"
                        )
                    self.spec_mode = raw

    @property
    def kv_dtype_jnp(self):
        """Single source of truth for the KV dtype (device pool, host
        pool, and every offload round-trip must agree bit-for-bit)."""
        import jax.numpy as jnp

        return jnp.bfloat16 if self.kv_dtype == "bfloat16" else jnp.float32

    @property
    def max_pages_per_seq(self) -> int:
        return (self.max_model_len + self.page_size - 1) // self.page_size

    @property
    def sliding_pool_pages(self) -> int | None:
        """Pages of the sliding layers' pool of a model whose cache is by
        kind (``ModelConfig.kv_by_kind``; None for any other): sized from
        the largest dispatch, NOT from the context, because a row gives its
        sliding pages back behind the width once a dispatch is consumed
        (``kv_manager.SlidingPagePool``). A row holds the pages of
        [its next position - (width - 1), the last position a dispatch in
        flight writes]:

        - the ``width - 1`` positions behind, and the two pages they and
          the span's end cut into: ``ceil((width - 1) / ps) + 2`` a row;
        - what its dispatches in flight write: a decode window and the one
          chained onto it (``2 * decode_window`` positions a row, a page
          more for where they start), or its share of a mixed dispatch,
          whose spans sum to at most the largest mixed bucket:
          ``prefill_batch`` rows' chunks and every slot's one token, each
          aligned to the kernel's tile (speculation, whose spans would add
          to it, is refused for such a model).

        ``max_decode_slots`` rows at the most. At 32 slots, pages of 16, a
        width of 128, windows of 8 and chunks of 512 that is a dispatch of
        8,192 tokens: 512 + 32 x (8 + 2 + 2) = 896 pages where a pool sized
        by a 32 k context would take 65,536."""
        if not self.model.kv_by_kind:
            return None
        ps, tile = self.page_size, self.ragged_q_tile
        behind = -(-(self.model.sliding_window - 1) // ps) + 2
        window = -(-2 * self.decode_window // ps) + 1
        dispatch = self.ragged_tokens_bucket_for(
            self.prefill_batch * (self.prefill_chunk + tile)
            + self.max_decode_slots * tile,
            mixed=True,
        )
        return -(-dispatch // ps) + self.max_decode_slots * (behind + window)

    def bucket_for(self, n: int) -> int | None:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        return None

    @staticmethod
    def _pow2_bucket(n: int, floor: int, cap: int | None = None) -> int:
        """Next power of two >= n, starting at ``floor``, optionally
        capped — the one bucketing policy every static-shape family
        (prefill rows, decode rows, page moves, attention pages) uses,
        bounding compiled-variant counts to O(log)."""
        b = floor
        while b < n:
            b *= 2
        return b if cap is None else min(b, cap)

    def rows_bucket_for(self, n: int) -> int:
        """Prefill-batch row bucket (1/2/4/.../prefill_batch)."""
        return self._pow2_bucket(n, 1, self.prefill_batch)

    def ragged_tokens_bucket_for(self, n: int, mixed: bool = False) -> int:
        """Total-padded-query-token bucket of one ragged dispatch
        (docs/engine_perf.md "One ragged dispatch"): the flat mixed
        query stream — every row's true query tokens, summed — pads to
        the next power of two. This single axis replaces the old
        (decode rows x prefill rows x prefill tokens x spec draft)
        shape dimensions: a lone decode row buckets to 1, a full decode
        batch to ``max_decode_slots``, a prefill chunk to its length —
        compute tracks the true total, and the variant lattice is
        O(log total) instead of a product of per-family axes.

        ``mixed`` batches floor at 16 tokens: a short prefill tail or a
        draft span costs one 16-wide forward either way, and the floor
        keeps transient small shapes from fragmenting the lattice. On a
        kernel engine ``n`` is the laid-out extent, not the token count:
        spans of several tokens padded to ``ragged_q_tile``, decode rows
        one slot each in the bucket's packed tail
        (:meth:`ragged_packed_slots`; ``engine._mixed_bucket_for`` picks the
        bucket that holds both).
        Windowed (pure-decode) batches floor at 1 — so decode cost
        keeps tracking occupancy exactly — and cap at
        ``max_decode_slots`` (a non-power-of-two slot envelope must not
        round its full-occupancy window up past the slots that exist)."""
        if mixed:
            return self._pow2_bucket(n, 16, self.ragged_max_tokens)
        return self._pow2_bucket(n, 1, self.max_decode_slots)

    def ragged_packed_slots(self, nb: int) -> int:
        """How many slots at the end of a kernel engine's mixed stream of
        ``nb`` are read one query a grid cell (the form a decode window
        uses) and not a tile of ``ragged_q_tile`` a row: where a decode
        row's single token costs one slot, not eight. A function of
        ``nb`` and this configuration alone, so the split is a static
        part of the program keyed by ``nb`` and the variant lattice has
        no second axis: ``min(max_decode_slots, nb // 4)``, widened
        until the tiled part before it is whole tiles (8 of 16, 8 of 32,
        16 of 64, 32 of 128, then 64 of every larger bucket at 64
        slots). A quarter because a dispatch that has more decode rows
        than that is nearly a window, and a prompt chunk keeps three
        quarters of its bucket in the cheaper tiled form."""
        tile = self.ragged_q_tile
        tiled = (nb - min(self.max_decode_slots, nb // 4)) // tile * tile
        return nb - tiled

    def ragged_page_bucket_for(self, n_pages: int) -> int:
        """Static page bound of a ragged dispatch's XLA attention
        gather. Floors at ~1024 tokens of pages: bucketing finer than
        that only multiplies compiled variants. The callers pass the
        dispatch's LONGEST row, so a window's bound (and the gather's
        width for every row in it) is set by one long request. Capped
        at the per-sequence table width; the Pallas kernel ignores the
        bound entirely (it DMAs true lengths), which is what deletes
        the page axis from the TPU lattice — every program of a kernel
        engine since PR 30 (``aot.lattice.resolve_ragged_key``)."""
        floor = min(self.max_pages_per_seq, max(4, 1024 // self.page_size))
        return self._pow2_bucket(
            max(n_pages, floor), 4, self.max_pages_per_seq
        )

    @property
    def ragged_max_tokens(self) -> int:
        """Upper bound of one ragged dispatch's flat query stream: every
        slot prefilling a full chunk plus every slot speculating at the
        widest draft (whichever mix arrives, the bucket can hold it)."""
        per_row = max(self.prefill_chunk, self.spec_max_draft + 1)
        n = self.max_decode_slots * (per_row + self.ragged_q_tile - 1)
        return self._pow2_bucket(n, 1)

    # Flat-stream alignment of a row's span of several query tokens (a
    # prefill chunk, a draft span) when the Pallas ragged kernel serves
    # the dispatch: every kernel grid cell must belong to exactly one
    # row (ops/ragged_attention.py). Decode rows go one slot each in the
    # stream's packed tail (ragged_packed_slots); the XLA reference
    # path packs everything tight (alignment 1).
    ragged_q_tile: int = 8

    def page_move_bucket_for(self, n: int) -> int:
        """Static page-count bucket for batched KV page gather/scatter
        (disagg extract/inject, G2 re-uploads, eviction offload bursts):
        next power of two >= n, min 8. One compiled variant per bucket
        moves a whole sequence's pages in one dispatch."""
        return self._pow2_bucket(n, 8)
