"""The compile lattice, enumerated offline (docs/aot.md).

The engine's ONE compiled-program cache (docs/engine_perf.md "One
ragged dispatch") keys every device program by

    (total padded query tokens, static page bound, windowed?,
     full-vs-greedy sampler, want_lp, with_spec)

plus the O(log Pmax) page-move gather/scatter bucket family. This
module derives the *complete reachable set* of those keys from an
:class:`~dynamo_exp_tpu.engine.config.EngineConfig` — every bucket the
``*_bucket_for`` helpers can emit, crossed with the boolean axes — as a
deterministic, hashable :class:`CompileManifest`.

One source of truth: :func:`resolve_ragged_key` is called by the
engine's ``_ragged_fn`` for every live dispatch AND by the enumeration
here, so the manifest cannot drift from what the loop dispatches — a
key the engine computes that the lattice failed to enumerate is a
regression the variant-count guard in ``tests/test_ragged_attention.py``
pins.

Everything here is pure (config in, manifest out): no wall clocks, no
``id()``/``uuid``, no environment — the manifest hash must be
byte-identical across processes and hosts for the same
(model, mesh, knobs, jax version) tuple, because it IS the cache
invalidation rule (docs/aot.md "Cache keying & invalidation").
"""

from __future__ import annotations

import hashlib
import json
from dataclasses import asdict, dataclass, field, fields


def resolve_ragged_key(
    cfg,
    attn_impl: str,
    nb: int,
    attn_pages: int | None,
    windowed: bool,
    full_sampler: bool,
    want_lp: bool,
    with_spec: bool = False,
) -> tuple:
    """The ragged variant key for one dispatch shape — the engine's
    ``_ragged_fn`` keying rule, extracted so offline enumeration and
    live dispatch share it verbatim.

    ``attn_impl`` is the engine's *resolved* implementation
    (``TPUEngine._attn_impl``: an explicit ``attention_impl`` is
    honored there, ``auto`` is the kernel on a TPU). The form is the
    whole rule: on the Pallas path the page bound vanishes from the
    key (the kernel DMAs true lengths), which is what deletes the page
    axis from the TPU lattice, windows and mixed dispatches alike; on
    the XLA path the key keeps the page bucket that bounds the gather —
    a window's is its LONGEST row's (``_build_windowed``), so one long
    request sets the gather's width for every row beside it.

    A pure-decode window takes the kernel like any other dispatch of a
    kernel engine. Whole decode steps timed on a TPU v5e in both forms
    at every row bucket, page size 16, contexts of 100-1000 tokens
    (``tools/attn_crossover.py``; the table is in PERF.md §6, PR 30)
    read the kernel no slower at the two head layouts measured: at
    qwen3-4b's 32/8 heads x 128 the gather step is 1.00x the kernel's
    at 1 row, 1.2x at 16, 1.7-1.9x at 32 and 2.8-3.3x at 64; at
    Mixtral's share of a chip under ``--tp 4`` (8/2 heads x 128) 1.00x
    at 1 row to 1.1-1.2x at 64. No other head count, head size or
    page size was measured: ``tools/attn_crossover.py --config
    <preset>`` is the check before resting on this at another layout.
    ``cfg`` is unused and stays for ``benchmark/rehearse/compile_v5e.py``'s
    positional call (ROADMAP D9b).

    A mixed dispatch never took the gather on an engine that has the
    kernel: the reference gathers the whole page bucket once per QUERY
    TOKEN (N x S), which is ruinous for a prefill batch. At llama-1b
    widths on a v5e (compile-only, CHANGES.md PR 21) its temporaries
    are 9 GB at N=2048, and XLA:TPU refuses to compile N >= 4096 in
    16 GB of HBM — a burst of eight 512-token prompts reaches that
    bucket."""
    del cfg
    pages = None if attn_impl == "pallas" else attn_pages
    return (nb, pages, windowed, full_sampler, want_lp, with_spec)


def impl_for_key(key: tuple) -> str:
    """The attention implementation a key's program must be built with:
    a ``None`` page bound is definitionally the Pallas path (it is how
    the bound left the key), anything else is the bounded XLA gather."""
    return "pallas" if key[1] is None else "xla"


# ------------------------------------------------------------ bucket spans
def _pow2_candidates(cap: int) -> list[int]:
    """1, 2, 4, ... up to (and including) ``cap`` — probe points that
    hit every reachable output of a ``_pow2_bucket``-based helper."""
    out, n = [], 1
    while n < cap:
        out.append(n)
        n *= 2
    out.append(max(cap, 1))
    return out


def windowed_token_buckets(cfg) -> list[int]:
    """Every token bucket a pure-decode windowed dispatch can key on
    (1/2/4/.../max_decode_slots, capped at the slot envelope)."""
    return sorted(
        {
            cfg.ragged_tokens_bucket_for(n)
            for n in _pow2_candidates(cfg.max_decode_slots)
        }
    )


def mixed_token_buckets(cfg) -> list[int]:
    """Every flat-stream token bucket a mixed dispatch can key on
    (16-floored powers of two up to ``ragged_max_tokens``)."""
    return sorted(
        {
            cfg.ragged_tokens_bucket_for(n, mixed=True)
            for n in _pow2_candidates(cfg.ragged_max_tokens)
        }
    )


def page_bound_buckets(cfg) -> list[int]:
    """Every static page bound the XLA attention gather can key on."""
    return sorted(
        {
            cfg.ragged_page_bucket_for(p)
            for p in _pow2_candidates(cfg.max_pages_per_seq)
        }
    )


def page_move_buckets(cfg) -> list[int]:
    """Every batched gather/scatter bucket the kv_move/offload family
    can key on. Per-sequence moves (disagg extract/inject, G2 uploads)
    are bounded by ``max_pages_per_seq``, but ``_flush_offloads``
    coalesces eviction bursts ACROSS sequences — one reclaim sweep can
    evict up to the whole pool — so the family is enumerated to
    ``num_pages`` (each extra bucket is one tiny gather/scatter
    compile; missing one would put an inline compile back on a
    warm-booted serving path)."""
    cap = max(cfg.num_pages, cfg.max_pages_per_seq)
    return sorted(
        {cfg.page_move_bucket_for(p) for p in _pow2_candidates(cap)}
    )


# --------------------------------------------------------------- variants
@dataclass(frozen=True)
class RaggedVariant:
    """One ragged compile-lattice entry (== one ``_ragged_fns`` key).
    ``pages=None`` is the Pallas path (no static page bound)."""

    nb: int
    pages: int | None
    windowed: bool
    full_sampler: bool
    want_lp: bool
    with_spec: bool

    @property
    def key(self) -> tuple:
        return (
            self.nb,
            self.pages,
            self.windowed,
            self.full_sampler,
            self.want_lp,
            self.with_spec,
        )

    def to_dict(self) -> dict:
        return asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "RaggedVariant":
        return cls(
            nb=int(d["nb"]),
            pages=None if d.get("pages") is None else int(d["pages"]),
            windowed=bool(d["windowed"]),
            full_sampler=bool(d["full_sampler"]),
            want_lp=bool(d["want_lp"]),
            with_spec=bool(d["with_spec"]),
        )


def ragged_variants(
    cfg,
    attn_impl: str,
    include_lp: bool = True,
    include_spec: bool | None = None,
) -> list[RaggedVariant]:
    """Enumerate the full reachable ragged lattice, deduplicated through
    :func:`resolve_ragged_key` (the Pallas page-bound collapse folds
    enumeration points together exactly as it folds live dispatches
    together).

    ``include_lp=False`` halves the lattice for deployments that never
    serve logprobs; ``include_spec`` defaults to whether the config has
    speculation on (draft-carrying variants only exist then)."""
    if include_spec is None:
        include_spec = cfg.spec_mode != "off"
    lp_axis = (False, True) if include_lp else (False,)
    seen: dict[tuple, RaggedVariant] = {}
    for windowed, nb_buckets in (
        (True, windowed_token_buckets(cfg)),
        (False, mixed_token_buckets(cfg)),
    ):
        spec_axis = (
            (False, True) if (include_spec and not windowed) else (False,)
        )
        for nb in nb_buckets:
            for pages in page_bound_buckets(cfg):
                for full_sampler in (False, True):
                    for want_lp in lp_axis:
                        for with_spec in spec_axis:
                            key = resolve_ragged_key(
                                cfg, attn_impl, nb, pages, windowed,
                                full_sampler, want_lp, with_spec,
                            )
                            if key not in seen:
                                seen[key] = RaggedVariant(*key)
    return sorted(
        seen.values(),
        key=lambda v: (
            not v.windowed,
            v.nb,
            -1 if v.pages is None else v.pages,
            v.full_sampler,
            v.want_lp,
            v.with_spec,
        ),
    )


# --------------------------------------------------------------- manifest
_SCHEMA = 1


@dataclass
class CompileManifest:
    """The deterministic compile-lattice artifact (docs/aot.md).

    ``hash()`` is the cache-invalidation key: it covers everything that
    changes compiled-program bytes or the lattice itself — the model
    config, the mesh shape, the lattice-shaping engine knobs, and the
    jax version. Two processes given the same inputs produce
    byte-identical manifests (and hashes); anything else is a bug the
    determinism tests pin."""

    model: dict
    mesh: dict
    engine: dict
    jax_version: str
    ragged: list[RaggedVariant] = field(default_factory=list)
    move_buckets: list[int] = field(default_factory=list)
    schema: int = _SCHEMA

    def to_dict(self) -> dict:
        return {
            "schema": self.schema,
            "model": self.model,
            "mesh": self.mesh,
            "engine": self.engine,
            "jax_version": self.jax_version,
            "ragged": [v.to_dict() for v in self.ragged],
            "move_buckets": list(self.move_buckets),
        }

    def to_json(self, indent: int | None = None) -> str:
        # sort_keys + no whitespace variance: the serialized form is
        # the hashed form, so it must be canonical.
        return json.dumps(self.to_dict(), sort_keys=True, indent=indent)

    @classmethod
    def from_dict(cls, d: dict) -> "CompileManifest":
        return cls(
            model=dict(d["model"]),
            mesh=dict(d["mesh"]),
            engine=dict(d["engine"]),
            jax_version=str(d["jax_version"]),
            ragged=[RaggedVariant.from_dict(v) for v in d["ragged"]],
            move_buckets=[int(b) for b in d["move_buckets"]],
            schema=int(d.get("schema", _SCHEMA)),
        )

    @classmethod
    def from_json(cls, text: str) -> "CompileManifest":
        return cls.from_dict(json.loads(text))

    def hash(self) -> str:
        return hashlib.sha256(self.to_json().encode()).hexdigest()

    def ragged_keys(self) -> set[tuple]:
        return {v.key for v in self.ragged}

    def __len__(self) -> int:
        return len(self.ragged) + len(self.move_buckets)


# ModelConfig fields younger than manifests in use. The rule, once: such a
# field is named only where a model sets it off its dataclass default, so the
# manifests of the models that do not keep the hash they had before the field
# was. A new field of ModelConfig is appended here.
_LATER_FIELDS = ("mrope_section", "count_expert_reads")


def _model_fingerprint(mcfg) -> dict:
    """Every ModelConfig field, JSON-normalized — a changed head count
    or dtype must change the manifest hash — but a ``_LATER_FIELDS`` one
    at its default."""
    defaults = {f.name: f.default for f in fields(mcfg) if f.name in _LATER_FIELDS}
    out = {}
    for k, v in sorted(asdict(mcfg).items()):
        if k in defaults and v == defaults[k]:
            continue
        out[k] = list(v) if isinstance(v, tuple) else v
    return out


def _attention_form(cfg, attn_impl: str, interpret: bool) -> str:
    """The form(s) of attention the programs hold: a latent model's mixed
    programs carry a second attention call where the kernel engine expands
    rows of many queries (``models/llama.py::latent_form_queries``), so
    executables built before that rule, or at another threshold, are not
    these; a model with an indexer names its selector behind the form(s),
    which both apply."""
    m = cfg.model
    if not m.is_latent:
        # a sliding width the kernel takes as its own argument, and whether
        # the layers' forms have pools of their own (the sliding pool's
        # shape follows from the fields beside this one); behind them what
        # only a model whose forms differ adds: each form's KV heads at the
        # key's and the value's width, and which forms have a sink
        shapes = ""
        if m.kv_heads_by_form or m.value_head_dim_ != m.head_dim_ or m.sink_forms:
            shapes = "-" + "+".join(
                f"{form}:{m.kv_heads_of(form)}x{m.head_dim_}/{m.value_head_dim_}"
                + ("-sink" if form in m.sink_forms else "")
                for form in ("full", "sliding") if form in m.attn_forms
            )
        if attn_impl == "pallas" and "sliding" in m.attn_forms and m.attn_kernel_form:
            return (
                f"heads-sliding-{m.sliding_window}"
                + ("-by-kind" if m.kv_by_kind else "") + shapes
            )
        if m.has_indexer:
            # a grouped-query read masked by the selector, whose key rides
            # the K pool's row from that lane on
            shapes += f"-index-key-at-{m.index_lane0}-selected-top-{m.index_topk}"
        return "heads" + shapes
    # the selector: every read masks what the top-k of the index scores
    # left out, inside whichever call reads the row
    selected = (
        f"-selected-top-{cfg.model.index_topk}" if cfg.model.has_indexer else ""
    )
    from ..models.llama import latent_form_queries

    least = latent_form_queries(
        cfg.model, cfg.ragged_q_tile, cfg.page_size, cfg.kv_dtype_jnp, interpret
    ) if attn_impl == "pallas" else None
    if least is None:
        return f"latent-absorbed{selected}"
    return f"latent-absorbed+expanded-from-{least}-queries{selected}"


def _engine_fingerprint(cfg, attn_impl: str, interpret: bool) -> dict:
    """The EngineConfig knobs that shape compiled-program bytes or the
    lattice: pool/envelope shapes, bucket policies, the resolved
    attention implementation, and the speculation axis."""
    return {
        "max_decode_slots": cfg.max_decode_slots,
        "page_size": cfg.page_size,
        "num_pages": cfg.num_pages,  # KV pool shape is in every program
        "max_model_len": cfg.max_model_len,
        "prefill_chunk": cfg.prefill_chunk,
        "decode_window": cfg.decode_window,
        "device_stop_width": cfg.device_stop_width,
        "kv_dtype": cfg.kv_dtype,
        "attention_impl": attn_impl,
        # what the kernel is told to read: heads of K and V in two pools, or
        # one latent pool, absorbed, and expanded for a row with that many
        # queries in a call; and the pools' row widths
        "attention_form": _attention_form(cfg, attn_impl, interpret),
        "kv_pool_widths": list(cfg.model.kv_pool_widths),
        "pallas_interpret": interpret,
        "ragged_q_tile": cfg.ragged_q_tile,
        "spec_on": cfg.spec_mode != "off",
        "spec_max_draft": cfg.spec_max_draft,
    }


def build_manifest(
    cfg,
    attn_impl: str,
    mesh_shape: dict,
    jax_version: str,
    interpret: bool = False,
    include_lp: bool = True,
    include_spec: bool | None = None,
) -> CompileManifest:
    """Enumerate the full compile lattice for one engine shape.

    ``attn_impl`` must be the engine's *resolved* implementation (the
    ``auto`` decision depends on the device platform, which is part of
    what the manifest pins); ``mesh_shape`` is the engine mesh's
    ``dict(mesh.shape)``."""
    return CompileManifest(
        model=_model_fingerprint(cfg.model),
        mesh={k: int(v) for k, v in sorted(mesh_shape.items())},
        engine=_engine_fingerprint(cfg, attn_impl, interpret),
        jax_version=jax_version,
        ragged=ragged_variants(
            cfg, attn_impl, include_lp=include_lp, include_spec=include_spec
        ),
        move_buckets=page_move_buckets(cfg),
    )
