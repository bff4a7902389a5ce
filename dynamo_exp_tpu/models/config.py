"""Model configuration for the TPU engine's Llama-family transformers.

The reference delegates model definition to wrapped engines (vLLM/sglang/
mistralrs — e.g. ``/root/reference/lib/engines/mistralrs/src/lib.rs:72-164``
loads HF configs). Here the engine is in-process JAX, so the config is
first-class: parsed from HF ``config.json`` and carried by the
ModelDeploymentCard.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field


def _layer_types_of(cfg: dict, model_type: str) -> tuple[str, ...] | None:
    """Per-layer attention kinds. Older gemma3 configs ship only
    ``sliding_window_pattern`` (every Nth layer is full attention, HF:
    ``is_sliding = bool((layer_idx + 1) % pattern)``) — derive the
    explicit list rather than silently treating every layer as sliding
    (which would also rope the full layers at the local base)."""
    explicit = cfg.get("layer_types")
    if explicit:
        return tuple(explicit)
    pattern = cfg.get("sliding_window_pattern")
    if model_type == "gemma3_text" and pattern:
        return tuple(
            "sliding_attention" if (i + 1) % pattern else "full_attention"
            for i in range(cfg.get("num_hidden_layers", 32))
        )
    return None


@dataclass(frozen=True)
class ModelConfig:
    """Decoder-transformer architecture hyperparameters. One config class
    covers the supported families — llama (Llama 2/3,
    DeepSeek-R1-Distill-Llama, TinyLlama), mistral (sliding-window
    attention), qwen2 (QKV bias), qwen3 (per-head q/k norm), gemma
    (gelu FFN, +1 norm offset, scaled embeddings), and the sparse-MoE
    line mixtral / qwen2_moe (shared expert) / qwen3_moe — with family
    differences expressed as fields, not subclasses, so the single
    scan-over-layers forward stays one compiled program per family."""

    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: int | None = None  # defaults to hidden_size // num_heads
    rope_theta: float = 10000.0
    # hash=False: HF rope_scaling is a dict (unhashable); excluded from the
    # dataclass hash so ModelConfig stays usable as a jit static argument.
    rope_scaling: dict | None = field(default=None, hash=False)
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 4096
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    # qwen3: per-head RMSNorm on q and k after projection, before rope.
    qk_norm: bool = False
    # FFN activation: "silu" (llama/qwen/mistral) or "gelu_tanh" (gemma).
    hidden_act: str = "silu"
    # gemma: norm weights are stored as w with scale (1 + w), and the
    # embedding output is scaled by sqrt(hidden_size).
    rms_norm_offset: bool = False
    scale_embeddings: bool = False
    # gemma2: extra norms on the attention and FFN OUTPUTS (4 norms per
    # layer), tanh softcaps on attention scores and final logits, an
    # explicit attention scale, and sliding window on alternating
    # (even) layers only.
    post_norms: bool = False
    attn_logit_softcap: float | None = None
    final_logit_softcap: float | None = None
    query_pre_attn_scalar: float | None = None
    alt_sliding_window: bool = False
    # gemma3: explicit per-layer attention kinds ("sliding_attention" /
    # "full_attention", 5:1 pattern) and a separate rope base for the
    # sliding layers (full layers use rope_theta + rope_scaling).
    layer_types: tuple[str, ...] | None = None
    rope_local_base_freq: float | None = None
    # Mistral: keys older than (q_pos - sliding_window + 1) are masked.
    # None = full causal attention.
    sliding_window: int | None = None
    # Sparse MoE (mixtral/qwen3_moe): 0 experts = dense FFN.
    num_experts: int = 0
    num_experts_per_tok: int = 2
    norm_topk_prob: bool = True
    # qwen3_moe: per-expert ffn width differs from the dense
    # intermediate_size. None = same as intermediate_size (mixtral).
    moe_intermediate_size: int | None = None
    # qwen2_moe: an always-on shared expert of this width, blended via
    # a learned sigmoid gate. None = no shared expert.
    shared_expert_intermediate_size: int | None = None
    # lfm2_moe: layers of two kinds in one stack. ``layer_types`` says
    # which layers' operator is a gated short convolution ("conv") and
    # which attention; the first ``num_dense_layers`` have a dense
    # feed-forward and the rest routed experts. The convolution spans
    # ``conv_L_cache`` tokens, so a sequence carries ``conv_L_cache - 1``
    # products a convolution layer from token to token (``has_seq_state``).
    num_dense_layers: int = 0
    conv_L_cache: int = 3
    # How the router scores and chooses (``ops/moe.py::moe_router``):
    # "softmax" (mixtral, qwen moe) or "sigmoid" (lfm2_moe: sigmoid
    # scores, chosen by score + ``router_bias`` where ``use_expert_bias``,
    # weighed by the unbiased score, times ``routed_scaling_factor``).
    router_score: str = "softmax"
    use_expert_bias: bool = False
    routed_scaling_factor: float = 1.0
    # jamba: layers whose operator is a selective scan (Mamba-1),
    # ``layer_types`` "mamba". Inner width ``mamba_expand * hidden_size``,
    # a depthwise causal convolution of ``mamba_d_conv`` taps ahead of the
    # scan, ``mamba_d_state`` states a channel, the step's projection of
    # rank ``mamba_dt_rank``. A sequence carries the convolution's last
    # ``mamba_d_conv - 1`` inputs and the scan's state
    # ``[inner, mamba_d_state]`` (float32) a layer (``has_seq_state``).
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0
    # Whether attention turns q and k by their positions. jamba's does
    # not: its Mamba layers carry the order of the tokens. Nor does
    # olmo_hybrid's (``rope_theta: null``).
    use_rope: bool = True
    # olmo_hybrid: layers whose operator is linear attention by the gated
    # delta rule (Gated DeltaNet), ``layer_types`` "linear_attention":
    # ``linear_num_heads`` heads of a ``linear_key_head_dim`` by
    # ``linear_value_head_dim`` float32 state, a depthwise causal
    # convolution of ``linear_conv_kernel_dim`` taps ahead of each of q, k
    # and v, a step in (0, 2) where ``linear_allow_neg_eigval`` and in
    # (0, 1) otherwise. A sequence carries the convolutions' last
    # ``linear_conv_kernel_dim - 1`` inputs and the state a layer
    # (``has_seq_state``).
    linear_num_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = False
    # olmo_hybrid's block (Olmo 2's): each half reads the stream un-normed
    # and its *output* is normed before the add: ``pre_norms`` False with
    # ``post_norms``. And its q/k norms span the whole projection
    # (``qk_norm_whole``), not a head.
    pre_norms: bool = True
    qk_norm_whole: bool = False
    # sdar_moe: generation by diffusion over blocks. ``block_length`` B > 0
    # (a power of two) makes a row's step a *pass* over a block of B
    # positions instead of one token: attention is causal across blocks
    # and full inside one, ``logits[i]`` is the distribution of the token
    # AT position ``i`` (no shift), a block starts as ``mask_token_id`` and
    # takes up to ``denoising_steps`` passes, each fixing the positions
    # ``remasking_strategy`` picks by confidence (``ops/sampling.py::
    # denoise_select``), then one pass that writes its final K/V. 0: one
    # token a step, every other family.
    block_length: int = 0
    denoising_steps: int = 0
    remasking_strategy: str = "low_confidence_dynamic"
    confidence_threshold: float = 0.9
    mask_token_id: int = 0
    # pangu_ultra_moe: multi-head latent attention. ``kv_lora_rank`` > 0
    # makes a layer's keys and values one latent a token, ``c_kv``
    # (``kv_lora_rank`` wide, after its norm) beside one rotary key ``k_r``
    # (``qk_rope_head_dim``, after RoPE) shared by every head: what the page
    # pool holds (``latent_width`` values a token and layer, in
    # ``latent_lanes`` lanes) and all it holds. Queries come through a
    # bottleneck of ``q_lora_rank``; a head's key is ``qk_nope_head_dim``
    # expanded from the latent beside the rotary part, its value
    # ``v_head_dim`` expanded likewise (``models/llama.py::_mla_half``).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # glm_moe_dsa: learned sparse attention over the latents. ``index_topk``
    # > 0 gives every layer an *indexer*: ``index_n_heads`` queries of
    # ``index_head_dim`` a token (from the queries' bottleneck) against one
    # key of that width a context token (from the normed stream, after a
    # LayerNorm; the first ``qk_rope_head_dim`` lanes of both rotary),
    # summed over the heads with a weight a head and token; a query attends
    # to the ``index_topk`` context tokens of largest index score and no
    # others. The index keys are the second page pool's rows
    # (``kv_pool_widths``; ``ops/sparse_index.py``). 0: no indexer, every
    # query reads its whole context.
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # KeyeVL2: the same indexer over GROUPED-QUERY attention. There is no
    # queries' bottleneck, so the index queries come from the normed stream
    # too; all ``index_head_dim`` lanes of an index query and key are rotary
    # (``index_rope_dim``); and the index key rides the last
    # ``index_lanes`` lanes of the K pool's row, behind the KV heads' keys
    # (``kv_row_parts``). ``mrope_section`` (not empty): the rotary of q and
    # k is by sections over three position components (temporal, height,
    # width: ``ops/rope.py::apply_mrope``), so many rotary pairs each; a
    # text token's three are equal, which is the plain rotary.
    mrope_section: tuple[int, ...] = ()
    # Experts *held* beside experts routed over: the router scores all
    # ``num_experts``; this device holds ``num_experts_held`` of them, ids
    # ``[first_expert_held, first_expert_held + num_experts_held)`` (one
    # rank's share of an expert-parallel layer), and a pick that lands
    # elsewhere adds nothing here (``ops/moe.py``). 0: all of them.
    num_experts_held: int = 0
    first_expert_held: int = 0
    # A model that holds ALL its experts and yet has its programs count, on
    # the device, the picks and the experts they reach as a held share's do
    # (``counts_picks``): a reader of the grouped matmul's bytes cannot know
    # from a call's shape how many of 128 matrices a crowding router left
    # unread. Set by the readers of the models whose programs were first
    # built with the count (KeyeVL2); the older models' programs stay the
    # executables they were.
    count_expert_reads: bool = False
    # Shared experts without a gate (``n_shared_experts`` SwiGLUs of the
    # routed experts' width, fused into one): added to every token's
    # routed sum as they are. 0: none.
    num_shared_experts: int = 0
    # What a sigmoid router adds to the sum it renormalises by (lfm2_moe
    # 1e-6, pangu_ultra_moe 1e-20).
    router_norm_eps: float = 1e-6
    # exaone_moe: only the sliding layers turn q and k by their positions;
    # a full-attention layer has no positional term (``attn_forms``).
    rope_sliding_only: bool = False
    # mimo_v2_flash: attention's shapes by the layers' form. A sliding
    # layer has ``sliding_num_kv_heads`` KV heads (0: ``num_kv_heads``, as a
    # full layer's), so the two page pools of a cache by kind have rows of
    # their own widths (``kv_pool_widths_of``) and ``wk`` / ``wv`` are
    # stacked by form (``kv_heads_by_form``). Outside latent attention
    # ``v_head_dim`` > 0 is a head's value width where it is not the key's
    # (``value_head_dim_``); the first ``rotary_dim`` values of a head turn
    # by position and the rest stay (0: all of them; the sliding layers at
    # ``rope_local_base_freq``); the values are multiplied by
    # ``value_scale`` ahead of the cache; and the layers of the forms in
    # ``sink_forms`` have a learned logit a query head that joins the
    # softmax's denominator and carries no value (``sink``, float32).
    sliding_num_kv_heads: int = 0
    rotary_dim: int = 0
    value_scale: float = 1.0
    sink_forms: tuple[str, ...] = ()
    # ouro: the whole stack of layers runs ``loop_passes`` times a token over
    # ONE set of weights (a looped language model), the final norm closing
    # every pass and its output entering the next. A token's K and V at a
    # layer differ from pass to pass, so the cache has a layer slot for every
    # (pass, layer) (``kv_layer_slots``). 1: every layer once, every other
    # family.
    loop_passes: int = 1
    # nemotron_h: each of the published layers is ONE half, ``x + mixer(norm
    # x)``, its mixer a Mamba-2 mixer, routed experts or attention. A
    # pre-norm pair ``x += op(norm1 x); x += ff(norm2 x)`` IS two of them, so
    # the stack is held as pairs whose feed-forward may be ABSENT:
    # ``layer_types`` names each pair's operator ("mamba2" or
    # "full_attention") and ``ff_types`` its feed-forward ("routed" or
    # "none"; None: every other family, ``ff_kinds`` by ``num_dense_layers``).
    # ``num_layers`` counts the pairs, ``num_block_layers`` the model's own.
    # The Mamba-2 mixer (``models/llama.py::mamba2_mixer``): ``mamba2_heads``
    # heads of ``mamba2_head_dim`` channels with a scalar decay a head,
    # ``mamba_d_state`` states a channel, B and C in ``mamba2_groups``
    # groups of heads, ONE depthwise convolution of ``mamba_d_conv`` taps
    # over x, B and C side by side (``mamba2_conv_width``), the output gated
    # and then normed a group at a time. A sequence carries the
    # convolution's last inputs and a ``[mamba_d_state, heads * head_dim]``
    # float32 state a Mamba-2 layer (``has_seq_state``).
    ff_types: tuple[str, ...] | None = None
    mamba2_heads: int = 0
    mamba2_head_dim: int = 0
    mamba2_groups: int = 1
    # Experts and shared expert of TWO matrices, ``down(act(up x))``: no gate
    # matrix anywhere in the tree (``w_gate`` / ``shared_gate`` absent).
    gated_experts: bool = True
    # The shared expert's width where it is a key of its own (0:
    # ``num_shared_experts`` experts of the routed width).
    shared_expert_width: int = 0
    dtype: str = "bfloat16"
    model_type: str = "llama"

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def op_kinds(self) -> tuple[str, ...]:
        """Each layer's operator: "conv" (gated short convolution),
        "mamba" (selective scan), "linear" (linear attention by the gated
        delta rule), "mamba2" (a Mamba-2 mixer) or "attn" (every softmax
        attention form, sliding or full: ``attn_forms``)."""
        types = self.layer_types or ("full_attention",) * self.num_layers
        names = {
            "conv": "conv", "mamba": "mamba", "linear_attention": "linear",
            "mamba2": "mamba2",
        }
        return tuple(names.get(t, "attn") for t in types)

    @property
    def attn_forms(self) -> tuple[str, ...]:
        """Each layer's form of softmax attention: "sliding" (a query sees
        the ``sliding_window`` newest keys, its own among them), "full", or
        "" where the operator is not attention. gemma2 alternates
        (``alt_sliding_window``), a model with ``layer_types`` follows
        them, a window without either (mistral) is every layer's."""
        if self.sliding_window is None:
            slides = [False] * self.num_layers
        elif self.layer_types:
            slides = [t == "sliding_attention" for t in self.layer_types]
        else:
            slides = [
                not self.alt_sliding_window or i % 2 == 0
                for i in range(self.num_layers)
            ]
        return tuple(
            ("sliding" if s else "full") if op == "attn" else ""
            for s, op in zip(slides, self.op_kinds)
        )

    @property
    def attn_kernel_form(self) -> bool:
        """Whether the attention kernel (``ops/ragged_attention.py``)
        computes this model's attention: it has neither a tanh cap on the
        scores nor a query pre-scale (gemma2, gemma3: the XLA gather's),
        and a sliding width, where there is one, is one width a run of
        layers (``layer_runs``), not one that alternates inside a run
        (gemma2). The one predicate ``engine.resolve_attn_impl`` and both
        paged forwards ask."""
        return (
            self.attn_logit_softcap is None
            and self.query_pre_attn_scalar is None
            and not (self.sliding_window is not None and self.alt_sliding_window)
        )

    @property
    def kv_by_kind(self) -> bool:
        """Whether the paged cache is two pools by the layers' form, each
        with its own pages and page table a row (``models/llama.py::
        init_kv_cache``): sliding AND full attention layers named by
        ``layer_types`` in a model the kernel serves. A sliding layer's
        pages are given back behind the window
        (``engine/kv_manager.py::SlidingPagePool``), so its pool is sized
        by the dispatch and not by the context. Every other model keeps
        one pool and one table."""
        forms = set(self.attn_forms) - {""}
        return bool(self.layer_types) and forms == {"sliding", "full"} and (
            self.attn_kernel_form
        )

    @property
    def num_sliding_layers(self) -> int:
        return self.attn_forms.count("sliding")

    @property
    def ff_kinds(self) -> tuple[str, ...]:
        """Each layer's feed-forward: "dense", "routed" or "none" (a layer
        of one half: ``ff_types``)."""
        if self.ff_types:
            return self.ff_types
        return tuple(
            "routed" if self.is_moe and i >= self.num_dense_layers else "dense"
            for i in range(self.num_layers)
        )

    @property
    def layer_runs(self) -> tuple[tuple[str, str, int, int, int, int], ...]:
        """The stack as runs of layers of one kind, in order: (operator
        kind, feed-forward kind, first layer, layers, the first layer's
        ordinal among layers of its operator kind, and among layers of its
        feed-forward kind). Parameters are stacked by kind
        (``models/llama.py::init_params``) and the paged layer loop is one
        ``lax.scan`` a run, so a model of one kind is one run. Where the
        cache is by kind (``kv_by_kind``) a run also ends where attention's
        form changes (``attn_forms``: the form is not in the tuple), so a
        run reads one pool at one static width."""
        runs: list[tuple[str, str, int, int, int, int]] = []
        seen = dict.fromkeys(
            ("conv", "mamba", "linear", "mamba2", "attn", "dense", "routed", "none"), 0
        )
        forms = self.attn_forms if self.kv_by_kind else ("",) * self.num_layers
        for i, kind in enumerate(zip(self.op_kinds, self.ff_kinds)):
            if runs and runs[-1][:2] == kind and forms[i] == forms[i - 1]:
                runs[-1] = (*runs[-1][:3], runs[-1][3] + 1, *runs[-1][4:])
            else:
                runs.append((*kind, i, 1, seen[kind[0]], seen[kind[1]]))
            seen[kind[0]] += 1
            seen[kind[1]] += 1
        return tuple(runs)

    @property
    def num_attn_layers(self) -> int:
        """Layers that write K and V: the depth of the attention weights'
        stacks (and of the paged pools, where every layer runs once:
        ``kv_layer_slots``)."""
        return self.op_kinds.count("attn")

    @property
    def kv_layer_slots(self) -> int:
        """Layer slots of the paged pools, the ONE place that says how many
        a page has: a slot for every (pass, attention layer), pass ``t``'s
        layer ``l`` at ``t * num_attn_layers + l``
        (``models/llama.py::init_kv_cache``, ``_scan_paged_layers``). What
        turns "attention layers" into a page's bytes reads this."""
        return self.loop_passes * self.num_attn_layers

    @property
    def num_conv_layers(self) -> int:
        return self.op_kinds.count("conv")

    @property
    def num_mamba_layers(self) -> int:
        return self.op_kinds.count("mamba")

    @property
    def num_linear_layers(self) -> int:
        return self.op_kinds.count("linear")

    @property
    def mamba_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def num_mamba2_layers(self) -> int:
        return self.op_kinds.count("mamba2")

    @property
    def mamba2_inner(self) -> int:
        """Channels of a Mamba-2 mixer: every head's side by side."""
        return self.mamba2_heads * self.mamba2_head_dim

    @property
    def mamba2_conv_width(self) -> int:
        """Channels of a Mamba-2 layer's convolution: x, then every group's
        B, then every group's C."""
        return self.mamba2_inner + 2 * self.mamba2_groups * self.mamba_d_state

    @property
    def num_block_layers(self) -> int:
        """The model's own count of layers (``num_hidden_layers``): a pair
        whose feed-forward is present is two of a model whose layers are one
        half each (``ff_types``), every other model's layer one."""
        if not self.ff_types:
            return self.num_layers
        return self.num_layers + sum(ff != "none" for ff in self.ff_types)

    @property
    def linear_conv_width(self) -> int:
        """Channels of a linear-attention layer's convolutions: q, k and v
        side by side."""
        return self.linear_num_heads * (
            2 * self.linear_key_head_dim + self.linear_value_head_dim
        )

    @property
    def num_routed_layers(self) -> int:
        return self.ff_kinds.count("routed")

    @property
    def has_seq_state(self) -> bool:
        """Whether a sequence holds state that is not a page: what a
        layer whose operator is not softmax attention carries from token to
        token: a convolution's last inputs, a scan's state, linear
        attention's matrix state (``models/llama.py::init_seq_state`` has
        the parts by kind). What
        the tiers that move pages alone refuse
        (``engine/config.py::refuse_seq_state_tiers``)."""
        return self.num_attn_layers < self.num_layers

    @property
    def expert_intermediate_size(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def expert_stack_width(self) -> int:
        """The width the expert matrices are STORED at: the experts' own,
        brought up to whole groups of 128 lanes where it is past one group
        and not whole (nemotron_h's 1856 -> 1920), the added columns of
        ``w_up`` and rows of ``w_down`` zeros, which add exactly nothing. A
        width that is no multiple of 128 the grouped-matmul kernel cannot
        tile (``ops/expert_gmm.py::supported``), and XLA:TPU lays an array
        whose last dimension is none out with another dimension on the
        lanes, so that every kernel call would first copy the whole stack."""
        width = self.expert_intermediate_size
        return width if width % 128 == 0 or width < 128 else -(-width // 128) * 128

    @property
    def experts_held(self) -> int:
        """Routed experts whose matrices this device holds."""
        return self.num_experts_held or self.num_experts

    @property
    def counts_picks(self) -> bool:
        """Whether the paged programs return, last, the live rows' picks
        that landed on experts held here and the held experts that had one
        (``models/llama.py::forward``'s ``count_picks``): a model that holds
        a share of its experts, or one that asks (``count_expert_reads``)."""
        return bool(self.num_experts and (self.num_experts_held or self.count_expert_reads))

    @property
    def shared_width(self) -> int:
        """Width of the shared expert that is added as it is (0: none)."""
        return self.shared_expert_width or (
            self.num_shared_experts * self.expert_intermediate_size
        )

    @property
    def is_latent(self) -> bool:
        """Whether attention is multi-head latent attention."""
        return self.kv_lora_rank > 0

    @property
    def latent_width(self) -> int:
        """Values a token and layer of a latent cache: ``[c_kv | k_r]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_lanes(self) -> int:
        """``latent_width`` padded to whole groups of 128 lanes: the width
        of a latent pool's row and of the absorbed query (the padding is
        zeros on both sides of the score product)."""
        return -(-self.latent_width // 128) * 128

    @property
    def has_indexer(self) -> bool:
        """Whether attention reads a learned selection of its context
        (``index_topk``) and not all of it."""
        return self.index_topk > 0

    @property
    def index_lanes(self) -> int:
        """``index_head_dim`` padded to whole groups of 128 lanes: the
        lanes an index key takes in the cache (``kv_row_parts``; 0: no
        indexer)."""
        return -(-self.index_head_dim // 128) * 128 if self.has_indexer else 0

    @property
    def index_rope_dim(self) -> int:
        """Lanes of an index query and key that turn by position, the
        first ones: the rotary key's width under latent attention
        (DeepSeek-V3.2's), all of them under grouped-query attention."""
        return self.qk_rope_head_dim if self.is_latent else self.index_head_dim

    @property
    def index_key_in_k_row(self) -> bool:
        """Whether the index key rides the K pool's row behind the heads'
        keys: a grouped-query model with an indexer, which so caches three
        things a token. (A latent model's index key has a pool of its own.)"""
        return self.has_indexer and not self.is_latent

    @property
    def index_lane0(self) -> int:
        """The first lane of the index key in the row of the pool that
        holds it, from ``kv_row_parts``: 0 in a pool of its own (latent
        attention), behind the KV heads' keys in the K pool's row
        (``index_key_in_k_row``)."""
        for pool in self.kv_row_parts():
            parts = [part for part, _ in pool]
            if "index" in parts:
                return sum(lanes for _, lanes in pool[: parts.index("index")])
        return 0

    def kv_row_parts(self, form: str = "full") -> tuple[tuple[tuple[str, int], ...], ...]:
        """What a token and layer's cache row holds, pool by pool, as
        (part, lanes) in lane order: the ONE place that says where each
        cached thing lies (``kv_pool_widths_of`` sums it). A latent model:
        ``(("latent", latent_lanes),)`` and, with an indexer, ``(("index",
        index_lanes),)`` in the second pool, else nothing there. A
        grouped-query model: ``(("k", Hkv x D),)`` and ``(("v", Hkv x
        Dv),)``; with an indexer the index key rides the K pool's row:
        ``(("k", Hkv x D), ("index", index_lanes))``."""
        index = (("index", self.index_lanes),) if self.has_indexer else ()
        if self.is_latent:
            return (("latent", self.latent_lanes),), index
        heads = self.kv_heads_of(form)
        return (
            (("k", heads * self.head_dim_), *index),
            (("v", heads * self.value_head_dim_),),
        )

    @property
    def kv_pool_widths(self) -> tuple[int, int]:
        """Lanes of a token's row in the two page pools
        (``models/llama.py::init_kv_cache``): K's and V's heads side by
        side (a grouped-query model's index key behind K's), or the latent
        row and, where the model has an indexer, its key (else nothing: the
        values are the latent row's first ``kv_lora_rank`` lanes either
        way). Of a cache by kind: the full layers' pools
        (``kv_pool_widths_of``)."""
        return self.kv_pool_widths_of("full")

    def kv_pool_widths_of(self, form: str) -> tuple[int, int]:
        """``kv_pool_widths`` of the pools that the layers of ``form``
        ("full" / "sliding") write: a head's keys at ``head_dim_`` lanes
        and its values at ``value_head_dim_``, ``kv_heads_of(form)`` heads
        side by side (``kv_row_parts``)."""
        first, second = self.kv_row_parts(form)
        return sum(n for _, n in first), sum(n for _, n in second)

    def kv_heads_of(self, form: str) -> int:
        """KV heads of a layer whose attention is of ``form``."""
        if form == "sliding" and self.sliding_num_kv_heads:
            return self.sliding_num_kv_heads
        return self.num_kv_heads

    @property
    def kv_heads_by_form(self) -> bool:
        """Whether sliding and full layers differ in their KV heads: then
        ``wk`` / ``wv`` hold the full layers' projections and ``swa_wk`` /
        ``swa_wv`` the sliding layers', each stacked over its form's layers
        (``models/llama.py::init_params``), and the cache is by kind."""
        return self.kv_heads_of("sliding") != self.num_kv_heads

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def value_head_dim_(self) -> int:
        """A head's value width outside latent attention: the key's unless
        ``v_head_dim`` names another."""
        return self.v_head_dim or self.head_dim_

    @property
    def rope_dim(self) -> int:
        """Values of a head (of the rotary key, in latent attention) that
        turn by position: what the inverse frequencies are made for."""
        if self.is_latent:
            return self.qk_rope_head_dim
        return self.rotary_dim or self.head_dim_

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // self.num_kv_heads

    @classmethod
    def from_hf_config(cls, cfg: dict) -> "ModelConfig":
        """Build from a HuggingFace ``config.json`` dict. Family quirks:
        qwen2 always carries QKV bias (its HF config has no
        ``attention_bias`` key); mistral/mixtral carry ``sliding_window``;
        mixtral's experts are ``num_local_experts``."""
        model_type = cfg.get("model_type", "llama")
        if model_type not in KNOWN_MODEL_TYPES:
            # Every family is this one class with other field values, so
            # an unknown family's config would build *something*: a
            # uniform softmax-routed llama under another model's name.
            raise ValueError(
                f"unsupported model_type {model_type!r}; supported: "
                f"{', '.join(sorted(KNOWN_MODEL_TYPES))}"
            )
        if model_type == "lfm2_moe":
            return cls._from_lfm2_moe(cfg)
        if model_type == "jamba":
            return cls._from_jamba(cfg)
        if model_type == "olmo_hybrid":
            return cls._from_olmo_hybrid(cfg)
        if model_type == "pangu_ultra_moe":
            return cls._from_pangu_ultra_moe(cfg)
        if model_type == "glm_moe_dsa":
            return cls._from_glm_moe_dsa(cfg)
        if model_type == "exaone_moe":
            return cls._from_exaone_moe(cfg)
        if model_type == "mimo_v2_flash":
            return cls._from_mimo_v2_flash(cfg)
        if model_type == "ouro":
            return cls._from_ouro(cfg)
        if model_type == "KeyeVL2":
            return cls._from_keye_vl2(cfg)
        if model_type == "nemotron_h":
            return cls._from_nemotron_h(cfg)
        if model_type in ("qwen2_moe", "qwen3_moe", "sdar_moe") and (
            cfg.get("mlp_only_layers") or cfg.get("decoder_sparse_step", 1) != 1
        ):
            # Per-layer dense/sparse mixing stores mlp.gate_proj for the
            # dense layers — the stacked-scan loader assumes a uniform
            # layer shape; fail loudly here instead of a bare KeyError
            # deep in the tensor loop.
            raise ValueError(
                "qwen MoE checkpoints with mlp_only_layers / "
                "decoder_sparse_step != 1 (mixed dense+sparse layers) "
                "are not supported"
            )
        return cls(
            vocab_size=cfg.get("vocab_size", 32000),
            hidden_size=cfg.get("hidden_size", 4096),
            intermediate_size=cfg.get("intermediate_size", 11008),
            num_layers=cfg.get("num_hidden_layers", 32),
            num_heads=cfg.get("num_attention_heads", 32),
            num_kv_heads=cfg.get(
                "num_key_value_heads", cfg.get("num_attention_heads", 32)
            ),
            head_dim=cfg.get("head_dim"),
            rope_theta=cfg.get("rope_theta", 10000.0),
            rope_scaling=cfg.get("rope_scaling"),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            max_position_embeddings=cfg.get("max_position_embeddings", 4096),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            attention_bias=cfg.get(
                "attention_bias", model_type in ("qwen2", "qwen2_moe")
            ),
            qk_norm=model_type in ("qwen3", "qwen3_moe", "sdar_moe", "gemma3_text"),
            hidden_act=(
                "gelu_tanh"
                if str(
                    cfg.get("hidden_activation")
                    or cfg.get("hidden_act", "silu")
                ).startswith("gelu")
                else "silu"
            ),
            rms_norm_offset=model_type in ("gemma", "gemma2", "gemma3_text"),
            scale_embeddings=model_type in ("gemma", "gemma2", "gemma3_text"),
            post_norms=model_type in ("gemma2", "gemma3_text"),
            attn_logit_softcap=cfg.get("attn_logit_softcapping"),
            final_logit_softcap=cfg.get("final_logit_softcapping"),
            query_pre_attn_scalar=cfg.get("query_pre_attn_scalar"),
            alt_sliding_window=model_type == "gemma2",
            layer_types=_layer_types_of(cfg, model_type),
            rope_local_base_freq=cfg.get("rope_local_base_freq"),
            # qwen2 ships a sliding_window value with
            # use_sliding_window=false — honour the switch, or every
            # HF-loaded qwen2 would lose the Pallas decode path and
            # ring prefill for a window it never uses.
            sliding_window=(
                cfg.get("sliding_window")
                if cfg.get("use_sliding_window", True)
                else None
            ),
            num_experts=cfg.get(
                "num_local_experts", cfg.get("num_experts", 0)
            ) or 0,
            num_experts_per_tok=cfg.get("num_experts_per_tok", 2),
            # HF defaults differ per family: Mixtral always renormalizes
            # top-k router weights; Qwen2Moe/Qwen3Moe default to False
            # when config.json omits the key.
            norm_topk_prob=cfg.get("norm_topk_prob", model_type == "mixtral"),
            moe_intermediate_size=cfg.get("moe_intermediate_size"),
            shared_expert_intermediate_size=cfg.get(
                "shared_expert_intermediate_size"
            ),
            dtype=cfg.get("torch_dtype", "bfloat16"),
            model_type=model_type,
            **(_block_generation(cfg) if model_type == "sdar_moe" else {}),
        )

    @classmethod
    def _from_lfm2_moe(cls, cfg: dict) -> "ModelConfig":
        """LiquidAI's ``lfm2_moe`` (LFM2-8B-A1B): the keys are its own
        (``norm_eps``, ``num_experts``, ``conv_L_cache``...). The tied
        head is the family's convention (its config class defaults it and
        the published file leaves the key out)."""
        if cfg.get("conv_bias", False):
            raise ValueError("lfm2_moe with conv_bias=true is not supported")
        if cfg.get("conv_L_cache", 3) < 2:
            raise ValueError("lfm2_moe needs conv_L_cache >= 2")
        n_layers = cfg["num_hidden_layers"]
        layer_types = tuple(cfg["layer_types"])
        if len(layer_types) != n_layers or not set(layer_types) <= {
            "conv", "full_attention"
        }:
            raise ValueError(
                f"lfm2_moe layer_types must name {n_layers} layers as "
                f"'conv' or 'full_attention', got {layer_types!r}"
            )
        return cls(
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=n_layers,
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg.get("head_dim"),
            rope_theta=cfg.get("rope_theta", 1000000.0),
            rms_norm_eps=cfg.get("norm_eps", 1e-5),
            max_position_embeddings=cfg.get("max_position_embeddings", 128000),
            tie_word_embeddings=cfg.get(
                "tie_word_embeddings", cfg.get("tie_embedding", True)
            ),
            qk_norm=True,
            layer_types=layer_types,
            num_experts=cfg["num_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            norm_topk_prob=cfg.get("norm_topk_prob", True),
            moe_intermediate_size=cfg["moe_intermediate_size"],
            num_dense_layers=cfg.get("num_dense_layers", 0),
            conv_L_cache=cfg.get("conv_L_cache", 3),
            router_score="sigmoid",
            use_expert_bias=cfg.get("use_expert_bias", True),
            routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
            dtype=cfg.get("torch_dtype", "bfloat16"),
            model_type="lfm2_moe",
        )

    @classmethod
    def _from_jamba(cls, cfg: dict) -> "ModelConfig":
        """AI21's ``jamba`` (Jamba2-3B): layer ``i`` is attention where
        ``i % attn_layer_period == attn_layer_offset`` and a Mamba-1 mixer
        otherwise; attention has no positional term, no bias and no q/k
        norm; every feed-forward is a dense SwiGLU unless ``num_experts``
        > 1 (the routed Jamba models, which are refused: their expert
        layers alternate by ``expert_layer_period``, a table this class
        does not hold)."""
        if cfg.get("num_experts", 1) > 1:
            raise ValueError(
                "jamba with num_experts > 1 (expert_layer_period/offset "
                "choosing routed layers) is not supported"
            )
        if cfg.get("mamba_proj_bias", False):
            raise ValueError("jamba with mamba_proj_bias=true is not supported")
        if not cfg.get("mamba_conv_bias", True):
            raise ValueError("jamba with mamba_conv_bias=false is not supported")
        if cfg.get("sliding_window") is not None:
            raise ValueError("jamba with a sliding_window is not supported")
        if str(cfg.get("hidden_act", "silu")) != "silu":
            raise ValueError("jamba with hidden_act other than silu is not supported")
        n_layers = cfg["num_hidden_layers"]
        period, offset = cfg["attn_layer_period"], cfg["attn_layer_offset"]
        hidden = cfg["hidden_size"]
        dt_rank = cfg.get("mamba_dt_rank", "auto")
        return cls(
            vocab_size=cfg["vocab_size"],
            hidden_size=hidden,
            intermediate_size=cfg["intermediate_size"],
            num_layers=n_layers,
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg.get("head_dim"),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-6),
            max_position_embeddings=cfg.get("max_position_embeddings", 262144),
            tie_word_embeddings=cfg.get("tie_word_embeddings", True),
            layer_types=tuple(
                "full_attention" if i % period == offset else "mamba"
                for i in range(n_layers)
            ),
            mamba_d_state=cfg.get("mamba_d_state", 16),
            mamba_d_conv=cfg.get("mamba_d_conv", 4),
            mamba_expand=cfg.get("mamba_expand", 2),
            mamba_dt_rank=(
                -(-hidden // 16) if dt_rank == "auto" else int(dt_rank)
            ),
            use_rope=False,
            dtype=cfg.get("torch_dtype", "bfloat16"),
            model_type="jamba",
        )

    @classmethod
    def _from_olmo_hybrid(cls, cfg: dict) -> "ModelConfig":
        """Ai2's ``olmo_hybrid`` (Olmo-Hybrid-7B): layer ``i`` is what
        ``layer_types[i]`` says, "linear_attention" (the gated delta rule,
        its widths under the ``linear_*`` keys as Qwen3-Next spells them)
        or "full_attention" (multi-head, no positional term: the config
        gives ``rope_theta: null``, and a base that is given is refused);
        every layer in Olmo 2's block (each half's output normed, its input
        not; q/k norms over the whole projection); every feed-forward a
        dense SwiGLU."""
        rope = cfg.get("rope_parameters") or {}
        if cfg.get("rope_theta", rope.get("rope_theta")) is not None:
            raise ValueError(
                "olmo_hybrid with a rope_theta is not supported: the family's "
                "attention has no positional term (rope_theta: null)"
            )
        if str(cfg.get("hidden_act", "silu")) != "silu":
            raise ValueError("olmo_hybrid with hidden_act other than silu is not supported")
        if cfg.get("attention_bias", False):
            raise ValueError("olmo_hybrid with attention_bias=true is not supported")
        n_layers = cfg["num_hidden_layers"]
        layer_types = tuple(cfg["layer_types"])
        if len(layer_types) != n_layers or not set(layer_types) <= {
            "linear_attention", "full_attention"
        }:
            raise ValueError(
                f"olmo_hybrid layer_types must name {n_layers} layers as "
                f"'linear_attention' or 'full_attention', got {layer_types!r}"
            )
        heads = cfg["linear_num_value_heads"]
        if cfg["linear_num_key_heads"] != heads:
            raise ValueError(
                "olmo_hybrid with linear_num_key_heads != linear_num_value_heads "
                "(value heads sharing a key head) is not supported"
            )
        if cfg.get("linear_conv_kernel_dim", 4) < 2:
            raise ValueError("olmo_hybrid needs linear_conv_kernel_dim >= 2")
        return cls(
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=n_layers,
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg.get("num_key_value_heads", cfg["num_attention_heads"]),
            head_dim=cfg.get("head_dim"),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-6),
            max_position_embeddings=cfg.get("max_position_embeddings", 65536),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            qk_norm=True,
            qk_norm_whole=True,
            pre_norms=False,
            post_norms=True,
            layer_types=layer_types,
            linear_num_heads=heads,
            linear_key_head_dim=cfg["linear_key_head_dim"],
            linear_value_head_dim=cfg["linear_value_head_dim"],
            linear_conv_kernel_dim=cfg.get("linear_conv_kernel_dim", 4),
            linear_allow_neg_eigval=bool(cfg.get("linear_allow_neg_eigval", False)),
            use_rope=False,
            dtype=cfg.get("torch_dtype", "bfloat16"),
            model_type="olmo_hybrid",
        )

    @classmethod
    def _from_pangu_ultra_moe(cls, cfg: dict) -> "ModelConfig":
        """``pangu_ultra_moe`` (openPangu-Ultra-MoE-718B): multi-head
        latent attention in every layer; ``first_k_dense_replace`` leading
        dense SwiGLUs, then ``n_routed_experts`` experts chosen top-k by a
        sigmoid router (no selection bias, no group limit), the chosen
        scores renormalised and times ``routed_scaling_factor``, beside
        ``n_shared_experts`` ungated shared experts; four norms a layer
        (``sandwich_norm``). ``n_routed_experts`` is what this device
        *holds*: where the file also gives ``n_routed_experts_global`` (an
        expert-parallel rank's share: ``benchmark/configs/openpangu-*``),
        the router is that wide and the held ids start at
        ``first_routed_expert``. The multi-token-prediction module
        (``num_nextn_predict_layers``) is refused: nothing here drafts
        from it."""
        if not cfg.get("sandwich_norm", True):
            raise ValueError("pangu_ultra_moe with sandwich_norm=false is not supported")
        if cfg.get("rope_scaling"):
            raise ValueError("pangu_ultra_moe with rope_scaling is not supported")
        held, routed, first = _latent_moe_share(cfg, "pangu_ultra_moe")
        return cls(
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            # one latent a token stands for every head's key and value
            num_kv_heads=1,
            head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            rope_theta=cfg.get("rope_theta", 10000.0),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            max_position_embeddings=cfg.get("max_position_embeddings", 131072),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            post_norms=True,
            num_experts=routed,
            num_experts_held=held if held < routed else 0,
            first_expert_held=first,
            num_experts_per_tok=cfg["num_experts_per_tok"],
            norm_topk_prob=cfg.get("norm_topk_prob", True),
            moe_intermediate_size=cfg["moe_intermediate_size"],
            num_shared_experts=cfg.get("n_shared_experts", 0) or 0,
            num_dense_layers=cfg.get("first_k_dense_replace", 0),
            router_score="sigmoid",
            router_norm_eps=1e-20,
            routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
            q_lora_rank=cfg["q_lora_rank"],
            kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"],
            dtype=cfg.get("torch_dtype", "bfloat16"),
            model_type="pangu_ultra_moe",
        )

    @classmethod
    def _from_glm_moe_dsa(cls, cfg: dict) -> "ModelConfig":
        """``glm_moe_dsa`` (GLM-5): multi-head latent attention in every
        layer whose queries read a learned selection of their context (an
        indexer a layer: ``index_n_heads`` x ``index_head_dim``, the
        ``index_topk`` context tokens of largest index score);
        ``first_k_dense_replace`` leading dense SwiGLUs, then
        ``n_routed_experts`` experts chosen top-k by ``sigmoid`` scores plus
        a selection bias (``noaux_tc``: ``e_score_correction_bias``), the
        chosen unbiased scores renormalised and times
        ``routed_scaling_factor``, beside ``n_shared_experts`` ungated
        shared experts; two norms a layer. ``n_routed_experts`` /
        ``n_routed_experts_global`` / ``first_routed_expert`` as
        ``_from_pangu_ultra_moe`` reads them (an expert-parallel rank's
        share). Refused, because nothing here computes it: the
        multi-token-prediction module, group-limited selection, a scaled
        rotary base, a bias on attention's projections, another
        activation than silu, another scoring than sigmoid."""
        name = "glm_moe_dsa"
        rope = cfg.get("rope_parameters") or {}
        if cfg.get("rope_scaling") or rope.get("rope_type", "default") != "default":
            raise ValueError(f"{name} with rope_scaling is not supported")
        if str(cfg.get("scoring_func", "sigmoid")) != "sigmoid":
            raise ValueError(f"{name} with scoring_func other than sigmoid is not supported")
        held, routed, first = _latent_moe_share(
            cfg, name, widths=("index_n_heads", "index_head_dim")
        )
        if int(cfg.get("index_topk") or 0) < 1:
            raise ValueError(f"{name} needs index_topk >= 1")
        if cfg["index_head_dim"] < cfg["qk_rope_head_dim"]:
            raise ValueError(f"{name} needs index_head_dim >= qk_rope_head_dim")
        return cls(
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=1,  # one latent a token stands for every head
            head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            # the published file keeps the base under ``rope_parameters``
            rope_theta=float(rope.get("rope_theta") or cfg.get("rope_theta", 10000.0)),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            max_position_embeddings=cfg.get("max_position_embeddings", 131072),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            num_experts=routed,
            num_experts_held=held if held < routed else 0,
            first_expert_held=first,
            num_experts_per_tok=cfg["num_experts_per_tok"],
            norm_topk_prob=cfg.get("norm_topk_prob", True),
            moe_intermediate_size=cfg["moe_intermediate_size"],
            num_shared_experts=cfg.get("n_shared_experts", 0) or 0,
            num_dense_layers=cfg.get("first_k_dense_replace", 0),
            router_score="sigmoid",
            use_expert_bias=True,
            router_norm_eps=1e-20,
            routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
            q_lora_rank=cfg["q_lora_rank"],
            kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"],
            index_n_heads=cfg["index_n_heads"],
            index_head_dim=cfg["index_head_dim"],
            index_topk=cfg["index_topk"],
            dtype=cfg.get("torch_dtype", "bfloat16"),
            model_type=name,
        )

    @classmethod
    def _from_exaone_moe(cls, cfg: dict) -> "ModelConfig":
        """``exaone_moe`` (K-EXAONE-236B-A23B): grouped-query attention with
        a per-head RMS norm on q and k; layer ``i`` is what ``layer_types[i]``
        says, "sliding_attention" (a query sees the ``sliding_window``
        newest keys, q and k turned by their positions) or "full_attention"
        (the whole context and NO positional term: ``rope_sliding_only``);
        two norms a layer, before each half; ``first_k_dense_replace``
        leading dense SwiGLUs (``mlp_layer_types`` has to say the same),
        then ``num_experts`` experts chosen top-k by sigmoid scores plus a
        selection bias, the chosen unbiased scores renormalised and times
        ``routed_scaling_factor``, beside ``num_shared_experts`` ungated
        shared experts. ``num_experts`` is what this device HOLDS: where the
        file also gives ``num_experts_global`` (an expert-parallel rank's
        share: ``benchmark/configs/k-exaone-*``) the router is that wide and
        the held ids start at ``first_routed_expert``. Refused, because
        nothing here computes it: the multi-token-prediction module,
        group-limited selection, a scaled rotary base, a bias on attention's
        projections, another activation than silu, another scoring than
        sigmoid."""
        name = "exaone_moe"
        rope = cfg.get("rope_parameters") or {}
        if cfg.get("rope_scaling") or rope.get("rope_type", "default") != "default":
            raise ValueError(f"{name} with a rope_type other than default is not supported")
        if str(cfg.get("scoring_func", "sigmoid")) != "sigmoid":
            raise ValueError(f"{name} with scoring_func other than sigmoid is not supported")
        _refuse_unserved_moe(cfg, name)
        n_layers = cfg["num_hidden_layers"]
        layer_types = tuple(cfg["layer_types"])
        if len(layer_types) != n_layers or not set(layer_types) <= {
            "sliding_attention", "full_attention"
        }:
            raise ValueError(
                f"{name} layer_types must name {n_layers} layers as "
                f"'sliding_attention' or 'full_attention', got {layer_types!r}"
            )
        width = cfg.get("sliding_window")
        slides = "sliding_attention" in layer_types
        if slides and int(width or 0) < 1:
            raise ValueError(f"{name} with sliding layers needs sliding_window >= 1")
        widths = cfg.get("sliding_windows")
        if widths is not None and list(widths) != [
            width if t == "sliding_attention" else 0 for t in layer_types
        ]:
            raise ValueError(
                f"{name} sliding_windows {list(widths)!r} disagrees with "
                f"layer_types x sliding_window {width}"
            )
        dense = int(cfg.get("first_k_dense_replace", 0))
        ff = cfg.get("mlp_layer_types")
        if ff is not None and list(ff) != (
            ["dense"] * dense + ["sparse"] * (n_layers - dense)
        ):
            raise ValueError(
                f"{name} mlp_layer_types must be first_k_dense_replace={dense} "
                f"'dense' layers then 'sparse' ones, got {list(ff)!r}"
            )
        held, routed, first = _expert_share(
            cfg, name, "num_experts", "num_experts_global"
        )
        return cls(
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=n_layers,
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg.get("head_dim"),
            rope_theta=float(rope.get("rope_theta") or cfg.get("rope_theta", 10000.0)),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            max_position_embeddings=cfg.get("max_position_embeddings", 262144),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            qk_norm=True,
            layer_types=layer_types,
            sliding_window=int(width) if slides else None,
            rope_sliding_only=True,
            num_experts=routed,
            num_experts_held=held if held < routed else 0,
            first_expert_held=first,
            num_experts_per_tok=cfg["num_experts_per_tok"],
            norm_topk_prob=cfg.get("norm_topk_prob", True),
            moe_intermediate_size=cfg["moe_intermediate_size"],
            num_shared_experts=cfg.get("num_shared_experts", 0) or 0,
            num_dense_layers=dense,
            router_score="sigmoid",
            use_expert_bias=True,
            router_norm_eps=1e-20,
            routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
            dtype=cfg.get("torch_dtype", "bfloat16"),
            model_type=name,
        )

    @classmethod
    def _from_mimo_v2_flash(cls, cfg: dict) -> "ModelConfig":
        """``mimo_v2_flash`` (MiMo-V2-Flash): layer ``i`` is full attention
        where ``hybrid_layer_pattern[i]`` is 0 and sliding (``sliding_window``
        keys, the query's own among them) where 1. The two forms have KV
        head counts of their own (``num_key_value_heads`` /
        ``swa_num_key_value_heads``), keys of ``head_dim`` and values of
        ``v_head_dim`` (narrower), the values times
        ``attention_value_scale``; the first ``int(head_dim *
        partial_rotary_factor)`` values of a head turn by position, at
        ``rope_theta`` in full layers and ``swa_rope_theta`` in sliding
        ones; a form with ``add_*_attention_sink_bias`` has a learned sink
        logit a query head. Two norms a layer (``layernorm_epsilon``); the
        leading layers whose ``moe_layer_freq`` is 0 are dense SwiGLUs, the
        rest ``n_routed_experts`` experts top-k by sigmoid scores plus a
        selection bias, the chosen unbiased scores renormalised
        (``norm_topk_prob``), with no shared expert unless
        ``n_shared_experts`` says so. ``n_routed_experts`` is what this
        device HOLDS where the file also gives ``n_routed_experts_global``
        (``_expert_share``). Refused, because nothing here computes it or
        no test has shown it: query heads or head sizes that differ by
        form, a dense layer behind a routed one, another selection than
        ``noaux_tc``, a rotary scaling, and what ``_refuse_unserved_moe``
        names."""
        name = "mimo_v2_flash"
        for mine, swa in (
            ("num_attention_heads", "swa_num_attention_heads"),
            ("head_dim", "swa_head_dim"),
            ("v_head_dim", "swa_v_head_dim"),
        ):
            if cfg.get(swa, cfg[mine]) != cfg[mine]:
                raise ValueError(
                    f"{name} with {swa}={cfg[swa]} != {mine}={cfg[mine]} is not "
                    "supported: only the KV-head count differs by layer kind"
                )
        scaling = cfg.get("rope_scaling") or {}
        if scaling.get("rope_type", scaling.get("type", "default")) != "default":
            raise ValueError(f"{name} with a rope_type other than default is not supported")
        if str(cfg.get("scoring_func", "sigmoid")) != "sigmoid":
            raise ValueError(f"{name} with scoring_func other than sigmoid is not supported")
        if str(cfg.get("topk_method", "noaux_tc")) != "noaux_tc":
            raise ValueError(f"{name} with topk_method other than noaux_tc is not supported")
        _refuse_unserved_moe(cfg, name)
        n_layers = cfg["num_hidden_layers"]
        pattern, freq = list(cfg["hybrid_layer_pattern"]), list(cfg["moe_layer_freq"])
        if len(pattern) != n_layers or not set(pattern) <= {0, 1}:
            raise ValueError(
                f"{name} hybrid_layer_pattern must name {n_layers} layers as 0 "
                f"(full) or 1 (sliding), got {pattern!r}"
            )
        dense = freq.index(1) if 1 in freq else len(freq)
        if len(freq) != n_layers or freq != [0] * dense + [1] * (n_layers - dense):
            raise ValueError(
                f"{name} moe_layer_freq must be leading 0s (dense) then 1s "
                f"(routed) over {n_layers} layers, got {freq!r}"
            )
        slides = 1 in pattern
        width = cfg.get("sliding_window")
        if slides and int(width or 0) < 1:
            raise ValueError(f"{name} with sliding layers needs sliding_window >= 1")
        if cfg.get("sliding_window_size", width) != width:
            raise ValueError(f"{name} sliding_window_size disagrees with sliding_window")
        hd = cfg["head_dim"]
        dv = cfg.get("v_head_dim", hd)
        rotary = int(hd * float(cfg.get("partial_rotary_factor", 1.0)))
        if rotary < 2 or rotary % 2:
            raise ValueError(f"{name} turns {rotary} of {hd} values a head: not a whole number of pairs")
        held, routed, first = _expert_share(
            cfg, name, "n_routed_experts", "n_routed_experts_global"
        )
        scale = cfg.get("routed_scaling_factor")
        return cls(
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=n_layers,
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            sliding_num_kv_heads=(
                cfg.get("swa_num_key_value_heads", cfg["num_key_value_heads"])
                if slides else 0
            ),
            head_dim=hd,
            v_head_dim=dv if dv != hd else 0,
            rotary_dim=rotary if rotary != hd else 0,
            value_scale=float(cfg.get("attention_value_scale") or 1.0),
            rope_theta=float(cfg.get("rope_theta", 10000.0)),
            rope_local_base_freq=(
                float(cfg["swa_rope_theta"])
                if slides and cfg.get("swa_rope_theta") is not None else None
            ),
            rms_norm_eps=cfg.get("layernorm_epsilon", 1e-5),
            max_position_embeddings=cfg.get("max_position_embeddings", 262144),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            layer_types=tuple(
                "sliding_attention" if p else "full_attention" for p in pattern
            ),
            sliding_window=int(width) if slides else None,
            sink_forms=tuple(
                form for form, key in (
                    ("full", "add_full_attention_sink_bias"),
                    ("sliding", "add_swa_attention_sink_bias"),
                ) if cfg.get(key, False) and (form == "full" or slides)
            ),
            num_experts=routed,
            num_experts_held=held if held < routed else 0,
            first_expert_held=first,
            num_experts_per_tok=cfg["num_experts_per_tok"],
            norm_topk_prob=cfg.get("norm_topk_prob", True),
            moe_intermediate_size=cfg["moe_intermediate_size"],
            num_shared_experts=cfg.get("n_shared_experts") or 0,
            num_dense_layers=dense,
            router_score="sigmoid",
            use_expert_bias=True,
            router_norm_eps=1e-20,
            routed_scaling_factor=1.0 if scale is None else float(scale),
            dtype=cfg.get("torch_dtype", "bfloat16"),
            model_type=name,
        )

    @classmethod
    def _from_ouro(cls, cfg: dict) -> "ModelConfig":
        """ByteDance's ``ouro`` (Ouro-2.6B, a looped language model): a
        dense stack of full-attention layers, four norms a layer in the
        sandwich placement (``post_norms``), run ``total_ut_steps`` times a
        token over one set of weights (``loop_passes``), the final norm
        closing every pass. Its exit gate (``early_exit_gate``: one logit a
        token after a pass's norm) is held in the parameter tree and not
        evaluated: at the published ``early_exit_threshold`` 1 no token
        leaves before the last pass. A token that left early would have no
        K/V for the passes it skipped, which nothing here fills, so a
        threshold below 1 is refused, as is every key that nothing here
        computes for this family."""
        name = "ouro"
        n_layers = cfg["num_hidden_layers"]
        passes = cfg.get("total_ut_steps", 1)
        if not isinstance(passes, int) or passes < 1:
            raise ValueError(f"{name} needs total_ut_steps >= 1, got {passes!r}")
        if float(cfg.get("early_exit_threshold", 1.0)) < 1.0:
            raise ValueError(
                f"{name} with early_exit_threshold < 1 is not supported: a token "
                "that leaves at an early pass has no K/V for the passes it "
                "skipped, and nothing here fills them"
            )
        if cfg.get("use_sliding_window", False):
            raise ValueError(f"{name} with use_sliding_window=true is not supported")
        if cfg.get("sliding_window") is not None:
            raise ValueError(f"{name} with a sliding_window is not supported")
        layer_types = tuple(cfg.get("layer_types") or ("full_attention",) * n_layers)
        if len(layer_types) != n_layers or set(layer_types) != {"full_attention"}:
            raise ValueError(
                f"{name} layer_types must name {n_layers} layers as "
                f"'full_attention', got {layer_types!r}"
            )
        if cfg.get("rope_scaling"):
            raise ValueError(f"{name} with a rope_scaling is not supported")
        if str(cfg.get("hidden_act", "silu")) != "silu":
            raise ValueError(f"{name} with hidden_act other than silu is not supported")
        return cls(
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=n_layers,
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg.get("num_key_value_heads", cfg["num_attention_heads"]),
            head_dim=cfg.get("head_dim"),
            rope_theta=float(cfg.get("rope_theta", 10000.0)),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-6),
            max_position_embeddings=cfg.get("max_position_embeddings", 65536),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            post_norms=True,
            loop_passes=passes,
            dtype=cfg.get("torch_dtype", "bfloat16"),
            model_type=name,
        )

    @classmethod
    def _from_keye_vl2(cls, cfg: dict) -> "ModelConfig":
        """Kwai's ``KeyeVL2`` (Keye-VL-2.0-30B-A3B), the language model:
        Qwen3-MoE's stack key for key (grouped-query attention with a
        per-head RMS norm on q and k, ``num_experts`` routed SwiGLUs top-k
        by softmax, no shared expert) under a learned sparse selection
        (``sa_config``: an indexer of ``indexer_num_heads`` heads of
        ``indexer_head_dim`` with ONE key head, the ``topk`` context tokens
        of largest index score) and a rotary by sections over three position
        components (``rope_scaling.mrope_section``). ``q_chunk_size`` /
        ``kv_chunk_size`` name the published code's tiles and change no sum.
        The vision tower's keys (``vision_config`` ...) are not read: the
        tower is not served (``refuse_unserved_tower``).
        Refused, because nothing here computes it: ``use_sliding_window``,
        another ``rope_type`` than default, sections that do not sum to half
        a head, more than one index key head, mixed dense and sparse
        layers, a bias on attention's projections, another activation than
        silu."""
        name = "KeyeVL2"
        if cfg.get("use_sliding_window", False):
            raise ValueError(f"{name} with use_sliding_window=true is not supported")
        if cfg.get("mlp_only_layers") or cfg.get("decoder_sparse_step", 1) != 1:
            raise ValueError(
                f"{name} with mlp_only_layers / decoder_sparse_step != 1 "
                "(mixed dense+sparse layers) is not supported"
            )
        if cfg.get("attention_bias", False):
            raise ValueError(f"{name} with attention_bias=true is not supported")
        if str(cfg.get("hidden_act", "silu")) != "silu":
            raise ValueError(f"{name} with hidden_act other than silu is not supported")
        scaling = cfg.get("rope_scaling") or {}
        kind = scaling.get("rope_type", scaling.get("type", "default"))
        if kind != "default":
            raise ValueError(f"{name} with rope_type {kind!r} is not supported")
        heads = cfg["num_attention_heads"]
        head_dim = cfg.get("head_dim") or cfg["hidden_size"] // heads
        sections = tuple(int(n) for n in scaling.get("mrope_section") or ())
        if sections and (len(sections) != 3 or sum(sections) != head_dim // 2):
            raise ValueError(
                f"{name} needs three mrope_section entries that sum to half "
                f"the head ({head_dim // 2}), got {list(sections)}"
            )
        sa = cfg.get("sa_config") or {}
        for key in ("indexer_num_heads", "indexer_head_dim", "topk"):
            if int(sa.get(key) or 0) < 1:
                raise ValueError(f"{name} needs sa_config.{key} >= 1")
        if int(sa.get("indexer_num_kv_heads", 1)) != 1:
            raise ValueError(f"{name} with more than one index key head is not supported")
        if sa["indexer_head_dim"] % 2:
            raise ValueError(f"{name} needs an even sa_config.indexer_head_dim")
        experts = cfg.get("num_experts") or cfg.get("num_local_experts") or 0
        if experts < 1:
            raise ValueError(f"{name} needs num_experts >= 1")
        return cls(
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg.get("intermediate_size", cfg["hidden_size"]),
            num_layers=cfg["num_hidden_layers"],
            num_heads=heads,
            num_kv_heads=cfg.get("num_key_value_heads", heads),
            head_dim=cfg.get("head_dim"),
            rope_theta=float(cfg.get("rope_theta", 10000.0)),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-6),
            max_position_embeddings=cfg.get("max_position_embeddings", 32768),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            qk_norm=True,
            num_experts=experts,
            num_experts_per_tok=cfg["num_experts_per_tok"],
            norm_topk_prob=cfg.get("norm_topk_prob", False),
            moe_intermediate_size=cfg["moe_intermediate_size"],
            index_n_heads=sa["indexer_num_heads"],
            index_head_dim=sa["indexer_head_dim"],
            index_topk=sa["topk"],
            mrope_section=sections,
            count_expert_reads=True,
            dtype=cfg.get("torch_dtype", "bfloat16"),
            model_type=name,
        )

    @classmethod
    def _from_nemotron_h(cls, cfg: dict) -> "ModelConfig":
        """NVIDIA's ``nemotron_h`` (Nemotron-3-Nano-30B-A3B): layer ``i`` is
        what letter ``i`` of ``hybrid_override_pattern`` says, ``M`` a
        Mamba-2 mixer, ``E`` routed experts beside a shared one, ``*``
        attention, each ONE half ``x + mixer(norm x)``. An ``E`` always
        follows an ``M`` or a ``*``, so the stack is read as pairs with the
        feed-forward present or absent (``ff_types``): ``ME``, ``M``, ``*E``,
        ``*``. Any other letter is refused by name (``-``, the family's
        dense feed-forward, among them), as is an ``E`` that follows no
        mixer. Attention has no positional term (the family's attention
        layer applies none: ``rope_theta`` and ``partial_rotary_factor``
        steer nothing). Experts are two matrices, ``down(relu(up x)^2)``,
        chosen top-k of sigmoid scores plus ``e_score_correction_bias`` and
        weighed by the renormalised scores times ``routed_scaling_factor``;
        the shared expert has the same form at
        ``moe_shared_expert_intermediate_size``. The mixer's inner width is
        ``mamba_num_heads * mamba_head_dim`` (``expand`` steers nothing).
        ``n_routed_experts`` is what this device *holds*, as for
        ``pangu_ultra_moe`` (``_expert_share``)."""
        name = "nemotron_h"
        pattern = str(cfg["hybrid_override_pattern"])
        n_layers = cfg["num_hidden_layers"]
        unknown = sorted(set(pattern) - set("ME*"))
        if unknown:
            raise ValueError(
                f"{name} hybrid_override_pattern has layers of kind "
                f"{', '.join(repr(c) for c in unknown)}: served are 'M' (Mamba-2), "
                "'E' (routed experts) and '*' (attention)"
            )
        if len(pattern) != n_layers:
            raise ValueError(
                f"{name} hybrid_override_pattern names {len(pattern)} layers, "
                f"num_hidden_layers {n_layers}"
            )
        ops, ffs = [], []
        for i, c in enumerate(pattern):
            if c == "E":
                if not ops or ffs[-1] != "none":
                    raise ValueError(
                        f"{name} layer {i} is an 'E' that follows no 'M' or '*': "
                        "the stack is served as (mixer, experts) pairs"
                    )
                ffs[-1] = "routed"
            else:
                ops.append("mamba2" if c == "M" else "full_attention")
                ffs.append("none")
        for key, want in (
            ("mlp_hidden_act", "relu2"), ("mamba_hidden_act", "silu"),
            ("use_conv_bias", True), ("mamba_proj_bias", False),
            ("use_bias", False), ("attention_bias", False), ("mlp_bias", False),
            ("sliding_window", None), ("norm_topk_prob", True),
            ("n_shared_experts", 1),
        ):
            if cfg.get(key, want) != want:
                raise ValueError(f"{name} with {key}={cfg[key]!r} is not supported")
        if cfg.get("n_group", 1) > 1 or cfg.get("topk_group", 1) > 1:
            raise ValueError(
                f"{name} with group-limited selection (n_group / topk_group > 1) "
                "is not supported"
            )
        if cfg.get("conv_kernel", 4) < 2:
            raise ValueError(f"{name} needs conv_kernel >= 2")
        heads, groups = cfg["mamba_num_heads"], cfg["n_groups"]
        if heads % groups:
            raise ValueError(f"{name} needs mamba_num_heads a multiple of n_groups")
        held, routed, first = _expert_share(
            cfg, name, "n_routed_experts", "n_routed_experts_global"
        )
        return cls(
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=len(ops),
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg.get("head_dim"),
            rms_norm_eps=cfg.get("layer_norm_epsilon", cfg.get("norm_eps", 1e-5)),
            max_position_embeddings=cfg.get("max_position_embeddings", 262144),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            hidden_act="relu2",
            layer_types=tuple(ops),
            ff_types=tuple(ffs),
            mamba_d_state=cfg["ssm_state_size"],
            mamba_d_conv=cfg.get("conv_kernel", 4),
            mamba2_heads=heads,
            mamba2_head_dim=cfg["mamba_head_dim"],
            mamba2_groups=groups,
            use_rope=False,
            num_experts=routed,
            num_experts_held=held if held < routed else 0,
            first_expert_held=first,
            num_experts_per_tok=cfg["num_experts_per_tok"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            num_shared_experts=1,
            shared_expert_width=cfg.get(
                "moe_shared_expert_intermediate_size", cfg["moe_intermediate_size"]
            ),
            gated_experts=False,
            router_score="sigmoid",
            use_expert_bias=True,
            router_norm_eps=1e-20,
            routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
            dtype=cfg.get("torch_dtype", "bfloat16"),
            model_type=name,
        )

    @classmethod
    def from_pretrained(cls, path: str) -> "ModelConfig":
        with open(os.path.join(path, "config.json")) as f:
            cfg = json.load(f)
        # A block-diffusion model's generation keys may also lie in the
        # directory's ``generation_config.json``, which then has the say.
        gen = os.path.join(path, "generation_config.json")
        if cfg.get("model_type") == "sdar_moe" and os.path.exists(gen):
            with open(gen) as f:
                cfg = {**cfg, **{
                    k: v for k, v in json.load(f).items() if k in _BLOCK_KEYS
                }}
        return cls.from_hf_config(cfg)


# Families whose published model has a vision (or audio) tower that is not
# served: the language model alone is, and a request that brings anything
# but text is refused (``refuse_unserved_tower``). What each would need.
UNSERVED_TOWERS = {
    "KeyeVL2": (
        "its SigLIP-class vision tower and projector, whose configuration is "
        "in no file here (models/vision.py is a CLIP tower with LLaVA's "
        "projector, not Keye's)"
    ),
}


def refuse_unserved_tower(model_type: str, messages) -> None:
    """Raise ``ValueError`` where a chat request's ``messages`` (dicts, or
    objects with ``content``) bring a content part that is not text to a
    family in ``UNSERVED_TOWERS``: the one place image, video and audio
    input is refused for a model whose tower is left out. Text parts and
    plain string contents pass, as does every other family."""
    if model_type not in UNSERVED_TOWERS:
        return
    for m in messages:
        content = m.get("content") if isinstance(m, dict) else getattr(m, "content", None)
        if not isinstance(content, list):
            continue
        for part in content:
            kind = part.get("type", "text") if isinstance(part, dict) else "text"
            if kind != "text":
                raise ValueError(
                    f"model_type {model_type!r} is served as its language model "
                    f"alone: a {kind!r} content part needs "
                    f"{UNSERVED_TOWERS[model_type]}. Send text only"
                )


# The families ``from_hf_config`` builds; any other ``model_type`` raises.
# ``sdar_moe`` is ``qwen3_moe``'s stack, block for block, generating by
# diffusion over blocks (``_block_generation``).

def _refuse_unserved_moe(cfg: dict, name: str) -> None:
    """What the readers of DeepSeek-V3's lineage of routed models (sigmoid
    routed experts beside shared ones: ``pangu_ultra_moe``, ``glm_moe_dsa``,
    ``exaone_moe``) refuse alike, because nothing here computes it: the
    multi-token-prediction module, another activation than silu, a bias on
    attention's projections, group-limited selection."""
    if cfg.get("num_nextn_predict_layers", 0):
        raise ValueError(
            f"{name} with num_nextn_predict_layers > 0 is not supported: "
            "the multi-token-prediction module is not served"
        )
    if str(cfg.get("hidden_act", "silu")) != "silu":
        raise ValueError(f"{name} with hidden_act other than silu is not supported")
    if cfg.get("attention_bias", False):
        raise ValueError(f"{name} with attention_bias=true is not supported")
    if cfg.get("n_group", 1) > 1 or cfg.get("topk_group", 1) > 1:
        raise ValueError(
            f"{name} with group-limited selection (n_group / topk_group > 1) "
            "is not supported"
        )


def _expert_share(cfg: dict, name: str, held_key: str, global_key: str) -> tuple[int, int, int]:
    """The share of the routed experts a file describes: (held, the
    router's width, the first held id). ``held_key`` counts what this
    device holds; where the file also gives ``global_key`` (an
    expert-parallel rank's share) the router is that wide and the held ids
    start at ``first_routed_expert``."""
    held = cfg[held_key]
    routed = cfg.get(global_key, held)
    first = cfg.get("first_routed_expert", 0)
    if not 0 <= first <= routed - held:
        raise ValueError(
            f"{name} holds experts [{first}, {first + held}) of {routed}: "
            "outside the router's range"
        )
    if cfg["num_experts_per_tok"] > routed:
        raise ValueError(f"{name} num_experts_per_tok exceeds its experts")
    return held, routed, first


def _latent_moe_share(cfg: dict, name: str, widths: tuple[str, ...] = ()) -> tuple[int, int, int]:
    """What the latent readers (``pangu_ultra_moe``, ``glm_moe_dsa``) refuse
    beyond :func:`_refuse_unserved_moe` (a missing or odd latent width or
    one of ``widths``), and their share of the routed experts
    (:func:`_expert_share` under their keys, ``n_routed_experts`` /
    ``n_routed_experts_global``)."""
    _refuse_unserved_moe(cfg, name)
    for key in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", *widths):
        if int(cfg.get(key) or 0) <= 0:
            raise ValueError(f"{name} needs {key} > 0")
    if cfg["qk_rope_head_dim"] % 2:
        raise ValueError(f"{name} needs an even qk_rope_head_dim")
    return _expert_share(cfg, name, "n_routed_experts", "n_routed_experts_global")


KNOWN_MODEL_TYPES = frozenset({
    "llama", "mistral", "qwen2", "qwen3", "gemma", "gemma2", "gemma3_text",
    "phi3", "mixtral", "qwen2_moe", "qwen3_moe", "sdar_moe", "lfm2_moe", "jamba",
    "olmo_hybrid", "pangu_ultra_moe", "glm_moe_dsa", "exaone_moe",
    "mimo_v2_flash", "ouro", "KeyeVL2", "nemotron_h",
})

# A block-diffusion model's generation keys and the SDAR family's
# published defaults (the catalog's row of ``config.json`` has none of
# them: benchmark/configs/sdar-30b-a3b-l6.json, ``assumed``).
_BLOCK_KEYS = {
    "block_length": 4,
    "denoising_steps": 4,
    "remasking_strategy": "low_confidence_dynamic",
    "confidence_threshold": 0.9,
    "mask_token_id": 151669,
}
REMASKING_STRATEGIES = ("low_confidence_dynamic", "low_confidence_static")


def _block_generation(cfg: dict) -> dict:
    """``ModelConfig``'s block-diffusion fields from a ``config.json``."""
    out = {k: cfg.get(k, default) for k, default in _BLOCK_KEYS.items()}
    B, T = int(out["block_length"]), int(out["denoising_steps"])
    if B < 1 or B & (B - 1) or T < 1:
        raise ValueError(
            f"block_length {B} must be a power of two and denoising_steps "
            f"{T} at least 1"
        )
    if out["remasking_strategy"] not in REMASKING_STRATEGIES:
        raise ValueError(
            f"remasking_strategy {out['remasking_strategy']!r}; supported: "
            f"{', '.join(REMASKING_STRATEGIES)}"
        )
    # Any id inside the vocabulary serves: a small test vocabulary lacks
    # the published one.
    vocab = int(cfg.get("vocab_size", 32000))
    out["mask_token_id"] = min(int(out["mask_token_id"]), vocab - 1)
    return out

# Presets used by tests, the dry-run entrypoints, and the benchmark.
TINY = ModelConfig(
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    num_layers=2,
    num_heads=4,
    num_kv_heads=2,
    max_position_embeddings=512,
    rms_norm_eps=1e-5,
)

LLAMA_1B = ModelConfig(  # Llama-3.2-1B shape
    vocab_size=128256,
    hidden_size=2048,
    intermediate_size=8192,
    num_layers=16,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    rope_theta=500000.0,
    max_position_embeddings=8192,
    tie_word_embeddings=True,
)

LLAMA_3B = ModelConfig(  # Llama-3.2-3B shape
    vocab_size=128256,
    hidden_size=3072,
    intermediate_size=8192,
    num_layers=28,
    num_heads=24,
    num_kv_heads=8,
    head_dim=128,
    rope_theta=500000.0,
    max_position_embeddings=8192,
    tie_word_embeddings=True,
)

LLAMA_8B = ModelConfig(  # Llama-3.1-8B / DeepSeek-R1-Distill-Llama-8B shape
    vocab_size=128256,
    hidden_size=4096,
    intermediate_size=14336,
    num_layers=32,
    num_heads=32,
    num_kv_heads=8,
    rope_theta=500000.0,
    max_position_embeddings=8192,
)

TINY_QWEN2 = ModelConfig(  # qwen2 family shape: QKV bias, tied embeddings
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    num_layers=2,
    num_heads=4,
    num_kv_heads=2,
    max_position_embeddings=512,
    attention_bias=True,
    tie_word_embeddings=True,
    model_type="qwen2",
)

TINY_MOE = ModelConfig(  # mixtral family shape: 4 experts, top-2 routing
    vocab_size=256,
    hidden_size=64,
    intermediate_size=96,
    num_layers=2,
    num_heads=4,
    num_kv_heads=2,
    max_position_embeddings=512,
    num_experts=4,
    num_experts_per_tok=2,
    model_type="mixtral",
)

TINY_QWEN3 = ModelConfig(  # qwen3 family shape: q/k norm, no bias
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    num_layers=2,
    num_heads=4,
    num_kv_heads=2,
    max_position_embeddings=512,
    qk_norm=True,
    tie_word_embeddings=True,
    rms_norm_eps=1e-6,
    model_type="qwen3",
)

QWEN3_8B = ModelConfig(  # Qwen3-8B shape
    vocab_size=151936,
    hidden_size=4096,
    intermediate_size=12288,
    num_layers=36,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    rope_theta=1000000.0,
    max_position_embeddings=40960,
    qk_norm=True,
    rms_norm_eps=1e-6,
    model_type="qwen3",
)

QWEN2_7B = ModelConfig(  # Qwen2-7B-Instruct shape
    vocab_size=152064,
    hidden_size=3584,
    intermediate_size=18944,
    num_layers=28,
    num_heads=28,
    num_kv_heads=4,
    rope_theta=1000000.0,
    max_position_embeddings=32768,
    attention_bias=True,
    rms_norm_eps=1e-6,
    model_type="qwen2",
)

GEMMA_2B = ModelConfig(  # Gemma-2B shape
    vocab_size=256000,
    hidden_size=2048,
    intermediate_size=16384,
    num_layers=18,
    num_heads=8,
    num_kv_heads=1,
    head_dim=256,
    max_position_embeddings=8192,
    tie_word_embeddings=True,
    rms_norm_eps=1e-6,
    hidden_act="gelu_tanh",
    rms_norm_offset=True,
    scale_embeddings=True,
    model_type="gemma",
)

GEMMA2_9B = ModelConfig(  # Gemma-2-9B shape
    vocab_size=256000,
    hidden_size=3584,
    intermediate_size=14336,
    num_layers=42,
    num_heads=16,
    num_kv_heads=8,
    head_dim=256,
    max_position_embeddings=8192,
    tie_word_embeddings=True,
    rms_norm_eps=1e-6,
    hidden_act="gelu_tanh",
    rms_norm_offset=True,
    scale_embeddings=True,
    post_norms=True,
    attn_logit_softcap=50.0,
    final_logit_softcap=30.0,
    query_pre_attn_scalar=256.0,
    sliding_window=4096,
    alt_sliding_window=True,
    model_type="gemma2",
)

MISTRAL_7B = ModelConfig(  # Mistral-7B-v0.1 shape (4k sliding window)
    vocab_size=32000,
    hidden_size=4096,
    intermediate_size=14336,
    num_layers=32,
    num_heads=32,
    num_kv_heads=8,
    sliding_window=4096,
    max_position_embeddings=32768,
    model_type="mistral",
)

TINY_LFM2 = ModelConfig(  # lfm2_moe family shape: both operator kinds, both
    # feed-forward kinds, every run boundary the 16-layer cut has
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    num_layers=6,
    num_heads=4,
    num_kv_heads=2,
    max_position_embeddings=512,
    qk_norm=True,
    tie_word_embeddings=True,
    layer_types=("conv", "conv", "full_attention", "conv", "conv", "full_attention"),
    num_experts=8,
    num_experts_per_tok=2,
    moe_intermediate_size=64,
    num_dense_layers=2,
    router_score="sigmoid",
    use_expert_bias=True,
    model_type="lfm2_moe",
)

TINY_JAMBA = ModelConfig(  # jamba family shape: period 4 with one attention
    # layer (offset 1), so runs mamba / attn / mamba; 1 KV head, no rope
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    num_layers=4,
    num_heads=4,
    num_kv_heads=1,
    max_position_embeddings=512,
    tie_word_embeddings=True,
    rms_norm_eps=1e-6,
    layer_types=("mamba", "full_attention", "mamba", "mamba"),
    mamba_d_state=16,
    mamba_d_conv=4,
    mamba_expand=2,
    mamba_dt_rank=8,
    use_rope=False,
    model_type="jamba",
)

TINY_OLMO_HYBRID = ModelConfig(  # olmo_hybrid family shape: one period of
    # (linear, linear, linear, full) and a linear layer more, so runs linear /
    # attn / linear; as many KV heads as heads, no rope, Olmo 2's block; two
    # heads' values fill a lane group, which the delta-rule kernel asks
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    num_layers=5,
    num_heads=4,
    num_kv_heads=4,
    max_position_embeddings=512,
    rms_norm_eps=1e-6,
    qk_norm=True,
    qk_norm_whole=True,
    pre_norms=False,
    post_norms=True,
    layer_types=(
        "linear_attention", "linear_attention", "linear_attention",
        "full_attention", "linear_attention",
    ),
    linear_num_heads=2,
    linear_key_head_dim=8,
    linear_value_head_dim=64,
    linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True,
    use_rope=False,
    model_type="olmo_hybrid",
)

TINY_PANGU = ModelConfig(  # pangu_ultra_moe family shape: latent attention
    # whose ranks are no multiples of 128 (the pool's row is 40 + 16 = 56
    # values in 128 lanes), one dense layer then two routed ones, 8 of 32
    # sigmoid-routed experts held (ids 8..15) beside one ungated shared
    # expert, four norms a layer
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    num_layers=3,
    num_heads=4,
    num_kv_heads=1,
    head_dim=32,
    rope_theta=25600000.0,
    max_position_embeddings=512,
    post_norms=True,
    num_experts=32,
    num_experts_held=8,
    first_expert_held=8,
    num_experts_per_tok=4,
    moe_intermediate_size=32,
    num_shared_experts=1,
    num_dense_layers=1,
    router_score="sigmoid",
    router_norm_eps=1e-20,
    routed_scaling_factor=2.5,
    q_lora_rank=24,
    kv_lora_rank=40,
    qk_nope_head_dim=16,
    qk_rope_head_dim=16,
    v_head_dim=16,
    model_type="pangu_ultra_moe",
)

TINY_GLM_DSA = ModelConfig(  # glm_moe_dsa family shape: latent attention
    # (values wider than the keys' nope part) read through an indexer of 4
    # heads of 32 whose keys lie in 128 lanes of the second pool, the 16
    # context tokens of largest index score a query; one dense layer then
    # two routed ones, 4 of 8 experts held (ids 4..7), chosen by sigmoid
    # scores plus a selection bias, beside one ungated shared expert; two
    # norms a layer
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    num_layers=3,
    num_heads=4,
    num_kv_heads=1,
    head_dim=32,
    rope_theta=1000000.0,
    max_position_embeddings=512,
    num_experts=8,
    num_experts_held=4,
    first_expert_held=4,
    num_experts_per_tok=2,
    moe_intermediate_size=32,
    num_shared_experts=1,
    num_dense_layers=1,
    router_score="sigmoid",
    use_expert_bias=True,
    router_norm_eps=1e-20,
    routed_scaling_factor=2.5,
    q_lora_rank=24,
    kv_lora_rank=40,
    qk_nope_head_dim=16,
    qk_rope_head_dim=16,
    v_head_dim=24,
    index_n_heads=4,
    index_head_dim=32,
    index_topk=16,
    model_type="glm_moe_dsa",
)

TINY_EXAONE_MOE = ModelConfig(  # exaone_moe family shape: sliding and full
    # attention layers 3 : 1 and a sliding layer more (runs S-dense / S S /
    # F / S), a width of 8 that is two pages of 4, rotary on the sliding
    # layers alone; one dense layer then four routed ones, 4 of 8 experts
    # held (ids 4..7), chosen by sigmoid scores plus a selection bias,
    # beside one ungated shared expert; two norms a layer
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    num_layers=5,
    num_heads=4,
    num_kv_heads=2,
    head_dim=16,
    rope_theta=1000000.0,
    max_position_embeddings=512,
    qk_norm=True,
    layer_types=(
        "sliding_attention", "sliding_attention", "sliding_attention",
        "full_attention", "sliding_attention",
    ),
    sliding_window=8,
    rope_sliding_only=True,
    num_experts=8,
    num_experts_held=4,
    first_expert_held=4,
    num_experts_per_tok=2,
    moe_intermediate_size=32,
    num_shared_experts=1,
    num_dense_layers=1,
    router_score="sigmoid",
    use_expert_bias=True,
    router_norm_eps=1e-20,
    routed_scaling_factor=2.5,
    model_type="exaone_moe",
)

TINY_MIMO = ModelConfig(  # mimo_v2_flash family shape: full and sliding
    # layers F-dense / S S / F / S S, 1 KV head in a full layer and 2 in a
    # sliding one (4 and 2 query heads a KV head), keys of 24 and values of
    # 16, the first 8 of a head's 24 values rotary (base 5e6 full, 1e4
    # sliding), a width of 8 that is two pages of 4, a sink on the sliding
    # layers, values times 0.707; one dense layer then five routed ones, 8 of
    # 16 experts held (ids 8..15) top-2 by sigmoid scores plus a selection
    # bias, no shared expert; two norms a layer
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    num_layers=6,
    num_heads=4,
    num_kv_heads=1,
    sliding_num_kv_heads=2,
    head_dim=24,
    v_head_dim=16,
    rotary_dim=8,
    value_scale=0.707,
    rope_theta=5000000.0,
    rope_local_base_freq=10000.0,
    max_position_embeddings=512,
    layer_types=(
        "full_attention", "sliding_attention", "sliding_attention",
        "full_attention", "sliding_attention", "sliding_attention",
    ),
    sliding_window=8,
    sink_forms=("sliding",),
    num_experts=16,
    num_experts_held=8,
    first_expert_held=8,
    num_experts_per_tok=2,
    moe_intermediate_size=32,
    num_dense_layers=1,
    router_score="sigmoid",
    use_expert_bias=True,
    router_norm_eps=1e-20,
    model_type="mimo_v2_flash",
)

TINY_OURO = ModelConfig(  # ouro family shape: 4 layers run 3 times over one
    # set of weights (passes != layers != 4 heads, so a transposed slot
    # shows), as many KV heads as heads, four norms a layer, an exit gate held
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    num_layers=4,
    num_heads=4,
    num_kv_heads=4,
    head_dim=16,
    rope_theta=1000000.0,
    max_position_embeddings=512,
    rms_norm_eps=1e-6,
    post_norms=True,
    loop_passes=3,
    model_type="ouro",
)

TINY_KEYE = ModelConfig(  # KeyeVL2 family shape: grouped-query attention (4
    # heads on 2 KV heads, q/k norms) under a learned selection (top 16 of
    # the context by an indexer of 4 heads of 16, its key behind K in the K
    # pool's row), rotary by sections 2 + 3 + 3 over three position
    # components; three layers, each 8 routed experts top 2, none shared
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    num_layers=3,
    num_heads=4,
    num_kv_heads=2,
    head_dim=16,
    rope_theta=1000000.0,
    max_position_embeddings=512,
    rms_norm_eps=1e-6,
    qk_norm=True,
    num_experts=8,
    num_experts_per_tok=2,
    norm_topk_prob=True,
    moe_intermediate_size=32,
    index_n_heads=4,
    index_head_dim=16,
    index_topk=16,
    mrope_section=(2, 3, 3),
    count_expert_reads=True,
    model_type="KeyeVL2",
)

MIXTRAL_8X7B = ModelConfig(  # Mixtral-8x7B shape
    vocab_size=32000,
    hidden_size=4096,
    intermediate_size=14336,
    num_layers=32,
    num_heads=32,
    num_kv_heads=8,
    rope_theta=1000000.0,
    max_position_embeddings=32768,
    num_experts=8,
    num_experts_per_tok=2,
    model_type="mixtral",
)

TINY_NEMOTRON_H = ModelConfig(  # nemotron_h family shape: layers of one
    # half, ``MEM*EMEM*`` as the pairs ME M *E ME M *, so six runs of one pair;
    # Mamba-2 mixers of 4 heads of 64 channels in 2 groups with 128 states (the
    # least the chunk kernel tiles: two heads fill a lane group, the states
    # one); 8 two-matrix relu^2 experts, top-2 by sigmoid
    # and a selection bias, beside a shared expert twice as wide; no rope; 512
    # ids, which a synthetic tokenizer needs (models/synth.py)
    vocab_size=512,
    hidden_size=64,
    intermediate_size=32,
    num_layers=6,
    num_heads=4,
    num_kv_heads=2,
    head_dim=16,
    max_position_embeddings=512,
    rms_norm_eps=1e-5,
    hidden_act="relu2",
    layer_types=(
        "mamba2", "mamba2", "full_attention", "mamba2", "mamba2", "full_attention",
    ),
    ff_types=("routed", "none", "routed", "routed", "none", "none"),
    mamba_d_state=128,
    mamba_d_conv=4,
    mamba2_heads=4,
    mamba2_head_dim=64,
    mamba2_groups=2,
    use_rope=False,
    num_experts=8,
    num_experts_per_tok=2,
    moe_intermediate_size=32,
    num_shared_experts=1,
    shared_expert_width=64,
    gated_experts=False,
    router_score="sigmoid",
    use_expert_bias=True,
    router_norm_eps=1e-20,
    routed_scaling_factor=2.5,
    model_type="nemotron_h",
)

PRESETS = {
    "tiny": TINY,
    "tiny-qwen2": TINY_QWEN2,
    "tiny-qwen3": TINY_QWEN3,
    "tiny-moe": TINY_MOE,
    "tiny-lfm2": TINY_LFM2,
    "tiny-jamba": TINY_JAMBA,
    "tiny-olmo-hybrid": TINY_OLMO_HYBRID,
    "tiny-pangu": TINY_PANGU,
    "tiny-glm-dsa": TINY_GLM_DSA,
    "tiny-exaone-moe": TINY_EXAONE_MOE,
    "tiny-mimo": TINY_MIMO,
    "tiny-ouro": TINY_OURO,
    "tiny-keye": TINY_KEYE,
    "tiny-nemotron-h": TINY_NEMOTRON_H,
    "llama-1b": LLAMA_1B,
    "llama-3b": LLAMA_3B,
    "llama-8b": LLAMA_8B,
    "qwen2-7b": QWEN2_7B,
    "qwen3-8b": QWEN3_8B,
    "gemma-2b": GEMMA_2B,
    "gemma2-9b": GEMMA2_9B,
    "mistral-7b": MISTRAL_7B,
    "mixtral-8x7b": MIXTRAL_8X7B,
}
