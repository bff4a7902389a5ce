"""Llama-family transformer, functional JAX, paged-KV, scan-over-layers.

TPU-first design decisions (vs the reference's delegation to vLLM/sglang,
SURVEY.md §2.3):

- **Stacked layer parameters + ``lax.scan``**: one compiled layer body,
  L-step scan. Compile time stays flat as L grows, and XLA pipelines the
  per-layer HBM traffic.
- **The KV pools are the scan's carry, never its inputs**: the layer
  scan carries ``(x, k_cache, v_cache)`` and scans over the stacked
  parameters and the layer index. Layer ``l`` scatters its new tokens
  into the whole ``[L, P, ps, W]`` pool at ``[l, page, offset]`` and
  reads through a flat ``[L*P, ps, W]`` view with the page table moved
  by ``l*P``, so XLA updates the (donated) pools in place. A scan's
  ``xs`` and ``ys`` are different buffers: state that the body writes
  does not belong there (as scanned inputs and stacked outputs the
  pools were sliced, written back and copied whole on every step).
- **A routed model's expert matrices are the scan's loop invariants,
  not its inputs**: ``w_gate``/``w_up``/``w_down`` of a configuration
  with a ``router`` are closed over whole (``[L, E, D, I]``), and the
  layer index that rides ``xs`` reaches ``ops/moe.py::moe_ffn(layer=l)``,
  whose grouped matmul reads layer ``l``'s blocks where they lie. As
  ``xs`` each would be sliced a layer at a time, and the slice handed
  to a custom call is a copy: 3 x 235 MB a layer on a Mixtral chip, half
  of a step (PERF.md, PR 28). Everything else of a layer (norms,
  attention projections, the router, a dense model's whole feed-forward)
  rides ``xs`` as before: XLA fuses those slices into the dots that
  read them. So do the expert matrices of a forward whose matmuls take
  ``ragged_dot`` on the slice: off the TPU, and widths the kernel cannot
  tile (``ops/moe.py::experts_in_place``; a forward's row count is no
  part of the rule).
- **One forward for prefill and decode**: write-then-gather paged
  attention (see ``ops/attention.py``) with static (B, T, Pmax) buckets.
- **bfloat16 everywhere the MXU touches**, float32 for norms/softmax/rope.
- **GSPMD tensor parallelism**: parameters carry head/ffn-sharded
  ``PartitionSpec``s (see ``param_shardings``); collectives are inserted
  by XLA over ICI, not hand-written.

Reference capability anchor: the engines in
``/root/reference/lib/engines/`` expose token-in/token-out forward passes;
this module is their TPU-native replacement.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.attention import paged_attention, write_kv_pages
from ..ops.rope import apply_mrope, apply_rope, rope_frequencies
from .config import ModelConfig

Params = dict[str, Any]
# A routed model's per-expert matrices, stacked [L, E, ..]: what the paged
# layer scan closes over instead of slicing (``_scan_paged_layers``).
EXPERT_STACKS = ("w_gate", "w_up", "w_down")


def _dtype(cfg: ModelConfig):
    """Param/compute dtype for a config. float16 maps to bfloat16 (the TPU
    native half type); unknown strings are an error at model-build time."""
    table = {"bfloat16": jnp.bfloat16, "float32": jnp.float32, "float16": jnp.bfloat16}
    try:
        return table[str(cfg.dtype)]
    except KeyError:
        raise ValueError(f"unsupported model dtype: {cfg.dtype!r}") from None


# The selection bias of a randomly initialised sigmoid router
# (``router_bias``): normal with this deviation. The scores it moves are
# sigmoids of unit-variance logits, whose 4th and 5th of 32 lie ~0.05
# apart at the median, so a bias of this size changes the chosen set of
# most rows (tests/test_lfm2.py counts them) and moves no weight.
ROUTER_BIAS_SCALE = 0.1
# The sink logits of a randomly initialised model (``sink``, a query head
# and layer): normal about this mean with this deviation. Scores of seeded
# weights are about unit normal, so a sink near 4 holds about a fifth of a
# 128-key window's mass: what a trained sink is for, and enough that a
# program without it, or with another head's, gives other logits.
SINK_MEAN, SINK_SCALE = 4.0, 1.0


def init_params(rng: jax.Array, cfg: ModelConfig) -> Params:
    """Random-init parameters (tests, benchmarks, and shape reference).

    Parameters are stacked by the kind of layer that has them
    (``ModelConfig.layer_runs``): the two norms over all ``L`` layers,
    attention's over the attention layers, the short convolution's
    (``conv_in``/``conv_w``/``conv_out``) over the convolution layers,
    the selective scan's (``mamba_*``, :func:`_init_mamba`) over the Mamba
    layers, the delta rule's (``gdn_*``, :func:`_init_linear`) over the
    linear-attention layers, the router and the expert matrices over the
    routed layers. A model whose block norms its halves' outputs alone
    (``pre_norms`` False) has no ``attn_norm``/``mlp_norm``. A model
    whose feed-forwards are of both kinds keeps its dense layers' under
    ``dense_gate``/``dense_up``/``dense_down``; ``w_gate``/``w_up``/
    ``w_down`` are then the routed layers' (``EXPERT_STACKS``). In a
    model of one kind every stack is ``[L, ...]``, as it always was. A
    model whose experts are two matrices (``gated_experts`` False:
    nemotron_h) has no ``w_gate`` and no ``shared_gate``; one whose layers
    are one half each (``ff_types``) has ``mlp_norm`` over the layers whose
    feed-forward is there, the routed ones, and the Mamba-2 mixer's
    (``m2_*``, :func:`_init_mamba2`) over the Mamba-2 layers.

    A model whose sliding and full layers differ in their KV heads
    (``ModelConfig.kv_heads_by_form``) stacks the K and V projections by
    form: ``wk`` / ``wv`` over the full layers, ``swa_wk`` / ``swa_wv``
    over the sliding ones (``wq`` and ``wo`` are one shape and stay over
    all attention layers). ``sink`` (float32 ``[layers of the forms in
    sink_forms, H]``): the learned logit a query head of those layers.

    A looped model (``ModelConfig.loop_passes`` > 1: ouro) has ``L`` layers
    of weights whatever its passes, and its exit gate at the top level:
    ``exit_gate_w`` ``[D]`` and ``exit_gate_b`` ``[1]``, in the model's dtype
    as the checkpoint stores them (a linear map ``D -> 1`` with a bias,
    after a pass's norm). The tree holds them and no forward evaluates them
    while no token may leave early (``ModelConfig._from_ouro``)."""
    dt = _dtype(cfg)
    hd, dv = cfg.head_dim_, cfg.value_head_dim_
    L, D, H, Hkv, I, V = (
        cfg.num_layers,
        cfg.hidden_size,
        cfg.num_heads,
        cfg.num_kv_heads,
        cfg.intermediate_size,
        cfg.vocab_size,
    )
    La, Lc, Lr = cfg.num_attn_layers, cfg.num_conv_layers, cfg.num_routed_layers
    Lm = cfg.num_mamba_layers
    Ld = L - Lr - cfg.ff_kinds.count("none")
    ks = jax.random.split(rng, 17)
    # Keys for what lfm2 added, split off a folded key: ``ks`` (and so
    # every other family's weights) stays what it was.
    kc = jax.random.split(jax.random.fold_in(rng, 1), 5)

    def init(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) * fan_in**-0.5).astype(dt)

    layers: Params = {
        "attn_norm": jnp.ones((L, D), dt),
        "wq": init(ks[1], (La, D, H * hd), D),
        "wk": init(ks[2], (La, D, Hkv * hd), D),
        "wv": init(ks[3], (La, D, Hkv * dv), D),
        "wo": init(ks[4], (La, H * dv, D), H * dv),
        "mlp_norm": jnp.ones((L, D), dt),
    }
    if cfg.kv_heads_by_form or cfg.sink_forms:  # mimo_v2_flash
        km = jax.random.split(jax.random.fold_in(rng, 6), 3)
        Ls, Hs = cfg.num_sliding_layers, cfg.kv_heads_of("sliding")
        if cfg.kv_heads_by_form:
            layers["wk"] = init(ks[2], (La - Ls, D, Hkv * hd), D)
            layers["wv"] = init(ks[3], (La - Ls, D, Hkv * dv), D)
            layers["swa_wk"] = init(km[0], (Ls, D, Hs * hd), D)
            layers["swa_wv"] = init(km[1], (Ls, D, Hs * dv), D)
        sinks = sum(form in cfg.sink_forms for form in cfg.attn_forms)
        if sinks:
            layers["sink"] = SINK_MEAN + SINK_SCALE * jax.random.normal(
                km[2], (sinks, H), jnp.float32
            )
    if not cfg.pre_norms:  # olmo_hybrid: the halves' outputs are normed alone
        del layers["attn_norm"], layers["mlp_norm"]
    if cfg.is_latent:  # pangu_ultra_moe: latent attention's seven tensors
        del layers["wq"], layers["wk"], layers["wv"]
        layers.update(_init_latent(jax.random.fold_in(rng, 4), cfg, init))
    if cfg.has_indexer:  # glm_moe_dsa, KeyeVL2: the selection's indexer, a layer's own
        layers.update(_init_indexer(jax.random.fold_in(rng, 5), cfg, init))
    if cfg.num_linear_layers:  # olmo_hybrid: the gated delta rule
        layers.update(_init_linear(jax.random.fold_in(rng, 3), cfg, init))
    if Lc:  # lfm2: gated short convolution, no bias
        Kc = cfg.conv_L_cache
        layers["conv_in"] = init(kc[0], (Lc, D, 3 * D), D)
        layers["conv_w"] = init(kc[1], (Lc, Kc, D), Kc)
        layers["conv_out"] = init(kc[2], (Lc, D, D), D)
    if Lm:  # jamba: the Mamba-1 mixer
        layers.update(_init_mamba(jax.random.fold_in(rng, 2), cfg, init))
    if cfg.num_mamba2_layers:  # nemotron_h: the Mamba-2 mixer
        layers.update(_init_mamba2(jax.random.fold_in(rng, 8), cfg, init))
    if cfg.ff_types:  # layers of one half: a norm a feed-forward that is there
        layers["mlp_norm"] = jnp.ones((Lr, D), dt)
    if cfg.attention_bias:  # qwen2: bias on q/k/v, none on o
        layers["bq"] = init(ks[9], (L, H * hd), H * hd)
        layers["bk"] = init(ks[10], (L, Hkv * hd), Hkv * hd)
        layers["bv"] = init(ks[11], (L, Hkv * hd), Hkv * hd)
    if cfg.qk_norm:  # qwen3: per-head q/k RMSNorm; olmo: over the projection
        whole = cfg.qk_norm_whole
        layers["q_norm"] = jnp.ones((La, H * hd if whole else hd), dt)
        layers["k_norm"] = jnp.ones((La, Hkv * hd if whole else hd), dt)
    if cfg.post_norms:  # gemma2: norms on block outputs too
        init_norm = jnp.zeros if cfg.rms_norm_offset else jnp.ones
        layers["post_attn_norm"] = init_norm((L, D), dt)
        layers["post_ffn_norm"] = init_norm((L, D), dt)
    if cfg.is_moe:
        # the router scores every expert; the matrices are the held ones'
        E, Ie, Is = cfg.experts_held, cfg.expert_intermediate_size, cfg.expert_stack_width
        layers["router"] = init(ks[12], (Lr, D, cfg.num_experts), D)
        if cfg.gated_experts:
            layers["w_gate"] = init(ks[5], (Lr, E, D, Is), D)
        layers["w_up"] = init(ks[6], (Lr, E, D, Is), D)
        layers["w_down"] = init(ks[7], (Lr, E, Is, D), Ie)
        if Is != Ie:  # stored in whole lane groups: zeros past the experts' width
            own = (jnp.arange(Is) < Ie).astype(dt)
            for k in EXPERT_STACKS:
                if k in layers:
                    layers[k] = layers[k] * (own[:, None] if k == "w_down" else own)
        if cfg.use_expert_bias:  # lfm2: chooses the experts, weighs nothing
            layers["router_bias"] = ROUTER_BIAS_SCALE * jax.random.normal(
                kc[3], (Lr, cfg.num_experts), jnp.float32
            )
        if Ld:  # lfm2: dense feed-forwards ahead of the routed ones
            layers["dense_gate"] = init(kc[4], (Ld, D, I), D)
            kd = jax.random.split(kc[4])
            layers["dense_up"] = init(kd[0], (Ld, D, I), D)
            layers["dense_down"] = init(kd[1], (Ld, I, D), I)
        if cfg.shared_expert_intermediate_size:  # qwen2_moe
            Is = cfg.shared_expert_intermediate_size
            layers["shared_gate"] = init(ks[13], (L, D, Is), D)
            layers["shared_up"] = init(ks[14], (L, D, Is), D)
            layers["shared_down"] = init(ks[15], (L, Is, D), Is)
            layers["shared_router"] = init(ks[16], (L, D), D)
        if cfg.shared_width:  # pangu_ultra_moe: shared experts, no gate
            Is = cfg.shared_width
            if cfg.gated_experts:
                layers["shared_gate"] = init(ks[13], (Lr, D, Is), D)
            layers["shared_up"] = init(ks[14], (Lr, D, Is), D)
            layers["shared_down"] = init(ks[15], (Lr, Is, D), Is)
    else:
        layers["w_gate"] = init(ks[5], (L, D, I), D)
        layers["w_up"] = init(ks[6], (L, D, I), D)
        layers["w_down"] = init(ks[7], (L, I, D), I)
    params: Params = {
        "embed": init(ks[0], (V, D), D),
        "layers": layers,
        "final_norm": jnp.ones((D,), dt),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = init(ks[8], (D, V), D)
    if cfg.loop_passes > 1:  # ouro: the exit gate, held and not evaluated
        params["exit_gate_w"] = init(jax.random.fold_in(rng, 7), (D,), D)
        params["exit_gate_b"] = jnp.zeros((1,), dt)
    return params


# Latent attention's parameters, each stacked over the attention layers:
# the queries' bottleneck (down, its norm, up to every head's ``[nope |
# rope]``), the latent's (down to ``[c_kv | k_r]``, the norm of ``c_kv``, up
# to every head's ``[k_nope | v]``) and the output projection ``wo``.
LATENT_PARAMS = ("w_dq", "q_a_norm", "w_uq", "w_dkv", "kv_a_norm", "w_ukv")


def _init_latent(rng, cfg: ModelConfig, init) -> Params:
    """Random latent-attention projections, ``[L_attn, ...]`` each, and the
    output projection at the values' width."""
    dt = _dtype(cfg)
    La, D, H = cfg.num_attn_layers, cfg.hidden_size, cfg.num_heads
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kl = jax.random.split(rng, 5)
    return {
        "w_dq": init(kl[0], (La, D, rq), D),
        "q_a_norm": jnp.ones((La, rq), dt),
        "w_uq": init(kl[1], (La, rq, H * (dn + dr)), rq),
        "w_dkv": init(kl[2], (La, D, rkv + dr), D),
        "kv_a_norm": jnp.ones((La, rkv), dt),
        "w_ukv": init(kl[3], (La, rkv, H * (dn + dv)), rkv),
        "wo": init(kl[4], (La, H * dv, D), H * dv),
    }


# The indexer's parameters (learned sparse attention), each stacked over
# the attention layers: its queries' projection from the queries'
# bottleneck (from the normed stream where attention is grouped-query and
# has no bottleneck), its key's from the normed stream with the key's
# LayerNorm (scale and bias), and the projection of a token's weight a head.
INDEXER_PARAMS = ("idx_wq", "idx_wk", "idx_k_norm", "idx_k_bias", "idx_wp")
# The key's LayerNorm's epsilon (DeepSeek-V3.2's indexer: torch's default).
INDEX_NORM_EPS = 1e-6


def _init_indexer(rng, cfg: ModelConfig, init) -> Params:
    """Random indexer projections, ``[L_attn, ...]`` each. The key's
    LayerNorm starts at scale 1 and a small random bias, so that a fault
    that drops the bias shows."""
    dt = _dtype(cfg)
    La, D = cfg.num_attn_layers, cfg.hidden_size
    rq = cfg.q_lora_rank if cfg.is_latent else D  # what ``q_I`` is projected from
    Hi, Di = cfg.index_n_heads, cfg.index_head_dim
    ki = jax.random.split(rng, 4)
    return {
        "idx_wq": init(ki[0], (La, rq, Hi * Di), rq),
        "idx_wk": init(ki[1], (La, D, Di), D),
        "idx_k_norm": jnp.ones((La, Di), dt),
        "idx_k_bias": (0.1 * jax.random.normal(ki[3], (La, Di), jnp.float32)).astype(dt),
        "idx_wp": init(ki[2], (La, D, Hi), D),
    }


# The step a randomly initialised Mamba layer takes: ``mamba_dt_b`` is the
# inverse softplus of a step drawn log-uniform in this range, the family's
# own initialisation. With ``A = -(1..S)`` a row the slowest state then
# forgets over 10 to 1000 tokens and the fastest within one: a state that
# neither dies nor explodes over 16k tokens.
MAMBA_DT_RANGE = (1e-3, 1e-1)
# The Mamba mixer's parameters, each stacked over the Mamba layers.
MAMBA_PARAMS = (
    "mamba_in", "mamba_conv_w", "mamba_conv_b", "mamba_x", "mamba_dt_w",
    "mamba_dt_b", "mamba_A_log", "mamba_D", "mamba_dt_norm", "mamba_b_norm",
    "mamba_c_norm", "mamba_out",
)


def _random_dt_bias(key, shape):
    """The inverse softplus of a step drawn log-uniform in ``MAMBA_DT_RANGE``
    (float32): what the Mamba, delta-rule and Mamba-2 mixers' steps start
    from."""
    lo, hi = MAMBA_DT_RANGE
    step = jnp.exp(
        jax.random.uniform(key, shape, jnp.float32)
        * (jnp.log(hi) - jnp.log(lo)) + jnp.log(lo)
    )
    return step + jnp.log(-jnp.expm1(-step))  # softplus^-1


def _init_mamba(rng, cfg: ModelConfig, init) -> Params:
    """Random Mamba-1 mixers, ``[L_mamba, ...]`` each: the projections by
    ``init`` (normal, fan-in scaled); ``A_log = log(1..S)`` a channel and
    ``D = 1`` as the family initialises them; the step's bias from
    ``MAMBA_DT_RANGE``. ``mamba_A_log`` is ``[S, E]`` (the published
    tensor transposed: the channels on lanes, as the scan's state lies),
    and with ``mamba_D`` and ``mamba_dt_b`` stays float32."""
    dt = _dtype(cfg)
    Lm, D, E = cfg.num_mamba_layers, cfg.hidden_size, cfg.mamba_inner
    S, K, R = cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_dt_rank
    km = jax.random.split(rng, 7)
    return {
        "mamba_in": init(km[0], (Lm, D, 2 * E), D),
        "mamba_conv_w": init(km[1], (Lm, K, E), K),
        "mamba_conv_b": init(km[6], (Lm, E), K),
        "mamba_x": init(km[2], (Lm, E, R + 2 * S), E),
        "mamba_dt_w": init(km[3], (Lm, R, E), R),
        "mamba_dt_b": _random_dt_bias(km[5], (Lm, E)),
        "mamba_A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, S + 1, dtype=jnp.float32))[None, :, None],
            (Lm, S, E),
        ),
        "mamba_D": jnp.ones((Lm, E), jnp.float32),
        "mamba_dt_norm": jnp.ones((Lm, R), dt),
        "mamba_b_norm": jnp.ones((Lm, S), dt),
        "mamba_c_norm": jnp.ones((Lm, S), dt),
        "mamba_out": init(km[4], (Lm, E, D), E),
    }


# A randomly initialised Mamba-2 layer's decay: ``m2_A_log = log a`` with
# ``a`` uniform in this range a head, and ``m2_dt_b`` from ``MAMBA_DT_RANGE``,
# the family's own initialisation (``NemotronHMamba2Mixer``). A step decays a
# head's state by ``exp(-dt a)`` and adds ``dt B x^T``: the state settles
# near ``B x^T / a`` and neither dies nor explodes over 16k tokens.
MAMBA2_A_RANGE = (1.0, 16.0)
# The Mamba-2 mixer's parameters, each stacked over the Mamba-2 layers.
MAMBA2_PARAMS = (
    "m2_in", "m2_dt_w", "m2_conv_w", "m2_conv_b", "m2_dt_b", "m2_A_log", "m2_D",
    "m2_norm", "m2_out",
)


def _init_mamba2(rng, cfg: ModelConfig, init) -> Params:
    """Random Mamba-2 mixers, ``[L_mamba2, ...]`` each. The published
    ``in_proj`` is held in two: ``m2_in`` ``[D, E + C]`` (the gate ``z``, then
    the convolution's ``x | B | C``) and ``m2_dt_w`` ``[D, H]`` (the step,
    whose product is taken in float32). ``m2_A_log``, ``m2_D`` and
    ``m2_dt_b`` (a head each) stay float32."""
    dt = _dtype(cfg)
    Lm, D, H = cfg.num_mamba2_layers, cfg.hidden_size, cfg.mamba2_heads
    E, C, K = cfg.mamba2_inner, cfg.mamba2_conv_width, cfg.mamba_d_conv
    km = jax.random.split(rng, 7)
    return {
        "m2_in": init(km[0], (Lm, D, E + C), D),
        "m2_dt_w": init(km[1], (Lm, D, H), D),
        "m2_conv_w": init(km[2], (Lm, K, C), K),
        "m2_conv_b": init(km[3], (Lm, C), K),
        "m2_dt_b": _random_dt_bias(km[5], (Lm, H)),
        "m2_A_log": jnp.log(
            jax.random.uniform(km[6], (Lm, H), jnp.float32, *MAMBA2_A_RANGE)
        ),
        "m2_D": jnp.ones((Lm, H), jnp.float32),
        "m2_norm": jnp.ones((Lm, E), dt),
        "m2_out": init(km[4], (Lm, E, D), E),
    }


# A randomly initialised delta-rule layer's decay: ``gdn_A_log = log a``
# with ``a`` uniform in this range a head, and ``gdn_dt_b`` from
# ``MAMBA_DT_RANGE`` as the Mamba step's, the family's own initialisation
# (flash-linear-attention's GatedDeltaNet). With keys of unit length and a
# step below 2 every token contracts the state: it neither dies nor
# explodes over 16k tokens.
LINEAR_A_RANGE = (0.0, 16.0)
# The delta-rule mixer's parameters, each stacked over the linear layers.
LINEAR_PARAMS = (
    "gdn_qkv", "gdn_conv_w", "gdn_ab", "gdn_A_log", "gdn_dt_b", "gdn_gate",
    "gdn_norm", "gdn_out",
)


def _init_linear(rng, cfg: ModelConfig, init) -> Params:
    """Random gated-delta-rule mixers, ``[L_linear, ...]`` each: the
    projections by ``init`` (normal, fan-in scaled), q, k and v's side by
    side in ``gdn_qkv`` (as their convolutions' taps in ``gdn_conv_w``) and
    the decay's and the step's in ``gdn_ab``; ``gdn_A_log`` and
    ``gdn_dt_b`` (float32) from ``LINEAR_A_RANGE`` and ``MAMBA_DT_RANGE``."""
    dt = _dtype(cfg)
    Ll, D, H = cfg.num_linear_layers, cfg.hidden_size, cfg.linear_num_heads
    dv, K, C = cfg.linear_value_head_dim, cfg.linear_conv_kernel_dim, cfg.linear_conv_width
    kl = jax.random.split(rng, 7)
    a_lo, a_hi = LINEAR_A_RANGE
    a = jax.random.uniform(kl[6], (Ll, H), jnp.float32, a_lo, a_hi)
    return {
        "gdn_qkv": init(kl[0], (Ll, D, C), D),
        "gdn_conv_w": init(kl[1], (Ll, K, C), K),
        "gdn_ab": init(kl[2], (Ll, D, 2 * H), D),
        "gdn_A_log": jnp.log(jnp.maximum(a, 1e-4)),
        "gdn_dt_b": _random_dt_bias(kl[5], (Ll, H)),
        "gdn_gate": init(kl[3], (Ll, D, H * dv), D),
        "gdn_norm": jnp.ones((Ll, dv), dt),
        "gdn_out": init(kl[4], (Ll, H * dv, D), H * dv),
    }


def param_shardings(
    cfg: ModelConfig, tp_axis: str = "tp", ep_axis: str | None = None
) -> Params:
    """PartitionSpec pytree matching ``init_params``: megatron-style TP —
    QKV/gate/up column-sharded over heads/ffn, O/down row-sharded, embed
    and lm_head vocab-sharded. With ``ep_axis``, MoE expert weights
    additionally shard their expert dim over it (the ``moe_ffn_ep``
    layout)."""
    layers: Params = {
        "attn_norm": P(None, None),
        "wq": P(None, None, tp_axis),
        "wk": P(None, None, tp_axis),
        "wv": P(None, None, tp_axis),
        "wo": P(None, tp_axis, None),
        "mlp_norm": P(None, None),
    }
    if not cfg.pre_norms:
        del layers["attn_norm"], layers["mlp_norm"]
    if cfg.kv_heads_by_form:
        layers["swa_wk"] = P(None, None, tp_axis)
        layers["swa_wv"] = P(None, None, tp_axis)
    if cfg.sink_forms:
        layers["sink"] = P(None, tp_axis)
    if cfg.is_latent:
        # Replicated: a latent cache has one head, and tp > 1 is refused
        # (``engine/config.py::refuse_latent_tiers``).
        del layers["wq"], layers["wk"], layers["wv"]
        layers.update((k, P()) for k in (*LATENT_PARAMS, "wo"))
    if cfg.has_indexer:
        layers.update((k, P()) for k in INDEXER_PARAMS)
    if cfg.num_linear_layers:
        # Replicated: the delta rule under tp > 1 is refused
        # (``engine/config.py::refuse_seq_state_tiers``).
        layers.update((k, P()) for k in LINEAR_PARAMS)
    if cfg.num_conv_layers:
        # Replicated: the three-way split of ``conv_in``'s columns and the
        # per-channel taps do not fall on a tp shard's boundaries. 17 M
        # parameters a layer, no collective.
        layers["conv_in"] = P(None, None, None)
        layers["conv_w"] = P(None, None, None)
        layers["conv_out"] = P(None, None, None)
    if cfg.num_mamba_layers:
        # Replicated: a selective scan under tp > 1 is refused
        # (``engine/config.py::refuse_seq_state_tiers``).
        layers.update((k, P()) for k in MAMBA_PARAMS)
    if cfg.num_mamba2_layers:
        # Replicated: refused under tp > 1 likewise.
        layers.update((k, P()) for k in MAMBA2_PARAMS)
    if cfg.attention_bias:
        layers["bq"] = P(None, tp_axis)
        layers["bk"] = P(None, tp_axis)
        layers["bv"] = P(None, tp_axis)
    if cfg.qk_norm:
        layers["q_norm"] = P(None, None)
        layers["k_norm"] = P(None, None)
    if cfg.post_norms:
        layers["post_attn_norm"] = P(None, None)
        layers["post_ffn_norm"] = P(None, None)
    if cfg.is_moe and cfg.use_expert_bias:
        layers["router_bias"] = P(None, None)
    if cfg.is_moe and cfg.ff_kinds.count("dense"):
        layers["dense_gate"] = P(None, None, tp_axis)
        layers["dense_up"] = P(None, None, tp_axis)
        layers["dense_down"] = P(None, tp_axis, None)
    if cfg.is_moe:
        # Replicated router; every expert's FFN tp-sharded on the ffn
        # dim (same layout as the dense path, so MoE composes with the
        # existing GSPMD collectives regardless of routing skew). With
        # an ep axis the expert dim shards too (moe_ffn_ep shard_map).
        layers["router"] = P(None, None, None)
        layers["w_gate"] = P(None, ep_axis, None, tp_axis)
        layers["w_up"] = P(None, ep_axis, None, tp_axis)
        layers["w_down"] = P(None, ep_axis, tp_axis, None)
        if cfg.shared_expert_intermediate_size:
            layers["shared_gate"] = P(None, None, tp_axis)
            layers["shared_up"] = P(None, None, tp_axis)
            layers["shared_down"] = P(None, tp_axis, None)
            layers["shared_router"] = P(None, None)
        if cfg.shared_width:
            layers["shared_gate"] = P(None, None, tp_axis)
            layers["shared_up"] = P(None, None, tp_axis)
            layers["shared_down"] = P(None, tp_axis, None)
        if not cfg.gated_experts:
            del layers["w_gate"], layers["shared_gate"]
    else:
        layers["w_gate"] = P(None, None, tp_axis)
        layers["w_up"] = P(None, None, tp_axis)
        layers["w_down"] = P(None, tp_axis, None)
    specs: Params = {
        "embed": P(tp_axis, None),
        "layers": layers,
        "final_norm": P(None),
    }
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P(None, tp_axis)
    if cfg.loop_passes > 1:
        specs["exit_gate_w"] = P(None)
        specs["exit_gate_b"] = P(None)
    return specs


def kv_cache_shardings(tp_axis: str = "tp") -> tuple[P, P]:
    """KV page pools are sharded over kv heads: [L, P, ps, Hkv*D] with
    heads collapsed into the lane dim (consecutive D-blocks per head, so
    sharding the fused axis over tp splits on head boundaries whenever
    tp divides Hkv)."""
    spec = P(None, None, None, tp_axis)
    return spec, spec


def init_kv_cache(
    cfg: ModelConfig, num_pages: int, page_size: int, dtype=None,
    shardings=(None, None), sliding_pages: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Allocate the paged KV pools: each [L, num_pages, ps, Hkv*D], L
    the cache's layer slots (``cfg.kv_layer_slots``: the layers that write
    K and V, all of them but for a model with layers of other kinds, times
    the passes of a looped model, whose every (pass, layer) has a slot).

    (kv head, head_dim) live collapsed in the trailing dim: TPU tiling
    pads the last dim to 128 lanes, so a bare D=64 axis would double
    every pool's HBM footprint; Hkv*D is 128-aligned for real configs.

    ``shardings`` (K, V) creates each pool already sharded, so no
    device ever holds more than its own shard.

    A latent-attention model has **one** pool, ``[L, num_pages, ps,
    cfg.latent_lanes]``: a token's row is ``[c_kv | k_r]`` (the latent
    after its norm, the rotary key after RoPE) padded with zeros to whole
    groups of 128 lanes, and its values are that row's first lanes. It is
    returned first; second comes an array of the same leading shape and
    **no lanes** (0 bytes), which only keeps the programs' two-pool
    signatures: nothing writes or reads it (``ModelConfig.kv_pool_widths``).

    A model whose cache is **by kind** (``ModelConfig.kv_by_kind``: sliding
    and full attention layers in one stack) has each of K and V as a PAIR
    ``(full, sliding)``: ``[L_full, num_pages, ps, Hkv*D]`` and
    ``[L_sliding, sliding_pages, ps, Hkv*D]`` (``sliding_pages`` defaults to
    ``num_pages``), each at its own form's KV heads and K's and V's own
    head widths (``ModelConfig.kv_pool_widths_of``: a model whose sliding
    layers have other KV heads than its full ones, or values narrower than
    keys, has pools of four row widths). The two have page ids, page tables
    and free lists of
    their own (``engine/kv_manager.py``), and every paged forward takes its
    ``page_table`` as the same kind of pair: a sliding layer's pages are
    given back behind its width, so its pool is sized by the dispatch and
    the full layers' by the context.
    """
    dt = dtype or _dtype(cfg)

    def pools(layers, pages, form="full"):
        lead = (layers, pages, page_size)
        return tuple(
            jnp.zeros((*lead, width), dt, device=sharding)
            for width, sharding in zip(cfg.kv_pool_widths_of(form), shardings)
        )

    if not cfg.kv_by_kind:
        return pools(cfg.kv_layer_slots, num_pages)
    n_sliding = cfg.num_sliding_layers
    full = pools(cfg.num_attn_layers - n_sliding, num_pages)
    sliding = pools(
        n_sliding, num_pages if sliding_pages is None else sliding_pages, "sliding"
    )
    return (full[0], sliding[0]), (full[1], sliding[1])


def _by_kind(cfg: ModelConfig, one) -> tuple:
    """``one`` as the pair by kind a paged forward works on: itself where
    the cache is by kind, else ``(one, one)`` (the one pool, table or write
    both forms share)."""
    return tuple(one) if cfg.kv_by_kind else (one, one)


def kv_page_size(k_cache) -> int:
    """Tokens a page of the pool(s) ``k_cache`` holds."""
    return (k_cache[0] if isinstance(k_cache, tuple) else k_cache).shape[2]


class MambaState(NamedTuple):
    """The two parts a Mamba sequence carries a layer, as pools
    ``[L_mamba, entries, ...]``: Mamba-1's (jamba) over ``E`` channels,
    Mamba-2's (nemotron_h) the convolution's over ``x | B | C`` and the
    state's over every head's channels."""

    conv: jnp.ndarray  # [Lm, entries, d_conv - 1, E or E + 2 G S], the model's dtype
    ssm: jnp.ndarray  # [Lm, entries, S, E] float32


class LinearState(NamedTuple):
    """The two parts a linear-attention sequence carries a layer, as pools
    ``[L_linear, entries, ...]``."""

    conv: jnp.ndarray  # [Ll, entries, K - 1, H * (2 dk + dv)], the model's dtype
    state: jnp.ndarray  # [Ll, entries, dk, H * dv] float32


def init_seq_state(cfg: ModelConfig, entries: int, dtype=None, sharding=None):
    """The pool of what a sequence carries from token to token beside its
    pages: a pytree by part, every leaf ``[layers of the kind, entries,
    ...]``, entry ``e`` holding the state of the sequence bound to ``e``.
    None for a model whose sequences hold pages alone.

    - a short-convolution model (lfm2_moe): one array ``[L_conv, entries,
      conv_L_cache - 1, D]``, the products ``z`` of the last tokens each
      convolution layer saw (``short_conv``);
    - a selective-scan model (jamba): :class:`MambaState`, the
      convolution's last ``mamba_d_conv - 1`` inputs and the scan's state
      ``[S, E]`` in float32 (``mamba_mixer``): 9.32 MB an entry at
      Jamba2-3B's 26 layers, not kilobytes, so the engine counts the pool
      among what it holds (``dynamo_seq_state_pool_bytes``);
    - a Mamba-2 model (nemotron_h): :class:`MambaState` too, the one
      convolution's last inputs over ``x | B | C`` and the matrix state
      ``[S, heads * head_dim]`` in float32 (``mamba2_mixer``;
      ``ops/ssd.py``): 2 MiB a layer at Nemotron-3-Nano's 64 heads of 64
      channels and 128 states, 12.8 MB an entry over the 6 Mamba-2 layers
      of its cut;
    - a linear-attention model (olmo_hybrid): :class:`LinearState`, the
      last ``linear_conv_kernel_dim - 1`` inputs of q's, k's and v's
      convolutions and the delta rule's matrix state ``[dk, H * dv]`` in
      float32 (``gated_delta_mixer``; the layout:
      ``ops/gated_delta.py``): 2.28 MB a layer at Olmo-Hybrid-7B's 30 heads
      of 96 by 192, 13.7 MB an entry over the 6 linear layers of its cut.

    Zeros are what a sequence starts from, though no forward relies on
    it: the first positions mask what lies before the sequence's start."""
    if not cfg.has_seq_state:
        return None
    dt = dtype or _dtype(cfg)
    kinds = [k for k in ("conv", "mamba", "linear", "mamba2") if k in cfg.op_kinds]
    if len(kinds) > 1:
        raise ValueError(
            f"a model of {' and '.join(kinds)} layers has no state pool: a "
            "pool holds one kind's parts"
        )
    if cfg.num_linear_layers:
        Ll, H = cfg.num_linear_layers, cfg.linear_num_heads
        return LinearState(
            jnp.zeros(
                (Ll, entries, cfg.linear_conv_kernel_dim - 1, cfg.linear_conv_width),
                dt, device=sharding,
            ),
            jnp.zeros(
                (Ll, entries, cfg.linear_key_head_dim, H * cfg.linear_value_head_dim),
                jnp.float32, device=sharding,
            ),
        )
    if cfg.num_mamba_layers or cfg.num_mamba2_layers:
        Lm, E, C = cfg.num_mamba_layers, cfg.mamba_inner, cfg.mamba_inner
        if cfg.num_mamba2_layers:
            Lm, E, C = cfg.num_mamba2_layers, cfg.mamba2_inner, cfg.mamba2_conv_width
        return MambaState(
            jnp.zeros((Lm, entries, cfg.mamba_d_conv - 1, C), dt, device=sharding),
            jnp.zeros((Lm, entries, cfg.mamba_d_state, E), jnp.float32, device=sharding),
        )
    return jnp.zeros(
        (cfg.num_conv_layers, entries, cfg.conv_L_cache - 1, cfg.hidden_size),
        dt, device=sharding,
    )


def seq_state_entries(state) -> int:
    """How many entries a state pool (``init_seq_state``) has."""
    return jax.tree.leaves(state)[0].shape[1]


def rms_norm(
    x: jnp.ndarray, w: jnp.ndarray, eps: float, offset: bool = False
) -> jnp.ndarray:
    """``offset`` (gemma): weights are stored as w with scale (1 + w),
    and the whole product stays float32 until one final cast (HF
    GemmaRMSNorm) — (w + 1) in bf16 would round away exactly the
    near-1.0 precision the storage convention exists to keep. The
    non-offset path multiplies after the downcast, matching HF
    LlamaRMSNorm."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    if offset:
        return (normed * (w.astype(jnp.float32) + 1.0)).astype(x.dtype)
    return normed.astype(x.dtype) * w


def _act(name: str):
    if name == "gelu_tanh":
        return lambda x: jax.nn.gelu(x, approximate=True)
    if name == "relu2":
        from ..ops.moe import relu2

        return relu2
    return jax.nn.silu


def _attn_mlp_layer(
    x, lp, cfg, inv_freq, rope_pos, eps, attend, reduce=None, mesh=None,
    live=None, layer=None, interpret=False,
):
    """One transformer layer, shared by the paged and ring paths.

    ``attend(q, k, v) -> (attn_out, kv_extra)`` is the only thing that
    differs between them; everything else (norms, projections, rope,
    residuals, SwiGLU) must stay identical or prefill logits silently
    diverge from decode.

    Head counts come from the weight shapes (not the config) so the
    body works unchanged on tensor-parallel shards, where each rank
    holds H/tp heads. ``reduce`` (e.g. ``psum`` over the tp axis) is
    applied to the two row-sharded matmul outputs before the residual
    adds; None means the weights are unsharded.

    Family variations live in the param pytree: ``bq/bk/bv`` present =
    QKV bias (qwen2); ``router`` present = sparse-MoE FFN (mixtral).
    ``live`` ([B, T] bool, False = padding) is for that FFN alone: the
    one operation in which the rows of a dispatch meet (``_ffn_block``),
    as are ``layer`` (``lp``'s expert matrices are the whole stacks and
    this is the layer to read) and ``interpret``.

    The ``jax.named_scope`` blocks (``qkv``, ``attn_out``, ``ffn`` /
    ``moe`` here; ``kv_write`` and ``attend`` in the callers' ``attend``)
    are HLO metadata only: they name each operation's place in the layer
    for a profiler trace (docs/observability.md "Engine phases and the
    device trace") and change no operation.
    """
    red = reduce if reduce is not None else (lambda y: y)
    x, kv_extra = _attn_half(x, lp, cfg, inv_freq, rope_pos, eps, attend, red)
    x = _ffn_half(x, lp, cfg, eps, red, mesh, live, layer, interpret)
    return x, kv_extra


def _attn_half(x, lp, cfg, inv_freq, rope_pos, eps, attend, red, rope=True):
    """A layer's first half where its operator is attention: norm,
    projections, rope, ``attend``, output projection, residual add.
    ``rope`` False: this layer has no positional term though the model's
    other layers do (exaone_moe's full-attention layers). The KV heads
    come from the weights' shapes (a model's forms may differ in them); a
    head's values may be narrower than its keys (``value_head_dim_``) and
    scaled (``value_scale``); with ``rotary_dim`` only a head's first values
    turn; with ``mrope_section`` a head's rotary pairs turn by sections over
    ``rope_pos`` [3, B, T].

    **The indexer** (``cfg.has_indexer``: KeyeVL2), scope ``dsa_index``:
    :func:`index_projections` from the normed stream. ``attend`` then also
    gets ``index = (q_I, the index key's row, w)``: it writes the row behind
    the token's keys in the K pool and lets a query attend only to the
    ``index_topk`` positions of largest index score, as ``_mla_half``'s
    ``read`` does."""
    B, T = x.shape[:2]
    hd, dv = cfg.head_dim_, cfg.value_head_dim_
    off = cfg.rms_norm_offset
    with jax.named_scope("qkv"):
        # olmo_hybrid's block has no norm here: its output is normed below
        h = rms_norm(x, lp["attn_norm"], eps, off) if "attn_norm" in lp else x
        q, k, v = h @ lp["wq"], h @ lp["wk"], h @ lp["wv"]
        if "bq" in lp:
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        whole = cfg.qk_norm_whole and "q_norm" in lp
        if whole:  # olmo: one RMSNorm over all heads' projection
            q = rms_norm(q, lp["q_norm"], eps, off)
            k = rms_norm(k, lp["k_norm"], eps, off)
        q = q.reshape(B, T, lp["wq"].shape[-1] // hd, hd)
        k = k.reshape(B, T, lp["wk"].shape[-1] // hd, hd)
        v = v.reshape(B, T, lp["wv"].shape[-1] // dv, dv)
        if cfg.value_scale != 1.0:  # mimo_v2_flash: ahead of the cache
            v = v * jnp.asarray(cfg.value_scale, v.dtype)
        if "q_norm" in lp and not whole:  # qwen3/gemma3: per-head RMSNorm before rope
            # (gemma3 stores these gemma-style: scale = 1 + w)
            q = rms_norm(q, lp["q_norm"], eps, off)
            k = rms_norm(k, lp["k_norm"], eps, off)
        if cfg.use_rope and rope:  # jamba's attention has no positional term
            q = _turn(q, rope_pos, inv_freq, cfg.rotary_dim, cfg.mrope_section)
            k = _turn(k, rope_pos, inv_freq, cfg.rotary_dim, cfg.mrope_section)
    if cfg.has_indexer:
        with jax.named_scope("dsa_index"):
            q_i, k_i, w_i = index_projections(h, h, lp, cfg, inv_freq, rope_pos)
        attn, kv_extra = attend(q, k, v, index=(q_i, k_i, w_i))
    else:
        attn, kv_extra = attend(q, k, v)
    with jax.named_scope("attn_out"):
        attn_out = red(attn.reshape(B, T, -1) @ lp["wo"])
        if "post_attn_norm" in lp:  # gemma2: norm the block OUTPUT too
            attn_out = rms_norm(attn_out, lp["post_attn_norm"], eps, off)
        x = x + attn_out
    return x, kv_extra


def _turn(x, rope_pos, inv_freq, rotary_dim: int, sections: tuple = ()):
    """``apply_rope`` over a head's first ``rotary_dim`` values (its pairs
    ``(i, i + rotary_dim / 2)``), the rest as they are; 0: the whole head.
    ``sections`` (KeyeVL2's ``mrope_section``): the whole head by sections
    over the three components of ``rope_pos`` [3, B, T] (``apply_mrope``)."""
    if sections:
        return apply_mrope(x, rope_pos, inv_freq, sections)
    if not rotary_dim:
        return apply_rope(x, rope_pos, inv_freq)
    return jnp.concatenate([
        apply_rope(x[..., :rotary_dim], rope_pos, inv_freq), x[..., rotary_dim:]
    ], axis=-1)


def latent_value_lanes(cfg: ModelConfig) -> int:
    """Lanes of a latent pool's row the attention kernel takes as a
    token's values: ``c_kv``'s, up to a whole group of 128 (what lies
    behind ``kv_lora_rank`` there is dropped after the read)."""
    return min(cfg.latent_lanes, -(-cfg.kv_lora_rank // 128) * 128)


def latent_form_queries(
    cfg: ModelConfig, q_tile: int, page_size: int, kv_dtype, interpret: bool = False
) -> int | None:
    """The rule by which a row of a kernel call's tiled stream takes the
    expanded form of latent attention: at least this many live queries of
    the row in the call (``ops/ragged_attention.py::expanded_min_queries``,
    from the model's widths and the tile: 160 at openPangu's, 360 at
    GLM-5's, whose selection both forms apply). None: no row does (another
    kind of model, or widths the expanded call does not tile on a chip). The
    program (``forward_ragged``) and the engine's counter
    (``_count_latent_reads``) both ask here."""
    from ..ops.ragged_attention import expanded_min_queries, expanded_supported

    if not cfg.is_latent:
        return None
    if not interpret and not expanded_supported(
        page_size, cfg.latent_lanes, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
        cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.num_heads, kv_dtype,
    ):
        return None
    return expanded_min_queries(
        cfg.latent_lanes, latent_value_lanes(cfg), cfg.kv_lora_rank,
        cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, q_tile,
    )


# Stream slots of a latent model's tiled part that one call of the
# attention kernel takes: the absorbed queries and the latent outputs of a
# slab ([slots, H, lanes]: 168 KB a slot at 128 heads) are built, read and
# dropped before the next slab's, so a dispatch of 8192 tokens holds 0.6 GB
# of them at a time and not 2.4 (``forward_ragged``).
LATENT_SLAB = 2048


def _mla_half(x, lp, cfg, inv_freq, rope_pos, eps, write, read, red):
    """A layer's first half where its operator is multi-head latent
    attention. The projections and the cache are one thing, the form of the
    attention a function of how many queries of a row meet its context in
    the call (``latent_form_queries``):

    ``c_q = RMS(u W_dq)``; ``q_h = c_q W_uq -> [q_nope | q_rope]``, RoPE on
    ``q_rope``; ``[c_kv | k_r] = u W_dkv``, ``c_kv = RMS(c_kv)``, RoPE on
    ``k_r``: the row ``write`` puts into the pool. ``W_ukv`` a head is
    ``[W_uk | W_uv]``.

    **Absorbed** (a row of few queries: a decode row, every step of a
    window, a prompt's short last chunk; every row off the kernel):
    ``q'_h = q_nope_h W_uk_h^T`` and the scores are ``(q'_h . c_kv +
    q_rope_h . k_r) / sqrt(nope + rope)``, so the kernel reads the pool's
    rows as keys and their first lanes as values, and ``o_h = o_lat_h
    W_uv_h``: the expanded form's mathematics reordered, with no per-head K
    or V ever built, at ``2 (lanes + value lanes)`` operations a query-key
    pair and head.

    **Expanded** (a row of many queries: a prompt chunk, of a model with an
    indexer too): ``k_h = [c_kv
    W_uk_h | k_r]`` with the one rotated ``k_r`` shared by the heads, ``v_h =
    c_kv W_uv_h``, built from the row's pages a chunk of context at a time
    inside the kernel (``ops/ragged_attention.py::
    ragged_paged_attention_expanded``), once for all the queries of a pass
    and never cached: ``2 (nope + rope + v)`` operations a pair and head,
    the published form's.

    ``u`` is the normed stream; a model with four norms a layer
    (pangu_ultra_moe) norms the output again before the add.

    **The indexer** (``cfg.has_indexer``: glm_moe_dsa), scope ``dsa_index``:
    ``q_I = c_q W_Iq`` (``index_n_heads`` heads of ``index_head_dim``), its
    first ``qk_rope_head_dim`` lanes rotary; ``k_I = LayerNorm(u W_Ik)``,
    its first lanes rotary likewise: the row ``write`` puts into the second
    pool beside the latent row; ``w = u W_Iw * (heads * width)^-1/2`` in
    float32. ``read`` gets ``index = (q_I, w, the index pool)`` and lets a
    query attend only to the ``index_topk`` positions of largest index
    score (``ops/sparse_index.py``), in whichever form its row takes: the
    selection of a stream's tiled part is computed once and both calls
    mask by it.

    ``write(row, index_row) -> (pool, index pool)``; ``index_row`` None
    where the model has no indexer.
    ``read(q, pool, absorb, expand, w_ukv, index) -> o`` ([B, T, H, v]) runs the
    kernel on ``absorb(q_part)`` for each part of the stream it reads
    absorbed at a time and hands ``expand`` of each part's result back: the
    two products either side of the kernel (scope ``mla_absorb``) are then
    as large as a part, not as the dispatch; ``w_ukv`` ([rank, H * (nope +
    v)]) is what the expanded call multiplies the latents by."""
    B, T = x.shape[:2]
    H, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    lanes = cfg.latent_lanes
    pad = lanes - cfg.latent_width
    with jax.named_scope("mla"):
        h = rms_norm(x, lp["attn_norm"], eps)
        with jax.named_scope("mla_q"):
            c_q = rms_norm(h @ lp["w_dq"], lp["q_a_norm"], eps)
            q = (c_q @ lp["w_uq"]).reshape(B, T, H, dn + dr)
            q = jnp.concatenate(
                [q[..., :dn], apply_rope(q[..., dn:], rope_pos, inv_freq)], axis=-1
            )
        with jax.named_scope("mla_kv"):
            ckr = h @ lp["w_dkv"]  # [B, T, r + dr]
            c_kv = rms_norm(ckr[..., :r], lp["kv_a_norm"], eps)
            k_r = apply_rope(ckr[..., None, r:], rope_pos, inv_freq)[..., 0, :]
            row = jnp.concatenate(
                [c_kv, k_r, jnp.zeros((B, T, pad), c_kv.dtype)], axis=-1
            )
            if not cfg.has_indexer:
                pool, index_pool = write(row, None)
        index = None
        if cfg.has_indexer:
            with jax.named_scope("dsa_index"):
                q_i, k_i, w_i = index_projections(
                    h, c_q, lp, cfg, inv_freq, rope_pos
                )
                pool, index_pool = write(row, k_i)
            index = (q_i, w_i, index_pool)
        w_ukv = lp["w_ukv"].reshape(r, H, -1)  # [r, H, nope + v]
        # One product lays the absorbed query out as the pool's row is:
        # ``[q_nope W_uk^T | q_rope | 0]``. The rotary part rides an identity
        # block (exact in any dtype), so the kernel's operand is written
        # once and never concatenated.
        w_abs = jnp.concatenate([
            jnp.pad(
                jnp.swapaxes(w_ukv[..., :dn], 0, 2), ((0, 0), (0, 0), (0, dr + pad))
            ),  # [nope, H, lanes]: W_uk^T, zeros behind it
            jnp.broadcast_to(
                jnp.pad(jnp.eye(dr, dtype=w_ukv.dtype), ((0, 0), (r, pad)))[:, None],
                (dr, H, lanes),
            ),  # [rope, H, lanes]: lane r + j takes q_rope[j]
        ])

        def absorb(q_part):  # [.., H, nope + rope] -> [.., H, lanes]
            with jax.named_scope("mla_absorb"):
                return jnp.einsum("...hd,dhc->...hc", q_part, w_abs)

        def expand(o_lat):  # [.., H, latent_value_lanes] -> [.., H, v]
            with jax.named_scope("mla_absorb"):
                return jnp.einsum("...hc,chv->...hv", o_lat[..., :r], w_ukv[..., dn:])

        o = read(q, pool, absorb, expand, lp["w_ukv"], index)
        with jax.named_scope("attn_out"):
            attn_out = red(o.reshape(B, T, -1) @ lp["wo"])
            if "post_attn_norm" in lp:
                attn_out = rms_norm(attn_out, lp["post_attn_norm"], eps)
        return x + attn_out, (pool, index_pool)


def index_projections(h, c_q, lp, cfg, inv_freq, rope_pos):
    """The indexer's three products of a layer, the one function both
    attention halves call (``_mla_half``, ``_attn_half``): from the normed
    stream ``h`` [B, T, D] and what the index queries are projected from,
    ``c_q`` (latent attention: the queries' bottleneck [B, T, q_lora_rank];
    grouped-query attention has none: the normed stream again), (``q_I`` [B,
    T, Hi, Di], its first ``index_rope_dim`` lanes rotary; the index key's
    row [B, T, index_lanes]: ``k_I`` after its LayerNorm, its first lanes
    rotary, zeros behind ``Di``; ``w`` [B, T, Hi] float32, scaled by ``(Hi *
    Di)^-1/2``). Under latent attention the rotary lanes are the rotary
    key's, at its frequencies (``inv_freq``); under grouped-query attention
    all ``Di`` lanes turn, at the model's base over that width, by the
    temporal component where ``rope_pos`` has three."""
    B, T = h.shape[:2]
    Hi, Di, dr = cfg.index_n_heads, cfg.index_head_dim, cfg.index_rope_dim
    if not cfg.is_latent:
        inv_freq = rope_frequencies(dr, cfg.rope_theta)
        if cfg.mrope_section:
            rope_pos = rope_pos[0]
    q_i = (c_q @ lp["idx_wq"]).reshape(B, T, Hi, Di)
    q_i = jnp.concatenate(
        [apply_rope(q_i[..., :dr], rope_pos, inv_freq), q_i[..., dr:]], axis=-1
    )
    k_i = _layer_norm(
        h @ lp["idx_wk"], lp["idx_k_norm"], lp["idx_k_bias"], INDEX_NORM_EPS
    )
    k_i = jnp.concatenate([
        apply_rope(k_i[..., None, :dr], rope_pos, inv_freq)[..., 0, :],
        k_i[..., dr:],
        jnp.zeros((B, T, cfg.index_lanes - Di), k_i.dtype),
    ], axis=-1)
    w_i = (h @ lp["idx_wp"]).astype(jnp.float32) * (Hi * Di) ** -0.5
    return q_i, k_i, w_i


def _layer_norm(x, w, b, eps):
    """LayerNorm over the last axis in float32, scale and bias, cast back."""
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (normed * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


class SeqStateIO(NamedTuple):
    """Where the rows of one forward keep what their sequences carry from
    token to token (``init_seq_state``'s pool), as the forward's flat
    token stream sees it. Tokens of a row lie in order; a token whose
    predecessor in the stream is its row's previous position reads that
    token's state, any other reads its row's pool entry.

    ``src``/``dst`` are the writes: the state as it stands after stream
    token ``src[w]`` goes to pool entry ``dst[w]``. A ``dst`` past the
    pool is no write (a padding row, a row with no token). The engine
    writes each row's own entry and, for a prefill row, a second entry a
    later prefix hit may restore from (``engine/kv_manager.py``).

    A state that is a few products a layer (the convolutions', all three
    kinds') is kept a token and any ``src`` is honoured. A selective
    scan's state is ``[S, E]`` float32 a token and layer (320 KiB at
    Jamba2-3B's widths) and is not: the scan kernel stores at the ends of
    its tiles, so there ``src`` is the last real token of its row in its
    tile of ``ragged_q_tile`` slots (``ops/selective_scan.py``, WRITES),
    which is what the engine's two writes a row are. The delta rule's
    matrix state (2.2 MB a token and layer at Olmo-Hybrid-7B's) is not
    kept a token either, and its kernel honours any ``src`` that is a real
    token, two a chunk (``ops/gated_delta.py``, WRITES)."""

    slots: jnp.ndarray  # [R] int32: the pool entry each row reads
    row_of: jnp.ndarray  # [N] int32: each stream token's row
    positions: jnp.ndarray  # [N] int32: its position, -1 = padding
    src: jnp.ndarray  # [W] int32: stream index of a token
    dst: jnp.ndarray  # [W] int32: pool entry the state after it goes to


class ScanForm(NamedTuple):
    """How a forward runs its recurrence, the selective scan or the delta
    rule (static): ``kernel`` (the
    Pallas kernel over the stream, else the oracle), ``packed_from`` (with
    the kernel: the stream's slots from here on are one-token rows unless
    a span runs on into them, and take the one-step form), ``one_token``
    (every row is one token and ``src`` its row's: a decode step)."""

    kernel: bool = False
    packed_from: int | None = None
    one_token: bool = False
    interpret: bool = False


def _stream_history(z, held, io: SeqStateIO, K: int):
    """``back[j] = z_{t-j}`` for each stream token ``t`` and ``j < K``:
    the stream's where token ``t-j`` of the same row is in it, the row's
    pool entry otherwise (``held`` [N, K-1, C], oldest first, ending at
    the last token the row has seen), zeros before the sequence's start.
    The one implementation of "predecessor in the stream, else the row's
    entry" for both convolutions; the scan reads the same rule
    (``ops/selective_scan.py::stream_predecessor``)."""
    from ..ops.selective_scan import stream_predecessor

    pos = io.positions
    prev = stream_predecessor(io.row_of, pos)[:, None]
    back = [z]
    for j in range(1, K):
        shifted = jnp.concatenate(
            [jnp.zeros((1, z.shape[1]), z.dtype), back[-1][:-1]]
        )
        zj = jnp.where(prev, shifted, held[:, K - 1 - j])
        back.append(jnp.where((pos >= j)[:, None], zj, 0))  # before the start
    return back


def _history_after(back, src):
    """The ``K-1`` newest of ``back`` as they stand after stream tokens
    ``src``, oldest first: what a pool entry holds ([W, K-1, C])."""
    K = len(back)
    return jnp.stack([back[K - 2 - i][src] for i in range(K - 1)], axis=1)


def short_conv(u, lp, pool, layer, io: SeqStateIO):
    """LFM2's gated short convolution over a flat token stream, with the
    carried state read from and written to ``pool[layer]``.

    ``[B, C, X] = split3(u @ conv_in)``; ``z = B * X``; ``c_t = sum_j
    conv_w[j] * z_{t-(K-1)+j}`` (depthwise, causal, zeros before the
    sequence's start); returns ``(C * c) @ conv_out`` ([N, D]) and the
    pool. ``z_{t-j}`` is the stream's where token ``t-j`` of the same row
    is in it and the row's entry otherwise; an entry holds the ``K-1``
    products that end at the last token its row has seen, oldest first.
    Plain ``jax.numpy``: three taps over D channels are a fused
    multiply-add between two matmuls."""
    taps = lp["conv_w"]  # [K, D]; taps[K-1] weighs z_t
    K = taps.shape[0]
    gate_b, gate_c, xin = jnp.split(u @ lp["conv_in"], 3, axis=-1)
    z = gate_b * xin  # [N, D]
    held = pool[layer][io.slots[io.row_of]]  # [N, K-1, D], oldest first
    back = _stream_history(z, held, io, K)
    acc = taps[K - 1] * z
    for j in range(1, K):
        acc = acc + taps[K - 1 - j] * back[j]
    y = (gate_c * acc) @ lp["conv_out"]
    new = _history_after(back, io.src)
    pool = pool.at[layer, io.dst].set(new.astype(pool.dtype), mode="drop")
    return y, pool


def _stream_recurrence(pool, io: SeqStateIO, form: ScanForm, ref, step, kernel):
    """A recurrence over this forward's stream in the form ``form`` names,
    whichever recurrence it is: ``ref(pool)`` the oracle over the stream,
    ``kernel(pool, positions, dst)`` the Pallas kernel over it,
    ``step(pool, part, slots, positions, dst)`` one token a row for the
    slots ``part`` of the stream. Each returns (y, pool); so does this."""
    from ..ops.selective_scan import stream_predecessor

    if form.one_token:
        return step(pool, slice(None), io.slots[io.row_of], io.positions, io.dst)
    if not form.kernel:
        return ref(pool)
    pos, src, dst = io.positions, io.src, io.dst
    at = form.packed_from
    if at is None:
        return kernel(pool, pos, dst)
    # The packed tail: a slot there is a row of one token (a decode row),
    # unless a span of the tiles ran on into it. The kernel walks every
    # span, the rows of one token take one step each.
    entries = pool.shape[1]
    n = pos.shape[0]
    prev = stream_predecessor(io.row_of, pos)
    alone = ~prev & ~jnp.concatenate([prev[1:], jnp.zeros((1,), bool)])
    alone = alone & (jnp.arange(n) >= at) & (pos >= 0)
    y, pool = kernel(
        pool, jnp.where(alone, -1, pos), jnp.where(alone[src], entries, dst)
    )
    tail = slice(at, None)
    to = jnp.full((n,), entries, jnp.int32).at[src].min(
        jnp.minimum(dst, entries).astype(jnp.int32)
    )
    y1, pool = step(
        pool, tail, io.slots[io.row_of[tail]],
        jnp.where(alone[tail], pos[tail], -1),
        jnp.where(alone[tail], to[tail], entries),
    )
    y = jnp.concatenate([y[:at], jnp.where(alone[tail][:, None], y1, y[tail])])
    return y, pool


def _selective_scan(x, dt, a, b, c, d, pool, layer, io: SeqStateIO, form: ScanForm):
    """The selective scan over this forward's stream
    (``ops/selective_scan.py`` has the three forms). Returns (y [N, E]
    float32, the scan's pool)."""
    from ..ops import selective_scan as ss

    return _stream_recurrence(
        pool, io, form,
        lambda pool: ss.selective_scan_ref(
            x, dt, a, b, c, d, pool, layer, io.slots, io.row_of, io.positions,
            io.src, io.dst,
        ),
        lambda pool, part, slots, pos, dst: ss.selective_scan_step(
            x[part], dt[part], a, b[part], c[part], d, pool, layer, slots, pos, dst,
        ),
        lambda pool, pos, dst: ss.selective_scan_kernel(
            x, dt, a, b, c, d, pool, layer, io.slots, io.row_of, pos, io.src, dst,
            interpret=form.interpret,
        ),
    )


def _gated_delta(qkv, g, beta, pool, layer, io: SeqStateIO, form: ScanForm):
    """The gated delta rule over this forward's stream
    (``ops/gated_delta.py`` has the four forms), ``qkv`` [N, H * (2 dk + dv)]
    as the convolution leaves it. Returns (o [N, H * dv] float32, the
    states' pool). The chunk kernel brings q and k to length 1 itself; the
    other forms take them from ``unit_heads``, over the slots they serve.
    One token a row takes the in-place kernel wherever the stream takes the
    chunk kernel (``form.kernel``: the chip), the plain form otherwise."""
    from ..ops import gated_delta as gd

    heads, dk = g.shape[-1], pool.shape[2]
    one_step = (
        functools.partial(gd.gated_delta_step_kernel, interpret=form.interpret)
        if form.kernel else gd.gated_delta_step
    )
    return _stream_recurrence(
        pool, io, form,
        lambda pool: gd.gated_delta_ref(
            *gd.unit_heads(qkv, heads, dk), g, beta, pool, layer, io.slots,
            io.row_of, io.positions, io.src, io.dst,
        ),
        lambda pool, part, slots, pos, dst: one_step(
            *gd.unit_heads(qkv[part], heads, dk), g[part], beta[part], pool, layer,
            slots, pos, dst,
        ),
        lambda pool, pos, dst: gd.gated_delta_kernel(
            qkv, g, beta, pool, layer, io.slots, io.row_of, pos, io.src, dst,
            interpret=form.interpret,
        ),
    )


def gated_delta_mixer(u, lp, cfg, state: "LinearState", layer, io: SeqStateIO,
                      form: ScanForm):
    """Olmo-Hybrid's linear attention (Gated DeltaNet) over a flat token
    stream ``u`` ([N, D], the un-normed residual stream), the carried state
    read from and written to ``state.conv[layer]`` and
    ``state.state[layer]``. Returns ([N, D], the state). ``H`` heads of
    ``dk`` by ``dv``:

    ``[z^q, z^k, z^v] = u @ gdn_qkv``; ``c_t = silu(sum_j conv_w[j] *
    z_{t-(K-1)+j})`` (depthwise, causal, no bias), split back into heads;
    ``q = c^q / |c^q| / sqrt(dk)``, ``k = c^k / |c^k|``, ``v = c^v``;
    ``[a, b] = u @ gdn_ab``; ``g = -exp(A_log) softplus(a + dt_b)``;
    ``beta = sigmoid(b)``, doubled where ``linear_allow_neg_eigval``; the
    recurrence (``ops/gated_delta.py``); returns ``(RMS_head(o) *
    silu(u @ gdn_gate)) @ gdn_out``, the norm a head at a time with one
    learned scale of ``dv``.

    The matrix products run in the model's dtype with float32 results
    where the decay and the step read them; everything between the
    convolution and the output's gate is float32, the state from end to
    end. WHO BRINGS q AND k TO LENGTH 1: the recurrence does
    (``_gated_delta`` takes ``c`` whole): the chunk kernel a head's rows
    in VMEM, the other forms through ``ops/gated_delta.py::unit_heads``
    over the slots they serve, both ``x rsqrt(sum x^2 + 1e-6)``."""
    f32 = jnp.float32
    H, dk, dv = cfg.linear_num_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    n = u.shape[0]
    # float32 from here to the output's gate; an entry holds its three
    # inputs in the model's dtype, as every kind's convolution does
    z = jnp.dot(u, lp["gdn_qkv"], preferred_element_type=f32)  # [N, H (2 dk + dv)]
    with jax.named_scope("gdn_conv"):
        taps = lp["gdn_conv_w"].astype(f32)  # [K, C]; taps[K-1] weighs z_t
        K = taps.shape[0]
        held = state.conv[layer, io.slots[io.row_of]]  # [N, K-1, C], one gather
        back = _stream_history(z, held, io, K)
        acc = taps[K - 1] * z
        for j in range(1, K):
            acc = acc + taps[K - 1 - j] * back[j]
        c = jax.nn.silu(acc)  # [N, C] float32
        conv = state.conv.at[layer, io.dst].set(
            _history_after(back, io.src).astype(state.conv.dtype), mode="drop"
        )
    ab = jnp.dot(u, lp["gdn_ab"], preferred_element_type=f32)  # [N, 2 H]
    g = -jnp.exp(lp["gdn_A_log"]) * jax.nn.softplus(ab[:, :H] + lp["gdn_dt_b"])
    beta = jax.nn.sigmoid(ab[:, H:]) * (2.0 if cfg.linear_allow_neg_eigval else 1.0)
    with jax.named_scope("gdn_scan"):
        o, pool = _gated_delta(c, g, beta, state.state, layer, io, form)
    o = rms_norm(o.reshape(n, H, dv), lp["gdn_norm"].astype(f32), cfg.rms_norm_eps)
    gate = jax.nn.silu((u @ lp["gdn_gate"]).astype(f32))
    out = (o.reshape(n, H * dv) * gate).astype(u.dtype) @ lp["gdn_out"]
    return out, LinearState(conv, pool)


def mamba_mixer(u, lp, cfg, state: "MambaState", layer, io: SeqStateIO, form: ScanForm):
    """Jamba's Mamba-1 mixer over a flat token stream ``u`` ([N, D], the
    normed input), the carried state read from and written to
    ``state.conv[layer]`` and ``state.ssm[layer]``. Returns ([N, D], the
    state). ``E`` the inner width, ``S`` states, ``R`` the step's rank:

    ``[x, g] = split2(u @ mamba_in)``; ``c_t = silu(conv_b + sum_j
    conv_w[j] * x_{t-(K-1)+j})`` (depthwise, causal); ``[tau, B, C] =
    split(c @ mamba_x)``, each RMS-normed (Jamba's inner norms);
    ``dt = softplus(tau @ mamba_dt_w + mamba_dt_b)``; ``A = -exp(A_log)``;
    ``h_t = exp(dt_t A) h_{t-1} + dt_t c_t B_t``; ``y_t = h_t C_t + D
    c_t``; returns ``(y * silu(g)) @ mamba_out``.

    The matrix products run in the model's dtype with float32 results
    where a norm, the softplus or the scan reads them; everything between
    the products is float32, the scan's state from end to end."""
    f32 = jnp.float32
    S, R, eps = cfg.mamba_d_state, cfg.mamba_dt_rank, cfg.rms_norm_eps
    xin, gate = jnp.split(u @ lp["mamba_in"], 2, axis=-1)  # [N, E] each
    with jax.named_scope("mamba_conv"):
        taps = lp["mamba_conv_w"].astype(f32)  # [K, E]; taps[K-1] weighs x_t
        K = taps.shape[0]
        held = state.conv[layer, io.slots[io.row_of]]  # [N, K-1, E], one gather
        back = _stream_history(xin, held, io, K)
        acc = lp["mamba_conv_b"].astype(f32) + taps[K - 1] * xin
        for j in range(1, K):
            acc = acc + taps[K - 1 - j] * back[j]
        c = jax.nn.silu(acc)  # [N, E] float32
        conv = state.conv.at[layer, io.dst].set(
            _history_after(back, io.src).astype(state.conv.dtype), mode="drop"
        )
    tbc = jnp.dot(c.astype(u.dtype), lp["mamba_x"], preferred_element_type=f32)
    tau = rms_norm(tbc[:, :R], lp["mamba_dt_norm"], eps)
    b = rms_norm(tbc[:, R : R + S], lp["mamba_b_norm"], eps)
    cc = rms_norm(tbc[:, R + S :], lp["mamba_c_norm"], eps)
    dt = jax.nn.softplus(
        jnp.dot(tau.astype(u.dtype), lp["mamba_dt_w"], preferred_element_type=f32)
        + lp["mamba_dt_b"]
    )
    with jax.named_scope("ssm_scan"):
        y, ssm = _selective_scan(
            c, dt, -jnp.exp(lp["mamba_A_log"]), b, cc, lp["mamba_D"],
            state.ssm, layer, io, form,
        )
    out = (y * jax.nn.silu(gate.astype(f32))).astype(u.dtype) @ lp["mamba_out"]
    return out, MambaState(conv, ssm)


def _ssd(xbc, dt, a, d, pool, layer, io: SeqStateIO, form: ScanForm):
    """Mamba-2's recurrence over this forward's stream (``ops/ssd.py`` has
    the four forms), ``xbc`` [N, H * P + 2 G S] as the convolution leaves it
    (x, every group's B, every group's C). Returns (y [N, H * P] float32,
    the states' pool). One token a row takes the in-place kernel wherever
    the stream takes the chunk kernel (``form.kernel``: the chip), the plain
    form otherwise."""
    from ..ops import ssd

    inner = pool.shape[3]
    cut = inner + (xbc.shape[-1] - inner) // 2
    x, b, c = xbc[:, :inner], xbc[:, inner:cut], xbc[:, cut:]
    one_step = (
        functools.partial(ssd.ssd_step_kernel, interpret=form.interpret)
        if form.kernel else ssd.ssd_step
    )
    return _stream_recurrence(
        pool, io, form,
        lambda pool: ssd.ssd_ref(
            x, dt, a, b, c, d, pool, layer, io.slots, io.row_of, io.positions,
            io.src, io.dst,
        ),
        lambda pool, part, slots, pos, dst: one_step(
            x[part], dt[part], a, b[part], c[part], d, pool, layer, slots, pos, dst,
        ),
        lambda pool, pos, dst: ssd.ssd_kernel(
            xbc, dt, a, d, pool, layer, io.slots, io.row_of, pos, io.src, dst,
            interpret=form.interpret,
        ),
    )


def mamba2_mixer(u, lp, cfg, state: "MambaState", layer, io: SeqStateIO, form: ScanForm):
    """Nemotron-H's Mamba-2 mixer over a flat token stream ``u`` ([N, D], the
    normed input), the carried state read from and written to
    ``state.conv[layer]`` and ``state.ssm[layer]``. Returns ([N, D], the
    state). ``H`` heads of ``P`` channels (``E = H P``), ``S`` states, B and
    C in ``G`` groups:

    ``[z | xBC] = u @ m2_in`` (widths ``E | E + 2 G S``), ``dt = softplus(u @
    m2_dt_w + m2_dt_b)`` (the published ``in_proj``'s three parts);
    ``[x | B | C] = split(silu(conv_b + sum_j conv_w[j] *
    xBC_{t-(K-1)+j}))`` (ONE depthwise causal convolution over all three);
    ``A = -exp(A_log)`` a head; the recurrence (``ops/ssd.py``); ``y =
    group_rms_norm(y * silu(z))``: the gate BEFORE the norm, each of the
    ``G`` groups of ``E / G`` channels normed apart under one weight of
    ``E``; returns ``y @ m2_out``.

    The matrix products run in the model's dtype, the step's with a float32
    result; everything between the convolution and the output's norm is
    float32, the state from end to end."""
    f32 = jnp.float32
    G, E = cfg.mamba2_groups, cfg.mamba2_inner
    n = u.shape[0]
    zx = u @ lp["m2_in"]  # [N, E + C]
    gate, xbc = zx[:, :E], zx[:, E:]
    dt = jax.nn.softplus(
        jnp.dot(u, lp["m2_dt_w"], preferred_element_type=f32) + lp["m2_dt_b"]
    )
    with jax.named_scope("mamba2_conv"):
        taps = lp["m2_conv_w"].astype(f32)  # [K, C]; taps[K-1] weighs xBC_t
        K = taps.shape[0]
        held = state.conv[layer, io.slots[io.row_of]]  # [N, K-1, C], one gather
        back = _stream_history(xbc, held, io, K)
        acc = lp["m2_conv_b"].astype(f32) + taps[K - 1] * xbc
        for j in range(1, K):
            acc = acc + taps[K - 1 - j] * back[j]
        c = jax.nn.silu(acc)  # [N, C] float32
        conv = state.conv.at[layer, io.dst].set(
            _history_after(back, io.src).astype(state.conv.dtype), mode="drop"
        )
    with jax.named_scope("ssd_scan"):
        y, ssm = _ssd(
            c, dt, -jnp.exp(lp["m2_A_log"]), lp["m2_D"], state.ssm, layer, io, form,
        )
    y = y * jax.nn.silu(gate.astype(f32))
    y = rms_norm(y.reshape(n, G, E // G), jnp.ones((), f32), cfg.rms_norm_eps)
    out = (y.reshape(n, E) * lp["m2_norm"].astype(f32)).astype(u.dtype) @ lp["m2_out"]
    return out, MambaState(conv, ssm)


def _mamba_half(x, lp, cfg, eps, state, layer, io, form):
    """A layer's first half where its operator is the Mamba mixer: norm,
    ``mamba_mixer`` on the flattened rows, residual add."""
    with jax.named_scope("mamba"):
        h = rms_norm(x, lp["attn_norm"], eps, cfg.rms_norm_offset)
        y, state = mamba_mixer(
            h.reshape(-1, h.shape[-1]), lp, cfg, state, layer, io, form
        )
        return x + y.reshape(x.shape), state


def _mamba2_half(x, lp, cfg, eps, state, layer, io, form):
    """A layer's first half where its operator is the Mamba-2 mixer (a
    whole ``M`` layer of nemotron_h): norm, ``mamba2_mixer`` on the flattened
    rows, residual add."""
    with jax.named_scope("mamba2"):
        h = rms_norm(x, lp["attn_norm"], eps, cfg.rms_norm_offset)
        y, state = mamba2_mixer(
            h.reshape(-1, h.shape[-1]), lp, cfg, state, layer, io, form
        )
        return x + y.reshape(x.shape), state


def _linear_half(x, lp, cfg, eps, state, layer, io, form):
    """A layer's first half where its operator is linear attention:
    ``gated_delta_mixer`` on the flattened rows, its output normed (Olmo's
    block), residual add."""
    with jax.named_scope("gated_delta"):
        h = rms_norm(x, lp["attn_norm"], eps, cfg.rms_norm_offset) if "attn_norm" in lp else x
        y, state = gated_delta_mixer(
            h.reshape(-1, h.shape[-1]), lp, cfg, state, layer, io, form
        )
        y = y.reshape(x.shape)
        if "post_attn_norm" in lp:
            y = rms_norm(y, lp["post_attn_norm"], eps, cfg.rms_norm_offset)
        return x + y, state


def _conv_half(x, lp, cfg, eps, pool, layer, io):
    """A layer's first half where its operator is the short convolution:
    norm, ``short_conv`` on the flattened rows, residual add."""
    with jax.named_scope("short_conv"):
        h = rms_norm(x, lp["attn_norm"], eps, cfg.rms_norm_offset)
        y, pool = short_conv(h.reshape(-1, h.shape[-1]), lp, pool, layer, io)
        return x + y.reshape(x.shape), pool


def _ffn_half(x, lp, cfg, eps, red, mesh, live, layer, interpret, tally=None):
    with jax.named_scope("moe" if "router" in lp else "ffn"):
        return _ffn_block(
            x, lp, cfg, eps, red, mesh, live, layer, interpret, tally=tally
        )


def _ffn_block(x, lp, cfg, eps, red, mesh, live=None, layer=None, interpret=False,
               any_rows=False,  # unused: benchmark/comparisons/block_diffusion.py:205 passes a tenth
               tally=None):
    """The layer's second half: norm, dense SwiGLU or sparse-MoE FFN,
    residual add. ``live`` ([B, T] bool) keeps a dispatch's padding
    rows out of the routed experts' sort (``ops/moe.py::moe_ffn``);
    every other form computes a row from that row alone and ignores
    it. ``layer`` (a scalar) says that ``lp``'s ``w_gate``/``w_up``/
    ``w_down`` are the stacked ``[L, E, ..]`` arrays and which layer of
    them to read (``_scan_paged_layers``); without it they are one
    layer's, as everything else in ``lp`` always is. ``interpret`` runs
    the experts' kernel off the TPU, as it does the attention kernel.

    Where the model *holds* a share of its experts
    (``ModelConfig.num_experts_held``: the matrices are experts
    ``[first_expert_held, first_expert_held + held)`` of the
    ``num_experts`` the router scores), the routed sum is the held picks'
    alone, each weighed as normalised over all of the token's picks; a
    pick elsewhere is another rank's and adds nothing here
    (``ops/moe.py``). The shared expert (qwen2_moe's behind its sigmoid
    gate, pangu_ultra_moe's as it is) is added once, and a model with
    ``post_norms`` norms the routed half's sum as it does a dense one's.
    ``tally``: ``moe_ffn``'s (the picks held and the experts they reach)."""
    B, T = x.shape[:2]
    off = cfg.rms_norm_offset
    h = rms_norm(x, lp["mlp_norm"], eps, off) if "mlp_norm" in lp else x
    if "router" in lp and "w_gate" not in lp and _expert_parallel(mesh):
        raise NotImplementedError("experts of two matrices are not sharded over ep")
    if "router" in lp:
        from ..ops.moe import moe_ffn, moe_ffn_ep

        shared = None
        if "shared_up" in lp:  # an always-on shared expert
            with jax.named_scope("shared_expert"):
                act = _act(cfg.hidden_act)
                if "shared_gate" in lp:
                    sg = act((h @ lp["shared_gate"]).astype(jnp.float32)).astype(x.dtype)
                    shared = (sg * (h @ lp["shared_up"])) @ lp["shared_down"]
                else:  # nemotron_h: two matrices, down(act(up x))
                    su = act((h @ lp["shared_up"]).astype(jnp.float32)).astype(x.dtype)
                    shared = su @ lp["shared_down"]
                if "shared_router" in lp:  # qwen2_moe: behind a gate
                    # Learned sigmoid blend; the gate logit uses the replicated
                    # [D] vector, so it is identical on every tp rank and
                    # commutes with the psum over the I-sharded shared FFN.
                    blend = jax.nn.sigmoid(
                        (h @ lp["shared_router"]).astype(jnp.float32)
                    )[..., None]
                    shared = (blend * shared.astype(jnp.float32)).astype(x.dtype)
        if _expert_parallel(mesh):
            if cfg.router_score != "softmax":
                raise NotImplementedError(
                    "experts sharded over ep route by softmax alone "
                    f"(moe_ffn_ep); {cfg.model_type} routes by {cfg.router_score}"
                )
            # Experts sharded over the mesh's ep axis (shard_map path);
            # the psum inside covers both ep and tp, so no outer reduce
            # (the shared expert stays on the GSPMD path).
            y = moe_ffn_ep(
                h.reshape(B * T, -1),
                lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"],
                cfg.num_experts_per_tok, cfg.norm_topk_prob, mesh,
            ).reshape(B, T, -1)
            x = x + y
            if shared is not None:
                x = x + red(shared)
        else:
            y = moe_ffn(
                h.reshape(B * T, -1),
                lp["router"],
                lp.get("w_gate"),  # None: experts of two matrices
                lp["w_up"],
                lp["w_down"],
                cfg.num_experts_per_tok,
                cfg.norm_topk_prob,
                live=None if live is None else live.reshape(B * T),
                layer=layer, mesh=mesh, interpret=interpret,
                score=cfg.router_score, select_bias=lp.get("router_bias"),
                scale=cfg.routed_scaling_factor,
                norm_eps=cfg.router_norm_eps,
                held_from=cfg.first_expert_held if cfg.num_experts_held else None,
                tally=tally, act=cfg.hidden_act,
            ).reshape(B, T, -1)
            if shared is not None:
                y = y + shared
            y = red(y)
            if "post_ffn_norm" in lp:  # pangu_ultra_moe: four norms a layer
                y = rms_norm(y, lp["post_ffn_norm"], eps, off)
            x = x + y
    else:
        act = _act(cfg.hidden_act)
        gate = act((h @ lp["w_gate"]).astype(jnp.float32)).astype(x.dtype)
        ffn_out = red((gate * (h @ lp["w_up"])) @ lp["w_down"])
        if "post_ffn_norm" in lp:  # gemma2
            ffn_out = rms_norm(ffn_out, lp["post_ffn_norm"], eps, off)
        x = x + ffn_out
    return x


def _expert_parallel(mesh) -> bool:
    """Whether the routed experts are sharded over the mesh's ``ep`` axis
    (``ops/moe.py::moe_ffn_ep``, which takes one layer's weights)."""
    return mesh is not None and mesh.shape.get("ep", 1) > 1


def routed_experts_form(cfg: ModelConfig, mesh, interpret: bool = False) -> str:
    """How a paged forward has its expert matmuls read their weights on
    this mesh, whatever rows it carries, by the rule the forwards
    themselves apply (``_scan_paged_layers``): ``"inplace"`` (the stacks
    closed over, ``ops/expert_gmm.py`` reading layer ``l``) or
    ``"sliced"`` (a layer's copy handed to ``ragged_dot`` or to
    ``moe_ffn_ep``). What ``dynamo_moe_dispatch_total`` is labelled by."""
    from ..ops.moe import experts_in_place

    in_place = not _expert_parallel(mesh) and experts_in_place(
        cfg.hidden_size, cfg.expert_stack_width, mesh, interpret
    )
    return "inplace" if in_place else "sliced"


def _final_logits(params, cfg, x, eps):
    with jax.named_scope("logits"):
        x = rms_norm(x, params["final_norm"], eps, cfg.rms_norm_offset)
        head = (
            params["embed"].T if cfg.tie_word_embeddings else params["lm_head"]
        )
        logits = (x @ head).astype(jnp.float32)
        if head.dtype == jnp.bfloat16:
            # Pin the logits to bf16 precision even inside a fused compiled
            # program: XLA's excess-precision rules may otherwise elide the
            # bf16 rounding between the matmul and a fused argmax/sampler,
            # silently un-tying exactly-tied bf16 logits — and greedy
            # identity across dispatch layouts (eager oracle, windowed
            # window, mixed ragged batch) depends on every layout rounding
            # the distribution identically before the tie-break.
            logits = jax.lax.reduce_precision(logits, 8, 7)
        if cfg.final_logit_softcap is not None:  # gemma2
            cap = cfg.final_logit_softcap
            logits = jnp.tanh(logits / cap) * cap
        return logits


def final_logits(params: Params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    """The model's end on hidden states ``x`` ([.., D]): final norm and
    head, float32 logits (what ``forward_ragged(project=False)`` left out)."""
    return _final_logits(params, cfg, x, cfg.rms_norm_eps)


def _maybe_scale_embeds(cfg, x):
    if not cfg.scale_embeddings:
        return x
    # gemma scales by sqrt(hidden) rounded through the param dtype.
    return x * jnp.asarray(cfg.hidden_size**0.5, x.dtype)


def _per_layer_xs(cfg, inv_freq):
    """What rides the layer scan as ``xs`` beside the stacked parameters:
    (window width [L], rope inverse frequencies [L, hd/2], layer index [L]).

    Per-layer window widths: gemma2 alternates sliding and full layers;
    gemma3 follows its explicit layer_types; mistral uses one width
    everywhere. 1<<30 ≈ no window for full layers. gemma3's sliding
    layers rope at a separate (local) base; full layers use rope_theta +
    rope_scaling.

    The KV pools are NOT here: they travel in the carry, and a routed
    model's expert matrices are closed over (module docstring)."""
    L = cfg.num_layers
    if cfg.layer_types:
        sliding = [t == "sliding_attention" for t in cfg.layer_types]
    else:
        sliding = [not cfg.alt_sliding_window or i % 2 == 0 for i in range(L)]
    win_arr = jnp.asarray(
        [
            cfg.sliding_window if form == "sliding" else 1 << 30
            for form in cfg.attn_forms
        ],
        jnp.int32,
    )
    if cfg.rope_local_base_freq is not None:
        invf_local = rope_frequencies(cfg.rope_dim, cfg.rope_local_base_freq)
        invf_arr = jnp.stack(
            [invf_local if s else inv_freq for s in sliding]
        )
    else:
        invf_arr = jnp.tile(inv_freq[None], (L, 1))
    return win_arr, invf_arr, jnp.arange(L, dtype=jnp.int32)


# A layer's parameters by the kind that has them, each stacked over the
# layers of that kind (``init_params``).
_EVERY_LAYER = ("attn_norm", "mlp_norm", "post_attn_norm", "post_ffn_norm")
_OP_PARAMS = {
    "attn": (
        "wq", "wk", "wv", "wo", "bq", "bk", "bv", "q_norm", "k_norm",
        *LATENT_PARAMS, *INDEXER_PARAMS,
    ),
    "conv": ("conv_in", "conv_w", "conv_out"),
    "mamba": MAMBA_PARAMS,
    "linear": LINEAR_PARAMS,
    "mamba2": MAMBA2_PARAMS,
}
_ROUTED_PARAMS = (
    "router", "router_bias", "shared_gate", "shared_up", "shared_down",
    "shared_router",
)


def _loop_passes(params, cfg, carry, run_layers):
    """A looped model's passes (``ModelConfig.loop_passes``: ouro), the ONE
    place the stack is run more than once: a ``lax.fori_loop`` over the
    pass ``t`` round ``run_layers`` (the runs' ``lax.scan``s), so a program
    holds one copy of the layer body whatever the passes. Pass ``t`` writes
    and reads the pools' layer slots from ``t * num_attn_layers`` on (a
    token's K and V differ in every pass), over the same weights and at the
    same positions. The final norm closes every pass, its output the next
    pass's input: between passes here (``loop_norm``), after the last by
    ``_final_logits`` as for every model. The exit gate is not evaluated
    (``init_params``)."""
    T, La = cfg.loop_passes, cfg.num_attn_layers

    def close(x):
        return rms_norm(x, params["final_norm"], cfg.rms_norm_eps, cfg.rms_norm_offset)

    def one_pass(t, carry):
        with jax.named_scope("loop_pass"):
            x, *rest = run_layers(carry, t * La)
        with jax.named_scope("loop_norm"):
            x = jax.lax.cond(t + 1 < T, close, lambda x: x, x)
        return (x, *rest)

    return jax.lax.fori_loop(0, T, one_pass, carry)


def _scan_paged_layers(
    params, cfg, x, k_cache, v_cache, rope_pos, write_at, table, read, mesh,
    live, interpret=False, seq_state=None, state_io=None, scan_form=ScanForm(),
    count_picks=False, take_kept=False,
):
    """The layer loop of both paged forwards: one ``lax.scan`` for each
    run of layers of one kind (``ModelConfig.layer_runs``), carrying
    ``(x, k_cache, v_cache)`` and, where the model has layers that are not
    softmax attention, ``seq_state`` (a pytree by part: ``init_seq_state``). Returns (x,
    k_cache, v_cache, seq_state, held_picks, kept): ``held_picks`` None unless
    ``count_picks`` (``ModelConfig.counts_picks``: int32 [2],
    the live rows' picks that landed on held experts and the held experts
    that had one, each summed over the routed layers); ``kept`` None unless
    ``take_kept`` (a model with an indexer: what ``read`` put into the
    ``taken`` list it was handed, stacked over the layers). A model of
    one kind is one run over the stacked parameters, the per-layer window
    / rope base and the layer index, as it always was.

    A latent-attention model's layers carry one pool (``k_cache``: rows
    ``[c_kv | k_r]``; ``v_cache`` has no lanes and passes through):
    ``_mla_half`` writes a token's row and ``read`` gets the pool with no
    value pool (None).

    A model whose cache is by kind (``ModelConfig.kv_by_kind``) hands
    ``k_cache``, ``v_cache``, ``write_at`` and ``table`` each as a pair
    (full, sliding): a run's layers are of one form (``layer_runs``), write
    and read that form's pool through that form's table at their ordinal
    among the layers of that form, and the other pool rides the carry
    untouched. A run of sliding layers whose attention the kernel serves
    hands ``read`` its width as ``sliding=`` (static) and stands under the
    ``swa_attn`` scope, so that a device trace tells its calls from the
    full layers'. Where the forms differ in their KV heads
    (``kv_heads_by_form``) a sliding run's ``wk`` / ``wv`` are the
    ``swa_`` stacks' at the run's ordinal among sliding layers, a full
    run's the plain stacks' among full ones, and ``read`` is told the
    run's KV heads (``heads=``); a run whose form has a sink hands ``read``
    its layer's logits (``sink=``).

    Attention layer ``l`` (counted among attention layers) writes its new
    K/V with one scatter into the whole ``[L_attn, P, ps, W]`` pool at
    ``[l, page, offset]`` (``write_at`` = (page_ids, offsets, valid), the
    same for every layer), then reads
    without slicing the pool: ``read(q, k_flat, v_flat, table_l, win_l)``
    gets the pools seen as one page axis ``[L_attn*P, ps, W]`` (a bitcast)
    and ``table`` moved to layer ``l``'s range. Every read path (the
    kernel's page DMAs, the XLA gather) takes a pool and page ids, so
    none needs to know the layer; a ``dynamic_slice`` of the layer's
    pool would be a copy of it. ``live`` ([B, T] bool) marks the rows
    that are not padding, for the routed experts (``_ffn_block``). A
    convolution layer reads and writes ``seq_state`` the same way, at its
    ordinal among convolution layers (``short_conv``; ``state_io``), a
    Mamba layer both parts of its state at its ordinal among Mamba layers
    (``mamba_mixer``; ``scan_form`` says which form the scan takes), a
    linear-attention layer both of its own at its ordinal among those
    (``gated_delta_mixer``; ``scan_form`` likewise).

    A routed model's expert matrices (``EXPERT_STACKS``) leave ``xs`` for
    the same reason the pools left ``ys``: the body closes over the whole
    stacks and ``moe_ffn(layer=l)`` addresses routed layer ``l`` in them.
    That is where ``routed_experts_form`` says ``inplace``, for a decode
    window's rows and a prompt chunk's alike. Everywhere else a one-run
    model's ``xs`` are all of ``params["layers"]``, as ever: a dense
    model, experts sharded over ``ep`` (``moe_ffn_ep``), and a forward
    whose expert matmuls take ``ragged_dot`` on the slice (off the TPU,
    or widths the kernel cannot tile).

    A run that is not the whole of a stack closes over the stack and
    indexes it by the layer's ordinal, which is what ``scan`` does with
    ``xs``: a static slice handed over as ``xs`` would be a copy of the
    run's parameters on every forward.

    A looped model (``ModelConfig.loop_passes`` > 1) walks the runs once a
    pass (:func:`_loop_passes`): the pools have ``passes * L_attn`` layer
    slots, and pass ``t``'s attention layer ``l`` writes and reads slot
    ``t * L_attn + l`` (the scatter's ``[l, page, offset]`` and the table
    "moved to layer l's range" both take the slot) while its weights stay
    layer ``l``'s."""
    L = cfg.num_layers
    by_kind = cfg.kv_by_kind
    forms = cfg.attn_forms
    tables, writes = _by_kind(cfg, table), _by_kind(cfg, write_at)
    inv_freq = rope_frequencies(cfg.rope_dim, cfg.rope_theta, cfg.rope_scaling)
    eps = cfg.rms_norm_eps
    layers = params["layers"]
    in_place = (
        "router" in layers
        and routed_experts_form(cfg, mesh, interpret) == "inplace"
    )
    win_arr, invf_arr, _ = _per_layer_xs(cfg, inv_freq)
    if cfg.is_latent:
        invf_arr = jnp.tile(inv_freq[None], (L, 1))
    dense_names = (
        ("dense_gate", "dense_up", "dense_down") if "dense_gate" in layers
        else EXPERT_STACKS
    )
    red = lambda y: y  # noqa: E731 - GSPMD reduces; no explicit psum here
    carry = (x, k_cache, v_cache, seq_state)
    held_picks = jnp.zeros((2,), jnp.int32)
    kept_runs = []

    def run_layers(carry, slot0=None):
        # every run of layers once; ``slot0`` (a looped model's pass: traced)
        # is the pass's first layer slot in the pools
        nonlocal held_picks
        for op, ff, first, n, op0, ff0 in cfg.layer_runs:
            # name in the body -> (stack, the run's first index in it)
            source = {k: (layers[k], first) for k in _EVERY_LAYER if k in layers}
            if cfg.ff_types:  # layers of one half: a norm a feed-forward that is there
                source.pop("mlp_norm", None)
                if ff != "none":
                    source["mlp_norm"] = (layers["mlp_norm"], ff0)
            source.update(
                (k, (layers[k], op0)) for k in _OP_PARAMS[op] if k in layers
            )
            if ff == "dense":
                source.update(
                    (k, (layers[d], ff0)) for k, d in zip(EXPERT_STACKS, dense_names)
                )
            elif ff == "routed":
                source.update(
                    (k, (layers[k], ff0)) for k in _ROUTED_PARAMS if k in layers
                )
            # Expert matrices: whole, with the layer to read, where the matmuls
            # read in place and wherever the run is not the whole stack
            # (``moe_ffn`` then slices the layer it multiplies); else ``xs``.
            stacks = {}
            if ff == "routed":
                names = [k for k in EXPERT_STACKS if k in layers]  # two where no gate
                whole = layers["w_up"].shape[0] == n
                if in_place or not whole:
                    stacks = {k: layers[k] for k in names}
                else:
                    source.update((k, (layers[k], ff0)) for k in names)
            # What a model whose forms differ stacks by form: the K and V
            # projections at the run's ordinal among the layers of its form,
            # the sink logits among the layers that have them.
            if op == "attn" and cfg.kv_heads_by_form:
                stack = "swa_" if forms[first] == "sliding" else ""
                nth = forms[:first].count(forms[first])
                source.update((k, (layers[stack + k], nth)) for k in ("wk", "wv"))
            if forms[first] in cfg.sink_forms:
                source["sink"] = (
                    layers["sink"], sum(f in cfg.sink_forms for f in forms[:first]),
                )
            xs = {k: a for k, (a, at) in source.items() if a.shape[0] == n}
            closed = {k: v for k, v in source.items() if k not in xs}
            # The run's form of attention: which pool of a cache by kind, the
            # run's first layer's ordinal in it, and the width the kernel takes
            # as a static argument (None: a full layer, or a width that rides
            # ``xs`` to the gather).
            form = forms[first]
            kind = int(by_kind and form == "sliding")
            pool0 = forms[:first].count(form) if by_kind else op0
            heads = cfg.kv_heads_of(form)
            width = (
                cfg.sliding_window
                if form == "sliding" and cfg.attn_kernel_form else None
            )
            rope = not (cfg.rope_sliding_only and form == "full")

            def layer(carry, layer_in, op=op, ff=ff, op0=op0, ff0=ff0, stacks=stacks,
                      closed=closed, kind=kind, pool0=pool0, width=width,
                      rope=rope, heads=heads):
                x, k_all, v_all, state = carry
                k_pool, v_pool = (
                    (k_all[kind], v_all[kind]) if by_kind else (k_all, v_all)
                )
                num_pages = k_pool.shape[1]
                flat = (k_pool.shape[0] * num_pages, *k_pool.shape[2:])
                table, write_at = tables[kind], writes[kind]
                lp, win_l, invf_l, i = layer_in
                lp = {**lp, **stacks}
                taken = []  # ``take_kept``: the layer's selection, as ``read`` hands it out
                for name, (stack, at) in closed.items():
                    lp[name] = jax.lax.dynamic_index_in_dim(
                        stack, at + i, 0, keepdims=False
                    )
                # ordinal among layers of this operator (of this form, where the
                # cache is by kind): the layer's place in its pool
                l = i + pool0 if pool0 else i
                if slot0 is not None:  # a looped model: this pass's slots
                    l = l + slot0
                if op == "conv":
                    x, state = _conv_half(x, lp, cfg, eps, state, l, state_io)
                elif op == "mamba":
                    x, state = _mamba_half(
                        x, lp, cfg, eps, state, l, state_io, scan_form
                    )
                elif op == "linear":
                    x, state = _linear_half(
                        x, lp, cfg, eps, state, l, state_io, scan_form
                    )
                elif op == "mamba2":
                    x, state = _mamba2_half(
                        x, lp, cfg, eps, state, l, state_io, scan_form
                    )
                elif cfg.is_latent:
                    # One pool: a token's row, its values among its lanes.
                    # ``v_pool`` (no lanes) rides the carry untouched.

                    def write(row, index_row):
                        # ``index_row`` (a model with an indexer): the second
                        # pool's row, written at the same page and offset
                        with jax.named_scope("kv_write"):
                            kp, vp = write_kv_pages(
                                k_pool, None if index_row is None else v_pool,
                                row.reshape(-1, row.shape[-1]),
                                None if index_row is None
                                else index_row.reshape(-1, index_row.shape[-1]),
                                *write_at, layer=l,
                            )
                        return kp, v_pool if index_row is None else vp

                    def read_latent(q, kp, absorb, expand, w_ukv, index):
                        selector = {}
                        if index is not None:
                            q_i, w_i, ip = index
                            selector["index"] = (
                                q_i, w_i, ip.reshape(-1, *ip.shape[2:])
                            )
                            if take_kept:
                                selector["taken"] = taken
                        return read(
                            q, kp.reshape(flat), None, table + l * num_pages,
                            win_l, wrap=(absorb, expand, w_ukv), **selector,
                        )

                    x, (k_pool, v_pool) = _mla_half(
                        x, lp, cfg, invf_l, rope_pos, eps, write, read_latent, red
                    )
                else:

                    def attend(q, k, v, index=None):
                        n = k.shape[0] * k.shape[1]  # this dispatch's new tokens
                        k_row = k.reshape(n, -1)
                        if index is not None:
                            # a model with an indexer: the index key rides the
                            # K pool's row behind the heads' keys, one scatter
                            q_i, k_i, w_i = index
                            k_row = jnp.concatenate(
                                [k_row, k_i.reshape(n, -1)], axis=-1
                            )
                        with jax.named_scope("kv_write"):
                            kp, vp = write_kv_pages(
                                k_pool, v_pool, k_row, v.reshape(n, -1),
                                *write_at, layer=l,
                            )
                        # the run's own KV heads, and its layer's sink logits if any
                        by_form = {"heads": heads, "sink": lp.get("sink")}
                        if index is not None:
                            by_form["index"] = (q_i, w_i, kp.reshape(flat))
                            if take_kept:
                                by_form["taken"] = taken
                        v_flat = (flat[0], flat[1], vp.shape[-1])
                        with jax.named_scope("attend"):
                            if width is None:
                                attn = read(
                                    q, kp.reshape(flat), vp.reshape(v_flat),
                                    table + l * num_pages, win_l, **by_form,
                                )
                            else:
                                with jax.named_scope("swa_attn"):
                                    attn = read(
                                        q, kp.reshape(flat), vp.reshape(v_flat),
                                        table + l * num_pages, win_l,
                                        sliding=width, **by_form,
                                    )
                        return attn, (kp, vp)

                    x, (k_pool, v_pool) = _attn_half(
                        x, lp, cfg, invf_l, rope_pos, eps, attend, red, rope
                    )
                tally = [] if count_picks else None
                if ff != "none":  # a layer of one half has no second
                    x = _ffn_half(
                        x, lp, cfg, eps, red, mesh, live,
                        (i + ff0 if ff0 else i) if stacks else None, interpret,
                        tally,
                    )
                picks = (tally[0] if tally else jnp.zeros((2,), jnp.int32)) if count_picks else None
                if by_kind:  # the other form's pool rides on untouched
                    k_pool = (*k_all[:kind], k_pool, *k_all[kind + 1:])
                    v_pool = (*v_all[:kind], v_pool, *v_all[kind + 1:])
                return (x, k_pool, v_pool, state), (picks, taken[0] if taken else None)

            whole_model = n == L
            carry, (picks, kept) = jax.lax.scan(
                layer,
                carry,
                (
                    xs,
                    win_arr if whole_model else win_arr[first : first + n],
                    invf_arr if whole_model else invf_arr[first : first + n],
                    jnp.arange(n, dtype=jnp.int32),
                ),
            )
            if count_picks:
                held_picks = held_picks + jnp.sum(picks, axis=0)
            if kept is not None:
                kept_runs.append(kept)
        return carry

    if cfg.loop_passes == 1:
        carry = run_layers(carry)
    else:
        if count_picks or take_kept:
            raise ValueError("a looped model counts no picks and keeps no selection")
        carry = _loop_passes(params, cfg, carry, run_layers)
    return (
        *carry, held_picks if count_picks else None,
        jnp.concatenate(kept_runs) if kept_runs else None,
    )


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, T] int32 (pad with 0 where pos < 0)
    positions: jnp.ndarray,  # [B, T] int32, -1 for padding rows
    page_table: jnp.ndarray,  # [B, Pmax] int32
    k_cache: jnp.ndarray,  # [L, P, ps, Hkv*D]
    v_cache: jnp.ndarray,
    *,
    attn_pages: int | None = None,
    attn_impl: str = "xla",
    mesh=None,
    interpret: bool = False,
    last_positions: jnp.ndarray | None = None,
    token_embeds: jnp.ndarray | None = None,
    seq_state: jnp.ndarray | None = None,
    state_slots: jnp.ndarray | None = None,
    count_picks: bool = False,
    kept_of: jnp.ndarray | None = None,
    rope_positions: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, ...]:
    """One forward step (prefill or decode by bucket shape).

    Writes new K/V into the paged pools, attends, and returns
    (logits [B, T, V] float32, new_k_cache, new_v_cache).

    A model whose sequences carry state beside their pages
    (``init_seq_state``) also takes that pool as ``seq_state`` and each
    row's entry in it as ``state_slots`` ([B] int32), and returns the pool
    fourth: every row with a token reads its entry and leaves the state
    after its last token there. Rows' real tokens come first (``t`` such
    that ``positions[b, t] >= 0`` are ``0..n_b-1``).

    ``last_positions`` ([B] int32, in-chunk index of each row's last real
    token) gathers one hidden state per row before the vocab projection,
    so chunked prefill pays lm_head FLOPs for B positions instead of
    B*T — the returned logits are then [B, 1, V].

    ``count_picks`` (``ModelConfig.counts_picks``: a model that holds a
    share of its experts, or all and asks) returns, last, int32 [2]: how
    many of the live rows' expert picks landed on held experts, and how
    many held experts had one (the matrices a layer has to fetch), each
    summed over the routed layers.

    ``kept_of`` ([M] int32 slots of the flat ``[B * T]`` stream; a model
    with an indexer) returns, last, bool [L, M, S]: which positions of its
    row's table each of those queries' selection kept in each layer, as the
    attention was given it (``_selection_tiled`` / ``_selection_ref``; a
    position the query does not see may read either).

    ``rope_positions`` ([3, B, T] int32; a model whose rotary goes by
    sections, ``ModelConfig.mrope_section``): the temporal, height and width
    component of each token's position, what q and k turn by, while the
    cache, the causal mask and the selection go by ``positions``. None: a
    text token, whose three are its position (the plain rotary, exactly);
    it is the seam through which a vision tower's soft tokens
    (``token_embeds``) would bring their grid.

    ``attn_pages`` (static) bounds the XLA path's page gather: attention
    reads only the first ``attn_pages`` table columns, so short contexts
    don't pay Pmax-wide HBM traffic. K/V *writes* always use the full
    table. ``attn_impl="pallas"`` switches decode (T==1) to the ragged
    Pallas kernel (``ops/ragged_attention.py``, at its one-query-per-row
    shape), which reads each sequence's true context length —
    ``attn_pages`` is then irrelevant. With a ``mesh`` whose ``tp`` axis
    is >1, the kernel runs under ``shard_map`` over the head axis
    (attention is embarrassingly parallel in heads).
    """
    B, T = tokens.shape
    ps = kv_page_size(k_cache)
    eps = cfg.rms_norm_eps
    by_kind = cfg.kv_by_kind  # then ``page_table`` is a pair, as the pools

    # Page-write coordinates, shared by every layer (of one form, where the
    # cache is by kind). Positions beyond the
    # page table's capacity are dropped (not clamped): a scheduler bug can
    # truncate a sequence but never silently corrupt another's pages.
    flat_pos = positions.reshape(-1)  # [B*T]
    safe_pos = jnp.maximum(flat_pos, 0)
    page_in_seq = safe_pos // ps
    # both tables of a cache by kind are one width
    width = (page_table[0] if by_kind else page_table).shape[1]
    valid = (flat_pos >= 0) & (page_in_seq < width)
    batch_idx = jnp.repeat(jnp.arange(B, dtype=jnp.int32), T)

    def write_at_of(table_):
        page_ids = table_[batch_idx, page_in_seq]  # [B*T]
        return page_ids, safe_pos % ps, valid

    write_at = (
        tuple(map(write_at_of, page_table)) if by_kind
        else write_at_of(page_table)
    )

    # ``token_embeds`` ([B, T, D]) overrides the id lookup — the
    # multimodal seam: image (or other modality) features projected to
    # hidden size enter as soft tokens (reference capability:
    # examples/multimodal encode worker → LLM worker handoff).
    x = (
        token_embeds.astype(params["embed"].dtype)
        if token_embeds is not None
        else jnp.take(params["embed"], tokens, axis=0)
    )  # [B, T, D]
    x = _maybe_scale_embeds(cfg, x)
    rope_pos = _rope_positions(cfg, positions, rope_positions)

    # Pallas decode reads each row's ragged context (a sliding layer's
    # through its width: ``sliding=``); softcapped and query-scaled models
    # (gemma2, gemma3: ``ModelConfig.attn_kernel_form``) stay on the XLA
    # path where those live, as do meshes whose tp doesn't divide the kv
    # heads (e.g. gemma's Hkv=1 with tp>1 — the shard_map head split would
    # be empty on some ranks).
    use_pallas = _kernel_serves(cfg, attn_impl, mesh) and T == 1
    if use_pallas:
        lengths = jnp.maximum(positions[:, 0] + 1, 0)
    attn_table = _cut_tables(page_table, None if use_pallas else attn_pages)
    sm_scale = (
        cfg.query_pre_attn_scalar ** -0.5
        if cfg.query_pre_attn_scalar
        else None
    )
    have_window = cfg.sliding_window is not None
    latent = _latent_read_args(cfg)

    def read(q, kf, vf, table_l, win_l, wrap=None, index=None, taken=None,
             sliding=None, sink=None, heads=cfg.num_kv_heads):
        # ``sink`` (a layer with sink logits, [H]) joins the softmax of
        # either path; ``heads``: the layer's KV heads, where its form's
        # are not the model's.
        # ``wrap`` (a latent model: ``_mla_half``'s absorb and expand, and
        # ``W_ukv`` for a form this forward never takes) stands either side
        # of the read, which then names itself; ``index`` (a model with an
        # indexer) is what the read's selection is computed from, and
        # ``taken`` the list that gets what it kept for ``kept_of``;
        # ``sliding`` (a run of sliding layers): the width, static, for the
        # kernel (the gather masks by ``win_l``, which says the same)
        pre, post, scope = _read_wrap(wrap)
        keep = {} if sliding is None else {"sliding": sliding}
        if sink is not None:
            keep["sink"] = sink
        if use_pallas:
            if index is not None:
                keep["keep"] = _selection_tiled(
                    cfg, (index[0][:, 0], index[1][:, 0], index[2]), table_l,
                    jnp.arange(B, dtype=jnp.int32), lengths - 1, 1, interpret,
                )
                if taken is not None:
                    taken.append(_kept_tiled(keep["keep"], kept_of, 1))
            with scope:
                o = _pallas_decode(
                    pre(q[:, 0]), kf, vf, table_l, lengths, heads,
                    mesh, interpret, **latent, **keep,
                )
            return post(o)[:, None]
        q = pre(q)
        keep.pop("sliding", None)
        if index is not None:
            keep["keep"] = _selection_ref(cfg, index, table_l, positions)
            if taken is not None:
                taken.append(keep["keep"].reshape(B * T, -1)[kept_of])
            if wrap is None:  # the K row holds the index key behind the heads' keys
                keep["kv_heads"] = heads
        with scope:
            o = paged_attention(
                q, kf, vf, table_l, positions,
                window=win_l if have_window else None,
                softcap=cfg.attn_logit_softcap,
                **{"sm_scale": sm_scale, **latent, **keep},
            )
        return post(o)

    state_io = None
    if seq_state is not None:
        n_real = jnp.sum(positions >= 0, axis=1).astype(jnp.int32)  # [B]
        state_io = SeqStateIO(
            slots=state_slots,
            row_of=batch_idx,
            positions=flat_pos,
            src=jnp.arange(B, dtype=jnp.int32) * T + jnp.maximum(n_real - 1, 0),
            dst=jnp.where(n_real > 0, state_slots, seq_state_entries(seq_state)),
        )
    x, k_cache, v_cache, seq_state, held_picks, kept = _scan_paged_layers(
        params, cfg, x, k_cache, v_cache, rope_pos,
        write_at, attn_table, read, mesh,
        positions >= 0, interpret, seq_state, state_io,
        _scan_form(cfg, attn_impl, T, None, interpret, one_token=T == 1),
        count_picks=count_picks, take_kept=kept_of is not None,
    )
    if last_positions is not None:
        x = jnp.take_along_axis(x, last_positions[:, None, None], axis=1)
    logits = _final_logits(params, cfg, x, eps)
    return _forward_outputs(logits, k_cache, v_cache, seq_state, held_picks, kept)


def _rope_positions(cfg: ModelConfig, positions, rope_positions=None):
    """What q and k turn by: the positions, padding (-1) as 0; for a model
    whose rotary goes by sections (``mrope_section``) three components a
    token, ``[3, ...]``: the caller's, or a text token's position three
    times."""
    if not cfg.mrope_section:
        if rope_positions is not None:
            raise ValueError(
                f"model_type {cfg.model_type!r} has no rotary by sections: "
                "rope_positions is not its argument"
            )
        return jnp.maximum(positions, 0)
    if rope_positions is None:
        rope_positions = jnp.broadcast_to(positions, (3, *positions.shape))
    return jnp.maximum(rope_positions, 0)


def _kernel_serves(cfg: ModelConfig, attn_impl: str, mesh) -> bool:
    """Whether a paged forward built with ``attn_impl`` reads its contexts
    through the attention kernel: the engine resolved to it
    (``engine.resolve_attn_impl``, which asks the same
    ``ModelConfig.attn_kernel_form``) and the mesh's ``tp`` divides the KV
    heads (else a ``shard_map`` rank would hold none: gemma's one KV head
    under tp > 1)."""
    tp_size = mesh.shape.get("tp", 1) if mesh is not None else 1
    return (
        attn_impl == "pallas"
        and cfg.attn_kernel_form
        and cfg.num_kv_heads % tp_size == 0
    )


def _cut_tables(page_table, attn_pages: int | None):
    """The table(s) an attention read walks: the first ``attn_pages``
    columns of each (the gather's bound), or all of them."""
    if attn_pages is None:
        return page_table
    if isinstance(page_table, tuple):
        return tuple(t[:, :attn_pages] for t in page_table)
    return page_table[:, :attn_pages]


def _forward_outputs(logits, k_cache, v_cache, seq_state, held_picks, kept=None):
    """A paged forward's results: the logits and the pools, then the state
    pool of a model that has one, then the held picks and the kept
    positions where asked for."""
    out = (logits, k_cache, v_cache)
    if seq_state is not None:
        out += (seq_state,)
    if held_picks is not None:
        out += (held_picks,)
    if kept is not None:
        out += (kept,)
    return out


def _scan_form(cfg, attn_impl, span_align, packed_from, interpret,
               one_token=False) -> ScanForm:
    """The recurrence's form (a selective scan's or the delta rule's) in a
    forward whose rows' spans are aligned to ``span_align`` slots: the
    kernel where the attention kernel serves the forward too (the engine
    then lays the stream in tiles) and the widths tile, the oracle
    otherwise."""
    from ..ops import gated_delta, selective_scan, ssd

    if cfg.num_mamba_layers:
        fits = selective_scan.supported(cfg.mamba_inner, span_align)
    elif cfg.num_mamba2_layers:
        fits = ssd.kernel_supported(
            cfg.mamba2_heads, cfg.mamba2_head_dim, cfg.mamba2_groups, cfg.mamba_d_state,
            ssd.TILE if one_token else span_align,  # a row of one token is no span to align
        )
    else:
        fits = cfg.num_linear_layers > 0 and gated_delta.supported(
            cfg.linear_num_heads, cfg.linear_key_head_dim,
            cfg.linear_value_head_dim,
            # a row of one token is no span to align
            gated_delta.TILE if one_token else span_align,
        )
    kernel = attn_impl == "pallas" and fits
    return ScanForm(kernel, packed_from if kernel else None, one_token, interpret)


def _read_wrap(wrap):
    """(before, after, scope) of an attention read: ``wrap``'s pair with the
    ``attend`` scope the caller then leaves to the read, or identities and
    no scope (the caller has named it)."""
    import contextlib

    if wrap is None:
        return (lambda a: a), (lambda a: a), contextlib.nullcontext()
    return (*wrap[:2], jax.named_scope("attend"))


def _selection_tiled(cfg, index, table, row_of, bounds, q_tile, interpret, pad=0):
    """A stream part's learned selection as the attention kernel takes it
    (its ``keep``): the index scores tiled, and a threshold and a number of
    ties a query (``ops/sparse_index.py::select_tiled``), ``index`` being (``q_I`` [n, Hi, Di], ``w``
    [n, Hi], the index pool as one page axis). A part none of whose queries
    sees more than ``index_topk`` positions computes neither: every
    threshold is ``-inf`` and the scores are not read. ``pad``: slots of no
    query behind the part (whole tiles), which the expanded call's windows
    may run into (``ragged_paged_attention_expanded``, ``keep``)."""
    from ..ops import sparse_index as si

    q_i, w_i, pool = index
    if pad:
        q_i, w_i, row_of = (
            jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
            for a in (q_i, w_i, row_of)
        )
        bounds = jnp.pad(bounds, (0, pad), constant_values=-1)
    n, k = bounds.shape[0], cfg.index_topk

    def selecting():
        with jax.named_scope("dsa_index"):
            scores = si.index_scores(
                q_i, w_i, pool, table, row_of, bounds, q_tile=q_tile,
                interpret=interpret, **_index_lanes(cfg),
            )
        with jax.named_scope("dsa_select"):
            tau, allow = si.select_tiled(
                scores, bounds.reshape(-1, q_tile), k, interpret=interpret
            )
        return scores, tau.reshape(n), allow.reshape(n)

    def all_seen():
        shape = si.tiled_shape(n, q_tile, pool.shape[1], table.shape[1])
        return (
            jnp.zeros(shape, jnp.float32), jnp.full((n,), -jnp.inf),
            jnp.full((n,), si.NO_LIMIT),
        )

    return jax.lax.cond(jnp.any(bounds >= k), selecting, all_seen)


def _selection_ref(cfg, index, table, bounds):
    """A learned selection as the gather takes it: which of its row's
    ``table`` positions each query keeps ([B, T, S] bool), from ``index`` =
    (``q_I`` [B, T, Hi, Di], ``w`` [B, T, Hi], the index pool) and the last
    position each query sees ([B, T])."""
    from ..ops import sparse_index as si

    q_i, w_i, pool = index
    with jax.named_scope("dsa_index"):
        scores = si.index_scores_ref(
            q_i, w_i, pool, table, bounds, **_index_lanes(cfg)
        )
    with jax.named_scope("dsa_select"):
        return si.kept(scores, *si.select(scores, bounds, cfg.index_topk))


def _index_lanes(cfg: ModelConfig) -> dict:
    """Where the scores read the index key in the pool they are handed:
    nothing to say for a pool of index keys alone (latent attention), the
    key's first lane in a K row that holds it behind the heads' keys
    (``ModelConfig.index_lane0``)."""
    return {"lane0": cfg.index_lane0} if cfg.index_key_in_k_row else {}


def _kept_tiled(selection, at, q_tile):
    """Which positions of its row's table each stream slot of ``at`` ([M],
    counted within the part) keeps ([M, S] bool), read off the part's
    ``_selection_tiled`` as the attention kernel reads it."""
    from ..ops import sparse_index as si

    scores, tau, allow = selection
    mine = scores[at // q_tile, :, at % q_tile].reshape(at.shape[0], -1)
    return si.kept(mine, tau[at], allow[at])


def _latent_read_args(cfg: ModelConfig) -> dict:
    """What every read of a latent pool is told beside the pool: the
    scores' scale (the published head's ``nope + rope`` width, not the
    absorbed query's) and which lanes of a row are its values. Empty for
    every other model."""
    if not cfg.is_latent:
        return {}
    return {
        "sm_scale": (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5,
        "value_dim": latent_value_lanes(cfg),
    }


def _no_sink_over_tp(read_args: dict) -> None:
    """The ``shard_map`` branches below split the heads of q and the pools
    and nothing else: a sink's logits would need the same split, which
    nothing has run (a cache by kind refuses ``tp > 1`` at start-up)."""
    if read_args.get("sink") is not None:
        raise ValueError("a sink logit a query head is not served under tp > 1")


def _pallas_decode(q, kp, vp, page_table, lengths, hkv, mesh, interpret,
                   **latent):
    """Dispatch the ragged kernel at its decode shape (one query per
    row), sharded over tp when the mesh has a tp axis wider than 1
    (heads are embarrassingly parallel, so the per-shard kernel sees its
    local heads and the full page pool rows for them — no collectives).
    The pool's fused Hkv*D lane dim shards on head boundaries
    (consecutive D-blocks per head)."""
    from functools import partial as _partial

    from ..ops.ragged_attention import ragged_decode_attention

    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    if tp <= 1:
        return ragged_decode_attention(
            q, kp, vp, page_table, lengths, num_kv_heads=hkv,
            interpret=interpret, **latent,
        )
    _no_sink_over_tp(latent)
    from ..parallel.mesh import shard_map

    @_partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P(None, "tp", None),
            P(None, None, "tp"),
            P(None, None, "tp"),
            P(None, None),
            P(None),
        ),
        out_specs=P(None, "tp", None),
        check_vma=False,
    )
    def f(q_l, k_l, v_l, table, lens):
        return ragged_decode_attention(
            q_l, k_l, v_l, table, lens, num_kv_heads=hkv // tp,
            interpret=interpret, sliding=latent.get("sliding"),
        )

    return f(q, kp, vp, page_table, lengths)


def _pallas_ragged(
    q, kp, vp, attn_table, row_of, positions, hkv, q_tile, mesh, interpret,
    q_block=None, **latent,
):
    """Dispatch the ragged kernel over a flat mixed query stream,
    sharded over tp exactly like :func:`_pallas_decode` (the kernel is
    per-head data-parallel; each shard sees its local heads, and a
    shard's query heads a KV head are the model's, so ``q_block``, the
    slots a grid cell serves, is every shard's; None: rows on one copy
    pipeline, as many a cell as the kernel's rule gives a shard's heads.
    ``forward_ragged`` says which callers name none)."""
    from functools import partial as _partial

    from ..ops.ragged_attention import ragged_paged_attention

    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    if tp <= 1:
        return ragged_paged_attention(
            q, kp, vp, attn_table, row_of, positions, num_kv_heads=hkv,
            q_tile=q_tile, interpret=interpret, q_block=q_block, **latent,
        )
    _no_sink_over_tp(latent)
    from ..parallel.mesh import shard_map

    @_partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P(None, "tp", None),
            P(None, None, "tp"),
            P(None, None, "tp"),
            P(None, None),
            P(None),
            P(None),
        ),
        out_specs=P(None, "tp", None),
        check_vma=False,
    )
    def f(q_l, k_l, v_l, table, rows, pos):
        return ragged_paged_attention(
            q_l, k_l, v_l, table, rows, pos, num_kv_heads=hkv // tp,
            q_tile=q_tile, interpret=interpret, q_block=q_block,
            sliding=latent.get("sliding"),
        )

    return f(q, kp, vp, attn_table, row_of, positions)


def ragged_query_block(
    cfg: ModelConfig, q_tile: int, kv_heads: int | None = None
) -> int:
    """Stream slots a grid cell of a mixed stream's tiled attention call
    serves (``ops/ragged_attention.py::query_block``, from the model's query
    heads a KV head; a layer's own where ``kv_heads`` names them: a model
    whose forms differ there has a block a form). Under a learned selection
    (a model with an indexer) a grouped-query pool's call has a rule of its
    own (``selected_query_block``: 64 slots, eight tiles, where a cell
    holds them, the thresholds and the index scores riding the block), and a
    latent pool's selection rides cells of one tile in the absorbed call (its
    rows of many queries take the expanded call, whose passes are
    ``expanded_plan``'s). The program and the engine's counter
    (``dynamo_attn_query_slots_total``) both ask here."""
    from ..ops.ragged_attention import query_block, selected_query_block

    if cfg.has_indexer and cfg.is_latent:
        return q_tile
    kv_heads = kv_heads or cfg.num_kv_heads
    if cfg.has_indexer:
        return selected_query_block(q_tile, cfg.num_heads, cfg.head_dim_, kv_heads)
    return query_block(q_tile, cfg.num_heads // kv_heads)


def one_query_rows(cfg: ModelConfig, n: int, tp: int = 1, q_tile: int = 1) -> int:
    """Rows a grid cell of the attention kernel's one-query call serves over
    a stream of ``n`` rows (a window's bucket, a mixed stream's packed
    tail; at ``q_tile`` > 1 a block program's ``n`` tiles, a tile a row) on
    a chip that holds one of ``tp`` shares of the heads
    (``ops/ragged_attention.py::one_query_block`` at the model's widths: a
    latent pool's absorbed queries are its row's lanes wide and the result
    its value lanes), or one for a model with an indexer, whose selection
    rides cells of one row. The engine's counters
    (``dynamo_attn_one_query_rows_total``, ``dynamo_attn_tile_rows_total``)
    ask here what the kernel asks of its own shapes."""
    from ..ops.ragged_attention import one_query_block

    if cfg.has_indexer:
        return 1
    width, value = cfg.head_dim_, cfg.value_head_dim_
    kv_heads = cfg.num_kv_heads // tp
    if cfg.is_latent:
        width, value, kv_heads = cfg.latent_lanes, latent_value_lanes(cfg), 1
    return one_query_block(n, cfg.num_heads // tp, width, value, q_tile, kv_heads)


def one_query_schedules(
    cfg: ModelConfig, n: int, tp: int = 1, q_tile: int = 1
) -> dict[str, float]:
    """The share of the model's attention layers whose one-query call over a
    stream of ``n`` rows (of one query, or of a tile of ``q_tile``) works a
    chunk for all its KV heads ``together``, and the share that goes
    ``by_head`` (``ops/ragged_attention.py::one_query_schedule`` at each
    form's KV heads on one of ``tp`` shares, at :func:`one_query_rows` a
    cell; a latent pool's absorbed form has one KV head). The engine's
    counters (``dynamo_attn_one_query_heads_total``,
    ``dynamo_attn_tile_rows_total``) ask here what the kernel asks of its
    own shapes."""
    from ..ops.ragged_attention import one_query_schedule

    rows = one_query_rows(cfg, n, tp, q_tile)
    stacked = cfg.num_heads // tp * q_tile
    shares = {"together": 0.0, "by_head": 0.0}
    forms = [form for form in cfg.attn_forms if form]
    for form in forms:
        kv_heads = 1 if cfg.is_latent else cfg.kv_heads_of(form) // tp
        shares[one_query_schedule(rows, kv_heads, stacked)] += 1 / len(forms)
    return shares


def _two_forms(q, pool, w_ukv, table, bounds, plan, slabs, cfg, q_tile,
               sm_scale, interpret, scope, absorbed, keep=None):
    """Latent attention over a stream's tiled part in both forms: the rows
    ``plan`` gave passes (``expanded_plan``: those with at least
    ``latent_form_queries`` live queries here) through the expanded call,
    every other row through ``absorbed(part, seen)`` a slab of ``slabs`` at
    a time, which sees ``-1`` (no query, no page read) where the expanded
    call serves; a slab with no query left for it skips its call and the
    two products beside it. The split rides the stream as data: no shape of
    the program knows it. ``keep``: the part's learned selection
    (``_selection_tiled``, computed once by the caller), which the expanded
    call applies to its rows and ``absorbed`` to a slab's own."""
    from ..ops.ragged_attention import ragged_paged_attention_expanded

    takes, items = plan
    with scope:
        o_x = ragged_paged_attention_expanded(
            q, pool, w_ukv, table, bounds, items, rank=cfg.kv_lora_rank,
            nope=cfg.qk_nope_head_dim, q_tile=q_tile, sm_scale=sm_scale,
            interpret=interpret, **({} if keep is None else {"keep": keep}),
        )
    seen = jnp.where(takes, -1, bounds)
    parts = []
    for part in slabs:
        shape = (part.stop - part.start, cfg.num_heads, cfg.v_head_dim)
        parts.append(jax.lax.cond(
            jnp.any(seen[part] >= 0),
            lambda part=part: absorbed(part, seen[part]),
            lambda shape=shape: jnp.zeros(
                shape, jnp.result_type(o_x.dtype, w_ukv.dtype)
            ),
        ))
    with jax.named_scope("mla_expand"):
        o_a = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        return jnp.where(takes[:, None, None], o_x, o_a)


def forward_ragged(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [N] int32 flat query stream (0 where pos < 0)
    positions: jnp.ndarray,  # [N] int32 absolute positions, -1 = padding
    row_of: jnp.ndarray,  # [N] int32 owning batch row per token
    page_table: jnp.ndarray,  # [R, Pmax] int32
    k_cache: jnp.ndarray,  # [L, P, ps, Hkv*D]
    v_cache: jnp.ndarray,
    out_idx: jnp.ndarray,  # [M] int32 flat indices projected to logits
    *,
    attn_pages: int | None = None,
    attn_impl: str = "xla",
    q_tile: int = 8,
    packed_from: int | None = None,
    mesh=None,
    interpret: bool = False,
    seq_state: jnp.ndarray | None = None,
    state_rows: tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray] | None = None,
    project: bool = True,
    count_picks: bool = False,  # ``forward``'s
    kept_of: jnp.ndarray | None = None,  # ``forward``'s: slots of the [N] stream
    rope_positions: jnp.ndarray | None = None,  # ``forward``'s: [3, N]
) -> tuple[jnp.ndarray, ...]:
    """One ragged forward over a flat mixed batch (the single-dispatch
    prefill+decode+spec path, docs/engine_perf.md).

    A block-diffusion model (``cfg.block_length`` B > 0) sees its whole
    block: a query at position ``p`` attends to kv positions up to
    ``p | (B - 1)`` (causal across blocks, full inside one), while rotary
    embedding and the page writes stay at ``p``. The caller feeds whole
    blocks (every position of a block a query of this dispatch, or
    written by an earlier one), since the write of a span's K/V precedes
    the read in the same layer.

    The kernel reads the stream in tiles of ``q_tile`` slots, one row a
    tile. With ``packed_from`` (static, a multiple of ``q_tile``) only
    the slots before it are such tiles; every slot from it on is a grid
    cell of its own (the kernel's one-query form, a decode window's), so
    rows of one token each lie there side by side. None: the whole
    stream is tiles. The reference reads any layout and ignores both.

    With ``seq_state`` (``init_seq_state``; returned fourth) the rows'
    sequences carry state beside their pages: ``state_rows`` = (the pool
    entry each row reads [R], and the writes ``src``/``dst`` [W] of
    :class:`SeqStateIO`).

    Every non-attention op is per-token, so the whole transformer runs
    on the flattened ``[N]`` stream — chunked-prefill rows, decode rows,
    and spec-verify rows each contribute their true query tokens, and
    compute tracks ``N`` (the bucketed total), never ``rows x chunk``.
    Attention is the ragged paged kernel (``ops/ragged_attention.py``)
    or its pure-JAX reference; K/V for every valid token is written to
    its row's pages first (write-then-gather), exactly like
    :func:`forward`.

    ``out_idx`` picks the flat positions that reach the vocab
    projection (each row's sampling position(s)): the lm_head runs on
    ``M`` tokens, not ``N``, so a 512-token chunk still pays one row of
    logits. Returns (logits [M, V] float32, new_k, new_v), then what
    ``forward`` adds for ``seq_state``, ``count_picks`` and ``kept_of``
    (slots of this stream; the kept positions [L, len(kept_of), S] bool).

    Sliding / softcapped / query-scaled models (mistral, gemma2) follow
    the same per-layer machinery as :func:`forward`: the kernel takes a
    sliding width (``sliding=``), and a cap or a pre-scale keeps a model
    on the reference path (``ModelConfig.attn_kernel_form``; the engine's
    attn resolution enforces it, mirroring ``forward``'s ``use_pallas``
    guard). A cache by kind (``ModelConfig.kv_by_kind``): ``page_table``,
    ``k_cache`` and ``v_cache`` are pairs (full, sliding), as ``forward``'s.
    """
    ps = kv_page_size(k_cache)
    eps = cfg.rms_norm_eps
    by_kind = cfg.kv_by_kind

    # Page-write coordinates: each flat token writes its row's page at
    # its own position; padding (-1) and table-overflow positions are
    # dropped, never clamped into another row's pages.
    safe_pos = jnp.maximum(positions, 0)
    page_in_seq = safe_pos // ps
    # both tables of a cache by kind are one shape
    n_rows, width = (page_table[0] if by_kind else page_table).shape
    valid = (positions >= 0) & (page_in_seq < width)

    def write_at_of(table_):
        page_ids = table_[row_of, page_in_seq]  # [N]
        return page_ids, safe_pos % ps, valid

    write_at = (
        tuple(map(write_at_of, page_table)) if by_kind
        else write_at_of(page_table)
    )

    x = jnp.take(params["embed"], tokens, axis=0)[None]  # [1, N, D]
    x = _maybe_scale_embeds(cfg, x)
    # [1, N]; [3, 1, N] where the rotary goes by sections
    rope_pos = _rope_positions(cfg, positions, rope_positions)[..., None, :]

    attn_table = _cut_tables(page_table, attn_pages)
    # forward()'s gate: a softcap or a query pre-scale lives on the
    # reference path, and a tp that doesn't divide the kv heads (gemma's
    # Hkv=1 with tp>1) would leave some shard_map ranks with zero heads.
    use_pallas = _kernel_serves(cfg, attn_impl, mesh)
    sm_scale = (
        cfg.query_pre_attn_scalar ** -0.5
        if cfg.query_pre_attn_scalar
        else None
    )
    have_window = cfg.sliding_window is not None
    latent = _latent_read_args(cfg)
    # What a query may see: its own position, or its block's last.
    bounds = positions
    if cfg.block_length:
        bounds = jnp.where(
            positions >= 0, positions | (cfg.block_length - 1), -1
        )

    # A latent model's rows of many queries take the expanded form (None:
    # none does; ``latent_form_queries``).
    expand_from = latent_form_queries(
        cfg, q_tile, ps, k_cache.dtype, interpret
    ) if use_pallas and cfg.is_latent else None
    # Which of the tiled part's slots, and in which passes: the stream's,
    # so planned once for every layer (None: the part is too short for any
    # row to have that many queries).
    tiled = positions.shape[0] if packed_from is None else packed_from
    expand_plan = None
    if expand_from is not None and tiled >= expand_from:
        from ..ops.ragged_attention import expanded_plan

        with jax.named_scope("mla_expand"):
            expand_plan = expanded_plan(
                row_of[:tiled], bounds[:tiled], n_rows, q_tile,
                expand_from,
            )

    def read(q, kf, vf, table_l, win_l, wrap=None, index=None, taken=None,
             sliding=None, sink=None, heads=cfg.num_kv_heads):
        # ``forward``'s, as ``index``, ``taken``, ``sliding``, ``sink`` and
        # ``heads``
        pre, post, scope = _read_wrap(wrap)
        swa = {} if sliding is None else {"sliding": sliding}
        sinks = {} if sink is None else {"sink": sink}
        if use_pallas:
            from ..ops.ragged_attention import selected_pad

            def select(part, tile, pad=0):
                # ``part``'s learned selection (``_selection_tiled``), and
                # what it kept for the slots of ``kept_of`` that lie in it
                selection = _selection_tiled(
                    cfg, (index[0][0, part], index[1][0, part], index[2]),
                    table_l, row_of[part], bounds[part], tile, interpret, pad,
                )
                if taken is not None:
                    start, stop, _ = part.indices(positions.shape[0])
                    mine = _kept_tiled(
                        selection,
                        jnp.clip(kept_of - start, 0, stop - start - 1), tile,
                    )
                    here = (kept_of >= start) & (kept_of < stop)
                    taken.append(jnp.where(
                        here[:, None], mine, taken.pop() if taken else False
                    ))
                return selection

            def kernel(part, tile, block=None, seen=None, selection=None):
                keep = {}
                if index is not None:
                    # a block of several tiles copies its tiles' scores: the
                    # selection runs to whole blocks (``selected_pad``)
                    start, stop, _ = part.indices(positions.shape[0])
                    pad = selected_pad(stop - start, block) if block and block > tile else 0
                    keep["keep"] = (
                        select(part, tile, pad) if selection is None else selection
                    )
                with scope:
                    o = _pallas_ragged(
                        pre(q[0, part]), kf, vf, table_l, row_of[part],
                        bounds[part] if seen is None else seen, heads,
                        tile, mesh, interpret, block, **latent, **keep, **swa,
                        **sinks,
                    )
                return post(o)

            def tiles(stop, block):
                # The tiled part [0, stop) in cells of ``block`` slots: one
                # call, or for a latent model and for one with an indexer
                # (whose index scores are [slots, context] float32) a call a
                # slab (LATENT_SLAB; a slab is whole cells, tiles or under a
                # selection over grouped-query pages blocks of several, and
                # no cell spans two: the same reads in the same order)
                if wrap is None and index is None:
                    return [kernel(slice(0, stop), q_tile, block)]
                cell = block or q_tile
                slab = LATENT_SLAB // cell * cell
                slabs = [
                    slice(a, min(a + slab, stop)) for a in range(0, stop, slab)
                ]
                if expand_plan is None:
                    return [kernel(part, q_tile, block) for part in slabs]
                # Both forms read ONE selection of the part, computed here
                # (outside the slabs' ``cond``s, so ``kept_of`` has every
                # row's); a slab's absorbed call takes its tiles of it.
                keep = None
                if index is not None:
                    from ..ops.ragged_attention import EXPANDED_SELECTION_PAD

                    keep = select(slice(0, stop), q_tile, EXPANDED_SELECTION_PAD)

                def absorbed(part, seen):
                    mine = None if keep is None else (
                        keep[0][part.start // q_tile : part.stop // q_tile],
                        keep[1][part], keep[2][part],
                    )
                    return kernel(part, q_tile, block, seen, mine)

                return [_two_forms(
                    q[0, :stop], kf, wrap[2], table_l, bounds[:stop],
                    expand_plan, slabs, cfg, q_tile, latent["sm_scale"],
                    interpret, scope, absorbed, keep,
                )]

            n = q.shape[1]
            if packed_from is None:
                # The ONE place a tiled call names no block: a block model's
                # pass (a tile of ``block_length`` a row, rows side by side),
                # whose tiles the kernel walks as the one-query call walks
                # rows of one query (``ops/ragged_attention.py``, form 2; so
                # does a stream at a tile of 1). Any other stream of whole
                # spans (a comparison's prompts a row) names a tile a cell.
                rows = q_tile == 1 or q_tile == cfg.block_length
                parts = tiles(n, None if rows else q_tile)
                return (parts[0] if len(parts) == 1 else jnp.concatenate(parts))[None]
            # A mixed stream's spans run over many tiles of one row: a
            # grid cell takes a block of them (ops/ragged_attention.py).
            return jnp.concatenate([
                *tiles(packed_from, ragged_query_block(cfg, q_tile, heads)),
                kernel(slice(packed_from, None), 1),
            ])[None]
        from ..ops.ragged_attention import ragged_paged_attention_ref

        q = pre(q[0])
        keep = {}
        if index is not None:
            keep["keep"] = _selection_ref(
                cfg, (index[0][0][:, None], index[1][0][:, None], index[2]),
                table_l[row_of], bounds[:, None],
            )[:, 0]
            if taken is not None:
                taken.append(keep["keep"][kept_of])
        with scope:
            o = ragged_paged_attention_ref(
                q, kf, vf, table_l, row_of, positions,
                num_kv_heads=heads,
                window=win_l if have_window else None,
                softcap=cfg.attn_logit_softcap, bounds=bounds,
                **{"sm_scale": sm_scale, **latent, **keep, **sinks},
            )
        return post(o)[None]

    state_io = None
    if seq_state is not None:
        slots, src, dst = state_rows
        state_io = SeqStateIO(slots, row_of, positions, src, dst)
    x, k_cache, v_cache, seq_state, held_picks, kept = _scan_paged_layers(
        params, cfg, x, k_cache, v_cache, rope_pos,
        write_at, attn_table, read, mesh,
        (positions >= 0)[None], interpret, seq_state, state_io,
        _scan_form(cfg, attn_impl, q_tile, packed_from, interpret),
        count_picks, kept_of is not None,
    )
    xo = x[0][out_idx]  # [M, D] — only sampled positions reach lm_head
    logits = _final_logits(params, cfg, xo, eps) if project else xo
    return _forward_outputs(logits, k_cache, v_cache, seq_state, held_picks, kept)


def forward_ring_prefill(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, T] — T must divide by the mesh's sp size
    positions: jnp.ndarray,  # [B, T] int32, -1 for padding
    mesh,
    sp_axis: str = "sp",
    tp_axis: str = "tp",
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sequence-parallel long-context prefill via ring attention,
    composable with tensor parallelism.

    A capability beyond the reference (SURVEY.md §5: it has no context
    parallelism of its own): the sequence axis is sharded over ``sp``,
    every non-attention op is local, and attention rotates K/V blocks
    around the ring (``ops/ring_attention.py``). Peak per-device
    *activation* memory scales 1/sp, so prefills longer than one chip's
    HBM limit become possible.

    With a mesh whose ``tp`` axis is >1, projections are megatron-
    sharded over heads/ffn on top of the sequence ring: each (sp, tp)
    rank computes its local heads' attention over its sequence shard,
    row-sharded matmuls psum over ``tp``, and the embedding is
    vocab-sharded with a masked-lookup + psum. Requires
    ``num_kv_heads % tp == 0``.

    Returns (logits [B,T,V], k, v [L,B,T,Hkv,D]). Logits shard over T
    and are full-vocab on every tp rank (the vocab-sharded locals are
    all-gathered); K/V shard over T and, when tp>1, over kv heads — the
    caller scatters K/V into its page pool or hands them to the
    disaggregation transfer plane.
    """
    from functools import partial as _partial

    from ..parallel.mesh import shard_map

    from ..ops.ring_attention import ring_attention

    sp = mesh.shape[sp_axis]
    tp = mesh.shape.get(tp_axis, 1)
    B, T = tokens.shape
    if cfg.sliding_window is not None:
        raise ValueError(
            "ring prefill does not implement sliding-window attention; "
            "use the paged prefill path for mistral-family models"
        )
    if cfg.is_latent:
        raise ValueError(
            f"ring prefill rotates per-head K and V; {cfg.model_type} caches "
            "one latent a token, which lives in the paged layer loop"
        )
    if cfg.has_seq_state:
        raise ValueError(
            f"ring prefill walks attention layers alone; {cfg.model_type} has "
            "layers that carry a state from token to token (a convolution's "
            "or a scan's), which live in the paged layer loop"
        )
    if T % sp:
        raise ValueError(f"seq len {T} not divisible by sp={sp}")
    if cfg.num_kv_heads % tp:
        raise ValueError(f"kv heads {cfg.num_kv_heads} not divisible by tp={tp}")
    hd = cfg.head_dim_
    eps = cfg.rms_norm_eps
    inv_freq = rope_frequencies(hd, cfg.rope_theta, cfg.rope_scaling)
    seq = P(None, sp_axis)

    if tp == 1:
        param_specs = jax.tree.map(lambda _: P(), params)
        kv_spec = P(None, None, sp_axis)
        reduce = None
    else:
        param_specs = param_shardings(cfg, tp_axis)
        kv_spec = P(None, None, sp_axis, tp_axis)

        def reduce(y):
            return jax.lax.psum(y, tp_axis)

    def embed_lookup(table, tokens_l):
        if tp == 1:
            return jnp.take(table, tokens_l, axis=0)
        # Vocab-sharded table: each rank resolves its slice, psum fills
        # the rest (standard megatron embedding).
        local_v = table.shape[0]
        start = jax.lax.axis_index(tp_axis) * local_v
        ids = tokens_l - start
        ok = (ids >= 0) & (ids < local_v)
        x = jnp.take(table, jnp.clip(ids, 0, local_v - 1), axis=0)
        x = jnp.where(ok[..., None], x, 0)
        return jax.lax.psum(x, tp_axis)

    def final_logits(params_l, x):
        # The tp==1 path IS _final_logits; with tp>1 both head choices
        # produce vocab-sharded locals, all-gathered to full V.
        local = _final_logits(params_l, cfg, x, eps)
        if tp == 1:
            return local
        return jax.lax.all_gather(local, tp_axis, axis=local.ndim - 1, tiled=True)

    @_partial(
        shard_map,
        mesh=mesh,
        in_specs=(param_specs, seq, seq),
        out_specs=(seq, kv_spec, kv_spec),
        check_vma=False,
    )
    def fwd(params_l, tokens_l, pos_l):
        x = _maybe_scale_embeds(cfg, embed_lookup(params_l["embed"], tokens_l))
        rope_pos = jnp.maximum(pos_l, 0)

        def layer(x, lp):
            def attend(q, k, v):
                attn = ring_attention(q, k, v, pos_l, pos_l, sp_axis, sp)
                return attn, (k, v)

            return _attn_mlp_layer(
                x, lp, cfg, inv_freq, rope_pos, eps, attend, reduce=reduce
            )

        x, (ks, vs) = jax.lax.scan(layer, x, params_l["layers"])
        return final_logits(params_l, x), ks, vs

    return fwd(params, tokens, positions)
