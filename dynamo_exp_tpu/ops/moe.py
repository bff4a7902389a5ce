"""Mixture-of-experts FFN: exact (dropless) top-k routing on TPU.

The reference delegates MoE models (Mixtral, DeepSeek) to the engines it
wraps (SURVEY.md §2.10 "Expert parallel / MoE: delegated to engines",
vLLM patch DeepSeek MLA hooks). Here the TPU engine owns the model, so
MoE is a first-class op.

TPU-first design:

- **Sorted dispatch + ``jax.lax.ragged_dot``**: tokens are replicated
  k ways, sorted by expert id, and each expert's contiguous group runs
  through a grouped matmul (MegaBlocks-style, but using XLA's native
  ragged_dot so Mosaic picks the tiling). Exact — no capacity factor,
  no dropped tokens, unlike the classic dispatch-einsum formulation.
- **float32 router**: routing logits/softmax in float32; a bf16 router
  flips top-k selections near ties and decodes diverge run-to-run.
- **Sharding**: expert weights carry ``P(None, None, tp)`` specs —
  every expert's FFN is tensor-parallel over the same ``tp`` axis as
  the dense path, so MoE composes with the existing GSPMD layout and
  XLA inserts the psum after ``w_down``. (Expert parallelism — experts
  sharded over their own mesh axis — is a layout change on the same
  weights; for inference the tp-within-expert layout keeps every chip
  busy regardless of routing skew.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def moe_router(
    x: jnp.ndarray,  # [N, D]
    router_w: jnp.ndarray,  # [D, E]
    num_experts_per_tok: int,
    norm_topk_prob: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k routing. Returns (weights [N, K] float32, expert ids [N, K]).

    Softmax over ALL experts first, then top-k (Mixtral order); with
    ``norm_topk_prob`` the selected weights are renormalised to sum to 1.
    """
    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, num_experts_per_tok)
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, ids.astype(jnp.int32)


def moe_ffn(
    x: jnp.ndarray,  # [N, D] tokens (flattened batch*seq)
    router_w: jnp.ndarray,  # [D, E]
    w_gate: jnp.ndarray,  # [E, D, I]
    w_up: jnp.ndarray,  # [E, D, I]
    w_down: jnp.ndarray,  # [E, I, D]
    num_experts_per_tok: int,
    norm_topk_prob: bool = True,
    live: jnp.ndarray | None = None,  # [N] bool, False = padding row
) -> jnp.ndarray:
    """SwiGLU expert FFN with exact top-k dispatch. Returns [N, D].

    Every (token, selected expert) pair is computed — the sort groups
    pairs by expert so each expert sees one contiguous slab, and
    ``ragged_dot`` runs the per-group matmuls without materialising a
    one-hot dispatch tensor or imposing a capacity.

    This is the one place where the rows of a dispatch meet: the sort
    and the group sizes run over every row. Rows that ``live`` marks
    False (a dispatch's padding) therefore leave the path: they sort
    past the last group, count in no ``group_sizes`` entry, add to no
    row and return zeros — so where a live row lands among the sorted
    rows, and hence every bit it computes, depends on the live rows
    alone.
    """
    N, D = x.shape
    E = router_w.shape[-1]
    K = num_experts_per_tok
    weights, ids = moe_router(x, router_w, K, norm_topk_prob)
    if live is not None:
        ids = jnp.where(live[:, None], ids, E)  # past every group
        weights = jnp.where(live[:, None], weights, 0.0)

    flat_ids = ids.reshape(-1)  # [N*K]
    # Stable sort so each token's k replicas keep a deterministic order.
    order = jnp.argsort(flat_ids, stable=True)  # [N*K]
    token_of = order // K  # originating token per sorted row
    xs = jnp.take(x, token_of, axis=0)  # [N*K, D] in expert order
    group_sizes = jnp.bincount(flat_ids, length=E)

    g = jax.lax.ragged_dot(xs, w_gate, group_sizes)
    u = jax.lax.ragged_dot(xs, w_up, group_sizes)
    h = (jax.nn.silu(g.astype(jnp.float32)).astype(u.dtype) * u)
    out = jax.lax.ragged_dot(h, w_down, group_sizes)  # [N*K, D]

    w_sorted = jnp.take(weights.reshape(-1), order)  # [N*K] float32
    out = out.astype(jnp.float32) * w_sorted[:, None]
    if live is not None:
        # Rows past the last group are not the ragged_dot's to define.
        out = jnp.where((jnp.take(flat_ids, order) < E)[:, None], out, 0.0)
    # Unsort + combine: scatter-add each replica back onto its token.
    y = jnp.zeros((N, D), jnp.float32).at[token_of].add(out)
    return y.astype(x.dtype)


def moe_ffn_ep(
    x: jnp.ndarray,  # [N, D] (replicated across the moe axes)
    router_w: jnp.ndarray,  # [D, E] replicated
    w_gate: jnp.ndarray,  # [E, D, I] sharded P(ep, None, tp)
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,  # [E, I, D] sharded P(ep, tp, None)
    num_experts_per_tok: int,
    norm_topk_prob: bool,
    mesh,
    ep_axis: str = "ep",
    tp_axis: str = "tp",
) -> jnp.ndarray:
    """Expert-parallel MoE FFN: experts sharded over the mesh's ``ep``
    axis (composing with ``tp`` inside each expert), tokens replicated.

    Every rank densely computes its E/ep local experts for all tokens
    masked by the combine weights, then a psum over (ep, tp) sums the
    expert contributions and the ffn partials. Inference-shaped N makes
    the E_local× overcompute cheap relative to moving tokens between
    ranks (the training-style all-to-all dispatch), and no routing skew
    can idle a rank. SURVEY.md §2.10: "Expert parallel / MoE → mesh
    ``expert`` axis in JAX engine".
    """
    from functools import partial as _partial

    from ..parallel.mesh import shard_map
    from jax.sharding import PartitionSpec as P

    N, D = x.shape
    E = router_w.shape[-1]
    weights, ids = moe_router(x, router_w, num_experts_per_tok, norm_topk_prob)
    combine = jnp.zeros((N, E), jnp.float32)
    combine = combine.at[jnp.arange(N)[:, None], ids].add(weights)

    @_partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P(),
            P(None, ep_axis),
            P(ep_axis, None, tp_axis),
            P(ep_axis, None, tp_axis),
            P(ep_axis, tp_axis, None),
        ),
        out_specs=P(),
        check_vma=False,
    )
    def f(x_l, comb_l, wg, wu, wd):
        g = jnp.einsum("nd,edi->eni", x_l, wg)
        u = jnp.einsum("nd,edi->eni", x_l, wu)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(u.dtype) * u
        o = jnp.einsum("eni,eid->end", h, wd).astype(jnp.float32)
        y = jnp.einsum("ne,end->nd", comb_l, o)
        # Sum expert contributions (ep) and ffn partials (tp) together.
        return jax.lax.psum(y, (ep_axis, tp_axis))

    return f(x, combine, w_gate, w_up, w_down).astype(x.dtype)


def moe_ffn_reference(
    x, router_w, w_gate, w_up, w_down, num_experts_per_tok,
    norm_topk_prob=True,
):
    """Dense oracle: every expert computes every token, combine masks the
    unselected ones. O(E·N) FLOPs — tests only."""
    N, D = x.shape
    E = router_w.shape[-1]
    weights, ids = moe_router(x, router_w, num_experts_per_tok, norm_topk_prob)
    combine = jnp.zeros((N, E), jnp.float32)
    combine = combine.at[jnp.arange(N)[:, None], ids].add(weights)
    g = jnp.einsum("nd,edi->eni", x, w_gate)
    u = jnp.einsum("nd,edi->eni", x, w_up)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(u.dtype) * u
    out = jnp.einsum("eni,eid->end", h, w_down)  # [E, N, D]
    y = jnp.einsum("ne,end->nd", combine, out.astype(jnp.float32))
    return y.astype(x.dtype)
