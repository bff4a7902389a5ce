"""Mixture-of-experts FFN: exact (dropless) top-k routing on TPU.

The reference delegates MoE models (Mixtral, DeepSeek) to the engines it
wraps (SURVEY.md §2.10 "Expert parallel / MoE: delegated to engines",
vLLM patch DeepSeek MLA hooks). Here the TPU engine owns the model, so
MoE is a first-class op.

TPU-first design:

- **Sorted dispatch + a grouped matmul**: tokens are replicated k
  ways, sorted by expert id, and each expert's contiguous group runs
  through a grouped matmul (MegaBlocks-style). Exact — no capacity
  factor, no dropped tokens, unlike the classic dispatch-einsum
  formulation.
- **The grouped matmul reads a layer's weights where they lie**: on a
  TPU it is ``ops/expert_gmm.py``'s Pallas kernel, which takes the
  stacked ``[L, E, K, N]`` weights and a layer index (``moe_ffn``'s
  ``layer``), so the layer scan hands over no slice. XLA's own
  ``jax.lax.ragged_dot`` lowers to a custom call that takes no slice in
  place, and the slice it was given was a copy of 235 MB a matrix
  (PERF.md, PR 28). The kernel serves a call of any row count, a decode
  window's few rows and a prompt chunk's thousands alike (PERF.md, PR 60).
  Off the TPU and for shapes the kernel cannot tile the same ``moe_ffn``
  takes ``ragged_dot`` on the slice (``experts_in_place`` says which; the
  engine counts it as ``dynamo_moe_dispatch_total{experts}``). One path,
  two ways in: a per-layer ``[E, K, N]`` array with no ``layer`` goes
  through the same code.
- **float32 router**: routing logits/softmax in float32; a bf16 router
  flips top-k selections near ties and decodes diverge run-to-run.
- **Sharding**: expert weights carry ``P(None, None, tp)`` specs —
  every expert's FFN is tensor-parallel over the same ``tp`` axis as
  the dense path. Under GSPMD (``ragged_dot``) XLA inserts the psum
  after ``w_down``. A ``pallas_call`` has no partitioning rule (GSPMD
  would all-gather the weights), so with the kernel the whole expert
  path runs under ``shard_map`` over ``tp``: rows replicated, each chip
  its columns of ``w_gate``/``w_up`` and its rows of ``w_down``, and
  the chips' float32 partial sums added after the combine, ``[N, D]``
  (before PR 28 GSPMD all-reduced ``[N*K, D]`` bfloat16 partials).
  (Expert parallelism — experts sharded over their own mesh axis — is a
  layout change on the same weights; for inference the tp-within-expert
  layout keeps every chip busy regardless of routing skew.)
- **A share of the experts** (``held``): a device that is one rank of an
  expert-parallel layer *holds* ``E_held`` of the ``E`` experts its router
  scores, ids ``[lo, lo + E_held)``; its weights are ``[.., E_held, ..]``
  while the router stays ``[D, E]``. ``moe_ffn`` routes over all ``E``
  (so a pick's weight is normalised over all of a token's picks), keeps
  the picks that land on held experts and computes those rows; a pick
  *elsewhere* belongs to another rank and adds nothing here: it sorts past
  the last group, as a padding row does, and is never gathered. The
  sorted rows are walked in blocks of ``held_block_rows`` (twice the
  share an even router gives, so one block in the usual case and as many
  as the picks ask for: exact at any skew, without a buffer of every
  pick). Summed over the ranks, with the shared expert added once, that is
  the whole layer (tests/test_pangu.py, the shares' test); on one device
  the exchange that would do the sum is absent, and nothing stands in for
  it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import expert_gmm

# What an expert applies to its gate's product (to its up product where it
# has no gate): silu everywhere but nemotron_h's ``relu(x)^2``.
def relu2(x):
    return jnp.square(jax.nn.relu(x))


_ACTS = {"silu": jax.nn.silu, "relu2": relu2}


def moe_router(
    x: jnp.ndarray,  # [N, D]
    router_w: jnp.ndarray,  # [D, E]
    num_experts_per_tok: int,
    norm_topk_prob: bool = True,
    *,
    score: str = "softmax",
    select_bias: jnp.ndarray | None = None,  # [E] float32
    scale: float = 1.0,
    norm_eps: float = 1e-6,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k routing. Returns (weights [N, K] float32, expert ids [N, K]).

    The rule is the model's (``ModelConfig.router_score``), in float32:

    - ``"softmax"``: softmax over ALL experts first, then top-k (Mixtral
      order); with ``norm_topk_prob`` the selected weights are
      renormalised to sum to 1.
    - ``"sigmoid"`` (lfm2_moe): ``s = sigmoid(logits)``; the experts are
      the top-k of ``s + select_bias`` and their weights are ``s`` there,
      so the bias chooses and the unbiased score weighs; with
      ``norm_topk_prob`` the weights are divided by their sum plus
      ``norm_eps`` (the published forms: lfm2_moe 1e-6, pangu_ultra_moe
      1e-20, and no ``select_bias``), then multiplied by ``scale``.
    """
    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    if score == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        weights, ids = jax.lax.top_k(probs, num_experts_per_tok)
        if norm_topk_prob:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return weights, ids.astype(jnp.int32)
    if score != "sigmoid":
        raise ValueError(f"unknown router score {score!r}")
    s = jax.nn.sigmoid(logits)
    chosen_by = s if select_bias is None else s + select_bias.astype(jnp.float32)
    _, ids = jax.lax.top_k(chosen_by, num_experts_per_tok)
    weights = jnp.take_along_axis(s, ids, axis=-1)
    if norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + norm_eps)
    return weights * scale, ids.astype(jnp.int32)


def experts_in_place(
    hidden: int, expert_width: int, mesh=None, interpret: bool = False,
) -> bool:
    """Whether ``moe_ffn`` multiplies through ``ops/expert_gmm.py`` (which
    reads a layer of the stacked weights in place) or through
    ``ragged_dot`` on a slice: the kernel where the mesh sits on a TPU
    (or ``interpret`` runs it on the CPU, as for the attention kernel:
    ``engine.resolve_attn_impl``) and a chip's share of the widths can be
    tiled. How many rows a call carries is no part of the rule: the kernel
    is ahead of the slice and ``ragged_dot`` from a decode window's rows to
    a prompt chunk's (``tools/expert_gmm_rows.py``; PERF.md, PR 60).
    ``expert_width`` is the experts' whole intermediate size; ``tp``
    divides it."""
    platform = (
        mesh.devices.flat[0].platform if mesh is not None
        else jax.default_backend()
    )
    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    return (
        (platform == "tpu" or interpret)
        and expert_width % tp == 0
        and expert_gmm.supported(hidden, expert_width // tp)
        and expert_gmm.supported(expert_width // tp, hidden)
    )


def moe_ffn(
    x: jnp.ndarray,  # [N, D] tokens (flattened batch*seq)
    router_w: jnp.ndarray,  # [D, E]
    w_gate: jnp.ndarray | None,  # [E, D, I], or with ``layer`` the stack [L, E, D, I]
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,  # [E, I, D] / [L, E, I, D]
    num_experts_per_tok: int,
    norm_topk_prob: bool = True,
    live: jnp.ndarray | None = None,  # [N] bool, False = padding row
    *,
    layer: jnp.ndarray | None = None,  # scalar: which layer of stacked weights
    mesh=None,
    interpret: bool = False,
    score: str = "softmax",
    select_bias: jnp.ndarray | None = None,  # [E]: moe_router's, one layer's
    scale: float = 1.0,
    norm_eps: float = 1e-6,  # moe_router's
    held_from: int | None = None,  # first expert id held; the weights' E = held
    held_row_block: int | None = None,  # rows a block of the held walk takes
    tally: list | None = None,  # gets [live picks on experts here, experts with one]
    act: str = "silu",  # "relu2": relu(x)^2
) -> jnp.ndarray:
    """SwiGLU expert FFN with exact top-k dispatch. Returns [N, D].

    ``w_gate`` None: experts of TWO matrices, ``down(act(up x))`` (nemotron_h,
    ``act`` "relu2"), two grouped products a pick where a gated expert is
    three; everything else is the one path.

    Every (token, selected expert) pair is computed — the sort groups
    pairs by expert so each expert sees one contiguous slab, and a
    grouped matmul runs the per-group products without materialising a
    one-hot dispatch tensor or imposing a capacity.

    This is the one place where the rows of a dispatch meet: the sort
    and the group sizes run over every row. Rows that ``live`` marks
    False (a dispatch's padding) therefore leave the path: they sort
    past the last group, count in no ``group_sizes`` entry, add to no
    row and return zeros — so where a live row lands among the sorted
    rows, and hence every bit it computes, depends on the live rows
    alone.

    With ``layer`` the three weights are the whole stacked arrays and
    the matmuls read layer ``layer`` of them (module docstring); without
    it they are one layer's. ``mesh`` says where the weights lie: on a
    TPU mesh with ``tp`` > 1 the path runs under ``shard_map``.

    ``held_from`` (module docstring, "a share of the experts"): the
    weights hold experts ``[held_from, held_from + E_held)`` of the
    ``E`` the router scores; the picks elsewhere add nothing.

    ``tally`` (a list, or None; not under ``shard_map``) is given, as one
    traced int32 pair, how many of the live rows' picks landed on the
    experts here (the caller's counter of ``held`` against ``elsewhere``;
    all of them where every expert is here) and how many of these experts
    had such a pick: the matrices this call has to fetch, which a router
    that crowds its picks makes far fewer than the held.
    """
    D, width = w_up.shape[-2:]
    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    kernel = experts_in_place(D, width, mesh, interpret)
    experts = functools.partial(
        _routed_experts,
        num_experts_per_tok=num_experts_per_tok, norm_topk_prob=norm_topk_prob,
        kernel=kernel, interpret=interpret, score=score, scale=scale,
        norm_eps=norm_eps, held_from=held_from, held_row_block=held_row_block,
        tally=tally, act=act,
    )
    if not (kernel and tp > 1):
        y = experts(x, router_w, w_gate, w_up, w_down, live, layer, select_bias)
        return y.astype(x.dtype)

    from ..parallel.mesh import shard_map

    lead = (None,) * (w_up.ndim - 2)  # [E] or [L, E]
    # None (no ``live``, no ``layer``) is an empty pytree: its spec is unused.
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P(), P(), P(*lead, None, "tp"), P(*lead, None, "tp"),
            P(*lead, "tp", None), P(), P(), P(),
        ),
        out_specs=P(),
        check_vma=False,
    )
    def sharded(x_l, router_l, wg, wu, wd, live_l, layer_l, bias_l):
        # Each chip holds its columns of the experts' width: the sum over
        # chips of what w_down's rows give is the whole product.
        return jax.lax.psum(
            experts(x_l, router_l, wg, wu, wd, live_l, layer_l, bias_l), "tp"
        )

    return sharded(
        x, router_w, w_gate, w_up, w_down, live, layer, select_bias
    ).astype(x.dtype)


def held_block_rows(rows: int, held: int, routed: int) -> int:
    """Sorted rows a block of the held walk takes, of ``rows`` picks over
    ``routed`` experts of which ``held`` are here: twice the share an even
    router sends here, in whole sublane groups of the kernel's rows, and
    never more than there are. The usual dispatch is then one block, half
    of whose rows are live: of a decode window's 256 picks 32 rows are
    gathered and multiplied, not 256, and a reader of the kernel's calls
    knows a call's live rows from its shape (``benchmark/lib/
    held_expert_calls.py``)."""
    align = expert_gmm.ROW_ALIGN
    want = -(-2 * rows * held // routed // align) * align
    return min(rows, max(align, want))


def _routed_experts(
    x, router_w, w_gate, w_up, w_down, live, layer, select_bias=None, *,
    num_experts_per_tok, norm_topk_prob, kernel, interpret,
    score="softmax", scale=1.0, norm_eps=1e-6, held_from=None,
    held_row_block=None, tally=None, act="silu",
):
    """``moe_ffn``'s arithmetic on the weights one device holds: [N, D]
    float32, before the sum over ``tp`` where the width is sharded."""
    N, D = x.shape
    E = w_up.shape[-3]  # experts held: all the router's unless ``held_from``
    K = num_experts_per_tok
    weights, ids = moe_router(
        x, router_w, K, norm_topk_prob,
        score=score, select_bias=select_bias, scale=scale, norm_eps=norm_eps,
    )
    if held_from is not None:
        # A pick elsewhere is another rank's: past every group here.
        here = (ids >= held_from) & (ids < held_from + E)
        ids = jnp.where(here, ids - held_from, E)
        weights = jnp.where(here, weights, 0.0)
    if live is not None:
        ids = jnp.where(live[:, None], ids, E)  # past every group
        weights = jnp.where(live[:, None], weights, 0.0)

    flat_ids = ids.reshape(-1)  # [N*K]
    # Stable sort so each token's k replicas keep a deterministic order.
    order = jnp.argsort(flat_ids, stable=True)  # [N*K]
    group_sizes = jnp.bincount(flat_ids, length=E)
    flat_w = weights.reshape(-1)
    if tally is not None:
        tally.append(jnp.stack(
            [jnp.sum(group_sizes), jnp.sum(group_sizes > 0)]
        ).astype(jnp.int32))

    def grouped(rows, w, sizes):
        if kernel:
            return expert_gmm.expert_gmm(
                rows, w, sizes, layer, interpret=interpret
            )
        if layer is not None:  # a copy of the layer: ragged_dot takes no slice
            w = jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)
        return jax.lax.ragged_dot(rows, w, sizes)

    def add_rows(acc, order_b, sizes, keep):
        """Add the sorted rows ``order_b`` (``sizes`` of them a group) onto
        their tokens in ``acc``; ``keep`` ([rows] bool or None) says which
        lie in a group: the others are not the grouped matmul's to define."""
        token_of = order_b // K  # originating token per sorted row
        xs = jnp.take(x, token_of, axis=0)  # [rows, D] in expert order
        if w_gate is None:  # two matrices an expert: down(act(up x))
            u = grouped(xs, w_up, sizes)
            h = _ACTS[act](u.astype(jnp.float32)).astype(u.dtype)
        else:
            g = grouped(xs, w_gate, sizes)
            u = grouped(xs, w_up, sizes)
            h = (_ACTS[act](g.astype(jnp.float32)).astype(u.dtype) * u)
        out = grouped(h, w_down, sizes)  # [rows, D]
        out = out.astype(jnp.float32) * jnp.take(flat_w, order_b)[:, None]
        if keep is not None:
            out = jnp.where(keep[:, None], out, 0.0)
        # Unsort + combine: scatter-add each replica back onto its token.
        return acc.at[token_of].add(out)

    acc = jnp.zeros((N, D), jnp.float32)
    block = N * K if held_from is None else (
        held_row_block or held_block_rows(N * K, E, router_w.shape[-1])
    )
    if block >= N * K:
        grouped_rows = live is not None or held_from is not None
        return add_rows(
            acc, order, group_sizes,
            jnp.take(flat_ids, order) < E if grouped_rows else None,
        )

    # The held rows lie first among the sorted ones: walk them a block at a
    # time, as many blocks as they fill. A block's groups are the parts of
    # the groups that fall inside it.
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    padded = jnp.concatenate([order, jnp.zeros((block,), order.dtype)])

    def walk(b, acc):
        lo = b * block
        sizes = jnp.clip(ends, lo, lo + block) - jnp.clip(starts, lo, lo + block)
        return add_rows(
            acc, jax.lax.dynamic_slice_in_dim(padded, lo, block), sizes,
            lo + jnp.arange(block) < ends[-1],
        )

    return jax.lax.fori_loop(0, -(-ends[-1] // block), walk, acc)


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
    norm_topk_prob=True, act="silu",
):
    """Dense oracle: every expert computes every token, combine masks the
    unselected ones. O(E·N) FLOPs — tests only. ``w_gate`` None: the
    two-matrix form, ``down(act(up x))``."""
    N, D = x.shape
    E = router_w.shape[-1]
    weights, ids = moe_router(x, router_w, num_experts_per_tok, norm_topk_prob)
    combine = jnp.zeros((N, E), jnp.float32)
    combine = combine.at[jnp.arange(N)[:, None], ids].add(weights)
    u = jnp.einsum("nd,edi->eni", x, w_up)
    if w_gate is None:
        h = _ACTS[act](u.astype(jnp.float32)).astype(u.dtype)
    else:
        g = jnp.einsum("nd,edi->eni", x, w_gate)
        h = _ACTS[act](g.astype(jnp.float32)).astype(u.dtype) * u
    out = jnp.einsum("eni,eid->end", h, w_down)  # [E, N, D]
    y = jnp.einsum("ne,end->nd", combine, out.astype(jnp.float32))
    return y.astype(x.dtype)
