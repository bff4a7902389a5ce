"""Grouped matmul over stacked expert weights, addressed by a layer index.

``ops/moe.py::moe_ffn`` sorts a dispatch's (token, expert) rows by expert
and multiplies each expert's contiguous group of rows by that expert's
matrix. ``jax.lax.ragged_dot`` does that for one layer's ``[E, K, N]``
weights, but XLA:TPU lowers it to a custom call that takes no slice in
place: handed layer ``l`` of the stacked ``[L, E, K, N]`` array it first
copies the layer out, 235 MB a matrix on a Mixtral chip, three times a
layer on every step (PERF.md, PR 28: half of the device's busy time).

This kernel takes the whole stack and ``layer`` as a scalar-prefetched
value, so a weight block's index map is ``(layer, group, k, n)``: the
DMAs read layer ``l``'s blocks where they lie and nothing is copied. A
per-layer ``[E, K, N]`` array is the same call on ``w[None]`` at layer 0.
Every routed forward on a TPU takes it, a decode window's few sorted rows
and a prompt chunk's thousands alike: it is ahead of the copy and
``ragged_dot`` at every row count (``tools/expert_gmm_rows.py``; PERF.md,
PR 60).

The grid is ``(n tiles, row tiles, k tiles)`` in the MegaBlocks manner
(``jax.experimental.pallas.ops.tpu.megablox``): a *row tile* is one
(group, ``tm`` rows of the sorted stream) pair, a group that straddles a
boundary of ``tm`` rows has a tile on each side, and a tile stores only
its own group's rows. Rows past the last group (``moe_ffn``'s padding
rows) and groups with no rows get no tile, so their weight blocks are
never fetched. The tile list is as long as it can get (row blocks +
groups - 1); steps past the live tiles repeat the last live tile's
block indices (no DMA is issued for a block that is already there) and
do nothing.

Float32 accumulation, result in the rows' dtype, as ``ragged_dot`` gives.
A row's bits do not turn with its place among the sorted rows: the MXU
computes each row of a tile from that row alone, and the split of ``K``
into ``tk`` blocks (the only order in which float32 partial sums are
added) is a function of ``K``, never of the row count.

Tiling is a function of shapes alone (``tiles``); a shape it cannot tile
(``supported`` False) takes ``ragged_dot`` on the slice instead.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
ROW_ALIGN = 16  # a bfloat16 block's sublane packing
MAX_TM, MAX_TK = 256, 512
# What the kernel's blocks may take of VMEM (two buffers of each operand
# and of the result, and the float32 accumulator), and the scoped limit
# asked of Mosaic. A v5e core has 128 MiB.
VMEM_BLOCK_BUDGET = 28 << 20
VMEM_LIMIT = 48 << 20


def supported(k: int, n: int) -> bool:
    """Whether ``[*, k] x [.., k, n]`` can be tiled: both are lane
    dimensions of a block (``lhs`` [tm, tk], weights [tk, tn])."""
    return k % LANE == 0 and n % LANE == 0


def _largest_tile(dim: int, cap: int) -> int:
    """The largest multiple of ``LANE`` that divides ``dim`` and is at
    most ``cap`` (``dim`` itself a multiple of ``LANE``)."""
    best = LANE
    for t in range(LANE, min(dim, cap) + 1, LANE):
        if dim % t == 0:
            best = t
    return best


# Where a call's rows span more than one block of ``MAX_TM`` and lie over
# many groups, a block of ``MAX_TM`` rows holds many groups of a few rows
# and every one of its tiles multiplies the whole block: ``tm`` is then
# this many times the mean group (``tiles``).
ROWS_OVER_MEAN_GROUP = 8


def tiles(
    m: int, k: int, n: int, itemsize: int, groups: int | None = None
) -> tuple[int, int, int]:
    """(tm, tk, tn) for ``[m, k] x [.., k, n]`` over ``groups`` groups.

    ``tm``: every row tile multiplies ``tm`` rows by a [k, n] matrix it
    reads whole, so at ~240 rows a v5e's MXU and HBM take as long as each
    other; fewer rows leave the tile HBM-bound, which is what a decode
    step is anyway, and more would multiply rows of other groups for
    nothing. A call of at most ``MAX_TM`` rows is one block whatever its
    groups (Mixtral's and LFM2's decode windows; pinned by
    tests/test_sdar.py). A call of more rows over many groups (a
    block-diffusion pass: 2048 rows over 128 experts, 16 a group) takes
    ``ROWS_OVER_MEAN_GROUP`` mean groups a block: a group that straddles
    two blocks has its matrix fetched twice, so a block is several groups
    long, and no longer, because each of a block's tiles multiplies all
    of its rows (at ``MAX_TM`` that was 1.7 ms of MXU a layer against
    1.5 ms of weights; ROADMAP R1). ``tk`` depends on ``k`` alone (module
    docstring). ``tn`` is as wide as VMEM allows: a [tk, n] block of a
    row-major matrix is one contiguous run, and ``lhs`` is read once per
    n tile."""
    tm = min(MAX_TM, -(-m // ROW_ALIGN) * ROW_ALIGN)
    if groups and m > MAX_TM:
        per_block = -(-ROWS_OVER_MEAN_GROUP * m // groups)
        tm = min(MAX_TM, max(ROW_ALIGN, -(-per_block // ROW_ALIGN) * ROW_ALIGN))
    tk = _largest_tile(k, MAX_TK)
    fixed = 2 * tm * tk * itemsize
    per_col = 2 * tk * itemsize + 2 * tm * itemsize + 4 * tm
    tn = _largest_tile(n, max(LANE, (VMEM_BLOCK_BUDGET - fixed) // per_col))
    return tm, tk, tn


def _row_tiles(group_sizes: jnp.ndarray, row_blocks: int, tm: int):
    """The (group, row block) pairs that hold rows, in sorted order.

    Returns (tile_group [T], tile_block [T], num_tiles [1], offsets
    [E+1]) with T = row_blocks + E - 1, the most there can be. Entries
    past ``num_tiles`` repeat the last live tile."""
    E = group_sizes.shape[0]
    T = row_blocks + E - 1
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tm
    per_group = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    tile_ends = jnp.cumsum(per_group)
    num_tiles = tile_ends[-1]
    t = jnp.minimum(jnp.arange(T, dtype=jnp.int32), jnp.maximum(num_tiles - 1, 0))
    # The group whose tiles hold tile t: how many groups' tiles end at or
    # before it (a searchsorted, as one compare over [T, E]).
    group = jnp.minimum(
        jnp.sum(t[:, None] >= tile_ends[None, :], axis=1), E - 1
    ).astype(jnp.int32)
    block = first[group] + t - (tile_ends - per_group)[group]
    # No live row at all: every step is idle, any block in range will do.
    block = jnp.clip(block, 0, row_blocks - 1).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends.astype(jnp.int32)])
    return group, block, num_tiles.astype(jnp.int32)[None], offsets


def _kernel(
    layer_ref, group_ref, block_ref, num_tiles_ref, offsets_ref,
    lhs_ref, w_ref, out_ref, acc_ref, *, tm: int, k_tiles: int,
):
    del layer_ref
    t, k = pl.program_id(1), pl.program_id(2)

    @pl.when(t < num_tiles_ref[0])
    def _tile():
        @pl.when(k == 0)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(
            lhs_ref[...], w_ref[...], preferred_element_type=jnp.float32
        )

        @pl.when(k == k_tiles - 1)
        def _store():
            g = group_ref[t]
            row = block_ref[t] * tm + jax.lax.broadcasted_iota(
                jnp.int32, acc_ref.shape, 0
            )
            mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
            acc = acc_ref[...]
            # The first tile of a row block also defines the rows that
            # belong to no group (zeros); a later tile of the same block
            # keeps what the earlier groups stored.
            opens = (t == 0) | (block_ref[jnp.maximum(t - 1, 0)] != block_ref[t])

            @pl.when(opens)
            def _first():
                out_ref[...] = jnp.where(mine, acc, 0.0).astype(out_ref.dtype)

            @pl.when(jnp.logical_not(opens))
            def _later():
                out_ref[...] = jnp.where(
                    mine, acc, out_ref[...].astype(jnp.float32)
                ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def expert_gmm(
    lhs: jnp.ndarray,  # [M, K] rows sorted by group
    w: jnp.ndarray,  # [L, E, K, N] stacked, or one layer's [E, K, N]
    group_sizes: jnp.ndarray,  # [E] int; may sum to less than M
    layer: jnp.ndarray | int | None = None,  # which [E, K, N] of a stack
    interpret: bool = False,
) -> jnp.ndarray:
    """``lhs[rows of group e] @ w[layer, e]`` for every group: [M, N] in
    ``lhs``'s dtype. Rows past the last group are not defined where their
    whole block of ``tm`` rows lies past it, zeros otherwise; the caller
    masks them (``moe_ffn``)."""
    if w.ndim == 3:
        w, layer = w[None], 0
    M, K = lhs.shape
    _L, E, _K, N = w.shape
    tm, tk, tn = tiles(M, K, N, lhs.dtype.itemsize, groups=E)
    row_blocks = -(-M // tm)
    if row_blocks * tm != M:
        lhs = jnp.pad(lhs, ((0, row_blocks * tm - M), (0, 0)))
    k_tiles = K // tk
    group, block, num_tiles, offsets = _row_tiles(
        group_sizes.astype(jnp.int32), row_blocks, tm
    )

    def k_of(t, k, num_tiles):
        # An idle step stays on the last live step's blocks.
        return jnp.where(t < num_tiles[0], k, k_tiles - 1)

    def lhs_map(n, t, k, layer, group, block, num_tiles, offsets):
        return block[t], k_of(t, k, num_tiles)

    def w_map(n, t, k, layer, group, block, num_tiles, offsets):
        return layer[0], group[t], k_of(t, k, num_tiles), n

    def out_map(n, t, k, layer, group, block, num_tiles, offsets):
        return block[t], n

    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm, k_tiles=k_tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(N // tn, row_blocks + E - 1, k_tiles),
            in_specs=[
                pl.BlockSpec((tm, tk), lhs_map),
                pl.BlockSpec((None, None, tk, tn), w_map),
            ],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((row_blocks * tm, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
        name="expert_gmm",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), group, block, num_tiles, offsets,
        lhs, w,
    )
    return out[:M] if row_blocks * tm != M else out
