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
(group, block of ``tm`` rows of the sorted stream) pair, and a group that
straddles a boundary of ``tm`` rows has a tile on each side. Every tile
has its group's ``[K, N]`` matrix fetched, so the matrices are read
``row blocks + groups - 1`` times at the most, and a long block fetches
few of them twice. What a tile FETCHES is cut loose from what it
MULTIPLIES (PR 64):

- A row block stays in VMEM for all of its tiles: the rows' block is
  ``[tm, K]`` at index ``(block, 0)``, which changes only where the block
  does, and a tile takes its ``k`` slice of it in the body. So the rows
  are read once an ``n`` tile (17 MB for 4,096 rows of LFM2's, where a
  ``[tm, tk]`` block a grid step read them once a TILE: 48 MB). A block's
  rows arrive whole before its first tile with only the step before to
  overlap (4 MB, ~5 us, for a block of 1,024 of LFM2's rows); keeping them
  from ``[tm, tk]`` slices that ride the weights' pipeline instead cost
  more in copies than it hid (PERF.md section 6, PR 64).
- A tile multiplies, accumulates and stores the *sub-blocks* of ``SUB`` =
  128 rows (one push through a v5e's MXU) that hold a row of its group,
  two at a time where it has two (``PRODUCT_SUB_BLOCKS``), and no other
  row of the block: a group of 8 rows in a block of 256 costs one push a
  matrix, not two, and a block of 1,024 rows costs it no more. The masked
  store keeps what earlier groups stored before the group's first row and
  defines the rows after its last as zeros, which is what the rows of no
  group are.

Rows past the last group (``moe_ffn``'s padding rows) and groups with no
rows get no tile, so their weight blocks are never fetched and their
sub-blocks never multiplied. The tile list is as long as it can get; steps
past the live tiles repeat the last live tile's block indices (no DMA is
issued for a block that is already there) and do nothing. The last row
block may be ragged: the rows are padded to a whole sublane group
(``ROW_ALIGN``), never to a block.

Float32 accumulation, result in the rows' dtype, as ``ragged_dot`` gives.
A row's bits do not turn with its place among the sorted rows: the MXU
computes each row of a tile from that row alone, and the split of ``K``
into ``tk`` blocks (the only order in which float32 partial sums are
added) is a function of ``K``, never of the row count. Which rows are
multiplied beside it (a whole block until PR 64, its sub-blocks since) is
no part of a row's result: the two kernels agree bit for bit
(tests/test_expert_gmm.py).

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
# Rows one push through a v5e's MXU multiplies: a product of a [K, N]
# matrix with fewer rows costs what this many cost.
SUB = 128
# Sub-blocks one product of a tile takes: the MXU holds a [128, 128] piece
# of the matrix while rows stream through it, so a product of 256 rows
# loads the matrix half as often a row as two of 128 (a call whose groups
# are hundreds of rows long is bound by the MXU: tools/expert_gmm_rows.py).
PRODUCT_SUB_BLOCKS = (2, 1)
MAX_TK = 512
# What the kernel's blocks may take of VMEM (two buffers of each operand
# and of the result, and the float32 accumulator), and the scoped limit
# asked of Mosaic. A v5e core has 128 MiB.
VMEM_BLOCK_BUDGET = 28 << 20
VMEM_LIMIT = 48 << 20


def supported(k: int, n: int) -> bool:
    """Whether ``[*, k] x [.., k, n]`` can be tiled: both are lane
    dimensions of a block (``lhs`` [tm, k], weights [tk, tn])."""
    return k % LANE == 0 and n % LANE == 0


def _largest_tile(dim: int, cap: int) -> int:
    """The largest multiple of ``LANE`` that divides ``dim`` and is at
    most ``cap`` (``dim`` itself a multiple of ``LANE``)."""
    best = LANE
    for t in range(LANE, min(dim, cap) + 1, LANE):
        if dim % t == 0:
            best = t
    return best


def _round_up(x: int, to: int) -> int:
    return -(-x // to) * to


def tiles(
    m: int, k: int, n: int, itemsize: int, groups: int | None = None,
    tm: int | None = None,
) -> tuple[int, int, int]:
    """(tm, tk, tn) for ``[m, k] x [.., k, n]`` over ``groups`` groups.

    One rule for a decode window's rows and a prompt chunk's: of the
    ``(tm, tn)`` whose blocks fit ``VMEM_BLOCK_BUDGET``, the pair that makes
    the kernel fetch least, counting the matrices ``ceil(m / tm) + groups
    - 1`` times (a tile a group and one more for every block boundary a
    group can lie across), the rows once an ``n`` tile and the result
    once. A tile multiplies its group's sub-blocks only (module
    docstring), so a long block costs no product and ``tm`` is a question
    of bytes: LFM2's 4,096 rows over 32 experts of [2048, 1792] take blocks
    of 1,024 (35 matrices of 7.3 MB at the most, where blocks of 256
    fetched 47), and a call that fits one block takes one (a decode
    window's 256 rows; 244 rows are a block of 256 whose last rows are
    past the array). For a given ``tn`` the longest block that fits is cut
    to the shortest that makes as few blocks, in whole sub-blocks. A
    narrower ``tn`` leaves room for a longer block and reads the rows once
    more for each ``n`` tile: a Mixtral chip's [4096, 3584] takes 1,792
    columns and 768 rows. What VMEM holds: two ``[tm, k]`` blocks of the
    rows, two ``[tk, tn]`` of a matrix, two ``[tm, tn]`` of the result and
    its float32 accumulator. ``tk`` depends on ``k`` alone (module
    docstring).

    ``tm`` forces a row block (tests and ``tools/expert_gmm_rows.py``'s
    sweep): whole sub-blocks, or at most one; ``ValueError`` where no
    ``tn`` holds it."""
    if tm is not None and tm > SUB and tm % SUB:
        raise ValueError(f"a row block of {tm} rows is no whole sub-blocks of {SUB}")
    tk = _largest_tile(k, MAX_TK)
    rows = _round_up(m, ROW_ALIGN)
    whole = rows if rows <= SUB else _round_up(rows, SUB)  # every row, one block
    best = None
    for tn in range(n, 0, -LANE):
        if n % tn:
            continue
        per_row = 2 * k * itemsize + 2 * tn * itemsize + 4 * tn
        fits = (VMEM_BLOCK_BUDGET - 2 * tk * tn * itemsize) // per_row
        if tm is not None:
            block = min(tm, whole)
        elif fits >= whole:
            block = whole
        else:  # as many blocks as that takes, of one length
            blocks = -(-rows // max(fits // SUB * SUB, 1))
            block = _round_up(-(-rows // blocks), SUB)
        if block > fits:
            continue
        fetched = (
            (-(-rows // block) + (groups or 1) - 1) * k * n
            + (n // tn) * m * k + m * n
        )
        if best is None or fetched < best[0]:
            best = fetched, block, tn
    if best is None:
        raise ValueError(f"no row block of [{tm or SUB}, {k}] fits the VMEM budget")
    return best[1], tk, best[2]


def fetches_and_product_rows(group_sizes, tm: int, sub: int | None = None):
    """What the tiling makes of a call, reckoned on the host from its group
    sizes: (matrices fetched, rows pushed through the MXU). A matrix is
    fetched for every (group, block of ``tm`` rows) pair that holds a row
    (``_row_tiles``' tiles), and such a tile multiplies ``sub`` rows for
    every sub-block of its block that holds a row of its group. ``sub``
    = ``tm`` is a whole block a tile, the kernel before PR 64."""
    sub = sub or min(SUB, tm)
    fetches = product_rows = start = 0
    for size in (int(g) for g in group_sizes):
        end = start + size
        for block in range(start // tm, -(-end // tm) if size else 0):
            lo, hi = max(start, block * tm), min(end, (block + 1) * tm)
            fetches += 1
            product_rows += ((hi - 1) // sub - lo // sub + 1) * sub
        start = end
    return fetches, product_rows


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
    lhs_ref, w_ref, out_ref, acc_ref,
):
    del layer_ref
    t, k = pl.program_id(1), pl.program_id(2)
    (tm, K), (tk, tn) = lhs_ref.shape, w_ref.shape
    sub = min(SUB, tm)

    @pl.when(t < num_tiles_ref[0])
    def _tile():
        g = group_ref[t]
        base = block_ref[t] * tm
        # The group's rows inside the resident block: a tile multiplies,
        # accumulates and stores the sub-blocks that hold one of them.
        lo = jnp.maximum(offsets_ref[g], base) - base
        hi = jnp.minimum(offsets_ref[g + 1], base + tm) - base
        cols = pl.ds(pl.multiple_of(k * tk, tk), tk)

        def multiply(at, size: int):
            """The product, the sum and the store over ``size`` rows of
            the block from row ``at``."""
            rows = pl.ds(pl.multiple_of(at, sub), size)

            @pl.when(k == 0)
            def _zero():
                acc_ref[rows, :] = jnp.zeros((size, tn), jnp.float32)

            acc_ref[rows, :] += jnp.dot(
                lhs_ref[rows, cols], w_ref[...], preferred_element_type=jnp.float32
            )

            @pl.when(k == K // tk - 1)
            def _store():
                row = at + jax.lax.broadcasted_iota(jnp.int32, (size, tn), 0)
                # Every row before the group's first is an earlier group's
                # and keeps what that stored; no tile has stored a row
                # after its last yet, and this one defines it (zero: a row
                # of a later group, or of none).
                out_ref[rows, :] = jnp.where(
                    (row >= lo) & (row < hi), acc_ref[rows, :],
                    jnp.where(row < lo, out_ref[rows, :].astype(jnp.float32), 0.0),
                ).astype(out_ref.dtype)

        # The longest products first: two sub-blocks at a time, then the odd one.
        s, end = lo // sub, (hi - 1) // sub + 1
        for span in (c for c in PRODUCT_SUB_BLOCKS if c * sub <= tm):
            count = (end - s) // span

            def products(i, carry, s=s, span=span):
                multiply((s + span * i) * sub, span * sub)
                return carry

            jax.lax.fori_loop(0, count, products, 0)
            s += span * count


@functools.partial(jax.jit, static_argnames=("interpret", "tm"))
def expert_gmm(
    lhs: jnp.ndarray,  # [M, K] rows sorted by group
    w: jnp.ndarray,  # [L, E, K, N] stacked, or one layer's [E, K, N]
    group_sizes: jnp.ndarray,  # [E] int; may sum to less than M
    layer: jnp.ndarray | int | None = None,  # which [E, K, N] of a stack
    interpret: bool = False,
    tm: int | None = None,  # a row block other than ``tiles``' (tests, tools)
) -> jnp.ndarray:
    """``lhs[rows of group e] @ w[layer, e]`` for every group: [M, N] in
    ``lhs``'s dtype. Rows past the last group are not defined where their
    whole sub-block of ``SUB`` rows lies past it, zeros otherwise; the
    caller masks them (``moe_ffn``)."""
    if w.ndim == 3:
        w, layer = w[None], 0
    M, K = lhs.shape
    _L, E, _K, N = w.shape
    tm, tk, tn = tiles(M, K, N, lhs.dtype.itemsize, groups=E, tm=tm)
    rows = _round_up(M, ROW_ALIGN)
    if rows != M:
        lhs = jnp.pad(lhs, ((0, rows - M), (0, 0)))
    row_blocks = -(-rows // tm)  # the last may be ragged
    k_tiles = K // tk
    group, block, num_tiles, offsets = _row_tiles(
        group_sizes.astype(jnp.int32), row_blocks, tm
    )

    def lhs_map(n, t, k, layer, group, block, num_tiles, offsets):
        return block[t], 0

    def w_map(n, t, k, layer, group, block, num_tiles, offsets):
        # An idle step stays on the last live step's blocks.
        return layer[0], group[t], jnp.where(t < num_tiles[0], k, k_tiles - 1), n

    def out_map(n, t, k, layer, group, block, num_tiles, offsets):
        return block[t], n

    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(N // tn, row_blocks + E - 1, k_tiles),
            in_specs=[
                pl.BlockSpec((tm, K), lhs_map),
                pl.BlockSpec((None, None, tk, tn), w_map),
            ],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, N), lhs.dtype),
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
    return out[:M] if rows != M else out
