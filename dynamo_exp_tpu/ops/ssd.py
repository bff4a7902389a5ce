"""Mamba-2's recurrence (the state-space dual form, SSD) over a ragged
token stream.

For every token ``t`` of a sequence and head ``h`` of ``H``, with ``P``
channels a head, ``S`` states a channel, a scalar decay a head ``A_h < 0``,
a step ``dt_t > 0`` a head and token, and ``B_t``, ``C_t`` in ``R^S`` shared
by the ``H / G`` heads of group ``g(h) = h // (H / G)``:

    S_t = exp(dt_t A_h) S_{t-1} + dt_t B_t x_t^T        (S_t in R^{S x P})
    y_t = S_t^T C_t + D_h x_t

``S`` is float32 from end to end. In the pool a sequence keeps it in
between dispatches as ``[layers, entries, S, H * P]`` (``models/llama.py::
init_seq_state``, ``MambaState.ssm``): the states' axis on sublanes and every
head's channels side by side on lanes, 2 MiB an entry and layer at 64 heads
of 64 channels and 128 states.

Four forms of one recurrence, as ``ops/gated_delta.py`` has them:

- :func:`ssd_kernel`: a Pallas TPU kernel, custom-call name ``ssd_chunk``,
  over the flat stream of a mixed dispatch. XLA's own forms do not serve
  there: a 4096-slot stream's per-token states are 2 MiB each, 8.6 GB a
  layer, and the chunked form as XLA's batched products (this PR's first
  reading: every chunk of the call's static bound, two thirds of them
  empty, a ``[chunks, H, C, C]`` mask in HBM, a product a write) took 49 %
  of the device's busy time at 0.8 % of the recurrence's roofline. The
  kernel takes a row's span in chunks of ``CHUNK`` tokens cut from the
  span's start (``ops/gated_delta.py::_chunk_tables``: one table of chunks
  for both recurrences) and a chunk in the matrix form below, its products
  on the MXU, with the whole ``[S, H * P]`` state in VMEM across the span's
  chunks, read from and stored to the pool in place by DMA; an empty chunk
  of the grid costs a step and nothing else. It takes ``x | B | C`` in one
  array as the mixer's convolution leaves it.
- :func:`ssd_step`: one token a row (a decode window's step, the packed tail
  of a mixed stream): gather the rows' states, one step, scatter. Plain
  ``jax.numpy``; the CPU engine's form and the next one's oracle.
- :func:`ssd_step_kernel`: the same step on the chip IN PLACE, a Pallas TPU
  kernel (custom-call name ``ssd_step``) that moves each live row's state
  once from the pool and once back in a ring of three buffers, under
  ``ops/gated_delta.py``'s contract (IN PLACE): reads follow ``slots``,
  writes follow ``dst``, and no row reads an entry another row of the call
  writes.
- :func:`ssd_ref`: the oracle and the definition, a ``lax.scan`` over the
  stream a token at a time.

THE MATRIX FORM of a chunk of ``C`` tokens entering with ``S_0``
(arXiv:2405.21060, section 6). ``a_i = dt_i A_h``, ``L_i = sum_{l<=i}
a_l`` (the decays only ever as differences of these sums, never a quotient
of products: ``exp(L_i)`` alone underflows over a chunk of fast heads),
``X_i = dt_i x_i``:

    Y   = (lower(exp(L_i - L_l)) * C B^T) X + diag(exp(L)) C S_0 + D x
    S_C = exp(L_C) S_0 + (diag(exp(L_C - L)) B)^T X

``C B^T`` is one ``[C, C]`` product a GROUP; the mask a head; ``L`` of every
head at once is one product with the triangle of ones (a head a lane). A
loop step takes a group and its heads a PAIR at a time: two heads' 64
channels are one lane group, so a product's 128 columns are a pair's, each
head's half kept from the product under its own mask. The state
after token ``m`` inside a chunk is the last line with ``L_m`` for ``L_C``
and the rows after ``m`` left out, which is how a write that does not fall
on a chunk's end is honoured (any ``src`` that is a real token; the engine
asks each row's own entry after its last token and one snapshot a prefill
row at a block boundary). Slots of a chunk's ``C`` that are not its own
(another row's, padding) have ``dt = 0``: they leave the state as it was and
reach no other token's output. What reaches the state is a float32 product
at full precision (``STATE_PRECISION``); a chunk's output beside it takes
the default, as the mixer rounds it to the model's dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gated_delta import (
    LANE, OUTPUT_PRECISION, STATE_PRECISION, STEP_BUFFERS, TILE, VMEM_LIMIT,
    _chunk_tables, _dot,
)
from .selective_scan import stream_predecessor

CHUNK = 128  # tokens a chunk: the family's own


def _widths(x, dt, b):
    h = dt.shape[-1]
    return h, x.shape[-1] // h, b.shape[-1]


def _one_step(s, x, dt, a, b, c):
    """One token of the recurrence for every head: ``s`` [.., S, H, P],
    ``x`` [.., H, P], ``dt`` [.., H], ``a`` [H], ``b``, ``c`` [.., G, S].
    Elementwise float32 and sums, no matrix product."""
    h = dt.shape[-1]
    per = h // b.shape[-2]
    bh = jnp.swapaxes(jnp.repeat(b, per, axis=-2), -1, -2)[..., None]  # [.., S, H, 1]
    ch = jnp.swapaxes(jnp.repeat(c, per, axis=-2), -1, -2)[..., None]
    s = s * jnp.exp(dt * a)[..., None, :, None] + bh * (dt[..., None] * x)[..., None, :, :]
    return s, jnp.sum(s * ch, axis=-3)


# ------------------------------------------------------------------ oracle
def ssd_ref(x, dt, a, b, c, d, pool, layer, slots, row_of, positions, src, dst):
    """The recurrence a token at a time over the stream. ``x`` [N, H * P];
    ``dt`` [N, H] (after its softplus); ``a`` [H] (< 0); ``b``, ``c``
    [N, G * S]; ``d`` [H]; ``pool`` [L, entries, S, H * P] float32. Returns
    (y [N, H * P] float32, pool). A token whose predecessor is not in the
    stream starts from its row's entry, from zeros at position 0; padding
    leaves the state alone."""
    f32 = jnp.float32
    h, p, gs = _widths(x, dt, b)
    n, s_ = x.shape[0], pool.shape[2]
    g = gs // s_
    x3 = x.astype(f32).reshape(n, h, p)
    b3, c3 = (v.astype(f32).reshape(n, g, s_) for v in (b, c))
    dt = dt.astype(f32)
    prev = stream_predecessor(row_of, positions)
    entry = slots[row_of]
    states = pool[layer]

    def step(s, t):
        x_t, dt_t, b_t, c_t, prev_t, entry_t, pos_t = t
        held = jnp.where(pos_t > 0, states[entry_t], 0.0)  # clamps past the pool
        s = jnp.where(prev_t, s, held)
        s3, y = _one_step(
            s.reshape(s_, h, p), x_t, jnp.where(pos_t >= 0, dt_t, 0.0), a, b_t, c_t
        )
        s = s3.reshape(s_, h * p)
        return s, ((y + d[:, None] * x_t).reshape(h * p), s)

    _, (y, ss) = jax.lax.scan(
        step, jnp.zeros_like(states[0]), (x3, dt, b3, c3, prev, entry, positions)
    )
    return y, pool.at[layer, dst].set(ss[src], mode="drop")


# ---------------------------------------------------------------- one step
def ssd_step(x, dt, a, b, c, d, pool, layer, slots, positions, dst):
    """One token a row: ``x`` [R, H * P]; ``dt`` [R, H]; ``b``, ``c``
    [R, G * S]; row ``r`` reads entry ``slots[r]`` (zeros at position 0) and
    leaves its new state in ``dst[r]`` (past the pool: no write; a padding
    row's ``dt`` is taken as 0)."""
    f32 = jnp.float32
    h, p, gs = _widths(x, dt, b)
    r, s_ = x.shape[0], pool.shape[2]
    g = gs // s_
    x3 = x.astype(f32).reshape(r, h, p)
    # One gather of (layer, entry, sublane group), as ``ops/gated_delta.py::
    # gated_delta_step``'s: a gather whose slice is a whole 2 MiB state
    # XLA:TPU splits over slices of the whole pool that it materialises.
    groups = pool.reshape(*pool.shape[:2], s_ // TILE, TILE, pool.shape[-1])
    held = groups[layer, slots[:, None], jnp.arange(s_ // TILE)[None, :]]
    s = jnp.where((positions > 0)[:, None, None], held.reshape(r, s_, h * p), 0.0)
    s, y = _one_step(
        s.reshape(r, s_, h, p), x3,
        jnp.where((positions >= 0)[:, None], dt.astype(f32), 0.0), a,
        b.astype(f32).reshape(r, g, s_), c.astype(f32).reshape(r, g, s_),
    )
    y = y + d[:, None] * x3
    return (
        y.reshape(r, h * p),
        pool.at[layer, dst].set(s.reshape(r, s_, h * p), mode="drop"),
    )


# ------------------------------------------------------ one step, in place
def _step_kernel(layer_ref, slots_ref, dst_ref, pos_ref,  # SMEM
                 b_ref, c_ref, x_ref, a_ref, pool_in,
                 y_ref, pool_out,
                 s_ref, load_sem, store_sem, *, groups, states, entries):
    """One grid step: one row, every head. ``s_ref`` [STEP_BUFFERS, S,
    H * P] is a ring: row ``r`` computes in place in buffer ``r % 3`` while
    row ``r + 1``'s state loads into the next and row ``r - 1``'s goes back
    to the pool from the one before (``ops/gated_delta.py::_step_kernel``'s
    schedule)."""
    f32 = jnp.float32
    r = pl.program_id(0)
    rows = pl.num_programs(0)
    layer = layer_ref[0]
    width = s_ref.shape[-1] // groups  # a group's heads' lanes

    def at(i):  # a row's scalars are read inside the call's rows only
        return jnp.clip(i, 0, rows - 1)

    def loads(i):  # i = r or r + 1
        return (i < rows) & (pos_ref[at(i)] > 0)

    def stores(i):  # i = r - 2 .. r
        e = dst_ref[at(i)]
        return (i >= 0) & (pos_ref[at(i)] >= 0) & (e >= 0) & (e < entries)

    def load(i):
        return pltpu.make_async_copy(
            pool_in.at[layer, slots_ref[at(i)]], s_ref.at[i % STEP_BUFFERS],
            load_sem.at[i % STEP_BUFFERS],
        )

    def store(i):
        return pltpu.make_async_copy(
            s_ref.at[i % STEP_BUFFERS], pool_out.at[layer, dst_ref[at(i)]],
            store_sem.at[i % STEP_BUFFERS],
        )

    @pl.when((r == 0) & loads(r))
    def _():
        load(r).start()

    @pl.when(stores(r - 2))
    def _():
        store(r - 2).wait()

    @pl.when(loads(r + 1))
    def _():
        load(r + 1).start()

    pos = pos_ref[r]

    @pl.when(pos < 0)  # padding: nothing read, computed or written
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(pos >= 0)
    def _():
        buf = r % STEP_BUFFERS

        @pl.when(pos == 0)  # a sequence's first token starts from zeros
        def _():
            s_ref[buf] = jnp.zeros(s_ref.shape[1:], f32)

        @pl.when(pos > 0)
        def _():
            load(r).wait()

        group_lane = jax.lax.broadcasted_iota(jnp.int32, b_ref.shape, 1)

        def column(ref, g):  # [S, groups on lanes] -> [S, 1]: one group's vector
            return jnp.sum(jnp.where(group_lane == g, ref[...], 0.0), axis=1, keepdims=True)

        def group(g, carry):
            lanes = pl.ds(pl.multiple_of(g * width, LANE), width)
            decay, xdt = a_ref[:, lanes], x_ref[:, lanes]  # [1, width]
            bb, cc = column(b_ref, g), column(c_ref, g)
            acc = jnp.zeros((TILE, width), f32)
            for t in range(states // TILE):
                at_t = pl.ds(t * TILE, TILE)
                s = decay * s_ref[buf, at_t, lanes] + bb[t * TILE:(t + 1) * TILE] * xdt
                s_ref[buf, at_t, lanes] = s
                acc = acc + s * cc[t * TILE:(t + 1) * TILE]
            y_ref[:, lanes] = jnp.sum(acc, axis=0, keepdims=True)
            return carry

        jax.lax.fori_loop(0, groups, group, 0)

        @pl.when(stores(r))
        def _():
            store(r).start()

    @pl.when(r == rows - 1)  # the last rows' stores end with the call
    def _():
        for i in (r - 1, r):
            @pl.when(stores(i))
            def _(i=i):
                store(i).wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_step_kernel(x, dt, a, b, c, d, pool, layer, slots, positions, dst,
                    interpret: bool = False):
    """:func:`ssd_step` on the TPU (module docstring): the same arguments and
    results, each live row's state moved once from the pool and once back.
    A padding row's output is zeros and its ``dst`` is never written."""
    f32 = jnp.float32
    h, p, gs = _widths(x, dt, b)
    r, entries, s_ = x.shape[0], pool.shape[1], pool.shape[2]
    g = gs // s_
    gp = -(-g // LANE) * LANE
    x, dt = x.astype(f32), dt.astype(f32)

    def columns(v):  # [R, G * S] -> [R, S, groups on a lane group]
        v = v.astype(f32).reshape(r, g, s_).transpose(0, 2, 1)
        return jnp.pad(v, ((0, 0), (0, 0), (0, gp - g)))

    def by_lane(v):  # [R, H] -> [R, 1, H * P]: a head's scalar on its lanes
        return jnp.repeat(v, p, axis=1)[:, None]

    def a_row(width, lanes):
        return pl.BlockSpec((None, width, lanes), lambda i, *_: (i, 0, 0))

    any_space = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(r,),
        in_specs=[a_row(s_, gp)] * 2 + [a_row(1, h * p)] * 2 + [any_space],
        out_specs=[a_row(1, h * p), any_space],
        scratch_shapes=[
            pltpu.VMEM((STEP_BUFFERS, s_, h * p), f32),
            pltpu.SemaphoreType.DMA((STEP_BUFFERS,)),
            pltpu.SemaphoreType.DMA((STEP_BUFFERS,)),
        ],
    )
    y, pool = pl.pallas_call(
        functools.partial(_step_kernel, groups=g, states=s_, entries=entries),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((r, 1, h * p), f32),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ],
        # operand 8 (4 prefetched scalars, then b c x decay pool)
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_LIMIT
        ),
        name="ssd_step",
        interpret=interpret,
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        jnp.clip(slots, 0, entries - 1).astype(jnp.int32),
        dst.astype(jnp.int32), positions.astype(jnp.int32),
        columns(b), columns(c), by_lane(dt) * x[:, None], by_lane(jnp.exp(dt * a)), pool,
    )
    live = (positions >= 0)[:, None]
    return jnp.where(live, y[:, 0] + jnp.repeat(d, p)[None] * x, 0.0), pool


# ------------------------------------------------------------------ kernel
def kernel_supported(heads: int, head_dim: int, groups: int, states: int, q_tile: int) -> bool:
    """Whether the chunk kernel can take these widths laid out in tiles of
    ``q_tile``: two heads' channels are one lane group, a group's heads whole
    pairs, the states one lane group (B's and C's of a group), the heads a
    lane each of one group, and the engine's spans aligned to the tiles."""
    return (
        2 * head_dim == LANE and heads % (2 * groups) == 0 and states == LANE
        and heads <= LANE and q_tile % TILE == 0
    )


def _chunk_kernel(layer_ref, base_ref, lo_ref, hi_ref, load_ref,  # SMEM
                  ea_ref, ata_ref, eb_ref, atb_ref,
                  a_ref, d_ref, x_hbm, dt_hbm, pool_in,
                  y_hbm, pool_out,
                  x_buf, dt_buf, y_buf, s_ref, snap_a, snap_b, cum_ref, cum_t_ref,
                  in_sem, out_sem, sem, *, heads, groups, c, entries):
    """One grid step: one chunk of one span, every head
    (``ops/gated_delta.py::_chunk_kernel``'s schedule: ``s_ref`` [S, H * P]
    the span's running state, kept from step to step; ``snap_a``/``snap_b``
    the states the chunk's writes ask for; ``x_buf``, ``dt_buf`` and
    ``y_buf`` hold two chunks each, the next one's inputs arriving and the
    last one's output leaving while this one computes). A loop step takes a
    GROUP's ``C B^T`` once and its heads a pair at a time, a pair's 128
    lanes one product's columns."""
    f32 = jnp.float32
    i = pl.program_id(0)
    steps = pl.num_programs(0)
    layer = layer_ref[0]
    lo, hi = lo_ref[i], hi_ref[i]
    inner = s_ref.shape[-1]  # H * P: x's lanes of a stream row, then B's, then C's
    pairs = heads // (2 * groups)  # pairs of heads a group

    def at(j):  # a chunk's scalars are read inside the call's chunks only
        return jnp.clip(j, 0, steps - 1)

    def live(j):
        return (j >= 0) & (j < steps) & (hi_ref[at(j)] > lo_ref[at(j)])

    def whole(j):  # its output leaves in one copy, waited for a step later
        return live(j) & (lo_ref[at(j)] == 0) & (hi_ref[at(j)] == c)

    def inputs(j):
        rows_at = pl.ds(pl.multiple_of(base_ref[at(j)], TILE), c)
        slot = j % 2
        return [
            pltpu.make_async_copy(hbm.at[rows_at], buf.at[slot], in_sem.at[slot, n])
            for n, (hbm, buf) in enumerate(((x_hbm, x_buf), (dt_hbm, dt_buf)))
        ]

    def output(j):
        rows_at = pl.ds(pl.multiple_of(base_ref[at(j)], TILE), c)
        return pltpu.make_async_copy(y_buf.at[j % 2], y_hbm.at[rows_at], out_sem.at[j % 2])

    @pl.when((i == 0) & live(0))
    def _():
        for dma in inputs(0):
            dma.start()

    @pl.when(live(i + 1))
    def _():
        for dma in inputs(i + 1):
            dma.start()

    @pl.when(hi > lo)
    def _():
        slot = i % 2
        load = load_ref[i]

        @pl.when(load == entries)  # a span's first chunk, at position 0
        def _():
            s_ref[...] = jnp.zeros_like(s_ref)

        @pl.when((load >= 0) & (load < entries))  # a span resumed from its entry
        def _():
            dma = pltpu.make_async_copy(pool_in.at[layer, load], s_ref, sem.at[0])
            dma.start()
            dma.wait()

        for dma in inputs(i):
            dma.wait()

        # Index planes of a chunk: a token a row, a token a column.
        row = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)
        incl = row >= col
        in_chunk = (row >= lo) & (row < hi)  # [c, 1]
        head_lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANE), 1)
        first = head_lane < LANE // 2  # a pair's first head's channels

        def dot(x, y, precision):
            return _dot(x, y, (1, 0), precision)

        # L_i = sum_{l<=i} dt_l A_h for every head at once (a head a lane): one
        # product with the triangle of ones; and its transpose, a head a row.
        steps_taken = jnp.where(in_chunk, dt_buf[slot], 0.0)  # [c, heads on lanes]
        cum = dot(jnp.where(incl, 1.0, 0.0), steps_taken * a_ref[...], STATE_PRECISION)
        cum_ref[...] = cum
        cum_t_ref[...] = cum.T

        def of_head(x, h):  # [.., heads on lanes] -> [.., 1]
            return jnp.sum(jnp.where(head_lane == h, x, 0.0), axis=-1, keepdims=True)

        def group(g, carry):
            at_b = pl.ds(pl.multiple_of(inner + g * LANE, LANE), LANE)
            at_c = pl.ds(pl.multiple_of(inner + (groups + g) * LANE, LANE), LANE)
            bb, cc = x_buf[slot, :, at_b], x_buf[slot, :, at_c]  # [c, S]
            cb = _dot(cc, bb, (1, 1), OUTPUT_PRECISION)  # C B^T, [c, c]
            for p in range(pairs):
                pair = g * pairs + p
                lanes = pl.ds(pl.multiple_of(pair * LANE, LANE), LANE)
                h0 = 2 * pair
                x2 = x_buf[slot, :, lanes]  # [c, two heads' channels]
                cum0, cum1 = of_head(cum_ref[...], h0), of_head(cum_ref[...], h0 + 1)
                dt0 = of_head(steps_taken, h0)
                dt1 = of_head(steps_taken, h0 + 1)
                xdt = x2 * jnp.where(first, dt0, dt1)
                s0 = s_ref[:, lanes]  # [S, two heads' channels]
                y_pair = []
                for cum_h, h in ((cum0, h0), (cum1, h0 + 1)):
                    as_row = cum_t_ref[pl.ds(h, 1), :]  # [1, c]
                    decay = jnp.where(incl, jnp.exp(jnp.minimum(cum_h - as_row, 0.0)), 0.0)
                    y_pair.append(dot(decay * cb, xdt, OUTPUT_PRECISION))
                grown = jnp.where(first, jnp.exp(cum0), jnp.exp(cum1))  # e^L, [c, 128]
                y = jnp.where(first, y_pair[0], y_pair[1]) + grown * dot(cc, s0, OUTPUT_PRECISION)
                y_buf[slot, :, lanes] = y + d_ref[:, lanes] * x2

                def state_after(slot_at):
                    """The pair's state after the chunk's slot ``slot_at`` (traced)."""
                    out = []
                    for cum_h in (cum0, cum1):
                        upto = jnp.sum(jnp.where(row == slot_at, cum_h, 0.0), keepdims=True)
                        weight = jnp.where(
                            row <= slot_at, jnp.exp(jnp.minimum(upto - cum_h, 0.0)), 0.0
                        )
                        out.append((
                            jnp.exp(upto), _dot(weight * bb, xdt, (0, 0), STATE_PRECISION)
                        ))
                    (k0, add0), (k1, add1) = out
                    return jnp.where(first, k0, k1) * s0 + jnp.where(first, add0, add1)

                # the slots after the chunk's last have dt = 0: its end is slot c - 1
                s_ref[:, lanes] = state_after(c - 1)
                for e_ref, at_ref, snap in (
                    (ea_ref, ata_ref, snap_a), (eb_ref, atb_ref, snap_b)
                ):
                    @pl.when(e_ref[i] < entries)
                    def _(at_ref=at_ref, snap=snap, lanes=lanes, state_after=state_after):
                        snap[:, lanes] = state_after(at_ref[i])
            return carry

        jax.lax.fori_loop(0, groups, group, 0)

    # The chunk's own slots of the output: a whole chunk's in one copy that
    # the next step waits for, a span's last chunk's a tile at a time.
    @pl.when(whole(i - 1))
    def _():
        output(i - 1).wait()

    @pl.when(whole(i))
    def _():
        output(i).start()

        @pl.when(i == steps - 1)
        def _():
            output(i).wait()

    @pl.when((hi > lo) & ~whole(i))
    def _():
        base = base_ref[i]

        def tile(t):
            return pltpu.make_async_copy(
                y_buf.at[i % 2, pl.ds(pl.multiple_of(t * TILE, TILE), TILE)],
                y_hbm.at[pl.ds(pl.multiple_of(base + t * TILE, TILE), TILE)], sem.at[0],
            )

        tiles = jax.lax.div(lo, TILE), jax.lax.div(hi + TILE - 1, TILE)  # a span begins a tile
        jax.lax.fori_loop(*tiles, lambda t, _: tile(t).start(), None)
        jax.lax.fori_loop(*tiles, lambda t, _: tile(t).wait(), None)

    @pl.when(hi > lo)
    def _():
        for e_ref, snap in ((ea_ref, snap_a), (eb_ref, snap_b)):
            entry = e_ref[i]

            @pl.when(entry < entries)
            def _(entry=entry, snap=snap):
                dma = pltpu.make_async_copy(snap, pool_out.at[layer, entry], sem.at[0])
                dma.start()
                dma.wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_kernel(xbc, dt, a, d, pool, layer, slots, row_of, positions, src, dst,
               interpret: bool = False):
    """The stream's recurrence on the TPU in chunks (module docstring): a
    Pallas kernel, custom-call name ``ssd_chunk``. ``xbc`` [N, H * P + 2 G S]
    float32 as the mixer's convolution leaves it (x, then every group's B,
    then every group's C), the rest and the results as :func:`ssd_ref`, the
    layout held to ``ops/gated_delta.py``'s contract (spans aligned to
    ``TILE``, one a row; at most two writes a chunk)."""
    f32 = jnp.float32
    h = dt.shape[-1]
    entries, s_, inner = pool.shape[1], pool.shape[2], pool.shape[3]
    g = (xbc.shape[-1] - inner) // (2 * s_)
    p = inner // h
    n0, c = xbc.shape[0], CHUNK
    xbc = xbc.astype(f32)
    live = (positions >= 0)[:, None]
    dt = jnp.pad(jnp.where(live, dt.astype(f32), 0.0), ((0, 0), (0, LANE - h)))
    if n0 < c:  # a stream shorter than a chunk: slots of padding after it
        grow = lambda v, fill=0: jnp.pad(  # noqa: E731
            v, ((0, c - n0),) + ((0, 0),) * (v.ndim - 1), constant_values=fill
        )
        xbc, dt, row_of = map(grow, (xbc, dt, row_of))
        positions = grow(positions, -1)
    n, width = xbc.shape
    chunks = n // c + slots.shape[0]
    tables = _chunk_tables(slots, row_of, positions, src, dst, c, chunks, entries)

    any_space = pl.BlockSpec(memory_space=pl.ANY)
    whole_row = lambda lanes: pl.BlockSpec((1, lanes), lambda i, *_: (0, 0))  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=9,
        grid=(chunks,),
        in_specs=[whole_row(LANE), whole_row(inner)] + [any_space] * 3,
        out_specs=[any_space, any_space],
        scratch_shapes=[
            pltpu.VMEM((2, c, width), f32),  # x | B | C, this chunk's and the next's
            pltpu.VMEM((2, c, LANE), f32),  # dt, a head a lane
            pltpu.VMEM((2, c, inner), f32),  # y, this chunk's and the last's
            pltpu.VMEM((s_, inner), f32),  # the running state
            pltpu.VMEM((s_, inner), f32),  # a write's
            pltpu.VMEM((s_, inner), f32),  # the other write's
            pltpu.VMEM((c, LANE), f32),  # L, a head a lane
            pltpu.VMEM((LANE, c), f32),  # L, a head a row
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((1,)),
        ],
    )
    y, pool = pl.pallas_call(
        functools.partial(_chunk_kernel, heads=h, groups=g, c=c, entries=entries),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n, inner), f32),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ],
        # operand 13 (9 prefetched scalars, then a d xbc dt pool)
        input_output_aliases={13: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_LIMIT
        ),
        name="ssd_chunk",
        interpret=interpret,
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32), *tables,
        jnp.pad(a.astype(f32), (0, LANE - h))[None], jnp.repeat(d.astype(f32), p)[None],
        xbc, dt, pool,
    )
    # slots no chunk owns were never written
    return jnp.where((positions >= 0)[:, None], y, 0.0)[:n0], pool
