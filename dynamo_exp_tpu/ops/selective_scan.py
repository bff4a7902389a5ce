"""The selective scan (Mamba-1) over a ragged token stream.

For every token ``t`` of a sequence, channel ``e`` of ``E`` and state
``s`` of ``S``:

    h_t[s, e] = exp(dt_t[e] * A[s, e]) * h_{t-1}[s, e] + dt_t[e] * x_t[e] * B_t[s]
    y_t[e]    = sum_s h_t[s, e] * C_t[s] + D[e] * x_t[e]

``h`` is float32 from end to end: in the pool a sequence keeps it in
between dispatches (``models/llama.py::init_seq_state``, part ``ssm``:
``[layers, entries, S, E]``, the states on sublanes and the channels on
lanes, which is also how the kernel holds it), in VMEM and in every
store. A decay of ``exp(-1e-3)`` a token is lost in bfloat16's 8 bits
within a few hundred tokens.

Three forms of one recurrence:

- :func:`selective_scan_kernel`: a Pallas TPU kernel over the flat stream
  of a mixed dispatch. XLA's own forms do not serve there: a 4096-token
  dispatch of Jamba2-3B would materialise ``[4096, 5120, 16]`` float32
  (1.34 GB) for each of the decays, the inputs and the states, a layer.
  The kernel walks the stream's tiles of ``TILE`` slots in order with the
  whole ``[S, E]`` state in VMEM, takes a row's starting state from its
  pool entry where a tile begins a span (zeros at position 0) and stores
  it where a write asks (DMAs to and from the pool, which is updated in
  place). Spans are aligned to ``TILE`` as ``engine._build_mixed`` lays
  them for the attention kernel (``ragged_q_tile``): a tile holds tokens
  of one row, real ones first. A padding slot has ``dt = 0``: it leaves
  the state as it was.
- :func:`selective_scan_step`: one token a row (a decode window's step,
  the packed tail of a mixed stream): gather the rows' states, one step
  of the recurrence, scatter. Plain ``jax.numpy``.
- :func:`selective_scan_ref`: the oracle, a ``lax.scan`` over the stream
  a token at a time. Any layout, any write. The CPU engine and the tests.

WRITES. ``src``/``dst`` [W] as ``SeqStateIO`` has them: the state after
stream token ``src[w]`` goes to entry ``dst[w]``; a ``dst`` past the pool
is no write. The kernel stores at the ends of tiles, so it honours a
write whose ``src`` is the last real token of its row in its tile: the
end of a row's span, or a position one short of a multiple of ``TILE``
into it (a block boundary of a page size that ``TILE`` divides, in a span
that starts on one). That is every write ``engine._build_mixed`` asks:
each row's own entry after its last token and one snapshot a prefill row
at a block boundary. At most two writes a tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
TILE = 8  # slots a tile: a float32 vreg's sublanes, the engine's ragged_q_tile
# Tiles a grid step: 128 tokens of x, dt, y ([128, E] float32 each) and of
# B and C ([128, S, 128] float32) are 12 MB of double-buffered blocks at
# Jamba2-3B's E = 5120, S = 16.
TILES_A_STEP = 16
VMEM_LIMIT = 64 << 20


def supported(inner: int, q_tile: int) -> bool:
    """Whether the kernel can take a stream of ``inner`` channels laid out
    in tiles of ``q_tile``: channels fill whole lane groups, and the
    engine's spans are aligned to the kernel's tiles."""
    return inner % LANE == 0 and q_tile % TILE == 0


def stream_predecessor(row_of, positions):
    """[N] bool: whether stream token i-1 is token i's predecessor in its
    row (the one rule ``models/llama.py::_stream_history`` also reads)."""
    return jnp.concatenate([
        jnp.zeros((1,), bool),
        (row_of[1:] == row_of[:-1])
        & (positions[1:] == positions[:-1] + 1) & (positions[:-1] >= 0),
    ])


# ------------------------------------------------------------------ oracle
def selective_scan_ref(x, dt, a, b, c, d, pool, layer, slots, row_of,
                       positions, src, dst):
    """The recurrence a token at a time over the stream. ``x``, ``dt``
    [N, E]; ``a`` [S, E] (negative); ``b``, ``c`` [N, S]; ``d`` [E];
    ``pool`` [L, entries, S, E] float32. Returns (y [N, E] float32, pool).
    A token whose predecessor is not in the stream starts from its row's
    entry, from zeros at position 0; padding leaves the state alone."""
    f32 = jnp.float32
    x, dt, b, c = (v.astype(f32) for v in (x, dt, b, c))
    prev = stream_predecessor(row_of, positions)
    entry = slots[row_of]
    states = pool[layer]

    def step(h, t):
        x_t, dt_t, b_t, c_t, prev_t, entry_t, pos_t = t
        held = jnp.where(pos_t > 0, states[entry_t], 0.0)  # clamps past the pool
        h = jnp.where(prev_t, h, held)
        dt_t = jnp.where(pos_t >= 0, dt_t, 0.0)
        h = jnp.exp(dt_t[None] * a) * h + (dt_t * x_t)[None] * b_t[:, None]
        return h, (jnp.sum(h * c_t[:, None], axis=0) + d * x_t, h)

    _, (y, hs) = jax.lax.scan(
        step, jnp.zeros_like(states[0]), (x, dt, b, c, prev, entry, positions)
    )
    return y, pool.at[layer, dst].set(hs[src], mode="drop")


# ---------------------------------------------------------------- one step
def selective_scan_step(x, dt, a, b, c, d, pool, layer, slots, positions, dst):
    """One token a row: ``x``, ``dt`` [R, E]; ``b``, ``c`` [R, S]; row
    ``r`` reads entry ``slots[r]`` (zeros at position 0) and leaves its
    new state in ``dst[r]`` (past the pool: no write; a padding row's
    ``dt`` is taken as 0)."""
    f32 = jnp.float32
    x, dt, b, c = (v.astype(f32) for v in (x, dt, b, c))
    # one gather of (layer, entry): pool[layer] first would copy the layer's 105 MB
    h = jnp.where((positions > 0)[:, None, None], pool[layer, slots], 0.0)
    dt = jnp.where((positions >= 0)[:, None], dt, 0.0)
    h = jnp.exp(dt[:, None, :] * a[None]) * h + (dt * x)[:, None, :] * b[:, :, None]
    y = jnp.sum(h * c[:, :, None], axis=1) + d * x
    return y, pool.at[layer, dst].set(h, mode="drop")


# ------------------------------------------------------------------ kernel
def _scan_kernel(layer_ref, load_ref, store_a_ref, store_b_ref,  # SMEM
                 x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, pool_in,
                 y_ref, pool_out, h_ref, sem, *, groups, tiles, entries):
    """One grid step: ``tiles`` tiles of the stream. ``h_ref`` [S, E] is
    the running state, kept from step to step."""
    # pool_in and pool_out are one buffer (input_output_aliases): a span
    # reads its entry before any tile of the call writes it.
    step = pl.program_id(0)
    layer = layer_ref[0]

    def tile(k, carry):
        t = step * tiles + k
        at = pl.multiple_of(k * TILE, TILE)
        load = load_ref[t]

        @pl.when(load == entries)  # a span's first tile, at position 0
        def _():
            h_ref[...] = jnp.zeros_like(h_ref)

        @pl.when((load >= 0) & (load < entries))  # a span resumed from its entry
        def _():
            dma = pltpu.make_async_copy(pool_in.at[layer, load], h_ref, sem)
            dma.start()
            dma.wait()

        def group(g, carry):
            lanes = pl.ds(pl.multiple_of(g * LANE, LANE), LANE)
            h = h_ref[:, lanes]  # [S, 128]
            a = a_ref[:, lanes]
            x8 = x_ref[pl.ds(at, TILE), lanes]  # [8, 128]
            dt8 = dt_ref[pl.ds(at, TILE), lanes]
            dtx8 = dt8 * x8
            ys = []
            for j in range(TILE):
                h = jnp.exp(dt8[j:j + 1] * a) * h + dtx8[j:j + 1] * b_ref[at + j]
                ys.append(jnp.sum(h * c_ref[at + j], axis=0, keepdims=True))
            y_ref[pl.ds(at, TILE), lanes] = (
                jnp.concatenate(ys, axis=0) + d_ref[:, lanes] * x8
            )
            h_ref[:, lanes] = h
            return carry

        jax.lax.fori_loop(0, groups, group, 0)

        for store_ref in (store_a_ref, store_b_ref):
            entry = store_ref[t]

            @pl.when(entry < entries)
            def _(entry=entry):
                dma = pltpu.make_async_copy(h_ref, pool_out.at[layer, entry], sem)
                dma.start()
                dma.wait()

        return carry

    jax.lax.fori_loop(0, tiles, tile, 0)


def _tile_tables(slots, row_of, positions, src, dst, n_tiles, entries):
    """What each tile does with the pool: (``load`` [tiles]: -1 go on from
    the tile before, ``entries`` start from zeros, else the entry to
    start from; ``store_a``, ``store_b`` [tiles]: the entries the state at
    the tile's end goes to, ``entries`` = none)."""
    first_row = row_of.reshape(n_tiles, TILE)[:, 0]
    first_pos = positions.reshape(n_tiles, TILE)[:, 0]
    follows = stream_predecessor(row_of, positions).reshape(n_tiles, TILE)[:, 0]
    entry = jnp.minimum(slots[first_row], entries)
    load = jnp.where(
        follows | (first_pos < 0), -1, jnp.where(first_pos == 0, entries, entry)
    ).astype(jnp.int32)
    at = src // TILE
    dst = jnp.minimum(dst, entries).astype(jnp.int32)
    none = jnp.full((n_tiles,), entries, jnp.int32)
    store_a = none.at[at].min(dst)
    store_b = none.at[at].min(jnp.where(dst == store_a[at], entries, dst))
    return load, store_a, store_b


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan_kernel(x, dt, a, b, c, d, pool, layer, slots, row_of,
                          positions, src, dst, interpret: bool = False):
    """The stream's recurrence on the TPU (module docstring): arguments
    and results as :func:`selective_scan_ref`, the layout and the writes
    held to the contract above. ``N`` a multiple of ``TILE``, ``E`` of 128."""
    f32 = jnp.float32
    n, e = x.shape
    s = a.shape[0]
    entries = pool.shape[1]
    n_tiles = n // TILE
    tiles = min(TILES_A_STEP, n_tiles)
    while n_tiles % tiles:
        tiles //= 2
    load, store_a, store_b = _tile_tables(
        slots, row_of, positions, src, dst, n_tiles, entries
    )
    dt = jnp.where((positions >= 0)[:, None], dt.astype(f32), 0.0)
    # B and C a token as [S, 128]: state s on sublane s, the same on every
    # lane, so the kernel multiplies a lane group of the state by them as
    # they come (a [S] column cannot be had from a [N, S] block without a
    # transpose a token).
    wide = lambda v: jnp.broadcast_to(  # noqa: E731
        v.astype(f32)[:, :, None], (n, s, LANE)
    )
    rows = tiles * TILE
    by_rows = lambda i, *_: (i, 0)  # noqa: E731
    whole = lambda i, *_: (0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_tiles // tiles,),
        in_specs=[
            pl.BlockSpec((rows, e), by_rows),  # x
            pl.BlockSpec((rows, e), by_rows),  # dt
            pl.BlockSpec((rows, s, LANE), lambda i, *_: (i, 0, 0)),  # B
            pl.BlockSpec((rows, s, LANE), lambda i, *_: (i, 0, 0)),  # C
            pl.BlockSpec((s, e), whole),  # A
            pl.BlockSpec((1, e), whole),  # D
            pl.BlockSpec(memory_space=pl.ANY),  # the pool
        ],
        out_specs=[
            pl.BlockSpec((rows, e), by_rows),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[pltpu.VMEM((s, e), f32), pltpu.SemaphoreType.DMA(())],
    )
    y, pool = pl.pallas_call(
        functools.partial(
            _scan_kernel, groups=e // LANE, tiles=tiles, entries=entries
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n, e), f32),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ],
        # operand 10 (4 prefetched scalars, then x dt B C A D pool)
        input_output_aliases={10: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_LIMIT
        ),
        name="ssm_scan",
        interpret=interpret,
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32), load, store_a, store_b,
        x.astype(f32), dt, wide(b), wide(c), a.astype(f32),
        d.astype(f32)[None], pool,
    )
    return y, pool
