"""The gated delta rule (Gated DeltaNet's linear attention) over a ragged
token stream.

For every token ``t`` of a sequence and head ``j`` of ``H``, with
``k_t`` of unit length in ``R^dk``, ``v_t`` in ``R^dv``, a decay
``a_t = exp(g_t)`` in (0, 1] and a step ``b_t`` in [0, 2):

    S_t = a_t S_{t-1} + b_t k_t (v_t - a_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

``S`` (``[dk, dv]`` a head) is float32 from end to end. In the pool a
sequence keeps it in between dispatches (``models/llama.py::
init_seq_state``, part ``state``: ``[layers, entries, dk, H * dv]``, the
keys' axis on sublanes and every head's values side by side on lanes, so
that 30 heads of 192 fill 45 lane groups where ``[H, dk, dv]`` would pad
each head's 192 lanes to 256), in VMEM and in every store.

Four forms of one recurrence (``ops/selective_scan.py`` has three of them):

- :func:`gated_delta_kernel`: a Pallas TPU kernel, custom-call name
  ``gated_delta_chunk``, over the flat stream of a mixed dispatch. XLA's
  own forms do not serve there: a 4096-token stream's per-token states
  are ``[4096, 30, 96, 192]`` float32, 9.1 GB a layer. The kernel takes a
  row's span in chunks of ``CHUNK`` tokens and a chunk in the matrix form
  (below), its products on the MXU, with the whole ``[dk, H * dv]`` state
  in VMEM across the span's chunks, read from and stored to the pool in
  place by DMA.
- :func:`gated_delta_step`: one token a row (a decode window's step, the
  packed tail of a mixed stream): gather the rows' states, one step of
  the recurrence, scatter. Plain ``jax.numpy``, no matrix product. The
  CPU engine's form and the next one's oracle.
- :func:`gated_delta_step_kernel`: the same step on the chip, IN PLACE
  (below): a Pallas TPU kernel, custom-call name ``gated_delta_step``,
  that moves each live row's state once from the pool and once back.
  XLA's form of the plain step moves it some twenty times (a gather that
  copies, a relayout of ``H * dv`` lanes to ``[H, dv]``, four fusions
  and a scatter: 1,458 MB for 70.8 MB of states at 32 rows of
  Olmo-Hybrid-7B's widths, compile-only) and runs at a tenth of its HBM
  bound. No matrix product either: float32 elementwise and sums.
- :func:`gated_delta_ref`: the oracle and the definition, a ``lax.scan``
  over the stream a token at a time. Any layout, any write. The CPU
  engine and the tests.

THE MATRIX FORM of a chunk of ``C`` tokens entering with ``S_0``
(arXiv:2412.06464, section 3.3). ``G_i = sum_{l<=i} g_l`` (the decays only
ever as differences of these sums, never a quotient of products),
``D_il = exp(G_i - G_l)`` for ``i >= l``:

    A  = strictLower(diag(b) (D * K K^T))         T = (I + A)^-1
    U  = T diag(b) V          W = T diag(b exp(G)) K
    V' = U - W S_0
    O  = diag(exp(G)) Q S_0 + lower(D * Q K^T) V'
    S_C = exp(G_C) S_0 + (diag(exp(G_C - G)) K)^T V'

``T`` is forward substitution in blocks (:func:`unit_lower_inverse`): the
inverse of ``[[I + A11, 0], [A21, I + A22]]`` is ``[[T11, 0], [-T22 A21
T11, T22]]``, from blocks of 2 doubling to ``C``, two products a level and
no power series (``A``'s entries reach 2 where neighbouring keys agree and
its powers cancel catastrophically). Everything that reaches ``V'`` or the
state is a float32 product at full precision (``STATE_PRECISION``): one
bfloat16 pass there costs the state what rounding it to bfloat16 a chunk
would. The state after token ``m`` inside a chunk is the last line with
``G_m`` for ``G_C`` and the rows after ``m`` left out, which is how a
write that does not fall on a chunk's end is honoured.

IN PLACE. The one-token kernel is a grid over the rows, ``layer``,
``slots``, ``dst`` and ``positions`` prefetched as scalars and the pool
aliased in to out. A row at a position below 0 (padding: a mixed stream's
tail hands over every slot, of which half hold a decode row) reads,
computes and writes nothing but a zero output row. Any other row has its
``[dk, H * dv]`` entry brought into VMEM by DMA (zeros at position 0, no
read), is stepped there and goes back by DMA to ``dst[r]`` unless that is
past the pool, in a ring of three buffers: row ``r + 1`` loads and row
``r - 1`` stores while row ``r`` computes, so the call costs what the
DMAs cost (0.24 ms for 31 rows of 2.2 MB each way, 567 GB/s; with the
computing taken out the call takes as long: my chip run, PR 46). The
state keeps the pool's layout throughout: heads two at a time as in the
chunk kernel (``2 * dv`` lanes, whole lane groups), ``k`` and ``q`` a head
as ``[dk, 1]`` columns broadcast over the pair's lanes (picked from ``[dk,
heads on lanes]`` by a masked sum over lanes), ``exp(g)``, ``beta`` and
``v`` as ``[1, 2 * dv]`` rows, the sums over ``dk`` down the sublanes a
group of 8 at a time. THE CONTRACT: reads follow ``slots``, writes follow
``dst``, any ``dst[r] != slots[r]`` is honoured (a snapshot), and within
one call no row reads an entry that another row writes. The plain form
gathers everything before it scatters and would not care; the kernel
takes the rows one after another with the next row's read already on its
way. The engine's rows own their entries and a snapshot's entry is fresh
(``engine._build_mixed``, ``engine._build_windowed``), so nothing asks
for more.

LAYOUT. Spans are aligned to ``TILE`` slots as ``engine._build_mixed`` lays
them for the attention kernel (``ragged_q_tile``), real tokens first, one
span a row; chunks are cut from each span's start, so the last chunk of a
span is partial. A chunk is read as ``CHUNK`` consecutive slots of the
stream (the slots beside a partial chunk belong to other rows): the slots
outside it, and padding slots, have ``b = 0`` and ``g = 0``, which leave
the state as it was and reach no other token's output, and only the
chunk's own slots of the output are written. Heads are taken two at a
time, ``2 * CHUNK`` rows by ``2 * dv`` lanes (``2 * 192 = 384``, whole lane
groups), head against head masked to zero: products of 128 rows, and no
lane slice that is not a multiple of 128.

WRITES. ``src``/``dst`` [W] as ``SeqStateIO`` has them: the state after
stream token ``src[w]`` goes to entry ``dst[w]``; a ``dst`` past the pool
is no write. The kernel honours any ``src`` that is a real token of a span
(the matrix form above), at most two a chunk: what ``engine._build_mixed``
asks is each row's own entry after its last token and one snapshot a
prefill row at a block boundary, which lies 16 tokens into a chunk as
often as at its end.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .selective_scan import stream_predecessor

LANE = 128
TILE = 8  # slots a span is aligned to: the engine's ragged_q_tile
CHUNK = 64  # tokens a chunk: two heads are 128 rows of every product
VMEM_LIMIT = 64 << 20
# Products that reach V' or the state, and the rest: a chunk's output
# beside what it hands on, which the mixer rounds to the model's dtype.
STATE_PRECISION = jax.lax.Precision.HIGHEST
OUTPUT_PRECISION = jax.lax.Precision.DEFAULT


def supported(heads: int, dk: int, dv: int, q_tile: int) -> bool:
    """Whether the kernel can take a stream of ``heads`` heads of ``dk`` by
    ``dv`` laid out in tiles of ``q_tile``: two heads' values fill whole
    lane groups, the keys' axis whole sublanes, and the engine's spans are
    aligned to the kernel's tiles."""
    return (
        heads % 2 == 0 and (2 * dv) % LANE == 0 and dk % TILE == 0
        and q_tile % TILE == 0
    )


def _heads(q, v, g):
    h = g.shape[-1]
    return h, q.shape[-1] // h, v.shape[-1] // h


# ------------------------------------------------------------------ oracle
def _one_step(s, q, k, v, g, b):
    """One token of the recurrence for every head: ``s`` [.., dk, H, dv],
    ``q``, ``k`` [.., H, dk], ``v`` [.., H, dv], ``g``, ``b`` [.., H].
    Elementwise float32 and sums, no matrix product: what the chip's
    default precision would do to one is not in it."""
    kk = jnp.swapaxes(k, -1, -2)[..., None]  # [.., dk, H, 1]
    s = s * jnp.exp(g)[..., None, :, None]
    err = b[..., None] * (v - jnp.sum(s * kk, axis=-3))  # [.., H, dv]
    s = s + kk * err[..., None, :, :]
    o = jnp.sum(s * jnp.swapaxes(q, -1, -2)[..., None], axis=-3)
    return s, o


def gated_delta_ref(q, k, v, g, beta, pool, layer, slots, row_of, positions,
                    src, dst):
    """The recurrence a token at a time over the stream. ``q``, ``k``
    [N, H * dk] (``k`` of unit length a head, ``q`` scaled); ``v``
    [N, H * dv]; ``g`` (the decay's logarithm, <= 0), ``beta`` [N, H];
    ``pool`` [L, entries, dk, H * dv] float32. Returns (o [N, H * dv]
    float32, pool). A token whose predecessor is not in the stream starts
    from its row's entry, from zeros at position 0; padding leaves the
    state alone."""
    f32 = jnp.float32
    h, dk, dv = _heads(q, v, g)
    n = q.shape[0]
    q, k = (x.astype(f32).reshape(n, h, dk) for x in (q, k))
    v = v.astype(f32).reshape(n, h, dv)
    g, beta = g.astype(f32), beta.astype(f32)
    prev = stream_predecessor(row_of, positions)
    entry = slots[row_of]
    states = pool[layer]

    def step(s, t):
        q_t, k_t, v_t, g_t, b_t, prev_t, entry_t, pos_t = t
        held = jnp.where(pos_t > 0, states[entry_t], 0.0)  # clamps past the pool
        s = jnp.where(prev_t, s, held)
        live = pos_t >= 0
        s3, o = _one_step(
            s.reshape(dk, h, dv), q_t, k_t, v_t,
            jnp.where(live, g_t, 0.0), jnp.where(live, b_t, 0.0),
        )
        s = s3.reshape(dk, h * dv)
        return s, (o.reshape(h * dv), s)

    _, (o, ss) = jax.lax.scan(
        step, jnp.zeros_like(states[0]),
        (q, k, v, g, beta, prev, entry, positions),
    )
    return o, pool.at[layer, dst].set(ss[src], mode="drop")


# ---------------------------------------------------------------- one step
def gated_delta_step(q, k, v, g, beta, pool, layer, slots, positions, dst):
    """One token a row: ``q``, ``k`` [R, H * dk]; ``v`` [R, H * dv]; ``g``,
    ``beta`` [R, H]; row ``r`` reads entry ``slots[r]`` (zeros at position
    0) and leaves its new state in ``dst[r]`` (past the pool: no write; a
    padding row's ``g`` and ``beta`` are taken as 0)."""
    f32 = jnp.float32
    h, dk, dv = _heads(q, v, g)
    r = q.shape[0]
    q, k = (x.astype(f32).reshape(r, h, dk) for x in (q, k))
    v = v.astype(f32).reshape(r, h, dv)
    live = (positions >= 0)[:, None]
    # One gather of (layer, entry, sublane group): pool[layer] first would
    # copy the layer, and a gather whose slice is a whole [dk, H * dv] state
    # (2.2 MB at Olmo-Hybrid-7B's widths) XLA:TPU splits over slices of the
    # WHOLE pool that it materialises (0.7 GB each there), a copy of the
    # pool a layer and step.
    groups = pool.reshape(*pool.shape[:2], dk // TILE, TILE, pool.shape[-1])
    held = groups[layer, slots[:, None], jnp.arange(dk // TILE)[None, :]]
    s = jnp.where((positions > 0)[:, None, None], held.reshape(r, dk, h * dv), 0.0)
    s, o = _one_step(
        s.reshape(r, dk, h, dv), q, k, v,
        jnp.where(live, g.astype(f32), 0.0), jnp.where(live, beta.astype(f32), 0.0),
    )
    return (
        o.reshape(r, h * dv),
        pool.at[layer, dst].set(s.reshape(r, dk, h * dv), mode="drop"),
    )


# ------------------------------------------------------ one step, in place
STEP_BUFFERS = 3  # a row's state computing, the next loading, the last storing


def _step_kernel(layer_ref, slots_ref, dst_ref, pos_ref,  # SMEM
                 k_ref, q_ref, v_ref, a_ref, b_ref, pool_in,
                 o_ref, pool_out,
                 s_ref, load_sem, store_sem, *, heads, dk, dv, entries):
    """One grid step: one row, every head. ``s_ref`` [STEP_BUFFERS, dk,
    H * dv] is a ring: row ``r`` computes in place in buffer ``r % 3``
    while row ``r + 1``'s state loads into the next and row ``r - 1``'s
    goes back to the pool from the one before."""
    # pool_in and pool_out are one buffer (input_output_aliases). What is
    # in flight follows from the prefetched scalars alone, so every step
    # recomputes it: a load for a live row past position 0, a store for a
    # live row whose dst is an entry.
    f32 = jnp.float32
    r = pl.program_id(0)
    rows = pl.num_programs(0)
    layer = layer_ref[0]
    w2 = 2 * dv

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

    # Row r + 1's buffer last held row r - 2: its store has had a whole
    # row's computing to finish.
    @pl.when(stores(r - 2))
    def _():
        store(r - 2).wait()

    @pl.when(loads(r + 1))
    def _():
        load(r + 1).start()

    pos = pos_ref[r]

    @pl.when(pos < 0)  # padding: nothing read, computed or written
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(pos >= 0)
    def _():
        buf = r % STEP_BUFFERS

        @pl.when(pos == 0)  # a sequence's first token starts from zeros
        def _():
            s_ref[buf] = jnp.zeros(s_ref.shape[1:], f32)

        @pl.when(pos > 0)
        def _():
            load(r).wait()

        first_lanes = jax.lax.broadcasted_iota(jnp.int32, (1, w2), 1) < dv
        head_lane = jax.lax.broadcasted_iota(jnp.int32, k_ref.shape, 1)

        def column(ref, head):  # [dk, HP] -> [dk, 1]: one head's vector
            return jnp.sum(jnp.where(head_lane == head, ref[...], 0.0), axis=1, keepdims=True)

        def pair(p, carry):
            lanes = pl.ds(pl.multiple_of(p * w2, LANE), w2)
            a, b, v = a_ref[:, lanes], b_ref[:, lanes], v_ref[:, lanes]  # [1, w2]
            kk = jnp.where(first_lanes, column(k_ref, 2 * p), column(k_ref, 2 * p + 1))
            qq = jnp.where(first_lanes, column(q_ref, 2 * p), column(q_ref, 2 * p + 1))
            # Two walks over the pair's [dk, w2] plane a sublane group at a
            # time, the sums over dk kept a group wide until their end.
            sk = jnp.zeros((TILE, w2), f32)
            for t in range(dk // TILE):
                at_t = pl.ds(t * TILE, TILE)
                sk = sk + (a * s_ref[buf, at_t, lanes]) * kk[t * TILE:(t + 1) * TILE]
            err = b * (v - jnp.sum(sk, axis=0, keepdims=True))
            so = jnp.zeros((TILE, w2), f32)
            for t in range(dk // TILE):
                at_t = pl.ds(t * TILE, TILE)
                s = a * s_ref[buf, at_t, lanes] + kk[t * TILE:(t + 1) * TILE] * err
                s_ref[buf, at_t, lanes] = s
                so = so + s * qq[t * TILE:(t + 1) * TILE]
            o_ref[:, lanes] = jnp.sum(so, axis=0, keepdims=True)
            return carry

        jax.lax.fori_loop(0, heads // 2, pair, 0)

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
def gated_delta_step_kernel(q, k, v, g, beta, pool, layer, slots, positions, dst,
                            interpret: bool = False):
    """:func:`gated_delta_step` on the TPU (module docstring, IN PLACE):
    the same arguments and results, each live row's state moved once from
    the pool and once back, under that section's contract: no row reads an
    entry another row of the call writes. A padding row's output is zeros
    and its ``dst`` is never written."""
    f32 = jnp.float32
    h, dk, dv = _heads(q, v, g)
    r = q.shape[0]
    entries = pool.shape[1]
    hp = -(-h // LANE) * LANE

    def columns(x):  # [R, H * dk] -> [R, dk, heads on a lane group]
        x = x.astype(f32).reshape(r, h, dk).transpose(0, 2, 1)
        return jnp.pad(x, ((0, 0), (0, 0), (0, hp - h)))

    def by_lane(x):  # [R, H] -> [R, 1, H * dv]: a head's scalar on its lanes
        return jnp.repeat(x.astype(f32), dv, axis=1)[:, None]

    def a_row(width, lanes):
        return pl.BlockSpec((None, width, lanes), lambda i, *_: (i, 0, 0))

    any_space = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(r,),
        in_specs=[a_row(dk, hp)] * 2 + [a_row(1, h * dv)] * 3 + [any_space],
        out_specs=[a_row(1, h * dv), any_space],
        scratch_shapes=[
            pltpu.VMEM((STEP_BUFFERS, dk, h * dv), f32),
            pltpu.SemaphoreType.DMA((STEP_BUFFERS,)),
            pltpu.SemaphoreType.DMA((STEP_BUFFERS,)),
        ],
    )
    o, pool = pl.pallas_call(
        functools.partial(_step_kernel, heads=h, dk=dk, dv=dv, entries=entries),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((r, 1, h * dv), f32),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ],
        # operand 9 (4 prefetched scalars, then k q v a b pool)
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_LIMIT
        ),
        name="gated_delta_step",
        interpret=interpret,
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        jnp.clip(slots, 0, entries - 1).astype(jnp.int32),
        dst.astype(jnp.int32), positions.astype(jnp.int32),
        columns(k), columns(q), v.astype(f32)[:, None], by_lane(jnp.exp(g.astype(f32))),
        by_lane(beta), pool,
    )
    return o[:, 0], pool


# --------------------------------------------------------- the matrix form
def unit_lower_inverse(a, rows, cols, dot, n=None):
    """``(I + a)^-1`` for ``a`` strictly lower triangular of size ``n``, a
    power of two (or block diagonal in blocks of ``n``): forward
    substitution in blocks, from 2 doubling to ``n``. ``rows`` [.., 1] and
    ``cols`` [1, ..] are the indices, ``dot`` the product: the kernel and
    the float64 test share this text."""
    n = n or a.shape[0]
    eye = rows == cols
    t = eye - a * (rows // 2 == cols // 2)
    b = 2
    while b < n:
        below = (rows // b == cols // b + 1) & (rows // (2 * b) == cols // (2 * b))
        t = t - dot(dot(t, a * below), t)
        b *= 2
    return t


# ------------------------------------------------------------------ kernel
def _chunk_kernel(layer_ref, base_ref, lo_ref, hi_ref, load_ref,  # SMEM
                  ea_ref, ata_ref, eb_ref, atb_ref,
                  q_hbm, k_hbm, v_hbm, g_hbm, b_hbm, pool_in,
                  o_hbm, pool_out,
                  q_buf, k_buf, v_buf, g_buf, b_buf, o_buf, s_ref, snap_a,
                  snap_b, sem, *, heads, dk, dv, c, entries):
    """One grid step: one chunk of one span, every head. ``s_ref``
    [dk padded, H * dv] is the span's running state, kept from step to
    step; ``snap_a``/``snap_b`` the states the chunk's writes ask for."""
    # pool_in and pool_out are one buffer (input_output_aliases): a span
    # reads its entry before any chunk of the call writes it.
    f32 = jnp.float32
    i = pl.program_id(0)
    layer = layer_ref[0]
    lo, hi = lo_ref[i], hi_ref[i]
    c2, w2 = 2 * c, 2 * dv

    @pl.when(i == 0)
    def _():  # the rows past dk are never written again: zeros, not what VMEM held
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(hi > lo)
    def _():
        base = pl.multiple_of(base_ref[i], TILE)
        load = load_ref[i]
        rows_at = pl.ds(base, c)
        copies = [
            pltpu.make_async_copy(q_hbm.at[:, rows_at], q_buf, sem.at[0]),
            pltpu.make_async_copy(k_hbm.at[:, rows_at], k_buf, sem.at[1]),
            pltpu.make_async_copy(v_hbm.at[rows_at], v_buf, sem.at[2]),
            pltpu.make_async_copy(g_hbm.at[rows_at], g_buf, sem.at[3]),
            pltpu.make_async_copy(b_hbm.at[rows_at], b_buf, sem.at[4]),
        ]
        for dma in copies:
            dma.start()

        @pl.when(load == entries)  # a span's first chunk, at position 0
        def _():
            s_ref[...] = jnp.zeros_like(s_ref)

        @pl.when((load >= 0) & (load < entries))  # a span resumed from its entry
        def _():
            dma = pltpu.make_async_copy(
                pool_in.at[layer, load], s_ref.at[pl.ds(0, dk)], sem.at[5]
            )
            dma.start()
            dma.wait()

        for dma in copies:
            dma.wait()

        # Index planes of a pair of heads: rows 0..c-1 the first head's
        # tokens, c..2c-1 the second's; lanes 0..dv-1 the first head's
        # values, dv..2dv-1 the second's.
        row = jax.lax.broadcasted_iota(jnp.int32, (c2, 1), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, c2), 1)
        tok, half = row % c, row // c
        same = half == col // c
        eye = row == col
        incl = same & (row >= col)
        strict = same & (row > col)
        own = half == jax.lax.broadcasted_iota(jnp.int32, (1, w2), 1) // dv  # [c2, w2]
        first_lanes = jax.lax.broadcasted_iota(jnp.int32, (1, w2), 1) < dv
        in_chunk = (tok >= lo) & (tok < hi)  # [c2, 1]
        head_lane = jax.lax.broadcasted_iota(jnp.int32, g_buf.shape, 1)

        def dot(a, b, precision=STATE_PRECISION):
            return jnp.dot(a, b, preferred_element_type=f32, precision=precision)

        def dot_nt(a, b, precision=STATE_PRECISION):  # a b^T
            return jax.lax.dot_general(
                a, b, (((1,), (1,)), ((), ())), preferred_element_type=f32,
                precision=precision,
            )

        def dot_tn(a, b):  # a^T b
            return jax.lax.dot_general(
                a, b, (((0,), (0,)), ((), ())), preferred_element_type=f32,
                precision=STATE_PRECISION,
            )

        def as_row(x):  # [c2, 1] -> [1, c2]
            return jnp.sum(jnp.where(eye, x, 0.0), axis=0, keepdims=True)

        def of_heads(buf, p):  # [c, GW] -> [c2, 1]: heads 2p and 2p + 1
            return jnp.concatenate([
                jnp.sum(jnp.where(head_lane == 2 * p + j, buf, 0.0), axis=1, keepdims=True)
                for j in (0, 1)
            ], axis=0)

        def state_after(at, cum, k2, vp, s0):
            """The pair's state after the chunk's slot ``at`` (traced)."""
            cum_at = [
                jnp.sum(jnp.where((tok == at) & (half == j), cum, 0.0), keepdims=True)
                for j in (0, 1)
            ]  # [1, 1] each
            cum_at2 = jnp.where(half == 0, cum_at[0], cum_at[1])  # [c2, 1]
            kd = jnp.where(tok <= at, jnp.exp(jnp.minimum(cum_at2 - cum, 0.0)), 0.0) * k2
            carry = jnp.where(first_lanes, jnp.exp(cum_at[0]), jnp.exp(cum_at[1]))
            return carry * s0 + dot_tn(kd, vp)

        def pair(p, carry):
            lanes = pl.ds(pl.multiple_of(p * w2, LANE), w2)
            g2 = jnp.where(in_chunk, of_heads(g_buf[...], p), 0.0)
            b2 = jnp.where(in_chunk, of_heads(b_buf[...], p), 0.0)
            cum = jnp.sum(jnp.where(incl, as_row(g2), 0.0), axis=1, keepdims=True)
            decay = jnp.where(incl, jnp.exp(jnp.minimum(cum - as_row(cum), 0.0)), 0.0)
            q2 = q_buf[pl.ds(2 * p, 2)].reshape(c2, q_buf.shape[-1])
            k2 = k_buf[pl.ds(2 * p, 2)].reshape(c2, k_buf.shape[-1])
            vv = v_buf[:, lanes]
            v2 = jnp.where(own, jnp.concatenate([vv, vv], axis=0), 0.0)
            s0 = s_ref[:, lanes]

            a = jnp.where(strict, b2 * decay * dot_nt(k2, k2), 0.0)
            t = unit_lower_inverse(a, row, col, dot, c)
            u = dot(t, b2 * v2)
            w = dot(t, (b2 * jnp.exp(cum)) * k2)
            vp = u - jnp.where(own, dot(w, s0), 0.0)
            o2 = jnp.where(own, jnp.exp(cum) * dot(q2, s0, OUTPUT_PRECISION), 0.0) + dot(
                decay * dot_nt(q2, k2, OUTPUT_PRECISION), vp, OUTPUT_PRECISION
            )
            o_buf[:, lanes] = o2[:c] + o2[c:]
            # the slots after the chunk's last have g = 0: its end is slot c - 1
            s_ref[:, lanes] = state_after(c - 1, cum, k2, vp, s0)
            for e_ref, at_ref, snap in (
                (ea_ref, ata_ref, snap_a), (eb_ref, atb_ref, snap_b)
            ):
                @pl.when(e_ref[i] < entries)
                def _(at_ref=at_ref, snap=snap):
                    snap[:, lanes] = state_after(at_ref[i], cum, k2, vp, s0)

            return carry

        jax.lax.fori_loop(0, heads // 2, pair, 0)

        for t in range(c // TILE):  # the chunk's own slots of the output
            @pl.when((lo <= t * TILE) & (t * TILE < hi))
            def _(t=t):
                dma = pltpu.make_async_copy(
                    o_buf.at[pl.ds(t * TILE, TILE)],
                    o_hbm.at[pl.ds(base + t * TILE, TILE)], sem.at[5],
                )
                dma.start()
                dma.wait()

        for e_ref, snap in ((ea_ref, snap_a), (eb_ref, snap_b)):
            entry = e_ref[i]

            @pl.when(entry < entries)
            def _(entry=entry, snap=snap):
                dma = pltpu.make_async_copy(
                    snap.at[pl.ds(0, dk)], pool_out.at[layer, entry], sem.at[5]
                )
                dma.start()
                dma.wait()


def _chunk_tables(slots, row_of, positions, src, dst, c, chunks, entries):
    """What each chunk of the stream is and does with the pool, ``chunks``
    of them (the spare ones empty, ``lo == hi``): ``base`` the first of the
    ``c`` slots it reads, ``lo``/``hi`` its own among them; ``load`` (-1 go
    on from the chunk before, ``entries`` start from zeros, else the entry
    to start from); two writes ``(entry, slot)``: the state after that
    slot of the ``c`` goes to that entry, ``entries`` = none."""
    i32 = jnp.int32
    n = positions.shape[0]
    idx = jnp.arange(n, dtype=i32)
    real = positions >= 0
    prev = stream_predecessor(row_of, positions) & real
    first_of = jax.lax.cummax(jnp.where(real & ~prev, idx, -1))
    into = idx - first_of  # tokens into its span
    starts_chunk = real & (into % c == 0)
    runs_on = jnp.concatenate([prev[1:], jnp.zeros((1,), bool)])
    last_of = jax.lax.cummin(jnp.where(real & ~runs_on, idx, n), reverse=True)
    (start,) = jnp.nonzero(starts_chunk, size=chunks, fill_value=n)
    start = start.astype(i32)
    at = jnp.minimum(start, n - 1)
    length = jnp.where(start < n, jnp.minimum(c, last_of[at] + 1 - at), 0)
    base = jnp.minimum(at, n - c)
    lo = at - base
    load = jnp.where(
        (start < n) & (into[at] == 0),
        jnp.where(positions[at] == 0, entries, jnp.minimum(slots[row_of[at]], entries)),
        -1,
    ).astype(i32)
    # A write's chunk and the slot of it: one key a write, the two
    # smallest a chunk.
    chunk_of = jnp.cumsum(starts_chunk.astype(i32)) - 1
    mine = jnp.clip(chunk_of[src], 0, chunks - 1)
    none = entries * c
    key = jnp.where(
        (dst < entries) & real[src],
        jnp.minimum(dst, entries).astype(i32) * c + (src - start[mine] + lo[mine]),
        none,
    ).astype(i32)
    empty = jnp.full((chunks,), none, i32)
    key_a = empty.at[mine].min(key)
    key_b = empty.at[mine].min(jnp.where(key == key_a[mine], none, key))
    return (
        base.astype(i32), lo.astype(i32), (lo + length).astype(i32), load,
        key_a // c, key_a % c, key_b // c, key_b % c,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def gated_delta_kernel(q, k, v, g, beta, pool, layer, slots, row_of, positions,
                       src, dst, interpret: bool = False):
    """The stream's recurrence on the TPU (module docstring): arguments
    and results as :func:`gated_delta_ref`, the layout held to the
    contract above (spans aligned to ``TILE``, one a row)."""
    f32 = jnp.float32
    h, dk, dv = _heads(q, v, g)
    n0 = q.shape[0]
    entries = pool.shape[1]
    c = CHUNK
    if n0 < c:  # a stream shorter than a chunk: slots of padding after it
        grow = lambda x, fill=0: jnp.pad(  # noqa: E731
            x, ((0, c - n0),) + ((0, 0),) * (x.ndim - 1), constant_values=fill
        )
        q, k, v, g, beta, row_of = map(grow, (q, k, v, g, beta, row_of))
        positions = grow(positions, -1)
    n = q.shape[0]
    chunks = n // c + slots.shape[0]
    tables = _chunk_tables(slots, row_of, positions, src, dst, c, chunks, entries)
    live = (positions >= 0)[:, None]
    dkp = -(-dk // LANE) * LANE
    gw = -(-h // LANE) * LANE

    def by_head(x):  # [N, H * dk] -> [H, N, dk padded to whole lane groups]
        x = x.astype(f32).reshape(n, h, dk).transpose(1, 0, 2)
        return jnp.pad(x, ((0, 0), (0, 0), (0, dkp - dk)))

    def by_token(x):  # [N, H] -> [N, a lane group], 0 where the slot is padding
        return jnp.pad(jnp.where(live, x.astype(f32), 0.0), ((0, 0), (0, gw - h)))

    any_space = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=9,
        grid=(chunks,),
        in_specs=[any_space] * 6,
        out_specs=[any_space, any_space],
        scratch_shapes=[
            pltpu.VMEM((h, c, dkp), f32),  # q
            pltpu.VMEM((h, c, dkp), f32),  # k
            pltpu.VMEM((c, h * dv), f32),  # v
            pltpu.VMEM((c, gw), f32),  # g
            pltpu.VMEM((c, gw), f32),  # beta
            pltpu.VMEM((c, h * dv), f32),  # o
            pltpu.VMEM((dkp, h * dv), f32),  # the running state
            pltpu.VMEM((dkp, h * dv), f32),  # a write's
            pltpu.VMEM((dkp, h * dv), f32),  # the other write's
            pltpu.SemaphoreType.DMA((6,)),
        ],
    )
    o, pool = pl.pallas_call(
        functools.partial(
            _chunk_kernel, heads=h, dk=dk, dv=dv, c=c, entries=entries
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n, h * dv), f32),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ],
        # operand 14 (9 prefetched scalars, then q k v g beta pool)
        input_output_aliases={14: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_LIMIT
        ),
        name="gated_delta_chunk",
        interpret=interpret,
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32), *tables,
        by_head(q), by_head(k), v.astype(f32), by_token(g), by_token(beta), pool,
    )
    # slots no chunk owns were never written
    return jnp.where(live, o, 0.0)[:n0], pool
