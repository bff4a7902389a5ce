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
  place by DMA. It alone takes ``q | k | v`` in one array as the mixer's
  convolution leaves it, q and k of no length in particular, and brings
  a head's rows to length 1 where it holds them; the other three take q
  and k ready (:func:`unit_heads`).
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

``T`` is forward substitution (:func:`unit_lower_inverse`): a row at a time
inside the diagonal blocks of ``INVERSE_START`` (8: the sixteen blocks of a
chunk side by side in one ``[8, 128]`` plane, a row's sum a shift by lanes
and a multiply, no product), then in blocks: the inverse of ``[[I + A11,
0], [A21, I + A22]]`` is ``[[T11, 0], [-T22 A21 T11, T22]]``, from blocks
of 8 doubling to ``C`` in four levels of two products (each over the rows
of every second block, the only ones a level changes), and no power series
(``A``'s entries reach 2 where neighbouring keys agree and its powers
cancel catastrophically). Everything that reaches ``V'`` or the state is
a float32 product at full precision (``STATE_PRECISION``, six bfloat16
passes of the MXU): one pass there costs the state what rounding it to
bfloat16 a chunk would, and three (the high and low halves by hand; Mosaic
lowers no ``Precision.HIGH``) leave the states 4e-5 from the recurrence's
where six leave them 4e-6 (interpreted, PR 59). The state after token
``m`` inside a chunk is the last line with ``G_m`` for ``G_C`` and the rows
after ``m`` left out, which is how a write that does not fall on a chunk's
end is honoured.

WHAT A CHUNK COSTS, in products of ``128 x 128 x 128`` a head of ``dk`` 96
by ``dv`` 192 (two lane groups hold its values; a product at full
precision is six passes, at the default one): ``K K^T`` 1, the inverse's
eight of 64 rows each (a level changes the rows of every second block and
no other) 4, ``T [b V | b e^G K]`` 2 + 1, ``W S_0`` 2, the state's ``dk``
rows 1.5 (and 1.5 more for each write the chunk holds), at full precision;
``Q S_0`` 2, ``Q K^T`` 1 and the output's 2 at the default: 74 passes for
128 tokens of a head. (Two heads a product, 128 rows of 64 tokens each by
their 384 lanes with head against head masked to zero, is 133 passes for
the same tokens, three quarters of the large products zeros.) Nothing but ``V'``, ``O`` and the
state reads ``S_0``, so a head's ``A``, ``T``, ``U`` and ``W`` do not wait
for the chunk before; a loop step takes a PAIR of heads, each its own
chain of products, so that one head's products fill the other's waits.

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
chunk's own slots of the output are written. A chunk's rows of the stream
``[N, H * (2 dk + dv)]`` (every head's q, then every head's k, then v, the
v part beginning a lane group: widths that do not give that are padded to
it before the call) come into VMEM whole, by one DMA that starts while the
chunk before computes; ``g`` and ``beta`` likewise, a lane a head. A
head's ``dk`` lanes of q or k begin anywhere in a lane group (head ``j``'s
at lane ``96 j``): the kernel loads the two groups they lie in, rotates
them to lane 0, masks the rest and scales the row by ``rsqrt(sum x^2 +
1e-6)``. A head's ``dv`` values and its columns of the state are read as
the ``dvp`` lanes of whole groups that hold them (256 for 192: the even
head of a pair from the pair's first lane, the odd one up to its last, the
64 lanes beside a head's own being the other's and dropped when the pair's
results are joined), so every product is 128 rows of ONE head and no lane
slice begins off a group. A whole chunk's output leaves in one copy that
the next step waits for; a span's last chunk's a tile at a time.

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
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .selective_scan import stream_predecessor

LANE = 128
TILE = 8  # slots a span is aligned to: the engine's ragged_q_tile
CHUNK = 128  # tokens a chunk: one head is 128 rows of every product
INVERSE_START = TILE  # the inverse's diagonal blocks by rows: a sublane tile of lanes
VMEM_LIMIT = 64 << 20
# Products that reach V' or the state, and the rest: a chunk's output
# beside what it hands on, which the mixer rounds to the model's dtype.
STATE_PRECISION = jax.lax.Precision.HIGHEST
OUTPUT_PRECISION = jax.lax.Precision.DEFAULT


def supported(heads: int, dk: int, dv: int, q_tile: int) -> bool:
    """Whether the kernel can take a stream of ``heads`` heads of ``dk`` by
    ``dv`` laid out in tiles of ``q_tile``: two heads' values fill whole
    lane groups, a head's keys whole sublanes and no more than one lane
    group, and the engine's spans are aligned to the kernel's tiles."""
    return (
        heads % 2 == 0 and (2 * dv) % LANE == 0 and dk % TILE == 0 and dk <= LANE
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
def unit_lower_inverse(a, rows, cols, dot, n=None, start=2, roll=np.roll):
    """``(I + a)^-1`` for ``a`` strictly lower triangular of size ``n``, a
    power of two (or block diagonal in blocks of ``n``): forward
    substitution, a row at a time inside the diagonal blocks of ``start``
    (elementwise, no product) and in blocks from there, doubling to ``n``.
    ``rows`` [.., 1] and ``cols`` [1, ..] are the indices, ``dot`` the
    product and ``roll`` the rotation along an axis: the kernel and the
    float64 test share this text."""
    return _in_step([inverse_steps(a, rows, cols, dot, n, start, roll)])[0]


def inverse_steps(a, rows, cols, dot, n=None, start=2, roll=np.roll):
    """:func:`unit_lower_inverse` as a generator that yields after every
    product and returns the inverse: the kernel runs two heads' in step."""
    size = a.shape[0]
    n = n or size
    eye = rows == cols

    def block(x, b):  # the block of ``b`` (a power of two) an index lies in: a shift, not a division
        return x >> (b.bit_length() - 1)

    if start == 2:
        t = eye - a * (block(rows, 2) == block(cols, 2))
    else:
        # The diagonal blocks side by side as ``a`` holds them, planes of
        # ``start`` rows (one sublane tile at 8): ``at[i, c]`` is ``a[first
        # of c's block + i, c]``, ``tt[i, c]`` that entry of the inverse. From
        # ``T (I + a) = I``, column ``c`` of a block is ``e_c - sum_{m>c}
        # a_mc column_m``, the block's last column first: the lanes of the
        # blocks' columns ``i`` take their block's column ``m`` from ``m - i``
        # lanes on.
        diagonal = ((block(rows, start) == block(cols, start)) * a.dtype.type(1)).reshape(
            size // start, start, size
        )
        at = (a.reshape(size // start, start, size) * diagonal).sum(axis=0)
        within = cols & (start - 1)
        tt = (rows[:start] == within) * a.dtype.type(1)
        for i in range(start - 2, -1, -1):
            acc = at[i + 1:i + 2] * roll(tt, size - 1, 1)
            for m in range(i + 2, start):
                acc = acc + at[m:m + 1] * roll(tt, size - (m - i), 1)
            tt = tt - acc * (within == i)
        t = (tt[None] * diagonal).reshape(size, size)
    second = (rows[:2] == 1).reshape(1, 2, 1, 1) * a.dtype.type(1)
    b = start
    while b < n:
        below = (block(rows, b) == block(cols, b) + 1) & (block(rows, 2 * b) == block(cols, 2 * b))
        # ``t - t (a * below) t`` changes the rows of every second block and
        # no other: those rows alone go through the two products, half of
        # each.
        pairs = size // (2 * b)
        part = dot(t.reshape(pairs, 2, b, size)[:, 1].reshape(size // 2, size), a * below)
        yield
        part = dot(part, t)
        yield
        t = t - (part.reshape(pairs, 1, b, size) * second).reshape(size, size)
        b *= 2
    return t


def _in_step(chains):
    """What each generator returns, all run to their ends a step each in
    turn."""
    done = {}
    while len(done) < len(chains):
        for j, chain in enumerate(chains):
            if j not in done:
                try:
                    next(chain)
                except StopIteration as end:
                    done[j] = end.value
    return [done[j] for j in range(len(chains))]


def unit_heads(qkv, heads, dk):
    """(q, k, v) of a stream as the mixer's convolution leaves it, ``qkv``
    [N, H * (2 dk + dv)]: ``q`` and ``k`` [N, H * dk] with each head's
    vector brought to length 1 (``q`` scaled by ``dk^-0.5`` besides), ``v``
    [N, H * dv], float32. What the chunk kernel does to a head's rows in
    VMEM, for the forms that take ``q`` and ``k`` ready."""
    f32 = jnp.float32
    n = qkv.shape[0]

    def unit(x):
        x = x.astype(f32).reshape(n, heads, dk)
        return (x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)).reshape(n, heads * dk)

    return (
        unit(qkv[:, : heads * dk]) * dk**-0.5, unit(qkv[:, heads * dk : 2 * heads * dk]),
        qkv[:, 2 * heads * dk :].astype(f32),
    )


# ------------------------------------------------------------------ kernel
def _dot(a, b, contract, precision):
    """Every product of the chunk kernel: ``a`` and ``b`` contracted over
    the axes ``contract`` names (``tools/gdn_chunk.py`` times the kernel
    with this replaced)."""
    return jax.lax.dot_general(
        a, b, ((contract[:1], contract[1:]), ((), ())),
        preferred_element_type=jnp.float32, precision=precision,
    )


def _chunk_kernel(layer_ref, base_ref, lo_ref, hi_ref, load_ref,  # SMEM
                  ea_ref, ata_ref, eb_ref, atb_ref,
                  x_hbm, g_hbm, b_hbm, pool_in,
                  o_hbm, pool_out,
                  x_buf, g_buf, b_buf, o_buf, s_ref, snap_a, snap_b,
                  in_sem, out_sem, sem, *, heads, dk, dv, c, entries):
    """One grid step: one chunk of one span, every head. ``s_ref``
    [dk padded, H * dv] is the span's running state, kept from step to
    step; ``snap_a``/``snap_b`` the states the chunk's writes ask for;
    ``x_buf``, ``g_buf``, ``b_buf`` and ``o_buf`` hold two chunks each, the
    next one's inputs arriving and the last one's output leaving while
    this one computes."""
    # pool_in and pool_out are one buffer (input_output_aliases): a span
    # reads its entry before any chunk of the call writes it.
    f32 = jnp.float32
    i = pl.program_id(0)
    steps = pl.num_programs(0)
    layer = layer_ref[0]
    lo, hi = lo_ref[i], hi_ref[i]
    w2 = 2 * dv
    dvp = -(-dv // LANE) * LANE  # a head's values in whole lane groups
    shared = 2 * dvp - w2  # lanes of the pair's middle group both windows hold
    v_at = x_buf.shape[-1] - heads * dv  # the first lane of v in a stream row

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
            for n, (hbm, buf) in enumerate(((x_hbm, x_buf), (g_hbm, g_buf), (b_hbm, b_buf)))
        ]

    def output(j):
        rows_at = pl.ds(pl.multiple_of(base_ref[at(j)], TILE), c)
        return pltpu.make_async_copy(o_buf.at[j % 2], o_hbm.at[rows_at], out_sem.at[j % 2])

    @pl.when(i == 0)
    def _():  # the rows past dk are never written again: zeros, not what VMEM held
        s_ref[...] = jnp.zeros_like(s_ref)

        @pl.when(live(0))
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
            dma = pltpu.make_async_copy(
                pool_in.at[layer, load], s_ref.at[pl.ds(0, dk)], sem.at[0]
            )
            dma.start()
            dma.wait()

        for dma in inputs(i):
            dma.wait()

        # Index planes of a head's chunk: a token a row, a token a column.
        row = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)
        eye = row == col
        incl = row >= col
        strict = row > col
        in_chunk = (row >= lo) & (row < hi)  # [c, 1]
        head_lane = jax.lax.broadcasted_iota(jnp.int32, g_buf.shape[1:], 1)
        key_lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANE), 1) < dk
        value_lane = jax.lax.broadcasted_iota(jnp.int32, (1, dvp), 1)

        def dot(a, b, precision=STATE_PRECISION):
            return _dot(a, b, (1, 0), precision)

        def dot_nt(a, b, precision=STATE_PRECISION):  # a b^T
            return _dot(a, b, (1, 1), precision)

        def as_row(x):  # [c, 1] -> [1, c]
            return jnp.sum(jnp.where(eye, x, 0.0), axis=0, keepdims=True)

        def of_head(buf, h):  # [c, GW] -> [c, 1]
            return jnp.sum(jnp.where(head_lane == h, buf[slot], 0.0), axis=1, keepdims=True)

        def unit(h):
            """Vector ``h`` of a stream row's ``2 H`` (the queries, then the
            keys) for every token of the chunk, brought to length 1: [c, a
            lane group], zeros past ``dk``. Its ``dk`` lanes begin anywhere
            in a lane group: the two groups they lie in, rotated."""
            group, lane = jax.lax.div(h * dk, LANE), jax.lax.rem(h * dk, LANE)
            two = x_buf[slot, :, pl.ds(pl.multiple_of(group * LANE, LANE), 2 * LANE)]
            x = pltpu.roll(two, jax.lax.rem(2 * LANE - lane, 2 * LANE), 1)[:, :LANE]
            x = jnp.where(key_lane, x, 0.0)
            return x * jax.lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + 1e-6)

        def join(x0, x1):  # two heads' [.., dvp] windows -> the pair's [.., 2 dv]
            alone = dvp - shared  # lanes of a window that only its head's window holds
            mine = jnp.where(value_lane[:, :shared] < dv - alone, x0[:, alone:], x1[:, :shared])
            parts = [x0[:, :alone], mine, x1[:, shared:]]
            return jnp.concatenate([x for x in parts if x.shape[1]], axis=1)

        def one_head(h, first):
            """Head ``h``, whose values are the lanes from ``first`` of the
            stream's: (its output [c, dvp], its states after the chunk and
            after each write's slot [dk, dvp]), all over the ``dvp`` lanes of
            whole groups that hold its ``dv`` (the lanes beside them are the
            pair's other head's, and :func:`join` drops them). A generator
            that yields after every product, so that a pair's two chains of
            products are laid down in step and one's fill the other's waits."""
            window = pl.ds(pl.multiple_of(first, LANE), dvp)
            g1 = jnp.where(in_chunk, of_head(g_buf, h), 0.0)
            b1 = jnp.where(in_chunk, of_head(b_buf, h), 0.0)
            cum = jnp.sum(jnp.where(incl, as_row(g1), 0.0), axis=1, keepdims=True)
            decay = jnp.where(incl, jnp.exp(jnp.minimum(cum - as_row(cum), 0.0)), 0.0)
            grown = jnp.exp(cum)  # e^G, [c, 1]
            q1 = unit(h) * dk**-0.5
            k1 = unit(heads + h)
            vv = x_buf[slot, :, pl.ds(pl.multiple_of(v_at + first, LANE), dvp)]
            s0 = s_ref[:, window]

            a = jnp.where(strict, b1 * decay * dot_nt(k1, k1), 0.0)
            yield
            t = yield from inverse_steps(a, row, col, dot, c, INVERSE_START, pltpu.roll)
            u = dot(t, b1 * vv)
            yield
            w = dot(t, (b1 * grown) * k1)
            yield
            vp = u - dot(w, s0)
            yield
            o1 = grown * dot(q1, s0, OUTPUT_PRECISION) + dot(
                decay * dot_nt(q1, k1, OUTPUT_PRECISION), vp, OUTPUT_PRECISION
            )

            def state_after(slot_at):
                """The head's state after the chunk's slot ``slot_at`` (traced)."""
                cum_at = jnp.sum(jnp.where(row == slot_at, cum, 0.0), keepdims=True)  # [1, 1]
                kd = jnp.where(row <= slot_at, jnp.exp(jnp.minimum(cum_at - cum, 0.0)), 0.0) * k1
                return jnp.exp(cum_at) * s0[:dk] + _dot(kd[:, :dk], vp, (0, 0), STATE_PRECISION)

            return o1, state_after

        def pair(p, carry):
            lanes = pl.ds(pl.multiple_of(p * w2, LANE), w2)
            (o_0, after_0), (o_1, after_1) = _in_step(
                [one_head(2 * p + j, p * w2 + j * (w2 - dvp)) for j in (0, 1)]
            )
            o_buf[slot, :, lanes] = join(o_0, o_1)
            # the slots after the chunk's last have g = 0: its end is slot c - 1
            s_ref[pl.ds(0, dk), lanes] = join(after_0(c - 1), after_1(c - 1))
            for e_ref, at_ref, snap in (
                (ea_ref, ata_ref, snap_a), (eb_ref, atb_ref, snap_b)
            ):
                @pl.when(e_ref[i] < entries)
                def _(at_ref=at_ref, snap=snap):
                    snap[pl.ds(0, dk), lanes] = join(after_0(at_ref[i]), after_1(at_ref[i]))

            return carry

        jax.lax.fori_loop(0, heads // 2, pair, 0)

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
                o_buf.at[i % 2, pl.ds(pl.multiple_of(t * TILE, TILE), TILE)],
                o_hbm.at[pl.ds(pl.multiple_of(base + t * TILE, TILE), TILE)], sem.at[0],
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
                dma = pltpu.make_async_copy(
                    snap.at[pl.ds(0, dk)], pool_out.at[layer, entry], sem.at[0]
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
def gated_delta_kernel(qkv, g, beta, pool, layer, slots, row_of, positions,
                       src, dst, interpret: bool = False):
    """The stream's recurrence on the TPU (module docstring): ``qkv``
    [N, H * (2 dk + dv)] float32 as the mixer's convolution leaves it (every
    head's q, then every head's k, then v; q and k NOT normalised: the
    kernel brings a head's rows to length 1 where it holds them, as
    :func:`unit_heads` does for the other forms), the rest and the results
    as :func:`gated_delta_ref`, the layout held to the contract above
    (spans aligned to ``TILE``, one a row)."""
    f32 = jnp.float32
    h = g.shape[-1]
    dk, dv = pool.shape[2], pool.shape[3] // h
    n0 = qkv.shape[0]
    entries = pool.shape[1]
    c = CHUNK
    qkv = qkv.astype(f32)
    spare = -(2 * h * dk) % LANE
    if spare:  # v begins a lane group: widths that do not give that are padded to it
        qkv = jnp.concatenate(
            [qkv[:, : 2 * h * dk], jnp.zeros((n0, spare), f32), qkv[:, 2 * h * dk :]], axis=1
        )
    if n0 < c:  # a stream shorter than a chunk: slots of padding after it
        grow = lambda x, fill=0: jnp.pad(  # noqa: E731
            x, ((0, c - n0),) + ((0, 0),) * (x.ndim - 1), constant_values=fill
        )
        qkv, g, beta, row_of = map(grow, (qkv, g, beta, row_of))
        positions = grow(positions, -1)
    n, width = qkv.shape
    chunks = n // c + slots.shape[0]
    tables = _chunk_tables(slots, row_of, positions, src, dst, c, chunks, entries)
    live = (positions >= 0)[:, None]
    dkp = -(-dk // LANE) * LANE
    gw = -(-h // LANE) * LANE

    def by_token(x):  # [N, H] -> [N, a lane group], 0 where the slot is padding
        return jnp.pad(jnp.where(live, x.astype(f32), 0.0), ((0, 0), (0, gw - h)))

    any_space = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=9,
        grid=(chunks,),
        in_specs=[any_space] * 4,
        out_specs=[any_space, any_space],
        scratch_shapes=[
            pltpu.VMEM((2, c, width), f32),  # q | k | v, this chunk's and the next's
            pltpu.VMEM((2, c, gw), f32),  # g
            pltpu.VMEM((2, c, gw), f32),  # beta
            pltpu.VMEM((2, c, h * dv), f32),  # o, this chunk's and the last's
            pltpu.VMEM((dkp, h * dv), f32),  # the running state
            pltpu.VMEM((dkp, h * dv), f32),  # a write's
            pltpu.VMEM((dkp, h * dv), f32),  # the other write's
            pltpu.SemaphoreType.DMA((2, 3)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((1,)),
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
        # operand 12 (9 prefetched scalars, then qkv g beta pool)
        input_output_aliases={12: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_LIMIT
        ),
        name="gated_delta_chunk",
        interpret=interpret,
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32), *tables,
        qkv, by_token(g), by_token(beta), pool,
    )
    # slots no chunk owns were never written
    return jnp.where(live, o, 0.0)[:n0], pool
