"""The gated delta rule's four forms (``ops/gated_delta.py``): the Pallas
kernels in interpret mode (the chunked one over a stream, the in-place one
of a single token a row) and the plain one-step form against the token
recurrence, states included, and the chunk's matrix form against the
recurrence in float64.

TOLERANCES. The kernel computes the same float32 numbers in another order
(a chunk's products against a token's sums, a head's q and k brought to
length 1 in the kernel where the other forms take them from
``unit_heads``): ``KERNEL_TOL`` 2e-5 on outputs and states of order 1,
some ten times the rounding read (3e-6 in the outputs and 7e-6 in the
states over 2048 tokens) and far below any fault (a state started from the wrong
entry, a slot of another row let in, a write one slot off: 1e-2 and more).
In float64 the matrix form agrees with the recurrence to 1e-12.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_exp_tpu.ops import gated_delta as gd

KERNEL_TOL = 2e-5
H, DK, DV = 4, 16, 64  # two pairs of heads; 2 * DV fills a lane group, every head's q and k one


def _inputs(rng, n, h=H, dk=DK, dv=DV, beta_top=2.0, dtype=np.float32):
    """(qkv, g, beta): q and k as a convolution leaves them (no length in
    particular: lengths from a fifth to five), decays whose memory runs
    from a token to hundreds, steps up to ``beta_top``."""
    qk = rng.standard_normal((n, 2 * h, dk))
    qk *= np.exp(rng.uniform(np.log(0.2), np.log(5.0), (n, 2 * h, 1))) / np.linalg.norm(
        qk, axis=-1, keepdims=True
    )
    v = rng.standard_normal((n, h * dv))
    g = -np.exp(rng.uniform(np.log(1e-3), np.log(3.0), (n, h)))
    beta = beta_top * rng.uniform(0.0, 1.0, (n, h)) ** 0.5
    return [x.astype(dtype) for x in (np.concatenate([qk.reshape(n, -1), v], axis=1), g, beta)]


def _ready(qkv, g, beta, h=H, dk=DK):
    """(q, k, v, g, beta) as the forms that do not normalise take them."""
    return [*gd.unit_heads(qkv, h, dk), g, beta]


def _stream(spans, tail=(), pad_tiles=1):
    """(row_of, positions) of ``spans`` = (row, first position, tokens),
    each padded to the tile of 8, then ``tail`` = (row, position) slots one
    a row, then tiles of padding."""
    row_of, pos = [], []
    for r, p0, n in spans:
        pad = -n % gd.TILE
        row_of += [r] * (n + pad)
        pos += list(range(p0, p0 + n)) + [-1] * pad
    for r, p in tail:
        row_of, pos = row_of + [r], pos + [p]
    more = -len(pos) % gd.TILE + pad_tiles * gd.TILE
    row_of += [len(spans) + len(tail)] * more
    pos += [-1] * more
    return jnp.asarray(row_of, jnp.int32), jnp.asarray(pos, jnp.int32)


def _check(spans, src, dst, seed=0, layers=2, entries=8, beta_top=2.0, tail=(), zeros=()):
    """``zeros``: (slot, first column, columns) of ``qkv`` set to 0."""
    rng = np.random.default_rng(seed)
    row_of, pos = _stream(spans, tail)
    n = pos.shape[0]
    args = _inputs(rng, n, beta_top=beta_top)
    for slot, first, columns in zeros:
        args[0][slot, first:first + columns] = 0.0
    args = [jnp.asarray(x) for x in args]
    pool = jnp.asarray(rng.standard_normal((layers, entries, DK, H * DV)), jnp.float32)
    rows = len(spans) + len(tail)
    slots = jnp.asarray([r for r, *_ in spans] + [r for r, _ in tail] + [entries], jnp.int32)
    pad = [0] * (2 * (rows + 1) - len(src))
    io = (
        slots, row_of, pos, jnp.asarray(src + pad, jnp.int32),
        jnp.asarray(dst + [entries] * len(pad), jnp.int32),
    )
    o_ref, pool_ref = gd.gated_delta_ref(*_ready(*args), pool, 1, *io)
    o, out = gd.gated_delta_kernel(*args, pool, jnp.int32(1), *io, interpret=True)
    live = np.asarray(pos) >= 0
    assert np.abs(np.asarray(o) - np.asarray(o_ref))[live].max() < KERNEL_TOL
    assert np.abs(np.asarray(out) - np.asarray(pool_ref)).max() < KERNEL_TOL
    assert np.array_equal(np.asarray(out[0]), np.asarray(pool[0]))  # the other layer
    assert out.dtype == jnp.float32
    untouched = [e for e in range(entries) if e not in set(dst)]
    assert np.array_equal(np.asarray(out[1, untouched]), np.asarray(pool[1, untouched]))
    return float(np.abs(np.asarray(out) - np.asarray(pool_ref)).max())


# ------------------------------------------------------------------ kernel
@pytest.mark.parametrize(
    "tokens", [1, 7, 8, 15, 63, 64, 65, 72, 127, 128, 129, 191, 255, 256, 257]
)
@pytest.mark.parametrize("start", [0, 16])
def test_a_span_of_every_remainder(tokens, start):
    """One span of every length round the chunk's 128 (and its half) and
    the tile's 8, from position 0 (zeros) and resumed from its entry, its
    own write after its last token wherever in a chunk that falls."""
    _check([(0, start, tokens)], [tokens - 1], [0], seed=tokens)


@pytest.mark.parametrize("spans,src,dst,zeros", [
    # several rows a stream: chunks that begin where another row's end
    ([(0, 0, 100), (1, 40, 5), (2, 8, 64), (3, 0, 3)], [99, 108, 175, 178], [0, 1, 2, 3], ()),
    # both writes a row: its own entry and a snapshot at a block boundary,
    # 16 tokens into a chunk, at a chunk's end, and in the chunk of the last token
    ([(0, 16, 200)], [199, 15], [0, 5], ()),
    ([(0, 0, 200)], [199, 63], [0, 5], ()),
    ([(0, 32, 200), (1, 0, 70)], [199, 191, 200 + 69, 200 + 47], [0, 5, 1, 6], ()),
    # a row with no write at all, and a write to an entry past the pool
    ([(0, 0, 24), (1, 0, 24)], [23, 47], [0, 8], ()),
    # chunks of 128: a snapshot at the first chunk's end, and 16 tokens into the second
    ([(0, 0, 200)], [199, 127], [0, 5], ()),
    ([(0, 16, 300)], [299, 128 + 15], [0, 5], ()),
    # two spans that end mid-chunk (slots 299 and 304 + 129), the second's snapshot at its
    # first chunk's end, the first's mid-chunk; the second span's chunks begin mid-stream
    ([(0, 0, 300), (1, 16, 130)], [299, 128 + 15, 304 + 129, 304 + 127], [0, 5, 1, 6], ()),
    # q and k of no length (the 1e-6 under the root): a token's whole q, a token's whole k,
    # one head's k of another token, and q and k of a head at a span's first token
    ([(0, 0, 140), (1, 8, 20)], [139, 63, 144 + 19], [0, 5, 1],
     ((3, 0, H * DK), (70, H * DK, H * DK), (131, H * DK + DK, DK), (144, 2 * DK, DK),
      (144, H * DK + 2 * DK, DK))),
])
def test_rows_and_writes(spans, src, dst, zeros):
    _check(spans, src, dst, seed=len(spans), zeros=zeros)


def test_a_packed_tail_is_left_to_the_one_step_form():
    """Decode rows in the packed tail are marked padding for the kernel
    (``models/llama.py::_stream_recurrence``): it must not touch their
    entries, nor let their slots into a chunk that is read across them."""
    rng = np.random.default_rng(5)
    row_of, pos = _stream([(0, 0, 40)], tail=[(1, 9), (2, 30)])
    alone = np.asarray(row_of) > 0
    n = pos.shape[0]
    args = [jnp.asarray(x) for x in _inputs(rng, n)]
    pool = jnp.asarray(rng.standard_normal((1, 4, DK, H * DV)), jnp.float32)
    slots = jnp.asarray([0, 1, 2, 4], jnp.int32)
    src = jnp.asarray([39, 40, 41, 0, 0, 0, 0, 0], jnp.int32)
    dst = jnp.asarray([0, 1, 2, 4, 4, 4, 4, 4], jnp.int32)
    o, out = gd.gated_delta_kernel(
        *args, pool, jnp.int32(0), slots, row_of, jnp.where(alone, -1, pos), src,
        jnp.where(jnp.asarray(alone)[src], 4, dst), interpret=True,
    )
    ready = _ready(*args)
    o_ref, pool_ref = gd.gated_delta_ref(*ready, pool, 0, slots, row_of, pos, src, dst)
    assert np.abs(np.asarray(o) - np.asarray(o_ref))[:40].max() < KERNEL_TOL
    assert np.abs(np.asarray(out[0, 0]) - np.asarray(pool_ref[0, 0])).max() < KERNEL_TOL
    assert np.array_equal(np.asarray(out[0, 1:]), np.asarray(pool[0, 1:]))
    # and the one-step form gives those rows what the oracle does
    tail = slice(40, 42)
    o1, out1 = gd.gated_delta_step(
        *(a[tail] for a in ready), out, 0, slots[1:3], pos[tail], slots[1:3]
    )
    assert np.abs(np.asarray(o1) - np.asarray(o_ref)[tail]).max() < KERNEL_TOL
    assert np.abs(np.asarray(out1) - np.asarray(pool_ref)).max() < KERNEL_TOL


def test_steps_up_to_two_and_2048_tokens():
    """2048 tokens in one span with steps up to 2 (the negative
    eigenvalues): 32 chunks, the state handed on 31 times; the error does
    not grow with the length."""
    worst = _check([(0, 0, 2048)], [2047, 1023], [0, 3], seed=11)
    assert worst < 1e-5


def test_a_stream_shorter_than_a_chunk():
    _check([(0, 4, 9)], [8], [0], seed=3)  # 24 slots


def test_chunk_tables_name_every_chunk():
    row_of, pos = _stream([(0, 0, 70), (1, 16, 8)])
    n = pos.shape[0]
    base, lo, hi, load, ea, ata, eb, atb = (np.asarray(t) for t in gd._chunk_tables(
        jnp.asarray([0, 1, 9], jnp.int32), row_of, pos,
        jnp.asarray([69, 79, 15, 0, 0, 0], jnp.int32), jnp.asarray([0, 1, 5, 9, 9, 9], jnp.int32),
        64, n // 64 + 3, 9,
    ))
    assert n == 88
    # chunks: row 0's [0, 64) and [64, 70) (read as slots 24..87), row 1's [72, 80)
    assert list(base[:3]) == [0, 24, 24] and list(lo[:3]) == [0, 40, 48]
    assert list(hi[:3]) == [64, 46, 56] and all(hi[3:] == lo[3:])
    assert list(load[:3]) == [9, -1, 1]  # zeros / go on / entry 1
    assert (ea[0], ata[0], eb[0]) == (5, 15, 9)  # the snapshot, 16 tokens in
    assert (ea[1], ata[1]) == (0, 45) and (ea[2], ata[2]) == (1, 55)


# ---------------------------------------------------------------- one step
def test_step_matches_its_oracle():
    rng = np.random.default_rng(9)
    r = 4
    args = _ready(*(jnp.asarray(x) for x in _inputs(rng, r)))
    pool = jnp.asarray(rng.standard_normal((2, 7, DK, H * DV)), jnp.float32)
    pos = jnp.asarray([0, 5, 9, -1], jnp.int32)
    slots = jnp.asarray([0, 1, 2, 7], jnp.int32)
    o, out = gd.gated_delta_step(*args, pool, 0, slots, pos, slots)
    o_ref, pool_ref = gd.gated_delta_ref(
        *args, pool, 0, slots, jnp.arange(r), pos, jnp.arange(r), slots
    )
    assert np.abs(np.asarray(o) - np.asarray(o_ref))[:3].max() < KERNEL_TOL
    assert np.abs(np.asarray(out) - np.asarray(pool_ref)).max() < KERNEL_TOL


# ------------------------------------------------------ one step, in place
# (rows, layers, entries, layer, positions, slots, dst) of a case of the
# in-place kernel; an index of ``entries`` is past the pool.
_E = 8
STEP_CASES = {
    # position 0 starts from zeros whatever its entry holds
    "position_0": (3, 1, _E, 0, [0, 4, 0], [0, 1, 2], [0, 1, 2]),
    # padding rows between and after the live ones
    "padding": (6, 1, _E, 0, [-1, 3, -1, 7, -1, -1], [5, 1, 6, 2, _E, 0], [_E, 1, _E, 2, _E, _E]),
    # no write, and o all the same
    "dst_past_the_pool": (3, 1, _E, 0, [2, 0, 9], [0, 1, 2], [_E, _E, 2]),
    # a snapshot: the source entry stays, the new state lands elsewhere
    "snapshot": (3, 1, _E, 0, [5, 1, 6], [0, 1, 2], [4, 1, 7]),
    "one_row": (1, 1, _E, 0, [3], [6], [6]),
    "31_rows": (31, 1, 33, 0, list(range(31)), list(range(31)), list(range(31))),
    "64_rows_30_live": (
        64, 1, 33, 0, [i + 1 if i < 30 else -1 for i in range(64)],
        [i if i < 30 else 33 for i in range(64)], [i if i < 30 else 33 for i in range(64)],
    ),
    "layer_2": (4, 3, _E, 2, [1, 0, -1, 8], [3, 2, _E, 0], [3, 2, _E, 0]),
}


@pytest.mark.parametrize("case", list(STEP_CASES))
def test_step_kernel_matches_the_plain_step(case):
    """The in-place kernel (interpret) against the plain one-step form: the
    outputs of the live rows and THE WHOLE POOL within ``KERNEL_TOL``; what
    a case is about is asserted on the kernel's pool alone too."""
    r, layers, entries, layer, pos, slots, dst = STEP_CASES[case]
    rng = np.random.default_rng(r + layer)
    args = _ready(*(jnp.asarray(x) for x in _inputs(rng, r)))
    pool = jnp.asarray(rng.standard_normal((layers, entries, DK, H * DV)), jnp.float32)
    pos, slots, dst = (jnp.asarray(a, jnp.int32) for a in (pos, slots, dst))
    o, out = gd.gated_delta_step_kernel(
        *args, pool, jnp.int32(layer), slots, pos, dst, interpret=True
    )
    o_ref, pool_ref = gd.gated_delta_step(*args, pool, layer, slots, pos, dst)
    live = np.asarray(pos) >= 0
    assert out.dtype == jnp.float32 and o.shape == o_ref.shape
    assert np.abs(np.asarray(o) - np.asarray(o_ref))[live].max() < KERNEL_TOL
    assert np.abs(np.asarray(out) - np.asarray(pool_ref)).max() < KERNEL_TOL
    assert not np.asarray(o)[~live].any()  # a padding row's output is zeros
    written = {int(d) for d, p in zip(dst, pos) if p >= 0 and d < entries}
    for lyr in range(layers):
        kept = [e for e in range(entries) if lyr != layer or e not in written]
        assert np.array_equal(np.asarray(out[lyr, kept]), np.asarray(pool[lyr, kept]))
    assert all(np.abs(np.asarray(out[layer, e] - pool[layer, e])).max() > 1e-3 for e in written)


def test_step_kernel_reads_slots_and_writes_dst_row_by_row():
    """The contract: reads follow ``slots``, writes follow ``dst``, and the
    rows go one after another, so a call in which no row reads an entry
    that another row writes is the plain form's gather-then-scatter. A
    chain of snapshots (row i reads entry i and writes entry i + 8, which
    nothing reads) is such a call; every source stays what it was."""
    rng = np.random.default_rng(2)
    r = 8
    args = _ready(*(jnp.asarray(x) for x in _inputs(rng, r)))
    pool = jnp.asarray(rng.standard_normal((1, 16, DK, H * DV)), jnp.float32)
    pos = jnp.arange(1, r + 1, dtype=jnp.int32)
    slots = jnp.arange(r, dtype=jnp.int32)
    o, out = gd.gated_delta_step_kernel(*args, pool, jnp.int32(0), slots, pos, slots + 8, interpret=True)
    o_ref, pool_ref = gd.gated_delta_ref(
        *args, pool, 0, slots, jnp.arange(r), pos, jnp.arange(r), slots + 8
    )
    assert np.abs(np.asarray(o) - np.asarray(o_ref)).max() < KERNEL_TOL
    assert np.abs(np.asarray(out) - np.asarray(pool_ref)).max() < KERNEL_TOL
    assert np.array_equal(np.asarray(out[0, :8]), np.asarray(pool[0, :8]))


@pytest.mark.parametrize("form", ["packed_tail", "one_token"])
def test_forward_takes_the_step_kernel_where_the_stream_takes_the_chunked(form):
    """``models/llama.py`` through its forwards: a mixed stream whose
    packed tail holds decode rows (``forward_ragged`` with ``ScanForm(kernel
    =True, packed_from=16, interpret=True)`` behind ``attn_impl="pallas"``)
    and a decode step of one token a row (``forward``: ``one_token=True``),
    both against the oracle's forward (``attn_impl="xla"``): logits and
    both parts of the state pool."""
    import dataclasses

    from dynamo_exp_tpu.models import llama
    from dynamo_exp_tpu.models.config import TINY_OLMO_HYBRID

    # Jitted (tests/programs.py): both cases' histories are one compiled program.
    from .programs import forward, forward_ragged

    cfg = dataclasses.replace(TINY_OLMO_HYBRID, dtype="float32")
    one_token = form == "one_token"
    tail = None if one_token else 16
    assert llama._scan_form(cfg, "pallas", 1 if one_token else 8, tail, True, one_token) == (
        llama.ScanForm(True, tail, one_token, True)
    )
    params = llama.init_params(jax.random.PRNGKey(3), cfg)
    toks = [int(t) for t in np.random.default_rng(1).integers(2, 256, 40)]
    tables = jnp.stack([jnp.arange(1 + 4 * i, 5 + 4 * i) for i in range(4)]).astype(jnp.int32)
    k, v = llama.init_kv_cache(cfg, 17, 8, dtype=jnp.float32)
    st = llama.init_seq_state(cfg, 6)
    for r, n in ((1, 9), (2, 6)):  # rows 1 and 2 have tokens behind them, in 16 slots
        _, k, v, st = forward(
            params, cfg, jnp.asarray(toks[:n] + [0] * (16 - n))[None],
            jnp.asarray(list(range(n)) + [-1] * (16 - n), jnp.int32)[None],
            tables[r:r + 1], k, v, seq_state=st, state_slots=jnp.asarray([r]),
        )

    def run(impl):
        if one_token:  # rows 1, 2, a row at position 0 and a padding row
            return forward(
                params, cfg, jnp.asarray([[toks[9]], [toks[6]], [toks[0]], [0]]),
                jnp.asarray([[9], [6], [0], [-1]], jnp.int32), tables, k, v,
                seq_state=st, state_slots=jnp.asarray([1, 2, 3, 6]), attn_impl=impl,
                interpret=True,
            )
        # a span of 11 tokens (16 slots), then the tail: rows 1 and 2, padding
        i32 = lambda a: jnp.asarray(a, jnp.int32)  # noqa: E731
        return forward_ragged(
            params, cfg, i32(toks[20:31] + [0] * 5 + [toks[9], toks[6]] + [0] * 6),
            i32(list(range(11)) + [-1] * 5 + [9, 6] + [-1] * 6),
            i32([0] * 16 + [1, 2] + [3] * 6), tables, k, v, i32([10, 16, 17]),
            attn_impl=impl, q_tile=8, interpret=True, seq_state=st, packed_from=16,
            state_rows=(i32([0, 1, 2, 6]), i32([10, 16, 17, 0, 7, 0, 0, 0]),
                        i32([0, 1, 2, 6, 5, 6, 6, 6])),
        )

    got, want = run("pallas"), run("xla")
    assert np.abs(np.asarray(got[0]) - np.asarray(want[0])).max() < 1e-3
    for a, b in zip(jax.tree.leaves(got[3]), jax.tree.leaves(want[3])):
        assert np.abs(np.asarray(a) - np.asarray(b)).max() < 1e-4


# --------------------------------------------------------- the matrix form
def _recurrence64(q, k, v, g, beta, s0):
    s, out, after = s0.copy(), [], []
    for t in range(q.shape[0]):
        s = np.exp(g[t]) * s
        s = s + np.outer(k[t], beta[t] * (v[t] - s.T @ k[t]))
        out.append(s.T @ q[t])
        after.append(s.copy())
    return np.stack(out), np.stack(after)


def chunk_form(q, k, v, g, beta, s0, start=2):
    """One head's chunk in the matrix form (the module's docstring), in
    NumPy at the inputs' precision: ``q``, ``k`` [C, dk]; ``v`` [C, dv];
    ``g``, ``beta`` [C]; ``s0`` [dk, dv]. Returns (o [C, dv], the state
    after every token [C, dk, dv]). The algebra the kernel runs, its
    ``unit_lower_inverse`` included, the diagonal blocks of ``start`` by
    rows."""
    c = q.shape[0]
    rows, cols = np.arange(c)[:, None], np.arange(c)[None, :]
    cum = np.cumsum(g)
    decay = np.where(rows >= cols, np.exp(np.minimum(cum[:, None] - cum[None, :], 0)), 0)
    a = np.where(rows > cols, beta[:, None] * decay * (k @ k.T), 0)
    t = gd.unit_lower_inverse(a, rows, cols, np.matmul, start=start)
    u = t @ (beta[:, None] * v)
    w = t @ ((beta * np.exp(cum))[:, None] * k)
    vp = u - w @ s0
    o = np.exp(cum)[:, None] * (q @ s0) + (decay * (q @ k.T)) @ vp
    after = np.stack([
        np.exp(cum[m]) * s0
        + (np.where(np.arange(c) <= m, np.exp(np.minimum(cum[m] - cum, 0)), 0)[:, None] * k).T @ vp
        for m in range(c)
    ])
    return o, after


@pytest.mark.parametrize(
    "c, start", [(2, 2), (16, 2), (64, 2), (128, 2), (16, 4), (64, 8), (128, 8), (128, 16),
                 (128, 128)],
)
def test_matrix_form_is_the_recurrence_in_float64(c, start):
    """``chunk_form`` (the algebra the kernel runs, the triangular inverse
    included: by rows inside diagonal blocks of ``start``, the kernel's 8
    among them, and by blocks from there) against the token recurrence,
    both in float64: outputs and the state after every token."""
    rng = np.random.default_rng(c)
    qkv, g, beta = _inputs(rng, c, h=1, dk=12, dv=20, dtype=np.float64)
    q, k, v = qkv[:, :12], qkv[:, 12:24], qkv[:, 24:]
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) / 12**0.5
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    s0 = rng.standard_normal((12, 20))
    o, after = chunk_form(q, k, v, g[:, 0], beta[:, 0], s0, start)
    o_ref, after_ref = _recurrence64(q, k, v, g[:, 0], beta[:, 0], s0)
    assert np.abs(o - o_ref).max() < 1e-12
    assert np.abs(after - after_ref).max() < 1e-12


@pytest.mark.parametrize("c, start", [(64, 2), (128, 2), (128, 8), (128, 16)])
def test_block_inverse_holds_where_a_power_series_would_not(c, start):
    """Neighbouring keys that agree and steps of 2: ``A``'s entries reach
    2 and its powers 1e18, while the inverse stays of order 1. Forward
    substitution, by rows in the diagonal blocks of ``start`` and in blocks
    from there, has it to float64's rounding."""
    rows, cols = np.arange(c)[:, None], np.arange(c)[None, :]
    a = np.where(rows > cols, 1.99, 0.0)
    t = gd.unit_lower_inverse(a, rows, cols, np.matmul, start=start)
    assert np.abs(t @ (np.eye(c) + a) - np.eye(c)).max() < 1e-12
    assert np.abs(t).max() < 2.0


# ----------------------------------------------------------------- compile
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("slots", [4096, 64])
def test_kernel_compiles_for_v5e_at_the_published_widths(one_chip, slots):
    """Mosaic takes the kernel at Olmo-Hybrid-7B's widths (30 heads of 96
    by 192, the pool of 6 layers x 160 entries) for a 4096-slot mixed
    dispatch and a 64-slot one, and the pool is updated in place (aliased,
    no copy of 2.1 GB among the temporaries)."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    f, i = jnp.float32, jnp.int32
    n, h, dk, dv = slots, 30, 96, 192
    assert gd.supported(h, dk, dv, 8)
    pool = (6, 160, dk, h * dv)
    args = (
        sds((n, h * (2 * dk + dv)), f), sds((n, h), f), sds((n, h), f), sds(pool, f), sds((), i),
        sds((33,), i), sds((n,), i), sds((n,), i), sds((66,), i), sds((66,), i),
    )
    compiled = jax.jit(gd.gated_delta_kernel, donate_argnums=(3,)).lower(*args).compile()
    mem = compiled.memory_analysis()
    pool_bytes = int(np.prod(pool)) * 4
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 8
    # what the trace's readers find the call by: its name, and o first among its results
    (call,) = re.findall(r"= \((.*?)\) custom-call\(.*gated_delta_chunk", compiled.as_text())
    assert call.startswith(f"f32[{max(n, gd.CHUNK)},{h * dv}]") and f"f32[{','.join(map(str, pool))}]" in call


@pytest.mark.parametrize("rows, plain_temp_mb", [(32, 243), (64, 487)])
def test_step_kernel_compiles_for_v5e_at_the_published_widths(one_chip, rows, plain_temp_mb):
    """Mosaic takes the in-place step at Olmo-Hybrid-7B's widths for a
    window's 32 rows and a mixed dispatch's packed tail of 64: the pool
    aliased, and under a tenth of the temporaries the plain form compiles
    to there (243 and 487 MB: its gather's copy and four fusions' results;
    compile-only, PR 46)."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    f, i = jnp.float32, jnp.int32
    r, h, dk, dv = rows, 30, 96, 192
    pool = (6, 160, dk, h * dv)
    args = (
        sds((r, h * dk), f), sds((r, h * dk), f), sds((r, h * dv), f), sds((r, h), f),
        sds((r, h), f), sds(pool, f), sds((), i), sds((r,), i), sds((r,), i), sds((r,), i),
    )
    compiled = jax.jit(gd.gated_delta_step_kernel, donate_argnums=(5,)).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= int(np.prod(pool)) * 4
    assert mem.temp_size_in_bytes < plain_temp_mb * 1e6 / 10
    assert "gated_delta_step" in compiled.as_text()


@pytest.mark.parametrize(
    "heads, kv_heads, head_dim, block",
    [(30, 30, 128, 128), (32, 8, 128, 32), (32, 4, 128, 16), (20, 1, 128, 8)],
    ids=["olmo-hybrid", "qwen3", "sdar", "jamba"],
)
def test_attention_query_block_compiles_for_v5e(one_chip, heads, kv_heads, head_dim, block):
    """Mosaic takes the attention kernel's tiled call of a 4096-slot mixed
    dispatch at the query block its head layout gives: 128 slots at this
    model's 30 KV heads of one query head (more VMEM than Mosaic's default
    16 MiB: the call asks for what its shapes need), 32 and 16 at grouped
    heads, and one tile at 20 heads a group. The call keeps its name, which
    the trace's ``attn_kernel`` class reads."""
    from dynamo_exp_tpu.ops.ragged_attention import query_block, ragged_paged_attention

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    assert query_block(8, heads // kv_heads) == block
    n, ps, pages = 4096 - 64, 16, 2048
    pool = sds((pages, ps, kv_heads * head_dim), jnp.bfloat16)
    compiled = jax.jit(
        lambda q, k, v, t, r, p: ragged_paged_attention(
            q, k, v, t, r, p, num_kv_heads=kv_heads, q_tile=8, q_block=block
        )
    ).lower(
        sds((n, heads, head_dim), jnp.bfloat16), pool, pool,
        sds((33, 1024), jnp.int32), sds((n,), jnp.int32), sds((n,), jnp.int32),
    ).compile()
    assert "ragged_paged_attention" in compiled.as_text()


@pytest.mark.parametrize("block", [64, 128, 8])
def test_selected_attention_block_compiles_for_v5e(one_chip, block):
    """Mosaic takes the tiled call under a learned selection over
    grouped-query pools (``keep``) at Keye-VL-2.0's widths (32 query heads
    on 4 KV heads of 128, the index key's 128 lanes behind the keys in the K
    row, a slab of 2048 slots over tables of 16,384 tokens) at the selected
    call's own block of 64 slots (``selected_query_block``: the scores left
    in HBM, a chunk of them copied behind the chunk's pages), at the 128 the
    sweep found no faster, and at the one-tile form beside them; the call
    keeps its name."""
    from dynamo_exp_tpu.ops import sparse_index as si
    from dynamo_exp_tpu.ops.ragged_attention import (
        ragged_paged_attention, selected_query_block,
    )

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    heads, kv_heads, hd, n, ps, pmax = 32, 4, 128, 2048, 16, 1024
    assert selected_query_block(8, heads, hd, kv_heads) == 64
    f, i = jnp.float32, jnp.int32
    compiled = jax.jit(
        lambda q, k, v, t, r, p, s, tau, allow: ragged_paged_attention(
            q, k, v, t, r, p, num_kv_heads=kv_heads, q_tile=8, q_block=block,
            keep=(s, tau, allow),
        )
    ).lower(
        sds((n, heads, hd), jnp.bfloat16), sds((2048, ps, kv_heads * hd + 128), jnp.bfloat16),
        sds((2048, ps, kv_heads * hd), jnp.bfloat16), sds((33, pmax), i), sds((n,), i),
        sds((n,), i), sds(si.tiled_shape(n, 8, ps, pmax), f), sds((n,), f), sds((n,), f),
    ).compile()
    assert "ragged_paged_attention" in compiled.as_text()
