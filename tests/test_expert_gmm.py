"""The routed experts' matmuls read their layer's weights where they lie
(PERF.md, PR 28): ``ops/expert_gmm.py`` takes the stacked ``[L, E, K, N]``
arrays and a layer index, ``ops/moe.py::moe_ffn(layer=l)`` is the one
expert path with two ways in (a stack and a layer, or one layer's
arrays), and the paged layer scan closes over the stacks instead of
slicing them.

On the CPU the kernel runs in interpret mode; the program's own CPU path
is ``ragged_dot`` on the slice, so what these hold is the arithmetic, the
addressing and the structure. That no copy of a matrix is left is read
off the chip's trace (``expert_slab_copy_time_share``) and, here, off
what XLA:TPU compiles for a described ``v5e:2x2`` (the last tests)."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from dynamo_exp_tpu.models import TINY, TINY_MOE, init_kv_cache, init_params
from dynamo_exp_tpu.models import llama
from dynamo_exp_tpu.ops import expert_gmm, moe

L, E, TOPK = 3, 8, 2
ROUTINGS = ("even", "skewed", "empty")


def _weights(key, d, width, dtype=jnp.bfloat16, layers=L, experts=E):
    ks = jax.random.split(key, 4)

    def init(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) * fan_in**-0.5).astype(dtype)

    return (
        init(ks[0], (layers, d, experts), d),
        init(ks[1], (layers, experts, d, width), d),
        init(ks[2], (layers, experts, d, width), d),
        init(ks[3], (layers, experts, width, d), width),
    )


def _sizes(routing: str, rows: int) -> np.ndarray:
    """Group sizes of ``rows`` sorted rows under a routing."""
    rng = np.random.default_rng(rows)
    p = {
        "even": np.ones(E), "skewed": np.asarray([1, 40, 1, 1, 1, 1, 1, 1.0]),
        "empty": np.asarray([0, 1, 1, 0, 1, 1, 1, 0.0]),
    }[routing]
    return np.bincount(rng.choice(E, rows, p=p / p.sum()), minlength=E).astype(np.int32)


# ------------------------------------------------------------ (a) one path
@pytest.mark.parametrize("rows", [8, 64, 512])
@pytest.mark.parametrize("routing", ROUTINGS)
@pytest.mark.parametrize("with_live", [False, True])
def test_a_layer_of_the_stack_is_that_layers_slice(rows, routing, with_live):
    """``moe_ffn(layer=l)`` on the stacked weights equals ``moe_ffn`` on
    layer ``l``'s slice, for every ``l``: bit for bit, since off the TPU
    both are ``ragged_dot`` on the same matrix."""
    d, width = 32, 48
    router, wg, wu, wd = _weights(jax.random.PRNGKey(rows), d, width, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (rows, d), jnp.float32)
    if routing != "even":
        # Rows of positive numbers: a router column of one constant gives every
        # row a logit of that sign, so expert 1 draws most rows ("skewed") or
        # experts 0, 3 and 7 draw none ("empty").
        x = jnp.abs(x)
        cols, value = ([1], 4.0 / d) if routing == "skewed" else ([0, 3, 7], -50.0 / d)
        router = router.at[:, :, jnp.asarray(cols)].set(value)
    live = None
    if with_live:
        live = jnp.asarray(np.random.default_rng(0).random(rows) < 0.6)
    for l in range(L):
        whole = moe.moe_ffn(
            x, router[l], wg, wu, wd, TOPK, True, live=live, layer=jnp.int32(l)
        )
        sliced = moe.moe_ffn(x, router[l], wg[l], wu[l], wd[l], TOPK, True, live=live)
        assert np.array_equal(np.asarray(whole), np.asarray(sliced))
        if routing == "empty":
            _w, ids = moe.moe_router(x, router[l], TOPK)
            assert not np.isin(np.asarray(ids), [0, 3, 7]).any()
    want = moe.moe_ffn_reference(x, router[1], wg[1], wu[1], wd[1], TOPK, True)
    got = moe.moe_ffn(x, router[1], wg, wu, wd, TOPK, True, layer=jnp.int32(1))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ------------------------------------------------------------ (b) the kernel
@pytest.mark.parametrize("rows", [8, 64, 512])
@pytest.mark.parametrize("routing", ROUTINGS)
@pytest.mark.parametrize("live_share", [1.0, 0.6])
def test_the_kernel_is_ragged_dot_within_bfloat16_rounding(rows, routing, live_share):
    """``expert_gmm`` in interpret mode on layer ``l`` of a stack against
    ``ragged_dot`` on that layer: float32 accumulation both, so they
    differ by the order of the partial sums, under a bfloat16 step.
    ``live_share`` < 1 leaves rows past the last group, as ``moe_ffn``'s
    padding rows are."""
    k, n = 256, 384
    key = jax.random.PRNGKey(rows)
    lhs = jax.random.normal(key, (rows, k), jnp.float32).astype(jnp.bfloat16)
    w = _weights(jax.random.fold_in(key, 1), k, n)[1]
    sizes = jnp.asarray(_sizes(routing, int(rows * live_share)))
    n_live = int(sizes.sum())
    for l in (0, L - 1):
        got = expert_gmm.expert_gmm(lhs, w, sizes, l, interpret=True)
        want = jax.lax.ragged_dot(lhs, w[l], sizes)
        assert got.shape == want.shape and got.dtype == want.dtype
        g, wv = (np.asarray(a.astype(jnp.float32))[:n_live] for a in (got, want))
        assert np.isfinite(g).all()
        # one bfloat16 step of the largest value there is
        assert np.abs(g - wv).max() <= np.abs(wv).max() * 2.0**-7
    per_layer = expert_gmm.expert_gmm(lhs, w[1], sizes, interpret=True)
    stacked = expert_gmm.expert_gmm(lhs, w, sizes, 1, interpret=True)
    assert np.array_equal(
        np.asarray(per_layer.astype(jnp.float32))[:n_live],
        np.asarray(stacked.astype(jnp.float32))[:n_live],
    )


@pytest.mark.parametrize("experts", [8, 32])
@pytest.mark.parametrize("sorted_rows", [257, 1024, 4096])
def test_a_dispatch_of_many_rows_through_the_kernel_is_ragged_dot_on_the_slice(
    sorted_rows, experts
):
    """``moe_ffn`` at a prompt chunk's row counts (tokens x experts a
    token: more than a decode window's 256, which until PR 60 took
    ``ragged_dot``), stacked weights and a TRACED ``layer``, some rows
    padding (``live``): the kernel (interpreted) against ``ragged_dot`` on
    that layer's slice, which is what the same call computes off the TPU."""
    d, width, top_k = 128, 128, 1 if sorted_rows % 2 else 2
    tokens = sorted_rows // top_k
    router, wg, wu, wd = _weights(jax.random.PRNGKey(experts), d, width, experts=experts)
    x = jax.random.normal(jax.random.PRNGKey(tokens), (tokens, d), jnp.float32)
    x = x.astype(jnp.bfloat16)
    live = jnp.arange(tokens) % 5 != 0

    @functools.partial(jax.jit, static_argnames="interpret")
    def run(x, router, wg, wu, wd, live, layer, interpret):
        return moe.moe_ffn(
            x, router[layer], wg, wu, wd, top_k, True, live=live, layer=layer,
            interpret=interpret,
        )

    args = (x, router, wg, wu, wd, live, jnp.int32(L - 1))
    text = str(jax.make_jaxpr(functools.partial(run, interpret=True))(*args))
    assert "expert_gmm" in text and "ragged_dot" not in text
    got, want = (
        np.asarray(run(*args, interpret=i).astype(jnp.float32)) for i in (True, False)
    )
    assert np.isfinite(got).all() and not got[~np.asarray(live)].any()
    # a bfloat16 step of the largest value, for each of a token's picks
    assert np.abs(got - want).max() <= np.abs(want).max() * 2.0**-7 * top_k


@pytest.mark.parametrize("rows,to", [(64, 5), (512, 5), (512, 100)])
def test_a_rows_bits_do_not_turn_with_its_place_among_the_sorted_rows(rows, to):
    """The same rows of one group, placed at another offset among other
    neighbours and other group sizes: bit-equal results (what kept
    ``repeatable`` true, PERF.md PR 27). Moved to row 100 of the one block
    of 512 the group lies across the block's first sub-block boundary,
    where it lay inside a sub-block."""
    k, n, take = 256, 384, rows // E
    key = jax.random.PRNGKey(7)
    lhs = jax.random.normal(key, (rows, k), jnp.float32).astype(jnp.bfloat16)
    w = _weights(jax.random.fold_in(key, 1), k, n)[1]
    even = jnp.full((E,), rows // E, jnp.int32)
    at = 3 * (rows // E)  # group 3's first row
    a = expert_gmm.expert_gmm(lhs, w, even, 2, interpret=True)
    # group 3's first `take` rows moved to rows to..to+take, a different row block
    # boundary and neighbours; groups 0-2 hold the rows before them.
    moved = jnp.concatenate([lhs[:to], lhs[at:at + take], lhs[to:at], lhs[at + take:]])
    sizes = jnp.asarray([2, to - 3, 1, take, 0, 0, 0, rows - to - take], jnp.int32)
    b = expert_gmm.expert_gmm(moved, w, sizes, 2, interpret=True)
    assert np.array_equal(
        np.asarray(a[at:at + take].astype(jnp.float32)),
        np.asarray(b[to:to + take].astype(jnp.float32)),
    )


def _whole_block_products(lhs, w, sizes, tk, tm=256):
    """The kernel's arithmetic before PR 64: every (group, block of ``tm``
    rows) pair that holds a row multiplies the WHOLE block, float32 partial
    sums added a ``tk`` slice of ``K`` at a time from zero, and keeps the
    group's rows; rows of no group are zeros."""
    out = jnp.zeros((lhs.shape[0], w.shape[-1]), lhs.dtype)
    ends = np.cumsum(sizes)
    for g, (lo, hi) in enumerate(zip(ends - sizes, ends)):
        for b in range(lo // tm, -(-hi // tm) if hi > lo else 0):
            rows = slice(b * tm, (b + 1) * tm)
            acc = jnp.zeros((lhs[rows].shape[0], w.shape[-1]), jnp.float32)
            for k in range(0, lhs.shape[1], tk):
                acc += jnp.dot(
                    lhs[rows, k:k + tk], w[g, k:k + tk],
                    preferred_element_type=jnp.float32,
                )
            mine = slice(max(lo, b * tm), min(hi, (b + 1) * tm))
            out = out.at[mine].set(acc[mine.start - b * tm:mine.stop - b * tm].astype(out.dtype))
    return out


# Group sizes that put a group inside one sub-block of 128 rows, across a
# sub-block boundary, across a block boundary (at a forced block of 256),
# empty, a whole sub-block and more, and that leave rows past the last group.
GROUPINGS = {
    "inside": [9, 40, 0, 30, 7, 0, 21, 13],
    "across-sub-blocks": [100, 60, 0, 5, 200, 0, 3, 90],
    "across-blocks": [250, 12, 300, 0, 0, 131, 1, 128],
    "aligned": [128, 128, 256, 0, 0, 128, 0, 0],
    "one-group": [0, 0, 0, 0, 0, 0, 0, 1000],
}


@pytest.mark.parametrize("rows,tm", [(244, None), (1000, None), (1000, 256), (1024, 384)])
@pytest.mark.parametrize("grouping", GROUPINGS)
def test_a_tile_that_multiplies_its_groups_sub_blocks_gives_the_whole_blocks_bits(
    grouping, rows, tm
):
    """Bit for bit what a whole-block product a tile gave (the kernel until
    PR 64), at a decode window's row count (one block) and at several
    blocks (a forced block of 256 rows, the last ragged; 384, whose third
    sub-block ends a block): a row is computed from that row alone and its
    partial sums are added in ``tk`` order, whatever rows are multiplied
    beside it. Rows past the last group are zeros as far as the last
    sub-block a tile touched."""
    k, n = 768, 256  # two k slices of 384
    sizes = np.asarray(GROUPINGS[grouping])
    if sizes.sum() > rows:  # a window's rows: the same pattern, fewer rows
        sizes = sizes * rows // (sizes.sum() + 12)
    key = jax.random.PRNGKey(rows)
    lhs = jax.random.normal(key, (rows, k), jnp.float32).astype(jnp.bfloat16)
    w = _weights(jax.random.fold_in(key, 1), k, n)[1]
    got = expert_gmm.expert_gmm(lhs, w, jnp.asarray(sizes, jnp.int32), 1, interpret=True, tm=tm)
    want = _whole_block_products(lhs, w[1], sizes, expert_gmm.tiles(rows, k, n, 2)[1])
    assert got.shape == want.shape and got.dtype == want.dtype
    live = int(sizes.sum())
    defined = min(rows, -(-live // expert_gmm.SUB) * expert_gmm.SUB)
    assert live and np.array_equal(
        np.asarray(got[:defined]).view(np.uint16), np.asarray(want[:defined]).view(np.uint16)
    )


def test_a_calls_matrix_fetches_and_product_rows_are_counted_on_the_host():
    count = expert_gmm.fetches_and_product_rows
    # One group inside a sub-block; one across a sub-block boundary; one
    # across a block boundary (two fetches); an empty one (none).
    assert count([100], 256) == (1, 128)
    assert count([100, 100], 256) == (2, 128 + 256)
    assert count([100, 100, 100], 256) == (4, 128 + 256 + 128 + 128)
    assert count([0, 300, 0], 256) == (2, 256 + 128)
    assert count([0, 300, 0], 1024) == (1, 384)
    assert count([0, 0], 256) == (0, 0)
    # a block shorter than a sub-block is one product of its own length
    assert count([3, 0, 9], 48) == (2, 96)
    # LFM2's decode window: 244 sorted rows over 32 experts in one block of
    # 256. Every expert's matrix is fetched once either way; a whole block
    # a tile (the rule before PR 64) multiplies 256 rows for each.
    sizes = np.bincount(np.random.default_rng(0).integers(0, 32, 244), minlength=32)
    assert (sizes > 0).all()
    fetches, product_rows = count(sizes, 256)
    assert fetches == 32 and product_rows <= 34 * expert_gmm.SUB
    assert count(sizes, 256, sub=256) == (32, 32 * 256)
    # 32 groups of 100 rows: no multiple of 128 is a group's first row, so
    # 24 groups lie across one (two sub-blocks) and 8 inside a sub-block,
    # whatever the block; a block boundary (12 at 256 rows, 3 at 1,024)
    # fetches the group across it twice.
    sizes = np.full(32, 100)
    assert count(sizes, 256) == (32 + 12, 56 * 128)
    assert count(sizes, 1024) == (32 + 3, 56 * 128)
    assert count(sizes, 256, sub=256) == (32 + 12, 44 * 256)


def test_tiling_is_a_function_of_shapes_and_k_alone_splits_the_sum():
    assert expert_gmm.supported(4096, 3584) and expert_gmm.supported(128, 128)
    assert not expert_gmm.supported(64, 96) and not expert_gmm.supported(4096, 100)
    for m in (16, 128, 2048, 16384):
        tm, tk, tn = expert_gmm.tiles(m, 4096, 3584, 2)
        # one block where VMEM holds the rows, blocks of 768 at half the columns
        assert (tm, tk, tn) == ((m, 512, 3584) if m <= 128 else (768, 512, 1792))
        assert expert_gmm.tiles(m, 3584, 4096, 2)[1:] == (512, 4096 if m <= 128 else 2048)
    # 768 = 6 x 128: the largest divisor under the cap; odd row counts round up.
    assert expert_gmm.tiles(40, 768, 2048, 2) == (48, 384, 2048)
    # a wide unsharded matrix is cut to what VMEM holds
    tm, tk, tn = expert_gmm.tiles(512, 4096, 14336, 2)
    assert 14336 % tn == 0 and tn < 14336
    blocks = 2 * tm * 4096 * 2 + 2 * tk * tn * 2 + 2 * tm * tn * 2 + 4 * tm * tn
    assert blocks <= expert_gmm.VMEM_BLOCK_BUDGET


# --------------------------------------------------------- (c) structure
MOE_128 = dataclasses.replace(  # widths the kernel can tile: 128 lanes
    TINY_MOE, hidden_size=128, intermediate_size=128, num_heads=4, num_kv_heads=2,
)
# A stream of more sorted rows (tokens x experts a token) than a decode
# window has had (256, one row block until PR 64): a prompt chunk's.
WINDOW_ROWS = 2 * expert_gmm.SUB
MANY_TOKENS = WINDOW_ROWS // MOE_128.num_experts_per_tok + 1


def _layer_scan(jaxpr):
    """The scan over layers in ``forward_ragged``'s jaxpr: (the avals of
    its scanned inputs, the avals of what it closes over)."""
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    (scan,) = [e for e in scans if e.params["length"] in (TINY.num_layers, TINY_MOE.num_layers)]
    nc, nk = scan.params["num_consts"], scan.params["num_carry"]
    avals = [v.aval for v in scan.invars]
    return avals[nc + nk:], avals[:nc]


def _ragged_jaxpr(cfg, n=16, **kw):
    params = init_params(jax.random.PRNGKey(0), cfg)
    k, v = init_kv_cache(cfg, 8, 8)
    args = (
        jnp.zeros(n, jnp.int32), jnp.arange(n, dtype=jnp.int32), jnp.zeros(n, jnp.int32),
        jnp.zeros((2, 4), jnp.int32), k, v, jnp.asarray([n - 1], jnp.int32),
    )
    return jax.make_jaxpr(lambda p, *a: llama.forward_ragged(p, cfg, *a, **kw))(params, *args).jaxpr, params


@pytest.mark.parametrize("n", [16, MANY_TOKENS], ids=["few-rows", "many-rows"])
def test_no_expert_matrix_rides_the_layer_scans_xs(n):
    jaxpr, params = _ragged_jaxpr(MOE_128, n, interpret=True)
    text = str(jaxpr)
    assert "expert_gmm" in text and "ragged_dot" not in text
    xs, consts = _layer_scan(jaxpr)
    stacks = {params["layers"][k].shape for k in llama.EXPERT_STACKS}
    assert all(len(s) == 4 for s in stacks)
    assert not [a.shape for a in xs if a.shape in stacks]
    # closed over whole, once each (w_gate and w_up share a shape)
    assert sorted(a.shape for a in consts if a.shape in stacks) == sorted(
        params["layers"][k].shape for k in llama.EXPERT_STACKS
    )
    # the router and everything else of a layer still ride xs
    assert params["layers"]["router"].shape in [a.shape for a in xs]
    assert params["layers"]["wq"].shape in [a.shape for a in xs]


@pytest.mark.parametrize("interpret", [False, True], ids=["no-tpu", "widths-untiled"])
def test_a_forward_that_takes_ragged_dot_scans_its_experts_as_it_did(interpret):
    """Where the matmuls take ``ragged_dot`` on the slice, the expert
    matrices ride ``xs`` with the rest of the layer, as before PR 28."""
    cfg = TINY_MOE  # 64 x 96: widths the kernel cannot tile
    assert llama.routed_experts_form(cfg, None, interpret) == "sliced"
    jaxpr, params = _ragged_jaxpr(cfg, interpret=interpret)
    xs, consts = _layer_scan(jaxpr)
    stacks = [params["layers"][k].shape for k in llama.EXPERT_STACKS]
    assert sorted(a.shape for a in xs if a.shape in stacks) == sorted(stacks)
    assert not [a.shape for a in consts if a.shape in stacks]
    text = str(jaxpr)
    assert "ragged_dot" in text and "expert_gmm" not in text


@pytest.mark.parametrize(
    "hidden,width,tp,want",
    [
        (4096, 14336, 1, True),  # Mixtral's
        (4096, 14336, 4, True),  # a chip's 3584 columns of them
        (2048, 1792, 1, True),  # LFM2's
        (2048, 768, 1, True),  # SDAR's
        (64, 96, 1, False),  # no lane dimension of a block
        (128, 384, 2, False),  # tiled whole, not a chip's 192 columns
        (128, 384, 4, False),  # 96 a chip
        (128, 640, 4, False),  # 160 a chip
    ],
)
def test_the_kernel_is_taken_by_place_and_widths_never_by_rows(hidden, width, tp, want):
    """``experts_in_place`` looks at where the mesh sits (or ``interpret``)
    and at a chip's share of the widths: it is not told a call's rows, so a
    prompt chunk and a decode window read their experts the same way."""
    mesh = None
    if tp > 1:
        mesh = Mesh(np.array(jax.devices()[:tp]).reshape(1, 1, 1, tp), ("dp", "sp", "ep", "tp"))
    assert moe.experts_in_place(hidden, width, mesh, interpret=True) is want
    assert not moe.experts_in_place(hidden, width, mesh)  # no TPU here


def test_a_dense_models_scan_is_what_it_was():
    jaxpr, params = _ragged_jaxpr(TINY)
    xs, consts = _layer_scan(jaxpr)
    want = sorted(v.shape for v in params["layers"].values())
    per_layer = sorted(a.shape for a in xs if len(a.shape) >= 2 and a.shape[0] == TINY.num_layers)
    # every stacked parameter is a scanned input (beside the rope table [L, hd/2])
    for shape in want:
        assert shape in per_layer
    assert not [a.shape for a in consts if a.shape in want]


def test_the_forward_with_the_kernel_reads_the_stack_and_agrees_with_the_slice():
    """``forward_ragged`` with ``interpret`` (the kernel, in place) against
    without (``ragged_dot`` on the slice): the kernel's call takes the
    4-D stacks, and the logits agree within bfloat16 rounding."""
    cfg = MOE_128
    assert llama.routed_experts_form(cfg, None, interpret=True) == "inplace"
    assert llama.routed_experts_form(cfg, None) == "sliced"  # no TPU here
    assert llama.routed_experts_form(TINY_MOE, None, interpret=True) == "sliced"  # 64 x 96
    params = init_params(jax.random.PRNGKey(0), cfg)
    k, v = init_kv_cache(cfg, 8, 8)
    n = 16
    tokens = jnp.arange(3, 3 + n, dtype=jnp.int32)
    positions = jnp.asarray(list(range(12)) + [-1] * 4, jnp.int32)
    args = (
        tokens, positions, jnp.zeros(n, jnp.int32),
        jnp.asarray([[1, 2, 0, 0], [0, 0, 0, 0]], jnp.int32), k, v, jnp.asarray([11], jnp.int32),
    )
    text = str(jax.make_jaxpr(
        lambda p, *a: llama.forward_ragged(p, cfg, *a, interpret=True)
    )(params, *args))
    assert "expert_gmm" in text and "ragged_dot" not in text
    stack = "bf16[%d,%d,%d,%d]" % params["layers"]["w_gate"].shape
    assert stack in text
    with_kernel, *_ = llama.forward_ragged(params, cfg, *args, interpret=True)
    on_slice, *_ = llama.forward_ragged(params, cfg, *args)
    assert np.isfinite(np.asarray(with_kernel)).all()
    np.testing.assert_allclose(
        np.asarray(with_kernel), np.asarray(on_slice), atol=0.06, rtol=0
    )


# ----------------------------------------------------------- (d) sharded
def test_under_a_tp_mesh_the_wrapper_gives_the_unsharded_result_and_gathers_no_weight():
    tp = 4
    mesh = Mesh(np.array(jax.devices()[:tp]).reshape(1, 1, 1, tp), ("dp", "sp", "ep", "tp"))
    d, width, rows = 128, 512, 64
    router, wg, wu, wd = _weights(jax.random.PRNGKey(3), d, width)
    x = jax.random.normal(jax.random.PRNGKey(4), (rows, d), jnp.float32).astype(jnp.bfloat16)
    live = jnp.asarray(np.random.default_rng(1).random(rows) < 0.7)
    assert moe.experts_in_place(d, width, mesh, interpret=True)
    assert not moe.experts_in_place(d, width, mesh)  # a CPU mesh: ragged_dot under GSPMD
    put = lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec))
    sharded_w = (
        put(wg, P(None, None, None, "tp")), put(wu, P(None, None, None, "tp")),
        put(wd, P(None, None, "tp", None)),
    )

    def run(x, r, wg, wu, wd, live, l):
        return moe.moe_ffn(
            x, r, wg, wu, wd, TOPK, True, live=live, layer=l, mesh=mesh, interpret=True
        )

    lowered = jax.jit(run).lower(x, router[1], *sharded_w, live, jnp.int32(1))
    text = lowered.compile().as_text()
    assert "all-reduce" in text  # the partial sums of w_down's rows
    for m in re.finditer(r"= \(?(\w+)\[([\d,]*)\][^\n]* all-gather", text):
        dims = [int(s) for s in m.group(2).split(",") if s]
        assert int(np.prod(dims)) < E * d * width // tp, m.group(0)  # no weight is gathered
    got = jax.jit(run)(x, router[1], *sharded_w, live, jnp.int32(1))
    alone = moe.moe_ffn(x, router[1], wg, wu, wd, TOPK, True, live=live, layer=jnp.int32(1), interpret=True)
    want = moe.moe_ffn(x, router[1], wg[1], wu[1], wd[1], TOPK, True, live=live)
    assert not np.asarray(got.astype(jnp.float32))[~np.asarray(live)].any()
    for other in (alone, want):
        np.testing.assert_allclose(
            np.asarray(got.astype(jnp.float32)), np.asarray(other.astype(jnp.float32)),
            atol=0.03, rtol=0,
        )
    # one layer's arrays with no layer index: the same path, the same numbers
    per_layer = jax.jit(
        lambda x, r, wg, wu, wd: moe.moe_ffn(x, r, wg, wu, wd, TOPK, True, mesh=mesh, interpret=True)
    )(x, router[1], *(jax.lax.index_in_dim(w, 1, 0, keepdims=False) for w in sharded_w))
    whole = jax.jit(
        lambda x, r, wg, wu, wd: moe.moe_ffn(x, r, wg, wu, wd, TOPK, True, layer=jnp.int32(1), mesh=mesh, interpret=True)
    )(x, router[1], *sharded_w)
    assert np.array_equal(np.asarray(per_layer.astype(jnp.float32)), np.asarray(whole.astype(jnp.float32)))


# -------------------------------------------------- compiled for the chip
@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.mark.parametrize("rows", [128, 4096])
def test_the_kernel_compiles_for_a_v5e_at_a_mixtral_chips_widths(topo, rows):
    """XLA:TPU and Mosaic take the kernel at real widths (VMEM, tiling,
    the stacked operand) and hand it the stack itself: no operation's
    result is a layer's matrix. Nothing runs."""
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    for k, n in ((4096, 3584), (3584, 4096)):
        text = jax.jit(expert_gmm.expert_gmm).lower(
            sds((rows, k), jnp.bfloat16), sds((16, 8, k, n), jnp.bfloat16),
            sds((8,), jnp.int32), sds((), jnp.int32),
        ).compile().as_text()
        assert re.search(rf"%expert_gmm[.\d]* = bf16\[{rows},{n}\]", text)
        assert not re.search(rf"= bf16\[8,{k},{n}\]", text)


def test_the_sharded_expert_path_compiles_for_four_chips_with_no_copy_of_a_weight(topo):
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 1, 1, 4), ("dp", "sp", "ep", "tp"))
    d, width, layers, rows = 4096, 14336, 16, 64
    sds = lambda shape, dt, spec: jax.ShapeDtypeStruct(shape, dt, sharding=NamedSharding(mesh, spec))
    args = (
        sds((rows, d), jnp.bfloat16, P()), sds((layers, d, E), jnp.bfloat16, P()),
        sds((layers, E, d, width), jnp.bfloat16, P(None, None, None, "tp")),
        sds((layers, E, d, width), jnp.bfloat16, P(None, None, None, "tp")),
        sds((layers, E, width, d), jnp.bfloat16, P(None, None, "tp", None)),
        sds((rows,), jnp.bool_, P()),
    )

    def step(x, routers, wg, wu, wd, live):
        def layer(x, xs):
            r, l = xs
            return x + moe.moe_ffn(x, r, wg, wu, wd, TOPK, True, live=live, layer=l, mesh=mesh), None

        return jax.lax.scan(layer, x, (routers, jnp.arange(layers, dtype=jnp.int32)))[0]

    compiled = jax.jit(step).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("expert_gmm") >= 3
    slab = r"bf16\[(?:16,)?8,(?:4096,3584|3584,4096|4096,14336|14336,4096)\]"
    makers = [
        line for line in text.splitlines()
        if re.search(r"= \(?" + slab, line)
        and not re.search(r" (parameter|get-tuple-element|bitcast|tuple)\(", line)
    ]
    assert not makers, makers[:3]
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_the_expanded_latent_call_compiles_for_a_v5e_at_the_published_widths(topo):
    """The other kernel of a main path that this file's fixture can reach
    (one process holds the TPU's compiler): latent attention's expanded
    call at openPangu-Ultra-MoE's widths over a 4,032-slot stream, as the
    mixed program of 4,096 slots calls it. Mosaic takes its VMEM (16 heads
    of 1,024 queries' softmax states, a 512-token chunk's K and V), its
    windows at any multiple of the tile and its name. Nothing runs."""
    from jax.sharding import SingleDeviceSharding

    from dynamo_exp_tpu.ops.ragged_attention import (
        expanded_min_queries, expanded_plan, ragged_paged_attention_expanded,
    )

    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    heads, rank, nope, rope, v, lanes, n = 128, 512, 128, 64, 128, 640, 4032
    least = expanded_min_queries(lanes, 512, rank, nope, rope, v, 8)

    def call(q, pool, w, table, row_of, positions):
        _, items = expanded_plan(row_of, positions, table.shape[0], 8, least)
        return ragged_paged_attention_expanded(
            q, pool, w, table, positions, items, rank=rank, nope=nope, q_tile=8,
            sm_scale=(nope + rope) ** -0.5,
        )

    compiled = jax.jit(call).lower(
        sds((n, heads, nope + rope), jnp.bfloat16), sds((5 * 24576, 16, lanes), jnp.bfloat16),
        sds((rank, heads * (nope + v)), jnp.bfloat16), sds((33, 1024), jnp.int32),
        sds((n,), jnp.int32), sds((n,), jnp.int32),
    ).compile()
    assert least == 160
    assert re.search(r"ragged_paged_attention_expanded[.\d]* = bf16\[4032,128,128\]", compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("q_tile,n", [(8, 2048), (1, 32)])
def test_the_selection_and_the_masked_call_compile_for_a_v5e_at_glm5s_widths(topo, q_tile, n):
    """Learned sparse attention's two kernels at GLM-5's widths (32 index
    heads of 128 over a 128-lane index pool; 64 heads absorbed over a
    640-lane latent pool with the selection masked inside the pass), a slab
    of a mixed dispatch's tiled part and a decode window's rows, over page
    tables of 16,384 tokens: Mosaic takes the scores' blocks, the tie
    count's product and the mask's broadcast over the heads. Nothing runs."""
    from jax.sharding import SingleDeviceSharding

    from dynamo_exp_tpu.ops import sparse_index
    from dynamo_exp_tpu.ops.ragged_attention import ragged_paged_attention

    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)

    def call(q, q_i, w_i, pool, keys, table, row_of, bounds):
        scores = sparse_index.index_scores(q_i, w_i, keys, table, row_of, bounds, q_tile=q_tile)
        tau, allow = sparse_index.select_tiled(scores, bounds.reshape(-1, q_tile), 2048)
        return ragged_paged_attention(
            q, pool, None, table, row_of, bounds, num_kv_heads=1, q_tile=q_tile,
            sm_scale=256 ** -0.5, value_dim=512,
            keep=(scores, tau.reshape(-1), allow.reshape(-1)),
        )

    compiled = jax.jit(call).lower(
        sds((n, 64, 640), jnp.bfloat16), sds((n, 32, 128), jnp.bfloat16),
        sds((n, 32), jnp.float32), sds((5 * 24576, 16, 640), jnp.bfloat16),
        sds((5 * 24576, 16, 128), jnp.bfloat16), sds((33, 1024), jnp.int32),
        sds((n,), jnp.int32), sds((n,), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert re.search(rf"dsa_index_scores[.\d]* = f32\[{n // q_tile},128,{q_tile},128\]", text)
    assert re.search(rf"dsa_kth_largest[.\d]* = \(f32\[{n // q_tile},{q_tile},128\]", text)
    assert re.search(rf"ragged_paged_attention[.\d]* = bf16\[{n},64,512\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


# ------------------------------------------------------ (e) the counter
def _counter(name: str) -> dict:
    from dynamo_exp_tpu.telemetry import get_telemetry

    out = {}
    for line in get_telemetry().render().decode().splitlines():
        if line.startswith(name + "{"):
            head, value = line.rsplit(" ", 1)
            out[head[len(name):]] = float(value)
    return out


def _grew(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items() if v != before.get(k, 0.0)}


@pytest.mark.parametrize(
    "model,interpret,label,prompt",
    [
        (TINY_MOE, False, "sliced", 9), (MOE_128, True, "inplace", 9),
        # a prompt chunk of more sorted rows than one row block of the kernel
        (MOE_128, True, "inplace", 160), (TINY, False, None, 9),
    ],
    ids=["cpu-sliced", "interpret-inplace", "interpret-inplace-many-rows", "dense-none"],
)
async def test_every_dispatch_of_a_routed_model_counts_once_under_how_it_read_its_experts(
    model, interpret, label, prompt
):
    from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
    from dynamo_exp_tpu.parallel import single_device_mesh
    from dynamo_exp_tpu.protocols.common import BackendInput

    # pages of 32: few page copies a chunk for the interpreted kernel to lower
    pool = dict(page_size=32, num_pages=16, max_model_len=256) if prompt > 9 else dict(
        page_size=8, num_pages=32, max_model_len=64
    )
    cfg = EngineConfig(
        model=model, max_decode_slots=2, eos_token_ids=[], pallas_interpret=interpret,
        decode_window=2, **pool,
    )
    engine = TPUEngine(cfg, mesh=single_device_mesh(), seed=0)
    engine.start()
    try:
        d0, m0 = _counter("dynamo_ragged_dispatch_total"), _counter("dynamo_moe_dispatch_total")
        b = BackendInput(token_ids=[5 + i % 200 for i in range(prompt)])
        b.stop_conditions.max_tokens = 5
        b.stop_conditions.ignore_eos = True
        out = []
        async for item in await engine.generate(b.to_dict()):
            out += item.get("token_ids", [])
        assert len(out) == 5
        by_variant = _grew(d0, _counter("dynamo_ragged_dispatch_total"))
        dispatches = sum(by_variant.values())
        assert dispatches >= 2  # a mixed dispatch and a window at the least
        if prompt > 9:  # its chunk went as ONE mixed dispatch of that many rows
            slots = max(
                int(re.search(r'nb="(\d+)"', k).group(1))
                for k in by_variant if 'program="mixed"' in k
            )
            assert slots * model.num_experts_per_tok > WINDOW_ROWS
        grew = _grew(m0, _counter("dynamo_moe_dispatch_total"))
        assert grew == ({} if label is None else {'{experts="%s"}' % label: dispatches})
    finally:
        engine.stop()
