"""Nothing a live row computes depends on memory no request owns.

A mixed dispatch pads: to the kernel's query tile (8) inside every row's
span and to a token bucket (16 at the least) at the end, so a cached
asking of one new token computes 1 live and 15 padding queries. These
tests hold the three places where padding met live rows (PERF.md, PR 27):

- the ragged kernel gave a padding query inside a live tile the MEAN of
  every V slot the tile's chunks brought in (the mask is the finite
  -1e30 and the running maximum starts at -1e30, so every
  ``exp(s - m)`` was ``exp(0)``): the row's context, its last page's
  slots past its length, and page 0 through clamped and zero table
  entries. It now returns exact zeros, as its comment always said;
- the XLA gather form did the same through a uniform softmax;
- ``ops/moe.py::moe_ffn`` sorted those rows in with the live ones, so
  their history-dependent hidden states moved every expert's group size
  and the live rows' place among the sorted rows. Padding rows now count
  in no group.

On the CPU ``ragged_dot`` computes a row alike wherever it lands, so the
last link (another place, other last bits, another expert, another
token: four chips, 16 layers) shows only on the chip; what the CPU can
hold is that the padding rows carry nothing in and count nowhere.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_exp_tpu.models import TINY_MOE, init_kv_cache, init_params
from dynamo_exp_tpu.models.llama import forward_ragged
from dynamo_exp_tpu.ops import moe
from dynamo_exp_tpu.ops.ragged_attention import (
    ragged_paged_attention,
    ragged_paged_attention_ref,
)

H, HKV, D, PS, PMAX, QT = 8, 4, 64, 16, 8, 8


def _one_live_query(fill: float):
    """One live query at position 20 in a tile of 8 (7 padding queries
    beside it), its row holding pages 3 and 5; page 0 (what zero table
    entries read) and the slots of page 5 past position 20 filled with
    ``fill``."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    k, v = (
        jax.random.normal(key, (8, PS, HKV * D), jnp.float32).at[0].set(fill).at[5, 5:].set(fill)
        for key in ks[1:]
    )
    table = jnp.zeros((2, PMAX), jnp.int32).at[0, :2].set(jnp.asarray([3, 5]))
    positions = jnp.asarray([20] + [-1] * (QT - 1) + [-1] * QT, jnp.int32)
    row_of = jnp.asarray([0] * QT + [1] * QT, jnp.int32)
    q = jax.random.normal(ks[0], (2 * QT, H, D), jnp.float32)
    return q, k, v, table, row_of, positions


@pytest.mark.parametrize("form", ["kernel", "xla"])
def test_a_padding_query_in_a_live_tile_is_exact_zeros_whatever_the_pages_hold(form):
    outs = []
    for fill in (0.5, 100.0):
        q, k, v, table, row_of, positions = _one_live_query(fill)
        if form == "kernel":
            out = ragged_paged_attention(
                q, k, v, table, row_of, positions, num_kv_heads=HKV, q_tile=QT,
                interpret=True,
            )
        else:
            out = ragged_paged_attention_ref(
                q, k, v, table, row_of, positions, num_kv_heads=HKV
            )
        outs.append(np.asarray(out))
        assert np.abs(outs[-1][0]).max() > 0  # the live query attends
        # The 7 padding queries of the live tile, and the empty tile.
        assert not outs[-1][1:].any(), np.abs(outs[-1][1:]).max()
    # The live query reads nothing it does not own: bit-equal.
    assert np.array_equal(outs[0][0], outs[1][0])


def test_padding_rows_count_in_no_experts_group_and_add_to_no_row(monkeypatch):
    """``moe_ffn`` with ``live``: the group sizes sum to K x the live
    rows, the dead rows come back as zeros, and a live row's output is
    bit-equal to what the live rows alone give, whatever the dead rows
    hold."""
    E, K, N, Dm, I = 8, 2, 16, 32, 48
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    x = jax.random.normal(ks[0], (N, Dm), jnp.float32)
    router = jax.random.normal(ks[1], (Dm, E), jnp.float32)
    wg = jax.random.normal(ks[2], (E, Dm, I), jnp.float32) * 0.1
    wu = jax.random.normal(ks[3], (E, Dm, I), jnp.float32) * 0.1
    wd = jax.random.normal(ks[4], (E, I, Dm), jnp.float32) * 0.1
    live = np.zeros(N, bool)
    live[[0, 5, 6]] = True
    seen = []
    plain = jax.lax.ragged_dot

    def recording(lhs, rhs, group_sizes, **kw):
        seen.append(np.asarray(group_sizes))
        return plain(lhs, rhs, group_sizes, **kw)

    monkeypatch.setattr(jax.lax, "ragged_dot", recording)
    outs = []
    for junk in (1.0, -37.0):
        xj = jnp.where(jnp.asarray(live)[:, None], x, junk * x + junk)
        outs.append(np.asarray(moe.moe_ffn(xj, router, wg, wu, wd, K, True, live=jnp.asarray(live))))
    assert all(int(g.sum()) == K * int(live.sum()) for g in seen) and len(seen) == 6
    assert not outs[0][~live].any()
    assert np.array_equal(outs[0], outs[1])
    alone = np.asarray(moe.moe_ffn(x[live], router, wg, wu, wd, K, True))
    assert np.array_equal(outs[0][live], alone)
    want = np.asarray(moe.moe_ffn_reference(x, router, wg, wu, wd, K, True))
    np.testing.assert_allclose(outs[0][live], want[live], atol=1e-5)


def test_a_live_rows_logits_do_not_turn_with_page_zero_or_the_padding_rows():
    """The mixed program's forward (``forward_ragged``, the ragged
    kernel in interpret mode, routed experts) over a cached asking's
    shape: one live token at position 20 whose context lies in pages 3
    and 5, 15 padding slots. Page 0, the slots past the row's length
    and the padding slots' token ids filled two ways: the live token's
    logits are bit-equal."""
    cfg = TINY_MOE
    params = init_params(jax.random.PRNGKey(2), cfg)
    n_pages, ps = 8, 8
    base_k, base_v = init_kv_cache(cfg, n_pages, ps)
    ctx = jax.random.normal(jax.random.PRNGKey(3), base_k.shape, jnp.float32)
    table = jnp.zeros((2, 4), jnp.int32).at[0, :3].set(jnp.asarray([3, 5, 6]))
    positions = jnp.asarray([20] + [-1] * 15, jnp.int32)
    row_of = jnp.asarray([0] * 8 + [1] * 8, jnp.int32)
    logits = []
    for fill, pad_id in ((0.25, 0), (64.0, 7)):
        k = ctx.astype(base_k.dtype).at[:, 0].set(fill).at[:, 6, 5:].set(fill)
        v = (ctx * 0.5).astype(base_v.dtype).at[:, 0].set(fill).at[:, 6, 5:].set(fill)
        tokens = jnp.asarray([11] + [pad_id] * 15, jnp.int32)
        out, _k, _v = forward_ragged(
            params, cfg, tokens, positions, row_of, table, k, v,
            jnp.asarray([0], jnp.int32), attn_impl="pallas", q_tile=8, interpret=True,
        )
        logits.append(np.asarray(out[0]))
    assert np.isfinite(logits[0]).all() and np.ptp(logits[0]) > 0
    assert np.array_equal(logits[0], logits[1])
