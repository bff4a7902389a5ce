"""Ragged paged attention over a latent pool, the EXPANDED call
(``ragged_paged_attention_expanded``) in interpret mode: against the two-pool
reference over every head's K and V built outright and against the absorbed
call on the same pool, without and with a learned selection (``keep``), in
the split and the joint score layout, in bfloat16. The cases and what each
crosses: ``tests/ragged_cases.py`` and the tables below; the rule and the
plan on the host: ``tests/test_ragged_rules.py``.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_exp_tpu.ops.ragged_attention import (
    _expanded_joint,
    expanded_plan,
    ragged_paged_attention,
    ragged_paged_attention_expanded,
    ragged_paged_attention_ref,
)

from .ragged_cases import (
    EXPANDED_SHAPE,
    EXPANDED_STREAMS,
    GLM_WIDTHS,
    KEPT_SHAPE,
    KEPT_STREAMS,
    PUBLISHED_WIDTHS,
    TINY_JOINT_WIDTHS,
    TINY_WIDTHS,
    kept_case,
    latent_case,
)


_WIDTHS = {
    "tiny": TINY_WIDTHS, "tiny_joint": TINY_JOINT_WIDTHS, "published": PUBLISHED_WIDTHS,
    "glm5": GLM_WIDTHS,
}


def _by_widths(streams, at_full_widths, full):
    """The table of a parametrised expanded test: every stream in the split
    layout at the tiny widths; ONE stream at ``full`` widths (the entry
    point's case at published widths: ``at_full_widths``); the others in the
    JOINT layout at tiny widths, which no case crossed at a small size. What
    a stream crosses (passes a row, score products a pass, chunks a context,
    the rule's two sides, padding) is in its data and said at its table
    (``tests/ragged_cases.py``); the layout, ``keep`` and the widths are the
    kernel's static facts. All streams of one widths share one shape
    (``EXPANDED_SHAPE`` / ``KEPT_SHAPE``), so a combination compiles once."""
    return [
        *((stream, "tiny") for stream in streams),
        *((stream, full if stream == at_full_widths else "tiny_joint") for stream in streams),
    ]


@pytest.mark.parametrize(
    "stream,widths", _by_widths(EXPANDED_STREAMS, "crosses_the_chunk", "published")
)
def test_expanded_form_matches_reference_and_the_absorbed_form(stream, widths):
    """The expanded call over a latent pool against the two-pool reference
    over every head's K and V built outright, and against the absorbed
    call on the same pool, row by row on each side of the rule: rows of at
    least 8 queries through the expanded call (in passes of at most 16
    slots, 8 a score product, 64 tokens of context a chunk, so that a row is
    several of each), the others
    through the absorbed one at ``-1`` where the expanded call serves;
    padding slots are exact zeros in what the two give together."""
    spans, widths = EXPANDED_STREAMS[stream], _WIDTHS[widths]
    c = latent_case(spans, 11, **widths, **EXPANDED_SHAPE)
    r, dn, dr, dv = (widths[k] for k in ("r", "dn", "dr", "dv"))
    scale = (dn + dr) ** -0.5
    want = ragged_paged_attention_ref(
        c["q"], c["kx"], c["vx"], c["table"], c["row_of"], c["positions"],
        num_kv_heads=widths["H"], sm_scale=scale,
    )[..., :dv]
    takes, items = expanded_plan(
        c["row_of"], c["positions"], EXPANDED_SHAPE["rows"], 8, 8, 16, 8
    )
    o_x = ragged_paged_attention_expanded(
        c["q"], c["pool"], c["w_ukv"], c["table"], c["positions"], items,
        rank=r, nope=dn, q_tile=8, sm_scale=scale, interpret=True,
        q_max=16, q_sub=8, kv_chunk=64,
    )
    takes = np.asarray(takes)
    by_row = {i: n >= 8 for i, (n, _) in enumerate(spans)}
    rows = np.asarray(c["row_of"])
    tile_live = np.repeat((np.asarray(c["positions"]).reshape(-1, 8) >= 0).any(axis=1), 8)
    assert (takes == (np.vectorize(lambda i: by_row.get(i, False))(rows) & tile_live)).all()

    def absorbed(positions):
        o = ragged_paged_attention(
            c["q_abs"], c["pool"], None, c["table"], c["row_of"], positions,
            num_kv_heads=1, q_tile=8, sm_scale=scale, interpret=True,
            value_dim=min(widths["lanes"], -(-r // 128) * 128),
        )
        return jnp.einsum("nhc,chv->nhv", o[..., :r], c["w"][..., dn:])

    both = jnp.where(takes[:, None, None], o_x, absorbed(jnp.where(takes, -1, c["positions"])))
    assert float(jnp.abs(both - want).max()) < 2e-5
    # the absorbed form of every row: the same mathematics, reordered
    assert float(jnp.abs(absorbed(c["positions"]) - want).max()) < 2e-5
    assert float(jnp.abs(both[np.asarray(c["positions"]) < 0]).max()) == 0.0


def test_expanded_form_in_bfloat16_rounds_k_and_v_once():
    """At the published head widths in bfloat16: the expanded call against
    the reference over K and V rounded to bfloat16 once (the published
    model's own rounding), float32 softmax."""
    w = PUBLISHED_WIDTHS
    c = latent_case([(24, 150), (16, 40)], 12, **w, ps=32)
    bf = {k: v.astype(jnp.bfloat16) for k, v in c.items() if v.dtype == jnp.float32}
    kx = jnp.einsum(
        "psc,chd->pshd", bf["pool"][..., : w["r"]], bf["w"], preferred_element_type=jnp.float32
    ).astype(jnp.bfloat16)
    pages, ps = kx.shape[:2]
    k_r = jnp.broadcast_to(
        bf["pool"][:, :, None, w["r"] : w["r"] + w["dr"]], (pages, ps, w["H"], w["dr"])
    )
    keys = jnp.concatenate([kx[..., : w["dn"]], k_r], axis=-1).reshape(pages, ps, -1)
    values = jnp.pad(kx[..., w["dn"]:], ((0, 0),) * 3 + ((0, w["dr"]),)).reshape(pages, ps, -1)
    scale = (w["dn"] + w["dr"]) ** -0.5
    want = ragged_paged_attention_ref(
        bf["q"], keys, values, c["table"], c["row_of"], c["positions"],
        num_kv_heads=w["H"], sm_scale=scale,
    )[..., : w["dv"]]
    takes, items = expanded_plan(c["row_of"], c["positions"], 3, 8, 8)
    got = ragged_paged_attention_expanded(
        bf["q"], bf["pool"], bf["w_ukv"], c["table"], c["positions"], items,
        rank=w["r"], nope=w["dn"], q_tile=8, sm_scale=scale, interpret=True,
    )
    assert bool(takes[:40].all()) and not bool(takes[40:].any())
    diff = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))[:40]
    assert float(diff.max()) < 2e-2


@pytest.mark.parametrize(
    "stream,widths", _by_widths(KEPT_STREAMS, "ties_straddle_chunks_and_sub_blocks", "glm5")
)
def test_expanded_form_applies_the_selection_as_the_reference_does(stream, widths):
    """The expanded call with ``keep`` against the two-pool reference given
    the same kept positions, and against the absorbed call with the same
    ``keep``: the rows of at least 8 queries through the expanded call, the
    others absorbed; in the split score layout (tiny widths) and the joint
    one (GLM-5's head of 192 + 64, and 96 + 32 at a small size)."""
    widths = _WIDTHS[widths]
    spans, topk, values, (q_max, q_sub, kv_chunk) = KEPT_STREAMS[stream]
    c = kept_case(spans, widths, topk, values, q_sub=q_sub, **KEPT_SHAPE)
    r, dn, dr, dv = (widths[k] for k in ("r", "dn", "dr", "dv"))
    scale = (dn + dr) ** -0.5
    want = ragged_paged_attention_ref(
        c["q"], c["kx"], c["vx"], c["table"], c["row_of"], c["positions"],
        num_kv_heads=widths["H"], sm_scale=scale, keep=c["kept"],
    )[..., :dv]
    takes, items = expanded_plan(
        c["row_of"], c["positions"], KEPT_SHAPE["rows"], 8, 8, q_max, q_sub
    )
    o_x = ragged_paged_attention_expanded(
        c["q"], c["pool"], c["w_ukv"], c["table"], c["positions"], items,
        rank=r, nope=dn, q_tile=8, sm_scale=scale, interpret=True,
        q_max=q_max, q_sub=q_sub, kv_chunk=kv_chunk, keep=c["keep"],
    )
    o_a = ragged_paged_attention(
        c["q_abs"], c["pool"], None, c["table"], c["row_of"],
        jnp.where(takes, -1, c["positions"]), num_kv_heads=1, q_tile=8,
        sm_scale=scale, interpret=True,
        value_dim=min(widths["lanes"], -(-r // 128) * 128), keep=c["keep_tiles"],
    )
    o_a = jnp.einsum("nhc,chv->nhv", o_a[..., :r], c["w"][..., dn:])
    both = jnp.where(takes[:, None, None], o_x, o_a)
    assert bool(takes.any()) and float(jnp.abs(both - want).max()) < 3e-5
    assert float(jnp.abs(both[np.asarray(c["positions"]) < 0]).max()) == 0.0
    # the selection is felt: the dense result differs
    dense = ragged_paged_attention_ref(
        c["q"], c["kx"], c["vx"], c["table"], c["row_of"], c["positions"],
        num_kv_heads=widths["H"], sm_scale=scale,
    )[..., :dv]
    assert float(jnp.abs(dense - want).max()) > 1e-3


@pytest.mark.parametrize("stream", ["one_row_several_blocks", "crosses_the_chunk"])
def test_joint_layout_matches_reference_without_a_selection(stream):
    """GLM-5's head widths with no ``keep``: one 256-wide score product,
    ``k_r`` placed behind ``k_nope`` before the one rounding."""
    w = GLM_WIDTHS
    assert _expanded_joint(w["dn"], w["dr"]) and not _expanded_joint(128, 64)
    assert _expanded_joint(TINY_JOINT_WIDTHS["dn"], TINY_JOINT_WIDTHS["dr"])
    spans = EXPANDED_STREAMS[stream]
    c = latent_case(spans, 13, **w, **EXPANDED_SHAPE)
    scale = (w["dn"] + w["dr"]) ** -0.5
    want = ragged_paged_attention_ref(
        c["q"], c["kx"], c["vx"], c["table"], c["row_of"], c["positions"],
        num_kv_heads=w["H"], sm_scale=scale,
    )[..., : w["dv"]]
    takes, items = expanded_plan(
        c["row_of"], c["positions"], EXPANDED_SHAPE["rows"], 8, 8, 16, 8
    )
    got = ragged_paged_attention_expanded(
        c["q"], c["pool"], c["w_ukv"], c["table"], c["positions"], items,
        rank=w["r"], nope=w["dn"], q_tile=8, sm_scale=scale, interpret=True,
        q_max=16, q_sub=8, kv_chunk=64,
    )
    assert float(jnp.abs(jnp.where(takes[:, None, None], got - want, 0.0)).max()) < 3e-5

