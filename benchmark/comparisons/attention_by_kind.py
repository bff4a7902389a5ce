"""The comparison for a model whose ATTENTION SHAPES DIFFER BY THE LAYERS'
KIND (``"comparison": "attention_by_kind"``): mimo_v2_flash. A sliding
layer has 8 KV heads, a sink logit a query head in its softmax and a rotary
base of its own; a full layer has 4 KV heads and 16 query heads a KV head;
in both a head's keys are 192 wide and its values 128, the first 64 values
of a key turn and the values are scaled ahead of the cache; the paged cache
is two pools by the layers' kind WHOSE ROWS HAVE DIFFERENT LANES (768 + 512
full, 1536 + 1024 sliding); the feed-forward is a held share of routed
experts chosen through a selection bias, with no shared expert.

It is ``sliding_attention``'s comparison, part for part (that module's text
has the reasoning; its parts 2 and 3 are run as they are, on this module's
two-layer model): what differs is what the two-layer model is made of (the
K and V projections of each form from that form's stack, the sliding
layer's sinks), which layers are read (the pairs (a sliding layer, the full
layer behind it); the leading full layer has no sliding layer before it and
is read by the served path alone), and the tolerances, each beside two
readings on the chip:

1. THE EXPERT BLOCK of the first, a middle and the last routed layer
   through ``_ffn_block`` against the reference given the same share (the
   router over all 256 with its selection bias, the 8 held experts), on
   rows a sixteenth as long as the stream's (``BLOCK_INPUT_SCALE``); a row
   none of whose chosen experts is held must come back as it went in.
2. BOTH POOLS' ROWS after one prefill of the probe sequences through a
   two-layer model (a sliding layer and the full layer behind it): K with
   its leading 64 of 192 values turned at the kind's base in rows of 8 x 192
   (sliding) and 4 x 192 (full) lanes, V scaled in rows of 8 x 128 and 4 x
   128; what no token wrote must be exact zeros.
3. THE READ AT THE TIMED SIZES through both pools with pages given back
   between chunks: ``LONG`` seeded rows, prefilled in chunks through
   ``forward_ragged`` (tiles of 8 merged into each form's own block: 16
   slots sliding, 8 full), then decoded through ``forward`` (the one-query
   form), every compared row against the reference's two halves.
4. THE SERVED PATH: ``logprob_walk``'s probes and one long probe past the
   width, asked while other requests decode beside it.

TOLERANCES: ``rehearse/check_seeds_mimo.py`` reads the sound program over
seeds and the controls that must fail (no sink; the sink on the full layers
too; rotary over 66 or over all 192 values; the sliding base on a full
layer; the value scale left out; a full layer's query heads grouped 8 a KV
head, the other kind's table; K in float8). PERF.md section 2 has the table.
"""
from __future__ import annotations

import contextlib
import dataclasses
import statistics

import numpy as np

from comparisons import sliding_attention as swa
from comparisons.latent_attention import (
    BLOCK_ROWS, CHUNK, DECODE, LONG, PROBES, REAL_ROW_SHARE, _engine_params,
)
from comparisons.sparse_attention import engine_block
from lib import correct
from lib.stats import percentile

NEAR_TIE = swa.NEAR_TIE
# What part 1's rows are multiplied by before both sides take them (a power
# of two: exact in bfloat16). The block norms its input, so what it ADDS does
# not depend on the input's length, but the engine rounds input + addition
# to bfloat16 together: with no shared expert and 8 of 256 experts held a
# row adds a sixteenth of what a whole layer would, and at the stream's own
# length that rounding alone read 0.016-0.028 of the addition, rising with
# the layer (my chip run, PR 53, seed 2147483747: above float8 experts'
# distance from bfloat16's). A sixteenth of the length puts it 16 x lower and
# leaves the experts' own arithmetic to be read.
BLOCK_INPUT_SCALE = 2.0 ** -4
# All readings my chip runs, PR 53 (rehearse/check_seeds_mimo.py on seeds 11-20 and the cell's own
# runs, mimo-v2-flash-l11e32 on one v5e chip; controls on seed 11; PERF.md section 2 has the
# table).
# Block part, largest row of |engine - reference| / |reference - input| on rows a sixteenth of the
# stream's length: sound 0.00529-0.00630 over 10 seeds (0.0049-0.0059 over the six layer-and-rows
# cells of a seed; 294-374 of 3,264 rows left out as near ties, 2,204-2,347 hold none of their
# experts and came back exactly as they went in). The held experts in float8 0.0716; the experts
# chosen without the selection bias 1.70 (and rows that hold nothing moved by 0.41). At the
# stream's own length the sound program read 0.0284 (one run): BLOCK_INPUT_SCALE's text.
BLOCK_TOL = 0.02
# Both pools' rows, largest row of |engine - reference| / |reference| (K 768 and V 512 values a
# token in the full pool, 1,536 and 1,024 in the sliding): sound 0.00334-0.00346 (the full pool's
# K at layer 5; the sliding pools' 0.0027); K rounded to float8 as it is cached 0.0298; the value
# scale left out 0.318 (V) and 0.130 (the full layer's K, behind a sliding half whose output is
# 1.41 x too large); rotary over 66 values 0.608, over all 192 1.10; the sliding base on a full
# layer 0.669; no sink 0.673 (the full layer's rows, behind the sliding half).
KV_TOL = 0.01
# The two halves with the head at the timed sizes: largest row of |engine - reference| over the
# log-probabilities relative to their spread: sound 0.00572-0.00622 over 10 seeds (prefilled and
# decoded rows alike; 0.00595 in the cell's first run); K in float8 0.0249; the value scale left
# out 0.0572; the sliding base on a full layer 0.262; rotary over 66 values 0.436, over all 192
# 0.744; the sink on the full layers too 0.772; no sink 0.807; a full layer's heads grouped 8 a
# KV head 1.23. The limit is the two nearest readings' geometric mean.
ATTN_TOL = 0.012
# Served part, the short probes: the median over their 32 steps: sound 0.0134-0.0215 over 10
# seeds (0.0163 in the cell's first run). NOT a precision limit (float8 experts 0.0190, K in
# float8 does not reach the served programs): the experts chosen without the selection bias
# 0.147, the sliding base on a full layer 0.287, the value scale left out 0.321, rotary over 66
# values 0.332, the sink on the full layers too 0.993, no sink 1.60, the other kind's head table
# 3.60.
MEDIAN_TOL = 0.06
# The long probe (8,192 tokens asked while three requests of 1,024 decode beside it; 9 mixed
# dispatches; the only part that reads the served engine's page tables and programs past the
# width): the median over its 8 steps: sound 0.0108-0.0177 over 10 seeds (0.0146 in the cell's
# first run). The sink on the full layers too 0.0687 (the smallest control that is its to
# catch), the sliding base on a full layer 0.174, no selection bias 0.202, rotary over 66 values
# 0.220, no sink 0.309, no value scale 0.703, the other kind's head table 2.65. Float8 experts
# read 0.0268 here and pass: part 1's.
LONG_MEDIAN_TOL = 0.035


def layers_read(hf: dict) -> tuple[list[tuple[int, int]], list[int]]:
    """(The pairs (a sliding layer, the full layer behind it) whose halves
    are read: of the full layers that have a sliding layer before them, the
    first with the first such sliding layer and the last with the one just
    before it; the routed layers whose block is read: first, middle, last)."""
    sliding = [bool(p) for p in hf["hybrid_layer_pattern"]]
    full = [i for i, s in enumerate(sliding) if not s and any(sliding[:i])]
    pairs = []
    for f, nearest in ((full[0], False), (full[-1], True)):
        before = [i for i in range(f) if sliding[i]]
        pair = (before[-1] if nearest else before[0], f)
        if pair not in pairs:
            pairs.append(pair)
    freq = list(hf["moe_layer_freq"])
    routed = [i for i, r in enumerate(freq) if r]
    return pairs, sorted({routed[0], routed[len(routed) // 2], routed[-1]})


def block_part(ctx: dict, before_ffn: np.ndarray, layers: list[int]) -> dict:
    """Part 1: ``latent_attention.block_part`` for a layer WITH NO SHARED
    EXPERT, where a row none of whose chosen experts is held adds exactly
    nothing (most rows, at 8 held of 256 and 8 chosen): such a row has no
    length to measure against, so it is counted, left out of the relative
    error, and the engine must add exactly nothing to it too where the
    row's choice is no near tie (``unheld_max_abs``, which has to read 0).
    Both sides take the rows at ``BLOCK_INPUT_SCALE`` of their length."""
    import jax.numpy as jnp

    reference, engine, hf = ctx["reference"], ctx["engine"], ctx["hf"]
    rng = np.random.default_rng(ctx["seed"] % 2**32)
    run = engine_block(engine)
    cells, near, here, unheld_abs = [], 0, [], 0.0
    for i, layer in enumerate(layers):
        real = before_ffn[i]
        rms = float(np.sqrt(np.mean(real * real)))
        for rows in BLOCK_ROWS:
            n_real = min(len(real), int(rows * REAL_ROW_SHARE))
            pick = np.linspace(0, len(real) - 1, n_real).astype(int)
            seeded = rng.standard_normal((rows - n_real, real.shape[1]), np.float32) * rms
            x = jnp.asarray(np.concatenate([real[pick], seeded]), jnp.bfloat16)
            x = x * jnp.asarray(BLOCK_INPUT_SCALE, x.dtype)
            shaped = x[:, None] if rows == BLOCK_ROWS[0] else x[None]
            got = np.asarray(
                run(_engine_params(ctx)["layers"], shaped, layer).astype(jnp.float32)
            ).reshape(rows, -1)
            want, margin, held = reference.ffn_half(ctx["params"], hf, layer, x)
            added = np.linalg.norm(want - np.asarray(x.astype(jnp.float32)), axis=1)
            adds = added > 0
            sure = margin >= NEAR_TIE  # the rows whose choice no rounding flips
            unheld_abs = max(
                unheld_abs, float(np.abs(got - want)[~adds & sure].max(initial=0.0))
            )
            err = np.linalg.norm(got - want, axis=1)[adds] / added[adds]
            keep = sure[adds]
            cells.append({
                "layer": layer, "rows": rows, "rows_near_tie": int((~keep).sum()),
                "rows_nothing_held": int((~adds).sum()),
                "max_rel_err": float(err[keep].max()),
                "median_rel_err": float(np.median(err[keep])),
                "max_rel_err_near_tie": float(err[~keep].max()) if (~keep).any() else None,
                "held_pick_share": held,
            })
            near += cells[-1]["rows_near_tie"]
            here.append(held)
    return {
        "block": cells, "block_max_rel_err": max(c["max_rel_err"] for c in cells),
        "rows_near_tie": near, "held_pick_share": float(np.mean(here)),
        "block_median_rel_err": max(c["median_rel_err"] for c in cells),
        "rows_nothing_held": sum(c["rows_nothing_held"] for c in cells),
        "unheld_max_abs": unheld_abs,
    }


def _pair_model(engine):
    """``sliding_attention._pair_model`` for a model whose forms have stacks
    and sinks of their own: ``build(params, x, pair)`` takes the attention
    halves of the layers ``pair`` = (sliding, full): the norm, ``wq`` and
    ``wo`` at the layers' own index, each form's ``wk`` / ``wv`` from that
    form's stack at the layer's ordinal among its form, the sinks of the
    layers whose form has them; a dense feed-forward of zeros behind each
    (which adds exactly 0), the engine's final norm and head; ``x`` stands
    in the embedding's place."""
    import jax.numpy as jnp

    mcfg = engine.cfg.model
    two = dataclasses.replace(
        mcfg, num_layers=2, layer_types=("sliding_attention", "full_attention"),
        num_experts=0, num_experts_held=0, first_expert_held=0, num_shared_experts=0,
        num_dense_layers=0, use_expert_bias=False, tie_word_embeddings=False,
    )
    assert two.kv_by_kind
    forms = mcfg.attn_forms
    # a layer's ordinal among the layers of its form, and among those with a sink
    nth = jnp.asarray([forms[:i].count(f) for i, f in enumerate(forms)], jnp.int32)
    nth_sink = jnp.asarray(
        [sum(f in mcfg.sink_forms for f in forms[:i]) for i in range(len(forms))], jnp.int32
    )

    def build(params, x, pair):
        src = params["layers"]
        d = x.shape[-1]
        x = x.astype(src["wq"].dtype)
        at = jnp.asarray(pair, jnp.int32)
        layers = {k: src[k][at] for k in ("attn_norm", "wq", "wo")}
        if mcfg.kv_heads_by_form:
            for k in ("wk", "wv"):
                layers["swa_" + k] = src["swa_" + k][nth[at[0]]][None]
                layers[k] = src[k][nth[at[1]]][None]
        else:
            layers.update((k, src[k][at]) for k in ("wk", "wv"))
        sinks = [
            src["sink"][nth_sink[at[i]]] for i, form in enumerate(("sliding", "full"))
            if form in mcfg.sink_forms
        ]
        if sinks:
            layers["sink"] = jnp.stack(sinks)
        layers["mlp_norm"] = jnp.ones((2, d), x.dtype)
        layers.update(
            w_gate=jnp.zeros((2, d, 128), x.dtype), w_up=jnp.zeros((2, d, 128), x.dtype),
            w_down=jnp.zeros((2, 128, d), x.dtype),
        )
        return {
            "embed": x, "layers": layers, "final_norm": params["final_norm"],
            "lm_head": params["lm_head"],
        }

    return two, build


@contextlib.contextmanager
def _this_model():
    """``sliding_attention``'s parts 2 and 3 over this module's two-layer
    model (they ask that module for it by name)."""
    plain = swa._pair_model
    swa._pair_model = _pair_model
    try:
        yield
    finally:
        swa._pair_model = plain


def pool_rows_part(ctx: dict, entering, pairs) -> dict:
    with _this_model():
        return swa.pool_rows_part(ctx, entering, pairs)


def long_part(ctx: dict, pairs, lengths=LONG, chunk: int = CHUNK) -> dict:
    with _this_model():
        return swa.long_part(ctx, pairs, lengths, chunk)


def compare(ctx: dict) -> dict:
    walk = correct.walk_reference(ctx, PROBES, whole_vocabulary=True)
    if "why" in walk:
        return {"ok": False, "why": walk["why"]}
    long = swa.long_walk(ctx)
    if "why" in long:
        return {"ok": False, "why": f"long probe: {long['why']}"}
    diffs = walk["step_diffs"]
    out = {
        "max_abs_logprob_diff": max(diffs), "tol": MEDIAN_TOL, "steps": len(diffs),
        "median_abs_logprob_diff": statistics.median(diffs),
        "p90_abs_logprob_diff": percentile(diffs, 90),
        "long_median_abs_logprob_diff": statistics.median(long["step_diffs"]),
        "long_max_abs_logprob_diff": max(long["step_diffs"]), "long_tol": LONG_MEDIAN_TOL,
        "long_prompt_tokens": len(long["sequences"][0]) - len(long["step_diffs"]),
        "long_mixed_dispatches": long.get("mixed_dispatches"),
        "long_live_beside": long.get("live_beside"),
        "block_tol": BLOCK_TOL, "near_tie": NEAR_TIE, "kv_tol": KV_TOL, "attn_tol": ATTN_TOL,
    }
    hf = ctx["hf"]
    pairs, routed_layers = layers_read(hf)
    every = sorted({a for a, _ in pairs} | set(routed_layers))
    states = [ctx["reference"].hidden_states(ctx["params"], hf, ids, every) for ids in walk["sequences"]]
    at = [every.index(l) for l in routed_layers]
    out.update(block_part(ctx, np.concatenate([b[at] for _e, b in states], axis=1), routed_layers))
    at = [every.index(a) for a, _ in pairs]
    out.update(pool_rows_part(ctx, [e[at] for e, _b in states], pairs))
    # No longer than the model's positions (a CPU rehearsal's 512), and a
    # test may name smaller sizes.
    room = int(hf.get("max_position_embeddings", max(LONG) + DECODE)) - DECODE
    lengths = ctx.get("long_tokens") or tuple(min(n, room // 8 * 8) for n in LONG)
    out.update(long_part(ctx, pairs, lengths, ctx.get("long_chunk", CHUNK)))
    out["step_diffs"] = [round(d, 5) for d in diffs]
    out["long_step_diffs"] = [round(d, 5) for d in long["step_diffs"]]
    faults = []
    if out["median_abs_logprob_diff"] > MEDIAN_TOL:
        faults.append(f"median |diff| {out['median_abs_logprob_diff']:.4f} > {MEDIAN_TOL}")
    if out["long_median_abs_logprob_diff"] > LONG_MEDIAN_TOL:
        faults.append(
            f"long probe: median |diff| {out['long_median_abs_logprob_diff']:.4f} > {LONG_MEDIAN_TOL}"
        )
    if out["unheld_max_abs"] > 0:
        faults.append(
            f"block: rows none of whose experts is held moved by {out['unheld_max_abs']:.3g}, not 0"
        )
    if out["pool_unwritten_max_abs"] > 0:
        faults.append(f"pool rows no token wrote hold {out['pool_unwritten_max_abs']:.3g}, not zeros")
    for part, key, tol, what in (
        ("block", "max_rel_err", BLOCK_TOL, "of what the block adds"),
        ("pool_rows", "max_rel_err", KV_TOL, "of the cached key's length"),
        ("pool_rows", "v_max_rel_err", KV_TOL, "of the cached value's length"),
        ("long", "max_rel_err", ATTN_TOL, "of the log-probabilities' spread"),
    ):
        bad = max(out[part], key=lambda c: c[key] if c[key] == c[key] else float("inf"))
        if not bad[key] <= tol:  # a NaN is a fault, not a pass
            faults.append(
                f"{part}: layer {bad['layer']} ({bad.get('tokens', bad['rows'])}) differs by "
                f"{bad[key]:.4f} {what} > {tol}"
            )
    out["ok"], out["why"] = not faults, "; ".join(faults) or None
    return out
