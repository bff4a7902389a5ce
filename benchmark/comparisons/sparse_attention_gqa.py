"""The comparison for a model whose GROUPED-QUERY attention reads a LEARNED
SELECTION of its context (``"comparison": "sparse_attention_gqa"``):
KeyeVL2. An indexer a layer scores every context position, its key cached
behind the token's keys in the K pool's row; a query attends the ``topk``
positions of largest index score and no others, 8 query heads a KV head over
one set; the feed-forward is 128 routed experts, all held, top 8 by softmax.

It is ``sparse_attention``'s comparison (that module's text has the
reasoning: the set is compared as a set, the arithmetic with the reference
GIVEN the engine's set), worded for this cache. The parts, all outside the
timed window, all needed for ``ok``:

1. THE EXPERT BLOCK of the first, middle and last layer at 64 and 1024 rows
   through ``models/llama.py::_ffn_block`` with the engine's own weights of
   that layer (the router over all 128, every expert), against the
   reference. Rows whose reference margin (the 8th of the 128 probabilities
   less the 9th, as a share of the 8th) is under ``NEAR_TIE`` are left out
   and counted. Number: the largest row of ``|engine - reference| /
   |reference - input|``.

2. THE CACHED ROWS after one prefill through ``forward_ragged`` (a one-layer
   model of the engine's own arrays, the reference's hidden states entering
   the layer): K after its norm and rotary (the K row's first ``Hkv x D``
   lanes), V, and the index key after LayerNorm and rotary (the K row's
   lanes behind the keys), each as the largest row of ``|engine -
   reference| / |reference|``; the index key's padding lanes must be exact
   zeros.

3. THE SELECTION ITSELF AT THE TIMED SIZES and 4. THE ATTENTION HALF WITH THE
   HEAD on those sequences, the reference GIVEN the engine's sets:
   ``sparse_attention.long_part`` as it stands (two sequences of 16,000 and
   4,096 seeded rows prefilled in chunks of 2,048 through ``forward_ragged``,
   then decoded tokens through ``forward``; the run's own kept sets,
   ``kept_of``, against the reference's ``lax.top_k``; every selecting row
   holding exactly ``topk``, the row at context ``topk + 1`` dropping
   exactly one), over this module's one-layer model.

5. THE ROTARY BY SECTIONS: the attention half with the head of layer 0 over
   one sequence of ``ROTARY_TOKENS`` seeded rows whose three position
   components DIFFER (temporal ``t``, height ``t // 7``, width ``t % 13``:
   what a tower's grid would bring) through ``forward(rope_positions=)``,
   against the reference given the same components; under the published
   ``topk``, so no row selects there and the number is the rotary's. All the traffic is text,
   whose components are equal and cannot tell the sections apart: this part
   is what holds ``mrope_section`` to its order. Number: part 4's.

6. THE SERVED PATH: ``sparse_attention``'s walk and long probe, each on the
   median of its steps' largest differences. The walk's median is held
   between the sound program's largest and what K or V rounded to float8 in
   the SERVED programs read; the long probe's cannot tell those (a dozen
   steps after thousands of prompt tokens) and is held against the faults of
   the selection it was shown to catch.

TOLERANCES, each between two readings on the chip beside the constants
below (PERF.md section 2 has the table): the sound program's largest over
the seeds read, and the smallest a control gave
(``rehearse/check_seeds_keye.py --control``).
"""
from __future__ import annotations

import dataclasses
import statistics

import numpy as np

from comparisons import sparse_attention as sparse
from comparisons.latent_attention import PROBES, SPAN_ALIGN, _engine_params, DECODE, LONG
from lib import correct
from lib.stats import percentile

# A row is left out of the block part when the reference's 8th and 9th router
# probabilities are nearer than this share of the 8th (``block_diffusion``'s
# reasoning and value: over 128 experts a probability is ~0.008 and what a
# bfloat16 input moves it by is a share of it).
NEAR_TIE = 0.01
SELECT_TIE = sparse.SELECT_TIE
# All readings my chip runs, PR 61 (rehearse/check_seeds_keye.py,
# keye-vl-2.0-30b-a3b-l5 on one v5e chip; PERF.md section 2 has the table
# with every control).
# Block part, largest row of |engine - reference| / |reference - input|.
BLOCK_TOL = 0.025
# K's and V's cached rows, largest row of |engine - reference| / |reference| over 512 values.
KV_ROW_TOL = 0.01
# The index key's cached row, the same over 64 values.
INDEX_KEY_TOL = 0.01
# The selection, smallest row of |engine and reference| / topk over the selecting rows.
SELECT_MIN_SHARE = 0.985
# Attention half with the head, the reference GIVEN the engine's selection: largest row of
# |engine - reference| over the log-probabilities relative to their spread.
ATTN_TOL = 0.015
# Served part: the median over the short probes' 32 steps. Sound 0.0216-0.0590 over 35
# seeds (mean 0.033, one reading over 0.05); K or V rounded to float8 as cached, planted
# before the server compiled so that the SERVED programs carry it (``check_seeds_keye.py
# --served-fault``): 0.0735, 0.0846 (K), 0.0845 (V). The two sides lie close: the limit
# leaves a sound run 19 % and the smallest fault 5 %. (Index keys so rounded read 0.0278
# there: parts 2 and 3 catch those.)
MEDIAN_TOL = 0.07
# The median over the long probe's 8 steps. Sound 0.0134-0.0613; float8 K or V in the
# served programs read 0.030-0.046 there and are NOT seen by it (the walk above is what
# sees them); no selection 0.134, half of ``topk`` 0.206, index lanes not rotary 0.314.
LONG_MEDIAN_TOL = 0.1

# Part 5: one sequence of this many rows (under the published ``topk``).
ROTARY_TOKENS = 512

_ATTN = ("wq", "wk", "wv", "wo", "q_norm", "k_norm", "attn_norm")


def layers_read(hf: dict) -> list[int]:
    """The layers read: first, middle, last (every layer is one kind)."""
    n = int(hf["num_hidden_layers"])
    return sorted({0, n // 2, n - 1})


def _one_layer_model(engine):
    """``sparse_attention._one_layer_model`` for grouped-query attention:
    ``build(params, x, layer)`` takes layer ``layer``'s attention, q/k norms,
    indexer and input norm, a dense feed-forward of zeros behind a norm of
    ones (which adds exactly 0), the engine's final norm and head; ``x``
    stands in the embedding's place."""
    import jax
    import jax.numpy as jnp

    from dynamo_exp_tpu.models import llama

    one = dataclasses.replace(
        engine.cfg.model, num_layers=1, num_experts=0, tie_word_embeddings=False,
    )
    names = (*_ATTN, *llama.INDEXER_PARAMS)

    def build(params, x, layer):
        src = params["layers"]
        d = x.shape[-1]
        layers = {k: jax.lax.dynamic_slice_in_dim(src[k], layer, 1, 0) for k in names}
        layers["mlp_norm"] = jnp.ones((1, d), x.dtype)
        layers.update(
            w_gate=jnp.zeros((1, d, 128), x.dtype), w_up=jnp.zeros((1, d, 128), x.dtype),
            w_down=jnp.zeros((1, 128, d), x.dtype),
        )
        return {
            "embed": x, "layers": layers, "final_norm": params["final_norm"],
            "lm_head": params["lm_head"],
        }

    return one, build


class _over_this_model:
    """``sparse_attention``'s functions over this module's one-layer model,
    margin and index top-k key: its module attributes swapped for the
    ``with`` block, as that module swaps ``latent_attention``'s."""

    def __init__(self, hf: dict):
        self.topk = int(hf["sa_config"]["topk"])

    def __enter__(self):
        self.plain = sparse._one_layer_model, sparse.NEAR_TIE
        sparse._one_layer_model, sparse.NEAR_TIE = _one_layer_model, NEAR_TIE
        return self

    def __exit__(self, *exc):
        sparse._one_layer_model, sparse.NEAR_TIE = self.plain


def cached_rows_part(ctx: dict, entering: list[np.ndarray], layers: list[int]) -> dict:
    """Part 2. ``entering``: for each probe sequence, [len(layers), T, D]:
    the reference's hidden state entering each layer."""
    import jax.numpy as jnp

    reference, engine, hf = ctx["reference"], ctx["engine"], ctx["hf"]
    kl = int(hf["num_key_value_heads"]) * int(hf["head_dim"])
    di = int(hf["sa_config"]["indexer_head_dim"])
    lengths = [e.shape[1] for e in entering]
    span = -(-max(lengths) // SPAN_ALIGN) * SPAN_ALIGN
    positions = np.concatenate([
        np.where(np.arange(span) < n, np.arange(span), -1) for n in lengths
    ]).astype(np.int32)
    with _over_this_model(hf):
        run, row_tokens = sparse.engine_prefill(engine, len(entering), span)
    cells = []
    for i, layer in enumerate(layers):
        x = np.zeros((len(entering), span, entering[0].shape[-1]), np.float32)
        for s, e in enumerate(entering):
            x[s, :lengths[s]] = e[i]
        x = jnp.asarray(x, engine.params["embed"].dtype)  # the stream's own type
        k_pool, v_pool = run(
            _engine_params(ctx), x.reshape(len(entering) * span, -1), jnp.asarray(positions), layer,
        )
        k_rows, v_rows = (
            np.asarray(p[0].astype(jnp.float32)).reshape(len(entering), row_tokens, -1)
            for p in (k_pool, v_pool)
        )
        err = {"k": [], "v": [], "index_key": []}
        pad = 0.0
        for s, n in enumerate(lengths):
            want_k, want_v, want_i = reference.pool_rows(ctx["params"], hf, layer, x[s, :n])
            for name, got, want in (
                ("k", k_rows[s, :n, :kl], want_k), ("v", v_rows[s, :n], want_v),
                ("index_key", k_rows[s, :n, kl : kl + di], want_i),
            ):
                err[name].extend(
                    np.linalg.norm(got - want, axis=1) / np.linalg.norm(want, axis=1)
                )
            pad = max(pad, float(np.abs(k_rows[s, :n, kl + di :]).max(initial=0.0)))
        cells.append({
            "layer": layer, "rows": len(err["k"]), "padding_max_abs": pad,
            **{f"{name}_max_rel_err": float(max(e)) for name, e in err.items()},
            **{f"{name}_median_rel_err": float(np.median(e)) for name, e in err.items()},
        })
    return {
        "cached_rows": cells,
        "k_rows_max_rel_err": max(c["k_max_rel_err"] for c in cells),
        "v_rows_max_rel_err": max(c["v_max_rel_err"] for c in cells),
        "index_keys_max_rel_err": max(c["index_key_max_rel_err"] for c in cells),
        "pool_padding_max_abs": max(c["padding_max_abs"] for c in cells),
    }


def long_part(ctx: dict, layers: list[int], lengths=LONG) -> dict:
    """Parts 3 and 4: ``sparse_attention.long_part`` over this model, which
    reads ``index_topk`` of the configuration's keys."""
    hf = ctx["hf"]
    with _over_this_model(hf) as me:
        return sparse.long_part({**ctx, "hf": {**hf, "index_topk": me.topk}}, layers, lengths)


def rotary_part(ctx: dict, layer: int = 0) -> dict:
    """Part 5."""
    import jax
    import jax.numpy as jnp

    from comparisons.latent_attention import long_inputs
    from comparisons.layer_kinds import _attention_form
    from dynamo_exp_tpu.models import llama

    reference, engine, hf = ctx["reference"], ctx["engine"], ctx["hf"]
    impl, interpret, _tile, page, kv_dtype = _attention_form(engine)
    n = min(ROTARY_TOKENS, int(hf.get("max_position_embeddings", ROTARY_TOKENS))) // page * page
    t = np.arange(n)
    pos3 = np.stack([t, t // 7, t % 13]).astype(np.int32)
    x = jnp.asarray(long_inputs(ctx, (n - 8,))[0][:n], engine.params["embed"].dtype)
    one, build = _one_layer_model(engine)
    table = jnp.arange(n // page, dtype=jnp.int32)[None]

    @jax.jit
    def run(params, x, pos3):
        k, v = llama.init_kv_cache(one, n // page, page, dtype=kv_dtype)
        logits, *_ = llama.forward(
            build(params, x, layer), one, jnp.arange(n, dtype=jnp.int32)[None],
            jnp.asarray(t, jnp.int32)[None], table, k, v, attn_impl=impl, mesh=engine.mesh,
            interpret=interpret, rope_positions=pos3[:, None],
        )
        return jax.nn.log_softmax(logits[0], axis=-1)

    have = np.asarray(run(_engine_params(ctx), x, jnp.asarray(pos3)))
    want = reference.op_half_and_head(ctx["params"], hf, layer, x, positions=pos3)
    spread = want - want.mean(axis=-1, keepdims=True)
    err = np.linalg.norm(have - want, axis=-1) / np.linalg.norm(spread, axis=-1)
    return {
        "rotary_tokens": n, "rotary_max_rel_err": float(err.max()),
        "rotary_median_rel_err": float(np.median(err)),
    }


def compare(ctx: dict) -> dict:
    walk = correct.walk_reference(ctx, PROBES, whole_vocabulary=True)
    if "why" in walk:
        return {"ok": False, "why": walk["why"]}
    long = sparse.long_walk(ctx)
    if "why" in long:
        return {"ok": False, "why": f"long probe: {long['why']}"}
    diffs = walk["step_diffs"]
    hf = ctx["hf"]
    topk = int(hf["sa_config"]["topk"])
    out = {
        "max_abs_logprob_diff": max(diffs), "tol": MEDIAN_TOL, "steps": len(diffs),
        "median_abs_logprob_diff": statistics.median(diffs),
        "p90_abs_logprob_diff": percentile(diffs, 90),
        "long_median_abs_logprob_diff": statistics.median(long["step_diffs"]),
        "long_max_abs_logprob_diff": max(long["step_diffs"]), "long_tol": LONG_MEDIAN_TOL,
        "long_prompt_tokens": len(long["sequences"][0]) - len(long["step_diffs"]),
        "long_mixed_dispatches": long.get("mixed_dispatches"),
        "long_live_beside": long.get("live_beside"),
        "block_tol": BLOCK_TOL, "near_tie": NEAR_TIE, "kv_row_tol": KV_ROW_TOL,
        "index_key_tol": INDEX_KEY_TOL, "select_min_share": SELECT_MIN_SHARE,
        "select_tie": SELECT_TIE, "attn_tol": ATTN_TOL,
    }
    layers = layers_read(hf)
    states = [ctx["reference"].hidden_states(ctx["params"], hf, ids, layers) for ids in walk["sequences"]]
    with _over_this_model(hf):
        out.update(sparse.block_part(ctx, np.concatenate([b for _e, b in states], axis=1), layers))
    out.update(cached_rows_part(ctx, [e for e, _b in states], layers))
    # No longer than the model's positions (a CPU rehearsal's 512).
    room = int(hf.get("max_position_embeddings", max(LONG) + DECODE)) - DECODE
    out.update(long_part(ctx, layers, tuple(min(n, room // 8 * 8) for n in LONG)))
    out.update(rotary_part(ctx))
    out["step_diffs"] = [round(d, 5) for d in diffs]
    out["long_step_diffs"] = [round(d, 5) for d in long["step_diffs"]]
    faults = []
    if out["median_abs_logprob_diff"] > MEDIAN_TOL:
        faults.append(f"median |diff| {out['median_abs_logprob_diff']:.4f} > {MEDIAN_TOL}")
    if out["long_median_abs_logprob_diff"] > LONG_MEDIAN_TOL:
        faults.append(
            f"long probe: median |diff| {out['long_median_abs_logprob_diff']:.4f} > {LONG_MEDIAN_TOL}"
        )
    chunks = -(-out["long_prompt_tokens"] // int(ctx["engine"].cfg.prefill_chunk))
    if out["long_mixed_dispatches"] is not None and out["long_mixed_dispatches"] < min(3, chunks):
        faults.append(
            f"long probe: its prompt took {out['long_mixed_dispatches']} mixed dispatches, "
            f"fewer than {min(3, chunks)}"
        )
    if out["pool_padding_max_abs"] > 0:
        faults.append(
            f"the index key's padding lanes hold {out['pool_padding_max_abs']:.3g}, not zeros"
        )
    for part, key, tol, what in (
        ("block", "max_rel_err", BLOCK_TOL, "of what the block adds"),
        ("cached_rows", "k_max_rel_err", KV_ROW_TOL, "of the cached keys' length"),
        ("cached_rows", "v_max_rel_err", KV_ROW_TOL, "of the cached values' length"),
        ("cached_rows", "index_key_max_rel_err", INDEX_KEY_TOL, "of the cached index key's length"),
        ("long", "max_rel_err", ATTN_TOL, "of the log-probabilities' spread"),
    ):
        bad = max(out[part], key=lambda c: c[key])
        if bad[key] > tol:
            faults.append(
                f"{part}: layer {bad['layer']} ({bad.get('tokens', bad['rows'])}) differs by "
                f"{bad[key]:.4f} {what} > {tol}"
            )
    bad = min(out["long"], key=lambda c: c["min_selection_share"])
    if bad["min_selection_share"] < SELECT_MIN_SHARE:
        faults.append(
            f"selection: layer {bad['layer']} ({bad['tokens']}) shares "
            f"{bad['min_selection_share']:.4f} of a row's {topk} positions with the "
            f"reference's < {SELECT_MIN_SHARE}"
        )
    for c in out["long"]:
        if not (c["selecting_rows_keep_topk"] and c["unselecting_rows_keep_all"]):
            faults.append(
                f"selection: layer {c['layer']} ({c['tokens']}): a row's set is not "
                f"min({topk}, t + 1) positions"
            )
        if c["dropped_at_topk_plus_1"] not in (None, 1):
            faults.append(
                f"selection: layer {c['layer']} ({c['tokens']}): the row at context "
                f"{topk + 1} dropped {c['dropped_at_topk_plus_1']}, not 1"
            )
    if out["rotary_max_rel_err"] > ATTN_TOL:
        faults.append(
            f"rotary by sections: {out['rotary_tokens']} tokens of unequal components differ by "
            f"{out['rotary_max_rel_err']:.4f} of the log-probabilities' spread > {ATTN_TOL}"
        )
    if out["selection_near_tie_share"] >= 0.05:
        faults.append(
            f"selection: {out['selection_near_tie_share']:.3f} of the selecting rows left out as ties"
        )
    out["ok"], out["why"] = not faults, "; ".join(faults) or None
    return out
