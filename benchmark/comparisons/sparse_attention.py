"""The comparison for a model whose latent attention reads a LEARNED
SELECTION of its context (``"comparison": "sparse_attention"``):
glm_moe_dsa. An indexer a layer scores every context position, its keys
cached in a second page pool; a query attends the ``index_topk`` positions
of largest index score and no others; the feed-forward is a held share of
routed experts chosen through a selection bias, beside a shared expert.

It keeps ``latent_attention``'s reasoning (that module's text: the
arithmetic is compared half a layer at a time on the SAME bfloat16 input,
and the served path is judged on its median). What a selection adds to the
question "does the served path compute the model": a query's output now
depends on a SET, decided by 16,000 scores of which the 2,048th and the
2,049th lie a thousandth of their spread apart, so bfloat16's rounding of
the index keys rightly moves a handful of positions across the threshold
on either side. A precision limit wide enough to cover those flips would
hide real faults; so the set is compared as a set, and the arithmetic with
the reference GIVEN the engine's set. The parts, all outside the timed
window, all needed for ``ok``:

1. THE EXPERT BLOCK of the first, a middle and the last routed layer at 64
   and 1024 rows through ``models/llama.py::_ffn_block`` with the engine's
   own weights of that layer (the router over all 256 with its selection
   bias, the 16 held experts, the shared expert), against the reference
   given the same share. Rows whose reference margin (the 8th of the 256
   biased scores less the 9th) is under ``NEAR_TIE`` are left out and
   counted. Number: the largest row of ``|engine - reference| / |reference
   - input|``.

2. BOTH POOLS' ROWS after one prefill through ``forward_ragged`` (a
   one-layer model of the engine's own arrays, the reference's hidden
   states entering the layer): the latent rows ``[c_kv after its norm | k_r
   after RoPE]`` and the index keys ``LayerNorm(u W_Ik)`` with their first
   64 lanes rotated, each as the largest row of ``|engine - reference| /
   |reference|``; padding lanes must be exact zeros.

3. THE SELECTION ITSELF AT THE TIMED SIZES: two sequences of ``LONG`` =
   (16,000, 4,096) seeded embedding rows prefilled in chunks of 2,048
   through ``forward_ragged`` (both rows' chunks in one stream, the scores
   kernel and the masked attention kernel in tiles of 8, every chunk's
   queries selecting among index keys earlier dispatches wrote), then
   ``DECODE`` tokens one at a time through ``forward`` (both kernels'
   one-query form). For the compared rows of each layer (``head_rows``, the
   row at context exactly ``index_topk + 1``, every decoded token) the set
   THAT RUN's selection kept, an output of the dispatch itself
   (``forward_ragged`` / ``forward`` with ``kept_of``: the scores kernel's
   tile, the bisection's threshold and the tie rule as the attention kernel
   is given them, read by ``ops/sparse_index.py::kept``; nothing is
   computed a second time: a copy of the selection from separately compiled
   projections differs from the run's by a float32 rounding of a score now
   and then, which moves a position across the threshold and the reference
   GIVEN that copy off the engine by one value vector in 2,048),
   against the reference's ``lax.top_k`` over float32 scores. Number: the
   smallest row of ``|engine and reference| / max(|engine|, |reference|)``
   over the rows that select; a row whose reference margin is under ``SELECT_TIE`` is
   left out and counted (an exact tie in float32: none is expected); every
   selecting row must hold exactly ``index_topk`` positions and the row at
   context ``index_topk + 1`` drop exactly one.

4. THE ATTENTION HALF WITH THE HEAD on those sequences, the reference GIVEN
   the engine's selection of part 3 in place of its own: the engine's
   logits of the same run at those rows against the reference's expanded
   form over the selected positions, prefilled and decoded rows apart.
   Number: the largest row of ``|engine - reference|`` over the
   log-probabilities relative to their spread. The selection's flips are
   not in it, so it stands where ``latent_attention``'s stands.

5. THE SERVED PATH: ``logprob_walk``'s walk over four probes (32 decode
   steps) and one long probe of ``LONG_PROBE_TOKENS`` asked while other
   requests decode beside it (at least three mixed dispatches; its last
   prompt tokens and every decoded one select), each on the median of its
   steps' largest differences: the wiring (scheduler, page tables over two
   pools, the windows, cached pages), not a precision check.

TOLERANCES, each between two readings on the chip beside the constants
below (PERF.md section 2 has the table): the sound program's largest over
the seeds read, and the smallest a control gave
(``rehearse/check_seeds_glm5.py --control``).
"""
from __future__ import annotations

import dataclasses
import statistics

import numpy as np

from comparisons import latent_attention as latent
from comparisons.latent_attention import (  # noqa: F401
    BLOCK_ROWS, CHUNK, DECODE, LONG, PROBES, SPAN_ALIGN, _engine_params, head_rows,
    layers_read, long_inputs,
)
from comparisons.layer_kinds import _attention_form
from lib import correct
from lib.stats import percentile

LONG_PROBE_TOKENS = 3000  # part 5's long probe: six chunks of 512, ~950 selecting queries
LIVE_BESIDE = 3
BESIDE_TOKENS = 1024

# A row is left out of the block part when the reference's 8th and 9th
# biased router scores are nearer than this (``latent_attention.NEAR_TIE``'s
# reasoning: the engine's router reads the normed row rounded to bfloat16).
NEAR_TIE = 0.004
# A row is left out of the selection part when the reference's own 2,048th
# and 2,049th index scores (float32) are nearer than this: an exact tie,
# where the tie rule and not the arithmetic decides.
SELECT_TIE = 1e-9
# All readings my chip runs, PR 47 (rehearse/check_seeds_glm5.py, glm-5-l5e16 on one v5e chip;
# PERF.md section 2 has the table with every control).
# Block part, largest row of |engine - reference| / |reference - input|.
BLOCK_TOL = 0.025
# The latent pool's rows, largest row of |engine - reference| / |reference| over 576 values.
LATENT_TOL = 0.01
# The index pool's rows, the same over 128 values.
INDEX_KEY_TOL = 0.01
# The selection, smallest row of |engine and reference| / index_topk over the selecting rows.
SELECT_MIN_SHARE = 0.97
# Attention half with the head, the reference GIVEN the engine's selection: largest row of
# |engine - reference| over the log-probabilities relative to their spread.
ATTN_TOL = 0.015
# Served part: the median over the short probes' 32 steps, and over the long probe's 8.
MEDIAN_TOL = 0.1
LONG_MEDIAN_TOL = 0.1

_FFN = ("router", "router_bias", "shared_gate", "shared_up", "shared_down")


# ------------------------------------------------------------ part 1: block
def engine_block(engine):
    """``latent_attention.engine_block`` for a layer of two norms whose
    router has a selection bias."""
    import functools

    import jax

    from dynamo_exp_tpu.models import llama

    mcfg, mesh = engine.cfg.model, engine.mesh
    interpret = bool(getattr(engine, "_attn_interpret", False))

    @functools.partial(jax.jit, static_argnums=2)
    def run(layers, x, layer):
        nth = mcfg.ff_kinds[:layer].count("routed")
        lp = {"mlp_norm": layers["mlp_norm"][layer]}
        lp.update((k, layers[k][nth]) for k in _FFN if k in layers)
        lp.update((k, layers[k][nth]) for k in llama.EXPERT_STACKS)
        return llama._ffn_block(
            x, lp, mcfg, mcfg.rms_norm_eps, lambda y: y, mesh, interpret=interpret
        )

    return run


def block_part(ctx: dict, before_ffn: np.ndarray, layers: list[int]) -> dict:
    """Part 1: ``latent_attention.block_part`` with this module's block and
    margin."""
    plain, tie = latent.engine_block, latent.NEAR_TIE
    latent.engine_block, latent.NEAR_TIE = engine_block, NEAR_TIE
    try:
        return latent.block_part(ctx, before_ffn, layers)
    finally:
        latent.engine_block, latent.NEAR_TIE = plain, tie


# ------------------------------------------------- the one-layer model, 2 to 4
def _one_layer_model(engine):
    """``latent_attention._one_layer_model`` with the indexer's tensors and
    two norms: ``build(params, x, layer)`` takes layer ``layer``'s latent
    attention, indexer and input norm, a dense feed-forward of zeros behind
    a norm of ones (which adds exactly 0), the engine's final norm and
    head; ``x`` stands in the embedding's place."""
    import jax
    import jax.numpy as jnp

    from dynamo_exp_tpu.models import llama

    mcfg = engine.cfg.model
    one = dataclasses.replace(
        mcfg, num_layers=1, num_experts=0, num_experts_held=0, first_expert_held=0,
        num_shared_experts=0, num_dense_layers=0, tie_word_embeddings=False,
        use_expert_bias=False,
    )
    names = (*llama.LATENT_PARAMS, *llama.INDEXER_PARAMS, "wo", "attn_norm")

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


def engine_prefill(engine, n_rows: int, span: int):
    """``run(params, x, positions, layer)``: the mixed program's forward over
    one layer, a whole probe a row of ``span`` slots. Returns both pools."""
    import jax
    import jax.numpy as jnp

    from dynamo_exp_tpu.models import llama

    impl, interpret, q_tile, page, kv_dtype = _attention_form(engine)
    if span % q_tile:
        raise ValueError(f"a row's span {span} is no multiple of the kernel's q_tile {q_tile}")
    one, build = _one_layer_model(engine)
    pages_a_row = -(-span // page)
    n = n_rows * span
    row_of = jnp.repeat(jnp.arange(n_rows, dtype=jnp.int32), span)
    table = jnp.arange(n_rows * pages_a_row, dtype=jnp.int32).reshape(n_rows, pages_a_row)

    @jax.jit
    def run(params, x, positions, layer):
        k, v = llama.init_kv_cache(one, n_rows * pages_a_row, page, dtype=kv_dtype)
        every = jnp.arange(n, dtype=jnp.int32)
        _, k, v = llama.forward_ragged(
            build(params, x, layer), one, every, positions, row_of, table, k, v, every[:1],
            attn_impl=impl, q_tile=q_tile, mesh=engine.mesh, interpret=interpret,
        )
        return k, v

    return run, pages_a_row * page


def pool_rows_part(ctx: dict, entering: list[np.ndarray], layers: list[int]) -> dict:
    """Part 2. ``entering``: for each probe sequence, [len(layers), T, D]:
    the reference's hidden state entering each layer."""
    import jax.numpy as jnp

    reference, engine, hf = ctx["reference"], ctx["engine"], ctx["hf"]
    widths = (int(hf["kv_lora_rank"]) + int(hf["qk_rope_head_dim"]), int(hf["index_head_dim"]))
    lengths = [e.shape[1] for e in entering]
    span = -(-max(lengths) // SPAN_ALIGN) * SPAN_ALIGN
    positions = np.concatenate([
        np.where(np.arange(span) < n, np.arange(span), -1) for n in lengths
    ]).astype(np.int32)
    run, row_tokens = engine_prefill(engine, len(entering), span)
    cells = []
    for i, layer in enumerate(layers):
        x = np.zeros((len(entering), span, entering[0].shape[-1]), np.float32)
        for s, e in enumerate(entering):
            x[s, :lengths[s]] = e[i]
        x = jnp.asarray(x, ctx["engine"].params["embed"].dtype)  # the stream's own type
        pools = run(
            _engine_params(ctx), x.reshape(len(entering) * span, -1), jnp.asarray(positions), layer,
        )
        held = [
            np.asarray(p[0].astype(jnp.float32)).reshape(len(entering), row_tokens, -1)
            for p in pools
        ]
        err, pad = ([], []), 0.0
        for s, n in enumerate(lengths):
            wants = reference.pool_rows(ctx["params"], hf, layer, x[s, :n])
            for which, (want, width) in enumerate(zip(wants, widths)):
                got = held[which][s, :n]
                err[which].extend(
                    np.linalg.norm(got[:, :width] - want, axis=1) / np.linalg.norm(want, axis=1)
                )
                pad = max(pad, float(np.abs(got[:, width:]).max(initial=0.0)))
        cells.append({
            "layer": layer, "rows": len(err[0]), "max_rel_err": float(max(err[0])),
            "median_rel_err": float(np.median(err[0])),
            "index_key_max_rel_err": float(max(err[1])),
            "index_key_median_rel_err": float(np.median(err[1])), "padding_max_abs": pad,
        })
    return {
        "pool_rows": cells,
        "latent_rows_max_rel_err": max(c["max_rel_err"] for c in cells),
        "index_keys_max_rel_err": max(c["index_key_max_rel_err"] for c in cells),
        "pool_padding_max_abs": max(c["padding_max_abs"] for c in cells),
    }


# ------------------------------------------------ parts 3 and 4: the timed sizes
def compared_rows(length: int, topk: int, chunk: int, tile: int) -> list[int]:
    """``head_rows`` and the row at context exactly ``topk + 1``."""
    rows = set(head_rows(length, chunk, tile))
    if length > topk:
        rows.add(topk)
    return sorted(rows)


def engine_long(engine, lengths, chunk: int = CHUNK):
    """``run(params, xs, layer)``: the sequences ``xs`` ([length + DECODE, D]
    bfloat16 each) through one layer as the timed path runs it
    (``latent_attention.engine_long``'s schedule: a chunk a row a dispatch
    through ``forward_ragged``, then ``DECODE`` steps through ``forward``).
    Returns for each sequence the logits at its compared rows and decoded
    positions, and which positions each of those rows' selection kept
    ([rows, length + DECODE] bool) IN THAT RUN (``kept_of``)."""
    import jax
    import jax.numpy as jnp

    from dynamo_exp_tpu.models import llama

    impl, interpret, q_tile, page, kv_dtype = _attention_form(engine)
    one, build = _one_layer_model(engine)
    n_rows = len(lengths)
    pages_a_row = [-(-(n + DECODE) // page) for n in lengths]
    starts = np.concatenate([[0], np.cumsum(pages_a_row)])[:-1]
    table = np.zeros((n_rows, max(pages_a_row)), np.int32)
    for r, (s, n) in enumerate(zip(starts, pages_a_row)):
        table[r, :n] = np.arange(s, s + n)
    table = jnp.asarray(table)
    offsets = np.concatenate([[0], np.cumsum([n + DECODE for n in lengths])])[:-1]
    wanted = [compared_rows(n, one.index_topk, chunk, q_tile) for n in lengths]
    every = jnp.arange(n_rows, dtype=jnp.int32)

    @jax.jit
    def prefill(model, k, v, tokens, positions, row_of, out_idx):
        logits, k, v, kept = llama.forward_ragged(
            model, one, tokens, positions, row_of, table, k, v, out_idx,
            attn_impl=impl, q_tile=q_tile, mesh=engine.mesh, interpret=interpret,
            kept_of=out_idx,
        )
        return logits, k, v, kept[0]

    @jax.jit
    def step(model, k, v, tokens, positions):
        logits, k, v, kept = llama.forward(
            model, one, tokens[:, None], positions[:, None], table, k, v,
            attn_impl=impl, mesh=engine.mesh, interpret=interpret, kept_of=every,
        )
        return logits[:, 0], k, v, kept[0]

    def run(params, xs, layer):
        flat = jnp.concatenate(xs)
        model = build(params, flat, layer)
        k, v = llama.init_kv_cache(one, int(sum(pages_a_row)), page, dtype=kv_dtype)
        got, kept = [[] for _ in lengths], [[] for _ in lengths]
        for a in range(0, max(lengths), chunk):
            tokens, positions, row_of, out_idx, owners = [], [], [], [], []
            for r, n in enumerate(lengths):
                b = min(a + chunk, n)
                if b <= a:
                    continue
                pad = -(-(b - a) // q_tile) * q_tile - (b - a)
                base = len(tokens)
                tokens += list(range(offsets[r] + a, offsets[r] + b)) + [0] * pad
                positions += list(range(a, b)) + [-1] * pad
                row_of += [r] * (b - a + pad)
                mine = [p for p in wanted[r] if a <= p < b]
                out_idx += [base + p - a for p in mine]
                owners += [r] * len(mine)
            logits, k, v, sets = prefill(
                model, k, v, jnp.asarray(tokens, jnp.int32), jnp.asarray(positions, jnp.int32),
                jnp.asarray(row_of, jnp.int32), jnp.asarray(out_idx, jnp.int32),
            )
            logits, sets = np.asarray(logits), np.asarray(sets)
            for i, r in enumerate(owners):
                got[r].append(logits[i])
                kept[r].append(sets[i, : lengths[r] + DECODE])
        for t in range(DECODE):
            logits, k, v, sets = step(
                model, k, v,
                jnp.asarray([offsets[r] + n + t for r, n in enumerate(lengths)], jnp.int32),
                jnp.asarray([n + t for n in lengths], jnp.int32),
            )
            logits, sets = np.asarray(logits), np.asarray(sets)
            for r, n in enumerate(lengths):
                got[r].append(logits[r])
                kept[r].append(sets[r, : n + DECODE])
        return [np.stack(g) for g in got], [np.stack(s) for s in kept]

    return run, wanted


def long_part(ctx: dict, layers: list[int], lengths=LONG, chunk: int = CHUNK) -> dict:
    """Parts 3 and 4."""
    import jax
    import jax.numpy as jnp

    reference, engine, hf = ctx["reference"], ctx["engine"], ctx["hf"]
    topk = int(hf["index_topk"])
    xs = [
        jnp.asarray(x, ctx["engine"].params["embed"].dtype) for x in long_inputs(ctx, lengths)
    ]
    run, wanted = engine_long(engine, lengths, chunk)
    cells = []
    for layer in layers:
        got, kept = run(_engine_params(ctx), xs, layer)
        for r, n in enumerate(lengths):
            rows = np.asarray(wanted[r] + list(range(n, n + DECODE)))
            own = reference.attn_rows_and_head(ctx["params"], hf, layer, xs[r], rows)
            given = reference.attn_rows_and_head(
                ctx["params"], hf, layer, xs[r], rows, selection=kept[r],
            )
            want = given["log_probs"]
            have = np.asarray(jax.nn.log_softmax(jnp.asarray(got[r]), axis=-1))
            spread = want - want.mean(axis=-1, keepdims=True)
            err = np.linalg.norm(have - want, axis=-1) / np.linalg.norm(spread, axis=-1)
            split = len(wanted[r])
            causal = np.arange(n + DECODE)[None] <= rows[:, None]
            mine, theirs = kept[r] & causal, own["keep"]
            selecting = rows >= topk
            sizes = mine.sum(axis=1)
            share = (mine & theirs).sum(axis=1) / np.maximum(
                np.maximum(sizes, theirs.sum(axis=1)), 1
            )
            tie = selecting & (own["margin"] < SELECT_TIE)
            judged = selecting & ~tie
            at_edge = rows == topk
            cells.append({
                "layer": layer, "tokens": n, "rows": len(rows),
                "max_rel_err": float(err.max()), "median_rel_err": float(np.median(err)),
                "max_rel_err_prefill": float(err[:split].max()),
                "max_rel_err_decode": float(err[split:].max()),
                "rows_selecting": int(selecting.sum()), "rows_near_tie": int(tie.sum()),
                "min_selection_share": float(share[judged].min()) if judged.any() else 1.0,
                "median_selection_share": float(np.median(share[judged])) if judged.any() else 1.0,
                "min_selection_share_decode":
                    float(share[split:][judged[split:]].min()) if judged[split:].any() else 1.0,
                "unselecting_rows_keep_all": bool(
                    (sizes[~selecting] == rows[~selecting] + 1).all()
                ),
                "selecting_rows_keep_topk": bool((sizes[selecting] == topk).all()),
                "dropped_at_topk_plus_1": int(rows[at_edge][0] + 1 - sizes[at_edge][0])
                if at_edge.any() else None,
            })
    selecting = sum(c["rows_selecting"] for c in cells)
    return {
        "long": cells, "long_max_rel_err": max(c["max_rel_err"] for c in cells),
        "min_selection_share": min(c["min_selection_share"] for c in cells),
        "median_selection_share": float(np.median([c["median_selection_share"] for c in cells])),
        "selection_rows": selecting,
        "selection_near_tie_share":
            sum(c["rows_near_tie"] for c in cells) / selecting if selecting else 0.0,
    }


# ------------------------------------------------------------ part 5: served
def long_walk(ctx: dict) -> dict:
    """``linear_attention.long_walk`` at ``LONG_PROBE_TOKENS``, or as many
    as the served model length leaves room for (a CPU rehearsal's 512)."""
    from comparisons import linear_attention as olmo

    room = int(ctx["engine"].cfg.max_model_len) - 128 if ctx.get("engine") else LONG_PROBE_TOKENS
    plain = olmo.LONG_PROBE_TOKENS, olmo.LIVE_BESIDE, olmo.BESIDE_TOKENS
    olmo.LONG_PROBE_TOKENS, olmo.LIVE_BESIDE, olmo.BESIDE_TOKENS = (
        min(LONG_PROBE_TOKENS, room), LIVE_BESIDE, min(BESIDE_TOKENS, room),
    )
    try:
        return olmo.long_walk(ctx)
    finally:
        olmo.LONG_PROBE_TOKENS, olmo.LIVE_BESIDE, olmo.BESIDE_TOKENS = plain


def compare(ctx: dict) -> dict:
    walk = correct.walk_reference(ctx, PROBES, whole_vocabulary=True)
    if "why" in walk:
        return {"ok": False, "why": walk["why"]}
    long = long_walk(ctx)
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
        "block_tol": BLOCK_TOL, "near_tie": NEAR_TIE, "latent_tol": LATENT_TOL,
        "index_key_tol": INDEX_KEY_TOL, "select_min_share": SELECT_MIN_SHARE,
        "select_tie": SELECT_TIE, "attn_tol": ATTN_TOL,
    }
    hf = ctx["hf"]
    attn_layers, routed_layers = layers_read(hf)
    every = sorted(set(attn_layers) | set(routed_layers))
    states = [ctx["reference"].hidden_states(ctx["params"], hf, ids, every) for ids in walk["sequences"]]
    at = [every.index(l) for l in routed_layers]
    out.update(block_part(ctx, np.concatenate([b[at] for _e, b in states], axis=1), routed_layers))
    at = [every.index(l) for l in attn_layers]
    out.update(pool_rows_part(ctx, [e[at] for e, _b in states], attn_layers))
    # No longer than the model's positions (a CPU rehearsal's 512).
    room = int(hf.get("max_position_embeddings", max(LONG) + DECODE)) - DECODE
    out.update(long_part(ctx, attn_layers, tuple(min(n, room // 8 * 8) for n in LONG)))
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
        faults.append(f"the pools' padding lanes hold {out['pool_padding_max_abs']:.3g}, not zeros")
    for part, key, tol, what in (
        ("block", "max_rel_err", BLOCK_TOL, "of what the block adds"),
        ("pool_rows", "max_rel_err", LATENT_TOL, "of the cached latent row's length"),
        ("pool_rows", "index_key_max_rel_err", INDEX_KEY_TOL, "of the cached index key's length"),
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
            f"{bad['min_selection_share']:.4f} of a row's {hf['index_topk']} positions with the "
            f"reference's < {SELECT_MIN_SHARE}"
        )
    for c in out["long"]:
        if not (c["selecting_rows_keep_topk"] and c["unselecting_rows_keep_all"]):
            faults.append(
                f"selection: layer {c['layer']} ({c['tokens']}): a row's set is not "
                f"min({hf['index_topk']}, t + 1) positions"
            )
        if c["dropped_at_topk_plus_1"] not in (None, 1):
            faults.append(
                f"selection: layer {c['layer']} ({c['tokens']}): the row at context "
                f"{int(hf['index_topk']) + 1} dropped {c['dropped_at_topk_plus_1']}, not 1"
            )
    if out["selection_near_tie_share"] >= 0.05:
        faults.append(
            f"selection: {out['selection_near_tie_share']:.3f} of the selecting rows left out as ties"
        )
    out["ok"], out["why"] = not faults, "; ".join(faults) or None
    return out
