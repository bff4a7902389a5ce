"""The comparison for a model whose attention is multi-head latent attention
over one latent page pool and whose feed-forward is a held share of routed
experts beside a shared expert (``"comparison": "latent_attention"``):
pangu_ultra_moe.

It keeps ``routed_experts``'s reasoning (that module's text: a routed
model's served walk cannot hold the precision, because a bfloat16 residual
stream sends a few tokens to other experts than the float32 reference does,
both rightly; so the arithmetic is compared half a layer at a time on the
SAME bfloat16 input, and the served path is judged on its median). What a
latent cache adds to the question "does the served path compute the model":
the program never builds the keys and values the model's text speaks of. It
caches 576 values a token where the text has 128 heads of 192 | 128, scores
against them through a reordered product (the absorbed form) in a kernel
told that its values are lanes of its keys, and does so for a 16,000-token
prompt in chunks whose queries meet latents that earlier dispatches wrote.
So the parts are, all outside the timed window, all needed for ``ok``:

1. THE EXPERT BLOCK of the first, a middle and the last routed layer, at 64
   rows ([64, 1, D], a decode bucket) and 1024 rows ([1, 1024, D], a mixed
   dispatch: the held rows walked in place), through
   ``models/llama.py::_ffn_block`` with the engine's own weights of that
   layer (the router over all 256, the 16 held experts, the shared expert,
   ``post_mlp_layernorm``), against the reference given the same share.
   Rows whose reference margin (the 8th of the 256 scores less the 9th) is
   under ``NEAR_TIE`` are left out and counted. Number: the largest row of
   ``|engine - reference| / |reference - input|``. A pick weighed over the
   held picks alone, a swapped expert or a shared expert left out is of
   order 1.

2. THE POOL'S OWN ROWS: what the first, a middle and the last layer's cache
   holds of the probe sequences after one prefill through
   ``forward_ragged`` (a one-layer model of the engine's own arrays, the
   hidden states entering the layer the reference's), against the
   reference's ``[c_kv after its norm | k_r after RoPE]``. Number: the
   largest row of ``|engine - reference| / |reference|`` over the 576
   values; the padding lanes must be exact zeros. A latent cached before
   its norm or a rotary key cached before RoPE is of order 1; a latent
   rounded once more than bfloat16 is what ``LATENT_TOL`` stands under.

3. THE LATENT HALF WITH THE HEAD AT THE TIMED SIZES: the first, a middle
   and the last layer's first half over a one-layer model (that layer's
   seven attention tensors and two norms, a feed-forward of zeros, the
   engine's final norm and head), for two sequences of ``LONG`` = (16,000,
   4,096) tokens whose inputs are seeded embedding rows (the same bfloat16
   rows on both sides): prefill in chunks of ``CHUNK`` = 2,048 through
   ``forward_ragged`` (both rows' chunks in one stream, the attention
   kernel in tiles at 128 heads, eight dispatches whose queries read
   latents the earlier ones wrote), then ``DECODE`` tokens one at a time
   through ``forward`` (the kernel's one-query form, a decode window's),
   against the reference's EXPANDED form over the whole sequence, computed
   for the compared rows alone a block of heads at a time. Number: the
   largest row of ``|engine - reference|`` over the log-probabilities
   relative to their spread, at ``head_rows`` (each chunk's first tile,
   its last, rows between, every decoded token).

4. THE SERVED PATH: ``logprob_walk``'s walk over four probes (32 decode
   steps), the median of the steps' largest differences. Not a precision
   check (``routed_experts``, part 3); it holds the wiring the other three
   call no part of: the scheduler, the page tables over the one pool,
   decode through the latent cache, a second asking from cached pages.

TOLERANCES, each between two readings on the chip, beside the constants
below (PERF.md section 2 has the table): the sound program's largest over
the seeds read, and the smallest a control gave
(``rehearse/check_seeds_pangu.py --control``: the program computing with
that fault, parts 1-3, against the sound reference).
"""
from __future__ import annotations

import dataclasses
import statistics

import numpy as np

# A tree that cannot serve such a model ends here, before the engine is
# built and the chip touched (``comparisons.resolve`` imports this module
# first), saying what its server would have said a minute later.
try:
    from dynamo_exp_tpu.models.llama import LATENT_PARAMS, _mla_half  # noqa: F401
except ImportError as e:
    raise ValueError(
        "unsupported model_type 'pangu_ultra_moe': this tree's models/llama.py has no "
        f"latent attention ({e})"
    ) from None

from comparisons.layer_kinds import _attention_form
from lib import correct
from lib.stats import percentile

PROBES = 4  # x correct.PROBE_DECODE_TOKENS = 32 decode steps
BLOCK_ROWS = (64, 1024)
REAL_ROW_SHARE = 0.75  # of a block input; the rest are seeded rows
SPAN_ALIGN = 64  # a probe's slots in the flat stream: a multiple of any q_tile
LONG = (16000, 4096)  # the two long sequences: the traffic's longest and its median
CHUNK = 2048  # prompt tokens a row feeds a dispatch of part 3
DECODE = 8  # tokens each long sequence then decodes, one a step
ROWS_A_CHUNK = 6  # compared rows of a chunk: its first and last tile's ends, four between

# A row is left out of the block part when the reference's 8th and 9th
# router scores (sigmoids over all 256 experts) are nearer than this: the
# engine's router reads the normed row rounded to bfloat16, which moves a
# score by ~1e-3. Of 256 scores the 8th and 9th lie ~0.01 apart.
NEAR_TIE = 0.004
# All readings my chip runs, PR 44 (rehearse/check_seeds_pangu.py, openpangu-ultra-moe-718b-l5e16
# on one v5e chip: 12 seeds sound in chiprun_out/bench/pangu-seeds-{a,b}.json and a thirteenth in
# a cell's own run; one or two seeds a control, the smallest given; ~12-25 s a comparison).
#
# Block part, largest row of |engine - reference| / |reference - input|. Sound 0.0071-0.0073
# over 13 seeds (layer 4; 0.0057-0.0059 at layer 1, 0.0067-0.0068 at layer 3; the same at 64 and
# 1024 rows). Controls: the reference with fp8 (e4m3) feed-forward matrices 0.0891-0.0900 (THE
# LOWER-PRECISION CONTROL of this part); a held pick weighed over the held picks alone 0.890;
# the reference choosing one expert a token 0.918; the shared expert left out 1.24. 3.4 x above
# the one, 3.6 x below the other.
BLOCK_TOL = 0.025
# The pool's rows, largest row of |engine - reference| / |reference| over the 576 values. Sound
# 0.00347-0.00374 over 13 seeds (bfloat16's own rounding of a row, 2^-9 a value). Controls: the
# row rounded to fp8 (e4m3) once more 0.0297 (THE LOWER-PRECISION CONTROL of the cache, not
# correct by this limit and by ATTN_TOL); c_kv cached before its norm 0.110 (the norm's scales
# are ones and the row's length near one already: the smallest of the wiring faults); k_r
# cached before RoPE 0.412. 2.7 x above, 3.0 x below.
LATENT_TOL = 0.01
# Latent half with the head at the timed sizes (16,000 and 4,096 tokens in chunks of 2,048, then
# 8 decoded), largest row of |engine - reference| over the log-probabilities relative to their
# spread. Sound 0.00669-0.0069 over 13 seeds, the same at every layer, at 16,000 tokens as at
# 4,096, and in the decoded rows (0.0061-0.0065) as in the prefilled ones: the absorbed form's
# extra rounding (q W_uk^T and the latent output to bfloat16) does not grow with the context.
# Controls: the cached row rounded to fp8 0.0354; W_ukv rounded to fp8 0.0399 (THE
# LOWER-PRECISION CONTROL of the absorbed products, not correct by this limit and by no other);
# c_kv before its norm 0.0763; the scores scaled by 1/sqrt(128) 0.188; k_r before RoPE 0.425.
# 2.2 x above, 2.4 x below.
ATTN_TOL = 0.015
# Served part, median over the 32 steps of the step's largest difference. NOT a precision limit
# (``routed_experts``, part 3: fp8 feed-forward matrices read 0.088 and pass): the wiring's. Sound
# 0.0179-0.0226 over 13 seeds (the largest step 0.031-0.275: a flip); a wrong routing rule (the
# reference choosing one expert a token) 0.188-0.262. Steadier than the other routed
# configurations' (4 routed layers, a sixteenth of the picks held: a flip moves little). 4.4 x
# above, 1.9 x below: the more room above, since fresh seeds read higher.
MEDIAN_TOL = 0.1

_FFN = ("router", "shared_gate", "shared_up", "shared_down")


def _engine_params(ctx: dict) -> dict:
    """The weights the engine's side computes with: the engine's own, or
    what a control put in their place (``ctx["engine_params"]``)."""
    return ctx.get("engine_params") or ctx["engine"].params


# ------------------------------------------------------------ part 1: block
def engine_block(engine):
    """``run(layers, x, layer)``: ``models/llama.py``'s second half of routed
    layer ``layer`` on ``x`` ([B, T, D] bfloat16), jitted on the engine's
    mesh, the layer's weights taken where they lie."""
    import functools

    import jax

    from dynamo_exp_tpu.models import llama

    mcfg, mesh = engine.cfg.model, engine.mesh
    interpret = bool(getattr(engine, "_attn_interpret", False))

    @functools.partial(jax.jit, static_argnums=2)
    def run(layers, x, layer):
        nth = mcfg.ff_kinds[:layer].count("routed")
        lp = {k: layers[k][layer] for k in ("mlp_norm", "post_ffn_norm")}
        lp.update((k, layers[k][nth]) for k in _FFN if k in layers)
        lp.update((k, layers[k][nth]) for k in llama.EXPERT_STACKS)
        return llama._ffn_block(
            x, lp, mcfg, mcfg.rms_norm_eps, lambda y: y, mesh, interpret=interpret
        )

    return run


def block_part(ctx: dict, before_ffn: np.ndarray, layers: list[int]) -> dict:
    """Part 1. ``before_ffn``: [len(layers), real rows, D], the reference's
    hidden state entering each layer's second half."""
    import jax.numpy as jnp

    reference, engine, hf = ctx["reference"], ctx["engine"], ctx["hf"]
    rng = np.random.default_rng(ctx["seed"] % 2**32)
    run = engine_block(engine)
    cells, near, here = [], 0, []
    for i, layer in enumerate(layers):
        real = before_ffn[i]
        rms = float(np.sqrt(np.mean(real * real)))
        for rows in BLOCK_ROWS:
            n_real = min(len(real), int(rows * REAL_ROW_SHARE))
            pick = np.linspace(0, len(real) - 1, n_real).astype(int)
            seeded = rng.standard_normal((rows - n_real, real.shape[1]), np.float32) * rms
            x = jnp.asarray(np.concatenate([real[pick], seeded]), jnp.bfloat16)
            shaped = x[:, None] if rows == BLOCK_ROWS[0] else x[None]
            got = np.asarray(
                run(_engine_params(ctx)["layers"], shaped, layer).astype(jnp.float32)
            ).reshape(rows, -1)
            want, margin, held = reference.ffn_half(ctx["params"], hf, layer, x)
            added = want - np.asarray(x.astype(jnp.float32))
            err = np.linalg.norm(got - want, axis=1) / np.linalg.norm(added, axis=1)
            keep = margin >= NEAR_TIE
            cells.append({
                "layer": layer, "rows": rows, "rows_near_tie": int((~keep).sum()),
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
    }


# ------------------------------------------------- the one-layer model, 2 and 3
def _one_layer_model(engine):
    """The configuration and the parameter builder of a one-layer model:
    ``build(params, x, layer)`` takes layer ``layer``'s latent attention and
    its two norms, a dense feed-forward of zeros between norms of ones
    (which adds exactly 0), the engine's final norm and head; ``x`` stands
    in the embedding's place."""
    import jax
    import jax.numpy as jnp

    from dynamo_exp_tpu.models import llama

    mcfg = engine.cfg.model
    one = dataclasses.replace(
        mcfg, num_layers=1, num_experts=0, num_experts_held=0, first_expert_held=0,
        num_shared_experts=0, num_dense_layers=0, tie_word_embeddings=False,
    )
    names = (*llama.LATENT_PARAMS, "wo", "attn_norm", "post_attn_norm")

    def build(params, x, layer):
        src = params["layers"]
        d = x.shape[-1]
        layers = {k: jax.lax.dynamic_slice_in_dim(src[k], layer, 1, 0) for k in names}
        layers["mlp_norm"] = layers["post_ffn_norm"] = jnp.ones((1, d), x.dtype)
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
    one latent layer, a whole probe a row of ``span`` slots. Returns the
    logits [n_rows * span, V] float32 and the layer's pool."""
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
        logits, k, _v = llama.forward_ragged(
            build(params, x, layer), one, every, positions, row_of, table, k, v, every,
            attn_impl=impl, q_tile=q_tile, mesh=engine.mesh, interpret=interpret,
        )
        return logits, k

    return run, pages_a_row * page


def latent_rows_part(ctx: dict, entering: list[np.ndarray], layers: list[int]) -> dict:
    """Part 2. ``entering``: for each probe sequence, [len(layers), T, D]:
    the reference's hidden state entering each layer."""
    import jax.numpy as jnp

    reference, engine, hf = ctx["reference"], ctx["engine"], ctx["hf"]
    width = int(hf["kv_lora_rank"]) + int(hf["qk_rope_head_dim"])
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
        x = jnp.asarray(x, jnp.bfloat16)
        _, pool = run(
            _engine_params(ctx), x.reshape(len(entering) * span, -1), jnp.asarray(positions), layer,
        )
        held = np.asarray(pool[0].astype(jnp.float32)).reshape(len(entering), row_tokens, -1)
        err, pad = [], 0.0
        for s, n in enumerate(lengths):
            want = reference.latent_rows(ctx["params"], hf, layer, x[s, :n])
            got = held[s, :n]
            err.extend(
                np.linalg.norm(got[:, :width] - want, axis=1) / np.linalg.norm(want, axis=1)
            )
            pad = max(pad, float(np.abs(got[:, width:]).max(initial=0.0)))
        cells.append({
            "layer": layer, "rows": len(err), "max_rel_err": float(max(err)),
            "median_rel_err": float(np.median(err)), "padding_max_abs": pad,
        })
    return {
        "latent_rows": cells,
        "latent_rows_max_rel_err": max(c["max_rel_err"] for c in cells),
        "latent_padding_max_abs": max(c["padding_max_abs"] for c in cells),
    }


# ---------------------------------------------------- part 3: the timed sizes
def head_rows(length: int, chunk: int = CHUNK, tile: int = 8) -> list[int]:
    """The compared positions of a prefilled sequence of ``length`` tokens:
    of each chunk the first tile's last row, the last row, and rows spread
    between them."""
    rows = set()
    for a in range(0, length, chunk):
        b = min(a + chunk, length)
        rows.update(
            int(r) for r in np.linspace(a + tile - 1, b - 1, ROWS_A_CHUNK).round()
        )
    return sorted(rows)


def long_inputs(ctx: dict, lengths=LONG) -> list[np.ndarray]:
    """The long sequences' inputs, [length + DECODE, D] float32 each: rows
    of the engine's embedding at seeded token ids (what layer 0 really
    reads, and a stand-in of the same scale for the deeper layers: the two
    sides are given the same bfloat16 rows either way)."""
    import jax.numpy as jnp

    rng = np.random.default_rng((ctx["seed"] + 17) % 2**32)
    embed = ctx["engine"].params["embed"]
    return [
        np.asarray(embed[jnp.asarray(rng.integers(0, embed.shape[0], n + DECODE))].astype(jnp.float32))
        for n in lengths
    ]


def engine_long(engine, lengths, chunk: int = CHUNK):
    """``run(params, xs, layer)`` for part 3: the sequences ``xs``
    ([length + DECODE, D] bfloat16 each) through one latent layer as the
    timed path runs it: prefill a chunk a row a dispatch through
    ``forward_ragged`` (the rows' chunks side by side in one stream) into
    one pool, then ``DECODE`` steps through ``forward``. Returns for each
    sequence the logits at ``head_rows`` and at every decoded position."""
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
    wanted = [head_rows(n, chunk, q_tile) for n in lengths]

    @jax.jit
    def prefill(model, k, v, tokens, positions, row_of, out_idx):
        return llama.forward_ragged(
            model, one, tokens, positions, row_of, table, k, v, out_idx,
            attn_impl=impl, q_tile=q_tile, mesh=engine.mesh, interpret=interpret,
        )

    @jax.jit
    def step(model, k, v, tokens, positions):
        logits, k, v = llama.forward(
            model, one, tokens[:, None], positions[:, None], table, k, v,
            attn_impl=impl, mesh=engine.mesh, interpret=interpret,
        )
        return logits[:, 0], k, v

    def run(params, xs, layer):
        model = build(params, jnp.concatenate(xs), layer)
        k, v = llama.init_kv_cache(one, int(sum(pages_a_row)), page, dtype=kv_dtype)
        got = [[] for _ in lengths]
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
            logits, k, v = prefill(
                model, k, v, jnp.asarray(tokens, jnp.int32), jnp.asarray(positions, jnp.int32),
                jnp.asarray(row_of, jnp.int32), jnp.asarray(out_idx, jnp.int32),
            )
            logits = np.asarray(logits)
            for i, r in enumerate(owners):
                got[r].append(logits[i])
        for t in range(DECODE):
            logits, k, v = step(
                model, k, v,
                jnp.asarray([offsets[r] + n + t for r, n in enumerate(lengths)], jnp.int32),
                jnp.asarray([n + t for n in lengths], jnp.int32),
            )
            logits = np.asarray(logits)
            for r in range(n_rows):
                got[r].append(logits[r])
        return [np.stack(g) for g in got]

    return run, wanted


def long_part(ctx: dict, layers: list[int], lengths=LONG, chunk: int = CHUNK) -> dict:
    """Part 3."""
    import jax
    import jax.numpy as jnp

    reference, engine, hf = ctx["reference"], ctx["engine"], ctx["hf"]
    xs = [jnp.asarray(x, jnp.bfloat16) for x in long_inputs(ctx, lengths)]
    run, wanted = engine_long(engine, lengths, chunk)
    cells = []
    for layer in layers:
        got = run(_engine_params(ctx), xs, layer)
        for r, n in enumerate(lengths):
            rows = wanted[r] + list(range(n, n + DECODE))
            want = reference.attn_rows_and_head(ctx["params"], hf, layer, xs[r], rows)
            have = np.asarray(jax.nn.log_softmax(jnp.asarray(got[r]), axis=-1))
            spread = want - want.mean(axis=-1, keepdims=True)
            err = np.linalg.norm(have - want, axis=-1) / np.linalg.norm(spread, axis=-1)
            split = len(wanted[r])
            cells.append({
                "layer": layer, "tokens": n, "rows": len(rows),
                "max_rel_err": float(err.max()), "median_rel_err": float(np.median(err)),
                "max_rel_err_prefill": float(err[:split].max()),
                "max_rel_err_decode": float(err[split:].max()),
            })
    return {"long": cells, "long_max_rel_err": max(c["max_rel_err"] for c in cells)}


def layers_read(hf: dict) -> tuple[list[int], list[int]]:
    """(The layers whose latent half is read: first, middle, last; the
    routed layers whose block is read: first, middle, last of them)."""
    n, dense = int(hf["num_hidden_layers"]), int(hf.get("first_k_dense_replace", 0))
    routed = list(range(dense, n))
    return (
        sorted({0, n // 2, n - 1}),
        sorted({routed[0], routed[len(routed) // 2], routed[-1]}),
    )


def compare(ctx: dict) -> dict:
    walk = correct.walk_reference(ctx, PROBES, whole_vocabulary=True)
    if "why" in walk:
        return {"ok": False, "why": walk["why"]}
    diffs = walk["step_diffs"]
    out = {
        "max_abs_logprob_diff": max(diffs), "tol": MEDIAN_TOL, "steps": len(diffs),
        "median_abs_logprob_diff": statistics.median(diffs),
        "p90_abs_logprob_diff": percentile(diffs, 90),
        "block_tol": BLOCK_TOL, "near_tie": NEAR_TIE, "latent_tol": LATENT_TOL,
        "attn_tol": ATTN_TOL,
    }
    hf = ctx["hf"]
    attn_layers, routed_layers = layers_read(hf)
    every = sorted(set(attn_layers) | set(routed_layers))
    states = [ctx["reference"].hidden_states(ctx["params"], hf, ids, every) for ids in walk["sequences"]]
    at = [every.index(l) for l in routed_layers]
    out.update(block_part(ctx, np.concatenate([b[at] for _e, b in states], axis=1), routed_layers))
    at = [every.index(l) for l in attn_layers]
    out.update(latent_rows_part(ctx, [e[at] for e, _b in states], attn_layers))
    # No longer than the model's positions (a CPU rehearsal's 512), and a
    # test may name smaller sizes (benchmark/tests/test_latent_attention.py).
    room = int(hf.get("max_position_embeddings", max(LONG) + DECODE)) - DECODE
    lengths = ctx.get("long_tokens") or tuple(min(n, room // 8 * 8) for n in LONG)
    out.update(long_part(ctx, attn_layers, lengths, ctx.get("long_chunk", CHUNK)))
    out["step_diffs"] = [round(d, 5) for d in diffs]
    faults = []
    if out["median_abs_logprob_diff"] > MEDIAN_TOL:
        faults.append(f"median |diff| {out['median_abs_logprob_diff']:.4f} > {MEDIAN_TOL}")
    if out["latent_padding_max_abs"] > 0:
        faults.append(f"the pool's padding lanes hold {out['latent_padding_max_abs']:.3g}, not zeros")
    for part, tol, what in (
        ("block", BLOCK_TOL, "of what the block adds"),
        ("latent_rows", LATENT_TOL, "of the cached row's length"),
        ("long", ATTN_TOL, "of the log-probabilities' spread"),
    ):
        bad = max(out[part], key=lambda c: c["max_rel_err"])
        if bad["max_rel_err"] > tol:
            faults.append(
                f"{part}: layer {bad['layer']} ({bad.get('tokens', bad['rows'])}) differs by "
                f"{bad['max_rel_err']:.4f} {what} > {tol}"
            )
    out["ok"], out["why"] = not faults, "; ".join(faults) or None
    return out
