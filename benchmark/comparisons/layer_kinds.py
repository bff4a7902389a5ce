"""The comparison for a model whose layers are of more than one kind
(``"comparison": "layer_kinds"``): lfm2_moe's stack of short-convolution
and attention operators over dense and routed feed-forwards.

It keeps ``routed_experts``'s three parts and its reasoning (that module's
text: a routed model's served walk cannot hold the precision, because a
bfloat16 residual stream sends a few tokens to other experts than the
float32 reference does, both rightly; so the arithmetic is compared half a
layer at a time on the SAME bfloat16 input, and the served path is judged
on its median). What differs is which layers are read and how:
``routed_experts`` reads "the first, the middle and the last layer" of a
uniform stack; here layer 0 is a dense convolution layer, parameters are
stacked by kind, and one kind of operator keeps state between calls.

1. THE EXPERT BLOCK of the first, the middle and the last ROUTED layer,
   at 64 rows ([64, 1, D], a decode bucket: 256 sorted rows, the expert
   kernel in place) and 1024 rows ([1, 1024, D], a mixed dispatch:
   ``ragged_dot`` on the layer), through ``models/llama.py::_ffn_block``
   with the engine's own weights of that layer, against the reference's
   router and dense experts. Rows whose reference margin (between the 4th
   and the 5th of ``s + b``, the scores that choose) is under
   ``NEAR_TIE`` are left out and counted. Number: the largest row of
   ``|engine - reference| / |reference - input|``.

2. THE OPERATOR HALF WITH THE HEAD, for the first and the last attention
   layer and the first and the last convolution layer, over a one-layer
   model made of the engine's own arrays (that layer's operator, a
   feed-forward of zeros, the engine's final norm and head; the input rows
   stand in the embedding's place). Attention as ``routed_experts`` has
   it: a whole prompt a row through ``forward_ragged``. **The convolution
   as a prefill chunk followed by decode steps that read the state it
   left**: the first ``CONV_PREFILL`` tokens of every probe through
   ``forward_ragged`` (the mixed program's forward) into a state pool,
   then every later token one at a time through ``forward`` (the decode
   window's forward), each step reading the two products the step before
   left. Number: the largest row of ``|engine - reference|`` over the
   log-probabilities, relative to their spread.

3. THE SERVED PATH: ``logprob_walk``'s walk over four probes (32 decode
   steps), the median of the steps' largest differences. Not a precision
   check (``routed_experts``, part 3); it holds the wiring the other two
   call no part of: the scheduler, the page tables, the state pool's
   entries, decode through the cache and the state, a second asking.

TOLERANCES, each between two readings on the chip, beside the constants
below (PERF.md section 2 has the table): the sound program's largest over
the seeds read, and the smallest a control gave
(``rehearse/check_seeds_lfm2.py --control``: the reference computing with
that fault against the sound program).
"""
from __future__ import annotations

import dataclasses
import statistics

import numpy as np

# A tree that cannot serve such a model ends here, before the engine is
# built (``comparisons.resolve`` imports this module first).
from dynamo_exp_tpu.models.llama import init_seq_state, short_conv  # noqa: F401

from lib import correct
from lib.stats import percentile

PROBES = 4  # x correct.PROBE_DECODE_TOKENS = 32 decode steps
BLOCK_ROWS = (64, 1024)
REAL_ROW_SHARE = 0.75  # of a block input; the rest are seeded rows
SPAN_ALIGN = 64  # a sequence's slots in the flat stream: a multiple of any q_tile
CONV_PREFILL = 32  # tokens of a probe the convolution layer prefills; the rest decode

# A row is left out of the block part when the reference's 4th and 5th of
# ``s + b`` are nearer than this: the engine's router reads the normed row
# rounded to bfloat16, which moves a sigmoid score by ~1e-3.
NEAR_TIE = 0.005
# Block part, largest row of |engine - reference| / |reference - input|. Steady from
# seed to seed. lfm2-8b-a1b-l16, one chip (my chip runs, PR 31): sound 0.0237-0.0252
# over 22 seeds (15 of check_seeds_lfm2.py, 7 cell runs; by layer 0.0124 at layer 2,
# 0.0203 at layer 9, 0.0252 at layer 15; the same at 64 rows, the kernel in place, and
# at 1024, ragged_dot); the reference with fp8 (e4m3) expert weights 0.0641-0.0658
# (three seeds); weights taken from s + b 0.232-0.259; top-1 0.981-0.986; no
# renormalisation 0.729-0.732; selection by s alone 1.44-1.49. The limit stands 1.6 x
# above the one and 1.6 x below the other.
BLOCK_TOL = 0.04
# Operator part, largest row of |engine - reference| over the log-probabilities
# relative to their spread. Steady. Sound 0.0061-0.0071 over 22 seeds (the first
# convolution layer, whose rows decoded a step at a time from the state read no worse
# than its prefilled ones: 0.0061-0.0068; the last convolution layer 0.0033-0.0038; the
# attention layers 0.0030-0.0036); fp8 attention weights 0.0289 (three seeds, layer 2;
# 0.0134 at layer 14); an fp8 head 0.0321-0.0325; fp8 convolution projections
# 0.0711-0.0729 (layer 0; 0.0196 at layer 15); the state zeroed at the first decode step
# 0.876-0.962; the taps reversed 1.39-1.46. 2.1 x above, 1.9 x below.
OP_HALF_TOL = 0.015
# Served part, median over the 32 steps of the step's largest difference. NOT steady and
# NOT a precision limit (routed_experts, part 3). Sound 0.120-0.238 over 22 seeds (the
# largest: seed 2147484101 in a cell's run; more than Mixtral's 0.03-0.12: 32 experts,
# top-4 of sigmoid scores plus a bias, 14 routed layers, so more tokens near a tie); a
# wrong routing rule: selection by s alone 0.857-1.079, top-1 2.36-2.56, no
# renormalisation 2.71-2.84; the taps reversed 3.88-4.33. 2.1 x above, 1.7 x below. For
# the record, what passes it and is held by parts 1 and 2: weights from s + b
# 0.232-0.267, fp8 attention 0.160-0.231, a zeroed state 0.261-0.412, fp8 experts
# 0.336-0.520 (one seed of three over); an fp8 head reads 0.561-0.603.
MEDIAN_TOL = 0.5


def _routed_lp(layers, mcfg, layer: int):
    """Layer ``layer``'s second half out of the stacks (traced ``layer``
    is not needed: the three layers read are three compiled calls)."""
    nth = mcfg.ff_kinds[:layer].count("routed")
    lp = {"mlp_norm": layers["mlp_norm"][layer], "router": layers["router"][nth]}
    if "router_bias" in layers:
        lp["router_bias"] = layers["router_bias"][nth]
    lp.update((k, layers[k][nth]) for k in ("w_gate", "w_up", "w_down"))
    return lp


def engine_block(engine):
    """``run(layers, x, layer)``: ``models/llama.py``'s second half of
    routed layer ``layer`` on ``x`` ([B, T, D] bfloat16), jitted on the
    engine's mesh, the layer's weights taken where they lie."""
    import functools

    import jax

    from dynamo_exp_tpu.models import llama

    mcfg, mesh = engine.cfg.model, engine.mesh

    @functools.partial(jax.jit, static_argnums=2)
    def run(layers, x, layer):
        lp = _routed_lp(layers, mcfg, layer)
        return llama._ffn_block(x, lp, mcfg, mcfg.rms_norm_eps, lambda y: y, mesh)

    return run


def block_part(ctx: dict, before_ffn: np.ndarray, layers: list[int]) -> dict:
    """Part 1. ``before_ffn``: [len(layers), real rows, D], the
    reference's hidden state entering each layer's second half."""
    import jax.numpy as jnp

    reference, engine, hf = ctx["reference"], ctx["engine"], ctx["hf"]
    rng = np.random.default_rng(ctx["seed"] % 2**32)
    run = engine_block(engine)
    cells, near = [], 0
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
                run(engine.params["layers"], shaped, layer).astype(jnp.float32)
            ).reshape(rows, -1)
            want, margin = reference.ffn_half(ctx["params"], hf, layer, x)
            added = want - np.asarray(x.astype(jnp.float32))
            err = np.linalg.norm(got - want, axis=1) / np.linalg.norm(added, axis=1)
            keep = margin >= NEAR_TIE
            cells.append({
                "layer": layer, "rows": rows, "rows_near_tie": int((~keep).sum()),
                "max_rel_err": float(err[keep].max()),
                "median_rel_err": float(np.median(err[keep])),
                "max_rel_err_near_tie": float(err[~keep].max()) if (~keep).any() else None,
            })
            near += cells[-1]["rows_near_tie"]
    return {
        "block": cells, "block_max_rel_err": max(c["max_rel_err"] for c in cells),
        "rows_near_tie": near,
        "block_median_rel_err": max(c["median_rel_err"] for c in cells),
    }


def _one_layer_model(engine, op: str):
    """The configuration and the parameter builder of a one-layer model of
    operator ``op`` with a dense feed-forward of zeros (which adds exactly
    0) and an untied head: ``build(params, x, nth)`` takes the ``nth``
    layer of that operator's stacks and the norm of layer ``layer``."""
    import jax
    import jax.numpy as jnp

    mcfg = engine.cfg.model
    one = dataclasses.replace(
        mcfg, num_layers=1, num_experts=0, num_dense_layers=0, tie_word_embeddings=False,
        layer_types=("conv" if op == "conv" else "full_attention",),
    )
    names = {
        "conv": ("conv_in", "conv_w", "conv_out"),
        "attn": ("wq", "wk", "wv", "wo", "q_norm", "k_norm"),
    }[op]

    def build(params, x, layer, nth):
        src = params["layers"]
        d = x.shape[-1]
        layers = {k: jax.lax.dynamic_slice_in_dim(src[k], nth, 1, 0) for k in names}
        layers["attn_norm"] = jax.lax.dynamic_slice_in_dim(src["attn_norm"], layer, 1, 0)
        layers["mlp_norm"] = jnp.ones((1, d), x.dtype)
        layers.update(
            w_gate=jnp.zeros((1, d, 128), x.dtype), w_up=jnp.zeros((1, d, 128), x.dtype),
            w_down=jnp.zeros((1, 128, d), x.dtype),
        )
        head = params["embed"].T if mcfg.tie_word_embeddings else params["lm_head"]
        return {"embed": x, "layers": layers, "final_norm": params["final_norm"], "lm_head": head}

    return one, build


def _attention_form(engine):
    pool = getattr(engine, "k_cache", None)
    if pool is None:  # the tests' stand-in for an engine: one device, XLA attention
        return "xla", False, 1, 16, None
    return (
        engine._attn_impl, engine._attn_interpret, engine._ragged_align(),
        pool.shape[2], pool.dtype,
    )


def engine_attention_and_head(engine, n_rows: int, span: int):
    """``run(params, x, positions, layer, nth)``: the mixed program's
    forward over one attention layer (``routed_experts``'s part 2): a
    whole prompt a row. Logits [n_rows * span, V] float32."""
    import jax
    import jax.numpy as jnp

    from dynamo_exp_tpu.models import llama

    impl, interpret, q_tile, page, kv_dtype = _attention_form(engine)
    if span % q_tile:
        raise ValueError(f"a row's span {span} is no multiple of the kernel's q_tile {q_tile}")
    one, build = _one_layer_model(engine, "attn")
    pages_a_row = -(-span // page)
    n = n_rows * span
    row_of = jnp.repeat(jnp.arange(n_rows, dtype=jnp.int32), span)
    table = jnp.arange(n_rows * pages_a_row, dtype=jnp.int32).reshape(n_rows, pages_a_row)

    @jax.jit
    def run(params, x, positions, layer, nth):
        k, v = llama.init_kv_cache(one, n_rows * pages_a_row, page, dtype=kv_dtype)
        every = jnp.arange(n, dtype=jnp.int32)
        return llama.forward_ragged(
            build(params, x, layer, nth), one, every, positions, row_of, table, k, v, every,
            attn_impl=impl, q_tile=q_tile, mesh=engine.mesh, interpret=interpret,
        )[0]

    return run


def engine_conv_and_head(engine, n_rows: int, span: int, state_fault=None):
    """``run(params, x, lengths, layer, nth)``: one convolution layer as
    the engine runs it: the first ``CONV_PREFILL`` tokens of every row in
    one ``forward_ragged`` (the mixed program's forward), which leaves
    each row's state in its entry of a pool, then every later token of
    every row one step at a time through ``forward`` at [n_rows, 1] (the
    decode window's forward), reading and leaving the state. Logits
    [n_rows, span, V] float32 (rows past a row's length are not
    computed). ``state_fault`` (the tests' faults on the engine's side)
    maps the pool between the chunk and the first step."""
    import jax
    import jax.numpy as jnp

    from dynamo_exp_tpu.models import llama

    impl, interpret, q_tile, page, kv_dtype = _attention_form(engine)
    one, build = _one_layer_model(engine, "conv")
    chunk = CONV_PREFILL
    if chunk % q_tile or chunk > span:
        raise ValueError(f"the prefill chunk {chunk} does not fit the span {span} / q_tile {q_tile}")
    row_of = jnp.repeat(jnp.arange(n_rows, dtype=jnp.int32), chunk)
    table = jnp.zeros((n_rows, 1), jnp.int32)  # no attention layer: no page is read
    slots = jnp.arange(n_rows, dtype=jnp.int32)
    base = slots * span  # row r's tokens are x[r * span + t]

    @jax.jit
    def prefill(params, x, layer, nth):
        k, v = llama.init_kv_cache(one, 1, page, dtype=kv_dtype)
        tokens = (base[:, None] + jnp.arange(chunk)[None]).reshape(-1)
        positions = jnp.tile(jnp.arange(chunk, dtype=jnp.int32), n_rows)
        every = jnp.arange(n_rows * chunk, dtype=jnp.int32)
        last = slots * chunk + chunk - 1  # each row's last token in the stream
        logits, _k, _v, state = llama.forward_ragged(
            build(params, x, layer, nth), one, tokens, positions, row_of, table, k, v, every,
            attn_impl=impl, q_tile=q_tile, mesh=engine.mesh, interpret=interpret,
            seq_state=llama.init_seq_state(one, n_rows, dtype=x.dtype),
            state_rows=(slots, last, slots),
        )
        return logits.reshape(n_rows, chunk, -1), state

    @jax.jit
    def step(params, x, state, t, live, layer, nth):
        k, v = llama.init_kv_cache(one, 1, page, dtype=kv_dtype)
        logits, _k, _v, state = llama.forward(
            build(params, x, layer, nth), one, (base + t)[:, None],
            jnp.where(live, t, -1)[:, None], table, k, v,
            attn_impl=impl, mesh=engine.mesh, interpret=interpret,
            seq_state=state, state_slots=slots,
        )
        return logits[:, 0], state

    def run(params, x, lengths, layer, nth):
        head, state = prefill(params, x, layer, nth)
        if state_fault is not None:
            state = state_fault(state)
        out = [np.asarray(head)]
        lengths = np.asarray(lengths)
        for t in range(chunk, int(lengths.max())):
            logits, state = step(
                params, x, state, jnp.asarray(t, jnp.int32), jnp.asarray(lengths > t), layer, nth
            )
            out.append(np.asarray(logits)[:, None])
        return np.concatenate(out, axis=1)

    return run


def op_half_part(ctx: dict, entering: list[np.ndarray], layers: list[int]) -> dict:
    """Part 2. ``entering``: for each probe sequence, [len(layers), T, D]:
    the reference's hidden state entering each of ``layers``."""
    import jax
    import jax.numpy as jnp

    reference, engine, hf = ctx["reference"], ctx["engine"], ctx["hf"]
    mcfg = engine.cfg.model
    lengths = [e.shape[1] for e in entering]
    span = -(-max(lengths) // SPAN_ALIGN) * SPAN_ALIGN
    n_rows = len(entering)
    positions = np.concatenate([
        np.where(np.arange(span) < n, np.arange(span), -1) for n in lengths
    ]).astype(np.int32)
    runs = {
        "attn": engine_attention_and_head(engine, n_rows, span),
        "conv": engine_conv_and_head(engine, n_rows, span, ctx.get("state_fault")),
    }
    cells = []
    for i, layer in enumerate(layers):
        op = mcfg.op_kinds[layer]
        nth = mcfg.op_kinds[:layer].count(op)
        x = np.zeros((n_rows, span, entering[0].shape[-1]), np.float32)
        for s, e in enumerate(entering):
            x[s, :lengths[s]] = e[i]
        x = jnp.asarray(x, jnp.bfloat16)
        flat = x.reshape(n_rows * span, -1)
        if op == "attn":
            logits = runs[op](engine.params, flat, jnp.asarray(positions), layer, nth)
            logits = np.asarray(logits).reshape(n_rows, span, -1)
        else:
            logits = runs[op](engine.params, flat, lengths, layer, nth)
        err, decoded = [], []
        for s, n in enumerate(lengths):
            got = np.asarray(jax.nn.log_softmax(jnp.asarray(logits[s, :n]), axis=-1))
            want = reference.op_half_and_head(ctx["params"], hf, layer, x[s, :n])
            spread = want - want.mean(axis=-1, keepdims=True)
            row_err = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(spread, axis=-1)
            err.extend(row_err)
            decoded.extend(row_err[CONV_PREFILL:])
        cells.append({
            "layer": layer, "op": op, "rows": len(err), "max_rel_err": float(max(err)),
            "median_rel_err": float(np.median(err)),
            # The rows computed a step at a time from the state (a convolution layer).
            "max_rel_err_decoded": float(max(decoded)) if op == "conv" and decoded else None,
        })
    return {"op_half": cells, "op_half_max_rel_err": max(c["max_rel_err"] for c in cells)}


def layers_read(hf: dict) -> tuple[list[int], list[int]]:
    """(the routed layers part 1 reads: first, middle, last; the layers
    part 2 reads: the first and last attention and convolution layers)."""
    kinds = ["conv" if t == "conv" else "attn" for t in hf["layer_types"]]
    routed = list(range(int(hf["num_dense_layers"]), len(kinds)))
    block = sorted({routed[0], routed[len(routed) // 2], routed[-1]})
    ops = []
    for op in ("attn", "conv"):
        of_kind = [i for i, k in enumerate(kinds) if k == op]
        ops += [of_kind[0], of_kind[-1]]
    return block, sorted(set(ops))


def compare(ctx: dict) -> dict:
    walk = correct.walk_reference(ctx, PROBES, whole_vocabulary=True)
    if "why" in walk:
        return {"ok": False, "why": walk["why"]}
    diffs = walk["step_diffs"]
    out = {
        "max_abs_logprob_diff": max(diffs), "tol": MEDIAN_TOL, "steps": len(diffs),
        "median_abs_logprob_diff": statistics.median(diffs),
        "p90_abs_logprob_diff": percentile(diffs, 90),
        "block_tol": BLOCK_TOL, "near_tie": NEAR_TIE, "op_half_tol": OP_HALF_TOL,
    }
    hf = ctx["hf"]
    block_layers, op_layers = layers_read(hf)
    every = sorted(set(block_layers) | set(op_layers))
    states = [ctx["reference"].hidden_states(ctx["params"], hf, ids, every) for ids in walk["sequences"]]
    at = {l: i for i, l in enumerate(every)}
    before_ffn = np.concatenate([b[[at[l] for l in block_layers]] for _e, b in states], axis=1)
    out.update(block_part(ctx, before_ffn, block_layers))
    out.update(op_half_part(ctx, [e[[at[l] for l in op_layers]] for e, _b in states], op_layers))
    out["step_diffs"] = [round(d, 5) for d in diffs]
    faults = []
    if out["median_abs_logprob_diff"] > MEDIAN_TOL:
        faults.append(f"median |diff| {out['median_abs_logprob_diff']:.4f} > {MEDIAN_TOL}")
    for part, tol, what in (
        ("block", BLOCK_TOL, "of what the block adds"),
        ("op_half", OP_HALF_TOL, "of the log-probabilities' spread"),
    ):
        bad = max(out[part], key=lambda c: c["max_rel_err"])
        if bad["max_rel_err"] > tol:
            faults.append(
                f"{part}: layer {bad['layer']} at {bad['rows']} rows differs by "
                f"{bad['max_rel_err']:.4f} {what} > {tol}"
            )
    out["ok"], out["why"] = not faults, "; ".join(faults) or None
    return out
