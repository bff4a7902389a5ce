"""The comparison for a model whose layers are selective scans and
attention (``"comparison": "state_space"``): jamba's stack of Mamba-1
mixers with two attention layers, dense feed-forwards throughout.

What a state-space layer adds to the question "does the served path
compute the model" is a recurrent state that outlives a dispatch: a
sequence's scan state is float32 ``[S, E]`` a layer in a pool beside its
pages, read where a prompt chunk begins and written where it ends, and a
fault in it (a state dropped at a chunk boundary, kept in too few bits,
restored from the wrong entry) decays within a few hundred tokens and
leaves short probes untouched. So the operator is compared at the sizes
the timed path runs it at, half a layer at a time on the SAME bfloat16
input, as ``layer_kinds`` compares its operators:

1. THE MAMBA HALF WITH THE HEAD of the first, a middle and the last Mamba
   layer, over a one-layer model made of the engine's own arrays (that
   layer's mixer, a feed-forward of zeros, the engine's final norm and
   head; the input rows stand in the embedding's place). Two sequences of
   ``LONG`` tokens whose hidden states entering the layer are the
   reference's: the first ``PREFILL`` tokens of both in chunks of
   ``CHUNK`` through ``forward_ragged`` (the mixed program's forward:
   both rows' chunks in one stream, the scan kernel on the chip), the
   state crossing every dispatch boundary through the pool, then every
   later token one step at a time through ``forward`` (the decode
   window's forward, the one-step form) reading the state. Two numbers.
   The largest row of ``|engine - reference|`` over the log-probabilities,
   relative to their spread, at ``head_rows``: the rows round every chunk
   boundary, every 32nd between, and every decoded row; rows decoded from
   the state are held to the same limit as prefilled ones. And THE STATE
   ITSELF: the pool's float32 ``[S, E]`` entry of each sequence after the
   last prefilled token and after the last decoded one, against the
   reference's, ``|engine - reference| / |reference|``. The
   log-probabilities pass through a bfloat16 head whose rounding (0.003
   of their spread) hides a state kept in bfloat16 (0.012 against a sound
   0.010 at worst); the state read directly does not.

2. THE ATTENTION HALF WITH THE HEAD of both attention layers: a whole
   sequence a row through ``forward_ragged`` (the attention kernel at 1
   KV head under 20 query heads, no rotary term), same number, own limit.

3. THE SERVED PATH: ``logprob_walk``'s walk over four probes (32 decode
   steps), the largest difference. A dense model's walk holds precision
   too, but on 56-token sequences it cannot see the state's faults; it
   holds the wiring the other two call no part of: the scheduler, the
   page tables, the state pool's entries, decode through cache and state,
   a second asking.

TOLERANCES, each between two readings on the chip, beside the constants
below (PERF.md section 2 has the table): the sound program's largest over
the seeds read, and the smallest a control gave
(``rehearse/check_seeds_jamba.py --control``: the reference computing
with that fault against the sound program).
"""
from __future__ import annotations

import dataclasses

import numpy as np

# A tree that cannot serve such a model ends here, before the engine is
# built (``comparisons.resolve`` imports this module first).
from dynamo_exp_tpu.models.llama import MAMBA_PARAMS, mamba_mixer  # noqa: F401

from comparisons.layer_kinds import _attention_form
from lib import correct

PROBES = 4  # x correct.PROBE_DECODE_TOKENS = 32 decode steps
ROWS = 2  # long sequences
PREFILL = 2048  # tokens of each fed in chunks through forward_ragged
CHUNK = 512  # the engine's prefill_chunk
DECODE = 32  # tokens of each fed a step at a time through forward
SPAN_ALIGN = 64  # a sequence's slots in the flat stream: a multiple of any q_tile

# Mamba part, largest row of |engine - reference| over the log-probabilities relative to
# their spread. Steady. jamba2-3b, one chip (my chip runs, PR 33; 41 seeds: 30 of
# check_seeds_jamba.py, 11 cell runs): sound 0.0063-0.0099 (the first Mamba layer, whose
# input is the embedding: 0.0063-0.0099, its rows decoded from the state 0.0059-0.0084, no
# worse than its prefilled ones; layer 14 0.0031-0.0032; layer 27 0.0035-0.0037). Controls
# (three seeds each, the smallest): an fp8 (e4m3) head 0.0341; fp8 W_in/W_out 0.0816; the
# state zeroed where the second chunk begins 0.239; the inner norms left out 0.669; the taps
# reversed 1.48; D left out 20.1; A unsigned and no softplus: not finite. 2.0 x above the
# one, 1.7 x below the other. What passes it and is held by STATE_TOL: the scan state kept
# in bfloat16, 0.0121-0.0144 (the bfloat16 head's own rounding, 0.003, hides most of it).
MAMBA_TOL = 0.02
# The scan's state in the pool against the reference's, |engine - reference| / |reference|,
# after 2048 prefilled and after 32 decoded tokens. Sound 0.0041-0.0054 over 12 seeds (the
# bfloat16 products that feed the scan); the state rounded to bfloat16 after every token
# 0.0441-0.0478 (three seeds; by layer 0.0155-0.0173 / 0.0118-0.0137 / 0.0441-0.0478 at
# layers 0 / 14 / 27); fp8 W_in/W_out 0.0445-0.0516. 2.8 x above, 2.9 x below.
STATE_TOL = 0.015
# Attention part, the same number for layers 7 and 21 (1 KV head under 20 query heads, no
# rotary term). Sound 0.0035-0.0038 over 41 seeds (layer 7; layer 21 0.0030-0.0031); fp8
# attention weights 0.0232 (three seeds; 0.0148 at layer 21); an fp8 head 0.0338; rotary
# positions applied to q and k 0.0937 (0.0554 at layer 21). 2.6 x above, 2.3 x below.
ATTN_TOL = 0.01
# Served part: the largest of 32 steps' differences. NOT a precision limit (fp8 attention
# reads 0.143-0.178, a bfloat16 state 0.126-0.200, a state zeroed at a chunk boundary the
# sound value: 56-token probes cross none) but the wiring's: sound 0.109-0.186 over 41
# seeds (more than qwen3-4b's 0.03-0.06: 26 mixers of five bfloat16 products each); rotary
# positions 0.448; an fp8 head 0.924; fp8 W_in/W_out 1.23; the inner norms left out 4.42;
# the taps reversed 6.19. 1.6 x above, 1.5 x below.
WALK_TOL = 0.3


def _one_layer_model(engine, op: str):
    """The configuration and the parameter builder of a one-layer model of
    operator ``op`` with a dense feed-forward of zeros (which adds exactly
    0) and an untied head: ``build(params, x, layer, nth)`` takes the
    ``nth`` layer of that operator's stacks and the norm of ``layer``."""
    import jax
    import jax.numpy as jnp

    mcfg = engine.cfg.model
    one = dataclasses.replace(
        mcfg, num_layers=1, tie_word_embeddings=False,
        layer_types=("mamba" if op == "mamba" else "full_attention",),
    )
    names = MAMBA_PARAMS if op == "mamba" else ("wq", "wk", "wv", "wo")

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


def head_rows(prefill: int, chunk: int, decode: int) -> list[int]:
    """The positions whose log-probabilities are compared: the four rows
    on either side of every chunk boundary (where the state crosses a
    dispatch), every 32nd prefilled row, and every decoded row."""
    rows = set(range(0, prefill, 32))
    for edge in range(0, prefill + 1, chunk):
        rows.update(r for r in range(edge - 4, edge + 4) if 0 <= r < prefill)
    return sorted(rows) + list(range(prefill, prefill + decode))


def engine_mamba_and_head(engine, n_rows: int, span: int, state_fault=None):
    """``run(params, x, layer, nth)``: one Mamba layer as the engine runs
    it over ``n_rows`` sequences of ``PREFILL + DECODE`` tokens (row
    ``r``'s tokens are ``x[r * span + t]``): chunk by chunk through
    ``forward_ragged``, the state in a pool, then a step at a time through
    ``forward``. Returns (the logits at ``head_rows`` [n_rows,
    len(head_rows), V] float32, the scan's state of every row [2, n_rows,
    S, E] after the last prefilled and after the last decoded token).
    ``state_fault`` (the tests' faults on the engine's side) maps the pool
    after the second chunk."""
    import jax
    import jax.numpy as jnp

    from dynamo_exp_tpu.models import llama

    impl, interpret, q_tile, page, kv_dtype = _attention_form(engine)
    one, build = _one_layer_model(engine, "mamba")
    if CHUNK % max(q_tile, 8) or PREFILL % CHUNK:
        raise ValueError(f"chunks of {CHUNK} do not tile {PREFILL} tokens / q_tile {q_tile}")
    wanted = head_rows(PREFILL, CHUNK, DECODE)
    row_of = jnp.repeat(jnp.arange(n_rows, dtype=jnp.int32), CHUNK)
    table = jnp.zeros((n_rows, 1), jnp.int32)  # no attention layer: no page is read
    slots = jnp.arange(n_rows, dtype=jnp.int32)
    base = slots * span

    @jax.jit
    def prefill(params, x, state, start, out_at, layer, nth):
        k, v = llama.init_kv_cache(one, 1, page, dtype=kv_dtype)
        within = jnp.arange(CHUNK, dtype=jnp.int32)
        tokens = (base[:, None] + start + within[None]).reshape(-1)
        positions = jnp.tile(start + within, n_rows)
        last = slots * CHUNK + CHUNK - 1  # each row's last token in the stream
        out_idx = (slots[:, None] * CHUNK + out_at[None]).reshape(-1)
        logits, _k, _v, state = llama.forward_ragged(
            build(params, x, layer, nth), one, tokens, positions, row_of, table, k, v, out_idx,
            attn_impl=impl, q_tile=q_tile, mesh=engine.mesh, interpret=interpret,
            seq_state=state, state_rows=(slots, last, slots),
        )
        return logits.reshape(n_rows, out_at.shape[0], -1), state

    @jax.jit
    def step(params, x, state, t, layer, nth):
        k, v = llama.init_kv_cache(one, 1, page, dtype=kv_dtype)
        logits, _k, _v, state = llama.forward(
            build(params, x, layer, nth), one, (base + t)[:, None],
            jnp.full((n_rows, 1), t, jnp.int32), table, k, v,
            attn_impl=impl, mesh=engine.mesh, interpret=interpret,
            seq_state=state, state_slots=slots,
        )
        return logits[:, 0], state

    def run(params, x, layer, nth):
        state = llama.init_seq_state(one, n_rows, dtype=x.dtype)
        out = []
        for c, start in enumerate(range(0, PREFILL, CHUNK)):
            at = [r - start for r in wanted if start <= r < start + CHUNK]
            logits, state = prefill(
                params, x, state, jnp.asarray(start, jnp.int32), jnp.asarray(at, jnp.int32),
                layer, nth,
            )
            out.append(np.asarray(logits))
            if state_fault is not None and c == 1:
                state = state_fault(state)
        held = [np.asarray(state.ssm[0])]
        for t in range(PREFILL, PREFILL + DECODE):
            logits, state = step(params, x, state, jnp.asarray(t, jnp.int32), layer, nth)
            out.append(np.asarray(logits)[:, None])
        held.append(np.asarray(state.ssm[0]))
        return np.concatenate(out, axis=1), np.stack(held)

    return run


def engine_attention_and_head(engine, n_rows: int, span: int, n_out: int):
    """``run(params, x, positions, out_idx, layer, nth)``: the mixed
    program's forward over one attention layer, a whole sequence a row.
    Logits [n_rows * n_out, V] float32 at the stream's ``out_idx``."""
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
    def run(params, x, positions, out_idx, layer, nth):
        k, v = llama.init_kv_cache(one, n_rows * pages_a_row, page, dtype=kv_dtype)
        every = jnp.arange(n, dtype=jnp.int32)
        return llama.forward_ragged(
            build(params, x, layer, nth), one, every, positions, row_of, table, k, v, out_idx,
            attn_impl=impl, q_tile=q_tile, mesh=engine.mesh, interpret=interpret,
        )[0]

    return run


def layers_read(hf: dict) -> tuple[list[int], list[int]]:
    """(the Mamba layers part 1 reads: first, middle, last; the attention
    layers part 2 reads: all of them, first and last where more than two)."""
    period, offset = int(hf["attn_layer_period"]), int(hf["attn_layer_offset"])
    attn = [i for i in range(int(hf["num_hidden_layers"])) if i % period == offset]
    mamba = [i for i in range(int(hf["num_hidden_layers"])) if i % period != offset]
    return sorted({mamba[0], mamba[len(mamba) // 2], mamba[-1]}), sorted({attn[0], attn[-1]})


def op_half_part(ctx: dict, entering: list[np.ndarray], layers: list[int]) -> dict:
    """Parts 1 and 2. ``entering``: for each long sequence, [len(layers),
    LONG, D]: the reference's hidden state entering each of ``layers``."""
    import jax
    import jax.numpy as jnp

    reference, engine, hf = ctx["reference"], ctx["engine"], ctx["hf"]
    mcfg = engine.cfg.model
    long = PREFILL + DECODE
    span = -(-long // SPAN_ALIGN) * SPAN_ALIGN
    n_rows = len(entering)
    wanted = head_rows(PREFILL, CHUNK, DECODE)
    positions = np.tile(np.where(np.arange(span) < long, np.arange(span), -1), n_rows)
    out_idx = np.concatenate([r * span + np.asarray(wanted) for r in range(n_rows)])
    runs = {
        "mamba": engine_mamba_and_head(engine, n_rows, span, ctx.get("state_fault")),
        "attn": engine_attention_and_head(engine, n_rows, span, len(wanted)),
    }
    cells = []
    for i, layer in enumerate(layers):
        op = mcfg.op_kinds[layer]
        nth = mcfg.op_kinds[:layer].count(op)
        x = np.zeros((n_rows, span, entering[0].shape[-1]), np.float32)
        for s, e in enumerate(entering):
            x[s, :long] = e[i]
        x = jnp.asarray(x, jnp.bfloat16)
        flat = x.reshape(n_rows * span, -1)
        if op == "attn":
            logits = runs[op](
                engine.params, flat, jnp.asarray(positions, jnp.int32),
                jnp.asarray(out_idx, jnp.int32), layer, nth,
            )
            logits = np.asarray(logits).reshape(n_rows, len(wanted), -1)
        else:
            logits, held = runs[op](engine.params, flat, layer, nth)
        err, state_err = [], []
        for s in range(n_rows):
            got = np.asarray(jax.nn.log_softmax(jnp.asarray(logits[s]), axis=-1))
            want = reference.op_half_and_head(ctx["params"], hf, layer, x[s, :long], rows=wanted)
            spread = want - want.mean(axis=-1, keepdims=True)
            err.append(np.linalg.norm(got - want, axis=-1) / np.linalg.norm(spread, axis=-1))
            for moment, upto in enumerate((PREFILL, long) if op == "mamba" else ()):
                state = reference.scan_state(ctx["params"], hf, layer, x[s, :upto])
                state_err.append(
                    float(np.linalg.norm(held[moment, s] - state) / np.linalg.norm(state))
                )
        err = np.stack(err)  # [n_rows, len(wanted)]
        decoded = err[:, -DECODE:]
        cells.append({
            "layer": layer, "op": op, "rows": int(err.size),
            "max_rel_err": float(np.nan_to_num(err, nan=np.inf).max()),
            "median_rel_err": float(np.median(err)),
            # The rows computed a step at a time from the state (a Mamba layer).
            "max_rel_err_decoded": (
                float(np.nan_to_num(decoded, nan=np.inf).max()) if op == "mamba" else None
            ),
            # The pool's scan state against the reference's (a Mamba layer).
            "state_rel_err": (
                float(np.nan_to_num(np.asarray(state_err), nan=np.inf).max())
                if op == "mamba" else None
            ),
        })
    return {"op_half": cells}


def long_sequences(seed: int, vocab: int) -> list[list[int]]:
    rng = np.random.default_rng(seed % 2**32)
    return [
        [int(t) for t in rng.integers(8, vocab, PREFILL + DECODE)] for _ in range(ROWS)
    ]


def compare(ctx: dict) -> dict:
    walk = correct.walk_reference(ctx, PROBES, whole_vocabulary=True)
    if "why" in walk:
        return {"ok": False, "why": walk["why"]}
    diffs = walk["step_diffs"]
    out = {
        "max_abs_logprob_diff": max(diffs), "tol": WALK_TOL, "steps": len(diffs),
        "mamba_tol": MAMBA_TOL, "state_tol": STATE_TOL, "attn_tol": ATTN_TOL,
    }
    hf = ctx["hf"]
    mamba_layers, attn_layers = layers_read(hf)
    every = sorted(set(mamba_layers) | set(attn_layers))
    entering = [
        ctx["reference"].hidden_states(ctx["params"], hf, ids, every)[0]
        for ids in long_sequences(ctx["seed"], int(hf["vocab_size"]))
    ]
    out.update(op_half_part(ctx, entering, every))
    out["step_diffs"] = [round(d, 5) for d in diffs]
    faults = []
    if not out["max_abs_logprob_diff"] <= WALK_TOL:
        faults.append(f"walk: max |diff| {out['max_abs_logprob_diff']:.4f} > {WALK_TOL}")
    for op, tol in (("mamba", MAMBA_TOL), ("attn", ATTN_TOL)):
        bad = max((c for c in out["op_half"] if c["op"] == op), key=lambda c: c["max_rel_err"])
        out[f"{op}_max_rel_err"] = bad["max_rel_err"]
        if not bad["max_rel_err"] <= tol:
            faults.append(
                f"{op}: layer {bad['layer']} differs by {bad['max_rel_err']:.4f} of the "
                f"log-probabilities' spread > {tol}"
            )
    worst = max((c for c in out["op_half"] if c["op"] == "mamba"), key=lambda c: c["state_rel_err"])
    out["state_max_rel_err"] = worst["state_rel_err"]
    if not worst["state_rel_err"] <= STATE_TOL:
        faults.append(
            f"state: layer {worst['layer']}'s scan state differs by "
            f"{worst['state_rel_err']:.4f} of its size > {STATE_TOL}"
        )
    out["ok"], out["why"] = not faults, "; ".join(faults) or None
    return out
