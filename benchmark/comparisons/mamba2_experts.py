"""The comparison for a model whose layers are ONE half each, a Mamba-2
mixer, routed experts of two matrices or attention (``"comparison":
"mamba2_experts"``): nemotron_h's stack. ``state_space``'s scheme (that
module's text: a recurrent state outlives a dispatch and a fault in it
decays within a few hundred tokens, so the operator is compared at the
sizes the timed path runs it at, on the SAME input, rounded to the model's bfloat16) with
``routed_experts``'s block part for the ``E`` layers, each at its own
constants.

1. THE MAMBA-2 LAYER WITH THE HEAD of the first, a middle and the last ``M``
   layer, over a one-layer model made of the engine's own arrays (that
   layer's norm and mixer, no second half, the engine's final norm and head;
   the input rows stand in the embedding's place). Two sequences of ``LONG``
   tokens whose hidden states entering the layer are the reference's: the
   first ``PREFILL`` tokens of both in chunks of ``CHUNK`` through
   ``forward_ragged`` (the mixed program's forward: both rows' chunks in one
   stream, the chunk kernel on the chip), the state crossing every
   dispatch boundary through the pool, then every later token one step at a
   time through ``forward`` (the decode window's forward, the in-place
   step). Two numbers. The largest row of ``|engine - reference|`` over the
   log-probabilities, relative to their spread, at ``head_rows``. And THE
   STATE ITSELF: the pool's float32 ``[S, H * P]`` entry of each sequence
   after the last prefilled token and after the last decoded one, against
   the reference's, ``|engine - reference| / |reference|``.

2. THE ATTENTION LAYER WITH THE HEAD of both ``*`` layers: a whole sequence a
   row through ``forward_ragged`` (the attention kernel at 2 KV heads under
   32 query heads, no rotary term), same number, own limit.

3. THE EXPERT LAYER of the first, the middle and the last ``E`` layer, at 64
   rows ([64, 1, D], a decode bucket) and 1024 rows ([1, 1024, D], a mixed
   dispatch), through ``models/llama.py::_ffn_block`` with the engine's own
   stacks and the layer's index (the grouped-matmul kernel in place over the
   held experts, two products a pick, the shared expert once), against the
   reference's router and dense held experts. Rows whose reference margin
   (between the 6th and the 7th of ``s + b``) is under ``NEAR_TIE`` are left
   out and counted. Number: the largest row of ``|engine - reference| /
   |reference - input|``: the picks, their weights, the held sum and the
   shared expert in one.

4. THE SERVED PATH: ``logprob_walk``'s walk over four probes (32 decode
   steps), the MEDIAN of the steps' largest differences (a routed model's
   walk holds no precision: ``routed_experts``, part 3); it holds the wiring
   the other three call no part of: the scheduler, the page tables, the
   state pool's entries, decode through cache and state, a second asking.

TOLERANCES, each between two readings on the chip, beside the constants
below (PERF.md section 2 has the table): the sound program's largest over
the seeds read, and the smallest a control gave
(``rehearse/check_seeds_nemotron.py --control``: the reference computing
with that fault against the sound program).
"""
from __future__ import annotations

import dataclasses
import statistics

import numpy as np

# A tree that cannot serve such a model ends here, before the engine is
# built (``comparisons.resolve`` imports this module first).
from dynamo_exp_tpu.models.llama import MAMBA2_PARAMS, mamba2_mixer  # noqa: F401

from comparisons.layer_kinds import _attention_form
from comparisons.state_space import head_rows
from lib import correct
from lib.stats import percentile

PROBES = 4  # x correct.PROBE_DECODE_TOKENS = 32 decode steps
ROWS = 2  # long sequences
PREFILL = 2048  # tokens of each fed in chunks through forward_ragged
CHUNK = 512  # the engine's prefill_chunk
DECODE = 32  # tokens of each fed a step at a time through forward
SPAN_ALIGN = 64  # a sequence's slots in the flat stream: a multiple of any q_tile
BLOCK_ROWS = (64, 1024)
REAL_ROW_SHARE = 0.75  # of a block input; the rest are seeded rows

# A row is left out of the expert part when the reference's 6th and 7th of ``s + b`` are
# nearer than this: the engine's router reads the normed row rounded to bfloat16, which
# moves a sigmoid score by ~1e-3.
NEAR_TIE = 0.005
# Mamba-2 part, largest row of |engine - reference| over the log-probabilities relative
# to their spread. Steady. nemotron-3-nano-30b-a3b-l13e64, one chip (my chip runs, PR 63:
# check_seeds_nemotron.py and the cell's own runs, the readings and their seeds in PERF.md
# section 2): sound 0.0054-0.0059 over 19 seeds (layer 0, whose input is the embedding;
# layers 7 / 11 0.0032-0.0037; the rows decoded from the state no worse than the prefilled
# ones). Controls (the smallest first): fp8 (e4m3) in_proj / out_proj 0.0654-0.0681; every
# weight in fp8 0.0735-0.0760; the norm before the gate 0.347; the state zeroed where the
# second chunk begins 0.572; one norm over 4096 channels in place of 8 groups 0.651. 3.4 x
# above the one, 3.3 x below the other. What passes it and is held by STATE_TOL: the state
# kept in bfloat16, 0.0080-0.0095.
MAMBA_TOL = 0.02
# The state in the pool against the reference's, |engine - reference| / |reference|,
# after 2048 prefilled tokens (the chunk kernel wrote it) and after 32 decoded more (the
# in-place step did). Sound 0.0029-0.0047 over 19 seeds (one seed 0.0047, the rest under
# 0.0039); the state rounded to bfloat16 after every token 0.0415-0.0460 (three seeds);
# fp8 in_proj / out_proj 0.0405-0.0437 (a state zeroed 1536 tokens earlier has all but
# come back: 0.0144, which the Mamba-2 part holds at 0.572). 3.0 x above, 2.9 x below.
STATE_TOL = 0.014
# Attention part, the same number for both * layers (2 KV heads under 32 query heads, no
# rotary term, a whole 2080-token sequence a row through the kernel). Sound 0.0032-0.0036
# over 19 seeds; every weight in fp8 0.0380-0.0390 (layer 5). 3.3 x above, 3.2 x below.
ATTN_TOL = 0.012
# Expert part, largest row of |engine - reference| / |reference - input| (layers 1 / 6 / 10:
# 0.0057-0.0061 / 0.0059-0.0063 / 0.0071-0.0076, the same at 64 and 1024 rows; ~890 of
# 6528 rows a seed near a tie and left out; 50-53 % of the chosen experts held). Sound
# 0.0072-0.0080 over 19 seeds. Controls: fp8 expert and shared matrices 0.0575-0.0582; the
# selection bias taken into the weights 0.108-0.125; the scale 2.5 left out 0.603-0.627;
# relu for relu^2 1.07; the
# shared expert left out: not finite (a row none of whose picks is held then adds
# nothing). 2.6 x above, 2.9 x below.
BLOCK_TOL = 0.02
# Served part, median over the 32 steps of the step's largest difference. NOT a precision
# limit (every weight in fp8 reads 0.34-0.42, the bias as a weight 0.26-0.27 and pass or
# nearly; parts 1 to 3 hold those) but the wiring's: sound 0.028-0.159 over 19 seeds (top-6
# of 128 sigmoid scores plus a bias in 5 layers: more tokens near a tie than 8 experts give);
# a wrong rule: the scale left out 0.686-0.924, one norm 1.11, the norm before the gate
# 1.13-1.17, relu 1.22-1.28, no shared expert 2.85-3.08. 2.1 x above, 2.1 x below.
MEDIAN_TOL = 0.33


def _one_layer_model(engine, kind: str):
    """The configuration and the parameter builder of a one-layer model of
    an ``M`` or a ``*`` layer (no second half: ``ff_types`` "none") and the
    engine's head: ``build(params, x, norm, nth)`` takes the ``nth`` layer of
    that mixer's stacks and ``attn_norm`` at ``norm``."""
    import jax

    mcfg = engine.cfg.model
    one = dataclasses.replace(
        mcfg, num_layers=1, num_experts=0, num_experts_held=0, first_expert_held=0,
        layer_types=("mamba2" if kind == "M" else "full_attention",), ff_types=("none",),
    )
    names = MAMBA2_PARAMS if kind == "M" else ("wq", "wk", "wv", "wo")

    def build(params, x, norm, nth):
        src = params["layers"]
        layers = {k: jax.lax.dynamic_slice_in_dim(src[k], nth, 1, 0) for k in names}
        layers["attn_norm"] = jax.lax.dynamic_slice_in_dim(src["attn_norm"], norm, 1, 0)
        return {
            "embed": x, "layers": layers, "final_norm": params["final_norm"],
            "lm_head": params["lm_head"],
        }

    return one, build


def engine_mamba2_and_head(engine, n_rows: int, span: int, state_fault=None):
    """``run(params, x, norm, nth)``: one Mamba-2 layer as the engine runs it
    over ``n_rows`` sequences of ``PREFILL + DECODE`` tokens (row ``r``'s
    tokens are ``x[r * span + t]``): chunk by chunk through
    ``forward_ragged``, the state in a pool, then a step at a time through
    ``forward``. Returns (the logits at ``head_rows`` [n_rows,
    len(head_rows), V] float32, the state of every row [2, n_rows, S, H * P]
    after the last prefilled and after the last decoded token).
    ``state_fault`` (the tests' faults on the engine's side) maps the pool
    after the second chunk."""
    import jax
    import jax.numpy as jnp

    from dynamo_exp_tpu.models import llama

    impl, interpret, q_tile, page, kv_dtype = _attention_form(engine)
    one, build = _one_layer_model(engine, "M")
    if CHUNK % max(q_tile, 8) or PREFILL % CHUNK:
        raise ValueError(f"chunks of {CHUNK} do not tile {PREFILL} tokens / q_tile {q_tile}")
    wanted = head_rows(PREFILL, CHUNK, DECODE)
    row_of = jnp.repeat(jnp.arange(n_rows, dtype=jnp.int32), CHUNK)
    table = jnp.zeros((n_rows, 1), jnp.int32)  # no attention layer: no page is read
    slots = jnp.arange(n_rows, dtype=jnp.int32)
    base = slots * span

    @jax.jit
    def prefill(params, x, state, start, out_at, norm, nth):
        k, v = llama.init_kv_cache(one, 1, page, dtype=kv_dtype)
        within = jnp.arange(CHUNK, dtype=jnp.int32)
        tokens = (base[:, None] + start + within[None]).reshape(-1)
        positions = jnp.tile(start + within, n_rows)
        last = slots * CHUNK + CHUNK - 1  # each row's last token in the stream
        out_idx = (slots[:, None] * CHUNK + out_at[None]).reshape(-1)
        logits, _k, _v, state = llama.forward_ragged(
            build(params, x, norm, nth), one, tokens, positions, row_of, table, k, v, out_idx,
            attn_impl=impl, q_tile=q_tile, mesh=engine.mesh, interpret=interpret,
            seq_state=state, state_rows=(slots, last, slots),
        )
        return logits.reshape(n_rows, out_at.shape[0], -1), state

    @jax.jit
    def step(params, x, state, t, norm, nth):
        k, v = llama.init_kv_cache(one, 1, page, dtype=kv_dtype)
        logits, _k, _v, state = llama.forward(
            build(params, x, norm, nth), one, (base + t)[:, None],
            jnp.full((n_rows, 1), t, jnp.int32), table, k, v,
            attn_impl=impl, mesh=engine.mesh, interpret=interpret,
            seq_state=state, state_slots=slots,
        )
        return logits[:, 0], state

    def run(params, x, norm, nth):
        state = llama.init_seq_state(one, n_rows, dtype=x.dtype)
        out = []
        for c, start in enumerate(range(0, PREFILL, CHUNK)):
            at = [r - start for r in wanted if start <= r < start + CHUNK]
            logits, state = prefill(
                params, x, state, jnp.asarray(start, jnp.int32), jnp.asarray(at, jnp.int32),
                norm, nth,
            )
            out.append(np.asarray(logits))
            if state_fault is not None and c == 1:
                state = state_fault(state)
        held = [np.asarray(state.ssm[0])]
        for t in range(PREFILL, PREFILL + DECODE):
            logits, state = step(params, x, state, jnp.asarray(t, jnp.int32), norm, nth)
            out.append(np.asarray(logits)[:, None])
        held.append(np.asarray(state.ssm[0]))
        return np.concatenate(out, axis=1), np.stack(held)

    return run


def engine_attention_and_head(engine, n_rows: int, span: int):
    """``run(params, x, positions, out_idx, norm, nth)``: the mixed program's
    forward over one attention layer, a whole sequence a row. Logits
    [len(out_idx), V] float32 at the stream's ``out_idx``."""
    import jax
    import jax.numpy as jnp

    from dynamo_exp_tpu.models import llama

    impl, interpret, q_tile, page, kv_dtype = _attention_form(engine)
    if span % q_tile:
        raise ValueError(f"a row's span {span} is no multiple of the kernel's q_tile {q_tile}")
    one, build = _one_layer_model(engine, "*")
    pages_a_row = -(-span // page)
    n = n_rows * span
    row_of = jnp.repeat(jnp.arange(n_rows, dtype=jnp.int32), span)
    table = jnp.arange(n_rows * pages_a_row, dtype=jnp.int32).reshape(n_rows, pages_a_row)

    @jax.jit
    def run(params, x, positions, out_idx, norm, nth):
        k, v = llama.init_kv_cache(one, n_rows * pages_a_row, page, dtype=kv_dtype)
        every = jnp.arange(n, dtype=jnp.int32)
        return llama.forward_ragged(
            build(params, x, norm, nth), one, every, positions, row_of, table, k, v, out_idx,
            attn_impl=impl, q_tile=q_tile, mesh=engine.mesh, interpret=interpret,
        )[0]

    return run


def engine_experts(engine):
    """``run(layers, x, nth)``: ``models/llama.py``'s ``_ffn_block`` for the
    ``nth`` routed layer on ``x`` ([B, T, D] bfloat16), jitted on the engine's
    mesh: the small parameters indexed out, the expert stacks whole with the
    layer's index, as ``_scan_paged_layers`` hands them over."""
    import jax

    from dynamo_exp_tpu.models import llama

    mcfg, mesh = engine.cfg.model, engine.mesh
    interpret = getattr(engine, "_attn_interpret", False)
    small = ("mlp_norm", "router", "router_bias", "shared_up", "shared_down")

    @jax.jit
    def run(layers, x, nth):
        lp = {k: jax.lax.dynamic_index_in_dim(layers[k], nth, 0, keepdims=False) for k in small}
        lp.update(w_up=layers["w_up"], w_down=layers["w_down"])
        return llama._ffn_block(
            x, lp, mcfg, mcfg.rms_norm_eps, lambda y: y, mesh, layer=nth, interpret=interpret,
        )

    return run


def layers_read(pattern: str) -> tuple[list[int], list[int], list[int]]:
    """(the ``M`` layers part 1 reads: first, middle, last; the ``*`` layers
    part 2 reads: first and last; the ``E`` layers part 3 reads: first,
    middle, last), as indices into the pattern."""
    def of(kind):
        return [i for i, c in enumerate(pattern) if c == kind]

    m, a, e = of("M"), of("*"), of("E")
    return (
        sorted({m[0], m[len(m) // 2], m[-1]}), sorted({a[0], a[-1]}),
        sorted({e[0], e[len(e) // 2], e[-1]}),
    )


def mixer_part(ctx: dict, entering: list[np.ndarray], layers: list[int]) -> dict:
    """Parts 1 and 2. ``entering``: for each long sequence, [len(layers),
    LONG, D]: the reference's hidden state entering each of ``layers``."""
    import jax
    import jax.numpy as jnp

    reference, engine, hf = ctx["reference"], ctx["engine"], ctx["hf"]
    pattern = str(hf["hybrid_override_pattern"])
    dtype = engine.params["final_norm"].dtype  # bfloat16 but in a float32 rehearsal
    long = PREFILL + DECODE
    span = -(-long // SPAN_ALIGN) * SPAN_ALIGN
    n_rows = len(entering)
    wanted = head_rows(PREFILL, CHUNK, DECODE)
    positions = np.tile(np.where(np.arange(span) < long, np.arange(span), -1), n_rows)
    out_idx = np.concatenate([r * span + np.asarray(wanted) for r in range(n_rows)])
    runs = {
        "M": engine_mamba2_and_head(engine, n_rows, span, ctx.get("state_fault")),
        "*": engine_attention_and_head(engine, n_rows, span),
    }
    cells = []
    for i, layer in enumerate(layers):
        kind, norm, nth = reference.where(pattern, layer)
        x = np.zeros((n_rows, span, entering[0].shape[-1]), np.float32)
        for s, e in enumerate(entering):
            x[s, :long] = e[i]
        x = jnp.asarray(x, dtype)
        flat = x.reshape(n_rows * span, -1)
        if kind == "*":
            logits = runs[kind](
                engine.params, flat, jnp.asarray(positions, jnp.int32),
                jnp.asarray(out_idx, jnp.int32), norm, nth,
            )
            logits = np.asarray(logits).reshape(n_rows, len(wanted), -1)
        else:
            logits, held = runs[kind](engine.params, flat, norm, nth)
        err, state_err = [], []
        for s in range(n_rows):
            got = np.asarray(jax.nn.log_softmax(jnp.asarray(logits[s]), axis=-1))
            want = reference.layer_and_head(ctx["params"], hf, layer, x[s, :long], rows=wanted)
            spread = want - want.mean(axis=-1, keepdims=True)
            err.append(np.linalg.norm(got - want, axis=-1) / np.linalg.norm(spread, axis=-1))
            for moment, upto in enumerate((PREFILL, long) if kind == "M" else ()):
                state = reference.scan_state(ctx["params"], hf, layer, x[s, :upto])
                state_err.append(
                    float(np.linalg.norm(held[moment, s] - state) / np.linalg.norm(state))
                )
        err = np.stack(err)  # [n_rows, len(wanted)]
        decoded = err[:, -DECODE:]
        cells.append({
            "layer": layer, "op": kind, "rows": int(err.size),
            "max_rel_err": float(np.nan_to_num(err, nan=np.inf).max()),
            "median_rel_err": float(np.median(err)),
            "max_rel_err_decoded": (
                float(np.nan_to_num(decoded, nan=np.inf).max()) if kind == "M" else None
            ),
            "state_rel_err": (
                float(np.nan_to_num(np.asarray(state_err), nan=np.inf).max())
                if kind == "M" else None
            ),
        })
    return {"op_half": cells}


def experts_part(ctx: dict, entering: np.ndarray, layers: list[int]) -> dict:
    """Part 3. ``entering``: [len(layers), real rows, D], the reference's
    hidden state entering each ``E`` layer, the sequences' rows one after
    another."""
    import jax.numpy as jnp

    reference, engine, hf = ctx["reference"], ctx["engine"], ctx["hf"]
    pattern = str(hf["hybrid_override_pattern"])
    rng = np.random.default_rng(ctx["seed"] % 2**32)
    run = engine_experts(engine)
    cells, near, share = [], 0, []
    for i, layer in enumerate(layers):
        _kind, _norm, nth = reference.where(pattern, layer)
        real = entering[i]
        rms = float(np.sqrt(np.mean(real * real)))
        for rows in BLOCK_ROWS:
            n_real = min(len(real), int(rows * REAL_ROW_SHARE))
            pick = np.linspace(0, len(real) - 1, n_real).astype(int)
            seeded = rng.standard_normal((rows - n_real, real.shape[1]), np.float32) * rms
            x = jnp.asarray(
                np.concatenate([real[pick], seeded]), engine.params["final_norm"].dtype
            )
            shaped = x[:, None] if rows == BLOCK_ROWS[0] else x[None]
            got = np.asarray(
                run(engine.params["layers"], shaped, nth).astype(jnp.float32)
            ).reshape(rows, -1)
            want, margin, here = reference.experts_layer(ctx["params"], hf, layer, x)
            added = want - np.asarray(x.astype(jnp.float32))
            err = np.linalg.norm(got - want, axis=1) / np.linalg.norm(added, axis=1)
            keep = margin >= NEAR_TIE
            cells.append({
                "layer": layer, "rows": rows, "rows_near_tie": int((~keep).sum()),
                "held_pick_share": here,
                "max_rel_err": float(np.nan_to_num(err[keep], nan=np.inf).max()),
                "median_rel_err": float(np.median(err[keep])),
                "max_rel_err_near_tie": float(err[~keep].max()) if (~keep).any() else None,
            })
            near += cells[-1]["rows_near_tie"]
            share.append(here)
    return {
        "block": cells, "block_max_rel_err": max(c["max_rel_err"] for c in cells),
        "rows_near_tie": near, "held_pick_share": float(np.mean(share)),
    }


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
        "max_abs_logprob_diff": max(diffs), "tol": MEDIAN_TOL, "steps": len(diffs),
        "median_abs_logprob_diff": statistics.median(diffs),
        "p90_abs_logprob_diff": percentile(diffs, 90),
        "mamba_tol": MAMBA_TOL, "state_tol": STATE_TOL, "attn_tol": ATTN_TOL,
        "block_tol": BLOCK_TOL, "near_tie": NEAR_TIE,
    }
    hf = ctx["hf"]
    reference = ctx["reference"]
    mamba_layers, attn_layers, expert_layers = layers_read(str(hf["hybrid_override_pattern"]))
    mixers = sorted(set(mamba_layers) | set(attn_layers))
    every = sorted(set(mixers) | set(expert_layers))
    states = [
        reference.hidden_states(ctx["params"], hf, ids, every)
        for ids in long_sequences(ctx["seed"], int(hf["vocab_size"]))
    ]
    at = {layer: i for i, layer in enumerate(every)}
    out.update(mixer_part(ctx, [s[[at[l] for l in mixers]] for s in states], mixers))
    # the expert layers' inputs: every 8th row of the long sequences
    rows = np.concatenate([s[[at[l] for l in expert_layers]][:, ::8] for s in states], axis=1)
    out.update(experts_part(ctx, rows, expert_layers))
    out["step_diffs"] = [round(d, 5) for d in diffs]
    faults = []
    if not out["median_abs_logprob_diff"] <= MEDIAN_TOL:
        faults.append(f"walk: median |diff| {out['median_abs_logprob_diff']:.4f} > {MEDIAN_TOL}")
    for op, name, tol in (("M", "mamba", MAMBA_TOL), ("*", "attn", ATTN_TOL)):
        bad = max((c for c in out["op_half"] if c["op"] == op), key=lambda c: c["max_rel_err"])
        out[f"{name}_max_rel_err"] = bad["max_rel_err"]
        if not bad["max_rel_err"] <= tol:
            faults.append(
                f"{name}: layer {bad['layer']} differs by {bad['max_rel_err']:.4f} of the "
                f"log-probabilities' spread > {tol}"
            )
    worst = max((c for c in out["op_half"] if c["op"] == "M"), key=lambda c: c["state_rel_err"])
    out["state_max_rel_err"] = worst["state_rel_err"]
    if not worst["state_rel_err"] <= STATE_TOL:
        faults.append(
            f"state: layer {worst['layer']}'s state differs by "
            f"{worst['state_rel_err']:.4f} of its size > {STATE_TOL}"
        )
    bad = max(out["block"], key=lambda c: c["max_rel_err"])
    if not bad["max_rel_err"] <= BLOCK_TOL:
        faults.append(
            f"experts: layer {bad['layer']} at {bad['rows']} rows differs by "
            f"{bad['max_rel_err']:.4f} of what the layer adds > {BLOCK_TOL}"
        )
    out["ok"], out["why"] = not faults, "; ".join(faults) or None
    return out
