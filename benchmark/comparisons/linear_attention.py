"""The comparison for a model whose layers are linear attention by the
gated delta rule and softmax attention (``"comparison":
"linear_attention"``): olmo_hybrid's periods of three Gated DeltaNet
layers and one multi-head attention layer without positions, dense
feed-forwards throughout, Olmo 2's block.

What a linear-attention layer adds to the question "does the served path
compute the model" is ``state_space``'s: a recurrent state that outlives
a dispatch, here a float32 matrix ``[dk, dv]`` a head (2.2 MB a layer and
sequence) in a pool beside the pages, read where a prompt chunk begins
and written where it ends, computed on the chip in a chunked matrix form
that is not the recurrence's own text. So the three parts are that
module's, at the sizes the timed path runs them at, half a layer at a
time on the SAME bfloat16 input:

1. THE LINEAR HALF WITH THE HEAD of the first, a middle and the last
   linear layer, over a one-layer model made of the engine's own arrays
   (that layer's mixer and its output's norm, a feed-forward of zeros, the
   engine's final norm and head; the input rows stand in the embedding's
   place). Two sequences of ``LONG`` tokens whose hidden states entering
   the layer are the reference's: the first ``PREFILL`` tokens of both in
   chunks of ``CHUNK`` through ``forward_ragged`` (both rows' chunks in
   one stream, the delta-rule kernel on the chip), the state crossing 3
   dispatch boundaries through the pool, then every later token one step
   at a time through ``forward`` (the one-step form). Two numbers. The
   largest row of ``|engine - reference|`` over the log-probabilities,
   relative to their spread, at ``head_rows``. And THE STATE ITSELF: the
   pool's float32 entry of each sequence after the last prefilled token
   and after the last decoded one, against the reference's, ``|engine -
   reference| / |reference|``, a head at a time and the worst head taken:
   the heads forget at rates three orders apart (``exp(A_log)`` in (0, 16)
   times a step of 1e-3 to 1e-1), a state kept in too few bits shows in
   the heads that remember, and a norm over all heads would average it
   away. EACH MOMENT BY ITS OWN LIMIT, because two programs wrote them:
   the chunked kernel the first (2048 tokens, float32 inputs from end to
   end), the one-step form the second (32 tokens, each reading the three
   convolution inputs before it as the pool's entry holds them, in
   bfloat16), and the second reads ten to a hundred times the first.

2. THE ATTENTION HALF WITH THE HEAD of both full layers: a whole
   sequence a row through ``forward_ragged`` (the attention kernel at 30
   KV heads of one query head each, no rotary term, q and k normed over
   the whole projection), same number, own limit.

3. THE SERVED PATH: ``logprob_walk``'s walk over four probes (32 decode
   steps), the largest difference: the wiring the other two call no part
   of (the scheduler, the page tables, the state pool's entries, decode
   through cache and state, a second asking). And the same walk over ONE
   LONG PROBE of ``LONG_PROBE_TOKENS``, asked while ``LIVE_BESIDE`` other
   requests decode: its prompt takes three mixed dispatches or more of
   the engine's own compiled program (``_plan_chunks``: at most 992 tokens
   a dispatch beside decode rows, 512 alone), the decode rows of the
   others in each one's packed tail, so its state crosses two dispatch
   boundaries or more through the pool's entry the engine gave it, among
   live entries. A state lost, stale or taken from another slot between
   a prompt's dispatches shows here and nowhere else on the chip.

TOLERANCES, each between two readings on the chip, beside the constants
below (PERF.md section 2 has the table): the sound program's largest over
the seeds read, and the smallest a control gave
(``rehearse/check_seeds_olmo_hybrid.py --control``: the reference
computing with that fault against the sound program).
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import json
import threading
import urllib.request

import numpy as np

# A tree that cannot serve such a model ends here, before the engine is
# built (``comparisons.resolve`` imports this module first).
from dynamo_exp_tpu.models.llama import LINEAR_PARAMS, gated_delta_mixer  # noqa: F401

from comparisons.layer_kinds import _attention_form
from comparisons.state_space import (  # the sizes and the rows are that module's
    CHUNK, DECODE, PREFILL, PROBES, SPAN_ALIGN, head_rows, long_sequences,
)
from lib import correct
from lib.stats import parse_prometheus, sample_sum

# All readings my chip runs, PR 39 (rehearse/check_seeds_olmo_hybrid.py, olmo-hybrid-7b-l8 on one
# v5e chip: 13 seeds sound, three seeds a control, the smallest given).
#
# Linear part, largest row of |engine - reference| over the log-probabilities relative to their
# spread. Sound 0.0060-0.0062 over 13 seeds (layer 0, whose input is the embedding's; layer 4
# 0.0033-0.0047; layer 6 0.0032-0.0033; the rows decoded a step at a time from the state no worse
# than the prefilled ones, 0.0048-0.0050 at layer 0). Controls: an fp8 (e4m3) head 0.0384; fp8
# q/k/v, gate and output projections 0.103; the step without its factor 2 0.406; the decay left
# out 0.944; the state zeroed where the second chunk begins 1.23; the output's gate left out
# 1.47; the taps reversed 1.49; q and k not normalised: not finite. 2.4 x above the one, 2.6 x
# below the other. What passes it and is held by the state's limits: the state rounded to
# bfloat16 after every chunk and step, 0.0089-0.0101, all of it in the decoded rows (the
# bfloat16 head's own rounding hides most of it).
MIXER_TOL = 0.015
# The delta rule's state in the pool against the reference's, the worst head's
# |engine - reference| / |reference|, at two moments that two programs wrote, each by its own
# limit (34 seeds sound, three or six a control: ``state_rel_err_by_moment`` in
# chiprun_out/bench/olmo-seeds-{a,b}.json, ``state_rel_err_prefill`` / ``_decode`` in -c.json).
#
# AFTER 2048 PREFILLED TOKENS: the chunked kernel's. Sound 0.00031-0.00033 at layer 0,
# 0.00001-0.00007 at layers 4 and 6: its float32 products at full precision add nothing to the
# bfloat16 products that feed it. THE STATE ROUNDED TO BFLOAT16 AFTER EVERY CHUNK of 64
# prefilled tokens (``bf16_state_prefill``: the prefill chunks alone, the decoded tokens sound)
# 0.00266-0.00270 (by layer 0.0027 / 0.0018 / 0.0017), not correct by this limit and by no
# other; zeroed at a chunk boundary 0.0238; fp8 projections 0.0759. 3.0 x above the one, 2.7 x
# below the other.
STATE_PREFILL_TOL = 0.001
# AFTER 32 DECODED TOKENS MORE: the one-step form's. Sound 0.0027-0.0033 (by layer 0.0022-0.0024
# / 0.0026-0.0033 / 0.0027-0.0031), TEN TO A HUNDRED TIMES the kernel's, and not the
# recurrence's doing: a decoded token reads the three convolution inputs before it from the
# pool's entry, which holds them in bfloat16 as every kind's convolution does, where a prefilled
# token reads them from the stream in float32; a rounding of 2**-9 on three of four taps of q, k
# and v is a step's whole input to the heads that forget in a token or two, which are the worst.
# Shown on the chip by ``bf16_conv_history`` (the REFERENCE reading a decoded token's three
# earlier inputs rounded to bfloat16): the state after decode then reads 0.00028-0.00029, the
# level after prefill. The state rounded to bfloat16 after every chunk and after every decoded
# token (``bf16_state``) 0.0090-0.0092 (by layer 0.0091 / 0.0065-0.0084 / 0.0059-0.0078);
# zeroed at a chunk boundary 2048 tokens before 0.0224 (the slow heads still hold the loss).
# 1.5 x above, 1.8 x below: the narrowest of the limits.
STATE_DECODE_TOL = 0.005
# Attention part, the same number for the two full layers (30 KV heads of one query head, no
# rotary term, q and k normed over the whole projection). Sound 0.0032-0.0036 over 13 seeds;
# fp8 attention weights 0.0262; an fp8 head 0.0381; rotary positions applied to q and k 0.238.
# 2.8 x above, 2.6 x below.
ATTN_TOL = 0.01
# Served part: the largest of 32 steps' differences. NOT a precision limit (an fp8 head reads
# 0.111-0.138, fp8 attention 0.126-0.145, a bfloat16 state and a state zeroed at a chunk boundary
# the sound value: 56-token probes cross none) but the wiring's: sound 0.055-0.088 over 13
# seeds; rotary positions 1.03; fp8 projections 1.02; the step without its 2 2.68; the decay
# left out 4.47; the gate left out 5.22; the taps reversed 5.44. 3.4 x above, 3.4 x below.
WALK_TOL = 0.3
# The long probe (part 3): its prompt's tokens, more than twice the 992 a mixed dispatch gives
# one prompt beside decode rows (a bucket of 1024 less its packed tail of 32), so three
# dispatches or more whatever rides along; the requests decoding beside it and the tokens each
# decodes (a window of 8 a dispatch: several times the probe's own dispatches).
LONG_PROBE_TOKENS = 2100
LIVE_BESIDE = 3
BESIDE_TOKENS = 1024
_ASKINGS = itertools.count()  # of the long probe by this process (``long_walk``)
# The largest of the long probe's 8 steps' differences: the wiring's limit again, and no
# precision's (my chip runs, PR 39: olmo-seeds-{c,d}.json and the cell's seven runs, 21 seeds
# sound, three a control and set). Sound 0.040-0.067, its prompt in 3 mixed dispatches (992 +
# 992 + 116) with the three requests beside it decoding to the end, in every run. The
# reference's state zeroed where the second chunk of 512 begins, 1,588 tokens before the first
# decoded one (``zero_state``) 0.289-0.533 over six; zeroed at token 2048, 52 before it
# (``zero_state_late``) 3.02-4.07; fp8 projections 0.97-1.04. What passes it: a bfloat16 state
# 0.054-0.099, an fp8 head 0.083-0.111. 3.0 x above the one; 1.4 x below a state lost at token
# 512 (a boundary the engine has only where the probe runs alone; beside decode rows its first
# is at 992, nearer the end), 15 x below one lost at the last.
LONG_WALK_TOL = 0.2


def _one_layer_model(engine, op: str):
    """The configuration and the parameter builder of a one-layer model of
    operator ``op`` in Olmo's block, with a dense feed-forward of zeros
    (whose normed output is exactly 0): ``build(params, x, layer, nth)``
    takes the ``nth`` layer of that operator's stacks and the output norm
    of ``layer``."""
    import jax
    import jax.numpy as jnp

    mcfg = engine.cfg.model
    one = dataclasses.replace(
        mcfg, num_layers=1,
        layer_types=("linear_attention" if op == "linear" else "full_attention",),
    )
    names = LINEAR_PARAMS if op == "linear" else ("wq", "wk", "wv", "wo", "q_norm", "k_norm")

    def build(params, x, layer, nth):
        src = params["layers"]
        d = x.shape[-1]
        layers = {k: jax.lax.dynamic_slice_in_dim(src[k], nth, 1, 0) for k in names}
        layers["post_attn_norm"] = jax.lax.dynamic_slice_in_dim(src["post_attn_norm"], layer, 1, 0)
        layers["post_ffn_norm"] = jnp.ones((1, d), x.dtype)
        layers.update(
            w_gate=jnp.zeros((1, d, 128), x.dtype), w_up=jnp.zeros((1, d, 128), x.dtype),
            w_down=jnp.zeros((1, 128, d), x.dtype),
        )
        return {
            "embed": x, "layers": layers, "final_norm": params["final_norm"],
            "lm_head": params["lm_head"],
        }

    return one, build


def engine_linear_and_head(engine, n_rows: int, span: int, state_fault=None):
    """``run(params, x, layer, nth)``: one linear-attention layer as the
    engine runs it over ``n_rows`` sequences of ``PREFILL + DECODE`` tokens
    (row ``r``'s tokens are ``x[r * span + t]``): chunk by chunk through
    ``forward_ragged``, the state in a pool, then a step at a time through
    ``forward``. Returns (the logits at ``head_rows`` [n_rows,
    len(head_rows), V] float32, the matrix state of every row [2, n_rows,
    dk, H * dv] after the last prefilled and after the last decoded
    token). ``state_fault`` (the tests' faults on the engine's side) maps
    the pool after every chunk and step (called with the chunk's or
    step's number)."""
    import jax
    import jax.numpy as jnp

    from dynamo_exp_tpu.models import llama

    impl, interpret, q_tile, page, kv_dtype = _attention_form(engine)
    one, build = _one_layer_model(engine, "linear")
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
            if state_fault is not None:
                state = state_fault(state, c)
        held = [np.asarray(state.state[0])]
        for t in range(PREFILL, PREFILL + DECODE):
            logits, state = step(params, x, state, jnp.asarray(t, jnp.int32), layer, nth)
            out.append(np.asarray(logits)[:, None])
            if state_fault is not None:
                state = state_fault(state, t)
        held.append(np.asarray(state.state[0]))
        return np.concatenate(out, axis=1), np.stack(held)

    return run


def engine_attention_and_head(engine, n_rows: int, span: int):
    """``run(params, x, positions, out_idx, layer, nth)``: the mixed
    program's forward over one full-attention layer, a whole sequence a
    row. Logits [len(out_idx), V] float32 at the stream's ``out_idx``."""
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
    """(the linear layers part 1 reads: first, middle, last; the full
    layers part 2 reads: first and last)."""
    kinds = list(hf["layer_types"])
    linear = [i for i, k in enumerate(kinds) if k == "linear_attention"]
    full = [i for i, k in enumerate(kinds) if k == "full_attention"]
    return sorted({linear[0], linear[len(linear) // 2], linear[-1]}), sorted({full[0], full[-1]})


def state_errors(got: np.ndarray, want: np.ndarray) -> tuple[float, float]:
    """``|engine - reference| / |reference|`` of one sequence's state:
    (the worst head's, all heads' together). ``got`` the pool's entry
    ``[dk, H * dv]``, ``want`` the reference's ``[H, dk, dv]``."""
    h, dk, dv = want.shape
    got = got.reshape(dk, h, dv).transpose(1, 0, 2)
    diff = np.linalg.norm((got - want).reshape(h, -1), axis=-1)
    size = np.linalg.norm(want.reshape(h, -1), axis=-1)
    return float((diff / size).max()), float(np.linalg.norm(diff) / np.linalg.norm(size))


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
        "linear": engine_linear_and_head(engine, n_rows, span, ctx.get("state_fault")),
        "attn": engine_attention_and_head(engine, n_rows, span),
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
        err, by_head, together = [], [], []
        for s in range(n_rows):
            got = np.asarray(jax.nn.log_softmax(jnp.asarray(logits[s]), axis=-1))
            want = reference.op_half_and_head(ctx["params"], hf, layer, x[s, :long], rows=wanted)
            spread = want - want.mean(axis=-1, keepdims=True)
            err.append(np.linalg.norm(got - want, axis=-1) / np.linalg.norm(spread, axis=-1))
            for moment, upto in enumerate((PREFILL, long) if op == "linear" else ()):
                state = reference.linear_state(ctx["params"], hf, layer, x[s, :upto])
                worst, whole = state_errors(held[moment, s], state)
                by_head.append(worst)
                together.append(whole)
        err = np.stack(err)  # [n_rows, len(wanted)]
        decoded = err[:, -DECODE:]

        def worst_of(values) -> float:
            return float(np.nan_to_num(np.asarray(values), nan=np.inf).max())

        linear = op == "linear"
        cells.append({
            "layer": layer, "op": op, "rows": int(err.size),
            "max_rel_err": float(np.nan_to_num(err, nan=np.inf).max()),
            "median_rel_err": float(np.median(err)),
            # The rows computed a step at a time from the state (a linear layer).
            "max_rel_err_decoded": (
                float(np.nan_to_num(decoded, nan=np.inf).max()) if linear else None
            ),
            # The pool's matrix state against the reference's (a linear layer): the worst
            # head's after the prefilled tokens and after the decoded ones, all heads'
            # together, and [after prefill, after decode] a sequence.
            "state_rel_err_prefill": worst_of(by_head[0::2]) if linear else None,
            "state_rel_err_decode": worst_of(by_head[1::2]) if linear else None,
            "state_rel_err_all_heads": worst_of(together) if linear else None,
            "state_rel_err_by_moment": [round(float(v), 6) for v in by_head] if linear else None,
        })
    return {"op_half": cells}


@contextlib.contextmanager
def others_decoding(base: str, model: str, n: int, tokens: int):
    """``n`` other requests of ``tokens`` tokens each in the server's decode
    slots: entered once each has streamed a token (its prompt is in, its
    state entry live), left when all have ended. Yields ``still()``: how
    many are streaming yet."""
    began = [threading.Event() for _ in range(n)]
    ended = [threading.Event() for _ in range(n)]

    def one(i: int) -> None:
        body = {
            "model": model, "messages": [{"role": "user", "content": f"row {i} " * (4 + i)}],
            "max_tokens": tokens, "temperature": 0, "ignore_eos": True, "stream": True,
        }
        req = urllib.request.Request(
            f"{base}/v1/chat/completions", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=correct.REQUEST_TIMEOUT_S) as resp:
                for line in resp:
                    if line.startswith(b"data:"):
                        began[i].set()
        finally:
            ended[i].set()
            began[i].set()

    threads = [threading.Thread(target=one, args=(i,), daemon=True) for i in range(n)]
    for t in threads:
        t.start()
    for e in began:
        e.wait(correct.REQUEST_TIMEOUT_S)
    try:
        yield lambda: sum(not e.is_set() for e in ended)
    finally:
        for t in threads:
            t.join(correct.REQUEST_TIMEOUT_S)


@contextlib.contextmanager
def _probe_tokens(n: int):
    """``correct.walk_reference``'s probes at ``n`` prompt tokens."""
    plain = correct.PROBE_PROMPT_TOKENS
    correct.PROBE_PROMPT_TOKENS = n
    try:
        yield
    finally:
        correct.PROBE_PROMPT_TOKENS = plain


def long_walk(ctx: dict) -> dict:
    """Part 3's long probe: ``walk_reference``'s result for one probe of
    ``LONG_PROBE_TOKENS`` (another seed's words than the short probes',
    and than any asking's before it of this process: a cell's run asks
    once, ``check_seeds`` a control after another on one seed, and a
    second asking of the same words would find its pages and a snapshot
    of its state cached and prefill the rest in one dispatch), with
    ``mixed_dispatches`` (what the server
    counted round the asking: the prompt's dispatches, since the requests
    beside it are in before and only decode) and ``live_beside`` (how
    many of those were still decoding when the reply ended). Without a
    server (``ctx["ask"]``, the tests') just the walk."""
    seen = {}
    ask = ctx.get("ask")
    if ask is None:
        base, model = ctx["base"], ctx["model"]

        def mixed() -> float:
            samples = parse_prometheus(correct.http_text(base + "/metrics"))
            return sample_sum(samples, "dynamo_ragged_dispatch_total", {"program": "mixed"}) or 0.0

        def ask(content, n_tokens):
            with others_decoding(base, model, LIVE_BESIDE, BESIDE_TOKENS) as still:
                before = mixed()
                asked = correct.ask_logprobs(base, model, content, n_tokens)
                seen.update(live_beside=still(), mixed_dispatches=int(mixed() - before))
            return asked

    with _probe_tokens(LONG_PROBE_TOKENS):
        walk = correct.walk_reference(
            {**ctx, "seed": ctx["seed"] + 1 + 104729 * next(_ASKINGS), "ask": ask}, 1,
            whole_vocabulary=True,
        )
    return {**walk, **seen}


def compare(ctx: dict) -> dict:
    walk = correct.walk_reference(ctx, PROBES, whole_vocabulary=True)
    if "why" in walk:
        return {"ok": False, "why": walk["why"]}
    long = long_walk(ctx)
    if "why" in long:
        return {"ok": False, "why": f"long probe: {long['why']}"}
    diffs = walk["step_diffs"]
    out = {
        "max_abs_logprob_diff": max(diffs), "tol": WALK_TOL, "steps": len(diffs),
        "long_max_abs_logprob_diff": max(long["step_diffs"]), "long_tol": LONG_WALK_TOL,
        "long_prompt_tokens": len(long["sequences"][0]) - len(long["step_diffs"]),
        "long_mixed_dispatches": long.get("mixed_dispatches"),
        "long_live_beside": long.get("live_beside"),
        "mixer_tol": MIXER_TOL, "state_prefill_tol": STATE_PREFILL_TOL,
        "state_decode_tol": STATE_DECODE_TOL, "attn_tol": ATTN_TOL,
    }
    hf = ctx["hf"]
    linear_layers, full_layers = layers_read(hf)
    every = sorted(set(linear_layers) | set(full_layers))
    entering = [
        ctx["reference"].hidden_states(ctx["params"], hf, ids, every)[0]
        for ids in long_sequences(ctx["seed"], int(hf["vocab_size"]))
    ]
    out.update(op_half_part(ctx, entering, every))
    out["step_diffs"] = [round(d, 5) for d in diffs]
    out["long_step_diffs"] = [round(d, 5) for d in long["step_diffs"]]
    faults = []
    if not out["max_abs_logprob_diff"] <= WALK_TOL:
        faults.append(f"walk: max |diff| {out['max_abs_logprob_diff']:.4f} > {WALK_TOL}")
    if not out["long_max_abs_logprob_diff"] <= LONG_WALK_TOL:
        faults.append(
            f"long walk: max |diff| {out['long_max_abs_logprob_diff']:.4f} > {LONG_WALK_TOL}"
        )
    if out["long_mixed_dispatches"] is not None and out["long_mixed_dispatches"] < 3:
        faults.append(
            f"long walk: its prompt took {out['long_mixed_dispatches']} mixed dispatches, so "
            "its state crossed fewer than two boundaries"
        )
    for op, name, tol in (("linear", "mixer", MIXER_TOL), ("attn", "attn", ATTN_TOL)):
        bad = max((c for c in out["op_half"] if c["op"] == op), key=lambda c: c["max_rel_err"])
        out[f"{name}_max_rel_err"] = bad["max_rel_err"]
        if not bad["max_rel_err"] <= tol:
            faults.append(
                f"{name}: layer {bad['layer']} differs by {bad['max_rel_err']:.4f} of the "
                f"log-probabilities' spread > {tol}"
            )
    linear = [c for c in out["op_half"] if c["op"] == "linear"]
    for moment, wrote, tol in (
        ("prefill", "the chunked kernel's", STATE_PREFILL_TOL),
        ("decode", "the one-step form's", STATE_DECODE_TOL),
    ):
        worst = max(linear, key=lambda c: c[f"state_rel_err_{moment}"])
        got = out[f"state_{moment}_max_rel_err"] = worst[f"state_rel_err_{moment}"]
        if not got <= tol:
            faults.append(
                f"state: layer {worst['layer']}'s matrix state after {moment} ({wrote}) "
                f"differs by {got:.5f} of its size in its worst head > {tol}"
            )
    out["ok"], out["why"] = not faults, "; ".join(faults) or None
    return out
