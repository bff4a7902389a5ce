"""The comparison for a model that generates by diffusion over blocks on a
stack of routed experts (``"comparison": "block_diffusion"`` in the
configuration's file; ``sdar-30b-a3b-l6``).

Three parts, as ``routed_experts.py`` has them and for its reasons (a
routed model's log-probability walk measures how near the nearest router
tie was, so the arithmetic is held half a layer at a time on the SAME
bfloat16 input, and the served path is judged on its median): all outside
the timed window, all reported, all needed for ``ok``. Parts 1 and 2 read
the first, the middle and the last layer and take their inputs from the
reference's own float32 forward pass over part 3's sequences.

1. THE EXPERT BLOCK at the two shapes the engine's programs give it: 256
   rows ([1, 256, D]: a block pass of 64 rows x 4 positions, 2048 sorted
   rows over the experts) through ``models/llama.py::_ffn_block`` as the
   BLOCK program calls it (the stacked weights whole, the layer to read,
   ``any_rows``: ``ops/expert_gmm.py`` in place at any row count), and
   1024 rows as the MIXED program calls it (its ``IN_PLACE_MAX_ROWS`` rule:
   ``ragged_dot`` on the layer's slice); against the reference's router
   and dense experts in float32 at ``highest``. Rows whose reference
   margin (relative: ``references/sdar_moe.py::_route``) is under
   ``NEAR_TIE`` are left out and counted. The number, a
   row: ``|engine - reference| / |reference - input|``.

2. THE FIRST HALF AND THE HEAD, ``routed_experts.first_half_part`` as it
   stands: ``models/llama.py::forward_ragged`` over a one-layer model of
   the engine's own arrays, which for this configuration runs UNDER THE
   BLOCK BOUND (``cfg.block_length``: a query sees its whole block),
   against the reference's first half under the block mask. Part 3's
   sequences end where a block does (a query of the engine sees its
   block's every slot, so the slots must all be written); their prompts
   have every remainder mod 4.

3. THE SERVED PATH: ``PROBE_LENGTHS`` seeded probes through the HTTP chat
   path with log-probabilities, 8 whole blocks each after the block the
   prompt's tail opens, replayed on the engine's tokens AND passes: every
   served token says the denoising pass that fixed it (the ``pass`` key),
   so the reference rebuilds each pass's input (``references/sdar_moe.py::
   pass_inputs``: earlier blocks as written, the block's tokens fixed by
   earlier passes, the mask token elsewhere) and reads the token's
   log-probability, and the top five, at the pass that fixed it. A token's
   id is found from its served text among the ids that decode to it, the
   one whose reference value lies nearest. Of each token the largest of
   its six differences; ``ok`` asks the **median** over all tokens to stay
   under ``MEDIAN_TOL``. Not a precision check (``routed_experts.py``,
   part 3); it holds what parts 1 and 2 call no part of: prefill cut at
   block boundaries, the block program's passes through the paged cache
   (each pass rewriting the block's K/V, the last leaving the final ones),
   the confidence rule's bookkeeping, the mixed program's single passes,
   no shift.

TOLERANCES, each from two readings on the chip (beside each constant;
PERF.md section 6, PR 37): the sound program's largest over the seeds
read, and the smallest a control gave (``rehearse/check_seeds_sdar.py
--control``; ``tests/test_block_diffusion.py`` has the same faults on the
CPU).
"""
from __future__ import annotations

import random
import statistics

import numpy as np

from comparisons import routed_experts
from lib import correct
from lib.stats import percentile

PROBE_LENGTHS = (45, 46, 47, 48)  # prompt tokens: every remainder mod 4
BLOCKS = 8  # whole blocks a probe decodes beside the one its prompt's tail opens
BLOCK_ROWS = ((256, True), (1024, False))  # (rows, as the block program calls it)
# A row is left out of the block part when the reference's 8th and 9th
# router scores lie nearer than this share of the 8th (their logits' difference):
# the engine's router reads the normed row rounded to bfloat16, which moves a
# logit by ~1e-3. (``routed_experts.NEAR_TIE`` is an amount of score, for 8
# experts at ~0.125 each; 128 experts at ~0.008 lie nearer than that everywhere.)
NEAR_TIE = 0.01
REAL_ROW_SHARE = routed_experts.REAL_ROW_SHARE
EXPERT_STACKS = ("w_gate", "w_up", "w_down")
# Block part, largest row of |engine - reference| / |reference - input|. Steady from
# seed to seed. sdar-30b-a3b-l6, one chip (my chip runs, PR 37): sound 0.0176-0.0204
# over 23 readings on 23 seeds (layer 5; 0.0069-0.0078 at layer 0, 0.0141-0.0158 at layer 3; the same
# at 256 rows, the kernel in place, and at 1024, ragged_dot on the slice); the reference
# with fp8 (e4m3) expert weights 0.0620-0.0631 (three seeds); top-8 weights not
# renormalised 4.46-4.76. The limit stands 1.7 x above the one and 1.8 x below the other.
BLOCK_TOL = 0.035
# First-half part, largest row of |engine - reference| over the log-probabilities
# relative to their spread, under the block bound. sdar-30b-a3b-l6, one chip (my chip
# runs, PR 37): sound 0.0056-0.0061 over 23 seeds (layer 0; 0.0032-0.0034 at layer 3,
# 0.0036-0.0037 at layer 5); the reference with fp8 attention weights 0.0663 (one seed); the
# reference under a causal mask in the block mask's place 1.22-1.25 (two seeds).
FIRST_HALF_TOL = 0.015
# Served part, median over a seed's 134 tokens of the token's largest difference. NOT a
# precision limit (fp8 expert weights read 0.058-0.072 and pass; the block part holds
# them). sdar-30b-a3b-l6, one chip (my chip runs, PR 37): sound 0.0221-0.0443 over 23
# readings on 23 seeds (16 in rehearse/check_seeds_sdar.py, 7 in runs of the cell);
# earlier blocks left as their last denoising pass wrote them (no writing pass)
# 0.1633-0.1938 (three seeds); logits shifted by one 0.2199-0.2629; a causal mask
# 0.352-0.601; fp8 attention weights 0.172. The limit stands 1.9 x above the sound
# program's largest and 1.9 x below the controls' smallest.
MEDIAN_TOL = 0.085


def probe_prompts(tok, seed: int) -> list[tuple[str, list[int]]]:
    """One seeded user message for each of ``PROBE_LENGTHS`` whose
    rendered chat prompt is that many tokens, with the ids
    (``lib/correct.py::_probe_prompts`` at a length of one's choosing)."""
    rng = random.Random(seed ^ 0x5DA2)
    letters = "abcdefghijklmnopqrstuvwxyz"

    def ids_of(content: str) -> list[int]:
        rendered = f"<|user|>{content}</s><|assistant|>"
        return tok.encode(rendered, add_special_tokens=False).ids

    out = []
    for want in PROBE_LENGTHS:
        content = ""
        while len(ids_of(content)) < want:
            content += (" " if content else "") + "".join(
                rng.choice(letters) for _ in range(rng.randint(2, 7))
            )
        while len(ids_of(content)) > want and len(content) > 1:
            content = content[:-1].rstrip()
        out.append((content, ids_of(content)))
    return out


def ids_by_text(tok) -> dict[str, list[int]]:
    """Every id of the vocabulary under the text the server shows for it."""
    by_text: dict[str, list[int]] = {}
    for i in range(tok.get_vocab_size()):
        by_text.setdefault(tok.decode([i], skip_special_tokens=True), []).append(i)
    return by_text


def walk_blocks(ctx: dict) -> dict:
    """Part 3. ``{"step_diffs", "sequences", "passes"}`` or ``{"why"}``.
    ``ctx`` as ``lib/correct.py::walk_reference`` takes it (``ask`` in
    place of the HTTP asking: the tests' way in without a server)."""
    from tokenizers import Tokenizer

    reference, hf = ctx["reference"], ctx["hf"]
    block = reference.block_constants(hf)["block"]
    ask = ctx.get("ask") or (lambda c, n: correct.ask_logprobs(ctx["base"], ctx["model"], c, n))
    tok = Tokenizer.from_file(ctx["tokenizer_file"])
    by_text = ids_by_text(tok)
    step_diffs, sequences, all_passes = [], [], []
    for content, prompt in probe_prompts(tok, ctx["seed"]):
        n = (-len(prompt)) % block + BLOCKS * block
        asked = ask(content, n)
        if isinstance(asked, str):
            return {"why": asked}
        served_prompt, entries = asked
        if served_prompt != len(prompt):
            return {
                "why": f"the server counted {served_prompt} prompt tokens where "
                       f"the benchmark's tokenization has {len(prompt)}",
            }
        if len(entries) != n or any("pass" not in e for e in entries):
            return {"why": f"logprob probe of {n} tokens returned {len(entries)} entries, "
                           f"{sum('pass' in e for e in entries)} with the pass that fixed them"}
        passes = [int(e["pass"]) for e in entries]
        tokens = [-1] * n  # filled in as the passes that fixed them are replayed
        for ids, rows, which in reference.pass_inputs(prompt, tokens, passes, hf):
            ref = reference.log_probs(ctx["params"], hf, ids, rows)
            for row, i in zip(ref, which):
                entry = entries[i]
                alike = by_text.get(entry["token"], [])
                if not alike:
                    return {"why": f"token {i}: no id of the vocabulary decodes to {entry['token']!r}"}
                chosen = min(alike, key=lambda t: abs(float(row[t]) - entry["logprob"]))
                tokens[i] = chosen
                worst = abs(float(row[chosen]) - entry["logprob"])
                best = np.sort(row)[::-1]
                served = sorted((t["logprob"] for t in entry["top_logprobs"]), reverse=True)
                for k, v in enumerate(served):
                    worst = max(worst, abs(v - float(best[k])))
                step_diffs.append(worst)
        sequences.append(list(prompt) + tokens)
        all_passes.extend(passes)
    return {"step_diffs": step_diffs, "sequences": sequences, "passes": all_passes}


def engine_block(engine):
    """``run(layers, x, l, any_rows)``: ``models/llama.py``'s second half
    of layer ``l`` on ``x`` ([1, N, D] bfloat16) as the paged forwards call
    it: the expert stacks whole with the layer to read, everything else
    layer ``l``'s slice; ``any_rows`` as the block program asks."""
    import functools

    import jax

    from dynamo_exp_tpu.models import llama

    mcfg, mesh = engine.cfg.model, engine.mesh
    interpret = getattr(engine, "_attn_interpret", False)

    @functools.partial(jax.jit, static_argnames=("any_rows",))
    def run(layers, x, l, any_rows):
        lp = {
            k: v if k in EXPERT_STACKS
            else jax.lax.dynamic_index_in_dim(v, l, 0, keepdims=False)
            for k, v in layers.items()
        }
        return llama._ffn_block(
            x, lp, mcfg, mcfg.rms_norm_eps, lambda y: y, mesh, None, l,
            interpret, any_rows,
        )

    return run


def block_part(ctx: dict, before_ffn: np.ndarray, layers: list[int]) -> dict:
    """Part 1. ``before_ffn``: [len(layers), real rows, D]."""
    import jax.numpy as jnp

    reference, engine, hf = ctx["reference"], ctx["engine"], ctx["hf"]
    rng = np.random.default_rng(ctx["seed"] % 2**32)
    run = engine_block(engine)
    cells, near = [], 0
    for i, layer in enumerate(layers):
        real = before_ffn[i]
        rms = float(np.sqrt(np.mean(real * real)))
        for rows, any_rows in BLOCK_ROWS:
            n_real = min(len(real), int(rows * REAL_ROW_SHARE))
            pick = np.linspace(0, len(real) - 1, n_real).astype(int)
            seeded = rng.standard_normal((rows - n_real, real.shape[1]), np.float32) * rms
            x = jnp.asarray(np.concatenate([real[pick], seeded]), jnp.bfloat16)
            got = np.asarray(
                run(engine.params["layers"], x[None], layer, any_rows=any_rows)
                .astype(jnp.float32)
            ).reshape(rows, -1)
            want, margin = reference.ffn_half(ctx["params"], hf, layer, x)
            added = want - np.asarray(x.astype(jnp.float32))
            err = np.linalg.norm(got - want, axis=1) / np.linalg.norm(added, axis=1)
            keep = margin >= NEAR_TIE
            cells.append({
                "layer": layer, "rows": rows, "rows_near_tie": int((~keep).sum()),
                "max_rel_err": float(err[keep].max()),
                "median_rel_err": float(np.median(err[keep])),
            })
            near += cells[-1]["rows_near_tie"]
    return {
        "block": cells, "block_max_rel_err": max(c["max_rel_err"] for c in cells),
        "rows_near_tie": near,
        "block_median_rel_err": max(c["median_rel_err"] for c in cells),
    }


def compare(ctx: dict) -> dict:
    walk = walk_blocks(ctx)
    if "why" in walk:
        return {"ok": False, "why": walk["why"]}
    diffs = walk["step_diffs"]
    out = {
        "max_abs_logprob_diff": max(diffs), "tol": MEDIAN_TOL, "steps": len(diffs),
        "median_abs_logprob_diff": statistics.median(diffs),
        "p90_abs_logprob_diff": percentile(diffs, 90),
        "block_tol": BLOCK_TOL, "near_tie": NEAR_TIE, "first_half_tol": FIRST_HALF_TOL,
        "tokens_by_pass": {
            str(s): walk["passes"].count(s) for s in sorted(set(walk["passes"]))
        },
    }
    hf = ctx["hf"]
    n_layers = int(hf["num_hidden_layers"])
    layers = sorted({0, n_layers // 2, n_layers - 1})
    states = [
        ctx["reference"].hidden_states(ctx["params"], hf, ids, layers)
        for ids in walk["sequences"]
    ]
    out.update(block_part(ctx, np.concatenate([b for _e, b in states], axis=1), layers))
    out.update(routed_experts.first_half_part(ctx, [e for e, _b in states], layers))
    out["step_diffs"] = [round(d, 5) for d in diffs]
    faults = []
    if out["median_abs_logprob_diff"] > MEDIAN_TOL:
        faults.append(f"median |diff| {out['median_abs_logprob_diff']:.4f} > {MEDIAN_TOL}")
    for part, tol, what in (
        ("block", BLOCK_TOL, "of what the block adds"),
        ("first_half", FIRST_HALF_TOL, "of the log-probabilities' spread"),
    ):
        bad = max(out[part], key=lambda c: c["max_rel_err"])
        if bad["max_rel_err"] > tol:
            faults.append(
                f"{part}: layer {bad['layer']} at {bad['rows']} rows differs by "
                f"{bad['max_rel_err']:.4f} {what} > {tol}"
            )
    out["ok"], out["why"] = not faults, "; ".join(faults) or None
    return out
