"""Token sampling under jit: greedy / temperature / top-k / top-p.

All sampling parameters are per-slot vectors so one compiled decode step
serves a heterogeneous continuous batch — no recompile when a request with
different sampling options joins. Branch-free (``jnp.where``), static
shapes, so XLA keeps the whole step fused on-device.

Capability parity: the reference forwards SamplingOptions to vLLM/sglang
(``/root/reference/lib/llm/src/protocols/common.rs`` SamplingOptions);
here the sampler is ours.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _mask_logits(
    logits: jnp.ndarray,  # [B, V] float32
    temperature: jnp.ndarray,  # [B] float32
    top_k: jnp.ndarray,  # [B] int32; <=0 disables
    top_p: jnp.ndarray,  # [B] float32; >=1 disables
) -> jnp.ndarray:
    """Temperature-scaled logits with top-k/top-p mass masked to -inf."""
    B, V = logits.shape
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / temp

    # Sort once (descending); reuse for both top-k and top-p masks.
    sorted_logits = jnp.sort(scaled, axis=-1)[:, ::-1]  # [B, V]

    # top-k: threshold at the k-th largest logit.
    k = jnp.clip(jnp.where(top_k <= 0, V, top_k), 1, V)
    kth = jnp.take_along_axis(sorted_logits, (k - 1)[:, None], axis=-1)  # [B,1]
    masked = jnp.where(scaled < kth, -jnp.inf, scaled)

    # top-p (nucleus): keep the smallest prefix of sorted probs with
    # cumsum >= p; a sorted logit is kept if the cumulative probability
    # *before* it is still < p.
    probs_sorted = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs_sorted, axis=-1)
    keep_sorted = (cum - probs_sorted) < jnp.clip(top_p, 0.0, 1.0)[:, None]
    # The top token always survives, so top_p=0.0 degrades to greedy
    # rather than masking the whole vocabulary.
    keep_sorted = keep_sorted.at[:, 0].set(True)
    # Map the sorted keep-mask back to a per-token logit threshold: the
    # smallest sorted logit still kept.
    min_kept = jnp.min(
        jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1, keepdims=True
    )
    return jnp.where(scaled < min_kept, -jnp.inf, masked)


def sample_tokens(
    logits: jnp.ndarray,  # [B, V] float32
    rng: jax.Array,  # PRNG key
    temperature: jnp.ndarray,  # [B] float32; <=0 means greedy
    top_k: jnp.ndarray,  # [B] int32; <=0 disables
    top_p: jnp.ndarray,  # [B] float32; >=1 disables
) -> jnp.ndarray:
    """Returns sampled token ids [B] int32 (shared-key batch draw)."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    masked = _mask_logits(logits, temperature, top_k, top_p)
    sampled = jax.random.categorical(rng, masked, axis=-1).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)


def sample_tokens_seeded(
    logits: jnp.ndarray,  # [B, V] float32
    seeds: jnp.ndarray,  # [B] int32 per-row sampling seed
    positions: jnp.ndarray,  # [B] int32 absolute position of the fed token
    temperature: jnp.ndarray,  # [B] float32; <=0 means greedy
    top_k: jnp.ndarray,  # [B] int32; <=0 disables
    top_p: jnp.ndarray,  # [B] float32; >=1 disables
) -> jnp.ndarray:
    """Counter-based per-row sampling: row ``r``'s draw depends only on
    ``(seeds[r], positions[r])`` and its own logits — independent of
    batch composition, decode-window layout, prefill chunking, and which
    engine instance runs the step. That independence is the determinism
    guarantee mid-stream failover replay relies on (docs/
    fault_tolerance.md "Resumable streams"): re-prefill the same tokens
    on any healthy worker, and the continuation samples the exact draws
    the dead worker would have."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    masked = _mask_logits(logits, temperature, top_k, top_p)

    def draw(row_logits, seed, pos):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), pos)
        return jax.random.categorical(key, row_logits)

    # Inactive rows carry position -1; clamp so fold_in sees a valid
    # counter (their draw is discarded anyway).
    sampled = jax.vmap(draw)(
        masked, seeds, jnp.maximum(positions, 0)
    ).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)


def sample_tokens_seeded_multi(
    logits: jnp.ndarray,  # [B, T, V] float32
    seeds: jnp.ndarray,  # [B] int32 per-row sampling seed
    positions: jnp.ndarray,  # [B, T] int32 absolute position of each fed token
    temperature: jnp.ndarray,  # [B] float32; <=0 means greedy
    top_k: jnp.ndarray,  # [B] int32; <=0 disables
    top_p: jnp.ndarray,  # [B] float32; >=1 disables
) -> jnp.ndarray:
    """Multi-position counter-based sampling: one draw per (row, offset)
    of a T-wide dispatch, each keyed by ``(seeds[b], positions[b, t])``
    exactly as :func:`sample_tokens_seeded` would key a decode step
    feeding that position. This is what makes a speculative verify pass
    (T = draft_len + 1 positions scored in one chunked-prefill-shaped
    dispatch) emit the *identical* tokens the step-by-step decode window
    would have — the draw never sees batch shape, window layout, or how
    many positions share the dispatch. Returns [B, T] int32."""
    B, T, V = logits.shape

    def rep(x):
        return jnp.repeat(x, T)

    toks = sample_tokens_seeded(
        logits.reshape(B * T, V),
        rep(seeds),
        positions.reshape(-1),
        rep(temperature),
        rep(top_k),
        rep(top_p),
    )
    return toks.reshape(B, T)


def spec_accept_length(
    targets: jnp.ndarray,  # [B, T] target-model tokens per position
    drafts: jnp.ndarray,  # [B, T-1] draft tokens (-1 padded)
    n_drafts: jnp.ndarray,  # [B] int32 true draft count per row
) -> jnp.ndarray:
    """Tokens emitted per row by one verify dispatch: the longest prefix
    where the target's token equals the draft fed at the next position,
    plus the first correction/bonus token — always >= 1, so a
    speculative row can never stall. Returns [B] int32."""
    K = drafts.shape[1]
    idx = jnp.arange(K, dtype=jnp.int32)[None, :]
    match = (targets[:, :K] == drafts) & (idx < n_drafts[:, None])
    accepted = jnp.cumprod(match.astype(jnp.int32), axis=-1).sum(axis=-1)
    return accepted + 1


def spec_verify_tokens(
    logits: jnp.ndarray,  # [B, T, V] float32 target logits per fed position
    drafts: jnp.ndarray,  # [B, T-1] draft tokens fed at offsets 1..T-1
    n_drafts: jnp.ndarray,  # [B] int32 true draft count per row
    seeds: jnp.ndarray,  # [B] int32
    positions: jnp.ndarray,  # [B, T] int32 absolute fed positions (-1 pad)
    temperature: jnp.ndarray,  # [B] float32; <=0 greedy
    top_k: jnp.ndarray,  # [B] int32
    top_p: jnp.ndarray,  # [B] float32
    counts: jnp.ndarray,  # [B, V] int32 penalty counts at dispatch
    frequency_penalty: jnp.ndarray,  # [B]
    presence_penalty: jnp.ndarray,  # [B]
    repetition_penalty: jnp.ndarray,  # [B]
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The full-sampler verify pass: target tokens for every position of
    a speculative dispatch with the penalty state threaded *exactly* as
    the decode window threads it — each step's logits are shaped by the
    counts of every token emitted so far, and a step's token is counted
    only while the row is still "alive" (all earlier drafts accepted),
    so rejected draft positions leave no trace in the counts (the
    penalty half of the KV/state rewind, docs/speculative.md).

    Covers greedy, seeded, and penalized rows in one code path:
    temperature <= 0 degrades each draw to argmax and zero penalties
    make ``apply_penalties`` the identity. Returns (targets [B, T],
    n_emit [B], new_counts [B, V]); positions with offset >= n_emit are
    teacher-forced garbage the caller must discard."""
    B, T, V = logits.shape
    # Fed "next draft" at step i is drafts[:, i]; the last step has none.
    drafts_pad = jnp.concatenate(
        [drafts, jnp.full((B, 1), -1, jnp.int32)], axis=1
    )
    xs = (
        jnp.swapaxes(logits, 0, 1),  # [T, B, V]
        jnp.swapaxes(positions, 0, 1),  # [T, B]
        jnp.swapaxes(drafts_pad, 0, 1),  # [T, B]
        jnp.arange(T, dtype=jnp.int32),
    )
    alive0 = positions[:, 0] >= 0  # pad rows never emit/count

    def step(carry, x):
        counts, alive = carry
        li, pi, di, i = x
        shaped = apply_penalties(
            li,
            counts,
            frequency_penalty,
            presence_penalty,
            repetition_penalty,
        )
        tgt = sample_tokens_seeded(
            shaped, seeds, pi, temperature, top_k, top_p
        )
        counts = counts.at[jnp.arange(B), tgt].add(alive.astype(jnp.int32))
        emit = alive
        alive = alive & (i < n_drafts) & (tgt == di)
        return (counts, alive), (tgt, emit)

    (counts, _), (tgts, emits) = jax.lax.scan(step, (counts, alive0), xs)
    targets = jnp.swapaxes(tgts, 0, 1)
    n_emit = jnp.sum(emits.astype(jnp.int32), axis=0)
    return targets, n_emit, counts


def apply_penalties(
    logits: jnp.ndarray,  # [B, V]
    output_counts: jnp.ndarray,  # [B, V] int32 — counts of generated tokens
    frequency_penalty: jnp.ndarray,  # [B]
    presence_penalty: jnp.ndarray,  # [B]
    repetition_penalty: jnp.ndarray,  # [B]; 1.0 disables
) -> jnp.ndarray:
    """OpenAI-style frequency/presence penalties + HF repetition penalty."""
    counts = output_counts.astype(logits.dtype)
    logits = logits - counts * frequency_penalty[:, None]
    logits = logits - (counts > 0) * presence_penalty[:, None]
    rep = jnp.where(repetition_penalty <= 0.0, 1.0, repetition_penalty)[:, None]
    seen = counts > 0
    logits = jnp.where(
        seen, jnp.where(logits > 0, logits / rep, logits * rep), logits
    )
    return logits


def stop_token_hit(
    tokens: jnp.ndarray,  # [B] int32 sampled token ids
    stop_sets: jnp.ndarray,  # [B, S] int32, -1 padded (never matches)
) -> jnp.ndarray:
    """Per-row on-device stop detection: True where the sampled token is
    in the row's (padded) stop set. Rows whose request disables EOS
    (ignore_eos) pass an all -1 set. Runs inside the decode window scan
    so a finished row flips its position to -1 mid-window instead of
    writing garbage KV the host later discards."""
    return jnp.any(tokens[:, None] == stop_sets, axis=-1)


# Top-N alternatives reported alongside every chosen-token logprob; the
# host slices down to each request's top_logprobs (OpenAI caps at 20,
# but 5 covers the common ask without widening the per-window sync).
TOP_LOGPROBS = 5


def token_logprobs(
    logits: jnp.ndarray,  # [B, V] raw model logits
    chosen: jnp.ndarray,  # [B] int32 sampled token ids
    top_n: int = TOP_LOGPROBS,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(chosen logprob [B], top-N ids [B, N], top-N logprobs [B, N]) of
    the model distribution (pre-penalty/temperature), matching OpenAI's
    logprobs semantics."""
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    chosen_lp = jnp.take_along_axis(lp, chosen[:, None], axis=-1)[:, 0]
    top_lp, top_ids = jax.lax.top_k(lp, top_n)
    return chosen_lp, top_ids.astype(jnp.int32), top_lp


# ``fixed_at`` of a block-diffusion block's position (``denoise_select``):
# the denoising pass that fixed it (0-based), or one of these.
UNFIXED = -2  # still the mask token
CONTEXT = -1  # fixed before the block began: the prompt's tail


def denoise_counts(block: int, steps: int) -> list[int]:
    """How many positions each of ``steps`` denoising passes fixes at
    the least: ``block // steps``, the first ``block % steps`` one more."""
    return [block // steps + (s < block % steps) for s in range(steps)]


def denoise_select(
    logits: jnp.ndarray,  # [R, B, V] float32: a pass's logits, a block a row
    fixed_at: jnp.ndarray,  # [R, B] int32: UNFIXED where the mask token stands
    passes: jnp.ndarray,  # [R] int32 denoising passes the block has had
    *,
    steps: int,
    dynamic: bool,
    threshold: float,
    sampler: tuple | None = None,  # (seeds [R], positions [R, B], temp, top_k, top_p)
    top_n: int | None = None,
) -> tuple[jnp.ndarray, ...]:
    """The confidence rule of generation by diffusion over blocks: which
    masked positions a denoising pass fixes, and to what.

    Of every masked position ``x0 = argmax logits`` (or, with ``sampler``,
    a draw keyed by (seed, position, pass): ``sample_tokens_seeded`` at
    position ``p * (steps + 1) + pass``) and its confidence ``c =
    softmax(logits)[x0]``, of the model's own distribution. With ``n`` the
    schedule's count for this pass (``denoise_counts``): ``dynamic``
    (``low_confidence_dynamic``) fixes every masked position with ``c >
    threshold`` if those are at least ``n``, else the ``n`` most
    confident; static always the ``n`` most confident (ties to the
    earlier position). The schedule's last pass fixes whatever is left.
    A row with nothing masked changes nothing.

    Returns (x0 [R, B], newly [R, B] bool, lp [R, B] = log c) and, with
    ``top_n``, the top ids and log-probabilities [R, B, top_n] of the
    pass's distribution: a token's log-probability is that of the pass
    that fixed it."""
    R, B, V = logits.shape
    flat = logits.reshape(R * B, V)
    if sampler is None:
        x0 = jnp.argmax(flat, axis=-1).astype(jnp.int32)
    else:
        seeds, positions, temp, top_k, top_p = sampler
        key_pos = jnp.maximum(positions, 0) * (steps + 1) + passes[:, None]
        x0 = sample_tokens_seeded(
            flat, jnp.repeat(seeds, B), key_pos.reshape(-1),
            jnp.repeat(temp, B), jnp.repeat(top_k, B), jnp.repeat(top_p, B),
        )
    # log softmax at the few entries read, not over the vocabulary.
    lse = jax.nn.logsumexp(flat, axis=-1)
    lp = (jnp.take_along_axis(flat, x0[:, None], axis=-1)[:, 0] - lse).reshape(R, B)
    x0 = x0.reshape(R, B)
    masked = fixed_at == UNFIXED
    conf = jnp.where(masked, jnp.exp(lp), -1.0)
    counts = jnp.asarray(denoise_counts(B, steps), jnp.int32)
    n = counts[jnp.clip(passes, 0, steps - 1)][:, None]  # [R, 1]
    # Rank among the row's positions, most confident first.
    ahead = (conf[:, None, :] > conf[:, :, None]) | (
        (conf[:, None, :] == conf[:, :, None])
        & (jnp.arange(B)[None, None, :] < jnp.arange(B)[None, :, None])
    )
    newly = masked & (jnp.sum(ahead, axis=-1) < n)
    if dynamic:
        high = masked & (conf > threshold)
        newly = jnp.where(
            jnp.sum(high, axis=-1, keepdims=True) >= n, high, newly
        )
    newly = jnp.where((passes >= steps - 1)[:, None], masked, newly)
    if top_n is None:
        return x0, newly, lp
    top, top_ids = jax.lax.top_k(flat, top_n)
    return (
        x0, newly, lp, top_ids.astype(jnp.int32).reshape(R, B, top_n),
        (top - lse[:, None]).reshape(R, B, top_n),
    )
