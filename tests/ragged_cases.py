"""Builders the ragged-attention test files share (``test_ragged_rules``,
``test_ragged_kernels``, ``test_ragged_expanded``, ``test_ragged_attention``;
``test_sdar`` takes ``flat_batch``): drawn page pools and flat query streams,
the latent pool's cases, and the tiny mixed engine with its two-program
oracle. Imported, never copied (docs/testing.md "Where a new kernel's cases
go").

SHAPES. A kernel in interpret mode costs by what XLA:CPU has to lower and
compile, not by what it computes: about half a second a page copy of a
chunk (``cp = min(128 // page_size, table width)`` copies a chunk and
pool), a few seconds a call, and nothing worth counting to run. So the
builders here draw on LARGE pages (few copies a chunk) and pad every stream
of a table of cases to ONE shape (one compile a static combination); what a
case crosses is in its data.
"""

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
from dynamo_exp_tpu.models import TINY
from dynamo_exp_tpu.parallel import single_device_mesh
from dynamo_exp_tpu.protocols.common import BackendInput, SamplingOptions

PS = 8


def flat_batch(rng, spans, H, Hkv, D, P, ps, pmax, q_tile, dtype=jnp.float32,
               Dv=None):
    """Build a q_tile-aligned flat query stream from per-row
    ``(q_len, kv_len)`` spans plus a scrambled page pool. ``Dv``: the
    value pool's heads are that wide, not ``D``."""
    # drawn on the host: a jitted draw compiles once a shape, ~0.3 s each
    draw = np.random.RandomState(rng)

    def normal(*shape):
        return jnp.asarray(draw.standard_normal(shape), dtype)

    k = normal(P, ps, Hkv * D)
    v = normal(P, ps, Hkv * (Dv or D))
    perm = np.random.RandomState(rng).permutation(P)
    table = np.zeros((len(spans), pmax), np.int32)
    used = 0
    row_of, positions = [], []
    for r, (q_len, kv_len) in enumerate(spans):
        n = max(1, -(-max(kv_len, 1) // ps))
        table[r, :n] = perm[used : used + n]
        used += n
        poss = list(range(kv_len - q_len, kv_len))
        pad = (-q_len) % q_tile
        row_of += [r] * (q_len + pad)
        positions += poss + [-1] * pad
    N = len(row_of)
    q = normal(N, H, D)
    return (
        q,
        k,
        v,
        jnp.asarray(table),
        jnp.asarray(row_of, jnp.int32),
        jnp.asarray(positions, jnp.int32),
    )


def padded(batch, slots: int, rows: int):
    """``flat_batch``'s arrays with padding slots behind the stream up to
    ``slots`` and empty table rows up to ``rows``: one shape for every stream
    of a table of cases. Padding slots carry queries too (the embedding of
    token 0): ones, so that a leak would show."""
    q, k, v, table, row_of, positions = batch
    pad = slots - q.shape[0]
    q = jnp.concatenate([q, jnp.ones((pad, *q.shape[1:]), q.dtype)])
    row_of = jnp.pad(row_of, (0, pad))
    positions = jnp.pad(positions, (0, pad), constant_values=-1)
    table = jnp.pad(table, ((0, rows - table.shape[0]), (0, 0)))
    return q, k, v, table, row_of, positions


def _narrow(D: int) -> int:
    """A value head narrower than the keys' ``D``: about two thirds of it
    (192 -> 128 at the published widths), whole groups of 8."""
    return D * 2 // 3 // 8 * 8


def extras(extra: str, H: int, D: int, seed: int = 11):
    """What a case named ``extra`` adds to a kernel-against-reference test
    (``""``, ``"sink"``, ``"narrow"`` or ``"sink+narrow"``): (the value
    head's width for ``flat_batch``, the keyword both implementations
    take). The sinks are of the scores' size, so that leaving them out, or
    giving one head another's, moves every output."""
    sink = {}
    if "sink" in extra:
        sink["sink"] = 2.0 * jax.random.normal(
            jax.random.PRNGKey(seed), (H,), jnp.float32
        )
    return (_narrow(D) if "narrow" in extra else None), sink


# ------------------------------------------- a latent pool, expanded form
TINY_WIDTHS = dict(H=4, r=40, dn=16, dr=16, dv=16, lanes=128)
PUBLISHED_WIDTHS = dict(H=2, r=512, dn=128, dr=64, dv=128, lanes=640)  # a few heads
# The JOINT score layout (``nope`` no whole lane group, ``nope + rope`` one:
# GLM-5's 192 + 64) at the smallest widths that take it.
TINY_JOINT_WIDTHS = dict(H=2, r=40, dn=96, dr=32, dv=32, lanes=128)
# (live queries, kv length) a row; a span is padded to whole tiles of 8
EXPANDED_STREAMS = {
    # one row cut into three passes (q_max 16) of two score products each,
    # deep in a context an earlier dispatch wrote
    "one_row_several_blocks": [(40, 100)],
    # both sides of the rule in one stream: 24 queries expanded, 5 absorbed
    "expanded_beside_absorbed": [(24, 60), (5, 30)],
    # a context of more than two 64-token chunks of latents
    "crosses_the_chunk": [(19, 150), (8, 129)],
    # the rule's two sides: one query under the threshold, and at it
    "under_and_at_the_threshold": [(7, 40), (8, 40)],
    # a prompt's first chunk: nothing before it
    "from_the_start": [(33, 33), (16, 16)],
}


# One shape for every stream of a table (slots, table rows, table width and
# pool pages at pages of 32 tokens): a static combination compiles once.
EXPANDED_SHAPE = dict(ps=32, slots=72, rows=3, pmax=5, pages=12)
KEPT_SHAPE = dict(ps=32, slots=64, rows=4, pmax=16, pages=18)


def latent_case(spans, seed, *, H, r, dn, dr, dv, lanes, ps=8, tail_tiles=2,
                slots=None, rows=None, pmax=None, pages=None):
    """A latent pool and the published form's queries over ``spans``, with
    ``tail_tiles`` padding tiles behind them (or padding up to ``slots``, a
    table of ``rows`` rows and ``pmax`` pages a row, a pool of ``pages``: one
    shape for a table of streams); beside them the pools a two-pool reference
    reads (every head's ``[c_kv W_uk | k_r]``, its ``c_kv W_uv`` padded to
    the key's width) and the absorbed queries."""
    rng = np.random.default_rng(seed)
    pmax = pmax or max(-(-kv // ps) for _, kv in spans)
    pages = pages or 1 + sum(-(-kv // ps) for _, kv in spans)
    rows = rows or len(spans) + 1
    table = np.zeros((rows, pmax), np.int32)
    row_of, positions, page = [], [], 1
    for i, (n, kv) in enumerate(spans):
        need = -(-kv // ps)
        table[i, :need] = page + rng.permutation(need)
        page += need
        pad = (-n) % 8
        row_of += [i] * (n + pad)
        positions += list(range(kv - n, kv)) + [-1] * pad
    assert page <= pages and len(spans) < rows
    tail = slots - len(row_of) if slots else 8 * tail_tiles
    assert tail >= 8 and tail % 8 == 0
    row_of += [rows - 1] * tail
    positions += [-1] * tail
    pool = np.zeros((pages, ps, lanes), np.float32)
    pool[..., : r + dr] = rng.standard_normal((pages, ps, r + dr))
    q = rng.standard_normal((len(row_of), H, dn + dr)).astype(np.float32)
    w = (rng.standard_normal((r, H, dn + dv)) * r ** -0.5).astype(np.float32)
    c, k_r = pool[..., :r], pool[..., r : r + dr]
    kx = np.concatenate([
        np.einsum("psc,chd->pshd", c, w[..., :dn]),
        np.broadcast_to(k_r[:, :, None], (pages, ps, H, dr)),
    ], axis=-1).reshape(pages, ps, -1)
    vx = np.pad(
        np.einsum("psc,chd->pshd", c, w[..., dn:]), ((0, 0),) * 3 + ((0, dn + dr - dv),)
    ).reshape(pages, ps, -1)
    q_abs = np.zeros((len(row_of), H, lanes), np.float32)
    q_abs[..., :r] = np.einsum("nhd,chd->nhc", q[..., :dn], w[..., :dn])
    q_abs[..., r : r + dr] = q[..., dn:]
    arrays = dict(
        q=q, pool=pool, w_ukv=w.reshape(r, -1), table=table, row_of=np.asarray(row_of, np.int32),
        positions=np.asarray(positions, np.int32), kx=kx, vx=vx, q_abs=q_abs, w=w,
    )
    return {k: jnp.asarray(a) for k, a in arrays.items()}


GLM_WIDTHS = dict(H=2, r=512, dn=192, dr=64, dv=256, lanes=640)  # the joint layout
# (spans, index_topk, distinct score values (0: all different), the call's
# q_max / q_sub / kv_chunk)
KEPT_STREAMS = {
    # a row wholly under index_topk (it keeps all it sees) beside one over it
    "under_beside_over": ([(24, 24), (24, 150)], 32, 0, (16, 8, 64)),
    # a handful of distinct scores: ties at the threshold in every chunk of
    # 128 tokens and every sub-block of 8 queries; lower positions win
    "ties_straddle_chunks_and_sub_blocks": ([(24, 300)], 40, 3, (32, 8, 128)),
    # one span in three passes, each with its own count of ties
    "a_span_in_passes": ([(40, 200)], 24, 4, (16, 8, 64)),
    # tiles with padding slots, a row under the rule beside them
    "padding_slots": ([(19, 150), (5, 90), (13, 129)], 16, 5, (16, 8, 64)),
    # a chunk of this call is two of the scores' chunks
    "two_score_chunks_a_chunk": ([(24, 500)], 64, 6, (16, 8, 256)),
}


def kept_case(spans, widths, topk, values, seed=21, q_sub=8, **shape):
    """``latent_case`` with a selection: drawn index scores in the scores
    kernel's layout (``-inf`` past a query's bound; ``values`` distinct
    ones, so that many tie), the threshold and the ties a query may keep,
    all longer by ``q_sub`` slots as the expanded call asks; and the kept
    positions a query as the reference takes them."""
    from dynamo_exp_tpu.ops import sparse_index as si

    c = latent_case(spans, seed, **widths, **shape)
    ps, pmax = c["pool"].shape[1], c["table"].shape[1]
    n = c["positions"].shape[0]
    T, C, qt, S = si.tiled_shape(n, 8, ps, pmax)
    rng = np.random.default_rng(seed + 1)
    drawn = rng.standard_normal((n, C * S)).astype(np.float32)
    if values:
        drawn = np.floor(rng.random((n, C * S)) * values).astype(np.float32)
    seen = np.arange(C * S)[None] <= np.asarray(c["positions"])[:, None]
    by_query = jnp.asarray(np.where(seen, drawn, -np.inf))
    tau, allow = si.select(by_query, c["positions"], topk)
    c["kept"] = si.kept(by_query, tau, allow)[:, : pmax * ps]
    tiled = jnp.swapaxes(by_query.reshape(T, qt, C, S), 1, 2)
    assert bool((si.by_query(tiled) == by_query).all())
    c["keep"] = (
        jnp.pad(tiled, ((0, q_sub // 8), (0, 0), (0, 0), (0, 0)), constant_values=7.0),
        jnp.pad(tau, (0, q_sub)), jnp.pad(allow, (0, q_sub)),
    )
    c["keep_tiles"] = (tiled, tau, allow)
    return c


# --------------------------------------------------- two-program oracle
def oracle_stream(
    engine: TPUEngine,
    prompt: list[int],
    n_steps: int,
    seed: int,
    sampling: "SamplingOptions",
) -> list[int]:
    """Replay of the seed two-program engine's semantics for ONE
    request, straight through the model forward: bucketed whole-prompt
    prefill samples the first token at the prompt's last absolute
    position WITHOUT penalties (the prefill rule), then strict
    one-token decode steps sample through the running penalty counts
    (the decode-window rule), every draw keyed by (seed, position).
    Counter-based sampling makes this independent of batch shape and
    window layout — exactly what the ragged engine must reproduce."""
    from dynamo_exp_tpu.models import forward
    from dynamo_exp_tpu.ops.sampling import (
        apply_penalties,
        sample_tokens_seeded,
    )

    # Jitted: an eager forward compiles its layer scan anew every call.
    forward = jax.jit(forward, static_argnums=(1,))

    cfg = engine.cfg.model
    params = engine.params
    from dynamo_exp_tpu.models.llama import init_kv_cache

    pmax = 32
    k, v = init_kv_cache(
        cfg, num_pages=pmax + 1, page_size=PS, dtype=engine.cfg.kv_dtype_jnp
    )
    table = jnp.arange(pmax, dtype=jnp.int32)[None, :] + 1
    so = sampling
    seeds = jnp.asarray([seed & 0x7FFFFFFF], jnp.int32)
    temp = jnp.asarray(
        [so.temperature if so.temperature is not None else 0.0], jnp.float32
    )
    top_k = jnp.asarray([so.top_k or 0], jnp.int32)
    top_p = jnp.asarray(
        [so.top_p if so.top_p is not None else 1.0], jnp.float32
    )
    freq = jnp.asarray([so.frequency_penalty or 0.0], jnp.float32)
    pres = jnp.asarray([so.presence_penalty or 0.0], jnp.float32)
    rep = jnp.asarray([so.repetition_penalty or 1.0], jnp.float32)
    counts = jnp.zeros((1, cfg.vocab_size), jnp.int32)

    logits, k, v = forward(
        params, cfg,
        jnp.asarray([prompt], jnp.int32),
        jnp.arange(len(prompt), dtype=jnp.int32)[None, :],
        table, k, v,
    )
    pos = len(prompt) - 1
    tok = int(
        sample_tokens_seeded(
            logits[:, pos], seeds, jnp.asarray([pos], jnp.int32),
            temp, top_k, top_p,
        )[0]
    )
    out = [tok]
    counts = counts.at[0, tok].add(1)
    while len(out) < n_steps:
        pos = len(prompt) + len(out) - 1
        logits, k, v = forward(
            params, cfg,
            jnp.asarray([[tok]], jnp.int32),
            jnp.asarray([[pos]], jnp.int32),
            table, k, v,
        )
        shaped = apply_penalties(logits[:, 0], counts, freq, pres, rep)
        tok = int(
            sample_tokens_seeded(
                shaped, seeds, jnp.asarray([pos], jnp.int32),
                temp, top_k, top_p,
            )[0]
        )
        out.append(tok)
        counts = counts.at[0, tok].add(1)
    return out


def mixed_engine(**kw) -> TPUEngine:
    cfg = EngineConfig(
        model=TINY,
        max_decode_slots=kw.pop("max_decode_slots", 4),
        page_size=kw.pop("page_size", PS),
        num_pages=kw.pop("num_pages", 64),
        max_model_len=kw.pop("max_model_len", 128),
        eos_token_ids=[],
        **kw,
    )
    return TPUEngine(cfg, mesh=single_device_mesh(), seed=0)


async def collect(engine, prompt, max_tokens, seed=None, **sampling):
    b = BackendInput(token_ids=list(prompt))
    b.stop_conditions.max_tokens = max_tokens
    b.stop_conditions.ignore_eos = True
    if sampling or seed is not None:
        b.sampling_options = SamplingOptions(seed=seed, **sampling)
    stream = await engine.generate(b.to_dict())
    toks = []
    async for item in stream:
        toks.extend(item.get("token_ids", []))
    return toks, b.sampling_options

