"""A block's K/V are written by the first pass of the block after it
(``engine/block.py``): what the engine serves is what the form before it
served, whose every block had a writing pass of its own.

THE ORACLE is that form, built here from ``forward_ragged`` and
``block_pass`` and in no path of the engine: a row alone, its prompt's
whole blocks prefilled in one call, then for each block its denoising
passes and ONE MORE pass over the final tokens, which leaves the block's
K/V in the row's pages. Tokens and the pass that fixed each are compared
exactly. Log-probabilities and K/V are float32 on both sides and differ by
the rounding of a matmul over another number of rows (a batch of several
rows, an opening pass of two blocks a row, a chunked prefill): ~2e-6, so
``TOL`` 2e-5 stands ten times above it and four orders below a fault (a
block read as its last denoising pass left it moves them by 1e-1:
``benchmark/tests/test_block_diffusion.py``).

After every consume the pages of each running row are read back: below the
row's written frontier they hold the oracle's K/V, and no page with a slot
at or past it is in the prefix index.
"""

import asyncio
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_exp_tpu.aot.lattice import ragged_variants
from dynamo_exp_tpu.aot.launch import launch_layout
from dynamo_exp_tpu.engine import EngineConfig
from dynamo_exp_tpu.engine.block import _no_lps, block_pass
from dynamo_exp_tpu.engine.scheduler import SeqState
from dynamo_exp_tpu.models import ModelConfig, llama
from dynamo_exp_tpu.models.config import PRESETS
from dynamo_exp_tpu.ops.sampling import CONTEXT, UNFIXED

from .test_sdar import HF, PROMPT, PS, B, _engine, _run, _total

TOL = 2e-5
MODES = [
    pytest.param({}, None, id="greedy"),
    pytest.param({}, 2, id="greedy-logprobs"),
    pytest.param({"temperature": 0.9, "seed": 11}, None, id="seeded"),
    pytest.param({"temperature": 0.9, "seed": 11}, 2, id="seeded-logprobs"),
]
WRITES = "dynamo_diffusion_block_writes_total"
LATTICE = 14  # ragged variants of ``_cfg`` without log-probabilities, as of PR 40


# ------------------------------------------------------------------ oracle
@functools.cache
def _forward(eng):
    mcfg = eng.cfg.model
    table = jnp.arange(eng.cfg.max_model_len // PS, dtype=jnp.int32)[None]

    @jax.jit
    def forward(k, v, tokens, positions):
        n = tokens.shape[0]
        hidden, k, v = llama.forward_ragged(
            eng.params, mcfg, tokens, positions, jnp.zeros(n, jnp.int32), table,
            k, v, jnp.arange(n), attn_impl="xla", q_tile=B, project=False,
        )
        return llama.final_logits(eng.params, mcfg, hidden), k, v

    return forward


@functools.cache
def _oracle(eng, prompt: tuple, n: int, temperature=0.0, seed=0, stop=None):
    """The row's tokens, passes, log-probabilities and, by position, the
    K/V its pages hold when every block has had a writing pass."""
    mcfg = eng.cfg.model
    forward = _forward(eng)
    shape = (eng.k_cache.shape[0], eng.cfg.max_model_len // PS, *eng.k_cache.shape[2:])
    k, v = jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)
    target = len(prompt) // B * B
    if target:
        _, k, v = forward(k, v, jnp.asarray(prompt[:target]), jnp.arange(target))
    sampler = None
    one = lambda x, dtype: jnp.asarray([x], dtype)  # noqa: E731
    seq, out = list(prompt), {"token_ids": [], "logprobs": [], "fixed_at_pass": []}
    while len(out["token_ids"]) < n and out.get("stopped") is None:
        start = len(seq) // B * B
        pre = seq[start:]
        positions = start + jnp.arange(B)
        if temperature:
            sampler = (
                one(seed, jnp.int32), positions[None], one(temperature, jnp.float32),
                one(0, jnp.int32), one(1.0, jnp.float32),
            )
        tokens = jnp.asarray([pre + [mcfg.mask_token_id] * (B - len(pre))])
        fixed_at = jnp.asarray([[CONTEXT] * len(pre) + [UNFIXED] * (B - len(pre))])
        lps, passes = _no_lps(1, B), 0
        while bool((fixed_at == UNFIXED).any()):
            fed = jnp.where(fixed_at != UNFIXED, tokens, mcfg.mask_token_id)[0]
            logits, k, v = forward(k, v, fed, positions)
            tokens, fixed_at, lps = block_pass(
                logits[None], tokens, fixed_at, one(passes, jnp.int32),
                one(True, bool), mcfg, sampler, lps,
            )
            passes += 1
        _, k, v = forward(k, v, tokens[0], positions)  # the pass that only writes
        for j in range(len(pre), B):
            token = int(tokens[0, j])
            seq.append(token)
            out["token_ids"].append(token)
            out["logprobs"].append(float(lps[0][0, j]))
            out["fixed_at_pass"].append(int(fixed_at[0, j]))
            if token == stop:
                out["stopped"] = True
            if len(out["token_ids"]) == n or token == stop:
                break
    out["sequence"] = seq
    flat = lambda c: np.asarray(c).reshape(c.shape[0], -1, c.shape[-1])  # noqa: E731
    out["k"], out["v"] = flat(k), flat(v)  # [L, position, Hkv * D]
    return out


# ------------------------------------------------------- the engine, watched
class Watch:
    """After each consume of ``eng``: every running row's pages against
    the oracle of its request (``expect``), and the prefix index against
    its written frontier."""

    def __init__(self, eng):
        self.eng, self.oracles, self.faults, self.checked = eng, [], [], 0
        consume = eng._consume_block

        def watched(pending):
            consume(pending)
            try:
                self.check()
            except Exception as exc:  # the loop thread must live to say so
                self.faults.append(repr(exc))

        eng._consume_block = watched

    def expect(self, *oracles):
        self.oracles, self.faults, self.checked = list(oracles), [], 0

    def check(self):
        eng = self.eng
        k, v = np.asarray(eng.k_cache), np.asarray(eng.v_cache)
        for seq in eng.sched.slots:
            if seq is None or seq.state is not SeqState.ACTIVE or seq.swap is not None:
                continue
            # A prompt's tail past its last whole block is its first
            # block's context: that block's K/V to write, as ever.
            frontier = (len(seq.tokens) - seq.unwritten) // B * B
            want = next(
                (o for o in self.oracles
                 if o["sequence"][: len(seq.tokens)] == seq.tokens), None)
            if want is None:
                self.faults.append(f"{seq.request_id}: tokens of no oracle")
                continue
            at = np.arange(frontier)
            pids = np.asarray(seq.page_ids)[at // PS]
            for name, pool, ref in (("k", k, want["k"]), ("v", v, want["v"])):
                diff = np.abs(pool[:, pids, at % PS] - ref[:, :frontier]).max(initial=0)
                if diff > TOL:
                    self.faults.append(
                        f"{seq.request_id}: {name} below {frontier} differs by {diff}")
            for i, pid in enumerate(seq.page_ids):
                if (i + 1) * PS > frontier and eng.kv.page_hash(pid) is not None:
                    self.faults.append(
                        f"{seq.request_id}: page {i} is in the prefix index with "
                        f"slots past the written frontier {frontier}")
            self.checked += 1

    def sound(self):
        assert not self.faults, self.faults[:5]
        assert self.checked > 0


@pytest.fixture(scope="module")
def watched():
    eng = _engine()
    watch = Watch(eng)
    eng.start()
    yield eng, watch
    eng.stop()


def _same(out, want, logprobs):
    """Tokens exactly; with log-probabilities asked for, the pass that
    fixed each exactly and its log-probability to float32 rounding."""
    assert out["token_ids"] == want["token_ids"]
    if logprobs is None:
        return
    assert out["fixed_at_pass"] == want["fixed_at_pass"]
    np.testing.assert_allclose(out["logprobs"], want["logprobs"], atol=TOL, rtol=0)


def _want(eng, prompt, n, sampling, stop=None):
    return _oracle(
        eng, tuple(prompt), n, sampling.get("temperature", 0.0),
        sampling.get("seed", 0), stop,
    )


# ------------------------------------------------------------------- cases
@pytest.mark.parametrize("sampling, logprobs", MODES)
async def test_a_chain_of_blocks(watched, sampling, logprobs):
    """7 blocks, each but the first launched before the host has the one
    before: block N's tokens reach N+1's opening pass on the device."""
    eng, watch = watched
    prompt = PROMPT[:26]  # its tail opens the first block
    want = _want(eng, prompt, 26, sampling)
    watch.expect(want)
    chained = _total("dynamo_ragged_dispatch_total", program="block", chained="1")
    _same(await _run(eng, prompt, 26, logprobs, **sampling), want, logprobs)
    assert _total("dynamo_ragged_dispatch_total", program="block", chained="1") - chained >= 5
    watch.sound()


@pytest.mark.parametrize("sampling, logprobs", MODES)
async def test_a_prompt_admitted_mid_stream_breaks_the_chain(watched, sampling, logprobs):
    """The mixed program takes the running row while the newcomer
    prefills: a pass of its own writes the row's closed block, and the
    row's blocks go on from the host's state."""
    eng, watch = watched
    # A newcomer the prefix index has not seen: it has a prompt to prefill.
    salt = 100 + 2 * bool(sampling) + (logprobs is not None)
    first, later = PROMPT[3:40], [salt] + PROMPT[41:90]
    wants = [_want(eng, first, 40, sampling), _want(eng, later, 12, sampling)]
    watch.expect(*wants)
    own = _total(WRITES, by="own_pass")
    running = asyncio.Event()
    task = asyncio.ensure_future(
        _run(eng, first, 40, logprobs, after=(8, running), **sampling))
    await running.wait()
    outs = [await _run(eng, later, 12, logprobs, **sampling)]
    outs.insert(0, await task)
    for out, want in zip(outs, wants):
        _same(out, want, logprobs)
    assert _total(WRITES, by="own_pass") > own
    watch.sound()


@pytest.mark.parametrize("sampling, logprobs", MODES)
async def test_a_stop_inside_a_block_with_its_successor_in_flight(watched, sampling, logprobs):
    eng, watch = watched
    prompt = PROMPT[7:31]
    cold = _want(eng, prompt, 28, sampling)
    # A token first seen in the middle of a block that is not the first.
    at = next(
        i for i, t in enumerate(cold["token_ids"])
        if i >= B and i % B in (1, 2) and cold["token_ids"].index(t) == i
    )
    want = _want(eng, prompt, 28, sampling, stop=cold["token_ids"][at])
    assert want["token_ids"] == cold["token_ids"][: at + 1]
    watch.expect(cold)
    wasted = eng.wasted_steps
    out = await _run(eng, prompt, 28, logprobs, stop_ids=[cold["token_ids"][at]], **sampling)
    _same(out, want, logprobs)
    assert out["final"]["finish_reason"] == "eos"
    assert eng.wasted_steps > wasted  # the block after was in flight, for nothing
    assert not watch.faults, watch.faults[:5]


@pytest.mark.parametrize("sampling, logprobs", MODES)
async def test_rows_of_one_dispatch_finish_at_different_blocks(watched, sampling, logprobs):
    eng, watch = watched
    asks = [(PROMPT[10:30], 9), (PROMPT[50:70], 30), (PROMPT[31:47], 18)]
    wants = [_want(eng, p, n, sampling) for p, n in asks]
    watch.expect(*wants)
    outs = await asyncio.gather(*[_run(eng, p, n, logprobs, **sampling) for p, n in asks])
    for out, want in zip(outs, wants):
        _same(out, want, logprobs)
    watch.sound()


@pytest.mark.parametrize("sampling, logprobs", [MODES[1], MODES[2]])
async def test_a_preempted_row_resumes_with_its_last_block_unwritten(sampling, logprobs):
    """8 pages under two requests that need 12: the victim leaves with a
    block no dispatch has written and comes back prefilling it."""
    eng = _engine(num_pages=8)
    watch = Watch(eng)
    eng.start()
    try:
        asks = [(PROMPT[:9], 36), (PROMPT[20:30], 36)]
        wants = [_want(eng, p, n, sampling) for p, n in asks]
        watch.expect(*wants)
        before = eng.preempted
        never = _total(WRITES, by="never")
        outs = await asyncio.gather(*[_run(eng, p, n, logprobs, **sampling) for p, n in asks])
        assert eng.preempted > before
        for out, want in zip(outs, wants):
            _same(out, want, logprobs)
        assert _total(WRITES, by="never") - never > len(asks)  # a victim's, too
        watch.sound()
    finally:
        eng.stop()


async def test_every_block_is_settled_once_and_a_chain_rides_the_opening_pass(watched):
    """A lone request of N blocks: one unchained dispatch, N - 1 chained
    ones, each writing the block before as it opens its own; the last
    block is never written. No pass fixes nothing."""
    eng, _watch = watched
    names = ("own_pass", "next_first_pass", "never")
    blocks = _total("dynamo_diffusion_blocks_total")
    by = {name: _total(WRITES, by=name) for name in names}
    write = _total("dynamo_diffusion_passes_total", kind="write")
    denoise = _total("dynamo_diffusion_passes_total", kind="denoise")
    out = await _run(eng, PROMPT[60:84], 32, logprobs=0)
    by = {name: _total(WRITES, by=name) - by[name] for name in names}
    assert _total("dynamo_diffusion_blocks_total") - blocks == 8
    assert by == {"own_pass": 0, "next_first_pass": 7, "never": 1}
    assert _total("dynamo_diffusion_passes_total", kind="write") == write
    took = _total("dynamo_diffusion_passes_total", kind="denoise") - denoise
    assert took == sum(max(out["fixed_at_pass"][i : i + B]) + 1 for i in range(0, 32, B))
    # Over everything this module's engines served, too.
    assert sum(_total(WRITES, by=name) for name in names) == _total(
        "dynamo_diffusion_blocks_total")


# ------------------------------------------------- nothing moves elsewhere
def _cfg(preset: str, **kw) -> EngineConfig:
    return EngineConfig(
        model=PRESETS[preset], max_decode_slots=4, page_size=8, num_pages=64,
        max_model_len=128, prefill_chunk=16, decode_window=4, **kw,
    )


_WINDOW = [
    ("tokens", (4,)), ("positions", (4,)), ("chained", (1,)), ("max_pos", (4,)),
    ("table", (4, 16)),
]
_MIXED = [
    ("tokens", (32,)), ("positions", (32,)), ("row_of", (32,)), ("table", (5, 16)),
    ("q_last", (5,)),
]
_SAMPLER = ["seeds", "temp", "top_k", "top_p", "freq", "pres", "rep"]
_GATES = [("stop_set", (4, 8)), ("eos_gate", (4,)), ("budget_gate", (4,))]


def _named(fields, rows):
    return fields if fields and isinstance(fields[0], tuple) else [(f, (rows,)) for f in fields]


@pytest.mark.parametrize("preset", ["tiny-qwen3", "tiny-moe"])
@pytest.mark.parametrize("windowed, full_sampler, want, size", [
    (True, False, _WINDOW + _GATES, 117),
    (True, True, _WINDOW + [("slot_map", (4,))] + _named(_SAMPLER, 4) + _GATES, 149),
    (False, False, _MIXED, 181),
    (False, True, _MIXED + [("pos0", (5,)), ("is_decode", (5,)), ("slot_map", (5,))]
     + _named(_SAMPLER, 5), 231),
])
def test_a_token_models_launch_is_what_it_was(preset, windowed, full_sampler, want, size):
    """The fields, shapes, offsets and length of PR 40's ``launch_layout``
    (read off a checkout of it), written out: only a block model's window
    grew."""
    nb = 4 if windowed else 32
    layout = launch_layout(
        _cfg(preset), (nb, None, windowed, full_sampler, False, False))
    assert [(f.name, f.shape) for f in layout] == want
    offsets = np.cumsum([0] + [int(np.prod(shape)) for _n, shape in want])
    assert [f.offset for f in layout] == list(offsets[:-1]) and offsets[-1] == size


def test_a_block_models_lattice_has_the_variants_it_had():
    """6 windows (3 row buckets x 2 samplers) beside the mixed variants,
    as many as PR 40 enumerated and as a token model of the same sizes
    has: the opening pass is inside the window every variant already is."""
    token = _cfg("tiny-moe")
    cfg = dataclasses.replace(token, model=ModelConfig.from_hf_config(HF))
    block = ragged_variants(cfg, "xla", include_lp=False)
    windows = [v for v in block if v.windowed]
    assert sorted({v.nb for v in windows}) == [1, 2, 4] and len(windows) == 6
    assert len(block) == LATTICE == len(ragged_variants(token, "xla", include_lp=False))
    grown = launch_layout(cfg, (4, None, True, False, False, False))
    assert [f.name for f in grown] == [
        "tokens", "positions", "prev_tokens", "prev", "chained", "fixed_at",
        "passes", "table",
    ]
