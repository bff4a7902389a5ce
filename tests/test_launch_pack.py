"""One host-to-device transfer a launch (``aot/launch.py``; docs/engine_perf.md
"How a launch reaches the device"): what a builder writes into the
buffer's views comes out of the program's unpack bit for bit, engines of
four model kinds launch with one transfer a dispatch, and the padding
arguments of prewarm and the AOT compiler fit every program of a tiny
lattice."""

import asyncio
import itertools

import jax
import numpy as np
import pytest

from dynamo_exp_tpu.aot.compile import manifest_for_engine
from dynamo_exp_tpu.aot.launch import launch_buffer, launch_layout, unpack_launch
from dynamo_exp_tpu.aot.warmup import variant_call_args
from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
from dynamo_exp_tpu.models.config import PRESETS
from dynamo_exp_tpu.parallel import single_device_mesh
from dynamo_exp_tpu.protocols.common import BackendInput
from dynamo_exp_tpu.telemetry import get_telemetry

PS = 8


def _cfg(preset: str, **over) -> EngineConfig:
    base = dict(
        model=PRESETS[preset], max_decode_slots=4, page_size=PS, num_pages=64,
        max_model_len=128, prefill_chunk=16, decode_window=4,
        kv_dtype="float32", eos_token_ids=[],
    )
    return EngineConfig(**(base | over))


# ------------------------------------------------------------ (a) round trip
FAMILIES = [
    (family, full, spec, state)
    for family, full, spec, state in itertools.product(
        ("window", "chained", "mixed"), (False, True), (False, True), (False, True)
    )
    if family == "mixed" or not spec  # a window carries no draft spans
]
# Bits a float field must bring through: -0.0, a NaN with a payload, 1.0.
FLOAT_BITS = np.array([0x80000000, 0x7FC12345, 0x3F800000], np.uint32)


def _field_values(layout, rng) -> dict:
    out = {}
    for f in layout:
        if f.dtype == "float32":
            a = rng.standard_normal(f.shape).astype(np.float32)
            a.reshape(-1)[: len(FLOAT_BITS)] = FLOAT_BITS.view(np.float32)[: f.size]
        elif f.dtype == "bool":
            a = (np.arange(f.size) % 2 == 0).reshape(f.shape)  # True and False
        else:
            a = rng.integers(-(2**31), 2**31, f.shape).astype(np.int32)
        out[f.name] = a
    return out


@pytest.mark.parametrize(
    "family,full_sampler,with_spec,state", FAMILIES,
    ids=lambda v: v if isinstance(v, str) else "ny"[v],
)
def test_every_field_returns_with_its_shape_dtype_and_bits(
    family, full_sampler, with_spec, state
):
    cfg = _cfg("tiny-lfm2" if state else "tiny-qwen3")
    windowed = family != "mixed"
    key = (4 if windowed else 32, None, windowed, full_sampler, False, with_spec)
    layout = launch_layout(cfg, key)
    names = [f.name for f in layout]
    assert len(set(names)) == len(names)
    # One definition: want_lp and the page bound change no input.
    assert layout is launch_layout(cfg, (key[0], 2, windowed, full_sampler, True, with_spec))
    assert ("temp" in names) == full_sampler and ("is_decode" in names) == (
        full_sampler and not windowed
    )
    assert ("spec_idx" in names) == with_spec and ("spec_pos" in names) == (
        with_spec and full_sampler
    )
    assert ("state_slots" in names) == state
    assert ("state_src" in names) == (state and not windowed)
    # Fields tile the buffer with no hole and no overlap.
    assert [f.offset for f in layout] == list(
        itertools.accumulate([0] + [f.size for f in layout[:-1]])
    )

    packed, fields = launch_buffer(cfg, key, no_entry=7)
    assert packed.dtype == np.int32 and packed.shape == (layout[-1].offset + layout[-1].size,)
    assert launch_buffer(cfg, key)[0] is not packed  # a fresh buffer every launch
    # A builder finds every array of its family, whatever the variant takes.
    assert set(names) <= set(fields) and {"temp", "slot_map", "state_slots"} <= set(fields)
    want = _field_values(layout, np.random.default_rng(len(names)))
    for name, a in want.items():  # as a builder does: through the views
        fields[name][...] = a
    for name in set(fields) - set(names):  # scratch: goes nowhere
        fields[name][...] = 123
    if family == "chained":
        # A chained window starts from the buffer the window before it
        # launched with (same rows) and says so; the earlier one stands.
        before = packed.copy()
        packed, fields = launch_buffer(cfg, key, no_entry=7, like=packed)
        np.testing.assert_array_equal(packed, before)
        fields["chained"][0] = 1
        want["chained"] = np.ones(1, np.int32)
        fields["max_pos"][0] = want["max_pos"][0] = 77

    back = jax.jit(lambda p: unpack_launch(layout, p))(packed)
    assert sorted(back) == sorted(names)
    for f in layout:
        got = np.asarray(back[f.name])
        assert got.shape == f.shape and got.dtype == f.dtype, f
        as_bits = (lambda a: a) if f.dtype == "bool" else (lambda a: a.view(np.int32))
        np.testing.assert_array_equal(as_bits(got), as_bits(want[f.name]), err_msg=f.name)


def test_a_fresh_buffer_is_the_all_padding_batch():
    """What prewarm launches and a builder starts from: rows parked, pad
    rows on the scratch counts row and the scratch per-row index, gates
    that never fire, every state entry past the pool."""
    cfg = _cfg("tiny-jamba")
    for windowed, full, spec in itertools.product((True, False), repeat=3):
        key = (8, None, windowed, full, False, spec and not windowed)
        packed, f = launch_buffer(cfg, key, no_entry=7)
        got = jax.jit(lambda p: unpack_launch(launch_layout(cfg, key), p))(packed)
        for name, a in got.items():  # the program sees what the builder's views hold
            np.testing.assert_array_equal(np.asarray(a), f[name], err_msg=name)
        assert (f["positions"] == -1).all() and (f["tokens"] == 0).all()
        assert (f["slot_map"] == cfg.max_decode_slots).all()
        assert (f["top_p"] == 1.0).all() and (f["rep"] == 1.0).all() and (f["temp"] == 0).all()
        assert (f["state_slots"] == 7).all()
        if windowed:
            assert (f["max_pos"] == -1).all() and (f["stop_set"] == -1).all()
            assert (f["budget_gate"] == cfg.decode_window).all() and f["chained"][0] == 0
        else:
            assert (f["row_of"] == cfg.max_decode_slots).all() and (f["pos0"] == -1).all()
            assert (f["spec_pos"] == -1).all() and (f["spec_drafts"] == -1).all()
            assert (f["state_dst"] == 7).all() and (f["state_src"] == 0).all()
            assert not f["is_decode"].any()


# ---------------------------------------------- (b) one transfer a dispatch
def _counter(name: str, by: str = "program") -> dict[str, float]:
    """A counter's value by one label, summed over its others."""
    out: dict[str, float] = {}
    for family in get_telemetry().registry.collect():
        for s in family.samples:
            if s.name == name:
                out[s.labels[by]] = out.get(s.labels[by], 0.0) + s.value
    return out


async def _ask(engine, prompt, max_tokens, temperature=None, seed=None):
    b = BackendInput(token_ids=list(prompt))
    b.stop_conditions.max_tokens = max_tokens
    b.stop_conditions.ignore_eos = True
    if temperature is not None:
        b.sampling_options.temperature = temperature
        b.sampling_options.top_p = 0.9
        b.sampling_options.seed = seed
    stream = await engine.generate(b.to_dict())
    out = []
    async for item in stream:
        out.extend(item.get("token_ids", []))
    return out


@pytest.mark.parametrize("preset", ["tiny-qwen3", "tiny-moe", "tiny-lfm2", "tiny-jamba"])
def test_an_engine_launches_with_one_transfer_a_dispatch(preset):
    """Greedy and seeded-sampled requests over mixed dispatches, windows
    and chained windows: every launch hands the device one host array,
    and a sampled request repeats under its seed (the sampler's float
    fields travel as their bits)."""
    engine = TPUEngine(_cfg(preset), mesh=single_device_mesh(), seed=0)
    transfers0 = _counter("dynamo_launch_transfers_total")
    dispatches0 = _counter("dynamo_ragged_dispatch_total")
    chained0 = _counter("dynamo_ragged_dispatch_total", "chained").get("1", 0)
    engine.start()
    try:
        async def drive():
            greedy = await asyncio.gather(
                _ask(engine, range(20, 60), 21), _ask(engine, range(30, 40), 9)
            )
            sampled = [
                await _ask(engine, range(40, 64), 18, temperature=0.8, seed=11)
                for _ in range(2)
            ]
            return greedy, sampled

        greedy, sampled = asyncio.run(drive())
    finally:
        engine.stop()
    assert [len(t) for t in greedy] == [21, 9]
    assert sampled[0] == sampled[1] and len(sampled[0]) == 18
    transfers = _counter("dynamo_launch_transfers_total")
    dispatches = _counter("dynamo_ragged_dispatch_total")
    for program in ("window", "mixed"):
        n = dispatches[program] - dispatches0.get(program, 0)
        assert n > 0, program
        assert transfers[program] - transfers0.get(program, 0) == n, program
    # Among them windows chained onto the one before.
    assert _counter("dynamo_ragged_dispatch_total", "chained")["1"] > chained0


# ------------------------------- (c) the padding arguments fit every program
@pytest.mark.parametrize("preset", ["tiny-qwen3", "tiny-lfm2"])
def test_variant_call_args_fit_every_program_of_a_tiny_lattice(preset):
    """For every key of the lattice ``variant_call_args`` builds what the
    program accepts: it traces (``eval_shape``: the unpack's slices, the
    sampler's dtypes and the state rows all meet their consumers), and the
    packed argument is the layout's size, placed as a live launch's is."""
    spec = not PRESETS[preset].has_seq_state  # refused beside a sequence state
    cfg = _cfg(preset, max_decode_slots=2, max_model_len=32, prefill_chunk=8,
               spec_mode="ngram" if spec else "off")
    engine = TPUEngine(cfg, mesh=single_device_mesh(), seed=0)
    keys = [v.key for v in manifest_for_engine(engine).ragged]
    assert {(k[2], k[3], k[5]) for k in keys} >= {
        (True, False, False), (True, True, False), (False, False, spec), (False, True, spec),
    }
    for key in keys:
        args = variant_call_args(engine, key)
        layout = launch_layout(cfg, key)
        packed = args[5] if key[2] else args[3]
        assert packed.shape == (layout[-1].offset + layout[-1].size,), key
        assert packed.sharding == engine._launch_sharding
        jax.eval_shape(engine._ragged_fn_from_key(key), *args)


def test_a_config_without_a_model_of_its_own_shares_the_layout():
    """The layout reads sizes, never a model's name: two configurations of
    equal sizes give the same object."""
    a, b = _cfg("tiny-qwen3"), _cfg("tiny-moe")
    key = (16, None, False, False, False, False)
    assert launch_layout(a, key) is launch_layout(b, key)
