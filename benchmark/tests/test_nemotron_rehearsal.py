"""``run_cell.py`` end to end on the CPU over layers of one half
(``tiny-nemotron-h.rehearsal-closed``: a rehearsal of the harness, never a
measurement): the server's normal path, the comparison ``mamba2_experts``
with its four parts, and the counters the new per-layer metrics read. Then
the comparison's teeth: the controls, made as
``rehearse/check_seeds_nemotron.py`` makes them on the chip, that the sound
program passes and each fault fails, at sizes cut to what the CPU runs in
seconds."""

import importlib.util
import os

import pytest

from comparisons import mamba2_experts
from test_rehearsal import reference_detail, result_of, run_cell

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_closed_loop_rehearsal_over_layers_of_one_half():
    proc, lines = run_cell("tiny-nemotron-h.rehearsal-closed", 1, seconds=5)
    out = result_of(proc, lines)
    assert out["correct"] is True and out["device"]["platform"] == "cpu"
    assert out["attempted"] >= 20 and out["failed"] == 0
    ref = reference_detail(proc)
    assert ref["steps"] == 32 and ref["why"] is None
    assert [(c["layer"], c["op"]) for c in ref["op_half"]] == [
        (0, "M"), (3, "*"), (5, "M"), (7, "M"), (8, "*"),
    ]
    assert [(c["layer"], c["rows"]) for c in ref["block"]] == [
        (1, 64), (1, 1024), (4, 64), (4, 1024), (6, 64), (6, 1024),
    ]
    assert 0 < ref["mamba_max_rel_err"] < ref["mamba_tol"]
    assert 0 < ref["state_max_rel_err"] < ref["state_tol"]
    assert 0 < ref["attn_max_rel_err"] < ref["attn_tol"]
    assert 0 < ref["block_max_rel_err"] < ref["block_tol"]
    assert ref["held_pick_share"] == 1.0  # the tiny model holds all it routes over
    assert ref["median_abs_logprob_diff"] < ref["tol"]
    # A traced CPU run prints counters' metrics and no device metric (the cell
    # is in no metric's ``workloads`` list, so only the metrics without one).
    assert "decode_rows_mean" in out["metrics"]
    assert not {"mamba2_mixer_time_share", "ssd_scan_roofline_share"} & set(out["metrics"])


def test_the_comparison_ends_a_tree_that_lacks_the_mixer():
    """What makes the parent of PR 63 fail at once in the new cell: the module
    takes ``mamba2_mixer`` from the program when it is imported, which is
    before the engine is built (``comparisons.resolve``)."""
    import inspect

    head = inspect.getsource(mamba2_experts).split("from comparisons.layer_kinds")[0]
    assert "from dynamo_exp_tpu.models.llama import MAMBA2_PARAMS, mamba2_mixer" in head


# --------------------------------------------------------------- the teeth
@pytest.fixture(scope="module")
def small():
    """The comparison's sizes cut to what the CPU runs in seconds."""
    saved = {k: getattr(mamba2_experts, k) for k in ("PREFILL", "CHUNK", "DECODE", "BLOCK_ROWS")}
    mamba2_experts.PREFILL, mamba2_experts.CHUNK, mamba2_experts.DECODE = 128, 32, 8
    mamba2_experts.BLOCK_ROWS = (64, 256)
    yield
    for k, v in saved.items():
        setattr(mamba2_experts, k, v)


@pytest.fixture(scope="module")
def ctx(small):
    import run_cell as harness
    from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
    from dynamo_exp_tpu.models import ModelConfig
    from dynamo_exp_tpu.parallel import single_device_mesh
    from references import nemotron_h

    hf = harness.hf_config(harness.load_json(harness.HERE, "configs", "tiny-nemotron-h.json"))
    engine = TPUEngine(EngineConfig(
        model=ModelConfig.from_hf_config(hf), max_decode_slots=2, page_size=8, num_pages=64,
        max_model_len=512, kv_dtype="float32",
    ), mesh=single_device_mesh(), seed=3)
    return {"engine": engine, "params": engine.params, "hf": hf, "seed": 7, "reference": nemotron_h}


def _parts(ctx) -> dict:
    """Parts 1 to 3 (no server: the walk is the rehearsal's)."""
    hf, reference = ctx["hf"], ctx["reference"]
    m, a, e = mamba2_experts.layers_read(hf["hybrid_override_pattern"])
    mixers, every = sorted(set(m) | set(a)), sorted(set(m) | set(a) | set(e))
    states = [
        reference.hidden_states(ctx["params"], hf, ids, every)
        for ids in mamba2_experts.long_sequences(ctx["seed"], hf["vocab_size"])
    ]
    at = {layer: i for i, layer in enumerate(every)}
    out = mamba2_experts.mixer_part(ctx, [s[[at[l] for l in mixers]] for s in states], mixers)
    import numpy as np

    rows = np.concatenate([s[[at[l] for l in e]] for s in states], axis=1)
    out.update(mamba2_experts.experts_part(ctx, rows, e))
    worst = lambda op, key: max(c[key] for c in out["op_half"] if c["op"] == op)  # noqa: E731
    return {
        "mamba": worst("M", "max_rel_err"), "state": worst("M", "state_rel_err"),
        "attn": worst("*", "max_rel_err"), "block": out["block_max_rel_err"],
    }


def _controls():
    spec = importlib.util.spec_from_file_location(
        "check_seeds_nemotron", os.path.join(BENCH, "rehearse", "check_seeds_nemotron.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


LIMITS = {
    "mamba": mamba2_experts.MAMBA_TOL, "state": mamba2_experts.STATE_TOL,
    "attn": mamba2_experts.ATTN_TOL, "block": mamba2_experts.BLOCK_TOL,
}


# ``bf16_state`` and the fp8 controls are left to the chip: a rounding of 2**-9 a
# token gathers over the state's memory, which 128 tokens are not, and a float32
# rehearsal's fp8 is not the bfloat16 configuration's.
@pytest.mark.parametrize("fault,fails", [
    (None, ()), ("zero_state", ("mamba", "state")), ("norm_before_gate", ("mamba",)),
    ("one_norm", ("mamba",)), ("relu", ("block",)), ("no_shared", ("block",)),
    ("no_scale", ("block",)), ("bias_as_weight", ("block",)), ("fp8_attention", ("attn",)),
])
def test_the_parts_pass_sound_and_fail_each_control(ctx, fault, fails):
    if fault is None:
        got = _parts(ctx)
        assert all(0 <= got[k] < LIMITS[k] for k in LIMITS), got
        return
    with _controls().control(fault, ctx) as faulty:
        got = _parts(faulty)
    for part in LIMITS:
        if part in fails:
            assert not got[part] <= LIMITS[part], (part, got)
        else:
            assert got[part] < LIMITS[part], (part, got)
