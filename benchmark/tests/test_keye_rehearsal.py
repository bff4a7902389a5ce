"""``run_cell.py`` end to end on the CPU over K rows that hold an index key
(``tiny-keye.rehearsal-closed``: a rehearsal of the harness, never a
measurement): the server's normal path, the comparison
``sparse_attention_gqa`` with all five of its parts, and the counters the new
per-layer metrics read. Then the comparison's teeth: a control, made as
``rehearse/check_seeds_keye.py`` makes it, that the sound program passes and
the fault fails (``test_comparisons.py``'s manner)."""

import pytest

from test_rehearsal import reference_detail, result_of, run_cell


def test_closed_loop_rehearsal_over_a_row_of_three_things_and_a_selection():
    proc, lines = run_cell("tiny-keye.rehearsal-closed", 1, seconds=5)
    out = result_of(proc, lines)
    assert out["correct"] is True and out["device"]["platform"] == "cpu"
    assert out["attempted"] >= 20 and out["failed"] == 0
    ref = reference_detail(proc)
    assert ref["steps"] == 32 and ref["why"] is None
    assert [(c["layer"], c["rows"]) for c in ref["block"]] == [
        (0, 64), (0, 1024), (1, 64), (1, 1024), (2, 64), (2, 1024),
    ]
    assert 0 < ref["block_max_rel_err"] < ref["block_tol"]
    assert [c["layer"] for c in ref["cached_rows"]] == [0, 1, 2]
    assert all(c["rows"] > 200 for c in ref["cached_rows"])
    for key, tol in (("k_rows_max_rel_err", "kv_row_tol"), ("v_rows_max_rel_err", "kv_row_tol"),
                     ("index_keys_max_rel_err", "index_key_tol")):
        assert 0 <= ref[key] < ref[tol], key
    assert ref["pool_padding_max_abs"] == 0.0
    # the model's 512 positions bound the long part: 504 tokens a row, 8 decoded
    assert {c["tokens"] for c in ref["long"]} == {504}
    assert all(c["dropped_at_topk_plus_1"] == 1 for c in ref["long"])
    assert all(c["selecting_rows_keep_topk"] and c["unselecting_rows_keep_all"] for c in ref["long"])
    assert ref["select_min_share"] <= ref["min_selection_share"] <= 1.0
    assert ref["selection_rows"] > 0 and ref["selection_near_tie_share"] < 0.05
    assert 0 < ref["long_max_rel_err"] < ref["attn_tol"]
    assert ref["rotary_tokens"] == 512 and 0 <= ref["rotary_max_rel_err"] < ref["attn_tol"]
    assert ref["median_abs_logprob_diff"] < ref["tol"]
    assert ref["long_median_abs_logprob_diff"] < ref["long_tol"]
    # A traced CPU run prints counters' metrics and no device metric (the cell
    # is in no metric's ``workloads`` list, so only the metrics without one).
    assert "decode_rows_mean" in out["metrics"]
    assert not {"attn_kernel_time_share", "keye_index_roofline_share"} & set(out["metrics"])


# --------------------------------------------------------------- the teeth
@pytest.fixture(scope="module")
def ctx():
    """A tiny engine and what a comparison is given beside the server: parts
    2 to 4 need no server."""
    import run_cell as harness
    from dynamo_exp_tpu.engine import EngineConfig, TPUEngine
    from dynamo_exp_tpu.models import ModelConfig
    from dynamo_exp_tpu.parallel import single_device_mesh
    from references import keye_vl2

    hf = harness.hf_config(harness.load_json(harness.HERE, "configs", "tiny-keye.json"))
    engine = TPUEngine(EngineConfig(
        model=ModelConfig.from_hf_config(hf), max_decode_slots=2, page_size=8, num_pages=64,
        max_model_len=512, kv_dtype="float32",
    ), mesh=single_device_mesh(), seed=3)
    return {"engine": engine, "params": engine.params, "hf": hf, "seed": 7, "reference": keye_vl2}


@pytest.mark.parametrize("fault,fails", [
    (None, False), ("top2047", True), ("no_relu", True), ("index_keys_fp8", True),
    ("dense", True), ("sections_permuted", True),
])
def test_the_long_and_rotary_parts_pass_sound_and_fail_each_control(ctx, fault, fails):
    """Parts 3 to 5 at a tiny size (two rows of 120 and 64 tokens, chunks of
    32; 512 tokens of unequal position components): the sound program shares
    every selecting row's set with the reference and drops one position at
    context 17; a selection of ``topk - 1``, scores not cut at zero, float8
    index keys and no selection each break a limit of parts 3 and 4;
    sections permuted cannot show there (text: three equal components) and
    break part 5's."""
    import contextlib
    import os
    import sys

    from comparisons import sparse_attention as sparse
    from comparisons import sparse_attention_gqa as gqa

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "rehearse"))
    import check_seeds_keye

    plain_chunk = sparse.CHUNK
    with contextlib.ExitStack() as stack:
        faulty = stack.enter_context(check_seeds_keye.control(fault, ctx)) if fault else ctx
        import comparisons.latent_attention as latent
        chunk = latent.CHUNK
        latent.CHUNK = sparse.CHUNK = 32
        try:
            with gqa._over_this_model(faulty["hf"]) as me:
                out = sparse.long_part(
                    {**faulty, "hf": {**faulty["hf"], "index_topk": me.topk}}, [0, 2],
                    lengths=(120, 64), chunk=32,
                )
            rotary = gqa.rotary_part(faulty)
        finally:
            latent.CHUNK, sparse.CHUNK = chunk, plain_chunk
    sets_right = all(
        c["selecting_rows_keep_topk"] and c["unselecting_rows_keep_all"]
        and c["dropped_at_topk_plus_1"] == 1 for c in out["long"]
    )
    sound = (
        sets_right and out["min_selection_share"] >= gqa.SELECT_MIN_SHARE
        and out["long_max_rel_err"] <= gqa.ATTN_TOL
    )
    if fault == "sections_permuted":
        assert sound and rotary["rotary_max_rel_err"] > gqa.ATTN_TOL, rotary
        return
    if fault is None:  # under the other faults part 5's rows select too (topk 16 < 512)
        assert rotary["rotary_max_rel_err"] <= gqa.ATTN_TOL, rotary
    assert sound is not fails, (fault, out["min_selection_share"], out["long_max_rel_err"])
