"""``run_cell.py`` end to end on the CPU over a latent pool beside an
index-key pool (``tiny-glm-dsa.rehearsal-closed``: a rehearsal of the
harness, never a measurement): the server's normal path, the comparison
``sparse_attention`` with all five of its parts, and the counters the new
per-layer metrics read."""

from test_rehearsal import reference_detail, result_of, run_cell


def test_closed_loop_rehearsal_over_two_pools_and_a_selection():
    proc, lines = run_cell("tiny-glm-dsa.rehearsal-closed", 1, seconds=5)
    out = result_of(proc, lines)
    assert out["correct"] is True and out["device"]["platform"] == "cpu"
    assert out["attempted"] >= 20 and out["failed"] == 0
    ref = reference_detail(proc)
    assert ref["steps"] == 32 and ref["why"] is None
    assert [(c["layer"], c["rows"]) for c in ref["block"]] == [(1, 64), (1, 1024), (2, 64), (2, 1024)]
    assert 0 < ref["block_max_rel_err"] < ref["block_tol"]
    assert [c["layer"] for c in ref["pool_rows"]] == [0, 1, 2]
    # float32 all through (the preset's dtype): the latent rows agree to the bit
    assert all(c["rows"] > 200 for c in ref["pool_rows"])
    assert 0 <= ref["latent_rows_max_rel_err"] < ref["latent_tol"]
    assert 0 < ref["index_keys_max_rel_err"] < ref["index_key_tol"]
    assert ref["pool_padding_max_abs"] == 0.0
    # the model's 512 positions bound the long part: 504 tokens a row, 8 decoded
    assert {c["tokens"] for c in ref["long"]} == {504}
    assert all(c["dropped_at_topk_plus_1"] == 1 for c in ref["long"])
    assert all(c["selecting_rows_keep_topk"] and c["unselecting_rows_keep_all"] for c in ref["long"])
    assert ref["select_min_share"] <= ref["min_selection_share"] <= 1.0
    assert ref["selection_rows"] > 0 and ref["selection_near_tie_share"] < 0.05
    assert 0 < ref["long_max_rel_err"] < ref["attn_tol"]
    assert ref["median_abs_logprob_diff"] < ref["tol"]
    assert ref["long_median_abs_logprob_diff"] < ref["long_tol"]
    # A traced CPU run prints counters' metrics and no device metric (the cell
    # is in no metric's ``workloads`` list, so only the metrics without one).
    assert "decode_rows_mean" in out["metrics"]
    assert not {"attn_kernel_time_share", "dsa_index_roofline_share"} & set(out["metrics"])
