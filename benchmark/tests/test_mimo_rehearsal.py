"""``run_cell.py`` end to end on the CPU over a cache by kind whose pools have
rows of different lanes (``tiny-mimo.rehearsal-closed``: a rehearsal of the
harness, never a measurement): the server's normal path, the comparison
``attention_by_kind`` with all four of its parts, and the series the new
per-layer metrics read."""

from test_rehearsal import reference_detail, result_of, run_cell


def test_closed_loop_rehearsal_over_pools_of_two_row_widths():
    proc, lines = run_cell("tiny-mimo.rehearsal-closed", 1, seconds=5)
    out = result_of(proc, lines)
    assert out["correct"] is True and out["device"]["platform"] == "cpu"
    assert out["attempted"] >= 20 and out["failed"] == 0
    ref = reference_detail(proc)
    assert ref["steps"] == 32 and ref["why"] is None
    # routed layers 1..5: first, middle, last
    assert [(c["layer"], c["rows"]) for c in ref["block"]] == [
        (1, 64), (1, 1024), (3, 64), (3, 1024), (5, 64), (5, 1024),
    ]
    assert 0 < ref["block_max_rel_err"] < ref["block_tol"]
    # layers F S S F S S: the pairs (1, 3) and (2, 3); the sliding pool's rows, then the full pool's
    assert [(c["layer"], c["form"]) for c in ref["pool_rows"]] == [
        (1, "sliding"), (3, "full"), (2, "sliding"), (3, "full"),
    ]
    assert all(c["rows"] > 200 for c in ref["pool_rows"])
    assert 0 <= ref["pool_rows_max_rel_err"] < ref["kv_tol"]
    assert ref["pool_unwritten_max_abs"] == 0.0
    # the model's 512 positions bound the long part: 504 tokens a row, 8 decoded
    assert {c["tokens"] for c in ref["long"]} == {504}
    assert [c["layer"] for c in ref["long"]] == [[1, 3], [1, 3], [2, 3], [2, 3]]
    assert 0 < ref["long_max_rel_err"] < ref["attn_tol"]
    assert ref["sliding_pages_given_back"] >= 2 * (504 // 4 - 3)
    assert ref["sliding_peak_held"] <= ref["sliding_pages"]
    assert ref["median_abs_logprob_diff"] < ref["tol"]
    assert ref["long_median_abs_logprob_diff"] < ref["long_tol"]
    # A traced CPU run prints counters' metrics and no device metric (the cell
    # is in no metric's ``workloads`` list, so only the metrics without one).
    assert "decode_rows_mean" in out["metrics"]
    assert not {"attn_kernel_time_share", "mimo_swa_attn_roofline_share"} & set(out["metrics"])
