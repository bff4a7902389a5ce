"""``run_cell.py`` end to end on the CPU over a cache by kind
(``tiny-exaone-moe.rehearsal-closed``: a rehearsal of the harness, never a
measurement): the server's normal path, the comparison
``sliding_attention`` with all four of its parts, and the series the new
per-layer metrics read."""

from test_rehearsal import reference_detail, result_of, run_cell


def test_closed_loop_rehearsal_over_a_cache_by_kind():
    proc, lines = run_cell("tiny-exaone-moe.rehearsal-closed", 1, seconds=5)
    out = result_of(proc, lines)
    assert out["correct"] is True and out["device"]["platform"] == "cpu"
    assert out["attempted"] >= 20 and out["failed"] == 0
    ref = reference_detail(proc)
    assert ref["steps"] == 32 and ref["why"] is None
    assert [(c["layer"], c["rows"]) for c in ref["block"]] == [
        (1, 64), (1, 1024), (3, 64), (3, 1024), (4, 64), (4, 1024),
    ]
    assert 0 < ref["block_max_rel_err"] < ref["block_tol"]
    # one pair (sliding layer 0, full layer 3): the sliding pool's rows, then the full pool's
    assert [(c["layer"], c["form"]) for c in ref["pool_rows"]] == [(0, "sliding"), (3, "full")]
    assert all(c["rows"] > 200 for c in ref["pool_rows"])
    assert 0 <= ref["pool_rows_max_rel_err"] < ref["kv_tol"]
    assert ref["pool_unwritten_max_abs"] == 0.0
    # the model's 512 positions bound the long part: 504 tokens a row, 8 decoded;
    # nearly every sliding page went back while the rows lived
    assert {c["tokens"] for c in ref["long"]} == {504} and ref["long"][0]["layer"] == [0, 3]
    assert 0 < ref["long_max_rel_err"] < ref["attn_tol"]
    assert ref["sliding_pages_given_back"] >= 2 * (504 // 4 - 3)
    assert ref["sliding_peak_held"] <= ref["sliding_pages"]
    assert ref["median_abs_logprob_diff"] < ref["tol"]
    assert ref["long_median_abs_logprob_diff"] < ref["long_tol"]
    # A traced CPU run prints counters' metrics and no device metric (the cell
    # is in no metric's ``workloads`` list, so only the metrics without one).
    assert "decode_rows_mean" in out["metrics"]
    assert not {"attn_kernel_time_share", "swa_attn_roofline_share"} & set(out["metrics"])
