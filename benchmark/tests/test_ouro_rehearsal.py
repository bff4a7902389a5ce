"""``run_cell.py`` end to end on the CPU over a looped model
(``tiny-ouro.rehearsal-closed``: 4 layers run 3 times over one set of
weights, 12 layer slots a page; a rehearsal of the harness, never a
measurement): the server's normal path, the default comparison
``logprob_walk`` against ``references/ouro.py``, and the series the new
per-layer metrics read."""

from test_rehearsal import reference_detail, result_of, run_cell


def test_closed_loop_rehearsal_over_a_looped_model():
    proc, lines = run_cell("tiny-ouro.rehearsal-closed", 1, seconds=5)
    out = result_of(proc, lines)
    assert out["correct"] is True and out["device"]["platform"] == "cpu"
    assert out["attempted"] >= 20 and out["failed"] == 0
    ref = reference_detail(proc)
    # two probes of 8 decode steps, float32 all through
    assert ref["steps"] == 16 and ref["why"] is None
    assert 0 < ref["max_abs_logprob_diff"] < 1e-3 < ref["tol"]
    # A traced CPU run prints counters' metrics and no device metric (the cell
    # is in no metric's ``workloads`` list, so only the metrics without one).
    assert "decode_rows_mean" in out["metrics"]
    assert not {"attn_kernel_time_share", "loop_norm_time_share",
                "layer_passes_per_token"} & set(out["metrics"])
