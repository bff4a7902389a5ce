"""Metric arithmetic: percentiles, due-time timing, window clipping,
counter and histogram deltas."""

import pytest

from lib import stats


def rec(due, sent, chunks, end, asked, ok=True, completion=None):
    return {
        "due": due, "sent": sent, "chunks": chunks, "end": end, "ok": ok,
        "asked": asked, "completion_tokens": asked if completion is None else completion,
        "prompt_tokens": 10, "status": 200, "error": None,
    }


@pytest.mark.parametrize(
    "values,q,want",
    [
        ([1, 2, 3, 4, 5], 50, 3.0),
        ([1, 2, 3, 4], 50, 2.5),
        ([10, 20], 95, 19.5),
        ([7], 99, 7.0),
        (list(range(1, 101)), 95, 95.05),
        ([3, 1, 2], 0, 1.0),
        ([3, 1, 2], 100, 3.0),
    ],
)
def test_percentile_interpolates_like_numpy(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


def test_percentile_of_nothing_is_an_error_not_zero():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_ttft_is_taken_from_the_due_time_not_the_send_time():
    r = rec(due=10.0, sent=10.4, chunks=[[10.9, 1], [11.3, 9]], end=11.4, asked=9)
    assert stats.ttft_s(r) == pytest.approx(0.9)  # a late generator counts


def test_tpot_is_last_minus_first_token_over_tokens_minus_one():
    r = rec(due=0, sent=0, chunks=[[1.0, 1], [1.4, 9], [1.8, 17]], end=2, asked=17)
    assert stats.tpot_s(r) == pytest.approx(0.8 / 16)
    assert stats.chunk_gaps_s(r) == pytest.approx([0.4, 0.4])
    one_chunk = rec(due=0, sent=0, chunks=[[1.0, 4]], end=2, asked=4)
    assert stats.tpot_s(one_chunk) is None


def test_window_membership_is_by_due_time():
    before = rec(due=9.99, sent=9.99, chunks=[[10.5, 1], [11, 5]], end=11, asked=5)
    inside = rec(due=10.0, sent=10.0, chunks=[[10.5, 1], [25, 5]], end=25, asked=5)
    after = rec(due=20.0, sent=20.0, chunks=[[20.5, 1], [21, 5]], end=21, asked=5)
    s = stats.summarize([before, inside, after], 10.0, 20.0)
    assert s["attempted"] == 1 and s["failed"] == 0
    assert s["ttft_s"] == pytest.approx([0.5])


def test_tokens_count_only_when_received_inside_the_window():
    # 1 token at 9.5 (before), 4 at 10.5 (inside), 4 at 20.5 (after), from
    # a request that was due BEFORE the window: a rate is over all the work.
    r = rec(due=9.0, sent=9.0, chunks=[[9.5, 1], [10.5, 5], [20.5, 9]], end=21, asked=9)
    assert stats.tokens_in_window([r], 10.0, 20.0) == 4


def test_unfinished_wrong_length_and_refused_requests_fail():
    unfinished = rec(due=1, sent=1, chunks=[[1.5, 1]], end=None, asked=8, ok=False)
    short = rec(due=1, sent=1, chunks=[[1.5, 1], [2, 7]], end=2, asked=8, completion=7)
    refused = rec(due=1, sent=1, chunks=[], end=1.1, asked=8, ok=False)
    good = rec(due=1, sent=1, chunks=[[1.5, 1], [2, 8]], end=2, asked=8)
    s = stats.summarize([unfinished, short, refused, good], 0.0, 5.0)
    assert (s["attempted"], s["failed"]) == (4, 3)
    assert len(s["ttft_s"]) == 1  # tails are over completed requests; failures are counted


def test_inflight_counts_sent_and_not_ended():
    a = rec(due=0, sent=0.0, chunks=[], end=2.0, asked=1)
    b = rec(due=1, sent=1.0, chunks=[], end=None, asked=1, ok=False)
    assert [stats.inflight_at([a, b], t) for t in (0.5, 1.5, 2.5)] == [1, 2, 1]


PROM = """
# HELP dynamo_dispatch_seconds x
# TYPE dynamo_dispatch_seconds histogram
dynamo_dispatch_seconds_bucket{kind="ragged",le="0.1"} 3.0
dynamo_dispatch_seconds_sum{kind="ragged"} %s
dynamo_dispatch_seconds_count{kind="ragged"} %s
dynamo_dispatch_seconds_sum{kind="kv_move"} 5.0
dynamo_dispatch_seconds_count{kind="kv_move"} 5.0
dynamo_kv_prefix_hits_total{kind="shared"} %s
dynamo_kv_prefix_hits_total{kind="miss"} %s
dynamo_engine_hbm_page_occupancy 0.25
dynamo_build_info{platform="tpu",attn_impl="pallas",device_count="1"} 1.0
garbage line here
"""


def test_prometheus_parse_and_deltas():
    before = stats.parse_prometheus(PROM % (1.0, 10.0, 100.0, 50.0))
    after = stats.parse_prometheus(PROM % (4.0, 40.0, 400.0, 150.0))
    assert stats.sample_sum(after, "dynamo_engine_hbm_page_occupancy") == 0.25
    assert stats.histogram_mean_delta(
        before, after, "dynamo_dispatch_seconds", {"kind": "ragged"}
    ) == pytest.approx(3.0 / 30.0)
    assert stats.counter_delta(
        before, after, "dynamo_kv_prefix_hits_total", {"kind": "shared"}
    ) == 300.0
    assert stats.counter_delta(before, after, "dynamo_kv_prefix_hits_total") == 400.0
    # A labelled child that did not exist at the first scrape reads 0 there.
    assert stats.counter_delta({}, after, "dynamo_kv_prefix_hits_total", {"kind": "miss"}) == 150.0
    assert stats.counter_delta(before, after, "dynamo_never_seen_total") == 0.0
    assert stats.histogram_mean_delta(after, after, "dynamo_dispatch_seconds") is None


def test_readers_read_their_context():
    import importlib

    before = stats.parse_prometheus(PROM % (1.0, 10.0, 100.0, 50.0))
    after = stats.parse_prometheus(PROM % (4.0, 40.0, 400.0, 150.0))
    ctx = {
        "prom_before": before, "prom_after": after, "gauge_scrapes": [before, after],
        "gen": {"lateness_s": [0.001, 0.002, 0.003], "chunk_gap_s": []},
        "trace": {"ops_s": {"fusion.1": 3.0, "ragged_paged_attention": 1.0},
                  "chips": {0: {"idle_share": 0.2}, 1: {"idle_share": 0.3}}},
        "memory": [{"peak_bytes_in_use": 8, "bytes_limit": 16}, {"peak_bytes_in_use": 4, "bytes_limit": 16}],
    }

    def read(kind, **spec):
        return importlib.import_module(f"readers.{kind}").read(spec, ctx)

    assert read("prom_ratio", metric="dynamo_kv_prefix_hits_total",
                numerator=[{"kind": "shared"}, {"kind": "restore"}], denominator=[{}],
                scale=100) == pytest.approx(75.0)
    assert read("prom_gauge_mean", metric="dynamo_engine_hbm_page_occupancy", scale=100) == 25.0
    assert read("prom_hist_mean", metric="dynamo_dispatch_seconds",
                match={"kind": "ragged"}, scale=1000) == pytest.approx(100.0)
    assert read("prom_counter_delta", metric="dynamo_compile_cache_misses_total") == 0.0
    assert read("gen_percentile", series="lateness_s", q=50, scale=1000) == pytest.approx(2.0)
    assert read("gen_percentile", series="chunk_gap_s", q=99) is None  # nothing to read
    assert read("trace_name_share", pattern="ragged") == pytest.approx(25.0)
    assert read("trace_idle_share") == pytest.approx(30.0)
    assert read("memory_peak_share") == pytest.approx(50.0)
    ctx["trace"] = None
    assert read("trace_idle_share") is None and read("trace_name_share", pattern="x") is None
