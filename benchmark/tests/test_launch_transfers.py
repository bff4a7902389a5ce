"""``launch_transfers_per_dispatch`` (PR 36): host arrays handed to the
device a ragged launch, ``prom_counter_ratio`` over two counters on two
scraped texts; nothing from a program without the counter."""

import pytest

from lib import stats
from test_loop_parts import metric


def scrape(transfers: dict | None, dispatches: dict) -> str:
    lines = [
        f'dynamo_ragged_dispatch_total{{chained="{c}",full_sampler="0",nb="{nb}",'
        f'pages="kernel",program="{p}",want_lp="0"}} {v}'
        for (p, nb, c), v in dispatches.items()
    ]
    lines += [
        f'dynamo_launch_transfers_total{{program="{p}"}} {v}'
        for p, v in (transfers or {}).items()
    ]
    return "# HELP x y\n" + "\n".join(lines) + "\n"


def ctx_of(before: str, after: str) -> dict:
    return {"prom_before": stats.parse_prometheus(before),
            "prom_after": stats.parse_prometheus(after)}


def test_transfers_a_dispatch_between_two_scrapes():
    dispatches0 = {("window", 32, 0): 10.0, ("window", 32, 1): 4.0, ("mixed", 512, 0): 30.0}
    # A bucket first launched inside the window has no series before it.
    dispatches1 = dispatches0 | {("window", 32, 1): 9.0, ("mixed", 512, 0): 70.0,
                                 ("mixed", 1024, 0): 5.0}
    packed = ctx_of(scrape({"window": 14.0, "mixed": 30.0}, dispatches0),
                    scrape({"window": 19.0, "mixed": 75.0}, dispatches1))
    assert metric("launch_transfers_per_dispatch", packed) == 1.0
    # Field by field: 7 a window, 11 a mixed dispatch of a model with state.
    fields = ctx_of(scrape({"window": 98.0, "mixed": 330.0}, dispatches0),
                    scrape({"window": 133.0, "mixed": 825.0}, dispatches1))
    assert metric("launch_transfers_per_dispatch", fields) == pytest.approx((35 + 495) / 50)


def test_a_program_without_the_counter_reads_nothing():
    """The parent of the PR that added it: dispatches counted, transfers
    not; and no launch in the window is no ratio."""
    dispatches = {("window", 32, 0): 10.0, ("mixed", 512, 0): 30.0}
    later = {("window", 32, 0): 20.0, ("mixed", 512, 0): 60.0}
    parent = ctx_of(scrape(None, dispatches), scrape(None, later))
    assert metric("launch_transfers_per_dispatch", parent) is None
    still = scrape({"window": 10.0, "mixed": 30.0}, dispatches)
    assert metric("launch_transfers_per_dispatch", ctx_of(still, still)) is None
