#!/usr/bin/env python3
"""Where a tier-1 run's CPU time went, from its junit file.

    python3 tools/tier1_times.py /tmp/_t1.xml

Prints the CPU-seconds by test file (the sum of the ``time`` of its
cases: each case's own seconds of set-up, call and tear-down on its
worker, summed over all workers) and every test of
20 s or more, then holds the run to the budgets of docs/testing.md:
3,000 s in total, 250 s a file, 150 s a test. Under the driver's
``-n 6 --dist loadfile`` a run's wall time is about
max(largest file, total / 6), so both the file and the total count.
Exits 1 where a budget is passed. Host CPU-seconds of the machine that
ran the tests: never a device's number.
"""

from __future__ import annotations

import sys
import xml.etree.ElementTree as ET
from collections import defaultdict

TOTAL_BUDGET_S = 3000.0
FILE_BUDGET_S = 250.0
TEST_BUDGET_S = 150.0
SHOWN_FROM_S = 20.0
WORKERS = 6


def main(path: str) -> int:
    by_file: dict[str, list[float]] = defaultdict(list)
    tests = []
    for case in ET.parse(path).getroot().iter("testcase"):
        seconds = float(case.get("time", 0.0))
        by_file[case.get("classname", "?")].append(seconds)
        tests.append((seconds, f"{case.get('classname')}::{case.get('name')}"))
    sums = {name: sum(times) for name, times in by_file.items()}
    total = sum(sums.values())
    over = []

    def held(kind: str, name: str, seconds: float, budget: float) -> str:
        """The mark behind a line that passed ``budget``, noted in ``over``."""
        if seconds <= budget:
            return ""
        over.append(f"{kind} {name}: {seconds:.1f} s")
        return f"  > {budget:.0f}"

    for name, seconds in sorted(sums.items(), key=lambda kv: -kv[1]):
        mark = held("file", name, seconds, FILE_BUDGET_S)
        print(f"{seconds:9.1f} s {len(by_file[name]):5d} tests  {name}{mark}")
    for seconds, name in sorted((t for t in tests if t[0] >= SHOWN_FROM_S), reverse=True):
        print(f"{seconds:9.1f} s  {name}{held('test', name, seconds, TEST_BUDGET_S)}")
    if total > TOTAL_BUDGET_S:
        over.append(f"total: {total:.1f} s")
    largest = max(sums.values(), default=0.0)
    print(
        f"total {total:.1f} s in {len(tests)} tests of {len(sums)} files; "
        f"wall on {WORKERS} workers about {max(largest, total / WORKERS):.0f} s"
    )
    for line in over:
        print(f"OVER BUDGET: {line}")
    return 1 if over else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
