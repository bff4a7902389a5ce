#!/usr/bin/env python3
"""What the engine loop's phase instrument costs an iteration, on the host.

    python3 tools/phase_overhead.py [--iterations 20000] [--rows 64]

One iteration of a saturated engine loop opens about ten phases
(``telemetry/dispatch.py`` PHASES), a launch, a dozen named parts
(PHASE_PARTS) and, in ``consume``, reads the clock four times a row. This
makes exactly those calls, with nothing between them, against a private
registry and no profiler session (as in a ``--trace 0`` run), and prints
the microseconds an iteration they take: the phases as they are (wall and
CPU clock, counters), what the parts add, and one read of either clock.
A micro-benchmark of host code: its numbers are this machine's CPU's,
never a device's (docs/observability.md "Engine phases and the device
trace").
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from prometheus_client import CollectorRegistry

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dynamo_exp_tpu.telemetry import Telemetry  # noqa: E402
from dynamo_exp_tpu.telemetry.dispatch import PHASE_PARTS, DispatchProfiler  # noqa: E402

# The phases of one iteration that admits, dispatches a mixed batch and
# consumes it (launch rides begin/end). ``assemble`` opens five times:
# ``_loop``'s own lists, ``_plan_ragged``, ``_build_mixed``'s plan and
# build, and ``_launch``'s lookup, each of which is a stretch of its own.
PHASES = (
    "kv_admin", "intake", "kv_admin", "admit", "kv_admin", "publish",
    "assemble", "assemble", "assemble", "assemble", "assemble", "sync",
    "consume",
)
ASSEMBLE_PARTS = (None, "plan", "plan", "build", "lookup")


def phases_only(prof: DispatchProfiler) -> None:
    for name in PHASES:
        with prof.phase(name):
            pass
    prof.end("ragged", prof.begin("ragged", "mixed"))


def phases_and_parts(prof: DispatchProfiler, rows: int) -> None:
    clock = time.monotonic
    assemble = iter(ASSEMBLE_PARTS)
    for name in PHASES:
        with prof.phase(name):
            if name == "admit":
                for part in PHASE_PARTS["admit"]:
                    with prof.part("admit", part):
                        pass
            elif name == "assemble":
                part = next(assemble)
                if part is not None:
                    with prof.part("assemble", part):
                        pass
            elif name == "consume":
                spent = 0.0
                for _ in range(rows):
                    t0 = clock(); t1 = clock(); t2 = clock(); t3 = clock()
                    spent += (t1 - t0) + (t3 - t2)
                prof.add_parts("consume", tokens=spent, pages=spent, emit=spent)
                with prof.part("consume", "finish"):
                    pass
    t0 = prof.begin("ragged", "mixed")
    for part in PHASE_PARTS["launch"]:
        with prof.part("launch", part):
            pass
    prof.end("ragged", t0)


def per_iteration_us(fn, n: int) -> float:
    fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--iterations", type=int, default=20000)
    ap.add_argument("--rows", type=int, default=64)
    args = ap.parse_args()
    prof = DispatchProfiler(Telemetry(CollectorRegistry()))
    n = args.iterations
    out = {
        "phases_us": per_iteration_us(lambda: phases_only(prof), n),
        "phases_and_parts_us": per_iteration_us(
            lambda: phases_and_parts(prof, args.rows), n
        ),
        "cpu_clock_read_us": per_iteration_us(time.thread_time, n),
        "wall_clock_read_us": per_iteration_us(time.monotonic, n),
        "phases_an_iteration": len(PHASES) + 1,
        "part_stretches_an_iteration": 10,
        "rows": args.rows,
    }
    out["parts_add_us"] = out["phases_and_parts_us"] - out["phases_us"]
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
