#!/usr/bin/env python3
"""Several runs of cells in one call, one after another, each a new
process (a chip belongs to one process at a time; this parent never
imports JAX). For a builder's sets of runs and for the knee sweep.

    python3 benchmark/rehearse/runs.py --tag set1 --seconds 51 \\
        --run qwen3-4b.chat-steady:11:0 --run qwen3-4b.chat-steady:12:0

    python3 benchmark/rehearse/runs.py --tag knee --seconds 30 \\
        --sweep qwen3-4b.chat-steady:rate_per_s:2,2.5,3.2,4 --seed 7

``--run cell:seed:trace``. ``--sweep cell:key:v1,v2,...`` runs the cell
with one field of its workload file replaced, through a temporary
workload file named ``sweep-<cell>-<value>`` that is removed afterwards
(the rate a cell is judged at stays a number in its own file). Every
result line, with the run's diagnostics, is appended to
``chiprun_out/bench/<tag>.jsonl``; the log of each run goes beside it.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(ROOT, "chiprun_out", "bench")


def one(tag: str, cell: str, seed: int, seconds: float, trace: int, extra=()) -> dict:
    os.makedirs(OUT, exist_ok=True)
    detail = os.path.join(OUT, f"{tag}.jsonl")
    log = os.path.join(OUT, f"{tag}.{cell}.{seed}.{trace}.log")
    t0 = time.monotonic()
    with open(log, "w") as lf:
        proc = subprocess.run(
            [sys.executable, os.path.join(BENCH, "run_cell.py"), "--workload", cell,
             "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace),
             "--detail-out", detail, *extra],
            cwd=ROOT, stdout=subprocess.PIPE, stderr=lf, text=True,
        )
    wall = time.monotonic() - t0
    last = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else ""
    print(f"== {cell} seed={seed} trace={trace} rc={proc.returncode} wall={wall:.1f}s\n{last}", flush=True)
    if proc.returncode != 0:
        with open(log, errors="replace") as f:
            print(f.read()[-3000:], flush=True)
    return {"rc": proc.returncode, "wall_s": wall, "line": last}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tag", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--run", action="append", default=[])
    ap.add_argument("--sweep", action="append", default=[])
    ap.add_argument("--describe-trace", action="store_true")
    args = ap.parse_args()
    rcs = []
    for spec in args.run:
        cell, seed, trace = spec.split(":")
        extra = ()
        if args.describe_trace and trace == "1":
            extra = ("--describe-trace", os.path.join(OUT, f"{args.tag}.{cell}.trace-planes.json"))
        rcs.append(one(args.tag, cell, int(seed), args.seconds, int(trace), extra)["rc"])
    for spec in args.sweep:
        cell, key, values = spec.split(":")
        with open(os.path.join(BENCH, "workloads", f"{cell}.json")) as f:
            base = json.load(f)
        for v in values.split(","):
            name = f"sweep-{cell}-{v}"
            path = os.path.join(BENCH, "workloads", f"{name}.json")
            with open(path, "w") as f:
                json.dump({**base, key: float(v)}, f)
            try:
                rcs.append(one(args.tag, name, args.seed, args.seconds, 0)["rc"])
            finally:
                os.remove(path)
    return 1 if any(rcs) else 0


if __name__ == "__main__":
    sys.exit(main())
