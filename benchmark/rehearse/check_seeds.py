#!/usr/bin/env python3
"""A configuration's comparison over many probe seeds in ONE process, the
same with the reference's controls in the program's place, and the record
of repeated askings of the fixed probes: what a tolerance is set from
(BENCHMARK contract, "How correct is decided", steps 3 and 4). No timed
window, no lattice warm-up, no load: the server is built as a cell's run
builds it and only asked probes.

    python3 benchmark/rehearse/check_seeds.py --workload mixtral-8x7b.chat-saturate \\
        --tag mixtral-seeds --seeds 11,12,13,14,15 --control fp8_experts,top1 \\
        --control-seeds 11,12,13 --askings 5

Writes ``chiprun_out/bench/<tag>.json``: ``sound`` (one comparison result
a seed, with every step's difference), ``controls`` ({control: results}),
``askings`` (per asking and probe: the text, the prompt tokens served
from cached pages, and which programs ran at which row counts, from the
deltas of /metrics round the asking) and ``repeatable`` (second asking
against the last). The controls (``CONTROLS``) are made here, on the
reference's side, and ``reference.py`` stays plain: the reference
computes with the fault against the sound program, which is roughly the
distance of the faulty program from the sound reference, without a
second engine beside weights that fill the chips.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(ROOT, "chiprun_out", "bench")
sys.path.insert(0, BENCH)
sys.path.insert(1, ROOT)

import run_cell  # noqa: E402

CONTROLS = ("fp8_attention", "fp8_head", "fp8_experts", "fp8_weights", "top1")
WATCHED = re.compile(
    r"^dynamo_(dispatch_seconds_count|decode_batch_rows_(sum|count)|prompt_tokens_total)$"
)


def _fp8(w):
    import jax.numpy as jnp

    return w.astype(jnp.float8_e4m3fn).astype(w.dtype)


def _retrace(reference) -> None:
    for f in vars(reference).values():
        if callable(getattr(f, "clear_cache", None)):
            f.clear_cache()


@contextlib.contextmanager
def control(name: str, ctx: dict):
    """``ctx`` with the fault ``name`` in what the reference computes."""
    reference = ctx["reference"]
    if name == "fp8_attention":  # the precision below bfloat16, outside the experts
        layers = dict(ctx["params"]["layers"])
        layers.update({k: _fp8(layers[k]) for k in ("wq", "wk", "wv", "wo")})
        yield {**ctx, "params": {**ctx["params"], "layers": layers}}
    elif name == "fp8_head":
        key = "lm_head" if "lm_head" in ctx["params"] else "embed"
        yield {**ctx, "params": {**ctx["params"], key: _fp8(ctx["params"][key])}}
    elif name == "fp8_experts":
        # The feed-forward's matrices, dense or routed. A second copy of expert
        # weights does not fit beside the first: they are rounded where the
        # reference takes them up, a layer at a time.
        plain = reference._swiglu
        reference._swiglu = lambda h, *w: plain(h, *(_fp8(x) for x in w))
        _retrace(reference)
        try:
            yield ctx
        finally:
            reference._swiglu = plain
            _retrace(reference)
    elif name == "fp8_weights":  # the contract's control for a bfloat16 configuration
        with contextlib.ExitStack() as stack:
            for part in ("fp8_attention", "fp8_head", "fp8_experts"):
                ctx = stack.enter_context(control(part, ctx))
            yield ctx
    elif name == "top1":
        yield {**ctx, "hf": {**ctx["hf"], "num_experts_per_tok": 1}}
    else:
        raise ValueError(f"unknown control {name!r}; there are {CONTROLS}")


class Checker(run_cell.Supervisor):
    def _deltas(self, before: dict, after: dict) -> dict:
        out = {}
        for key, v in after.items():
            name, labels = key
            d = v - before.get(key, 0)
            if d and WATCHED.match(name):
                out[name + "{" + ",".join(f"{k}={x}" for k, x in labels) + "}"] = d
        return out

    def _askings(self, n: int) -> list:
        from lib import correct

        record = []
        for k in range(n):
            for i, (content, n_tokens) in enumerate(correct.FIXED_PROBES):
                before = self._scrape()
                status, resp = correct.ask_greedy(self.base, run_cell.MODEL_NAME, content, n_tokens)
                time.sleep(0.3)  # the loop publishes its counters after the reply
                record.append({
                    "asking": k, "probe": i, "status": status,
                    "text": resp["choices"][0]["message"].get("content") if status == 200 else str(resp)[:200],
                    "usage": resp.get("usage") if status == 200 else None,
                    "metrics": self._deltas(before, self._scrape()),
                })
        return record

    def _run(self) -> dict:
        spec, args = self.spec, self.args
        self._wait_ready()
        run_cell.say(f"server ready after {time.monotonic() - run_cell.T_START:.1f}s")
        comparison = spec["comparison"][0]
        result = {"workload": spec["name"], "device": self.device, "sound": [], "controls": {}}

        def flush():
            os.makedirs(OUT, exist_ok=True)
            with open(os.path.join(OUT, f"{args.tag}.json"), "w") as f:
                json.dump(result, f, indent=1)

        if args.askings:
            result["askings"] = self._askings(args.askings)
            texts = [[r["text"] for r in result["askings"] if r["asking"] == k] for k in range(args.askings)]
            result["repeatable"] = texts[min(1, len(texts) - 1)] == texts[-1]
            flush()

        def compare(label: str, seed: int, into: list, ctx: dict | None = None) -> None:
            t = time.monotonic()
            out = comparison.compare(ctx or self.comparison_ctx(seed))
            out.update(seed=seed, seconds=time.monotonic() - t)
            brief = {k: v for k, v in out.items() if k not in ("block", "step_diffs")}
            run_cell.say(f"{label} seed {seed}: {json.dumps(brief)}")
            into.append(out)
            flush()

        for seed in args.seeds:
            compare("sound", seed, result["sound"])
        for name in args.control:
            for seed in args.control_seeds:
                with control(name, self.comparison_ctx(seed)) as faulty:
                    compare(f"control {name}", seed, result["controls"].setdefault(name, []), faulty)
        memory = [(d.memory_stats() or {}) for d in self.engine.mesh.devices.flat]
        result["memory_peak_bytes"] = max((m.get("peak_bytes_in_use", 0) for m in memory), default=0)
        flush()
        return result


def ints(text: str) -> list[int]:
    return [int(x) for x in text.split(",") if x]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--tag", required=True)
    ap.add_argument("--seeds", type=ints, default=[])
    ap.add_argument("--control", type=lambda s: [x for x in s.split(",") if x], default=[])
    ap.add_argument("--control-seeds", type=ints, default=[])
    ap.add_argument("--askings", type=int, default=0)
    args = ap.parse_args()
    args.trace, args.seed, args.seconds = 0, 0, 0.0
    sup, server_error = run_cell.serve(run_cell.load_cell(args.workload), args, Checker, warm=False)
    if server_error or sup.error or sup.result is None:
        run_cell.say(f"no result: server={server_error!r} supervisor={sup.error!r}")
        return 1
    ok = [r.get("ok") for r in sup.result["sound"]]
    failed = {c: [r.get("ok") for r in rs] for c, rs in sup.result["controls"].items()}
    print(json.dumps({"sound_ok": ok, "controls_ok": failed, "repeatable": sup.result.get("repeatable")}))
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
