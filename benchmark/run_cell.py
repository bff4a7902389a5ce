#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmark/run_cell.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

A new process that builds the cell's model directory, starts the normal
server (``dynamo_exp_tpu.run`` ``in=http out=tpu``) in this process's
main thread, warms it up, drives it over HTTP from a child process that
never imports JAX, checks the outputs, and prints ONE JSON line last on
stdout: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(and ``breakdown`` when traced). See README.md beside this file.

The server lives in this process because only the process that holds the
chip can trace it. A supervisor thread drives the phases and ends the
server with SIGTERM to this process, the way an operator would.
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # process start, for setup_s

import argparse
import importlib
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK = os.path.join(HERE, ".work")  # generated: model dirs, plans, traces
MODEL_NAME = "bench"
BOOT_TIMEOUT_S = 1100.0
CONFIG_OWN_KEYS = (
    "source", "reduced", "reduced_from", "assumed", "deployment", "chips",
    "serve_args", "num_pages_arithmetic", "comparison", "reference",
)
TRACE_START_S = 2.0  # into the window
TRACE_SLICE_S = 4.0

sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)


class BenchFailure(Exception):
    """The run cannot give a result. Ends the process non-zero."""


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


# ------------------------------------------------------------------ spec
def load_cell(name: str) -> dict:
    """The cell, its configuration, its traffic mix and the metrics
    ``BENCHMARK.json`` lists for it, each from the file its name finds."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = load_json(HERE, "workloads", f"{name}.json")
    config = load_json(HERE, "configs", f"{cell['config']}.json")
    traffic = load_json(HERE, "traffic", f"{cell['traffic']}.json")

    def mine(metrics):
        return [m for m in metrics if "workloads" not in m or name in m["workloads"]]

    return {
        "name": name, "cell": cell, "config_name": cell["config"], "config": config,
        "traffic": traffic, "end_to_end": mine(bench["end_to_end"]),
        "per_layer": mine(bench["per_layer"]),
    }


def hf_config(config: dict) -> dict:
    return {k: v for k, v in config.items() if k not in CONFIG_OWN_KEYS}


def prepare_model_dir(spec: dict) -> str:
    from lib import modeldir

    return modeldir.prepare(
        os.path.join(WORK, spec["config_name"], "model"), hf_config(spec["config"])
    )


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------- device
def rehearsal_allowed(spec: dict) -> bool:
    """A CPU run exists only as a rehearsal of the harness: asked for by
    name (``JAX_PLATFORMS=cpu``) and only at a ``tiny-*`` configuration."""
    return (
        os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
        and spec["config_name"].startswith("tiny-")
    )


def check_devices(spec: dict) -> dict:
    import jax

    devs = jax.devices()
    want = int(spec["config"]["chips"])
    platform = devs[0].platform
    if platform != "tpu" and not rehearsal_allowed(spec):
        raise BenchFailure(
            f"no TPU: jax reports platform={platform!r}; a cell is measured on "
            "the chip or not at all"
        )
    if len(devs) < want:
        raise BenchFailure(f"cell needs {want} chips, jax sees {len(devs)}")
    kind = devs[0].device_kind
    if platform == "tpu":
        peaks = load_json(HERE, "peaks.json")["devices"]
        if kind not in peaks:
            raise BenchFailure(f"device_kind {kind!r} is not in peaks.json")
    return {"platform": platform, "kind": kind, "count": want}


def warm_lattice(engine) -> dict:
    """Build every greedy program variant this engine can reach before
    traffic, so that nothing compiles inside the window whatever mix of
    rows and prompt chunks a moment of the run produces. Sampled,
    log-probability and speculative variants are not built: every
    benchmark request is greedy (the two reference probes build the two
    log-probability variants they use, during set-up)."""
    from dynamo_exp_tpu.aot.compile import manifest_for_engine

    cfg = engine.cfg
    manifest = manifest_for_engine(engine, include_lp=False, include_spec=False)
    # The largest flat query stream a dispatch can carry: a full prefill
    # batch plus every slot decoding, each aligned to the kernel's tile.
    reach = cfg.ragged_tokens_bucket_for(
        cfg.prefill_batch * cfg.prefill_chunk
        + cfg.max_decode_slots * cfg.ragged_q_tile,
        mixed=True,
    )
    manifest.ragged = [
        v for v in manifest.ragged
        if not v.full_sampler and (v.windowed or v.nb <= reach)
    ]
    manifest.move_buckets = []
    t0 = time.monotonic()
    report = engine.prewarm(manifest)
    return {"variants": report.ragged_variants, "seconds": time.monotonic() - t0}


# ------------------------------------------------------------ supervisor
class Supervisor:
    """Drives the phases from a thread while the server owns the main one."""

    def __init__(self, spec: dict, args, base: str, model_dir: str):
        self.spec, self.args, self.base, self.model_dir = spec, args, base, model_dir
        self.engine = None  # set by the build hook, in the main thread
        self.device: dict = {}
        self.warm: dict = {}
        self.server_exited = threading.Event()
        self.result: dict | None = None
        self.error: str | None = None
        self.thread = threading.Thread(target=self._main, name="bench-supervisor", daemon=True)

    # -- helpers
    def _scrape(self) -> dict:
        from lib.correct import http_text
        from lib.stats import parse_prometheus

        return parse_prometheus(http_text(self.base + "/metrics"))

    def _wait_ready(self) -> None:
        from lib.correct import http_json

        deadline = time.monotonic() + BOOT_TIMEOUT_S
        while time.monotonic() < deadline:
            if self.server_exited.is_set():
                raise BenchFailure("the server ended before it was ready")
            try:
                status, _ = http_json(self.base + "/health", timeout_s=5.0)
                if status == 200:
                    return
            except OSError:
                pass
            time.sleep(0.25)
        raise BenchFailure(f"server not ready within {BOOT_TIMEOUT_S:.0f}s")

    def comparison_ctx(self, seed: int) -> dict:
        """What a comparison is given (``comparisons/__init__.py``)."""
        return {
            "base": self.base, "model": MODEL_NAME, "engine": self.engine,
            "params": self.engine.params, "hf": hf_config(self.spec["config"]),
            "tokenizer_file": os.path.join(self.model_dir, "tokenizer.json"),
            "seed": seed, "reference": self.spec["comparison"][1],
        }

    def _sleep_until(self, t: float) -> None:
        while True:
            left = t - time.monotonic()
            if left <= 0:
                return
            if self.server_exited.is_set():
                raise BenchFailure("the server ended inside the run")
            time.sleep(min(left, 0.2))

    # -- the run
    def _main(self) -> None:
        try:
            self.result = self._run()
        except Exception as e:  # the boundary: report, then end the server
            self.error = f"{type(e).__name__}: {e}"
            traceback.print_exc(file=sys.stderr)
        finally:
            if not self.server_exited.is_set():
                os.kill(os.getpid(), signal.SIGTERM)

    def _run(self) -> dict:
        from lib import correct, stats

        spec, args = self.spec, self.args
        traced = bool(args.trace)
        self._wait_ready()
        say(f"server ready after {time.monotonic() - T_START:.1f}s; warm: {self.warm}")
        on_chip = self.device["platform"] == "tpu"

        # (b) placement, before anything else is asked of the engine.
        place = correct.placement(self._scrape())
        info = place["build_info"]
        placed_ok = (
            info.get("platform") == self.device["platform"]
            and info.get("device_count") == str(self.device["count"])
            and (not on_chip or info.get("attn_impl") == "pallas")
        )

        # (d) the configuration's comparison with its reference.
        compare = spec["comparison"][0].compare
        t = time.monotonic()
        ref = compare(self.comparison_ctx(args.seed))
        say(f"reference check {time.monotonic() - t:.1f}s: {ref}")
        # (c) the fixed probes, at their second asking (warm: lib/correct.py).
        probes_before = correct.second_asking_texts(self.base, MODEL_NAME)

        # The plan, from the seed.
        import random

        from lib.textgen import TextMaker

        text = TextMaker(
            os.path.join(self.model_dir, "tokenizer.json"), random.Random(args.seed)
        )
        t = time.monotonic()
        self._ramp(text)
        say(f"ramp {time.monotonic() - t:.1f}s")
        gen = importlib.import_module(f"generators.{spec['traffic']['generator']}")
        plan = gen.generate(spec["traffic"], spec["cell"], args.seed, float(args.seconds), text)
        lead_in = float(plan["lead_in_s"])
        plan.update(
            base=self.base, model=MODEL_NAME, t0=time.monotonic() + 1.0,
            w0=lead_in, w1=lead_in + float(args.seconds),
            drain_s=float(spec["cell"].get("drain_s", 60)),
        )
        run_dir = os.path.join(WORK, spec["config_name"], "run")
        os.makedirs(run_dir, exist_ok=True)
        plan_path, out_path = os.path.join(run_dir, "plan.json"), os.path.join(run_dir, "records.json")
        with open(plan_path, "w") as f:
            json.dump(plan, f)
        if os.path.exists(out_path):
            os.remove(out_path)
        child_env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
        child = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "lib", "loadgen.py"), plan_path, out_path],
            env=child_env, stdout=sys.stderr, stderr=sys.stderr,
        )
        try:
            w0, w1 = plan["t0"] + plan["w0"], plan["t0"] + plan["w1"]
            self._sleep_until(w0)
            setup_s = time.monotonic() - T_START
            prom_before = self._scrape() if traced else {}
            gauge_scrapes, trace_dir = [], None
            if traced:
                trace_dir = os.path.join(run_dir, "trace")
                self._traced_window(w0, w1, trace_dir, gauge_scrapes, on_chip)
            self._sleep_until(w1)
            prom_after = self._scrape() if traced else {}
            child.wait(timeout=plan["drain_s"] + 60)
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()
        if child.returncode != 0:
            raise BenchFailure(f"load generator exited {child.returncode}")
        records = load_json(out_path)["records"]
        summary = stats.summarize(records, w0, w1)

        probes_after = correct.second_asking_texts(self.base, MODEL_NAME)
        final = correct.placement(self._scrape())
        memory = [
            (d.memory_stats() or {}) for d in self.engine.mesh.devices.flat
        ]
        checks = {
            "lengths": summary["failed"] == 0 and summary["attempted"] > 0,
            "placement": placed_ok,
            "kv_ledger": final["kv_ledger_violations"] == 0,
            "repeatable": probes_before == probes_after,
            "reference": bool(ref["ok"]),
        }
        say(f"checks: {checks}; build_info: {info}")

        trace = None
        if traced and on_chip and trace_dir:
            from lib import trace_reduce

            xplane = trace_reduce.find_xplane(trace_dir)
            trace = trace_reduce.reduce(trace_reduce.load(xplane))
            if args.describe_trace:
                with open(args.describe_trace, "w") as f:
                    json.dump(trace_reduce.describe(xplane), f, indent=1)
        ctx = {
            "gen": summary, "prom_before": prom_before, "prom_after": prom_after,
            "gauge_scrapes": gauge_scrapes, "trace": trace,
            "memory": memory if on_chip else [],
        }
        metrics = (
            self._per_layer(ctx) if traced else self._end_to_end(summary, setup_s)
        )
        device = dict(self.device)
        peaks = [m.get("peak_bytes_in_use") for m in memory if m.get("peak_bytes_in_use")]
        device["memory_peak_bytes"] = max(peaks) if peaks else 0
        result = {
            "correct": all(checks.values()),
            "attempted": summary["attempted"],
            "failed": summary["failed"],
            "metrics": metrics,
            "device": device,
        }
        detail = {
            "checks": checks,
            "reference": {
                "max_abs_logprob_diff": None, "tol": None, "steps": None, "why": None,
                **{k: v for k, v in ref.items() if k != "ok"},
            },
            "seed": args.seed,
            "workload": spec["name"],
            "setup": {"setup_s": setup_s, "warm": self.warm},
            "load": {
                "inflight_first_half_mean": summary["inflight_half_mean"],
                "inflight_at_end": summary["inflight_end"],
                "requests_sent": len(records),
                "ttft_ms": self._quantiles(summary["ttft_s"]),
                "tpot_ms": self._quantiles(summary["tpot_s"]),
                "output_tok_s": summary["tokens_in_window"] / summary["window_s"],
                "prompt_tokens_mean": (
                    sum(summary["prompt_tokens"]) / len(summary["prompt_tokens"])
                    if summary["prompt_tokens"] else None
                ),
            },
        }
        if trace:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            result["breakdown"] = {
                "device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"],
            }
        # What a builder wants beside the result: on stderr, and in a file
        # when asked; the result line itself carries only the contract's keys.
        detail["result"] = result
        say("detail: " + json.dumps(detail))
        if args.detail_out:
            with open(args.detail_out, "a") as f:
                f.write(json.dumps(detail) + "\n")
        return result

    def _ramp(self, text) -> None:
        """Decode at every row bucket once, in both attention forms, before
        the lead-in. ``warm_lattice`` builds each program, but the engine
        feeds a decode window's outputs straight into the next one, and the
        first call of a program with device-resident inputs is traced and
        lowered anew (a second signature of the same program; the
        executable comes from the cache). Inside a window that is seconds
        of a busy host with an idle device (PERF.md, PR 23), and the
        engine's own miss counter does not see it. So: for each bucket,
        that many requests at once, long enough for several chained
        windows; once with short contexts (the XLA gather) and once with
        one row past 1k tokens (the kernel)."""
        from concurrent.futures import ThreadPoolExecutor

        from lib.correct import http_json

        cfg = self.engine.cfg
        ctx = min(1100, cfg.max_model_len // 2)
        long_row = text.user_message(ctx) if ctx > 1024 else None
        short = text.user_message(24)
        tokens = 5 * cfg.decode_window

        def ask(messages):
            status, resp = http_json(
                self.base + "/v1/chat/completions",
                {"model": MODEL_NAME, "messages": messages, "max_tokens": tokens,
                 "temperature": 0, "ignore_eos": True, "stream": False},
            )
            if status != 200:
                raise BenchFailure(f"ramp request: HTTP {status}: {str(resp)[:200]}")

        buckets, n = [], 1
        while n < cfg.max_decode_slots:
            buckets.append(n)
            n *= 2
        buckets.append(cfg.max_decode_slots)
        with ThreadPoolExecutor(max_workers=cfg.max_decode_slots) as pool:
            for rows in buckets:
                for first in ([short] if long_row is None else [short, long_row]):
                    list(pool.map(ask, [first] + [short] * (rows - 1)))

    def _traced_window(self, w0, w1, trace_dir, gauge_scrapes, on_chip):
        """Inside the window of a traced run: scrape the gauges each
        second and, on the chip, profile one short slice."""
        import shutil

        import jax

        shutil.rmtree(trace_dir, ignore_errors=True)
        t_on = min(w0 + TRACE_START_S, w1 - 1.0)
        t_off = min(t_on + TRACE_SLICE_S, w1 - 0.5)
        tracing = False
        k = 0
        while True:
            now = time.monotonic()
            if now >= w1 - 0.2:
                break
            if on_chip and not tracing and t_on <= now < t_off:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0  # the tracer must not slow the loop it watches
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                tracing = True
            if tracing and now >= t_off:
                jax.profiler.stop_trace()
                tracing = False
                t_on = float("inf")
            if now >= w0 + k:
                gauge_scrapes.append(self._scrape())
                k += 1
            nxt = min(w0 + k, t_off if tracing else t_on, w1 - 0.2)
            self._sleep_until(max(nxt, now + 0.01))
        if tracing:
            jax.profiler.stop_trace()

    # -- metrics
    @staticmethod
    def _quantiles(xs) -> dict | None:
        from lib.stats import percentile

        if not xs:
            return None
        return {f"p{q}": percentile(xs, q) * 1e3 for q in (50, 90, 95, 99)} | {"n": len(xs)}

    def _end_to_end(self, s: dict, setup_s: float) -> dict:
        from lib.stats import percentile

        values = {"setup_s": setup_s}
        if s["ttft_s"]:
            values["ttft_p50_ms"] = percentile(s["ttft_s"], 50) * 1e3
            values["ttft_p95_ms"] = percentile(s["ttft_s"], 95) * 1e3
        if s["tpot_s"]:
            values["tpot_p95_ms"] = percentile(s["tpot_s"], 95) * 1e3
        if s["tokens_in_window"]:
            values["output_tok_s"] = s["tokens_in_window"] / s["window_s"]
        return {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in self.spec["end_to_end"] if m["name"] in values
        }

    def _per_layer(self, ctx: dict) -> dict:
        out = {}
        for m in self.spec["per_layer"]:
            if m["source"] == "device_trace" and not ctx["trace"]:
                continue  # a CPU rehearsal prints no device metric
            reader = load_json(HERE, "layer_metrics", f"{m['name']}.json")["reader"]
            value = importlib.import_module(f"readers.{reader['kind']}").read(reader, ctx)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out


# ------------------------------------------------------------------ main
def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--detail-out", default="",
                    help="append the result with its diagnostics to this JSONL file")
    ap.add_argument("--describe-trace", default="",
                    help="also write the trace's planes, lines and names here")
    args = ap.parse_args(argv)

    sup, server_error = serve(load_cell(args.workload), args)
    if server_error or sup.error or sup.result is None:
        say(f"no result: server={server_error!r} supervisor={sup.error!r}")
        return 1
    sys.stdout.flush()
    print(json.dumps(sup.result), flush=True)
    return 0


def serve(spec: dict, args, supervisor=Supervisor, warm: bool = True):
    """Start the cell's server in this thread and ``supervisor`` beside
    it; returns (the supervisor, what ended the server early or None)
    when both have ended. ``warm=False`` leaves the lattice unbuilt (for
    a supervisor that only asks probes: rehearse/check_seeds.py)."""
    import comparisons

    # Before the engine is built: a name that finds no file costs no chip time.
    spec["comparison"] = comparisons.resolve(spec["config"])
    import dynamo_exp_tpu.run as server  # fails here outside a checkout

    model_dir = prepare_model_dir(spec)
    port = free_port()
    sup = supervisor(spec, args, f"http://127.0.0.1:{port}", model_dir)

    build = server.build_tpu_engine

    def build_and_warm(opts):
        # The one seam into the program: the engine the server built, for
        # the device check, the lattice warm-up and the reference's weights.
        sup.device = check_devices(spec)
        engine, mdc = build(opts)
        sup.engine = engine
        sup.warm = warm_lattice(engine) if warm else {}
        return engine, mdc

    server.build_tpu_engine = build_and_warm
    sup.thread.start()
    server_error = None
    try:
        server.main([
            "in=http", "out=tpu", "--model-path", model_dir, "--model-name", MODEL_NAME,
            "--random-weights", "--http-host", "127.0.0.1", "--http-port", str(port),
            *spec["config"]["serve_args"],
        ])
    except (SystemExit, BenchFailure) as e:
        server_error = str(e) or repr(e)
    finally:
        sup.server_exited.set()
    sup.thread.join(timeout=120)
    return sup, server_error


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)  # the server's worker threads must not hold the exit
