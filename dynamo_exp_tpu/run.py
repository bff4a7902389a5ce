"""``dynamo-run`` equivalent: one CLI for every node shape.

Capability parity with ``/root/reference/launch/dynamo-run/``
(``src/lib.rs:57-404``, ``opt.rs``, ``input/*.rs``):

    python -m dynamo_exp_tpu.run in=<INPUT> out=<OUTPUT> [flags]

INPUT:  http | text | stdin | batch:<prompts.jsonl> | dyn://ns.comp.ep
OUTPUT: tpu | echo_core | echo_full | dyn://ns.comp.ep

Node shapes this builds (reference call stack §3.1/§3.2):
- ``in=http out=tpu``      single-process OpenAI serve on the local TPU
- ``in=http out=dyn://…``  ingress: HTTP + preprocessor + router to workers
                           (with --model-path: static chain; without:
                           dynamic model discovery via the coordinator)
- ``in=dyn://… out=tpu``   worker: engine behind a discoverable endpoint,
                           publishes model card + KV events + load metrics
- ``in=text|stdin|batch:…`` local drivers for smoke tests and batch runs

Router modes (``--router-mode``): random | round-robin | kv.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import json
import logging
import os
import sys
import time

logger = logging.getLogger("dynamo_exp_tpu.run")


# ----------------------------------------------------------------- arguments
def parse_args(argv: list[str]):
    io = {"in": "text", "out": "echo_full"}
    rest = []
    for a in argv:
        if a.startswith("in=") or a.startswith("out="):
            k, _, v = a.partition("=")
            io[k] = v
        else:
            rest.append(a)
    p = argparse.ArgumentParser(prog="dynamo_exp_tpu.run", description=__doc__)
    p.add_argument("--model-path", default="", help="HF-style model directory")
    p.add_argument("--model-name", default="", help="served model name")
    p.add_argument("--preset", default="", help="built-in model preset (random weights)")
    p.add_argument("--random-weights", action="store_true",
                   help="random-init instead of loading safetensors")
    p.add_argument("--http-host", default="0.0.0.0")
    p.add_argument("--http-port", type=int, default=8080)
    p.add_argument("--coordinator", default=os.environ.get("DYN_COORDINATOR", ""),
                   help="control-plane address host:port (enables dynamic mode)")
    p.add_argument("--router-mode", default="random",
                   choices=["random", "round-robin", "kv"])
    # Engine shape (reference: --tensor-parallel-size etc., flags.rs:26-238).
    p.add_argument("--tensor-parallel-size", "--tp", dest="tp", type=int, default=1)
    p.add_argument("--max-decode-slots", type=int, default=16)
    p.add_argument("--page-size", type=int, default=16)
    p.add_argument("--num-pages", type=int, default=0, help="0 = auto")
    p.add_argument("--max-model-len", type=int, default=2048)
    p.add_argument("--host-cache-pages", type=int, default=0)
    p.add_argument("--kv-dtype", default="bfloat16", choices=["bfloat16", "float32"])
    p.add_argument("--max-tokens", type=int, default=256, help="default completion cap")
    # Speculative decoding (docs/speculative.md): multi-token-per-
    # dispatch decode with a deterministic draft/verify pass — output
    # streams stay token-identical to the non-speculative run.
    p.add_argument("--spec", default="off",
                   help="speculative decoding drafter: off | ngram "
                        "(prompt-lookup, no second model) | any "
                        "registered drafter name")
    p.add_argument("--spec-draft-len", type=int, default=4,
                   help="initial draft length per row (adapted per row "
                        "from the rolling acceptance rate)")
    p.add_argument("--spec-max-draft", type=int, default=8,
                   help="upper bound the adaptive controller may grow a "
                        "row's draft length to")
    p.add_argument("--spec-ngram", type=int, default=3,
                   help="widest trailing n-gram the prompt-lookup "
                        "drafter matches")
    # AOT warm boot (docs/aot.md): precompile/load the engine's whole
    # compile lattice BEFORE the endpoint registers, so the first
    # request of every shape is steady-state fast. With a populated
    # persistent compilation cache (llmctl aot compile) the compiles
    # are deserializations and scale-up collapses to program-load time.
    p.add_argument("--prewarm", action="store_true",
                   help="prewarm the engine's compile lattice before "
                        "serving (docs/aot.md warm boot)")
    p.add_argument("--compile-cache-dir", default="",
                   help="JAX persistent compilation cache directory; "
                        "ignored when $JAX_COMPILATION_CACHE_DIR is set "
                        "(default: <checkout>/.jax_cache)")
    p.add_argument("--echo-token-delay-ms", type=float, default=0.0)
    p.add_argument("--request-template", default="",
                   help="JSON file of request defaults (model/temperature/"
                        "max_completion_tokens), reference request_template.rs")
    # Overload protection at the HTTP edge (docs/fault_tolerance.md
    # "Overload protection"): bounded in-flight work, priority-aware
    # shedding (429 + Retry-After), hard-cap 503.
    p.add_argument("--max-inflight", type=int, default=0,
                   help="hard cap on concurrently admitted HTTP requests "
                        "(503 above it); 0 disables admission control")
    p.add_argument("--shed-watermark", type=int, default=0,
                   help="in-flight level where low-priority requests start "
                        "shedding with 429 (default: 3/4 of --max-inflight)")
    # SLO attribution (docs/observability.md "SLO attribution &
    # goodput"): per-request TTFT/ITL measured at the HTTP edge against
    # these targets -> dynamo_slo_violations_total{slo,priority} /
    # dynamo_goodput_requests_total{priority}; the SLO planner's
    # pressure inputs read the same window.
    p.add_argument("--slo-ttft-ms", type=float, default=0.0,
                   help="time-to-first-token SLO target in ms (0 = not "
                        "an SLO; still unmeasured unless --slo-itl-ms set)")
    p.add_argument("--slo-itl-ms", type=float, default=0.0,
                   help="inter-token-latency SLO target in ms (0 = not "
                        "an SLO)")
    p.add_argument("--profiler-port", type=int, default=0,
                   help="expose the jax.profiler gRPC server on this port "
                        "(attach with tensorboard/xprof); 0 = off")
    p.add_argument("--trace-file", default="",
                   help="record request span telemetry to exactly this JSONL "
                        "file (reconstruct with `llmctl trace <id>`). "
                        "DYN_TRACE_FILE does the same but records to "
                        "<path>.pid<pid>, safe for multi-process graphs")
    # Multi-host engine (reference: MultiNodeConfig, engines.rs:41-50 +
    # ray.rs leader/follower join): every node runs this CLI with the
    # same flags plus its own --node-rank; rank 0 is the leader.
    p.add_argument("--num-nodes", type=int, default=1,
                   help="hosts in the engine's global JAX runtime")
    p.add_argument("--node-rank", type=int, default=0,
                   help="this host's rank (0 = leader)")
    p.add_argument("--dist-leader", default="",
                   help="rank-0 host:port for jax.distributed; empty = "
                        "leader self-derives and publishes via --coordinator")
    p.add_argument("--dist-port", type=int, default=9911,
                   help="port the leader binds for jax.distributed")
    p.add_argument("--deployment", default="default",
                   help="namespaces the published leader address so two "
                        "multi-node graphs on one coordinator don't read "
                        "each other's")
    opts = p.parse_args(rest)
    opts.input, opts.output = io["in"], io["out"]
    return opts


ROUTER_MODES = {"random": "RANDOM", "round-robin": "ROUND_ROBIN", "kv": "KV"}


def router_mode(opts):
    from .runtime.push_router import RouterMode

    return RouterMode[ROUTER_MODES[opts.router_mode]]


# ------------------------------------------------------------------- engines
def build_tpu_engine(opts):
    """Construct the TPU engine (and MDC when a model dir is given)."""
    from .engine import EngineConfig, TPUEngine
    from .model_card import ModelDeploymentCard
    from .models import PRESETS, ModelConfig

    mdc = None
    params = None
    if opts.model_path:
        from .models.hub import resolve_model_path

        opts.model_path = resolve_model_path(opts.model_path)
        if opts.model_path.endswith(".gguf"):
            from .models.gguf import (
                GGUFFile,
                config_from_gguf,
                load_params_from_gguf,
            )

            # Parse the metadata section once; a real vocab is ~100k+
            # strings and re-parsing per consumer wastes startup time.
            gguf = GGUFFile.parse(opts.model_path)
            if opts.random_weights:
                mcfg = config_from_gguf(gguf)
            else:
                params, mcfg = load_params_from_gguf(opts.model_path, gguf=gguf)
            # Self-contained GGUF: tokenizer + chat template come from
            # the file's own metadata, so the OpenAI surface serves with
            # no side tokenizer.json (gguf_tokenizer.rs parity). A GGUF
            # without an embedded tokenizer serves token-level only.
            if "tokenizer.ggml.tokens" in gguf.metadata:
                mdc = ModelDeploymentCard.from_gguf(
                    opts.model_path, opts.model_name or None, gguf=gguf
                )
                mdc.kv_cache_block_size = opts.page_size
            else:
                logger.warning(
                    "GGUF has no embedded tokenizer; serving token-level only"
                )
        else:
            mcfg = ModelConfig.from_pretrained(opts.model_path)
            mdc = ModelDeploymentCard.from_local_path(
                opts.model_path, opts.model_name or None
            )
            mdc.kv_cache_block_size = opts.page_size
            has_weights = any(
                f.endswith(".safetensors")
                for f in os.listdir(opts.model_path)
            )
            if opts.random_weights:
                pass  # explicit opt-in: serve random weights (tests, smoke)
            elif has_weights:
                from .models.loader import load_params

                params, mcfg = load_params(opts.model_path, mcfg)
            else:
                # Never silently serve garbage under a real model's name.
                raise SystemExit(
                    f"no .safetensors weights in {opts.model_path}; "
                    "pass --random-weights to serve a random-initialized "
                    "model"
                )
    elif opts.preset:
        mcfg = PRESETS[opts.preset]
    else:
        raise SystemExit("out=tpu needs --model-path or --preset")

    max_len = min(opts.max_model_len, mcfg.max_position_embeddings)
    num_pages = opts.num_pages or (
        opts.max_decode_slots * (max_len // opts.page_size + 1) + 64
    )
    ecfg = EngineConfig(
        model=mcfg,
        max_decode_slots=opts.max_decode_slots,
        page_size=opts.page_size,
        num_pages=num_pages,
        max_model_len=max_len,
        tp=opts.tp,
        eos_token_ids=list(mdc.eos_token_ids) if mdc else [],
        kv_dtype=opts.kv_dtype,
        host_cache_pages=opts.host_cache_pages,
        default_max_tokens=opts.max_tokens,
        # getattr: callers besides the CLI drive this builder with
        # duck-typed opts objects (examples/llm TpuWorker) that predate
        # speculation; absent attributes mean the defaults.
        spec_mode=getattr(opts, "spec", "off"),
        spec_draft_len=getattr(opts, "spec_draft_len", 4),
        spec_max_draft=getattr(opts, "spec_max_draft", 8),
        spec_ngram=getattr(opts, "spec_ngram", 3),
    )
    from .aot import enable_persistent_cache

    cache_dir = enable_persistent_cache(getattr(opts, "compile_cache_dir", ""))
    logger.info("compile cache: %s", cache_dir)
    engine = TPUEngine(ecfg, params=params)
    if getattr(opts, "prewarm", False):
        # Warm boot (docs/aot.md): the lattice compiles/loads NOW, not
        # under first traffic — with a populated cache this is seconds,
        # and the compile-miss counters stay flat from the first
        # dispatch.
        report = engine.prewarm()
        logger.info(
            "prewarmed %d variants in %.2fs (manifest %s)",
            report.variants, report.seconds, report.manifest_hash[:12],
        )
    return engine, mdc


def build_output(opts, drt):
    """Resolve out=… to (core_engine, full_engine, mdc, tpu_engine)."""
    from .engines.echo import EchoEngineCore, EchoEngineFull

    if opts.output == "echo_core":
        return EchoEngineCore(opts.echo_token_delay_ms), None, None, None
    if opts.output == "echo_full":
        return None, EchoEngineFull(opts.echo_token_delay_ms), None, None
    if opts.output == "tpu":
        engine, mdc = build_tpu_engine(opts)
        return engine, None, mdc, engine
    if opts.output.startswith("dyn://"):
        return None, None, None, None  # resolved by the input builder
    raise SystemExit(f"unknown out={opts.output!r}")


async def remote_core(opts, drt, block_size: int):
    """out=dyn://… : a core-engine seam over the request plane.

    Returns (engine, kv_router_or_None); the caller stops the router."""
    from .kv_router.router import build_routed_core
    from .runtime.transports.base import EndpointAddress

    addr = EndpointAddress.from_url(opts.output)
    ep = drt.namespace(addr.namespace).component(addr.component).endpoint(addr.name)
    return await build_routed_core(ep, router_mode(opts), block_size)


def require_mdc(opts):
    from .model_card import ModelDeploymentCard
    from .models.hub import resolve_model_path

    if not opts.model_path:
        raise SystemExit(f"in={opts.input} with out={opts.output} needs --model-path")
    opts.model_path = resolve_model_path(opts.model_path)
    if opts.model_path.endswith(".gguf"):
        from .models.gguf import GGUFFile

        g = GGUFFile.parse(opts.model_path)
        if "tokenizer.ggml.tokens" not in g.metadata:
            raise SystemExit(
                "this node shape needs a tokenizer/chat template and this "
                "GGUF has no embedded tokenizer (tokenizer.ggml.*) — pass "
                "an HF-style --model-path dir or a self-contained GGUF"
            )
        mdc = ModelDeploymentCard.from_gguf(
            opts.model_path, opts.model_name or None, gguf=g
        )
        mdc.kv_cache_block_size = opts.page_size
        return mdc
    mdc = ModelDeploymentCard.from_local_path(opts.model_path, opts.model_name or None)
    mdc.kv_cache_block_size = opts.page_size
    return mdc


async def resolve_openai_engine(opts, drt, core, full, mdc):
    """One place that turns (out=…, --model-path) into an OpenAI-level
    engine. Returns (engine, mdc, kv_router_or_None); the caller stops
    the router on shutdown."""
    from .http import build_pipeline_engine

    kv_router = None
    if opts.output.startswith("dyn://"):
        mdc = require_mdc(opts)
        core, kv_router = await remote_core(opts, drt, mdc.kv_cache_block_size)
    if core is not None:
        if mdc is None:
            mdc = require_mdc(opts)  # core engines need tokenizer/template
        return build_pipeline_engine(mdc, core), mdc, kv_router
    return full, mdc, kv_router


# -------------------------------------------------------------------- inputs
ENGINE_LOOP_CRASHED = "engine loop crashed"


async def run_http(opts, drt, core, full, mdc, tpu_engine=None):
    """OpenAI ingress (reference: input/http.rs + http/service)."""
    from .http import HttpService
    from .http.discovery import ModelWatcher

    template = None
    if opts.request_template:
        from .protocols.request_template import RequestTemplate

        template = RequestTemplate.load(opts.request_template)
    admission = None
    if opts.max_inflight > 0:
        from .http import AdmissionController

        admission = AdmissionController(
            max_inflight=opts.max_inflight,
            shed_watermark=opts.shed_watermark or None,
        )
    slo = None
    if opts.slo_ttft_ms > 0 or opts.slo_itl_ms > 0:
        from .telemetry import SloAttribution, SloConfig, get_telemetry

        slo = SloAttribution(
            SloConfig(
                ttft_s=opts.slo_ttft_ms / 1e3 if opts.slo_ttft_ms > 0 else None,
                itl_s=opts.slo_itl_ms / 1e3 if opts.slo_itl_ms > 0 else None,
            ),
            get_telemetry(),
        )
    svc = HttpService(
        host=opts.http_host,
        port=opts.http_port,
        request_template=template,
        admission=admission,
        slo=slo,
        unhealthy=(
            (lambda: ENGINE_LOOP_CRASHED if tpu_engine.loop_crashed else None)
            if tpu_engine is not None
            else None
        ),
    )
    watcher = None
    kv_router = None
    if opts.output.startswith("dyn://") and not opts.model_path:
        # Dynamic: models appear/disappear with workers (discovery.rs).
        watcher = ModelWatcher(drt, svc.manager, router_mode(opts))
        await watcher.start()
    else:
        engine, mdc, kv_router = await resolve_openai_engine(
            opts, drt, core, full, mdc
        )
        name = (mdc.display_name if mdc else "") or opts.model_name or "default"
        svc.manager.add_chat_model(name, engine)
        svc.manager.add_completion_model(name, engine)
    port = await svc.start()
    print(f"listening on http://{opts.http_host}:{port}", flush=True)
    try:
        await drt.runtime.primary_token.cancelled()
    finally:
        if watcher:
            await watcher.close()
        if kv_router is not None:
            await kv_router.stop()
        await svc.stop()


def tokenizer_registrable(model_path: str) -> bool:
    """Can an ingress build a preprocessor chain from this model dir?

    Probe for actual tokenizer artifacts instead of assuming — a
    weights-only dir registered with ingress would strand it in a
    rebuild loop. Beyond the fast/SentencePiece artifacts, GPT-2-style
    dirs ship ``vocab.json`` + ``merges.txt``; anything else gets one
    real load attempt (the transformers fallback) so exotic-but-loadable
    layouts still register.
    """
    if any(
        os.path.exists(os.path.join(model_path, name))
        for name in ("tokenizer.json", "tokenizer.model")
    ):
        return True
    if all(
        os.path.exists(os.path.join(model_path, name))
        for name in ("vocab.json", "merges.txt")
    ):
        return True
    from .tokenizer import Tokenizer

    try:
        Tokenizer.from_pretrained(model_path)
        return True
    except Exception:  # noqa: BLE001 - genuinely tokenizer-less
        return False


async def run_worker(opts, drt, core, tpu_engine, mdc=None):
    """Worker node: serve the core engine on a discoverable endpoint
    (reference: EngineConfig::StaticCore + Ingress, lib.rs:200-300)."""
    from .kv_router.publisher import KvEventPublisher, KvMetricsPublisher
    from .local_model import register_llm
    from .runtime.component import annotated_stream
    from .runtime.engine import AsyncEngineContext
    from .runtime.transports.base import EndpointAddress

    addr = EndpointAddress.from_url(opts.input)
    ep = drt.namespace(addr.namespace).component(addr.component).endpoint(addr.name)

    async def handler(request: dict, context: AsyncEngineContext):
        async for frame in annotated_stream(core, request, context):
            yield frame

    metrics_pub = KvMetricsPublisher()
    # Spot-reclamation plane (docs/fault_tolerance.md "Spot reclamation
    # & live migration"): advertise the metadata survivors select on —
    # this worker's telemetry/ledger name, its topology coordinate, and
    # (when the engine can park migrated prefixes) a live KV-migration
    # landing address — then arm the triage controller below.
    from .parallel.multihost import TOPOLOGY_KEY, TopologyCoordinate
    from .runtime.reclaim import (
        MigrationSink,
        ReclaimController,
        install_sigterm_reclaim,
        survivors_from_instances,
    )
    from .telemetry import get_telemetry

    topo = TopologyCoordinate.from_env()
    metadata: dict = {"instance": get_telemetry().instance}
    if topo is not None:
        metadata[TOPOLOGY_KEY] = topo.encode()
    migrate_rx = None
    migrate_sink = None
    if tpu_engine is not None and tpu_engine.cfg.model.has_seq_state:
        # Migration ships pages alone (engine/config.py::
        # refuse_seq_state_tiers): this worker serves, and a reclaim
        # notice drains it like any SIGTERM.
        logger.warning(
            "model_type %s keeps per-sequence state beside its KV pages: "
            "live migration is not armed for this worker",
            tpu_engine.cfg.model.model_type,
        )
        migrates = False
    else:
        migrates = tpu_engine is not None
    if migrates and tpu_engine.kv.sharing:
        from .disagg.transfer import KvPageReceiver

        migrate_rx = KvPageReceiver()
        await migrate_rx.start()
        migrate_sink = MigrationSink(tpu_engine, migrate_rx)
        metadata["migrate_addr"] = migrate_rx.address
    served = await ep.serve_endpoint(
        handler, stats_handler=metrics_pub.stats_handler, metadata=metadata
    )
    if migrates:

        async def _survivors():
            infos = await drt.discovery.list_instances(ep.component.path)
            return survivors_from_instances(infos, served.instance_id)

        ReclaimController(
            tpu_engine, topology=topo, survivors_fn=_survivors
        ).attach(served)
        # SIGTERM == the spot platform's reclaim notice: triage the
        # in-flight KV within the grace window, then fall through to
        # the graceful drain this handler displaced (cancel the main
        # task, exactly what run_main's own SIGTERM handler did).
        install_sigterm_reclaim(served, then=asyncio.current_task().cancel)

    if tpu_engine is not None:
        # KV events -> router index, attributed to this instance.
        kv_pub = KvEventPublisher(
            drt.event_plane,
            ep.component.path,
            served.instance_id,
            loop=asyncio.get_running_loop(),
        )
        tpu_engine.kv.event_cb = kv_pub.engine_callback()

        async def pump_metrics():
            from .kv_router.protocols import ForwardPassMetrics

            while True:
                await asyncio.sleep(0.5)
                metrics_pub.update(ForwardPassMetrics.from_dict(tpu_engine.metrics()))

        drt.runtime.spawn(pump_metrics())
    if opts.model_path:
        # A tokenizer-less artifact (weights-only GGUF) must not be
        # advertised to OpenAI ingress: the frontend would loop forever
        # failing to build a preprocessor chain from its card. Model
        # dirs always carry a tokenizer; GGUFs only sometimes do (when
        # the tpu engine built an mdc we already know the answer).
        if mdc is not None:
            registrable = True
        elif opts.model_path.endswith(".gguf"):
            if opts.output == "tpu":
                # build_tpu_engine already parsed this GGUF: mdc is None
                # exactly because it has no embedded tokenizer — don't
                # re-parse a multi-GB file to re-derive that.
                registrable = False
            else:
                from .models.gguf import GGUFFile

                registrable = (
                    "tokenizer.ggml.tokens"
                    in GGUFFile.parse(opts.model_path).metadata
                )
        else:
            # Off the event loop: the probe's fallback may run a full
            # tokenizer load (transformers import) and must not stall
            # this process's coordinator read loop and heartbeats.
            registrable = await asyncio.to_thread(
                tokenizer_registrable, opts.model_path
            )
        if registrable:
            await register_llm(
                drt, ep, opts.model_path, opts.model_name or None,
                kv_cache_block_size=opts.page_size,
            )
        else:
            logger.warning(
                "not registering %s with ingress: no tokenizer available "
                "(token-level clients can still target this endpoint "
                "directly)",
                opts.model_path,
            )
    print(f"worker serving {opts.input} (instance {served.instance_id})", flush=True)
    try:
        await drt.runtime.primary_token.cancelled()
    finally:
        # Bounded: an unresponsive coordinator must not wedge shutdown
        # (the lease expiring cleans up registrations anyway).
        t0 = time.monotonic()
        try:
            await asyncio.wait_for(served.close(), 15)
        except asyncio.TimeoutError:
            logger.warning("endpoint close timed out after 15s")
        if migrate_sink is not None:
            migrate_sink.close()
        if migrate_rx is not None:
            await migrate_rx.close()
        logger.info("endpoint closed in %.2fs", time.monotonic() - t0)


def _chat_payload(model: str, prompt: str, opts) -> dict:
    return {
        "model": model,
        "messages": [{"role": "user", "content": prompt}],
        "stream": True,
        "max_tokens": opts.max_tokens,
    }


async def _stream_chat(engine, payload, out=sys.stdout):
    from .runtime.engine import AsyncEngineContext

    n_tokens = 0
    first = None
    t0 = time.monotonic()
    stream = await engine.generate(payload, AsyncEngineContext())
    async for item in stream:
        chunk = item if isinstance(item, dict) else item.model_dump()
        for choice in chunk.get("choices", []):
            text = (choice.get("delta") or {}).get("content")
            if text:
                if first is None:
                    first = time.monotonic() - t0
                n_tokens += 1
                out.write(text)
                out.flush()
    return n_tokens, first, time.monotonic() - t0


async def run_text(opts, drt, engine, mdc):
    """Interactive chat REPL (reference: input/text.rs).

    stdin is read on a dedicated *daemon* thread: the default executor's
    threads are non-daemon and joined at interpreter exit, so a thread
    blocked in input() would keep the process alive after Ctrl-C."""
    import threading

    name = (mdc.display_name if mdc else "") or "default"
    loop = asyncio.get_running_loop()
    lines: asyncio.Queue = asyncio.Queue()

    def _reader():
        while True:
            try:
                line = input("> ")
            except EOFError:
                loop.call_soon_threadsafe(lines.put_nowait, None)
                return
            loop.call_soon_threadsafe(lines.put_nowait, line)

    threading.Thread(target=_reader, name="stdin-reader", daemon=True).start()
    print("Ctrl-D to exit.", flush=True)
    while True:
        prompt = await lines.get()
        if prompt is None:
            return
        await _stream_chat(engine, _chat_payload(name, prompt, opts))
        print(flush=True)


async def run_stdin(opts, drt, engine, mdc):
    """One prompt per stdin line, streamed to stdout."""
    name = (mdc.display_name if mdc else "") or "default"
    for line in sys.stdin:
        prompt = line.rstrip("\n")
        if not prompt:
            continue
        await _stream_chat(engine, _chat_payload(name, prompt, opts))
        print(flush=True)


async def run_batch(opts, drt, engine, mdc, path: str):
    """JSONL prompts, concurrent, tok/s stats (reference: input/batch.rs)."""
    name = (mdc.display_name if mdc else "") or "default"
    prompts = []
    with open(path) as f:
        for line in f:
            if line.strip():
                d = json.loads(line)
                prompts.append(d.get("text") or d.get("prompt") or "")

    class _Null:
        def write(self, s):  # batch mode: tokens counted, not printed
            pass

        def flush(self):
            pass

    t0 = time.monotonic()
    results = await asyncio.gather(
        *[
            _stream_chat(engine, _chat_payload(name, p, opts), out=_Null())
            for p in prompts
        ]
    )
    wall = time.monotonic() - t0
    total = sum(r[0] for r in results)
    ttfts = sorted(r[1] for r in results if r[1] is not None)
    stats = {
        "requests": len(prompts),
        "output_tokens": total,
        "wall_s": round(wall, 3),
        "output_tok_s": round(total / wall, 2) if wall else 0.0,
        "ttft_p50_ms": round(ttfts[len(ttfts) // 2] * 1000, 1) if ttfts else None,
    }
    print(json.dumps(stats), flush=True)


# --------------------------------------------------------------------- main
async def main_async(opts) -> None:

    from .runtime.component import DistributedRuntime
    from .runtime.config import RuntimeConfig

    if opts.profiler_port:
        from .runtime.profiler import start_profiler_server

        start_profiler_server(opts.profiler_port)

    if opts.trace_file:
        from .telemetry import get_telemetry

        get_telemetry().configure(opts.trace_file)

    needs_cluster = opts.input.startswith("dyn://") or opts.output.startswith("dyn://")
    if needs_cluster and not opts.coordinator:
        raise SystemExit("dyn:// endpoints need --coordinator (or DYN_COORDINATOR)")
    cfg = RuntimeConfig.from_settings()
    if opts.coordinator:
        cfg.coordinator_endpoint = opts.coordinator
    drt = DistributedRuntime(config=cfg)

    if opts.num_nodes > 1:
        # Join the global JAX runtime before any engine touches a
        # device: after this, jax.devices() spans every node.
        from .parallel.multihost import MultiNodeConfig, bringup

        await bringup(
            MultiNodeConfig(
                num_nodes=opts.num_nodes,
                node_rank=opts.node_rank,
                leader_addr=opts.dist_leader or None,
                dist_port=opts.dist_port,
                deployment=opts.deployment,
            ),
            discovery=drt.discovery if opts.coordinator else None,
        )

    core, full, mdc, tpu_engine = build_output(opts, drt)
    try:
        if opts.input == "http":
            await run_http(opts, drt, core, full, mdc, tpu_engine)
            return
        if opts.input.startswith("dyn://"):
            if core is None:
                raise SystemExit("in=dyn:// needs a local engine (out=tpu|echo_core)")
            await run_worker(opts, drt, core, tpu_engine, mdc)
            return
        # Local text-ish drivers need an OpenAI-level engine.
        engine, mdc, kv_router = await resolve_openai_engine(
            opts, drt, core, full, mdc
        )
        try:
            if opts.input == "text":
                await run_text(opts, drt, engine, mdc)
            elif opts.input == "stdin":
                await run_stdin(opts, drt, engine, mdc)
            elif opts.input.startswith("batch:"):
                await run_batch(opts, drt, engine, mdc, opts.input[len("batch:") :])
            else:
                raise SystemExit(f"unknown in={opts.input!r}")
        finally:
            if kv_router is not None:
                await kv_router.stop()
    finally:
        if tpu_engine is not None:
            t0 = time.monotonic()
            tpu_engine.stop()
            logger.info("engine stopped in %.2fs", time.monotonic() - t0)
        t0 = time.monotonic()
        try:
            await asyncio.wait_for(drt.close(), 15)
        except asyncio.TimeoutError:
            logger.warning("runtime close timed out after 15s")
        logger.info("runtime closed in %.2fs", time.monotonic() - t0)
        if tpu_engine is not None and tpu_engine.loop_crashed:
            # A node whose engine loop died must not look like a clean
            # shutdown, whatever ended it (SIGTERM included).
            raise SystemExit(f"{ENGINE_LOOP_CRASHED}; exiting non-zero")


def main(argv: list[str] | None = None) -> None:
    # DYN_LOG level + DYN_LOGGING_JSONL format; JSONL lines carry the
    # current request's trace_id (telemetry log correlation).
    from .runtime.logging import configure_logging

    configure_logging()
    opts = parse_args(argv if argv is not None else sys.argv[1:])
    loop = asyncio.new_event_loop()
    main_task = loop.create_task(main_async(opts))
    # SIGINT/SIGTERM -> cancel -> graceful drain (reference worker.rs).
    import signal

    def _cancel(*_):
        main_task.cancel()

    for sig in (signal.SIGINT, signal.SIGTERM):
        with contextlib.suppress(NotImplementedError, ValueError):
            loop.add_signal_handler(sig, _cancel)

    def _dump_tasks(*_):
        """SIGUSR1: print every pending task's stack — the first tool to
        reach for when a node wedges during drain — and dump every live
        engine's flight recorder ring (telemetry/flight.py; render with
        ``llmctl flight <file>``)."""
        import faulthandler

        from .telemetry import dump_all

        n = dump_all("sigusr1")
        if n:
            print(
                f"==== SIGUSR1 flight dump ({n} engine(s)) ====",
                file=sys.stderr, flush=True,
            )
        print("==== SIGUSR1 task dump ====", file=sys.stderr, flush=True)
        for t in asyncio.all_tasks(loop):
            print(f"-- {t.get_name()}: {t.get_coro()}", file=sys.stderr)
            for f in t.get_stack(limit=6):
                print(
                    f"     {f.f_code.co_filename}:{f.f_lineno} "
                    f"{f.f_code.co_name}",
                    file=sys.stderr,
                )
        faulthandler.dump_traceback(file=sys.stderr)
        sys.stderr.flush()

    with contextlib.suppress(NotImplementedError, ValueError, AttributeError):
        loop.add_signal_handler(signal.SIGUSR1, _dump_tasks)
    try:
        loop.run_until_complete(main_task)
    except asyncio.CancelledError:
        pass
    finally:
        loop.close()


if __name__ == "__main__":
    main()
