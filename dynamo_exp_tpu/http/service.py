"""OpenAI-compatible HTTP ingress (aiohttp).

Capability parity with ``/root/reference/lib/llm/src/http/service/``:
``/v1/chat/completions``, ``/v1/completions``, ``/v1/models``, ``/metrics``,
``/health``; always streams from the engine, aggregates for
``stream=false``; per-model engine registry with dynamic attach/detach;
client disconnect kills the request context.
"""

from __future__ import annotations

import asyncio
import logging
import time
from typing import Any

from aiohttp import web

from ..protocols.aggregator import aggregate_chat_stream, aggregate_completion_stream
from ..protocols.openai import (
    ChatCompletionChunk,
    ChatCompletionRequest,
    CompletionChunk,
    CompletionRequest,
    ModelInfo,
    ModelList,
)
from ..preprocessor.preprocessor import InvalidRequestError, PromptTooLongError
from ..protocols.sse import encode_done, encode_frame
from ..runtime.annotated import Annotated
from ..runtime.engine import AsyncEngine, AsyncEngineContext, DeadlineExceededError
from ..runtime.push_router import NoInstancesError, RecoveryExhaustedError
from ..telemetry import get_telemetry, span
from ..telemetry.dispatch import http_chunk_clock
from .admission import AdmissionController, RequestShedError, parse_priority
from .metrics import CONTENT_TYPE_LATEST, ServiceMetrics

# Clients hint how soon to retry a 503 (no instances / breaker open):
# instance churn resolves within a lease TTL or breaker cooldown.
RETRY_AFTER_S = "1"

logger = logging.getLogger(__name__)


class ModelManager:
    """Per-model engine registry with dynamic attach/detach."""

    def __init__(self):
        self._chat: dict[str, AsyncEngine] = {}
        self._completion: dict[str, AsyncEngine] = {}

    def add_chat_model(self, name: str, engine: AsyncEngine) -> None:
        self._chat[name] = engine

    def add_completion_model(self, name: str, engine: AsyncEngine) -> None:
        self._completion[name] = engine

    def remove_model(self, name: str) -> None:
        self._chat.pop(name, None)
        self._completion.pop(name, None)

    def remove_chat_model(self, name: str) -> None:
        self._chat.pop(name, None)

    def remove_completion_model(self, name: str) -> None:
        self._completion.pop(name, None)

    def chat_engine(self, name: str) -> AsyncEngine | None:
        return self._chat.get(name)

    def completion_engine(self, name: str) -> AsyncEngine | None:
        return self._completion.get(name)

    def model_names(self) -> list[str]:
        return sorted(set(self._chat) | set(self._completion))


class HttpService:
    def __init__(
        self,
        manager: ModelManager | None = None,
        metrics: ServiceMetrics | None = None,
        host: str = "0.0.0.0",
        port: int = 8080,
        request_template=None,
        admission: AdmissionController | None = None,
        slo=None,
        unhealthy=None,
    ):
        self.manager = manager or ModelManager()
        # ``unhealthy() -> str | None``: why the engine behind this
        # edge can no longer be trusted (run.py: its loop thread died),
        # or None. /health answers 503 with the reason.
        self.unhealthy = unhealthy
        self.metrics = metrics or ServiceMetrics()
        self.host = host
        self.port = port
        # Server-side defaults for sparse request bodies (reference:
        # request_template.rs applied in dynamo-run's HTTP input).
        self.request_template = request_template
        # Overload protection: bounded in-flight work with priority-aware
        # shedding (docs/fault_tolerance.md). None = accept unboundedly
        # (embedded/test deployments that bound load elsewhere).
        self.admission = admission
        # SLO attribution (docs/observability.md "SLO attribution &
        # goodput"): a telemetry.SloAttribution measuring per-request
        # TTFT/ITL at this edge against the configured targets — the
        # same code path the cluster simulator counts SimReport goodput
        # with, and the window the live SLO planner reads its pressure
        # inputs from. None = not measured.
        self.slo = slo
        self.app = web.Application()
        self.app.router.add_post("/v1/chat/completions", self._chat)
        self.app.router.add_post("/v1/completions", self._completions)
        self.app.router.add_get("/v1/models", self._models)
        self.app.router.add_get("/metrics", self._metrics)
        self.app.router.add_get("/health", self._health)
        self.app.router.add_get("/live", self._health)
        self._runner: web.AppRunner | None = None

    # --- lifecycle ----------------------------------------------------
    async def start(self) -> int:
        """Start serving; returns the bound port."""
        self._runner = web.AppRunner(self.app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port)
        await site.start()
        for s in self._runner.sites:
            sockets = getattr(s, "_server", None)
            if sockets and sockets.sockets:
                self.port = sockets.sockets[0].getsockname()[1]
        logger.info("HTTP service listening on %s:%d", self.host, self.port)
        return self.port

    async def stop(self) -> None:
        if self._runner is not None:
            await self._runner.cleanup()

    # --- handlers -----------------------------------------------------
    async def _health(self, request: web.Request) -> web.Response:
        reason = self.unhealthy() if self.unhealthy is not None else None
        if reason:
            return web.json_response(
                {"status": "unhealthy", "reason": reason}, status=503
            )
        return web.json_response(
            {"status": "healthy", "models": self.manager.model_names()}
        )

    async def _models(self, request: web.Request) -> web.Response:
        listing = ModelList(
            data=[ModelInfo(id=name) for name in self.manager.model_names()]
        )
        return web.json_response(listing.model_dump())

    async def _metrics(self, request: web.Request) -> web.Response:
        # ServiceMetrics.render() already merges the telemetry registry.
        return web.Response(
            body=self.metrics.render(), content_type="text/plain", charset="utf-8"
        )

    async def _chat(self, request: web.Request) -> web.StreamResponse:
        return await self._serve_llm(
            request,
            parse=ChatCompletionRequest.model_validate,
            lookup=self.manager.chat_engine,
            chunk_type=ChatCompletionChunk,
            aggregate=aggregate_chat_stream,
            endpoint="chat_completions",
        )

    async def _completions(self, request: web.Request) -> web.StreamResponse:
        return await self._serve_llm(
            request,
            parse=_parse_completion_request,
            lookup=self.manager.completion_engine,
            chunk_type=CompletionChunk,
            aggregate=aggregate_completion_stream,
            endpoint="completions",
            expand_batch=_expand_completion_batch,
        )

    async def _serve_llm(
        self,
        request: web.Request,
        parse,
        lookup,
        chunk_type,
        aggregate,
        endpoint: str,
        expand_batch=None,
    ) -> web.StreamResponse:
        try:
            payload = await request.json()
            if self.request_template is not None:
                payload = self.request_template.apply(payload)
            # End-to-end deadline: an explicit per-request budget via the
            # ``timeout_s`` body field or ``X-Request-Timeout-S`` header.
            # Popped before parsing so strict models don't reject it.
            timeout_s = _request_timeout_s(payload, request)
            req = parse(payload)
            priority = _request_priority(req, request)
            # Canonicalize into the forwarded payload: the engine's
            # preemption victim selection must see the same class the
            # edge admitted under — a header-only spelling would
            # otherwise never reach the preprocessor.
            if isinstance(payload, dict):
                payload["priority"] = priority
        except Exception as e:
            return _error_response(400, f"invalid request: {e}")
        engine = lookup(req.model)
        if engine is None:
            return _error_response(
                404, f"model {req.model!r} not found", err_type="model_not_found"
            )
        if self.admission is not None:
            # Overload protection: bounded in-flight work. Above the shed
            # watermark lower-priority classes get 429; at the hard cap
            # everything gets 503. Both carry Retry-After — the request
            # was fine, the service is busy.
            try:
                self.admission.acquire(priority)
            except RequestShedError as e:
                self.metrics.count_shed(req.model, endpoint, e.status)
                return _error_response(
                    e.status,
                    str(e),
                    err_type=(
                        "service_overloaded" if e.status == 503 else "request_shed"
                    ),
                    headers={"Retry-After": str(max(int(e.retry_after_s), 1))},
                )
        try:
            return await self._serve_admitted(
                request,
                req,
                engine,
                timeout_s,
                payload=payload,
                chunk_type=chunk_type,
                aggregate=aggregate,
                endpoint=endpoint,
                expand_batch=expand_batch,
                priority=priority,
            )
        finally:
            # Released only when the response is complete (the SSE stream
            # has drained) — in-flight covers the full request lifetime.
            if self.admission is not None:
                self.admission.release()

    async def _serve_admitted(
        self,
        request: web.Request,
        req,
        engine: AsyncEngine,
        timeout_s: float | None,
        *,
        payload,
        chunk_type,
        aggregate,
        endpoint: str,
        expand_batch,
        priority: int = 1,
    ) -> web.StreamResponse:
        # SLO attribution clock: TTFT/ITL are measured from request
        # arrival at this edge — the latency the client experiences,
        # which is what the targets are promises about.
        t_arrival = time.monotonic()
        # Per-request latency marks filled in by _typed_chunks below
        # (first/last content chunk, cumulative token watermark).
        lat = {"first": 0.0, "last": 0.0, "tokens": 0}
        # OpenAI allows a list of prompts in one completion request; fan the
        # batch out as independent sub-requests with re-indexed choices.
        sub_payloads = expand_batch(payload) if expand_batch else [payload]
        # One context per sub-request: a finished sub-stream must not stop
        # its batch siblings; disconnect kills them all.
        ctxs = [AsyncEngineContext() for _ in sub_payloads]
        if timeout_s is not None:
            for c in ctxs:
                c.start_timeout(timeout_s)
        ctx = _FanoutContext(ctxs)
        request_type = "stream" if req.stream else "unary"
        streaming = req.stream
        # Root span of the request's trace: everything below (preprocess,
        # routing, engine stages, KV transfer) parents onto this via the
        # trace contextvar, and log lines emitted during handling carry
        # its trace_id.
        with span(
            "http_request",
            request_id=ctx.id,
            model=req.model,
            endpoint=endpoint,
            request_type=request_type,
        ) as root, self.metrics.track(req.model, endpoint, request_type) as tracker:
            # Inside the trace context: this line (and everything logged
            # below it while handling) carries the trace_id in JSONL mode.
            logger.info(
                "request %s: model=%s endpoint=%s type=%s",
                ctx.id, req.model, endpoint, request_type,
            )
            try:
                streams = [
                    await engine.generate(p, c) for p, c in zip(sub_payloads, ctxs)
                ]
            except PromptTooLongError as e:
                tracker.status = "rejected"
                root.set(status="rejected")
                return _error_response(400, str(e), err_type="context_length_exceeded")
            except InvalidRequestError as e:
                tracker.status = "rejected"
                root.set(status="rejected")
                return _error_response(400, str(e), err_type="invalid_request_error")
            except NoInstancesError as e:
                # No live/healthy workers (includes breaker-open). The
                # condition is transient — tell clients when to retry.
                tracker.status = "unavailable"
                root.set(status="unavailable")
                return _error_response(
                    503,
                    str(e) or "no instances available",
                    err_type="service_unavailable",
                    headers={"Retry-After": RETRY_AFTER_S},
                )
            except DeadlineExceededError as e:
                tracker.status = "deadline"
                root.set(status="deadline")
                return _error_response(
                    504, str(e), err_type="deadline_exceeded"
                )
            except Exception as e:
                logger.exception("engine rejected request")
                tracker.status = "error"
                root.set(status="error")
                return _error_response(500, str(e))

            # This thread's handling of each chunk in parts
            # (telemetry/dispatch.py HTTP_PARTS): what comes up the
            # stream is detokenised; from here it is framed and written.
            chunks = http_chunk_clock()

            async def _typed_chunks():
                for idx, stream in enumerate(streams):
                    # Resumable-stream belt-and-braces: chunks carry a
                    # cumulative sequence index (``seq_index``); anything
                    # at or below the emitted watermark is a replayed
                    # duplicate from a mid-stream failover splice and is
                    # dropped here, so the client-facing SSE stream is
                    # duplicate-free even if a lower layer misbehaves.
                    high = 0  # emitted watermark (cumulative tokens)
                    last = 0  # previous chunk's index, arrival order
                    async for item in stream:
                        chunks.lap("frame")
                        if streaming:
                            tracker.first_token()
                        chunk = (
                            chunk_type.model_validate(item)
                            if isinstance(item, dict)
                            else item
                        )
                        si = getattr(chunk, "seq_index", None)
                        if si is not None:
                            if si <= high:
                                get_telemetry().tokens_deduplicated.inc(
                                    max(si - last, 0)
                                )
                                last = si
                                continue
                            delta = si - high
                            last = high = si
                        else:
                            delta = 1
                        if chunk.choices:
                            # SLO marks: first/last content chunk and
                            # cumulative tokens (seq_index watermark
                            # delta when present, chunk count floor
                            # otherwise) — the per-request TTFT/ITL fed
                            # to the edge SLO attribution.
                            now = time.monotonic()
                            if not lat["first"]:
                                lat["first"] = now
                            lat["last"] = now
                            lat["tokens"] += max(delta, 1)
                        if idx and chunk.choices:
                            for choice in chunk.choices:
                                choice.index = idx
                        yield chunk

            if not req.stream:
                try:
                    full = await aggregate(_typed_chunks())
                except RecoveryExhaustedError as e:
                    # A resumable stream broke more times than
                    # max_recoveries allows: the upstream fleet kept
                    # dying mid-generation — a gateway failure, not a
                    # client error and not "no instances".
                    tracker.status = "recovery_exhausted"
                    root.set(status="recovery_exhausted")
                    ctx.kill()
                    return _error_response(
                        502, str(e), err_type="bad_gateway"
                    )
                except NoInstancesError as e:
                    tracker.status = "unavailable"
                    root.set(status="unavailable")
                    ctx.kill()
                    return _error_response(
                        503,
                        str(e) or "no instances available",
                        err_type="service_unavailable",
                        headers={"Retry-After": RETRY_AFTER_S},
                    )
                except DeadlineExceededError as e:
                    tracker.status = "deadline"
                    root.set(status="deadline")
                    ctx.kill()
                    return _error_response(504, str(e), err_type="deadline_exceeded")
                except Exception as e:
                    logger.exception("request failed")
                    tracker.status = "error"
                    root.set(status="error")
                    ctx.kill()
                    return _error_response(500, str(e))
                self._record_slo(priority, t_arrival, lat, root)
                return web.json_response(full.model_dump(exclude_none=True))

            resp = web.StreamResponse(
                headers={
                    "Content-Type": "text/event-stream",
                    "Cache-Control": "no-cache",
                }
            )
            await resp.prepare(request)
            try:
                async for chunk in _typed_chunks():
                    frame = Annotated.from_data(chunk.model_dump(exclude_none=True))
                    data = encode_frame(frame).encode()
                    chunks.lap("write")
                    await resp.write(data)
                    chunks.idle()
                await resp.write(encode_done().encode())
                # Attributed only on a fully drained stream: a request
                # that errored or lost its client is not goodput and
                # its truncated latencies would poison the window.
                self._record_slo(priority, t_arrival, lat, root)
            except (ConnectionResetError, asyncio.CancelledError):
                # Client went away: kill generation immediately.
                logger.info("client disconnected; killing request %s", ctx.id)
                tracker.status = "disconnect"
                root.set(status="disconnect")
                ctx.kill()
                raise
            except Exception as e:
                logger.exception("stream failed mid-flight")
                tracker.status = "error"
                root.set(status="error")
                ctx.kill()
                err = Annotated.from_error(str(e))
                await resp.write(encode_frame(err).encode())
            await resp.write_eof()
            return resp

    def _record_slo(
        self, priority: int, t_arrival: float, lat: dict, root=None
    ) -> None:
        """Feed one completed request into the SLO attribution: TTFT =
        arrival -> first content chunk, ITL = mean inter-token interval
        after it (None for single-token responses — never a violation).
        The same edge measurements stamp the root http_request span
        (``ttft_s`` / ``itl_s`` / ``latency_s``) — the ground truth the
        request-anatomy component sum is checked against
        (telemetry/anatomy.py, `llmctl trace --why`)."""
        if not lat["first"]:
            return
        ttft = max(lat["first"] - t_arrival, 0.0)
        itl = None
        if lat["tokens"] > 1:
            itl = max(lat["last"] - lat["first"], 0.0) / (lat["tokens"] - 1)
        if root is not None:
            root.set(
                ttft_s=round(ttft, 6),
                latency_s=round(max(lat["last"] - t_arrival, 0.0), 6),
                tokens=lat["tokens"],
                priority=priority,
            )
            if itl is not None:
                root.set(itl_s=round(itl, 6))
        if self.slo is None:
            return
        self.slo.record(priority, ttft_s=ttft, itl_s=itl)


class _FanoutContext:
    """Kill/stop fan-out over a batch's per-sub-request contexts."""

    def __init__(self, ctxs: list[AsyncEngineContext]):
        self._ctxs = ctxs
        self.id = ctxs[0].id if ctxs else ""

    def kill(self) -> None:
        for c in self._ctxs:
            c.kill()

    def stop_generating(self) -> None:
        for c in self._ctxs:
            c.stop_generating()


def _parse_completion_request(payload: dict) -> CompletionRequest:
    return CompletionRequest.model_validate(payload)


def _expand_completion_batch(payload: dict) -> list[dict]:
    """Split a multi-prompt completion payload into per-prompt payloads."""
    prompt = payload.get("prompt")
    if isinstance(prompt, list) and prompt and not isinstance(prompt[0], int):
        return [{**payload, "prompt": p} for p in prompt]
    return [payload]


def _request_priority(req: Any, request: web.Request) -> int:
    """Admission priority class: the body/nvext ``priority`` field wins
    over the ``X-Request-Priority`` header; absent means ``normal``.
    Invalid spellings raise (the caller maps to 400)."""
    raw = None
    getter = getattr(req, "request_priority", None)
    if getter is not None:
        raw = getter()
    if raw is None:
        raw = request.headers.get("X-Request-Priority")
    return parse_priority(raw)


def _request_timeout_s(payload: Any, request: web.Request) -> float | None:
    """Per-request deadline budget: body ``timeout_s`` wins over the
    ``X-Request-Timeout-S`` header; absent/invalid means no deadline."""
    raw = None
    if isinstance(payload, dict):
        raw = payload.pop("timeout_s", None)
    if raw is None:
        raw = request.headers.get("X-Request-Timeout-S")
    if raw is None:
        return None
    try:
        timeout_s = float(raw)
    except (TypeError, ValueError):
        raise ValueError(f"timeout_s must be a number, got {raw!r}") from None
    if timeout_s <= 0:
        raise ValueError("timeout_s must be positive")
    return timeout_s


def _error_response(
    status: int,
    message: str,
    err_type: str = "invalid_request_error",
    headers: dict[str, str] | None = None,
) -> web.Response:
    return web.json_response(
        {"error": {"message": message, "type": err_type, "code": status}},
        status=status,
        headers=headers,
    )


def build_pipeline_engine(mdc, core_engine) -> AsyncEngine:
    """preprocessor -> backend -> core engine, as one OpenAI-level engine."""
    from ..backend import Backend
    from ..preprocessor.preprocessor import OpenAIPreprocessor
    from ..runtime.pipeline import build_pipeline

    pre = OpenAIPreprocessor(mdc)
    backend = Backend(core_engine, pre.tokenizer)
    return build_pipeline([pre], backend)
