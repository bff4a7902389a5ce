"""Flagship SDK graph e2e: the full Frontend → Processor → TpuWorker
stack launched by the real supervisor, driven over HTTP.

Reference capability anchors: ``examples/llm/graphs/{agg,agg_router,
disagg}.py`` + ``configs/*.yaml`` (the reference's headline deploy
shapes).
"""

import asyncio
import json
import os
import socket
import subprocess
import sys

import aiohttp

from dynamo_exp_tpu.sdk.service import discover_graph
from .fixtures import free_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))



def test_graph_discovery_shapes():
    from examples.llm.graphs.agg import Frontend
    from examples.llm.graphs.disagg import Graph

    agg = [s.name for s in discover_graph(Frontend)]
    assert agg == ["TpuWorker", "Processor", "Frontend"]
    dis = [s.name for s in discover_graph(Graph)]
    assert set(dis) == {
        "TpuWorker", "Processor", "Frontend", "PrefillTpuWorker", "Graph",
    }


async def test_agg_graph_serves_openai_over_http(tiny_model_dir):
    """Launch the agg graph through the supervisor; a chat completion
    streams back through Frontend → Processor → TpuWorker."""
    from dynamo_exp_tpu.runtime.transports.coordinator import CoordinatorServer

    server = CoordinatorServer()
    await server.start()
    port = free_port()
    overrides = {
        "Frontend": {"served_model_name": "tiny", "port": port,
                     "host": "127.0.0.1"},
        "Processor": {"model_path": tiny_model_dir,
                      "served_model_name": "tiny", "page_size": 8},
        "TpuWorker": {
            "model_path": tiny_model_dir, "served_model_name": "tiny",
            "random_weights": True, "max_decode_slots": 2,
            "num_pages": 64, "max_model_len": 128, "page_size": 8,
            "kv_dtype": "float32",
        },
    }
    env = dict(
        os.environ,
        PYTHONPATH=REPO,
        JAX_PLATFORMS="cpu",
        DYN_TPU_CHIPS="4",  # no chips here: a pretend budget for CPU workers
        DYN_SERVICE_CONFIG=json.dumps(overrides),
    )
    sup = await asyncio.create_subprocess_exec(
        sys.executable, "-m", "dynamo_exp_tpu.sdk.serve",
        "examples.llm.graphs.agg:Frontend",
        "--coordinator", server.address,
        env=env, cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
    )
    base = f"http://127.0.0.1:{port}"
    try:
        async with aiohttp.ClientSession() as session:
            up = False
            for _ in range(300):
                if sup.returncode is not None:
                    break
                try:
                    async with session.get(f"{base}/v1/models") as r:
                        if r.status == 200:
                            up = True
                            break
                except aiohttp.ClientError:
                    pass
                await asyncio.sleep(0.25)
            if not up:
                out = b""
                if sup.returncode is not None:
                    out, _ = await sup.communicate()
                raise AssertionError(
                    f"frontend never served (rc={sup.returncode}):\n"
                    + out.decode()
                )
            body = {
                "model": "tiny",
                "messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 6,
                "stream": True,
            }
            chunks = []
            async with session.post(
                f"{base}/v1/chat/completions", json=body
            ) as r:
                assert r.status == 200, await r.text()
                async for line in r.content:
                    line = line.decode().strip()
                    if line.startswith("data: ") and line != "data: [DONE]":
                        chunks.append(json.loads(line[6:]))
            assert chunks, "no SSE chunks"
            assert chunks[0]["object"] == "chat.completion.chunk"
            text = "".join(
                c["choices"][0]["delta"].get("content", "") for c in chunks
            )
            assert isinstance(text, str)  # random weights: any text is fine

            # Unary completion through the same stack.
            async with session.post(
                f"{base}/v1/completions",
                json={"model": "tiny", "prompt": "x", "max_tokens": 4},
            ) as r:
                assert r.status == 200, await r.text()
                data = await r.json()
            assert data["choices"][0]["finish_reason"] == "length"
    finally:
        sup.terminate()
        try:
            await asyncio.wait_for(sup.wait(), 30)
        except asyncio.TimeoutError:
            sup.kill()
        await server.close()


async def test_disagg_router_graph_remote_prefill_over_http(tiny_model_dir):
    """The full fleet shape (reference graphs/disagg_router.py): KV-routed
    processor + disagg decode worker + prefill fleet, launched by the
    supervisor. max_local_prefill_length=0 forces every prefill through
    the queue + KV transfer plane, so a streamed completion proves the
    whole disagg chain; llmctl then retunes the live-watched config."""
    from dynamo_exp_tpu.runtime.transports.coordinator import CoordinatorServer

    server = CoordinatorServer()
    await server.start()
    port = free_port()
    worker_cfg = {
        "model_path": tiny_model_dir, "served_model_name": "tiny",
        "random_weights": True, "max_decode_slots": 2,
        "num_pages": 64, "max_model_len": 128, "page_size": 8,
        "kv_dtype": "float32",
    }
    overrides = {
        "Frontend": {"served_model_name": "tiny", "port": port,
                     "host": "127.0.0.1"},
        "Processor": {"model_path": tiny_model_dir,
                      "served_model_name": "tiny", "page_size": 8,
                      "router": "kv"},
        "TpuWorker": {**worker_cfg, "disagg_mode": "decode",
                      "max_local_prefill_length": 0},
        "PrefillTpuWorker": dict(worker_cfg),
    }
    env = dict(
        os.environ,
        PYTHONPATH=REPO,
        JAX_PLATFORMS="cpu",
        DYN_TPU_CHIPS="4",  # no chips here: a pretend budget for CPU workers
        DYN_SERVICE_CONFIG=json.dumps(overrides),
    )
    sup = await asyncio.create_subprocess_exec(
        sys.executable, "-m", "dynamo_exp_tpu.sdk.serve",
        "examples.llm.graphs.disagg_router:Graph",
        "--coordinator", server.address,
        env=env, cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
    )
    base = f"http://127.0.0.1:{port}"
    try:
        async with aiohttp.ClientSession() as session:
            up = False
            for _ in range(300):
                if sup.returncode is not None:
                    break
                try:
                    async with session.get(f"{base}/v1/models") as r:
                        if r.status == 200 and (await r.json())["data"]:
                            up = True
                            break
                except aiohttp.ClientError:
                    pass
                await asyncio.sleep(0.25)
            if not up:
                out = b""
                if sup.returncode is not None:
                    out, _ = await sup.communicate()
                raise AssertionError(
                    f"frontend never served (rc={sup.returncode}):\n"
                    + out.decode()
                )
            # Long prompt: with threshold 0 this prefills on the prefill
            # fleet and the pages ride the transfer plane home.
            body = {
                "model": "tiny",
                "messages": [{"role": "user", "content": "hello " * 40}],
                "max_tokens": 5,
                "stream": True,
            }
            chunks = []
            async with session.post(
                f"{base}/v1/chat/completions", json=body
            ) as r:
                assert r.status == 200, await r.text()
                async for line in r.content:
                    line = line.decode().strip()
                    if line.startswith("data: ") and line != "data: [DONE]":
                        chunks.append(json.loads(line[6:]))
            assert chunks, "no SSE chunks through the disagg chain"

        # Live reconfig via llmctl: the watched KV key round-trips.
        from dynamo_exp_tpu import llmctl
        from dynamo_exp_tpu.disagg.config import DisaggConfig, disagg_config_key
        from dynamo_exp_tpu.runtime.transports.coordinator import (
            CoordinatorDiscovery,
        )

        rc = await llmctl.run(
            llmctl.build_parser().parse_args([
                "--coordinator", server.address, "disagg", "set", "tiny",
                "--max-local-prefill-length", "2048",
                "--max-prefill-queue-size", "5",
            ])
        )
        assert rc == 0
        disc = CoordinatorDiscovery(server.address)
        raw = await disc.kv_get(disagg_config_key("tiny"))
        cfg = DisaggConfig.from_bytes(raw)
        assert cfg.max_local_prefill_length == 2048
        assert cfg.max_prefill_queue_size == 5
        await disc.close()
    finally:
        sup.terminate()
        try:
            await asyncio.wait_for(sup.wait(), 30)
        except asyncio.TimeoutError:
            sup.kill()
        await server.close()
