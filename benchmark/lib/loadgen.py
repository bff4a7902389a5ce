#!/usr/bin/env python3
"""The load generator: a child process that never imports JAX.

    python3 benchmark/lib/loadgen.py <plan.json> <out.json>

It executes one plan — chains of chat requests with due times — against
the server's OpenAI HTTP surface with SSE streaming, and writes the raw
timing of every request it started. It decides nothing and reduces
nothing: ``stats.py`` turns the records into metrics.

Plan::

    {"base": "http://127.0.0.1:8123", "model": "bench",
     "t0": <time.monotonic() at schedule time 0>,
     "w0": 10.0, "w1": 40.0,          # the measured window, from t0
     "drain_s": 60.0,                 # bounded wait after w1
     "chains": [{"start_s": 0.37,
                 "requests": [{"messages": [...], "max_tokens": 140,
                               "gap_s": 0.0}, ...]}]}

A chain's first request is due at ``t0 + start_s``; each later one is
due ``gap_s`` after the one before it ENDED (a conversation's next turn,
a closed-loop client's next request). An open loop is chains of one
request. A request that would be due at or after ``w1`` is never sent.
After ``w1`` the generator waits, at most ``drain_s``, for every request
due inside the window, then cancels what is left and exits.

One process, one thread, asyncio: at some hundreds of chunks a second
the generator's own lateness stays in microseconds, and it is reported.
"""

from __future__ import annotations

import asyncio
import json
import sys
import time

import aiohttp


async def _sleep_until(t: float) -> None:
    delay = t - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)


async def _send(session, plan: dict, rec: dict, req: dict) -> None:
    body = {
        "model": plan["model"],
        "messages": req["messages"],
        "max_tokens": req["max_tokens"],
        "temperature": 0,
        "ignore_eos": True,
        "stream": True,
        "stream_options": {"include_usage": True},
    }
    rec["sent"] = time.monotonic()
    try:
        async with session.post(
            plan["base"] + "/v1/chat/completions", json=body
        ) as resp:
            rec["status"] = resp.status
            if resp.status != 200:
                rec["error"] = (await resp.text())[:300]
                return
            event, cum, finished = None, 0, False
            async for raw in resp.content:
                line = raw.decode("utf-8", "replace").strip()
                if line.startswith("event:"):
                    event = line[6:].strip()
                    continue
                if not line.startswith("data:"):
                    continue
                now = time.monotonic()
                data = line[5:].strip()
                if event == "error":
                    rec["error"] = data[:300]
                    return
                if data == "[DONE]":
                    finished = True
                    continue
                chunk = json.loads(data)
                usage = chunk.get("usage")
                if usage:
                    rec["completion_tokens"] = usage.get("completion_tokens")
                    rec["prompt_tokens"] = usage.get("prompt_tokens")
                for choice in chunk.get("choices") or []:
                    if (choice.get("delta") or {}).get("content"):
                        si = chunk.get("seq_index")
                        cum = si if si is not None else cum + 1
                        rec["chunks"].append([now, cum])
                    if choice.get("finish_reason"):
                        rec["finish_reason"] = choice["finish_reason"]
            rec["ok"] = finished and rec.get("finish_reason") in ("length", "stop")
    except (aiohttp.ClientError, asyncio.TimeoutError, ValueError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"[:300]
    # Not in a ``finally``: a request cancelled at the end of the drain
    # never ended, and its record says so by ``end`` staying None.
    rec["end"] = time.monotonic()


async def _run_chain(session, plan: dict, ci: int, chain: dict, records: list):
    t0, w1 = plan["t0"], plan["t0"] + plan["w1"]
    due = t0 + chain["start_s"]
    prev_end = due
    for k, req in enumerate(chain["requests"]):
        if k:
            due = prev_end + req.get("gap_s", 0.0)
        if due >= w1:
            return
        await _sleep_until(due)
        rec = {
            "chain": ci, "index": k, "due": due, "sent": None, "chunks": [],
            "end": None, "ok": False, "status": None, "error": None,
            "asked": req["max_tokens"], "completion_tokens": None,
            "prompt_tokens": None,
        }
        records.append(rec)
        await _send(session, plan, rec, req)
        if not rec["ok"]:
            return  # a failed turn ends its chain; the record says why
        prev_end = rec["end"]


async def run_plan(plan: dict) -> list[dict]:
    records: list[dict] = []
    t0 = plan["t0"]
    w0, w1 = t0 + plan["w0"], t0 + plan["w1"]
    timeout = aiohttp.ClientTimeout(total=None, sock_connect=30, sock_read=None)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as session:
        tasks = [
            asyncio.ensure_future(_run_chain(session, plan, i, c, records))
            for i, c in enumerate(plan["chains"])
        ]
        await _sleep_until(w1)
        deadline = w1 + plan["drain_s"]
        while time.monotonic() < deadline:
            if all(
                r["end"] is not None for r in records if w0 <= r["due"] < w1
            ):
                break
            await asyncio.sleep(0.05)
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
    return records


def main(argv: list[str]) -> int:
    plan_path, out_path = argv
    with open(plan_path) as f:
        plan = json.load(f)
    records = asyncio.run(run_plan(plan))
    with open(out_path, "w") as f:
        json.dump({"records": records, "ended": time.monotonic()}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
