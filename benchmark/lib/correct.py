"""The checks behind ``correct``, made outside the timed window.

(a) lengths: every completed request returned exactly its ``max_tokens``
    (``stats.completed``; counted in ``failed`` otherwise);
(b) placement: what the engine says about where it ran
    (``dynamo_build_info``) and its KV ledger;
(c) repeatability: four fixed greedy probes give the same text before
    and after the window;
(d) agreement with ``reference.py`` on two seeded 48-token prompts and 8
    decoded tokens each, through the HTTP chat path: the served top
    log-probabilities against the reference's log-softmax.
"""

from __future__ import annotations

import json
import random
import urllib.error
import urllib.request

PROBE_PROMPT_TOKENS = 48
PROBE_DECODE_TOKENS = 8
REQUEST_TIMEOUT_S = 600.0


def http_json(url: str, body: dict | None = None, timeout_s: float = REQUEST_TIMEOUT_S):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout_s) as resp:
            status, raw = resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:
        status, raw = e.code, e.read().decode(errors="replace")
    try:
        return status, json.loads(raw)
    except ValueError:
        return status, raw


def http_text(url: str, timeout_s: float = 30.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout_s) as resp:
        return resp.read().decode()


# ------------------------------------------------------------ (c) probes
FIXED_PROBES = (
    ("the quick brown fox jumps over the lazy dog", 12),
    ("a b c d e f g h i j k l m n o p q r s t u v w x y z " * 4, 16),
    ("one two three four five six seven eight nine ten " * 6, 24),
    ("x", 9),
)


def greedy_probe_texts(base: str, model: str) -> list[str]:
    out = []
    for content, n in FIXED_PROBES:
        status, resp = http_json(
            f"{base}/v1/chat/completions",
            {
                "model": model, "messages": [{"role": "user", "content": content}],
                "max_tokens": n, "temperature": 0, "ignore_eos": True, "stream": False,
                "stream_options": {"include_usage": True},
            },
        )
        if status != 200:
            raise RuntimeError(f"greedy probe: HTTP {status}: {str(resp)[:300]}")
        usage = resp.get("usage") or {}
        if usage.get("completion_tokens") != n:
            raise RuntimeError(f"greedy probe asked {n} tokens, got {usage}")
        out.append(resp["choices"][0]["message"].get("content") or "")
    return out


# --------------------------------------------------------- (b) placement
def placement(samples: dict) -> dict:
    """Labels of the ``dynamo_build_info`` sample, and the ledger count."""
    info = {}
    for (name, labels), _v in samples.items():
        if name == "dynamo_build_info":
            info = dict(labels)
    violations = sum(
        v for (name, _l), v in samples.items()
        if name == "dynamo_kv_ledger_violations_total"
    )
    return {"build_info": info, "kv_ledger_violations": violations}


# ------------------------------------------------- (d) against reference
def _probe_prompts(tok, seed: int) -> list[tuple[str, list[int]]]:
    """Two seeded user messages whose rendered chat prompt (models/synth.py
    CHAT_TEMPLATE) is PROBE_PROMPT_TOKENS tokens, with the ids."""
    rng = random.Random(seed ^ 0x5EED)
    letters = "abcdefghijklmnopqrstuvwxyz"

    def ids_of(content: str) -> list[int]:
        rendered = f"<|user|>{content}</s><|assistant|>"
        return tok.encode(rendered, add_special_tokens=False).ids

    out = []
    for _ in range(2):
        content = ""
        while len(ids_of(content)) < PROBE_PROMPT_TOKENS:
            content += (" " if content else "") + "".join(
                rng.choice(letters) for _ in range(rng.randint(2, 7))
            )
        while len(ids_of(content)) > PROBE_PROMPT_TOKENS and len(content) > 1:
            content = content[:-1].rstrip()
        out.append((content, ids_of(content)))
    return out


def compare_with_reference(base, model, params, hf, tokenizer_file, seed, tol) -> dict:
    """Send each probe through ``/v1/chat/completions`` with
    ``top_logprobs``, then walk the reference along the tokens the engine
    chose (teacher forcing): prefill, then decoding through the cache,
    against the reference's full forward pass. At every step the
    engine's k-th best log-probability is held against the reference's
    k-th best, and the chosen token's against the reference's for it."""
    import numpy as np
    from tokenizers import Tokenizer

    import reference

    tok = Tokenizer.from_file(tokenizer_file)
    worst, steps = 0.0, 0
    for content, prompt in _probe_prompts(tok, seed):
        status, resp = http_json(
            f"{base}/v1/chat/completions",
            {
                "model": model, "messages": [{"role": "user", "content": content}],
                "max_tokens": PROBE_DECODE_TOKENS, "temperature": 0,
                "ignore_eos": True, "logprobs": True, "top_logprobs": 5,
                "stream": False, "stream_options": {"include_usage": True},
            },
        )
        if status != 200:
            return {"ok": False, "why": f"logprob probe: HTTP {status}: {str(resp)[:300]}"}
        served_prompt = (resp.get("usage") or {}).get("prompt_tokens")
        if served_prompt != len(prompt):
            return {
                "ok": False,
                "why": f"the server counted {served_prompt} prompt tokens where "
                       f"the benchmark's tokenization has {len(prompt)}",
            }
        entries = (resp["choices"][0].get("logprobs") or {}).get("content") or []
        if len(entries) != PROBE_DECODE_TOKENS:
            return {"ok": False, "why": f"logprob probe returned {len(entries)} entries"}
        ids = list(prompt)
        for step, entry in enumerate(entries):
            ref = reference.log_probs(params, hf, ids, [len(ids) - 1])[0]
            cand = [int(i) for i in np.argsort(-ref)[:64]]
            served = sorted((t["logprob"] for t in entry["top_logprobs"]), reverse=True)
            for k, v in enumerate(served):
                worst = max(worst, abs(v - float(ref[cand[k]])))
            # The id the engine chose: the reference candidate that decodes
            # to the served text and sits nearest the served value.
            alike = [
                i for i in cand
                if tok.decode([i], skip_special_tokens=True) == entry["token"]
            ]
            if not alike:
                return {
                    "ok": False,
                    "why": f"step {step}: the engine chose {entry['token']!r} "
                           f"({entry['logprob']:.4f}), which is not among the "
                           "reference's 64 most likely tokens",
                }
            chosen = min(alike, key=lambda i: abs(float(ref[i]) - entry["logprob"]))
            worst = max(worst, abs(float(ref[chosen]) - entry["logprob"]))
            ids.append(chosen)
            steps += 1
    return {
        "ok": worst <= tol, "max_abs_logprob_diff": worst, "tol": tol, "steps": steps,
        "why": None if worst <= tol else f"max |diff| {worst:.4f} > tol {tol}",
    }
