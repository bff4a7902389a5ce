"""The checks behind ``correct``, made outside the timed window.

(a) lengths: every completed request returned exactly its ``max_tokens``
    (``stats.completed``; counted in ``failed`` otherwise);
(b) placement: what the engine says about where it ran
    (``dynamo_build_info``) and its KV ledger;
(c) repeatability: four fixed greedy probes give the same text before
    and after the window, each side at its second asking in a row
    (``second_asking_texts``);
(d) agreement with the configuration's reference on seeded 48-token
    prompts and 8 decoded tokens each, through the HTTP chat path: the
    served top log-probabilities against the reference's log-softmax
    (``walk_reference``). What is asked of the differences is the
    configuration's comparison, a module under ``comparisons/``.
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


def ask_greedy(base: str, model: str, content: str, n_tokens: int):
    """One greedy, unstreamed chat request of exactly ``n_tokens``:
    (HTTP status, the decoded reply)."""
    return http_json(
        f"{base}/v1/chat/completions",
        {
            "model": model, "messages": [{"role": "user", "content": content}],
            "max_tokens": n_tokens, "temperature": 0, "ignore_eos": True, "stream": False,
            "stream_options": {"include_usage": True},
        },
    )


def greedy_probe_texts(base: str, model: str) -> list[str]:
    out = []
    for content, n in FIXED_PROBES:
        status, resp = ask_greedy(base, model, content, n)
        if status != 200:
            raise RuntimeError(f"greedy probe: HTTP {status}: {str(resp)[:300]}")
        usage = resp.get("usage") or {}
        if usage.get("completion_tokens") != n:
            raise RuntimeError(f"greedy probe asked {n} tokens, got {usage}")
        out.append(resp["choices"][0]["message"].get("content") or "")
    return out


def second_asking_texts(base: str, model: str) -> list[str]:
    """The probes' texts at their second asking in a row: warm. A second
    asking finds the prompt's pages as the first left them and takes all
    but one token from them, before the window and after it alike, so
    the two sides of ``repeatable`` run the same programs on the same
    kind of cache state, and a program that computes one function gives
    the same text, bit for bit, whatever the model. A first asking is
    not like that: before the window it prefills the whole prompt, after
    it it may find some pages, which differs in the last bits, and a
    routed model at depth turns last bits into another expert and
    another token (PERF.md, PR 26: on four chips the 207-token probe
    reads one text cold and another from cached pages, the same ten
    times out of ten). For a dense model the first and second askings
    agree (PR 23-26, every run), so this is the check the dense cells
    had, with one asking more on each side."""
    greedy_probe_texts(base, model)
    return greedy_probe_texts(base, model)


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
def _probe_prompts(tok, seed: int, n: int = 2) -> list[tuple[str, list[int]]]:
    """``n`` seeded user messages whose rendered chat prompt
    (models/synth.py CHAT_TEMPLATE) is PROBE_PROMPT_TOKENS tokens, with
    the ids. The first two are the same whatever ``n``."""
    rng = random.Random(seed ^ 0x5EED)
    letters = "abcdefghijklmnopqrstuvwxyz"

    def ids_of(content: str) -> list[int]:
        rendered = f"<|user|>{content}</s><|assistant|>"
        return tok.encode(rendered, add_special_tokens=False).ids

    out = []
    for _ in range(n):
        content = ""
        while len(ids_of(content)) < PROBE_PROMPT_TOKENS:
            content += (" " if content else "") + "".join(
                rng.choice(letters) for _ in range(rng.randint(2, 7))
            )
        while len(ids_of(content)) > PROBE_PROMPT_TOKENS and len(content) > 1:
            content = content[:-1].rstrip()
        out.append((content, ids_of(content)))
    return out


def ask_logprobs(base: str, model: str, content: str, n_tokens: int):
    """One probe through ``/v1/chat/completions`` with ``top_logprobs``:
    (the prompt tokens the server counted, the log-probability entries),
    or a string saying why not."""
    status, resp = http_json(
        f"{base}/v1/chat/completions",
        {
            "model": model, "messages": [{"role": "user", "content": content}],
            "max_tokens": n_tokens, "temperature": 0,
            "ignore_eos": True, "logprobs": True, "top_logprobs": 5,
            "stream": False, "stream_options": {"include_usage": True},
        },
    )
    if status != 200:
        return f"logprob probe: HTTP {status}: {str(resp)[:300]}"
    entries = (resp["choices"][0].get("logprobs") or {}).get("content") or []
    return (resp.get("usage") or {}).get("prompt_tokens"), entries


def walk_reference(ctx: dict, n_probes: int = 2, whole_vocabulary: bool = False) -> dict:
    """Send each probe through the chat path, then walk the reference
    along the tokens the engine chose (teacher forcing): prefill, then
    decoding through the cache, against the reference's full forward
    pass. At every step the engine's k-th best log-probability is held
    against the reference's k-th best, and the chosen token's against
    the reference's for it. Returns ``{"step_diffs": [...], "sequences":
    [...]}``: the largest of a step's differences, for every step, and
    each probe's prompt with the chosen tokens; or ``{"why": ...}``
    where the walk could not be made: as when the engine chose a token
    outside the reference's 64 most likely, unless ``whole_vocabulary``
    has the walk look further and let the size of the difference speak.

    ``ctx``: ``base``, ``model``, ``params`` (the weights the reference
    computes with), ``hf``, ``tokenizer_file``, ``seed``, ``reference``
    (the module) and optionally ``ask(content, n_tokens)`` in place of
    ``ask_logprobs`` (the tests' way in without a server)."""
    import numpy as np
    from tokenizers import Tokenizer

    reference = ctx["reference"]
    ask = ctx.get("ask") or (lambda c, n: ask_logprobs(ctx["base"], ctx["model"], c, n))
    tok = Tokenizer.from_file(ctx["tokenizer_file"])
    step_diffs, sequences = [], []
    for content, prompt in _probe_prompts(tok, ctx["seed"], n_probes):
        asked = ask(content, PROBE_DECODE_TOKENS)
        if isinstance(asked, str):
            return {"why": asked}
        served_prompt, entries = asked
        if served_prompt != len(prompt):
            return {
                "why": f"the server counted {served_prompt} prompt tokens where "
                       f"the benchmark's tokenization has {len(prompt)}",
            }
        if len(entries) != PROBE_DECODE_TOKENS:
            return {"why": f"logprob probe returned {len(entries)} entries"}
        ids = list(prompt)
        for step, entry in enumerate(entries):
            ref = reference.log_probs(ctx["params"], ctx["hf"], ids, [len(ids) - 1])[0]
            cand = [int(i) for i in np.argsort(-ref)[:64]]
            served = sorted((t["logprob"] for t in entry["top_logprobs"]), reverse=True)
            worst = 0.0
            for k, v in enumerate(served):
                worst = max(worst, abs(v - float(ref[cand[k]])))
            # The id the engine chose: the reference candidate that decodes
            # to the served text and sits nearest the served value.
            alike = [
                i for i in cand
                if tok.decode([i], skip_special_tokens=True) == entry["token"]
            ]
            if not alike and whole_vocabulary:
                alike = [
                    int(i) for i in np.argsort(-ref)[64:]
                    if tok.decode([int(i)], skip_special_tokens=True) == entry["token"]
                ][:8]
            if not alike:
                return {
                    "why": f"step {step}: the engine chose {entry['token']!r} "
                           f"({entry['logprob']:.4f}), which is not among the "
                           "reference's 64 most likely tokens"
                           + (" nor anywhere in its vocabulary" if whole_vocabulary else ""),
                }
            chosen = min(alike, key=lambda i: abs(float(ref[i]) - entry["logprob"]))
            worst = max(worst, abs(float(ref[chosen]) - entry["logprob"]))
            ids.append(chosen)
            step_diffs.append(worst)
        sequences.append(ids)
    return {"step_diffs": step_diffs, "sequences": sequences}
