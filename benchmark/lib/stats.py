"""Metric arithmetic of the benchmark: percentiles, per-request timings
taken from raw load-generator records, window clipping, and deltas of
Prometheus counters and histograms.

Everything here is pure (records in, numbers out) so that it can be
tested without a server. Times are seconds on one monotonic clock shared
by the load generator and the supervisor (``time.monotonic`` is
system-wide on Linux).

A request record, as ``loadgen.py`` writes it::

    {"chain": 3, "index": 0, "due": 12.5, "sent": 12.5004,
     "chunks": [[t, cumulative_tokens], ...],   # content-bearing chunks
     "end": 14.1, "ok": true, "status": 200, "error": null,
     "asked": 140, "completion_tokens": 140, "prompt_tokens": 171}
"""

from __future__ import annotations

import math
import re


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) with linear interpolation between
    order statistics (numpy's default). Raises on an empty input: a
    metric of nothing is left out by the caller, never reported as 0."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    if len(xs) == 1:
        return float(xs[0])
    rank = (len(xs) - 1) * q / 100.0
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (rank - lo))


def in_window(rec: dict, w0: float, w1: float) -> bool:
    """A request belongs to the window when it was DUE inside it (open
    loop: the schedule's time; closed loop: when the client was free to
    send it), whenever it was actually sent or answered."""
    return w0 <= rec["due"] < w1


def completed(rec: dict) -> bool:
    """Answered in full: HTTP 200, the stream ended, no error frame, and
    exactly the tokens asked for."""
    return bool(
        rec.get("ok")
        and rec.get("end") is not None
        and rec.get("completion_tokens") == rec.get("asked")
    )


def ttft_s(rec: dict) -> float | None:
    """First content chunk minus DUE time, so a generator that ran late
    or a stalled server both count against the request."""
    if not rec.get("chunks"):
        return None
    return rec["chunks"][0][0] - rec["due"]


def tpot_s(rec: dict) -> float | None:
    """(last token - first token) / (tokens - 1) for one request."""
    chunks = rec.get("chunks") or []
    if not chunks:
        return None
    n = chunks[-1][1]
    if n < 2 or len(chunks) < 2:
        return None
    return (chunks[-1][0] - chunks[0][0]) / (n - 1)


def chunk_gaps_s(rec: dict) -> list[float]:
    """Gaps between successive content-bearing SSE chunks."""
    ts = [c[0] for c in rec.get("chunks") or []]
    return [b - a for a, b in zip(ts, ts[1:])]


def tokens_in_window(records, w0: float, w1: float) -> int:
    """Output tokens RECEIVED inside [w0, w1), from any request, whenever
    it was due: a rate is over all the work of the window."""
    total = 0
    for rec in records:
        prev = 0
        for t, cum in rec.get("chunks") or []:
            if w0 <= t < w1:
                total += cum - prev
            prev = cum
    return total


def inflight_at(records, t: float) -> int:
    """Requests sent and not yet ended at time ``t``."""
    n = 0
    for rec in records:
        if rec.get("sent") is None or rec["sent"] > t:
            continue
        if rec.get("end") is None or rec["end"] > t:
            n += 1
    return n


def summarize(records, w0: float, w1: float) -> dict:
    """Everything the load generator's records say about one window."""
    mine = [r for r in records if in_window(r, w0, w1)]
    done = [r for r in mine if completed(r)]
    ttfts = [x for x in map(ttft_s, done) if x is not None]
    tpots = [x for x in map(tpot_s, done) if x is not None]
    gaps = [g for r in done for g in chunk_gaps_s(r)]
    late = [r["sent"] - r["due"] for r in mine if r.get("sent") is not None]
    out = {
        "attempted": len(mine),
        "failed": len(mine) - len(done),
        "window_s": w1 - w0,
        "tokens_in_window": tokens_in_window(records, w0, w1),
        "ttft_s": ttfts,
        "tpot_s": tpots,
        "chunk_gap_s": gaps,
        "lateness_s": late,
        "prompt_tokens": [r["prompt_tokens"] for r in done if r.get("prompt_tokens")],
        "inflight_half_mean": _mean(
            [inflight_at(records, w0 + (w1 - w0) * k / 40.0) for k in range(20)]
        ),
        "inflight_end": inflight_at(records, w1),
    }
    return out


def _mean(xs) -> float | None:
    xs = list(xs)
    return sum(xs) / len(xs) if xs else None


# ------------------------------------------------------------ prometheus
_SAMPLE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+([^\s]+)(?:\s+\d+)?$"
)
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_prometheus(text: str) -> dict:
    """``{(name, (("label", "value"), ...)): float}`` from the text
    exposition format. Comment lines and malformed lines are skipped."""
    out: dict = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE.match(line)
        if not m:
            continue
        name, labels, value = m.groups()
        try:
            v = float(value)
        except ValueError:
            continue
        key = tuple(sorted(_LABEL.findall(labels or "")))
        out[(name, key)] = v
    return out


def sample_sum(samples: dict, name: str, match: dict | None = None) -> float | None:
    """Sum of every series of ``name`` whose labels include ``match``.
    None when no such series exists (a counter never touched is absent
    from the exposition, which a delta reads as 0 — see counter_delta)."""
    match = match or {}
    total, found = 0.0, False
    for (n, labels), v in samples.items():
        if n != name:
            continue
        d = dict(labels)
        if all(d.get(k) == val for k, val in match.items()):
            total += v
            found = True
    return total if found else None


def counter_delta(before: dict, after: dict, name: str, match=None) -> float:
    """after - before of a counter; a series absent on either side reads 0
    there (prometheus_client omits a labelled child until first use)."""
    a = sample_sum(after, name, match) or 0.0
    b = sample_sum(before, name, match) or 0.0
    return a - b


def histogram_mean_delta(before: dict, after: dict, name: str, match=None):
    """Mean of the observations a histogram took between two scrapes:
    delta(_sum) / delta(_count). None when it took none."""
    n = counter_delta(before, after, name + "_count", match)
    if n <= 0:
        return None
    return counter_delta(before, after, name + "_sum", match) / n
