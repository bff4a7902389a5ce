"""Seeded inputs: lengths drawn from a distribution given as data, and
text of a wanted token length under the served tokenizer.

Every seed gives the same SET of lengths and of arrival gaps, in another
order (evenly spaced quantiles of the distribution, then a seeded
shuffle). Two seeds then offer the same work, so runs with different
seeds differ as two runs of one seed do, and a bound set from their
spread is a bound on the system, not on the draw.
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist

# A chat message costs its role marker and its end marker besides its
# content, and the generation prompt one more (models/synth.py
# CHAT_TEMPLATE: "<|role|>content</s>" ... "<|assistant|>").
TEMPLATE_TOKENS_PER_MESSAGE = 2
TEMPLATE_TOKENS_PER_PROMPT = 1


def quantile(dist: dict, u: float) -> float:
    """The ``u``-quantile (0 < u < 1) of a distribution given as data."""
    kind = dist["dist"]
    if kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(u))
    elif kind == "exponential":
        x = -dist["mean"] * math.log(1.0 - u)
    elif kind == "uniform_int":
        x = dist["min"] + math.floor(u * (dist["max"] - dist["min"] + 1))
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "min" in dist:
        x = max(x, dist["min"])
    if "max" in dist:
        x = min(x, dist["max"])
    return x


def stratified(dist: dict, n: int, rng: random.Random, integer: bool = True) -> list:
    """``n`` values at the quantiles (i + 0.5) / n, shuffled by ``rng``."""
    xs = [quantile(dist, (i + 0.5) / n) for i in range(n)]
    if integer:
        xs = [int(round(x)) for x in xs]
    rng.shuffle(xs)
    return xs


def arrival_times(rate_per_s: float, horizon_s: float, rng: random.Random) -> list[float]:
    """Poisson-like arrivals over [0, horizon): round(rate x horizon)
    exponential gaps taken at evenly spaced quantiles, shuffled, and
    scaled so that they fill the horizon exactly. The count and the set
    of gaps are the same for every seed."""
    n = max(1, int(round(rate_per_s * horizon_s)))
    gaps = stratified({"dist": "exponential", "mean": 1.0}, n, rng, integer=False)
    scale = horizon_s / sum(gaps)
    out, t = [], 0.0
    first = gaps[0] * scale * rng.random()  # the schedule does not start on an arrival
    for g in gaps:
        out.append((t + first) % horizon_s)
        t += g * scale
    return sorted(out)


class TextMaker:
    """Text of about ``n`` tokens under the tokenizer in ``model_dir``.

    The byte-level pre-tokenizer splits at spaces, so the token count of
    " word" does not depend on its neighbours: a text's count is the sum
    over its words, known without encoding the text."""

    def __init__(self, tokenizer_file: str, rng: random.Random, words: int = 4096):
        from tokenizers import Tokenizer

        tok = Tokenizer.from_file(tokenizer_file)
        letters = "abcdefghijklmnopqrstuvwxyz"
        vocab = [
            " " + "".join(rng.choice(letters) for _ in range(rng.randint(2, 9)))
            for _ in range(words)
        ]
        counts = [len(e.ids) for e in tok.encode_batch(vocab, add_special_tokens=False)]
        self._words = list(zip(vocab, counts))
        self._by_count: dict[int, list[str]] = {}
        for w, c in self._words:
            self._by_count.setdefault(c, []).append(w)
        self._rng = rng

    def text(self, n_tokens: int) -> str:
        parts, left = [], max(1, n_tokens)
        while left > 0:
            w, c = self._rng.choice(self._words)
            if c > left:
                # Close the text with the longest word that still fits.
                fits = [k for k in self._by_count if k <= left]
                if not fits:
                    break
                c = max(fits)
                w = self._rng.choice(self._by_count[c])
            parts.append(w)
            left -= c
        return "".join(parts).lstrip()

    def user_message(self, prompt_tokens: int) -> list[dict]:
        """One user message whose rendered prompt is about ``prompt_tokens``."""
        n = prompt_tokens - TEMPLATE_TOKENS_PER_MESSAGE - TEMPLATE_TOKENS_PER_PROMPT
        return [{"role": "user", "content": self.text(n)}]
