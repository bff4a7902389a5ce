"""The model directory a cell is served from: ``config.json`` from the
configuration's own file, the program's chat template, and a byte-level
BPE tokenizer with exactly ``vocab_size`` ids whose merged tokens are all
printable ASCII.

Why not ``models/synth.py::write_full_vocab_tokenizer``: its merges draw
from all 256 byte symbols, so almost every token is an invalid UTF-8
fragment, and the server's incremental detokenizer rightly withholds text
until it is well-formed. With random weights a reply then sends no
content for many tokens, and "first content chunk" measured the
detokenizer's patience and the seed's luck, not the engine (my chip runs,
PR 23: 7 of 102 completed requests sent no content chunk at all, and the
median time to first token moved 30 % between two seeds of one
schedule). Real vocabularies are almost wholly valid text; this one is
too: the specials, the 256 byte symbols (as any byte-level vocabulary
has), then merges over the 94 printable ASCII symbols and the space.
"""

from __future__ import annotations

import json
import os
import random

_MAX_TOKEN_SYMBOLS = 12


def write_printable_tokenizer(path: str, vocab_size: int, seed: int) -> None:
    from tokenizers import AddedToken, Tokenizer, decoders, models, pre_tokenizers

    from dynamo_exp_tpu.models.synth import SPECIAL_TOKENS

    alphabet = sorted(pre_tokenizers.ByteLevel.alphabet())
    printable = [chr(c) for c in range(33, 127)]  # byte-level maps these to themselves
    space = "Ġ"  # the byte-level symbol of " "
    n_merges = vocab_size - len(SPECIAL_TOKENS) - len(alphabet)
    if n_merges < 0:
        raise ValueError(f"vocab_size {vocab_size} is below the byte alphabet's")
    rng = random.Random(seed)
    vocab = {tok: i for i, tok in enumerate(SPECIAL_TOKENS)}
    for sym in alphabet:
        vocab[sym] = len(vocab)
    growable = printable + [space]  # a token may start with a space, as words do
    merges: list[tuple[str, str]] = []
    while len(merges) < n_merges:
        left, right = rng.choice(growable), rng.choice(printable)
        token = left + right
        if token in vocab:
            continue
        vocab[token] = len(vocab)
        merges.append((left, right))
        if len(token) < _MAX_TOKEN_SYMBOLS:
            growable.append(token)
    tok = Tokenizer(models.BPE(vocab=vocab, merges=merges, unk_token=None))
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tok.decoder = decoders.ByteLevel()
    tok.add_special_tokens([AddedToken(t, special=True) for t in SPECIAL_TOKENS])
    if tok.get_vocab_size() != vocab_size:
        raise RuntimeError(f"tokenizer has {tok.get_vocab_size()} ids, wanted {vocab_size}")
    tok.save(os.path.join(path, "tokenizer.json"))


def prepare(path: str, hf: dict) -> str:
    """Write the directory once a checkout; reuse it when its
    ``config.json`` already says what the configuration's file says."""
    from dynamo_exp_tpu.models.synth import write_tokenizer_config

    marker = os.path.join(path, "config.json")
    if os.path.exists(marker):
        with open(marker) as f:
            if json.load(f) == hf:
                return path
    os.makedirs(path, exist_ok=True)
    write_printable_tokenizer(path, int(hf["vocab_size"]), seed=0)
    write_tokenizer_config(path)
    with open(marker, "w") as f:  # last: its presence says the dir is whole
        json.dump(hf, f)
    return path
