"""Shared test fixtures: build a tiny self-contained model directory
(byte-level BPE tokenizer + llama-style config + chat template) offline."""

from __future__ import annotations

import os

from dynamo_exp_tpu.models import ModelConfig
from dynamo_exp_tpu.models.synth import (
    SPECIAL_TOKENS,
    write_config,
    write_tokenizer_config,
)

TRAIN_TEXT = [
    "hello world, this is a test of the emergency tokenizer system.",
    "the quick brown fox jumps over the lazy dog. 0123456789",
    "café naïve 日本語 emoji ☃ snowman",
    "STOP stop Stop sequences are hidden from the client output.",
    "<|user|><|assistant|><|system|></s><s>",
]


def build_tiny_model_dir(
    path: str,
    vocab_size: int = 384,
    hidden_size: int = 64,
    num_layers: int = 2,
    num_heads: int = 4,
    num_kv_heads: int = 2,
    intermediate_size: int = 128,
    max_position_embeddings: int = 512,
) -> str:
    """Create a HF-style model dir with tokenizer + config, no weights."""
    os.makedirs(path, exist_ok=True)
    tok_json = os.path.join(path, "tokenizer.json")
    if not os.path.exists(tok_json):
        from tokenizers import Tokenizer, decoders, models, pre_tokenizers, trainers

        tok = Tokenizer(models.BPE(unk_token=None))
        tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
        tok.decoder = decoders.ByteLevel()
        trainer = trainers.BpeTrainer(
            vocab_size=vocab_size,
            special_tokens=SPECIAL_TOKENS,
            initial_alphabet=pre_tokenizers.ByteLevel.alphabet(),
        )
        tok.train_from_iterator(TRAIN_TEXT, trainer)
        tok.save(tok_json)
    write_config(
        path,
        ModelConfig(
            vocab_size=_vocab_size(tok_json),
            hidden_size=hidden_size,
            num_layers=num_layers,
            num_heads=num_heads,
            num_kv_heads=num_kv_heads,
            intermediate_size=intermediate_size,
            max_position_embeddings=max_position_embeddings,
        ),
    )
    write_tokenizer_config(path)
    return path


def _vocab_size(tok_json: str) -> int:
    from tokenizers import Tokenizer

    return Tokenizer.from_file(tok_json).get_vocab_size()


def free_port() -> int:
    """Pick an OS-assigned free TCP port (shared by the e2e suites)."""
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port
