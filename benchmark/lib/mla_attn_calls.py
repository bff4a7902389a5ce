"""What multi-head latent attention has to do, and what a chip needs at the
least to do it (``dynamo_exp_tpu/models/llama.py::_mla_half``, the kernel
``ops/ragged_attention.py`` at ``value_dim``).

THE WORK COUNTED IS THE PUBLISHED FORM'S, whatever implements it, so that a
cheaper form raises the share and does not change the count:

- operations: a query-key pair and head costs the score over ``nope +
  rope`` and the value sum over ``v``: ``2 * (nope + rope + v)``; times the
  heads and the layers. (The absorbed form the program runs spends ``2 *
  (lanes + value_lanes)`` a pair and head, 3.6 x as much at the published
  widths: the share's ceiling while every query is absorbed.)
- bytes: each row's context latents once a dispatch and layer at the
  pool's REAL row bytes (its lanes, padding included), and the queries
  and results of the kernel as it is called (``heads * (lanes +
  value_lanes)`` values a query token and layer).

Pairs, context tokens and query tokens come from the program's counters
(``dynamo_mla_attended_pairs_total``, ``dynamo_mla_context_tokens_total``,
``dynamo_mla_query_tokens_total``: a layer's, and the pairs a head's too).
"""

from __future__ import annotations


def operations(pairs: float, hf: dict) -> float:
    a_pair = 2 * (int(hf["qk_nope_head_dim"]) + int(hf["qk_rope_head_dim"]) + int(hf["v_head_dim"]))
    return pairs * a_pair * int(hf["num_attention_heads"]) * int(hf["num_hidden_layers"])


def lanes(hf: dict) -> tuple[int, int]:
    """(A pool row's lanes, the lanes of it the kernel takes as values):
    the latent row and its ``kv_lora_rank``, each in whole groups of 128."""
    width = int(hf["kv_lora_rank"]) + int(hf["qk_rope_head_dim"])
    return -(-width // 128) * 128, -(-int(hf["kv_lora_rank"]) // 128) * 128


def bytes_moved(context_tokens: float, query_tokens: float, hf: dict, row_bytes: int,
                itemsize: int = 2) -> float:
    row, values = lanes(hf)
    a_query = int(hf["num_attention_heads"]) * (row + values) * itemsize
    return int(hf["num_hidden_layers"]) * (context_tokens * row_bytes + query_tokens * a_query)


def least_seconds(pairs: float, context_tokens: float, query_tokens: float, hf: dict,
                  row_bytes: int, peak: dict) -> tuple[float, str]:
    """The least time the counted attention can take on a chip with
    ``peak`` (an entry of ``peaks.json``), and which bound sets it."""
    by_hbm = bytes_moved(context_tokens, query_tokens, hf, row_bytes) / (peak["hbm_gbps"] * 1e9)
    by_mxu = operations(pairs, hf) / (peak["bf16_tflops"] * 1e12)
    return (by_hbm, "hbm") if by_hbm >= by_mxu else (by_mxu, "bf16")
